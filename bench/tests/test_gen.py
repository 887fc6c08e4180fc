"""The traffic generator: the same seed gives the same inputs, another seed
the same sizes in another order, and the sizes follow each mix's file."""
import math
import statistics

import numpy as np
import pytest

from bench import gen, spec

BIG_SEED = 2**31 + 12345


def mix(name):
    return spec.load_json(f"{spec.BENCH}/traffic/{name}.json")


def test_quantiles_follow_the_stated_distribution():
    d = {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 16, "max": 1024}
    qs = gen.quantile_set(d, 1001)
    assert qs.min() >= 16 and qs.max() <= 1024
    assert abs(statistics.median(qs) - 128) <= 1
    # the 84th percentile of a lognormal is median * e^sigma
    assert abs(np.percentile(qs, 84.13) / (128 * math.exp(0.8)) - 1) < 0.02
    u = gen.quantile_set({"dist": "uniform", "min": 16, "max": 64}, 4900)
    assert u.min() == 16 and u.max() == 64
    assert abs(np.bincount(u)[16:].std() / np.bincount(u)[16:].mean()) < 0.05


@pytest.mark.parametrize("seq", [2048, 256])
def test_train_batches_are_deterministic_and_packed(seq):
    t = dict(mix("train-2k"), seq=seq)
    t = dict(t, batch=2, seq=min(t["seq"], 512))
    a = gen.train_batches(t, 1000, 0, BIG_SEED)
    b = gen.train_batches(t, 1000, 0, BIG_SEED)
    c = gen.train_batches(t, 1000, 0, BIG_SEED + 1)
    x0, y0, z0 = next(a), next(b), next(c)
    x1 = next(a)
    assert x0["tokens"].shape == (2, t["seq"]) and x0["tokens"].dtype == np.int32
    np.testing.assert_array_equal(x0["tokens"], y0["tokens"])
    np.testing.assert_array_equal(x0["tokens"][:, 1:], x0["labels"][:, :-1])
    assert not np.array_equal(x0["tokens"], z0["tokens"])
    assert not np.array_equal(x0["tokens"], x1["tokens"])
    assert ((0 <= x0["tokens"]) & (x0["tokens"] < 1000)).all()


def test_documents_are_separated_by_eos():
    t = dict(mix("train-2k"), batch=8, seq=2048)
    eos = 7
    toks = next(gen.train_batches(t, 50_000, eos, 3))["tokens"]
    counts = (toks == eos).sum(axis=1)
    # median document 512 tokens: a 2048-token row holds a few documents
    assert 1 <= np.median(counts) <= 8


def test_open_loop_counts_rate_and_same_work_for_every_seed():
    t = mix("serve-chat")
    a = gen.open_loop(t, 1000, BIG_SEED, 30.0, blocks=2)
    b = gen.open_loop(t, 1000, BIG_SEED + 1, 30.0, blocks=2)
    n = round(t["rate_per_s"] * 30)
    assert sum(r.counted for r in a) == n and len(a) == 2 * n
    win = [r for r in a if r.counted]
    assert all(0 < r.due < 30.0 for r in win)
    assert all(r.due >= 30.0 for r in a if not r.counted)
    assert sorted(len(r.prompt) for r in win) == sorted(
        len(r.prompt) for r in b if r.counted)
    assert sorted(r.out_len for r in win) == sorted(
        r.out_len for r in b if r.counted)
    assert [r.out_len for r in win] != [r.out_len for r in b if r.counted]
    again = gen.open_loop(t, 1000, BIG_SEED, 30.0, blocks=2)
    assert [(r.due, r.out_len, r.prompt.tolist()) for r in a] == [
        (r.due, r.out_len, r.prompt.tolist()) for r in again]
    lo, hi = t["prompt_len"]["min"], t["prompt_len"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)

