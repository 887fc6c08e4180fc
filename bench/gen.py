"""The one traffic generator: every mix is a data file of its parameters.

A mix names a ``driver`` and the distributions it draws from:

  train        packed documents: rows of ``seq`` tokens, ``batch`` rows a
               step, document lengths from ``documents``, EOS between them.
  open_loop    requests arriving at ``rate_per_s`` (Poisson), prompt and
               answer lengths from ``prompt_len`` and ``output_len``; after
               the window closes its requests drain for up to ``drain_s``.

Token text is a Zipfian stream with a bigram structure (``tokens``: the
Zipf exponent, and how often a token is followed by its fixed successor),
after ``repro.data.SyntheticLMDataset``.

Request sizes and arrival gaps are the same set for every seed: they sit
at evenly spaced quantiles of their distributions, and the seed only
shuffles their order and draws the tokens.  So two seeds offer the same
work and differ only in how it is interleaved.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, Iterator, List

import numpy as np

_NORMAL = statistics.NormalDist()


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def quantile(dist: Dict[str, Any], q: float) -> int:
    """The ``q`` quantile of a length distribution, clipped to its range."""
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    elif dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def quantile_set(dist: Dict[str, Any], n: int) -> np.ndarray:
    return np.array([quantile(dist, (i + 0.5) / n) for i in range(n)], np.int64)


class Text:
    """Zipfian tokens over a seeded permutation of the vocabulary; each
    token has a fixed successor that follows it with probability
    ``follow``."""

    def __init__(self, spec: Dict[str, Any], vocab: int, rng: np.random.Generator):
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(spec["zipf"])
        self.cdf = np.cumsum(p / p.sum())
        self.token_of_rank = rng.permutation(vocab).astype(np.int32)
        self.succ = rng.integers(0, vocab, vocab, dtype=np.int32)
        self.follow = float(spec["follow"])
        self.vocab = vocab

    def draw(self, rng: np.random.Generator, shape) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(shape), side="right")
        return self.token_of_rank[np.minimum(r, self.vocab - 1)]

    def rows(self, rng: np.random.Generator, n: int, length: int,
             starts: np.ndarray = None, eos: int = -1) -> np.ndarray:
        """(n, length) tokens.  ``starts`` marks positions that begin a
        document (a fresh draw); ``eos`` >= 0 goes just before each
        start after the first position."""
        fresh = self.draw(rng, (n, length))
        coin = rng.random((n, length)) < self.follow
        if starts is None:
            starts = np.zeros((n, length), bool)
            starts[:, 0] = True
        out = np.empty((n, length), np.int32)
        out[:, 0] = fresh[:, 0]
        for t in range(1, length):
            nxt = np.where(coin[:, t], self.succ[out[:, t - 1]], fresh[:, t])
            out[:, t] = np.where(starts[:, t], fresh[:, t], nxt)
            if eos >= 0 and t + 1 < length:
                out[:, t] = np.where(starts[:, t + 1], eos, out[:, t])
        return out


def train_batches(traffic: Dict[str, Any], vocab: int, eos: int,
                  seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """Step i's batch, for i = 0, 1, ...: ``tokens`` and ``labels`` of
    shape (batch, seq), the labels the tokens shifted by one."""
    B, S = traffic["batch"], traffic["seq"]
    text = Text(traffic["tokens"], vocab, rng_for(seed, 1))
    docs = traffic["documents"]
    step = 0
    while True:
        rng = rng_for(seed, 2, step)
        starts = np.zeros((B, S + 1), bool)
        for b in range(B):
            pos = 0
            while pos < S + 1:
                starts[b, pos] = True
                pos += quantile(docs, rng.uniform(1e-6, 1 - 1e-6)) + 1
        toks = text.rows(rng, B, S + 1, starts, eos)
        yield {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        step += 1


@dataclasses.dataclass
class Req:
    rid: int
    due: float          # seconds after the window opens (open loop)
    prompt: np.ndarray
    out_len: int
    counted: bool       # due inside the window


def _prompts(text: Text, rng: np.random.Generator, lens: np.ndarray) -> List[np.ndarray]:
    rows = text.rows(rng, len(lens), int(lens.max()))
    return [rows[i, :n] for i, n in enumerate(lens)]


def request_block(traffic: Dict[str, Any], vocab: int, seed: int, block: int,
                  n: int) -> List[Req]:
    """``n`` requests: the quantile sets of prompt and answer lengths,
    each shuffled by the seed and the block number."""
    rng = rng_for(seed, 3, block)
    text = Text(traffic["tokens"], vocab, rng_for(seed, 1))
    p = rng.permutation(quantile_set(traffic["prompt_len"], n))
    o = rng.permutation(quantile_set(traffic["output_len"], n))
    return [Req(rid=block * n + i, due=0.0, prompt=pr, out_len=int(o[i]),
                counted=False)
            for i, pr in enumerate(_prompts(text, rng, p))]


def open_loop(traffic: Dict[str, Any], vocab: int, seed: int, seconds: float,
              blocks: int) -> List[Req]:
    """Arrivals at ``rate_per_s``: the first block of round(rate x
    seconds) requests is due inside the window and counted; later blocks
    keep the load on while the window's requests drain."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    out: List[Req] = []
    for b in range(blocks):
        reqs = request_block(traffic, vocab, seed, b, n)
        due = b * seconds + np.cumsum(rng_for(seed, 4, b).permutation(gaps))
        for r, d in zip(reqs, due):
            r.due, r.counted = float(d), b == 0
        out += reqs
    return out
