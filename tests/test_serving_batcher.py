"""Continuous-batching serving layer: correctness vs sequential decode,
request latency, and the step's profiler spans."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import Model
from repro.models.params import init_params
from repro.obs import metrics as obs_metrics
from repro.serving import ContinuousBatcher, Request


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b", smoke=True)
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _sequential_decode(model, params, prompt, n_new, max_seq=64):
    cache = init_params(model.init_cache_desc(batch=1, max_seq=max_seq),
                        jax.random.PRNGKey(1))
    toks = list(prompt)
    out = []
    pos = 0
    logits = None
    for t in toks:
        logits, cache = model.serve_step(
            params, cache, jnp.array([[t]], jnp.int32), jnp.array(pos))
        pos += 1
    for _ in range(n_new):
        nxt = int(jnp.argmax(logits[0, 0, : model.cfg.vocab_size]))
        out.append(nxt)
        logits, cache = model.serve_step(
            params, cache, jnp.array([[nxt]], jnp.int32), jnp.array(pos))
        pos += 1
    return out


def test_batched_requests_match_sequential(setup):
    cfg, model, params = setup
    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(0, cfg.vocab_size, (n,)))
               for n in (3, 5, 4, 6, 2)]
    want = [_sequential_decode(model, params, p, 6) for p in prompts]

    batcher = ContinuousBatcher(model, params, n_slots=3, max_seq=64)
    for i, p in enumerate(prompts):
        batcher.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    results = batcher.run_until_drained()
    assert len(results) == len(prompts)
    for i in range(len(prompts)):
        assert results[i].tokens == want[i], (i, results[i].tokens, want[i])


def test_continuous_refill_keeps_slots_busy(setup):
    cfg, model, params = setup
    rs = np.random.RandomState(1)
    batcher = ContinuousBatcher(model, params, n_slots=2, max_seq=64)
    for i in range(6):
        batcher.submit(Request(rid=i, prompt=list(rs.randint(0, 64, (2,))),
                               max_new_tokens=3))
    results = batcher.run_until_drained()
    assert len(results) == 6
    # 6 requests through 2 slots: slots were refilled continuously
    assert batcher.occupancy() > 0.8


def test_eos_terminates_early(setup):
    cfg, model, params = setup
    # find the greedy first token, then use it as eos
    first = _sequential_decode(model, params, [1, 2, 3], 1)[0]
    batcher = ContinuousBatcher(model, params, n_slots=1, max_seq=64)
    batcher.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=10,
                           eos_id=first))
    results = batcher.run_until_drained()
    assert results[0].tokens == [first]


def test_latency_counts_the_wait_in_the_queue(setup):
    cfg, model, params = setup
    batcher = ContinuousBatcher(model, params, n_slots=1, max_seq=64)
    hist = obs_metrics.histogram("serving.request_latency_s")
    count0, sum0 = hist.count, hist.sum
    t_submit = time.perf_counter()
    batcher.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=4))
    batcher.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=2))
    time.sleep(0.2)  # both wait in the queue; then rid 1 waits behind rid 0
    t_admit = None
    while batcher.queue.size() or batcher._live():
        t = time.perf_counter()
        batcher.step()
        req = batcher.slot_req[0]
        if t_admit is None and req is not None and req.rid == 1:
            t_admit = t
    t_end = time.perf_counter()
    res = batcher.results
    assert t_admit is not None and res[0].steps > 0
    # from submit(), not from admission into the slot
    assert t_admit - t_submit <= res[1].latency_s <= t_end - t_submit
    assert t_admit - t_submit >= 0.2
    assert res[0].latency_s >= 0.2
    assert hist.count == count0 + 2
    assert hist.sum - sum0 == pytest.approx(
        res[0].latency_s + res[1].latency_s)


SERVE_SPANS = ("serve.admit", "serve.dispatch", "serve.device_wait",
               "serve.logits_to_host", "serve.sample")


def test_step_phases_are_profiler_spans(setup, tmp_path):
    """Each phase of a step is a profiler span nested in ``serve.step``, in
    the order it runs, once per step."""
    from jax.profiler import ProfileData

    cfg, model, params = setup
    batcher = ContinuousBatcher(model, params, n_slots=2, max_seq=64)
    batcher.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
    batcher.run_until_drained()  # compiles outside the trace
    batcher.submit(Request(rid=1, prompt=[1, 2, 3], max_new_tokens=3))
    batcher.submit(Request(rid=2, prompt=[4], max_new_tokens=2))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        steps = 0
        while batcher.queue.size() or batcher._live():
            batcher.step()
            steps += 1
    finally:
        jax.profiler.stop_trace()
    assert steps == 5 and len(batcher.results) == 3

    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = sorted((ev.start_ns, -ev.duration_ns, ev.name,
                     ev.start_ns + ev.duration_ns)
                    for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events
                    if ev.name.startswith("serve."))
    outer = [(s, e) for s, _, n, e in events if n == "serve.step"]
    assert len(outer) == steps
    for s0, e0 in outer:
        inner = [(n, s, e) for s, _, n, e in events
                 if n not in ("serve.step", "serve.reset_slot")
                 and s0 <= s and e <= e0]
        assert [n for n, _, _ in inner] == list(SERVE_SPANS)
        # one after another, without overlap
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    # each admission's slot reset is a span inside ``serve.admit``
    resets = [(s, e) for s, _, n, e in events if n == "serve.reset_slot"]
    admits = [(s, e) for s, _, n, e in events if n == "serve.admit"]
    assert len(resets) == 2
    assert all(any(a0 <= s and e <= a1 for a0, a1 in admits) for s, e in resets)
    assert len(events) == steps * (1 + len(SERVE_SPANS)) + len(resets)



def _sequential_logits(model, params, tokens, max_seq=64):
    """Each position's logits row, from one-token steps over ``tokens``."""
    cache = init_params(model.init_cache_desc(batch=1, max_seq=max_seq),
                        jax.random.PRNGKey(1))
    rows = []
    for pos, t in enumerate(tokens):
        logits, cache = model.serve_step(
            params, cache, jnp.array([[t]], jnp.int32), jnp.array(pos))
        rows.append(np.asarray(logits[0, 0]))
    return np.stack(rows)


def test_reused_slot_matches_sequential(setup):
    """A slot that served a long request serves a short one as a fresh
    cache would, while the other slot's request runs on undisturbed: the
    in-place reset and the in-place writes leave nothing stale.  Each
    step's logits are compared, since a small model's greedy tokens
    barely depend on what attention reads."""
    cfg, model, params = setup
    rs = np.random.RandomState(2)
    # (prompt length, new tokens): the long request fills slot 0 first,
    # the middle one holds slot 1 while the short one reuses slot 0
    sizes = [(9, 8), (2, 20), (3, 4)]
    prompts = [list(rs.randint(0, cfg.vocab_size, (n,))) for n, _ in sizes]
    want = [_sequential_decode(model, params, p, k)
            for p, (_, k) in zip(prompts, sizes)]

    batcher = ContinuousBatcher(model, params, n_slots=2, max_seq=64)
    for i, (p, (_, k)) in enumerate(zip(prompts, sizes)):
        batcher.submit(Request(rid=i, prompt=p, max_new_tokens=k))
    rows, served_by = {}, {}
    sample = batcher._sample

    def record(live, logits_np):
        for s in live:
            rid = batcher.slot_req[s].rid
            rows.setdefault(rid, []).append(logits_np[s].copy())
            served_by.setdefault(rid, s)
        return sample(live, logits_np)

    batcher._sample = record
    batcher.run_until_drained()
    assert served_by == {0: 0, 1: 1, 2: 0}
    for i, p in enumerate(prompts):
        assert batcher.results[i].tokens == want[i], i
        ref = _sequential_logits(model, params, p + want[i][:-1])
        np.testing.assert_allclose(np.stack(rows[i]), ref,
                                   rtol=1e-5, atol=1e-5, err_msg=str(i))


def test_greedy_tokens_come_from_the_device_and_sampling_from_one_row(
        setup, monkeypatch):
    """A greedy and a ``temperature > 0`` request share the step: the
    greedy one takes the token the device chose, as sequential decode
    does; the other draws from its own slot's logits row on the device,
    as ``jax.random.categorical`` over that row at that temperature does.
    Each path's counter counts its tokens, and no step brings an array of
    a vocabulary's size to the host."""
    cfg, model, params = setup
    prompts, k, temp = [[5, 6, 7], [8, 9]], 6, 0.7
    want = _sequential_decode(model, params, prompts[0], k)
    batcher = ContinuousBatcher(model, params, n_slots=2, max_seq=64, seed=3)
    batcher.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=k))
    batcher.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=k,
                           temperature=temp))
    greedy = obs_metrics.counter("serving.tokens_greedy_device")
    sampled = obs_metrics.counter("serving.tokens_sampled_row")
    greedy0, sampled0 = greedy.value, sampled.value
    pulled = []

    def recorded(fn):
        def wrapped(x, *args, **kwargs):
            pulled.extend(leaf.shape for leaf in jax.tree.leaves(x)
                          if isinstance(leaf, jax.Array))
            return fn(x, *args, **kwargs)
        return wrapped

    for mod, name in ((np, "asarray"), (np, "array"), (jax, "device_get")):
        monkeypatch.setattr(mod, name, recorded(getattr(mod, name)))
    results = batcher.run_until_drained()
    monkeypatch.undo()

    assert results[0].tokens == want
    assert greedy.value - greedy0 == k
    assert sampled.value - sampled0 == k
    assert (2,) in pulled  # the step's tokens
    assert all(int(np.prod(shape)) < cfg.vocab_size for shape in pulled), \
        pulled

    served = results[1].tokens
    rows = _sequential_logits(model, params, prompts[1] + served[:-1])
    key, drawn = jax.random.PRNGKey(3), []
    for row in rows[len(prompts[1]) - 1:]:
        key, sub = jax.random.split(key)
        drawn.append(int(jax.random.categorical(
            sub, jnp.asarray(row[:cfg.vocab_size]) / temp)))
    assert served == drawn


def test_step_donates_the_cache(setup):
    """The slot reset and the slot step update the batcher's cache in
    place: the tree it held before a step is deleted after it."""
    probe = jnp.zeros(4)
    jax.jit(lambda x: x + 1, donate_argnums=0)(probe)
    if not probe.is_deleted():
        pytest.skip(f"the {jax.default_backend()} backend ignores buffer "
                    "donation, so nothing is updated in place to check")
    cfg, model, params = setup
    batcher = ContinuousBatcher(model, params, n_slots=2, max_seq=64)
    batcher.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=3))
    held = jax.tree.leaves(batcher.cache)
    batcher.step()  # admits (a reset), then steps
    assert all(leaf.is_deleted() for leaf in held)
    held = jax.tree.leaves(batcher.cache)
    batcher.step()  # steps alone
    assert all(leaf.is_deleted() for leaf in held)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(batcher.cache))
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(batcher._empty_cache))


@pytest.fixture(scope="module")
def hybrid():
    cfg = get_config("granite-4.0-h-micro", smoke=True)
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _slot_leaves(cache, s):
    """Slot ``s``'s leaves, by path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    return {jax.tree_util.keystr(p): np.asarray(leaf[s]) for p, leaf in flat}


def test_hybrid_slots_match_the_full_forward_and_reset_to_zero(hybrid):
    """Mamba-2 and attention layers in one slot: each step's logits equal
    the model's full forward pass over the request's tokens, for a slot
    reused after another request too, whose SSM state, conv windows and
    KV cache the admission's reset set to zero."""
    cfg, model, params = hybrid
    rs = np.random.RandomState(4)
    sizes = [(7, 6), (2, 14), (4, 5)]
    prompts = [list(rs.randint(0, cfg.vocab_size, (n,))) for n, _ in sizes]
    batcher = ContinuousBatcher(model, params, n_slots=2, max_seq=32)
    for i, (p, (_, k)) in enumerate(zip(prompts, sizes)):
        batcher.submit(Request(rid=i, prompt=p, max_new_tokens=k))
    rows, served_by, resets = {}, {}, []
    sample, reset = batcher._sample, batcher._reset_slot_cache

    def record(live, logits_np):
        for s in live:
            rid = batcher.slot_req[s].rid
            rows.setdefault(rid, []).append(logits_np[s].copy())
            served_by.setdefault(rid, s)
        return sample(live, logits_np)

    def record_reset(s):
        before = _slot_leaves(batcher.cache, s)
        reset(s)
        resets.append((s, before, _slot_leaves(batcher.cache, s)))

    batcher._sample = record
    batcher._reset_slot_cache = record_reset
    batcher.run_until_drained()
    assert served_by == {0: 0, 1: 1, 2: 0}
    s, before, after = resets[2]
    assert s == 0
    ssm = [k for k in after if "'ssm'" in k]
    assert {k.rsplit("'", 2)[-2] for k in ssm} == {"state", "conv_x", "conv_B",
                                                  "conv_C"}
    assert all(np.any(before[k]) for k in ssm)  # the first request's state
    assert not any(np.any(v) for v in after.values())
    for i, p in enumerate(prompts):
        served = batcher.results[i].tokens
        full = model.forward_logits(
            params, {"tokens": jnp.asarray([p + served[:-1]], jnp.int32)})[0]
        np.testing.assert_allclose(np.stack(rows[i]), full, rtol=1e-5,
                                   atol=1e-5, err_msg=str(i))


def test_slot_bytes_and_resets_are_recorded(setup, hybrid):
    """The batcher sets one slot's KV and SSM bytes as gauges when it is
    built, and counts each slot reset."""
    for cfg, model, params in (setup, hybrid):
        batcher = ContinuousBatcher(model, params, n_slots=2, max_seq=32)
        one = model.init_cache_desc(batch=1, max_seq=32)
        flat, _ = jax.tree_util.tree_flatten_with_path(
            one, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "init"))
        want = {"kv": 0, "ssm": 0}
        for p, spec in flat:
            n = int(np.prod(spec.shape)) * jnp.dtype(spec.dtype).itemsize
            want["ssm" if any(getattr(k, "key", None) == "ssm" for k in p)
                 else "kv"] += n
        assert obs_metrics.gauge("serving.slot_kv_bytes").value == want["kv"] > 0
        assert obs_metrics.gauge("serving.slot_ssm_bytes").value == want["ssm"]
        assert (want["ssm"] > 0) == (cfg.family == "hybrid")
        resets = obs_metrics.counter("serving.slot_resets")
        count0 = resets.value
        for i in range(3):
            batcher.submit(Request(rid=i, prompt=[1, 2], max_new_tokens=2))
        batcher.run_until_drained()
        assert resets.value - count0 == 3
