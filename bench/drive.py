"""The drivers a traffic mix names: ``train`` and ``open_loop``.

Each builds the system under test from the seed, warms up the shapes its
cell uses (set-up), measures for ``seconds`` (the window), reads the peak
memory, frees the program's state and checks what the window produced
against the reference.  It returns the end-to-end values, the run record
the per-layer readers take their metrics from, and the readings compared.
"""
from __future__ import annotations

import contextlib
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import check, flops, gen, system, trace_reduce
from . import weights as W

now = time.perf_counter

# seconds of training steps kept in flight in the window, so that a stall
# of the host does not idle the chip; each loss is read that much later
AHEAD_S = 5.0


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Lowerings:
    """Counts the programs JAX lowers while ``active``: any inside the
    window means something compiled or was fetched from the cache there."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


@contextlib.contextmanager
def window(trace: bool, lowerings: Lowerings, out: Dict[str, Any]):
    """The measured window: host span ``bench.window``, lowering count,
    and with ``trace`` the profiler on; ``reduce_trace`` reads the trace
    once the run no longer waits on the system."""
    import jax

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        # host annotations and device ops; no Python call tracing, which
        # would slow the host loop it measures and swell the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    lowerings.active = True
    try:
        with annotate(trace_reduce.WINDOW):
            yield
    finally:
        lowerings.active = False
        if trace:
            jax.profiler.stop_trace()
    out["lowerings_in_window"] = lowerings.count
    out["trace_dir"] = tdir


def reduce_trace(out: Dict[str, Any]) -> None:
    tdir = out.pop("trace_dir", None)
    if tdir is None:
        return
    try:
        path = trace_reduce.find_xplane(tdir)
        out["trace"] = trace_reduce.reduce(path) if path else None
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def memory_peak() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# training


def train(cell, seed: int, seconds: float, trace: bool, t_start: float,
          peak: Dict[str, float], compute_dtype: Optional[str] = None
          ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    c, t = cell.config, cell.traffic
    B, S = t["batch"], t["seq"]
    b1 = c["deployment"]["optimizer"]["b1"]
    lowerings = Lowerings()
    sb, step = system.train_step(c, B, S, compute_dtype or
                                 c["deployment"]["compute_dtype"])
    params = W.make(c, W.key_for(seed, 0))
    system.check_layout(sb.model, params)
    state = {"params": params, "opt": system.optimizer_init(params)}
    del params
    source = gen.train_batches(t, c["vocab_size"], c["eos_token_id"], seed)
    pipe = system.prefetcher(source)
    losses: List[float] = []
    waits: List[float] = []
    step_s: List[float] = []
    run: Dict[str, Any] = {}

    def dispatch(state):
        t0 = now()
        with annotate("bench.input"):
            raw = pipe.get()
            feeds = {k: jnp.asarray(raw[k]) for k in ("tokens", "labels")}
        waits.append(now() - t0)
        with annotate("bench.step"):
            return step(feeds, state)

    def one(state):
        t0 = now()
        loss, state = dispatch(state)
        jax.block_until_ready((loss, state))
        losses.append(float(loss))
        step_s.append(now() - t0)
        return state

    try:
        # set-up: the window's own step on its first rows, read for the check
        state = one(state)
        g1 = jax.tree.map(lambda m: m / (1 - b1), state["opt"].m)
        grad1, grad1_tree = check.leaf_norms(g1), jax.device_get(g1)
        del g1
        p0 = W.make(c, W.key_for(seed, 0))
        change1 = check.change_norms(state["params"], p0)
        state = one(state)
        state = one(state)
        change3 = check.change_norms(state["params"], p0)
        del p0
        setup_s = now() - t_start

        first = len(losses)
        ahead = max(1, round(AHEAD_S / step_s[-1]))
        pending: List[Any] = []
        with window(trace, lowerings, run):
            w0 = now()
            while now() - w0 < seconds:
                loss, state = dispatch(state)
                pending.append(loss)
                if len(pending) > ahead:
                    losses.append(float(pending.pop(0)))
            # nothing more is sent; the window closes once all sent is done
            jax.block_until_ready(state)
            losses += [float(x) for x in pending]
            window_s = now() - w0
        steps = len(losses) - first
        mem = memory_peak()
    finally:
        pipe.stop()
        _join(pipe)
        reduce_trace(run)
    del state
    t_check = now()
    readings = check.train(c, t, seed, {"losses": losses[:3], "grad1": grad1,
                                        "grad1_tree": grad1_tree,
                                        "change1": change1, "change3": change3})
    del grad1_tree
    t_check = now() - t_check
    run.update(config=c, traffic=t, window_s=window_s, steps=steps,
               tokens=steps * B * S, input_wait_s=waits[first:first + steps])
    failed = sum(1 for x in losses[first:] if not np.isfinite(x))
    return {"setup_s": setup_s, "run": run, "memory_peak_bytes": mem,
            "attempted": steps, "failed": failed, "readings": readings,
            "end_to_end": {"train_tokens_per_s": run["tokens"] / window_s,
                           "setup_s": setup_s},
            "log": [f"steps in window {steps} ({ahead} in flight), first losses "
                    f"{[round(x, 5) for x in losses[:3]]}, last loss "
                    f"{losses[-1]:.5f}",
                    f"input wait mean {1e3 * np.mean(run['input_wait_s']):.3f} ms",
                    f"reference losses {readings['reference_losses']}, loss "
                    f"gaps {readings['loss_gaps']}, worst leaves "
                    f"{readings['grad_leaf']} / {readings['update_leaf']}, "
                    f"change after three steps (not compared) "
                    f"{readings['update_norm_gap']:.6g}, left out "
                    f"{readings['left_out']}; check {t_check:.1f} s"]}


def _join(pipe) -> None:
    thread = getattr(pipe, "_thread", None)
    if isinstance(thread, threading.Thread) and thread.is_alive():
        thread.join(timeout=30)


# ---------------------------------------------------------------------------
# serving


class Served:
    """Host-side record of the requests a batcher serves: due, admission,
    first and last token times (seconds after the window opens)."""

    def __init__(self, batcher, t0: float):
        self.b, self.t0 = batcher, t0
        self.reqs: Dict[int, gen.Req] = {}
        self.admit: Dict[int, float] = {}
        self.first: Dict[int, float] = {}
        self.last: Dict[int, float] = {}
        self.done_tokens = 0
        self.step_s: List[float] = []
        self.positions: List[List[int]] = []

    def submit(self, r: gen.Req) -> None:
        self.reqs[r.rid] = r
        self.b.submit(system.request(r.rid, r.prompt, r.out_len))

    def room(self) -> bool:
        return self.b.queue.size() < self.b.queue.capacity

    def busy(self) -> bool:
        return self.b.queue.size() > 0 or any(r is not None for r in self.b.slot_req)

    def step(self, record: bool) -> List[int]:
        """One batcher step; returns the ids it completed."""
        b = self.b
        start = now()
        before = set(b.results)
        with annotate("bench.batcher_step"):
            b.step()
        end = now()
        ts, te = start - self.t0, end - self.t0
        done = [rid for rid in b.results if rid not in before]
        if record:
            self.step_s.append(end - start)
        for s, req in enumerate(b.slot_req):
            if req is None:
                continue
            self.admit.setdefault(req.rid, ts)
            if b.slot_out[s]:
                self.first.setdefault(req.rid, te)
        for rid in done:
            self.admit.setdefault(rid, ts)
            self.first.setdefault(rid, te)
            self.last[rid] = te
            self.done_tokens += len(b.results[rid].tokens)
        if record:
            # positions the step served (the position before it advanced)
            self.positions.append([int(b.slot_pos[s]) - 1
                                   for s, req in enumerate(b.slot_req)
                                   if req is not None] +
                                  [len(b.results[rid].tokens) +
                                   b.results[rid].prompt_len - 2
                                   for rid in done])
        return done

    def emitted(self) -> int:
        b = self.b
        return self.done_tokens + sum(len(b.slot_out[s])
                                      for s, req in enumerate(b.slot_req)
                                      if req is not None)


def _warm(b) -> None:
    """Fill every slot once with a two-token request and drain: compiles
    (or loads) the slot step, every slot's reset and the logits read."""
    for s in range(b.n_slots):
        b.submit(system.request(-1 - s, [1, 2], 2))
    b.run_until_drained(max_steps=100)
    b.results.clear()


def _serve_common(cell, seed: int, t_start: float):
    """The batcher on the seed's weights, warmed up; and set-up's length."""
    c = cell.config
    lowerings = Lowerings()
    b = system.batcher(c, W.make(c, W.key_for(seed, 0)))
    _warm(b)
    return c, lowerings, b, now() - t_start


def _decode_least(c, positions: List[List[int]], peak) -> Dict[str, Any]:
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for pos in positions:
        if pos:
            r = flops.least_seconds(flops.decode_step(c, pos), peak)
            least += r["seconds"]
            bounds[r["bound"]] += 1
    return {"seconds": least, "bounds": bounds}


def open_loop(cell, seed: int, seconds: float, trace: bool, t_start: float,
              peak: Dict[str, float], drain_s: Optional[float] = None
              ) -> Dict[str, Any]:
    c, t = cell.config, cell.traffic
    drain_s = t["drain_s"] if drain_s is None else drain_s
    blocks = 1 + int(np.ceil(drain_s / seconds))
    reqs = gen.open_loop(t, c["vocab_size"], seed, seconds, blocks)
    c, lowerings, b, setup_s = _serve_common(cell, seed, t_start)
    counted = [r.rid for r in reqs if r.counted]
    run: Dict[str, Any] = {}
    lag: List[float] = []
    sv = Served(b, now())
    i = 0

    def offer(elapsed: float) -> None:
        nonlocal i
        while i < len(reqs) and reqs[i].due <= elapsed and sv.room():
            lag.append(elapsed - reqs[i].due)
            sv.submit(reqs[i])
            i += 1

    with window(trace, lowerings, run):
        sv.t0 = now()
        while True:
            elapsed = now() - sv.t0
            if elapsed >= seconds:
                break
            offer(elapsed)
            if sv.busy():
                sv.step(record=True)
            elif i < len(reqs):
                with annotate("bench.wait_arrival"):
                    time.sleep(max(0.0, min(reqs[i].due, seconds) - elapsed))
        window_s = now() - sv.t0
    backlog = len([r for r in reqs[:i] if r.rid not in sv.last]) + sum(
        1 for r in reqs[i:] if r.due <= seconds)
    positions = sv.positions
    # drain: the window's requests finish under the same offered load; its
    # time starts once the window, and with it the trace, has stopped
    deadline = now() + drain_s
    while any(r not in sv.last for r in counted) and now() < deadline:
        offer(now() - sv.t0)
        if sv.busy():
            sv.step(record=False)
        elif i < len(reqs):
            time.sleep(max(0.0, reqs[i].due - (now() - sv.t0)))
        else:
            break
    mem = memory_peak()
    # read the trace only now: its reduction takes tens of seconds, in which
    # the window's last requests would stand still
    reduce_trace(run)
    results = {rid: b.results[rid].tokens for rid in counted if rid in b.results}
    del b
    sv.b = None
    done = [r for r in counted if r in sv.last]
    ttft = [sv.first[r] - sv.reqs[r].due for r in done]
    tpot = [(sv.last[r] - sv.first[r]) / (len(results[r]) - 1)
            for r in done if len(results[r]) > 1]
    admit = [sv.admit[r] - sv.reqs[r].due for r in done]
    samples = _samples(sv.reqs, results, seed, t["check_tokens"])
    t_check = now()
    readings = check.serve(c, seed, samples)
    readings["check_s"] = now() - t_check
    least = _decode_least(c, positions, peak)
    run.update(config=c, traffic=t, window_s=window_s, step_s=sv.step_s,
               admit_wait_s=admit, decode_least_s=least["seconds"])
    bad = [r for r in done if len(results[r]) != sv.reqs[r].out_len]
    return {"setup_s": setup_s, "run": run, "memory_peak_bytes": mem,
            "samples": samples, "backlog_at_close": backlog,
            "completed_in_window": sum(1 for x in sv.last.values()
                                       if x <= window_s),
            "attempted": len(counted),
            "failed": len(counted) - len(done) + len(bad),
            "readings": readings,
            "end_to_end": {"ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
                           "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)),
                           "setup_s": setup_s},
            "log": [f"requests due in window {len(counted)}, finished "
                    f"{len(done)}, wrong length {len(bad)}",
                    f"generator lag: max {1e3 * max(lag[:len(counted)]):.3f} ms,"
                    f" mean {1e3 * np.mean(lag[:len(counted)]):.3f} ms",
                    f"ttft p50 {1e3 * np.median(ttft):.1f} ms, tpot p50 "
                    f"{1e3 * np.median(tpot):.2f} ms, admit wait p50 "
                    f"{1e3 * np.median(admit):.1f} ms",
                    f"unfinished at the window's close {backlog}; steps in "
                    f"window {len(sv.step_s)}, slots "
                    f"{c['deployment']['n_slots']}, decode roofline bound "
                    f"{least['bounds']}",
                    f"served tokens compared {readings['tokens_compared']} in "
                    f"{readings['requests_compared']} requests; check "
                    f"{readings['check_s']:.1f} s"]}


def _samples(reqs: Dict[int, gen.Req], results: Dict[int, List[int]], seed: int,
             want_tokens: int) -> List[Dict[str, Any]]:
    """Finished requests to compare: the one with the most served tokens,
    then others in an order drawn from the seed, until ``want_tokens``."""
    rids = sorted(results)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(results[r]), -r))
    order = [longest] + [r for r in gen.rng_for(seed, 7).permutation(rids)
                         if r != longest]
    out, total = [], 0
    for rid in order:
        out.append({"rid": int(rid), "prompt": np.asarray(reqs[rid].prompt),
                    "served": np.asarray(results[rid], np.int32)})
        total += len(results[rid])
        if total >= want_tokens:
            break
    return out


DRIVERS: Dict[str, Callable[..., Dict[str, Any]]] = {
    "train": train, "open_loop": open_loop}
