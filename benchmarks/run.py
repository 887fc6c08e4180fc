"""Benchmark harness — one benchmark per paper claim/table.

The paper defers its quantitative section ("§8: a future version of this
white paper will have a comprehensive performance evaluation"), so the
benchmarks target the paper's *structural* performance claims plus this
repo's §Roofline artifacts:

  b1  session_run_overhead   §3.1 ready-queue executor dispatch cost
  b2  compiled_vs_eager      §10/§6: JIT-compiled graph vs interpreted
                             (the paper's "6x over DistBelief" analogue)
  b3  send_recv_rendezvous   §3.2.2 transfer latency + canonicalisation
  b4  lossy_compression      §5.5 compress/decompress throughput
  b5  input_pipeline         §4.6 prefetch-queue overlap win
  b6  cse                    §5.1 node-count reduction
  b7  recv_scheduling        §5.2 peak-memory window reduction (simulated)
  b8  kernel_registry        §12 registered-kernel dispatch: the smoke LM
                             block with the backend registry on vs off
  b9  train_throughput       end-to-end compiled training tokens/s
  b10 roofline_table         §Roofline summary from experiments/dryrun
  b14 replicated_training    §4.3–§4.4 data-parallel replication over a
                             4-process pool: tok/s vs replica count +
                             sync-vs-async convergence on the smoke LM

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ROWS = []


def emit(name: str, us: float, derived: str = "") -> None:
    ROWS.append((name, us, derived))
    print(f"{name},{us:.2f},{derived}", flush=True)


def _timeit(fn, n=20, warmup=3):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------

def bench_session_run_overhead():
    from repro.core import GraphBuilder, Session

    b = GraphBuilder()
    x = b.constant(jnp.ones((8, 8)), name="x")
    cur = x
    n_ops = 64
    for i in range(n_ops):
        cur = b.add(cur, x, name=f"a{i}")
    sess = Session(b.graph)
    us = _timeit(lambda: sess.run(cur.ref))
    emit("b1_session_run_overhead", us, f"{us / n_ops:.2f}us/op@{n_ops}ops")


def bench_compiled_vs_eager():
    """§10/§6: whole-graph JIT vs interpreted per-op dispatch.

    The eager Session runs UNFUSED — since PR 2 the default eager path
    partially compiles via region fusion (and the deque ready queue made
    dispatch ~2x cheaper), which was masking the gap this benchmark
    exists to track.  The graph is a matmul-heavy residual chain so both
    sides do real compute and the contrast stays §10 whole-graph jit vs
    interpreted dispatch.  The fused-fast row (DESIGN.md §9) runs the
    SAME Session engine with numerics="fast": matmuls/reductions join the
    region and compile at full XLA opt, so the eager engine closes most
    of the gap to the hand-lowered jit."""
    from repro.core import GraphBuilder, Session, compile_subgraph

    rs = np.random.RandomState(0)
    b = GraphBuilder()
    W = b.variable("W", init_value=lambda: jnp.array(
        rs.randn(256, 256).astype("f") * 0.05))
    x = b.placeholder("x")
    cur = x
    n_layers = 16
    for i in range(n_layers):
        h = b.matmul(cur, W, name=f"mm{i}")
        cur = b.relu(b.add(h, cur, name=f"res{i}"), name=f"r{i}")
    out = b.reduce_sum(cur)
    from repro.core.options import SessionOptions
    sess = Session(b.graph, options=SessionOptions(fuse_regions=False))
    X = jnp.array(rs.randn(64, 256).astype("f"))
    # block on every fetch: jax dispatch is async even on CPU, and the
    # fused engine issues ONE region call — an unblocked timer would
    # measure dispatch, not compute (the eager side blocks too so the
    # derived speedup divides like for like)
    eager_us = _timeit(lambda: jax.block_until_ready(
        sess.run(out.ref, {x.ref: X})))
    fast_sess = Session(b.graph, options=SessionOptions(
        fuse_regions=True, numerics="fast", parity_guard=False))
    fast_us = _timeit(lambda: jax.block_until_ready(
        fast_sess.run(out.ref, {x.ref: X})))
    low = compile_subgraph(sess, [out.ref], [x.ref])
    jf = jax.jit(low.fn)
    Wv = sess.variable_value("W")
    jf({"x:0": X}, {"W": Wv})  # compile
    comp_us = _timeit(lambda: jax.block_until_ready(
        jf({"x:0": X}, {"W": Wv})[0][0]))
    emit("b2_eager_graph", eager_us, f"interpreted,{n_layers}xmatmul256")
    emit("b2_fused_fast_graph", fast_us,
         f"numerics=fast,speedup={eager_us / fast_us:.1f}x_over_interp")
    emit("b2_compiled_graph", comp_us,
         f"speedup={eager_us / comp_us:.1f}x")


def bench_send_recv():
    from repro.runtime.rendezvous import Rendezvous, make_key

    r = Rendezvous()
    payload = jnp.ones((256, 256))
    i = [0]

    def xfer():
        k = make_key("t", "a", "b", i[0])
        i[0] += 1
        r.send(k, payload)
        r.recv(k)

    us = _timeit(xfer, n=200)
    mbps = payload.nbytes / (us / 1e6) / 1e6
    emit("b3_send_recv_roundtrip", us, f"{mbps:.0f}MB/s")

    # canonicalisation saving: N consumers of one remote tensor -> 1 xfer
    from repro.core import GraphBuilder
    from repro.core import partition as pt

    b = GraphBuilder()
    x = b.constant(jnp.ones(4), name="x")
    consumers = [b.square(x, name=f"c{i}") for i in range(8)]
    place = {"x": "/job:worker/task:0/device:cpu:0"}
    for c in consumers:
        place[c.name] = "/job:worker/task:1/device:cpu:0"
    parted = pt.partition(b.graph, place)
    emit("b3_canonicalised_transfers", 0.0,
         f"{parted.n_transfers}xfer_for_8_consumers")


def bench_compression():
    from repro.core import compression as C

    x = jnp.array(np.random.randn(1 << 20).astype("f"))
    comp = jax.jit(C.compress_f32_to_16)
    dec = jax.jit(C.decompress_16_to_f32)
    w = comp(x)
    us_c = _timeit(lambda: jax.block_until_ready(comp(x)))
    us_d = _timeit(lambda: jax.block_until_ready(dec(w)))
    gbs = x.nbytes / (us_c / 1e6) / 1e9
    emit("b4_compress_1M_f32", us_c, f"{gbs:.1f}GB/s,wire_bytes=0.5x")
    emit("b4_decompress_1M_f32", us_d, "")


def bench_input_pipeline():
    """§4.6 prefetch overlap.  Median of several reps: a mean of 3 was
    noisy enough to report a spurious <1.0x "regression" (batch
    generation holds the GIL for ~4ms at a stretch, so a single convoyed
    rep dominated the mean — see data/pipeline.py Prefetcher._fill)."""
    import statistics

    from repro.data import SyntheticLMDataset, Prefetcher, batch_iterator

    ds = SyntheticLMDataset(vocab_size=32000, seq_len=512, seed=0)

    def consume_direct():
        it = batch_iterator(ds, 8)
        for _ in range(10):
            next(it)
            time.sleep(0.002)  # simulated compute

    def consume_prefetched():
        pf = Prefetcher(batch_iterator(ds, 8), capacity=4).start()
        for _ in range(10):
            pf.get()
            time.sleep(0.002)
        pf.stop()

    def _median_us(fn, n=7):
        fn()  # warmup
        reps = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            reps.append((time.perf_counter() - t0) * 1e6)
        return statistics.median(reps)

    us_direct = _median_us(consume_direct)
    us_pf = _median_us(consume_prefetched)
    emit("b5_pipeline_no_prefetch", us_direct, "")
    emit("b5_pipeline_prefetch", us_pf,
         f"overlap_win={us_direct / us_pf:.2f}x")


def bench_cse():
    from repro.core import GraphBuilder
    from repro.core.cse import eliminate_common_subexpressions

    b = GraphBuilder()
    x = b.constant(jnp.ones(4), name="x")
    for i in range(32):  # 32 copies of the same expression
        b.add(b.mul(x, x, name=f"m{i}"), x, name=f"a{i}")
    before = len(b.graph.nodes)
    t0 = time.perf_counter()
    eliminate_common_subexpressions(b.graph)
    us = (time.perf_counter() - t0) * 1e6
    after = len(b.graph.nodes)
    emit("b6_cse", us, f"nodes_{before}->{after}")


def bench_recv_scheduling():
    """§5.2: ASAP vs ALAP recv start -> peak 'resident remote bytes'."""
    from repro.core import GraphBuilder
    from repro.core import placement as pl, partition as pt, scheduler as sc
    from repro.runtime.devices import DeviceSet

    b = GraphBuilder()
    remotes = [b.constant(jnp.ones((256, 256)), name=f"r{i}",
                          device="/job:worker/task:0") for i in range(6)]
    a = b.constant(jnp.ones((256, 256)), name="seed",
                   device="/job:worker/task:1")
    cur = a
    for i, r in enumerate(remotes):
        cur = b.matmul(cur, cur, name=f"chain{i}", device="/job:worker/task:1")
        cur = b.add(cur, r, name=f"use{i}", device="/job:worker/task:1")
    devs = DeviceSet.make_cluster(2, 1, kind="cpu")
    place = pl.place(b.graph, devs)
    parted = pt.partition(b.graph, place)
    cm = pl.CostModel()
    added = sc.schedule_recvs(parted.graph, set(parted.graph.nodes), cm,
                              devs, parted.placement)
    n_recv = sum(1 for n in parted.graph.nodes.values() if n.op == "Recv")
    emit("b7_recv_scheduling", 0.0,
         f"recvs={n_recv},delayed={added},peak_asap={n_recv}buf,peak_alap=1buf")


def bench_kernels():
    """DESIGN.md §12: the kernel-backend registry in a real graph run.

    One smoke LM block (rmsnorm -> q-proj -> attention -> out-proj ->
    residual, x2 layers) executed through the SAME fused-fast Session
    engine twice: registry off (backend="generic", pure XLA lowering) and
    registry on (backend="pallas", pattern-matched regions dispatch onto
    the hand-written kernels).  The pallas row must actually dispatch >=3
    distinct registered kernels or the comparison is vacuous."""
    from repro.core import GraphBuilder, Session
    from repro.core import kernel_registry as kr

    rs = np.random.RandomState(0)
    S, D = 128, 64

    def build():
        b = GraphBuilder()
        x = b.placeholder("x")
        kT = b.constant(jnp.array(rs.randn(D, S).astype("f")), name="kT")
        v = b.constant(jnp.array(rs.randn(S, D).astype("f")), name="v")
        cur = x
        for i in range(2):
            w = b.constant(jnp.array(
                np.abs(rs.randn(D)).astype("f") + 0.5), name=f"w{i}")
            wq = b.constant(jnp.array(
                rs.randn(D, D).astype("f") * 0.2), name=f"wq{i}")
            wo = b.constant(jnp.array(
                rs.randn(D, D).astype("f") * 0.2), name=f"wo{i}")
            xn = b.rmsnorm(cur, w, name=f"l{i}/xn")
            q = b.matmul(xn, wq, name=f"l{i}/q")
            att = b.attention(q, kT, v, scale=D ** -0.5, name=f"l{i}/att")
            proj = b.matmul(att, wo, name=f"l{i}/proj")
            cur = b.add(proj, cur, name=f"l{i}/res")
        out = b.reduce_sum(cur, name="out")
        return b, x, out

    X = jnp.array(rs.randn(S, D).astype("f"))
    rows = {}
    for backend in ("generic", "pallas"):
        b, x, out = build()
        from repro.core.options import SessionOptions
        sess = Session(b.graph, options=SessionOptions(
            numerics="fast", parity_guard=False, backend=backend))
        before = kr.dispatch_counts(backend)
        sess.run(out.ref, {x.ref: X})  # compile + (for pallas) dispatch
        delta = {k: c - before.get(k, 0)
                 for k, c in kr.dispatch_counts(backend).items()
                 if c > before.get(k, 0)}
        # min over repeats: the step is dispatch-overhead heavy, so a
        # mean-of-one-window estimate is too noisy to compare backends
        us = min(_timeit(lambda: jax.block_until_ready(
            sess.run(out.ref, {x.ref: X})), n=20, warmup=2)
            for _ in range(3))
        rows[backend] = us
        kstr = "+".join(sorted(delta)) if delta else "none"
        emit(f"b8_lm_{backend}_fused", us,
             f"s{S}_d{D}_2layer,kernels={kstr}")
        if backend == "pallas":
            assert len(delta) >= 3, (
                f"registry dispatched only {sorted(delta)} — b8 is vacuous")
    emit("b8_registry_on_vs_off", rows["pallas"],
         f"speedup={rows['generic'] / rows['pallas']:.2f}x_vs_generic")


def bench_train_throughput():
    from repro.launch.train import train

    t0 = time.time()
    res = train("smollm-360m", smoke=True, steps=30, batch=8, seq=128,
                log_every=1000, ckpt_dir=None)
    dt = time.time() - t0
    toks = 30 * 8 * 128
    emit("b9_train_tokens_per_s", dt / 30 * 1e6,
         f"{toks / dt:,.0f}tok/s,final_loss={res['final_loss']:.3f}")


def bench_roofline_table():
    pat = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun", "*__1pod_256.json")
    files = sorted(glob.glob(pat))
    if not files:
        emit("b10_roofline_table", 0.0, "no_dryrun_artifacts")
        return
    worst = None
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        rl = rec["roofline"]
        key = f"{rec['arch']}__{rec['shape']}"
        dom = rl["dominant"]
        tot = rl["compute_s"] + rl["memory_s"] + rl["collective_s"]
        emit(f"b10_roofline[{key}]", tot * 1e6,
             f"dom={dom},useful={rl['useful_ratio']:.2f},"
             f"hbm_gib={rec['per_device_total_bytes'] / 2**30:.1f}")
        if worst is None or tot > worst[1]:
            worst = (key, tot)
    if worst:
        emit("b10_roofline_worst", worst[1] * 1e6, worst[0])


def _two_worker_graph(n_remote=96):
    # fan-in: many remote tensors consumed along a local chain — lots
    # of Recvs, so the §3.2.1/§3.2.2/§5.2 build passes dominate the
    # uncached path while per-run execution stays cheap
    from repro.core import GraphBuilder

    b = GraphBuilder()
    remotes = [b.constant(jnp.ones((4, 4)), name=f"r{i}",
                          device="/job:worker/task:0")
               for i in range(n_remote)]
    cur = b.constant(jnp.ones((4, 4)), name="seed",
                     device="/job:worker/task:1")
    for i, r in enumerate(remotes):
        cur = b.add(b.mul(cur, cur, name=f"m{i}",
                          device="/job:worker/task:1"),
                    r, name=f"u{i}", device="/job:worker/task:1")
    out = b.reduce_sum(cur, name="out", device="/job:worker/task:1")
    return b.graph, out


def bench_executable_cache():
    """DESIGN.md §5: steady-state Session.run steps/sec, cached Executable
    vs rebuilding prune/place/partition/schedule/executors every run, on a
    2-worker graph (the paper's "caches these graphs" master optimisation).
    Both sessions run UNFUSED so b12 keeps measuring the interpreted
    dispatch path across PRs (b13 measures the fused path)."""
    from repro.core import Session
    from repro.runtime.devices import DeviceSet

    g1, out1 = _two_worker_graph()
    g2, out2 = _two_worker_graph()
    from repro.core.options import SessionOptions
    cached = Session(g1, options=SessionOptions(
        devices=DeviceSet.make_cluster(2, 1, kind="cpu"),
        fuse_regions=False))
    uncached = Session(g2, options=SessionOptions(
        devices=DeviceSet.make_cluster(2, 1, kind="cpu"),
        max_cached_executables=0, fuse_regions=False))
    us_uncached = _timeit(lambda: uncached.run(out2.ref), n=8, warmup=2)
    us_cached = _timeit(lambda: cached.run(out1.ref), n=8, warmup=2)
    sps_cached = 1e6 / us_cached
    sps_uncached = 1e6 / us_uncached
    emit("b12_run_uncached", us_uncached, f"{sps_uncached:.0f}steps/s")
    emit("b12_run_cached_executable", us_cached,
         f"{sps_cached:.0f}steps/s,speedup={us_uncached / us_cached:.1f}x,"
         f"hits={cached.cache_stats['hits']}")


def bench_fused_partitioned_step():
    """§10 region fusion (DESIGN.md §7): the b12 2-worker graph executed
    as a handful of FusedRegion kernels + Send/Recv, vs the same cached
    Executable interpreted node-by-node; plus per-op dispatch overhead on
    a fused 64-op chain vs the b1-style interpreted chain.  The fused
    session runs numerics="fast" — the shipping default for the graph
    engine (DESIGN.md §9) — so the terminal ReduceSum joins the region
    and regions compile at full XLA optimization."""
    from repro.core import GraphBuilder, Session
    from repro.runtime.devices import DeviceSet

    g1, out1 = _two_worker_graph()
    g2, out2 = _two_worker_graph()
    from repro.core.options import SessionOptions
    fused = Session(g1, options=SessionOptions(
        devices=DeviceSet.make_cluster(2, 1, kind="cpu"),
        fuse_regions=True, numerics="fast", parity_guard=False))
    interp = Session(g2, options=SessionOptions(
        devices=DeviceSet.make_cluster(2, 1, kind="cpu"),
        fuse_regions=False))
    us_interp = _timeit(lambda: interp.run(out2.ref), n=8, warmup=2)
    us_fused = _timeit(lambda: fused.run(out1.ref), n=8, warmup=2)
    emit("b13_fused_partitioned_step", us_fused,
         f"{1e6 / us_fused:.0f}steps/s,interp={1e6 / us_interp:.0f}steps/s,"
         f"speedup={us_interp / us_fused:.1f}x,numerics=fast")

    # per-op dispatch overhead: placeholder-fed so constant folding cannot
    # collapse the chain — the fused run dispatches ONE super-node
    n_ops = 64
    b = GraphBuilder()
    x = b.placeholder("x")
    cur = x
    for i in range(n_ops):
        cur = b.add(cur, x, name=f"a{i}")
    from repro.core.options import SessionOptions
    sf = Session(b.graph, options=SessionOptions(
        fuse_regions=True, numerics="fast", parity_guard=False))
    su = Session(b.graph, options=SessionOptions(fuse_regions=False))
    X = jnp.ones((8, 8))
    us_u = _timeit(lambda: su.run(cur.ref, {x.ref: X}))
    us_f = _timeit(lambda: sf.run(cur.ref, {x.ref: X}))
    emit("b13_fused_chain_dispatch", us_f,
         f"{us_f / n_ops:.2f}us/op@{n_ops}ops,interp={us_u / n_ops:.2f}us/op,"
         f"speedup={us_u / us_f:.1f}x")


def bench_replicated_training():
    """§4.3–§4.4 / DESIGN.md §15: the factory-Call smoke-LM train step
    replicated over a real 4-process worker pool.

    Reports aggregate tok/s at 1 vs 4 sync replicas plus a 4-replica
    async (parameter-server) leg, and the sync-vs-async loss after the
    same 20-shard stream.  NOTE the scaling derived field is hardware-
    bound: on a single-core container every replica's XLA compute and
    every wire pickle shares one core, so aggregate tok/s is capped near
    1x regardless of replica count (the per-process CPU accounting in
    the wire `timings` stats shows the step is CPU-bound, not
    latency-bound).  On an m-core pool the replica compute runs in
    separate worker processes and the same graph scales.
    """
    from repro.configs import get_config
    from repro.core.options import SessionOptions
    from repro.distrib.replication import ReplicaPlan
    from repro.distrib.worker import (start_worker_processes,
                                      stop_worker_processes)
    from repro.launch.steps import build_lm_replica_spec
    from repro.models.api import Shape

    cfg = get_config("smollm_360m", smoke=True)
    batch, seq, conv_steps = 2, 64, 20
    spec = build_lm_replica_spec(
        cfg, Shape("custom", seq, batch, "train"), lr=1e-2, seed=0,
        hparam_overrides={"compute_dtype": jnp.float32,
                          "loss_chunk": 0, "q_chunk": 0})

    def shard(i, r):
        # a 4-shard cycle per replica: repeated data makes the loss drop
        # visibly within the 20-step convergence window
        rs = np.random.RandomState(1000003 * (i % 4) + 131 * r)
        return {n: rs.randint(0, cfg.vocab_size, (batch, seq))
                .astype(np.int32) for n in spec.feed_names}

    procs, cspec = start_worker_processes(4)
    opts = SessionOptions(numerics="fast", parity_guard=False)
    try:
        results = {}
        for n_rep in (1, 4):
            plan = ReplicaPlan(spec, n_rep, mode="sync", cluster=cspec,
                               options=opts)
            losses = [plan.step([shard(i, r) for r in range(n_rep)])
                      for i in range(conv_steps)]
            fixed = [shard(0, r) for r in range(n_rep)]
            us = _timeit(lambda: plan.step(fixed), n=10, warmup=3)
            results[n_rep] = (us, losses)
            plan.close()
        us1, _ = results[1]
        us4, sync_losses = results[4]
        tok1 = batch * seq / (us1 / 1e6)
        tok4 = 4 * batch * seq / (us4 / 1e6)
        emit("b14_replicated_sync_1x", us1, f"{tok1:.0f}tok/s")
        emit("b14_replicated_sync_4x", us4,
             f"{tok4:.0f}tok/s,scaling={tok4 / tok1:.2f}x,"
             f"loss={sync_losses[0]:.3f}->{sync_losses[-1]:.3f},"
             f"1core-serialized-compute")

        plan = ReplicaPlan(spec, 4, mode="async", cluster=cspec,
                           options=opts)
        plan.run_async(shard, 8)  # warm: registration + per-replica compile
        plan.set_variable_values(spec.init_values)
        # a longer window than sync: interleaved applies see ~n_replicas
        # of gradient staleness, so early losses churn before descending
        async_steps = 2 * conv_steps
        t0 = time.perf_counter()
        applies = plan.run_async(shard, async_steps)
        us_async = (time.perf_counter() - t0) / async_steps * 1e6
        async_last = applies[-1][2]
        plan.close()
        tok_async = batch * seq / (us_async / 1e6)
        emit("b14_replicated_async_4x", us_async,
             f"{tok_async:.0f}tok/s,loss={applies[0][2]:.3f}->"
             f"{async_last:.3f},sync_loss={sync_losses[-1]:.3f}")
    finally:
        stop_worker_processes(procs, cspec)


BENCHES = [
    bench_session_run_overhead,
    bench_compiled_vs_eager,
    bench_send_recv,
    bench_compression,
    bench_input_pipeline,
    bench_cse,
    bench_recv_scheduling,
    bench_kernels,
    bench_train_throughput,
    bench_roofline_table,
    bench_executable_cache,
    bench_fused_partitioned_step,
    bench_replicated_training,
]


def _git_rev() -> str:
    try:
        import subprocess

        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — best effort outside a checkout
        return "unknown"


def write_json(path: str) -> None:
    """Persist the run as BENCH_latest.json (the --check baseline) AND
    append it to BENCH_history.jsonl — one line per full run, so perf is
    a time series across PRs/CI runs, not a single overwritten snapshot."""
    rec = {name: {"us_per_call": us, "derived": derived}
           for name, us, derived in ROWS}
    with open(path, "w") as fh:
        json.dump(rec, fh, indent=2, sort_keys=True)
    print(f"# wrote {path}", flush=True)
    hist = os.path.join(os.path.dirname(os.path.abspath(path)),
                        "BENCH_history.jsonl")
    entry = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
             "rev": _git_rev(), "metrics": rec}
    with open(hist, "a") as fh:
        fh.write(json.dumps(entry, sort_keys=True) + "\n")
    print(f"# appended {hist}", flush=True)


# --- regression gate (CI / `pytest -m benchcheck`) --------------------------

# key metrics guarded against regression, with the benchmark function
# that produces each (b1: dispatch overhead, b2: fused-fast eager engine,
# b8: LM step with the kernel registry off/on, b9: end-to-end training,
# b12: cached multi-device step, b13: fused multi-device step)
KEY_METRICS = {
    "b1_session_run_overhead": bench_session_run_overhead,
    "b2_fused_fast_graph": bench_compiled_vs_eager,
    "b8_lm_generic_fused": bench_kernels,
    "b8_lm_pallas_fused": bench_kernels,
    "b9_train_tokens_per_s": bench_train_throughput,
    "b12_run_cached_executable": bench_executable_cache,
    "b13_fused_partitioned_step": bench_fused_partitioned_step,
    "b14_replicated_sync_1x": bench_replicated_training,
    "b14_replicated_sync_4x": bench_replicated_training,
}

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "BENCH_latest.json")


def run_check(threshold: float = 0.25, baseline_path: str = BASELINE_PATH,
              metrics=None) -> int:
    """Re-run the key benchmarks and compare against the committed
    baseline artifact; returns the number of metrics that regressed by
    more than ``threshold`` (so 0 == pass).  A metric missing from the
    baseline (e.g. first run after adding it) is reported but not failed.
    """
    with open(baseline_path) as fh:
        baseline = json.load(fh)
    wanted = dict(KEY_METRICS if metrics is None else
                  {m: KEY_METRICS[m] for m in metrics})

    def run_bench(bench) -> None:
        try:
            bench()
        except Exception as e:  # noqa: BLE001
            emit(f"FAIL_{bench.__name__}", -1.0, repr(e)[:80])

    def best(metric: str):
        # min across (re)runs: the noise-robust latency estimator
        vals = [us for name, us, _ in ROWS if name == metric and us >= 0]
        return min(vals) if vals else None

    for bench in dict.fromkeys(wanted.values()):
        run_bench(bench)
    failures = 0
    for metric, bench in wanted.items():
        if metric not in baseline:
            print(f"# CHECK SKIP {metric}: not in baseline "
                  f"({os.path.basename(baseline_path)})")
            continue
        base_us = baseline[metric]["us_per_call"]

        def ratio():
            new_us = best(metric)
            if new_us is None or base_us <= 0:
                return None
            return new_us / base_us

        r = ratio()
        retries = 2
        while r is not None and r > 1.0 + threshold and retries:
            retries -= 1  # looks like a regression: re-measure before failing
            run_bench(bench)
            r = ratio()
        if r is None:
            print(f"# CHECK FAIL {metric}: benchmark did not produce it")
            failures += 1
            continue
        status = "FAIL" if r > 1.0 + threshold else "ok"
        print(f"# CHECK {status} {metric}: {best(metric):.1f}us vs "
              f"baseline {base_us:.1f}us ({r:.2f}x)")
        if r > 1.0 + threshold:
            failures += 1
    return failures


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark function names")
    ap.add_argument("--json", default=None,
                    help="path for the BENCH_*.json artifact ('' disables; "
                         "default: BENCH_latest.json for full runs, disabled "
                         "for --only runs so a filtered subset never "
                         "clobbers the tracked artifact)")
    ap.add_argument("--check", action="store_true",
                    help="re-run the key metrics (b1, b2-fast, b8, b9, b12, "
                         "b13) "
                         "and exit non-zero if any regressed >25%% vs the "
                         "committed BENCH_latest.json")
    ap.add_argument("--check-threshold", type=float, default=0.25,
                    help="allowed relative regression for --check")
    args = ap.parse_args(argv)
    from repro.launch.cli import enable_compile_cache

    enable_compile_cache()
    if args.check:
        print("name,us_per_call,derived")
        failures = run_check(threshold=args.check_threshold)
        sys.exit(1 if failures else 0)
    if args.json is None:
        args.json = "" if args.only else os.path.join(
            os.path.dirname(__file__), "BENCH_latest.json")
    print("name,us_per_call,derived")
    for bench in BENCHES:
        if args.only and args.only not in bench.__name__:
            continue
        try:
            bench()
        except Exception as e:  # noqa: BLE001
            emit(f"FAIL_{bench.__name__}", -1.0, repr(e)[:80])
    failed = [name for name, _us, _d in ROWS if name.startswith("FAIL_")]
    if args.json and failed:
        print(f"# not writing {args.json}: {len(failed)} benchmark(s) failed "
              f"({', '.join(failed)}) — keeping the last good artifact", flush=True)
    elif args.json:
        write_json(args.json)




def bench_continuous_batching():
    """Serving layer: occupancy + throughput with continuous slot refill."""
    import jax
    from repro.configs import get_config
    from repro.models.api import Model
    from repro.serving import ContinuousBatcher, Request

    cfg = get_config("qwen2-0.5b", smoke=True)
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batcher = ContinuousBatcher(model, params, n_slots=4, max_seq=64)
    rs = np.random.RandomState(0)
    n_req = 12
    for i in range(n_req):
        batcher.submit(Request(rid=i, prompt=list(rs.randint(0, 64, (4,))),
                               max_new_tokens=8))
    t0 = time.time()
    results = batcher.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.tokens) + r.prompt_len for r in results.values())
    emit("b11_continuous_batching", dt / max(batcher.stats['steps'], 1) * 1e6,
         f"{toks / dt:.0f}tok/s,occupancy={batcher.occupancy():.2f},"
         f"reqs={len(results)}")


BENCHES.append(bench_continuous_batching)


def bench_trace_overhead():
    """§16 distributed EEG: steps/s with tracing off vs on.

    The off row is the headline — SessionOptions(trace_dir=None) must be
    indistinguishable from pre-§16 builds, because every instrumentation
    site reduces to one ``is None`` check.  The bench also asserts the
    structural half of that claim: an untraced run records zero events
    into any recorder (no buffer even exists to fill)."""
    from repro.core import GraphBuilder, Session
    from repro.core.options import SessionOptions
    from repro.obs import spans as spans_mod

    def build():
        b = GraphBuilder()
        x = b.constant(jnp.ones((8, 8)), name="x")
        cur = x
        for i in range(64):
            cur = b.add(cur, x, name=f"a{i}")
        return b, cur

    spans_mod.install(None)
    b_off, cur_off = build()
    sess_off = Session(b_off.graph)
    b_on, cur_on = build()
    sess_on = Session(b_on.graph, options=SessionOptions(trace_dir="/tmp/b15"))
    # warm BOTH before timing either: the second session to compile the
    # (identical) fused region hits jax's compile cache, and timing it
    # cold-vs-warm would swamp the instrumentation cost being measured
    for _ in range(3):
        sess_off.run(cur_off.ref)
        sess_on.run(cur_on.ref)

    us_off = _timeit(lambda: sess_off.run(cur_off.ref))
    assert sess_off._spans is None and spans_mod.get() is sess_on._spans, \
        "trace-off session must not own a span recorder"
    us_on = _timeit(lambda: sess_on.run(cur_on.ref))
    n_events = len(sess_on._spans)
    spans_mod.install(None)
    sess_off.close()
    sess_on.close()
    assert n_events > 0, "traced run recorded nothing"

    emit("b15_trace_off", us_off, f"traced={us_on:.2f}us,"
         f"overhead={us_on / us_off - 1.0:+.1%},events={n_events}")


BENCHES.append(bench_trace_overhead)


if __name__ == "__main__":
    main()
