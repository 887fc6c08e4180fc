"""End-to-end training driver (deliverable (b): the e2e example).

Builds the training step AS A repro.core GRAPH (Session + §4.1 gradients
+ optimizer nodes), lowers it (§10), jits it, and drives it from the
§4.5/§4.6 input pipeline with §3.3 periodic checkpointing + restart
recovery.  On CPU use a reduced config; on a pod pass --mesh.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \\
      --steps 200 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager, FileCheckpointIO
from ..configs import get_config
from ..core.options import SessionOptions
from ..data import SyntheticLMDataset, Prefetcher, batch_iterator
from ..models.api import Shape
from ..models.params import init_params, count_params
from ..obs import metrics as obs_metrics
from ..optim import adamw_init
from .cli import (add_cluster_options, add_engine_options, add_model_options,
                  add_obs_options, enable_compile_cache)
from .steps import build_train_step, build_eager_train_step


#: The step hyperparameters every training entry point runs with: f32
#: compute, no loss or query chunking.
TRAIN_HPARAMS = {"compute_dtype": jnp.float32, "loss_chunk": 0, "q_chunk": 0}


def train(arch: str = "smollm-360m", *, smoke: bool = False, steps: int = 200,
          batch: int = 8, seq: int = 256, lr: float = 1e-3,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 100,
          log_every: int = 10, seed: int = 0,
          resume: bool = True, engine: str = "jit",
          numerics: str = "fast",
          backend: Optional[str] = None,
          summary_dir: Optional[str] = None,
          metrics_every: int = 0) -> Dict[str, Any]:
    """``engine="jit"`` lowers the step graph and jits it (§10);
    ``engine="graph"`` drives the same graph through ``Session.run``, where
    the steady-state loop re-runs one cached Executable per step
    (compile once, run many; DESIGN.md §5).  The graph engine defaults to
    ``numerics="fast"`` — fused regions (incl. matmuls/reductions) compile
    at full XLA optimization under the §9 tolerance contract enforced by
    the CI parity gate; ``numerics="strict"`` restores bit-parity with
    unfused execution.

    Returns the per-step ``losses``, the per-step wall seconds
    (``step_seconds``, each timed to the step's outputs being ready; the
    first includes compilation) and the final training state
    (``variables``: ``params`` and ``opt``)."""
    cfg = get_config(arch, smoke=smoke)
    shape = Shape("custom", seq, batch, "train")
    eb = None
    if engine == "graph":
        eb = build_eager_train_step(cfg, shape, lr=lr,
                                    hparam_overrides=TRAIN_HPARAMS,
                                    numerics=numerics,
                                    options=SessionOptions(backend=backend))
        model, graph_nodes = eb.model, eb.graph_nodes
    else:
        sb = build_train_step(cfg, shape, lr=lr,
                              hparam_overrides=TRAIN_HPARAMS)
        model, graph_nodes = sb.model, sb.graph_nodes
    n_params = count_params(model.describe_params())
    print(f"[train] arch={cfg.arch_id} engine={engine}"
          f"{'/' + numerics if engine == 'graph' else ''} "
          f"params={n_params/1e6:.1f}M "
          f"batch={batch} seq={seq} graph_nodes={graph_nodes}")

    params = init_params(model.describe_params(), jax.random.PRNGKey(seed))
    variables = {"params": params, "opt": adamw_init(params)}
    if engine == "graph":
        def step_fn(feeds, variables):
            # params/opt live in the Session's variable store; the Assign
            # nodes in the cached Executable update them in place.
            return eb.step(feeds), variables
    else:
        step_fn = jax.jit(sb.fn, donate_argnums=(1,))

    mgr = None
    start_step = 0
    if ckpt_dir:
        mgr = CheckpointManager(FileCheckpointIO(ckpt_dir), every_steps=ckpt_every)
        if resume and mgr.latest_step() is not None:
            restored = mgr.restore_latest()
            rv = restored["variables"]
            if not isinstance(rv, dict):
                # cross-process restore: FileCheckpointIO keeps treedefs
                # in-process only and hands back flat leaves — rebuild
                # against the freshly-initialised template structure
                rv = jax.tree.unflatten(jax.tree.structure(variables), rv)
            variables = rv
            start_step = int(mgr.latest_step())
            print(f"[train] resumed from step {start_step} (§3.3 recovery)")
    if engine == "graph":
        for name, value in variables.items():
            eb.session.set_variable(name, value)
        variables = {}  # the Session's store holds the only copy

    def snapshot_variables() -> Dict[str, Any]:
        return eb.variables() if engine == "graph" else variables

    ds = SyntheticLMDataset(cfg.vocab_size, seq, seed=seed)
    pipe = Prefetcher(batch_iterator(ds, batch, start_step), capacity=4).start()

    writer = None
    if summary_dir or ckpt_dir:  # §9.1: explicit dir, else next to ckpts
        from ..tools import SummaryWriter

        writer = SummaryWriter(summary_dir or os.path.join(ckpt_dir, "events"))

    losses = []
    step_seconds = []
    t0 = time.time()
    for i in range(start_step, steps):
        raw = pipe.get()
        feeds = {"tokens": jnp.asarray(raw["tokens"]),
                 "labels": jnp.asarray(raw["labels"])}
        if model.is_encdec:
            feeds["frames"] = jnp.zeros(
                (batch, cfg.enc_seq, cfg.d_model), jnp.float32)
        t_step = time.time()
        loss, variables = step_fn(feeds, variables)
        jax.block_until_ready((loss, variables))
        step_seconds.append(time.time() - t_step)
        losses.append(float(loss))
        if writer:
            writer.add(i + 1, "train/loss", losses[-1])
            writer.add(i + 1, "train/tokens_per_sec",
                       batch * seq / max(step_seconds[-1], 1e-9))
        if mgr and mgr.should_save(i + 1):
            mgr.save(i + 1, {"variables": snapshot_variables()})
        if (i + 1) % log_every == 0:
            rate = (i + 1 - start_step) * batch * seq / (time.time() - t0)
            print(f"[train] step {i+1:5d} loss {float(loss):.4f} "
                  f"({rate:,.0f} tok/s)")
        if metrics_every and (i + 1) % metrics_every == 0:
            snap = obs_metrics.snapshot()
            interesting = {k: v for k, v in snap["counters"].items() if v}
            print(f"[train] metrics step={i+1}: "
                  + (" ".join(f"{k}={v}" for k, v
                              in sorted(interesting.items())) or "empty"))
    pipe.stop()
    if writer:
        writer.close()
    if mgr:
        mgr.save(steps, {"variables": snapshot_variables()})
    out: Dict[str, Any] = {"losses": losses,
                           "final_loss": losses[-1] if losses else None,
                           "params": n_params,
                           "step_seconds": step_seconds,
                           "variables": snapshot_variables()}
    if engine == "graph":
        out["executable_cache"] = eb.session.cache_stats
    return out


def train_cluster(cluster: str, *, steps: int = 50, batch: int = 64,
                  lr: float = 0.1, ckpt_dir: Optional[str] = None,
                  ckpt_every: int = 10, log_every: int = 10, seed: int = 0,
                  max_recoveries: int = 3, retry_wait: float = 3.0,
                  run_timeout: float = 60.0,
                  standby: Optional[str] = None,
                  trace_dir: Optional[str] = None,
                  metrics_every: int = 0) -> Dict[str, Any]:
    """§3.3/DESIGN.md §11/§13 multi-process training over a TCP pool.

    Drives the wire-shippable primitive-op classifier step
    (``launch/steps.build_wire_train_step``) across ``--cluster
    host:port,...`` workers: place/partition once, RegisterGraph each
    subgraph to its owning process, then one RunGraph fan-out per step
    with Send/Recv riding the wire rendezvous.

    Worker death (heartbeat timeout or transport error) aborts the step.
    Recovery prefers §13 **partial re-placement**: the dead task's
    subgraph is re-placed onto a ``--standby`` worker or a survivor,
    only its Variables restore from the last checkpoint (survivors keep
    live state), and the recovery log says exactly what was kept.  When
    nothing can host the dead task, the whole-pool fallback remains:
    wait for the pool, restore the checkpoint, rebind, resume.

    For data-parallel LM training over the pool (the §15 factory-Call
    step stamped N times), see ``train_replicated`` / ``--replicas N``.
    """
    from ..core import Session
    from ..core.executor import ExecutorError
    from ..distrib.master import RecoveryError
    from ..distrib.wire import ClusterSpec
    from .steps import build_wire_train_step

    spec = ClusterSpec.parse(cluster)
    tasks = [f"/job:worker/task:{t}" for t in range(len(spec.workers))]
    ws = build_wire_train_step(tasks, lr=lr, seed=seed)
    sess = Session(ws.builder.graph,
                   options=SessionOptions(cluster=spec, standby=standby or (),
                                          trace_dir=trace_dir))
    run = sess.make_callable([ws.loss, ws.train_op], [ws.feed_x, ws.feed_y])

    def step_stats_line() -> str:
        """Per-task StepStats from the last run_graph fan-out (§16.4):
        device wall/cpu totals plus wire counters, one clause per task."""
        master = getattr(sess, "_master", None)
        if master is None:
            return ""
        parts = []
        for plan in master.live_plans():
            stats = getattr(plan, "last_run_stats", None) or {}
            for task, st in sorted(stats.items()):
                t = st.get("timings", {})
                wall = sum(d.get("wall_s", 0.0) for d in t.values())
                cpu = sum(d.get("cpu_s", 0.0) for d in t.values())
                parts.append(
                    f"task{task} wall={wall*1e3:.1f}ms cpu={cpu*1e3:.1f}ms "
                    f"sends={st.get('sends', 0)} "
                    f"bytes={st.get('bytes_sent', 0)}")
            if parts:
                break
        return "; ".join(parts)

    def print_cluster_metrics(step: int) -> None:
        """Master-side distrib counters + each live worker's
        ``metrics_snapshot`` digest (§16.4)."""
        snap = obs_metrics.snapshot()
        dist = {k: v for k, v in snap["counters"].items()
                if v and k.startswith("distrib.")}
        print(f"[train] metrics step={step} master: "
              + (" ".join(f"{k}={v}" for k, v in sorted(dist.items()))
                 or "none"))
        master = getattr(sess, "_master", None)
        if master is None:
            return
        for task in range(len(spec.workers)):
            if task in master.dead:
                continue
            try:
                rep = master.channels[task].call("metrics_snapshot",
                                                 _timeout=5.0)
            except Exception:  # noqa: BLE001 — diagnostics stay best-effort
                continue
            h = rep["metrics"]["histograms"].get("worker.device_wall_s") or {}
            if h.get("count"):
                print(f"[train]   task{task}: device wall "
                      f"p50={h['p50']*1e3:.1f}ms p99={h['p99']*1e3:.1f}ms "
                      f"n={h['count']}")
    print(f"[train] cluster={','.join(spec.workers)} tasks={len(tasks)} "
          f"graph_nodes={len(ws.builder.graph.nodes)} (wire step)")

    mgr = None
    start_step = 0
    if ckpt_dir:
        mgr = CheckpointManager(FileCheckpointIO(ckpt_dir), prefix="wire",
                                every_steps=ckpt_every)
        if mgr.latest_step() is not None:
            for name, value in mgr.restore_latest().items():
                sess.set_variable(name, value)
            start_step = int(mgr.latest_step())
            print(f"[train] resumed from step {start_step} (§3.3 recovery)")

    def step_batch(i: int):
        rs = np.random.RandomState(seed * 100003 + i)  # replayable per step
        return (jnp.asarray(rs.randn(batch, 16).astype("f")),
                jnp.asarray(rs.randint(0, 8, (batch,)).astype("i")))

    from ..distrib.protocol import WorkerError

    losses = []
    recoveries = 0
    i = start_step
    t0 = time.time()
    try:
        while i < steps:
            x, y = step_batch(i)
            try:
                loss, _ = run(x, y)
                losses.append(float(loss))
                i += 1
                if mgr and mgr.should_save(i):
                    # the checkpoint pull is inside the recovery scope
                    # too: a worker lost between the step and the save
                    # must trigger recovery, not abort training
                    mgr.save(i, sess.pull_cluster_variables())
                if i % log_every == 0:
                    rate = (i - start_step) / max(time.time() - t0, 1e-9)
                    print(f"[train] step {i:5d} loss {losses[-1]:.4f} "
                          f"({rate:.1f} steps/s over the wire)")
                    stats_line = step_stats_line()
                    if stats_line:
                        print(f"[train] StepStats step={i}: {stats_line}")
                if metrics_every and i % metrics_every == 0:
                    print_cluster_metrics(i)
            except (ExecutorError, WorkerError, OSError) as e:
                if recoveries >= max_recoveries:
                    raise
                recoveries += 1
                print(f"[train] §3.3 worker-pool failure: {e}\n"
                      f"[train] recovery {recoveries}/{max_recoveries}: "
                      f"trying §13 partial re-placement first")
                # --- §13 partial path: re-place only the dead task(s),
                # survivors keep live state; only the dead task's
                # Variables restore from the last checkpoint.
                try:
                    ckpt = (mgr.restore_latest()
                            if mgr and mgr.latest_step() is not None else None)
                    report = sess.recover_dead_tasks(ckpt)
                    if report.mode != "noop":
                        print(report.describe())
                        if ckpt is not None and report.restored:
                            # replacement tasks restart from the checkpoint
                            # step; survivors being ahead is tolerated by
                            # the §4.1 parameter-server async lineage (§13)
                            i = int(mgr.latest_step())
                        continue
                    print("[train] no task marked dead (transient "
                          "transport failure) — whole-pool path")
                except RecoveryError as pe:
                    print(f"[train] partial re-placement unavailable: {pe}\n"
                          f"[train] falling back to whole-pool restart: "
                          f"waiting {retry_wait:.0f}s for the pool, restoring "
                          f"last checkpoint")
                except Exception as pe:  # noqa: BLE001 — replacement died too
                    print(f"[train] partial re-placement failed: {pe}\n"
                          f"[train] falling back to whole-pool restart")
                time.sleep(retry_wait)
                if mgr and mgr.latest_step() is not None:
                    for name, value in mgr.restore_latest().items():
                        sess.set_variable(name, value)
                    i = int(mgr.latest_step())
                else:
                    # no checkpoint yet: try to salvage live state (the
                    # pool may be up with the failure transient);
                    # otherwise the rebind push would overwrite trained
                    # worker weights with the session store's step-0
                    # values, so training must honestly restart at step 0
                    try:
                        salvaged = sess.pull_cluster_variables()
                    except Exception:  # noqa: BLE001 — workers really gone
                        salvaged = {}
                    if not salvaged:
                        print("[train] no checkpoint and worker state "
                              "lost: restarting training from step 0 "
                              "(§3.3 — pass --ckpt-dir to bound the loss)")
                        i = 0
                try:
                    sess.rebind_cluster()  # reconnect + push restored state
                except Exception as re_err:  # noqa: BLE001 — pool still down
                    print(f"[train] pool still unavailable: {re_err}")
                    # a fresh pool re-seeds from the (restored) session
                    # store at registration, so the next attempt is correct
        if mgr:
            mgr.save(steps, sess.pull_cluster_variables())
        if trace_dir:
            path = sess.export_trace()
            if path:
                print(f"[train] wrote merged trace to {path} "
                      f"(load in Perfetto / chrome://tracing)")
    finally:
        sess.close()
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "recoveries": recoveries,
            "executable_cache": sess.cache_stats}


def train_replicated(cluster: Optional[str], *, arch: str = "smollm-360m",
                     smoke: bool = False, replicas: int = 4,
                     mode: str = "sync", steps: int = 30, batch: int = 8,
                     seq: int = 64, lr: float = 1e-2, log_every: int = 5,
                     seed: int = 0, numerics: str = "fast",
                     backend: Optional[str] = None) -> Dict[str, Any]:
    """§15 data-parallel LM training: the factory-Call train step stamped
    ``replicas`` times over the ``--cluster`` pool by a ReplicaPlan.

    ``mode="sync"`` runs one barrier step per iteration — every replica's
    gradient flows through the per-Variable reduce tree (Send/Recv over
    the wire) into a single averaged AdamW apply on the parameters' home
    task.  ``mode="async"`` keeps the parameters master-side and drives
    one thread per replica with interleaved applies and no barrier
    (Downpour-style; bounded staleness ~ replicas).  ``cluster=None``
    runs the same plan on in-process devices (testing/benchmarks).
    """
    from ..distrib.replication import ReplicaPlan
    from .steps import build_lm_replica_spec

    cfg = get_config(arch, smoke=smoke)
    shape = Shape("custom", seq, batch, "train")
    spec = build_lm_replica_spec(
        cfg, shape, lr=lr, seed=seed,
        hparam_overrides=TRAIN_HPARAMS)
    # parity_guard off: a whole fused train step (loss+grad+adamw+reduce)
    # legitimately drifts past the per-op-class §9 tolerance, and the
    # guard's strict fallback would serialize every step; --numerics
    # strict restores bit-exact execution when that trade is wanted
    plan = ReplicaPlan(spec, replicas, mode=mode, cluster=cluster,
                       options=SessionOptions(numerics=numerics,
                                              backend=backend,
                                              parity_guard=False))
    n_params = sum(np.asarray(x).size
                   for x in jax.tree.leaves(spec.init_values["params"]))
    print(f"[train] replicated arch={cfg.arch_id} replicas={replicas} "
          f"mode={mode} cluster={cluster or 'in-process'} "
          f"params={n_params/1e6:.1f}M batch={batch}x{seq} "
          f"graph_nodes={len(plan.builder.graph.nodes)}")

    def rep_batch(i: int, r: int) -> Dict[str, Any]:
        rs = np.random.RandomState(seed * 1000003 + i * 131 + r)
        return {"tokens": jnp.asarray(
                    rs.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32),
                "labels": jnp.asarray(
                    rs.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)}

    losses = []
    t0 = time.time()
    try:
        if mode == "sync":
            for i in range(steps):
                shards = [rep_batch(i, r) for r in range(replicas)]
                losses.append(float(plan.step(shards)))
                if (i + 1) % log_every == 0:
                    rate = ((i + 1) * replicas * batch * seq
                            / (time.time() - t0))
                    print(f"[train] step {i+1:5d} loss {losses[-1]:.4f} "
                          f"({rate:,.0f} tok/s across {replicas} replicas)")
        else:
            def on_step(i, r, loss):
                if (len(losses) + 1) % log_every == 0:
                    rate = ((len(losses) + 1) * batch * seq
                            / (time.time() - t0))
                    print(f"[train] apply {len(losses)+1:5d} "
                          f"(replica {r}) loss {loss:.4f} "
                          f"({rate:,.0f} tok/s, interleaved)")
                losses.append(loss)
            plan.run_async(rep_batch, steps, on_step=on_step)
    finally:
        plan.close()
    dt = time.time() - t0
    n_batches = steps * (replicas if mode == "sync" else 1)
    tok_s = n_batches * batch * seq / dt if dt > 0 else float("inf")
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "tok_per_s": tok_s, "mode": mode, "replicas": replicas}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    add_model_options(ap, arch="smollm-360m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    add_engine_options(ap)
    add_cluster_options(ap, replication=True, standby=True)
    add_obs_options(ap, summary=True)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.cluster and args.replicas > 1:
        res = train_replicated(args.cluster, arch=args.arch, smoke=args.smoke,
                               replicas=args.replicas, mode=args.mode,
                               steps=args.steps, batch=args.batch,
                               seq=args.seq, lr=args.lr,
                               numerics=args.numerics, backend=args.backend)
    elif args.cluster:
        res = train_cluster(args.cluster, steps=args.steps, batch=args.batch,
                            lr=args.lr, ckpt_dir=args.ckpt_dir,
                            ckpt_every=args.ckpt_every, standby=args.standby,
                            trace_dir=args.trace_dir,
                            metrics_every=args.metrics_every)
    else:
        res = train(args.arch, smoke=args.smoke, steps=args.steps,
                    batch=args.batch, seq=args.seq, lr=args.lr,
                    ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                    engine=args.engine, numerics=args.numerics,
                    backend=args.backend, summary_dir=args.summary_dir,
                    metrics_every=args.metrics_every)
    print(f"[train] done: final loss {res['final_loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
