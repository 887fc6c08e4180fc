"""Compile-once / run-many Executables (§3.2, §4.2; DESIGN.md §5).

The paper's master "caches these graphs so that subsequent uses incur no
recomputation overhead": pruning, placement, partitioning and Recv
scheduling happen once per *run signature* — the (fetches, fed-tensor
keys, device set, graph version) tuple — not once per ``Session.run``.

An :class:`Executable` is the cached product of that pipeline:

* the pruned node set (§4.2 feed/fetch rewrite),
* for multi-device graphs: the placement (§3.2.1), the partitioned
  graph with canonicalised Send/Recv pairs (§3.2.2) and the §5.2 Recv
  schedule,
* one *reusable* :class:`~repro.core.executor.Executor` per device —
  executors hold only immutable static analysis, so the same Executable
  can run repeatedly and concurrently; each ``run`` allocates nothing
  but per-run :class:`~repro.core.executor.ExecutorState` (plus a fresh
  rendezvous for multi-device runs).

:class:`ExecutableCache` is the small thread-safe LRU the Session keys
by :class:`RunSignature`.  The serving layer applies the same
compile-once/run-many discipline with a lighter mechanism — the batcher
caches its jitted slot step directly on the model instance
(serving/batcher.py).
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, FrozenSet, Hashable, List, Optional, Sequence, Set, Tuple

from .graph import TensorRef
from .executor import ExecutionContext, Executor, ExecutorError
from . import fusion as fusion_mod
from . import placement as placement_mod
from . import partition as partition_mod
from . import scheduler as scheduler_mod
from ..analysis import verifier as verifier_mod
from ..obs import metrics as metrics_mod
from ..runtime.devices import local_kind
from ..runtime.rendezvous import Rendezvous

# Ops whose side effects cannot be replayed for a reference re-execution:
# running the unfused-strict reference AND the fused-fast candidate on the
# same feeds would double-consume queue items / double-write checkpoints.
# Executables containing these skip the per-session parity guard (the CI
# gate covers their op classes instead; DESIGN.md §9).
GUARD_UNSAFE = frozenset(
    {"QueueEnqueue", "QueueDequeue", "Save", "Restore", "Send", "Recv"})


@dataclasses.dataclass(frozen=True)
class RunSignature:
    """Cache key for one prepared run pipeline (DESIGN.md §5).

    Two ``Session.run`` calls share an Executable iff they fetch the same
    tensors, feed the same tensor *keys* (values differ per run), see the
    same device set, and the graph has not been extended in between.
    """

    fetches: Tuple[TensorRef, ...]
    feed_keys: FrozenSet[TensorRef]
    device_fingerprint: Tuple[str, ...]
    graph_version: int
    # region fusion and its numerics mode are part of the signature:
    # flipping ``Session.fuse_regions`` or ``Session.numerics`` mid-
    # process must rebuild, never reuse a stale plan — strict and fast
    # executables cache separately (a cached strict executable silently
    # serving a fast-mode session, or vice versa, would make results
    # signature-dependent; DESIGN.md §9)
    fuse_regions: bool = True
    fuse_numerics: str = "strict"
    # the kernel-backend registry key (DESIGN.md §12): flipping
    # Session(backend=...) must rebuild, never reuse — a cached
    # generic-lowered Executable serving a pallas session (or vice
    # versa) would make which kernels run signature-dependent
    kernel_backend: str = "generic"
    # §14 verify mode: a cached warn-mode Executable must not silently
    # serve a Session that asked for verify="error" (the error-mode
    # build is the one that raises), so the mode is part of the key
    verify: str = "warn"

    @staticmethod
    def for_session(session, fetch_refs: Sequence[TensorRef],
                    feed_keys) -> "RunSignature":
        devs = session.devices
        fp = devs.fingerprint() if devs is not None else ()
        cluster = getattr(session, "cluster", None)
        if cluster is not None:
            # §3.3/DESIGN.md §13: the cluster's SHAPE (task count, devices
            # per task, kind) is part of the device fingerprint — a
            # different topology must rebuild Executables.  Endpoints are
            # deliberately absent: partial re-placement and whole-pool
            # rebinds keep cached Executables (placement depends only on
            # virtual device names) and re-register through the master's
            # generation counter / per-task re-registration instead
            fp = tuple(fp) + cluster.fingerprint()
        # every options-dependent key component derives from the session's
        # resolved SessionOptions in this one place (repro.core.options) —
        # the getattr fallbacks only serve bare session-like test doubles
        opts = getattr(session, "options", None)
        if opts is not None:
            fuse_regions, fuse_numerics = opts.fuse_regions, opts.numerics
            kernel_backend, verify = opts.backend, opts.verify
        else:
            fuse_regions = getattr(session, "fuse_regions", True)
            fuse_numerics = getattr(
                session, "numerics",
                os.environ.get("REPRO_FUSE_NUMERICS", "strict"))
            kernel_backend = getattr(session, "kernel_backend", "generic")
            verify = getattr(session, "verify", "warn")
        return RunSignature(
            fetches=tuple(fetch_refs),
            feed_keys=frozenset(feed_keys),
            device_fingerprint=fp,
            graph_version=session.graph.version,
            fuse_regions=fuse_regions,
            fuse_numerics=fuse_numerics,
            kernel_backend=kernel_backend,
            verify=verify,
        )


class ExecutableCache:
    """Thread-safe LRU of prepared execution state.

    ``maxsize == 0`` disables caching entirely (every lookup misses and
    nothing is stored) — used to benchmark the uncached path.
    """

    def __init__(self, maxsize: int = 16) -> None:
        self.maxsize = maxsize
        self._entries: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self.stats = {"hits": 0, "misses": 0, "evictions": 0, "invalidations": 0}

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, key: Hashable) -> Optional[Any]:
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.stats["hits"] += 1
                return self._entries[key]
            self.stats["misses"] += 1
            return None

    def put(self, key: Hashable, value: Any) -> None:
        if self.maxsize <= 0:
            return
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.stats["evictions"] += 1

    def get_or_build(self, key: Hashable, builder: Callable[[], Any]) -> Any:
        cached = self.get(key)
        if cached is not None:
            return cached
        value = builder()
        self.put(key, value)
        return value

    def invalidate(self, predicate: Optional[Callable[[Hashable], bool]] = None) -> int:
        """Drop entries whose key matches ``predicate`` (all if None)."""
        with self._lock:
            if predicate is None:
                n = len(self._entries)
                self._entries.clear()
            else:
                stale = [k for k in self._entries if predicate(k)]
                for k in stale:
                    del self._entries[k]
                n = len(stale)
            self.stats["invalidations"] += n
            return n

    def keys(self) -> List[Hashable]:
        with self._lock:
            return list(self._entries)


class Executable:
    """One fully-prepared run pipeline bound to a Session.

    Construction performs prune -> place -> partition -> schedule-recvs ->
    executor static analysis exactly once; ``run`` only allocates per-run
    state (and, multi-device, a fresh rendezvous + worker threads), so it
    is safe to call repeatedly and concurrently.
    """

    def __init__(self, session, fetch_refs: Sequence[TensorRef],
                 feed_keys, *,
                 node_set: Optional[Set[str]] = None,
                 compress: bool = False,
                 cost_model: Optional[placement_mod.CostModel] = None,
                 force_partitioned: bool = False,
                 fuse_regions: Optional[bool] = None,
                 numerics: Optional[str] = None) -> None:
        self.session = session
        self.fetches: Tuple[TensorRef, ...] = tuple(fetch_refs)
        self.feed_keys: FrozenSet[TensorRef] = frozenset(feed_keys)
        self.graph_version = session.graph.version
        self.compress = compress
        self.fuse_regions = (getattr(session, "fuse_regions", True)
                             if fuse_regions is None else fuse_regions)
        # numerics policy for fused regions (DESIGN.md §9): "strict"
        # (bit-parity) or "fast" (full XLA opt, tolerance-bounded drift)
        self.numerics: str = (
            numerics if numerics is not None
            else getattr(session, "numerics",
                         os.environ.get("REPRO_FUSE_NUMERICS", "strict")))
        # kernel-backend registry key (DESIGN.md §12); cluster executions
        # ship it in the WirePlan payloads so workers re-fuse their slices
        # under the same backend (distrib/worker.py, §15)
        self.kernel_backend: str = getattr(session, "kernel_backend",
                                           "generic")
        # DESIGN.md §7: region fusion runs once per signature, here; the
        # result (incl. each region's lazily-jitted kernel) is cached with
        # the Executable.  Fetches into fused members are remapped to the
        # exporting region's output port.
        self.fusion: Optional[fusion_mod.FusionResult] = None
        self._fetch_remap: Dict[TensorRef, TensorRef] = {}
        # tracer= runs observe the faithful unfused interpretation (per-
        # kernel EEG events, §9.2); built lazily on the first traced run
        self._unfused: Optional[Tuple[Any, Any]] = None
        self._unfused_lock = threading.Lock()

        if node_set is None:
            node_set = session.pruned_nodes(
                self.fetches, {k: None for k in self.feed_keys})
        self.node_set: Set[str] = set(node_set)

        devices = session.devices
        # Session.run uses the plain in-thread executor for 0/1-device
        # sessions; run_partitioned forces the worker-thread path even for
        # one device (it carries the device-kind kernel dispatch and the
        # join timeout), and a cluster session always partitions — even a
        # one-worker pool executes in its worker process, not here.
        self.multi_device = devices is not None and (
            len(devices) > 1 or force_partitioned
            or getattr(session, "cluster", None) is not None)
        # §3.3/DESIGN.md §11: a cluster session ships per-device subgraphs
        # to worker processes instead of running local executor threads
        self.cluster = getattr(session, "cluster", None)
        self.wire_plan = None
        if self.multi_device:
            cm = self._cost_model = cost_model or placement_mod.CostModel()
            self.placement = placement_mod.place(
                session.graph, devices, cm, self.node_set)
            # §4.4/DESIGN.md §8: partition is frame-aware — a while-loop
            # whose body straddles devices gets its control skeleton
            # replicated per device here, once, and the resulting
            # loop-bearing partition is cached by RunSignature exactly
            # like any straight-line graph.
            self.partitioned = partition_mod.partition(
                session.graph, self.placement, self.node_set, compress=compress)
            # §14 verifier (DESIGN.md): analyze the partitioned plan —
            # the canonical Send/Recv pairs and per-device schedule are
            # what actually runs — once per build; the report rides the
            # Executable so a cache hit re-runs no analysis.
            self.verify_report = verifier_mod.verify_executable(self)
            exec_graph = self.partitioned.graph
            exec_placement = self.partitioned.placement
            device_nodes = self.partitioned.device_nodes
            if self.cluster is not None:
                # ship the *unfused* partitioned subgraphs (fusion specs
                # hold jitted closures that cannot cross a process
                # boundary); each worker re-fuses its local slice under
                # the same numerics policy (distrib/worker.py, §7/§9)
                scheduler_mod.schedule_recvs(
                    exec_graph, set(exec_graph.nodes), cm, devices,
                    exec_placement)
                self.device_executors = {}
                self.fetch_by_dev = self._route_fetches(
                    exec_placement, device_nodes, remap=False)
                self.n_nodes = len(exec_graph.nodes)
                from ..distrib.master import WirePlan

                self.wire_plan = WirePlan(self, device_nodes)
                # kept for the §13 distributed parity guard: the strict
                # reference plan is built lazily from the same partition
                self._wire_device_nodes = device_nodes
                self._wire_strict: Optional[WirePlan] = None
                self._init_parity_guard(session)
                return
            if self.fuse_regions:
                fus = fusion_mod.try_fuse(
                    exec_graph, set(exec_graph.nodes),
                    placement=exec_placement,
                    feeds=self.feed_keys, fetch_refs=self.fetches,
                    written_vars=fusion_mod.written_variables(
                        exec_graph, exec_graph.nodes),
                    numerics=self.numerics,
                    backend=self.kernel_backend)
                if fus is not None and (fus.regions or fus.changed):
                    self.fusion = fus
                    exec_graph = fus.graph
                    exec_placement = fus.placement
                    self._fetch_remap = fus.fetch_map
                    device_nodes = {}
                    for n in fus.names:
                        device_nodes.setdefault(
                            exec_placement[n], set()).add(n)
            scheduler_mod.schedule_recvs(
                exec_graph, set(exec_graph.nodes), cm, devices, exec_placement)
            # one immutable Executor per device, reused across runs
            self.device_executors = self._build_executors(
                exec_graph, device_nodes)
            self.fetch_by_dev = self._route_fetches(
                exec_placement, device_nodes, remap=True)
            self.n_nodes = len(exec_graph.nodes)
        else:
            # §14 verifier, single-device path: the pruned subgraph.
            self.verify_report = verifier_mod.verify_executable(self)
            exec_graph, exec_names = session.graph, self.node_set
            if self.fuse_regions:
                fus = fusion_mod.try_fuse(
                    session.graph, self.node_set, placement=None,
                    device_kind=local_kind(),
                    feeds=self.feed_keys, fetch_refs=self.fetches,
                    written_vars=fusion_mod.written_variables(
                        session.graph, self.node_set),
                    numerics=self.numerics,
                    backend=self.kernel_backend)
                if fus is not None and (fus.regions or fus.changed):
                    self.fusion = fus
                    exec_graph, exec_names = fus.graph, fus.names
                    self._fetch_remap = fus.fetch_map
            self.executor = Executor(exec_graph, node_filter=exec_names)
            self.n_nodes = len(exec_names)

        self._init_parity_guard(session)

    def _init_parity_guard(self, session) -> None:
        # ---- fast-mode parity guard (DESIGN.md §9) -------------------
        # The first run of a fast-numerics Executable is verified against
        # the unfused-strict reference within the §9 per-op-class
        # tolerances; with ``REPRO_NUMERICS_GUARD=sample:N`` every Nth
        # subsequent run re-verifies too (long-lived serving processes:
        # input distribution shift can expose drift the first batch
        # didn't).  A breach warns and permanently falls back to strict
        # (unfused) execution.  Skipped when the executed set contains
        # ops whose side effects cannot be replayed (queues, checkpoint
        # IO) — the CI parity gate still covers those op classes.
        # Cluster Executables get the DISTRIBUTED guard (§13): Variable
        # state lives worker-side, so the snapshot/restore rides
        # get_variables/set_variables and the reference is a strict wire
        # run of the same partition (strict == unfused bit-for-bit, §7);
        # a breach demotes to the strict WirePlan, never to local
        # execution (which would desync from worker-side state).
        self._strict_fallback = False
        self._parity_pending = False
        self._guard_lock = threading.Lock()
        self._guard_vars: List[str] = []
        self._guard_update_vars: List[str] = []
        self._guard_update_tol = 0.0
        self._guard_tol = None
        self._guard_every: Optional[int] = None
        self._guard_runs = 0
        fused = self.fusion is not None and self.fusion.regions
        if (self.numerics == "fast"
                and (fused or self.wire_plan is not None)
                and getattr(session, "parity_guard", False)):
            ops = {session.graph.nodes[n].op for n in self.node_set}
            if not ops & GUARD_UNSAFE:
                from . import numerics as numerics_mod  # lazy: import cycle

                self._parity_pending = True
                # only *written* variables can drift (read-only ones are
                # restored-snapshot-identical by construction); limiting
                # the snapshot avoids holding 3 extra copies of e.g. a
                # serve graph's full params through the first token
                self._guard_vars = sorted(
                    fusion_mod.written_variables(session.graph,
                                                 self.node_set)
                    & {n for n in self.node_set
                       if session.graph.nodes[n].op == "Variable"})
                kinds = (local_kind(),)
                if self.multi_device and getattr(self, "placement", None):
                    kinds = tuple(sorted(
                        {fusion_mod._device_kind(d, kinds[0])
                         for d in self.placement.values()})) or kinds
                self._guard_tol = numerics_mod.tolerance_for_ops(
                    ops, device_kinds=kinds, backend=self.kernel_backend)
                # parameters an optimizer rewrites are judged by their
                # update, not elementwise (numerics.UPDATE_TOLERANCE)
                self._guard_update_vars = sorted(
                    numerics_mod.optimizer_written_variables(
                        session.graph, self.node_set)
                    & set(self._guard_vars))
                self._guard_update_tol = numerics_mod.update_tolerance(kinds)
                self._guard_every = getattr(session, "parity_guard_every", None)

    # ------------------------------------------------------------------
    def run(self, feeds: Optional[Dict[TensorRef, Any]] = None, *,
            trace: Optional[List[str]] = None, tracer: Any = None,
            spans: Any = None, timeout: float = 60.0) -> List[Any]:
        feeds = feeds or {}
        if frozenset(feeds) != self.feed_keys:
            raise ExecutorError(
                f"feed keys {sorted(map(str, feeds))} do not match the keys this "
                f"Executable was compiled for {sorted(map(str, self.feed_keys))}")
        # Session(trace_dir=) turns on the §16 span stream for every run of
        # this session, including make_callable paths that pass no kwargs.
        # Unlike trace=/tracer= it is NOT part of the run signature: spans
        # observe the compiled artifact without changing it.
        if spans is None:
            spans = getattr(self.session, "_spans", None)
        if self.wire_plan is not None:
            # DESIGN.md §11: multi-process execution over the wire
            # rendezvous; the legacy per-kernel tracer needs the in-process
            # engine, but the §16 span stream traces cluster runs natively
            if tracer is not None or trace is not None:
                raise ExecutorError(
                    "trace=/tracer= are not supported for cluster execution "
                    "(use Session(trace_dir=) / REPRO_TRACE for the "
                    "distributed EEG, or run without cluster= for legacy "
                    "per-kernel tracing)")
            if self._strict_fallback:
                # §13 breach demotion: route through the strict wire plan
                # (same partition, strict numerics worker-side) — NOT the
                # local unfused pipeline, which would run against stale
                # master-side Variable state
                return self._wire_strict_plan().run(feeds, timeout=timeout,
                                                    spans=spans)
            if self._parity_pending:
                return self._guarded_wire_run(feeds, timeout, spans=spans)
            if self._sample_due():
                return self._guarded_wire_run(feeds, timeout, sampled=True,
                                              spans=spans)
            return self.wire_plan.run(feeds, timeout=timeout, spans=spans)
        if tracer is not None and self.fusion is not None:
            # per-kernel tracing: run the faithful unfused interpretation
            # (fused kernels are opaque blobs to an EEG-style tracer)
            return self._run_unfused(feeds, trace=trace, tracer=tracer,
                                     timeout=timeout)
        if self._strict_fallback:
            # a parity breach demoted this Executable (DESIGN.md §9): the
            # unfused pipeline IS strict execution, bit-identical to the
            # pre-fusion engine
            return self._run_unfused(feeds, trace=trace, tracer=tracer,
                                     spans=spans, timeout=timeout)
        if self._parity_pending:
            return self._guarded_run(feeds, trace, tracer, timeout,
                                     spans=spans)
        if self._sample_due():
            return self._guarded_run(feeds, trace, tracer, timeout,
                                     sampled=True, spans=spans)
        return self._dispatch(feeds, trace=trace, tracer=tracer, spans=spans,
                              timeout=timeout)

    def _sample_due(self) -> bool:
        """REPRO_NUMERICS_GUARD=sample:N — is this run a re-verification?
        The counter starts after the (always-verified) first run."""
        if self._guard_every is None or self._strict_fallback:
            return False
        with self._guard_lock:
            self._guard_runs += 1
            return self._guard_runs % self._guard_every == 0

    def _dispatch(self, feeds: Dict[TensorRef, Any], *,
                  trace: Optional[List[str]], tracer: Any,
                  timeout: float, spans: Any = None) -> List[Any]:
        """The prepared (possibly fused) pipeline, no guard logic."""
        if self.multi_device:
            return self._run_multi(feeds, trace=trace, tracer=tracer,
                                   spans=spans, timeout=timeout)
        fetches = [self._fetch_remap.get(r, r) for r in self.fetches]
        return self.executor.run(fetches, feeds, ctx=self.session._ctx(),
                                 trace=trace, tracer=tracer, spans=spans)

    def _run_unfused(self, feeds: Dict[TensorRef, Any], *,
                     trace: Optional[List[str]], tracer: Any,
                     timeout: float, spans: Any = None) -> List[Any]:
        """The lazily-built unfused pipeline: per-kernel tracing, the
        parity-guard reference, and the post-breach strict fallback."""
        if self.multi_device:
            execs, fetch_by_dev = self._unfused_pipeline()
            return self._run_multi(
                feeds, trace=trace, tracer=tracer, spans=spans,
                timeout=timeout, executors=execs, fetch_by_dev=fetch_by_dev,
                remap=False)
        executor, _ = self._unfused_pipeline()
        return executor.run(self.fetches, feeds, ctx=self.session._ctx(),
                            trace=trace, tracer=tracer, spans=spans)

    def _guarded_run(self, feeds: Dict[TensorRef, Any],
                     trace: Optional[List[str]], tracer: Any,
                     timeout: float, *, sampled: bool = False,
                     spans: Any = None) -> List[Any]:
        """Verified run of a fast-numerics Executable (the first run, and
        with guard sampling every Nth thereafter): execute the unfused-
        strict reference AND the fused-fast pipeline on the same feeds
        (variable state snapshotted in between so both start identically)
        and require the drift to stay within the §9 tolerances.  On a
        breach: warn, restore the reference results/state, and demote the
        Executable to strict execution permanently.
        """
        with self._guard_lock:
            if not sampled and not self._parity_pending:
                # raced with another first run
                if self._strict_fallback:
                    return self._run_unfused(feeds, trace=trace,
                                             tracer=tracer, spans=spans,
                                             timeout=timeout)
                return self._dispatch(feeds, trace=trace, tracer=tracer,
                                      spans=spans, timeout=timeout)
            import jax

            store = self.session.variables
            g = self.session.graph
            # force-init so both executions observe identical initial state
            snap = {n: store.read(n, g.nodes[n].attrs)
                    for n in self._guard_vars}
            before = jax.device_get({n: snap[n]
                                     for n in self._guard_update_vars})
            ref = self._run_unfused(feeds, trace=None, tracer=None,
                                    timeout=timeout)
            # held on the host, so the device keeps one copy of the state
            # through the fast run, not three
            ref_vars = jax.device_get({n: store.read(n, g.nodes[n].attrs)
                                       for n in self._guard_vars})
            for n, v in snap.items():
                store.write(n, v)
            del snap
            got = self._dispatch(feeds, trace=trace, tracer=tracer,
                                 spans=spans, timeout=timeout)
            got_vars = {n: store.read(n, g.nodes[n].attrs)
                        for n in self._guard_vars}
            ok, drift = self._guard_verdict(ref, got, ref_vars, got_vars,
                                            before)
            if not ok:
                import warnings

                warnings.warn(
                    f"fast-numerics parity breach: fused-fast drifted "
                    f"{drift} from the unfused-strict reference; falling "
                    f"back to strict execution for fetches "
                    f"{[str(r) for r in self.fetches]} (DESIGN.md §9)",
                    RuntimeWarning, stacklevel=3)
                self._strict_fallback = True
                metrics_mod.counter("numerics.guard_demotions").inc()
                for n, v in ref_vars.items():
                    store.write(n, jax.device_put(v))
                # cleared only with the verdict, inside the lock: an
                # early clear would let a concurrent run() slip past the
                # guard unverified and race the comparison; and if either
                # execution raised above, the Executable stays pending so
                # the next run re-verifies
                self._parity_pending = False
                return ref
            self._parity_pending = False
            return got

    def _guard_verdict(self, ref: Sequence[Any], got: Sequence[Any],
                       ref_vars: Dict[str, Any], got_vars: Dict[str, Any],
                       before: Dict[str, Any]) -> Tuple[bool, str]:
        """Judge one guarded run: fetches and variables by the §9
        per-class tolerance, variables an optimizer rewrote (the keys of
        ``before``, their pre-run values) by their update drift.  Returns
        (ok, a description of the drift against its bounds)."""
        from . import numerics as numerics_mod

        plain = sorted(set(ref_vars) - set(before))
        upd = sorted(before)
        # elementwise either-criterion (compare), NOT an aggregate
        # max-drift check: max ULP and max rel may come from different
        # tensors that each pass on their own bound — merging them first
        # would demote spuriously
        ok, drift = numerics_mod.compare(
            list(ref) + [ref_vars[n] for n in plain],
            list(got) + [got_vars[n] for n in plain], self._guard_tol)
        metrics_mod.counter("numerics.guard_checks").inc()
        metrics_mod.gauge("numerics.guard_rel_drift").set(drift.rel)
        desc = f"{drift} against {self._guard_tol}"
        if upd:
            u = numerics_mod.update_drift([before[n] for n in upd],
                                          [ref_vars[n] for n in upd],
                                          [got_vars[n] for n in upd])
            metrics_mod.gauge("numerics.guard_update_drift").set(u)
            ok = ok and u <= self._guard_update_tol
            desc += (f", update drift {u:.3g} of {upd} against "
                     f"{self._guard_update_tol:g}")
        return ok, desc

    # ------------------------------------------------------------------
    def _wire_strict_plan(self):
        """Companion strict-numerics WirePlan over the same partition —
        the §13 distributed guard's reference pipeline and the
        post-breach fallback.  Registered lazily, on first need."""
        from ..distrib.master import WirePlan

        with self._unfused_lock:
            if self._wire_strict is None:
                self._wire_strict = WirePlan(
                    self, self._wire_device_nodes, numerics="strict",
                    backend="generic")
            return self._wire_strict

    def _guarded_wire_run(self, feeds: Dict[TensorRef, Any],
                          timeout: float, *, sampled: bool = False,
                          spans: Any = None) -> List[Any]:
        """The §9 parity guard, distributed (§13): Variable state lives in
        the worker processes, so the snapshot/rewind rides
        ``get_variables``/``set_variables`` and the strict reference is a
        wire run of the same partition under strict numerics (workers
        re-fuse strict, which is bit-identical to unfused; §7).  Both
        executions therefore observe identical worker-side starting
        state.  A breach warns, force-restores the reference's Variable
        values, and demotes this Executable to the strict plan."""
        with self._guard_lock:
            if not sampled and not self._parity_pending:
                # raced with another first run
                if self._strict_fallback:
                    return self._wire_strict_plan().run(feeds, timeout=timeout,
                                                        spans=spans)
                return self.wire_plan.run(feeds, timeout=timeout, spans=spans)
            plan = self.wire_plan
            strict = self._wire_strict_plan()
            # register (and SEED Variables) before snapshotting: on the
            # very first run nothing exists worker-side yet, and the
            # reference run below mutates the real worker state
            plan.ensure_registered()
            strict.ensure_registered()
            snap = plan.snapshot_variables(self._guard_vars)
            ref = strict.run(feeds, timeout=timeout)
            ref_vars = plan.snapshot_variables(self._guard_vars)
            plan.restore_variables(snap)
            got = plan.run(feeds, timeout=timeout, spans=spans)
            got_vars = plan.snapshot_variables(self._guard_vars)
            names = set(ref_vars) & set(got_vars)
            ok, drift = self._guard_verdict(
                ref, got, {n: ref_vars[n] for n in names},
                {n: got_vars[n] for n in names},
                {n: snap[n] for n in self._guard_update_vars if n in names})
            if not ok:
                import warnings

                warnings.warn(
                    f"fast-numerics parity breach (distributed): fused-fast "
                    f"drifted {drift} from the strict wire reference; "
                    f"falling back to strict wire execution for fetches "
                    f"{[str(r) for r in self.fetches]} "
                    f"(DESIGN.md §9/§13)", RuntimeWarning, stacklevel=3)
                self._strict_fallback = True
                metrics_mod.counter("numerics.guard_demotions").inc()
                plan.restore_variables(ref_vars)
                self._parity_pending = False
                return ref
            self._parity_pending = False
            return got

    # ------------------------------------------------------------------
    @staticmethod
    def _build_executors(graph, device_nodes) -> Dict[str, Executor]:
        return {
            dev: Executor(graph, node_filter=names, device_label=dev)
            for dev, names in device_nodes.items()
        }

    def _route_fetches(self, placement: Dict[str, str], device_nodes,
                       *, remap: bool) -> Dict[str, List[int]]:
        """device -> indices of ``self.fetches`` that device produces.

        ``remap`` routes fetches into fused members through the exporting
        region's node (the fused pipeline); the unfused pipeline routes
        the original refs.
        """
        fetch_by_dev: Dict[str, List[int]] = {}
        for i, ref in enumerate(self.fetches):
            mref = self._fetch_remap.get(ref, ref) if remap else ref
            dev = placement.get(mref.node)
            if dev is None and ref in self.feed_keys:
                # fully-fed fetch: any worker returns the fed value
                dev = next(iter(device_nodes))
            fetch_by_dev.setdefault(dev, []).append(i)
        return fetch_by_dev

    def _unfused_pipeline(self):
        """Lazily-built unfused executors for tracer= runs (DESIGN.md §7)."""
        with self._unfused_lock:
            if self._unfused is None:
                if self.multi_device:
                    pg = self.partitioned.graph
                    scheduler_mod.schedule_recvs(
                        pg, set(pg.nodes), self._cost_model,
                        self.session.devices, self.partitioned.placement)
                    self._unfused = (
                        self._build_executors(
                            pg, self.partitioned.device_nodes),
                        self._route_fetches(
                            self.partitioned.placement,
                            self.partitioned.device_nodes, remap=False))
                else:
                    self._unfused = (
                        Executor(self.session.graph, node_filter=self.node_set),
                        None)
            return self._unfused

    # ------------------------------------------------------------------
    def _run_multi(self, feeds: Dict[TensorRef, Any], *,
                   trace: Optional[List[str]], tracer: Any,
                   timeout: float,
                   executors: Optional[Dict[str, Executor]] = None,
                   fetch_by_dev: Optional[Dict[str, List[int]]] = None,
                   remap: bool = True, spans: Any = None) -> List[Any]:
        session = self.session
        executors = executors if executors is not None else self.device_executors
        fetch_by_dev = (fetch_by_dev if fetch_by_dev is not None
                        else self.fetch_by_dev)
        # per-run rendezvous: concurrent runs never mix; its recv timeout
        # tracks the run deadline so a caller-raised timeout is honoured
        run_rdv = Rendezvous(timeout=timeout)
        results: Dict[int, Any] = {}
        errors: List[BaseException] = []
        lock = threading.Lock()

        def mark_progress(dev_name: str) -> None:
            # §16.4 last-progress gauge: a hung run's report reads this to
            # say how long each stuck device has been silent
            metrics_mod.gauge(
                f"exec.device.{dev_name}.last_progress_ts").set(time.time())

        def worker(dev_name: str, executor: Executor) -> None:
            mark_progress(dev_name)
            ctx = ExecutionContext(
                variables=session.variables,
                rendezvous=run_rdv,
                queues=session.queues,
                checkpoint_io=session.checkpoint_io,
                device_kind=dev_name.split("device:")[-1].split(":")[0],
            )
            local_trace: Optional[List[str]] = [] if trace is not None else None
            idxs = fetch_by_dev.get(dev_name, [])
            if remap:
                local_fetches = [
                    self._fetch_remap.get(self.fetches[i], self.fetches[i])
                    for i in idxs]
            else:
                local_fetches = [self.fetches[i] for i in idxs]
            try:
                vals = executor.run(local_fetches, feeds, ctx=ctx,
                                    trace=local_trace, tracer=tracer,
                                    spans=spans)
                with lock:
                    for i, v in zip(idxs, vals):
                        results[i] = v
                    if trace is not None:
                        trace.extend(local_trace or [])
            except BaseException as e:  # noqa: BLE001 — §3.3: surface any worker failure
                with lock:
                    errors.append(e)
            finally:
                mark_progress(dev_name)

        threads = {
            dev: threading.Thread(target=worker, args=(dev, ex), daemon=True)
            for dev, ex in executors.items()
        }
        for t in threads.values():
            t.start()
        deadline = time.monotonic() + timeout
        for t in threads.values():
            t.join(timeout=max(0.0, deadline - time.monotonic()))

        if errors:
            # §3.3 fault tolerance: abort the whole graph execution on any failure
            raise errors[0]
        stuck = sorted(dev for dev, t in threads.items() if t.is_alive())
        if stuck:
            # §3.3: name the owning worker *process*, not just the virtual
            # device — multi-process hangs are diagnosed by which OS
            # process holds the stuck executor (distrib workers report
            # their task/pid the same way; DESIGN.md §11).  Each stuck
            # device also reports its last-progress timestamp from the
            # metrics registry (§16.4) so the report distinguishes
            # never-started from wedged-mid-run.
            now = time.time()

            def _age(dev: str) -> str:
                ts = metrics_mod.gauge(
                    f"exec.device.{dev}.last_progress_ts").value
                return f"{now - ts:.1f}s ago" if ts else "never"

            ident = ", ".join(
                f"{dev} (in-process worker thread {threads[dev].name!r}, "
                f"pid {os.getpid()}, last progress {_age(dev)})"
                for dev in stuck)
            raise ExecutorError(
                f"graph execution timed out after {timeout:.1f}s: worker(s) for "
                f"{ident} never finished (stuck Send/Recv or a hung "
                f"kernel; §3.3 failure reporting)")
        missing = [str(self.fetches[i]) for i in range(len(self.fetches))
                   if i not in results]
        if missing:
            raise ExecutorError(
                f"workers finished but fetches {missing} were never produced "
                f"(partition/fetch routing bug; §3.3 failure reporting)")
        return [results[i] for i in range(len(self.fetches))]
