"""§2 Sessions: Extend + Run, with §4.2 partial execution (feed/fetch).

``Session.run(fetches, feed_dict)`` rewrites the graph with feed/fetch
semantics: fed tensors shadow their producing nodes, the executed node set
is the transitive closure working backwards from the fetches through the
rewritten graph, and everything else is pruned (Figure 6).

The prune -> place -> partition -> schedule -> executor-static-analysis
pipeline runs once per :class:`~repro.core.executable.RunSignature`, not
once per call: the Session keeps an LRU of prepared
:class:`~repro.core.executable.Executable`\\ s keyed by (fetches, fed
keys, device set, graph version), so steady-state ``run`` loops only pay
per-run executor state (§3.2 "caches these graphs"; DESIGN.md §5).
``Session.extend`` bumps the graph version, invalidating stale entries
automatically.  The same Session can also *compile* a (feeds, fetches)
signature through the JIT lowering (§10 / DESIGN.md §2) into a pure JAX
function.
"""
from __future__ import annotations

import dataclasses
import itertools
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from .graph import Graph, Node, TensorRef, as_ref
from .executor import ExecutionContext, Executor
from .executable import Executable, ExecutableCache, RunSignature
from .options import SessionOptions, parse_guard
from . import ops as ops_mod
from . import kernel_registry
from ..runtime.containers import VariableStore, ContainerManager
from ..runtime.devices import local_kind
from ..runtime.rendezvous import Rendezvous


class _DictCheckpointIO:
    """In-memory checkpoint table (file-backed IO lives in repro.checkpoint)."""

    def __init__(self) -> None:
        self.table: Dict[str, Dict[str, Any]] = {}

    def save(self, path: str, values: Dict[str, Any]) -> None:
        self.table[path] = dict(values)

    def load(self, path: str) -> Dict[str, Any]:
        return self.table[path]


# Legacy config kwargs (pre-SessionOptions): sentinel distinguishes
# "not passed" from an explicit None/()/16.
_UNSET = object()
_LEGACY_OPTION_KWARGS = ("devices", "cluster", "standby",
                         "max_cached_executables", "fuse_regions",
                         "numerics", "parity_guard", "backend", "verify")
_warned_legacy_kwargs = False


def _parse_guard(value) -> Tuple[bool, Optional[int]]:
    # retained alias; the implementation moved to repro.core.options
    return parse_guard(value)


class Session:
    _ids = itertools.count()

    def __init__(self, graph: Optional[Graph] = None, *,
                 options: Optional[SessionOptions] = None,
                 containers: Optional[ContainerManager] = None,
                 checkpoint_io: Any = None,
                 devices: Any = _UNSET,
                 cluster: Any = _UNSET,
                 standby: Any = _UNSET,
                 max_cached_executables: Any = _UNSET,
                 fuse_regions: Any = _UNSET,
                 numerics: Any = _UNSET,
                 parity_guard: Any = _UNSET,
                 backend: Any = _UNSET,
                 verify: Any = _UNSET) -> None:
        self.graph = graph or Graph()
        # All configuration lives on one SessionOptions (repro.core.options;
        # DESIGN.md §15) with a single documented resolution order:
        # explicit value > REPRO_* env var > default.  The per-field kwargs
        # are a deprecation shim — they fold into the options object, with
        # an explicit kwarg overriding the corresponding options= field.
        #
        # Field notes (details in repro.core.options):
        #   verify        §14 pre-execution verifier: off|warn|error; part
        #                 of the RunSignature (flipping warn->error
        #                 re-verifies, never reuses a stale Executable).
        #   fuse_regions  §10 region fusion (DESIGN.md §7), default-on;
        #                 in the RunSignature.
        #   numerics      DESIGN.md §9 strict|fast policy; in the
        #                 RunSignature so the modes never share a cache
        #                 entry.
        #   parity_guard  fast-mode safety net: first-run (and sample:N)
        #                 verification against unfused-strict, with
        #                 permanent strict fallback on a breach.
        #   backend       DESIGN.md §12 kernel-backend registry choice;
        #                 in the RunSignature.
        legacy = {k: v for k, v in (
            ("devices", devices), ("cluster", cluster), ("standby", standby),
            ("max_cached_executables", max_cached_executables),
            ("fuse_regions", fuse_regions), ("numerics", numerics),
            ("parity_guard", parity_guard), ("backend", backend),
            ("verify", verify)) if v is not _UNSET}
        if legacy:
            global _warned_legacy_kwargs
            if not _warned_legacy_kwargs:
                warnings.warn(
                    "per-field Session(...) config kwargs are deprecated; "
                    "pass Session(options=SessionOptions(...)) instead "
                    "(repro.core.options)", DeprecationWarning, stacklevel=2)
                _warned_legacy_kwargs = True
        opts = dataclasses.replace(options or SessionOptions(), **legacy)
        self.options = opts = opts.resolve()
        # verify/fuse_regions/numerics/kernel_backend are write-through
        # properties over self.options (below): mid-session flips like
        # ``sess.numerics = "strict"`` fold back into the options object,
        # so RunSignature.for_session — which derives every key component
        # from the resolved options — re-keys and rebuilds, never reuses.
        self.parity_guard, self.parity_guard_every = parse_guard(opts.parity_guard)
        self.containers = containers or ContainerManager()
        self.variables = VariableStore(self.containers)
        self.rendezvous = Rendezvous()
        self.queues: Dict[str, Any] = {}
        self.checkpoint_io = checkpoint_io or _DictCheckpointIO()
        # §3.3/DESIGN.md §11: a cluster spec turns multi-device execution
        # into multi-*process* execution — the same place/partition/
        # schedule pipeline, with per-device subgraphs shipped to worker
        # processes and Send/Recv riding the wire rendezvous.
        self.cluster = None
        self._master: Any = None
        devices = opts.devices
        if opts.cluster is not None:
            import uuid

            from ..distrib.wire import ClusterSpec

            self.cluster = ClusterSpec.parse(opts.cluster)
            if devices is None:
                devices = self.cluster.device_set()
            # worker-side Variable containers are namespaced per session,
            # mirroring the in-process default of one ContainerManager
            # per Session (§4.7): two sessions sharing a worker pool must
            # not silently share state through colliding Variable names.
            # Stable across pool restarts (recovery keeps the session).
            self.wire_namespace = uuid.uuid4().hex[:8]
        # §13: endpoints of idle standby workers — partial re-placement
        # consumes them before falling back to survivor hosting
        self.standby = list(opts.standby)
        self.devices = devices  # DeviceSet for the multi-device eager path
        self.id = next(Session._ids)
        self._run_count = 0
        # compile-once/run-many: RunSignature -> Executable (DESIGN.md §5);
        # max_cached_executables=0 disables caching (benchmark baseline).
        self._executables = ExecutableCache(maxsize=opts.max_cached_executables)
        # §16 distributed EEG: trace_dir turns on the span stream for every
        # run of this session (including make_callable, which passes no
        # per-call kwargs — Executable.run consults self._spans).  The
        # recorder is installed process-globally too, so the RPC client
        # layer records wire calls.  trace_dir unset => self._spans is
        # None and every instrumentation site stays a single None check.
        self.trace_dir = opts.trace_dir
        self._spans = None
        self._trace_exported = False
        if self.trace_dir:
            from ..obs import spans as spans_mod

            self._spans = spans_mod.install(
                spans_mod.SpanRecorder(process="master"))

    # ------------------------------------------------------------------
    # -- mirrored option attrs --------------------------------------------
    # One source of truth: reads come from self.options, writes fold back
    # into it (validated through resolve()), so a mid-session flip reaches
    # RunSignature.for_session through the same options-derived path as a
    # constructor value.

    @property
    def verify(self) -> str:
        return self.options.verify

    @verify.setter
    def verify(self, v: str) -> None:
        self.options = dataclasses.replace(self.options, verify=v).resolve()

    @property
    def fuse_regions(self) -> bool:
        return self.options.fuse_regions

    @fuse_regions.setter
    def fuse_regions(self, v: bool) -> None:
        self.options = dataclasses.replace(
            self.options, fuse_regions=v).resolve()

    @property
    def numerics(self) -> str:
        return self.options.numerics

    @numerics.setter
    def numerics(self, v: str) -> None:
        self.options = dataclasses.replace(self.options, numerics=v).resolve()

    @property
    def kernel_backend(self) -> str:
        return self.options.backend

    @kernel_backend.setter
    def kernel_backend(self, v: str) -> None:
        self.options = dataclasses.replace(self.options, backend=v).resolve()

    @property
    def master(self):
        """Lazily-started :class:`repro.distrib.master.Master` for cluster
        sessions (heartbeats begin on first touch; DESIGN.md §11)."""
        if self.cluster is None:
            raise RuntimeError("Session has no cluster= spec")
        if self._master is None:
            from ..distrib.master import Master

            self._master = Master(self.cluster, standbys=self.standby)
            self._master.start()
        return self._master

    def rebind_cluster(self, cluster: Any = None) -> None:
        """§3.3 recovery: point this session at a restarted worker pool.

        The pool must have the same shape (task count / devices per task
        — placement is per-task).  The session store's *current* Variable
        values are pushed to the pool here and cached Executables
        re-register lazily, so the recovery recipe is: restore the last
        checkpoint into the session (``set_variable``), restart the
        workers, call this, keep running.
        """
        from ..distrib.wire import ClusterSpec

        spec = ClusterSpec.parse(cluster) if cluster is not None else self.cluster
        if spec is None:
            raise RuntimeError("Session has no cluster= spec")
        self.cluster = spec
        self.master.reset(spec)
        # registration only *seeds* worker Variables (it must not clobber
        # live mid-training state); recovery state is pushed explicitly —
        # restore the checkpoint into the session store BEFORE calling
        for plan in self.master.live_plans():
            plan.push_variables()

    def recover_dead_tasks(self, checkpoint: Optional[Dict[str, Any]] = None,
                           *, standby: Any = None):
        """§13 partial re-placement: recover from dead workers WITHOUT
        restarting the pool or discarding survivors' live Variable state.

        Each dead task's subgraph slice is re-placed onto a standby
        worker (``standby=`` here, ``Session(standby=...)``, or
        ``master.add_standby``) or, failing that, onto a survivor's
        process; only the dead task's Variables are pushed from
        ``checkpoint`` (``{name: value}`` — typically the last
        checkpoint's values), survivors keep live state, and only the
        replaced task re-registers — cached Executables stay valid.

        Returns a :class:`~repro.distrib.master.RecoveryReport` saying
        what was kept vs restored.  Raises
        :class:`~repro.distrib.master.RecoveryError` when nothing can
        host the dead tasks — the whole-pool path (restart workers,
        ``set_variable`` the checkpoint, ``rebind_cluster``) remains the
        fallback.
        """
        from ..distrib.master import RecoveryError, RecoveryReport

        m = self.master
        if isinstance(standby, str):
            standby = [s.strip() for s in standby.split(",") if s.strip()]
        for ep in (standby or ()):
            m.add_standby(ep)
        dead = dict(m.dead)
        if not dead:
            return RecoveryReport(
                mode="noop", dead={}, replacements={},
                survivors=tuple(range(len(m.cluster.workers))),
                kept_live=(), restored=())
        survivors = tuple(t for t in range(len(m.cluster.workers))
                          if t not in dead)
        plans = m.live_plans()
        replacements: Dict[str, Any] = {}
        for i, t in enumerate(sorted(dead)):
            if m.standbys:
                replacements[t] = m.standbys.pop(0)
            elif survivors:
                # round-robin over survivors: the replacement process then
                # hosts two tasks' devices of the same plan (worker
                # registry is keyed by (handle, task))
                replacements[t] = m.cluster.workers[survivors[i % len(survivors)]]
            else:
                raise RecoveryError(
                    f"§13: no standby or survivor can host dead task(s) "
                    f"{sorted(dead)} ("
                    + "; ".join(f"task:{k}: {v}" for k, v in sorted(dead.items()))
                    + ") — fall back to whole-pool recovery: restart the "
                    f"pool, restore the last checkpoint (set_variable) and "
                    f"rebind_cluster")
        # restore ONLY the dead tasks' Variables into the session store;
        # survivors' names in the checkpoint are ignored — their live
        # (newer) worker-side state is the whole point of this path
        dead_owned = {name for plan in plans
                      for name, owner in plan.var_owner.items()
                      if owner in dead}
        if checkpoint:
            for name in sorted(dead_owned & set(checkpoint)):
                self.set_variable(name, checkpoint[name])
        for t, ep in sorted(replacements.items()):
            m.replace_task(t, ep)
        self.cluster = m.cluster  # same shape: fingerprint (and cache) hold
        kept: set = set()
        for plan in plans:
            for t in sorted(replacements):
                plan.reregister_task(t)
            plan.update_survivors(set(replacements))
            # registration only SEEDs: force-push the restored values — a
            # survivor hosting the dead task may hold stale state for it
            plan.push_variables(tasks=set(replacements))
            kept |= {name for name, owner in plan.var_owner.items()
                     if owner not in dead}
        return RecoveryReport(
            mode="partial", dead=dead, survivors=survivors,
            replacements=replacements, kept_live=tuple(sorted(kept)),
            restored=tuple(sorted(dead_owned)))

    def pull_cluster_variables(self) -> Dict[str, Any]:
        """Fetch Variable state back from the worker pool into the local
        store; returns the pulled values (checkpoint them with
        CheckpointManager for §3.3 recovery)."""
        if self._master is None:
            return {}
        out: Dict[str, Any] = {}
        seen = set()
        for plan in self._master.live_plans():
            names = set(plan.var_owner) - seen
            if names:
                out.update(plan.pull_variables())
                seen |= set(plan.var_owner)
        return out

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write the merged Chrome-trace JSON (§16.3): the local span
        stream plus, for cluster sessions, every worker's buffered events
        (shipped on ``run_graph`` replies and drained via the
        ``collect_trace`` RPC), aligned by the master's per-task
        clock-offset estimates.  Returns the path written, or None when
        the session was not constructed with ``trace_dir=``."""
        if self._spans is None:
            return None
        import os

        from ..obs import export as export_mod

        streams = [{"process": "master", "offset_s": 0.0,
                    "events": self._spans.snapshot()}]
        if self.cluster is not None and self._master is not None:
            streams.extend(self._master.collect_trace_streams())
        path = path or os.path.join(self.trace_dir, "trace.json")
        export_mod.write_trace(path, streams)
        self._trace_exported = True
        return path

    def close(self) -> None:
        """Stop heartbeat threads / close worker channels (cluster sessions).
        A pending ``trace_dir=`` trace is flushed first (best-effort: an
        export failure must never mask shutdown)."""
        if self._spans is not None and not self._trace_exported:
            try:
                self.export_trace()
            except Exception:
                pass
        if self._master is not None:
            self._master.stop()
            self._master = None

    # ------------------------------------------------------------------
    def extend(self, graph: Graph) -> None:
        """Session.Extend (§2): augment the current graph."""
        self.graph.extend(graph)

    def register_queue(self, name: str, q: Any) -> None:
        self.queues[name] = q

    def _ctx(self) -> ExecutionContext:
        return ExecutionContext(
            variables=self.variables,
            rendezvous=self.rendezvous,
            queues=self.queues,
            checkpoint_io=self.checkpoint_io,
            device_kind=local_kind(),
        )

    # ------------------------------------------------------------------
    def _normalize(self, fetches, feed_dict):
        fetch_refs = [as_ref(f) for f in (fetches if isinstance(fetches, (list, tuple)) else [fetches])]
        feeds = {as_ref(k): v for k, v in (feed_dict or {}).items()}
        return fetch_refs, feeds

    def pruned_nodes(self, fetch_refs: Sequence[TensorRef],
                     feeds: Dict[TensorRef, Any]) -> Set[str]:
        """§4.2: nodes needed for the fetches, stopping at fed tensors.

        A node whose *every* output is fed need not run; we model the
        feed-node rewrite by cutting traversal through fed edges.
        """
        g = self.graph
        needed: Set[str] = set()
        stack = [r.node for r in fetch_refs]
        fed_ports = {(r.node, r.port) for r in feeds}
        while stack:
            n = stack.pop()
            if n in needed:
                continue
            needed.add(n)
            node = g.nodes[n]
            for ref in node.inputs:
                if (ref.node, ref.port) in fed_ports:
                    continue  # edge replaced by a feed node
                stack.append(ref.node)
            stack.extend(node.control_inputs)
        # nodes that are fetch targets but fully fed: keep out of execution
        fed_nodes = {r.node for r in fetch_refs if (r.node, r.port) in fed_ports}
        return needed - fed_nodes

    def executable(self, fetch_refs: Sequence[TensorRef],
                   feed_keys) -> Executable:
        """The cached Executable for one run signature (built on miss).

        Stale entries (older graph version, different device set) are
        purged lazily on every miss; ``Session.extend`` therefore
        invalidates automatically via the graph version in the key.
        """
        sig = RunSignature.for_session(self, fetch_refs, feed_keys)

        def build() -> Executable:
            self._executables.invalidate(
                lambda s: s.graph_version != sig.graph_version
                or s.device_fingerprint != sig.device_fingerprint)
            return Executable(self, sig.fetches, sig.feed_keys)

        return self._executables.get_or_build(sig, build)

    @property
    def cache_stats(self) -> Dict[str, int]:
        return dict(self._executables.stats)

    def run(self, fetches, feed_dict: Optional[Dict] = None,
            trace: Optional[List[str]] = None, tracer=None):
        """Eagerly execute the subgraph needed for ``fetches`` (§2/§4.2).

        Steady-state loops over one signature hit the Executable cache and
        skip prune/place/partition/schedule/static-analysis entirely.
        """
        fetch_refs, feeds = self._normalize(fetches, feed_dict)
        self._run_count += 1
        exe = self.executable(fetch_refs, feeds.keys())
        results = exe.run(feeds, trace=trace, tracer=tracer)
        if isinstance(fetches, (list, tuple)):
            return results
        return results[0]

    def make_callable(self, fetches, feed_refs: Sequence = ()) -> Callable[..., List[Any]]:
        """TF's ``Session.make_callable``: a fast positional-feed entry point.

        Returns ``call(*feed_values) -> [fetch_values]`` bound to the cached
        Executable for this signature; the signature is re-resolved through
        the cache on every call, so graph extension or device swaps rebuild
        transparently while the steady state stays a single dict lookup.
        """
        fetch_refs = [as_ref(f) for f in (fetches if isinstance(fetches, (list, tuple)) else [fetches])]
        feed_key_list = [as_ref(k) for k in feed_refs]
        feed_key_set = frozenset(feed_key_list)

        def call(*feed_values) -> List[Any]:
            if len(feed_values) != len(feed_key_list):
                raise ValueError(
                    f"expected {len(feed_key_list)} feed values, got {len(feed_values)}")
            self._run_count += 1
            exe = self.executable(fetch_refs, feed_key_set)
            return exe.run(dict(zip(feed_key_list, feed_values)))

        return call

    # ------------------------------------------------------------------
    def initialize_variables(self, names: Optional[Sequence[str]] = None) -> None:
        """Force-initialize Variables (reads them once so inits run)."""
        ctx = self._ctx()
        for node in self.graph.nodes.values():
            if node.op == "Variable" and (names is None or node.name in names):
                ctx.read_variable(node)

    def variable_value(self, name: str):
        return self.variables.read(name, self.graph.nodes[name].attrs)

    def set_variable(self, name: str, value) -> None:
        self.variables.write(name, value)

    # ------------------------------------------------------------------
    def compile(self, fetches, feeds: Sequence, **kw):
        """Lower a (feeds, fetches) signature to a pure JAX function (§10)."""
        from . import lowering

        return lowering.compile_subgraph(self, fetches, feeds, **kw)
