"""Region fusion: compile pure subregions of partitioned graphs (§10).

The OSDI follow-up to the whitepaper closed the interpreter-dispatch gap
by fusing dataflow subgraphs into compiled kernels while leaving
communication and state in the runtime.  This pass does the same on top
of the §10 lowering: after placement/partitioning, each per-device
subgraph is decomposed into maximal acyclic *pure regions* — connected
node sets containing no Send/Recv, no control-flow primitives and no
eager-runtime-only stateful ops — and each region becomes a single
``FusedRegion`` super-node whose kernel is the region lowered through
:func:`repro.core.lowering.lower_region` and ``jax.jit``-compiled.  The
executor then dispatches a handful of fused kernels interleaved with the
runtime ops it must interpret (Send/Recv, queues, control flow) instead
of hundreds of Python-dispatched nodes.

Region criteria (the fused/unfused bit-parity contract, DESIGN.md §7):

* no runtime-only op (Send/Recv, queues, Save/Restore, Placeholder) and
  no control-flow primitive;
* no node *downstream* of a control-flow primitive — dead tensors
  (§4.4) must never cross a region boundary;
* no ``Variable`` node whose variable is written anywhere in the
  executed node set — the eager executor reads such variables in the
  first ready wave, before any assignment can run, and fusing the read
  into a later-dispatched region would observe the post-write value;
* no op with a per-device kernel override for the node's device kind
  (the lowering always traces the reference ``compute`` kernel);
* no node marked ``attrs={"nofuse": True}`` (the per-node escape hatch);
* no fetched zero-output node (operation fetches are resolved through
  the executor's ``done`` set, which only tracks dispatched nodes).

Acyclicity: nodes are labelled with a *phase* that is monotone along
every dependency edge — including the implicit Send→Recv pairing across
devices — and strictly increases when an edge leaves a non-fusible
node.  All fusible nodes of one device that share a phase form one
region: any would-be cycle through external nodes must pass a runtime
op and therefore re-enter at a strictly larger phase, a contradiction.

Before region discovery each partition runs a pre-fusion optimization
pipeline — prune → constant-fold → (scoped) CSE (§5.1) — so fusion
operates on a minimized graph.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, FrozenSet, Iterable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from .graph import Graph, GraphError, Node, TensorRef
from . import control_flow as cf_mod
from . import cse as cse_mod
from . import ops as ops_mod
from ..obs.metrics import StatsDict

CF_PRIMITIVES = {"Switch", "Merge", "Enter", "Exit", "NextIteration", "LoopCond"}
RUNTIME_ONLY = {"Send", "Recv", "Save", "Restore", "QueueEnqueue",
                "QueueDequeue", "Placeholder"} | CF_PRIMITIVES
# stateful ops the §10 lowering models functionally (reads become inputs,
# writes become outputs committed by the dispatcher)
FUSIBLE_STATEFUL = {"Variable", "Assign", "AssignAdd"}
# Ops whose result depends on an accumulation/library-kernel order:
# MatMul (Eigen gemm vs naive loops), reductions (vectorized partial
# sums vs linear), Call (user closures may contain either).  Under the
# bit-parity contract ("strict" numerics) they stay eagerly dispatched —
# a fused kernel compiled at a different backend optimization level
# reassociates them — while order-insensitive elementwise/data-movement
# ops fuse freely.  numerics="fast" fuses everything at full XLA
# optimization under the per-op-class tolerance contract of DESIGN.md §9
# (repro.core.numerics), re-proven by the CI parity gate.
STRICT_UNFUSIBLE = {"MatMul", "Call", "ReduceSum", "ReduceMean",
                    "SoftMax", "SoftmaxXent", "SSDScan"}

# pass-invocation counters (see placement.STATS; DESIGN.md §5/§7),
# registry-backed since §16.4 — also visible as fusion.* counters
STATS = StatsDict("fusion", keys=(
    "fuse_calls", "regions_built", "nodes_fused",
    "consts_folded", "nodes_pruned", "cse_merged", "fallbacks"))


def REGION_CACHE_SIZE() -> int:
    """Per-region cap on cached (shape, dtype) -> jitted-executable entries
    (``REPRO_REGION_CACHE``, default 32; DESIGN.md §7)."""
    import os

    try:
        return int(os.environ.get("REPRO_REGION_CACHE", "32"))
    except ValueError:
        return 32


class FusionError(Exception):
    pass


def written_variables(g: Graph, names: Iterable[str]) -> Set[str]:
    """Variables mutated by any node of ``names`` (Assign/AssignAdd/Restore)."""
    written: Set[str] = set()
    for n in names:
        node = g.nodes[n]
        if node.op in ("Assign", "AssignAdd") and node.inputs:
            written.add(node.inputs[0].node)
        elif node.op == "Restore":
            written.update(node.attrs.get("var_names", ()))
    return written


def _device_kind(dev: Optional[str], default: str = "cpu") -> str:
    if not dev or "device:" not in dev:
        return default
    return dev.split("device:")[-1].split(":")[0]


@dataclasses.dataclass
class RegionSpec:
    """One fused region: members + the cut-edge contract (DESIGN.md §7).

    ``input_refs``/``output_refs`` are in the *original* node namespace
    (the partitioned graph before the rewrite); the rewritten
    ``FusedRegion`` node's inputs are positionally aligned with
    ``input_refs`` and its output port ``i`` carries ``output_refs[i]``.
    """

    name: str
    members: List[str]                 # topo order (also the effect order)
    subgraph: Graph                    # member nodes, original external refs
    input_refs: List[TensorRef]        # external data cut edges, positional
    output_refs: List[TensorRef]       # exported member tensors, positional
    control_externals: List[str]       # external control-dep sources
    var_read_attrs: Dict[str, Dict[str, Any]]  # Variable member -> attrs
    var_writes: List[str]
    device: Optional[str] = None
    # "strict": compile at XLA backend-optimization-level 0 so the fused
    # kernel is bit-identical to per-op eager dispatch (no FMA contraction
    # or cross-op rewrites) — the parity contract.  "fast": full backend
    # optimization; results may differ from the interpreter by ~1 ulp.
    numerics: str = "strict"
    # kernel-backend registry key (DESIGN.md §12): under a non-generic
    # backend, lower_region rewrites recognized idioms among the members
    # onto registered kernels for this region's device kind.  Dispatch is
    # fast-numerics-only: strict's bit-parity contract (and its
    # STRICT_UNFUSIBLE exclusions) keeps the matchable anchors out of
    # strict regions anyway.
    backend: str = "generic"
    device_kind: str = "cpu"

    def __post_init__(self) -> None:
        self._fn: Optional[Any] = None   # lowered python callable (trace source)
        self._jit_cache: Optional[Any] = None  # per-signature LRU of jitted fns
        self._var_order = sorted(self.var_read_attrs)  # fixed signature order
        # steady-state fast path: the last (signature, jitted fn) pair,
        # read/written without the LRU lock (a lost race merely rebuilds)
        self._last: Optional[Tuple[Any, Any]] = None
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def _lowered(self):
        with self._lock:
            if self._fn is None:
                from . import lowering

                backend = self.backend if self.numerics == "fast" else "generic"
                self._fn = lowering.lower_region(
                    self.subgraph, self.members, self.input_refs,
                    self.output_refs, self.members,
                    backend=backend, device_kind=self.device_kind)
            return self._fn

    def _cache(self):
        with self._lock:
            if self._jit_cache is None:
                # lazy import: executable.py imports this module at top level
                from .executable import ExecutableCache

                self._jit_cache = ExecutableCache(maxsize=REGION_CACHE_SIZE())
            return self._jit_cache

    def _jit(self):
        """A fresh jitted callable for one input signature.

        One ``jax.jit`` wrapper per (shape, dtype) signature, held in a
        bounded LRU: ``jax.jit``'s own per-wrapper trace cache is
        unbounded, so a serving workload feeding many shapes through one
        long-lived wrapper would grow memory without limit.  Evicting a
        wrapper drops its traces/executables; re-feeding that signature
        re-compiles transparently.
        """
        fn = self._lowered()
        if self.numerics == "strict":
            return jax.jit(fn, compiler_options={
                "xla_backend_optimization_level": 0})
        # "fast": plain jax.jit == full XLA backend optimization (FMA
        # contraction, reduction reassociation) — the §9 tolerance
        # contract bounds the drift and the CI parity gate enforces it
        return jax.jit(fn)

    @staticmethod
    def _abstract(v: Any):
        return (tuple(getattr(v, "shape", ()) or ()),
                str(getattr(v, "dtype", type(v).__name__)))

    def executable_for(self, inputs: Sequence[Any],
                       var_values: Dict[str, Any]):
        sig = (tuple(self._abstract(v) for v in inputs),
               tuple(self._abstract(var_values[k]) for k in self._var_order))
        last = self._last
        if last is not None and last[0] == sig:
            return last[1]  # single-signature steady state: no lock, no LRU
        jfn = self._cache().get_or_build(sig, self._jit)
        self._last = (sig, jfn)
        return jfn

    def dispatch(self, ctx, inputs: Sequence[Any]) -> Tuple[Any, ...]:
        """Run the compiled region: read vars, call the jitted kernel,
        commit variable writes (the FusedRegion opdef's kernel)."""
        var_values = {name: ctx.variables.read(name, attrs)
                      for name, attrs in self.var_read_attrs.items()}
        jfn = self.executable_for(inputs, var_values)
        outs, new_vars = jfn(tuple(inputs), var_values)
        for vname, v in new_vars.items():
            ctx.write_variable(vname, v)
        return tuple(outs)


@dataclasses.dataclass
class FusionResult:
    graph: Graph                        # rewritten graph with FusedRegion nodes
    names: Set[str]                     # executed node set in ``graph``
    regions: List[RegionSpec]
    fetch_map: Dict[TensorRef, TensorRef]   # original fetch ref -> rewritten
    placement: Optional[Dict[str, str]]     # node -> device (incl. regions)
    # True if the pre-fusion pipeline (prune/fold/CSE) or the rewrite
    # changed anything — the optimized graph is worth executing even when
    # no region met the size threshold
    changed: bool = False


# ---------------------------------------------------------------------------
# pre-fusion optimization pipeline: prune -> constant-fold -> scoped CSE


def _prune(g: Graph, names: Set[str], fetch_refs: Sequence[TensorRef],
           fed_ports: Set[Tuple[str, int]]) -> Set[str]:
    """Drop pure nodes that feed neither a fetch nor a stateful op."""
    roots = [r.node for r in fetch_refs if r.node in names]
    roots += [n for n in names if ops_mod.opdef(g.nodes[n].op).stateful]
    keep: Set[str] = set()
    stack = list(roots)
    while stack:
        n = stack.pop()
        if n in keep or n not in names:
            continue
        keep.add(n)
        node = g.nodes[n]
        for r in node.inputs:
            if (r.node, r.port) in fed_ports:
                continue  # §4.2: traversal stops at fed tensors
            stack.append(r.node)
        stack.extend(node.control_inputs)
    for n in names - keep:
        del g.nodes[n]
    STATS["nodes_pruned"] += len(names) - len(keep)
    return keep


def _fold_constants(g: Graph, names: Set[str],
                    fed_ports: Set[Tuple[str, int]],
                    kind_of) -> int:
    """Evaluate pure single-output ops whose inputs are all Const (§5.1)."""
    folded = 0
    for n in g.topo_sort(names):
        node = g.nodes[n]
        od = ops_mod.opdef(node.op)
        if (node.op == "Const" or node.op == "Call" or node.op in RUNTIME_ONLY
                or od.stateful or node.control_inputs or not node.inputs
                or od.num_outputs(node) != 1
                or kind_of(n) in od.kernels):
            continue
        vals = []
        for r in node.inputs:
            p = g.nodes.get(r.node)
            if (r.node, r.port) in fed_ports or p is None \
                    or p.op != "Const" or r.port != 0:
                vals = None
                break
            vals.append(jnp.asarray(p.attrs["value"]))
        if vals is None:
            continue
        try:
            out = od.compute(None, node, *vals)
        except Exception:  # noqa: BLE001 — a kernel that needs ctx stays unfolded
            continue
        node.op = "Const"
        node.inputs = []
        node.attrs = {"value": out[0]}
        folded += 1
    STATS["consts_folded"] += folded
    return folded


# ---------------------------------------------------------------------------
# region planning


def _classify(g: Graph, names: Set[str], placement: Optional[Dict[str, str]],
              default_kind: str, fed_ports: Set[Tuple[str, int]],
              fetch_nodes: Set[str], written_vars: Set[str],
              numerics: str = "strict"):
    """Per-node fusibility + phase labels (see module docstring)."""
    order = g.topo_sort(names)  # GraphError on real cycles
    idx = {n: i for i, n in enumerate(order)}

    # dependency edges, back edges dropped, plus Send->Recv pairing edges
    edges: List[Tuple[str, str]] = []
    by_key: Dict[str, Dict[str, str]] = {}
    for n in order:
        node = g.nodes[n]
        for d in g.deps(node):
            if d in names and g.nodes[d].op != "NextIteration":
                edges.append((d, n))
        if node.op in ("Send", "Recv"):
            by_key.setdefault(node.attrs["rendezvous_key"], {})[node.op] = n
    for pair in by_key.values():
        if "Send" in pair and "Recv" in pair:
            edges.append((pair["Send"], pair["Recv"]))
    edges.sort(key=lambda e: idx[e[0]])

    # frame boundary rule (§4.4 / DESIGN.md §8): a region never spans a
    # loop-frame boundary — every node with a non-root static frame stays
    # interpreted so the tagged-frame executor keeps driving it once per
    # iteration.  (The control-flow taint below subsumes this for graphs
    # built by the while_loop builder; the explicit frame check keeps the
    # invariant independent of how the frame was constructed.)
    frames = cf_mod.static_frames(g, names)

    # taint: anything downstream of a control-flow primitive may carry
    # dead tensors (§4.4) and must stay interpreted
    tainted = {n for n in names if g.nodes[n].op in CF_PRIMITIVES}
    for _ in range(len(names) + 2):
        changed = False
        for a, b in edges:
            if a in tainted and b not in tainted:
                tainted.add(b)
                changed = True
        if not changed:
            break

    def kind_of(n: str) -> str:
        if placement is not None and n in placement:
            return _device_kind(placement[n], default_kind)
        return _device_kind(g.nodes[n].device, default_kind)

    fusible: Dict[str, bool] = {}
    for n in names:
        node = g.nodes[n]
        od = ops_mod.opdef(node.op)
        fusible[n] = not (
            node.op in RUNTIME_ONLY
            or (numerics == "strict" and node.op in STRICT_UNFUSIBLE)
            or n in tainted
            or bool(frames.get(n))
            or (od.stateful and node.op not in FUSIBLE_STATEFUL)
            or (node.op == "Variable" and n in written_vars)
            or node.attrs.get("nofuse", False)
            or kind_of(n) in od.kernels
            or (n in fetch_nodes and od.num_outputs(node) == 0)
        )

    # phases: monotone along edges, +1 when leaving a non-fusible node.
    phase = {n: 0 for n in names}
    for it in range(len(names) + 2):
        changed = False
        for a, b in edges:
            p = phase[a] + (0 if fusible[a] else 1)
            if p > phase[b]:
                phase[b] = p
                changed = True
        if not changed:
            break
    else:
        raise FusionError("phase labelling did not converge (cyclic Send/Recv?)")
    return order, fusible, phase, kind_of


# ---------------------------------------------------------------------------


def fuse(
    g: Graph,
    node_names: Iterable[str],
    *,
    placement: Optional[Dict[str, str]] = None,
    device_kind: str = "cpu",
    feeds: Iterable[TensorRef] = (),
    fetch_refs: Sequence[TensorRef] = (),
    written_vars: Optional[Set[str]] = None,
    min_region_size: int = 2,
    run_optimizations: bool = True,
    numerics: Optional[str] = None,
    backend: str = "generic",
) -> FusionResult:
    """Plan regions over ``node_names`` of ``g`` and rewrite into a new
    graph where each region is one ``FusedRegion`` super-node.

    ``g`` is never mutated; the optimization pipeline and the rewrite
    operate on private copies.  ``placement`` (multi-device) groups
    regions per device; without it the whole set is one device of kind
    ``device_kind``.
    """
    STATS["fuse_calls"] += 1
    if numerics is None:
        import os
        numerics = os.environ.get("REPRO_FUSE_NUMERICS", "strict")
    names = set(node_names)
    g2 = g.subgraph(names)
    fed_ports = {(r.node, r.port) for r in feeds}
    fetch_nodes = {r.node for r in fetch_refs}
    if written_vars is None:
        written_vars = written_variables(g2, names)

    n_changes = 0
    if run_optimizations:
        n_changes += _fold_constants(
            g2, names, fed_ports,
            lambda n: _device_kind(
                placement[n] if placement and n in placement else g2.nodes[n].device,
                device_kind))
        kept = _prune(g2, names, fetch_refs, fed_ports)
        n_changes += len(names) - len(kept)
        names = kept

    order, fusible, phase, kind_of = _classify(
        g2, names, placement, device_kind, fed_ports, fetch_nodes,
        written_vars, numerics)

    def dev_of(n: str) -> str:
        if placement is not None:
            return placement.get(n, "")
        return ""

    if run_optimizations:
        # scoped CSE (§5.1): merge only within ONE device's fusible set —
        # the CSE key carries the node's *constraint* (often None), not
        # its placement, so a cross-device merge would leave a
        # cross-device edge with no Send/Recv pair.  Fetched nodes and
        # fed-port producers keep their identity.
        protected = fetch_nodes | {p for (p, _port) in fed_ports}
        by_dev: Dict[str, Set[str]] = {}
        for n in names:
            if fusible[n] and n not in protected:
                by_dev.setdefault(dev_of(n), set()).add(n)
        replaced: Dict[str, str] = {}
        for _dev, mergeable in sorted(by_dev.items()):
            if len(mergeable) > 1:
                replaced.update(
                    cse_mod.eliminate_common_subexpressions(g2, mergeable))
        if replaced:
            STATS["cse_merged"] += len(replaced)
            n_changes += len(replaced)
            names -= set(replaced)
            order = [n for n in order if n not in replaced]

    # group fusible nodes by (device, phase), members in topo order
    groups: Dict[Tuple[str, int], List[str]] = {}
    for n in order:
        if fusible[n]:
            groups.setdefault((dev_of(n), phase[n]), []).append(n)

    specs: List[RegionSpec] = []
    member_to_region: Dict[str, str] = {}
    for gi, ((dev, ph), members) in enumerate(sorted(
            groups.items(), key=lambda kv: (kv[0][1], kv[0][0]))):
        if len(members) < min_region_size:
            continue
        mset = set(members)
        rname = f"fused/d{gi}/p{ph}"
        while rname in g2.nodes:
            rname += "_"
        in_refs: List[TensorRef] = []
        seen_in: Set[Tuple[str, int]] = set()
        ctrl: List[str] = []
        for m in members:
            node = g2.nodes[m]
            for r in node.inputs:
                key = (r.node, r.port)
                if (r.node not in mset or key in fed_ports) and key not in seen_in:
                    seen_in.add(key)
                    in_refs.append(TensorRef(r.node, r.port))
            for c in node.control_inputs:
                if c not in mset and c not in ctrl:
                    ctrl.append(c)
        out_refs: List[TensorRef] = []
        seen_out: Set[Tuple[str, int]] = set()

        def _export(r: TensorRef) -> None:
            key = (r.node, r.port)
            if r.node in mset and key not in fed_ports and key not in seen_out:
                seen_out.add(key)
                out_refs.append(TensorRef(r.node, r.port))

        for n2 in order:
            if n2 in mset:
                continue
            for r in g2.nodes[n2].inputs:
                _export(r)
        for fr in fetch_refs:
            _export(fr)

        sub = g2.subgraph(members)
        sub.loop_specs = {}
        sub.cond_specs = {}
        specs.append(RegionSpec(
            name=rname,
            members=members,
            subgraph=sub,
            input_refs=in_refs,
            output_refs=out_refs,
            control_externals=ctrl,
            var_read_attrs={m: dict(g2.nodes[m].attrs) for m in members
                            if g2.nodes[m].op == "Variable"},
            var_writes=sorted({g2.nodes[m].inputs[0].node for m in members
                               if g2.nodes[m].op in ("Assign", "AssignAdd")}),
            device=dev or None,
            numerics=numerics,
            backend=backend,
            device_kind=_device_kind(dev or None, device_kind),
        ))
        for m in members:
            member_to_region[m] = rname

    # ---- rewrite -----------------------------------------------------
    out_index: Dict[Tuple[str, int], Tuple[str, int]] = {}
    spec_by_name = {s.name: s for s in specs}
    for s in specs:
        for i, r in enumerate(s.output_refs):
            out_index[(r.node, r.port)] = (s.name, i)

    def map_ref(r: TensorRef) -> TensorRef:
        key = (r.node, r.port)
        if r.node in member_to_region and key not in fed_ports:
            rn, i = out_index[key]
            return TensorRef(rn, i)
        return r

    def map_ctrls(ctrls: Iterable[str]) -> List[str]:
        mapped: List[str] = []
        for c in ctrls:
            mc = member_to_region.get(c, c)
            if mc not in mapped:
                mapped.append(mc)
        return mapped

    fg = Graph()
    emitted: Set[str] = set()
    for n in g2.nodes:  # insertion order preserved for topo tie-breaks
        if n not in names:
            continue
        if n in member_to_region:
            rn = member_to_region[n]
            if rn in emitted:
                continue
            emitted.add(rn)
            s = spec_by_name[rn]
            fg.nodes[rn] = Node(
                name=rn, op="FusedRegion",
                inputs=[map_ref(r) for r in s.input_refs],
                control_inputs=map_ctrls(s.control_externals),
                attrs={"spec": s}, device=s.device)
        else:
            node = g2.nodes[n]
            fg.nodes[n] = Node(
                name=n, op=node.op,
                inputs=[map_ref(r) for r in node.inputs],
                control_inputs=map_ctrls(node.control_inputs),
                attrs=dict(node.attrs), device=node.device)
    fg.loop_specs = dict(g2.loop_specs)
    fg.cond_specs = dict(g2.cond_specs)
    fg_names = set(fg.nodes)

    try:  # safety net: region contraction must never create a cycle
        fg.topo_sort(fg_names)
    except GraphError as e:
        raise FusionError(f"region contraction created a cycle: {e}") from e

    fetch_map = {fr: map_ref(fr) for fr in fetch_refs
                 if map_ref(fr) != fr}

    new_placement: Optional[Dict[str, str]] = None
    if placement is not None:
        new_placement = {n: placement[n] for n in fg_names if n in placement}
        for s in specs:
            new_placement[s.name] = s.device or ""

    STATS["regions_built"] += len(specs)
    STATS["nodes_fused"] += len(member_to_region)
    return FusionResult(graph=fg, names=fg_names, regions=specs,
                        fetch_map=fetch_map, placement=new_placement,
                        changed=bool(n_changes or specs))


def try_fuse(*args, **kwargs) -> Optional[FusionResult]:
    """``fuse`` with a fail-open contract: any planning/rewrite error
    falls back to the unfused executable (counted in STATS)."""
    try:
        return fuse(*args, **kwargs)
    except (FusionError, GraphError, KeyError) as _e:  # noqa: F841
        STATS["fallbacks"] += 1
        return None
