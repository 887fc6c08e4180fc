"""Step-function builders — the paper's system end to end.

``build_train_step`` constructs the training step AS A repro.core GRAPH
(loss Call node, §4.1 ``gradients()`` backward extension, AdamW update +
Assign nodes on Variables) and lowers it through the §10 JIT path to a
pure JAX function.  ``build_serve_step`` does the same for one decode
step with the KV/SSD cache as a Variable.  The launch layer then wraps
the lowered function in ``jax.jit`` with the mesh shardings from
parallel.sharding — placement-as-sharding-rules (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import (GraphBuilder, Session, SessionOptions, compile_subgraph,
                    gradients)
from ..core import numerics
from ..models.api import Model, Shape, SHAPES
from ..models.config import ModelConfig
from ..models.params import abstract_params, param_axes, init_params
from ..optim import adamw_init, adamw_update
from ..parallel import sharding as shd
from . import mesh as mesh_mod


@dataclasses.dataclass
class StepBundle:
    """Everything needed to run/lower one workload step."""

    fn: Callable                   # (feeds dict, vars dict) -> (outs, new_vars)
    feed_specs: Dict[str, jax.ShapeDtypeStruct]
    var_specs: Dict[str, Any]      # abstract values for Variables
    feed_shardings: Dict[str, Any]
    var_shardings: Dict[str, Any]
    out_shardings: Any
    model: Model
    kind: str
    graph_nodes: int = 0


@dataclasses.dataclass
class EagerStepBundle:
    """A step driven through ``Session.run`` (the §2 eager path).

    ``step`` is bound to the Session's cached Executable for its run
    signature (DESIGN.md §5): the first call pays prune/place/partition/
    schedule + executor static analysis, every subsequent call only
    allocates per-run executor state.  Variables (params/opt/cache) live
    in the Session's variable store — set them with
    ``bundle.session.set_variable`` before the first step.
    """

    session: Session
    step: Callable[[Dict[str, Any]], Any]  # feeds by name -> primary output
    model: Model
    feed_names: Tuple[str, ...]
    kind: str
    graph_nodes: int = 0

    def variables(self) -> Dict[str, Any]:
        """Snapshot the step's Variables (e.g. for checkpointing)."""
        return {name: self.session.variable_value(name)
                for name, node in self.session.graph.nodes.items()
                if node.op == "Variable"}


def _named(mesh: Optional[Mesh], spec_tree):
    if mesh is None:
        return None
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _feed_key(name: str) -> str:
    return f"{name}:0"


def step_hparams(cfg: ModelConfig, shape: Shape, n_groups: int) -> Dict[str, Any]:
    """Workload-dependent chunking knobs (memory-safety defaults)."""
    hp: Dict[str, Any] = {
        "compute_dtype": jnp.bfloat16,
        "n_token_groups": n_groups,
        "q_chunk": 0,
        "loss_chunk": 0,
        "scan_unroll": 1,
        "microbatch": 1,   # gradient-accumulation steps (memory lever)
    }
    if shape.kind in ("train", "prefill"):
        if shape.seq_len >= 4096:
            hp["q_chunk"] = 256
        hp["loss_chunk"] = 512 if shape.seq_len >= 4096 else 0
    if shape.global_batch < n_groups or shape.global_batch % n_groups:
        hp["n_token_groups"] = 1
    return hp


# ---------------------------------------------------------------------------
# Wire-shippable Call factories (DESIGN.md §15): the LM step kernels as
# importable ``module:qualname`` constructors over picklable statics, so
# the graphs built below register on a §11 worker pool unchanged.  A
# worker resolves them at registration time via ``ops.resolve_call_fn``
# (one model build per process, shared across replicas).

LM_LOSS_FACTORY = "repro.launch.steps:lm_loss_factory"
LM_LOSS_GRAD_FACTORY = "repro.launch.steps:lm_loss_and_grad_factory"
LM_UPDATE_FACTORY = "repro.launch.steps:lm_update_factory"
LM_SERVE_FACTORY = "repro.launch.steps:lm_serve_factory"


def lm_loss_factory(cfg: ModelConfig, shard: int, feed_names, loss_kw):
    """Rebuild the LM loss kernel: ``(params, *feeds) -> scalar loss``."""
    model = Model.for_config(cfg, shard)
    feed_names, loss_kw = tuple(feed_names), dict(loss_kw)

    def graph_loss(params, *feeds):
        return model.loss_fn(params, dict(zip(feed_names, feeds)), **loss_kw)

    return graph_loss


def lm_loss_and_grad_factory(cfg: ModelConfig, shard: int, feed_names,
                             loss_kw, n_micro: int):
    """Rebuild the fused loss+grad kernel with gradient accumulation over
    ``n_micro`` microbatches (memory lever: stored activations scale with
    B/n_micro, grads accumulate fp32)."""
    feed_names = tuple(feed_names)
    loss_feeds = lm_loss_factory(cfg, shard, feed_names, loss_kw)

    def loss_of(params, batch):
        return loss_feeds(params, *[batch[n] for n in feed_names])

    def graph_loss_grad(params, *feeds):
        batch = dict(zip(feed_names, feeds))
        if n_micro <= 1:
            return jax.value_and_grad(loss_of)(params, batch)
        B = batch["tokens"].shape[0]
        assert B % n_micro == 0, (B, n_micro)
        mb = {k: v.reshape((n_micro, B // n_micro) + v.shape[1:])
              for k, v in batch.items()}

        def body(carry, mbatch):
            tot_loss, acc = carry
            l, g = jax.value_and_grad(loss_of)(params, mbatch)
            acc = jax.tree.map(
                lambda a, gi: a + gi.astype(jnp.float32) / n_micro, acc, g)
            return (tot_loss + l / n_micro, acc), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_val, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), mb)
        return loss_val, grads

    return graph_loss_grad


#: the AdamW apply's outputs: updated params (judged by their update, see
#: numerics.UPDATE_TOLERANCE) and optimizer state
ADAMW_ATTRS = {"numerics_class": (numerics.OPTIMIZER, "call")}


def lm_update_factory(lr: float):
    """Rebuild the AdamW apply: ``(params, grads, opt) -> (params, opt)``."""

    def update(params, grads, opt):
        return adamw_update(params, grads, opt, lr=lr)

    return update


def lm_serve_factory(cfg: ModelConfig, shard: int, serve_kw):
    """Rebuild one-token decode: ``(params, cache, tokens, pos) ->
    (logits, cache)``."""
    model = Model.for_config(cfg, shard)
    serve_kw = dict(serve_kw)

    def serve(params, cache, tokens, pos):
        return model.serve_step(params, cache, tokens, pos, **serve_kw)

    return serve


def _train_graph(feed_names, cfg: ModelConfig, shard: int, loss_kw,
                 lr: float, n_micro: int):
    """The training step AS A repro.core GRAPH: loss Call node, §4.1
    ``gradients()`` backward extension, AdamW update + Assign nodes —
    shared by the lowered (JIT) and eager (Session.run) paths.  Every
    Call is declared through a wire-shippable factory (§15), so the same
    graph also registers on a worker pool."""
    b = GraphBuilder()
    v_params = b.variable("params")
    v_opt = b.variable("opt")
    feed_names = tuple(feed_names)
    feed_nodes = {n: b.placeholder(n) for n in feed_names}
    ins = [v_params] + [feed_nodes[n] for n in feed_names]

    if n_micro <= 1:
        # faithful path: §4.1 gradients() extends the graph
        loss_node = b.call_factory(LM_LOSS_FACTORY, ins,
                                   args=(cfg, shard, feed_names, loss_kw),
                                   name="loss")
        (gref,) = gradients(b.graph, [loss_node], [v_params])
    else:
        # accumulated grads are one fused node (still "just nodes")
        lg = b.call_factory(LM_LOSS_GRAD_FACTORY, ins,
                            args=(cfg, shard, feed_names, loss_kw, n_micro),
                            name="loss_and_grad", n_out=2)
        loss_node, gref = lg, lg.output(1)
    upd = b.call_factory(LM_UPDATE_FACTORY, [v_params, gref, v_opt],
                         args=(lr,), name="adamw", n_out=2,
                         attrs=ADAMW_ATTRS)
    a1 = b.assign(v_params, upd.output(0))
    a2 = b.assign(v_opt, upd.output(1))
    return b, loss_node, a1, a2, feed_nodes


def build_train_step(
    cfg: ModelConfig,
    shape: Shape,
    mesh: Optional[Mesh] = None,
    rules: Optional[Dict[str, Any]] = None,
    *,
    lr: float = 3e-4,
    hparam_overrides: Optional[Dict[str, Any]] = None,
    via_graph: bool = True,
) -> StepBundle:
    shard = mesh.shape["model"] if mesh is not None else 1
    n_groups = mesh_mod.batch_shard_size(mesh) if mesh is not None else 1
    model = Model.for_config(cfg, shard)
    hp = step_hparams(cfg, shape, n_groups)
    hp.update(hparam_overrides or {})
    loss_kw = dict(q_chunk=hp["q_chunk"], loss_chunk=hp["loss_chunk"],
                   compute_dtype=hp["compute_dtype"],
                   scan_unroll=hp["scan_unroll"])
    if not model.is_encdec:
        loss_kw["n_token_groups"] = hp["n_token_groups"]

    def loss_of(params, batch):
        return model.loss_fn(params, batch, **loss_kw)

    def update_of(params, grads, opt):
        return adamw_update(params, grads, opt, lr=lr)

    batch_desc = model.batch_desc(shape)
    feed_names = list(batch_desc)
    n_micro = int(hp.get("microbatch", 1))

    def loss_and_grad_of(params, batch):
        """Gradient accumulation over n_micro microbatches (memory lever:
        stored activations scale with B/n_micro, grads accumulate fp32)."""
        if n_micro <= 1:
            return jax.value_and_grad(loss_of)(params, batch)
        B = batch["tokens"].shape[0]
        assert B % n_micro == 0, (B, n_micro)
        mb = {k: v.reshape((n_micro, B // n_micro) + v.shape[1:])
              for k, v in batch.items()}

        def body(carry, mbatch):
            tot_loss, acc = carry
            l, g = jax.value_and_grad(loss_of)(params, mbatch)
            acc = jax.tree.map(
                lambda a, gi: a + gi.astype(jnp.float32) / n_micro, acc, g)
            return (tot_loss + l / n_micro, acc), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_val, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), mb)
        return loss_val, grads

    if via_graph:
        b, loss_node, a1, a2, feed_nodes = _train_graph(
            feed_names, cfg, shard, loss_kw, lr, n_micro)
        sess = Session(b.graph)
        lowered = compile_subgraph(
            sess, [loss_node.ref], [feed_nodes[n].ref for n in feed_names],
            extra_updates=[a1.name, a2.name])
        n_nodes = lowered.n_nodes

        def fn(feeds: Dict[str, Any], variables: Dict[str, Any]):
            feed_vals = {_feed_key(n): feeds[n] for n in feed_names}
            (loss_val,), new_vars = lowered.fn(feed_vals, variables)
            return loss_val, new_vars
    else:
        n_nodes = 0

        def fn(feeds: Dict[str, Any], variables: Dict[str, Any]):
            params, opt = variables["params"], variables["opt"]
            loss_val, grads = loss_and_grad_of(params, feeds)
            new_params, new_opt = update_of(params, grads, opt)
            return loss_val, {"params": new_params, "opt": new_opt}

    # --- specs + shardings
    pdesc = model.describe_params()
    params_abs = abstract_params(pdesc)
    opt_abs = jax.eval_shape(adamw_init, params_abs)
    rules = rules if rules is not None else (
        mesh_mod.mesh_rules(mesh) if mesh is not None else None)
    if rules is not None:
        paxes = param_axes(pdesc)
        pspec = shd.param_pspecs(paxes, rules)
        opt_spec = jax.eval_shape(adamw_init, params_abs)  # structure template
        opt_pspec = dataclasses_replace_optstate(pspec, opt_spec)
        var_shardings = _named(mesh, {"params": pspec, "opt": opt_pspec})
        feed_shardings = {
            n: NamedSharding(mesh, shd.pspec_of(batch_desc[n].axes, rules))
            for n in feed_names}
        out_shardings = (NamedSharding(mesh, P()),
                         var_shardings)
    else:
        var_shardings = feed_shardings = out_shardings = None

    feed_specs = {n: jax.ShapeDtypeStruct(batch_desc[n].shape, batch_desc[n].dtype)
                  for n in feed_names}
    return StepBundle(fn=fn, feed_specs=feed_specs,
                      var_specs={"params": params_abs, "opt": opt_abs},
                      feed_shardings=feed_shardings,
                      var_shardings=var_shardings,
                      out_shardings=out_shardings,
                      model=model, kind="train", graph_nodes=n_nodes)


def dataclasses_replace_optstate(pspec_tree, opt_template):
    """OptState(step, m, v): m/v shard like params, step replicated."""
    from ..optim import OptState
    return OptState(step=P(), m=pspec_tree, v=pspec_tree)


# ---------------------------------------------------------------------------


def build_prefill_step(
    cfg: ModelConfig,
    shape: Shape,
    mesh: Optional[Mesh] = None,
    rules: Optional[Dict[str, Any]] = None,
    *,
    hparam_overrides: Optional[Dict[str, Any]] = None,
) -> StepBundle:
    """Forward over the full prompt; returns last-position logits."""
    shard = mesh.shape["model"] if mesh is not None else 1
    n_groups = mesh_mod.batch_shard_size(mesh) if mesh is not None else 1
    model = Model.for_config(cfg, shard)
    hp = step_hparams(cfg, shape, n_groups)
    hp.update(hparam_overrides or {})

    fwd_kw = dict(q_chunk=hp["q_chunk"], compute_dtype=hp["compute_dtype"],
                  scan_unroll=hp["scan_unroll"])
    if not model.is_encdec:
        fwd_kw["n_token_groups"] = hp["n_token_groups"]

    from ..models import lm as lm_mod
    from ..models import encdec as encdec_mod

    def fn(feeds: Dict[str, Any], variables: Dict[str, Any]):
        params = variables["params"]
        if model.is_encdec:
            x, _ = encdec_mod.forward(cfg, model.plan, params, feeds["tokens"],
                                      feeds["frames"], q_chunk=hp["q_chunk"],
                                      compute_dtype=hp["compute_dtype"],
                                      scan_unroll=hp["scan_unroll"])
        else:
            x, _ = lm_mod.forward(cfg, model.plan, params, feeds["tokens"],
                                  **fwd_kw)
        last = x[:, -1:, :]
        logits = lm_mod.logits_from_hidden(cfg, model.plan, params, last)
        return logits, {}

    batch_desc = model.batch_desc(shape)
    batch_desc.pop("labels", None)
    feed_names = list(batch_desc)
    pdesc = model.describe_params()
    params_abs = abstract_params(pdesc)
    rules = rules if rules is not None else (
        mesh_mod.mesh_rules(mesh) if mesh is not None else None)
    if rules is not None:
        pspec = shd.param_pspecs(param_axes(pdesc), rules)
        var_shardings = _named(mesh, {"params": pspec})
        feed_shardings = {
            n: NamedSharding(mesh, shd.pspec_of(batch_desc[n].axes, rules))
            for n in feed_names}
        out_shardings = (NamedSharding(
            mesh, shd.pspec_of(("batch", None, "vocab"), rules)), {})
    else:
        var_shardings = feed_shardings = out_shardings = None
    feed_specs = {n: jax.ShapeDtypeStruct(batch_desc[n].shape, batch_desc[n].dtype)
                  for n in feed_names}
    return StepBundle(fn=fn, feed_specs=feed_specs,
                      var_specs={"params": params_abs},
                      feed_shardings=feed_shardings,
                      var_shardings=var_shardings, out_shardings=out_shardings,
                      model=model, kind="prefill")


# ---------------------------------------------------------------------------


def build_serve_step(
    cfg: ModelConfig,
    shape: Shape,
    mesh: Optional[Mesh] = None,
    rules: Optional[Dict[str, Any]] = None,
    *,
    hparam_overrides: Optional[Dict[str, Any]] = None,
    via_graph: bool = True,
) -> StepBundle:
    """One-token decode against a seq_len cache (Variable in the graph)."""
    shard = mesh.shape["model"] if mesh is not None else 1
    n_groups = mesh_mod.batch_shard_size(mesh) if mesh is not None else 1
    model = Model.for_config(cfg, shard)
    longctx = shape.name == "long_500k"
    hp = step_hparams(cfg, shape, n_groups)
    hp.update(hparam_overrides or {})

    serve_kw: Dict[str, Any] = dict(compute_dtype=hp["compute_dtype"],
                                    serve_longctx=longctx,
                                    scan_unroll=hp["scan_unroll"])
    if not model.is_encdec:
        serve_kw["n_token_groups"] = hp["n_token_groups"]

    def serve_of(params, cache, tokens, pos):
        return model.serve_step(params, cache, tokens, pos, **serve_kw)

    if via_graph:
        b = GraphBuilder()
        v_params = b.variable("params")
        v_cache = b.variable("cache")
        t_ph = b.placeholder("tokens")
        p_ph = b.placeholder("pos")
        out = b.call_factory(LM_SERVE_FACTORY, [v_params, v_cache, t_ph, p_ph],
                             args=(cfg, shard, serve_kw), name="serve",
                             n_out=2)
        a_cache = b.assign(v_cache, out.output(1))
        sess = Session(b.graph)
        lowered = compile_subgraph(sess, [out.output(0)],
                                   [t_ph.ref, p_ph.ref],
                                   extra_updates=[a_cache.name])
        n_nodes = lowered.n_nodes

        def fn(feeds: Dict[str, Any], variables: Dict[str, Any]):
            feed_vals = {"tokens:0": feeds["tokens"], "pos:0": feeds["pos"]}
            (logits,), new_vars = lowered.fn(feed_vals, variables)
            return logits, new_vars
    else:
        n_nodes = 0

        def fn(feeds, variables):
            logits, new_cache = serve_of(variables["params"], variables["cache"],
                                         feeds["tokens"], feeds["pos"])
            return logits, {"cache": new_cache}

    pdesc = model.describe_params(serve_longctx=longctx)
    if hp.get("param_dtype") is not None:
        # serving-mode weights (e.g. bf16): checkpoint-cast at load time
        import dataclasses as _dc

        pdesc = jax.tree.map(
            lambda sp: _dc.replace(sp, dtype=hp["param_dtype"]), pdesc,
            is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init"))
    cdesc = model.init_cache_desc(batch=shape.global_batch,
                                  max_seq=shape.seq_len, serve_longctx=longctx,
                                  dtype=hp["compute_dtype"])
    params_abs = abstract_params(pdesc)
    cache_abs = abstract_params(cdesc)
    batch_desc = model.batch_desc(shape)
    feed_names = list(batch_desc)
    rules = rules if rules is not None else (
        mesh_mod.mesh_rules(mesh) if mesh is not None else None)
    if rules is not None:
        pspec = shd.param_pspecs(param_axes(pdesc), rules)
        caxes = param_axes(cdesc)
        if shape.global_batch == 1:  # long_500k: nothing to shard on batch
            caxes = jax.tree.map(
                lambda axes: tuple(None if a == "batch" else a for a in axes),
                caxes, is_leaf=lambda x: isinstance(x, tuple) and all(
                    a is None or isinstance(a, str) for a in x))
        cspec = shd.param_pspecs(caxes, rules)
        var_shardings = _named(mesh, {"params": pspec, "cache": cspec})
        feed_shardings = {}
        for n in feed_names:
            axes = batch_desc[n].axes
            if shape.global_batch == 1:
                axes = tuple(None for _ in axes)
            feed_shardings[n] = NamedSharding(mesh, shd.pspec_of(axes, rules))
        out_vocab = shd.pspec_of(
            ("batch" if shape.global_batch > 1 else None, None, "vocab"), rules)
        out_shardings = (NamedSharding(mesh, out_vocab),
                         _named(mesh, {"cache": cspec}))
    else:
        var_shardings = feed_shardings = out_shardings = None
    feed_specs = {n: jax.ShapeDtypeStruct(batch_desc[n].shape, batch_desc[n].dtype)
                  for n in feed_names}
    return StepBundle(fn=fn, feed_specs=feed_specs,
                      var_specs={"params": params_abs, "cache": cache_abs},
                      feed_shardings=feed_shardings,
                      var_shardings=var_shardings, out_shardings=out_shardings,
                      model=model, kind="decode", graph_nodes=n_nodes)


def build_eager_train_step(
    cfg: ModelConfig,
    shape: Shape,
    *,
    lr: float = 3e-4,
    hparam_overrides: Optional[Dict[str, Any]] = None,
    numerics: Optional[str] = None,
    options: Optional[SessionOptions] = None,
) -> EagerStepBundle:
    """Train step for the eager multi-run path: the same graph as
    ``build_train_step(via_graph=True)`` but *run*, not lowered — each call
    re-enters ``Session.run`` and hits the cached Executable for the
    (loss, train_op) signature (compile once, run many; DESIGN.md §5).
    ``numerics`` selects the fused-region policy (DESIGN.md §9): the
    train tool defaults the graph engine to "fast".  The graph is built
    from §15 Call factories, so with ``options.cluster`` set the same
    step registers and runs on a worker pool."""
    model = Model.for_config(cfg)
    hp = step_hparams(cfg, shape, 1)
    hp.update(hparam_overrides or {})
    loss_kw = dict(q_chunk=hp["q_chunk"], loss_chunk=hp["loss_chunk"],
                   compute_dtype=hp["compute_dtype"],
                   scan_unroll=hp["scan_unroll"])
    if not model.is_encdec:
        loss_kw["n_token_groups"] = hp["n_token_groups"]

    feed_names = list(model.batch_desc(shape))
    b, loss_node, a1, a2, feed_nodes = _train_graph(
        feed_names, cfg, 1, loss_kw, lr, 1)
    train_op = b.group([a1, a2], name="train_op")
    opts = options or SessionOptions()
    if numerics is not None:
        opts = dataclasses.replace(opts, numerics=numerics)
    sess = Session(b.graph, options=opts)
    run = sess.make_callable([loss_node.ref, train_op.ref],
                             [feed_nodes[n].ref for n in feed_names])

    def step(feeds: Dict[str, Any]):
        loss_val, _ = run(*[feeds[n] for n in feed_names])
        return loss_val

    return EagerStepBundle(session=sess, step=step, model=model,
                           feed_names=tuple(feed_names), kind="train",
                           graph_nodes=len(b.graph.nodes))


def build_eager_serve_step(cfg: ModelConfig,
                           numerics: Optional[str] = None,
                           options: Optional[SessionOptions] = None
                           ) -> EagerStepBundle:
    """One-token decode as a Session graph: the KV cache is a Variable
    updated by an Assign node, so the decode loop is exactly the paper's
    steady-state serving shape — one cached Executable re-run per token.
    Under ``numerics="fast"`` (the serve tool's graph-engine default) the
    ``Call`` + cache Assign fuse into one jitted region (DESIGN.md §9).
    The serve Call is factory-form (§15), so the graph is wire-shippable."""
    model = Model.for_config(cfg)

    b = GraphBuilder()
    v_params = b.variable("params")
    v_cache = b.variable("cache")
    t_ph = b.placeholder("tokens")
    p_ph = b.placeholder("pos")
    out = b.call_factory(LM_SERVE_FACTORY, [v_params, v_cache, t_ph, p_ph],
                         args=(cfg, 1, {}), name="serve", n_out=2)
    a_cache = b.assign(v_cache, out.output(1))
    opts = options or SessionOptions()
    if numerics is not None:
        opts = dataclasses.replace(opts, numerics=numerics)
    sess = Session(b.graph, options=opts)
    run = sess.make_callable([out.output(0), a_cache.ref],
                             [t_ph.ref, p_ph.ref])

    def step(feeds: Dict[str, Any]):
        logits, _ = run(feeds["tokens"], feeds["pos"])
        return logits

    return EagerStepBundle(session=sess, step=step, model=model,
                           feed_names=("tokens", "pos"), kind="decode",
                           graph_nodes=len(b.graph.nodes))


@dataclasses.dataclass
class WireStepBundle:
    """A train/score step whose graph can ship to a §11 worker pool.

    Every node is a registered primitive op (MatMul/ReLU/SoftmaxXent/
    Assign/...), so the graph pickles onto the wire with no Call
    machinery at all — the minimal exemplar.  The Call-based LM steps
    ship too, now that they are declared through §15 factories
    (``GraphBuilder.call_factory``); see ``build_lm_replica_spec``.
    """

    builder: Any                     # GraphBuilder owning the graph
    loss: Any                        # TensorRef: scalar mean xent
    logits: Any                      # TensorRef: pre-softmax scores
    train_op: Any                    # TensorRef: grouped Assign updates
    feed_x: Any                      # TensorRef: [batch, n_features] float32
    feed_y: Any                      # TensorRef: [batch] int labels
    var_names: Tuple[str, ...]


def build_wire_train_step(tasks: Sequence[str], *, n_features: int = 16,
                          n_hidden: int = 32, n_classes: int = 8,
                          lr: float = 0.1, seed: int = 0) -> WireStepBundle:
    """Primitive-op MLP softmax classifier, device-tagged across ``tasks``.

    The forward pass alternates devices (x@W1+ReLU on the first task, the
    logits matmul on the last), so every step exercises cross-task
    Send/Recv in both directions; §4.1 ``gradients()`` extends the graph
    with the backward pass and SGD updates land in Assign nodes that the
    §3.2.1 placer colocates with their Variables — which is what keeps
    each worker's variable store authoritative for the state it owns.
    """
    import numpy as np

    from ..core import GraphBuilder, gradients

    rs = np.random.RandomState(seed)
    b = GraphBuilder()
    d0, d1 = tasks[0], tasks[-1]
    x = b.placeholder("x")
    y = b.placeholder("y")
    w1 = b.variable("w1", jnp.asarray(
        rs.randn(n_features, n_hidden).astype("f") * 0.2), device=d0)
    w2 = b.variable("w2", jnp.asarray(
        rs.randn(n_hidden, n_classes).astype("f") * 0.2), device=d1)
    h = b.relu(b.matmul(x, w1, name="mm1", device=d0), name="h", device=d0)
    logits = b.matmul(h, w2, name="logits", device=d1)
    loss = b.softmax_xent(logits, y, name="loss")
    g1, g2 = gradients(b.graph, [loss], [w1, w2])
    lrc = b.constant(jnp.float32(lr), name="lr")
    a1 = b.assign(w1, b.sub(w1, b.mul(lrc, g1, name="upd1/scaled"),
                            name="upd1/new"))
    a2 = b.assign(w2, b.sub(w2, b.mul(lrc, g2, name="upd2/scaled"),
                            name="upd2/new"))
    train_op = b.group([a1, a2], name="train_op")
    return WireStepBundle(builder=b, loss=loss.ref, logits=logits.ref,
                          train_op=train_op.ref, feed_x=x.ref, feed_y=y.ref,
                          var_names=("w1", "w2"))


# ---------------------------------------------------------------------------
# §15 replica specs: train-step shapes for distrib.replication.ReplicaPlan


def _sgd_apply(lr, values, grads):
    """Master-side parameter-server SGD (async mode)."""
    return {k: values[k] - lr * g for k, g in grads.items()}


def _lm_apply(lr, values, grads):
    """Master-side parameter-server AdamW (async mode)."""
    new_params, new_opt = adamw_update(values["params"], grads["params"],
                                       values["opt"], lr=lr)
    return {"params": new_params, "opt": new_opt}


def build_mlp_replica_spec(*, n_features: int = 16, n_hidden: int = 32,
                           n_classes: int = 8, lr: float = 0.1,
                           seed: int = 0):
    """The primitive-op MLP of ``build_wire_train_step`` reshaped as a
    ReplicaSpec: N data-parallel copies sharing (w1, w2)."""
    import numpy as np

    from ..distrib.replication import ReplicaSpec, ReplicaStep

    rs = np.random.RandomState(seed)
    init = {
        "w1": jnp.asarray(rs.randn(n_features, n_hidden).astype("f") * 0.2),
        "w2": jnp.asarray(rs.randn(n_hidden, n_classes).astype("f") * 0.2),
    }

    def build_replica(b, r, dev, var_inputs):
        x = b.placeholder(f"rep{r}/x")
        y = b.placeholder(f"rep{r}/y")
        w1, w2 = var_inputs["w1"], var_inputs["w2"]
        h = b.relu(b.matmul(x, w1, name=f"rep{r}/mm1", device=dev),
                   name=f"rep{r}/h", device=dev)
        logits = b.matmul(h, w2, name=f"rep{r}/logits", device=dev)
        loss = b.softmax_xent(logits, y, name=f"rep{r}/loss")
        g1, g2 = gradients(b.graph, [loss], [w1, w2])
        return ReplicaStep(loss=loss.ref, grads={"w1": g1, "w2": g2},
                           feeds={"x": x.ref, "y": y.ref})

    def build_apply(b, var_nodes, mean_grads, dev):
        lrc = b.constant(jnp.float32(lr), name="lr", device=dev)
        a1 = b.assign(var_nodes["w1"], b.sub(
            var_nodes["w1"], b.mul(lrc, mean_grads["w1"], name="upd1/scaled"),
            name="upd1/new"))
        a2 = b.assign(var_nodes["w2"], b.sub(
            var_nodes["w2"], b.mul(lrc, mean_grads["w2"], name="upd2/scaled"),
            name="upd2/new"))
        return b.group([a1, a2], name="train_op")

    return ReplicaSpec(var_names=("w1", "w2"), read_vars=("w1", "w2"),
                       grad_vars=("w1", "w2"), feed_names=("x", "y"),
                       init_values=init, build_replica=build_replica,
                       build_apply=build_apply,
                       apply_fn=functools.partial(_sgd_apply, lr))


def build_lm_replica_spec(cfg: ModelConfig, shape: Shape, *, lr: float = 1e-2,
                          hparam_overrides: Optional[Dict[str, Any]] = None,
                          seed: int = 0):
    """The factory-Call LM train step as a ReplicaSpec: each replica is
    one ``lm_loss_factory`` Call plus its §4.1 backward extension, with
    parameters shared (sync) or parameter-served (async)."""
    from ..distrib.replication import ReplicaSpec, ReplicaStep

    model = Model.for_config(cfg)
    hp = step_hparams(cfg, shape, 1)
    hp.update(hparam_overrides or {})
    loss_kw = dict(q_chunk=hp["q_chunk"], loss_chunk=hp["loss_chunk"],
                   compute_dtype=hp["compute_dtype"],
                   scan_unroll=hp["scan_unroll"])
    if not model.is_encdec:
        loss_kw["n_token_groups"] = hp["n_token_groups"]
    feed_names = tuple(model.batch_desc(shape))
    params = init_params(model.describe_params(), jax.random.PRNGKey(seed))
    init = {"params": params, "opt": adamw_init(params)}

    def build_replica(b, r, dev, var_inputs):
        feeds = {n: b.placeholder(f"rep{r}/{n}") for n in feed_names}
        loss = b.call_factory(
            LM_LOSS_FACTORY,
            [var_inputs["params"]] + [feeds[n] for n in feed_names],
            args=(cfg, 1, feed_names, loss_kw), name=f"rep{r}/loss",
            device=dev)
        (g,) = gradients(b.graph, [loss], [var_inputs["params"]])
        return ReplicaStep(loss=loss.ref, grads={"params": g},
                           feeds={n: feeds[n].ref for n in feed_names})

    def build_apply(b, var_nodes, mean_grads, dev):
        upd = b.call_factory(
            LM_UPDATE_FACTORY,
            [var_nodes["params"], mean_grads["params"], var_nodes["opt"]],
            args=(lr,), name="adamw", n_out=2, attrs=ADAMW_ATTRS,
            device=dev)
        a1 = b.assign(var_nodes["params"], upd.output(0))
        a2 = b.assign(var_nodes["opt"], upd.output(1))
        return b.group([a1, a2], name="train_op")

    return ReplicaSpec(var_names=("params", "opt"), read_vars=("params",),
                       grad_vars=("params",), feed_names=feed_names,
                       init_values=init, build_replica=build_replica,
                       build_apply=build_apply,
                       apply_fn=functools.partial(_lm_apply, lr))


def build_step(cfg: ModelConfig, shape_name: str, mesh=None, rules=None, **kw
               ) -> StepBundle:
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        return build_train_step(cfg, shape, mesh, rules, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh, rules, **kw)
    return build_serve_step(cfg, shape, mesh, rules, **kw)
