"""End-to-end system behaviour: the whole paper stack working together.

train: data pipeline (§4.5/4.6) -> Session graph with loss + §4.1
gradients + optimizer-as-nodes -> §10 lowering -> jax.jit, with §3.3
periodic checkpointing.  Asserts: loss actually decreases on the
structured synthetic LM task, and eager Session.run matches the compiled
path step for step.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, FileCheckpointIO
from repro.configs import get_config
from repro.core import GraphBuilder, Session, compile_subgraph, gradients
from repro.data import SyntheticLMDataset
from repro.launch.steps import build_step
from repro.models.api import Model
from repro.models.params import init_params
from repro.optim import adamw_init


def _tiny_cfg():
    from repro.models.config import ModelConfig

    return ModelConfig(arch_id="tiny-lm", family="dense", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=128, tie_embeddings=True)


def test_training_loss_decreases_end_to_end(tmp_path):
    cfg = _tiny_cfg()
    sb = build_step(cfg, "train_4k",
                    hparam_overrides={"compute_dtype": jnp.float32},
                    lr=2e-3)
    ds = SyntheticLMDataset(cfg.vocab_size, seq_len=32, seed=0)
    params = init_params(sb.model.describe_params(), jax.random.PRNGKey(0))
    variables = {"params": params, "opt": adamw_init(params)}
    step = jax.jit(sb.fn)
    io = FileCheckpointIO(str(tmp_path))
    mgr = CheckpointManager(io, every_steps=20, keep=2)

    losses = []
    for i in range(60):
        batch = {k: jnp.asarray(v) for k, v in ds.batch(8, i).items()}
        loss, variables = step(batch, variables)
        losses.append(float(loss))
        if mgr.should_save(i):
            mgr.save(i, {"variables": variables})

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    assert last < first - 0.3, (first, last)
    assert np.isfinite(losses).all()
    assert mgr.latest_step() is not None
    restored = mgr.restore_latest()
    assert "variables" in restored


def test_eager_session_matches_compiled_training():
    """The same Session graph run eagerly (§3.1 executor) and through the
    §10 lowering gives identical parameter trajectories."""
    rs = np.random.RandomState(0)
    X = jnp.array(rs.randn(32, 4).astype("f"))
    Y = jnp.array((np.asarray(X) @ np.array([[1.], [2.], [-1.], [0.5]], "f")))

    def build():
        b = GraphBuilder()
        W = b.variable("W", init_value=lambda: jnp.zeros((4, 1), "f"))
        x = b.placeholder("x")
        y = b.placeholder("y")
        loss = b.reduce_mean(b.square(b.sub(b.matmul(x, W), y)), name="loss")
        (gW,) = gradients(b.graph, [loss], [W])
        upd = b.assign(W, b.sub(W, b.mul(
            b.constant(jnp.array(0.05), name="lr"), gW)))
        return b, W, x, y, loss, upd

    b, W, x, y, loss, upd = build()
    sess = Session(b.graph)
    for _ in range(15):
        sess.run(upd.ref, {x.ref: X, y.ref: Y})
    w_eager = np.asarray(sess.variable_value("W"))

    b2, W2, x2, y2, loss2, upd2 = build()
    low = compile_subgraph(Session(b2.graph), [loss2.ref], [x2.ref, y2.ref],
                           extra_updates=[upd2.name])
    jf = jax.jit(low.fn)
    vals = {"W": jnp.zeros((4, 1), "f")}
    for _ in range(15):
        _, new = jf({"x:0": X, "y:0": Y}, vals)
        vals.update(new)
    np.testing.assert_allclose(vals["W"], w_eager, rtol=1e-5, atol=1e-6)


def test_sharded_jit_path_on_host_mesh():
    """The mesh/sharding machinery end to end on a degenerate 1x1 mesh."""
    from repro.launch import mesh as mesh_mod
    from repro.parallel import sharding as shd

    cfg = get_config("smollm-360m", smoke=True)
    mesh = mesh_mod.make_host_mesh()
    rules = mesh_mod.mesh_rules(mesh)
    with shd.axis_rules(rules, mesh):
        sb = build_step(cfg, "train_4k", mesh, rules,
                        hparam_overrides={"compute_dtype": jnp.float32})
        jf = jax.jit(sb.fn,
                     in_shardings=(sb.feed_shardings, sb.var_shardings),
                     out_shardings=sb.out_shardings)
        params = init_params(sb.model.describe_params(), jax.random.PRNGKey(0))
        variables = {"params": params, "opt": adamw_init(params)}
        rs = np.random.RandomState(0)
        batch = {
            "tokens": jnp.array(rs.randint(0, cfg.vocab_size, (2, 32)), jnp.int32),
            "labels": jnp.array(rs.randint(0, cfg.vocab_size, (2, 32)), jnp.int32),
        }
        loss, variables = jf(batch, variables)
    assert np.isfinite(float(loss))


def test_serve_graph_cache_threading():
    """Decode through the graph path: cache Variable advances per step."""
    cfg = _tiny_cfg()
    sb = build_step(cfg, "decode_32k",
                    hparam_overrides={"compute_dtype": jnp.float32})
    model = sb.model
    B, S = 2, 8
    params = model.init(jax.random.PRNGKey(0))
    cache = init_params(model.init_cache_desc(batch=B, max_seq=S),
                        jax.random.PRNGKey(1))
    rs = np.random.RandomState(0)
    tokens = jnp.array(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32)

    from repro.models import lm

    hid, _ = lm.forward(cfg, model.plan, params, tokens)
    want = lm.logits_from_hidden(cfg, model.plan, params, hid)

    step = jax.jit(sb.fn)
    variables = {"params": params, "cache": cache}
    worst = 0.0
    for t in range(S):
        logits, new_vars = step(
            {"tokens": tokens[:, t:t + 1], "pos": jnp.array(t, jnp.int32)},
            variables)
        variables = {"params": params, **new_vars}
        worst = max(worst, float(jnp.max(jnp.abs(logits[:, 0] - want[:, t]))))
    assert worst < 1e-3


def test_inception_style_parameter_accounting():
    """§6 lesson 1: tools to count parameters catch spec flaws.  We check
    the param-count tool against a hand computation for a small dense cfg."""
    from repro.models.params import count_params

    cfg = _tiny_cfg()
    model = Model.for_config(cfg)
    D, H, KV, hd, F, V = 64, 4, 2, 16, 128, 128
    per_layer = (D + D * H * hd + 2 * D * KV * hd + H * hd * D  # ln1+qkv+o
                 + D + 3 * D * F)                                # ln2+mlp
    want = V * D + D + 2 * per_layer  # embed(tied) + final_norm + 2 layers
    assert count_params(model.describe_params()) == want
