"""Launch-layer hparam levers: exactness guarantees for the §Perf knobs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.steps import build_step
from repro.models.params import init_params
from repro.optim import adamw_init


def _feeds(cfg, B=4, S=32, seed=0):
    rs = np.random.RandomState(seed)
    f = {"tokens": jnp.array(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
         "labels": jnp.array(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32)}
    if cfg.family == "encdec":
        f["frames"] = jnp.array(
            (rs.randn(B, cfg.enc_seq, cfg.d_model) * 0.1).astype("f"))
    return f


def test_microbatch_gradient_accumulation_is_exact():
    """EXPERIMENTS §Perf H1 lever: k-microbatch accumulation == full batch."""
    cfg = get_config("smollm-360m", smoke=True)
    feeds = _feeds(cfg)
    results = {}
    for k in (1, 2, 4):
        sb = build_step(cfg, "train_4k",
                        hparam_overrides={"compute_dtype": jnp.float32,
                                          "microbatch": k})
        params = init_params(sb.model.describe_params(), jax.random.PRNGKey(0))
        loss, newv = sb.fn(feeds, {"params": params, "opt": adamw_init(params)})
        results[k] = (float(loss), newv["params"])
    for k in (2, 4):
        assert abs(results[k][0] - results[1][0]) < 1e-4
        for a, b in zip(jax.tree.leaves(results[1][1]),
                        jax.tree.leaves(results[k][1])):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


def test_microbatch_moe_arch_runs():
    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)
    sb = build_step(cfg, "train_4k",
                    hparam_overrides={"compute_dtype": jnp.float32,
                                      "microbatch": 2})
    feeds = _feeds(cfg)
    params = init_params(sb.model.describe_params(), jax.random.PRNGKey(0))
    loss, _ = sb.fn(feeds, {"params": params, "opt": adamw_init(params)})
    assert np.isfinite(float(loss))


def test_serve_param_dtype_bf16():
    """§Perf H2 lever: bf16 serving weights thread through the serve step."""
    cfg = get_config("qwen2-0.5b", smoke=True)
    sb = build_step(cfg, "decode_32k",
                    hparam_overrides={"param_dtype": jnp.bfloat16})
    leaves = jax.tree.leaves(sb.var_specs["params"])
    assert all(l.dtype == jnp.bfloat16 for l in leaves)
    params = init_params(sb.model.describe_params(), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    cache = init_params(sb.model.init_cache_desc(batch=2, max_seq=8,
                                                 dtype=jnp.bfloat16),
                        jax.random.PRNGKey(1))
    logits, _ = sb.fn({"tokens": jnp.zeros((2, 1), jnp.int32),
                       "pos": jnp.array(0, jnp.int32)},
                      {"params": params, "cache": cache})
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_seq_res_rules_preserve_loss_on_host_mesh():
    """SP sharding rules are semantics-preserving (1x1 mesh sanity)."""
    from repro.launch import mesh as mesh_mod
    from repro.parallel import sharding as shd

    cfg = get_config("smollm-360m", smoke=True)
    feeds = _feeds(cfg)
    losses = {}
    for tag, overrides in [("base", None), ("sp", {"seq_res": "model"})]:
        mesh = mesh_mod.make_host_mesh()
        rules = mesh_mod.mesh_rules(mesh, overrides=overrides)
        with shd.axis_rules(rules, mesh):
            sb = build_step(cfg, "train_4k", mesh, rules,
                            hparam_overrides={"compute_dtype": jnp.float32})
            params = init_params(sb.model.describe_params(),
                                 jax.random.PRNGKey(0))
            loss, _ = jax.jit(sb.fn)(feeds, {"params": params,
                                             "opt": adamw_init(params)})
            losses[tag] = float(loss)
    assert abs(losses["base"] - losses["sp"]) < 1e-5
