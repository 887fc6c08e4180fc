"""Per-architecture smoke tests: reduced same-family config, one forward
+ one train step on CPU; output shapes + finiteness (assignment req)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.steps import build_step
from repro.models.api import Model
from repro.models.params import init_params
from repro.optim import adamw_init


def _batch(cfg, B=2, S=32, seed=0):
    rs = np.random.RandomState(seed)
    batch = {
        "tokens": jnp.array(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.array(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
    }
    if cfg.family == "encdec":
        batch["frames"] = jnp.array(
            (rs.randn(B, cfg.enc_seq, cfg.d_model) * 0.1).astype("float32"))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_config(arch, smoke=True)
    assert cfg.n_layers <= 3 and cfg.d_model <= 512
    assert cfg.n_experts <= 4
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = model.forward_logits(params, batch)
    assert logits.shape == (2, 32, model.plan.vocab_pad)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step_via_graph(arch):
    """One optimizer step through the full stack: graph -> lowering -> jit."""
    cfg = get_config(arch, smoke=True)
    sb = build_step(cfg, "train_4k",
                    hparam_overrides={"compute_dtype": jnp.float32})
    batch = _batch(cfg)
    params = init_params(sb.model.describe_params(), jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(sb.fn)
    loss, newv = step(batch, {"params": params, "opt": opt})
    assert np.isfinite(float(loss))
    assert float(loss) > 0.5 * np.log(cfg.vocab_size)
    # parameters actually moved
    moved = any(
        not np.allclose(a, b) for a, b in zip(
            jax.tree.leaves(params), jax.tree.leaves(newv["params"])))
    assert moved
    assert int(newv["opt"].step) == 1
    # second step decreases loss on the same batch (sanity, not science)
    loss2, _ = step(batch, newv)
    assert float(loss2) < float(loss)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serve_step_shapes(arch):
    cfg = get_config(arch, smoke=True)
    model = Model.for_config(cfg)
    B, max_seq = 2, 16
    params = model.init(jax.random.PRNGKey(0))
    cache = init_params(model.init_cache_desc(batch=B, max_seq=max_seq),
                        jax.random.PRNGKey(1))
    toks = jnp.zeros((B, 1), jnp.int32)
    logits, new_cache = model.serve_step(params, cache, toks, jnp.array(0))
    assert logits.shape == (B, 1, model.plan.vocab_pad)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "hymba-1.5b", "qwen2-0.5b",
                                  "granite-4.0-h-micro"])
def test_decode_matches_forward(arch):
    """Greedy per-position decode logits == teacher-forced forward."""
    cfg = get_config(arch, smoke=True)
    model = Model.for_config(cfg)
    B, S = 2, 12
    params = model.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    tokens = jnp.array(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    from repro.models import lm

    hid, _ = lm.forward(cfg, model.plan, params, tokens)
    full = lm.logits_from_hidden(cfg, model.plan, params, hid)
    cache = init_params(model.init_cache_desc(batch=B, max_seq=S),
                        jax.random.PRNGKey(1))
    step = jax.jit(lambda c, tk, t: model.serve_step(params, c, tk, t))
    worst = 0.0
    for t in range(S):
        lg, cache = step(cache, tokens[:, t:t + 1], jnp.array(t))
        worst = max(worst, float(jnp.max(jnp.abs(lg[:, 0] - full[:, t]))))
    assert worst < 1e-3


def test_full_configs_match_assignment():
    """The full configs carry the exact assigned dimensions."""
    spec = {
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
        "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
        "smollm-360m": (32, 960, 15, 5, 2560, 49152),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }
    for arch, (L, D, H, KV, FF, V) in spec.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L and cfg.d_model == D, arch
        assert cfg.n_heads == H and cfg.n_kv_heads == KV, arch
        assert cfg.d_ff == FF and cfg.vocab_size == V, arch
        assert cfg.source, arch
    assert get_config("qwen3-moe-30b-a3b").n_experts == 128
    assert get_config("qwen3-moe-30b-a3b").top_k == 8
    assert get_config("qwen2-moe-a2.7b").n_experts == 60
    assert get_config("qwen2-moe-a2.7b").top_k == 4
    assert get_config("qwen2-moe-a2.7b").n_shared_experts == 4
    assert get_config("mamba2-2.7b").ssm_state == 128
    assert get_config("hymba-1.5b").ssm_state == 16
    granite = get_config("granite-4.0-h-micro")
    assert granite.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(granite.layer_types) if t == "attention"] == [
        5, 15, 25, 35]
    assert (granite.ssm_heads, granite.ssm_head_dim, granite.ssm_state,
            granite.ssm_conv, granite.ssm_conv_bias) == (64, 64, 128, 4, True)
    assert (granite.embedding_multiplier, granite.residual_multiplier,
            granite.attn_scale, granite.logits_scaling, granite.rope) == (
        12.0, 0.22, 1 / 64, 8.0, False)
