"""Hybrids of Mamba-2 and attention layers in the order ``layer_types``
gives (granite-4.0-h): the layer grouping, the scalar multipliers, the
conv bias, attention without positions at a set scale; and that a config
that sets none of them computes exactly what it computed before."""
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import lm
from repro.models.api import Model
from repro.models.params import init_params


def test_block_groups_follow_layer_types():
    cfg = get_config("granite-4.0-h-micro")
    groups = [(g.kind, g.count) for g in lm.block_groups(cfg)]
    assert groups == [("ssm_mlp", 5), ("attn", 1)] + [
        ("ssm_mlp", 9), ("attn", 1)] * 3 + [("ssm_mlp", 4)]
    assert sum(n for _, n in groups) == cfg.n_layers == 40
    smoke = get_config("granite-4.0-h-micro", smoke=True)
    assert [(g.kind, g.count) for g in lm.block_groups(smoke)] == [
        ("ssm_mlp", 1), ("attn", 1), ("ssm_mlp", 1)]
    # an Mamba group holds the mixer and the MLP; its cache the SSM state
    model = Model.for_config(smoke)
    assert {"wz", "conv_x_b", "norm", "wout", "ln2", "w1", "w2", "w3"} <= set(
        model.describe_params()["g0"])
    cache = model.init_cache_desc(batch=1, max_seq=8)
    assert set(cache["g0"]) == {"ssm"} and set(cache["g1"]) == {"k", "v"}


# sha256 (first 16 hex digits) of the SMOKE models' full-forward logits,
# four decode steps' logits and loss, on PRNGKey(0) weights, as computed
# before the multipliers, the conv bias and NoPE existed
DENSE_GOLDEN = {
    "qwen2-0.5b": ("6989b27dbbba74b9", "921088b278164f35", "4c7ecb023a383993"),
    "smollm-360m": ("ea646cd7fa3133a1", "cd0277ba0786c8cd", "8067488885a5a2fc"),
}


def _sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("arch", sorted(DENSE_GOLDEN))
def test_unset_multipliers_leave_dense_logits_bit_for_bit(arch):
    cfg = get_config(arch, smoke=True)
    m = Model.for_config(cfg)
    p = m.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16)),
                       jnp.int32)
    full = m.forward_logits(p, {"tokens": toks})
    cache = init_params(m.init_cache_desc(batch=2, max_seq=16), jax.random.PRNGKey(1))
    rows = []
    for t in range(4):
        lg, cache = m.serve_step(p, cache, toks[:, t:t + 1], jnp.array(t))
        rows.append(np.asarray(lg))
    loss = m.loss_fn(p, {"tokens": toks, "labels": toks})
    assert (_sha(full), _sha(np.stack(rows)), _sha(loss)) == DENSE_GOLDEN[arch]


def test_conv_bias_adds_to_every_position_and_step():
    rs = np.random.RandomState(0)
    B, S, C, K = 2, 12, 6, 4
    x = jnp.asarray(rs.randn(B, S, C).astype("f"))
    w = jnp.asarray(rs.randn(C, K).astype("f"))
    b = jnp.asarray(rs.randn(C).astype("f"))
    plain, _ = L.causal_conv1d(x, w)
    biased, _ = L.causal_conv1d(x, w, bias=b)
    np.testing.assert_allclose(biased, plain + b, rtol=1e-6, atol=1e-6)
    cache = jnp.zeros((B, K - 1, C))
    steps = []
    for t in range(S):
        y, cache = L.causal_conv1d(x[:, t:t + 1], w, cache, bias=b)
        steps.append(y)
    np.testing.assert_allclose(jnp.concatenate(steps, 1), biased,
                               rtol=1e-5, atol=1e-5)


def test_attention_scale_replaces_one_over_root_head_dim():
    rs = np.random.RandomState(1)
    B, S, KV, G, D = 1, 10, 2, 2, 16
    q = jnp.asarray(rs.randn(B, S, KV, G, D).astype("f"))
    k = jnp.asarray(rs.randn(B, S, KV, D).astype("f"))
    v = jnp.asarray(rs.randn(B, S, KV, D).astype("f"))
    pos = jnp.arange(S)
    s = 1.0 / 64
    # a scale s on q at the default 1/sqrt(D) is the scale s * sqrt(D)
    want = L.attention(q * (s * math.sqrt(D)), k, v, pos_q=pos, pos_kv=pos)
    got = L.attention(q, k, v, pos_q=pos, pos_kv=pos, scale=s)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    chunked = L.attention(q, k, v, pos_q=pos, pos_kv=pos, scale=s, q_chunk=5)
    np.testing.assert_allclose(chunked, want, rtol=1e-5, atol=1e-6)
    # decode: the last query against the cache of the earlier positions
    t = S - 1
    dec = L.decode_attention(q[:, t:t + 1], k[:, :t], v[:, :t], k[:, t:t + 1],
                             v[:, t:t + 1], pos_q=pos[t:t + 1], pos_kv=pos[:t],
                             scale=s)
    np.testing.assert_allclose(dec, want[:, t:t + 1], rtol=1e-5, atol=1e-6)


def test_nope_projections_do_not_see_positions():
    cfg = get_config("granite-4.0-h-micro", smoke=True)
    assert not cfg.rope
    model = Model.for_config(cfg)
    p = model.init(jax.random.PRNGKey(0))["g1"]
    layer = jax.tree.map(lambda a: a[0], p)
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 3, cfg.d_model))
    a = lm._project_qkv(cfg, model.plan, layer, h, jnp.arange(3))
    b = lm._project_qkv(cfg, model.plan, layer, h, jnp.arange(3) + 100)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    roped = cfg.__class__(**{**cfg.__dict__, "rope": True})
    c = lm._project_qkv(roped, model.plan, layer, h, jnp.arange(3) + 100)
    assert not np.allclose(a[0], c[0])


def test_multipliers_scale_what_they_name():
    cfg = get_config("granite-4.0-h-micro", smoke=True)
    plain = cfg.__class__(**{**cfg.__dict__, "embedding_multiplier": 1.0,
                             "residual_multiplier": 1.0, "logits_scaling": 1.0})
    m, m0 = Model.for_config(cfg), Model.for_config(plain)
    p = m.init(jax.random.PRNGKey(0))
    toks = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    x = lm._embed(cfg, p, toks, jnp.float32)
    np.testing.assert_array_equal(x, 12.0 * lm._embed(plain, p, toks, jnp.float32))
    h, _ = lm.forward(cfg, m.plan, p, toks)
    np.testing.assert_allclose(lm.logits_from_hidden(cfg, m.plan, p, h),
                               lm.logits_from_hidden(plain, m.plan, p, h) / 8.0,
                               rtol=1e-6)
    # with the residual multiplier, every layer's branch is scaled down
    full = m.forward_logits(p, {"tokens": toks})
    unscaled = m0.forward_logits(p, {"tokens": toks}) / 8.0
    assert not np.allclose(full, unscaled, atol=1e-4)
