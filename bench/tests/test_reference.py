"""The plain reference against the system's own forward pass and loss, at
a size a CPU holds, on the weights the benchmark makes from a seed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import decoder as ref
from bench.tests.conftest import CHAT, TRAIN, small_cell


@pytest.mark.parametrize("name", [TRAIN, CHAT])
def test_logits_match_the_program(program, name):
    from repro.models import lm
    from repro.models.api import Model

    c = small_cell(name).config
    model = Model.for_config(program.model_config(c))
    w = weights.make(c, weights.key_for(5, 0))
    program.check_layout(model, w)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 24))
    with jax.default_matmul_precision("highest"):
        x, _ = lm.forward(model.cfg, model.plan, w, jnp.asarray(toks))
        want = lm.logits_from_hidden(model.cfg, model.plan, w, x)
        got = jnp.stack([ref.logits(c, w, jnp.asarray(t)) for t in toks])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_loss_and_gradients_match_the_program(program):
    from repro.models.api import Model

    c = small_cell(TRAIN).config
    model = Model.for_config(program.model_config(c))
    w = weights.make(c, weights.key_for(6, 0))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, c["vocab_size"], (3, 16)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, c["vocab_size"], (3, 16)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(lambda p: model.loss_fn(
            p, {"tokens": toks, "labels": labels}))(w)
        rows = [jax.value_and_grad(ref.row_loss, argnums=1)(c, w, t, y)
                for t, y in zip(toks, labels)]
    np.testing.assert_allclose(np.mean([r[0] for r in rows]), want, rtol=1e-6)
    g_got = jax.tree.map(lambda *g: sum(g) / len(g), *[r[1] for r in rows])
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


def test_adamw_matches_the_program(program):
    from repro.optim import adamw_init, adamw_update

    c = small_cell(TRAIN).config
    o = c["deployment"]["optimizer"]
    w = weights.make(c, weights.key_for(7, 0))
    g = jax.tree.map(lambda x: x * 3.0, weights.make(c, weights.key_for(7, 1)))
    state = adamw_init(w)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    p_want, p_got = w, w
    for step in (1, 2):
        p_want, state = adamw_update(p_want, g, state, lr=o["lr"])
        p_got, m, v, _ = ref.adamw(p_got, g, m, v, step, o)
    for a, b in zip(jax.tree.leaves(p_got), jax.tree.leaves(p_want)):
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-6)
