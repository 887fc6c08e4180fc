"""Numbers the harness gave before its model code moved into
``bench/arch/dense_gqa.py``, which it must still give exactly: a checksum
of every leaf of ``weights.make`` and the reference's logits for a few
tokens, at the tests' small size (``conftest.small_config``) on the CPU
backend, and the counts at the published sizes (pure arithmetic)."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, flops, spec, weights
from bench.tests.conftest import small_config

SEEDS = {"smollm-360m": 11, "qwen2-0.5b": 12}
TOKENS = [3, 1, 4, 1, 5, 9, 2, 6]
PICKS = ((0, 0), (3, 17), (7, 511))

# sha256 of each leaf's bytes, first 16 hex digits
LEAVES = {
    "smollm-360m": {
        "embed": "0df5a9fde3cea85e",
        "final_norm": "1e085775ec57b3e7",
        "g0/ln1": "f8f4ec1ba294cc5d",
        "g0/ln2": "0f56ea72778a8f9e",
        "g0/w1": "81cf52c2b7dc7f4d",
        "g0/w2": "e0e5254e474a0a10",
        "g0/w3": "71d63795b3d4c276",
        "g0/wk": "6eed4be8124af417",
        "g0/wo": "16f1c8a46ae70ea3",
        "g0/wq": "385f37853e9a4189",
        "g0/wv": "09b95f1e060caa14"
    },
    "qwen2-0.5b": {
        "embed": "aecbdb89d8a9d7f6",
        "final_norm": "cfae301a80f70d38",
        "g0/bk": "7696514e1024c50c",
        "g0/bq": "628eb3c289d3fbe1",
        "g0/bv": "5267534213aac3f2",
        "g0/ln1": "f940f21b109594ae",
        "g0/ln2": "5af186328fa00aba",
        "g0/w1": "de25e3b8f2f3bb41",
        "g0/w2": "779be315e2fa1392",
        "g0/w3": "109cf72c0fa289bd",
        "g0/wk": "39a2f923e7ef996d",
        "g0/wo": "6f23eb49cf782c09",
        "g0/wq": "5ebc6d4e7e8e123d",
        "g0/wv": "94f4bdd2a22e638b"
    }
}

LOGITS = {
    "smollm-360m": {
        "sha": "bb5a472025610b4a",
        "picks": [
            0.1270165592432022,
            0.33363109827041626,
            -0.030886700376868248
        ]
    },
    "qwen2-0.5b": {
        "sha": "63ad9a7c9ae9d51b",
        "picks": [
            0.13784879446029663,
            -0.011055088602006435,
            0.2808043956756592
        ]
    }
}

TRAIN_FLOPS = {
    "smollm-360m": {
        256: 2265298560.0,
        2048: 2925901440.0
    },
    "qwen2-0.5b": {
        256: 3030256896.0,
        2048: 3492678912.0
    }
}

DECODE = [
    ([], {"flops": 0, "bytes": 1976131072.0}),
    ([0], {"flops": 988008448.0, "bytes": 1976180224.0}),
    ([0, 9], {"flops": 1976791040.0, "bytes": 1976450560.0}),
    (list(range(0, 2048, 32)), {"flops": 68781604864.0, "bytes": 3564723712.0}),
]



def config(name):
    return spec.load_json(f"{spec.BENCH}/configs/{name}.json")


def sha(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module", params=sorted(SEEDS))
def small(request):
    name = request.param
    c = small_config(config(name))
    return name, c, weights.make(c, weights.key_for(SEEDS[name], 0))


def test_weight_leaves(small):
    name, _, w = small
    flat, _ = jax.tree_util.tree_flatten_with_path(w)
    got = {"/".join(str(k.key) for k in p): sha(x) for p, x in flat}
    assert got == LEAVES[name]


def test_reference_logits(small):
    name, c, w = small
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(arch.of(c).reference.logits(
            c, w, jnp.asarray(TOKENS, jnp.int32)))
    assert [float(lg[i, j]) for i, j in PICKS] == LOGITS[name]["picks"]
    assert sha(lg) == LOGITS[name]["sha"]


@pytest.mark.parametrize("name", sorted(SEEDS))
def test_train_flops_at_published_sizes(name):
    c = config(name)
    assert {s: flops.train_flops_per_token(c, s) for s in (256, 2048)} == \
        TRAIN_FLOPS[name]


@pytest.mark.parametrize("positions,want", DECODE)
def test_decode_step_at_published_sizes(positions, want):
    assert flops.decode_step(config("qwen2-0.5b"), positions) == want
