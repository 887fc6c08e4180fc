"""A configuration's ``"arch"`` alone brings the harness to its
architecture's module: weights, counts and the program's configuration
follow a toy architecture kept in ``data/toy_arch.py``; an unknown one is
an error that names the known ones."""
import importlib.util
import os
import sys

import numpy as np
import pytest

from bench import arch, flops, spec, system, weights

TOY = os.path.join(os.path.dirname(__file__), "data", "toy_arch.py")


@pytest.fixture
def toy(monkeypatch):
    spec = importlib.util.spec_from_file_location("bench.arch.toy", TOY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setitem(sys.modules, "bench.arch.toy", mod)
    return {"name": "toy-1", "arch": "toy", "hidden_size": 4, "vocab_size": 6}


def test_weights_follow_the_architecture(toy):
    w = weights.make(toy, weights.key_for(1, 0))
    assert sorted(w) == ["embed", "mix"] and w["embed"].shape == (6, 4)
    np.testing.assert_array_equal(w["mix"]["w"], np.full((4, 4), 3.0))
    np.testing.assert_array_equal(w["embed"], np.full((6, 4), 2.0))
    assert weights.n_params(toy) == 6 * 4 + 4 * 4


def test_counts_follow_the_architecture(toy):
    assert flops.train_flops_per_token(toy, 64) == 64_000.0
    assert flops.decode_step(toy, [3, 4]) == {"flops": 10.0, "bytes": 18.0}
    assert flops.matmul_params(toy) == 16 and flops.kv_entry_bytes(toy) == 7


def test_program_config_follows_the_architecture(toy):
    cfg = system.model_config(toy)
    assert (cfg.family, cfg.arch_id, cfg.d) == ("toy", "toy-1", 4)


@pytest.mark.parametrize("name", ["smollm-360m", "qwen2-0.5b"])
def test_the_configurations_name_their_architecture(name):
    c = spec.load_json(os.path.join(spec.BENCH, "configs", name + ".json"))
    assert c["arch"] == "dense_gqa"
    assert arch.of(c).__name__ == "bench.arch.dense_gqa"


@pytest.mark.parametrize("name", ["no_such_arch", None])
def test_unknown_arch_names_the_known_ones(name):
    with pytest.raises(KeyError, match="dense_gqa"):
        flops.decode_step({"name": "x", "arch": name}, [0])
