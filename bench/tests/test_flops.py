"""bench/flops.py against counts made by hand from the published sizes."""
import pytest

from bench import flops, peaks, spec, weights


def cfg(name):
    return spec.load_json(f"{spec.BENCH}/configs/{name}.json")


def test_smollm_parameters_and_train_flops():
    c = cfg("smollm-360m")
    # embed 49152x960; per layer: two norms, q and o 960x960, k and v
    # 960x320, three MLP matrices 960x2560; final norm
    per_layer = 2 * 960 + 2 * 921_600 + 2 * 307_200 + 3 * 2_457_600
    assert per_layer == 9_832_320
    n = 47_185_920 + 960 + 32 * per_layer
    assert weights.n_params(c) == n == 361_821_120
    assert flops.train_flops_per_token(c, 2048) == 6 * n + 12 * 32 * 960 * 2048
    assert flops.train_flops_per_token(c, 2048) == 2_925_901_440
    assert flops.train_flops_per_token(c, 256) == 6 * n + 94_371_840


def test_qwen_parameters_and_decode_step():
    c = cfg("qwen2-0.5b")
    # per layer: q and o 896x896, k and v 896x128, biases 896+128+128,
    # two norms, three MLP matrices 896x4864
    mm = 2 * 802_816 + 2 * 114_688 + 3 * 4_358_144
    per_layer = mm + 896 + 128 + 128 + 2 * 896
    n = 151_936 * 896 + 896 + 24 * per_layer
    assert weights.n_params(c) == n == 494_032_768
    assert flops.matmul_params(c) == 24 * mm + 151_936 * 896 == 493_961_216
    kv = 24 * 2 * 2 * 64 * 4
    assert flops.kv_entry_bytes(c) == kv == 24_576
    w = flops.decode_step(c, [0, 9])
    assert w["flops"] == 2 * (2 * 493_961_216) + 4 * 24 * 896 * (1 + 10)
    assert w["bytes"] == 4 * n + (1 + 10) * kv + 2 * kv
    assert flops.decode_step(c, [])["bytes"] == 4 * n


def test_least_time_picks_the_larger_bound():
    p = peaks.peaks("TPU v5 lite")
    assert flops.least_seconds({"flops": 197e12, "bytes": 1.0}, p) == {
        "seconds": 1.0, "bound": "compute"}
    assert flops.least_seconds({"flops": 1.0, "bytes": 819e9 * 2}, p) == {
        "seconds": 2.0, "bound": "memory"}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
