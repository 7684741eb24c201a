"""Operations and bytes against hand-worked numbers."""
import pytest

from benchmark import rooflines

B560 = {"vocab_size": 250880, "hidden_size": 1024, "n_layer": 24,
        "n_head": 16}


def test_matmul_parameters_of_bloom_560m():
    blocks = 12 * 24 * 1024 * 1024             # 301,989,888
    head = 250880 * 1024                       # 256,901,120
    assert rooflines.matmul_params(B560) == blocks + head == 558_891_008
    assert head / (blocks + head) == pytest.approx(0.46, abs=0.005)
    # every parameter, biases and layer norms too: HF counts 559,214,592
    assert rooflines.all_params(B560) == 559_214_592


def test_training_flops_per_token():
    # 6 x 558,891,008 + causal attention 6 x 2048 x 1024 x 24
    want = 3_353_346_048 + 301_989_888
    assert rooflines.train_flops_per_token(B560, 2048) == want


def test_flash_forward_call_is_compute_bound_at_cell_shapes():
    flops, nbytes = rooflines.flash_call_cost("fwd", 8, 2048, 16, 64)
    assert flops == 2 * 2 * 8 * 16 * 2048 * 2048 * 64 / 2    # 68.7 GFLOP
    assert nbytes == 4 * 8 * 2048 * 16 * 64 * 2 + 8 * 2048 * 16 * 4
    peaks = rooflines.peaks_for("TPU v5 lite")
    t, bound = rooflines.least_time_s(flops, nbytes, peaks)
    assert bound == "compute" and t == pytest.approx(flops / 197e12)
    assert rooflines.flash_call_cost("dkv", 8, 2048, 16, 64)[0] == 2 * flops


def test_decode_step_bytes():
    # weights in bf16 plus 2 x 24 x 1024 x 2 bytes = 98,304 a live token
    assert rooflines.decode_step_bytes(B560, 1000) == (
        559_214_592 * 2 + 1000 * 98_304)


def test_unknown_device_kind_is_an_error():
    assert rooflines.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        rooflines.peaks_for("cpu")
