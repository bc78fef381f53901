"""``benchmark/flops.py`` against counts made by hand."""
import pytest

from benchmark import flops

OPT = {"program": {"vocab": 50272, "d_model": 2048, "n_heads": 32, "n_layers": 8,
                   "d_ff": 8192, "max_len": 2048, "dtype": "bfloat16"}}
RESNET50 = {"network": {"num_layers": 50, "image_shape": [3, 224, 224],
                        "num_classes": 1000}}


def test_opt_layer_has_50_3_million_matrix_parameters():
    assert flops.lm_layer_matrix_params(OPT["program"]) == 4 * 2048 ** 2 + 2 * 2048 * 8192
    assert flops.lm_layer_matrix_params(OPT["program"]) == 50_331_648


def test_opt_train_flops_per_token_at_depth_8():
    # 8 layers + the tied head: 505.6 M matrix parameters; attention adds
    # 6 * S * d * L = 0.2 GFLOP a token at S = 2048
    per_token = flops.lm_train_flops_per_token(OPT, {"seq_len": 2048})
    assert flops.lm_matrix_params(OPT["program"]) == 8 * 50_331_648 + 50272 * 2048
    assert per_token == 6 * 505_610_240 + 6 * 2048 * 2048 * 8
    assert per_token == pytest.approx(3.235e9, rel=1e-3)


def test_flash_attention_cost_is_compute_bound_at_the_cells_shape():
    cost = flops.flash_attention_train(OPT, {"batch": 2, "seq_len": 2048})
    # 7 products of 2 * S^2 * dh / 2 operations a head, 64 heads in the batch
    assert cost["flops"] == 64 * 7 * 2048 * 2048 * 64
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9


def test_resnet50_forward_is_4_1_billion_multiply_adds():
    macs = flops.resnet_forward_macs(RESNET50["network"])
    assert macs == pytest.approx(4.1e9, rel=0.02)
    # the stem alone: 112 * 112 * 3 * 64 * 49
    assert flops._conv_macs(224, 224, 3, 64, 7, 2)[0] == 118_013_952
    assert flops.resnet_train_flops_per_image(RESNET50, {}) == 6 * macs
