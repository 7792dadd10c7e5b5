"""benchmark/flops.py against hand counts, and the table of peaks."""

import pytest

from benchmark import flops

RESNET50 = {"image_size": 224, "width": 64, "stage_sizes": [3, 4, 6, 3],
            "num_classes": 1000}
MISTRAL = {"hidden_size": 4096, "head_dim": 128, "num_attention_heads": 32,
           "num_key_value_heads": 8, "intermediate_size": 14336,
           "num_hidden_layers": 1, "vocab_size": 32768}


def test_resnet50_forward_is_about_4_1_g_multiply_adds():
    macs = flops.resnet_forward_macs(RESNET50)
    assert 4.05e9 < macs < 4.15e9
    # by hand: the stem is 112*112 outputs of a 7x7x3 window into 64 channels,
    # the head 2048 x 1000
    stem, head = 112 * 112 * 7 * 7 * 3 * 64, 2048 * 1000
    first_block = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert macs > stem + head + first_block
    assert flops.train_step_flops({"family": "resnet", "model": RESNET50},
                                  {"batch": 256}) == 3 * 2 * macs * 256


def test_one_mistral_layer_at_s8192_by_hand():
    S = 8192
    proj = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert flops.decoder_layer_matmul_params(MISTRAL) == proj
    full_attention = 2 * 2 * S * S * 128 * 32          # QK^T and PV, every pair
    assert flops.attention_forward_flops(S, 32, 128, causal=False) == full_attention
    assert flops.attention_forward_flops(S, 32, 128) == full_attention / 2
    forward = S * 2 * (proj + 4096 * 32768) + full_attention / 2
    assert flops.decoder_forward_flops(MISTRAL, 1, S) == forward
    # 4 layers, forward and backward: the issue's 6.85 GFLOP a token
    four = dict(MISTRAL, num_hidden_layers=4)
    per_token = flops.train_step_flops({"family": "decoder", "model": four},
                                       {"batch": 1, "seq_len": S}) / S
    assert per_token == pytest.approx(6.845e9, rel=1e-3)


def test_flash_calls_count_their_products_and_are_compute_bound():
    peak = flops.peaks("TPU v5 lite")
    unit = 2.0 * 8192 * 8192 * 128 * 32 / 2
    for kind, products in (("fwd", 2), ("dkv", 4), ("dq", 3)):
        ops, moved = flops.flash_call(kind, 1, 8192, 32, 8, 128)
        assert ops == products * unit and moved > 0
        assert flops.roofline_seconds(ops, moved, peak)[1] == "compute"
    assert flops.roofline_seconds(1.0, 1e9, peak)[1] == "memory"


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("cpu")
