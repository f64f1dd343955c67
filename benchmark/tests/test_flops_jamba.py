"""The Jamba operation and byte counts against the published arithmetic of
its configuration (ISSUE 34's reckoning: 41.24 M in a Mamba mixer, 13.76 M
in an attention mixer, 62.91 M in an MLP, 3,029 M parameters and 6.06 GB
in all; 18.64 MB of state and windows a row a step) and against a hand
count at toy size."""

import json
import os

from benchmark import flops_jamba as fl

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs", "ai21-jamba2-3b.json")) as f:
    SIZES = json.load(f)
with open(os.path.join(HERE, "toy_jamba", "configs", "toy-jamba.json")) as f:
    TOY = json.load(f)


def test_the_configuration_holds_the_catalog_row_uncut():
    row = {"attn_layer_offset": 7, "attn_layer_period": 14,
           "expert_layer_offset": 1, "expert_layer_period": 2,
           "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 8192, "mamba_conv_bias": True,
           "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
           "mamba_expand": 2, "mamba_proj_bias": False,
           "max_position_embeddings": 262144, "model_type": "jamba",
           "num_attention_heads": 20, "num_experts": 1,
           "num_experts_per_tok": 1, "num_hidden_layers": 28,
           "num_key_value_heads": 1, "num_logits_to_keep": 1,
           "rms_norm_eps": 1e-06, "sliding_window": None,
           "tie_word_embeddings": True, "use_mamba_kernels": True,
           "vocab_size": 65536}
    assert {key: SIZES[key] for key in row} == row
    assert SIZES["reduced"] == [] and "published" not in SIZES
    assert SIZES["head_dim"] == 128 and "head_dim" in SIZES["assumed"]
    assert SIZES["deployment"].startswith("one chip holds the whole model")


def test_layer_parameters_are_the_published_ones():
    assert fl.layers(SIZES) == (26, 2)
    assert fl.mamba_params(SIZES) == 41_241_792
    assert fl.attention_params(SIZES) == 13_762_560
    assert fl.mlp_params(SIZES) == 62_914_560
    assert fl.head_params(SIZES) == 167_772_160
    total = fl.total_params(SIZES)
    assert total // 10**6 == 3029
    assert abs(fl.weight_bytes(SIZES) / 1e9 - 6.06) < 0.005


def test_a_step_at_256_rows_moves_eleven_gigabytes():
    assert abs(fl.state_bytes_per_row(SIZES) / 1e6 - 18.64) < 0.005
    assert fl.decode_kv_bytes(SIZES, 1000) == 1_024_000   # 1 KB a token
    step = fl.decode_bytes(SIZES, 1, [0] * 256)
    assert abs(step / 1e9 - 10.83) < 0.01
    # state and windows are 44 % of the step's bytes, 13.2 ms at 819 GB/s
    assert abs(256 * fl.state_bytes_per_row(SIZES) / step - 0.44) < 0.005
    assert abs(step / 819e9 * 1e3 - 13.2) < 0.05
    # at the registered cell's 128 rows: 28 % of 8.45 GB, 10.3 ms
    step = fl.decode_bytes(SIZES, 1, [0] * 128)
    assert abs(128 * fl.state_bytes_per_row(SIZES) / step - 0.28) < 0.005
    assert abs(step / 819e9 * 1e3 - 10.3) < 0.05


def test_a_decoded_token_is_six_gigaflops():
    per_token = fl.token_flops(SIZES, 1000, logits=True)
    weights = 2.0 * (fl.total_params(SIZES) - 26 * (
        fl.mamba_params(SIZES) - fl.mamba_matmul_params(SIZES))
        - 28 * 2 * 2560 - 2560)
    state = 26 * 7.0 * 5120 * 16
    attend = 4.0 * 2 * 20 * 128 * 1000
    assert per_token == weights + state + attend
    assert 6.0e9 < per_token < 6.2e9
    grow = fl.token_flops(SIZES, 2000, logits=True) - per_token
    assert grow == attend


def test_toy_counts_against_a_hand_count():
    """6 layers, attention at 1 and 4: D 64, Di 128, N 8, R 6, K 4, F 96,
    4 heads of 16 on one KV head, 256 rows of vocabulary."""
    assert fl.layers(TOY) == (4, 2)
    mixer = 64 * 256 + 4 * 128 + 128 * (6 + 16) + 6 * 128 + 128 * 64
    assert fl.mamba_matmul_params(TOY) == mixer
    assert fl.mamba_params(TOY) == mixer + 2 * 128 + 128 * 8 + 128 + 6 + 16
    attn = 2 * 64 * 64 + 2 * 64 * 16
    assert fl.attention_params(TOY) == attn
    mlp = 3 * 64 * 96
    n = 10
    each = 2.0 * (4 * mixer + 2 * attn + 6 * mlp) + 4 * 7.0 * 128 * 8
    want = (each * n + 4.0 * 2 * 4 * 16 * n * (n + 1) / 2
            + 2.0 * 64 * 256)
    assert fl.prefill_flops(TOY, n) == want
    assert fl.prefill_flops(TOY, 0) == 0.0
    assert fl.token_flops(TOY, 7, logits=True) == (
        each + 4.0 * 2 * 4 * 16 * 7 + 2.0 * 64 * 256)
    assert fl.state_bytes_per_row(TOY) == 2 * 4 * (128 * 8 * 4 + 3 * 128 * 2)
    assert fl.decode_bytes(TOY, 3, [5, 9]) == (
        3 * fl.weight_bytes(TOY) + 2 * fl.state_bytes_per_row(TOY)
        + 2 * 2 * 16 * 2 * (5 + 9))
