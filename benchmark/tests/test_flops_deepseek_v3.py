"""The DeepSeek-V3 operation and byte counts against the published
arithmetic of GigaChat3.1's configuration, reckoned by hand in this
repository's bytes: an attention layer 132.58 M, the shared expert and a
routed expert 44.04 M each, the router 1.84 M; an expert layer on this
chip 178.45 M + 16 x 44.04 M = 883.1 M, the dense layer 529.0 M, an
eighth of the vocabulary in the embedding and the head 229.8 M; 4,291 M
in all, 8.58 GB; the latent cache 5,760 bytes a token, 3.02 GB at 64
slots of 8,192."""

import json
import os

from benchmark import flops_deepseek_v3 as fl

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "gigachat3.1-702b-a36b.json")) as f:
    SIZES = json.load(f)


def _m(n):
    return round(n / 1e6, 2)


def test_layer_parameters_are_the_reckoned_ones():
    assert _m(fl.attention_params(SIZES)) == 132.58
    assert _m(fl.expert_params(SIZES)) == _m(fl.shared_params(SIZES)) == 44.04
    assert _m(fl.router_params(SIZES)) == 1.84
    assert round(fl.expert_layer_params_held(SIZES) / 1e6, 1) == 883.1
    dense = fl.attention_params(SIZES) + fl.dense_ffn_params(SIZES)
    assert round(dense / 1e6) == 529
    assert round(2 * fl.head_params(SIZES) / 1e6, 1) == 229.8
    assert fl.layers(SIZES) == (1, 4)
    assert round(fl.params_held(SIZES) / 1e6) == 4291
    assert abs(2 * fl.params_held(SIZES) / 1e9 - 8.58) < 0.01


def test_latent_bytes_are_a_576_value_row_a_layer():
    assert fl.latent_width(SIZES) == 576
    assert fl.latent_bytes(SIZES, 1) == 5 * 576 * 2 == 5760
    cache = 64 * fl.latent_bytes(SIZES, 8192)
    assert abs(cache / 1e9 - 3.02) < 0.01


def test_a_token_meets_a_sixteenth_of_its_eight_experts():
    met = fl.expert_ffn_params_met(SIZES)
    assert abs(met - (7168 * 256 + 3 * 7168 * 2048
                      + 0.5 * fl.expert_params(SIZES))) < 1
    # absorbed attention: 64 heads score 576 values and read back 512,
    # about 121 FLOPs a byte of the row
    per_layer = fl.absorbed_attention_flops(SIZES, 1000)
    assert per_layer == 64 * 1000 * (2 * 576 + 2 * 512)
    intensity = per_layer * 5 / fl.latent_bytes(SIZES, 1000)
    assert 120 < intensity < 122
    grow = (fl.token_flops(SIZES, 2000, logits=True)
            - fl.token_flops(SIZES, 1000, logits=True))
    assert grow == 5 * per_layer


def test_a_prompt_is_its_tokens_expanded_plus_one_logit():
    n = 512
    want = (fl.weight_flops_per_token(SIZES) * n
            + 5 * 2.0 * 64 * (192 + 192) * n * (n + 1) / 2
            + 2.0 * fl.head_params(SIZES))
    assert fl.prefill_flops(SIZES, n) == want
    assert fl.prefill_flops(SIZES, 0) == 0.0
