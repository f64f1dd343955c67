"""The Qwen3-Next operation counts against the published arithmetic of
its configuration (ISSUE 30's reckoning: per layer outside the routed
experts 33.7 M in a DeltaNet mixer, 27.3 M in an attention mixer; one
routed expert 3.146 M; 7.34 GB in all on this chip)."""

import json
import os

from benchmark import flops_qwen3_next as fl

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs", "qwen3-next-80b-a3b.json")) as f:
    SIZES = json.load(f)


def test_layer_parameters_are_the_published_ones():
    assert round(fl.delta_net_params(SIZES) / 1e6, 1) == 33.7
    assert round(fl.attention_params(SIZES) / 1e6, 1) == 27.3
    assert fl.expert_params(SIZES) == 3 * 2048 * 512
    assert fl.layers(SIZES) == (6, 2)
    held = SIZES["num_experts"] * fl.expert_params(SIZES)
    other = 2048 * 512 + 3 * 2048 * 512 + 2048
    total = (6 * fl.delta_net_params(SIZES) + 2 * fl.attention_params(SIZES)
             + 8 * (held + other) + 2 * fl.head_params(SIZES))
    assert abs(2 * total / 1e9 - 7.34) < 0.01


def test_a_token_meets_a_quarter_of_its_ten_experts():
    met = fl.expert_layer_params_met(SIZES)
    assert abs(met - (2048 * 512 + 3 * 2048 * 512 + 2048
                      + 2.5 * fl.expert_params(SIZES))) < 1
    # a decoded token at 1,000 positions: about 0.9 GFLOP
    per_token = fl.token_flops(SIZES, 1000, logits=True)
    assert 0.8e9 < per_token < 1.0e9
    # attention grows with the context in the 2 attention layers alone
    grow = fl.token_flops(SIZES, 2000, logits=True) - per_token
    assert grow == 4.0 * 2 * 16 * 256 * 1000


def test_a_prompt_is_its_tokens_plus_one_logit():
    n = 512
    each = fl.token_flops(SIZES, 0, logits=False)
    want = (each * n + 4.0 * 2 * 16 * 256 * n * (n + 1) / 2
            + 2.0 * fl.head_params(SIZES))
    assert fl.prefill_flops(SIZES, n) == want
    assert fl.prefill_flops(SIZES, 0) == 0.0
