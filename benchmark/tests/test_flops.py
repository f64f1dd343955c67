"""``flops.py`` against hand counts for both configurations."""

import json
import os

import pytest

from benchmark import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sizes(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_hand_counts():
    s = sizes("mistral-7b-v0.3")
    # q and o: 4096x4096 each; k and v: 4096x1024 each; three 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert flops.layer_matmul_params(s) == layer
    assert flops.head_params(s) == 32768 * 4096 == 134_217_728
    assert flops.weight_bytes(s) == 2 * (16 * layer + 2 * 134_217_728)
    # 64 KB of cache per token at depth 16: 2 (k, v) x 2 bytes x 16 x 8 x 128
    assert flops.kv_bytes_per_token(s) == 65_536
    assert flops.decode_kv_bytes(s, 1000) == 65_536 * 1000
    # one decoded token at context 1000 with its logits
    hand = 2 * 16 * layer + 4 * 16 * 32 * 128 * 1000 + 2 * 134_217_728
    assert flops.token_flops(s, 1000, logits=True) == pytest.approx(hand)


def test_deepseek_hand_counts():
    s = sizes("deepseek-llm-7b")
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert layer == 202_375_168
    assert flops.layer_matmul_params(s) == layer
    assert flops.head_params(s) == 102400 * 4096 == 419_430_400
    assert flops.kv_bytes_per_token(s) == 262_144  # 256 KB: four times Mistral's


@pytest.mark.parametrize("name", ["mistral-7b-v0.3", "deepseek-llm-7b"])
def test_prefill_is_the_sum_of_its_tokens(name):
    s = sizes(name)
    whole = flops.prefill_flops(s, 0, 300)
    by_token = sum(flops.token_flops(s, i + 1, logits=False) for i in range(300)) \
        + 2 * flops.head_params(s)
    assert whole == pytest.approx(by_token)
    # a cached prefix is attended to, not recomputed
    suffix = flops.prefill_flops(s, 200, 300)
    by_token = sum(flops.token_flops(s, i + 1, logits=False) for i in range(200, 300)) \
        + 2 * flops.head_params(s)
    assert suffix == pytest.approx(by_token)
    assert flops.prefill_flops(s, 300, 300) == 0.0
