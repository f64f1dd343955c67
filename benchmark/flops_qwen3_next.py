"""Operations and bytes that the Qwen3-Next block needs, from shapes
alone (``benchmark/flops.py`` is the dense Llama block's). Part of the
yardstick. ``sizes`` is a configuration file's keys: ``num_experts`` the
routed experts held here, ``router_width`` the experts the router ranks.
"""

from __future__ import annotations


def delta_net_params(sizes: dict) -> int:
    """Weights of one Gated DeltaNet mixer that a token is multiplied by."""
    D = sizes["hidden_size"]
    kd = sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
    vd = sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"]
    return (D * (2 * kd + 2 * vd) + D * 2 * sizes["linear_num_value_heads"]
            + vd * D + sizes["linear_conv_kernel_dim"] * (2 * kd + vd))


def attention_params(sizes: dict) -> int:
    """Weights of one gated attention mixer (q carries its gate)."""
    D, hd = sizes["hidden_size"], sizes["head_dim"]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return D * H * 2 * hd + 2 * D * KV * hd + H * hd * D


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_layer_params_met(sizes: dict) -> float:
    """Weights of one expert layer that a token meets on this chip: the
    router, the shared expert and its gate, and of its ``top_k`` routed
    experts the held share in expectation (``held / router_width``)."""
    D = sizes["hidden_size"]
    share = sizes["num_experts"] / sizes["router_width"]
    return (D * sizes["router_width"]
            + 3 * D * sizes["shared_expert_intermediate_size"] + D
            + sizes["num_experts_per_tok"] * share * expert_params(sizes))


def head_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["vocab_size"]


def layers(sizes: dict) -> tuple:
    """(DeltaNet layers, attention layers) of the stack."""
    periods = sizes["num_hidden_layers"] // sizes["full_attention_interval"]
    return periods * (sizes["full_attention_interval"] - 1), periods


def state_flops_per_token(sizes: dict) -> float:
    """One DeltaNet layer's recurrent update for one token: 6 FLOPs an
    element of the state (the decay, the read for the correction, the
    rank-one update, the read for the output), the count ISSUE 30 fixed."""
    return 6.0 * (sizes["linear_num_value_heads"]
                  * sizes["linear_key_head_dim"]
                  * sizes["linear_value_head_dim"])


def token_flops(sizes: dict, context: int, *, logits: bool) -> float:
    """FLOPs to process one token that attends to ``context`` positions in
    the attention layers: 2 per weight met, the state update in the
    DeltaNet layers, 4 per head dimension and attended position in the
    attention layers, and the output head where a logit is needed."""
    n_lin, n_attn = layers(sizes)
    f = 2.0 * (n_lin * delta_net_params(sizes)
               + n_attn * attention_params(sizes)
               + (n_lin + n_attn) * expert_layer_params_met(sizes))
    f += n_lin * state_flops_per_token(sizes)
    f += (4.0 * n_attn * sizes["num_attention_heads"] * sizes["head_dim"]
          * context)
    if logits:
        f += 2.0 * head_params(sizes)
    return f


def prefill_flops(sizes: dict, n: int) -> float:
    """A prompt of ``n`` tokens from an empty state; one logit at its end."""
    if n <= 0:
        return 0.0
    _, n_attn = layers(sizes)
    per_token = token_flops(sizes, 0, logits=False)
    contexts = n * (n + 1) / 2.0
    return (per_token * n
            + 4.0 * n_attn * sizes["num_attention_heads"] * sizes["head_dim"]
            * contexts + 2.0 * head_params(sizes))
