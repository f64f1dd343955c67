"""Operations and bytes that the DeepSeek-V3 block needs, from shapes
alone (``benchmark/flops.py`` is the dense Llama block's). Part of the
yardstick. ``sizes`` is a configuration file's keys: ``n_routed_experts``
the routed experts held here, ``router_width`` the experts the router
ranks.

Attention is counted as the served algorithm computes it: a prompt
expanded (every head's key and value made from the latent rows, scores
and values ``d_nope + d_rope`` and ``d_v`` wide), a decoded token absorbed
(every head scores the 576-value row, ``[c_kv | k_pe]``, and reads back
its 512-value ``c_kv``). Either way every projection weight is met once a
token.
"""

from __future__ import annotations


def latent_width(sizes: dict) -> int:
    """Values of a latent cache row: ``[c_kv | k_pe]``."""
    return sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]


def attention_params(sizes: dict) -> int:
    """One latent attention layer: ``W_dq``, ``W_uq``, ``W_dkv``,
    ``W_ukv`` (``W_uk`` and ``W_uv``), ``W_o``."""
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    return (D * rq + rq * H * (dn + dr) + D * (rkv + dr)
            + rkv * H * (dn + dv) + H * dv * D)


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def shared_params(sizes: dict) -> int:
    return sizes["n_shared_experts"] * expert_params(sizes)


def router_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["router_width"]


def dense_ffn_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def expert_layer_params_held(sizes: dict) -> int:
    """What one expert layer keeps on this chip: attention, router, shared
    expert and the held experts."""
    return (attention_params(sizes) + router_params(sizes)
            + shared_params(sizes)
            + sizes["n_routed_experts"] * expert_params(sizes))


def expert_ffn_params_met(sizes: dict) -> float:
    """Feed-forward weights of one expert layer that a token meets on this
    chip: the router, the shared expert, and of its ``top_k`` routed
    experts the held share in expectation (``held / router_width``)."""
    share = sizes["n_routed_experts"] / sizes["router_width"]
    return (router_params(sizes) + shared_params(sizes)
            + sizes["num_experts_per_tok"] * share * expert_params(sizes))


def head_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["vocab_size"]


def layers(sizes: dict) -> tuple:
    """(dense layers, expert layers) of the stack."""
    dense = sizes["first_k_dense_replace"]
    return dense, sizes["num_hidden_layers"] - dense


def params_held(sizes: dict) -> int:
    """Every weight on this chip: the stack, the embedding and the head."""
    n_dense, n_expert = layers(sizes)
    return (n_dense * (attention_params(sizes) + dense_ffn_params(sizes))
            + n_expert * expert_layer_params_held(sizes)
            + 2 * head_params(sizes))


def latent_bytes(sizes: dict, context: int) -> float:
    """Latent rows one decoded token's attention reads in all layers at
    ``context`` positions: ``[c_kv | k_pe]`` each, in the served dtype."""
    itemsize = 2 if sizes["torch_dtype"] in ("bfloat16", "float16") else 4
    return (sizes["num_hidden_layers"] * context * latent_width(sizes)
            * itemsize)


def absorbed_attention_flops(sizes: dict, context: int) -> float:
    """One layer's absorbed attention for one decoded token: every head
    scores ``context`` rows of ``latent_width`` values and reads back
    ``kv_lora_rank`` of each."""
    return (sizes["num_attention_heads"] * context
            * (2.0 * latent_width(sizes) + 2.0 * sizes["kv_lora_rank"]))


def expanded_attention_flops(sizes: dict, context: int) -> float:
    """One layer's expanded attention for one prompt token over
    ``context`` positions: scores ``d_nope + d_rope`` wide, values
    ``d_v`` wide."""
    return (2.0 * sizes["num_attention_heads"] * context
            * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
               + sizes["v_head_dim"]))


def weight_flops_per_token(sizes: dict) -> float:
    """2 per weight a token meets in the stack."""
    n_dense, n_expert = layers(sizes)
    return 2.0 * (sizes["num_hidden_layers"] * attention_params(sizes)
                  + n_dense * dense_ffn_params(sizes)
                  + n_expert * expert_ffn_params_met(sizes))


def token_flops(sizes: dict, context: int, *, logits: bool) -> float:
    """FLOPs to decode one token that attends to ``context`` positions,
    and the output head where a logit is needed."""
    f = weight_flops_per_token(sizes)
    f += sizes["num_hidden_layers"] * absorbed_attention_flops(sizes, context)
    if logits:
        f += 2.0 * head_params(sizes)
    return f


def prefill_flops(sizes: dict, n: int) -> float:
    """A prompt of ``n`` tokens from an empty cache; one logit at its end.
    Causal: token ``t`` attends to ``t + 1`` positions."""
    if n <= 0:
        return 0.0
    contexts = n * (n + 1) / 2.0
    return (weight_flops_per_token(sizes) * n
            + sizes["num_hidden_layers"]
            * expanded_attention_flops(sizes, 1) * contexts
            + 2.0 * head_params(sizes))
