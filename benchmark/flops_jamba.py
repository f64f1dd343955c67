"""Operations and bytes that the Jamba block needs, from shapes alone
(``benchmark/flops.py`` is the dense Llama block's, ``flops_qwen3_next.py``
Qwen3-Next's). Part of the yardstick. ``sizes`` is a configuration file's
keys. Bytes are the least a step can move, never what a program happens to
move.
"""

from __future__ import annotations

STATE_ITEMSIZE = 4      # the recurrent state is float32 (``assumed``)
ITEMSIZE = 2            # weights, windows, keys and values: bfloat16


def d_inner(sizes: dict) -> int:
    return sizes["mamba_expand"] * sizes["hidden_size"]


def layers(sizes: dict) -> tuple:
    """(Mamba layers, attention layers) of the stack."""
    n_attn = sum(1 for i in range(sizes["num_hidden_layers"])
                 if i % sizes["attn_layer_period"]
                 == sizes["attn_layer_offset"])
    return sizes["num_hidden_layers"] - n_attn, n_attn


def mamba_matmul_params(sizes: dict) -> int:
    """Weights of one Mamba mixer that a token is multiplied by: in_proj,
    the convolution's taps, x_proj, dt_proj, out_proj."""
    D, Di = sizes["hidden_size"], d_inner(sizes)
    R, N = sizes["mamba_dt_rank"], sizes["mamba_d_state"]
    return (D * 2 * Di + sizes["mamba_d_conv"] * Di + Di * (R + 2 * N)
            + R * Di + Di * D)


def mamba_params(sizes: dict) -> int:
    """Every parameter of one Mamba mixer: the above, the convolution's
    and dt's biases, ``A_log``, ``D`` and the three small norms."""
    Di = d_inner(sizes)
    R, N = sizes["mamba_dt_rank"], sizes["mamba_d_state"]
    return mamba_matmul_params(sizes) + 2 * Di + Di * N + Di + R + 2 * N


def attention_params(sizes: dict) -> int:
    D, hd = sizes["hidden_size"], sizes["head_dim"]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * D * H * hd + 2 * D * KV * hd


def mlp_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def head_params(sizes: dict) -> int:
    """The embedding, which is also the head (``tie_word_embeddings``)."""
    return sizes["hidden_size"] * sizes["vocab_size"]


def total_params(sizes: dict) -> int:
    n_mamba, n_attn = layers(sizes)
    norms = 2 * sizes["hidden_size"]  # a layer's two
    return (n_mamba * (mamba_params(sizes) + mlp_params(sizes) + norms)
            + n_attn * (attention_params(sizes) + mlp_params(sizes) + norms)
            + head_params(sizes) + sizes["hidden_size"])


def state_flops_per_token(sizes: dict) -> float:
    """One Mamba layer's recurrence for one token: 7 operations an element
    of the ``d_inner x N`` state, as the equations spell them out
    (``dt A``, its ``exp``, the decay's product with ``S``, ``(dt c) B``,
    the sum, and ``S C``'s product and sum)."""
    return 7.0 * d_inner(sizes) * sizes["mamba_d_state"]


def _attend_flops(sizes: dict, contexts: float) -> float:
    n_attn = layers(sizes)[1]
    return (4.0 * n_attn * sizes["num_attention_heads"] * sizes["head_dim"]
            * contexts)


def token_flops(sizes: dict, context: int, *, logits: bool) -> float:
    """FLOPs to process one token that attends to ``context`` positions in
    the attention layers: 2 per weight met, the recurrence in the Mamba
    layers, 4 per head dimension and attended position in the attention
    layers, and the tied head where a logit is needed."""
    n_mamba, n_attn = layers(sizes)
    f = 2.0 * (n_mamba * mamba_matmul_params(sizes)
               + n_attn * attention_params(sizes)
               + (n_mamba + n_attn) * mlp_params(sizes))
    f += n_mamba * state_flops_per_token(sizes)
    f += _attend_flops(sizes, context)
    if logits:
        f += 2.0 * head_params(sizes)
    return f


def prefill_flops(sizes: dict, n: int) -> float:
    """A prompt of ``n`` tokens from an empty state; one logit at its end."""
    if n <= 0:
        return 0.0
    return (token_flops(sizes, 0, logits=False) * n
            + _attend_flops(sizes, n * (n + 1) / 2.0)
            + 2.0 * head_params(sizes))


def weight_bytes(sizes: dict) -> int:
    """The weights once: what one decode step reads of them whatever its
    rows (the embedding is read as the head)."""
    return ITEMSIZE * total_params(sizes)


def state_bytes_per_row(sizes: dict) -> int:
    """One slot's recurrent state and convolution windows, read and
    written once: what a decode step moves of them for one row."""
    n_mamba = layers(sizes)[0]
    Di = d_inner(sizes)
    state = Di * sizes["mamba_d_state"] * STATE_ITEMSIZE
    window = (sizes["mamba_d_conv"] - 1) * Di * ITEMSIZE
    return 2 * n_mamba * (state + window)


def decode_kv_bytes(sizes: dict, context: int) -> int:
    """Keys and values one decoded token reads at ``context`` positions."""
    return (layers(sizes)[1] * 2 * sizes["num_key_value_heads"]
            * sizes["head_dim"] * ITEMSIZE * context)


def decode_bytes(sizes: dict, steps: int, contexts: list) -> float:
    """The least HBM traffic of ``steps`` decode steps that delivered one
    token at each of ``contexts``: the weights once a step; state, windows
    and that context's keys and values once a token."""
    return (steps * weight_bytes(sizes)
            + len(contexts) * state_bytes_per_row(sizes)
            + sum(decode_kv_bytes(sizes, ctx) for ctx in contexts))
