"""Operations and bytes that the algorithm needs, from shapes alone.

Part of the yardstick: every PR's utilisation and roofline shares are
computed with these functions, whatever the program does inside.
``sizes`` is a configuration file's keys (HF names).
"""

from __future__ import annotations


def layer_matmul_params(sizes: dict) -> int:
    """Weights of one decoder layer that a token is multiplied by."""
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    H, KV, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def head_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["vocab_size"]


def weight_bytes(sizes: dict, bytes_per: int = 2) -> int:
    """All matmul weights and the embedding table (norms left out)."""
    L = sizes["num_hidden_layers"]
    return bytes_per * (L * layer_matmul_params(sizes)
                        + 2 * head_params(sizes))


def token_flops(sizes: dict, context: int, *, logits: bool) -> float:
    """FLOPs to process one token that attends to ``context`` positions
    (itself included): 2 per weight in the layers, 4 per head dimension
    and attended position (scores and weighted values), and the output
    head where a logit is needed."""
    L = sizes["num_hidden_layers"]
    H, hd = sizes["num_attention_heads"], sizes["head_dim"]
    f = 2.0 * L * layer_matmul_params(sizes) + 4.0 * L * H * hd * context
    if logits:
        f += 2.0 * head_params(sizes)
    return f


def prefill_flops(sizes: dict, start: int, end: int) -> float:
    """Tokens ``start..end-1`` of a prompt (``start`` cached positions
    before them are attended to, not recomputed); one logit at the end."""
    n = end - start
    if n <= 0:
        return 0.0
    L = sizes["num_hidden_layers"]
    H, hd = sizes["num_attention_heads"], sizes["head_dim"]
    contexts = n * (start + 1 + end) / 2.0  # sum of (i + 1) for i in start..end-1
    return (2.0 * L * layer_matmul_params(sizes) * n
            + 4.0 * L * H * hd * contexts + 2.0 * head_params(sizes))


def kv_bytes_per_token(sizes: dict, bytes_per: int = 2) -> int:
    """Cache bytes one position takes over all layers: K and V of every
    KV head."""
    return (2 * bytes_per * sizes["num_hidden_layers"]
            * sizes["num_key_value_heads"] * sizes["head_dim"])


def decode_kv_bytes(sizes: dict, context: int, bytes_per: int = 2) -> float:
    """Key and value bytes one decoded token's attention has to read:
    ``context`` positions of the cache."""
    return float(kv_bytes_per_token(sizes, bytes_per)) * context
