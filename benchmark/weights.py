"""Random weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights (not the program), in the type they are
served in, and hands the same arrays to the system under test and, after
the window, to the plain reference. The tree is the one the llama example
serves: stacked layers, ``x @ w`` orientation.
"""

from __future__ import annotations


def key_from_seed(seed: int):
    """A PRNG key for any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make(sizes: dict, seed: int, dtype="bfloat16"):
    """``sizes``: a configuration file's keys. One program draws every
    leaf: a layer at a time under ``lax.map``, so the float32 draw of one
    layer (under 1 GB at 7B widths) is the largest temporary."""
    import jax
    import jax.numpy as jnp

    V, D, L = sizes["vocab_size"], sizes["hidden_size"], sizes["num_hidden_layers"]
    H, KV, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    F = sizes["intermediate_size"]
    dt = jnp.dtype(dtype)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    def norm(key, shape):
        # near 1, not exactly 1: a norm scale that is dropped must show
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def layer(key):
        ks = jax.random.split(key, 9)
        return {
            "attn_norm": norm(ks[0], (D,)),
            "mlp_norm": norm(ks[1], (D,)),
            "wq": dense(ks[2], (D, H * hd), D),
            "wk": dense(ks[3], (D, KV * hd), D),
            "wv": dense(ks[4], (D, KV * hd), D),
            "wo": dense(ks[5], (H * hd, D), H * hd),
            "w_gate": dense(ks[6], (D, F), D),
            "w_up": dense(ks[7], (D, F), D),
            "w_down": dense(ks[8], (F, D), F),
        }

    @jax.jit
    def draw(key):
        k_embed, k_layers, k_norm, k_head = jax.random.split(key, 4)
        return {
            "embed": dense(k_embed, (V, D), D),
            "layers": jax.lax.map(layer, jax.random.split(k_layers, L)),
            "final_norm": norm(k_norm, (D,)),
            "lm_head": dense(k_head, (D, V), D),
        }

    return jax.block_until_ready(draw(key_from_seed(seed)))
