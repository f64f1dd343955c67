"""Random weights of the Jamba block from ``--seed``, made on the device
in one jitted call, in the tree the program serves
(``gofr_tpu/models/jamba.py``: the Mamba layers stacked in one tree, the
attention layers in another, ``x @ w`` orientation, the head tied to the
embedding).

What the public config does not fix is drawn as the configuration's
``assumed`` says, so that a dropped term shows: ``A_log = log(1..N)`` in
every channel and ``D = 1`` (the model family's initialisation), ``b_dt``
the inverse softplus of ``exp(uniform(log 0.001, log 0.1))``, norm weights
``1 + 0.1 * normal``, the convolution's bias ``0.1 * normal``.
"""

from __future__ import annotations


def make(sizes: dict, seed: int, dtype="bfloat16"):
    """One program draws every leaf, a layer at a time under ``lax.map``,
    so one layer's float32 draw (0.1 GB for ``w_in``) is the largest
    temporary."""
    import jax
    import jax.numpy as jnp

    from benchmark.weights import key_from_seed

    V, D, F = (sizes["vocab_size"], sizes["hidden_size"],
               sizes["intermediate_size"])
    H, KV, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    Di = sizes["mamba_expand"] * D
    N, R, K = (sizes["mamba_d_state"], sizes["mamba_dt_rank"],
               sizes["mamba_d_conv"])
    L, period, offset = (sizes["num_hidden_layers"],
                         sizes["attn_layer_period"],
                         sizes["attn_layer_offset"])
    La = sum(1 for i in range(L) if i % period == offset)
    dt = jnp.dtype(dtype)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    def near(key, centre, shape):
        return centre + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def mlp(ks):
        return {"mlp_norm": near(ks[0], 1.0, (D,)),
                "w_gate": dense(ks[1], (D, F), D),
                "w_up": dense(ks[2], (D, F), D),
                "w_down": dense(ks[3], (F, D), F)}

    def mamba(key):
        ks = jax.random.split(key, 15)
        step = jnp.exp(jax.random.uniform(ks[8], (Di,), jnp.float32,
                                          jnp.log(1e-3), jnp.log(0.1)))
        return {
            "norm": near(ks[0], 1.0, (D,)),
            "w_in": dense(ks[1], (D, 2 * Di), D),
            "conv_w": dense(ks[2], (K, Di), K),
            "conv_b": near(ks[3], 0.0, (Di,)),
            "w_x": dense(ks[4], (Di, R + 2 * N), Di),
            "dt_norm": near(ks[5], 1.0, (R,)),
            "b_norm": near(ks[6], 1.0, (N,)),
            "c_norm": near(ks[7], 1.0, (N,)),
            "w_dt": dense(ks[9], (R, Di), R),
            "b_dt": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1.0, N + 1.0))[:, None], (N, Di)),
            "D": jnp.ones((Di,), jnp.float32),
            "w_out": dense(ks[10], (Di, D), Di),
            **mlp(ks[11:]),
        }

    def attention(key):
        ks = jax.random.split(key, 9)
        return {
            "attn_norm": near(ks[0], 1.0, (D,)),
            "wq": dense(ks[1], (D, H * hd), D),
            "wk": dense(ks[2], (D, KV * hd), D),
            "wv": dense(ks[3], (D, KV * hd), D),
            "wo": dense(ks[4], (H * hd, D), H * hd),
            **mlp(ks[5:]),
        }

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 4)
        return {
            "embed": dense(ks[0], (V, D), D),
            "final_norm": near(ks[1], 1.0, (D,)),
            "mamba": jax.lax.map(mamba, jax.random.split(ks[2], L - La)),
            "attn": jax.lax.map(attention, jax.random.split(ks[3], La)),
        }

    return jax.block_until_ready(draw(key_from_seed(seed)))
