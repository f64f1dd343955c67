"""Random weights of the Qwen3-Next block from ``--seed``, made on the
device in one jitted call, in the tree the program serves
(``gofr_tpu/models/qwen3_next.py``: a period's layers side by side, each
stacked over the periods, the routed experts as one stack beside them,
``x @ w`` orientation).

``sizes`` is the configuration file: ``num_experts`` counts the routed
experts held here, ``router_width`` the experts the router ranks. What the
public config does not fix is drawn as the configuration's ``assumed``
says: ``A_log`` and ``dt_bias`` as the model's own initialisation draws
them, zero-centred norm weights near 0 and the DeltaNet output norm's near
1 (0.1 wide, so that a dropped or misplaced norm shows).
"""

from __future__ import annotations


def make(sizes: dict, seed: int, dtype="bfloat16"):
    """One program draws every leaf, a layer at a time under ``lax.map``,
    so one layer's float32 draw (1.1 GB for the held experts' gate and up
    projections) is the largest temporary."""
    import jax
    import jax.numpy as jnp

    from benchmark.weights import key_from_seed

    V, D = sizes["vocab_size"], sizes["hidden_size"]
    I = sizes["full_attention_interval"]
    P = sizes["num_hidden_layers"] // I
    H, KV, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    Hk, Hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps = sizes["linear_conv_kernel_dim"]
    kd, vd = Hk * dk, Hv * dv
    E, held = sizes["router_width"], sizes["num_experts"]
    F, Fs = (sizes["moe_intermediate_size"],
             sizes["shared_expert_intermediate_size"])
    dt = jnp.dtype(dtype)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    def near(key, centre, shape):
        return centre + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def delta_net(key):
        ks = jax.random.split(key, 8)
        step = jnp.exp(jax.random.uniform(ks[5], (Hv,), jnp.float32,
                                          jnp.log(1e-3), jnp.log(0.1)))
        return {
            "norm": near(ks[0], 0.0, (D,)),
            "w_qkvz": dense(ks[1], (D, 2 * kd + 2 * vd), D),
            "w_ba": dense(ks[2], (D, 2 * Hv), D),
            "conv": dense(ks[3], (taps, 2 * kd + vd), taps),
            "A_log": jnp.log(jax.random.uniform(ks[4], (Hv,), jnp.float32,
                                                1e-3, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "o_norm": near(ks[6], 1.0, (dv,)),
            "w_out": dense(ks[7], (vd, D), vd),
        }

    def attention(key):
        ks = jax.random.split(key, 7)
        return {
            "norm": near(ks[0], 0.0, (D,)),
            "wq": dense(ks[1], (D, H * 2 * hd), D),
            "wk": dense(ks[2], (D, KV * hd), D),
            "wv": dense(ks[3], (D, KV * hd), D),
            "q_norm": near(ks[4], 0.0, (hd,)),
            "k_norm": near(ks[5], 0.0, (hd,)),
            "wo": dense(ks[6], (H * hd, D), H * hd),
        }

    def expert_layer(key):
        ks = jax.random.split(key, 5)
        return {
            "norm": near(ks[0], 0.0, (D,)),
            "router": dense(ks[1], (D, E), D),
            "s_gate_up": dense(ks[2], (D, 2 * Fs), D),
            "s_down": dense(ks[3], (Fs, D), Fs),
            "s_mix": dense(ks[4], (D,), D),
        }

    def routed(key):
        k_in, k_out = jax.random.split(key)
        return {"w_gate_up": dense(k_in, (held, D, 2 * F), D),
                "w_down": dense(k_out, (held, F, D), F)}

    def stacked(layer, key, n):
        return jax.lax.map(layer, jax.random.split(key, n))

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 7)
        experts = stacked(routed, ks[3], P * I)
        k_lin = jax.random.split(ks[4], I - 1)
        k_moe = jax.random.split(ks[6], I)
        return {
            "embed": dense(ks[0], (V, D), D),
            "final_norm": near(ks[1], 0.0, (D,)),
            "lm_head": dense(ks[2], (D, V), D),
            # layer l's held experts are rows l * held ... (l + 1) * held - 1
            "experts": jax.tree.map(
                lambda a: a.reshape(P * I * held, *a.shape[2:]), experts),
            # a period's layers side by side, each stacked over the periods
            "periods": {
                "lin": [stacked(delta_net, k, P) for k in k_lin],
                "attn": stacked(attention, ks[5], P),
                "moe": [stacked(expert_layer, k, P) for k in k_moe],
            },
        }

    return jax.block_until_ready(draw(key_from_seed(seed)))
