"""Random weights of the DeepSeek-V3 block from ``--seed``, made on the
device in one jitted call, in the tree the program serves
(``gofr_tpu/models/deepseek_v3.py``: attention stacked over every layer,
the leading dense layers' SwiGLU and the expert layers' router, routing
bias and shared expert each stacked over their own layers, the held
routed experts as one stack beside them, ``x @ w`` orientation).

``sizes`` is the configuration file: ``n_routed_experts`` counts the
routed experts held here, ``router_width`` the experts the router ranks.
What the public config does not fix is drawn as the configuration's
``assumed`` says: norm weights near 1 (0.1 wide, so that a dropped or
misplaced norm shows) and ``e_score_correction_bias`` drawn 0.001 wide.
"""

from __future__ import annotations


def make(sizes: dict, seed: int, dtype="bfloat16"):
    """One program draws every leaf, a layer at a time under ``lax.map``,
    so one layer's float32 draw (1.9 GB for the held experts' gate and up
    projections) is the largest temporary."""
    import jax
    import jax.numpy as jnp

    from benchmark.weights import key_from_seed

    V, D = sizes["vocab_size"], sizes["hidden_size"]
    L, Ld = sizes["num_hidden_layers"], sizes["first_k_dense_replace"]
    Le = L - Ld
    H, rq, rkv = (sizes["num_attention_heads"], sizes["q_lora_rank"],
                  sizes["kv_lora_rank"])
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    F, Fm = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    Fs = Fm * sizes["n_shared_experts"]
    E, held = sizes["router_width"], sizes["n_routed_experts"]
    dt = jnp.dtype(dtype)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    def norm(key, shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def attention(key):
        ks = jax.random.split(key, 10)
        return {
            "norm": norm(ks[0], (D,)),
            "w_dq": dense(ks[1], (D, rq), D),
            "q_norm": norm(ks[2], (rq,)),
            "w_uq": dense(ks[3], (rq, H * (dn + dr)), rq),
            "w_dkv": dense(ks[4], (D, rkv + dr), D),
            "kv_norm": norm(ks[5], (rkv,)),
            "w_uk": dense(ks[6], (H, dn, rkv), rkv),
            "w_uv": dense(ks[7], (H, rkv, dv), rkv),
            "wo": dense(ks[8], (H * dv, D), H * dv),
            "ffn_norm": norm(ks[9], (D,)),
        }

    def swiglu(key, width):
        k_in, k_out = jax.random.split(key)
        return {"w_gate_up": dense(k_in, (D, 2 * width), D),
                "w_down": dense(k_out, (width, D), width)}

    def expert_layer(key):
        ks = jax.random.split(key, 4)
        shared = swiglu(ks[2], Fs)
        return {"router": dense(ks[0], (D, E), D),
                # e_score_correction_bias: 0.001 wide, so that the biased
                # top 8 differ from the unbiased on some tokens (about 8 %
                # of random unit inputs) while the held experts' loads
                # vary as they do at zero; 0.01 wide would move the choice
                # on some 60 % of tokens and unbalance the loads seed by
                # seed, and with them a decode step's work
                "bias": 0.001 * jax.random.normal(ks[3], (E,), jnp.float32),
                "s_gate_up": shared["w_gate_up"],
                "s_down": shared["w_down"]}

    def routed(key):
        k_in, k_out = jax.random.split(key)
        return {"w_gate_up": dense(k_in, (held, D, 2 * Fm), D),
                "w_down": dense(k_out, (held, Fm, D), Fm)}

    def stacked(layer, key, n):
        return jax.lax.map(layer, jax.random.split(key, n))

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 7)
        experts = stacked(routed, ks[3], Le)
        return {
            "embed": dense(ks[0], (V, D), D),
            "final_norm": norm(ks[1], (D,)),
            "lm_head": dense(ks[2], (D, V), D),
            "attn": stacked(attention, ks[4], L),
            "dense": stacked(lambda k: swiglu(k, F), ks[5], Ld),
            "moe": stacked(expert_layer, ks[6], Le),
            # expert layer l's held experts are rows l * held ... (l + 1) *
            # held - 1
            "experts": jax.tree.map(
                lambda a: a.reshape(Le * held, *a.shape[2:]), experts),
        }

    return jax.block_until_ready(draw(key_from_seed(seed)))
