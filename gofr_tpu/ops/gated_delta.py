"""Pallas TPU gated-delta update for a Gated DeltaNet decode step.

One token a row moves every row's state ``S`` [Hv, dk, dv] float32 (key x
value a head): ``S' = exp(g) S + k delta^T`` with ``delta = beta (v -
exp(g) S^T k)``, and the mixer reads ``o = S'^T q``. In a decode step of
the Qwen3-Next cell (128 rows, 32 value heads of 128 x 128) a layer's
state is 268 MB, and XLA sweeps it three times: one fusion reads it for
the two reductions over ``dk`` (``S^T k`` and ``S^T q``), a second reads
it again for the in-place update and writes it. This kernel reads it once
and writes it once:

- the state stays one stack ``[layers, rows, Hv, dk, dv]`` as it lies in
  the cache, aliased from input to output; the layer is named through
  scalar prefetch in the state's index map, as ``selective_state.py``
  names one. No layer's slice is cut out before the call or put back after
  it: a slice handed to a custom call is a copy in and a copy out.
- a grid step takes one row (its 32 heads are 2 MB, contiguous in HBM;
  alone on a v5e at 128 rows, blocks of 1, 2, 4 and 8 rows read within
  0.8 % of one another); for each head it holds the ``[dk, dv]`` tile in
  VMEM, reduces ``S k`` and ``S q`` over ``dk`` (the sublanes), writes
  the updated tile and the head's row of ``o``; the pipeline fetches the
  next row under that work.
- ``q`` and ``k`` come lane-dense, ``[rows, Hv, dk]`` as the projections
  leave them, and a row's ``[Hv, dk]`` tile is transposed in the kernel
  so that each head's key is a column along ``dk`` that broadcasts across
  the ``dv`` lanes. Handed over as ``[rows, Hv, dk, 1]`` such a column
  pads 128-fold on the chip (268 MB at the cell's size, the state's own
  bytes), and as ``[rows, dk, Hv]`` 4-fold (8.4 MB each) behind a
  transpose of XLA's; lane-dense, every operand beside the state is 2 MB
  or less, under 1 % of the state's bytes.

Elementwise float32 as ``gated_delta_step``, the plain recurrence that
any other shape runs; no product goes through the MXU, so no operand is
rounded to bfloat16. Only the order of the sums over ``dk`` may differ
from XLA's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta_step", "gated_delta_update_tpu", "tiles"]


def gated_delta_step(S, q, k, v, g, beta):
    """One token of the gated delta rule, any leading axes. ``S``
    [..., dk, dv] float32 (key x value); ``q``, ``k`` [..., dk]; ``v``
    [..., dv]; ``g``, ``beta`` [...]. ``S <- exp(g) S``; ``delta = beta
    (v - S^T k)``; ``S <- S + k delta^T``; ``o = S^T q``. The old state is
    swept once for both products (``S_new^T q = exp(g) S^T q + delta
    (k.q)``) and once for the update; products are elementwise float32,
    not matmuls, so no operand is rounded to bfloat16."""
    decay = jnp.exp(g)[..., None]
    Sk = jnp.sum(S * k[..., :, None], axis=-2) * decay
    Sq = jnp.sum(S * q[..., :, None], axis=-2) * decay
    delta = beta[..., None] * (v - Sk)
    S = S * decay[..., None] + k[..., :, None] * delta[..., None, :]
    o = Sq + delta * jnp.sum(k * q, axis=-1, keepdims=True)
    return S, o


def tiles(heads: int, dk: int, dv: int) -> bool:
    """Whether a row's state [heads, dk, dv] float32 tiles: ``dk`` and
    ``heads`` of whole sublane tiles, ``dv`` of whole lanes."""
    return dk % 8 == 0 and heads % 8 == 0 and dv % 128 == 0


def _kernel(layer_ref, s_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
            s_out, o_out):
    del layer_ref  # the state's index map reads it
    # the row's keys and queries as columns along dk: [dk, heads]
    k_cols = k_ref[...].T
    q_cols = q_ref[...].T
    kq = jnp.sum(k_ref[...] * q_ref[...], axis=1, keepdims=True)  # [heads, 1]
    decay = jnp.exp(g_ref[...])                                  # [1, heads]
    beta = b_ref[...]
    for h in range(s_ref.shape[0]):
        kc, qc = k_cols[:, h:h + 1], q_cols[:, h:h + 1]          # [dk, 1]
        d = decay[:, h:h + 1]                                    # [1, 1]
        s = s_ref[h]                                             # [dk, dv]
        sk = jnp.sum(s * kc, axis=0, keepdims=True) * d          # [1, dv]
        sq = jnp.sum(s * qc, axis=0, keepdims=True) * d
        delta = beta[:, h:h + 1] * (v_ref[pl.ds(h, 1), :] - sk)
        s_out[h] = s * d + kc * delta
        o_out[pl.ds(h, 1), :] = sq + delta * kq[h:h + 1, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_update_tpu(state, layer, q, k, v, g, beta, *,
                           interpret: bool = False):
    """``state`` [L, rows, Hv, dk, dv] float32, updated in place at layer
    ``layer`` (traced); ``q``, ``k`` [rows, Hv, dk]; ``v`` [rows, Hv, dv];
    ``g``, ``beta`` [rows, Hv]. Returns ``(state, o)``, ``o`` [rows, Hv,
    dv] float32."""
    _, rows, heads, dk, dv = state.shape
    if not tiles(heads, dk, dv):
        raise ValueError(f"no tiling for a state of {state.shape}")
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    # [rows, 1, Hv]: a row's scalars a head along the lanes
    g, beta = g.astype(f32)[:, None], beta.astype(f32)[:, None]
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    state_spec = pl.BlockSpec((None, None, heads, dk, dv),
                              lambda i, l: (l[0], i, 0, 0, 0))
    key_spec = pl.BlockSpec((None, heads, dk), lambda i, l: (i, 0, 0))
    value_spec = pl.BlockSpec((None, heads, dv), lambda i, l: (i, 0, 0))
    head_spec = pl.BlockSpec((None, 1, heads), lambda i, l: (i, 0, 0))
    # the state's row in and out, double-buffered, and room for the rest
    vmem = 4 * heads * dk * dv * 4 + 16 * 2**20
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows,),
            in_specs=[state_spec, key_spec, key_spec, value_spec,
                      head_spec, head_spec],
            out_specs=[state_spec, value_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((rows, heads, dv), f32)],
        # operand 1 (after the layer) is output 0: the stack in place
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="gated_delta_update_tpu",
    )(layer, state, q, k, v, g, beta)
