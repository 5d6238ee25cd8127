"""One decode step of a delta-rule linear-attention layer with a
per-channel decay (KDA), on the rows' float32 states in place.

A sequence's state is one matrix ``S (d_k, d_v)`` a head, float32.  One
position ``t`` with ``q, k, alpha (d_k,)``, ``v (d_v,)`` and a write
strength ``beta``:

    S'  = Diag(alpha) S
    u   = beta (v - S'^T k)
    S'' = S' + k u^T                    (= (I - beta k k^T) S' + beta k v^T)
    o   = S''^T q

The step reads a state once and writes it once, and that stream is all it
costs: 2 x 4 x d_k x d_v bytes a head against ``6 d_k d_v`` operations.

TPU mapping: the states of every layer and entry are ONE operand ``(L, N,
heads, d_k, d_v)`` addressed in place by ``(layer, row)`` and aliased to
the output, so no layer slice of it is made and nothing of its size
stands beside it; rows ``0 .. B - 1`` are the call's (entry = row: a
serving slot's running state).  The grid is ``(head block, row)``, rows
innermost; a program holds ``block_heads`` states in VMEM.  The step's
vectors ride one ``(8, lanes)`` tile a head (``q, k, alpha, v, beta``, one
a sublane); the three that scale the state's ROWS are turned to columns by
one transpose of that tile, padded to a square, on the XLU.  A row that is
not ``active`` (an idle slot, or one whose prompt the lane is still
running: its state must stand) is never moved: its program is pointed at
the block of the nearest active row before it (the first active row, for
the rows ahead of that), which is the block the neighbouring program holds
already, so no copy is issued for it and it touches nothing (only where no
row at all is active are the blocks copied through as they are).

API:
  kda_decode_step(states, layer, q, k, v, alpha, beta, active)
    states (L, N, H, d_k, d_v) float32        donated by the caller's jit
    q, k, alpha (B, H, d_k); v (B, H, d_v); beta (B, H); active (B,) bool
    -> (o (B, H, d_v) float32, states')
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import interpret as _interpret

_ROWS = 8        # q, k, alpha, v, beta and three rows of padding


def _kda_kernel(act_ref, src_ref, vec_ref, s_ref, o_ref, s_out, *,
                block_heads, dk):
    b = pl.program_id(1)
    active = act_ref[b] > 0
    none_active = act_ref[src_ref[b]] == 0
    for h in range(block_heads):
        S = s_ref[h]                                   # (dk, dv)

        @pl.when(active)
        def _step(h=h, S=S):
            r = vec_ref[0, h]                          # (8, lanes)
            lanes = r.shape[-1]
            # rows -> columns: column j of ``rt`` is row j of ``r``
            rt = jnp.transpose(jnp.concatenate(
                [r, jnp.zeros((lanes - _ROWS, lanes), r.dtype)], axis=0))
            q_col, k_col, a_col = (rt[:dk, j:j + 1] for j in range(3))
            v_row, b_row = r[3:4, :S.shape[1]], r[4:5, :S.shape[1]]
            S1 = S * a_col
            kS = jnp.sum(S1 * k_col, axis=0, keepdims=True)
            u = b_row * (v_row - kS)                   # (1, dv)
            S2 = S1 + k_col * u
            s_out[h] = S2
            o_ref[0, h:h + 1, :] = jnp.sum(S2 * q_col, axis=0, keepdims=True)

        @pl.when(jnp.logical_not(active))
        def _idle(h=h, S=S):
            o_ref[0, h:h + 1, :] = jnp.zeros((1, S.shape[1]), jnp.float32)

        @pl.when(none_active)
        def _stand(h=h, S=S):
            s_out[h] = S


def kda_decode_step(states, layer: int, q, k, v, alpha, beta, active,
                    block_heads: int = 8):
    L, N, H, dk, dv = states.shape
    B = q.shape[0]
    lanes = max(dk, dv, _ROWS)
    block_heads = min(block_heads, H)
    if H % block_heads:
        raise ValueError(f"kda_decode_step: {H} heads do not divide into "
                         f"blocks of {block_heads}")
    f32 = jnp.float32

    def row(x, d):
        return jnp.pad(x.astype(f32), ((0, 0), (0, 0), (0, lanes - d)))
    vec = jnp.stack(
        [row(q, dk), row(k, dk), row(alpha, dk), row(v, dv),
         jnp.broadcast_to(beta.astype(f32)[..., None], (B, H, lanes))]
        + [jnp.zeros((B, H, lanes), f32)] * (_ROWS - 5), axis=2)
    # the row whose block a row's program holds: its own where it is
    # active, else the nearest active row before it, else the first one
    act = jnp.asarray(active, jnp.int32).reshape(B)
    rows = jnp.arange(B, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(act > 0, rows, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(act).astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // block_heads, B),
        in_specs=[
            pl.BlockSpec((1, block_heads, _ROWS, lanes),
                         lambda h, b, act, src: (b, h, 0, 0)),
            pl.BlockSpec((None, None, block_heads, dk, dv),
                         lambda h, b, act, src: (layer, src[b], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_heads, dv),
                         lambda h, b, act, src: (b, h, 0)),
            pl.BlockSpec((None, None, block_heads, dk, dv),
                         lambda h, b, act, src: (layer, src[b], h, 0, 0)),
        ],
    )
    o, states = pl.pallas_call(
        functools.partial(_kda_kernel, block_heads=block_heads, dk=dk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operands: (active, src, vec, states) -> states is output 1
        input_output_aliases={3: 1},
        interpret=_interpret(),
        name="kda_decode_step",
        compiler_params=pltpu.CompilerParams(
            # rows in order: an idle row's program relies on its
            # neighbour's block being the one in VMEM
            dimension_semantics=("parallel", "arbitrary")),
    )(act, src, vec, states)
    return o, states


def kda_decode_step_reference(states, layer: int, q, k, v, alpha, beta,
                              active):
    """The same step in jax.numpy (the tests' oracle)."""
    B = q.shape[0]
    hi = jax.lax.Precision.HIGHEST
    S = states[layer, :B]
    S1 = alpha.astype(jnp.float32)[..., None] * S
    kS = jnp.einsum("bhd,bhde->bhe", k.astype(jnp.float32), S1, precision=hi)
    u = beta.astype(jnp.float32)[..., None] * (v.astype(jnp.float32) - kS)
    S2 = S1 + k.astype(jnp.float32)[..., None] * u[..., None, :]
    o = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), S2, precision=hi)
    keep = jnp.asarray(active, bool)[:, None, None, None]
    S2 = jnp.where(keep, S2, S)
    o = jnp.where(keep[..., 0], o, 0.0)
    return o, states.at[layer, :B].set(S2)
