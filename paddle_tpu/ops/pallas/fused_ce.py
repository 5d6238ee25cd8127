"""Fused softmax-crossentropy Pallas kernel.

Replaces the reference's fused softmax+CE CUDA path
(paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cu and
phi softmax_with_cross_entropy kernels): one VMEM pass computes the row
max, log-sum-exp and the label logit without materializing the (N, V)
softmax in HBM — on a 32k vocab that intermediate is the single largest
HBM write of the training loss. Backward is the closed form
softmax(x) - onehot(label), likewise tiled.

All rank-1 per-row operands (labels, loss, lse, grad) are carried as
(N, 1) so every block is rank-2: Mosaic requires rank-1 blocks to be
lane-aligned (multiples of 128), while an (R, 1) block only needs the
sublane rule (R % 8 == 0), which BLOCK_ROWS=16 satisfies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .lowering import interpret as _interpret

BLOCK_ROWS = 16
LANES = 128


def _ce_fwd_kernel(logits_ref, labels_ref, loss_ref, lse_ref):
    x = logits_ref[...].astype(jnp.float32)          # (R, V)
    lbl = labels_ref[...][:, 0]                      # (R, 1) -> (R,)
    m = jnp.max(x, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m[:, None]), axis=-1))
    R, V = x.shape
    onehot = jax.lax.broadcasted_iota(jnp.int32, (R, V), 1) == lbl[:, None]
    label_logit = jnp.sum(jnp.where(onehot, x, 0.0), axis=-1)
    loss_ref[...] = (lse - label_logit)[:, None]
    lse_ref[...] = lse[:, None]


def _ce_bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, dx_ref):
    x = logits_ref[...].astype(jnp.float32)
    lbl = labels_ref[...][:, 0]
    lse = lse_ref[...][:, 0]
    g = g_ref[...][:, 0]
    p = jnp.exp(x - lse[:, None])
    R, V = x.shape
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (R, V), 1)
              == lbl[:, None]).astype(jnp.float32)
    dx_ref[...] = ((p - onehot) * g[:, None]).astype(dx_ref.dtype)


def _rows_block(n):
    return min(BLOCK_ROWS, n)


def _fusable(n_rows: int, vocab: int) -> bool:
    """The TPU lowering needs lane-aligned V and whole row blocks; the CPU
    interpreter accepts anything."""
    if n_rows % _rows_block(n_rows):
        return False
    if _interpret():
        return True
    return vocab % LANES == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def softmax_cross_entropy(logits, labels):
    """Per-token CE loss. logits (N, V), labels (N,) int32 -> (N,) f32."""
    loss, _ = _ce_fwd(logits, labels)
    return loss


def _ce_fwd(logits, labels):
    N, V = logits.shape
    R = _rows_block(N)
    assert N % R == 0, (N, R)
    loss, lse = pl.pallas_call(
        _ce_fwd_kernel,
        grid=(N // R,),
        in_specs=[pl.BlockSpec((R, V), lambda i: (i, 0)),
                  pl.BlockSpec((R, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((R, 1), lambda i: (i, 0)),
                   pl.BlockSpec((R, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, 1), jnp.float32)],
        interpret=_interpret(),
    )(logits, labels.astype(jnp.int32)[:, None])
    return loss[:, 0], lse[:, 0]


def _fwd(logits, labels):
    loss, lse = _ce_fwd(logits, labels)
    return loss, (logits, labels, lse)


def _bwd(res, g):
    logits, labels, lse = res
    N, V = logits.shape
    R = _rows_block(N)
    dx = pl.pallas_call(
        _ce_bwd_kernel,
        grid=(N // R,),
        in_specs=[pl.BlockSpec((R, V), lambda i: (i, 0)),
                  pl.BlockSpec((R, 1), lambda i: (i, 0)),
                  pl.BlockSpec((R, 1), lambda i: (i, 0)),
                  pl.BlockSpec((R, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((R, V), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, V), logits.dtype),
        interpret=_interpret(),
    )(logits, labels.astype(jnp.int32)[:, None], lse[:, None],
      g.astype(jnp.float32)[:, None])
    return dx, None


softmax_cross_entropy.defvjp(_fwd, _bwd)


def causal_lm_loss(logits, labels):
    """Mean CE over (B, S, V) logits vs (B, S) labels using the fused
    kernel when shapes allow; dense log_softmax fallback otherwise."""
    B, S, V = logits.shape
    flat = logits.reshape(B * S, V)
    lbl = labels.reshape(B * S)
    if _fusable(B * S, V):
        return jnp.mean(softmax_cross_entropy(flat, lbl))
    logp = jax.nn.log_softmax(flat.astype(jnp.float32), -1)
    return jnp.mean(-jnp.take_along_axis(logp, lbl[:, None], -1)[:, 0])
