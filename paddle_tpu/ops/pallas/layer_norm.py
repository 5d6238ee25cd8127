"""Fused LayerNorm / RMSNorm Pallas kernels.

Replaces the reference's layer_norm CUDA kernel
(paddle/phi/kernels/gpu/layer_norm_kernel.cu) and the fused
bias+dropout+residual+LN of fused_attention. One VMEM pass per row block:
load, reduce, normalize, scale — no intermediate HBM round trips. Stats in
f32 regardless of input dtype (bf16-safe).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import interpret as _interpret

BLOCK_ROWS = 256


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    y = y * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _rows_block(n_rows):
    for cand in (BLOCK_ROWS, 128, 64, 32, 16, 8, 4, 2, 1):
        if n_rows % cand == 0:
            return cand
    return 1


@functools.partial(jax.jit, static_argnames=("eps",))
def fused_layer_norm(x, weight, bias, eps=1e-5):
    """x: (..., hidden). weight/bias: (hidden,)."""
    shape = x.shape
    H = shape[-1]
    xr = x.reshape(-1, H)
    R = xr.shape[0]
    br = _rows_block(R)
    out = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_ln_kernel, eps=eps),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, H), lambda i: (i, 0)),
            pl.BlockSpec((H,), lambda i: (0,)),
            pl.BlockSpec((H,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H), x.dtype),
    )(xr, weight, bias)
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("eps",))
def fused_rms_norm(x, weight, eps=1e-6):
    shape = x.shape
    H = shape[-1]
    xr = x.reshape(-1, H)
    R = xr.shape[0]
    br = _rows_block(R)
    out = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_rms_kernel, eps=eps),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, H), lambda i: (i, 0)),
            pl.BlockSpec((H,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H), x.dtype),
    )(xr, weight)
    return out.reshape(shape)
