"""Fused dropout + residual-add + layernorm Pallas kernel.

~ the reference's fused_bias_dropout_residual_layer_norm family
(paddle/fluid/operators/fused/fused_attention_op.cu,
fused_dropout_helper.h): the transformer residual path
``ln(residual + dropout(x))`` done in one VMEM pass — the three
intermediates never round-trip HBM. Dropout randomness comes in as a
uint32 bits tensor generated with the framework Generator outside the
kernel (seed+offset reproducibility, phi/core/generator.h:23 semantics)
so the kernel itself stays deterministic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .lowering import interpret as _interpret

BLOCK_ROWS = 128


def _kernel(x_ref, res_ref, bits_ref, w_ref, b_ref, o_ref, *, p, eps,
            training):
    x = x_ref[...].astype(jnp.float32)
    if training and p > 0.0:
        # keep when uniform(bits) >= p; inverted scaling keeps E[out]=x
        u = bits_ref[...].astype(jnp.float32) / 4294967296.0
        keep = (u >= p).astype(jnp.float32)
        x = x * keep / (1.0 - p)
    h = x + res_ref[...].astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    hc = h - mu
    var = jnp.mean(hc * hc, axis=-1, keepdims=True)
    y = hc * jax.lax.rsqrt(var + eps)
    y = y * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _core(x2, r2, weight, bias, bits, p, eps, training):
    """Differentiable core over flat (N, H) operands.

    Forward is the Pallas kernel; backward is the closed-form layernorm
    gradient (recomputing h/mu/rsig from the saved inputs — cheap
    elementwise work that XLA fuses; the HBM win of the kernel is in the
    forward intermediates)."""
    return _core_fwd(x2, r2, weight, bias, bits, p, eps, training)[0]


def _core_fwd(x2, r2, weight, bias, bits, p, eps, training):
    out = _pallas_forward(x2, r2, weight, bias, bits, p, eps, training)
    return out, (x2, r2, weight, bits)


def _core_bwd(p, eps, training, res, g):
    x2, r2, weight, bits = res
    gf = g.astype(jnp.float32)
    xf = x2.astype(jnp.float32)
    if training and p > 0.0:
        u = bits.astype(jnp.float32) / 4294967296.0
        keep = (u >= p).astype(jnp.float32) / (1.0 - p)
        xf = xf * keep
    else:
        keep = None
    h = xf + r2.astype(jnp.float32)
    mu = h.mean(-1, keepdims=True)
    hc = h - mu
    rsig = jax.lax.rsqrt((hc * hc).mean(-1, keepdims=True) + eps)
    yhat = hc * rsig
    wf = weight.astype(jnp.float32)
    wg = gf * wf
    dh = (wg - wg.mean(-1, keepdims=True)
          - yhat * (wg * yhat).mean(-1, keepdims=True)) * rsig
    dw = jnp.sum(gf * yhat, axis=0).astype(weight.dtype)
    db = jnp.sum(gf, axis=0).astype(weight.dtype)
    dres = dh.astype(r2.dtype)
    dx = (dh * keep if keep is not None else dh).astype(x2.dtype)
    import numpy as np
    dbits = np.zeros(bits.shape, jax.dtypes.float0)
    return dx, dres, dw, db, dbits


_core.defvjp(_core_fwd, _core_bwd)


def fused_dropout_add_layer_norm(x, residual, weight, bias, p=0.1,
                                 eps=1e-5, training=True, bits=None):
    """x, residual: (..., H); weight/bias: (H,). Returns ln(res+drop(x)).

    bits: optional uint32 tensor shaped like x (dropout randomness); when
    None and training, drawn from the framework Generator. Differentiable
    (custom VJP) so it can serve the training-time fused transformer
    layers (incubate/nn), not just inference.
    """
    orig_shape = x.shape
    H = orig_shape[-1]
    x2 = x.reshape(-1, H)
    r2 = residual.reshape(-1, H)
    N = x2.shape[0]
    if bits is None:
        if training and p > 0.0:
            from ...core.generator import default_generator
            bits = jax.random.bits(default_generator().next_key(), (N, H),
                                   jnp.uint32)
        else:
            bits = jnp.zeros((N, H), jnp.uint32)
    out = _core(x2, r2, weight, bias, bits.reshape(N, H),
                float(p), float(eps), bool(training))
    return out.reshape(orig_shape)


def _pallas_forward(x2, r2, weight, bias, bits, p, eps, training):
    N, H = x2.shape
    R = min(BLOCK_ROWS, N)
    if N % R != 0:  # ragged: dense fallback keeps semantics
        xf = x2.astype(jnp.float32)
        if training and p > 0.0:
            u = bits.astype(jnp.float32) / 4294967296.0
            xf = xf * (u >= p).astype(jnp.float32) / (1.0 - p)
        h = xf + r2.astype(jnp.float32)
        mu = h.mean(-1, keepdims=True)
        var = ((h - mu) ** 2).mean(-1, keepdims=True)
        y = (h - mu) * jax.lax.rsqrt(var + eps) * weight + bias
        return y.astype(x2.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, p=float(p), eps=float(eps),
                          training=bool(training)),
        grid=(N // R,),
        in_specs=[pl.BlockSpec((R, H), lambda i: (i, 0)),
                  pl.BlockSpec((R, H), lambda i: (i, 0)),
                  pl.BlockSpec((R, H), lambda i: (i, 0)),
                  pl.BlockSpec((H,), lambda i: (0,)),
                  pl.BlockSpec((H,), lambda i: (0,))],
        out_specs=pl.BlockSpec((R, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H), x2.dtype),
        interpret=_interpret(),
    )(x2, r2, bits, weight, bias)
    return out
