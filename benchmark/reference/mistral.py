"""Plain reference: Mistral-7B's decoder in straightforward jax.numpy.

float32 arithmetic at ``highest`` matmul precision, no kernels, no cache,
no batching tricks.  It follows the published architecture (pre-norm
RMSNorm, rotary embeddings over split halves as in the Hugging Face
implementation, grouped-query causal attention, SwiGLU, untied head).
Departures, each for memory alone: attention is taken over blocks of query
rows, layers are recomputed in the backward pass, and weights stay stored
in bfloat16 (the type the configurations state) and are widened where used.

``quant="int8"`` is the control: the same mathematics with every matmul's
operands rounded to int8 (weights per output channel, activations per
row), the nearest precision below the stated bfloat16.

Imports nothing of the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512


def _fake_int8(x, axis):
    """Round to the 255 levels of a symmetric int8 along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _int8_mm(x, w):
    """An int8 matmul as a training stack would run it: both operands of the
    forward product and of the two backward products rounded to int8."""
    return jnp.dot(_fake_int8(x, -1), _fake_int8(w, 0), precision=HI)


def _int8_mm_fwd(x, w):
    return _int8_mm(x, w), (x, w)


def _int8_mm_bwd(res, dy):
    x, w = res
    x2, dy2 = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    dx = jnp.dot(_fake_int8(dy2, -1), _fake_int8(w, 1).T, precision=HI).reshape(x.shape)
    dw = jnp.dot(_fake_int8(x2, 0).T, _fake_int8(dy2, 0), precision=HI)
    return dx, dw


_int8_mm.defvjp(_int8_mm_fwd, _int8_mm_bwd)


def _mm(x, w, quant):
    """x (..., in) @ w (in, out)."""
    w = w.astype(F32)
    if quant == "int8":
        return _int8_mm(x, w)
    return jnp.dot(x, w, precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rotary(x, positions, theta):
    """x (B, S, heads, head_dim); rotate the two halves of each head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv            # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped-query attention. q (B,S,Hq,D), k/v (B,S,Hkv,D)."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    q = q.reshape(B, S, Hkv, Hq // Hkv, D)
    blk = min(Q_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    key_pos = jnp.arange(S)

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 1)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k, precision=HI) / jnp.sqrt(F32(D))
        ok = key_pos[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        s = jnp.where(ok[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(S // blk))        # (nb,B,blk,g,r,D)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, Hq * D)
    return out


def layer(cfg, w, x, positions, quant=None):
    """One decoder layer; ``w`` maps the layer's short names to weights."""
    B, S, _ = x.shape
    Hq, Hkv, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h = rms_norm(x, w["input_layernorm.weight"], cfg["rms_norm_eps"])
    q = _mm(h, w["self_attn.q_proj.weight"], quant).reshape(B, S, Hq, D)
    k = _mm(h, w["self_attn.k_proj.weight"], quant).reshape(B, S, Hkv, D)
    v = _mm(h, w["self_attn.v_proj.weight"], quant).reshape(B, S, Hkv, D)
    q = rotary(q, positions, cfg["rope_theta"])
    k = rotary(k, positions, cfg["rope_theta"])
    x = x + _mm(attention(q, k, v), w["self_attn.o_proj.weight"], quant)
    h = rms_norm(x, w["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    gate = _mm(h, w["mlp.gate_proj.weight"], quant)
    up = _mm(h, w["mlp.up_proj.weight"], quant)
    return x + _mm(jax.nn.silu(gate) * up, w["mlp.down_proj.weight"], quant)


def layer_weights(weights: dict, i: int) -> dict:
    p = f"model.layers.{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def hidden_states(cfg, weights, tokens, quant=None, remat=False):
    """Final-norm hidden states (B, S, H) of ``tokens`` (B, S)."""
    x = jnp.take(weights["model.embed_tokens.weight"], tokens, axis=0).astype(F32)
    pos = jnp.arange(tokens.shape[1])
    step = partial(layer, cfg, quant=quant)
    if remat:
        step = jax.checkpoint(step)
    for i in range(cfg["num_hidden_layers"]):
        x = step(layer_weights(weights, i), x, pos)
    return rms_norm(x, weights["model.norm.weight"], cfg["rms_norm_eps"])


def logits(weights, hidden, quant=None):
    return _mm(hidden, weights["lm_head.weight"], quant)


def loss_fn(cfg, weights, tokens, labels, quant=None, by_row=True):
    """Mean next-token cross-entropy over (B, S).  On one chip the rows go
    one at a time (memory); with the batch spread over several chips they go
    together, one row to a chip."""
    @jax.checkpoint
    def rows(t, l):
        h = hidden_states(cfg, weights, t, quant, remat=True)
        lg = logits(weights, h, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(lg, l[..., None], -1)[..., 0], axis=-1)
    if by_row:
        per_row = jax.lax.map(lambda a: rows(a[0][None], a[1][None])[0], (tokens, labels))
    else:
        per_row = rows(tokens, labels)
    return jnp.mean(per_row)


def adamw(p, g, m, v, t, hp):
    """Decoupled-weight-decay Adam on one leaf: float32 arithmetic on the
    stored values, results rounded to the stored types."""
    pf, mf, vf = p.astype(F32), m.astype(F32), v.astype(F32)
    m2 = hp["beta1"] * mf + (1 - hp["beta1"]) * g
    v2 = hp["beta2"] * vf + (1 - hp["beta2"]) * jnp.square(g)
    mhat = m2 / (1 - hp["beta1"] ** t)
    vhat = v2 / (1 - hp["beta2"] ** t)
    new = pf - hp["learning_rate"] * (mhat / (jnp.sqrt(vhat) + hp["eps"])
                                      + hp["weight_decay"] * pf)
    return new.astype(p.dtype), m2.astype(m.dtype), v2.astype(v.dtype)


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


def loss_and_grads(cfg, quant, by_row, params, tokens, labels):
    """Loss and every leaf's gradient, float32 throughout and rounded once,
    on the way out, to the leaf's stored type (which keeps them beside the
    state in the chip's memory)."""
    return jax.value_and_grad(partial(loss_fn, cfg, quant=quant, by_row=by_row))(
        params, tokens, labels)


def leaf_norm(x):
    return _norm(x)


def leaf_diff_norm(other, scale, grad):
    """Norm of (another side's gradient of this leaf, ``other`` x ``scale``,
    minus this one's)."""
    return _norm(other.astype(F32) * scale - grad)
