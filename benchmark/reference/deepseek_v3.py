"""Plain reference: a ``deepseek_v3`` decoder (Moonlight-16B-A3B's
``config.json``) in straightforward jax.numpy.

float32 arithmetic at ``highest`` matmul precision, no kernels, no cache
(K and V are expanded from the latent for every position), no batching
tricks, every expert computed for every token and masked by the router's
choice.  Per layer, ``x = RMSNorm(h)``:

* latent attention without query compression: ``q = x W_q`` -> heads x
  (nope | rope); ``[c_kv | k_rope] = x W_kva``; ``c = RMSNorm(c_kv)``;
  ``[k_nope | v] = c W_kvb`` per head; rotary on ``q_rope`` per head and on
  the one ``k_rope`` all heads share; ``score = (q_nope k_nope + q_rope
  k_rope) / sqrt(nope + rope)``; causal softmax; ``o = sum p v``; ``W_o``.
  **Rotary pairing**: dimensions ``(2i, 2i + 1)`` form pair ``i``, rotated
  by ``pos * theta^(-2i/rope)``.  That is what Hugging Face's
  ``deepseek_v3`` computes: it first gathers q and k alike into
  ``[evens | odds]`` and then rotates the two halves, and a permutation
  applied to q and k alike leaves every score as it is.  The split-half
  pairing (``i`` with ``i + rope/2``, as in ``reference/mistral.py``)
  is the same function of weights whose rope columns of ``W_q`` and
  ``W_kva`` are permuted once; with weights drawn from a seed the two are
  equally good draws, and this file keeps the published pairing.
* feed-forward: SwiGLU in the first ``first_k_dense_replace`` layers; then
  ``s = sigmoid(x W_g)``, chosen = top-k of ``s + b``
  (``e_score_correction_bias``; one group), ``w = s[chosen] / (sum
  s[chosen] + 1e-20) * routed_scaling_factor``, ``y = sum_k w_k
  expert_k(x) + shared(x)``, ``shared`` one SwiGLU of ``n_shared_experts
  * moe_intermediate_size``.  No capacity, no dropped token.

Departures, each for memory alone: attention is taken over blocks of query
rows, the experts run one after another, and weights stay stored in
bfloat16 (the type the configuration states) and are widened where used.

``quant="int8"`` is the control: the same mathematics with every matmul's
operands rounded to int8 (weights per output channel, activations per
row), the nearest precision below the stated bfloat16.

Imports nothing of the program.
"""
from __future__ import annotations

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


def _mm(x, w, quant):
    """x (..., in) @ w (in, out)."""
    w = w.astype(F32)
    if quant == "int8":
        return jnp.dot(_fake_int8(x, -1), _fake_int8(w, 0), precision=HI)
    return jnp.dot(x, w, precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rotary(x, positions, theta):
    """x (S, ..., d) with positions (S,): rotate pairs (2i, 2i + 1)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv                  # (S, d/2)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1).reshape(x.shape)


def attention(q, k, v, scale):
    """Causal attention of one sequence. q, k (S, heads, dk); v (S, heads, dv)."""
    S = q.shape[0]
    blk = min(Q_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    key_pos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        ok = key_pos[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(S // blk))            # (nb, blk, h, dv)
    return out.reshape(S, -1)


def latent_attention(cfg, w, h, positions, quant):
    """h (S, H) -> (S, H)."""
    S = h.shape[0]
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = _mm(h, w["self_attn.q_proj.weight"], quant).reshape(S, nh, nope + rope)
    kva = _mm(h, w["self_attn.kv_a_proj_with_mqa.weight"], quant)
    c = rms_norm(kva[:, :rank], w["self_attn.kv_a_layernorm.weight"], cfg["rms_norm_eps"])
    kv = _mm(c, w["self_attn.kv_b_proj.weight"], quant).reshape(S, nh, nope + dv)
    q_rope = rotary(q[..., nope:], positions, cfg["rope_theta"])
    k_rope = rotary(kva[:, rank:], positions, cfg["rope_theta"])
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope[:, None, :], (S, nh, rope))], -1)
    qq = jnp.concatenate([q[..., :nope], q_rope], -1)
    o = attention(qq, k, kv[..., nope:], 1.0 / jnp.sqrt(F32(nope + rope)))
    return _mm(o, w["self_attn.o_proj.weight"], quant)


def swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down, quant)


def router(cfg, w, x, quant):
    """x (S, H) -> dense weights (S, E): zero but for each token's chosen
    experts."""
    s = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], quant))
    bias = w["mlp.gate.e_score_correction_bias"].astype(F32)
    _, idx = jax.lax.top_k(s + bias[None, :], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    chosen = chosen * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(chosen)


def sparse_feed_forward(cfg, w, x, quant, held=None):
    """x (S, H) -> (S, H): every held expert on every token, one expert
    after another, weighted by the router (zero where it was not chosen),
    plus the shared expert.  ``held`` (ids, in the stacks' order) is a
    chip's share of the experts; None = all."""
    weights = router(cfg, w, x, quant)
    if held is not None:
        weights = weights[:, jnp.asarray(held)]

    def one(y, e):
        gate, up, down, we = e
        return y + we[:, None] * swiglu(x, gate, up, down, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
                         w["mlp.experts.down_proj"], weights.T))
    return y + swiglu(x, w["mlp.shared_experts.gate_proj.weight"],
                      w["mlp.shared_experts.up_proj.weight"],
                      w["mlp.shared_experts.down_proj.weight"], quant)


def layer(cfg, w, x, positions, quant=None):
    """One decoder layer of one sequence, x (S, H); ``w`` maps the layer's
    short names to weights; a layer with a router is a sparse one."""
    h = rms_norm(x, w["input_layernorm.weight"], cfg["rms_norm_eps"])
    x = x + latent_attention(cfg, w, h, positions, quant)
    h = rms_norm(x, w["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    if "mlp.gate.weight" in w:
        return x + sparse_feed_forward(cfg, w, h, quant)
    return x + swiglu(h, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
                      w["mlp.down_proj.weight"], quant)


def layer_weights(weights: dict, i: int) -> dict:
    p = f"model.layers.{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def logits(cfg, weights, tokens, quant=None):
    """Logits (S, V) of one sequence ``tokens`` (S,)."""
    x = jnp.take(weights["model.embed_tokens.weight"], tokens, axis=0).astype(F32)
    pos = jnp.arange(tokens.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        x = layer(cfg, layer_weights(weights, i), x, pos, quant)
    x = rms_norm(x, weights["model.norm.weight"], cfg["rms_norm_eps"])
    return _mm(x, weights["lm_head.weight"], quant)
