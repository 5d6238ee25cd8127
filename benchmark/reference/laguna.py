"""Plain reference: a ``laguna`` decoder (Laguna-XS.2's ``config.json``) in
straightforward jax.numpy.

float32 arithmetic at ``highest`` matmul precision, no kernels, no cache, no
paging, no batching; the sliding window is a mask, and every expert is
computed for every token and masked by the router's choice.  Layer ``i``
has kind ``layer_types[i]`` and ``n_i = num_attention_heads_per_layer[i]``
query heads over ``num_key_value_heads`` KV heads of ``head_dim``.  ``x =
RMSNorm(h)``:

* ``q = x W_q`` -> ``n_i`` heads; ``k = x W_k``, ``v = x W_v`` -> the KV
  heads; no bias, no per-head q/k norm (assumed: the config names none).
* Rotary, split-half pairing ``(d, d + r/2)`` as Hugging Face's
  ``rotate_half``, on the first ``r = head_dim * partial_rotary_factor``
  dimensions of q and k, the rest passed through.  A sliding layer: ``r =
  head_dim``, ``inv_freq_d = theta^(-2d/r)``, unscaled.  A full layer under
  YaRN (``yarn_inv_freq``): this is ``transformers``'
  ``_compute_yarn_parameters`` with ``truncate`` on, written out: with
  ``base_d = theta^(-2d/r)``, ``corr(beta) = r ln(original / (2 pi beta)) /
  (2 ln theta)``, ``low = floor(corr(beta_fast))``, ``high =
  ceil(corr(beta_slow))`` clipped to ``[0, r - 1]``, ``ramp_d = clip((d -
  low) / (high - low), 0, 1)``, ``inv_freq_d = base_d / factor * ramp_d +
  base_d * (1 - ramp_d)``; ``cos`` and ``sin`` are multiplied by
  ``attention_factor`` (so the scores by its square).
* Query head ``g`` reads KV head ``g // (n_i / n_kv)``; ``score = q k /
  sqrt(head_dim)``; a full layer is causal, a sliding layer lets position
  ``t`` see ``j`` with ``t - sliding_window < j <= t``; float32 softmax.
* The gate (assumed per head, as the sibling config's ``gating:
  "per-head"``): ``g = sigmoid(x W_g)`` -> ``n_i`` values; each head's
  output times its value; then ``W_o``.
* Feed-forward on ``RMSNorm(h)``: a SwiGLU of ``intermediate_size`` where
  ``mlp_layer_types[i]`` is ``dense``; else ``s = sigmoid(x W_r)``, chosen =
  the ``num_experts_per_tok`` largest ``s`` (no selection bias: the config
  names none), ``w = s[chosen] / (sum s[chosen] + 1e-20) *
  moe_routed_scaling_factor``, ``y = sum_k w_k expert_k(x) + shared(x)``,
  ``shared`` one ungated SwiGLU of ``shared_expert_intermediate_size``.  No
  capacity, no dropped token.

Departures, each for memory alone: attention is taken over blocks of query
rows, the experts run one after another, and weights stay stored in
bfloat16 (the type the configuration states) and are widened where used.

``quant="int8"`` is the control: the same mathematics with every matmul's
operands rounded to int8 (weights per output channel, activations per
row), the nearest precision below the stated bfloat16.
``quant="window_ignored"`` is a planted fault, not a precision: sliding
layers attend to everything before them.

Imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512
FULL, SLIDING = "full_attention", "sliding_attention"


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


def yarn_inv_freq(rp: dict, r: int):
    """The ``r / 2`` frequencies of a YaRN layer (module docstring)."""
    base, factor = float(rp["rope_theta"]), float(rp["factor"])
    orig = rp["original_max_position_embeddings"]

    def corr(beta):
        return r * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), r - 1)
    d = jnp.arange(r // 2, dtype=F32)
    plain = base ** (-2.0 * d / r)
    ramp = jnp.clip((d - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary(cfg, kind, x, positions):
    """x (S, heads, D): rotate pairs ``(d, d + r/2)`` of the first ``r``
    dimensions by this layer kind's rule."""
    rp = cfg["rope_parameters"][kind]
    r = int(cfg["head_dim"] * rp.get("partial_rotary_factor", 1))
    if rp["rope_type"] == "yarn":
        inv, scale = yarn_inv_freq(rp, r), float(rp["attention_factor"])
    else:
        inv = float(rp["rope_theta"]) ** (-2.0 * jnp.arange(r // 2, dtype=F32) / r)
        scale = 1.0
    ang = positions.astype(F32)[:, None, None] * inv              # (S, 1, r/2)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], -1)


def attention(q, k, v, scale, window):
    """One sequence. q (S, n, D); k, v (S, n_kv, D); ``window`` None for a
    causal layer."""
    S, n, D = q.shape
    nkv = k.shape[1]
    blk = min(Q_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    key_pos = jnp.arange(S)
    qg = q.reshape(S, nkv, n // nkv, D)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * blk, blk, 0)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI) * scale
        row = (i * blk + jnp.arange(blk))[:, None]
        ok = key_pos[None, :] <= row
        if window is not None:
            ok = ok & (key_pos[None, :] > row - window)
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(S // blk))        # (nb, blk, nkv, g, D)
    return out.reshape(S, n, D)


def attention_layer(cfg, i, w, h, positions, quant):
    """h (S, H) -> (S, H): layer ``i``'s attention, gate and output."""
    S = h.shape[0]
    kind = cfg["layer_types"][i]
    n, nkv, D = cfg["num_attention_heads_per_layer"][i], cfg["num_key_value_heads"], cfg["head_dim"]
    q = rotary(cfg, kind, _mm(h, w["self_attn.q_proj.weight"], quant).reshape(S, n, D), positions)
    k = rotary(cfg, kind, _mm(h, w["self_attn.k_proj.weight"], quant).reshape(S, nkv, D), positions)
    v = _mm(h, w["self_attn.v_proj.weight"], quant).reshape(S, nkv, D)
    window = cfg["sliding_window"] if kind == SLIDING and quant != "window_ignored" else None
    o = attention(q, k, v, 1.0 / jnp.sqrt(F32(D)), window)
    o = gate(w, h, o, quant)
    return _mm(o.reshape(S, n * D), w["self_attn.o_proj.weight"], quant)


def gate(w, h, o, quant):
    """The per-head output gate (assumed): o (S, n, D) times ``sigmoid(h
    W_g)`` (S, n)."""
    return o * jax.nn.sigmoid(_mm(h, w["self_attn.gate_proj.weight"], quant))[..., None]


def swiglu(x, gate_w, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate_w, quant)) * _mm(x, up, quant), down, quant)


def router(cfg, w, x, quant):
    """x (S, H) -> dense weights (S, E): zero but for each token's chosen
    experts (assumed: sigmoid scores, the k largest, normalised, scaled)."""
    s = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], quant))
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    chosen = chosen * cfg["moe_routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(chosen)


def sparse_feed_forward(cfg, w, x, quant):
    """x (S, H) -> (S, H): every expert on every token, one after another,
    weighted by the router (zero where it was not chosen), plus the shared
    expert."""
    weights = router(cfg, w, x, quant)

    def one(y, e):
        gate_w, up, down, we = e
        return y + we[:, None] * swiglu(x, gate_w, up, down, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
                         w["mlp.experts.down_proj"], weights.T))
    return y + swiglu(x, w["mlp.shared_experts.gate_proj.weight"],
                      w["mlp.shared_experts.up_proj.weight"],
                      w["mlp.shared_experts.down_proj.weight"], quant)


def layer(cfg, i, w, x, positions, quant=None):
    """Decoder layer ``i`` of one sequence, x (S, H); ``w`` maps the
    layer's short names to weights; a layer with a router is a sparse one."""
    h = rms_norm(x, w["input_layernorm.weight"], cfg["rms_norm_eps"])
    x = x + attention_layer(cfg, i, w, h, positions, quant)
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
        x = layer(cfg, i, layer_weights(weights, i), x, pos, quant)
    x = rms_norm(x, weights["model.norm.weight"], cfg["rms_norm_eps"])
    return _mm(x, weights["lm_head.weight"], quant)
