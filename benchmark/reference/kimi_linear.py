"""Plain reference: a ``kimi_linear`` decoder
(Kimi-Linear-48B-A3B-Instruct's ``config.json``; arXiv:2510.26692 section 3
and the ``KimiDeltaAttention`` module of flash-linear-attention, which the
Hugging Face model code calls) in straightforward jax.numpy.

float32 arithmetic at ``highest`` matmul precision, no kernels, no cache, no
state carried between calls, no chunking of the recurrence, no batching;
every held expert is computed for every token and masked by the router's
choice.  Layer ``i`` (0-based) is KDA where ``i + 1`` is in
``linear_attn_config.kda_layers`` and MLA where it is in
``full_attn_layers`` (the lists as published, read to the depth).  ``x =
RMSNorm(h)``, eps ``rms_norm_eps``:

* **KDA**: ``H = num_heads`` heads of ``d = head_dim`` (of
  ``linear_attn_config``).  ``q, k, v = SiLU(conv(x W_q)), SiLU(conv(x
  W_k)), SiLU(conv(x W_v))``; ``conv`` a causal depthwise convolution of
  ``short_conv_kernel_size`` taps over positions (tap ``K - 1`` on the
  current input, zeros before the sequence, no bias); ``q, k`` divided by
  ``sqrt(sum x^2 + 1e-6)`` a head, ``q`` times ``d^-0.5``.  ``g = -exp(A_log)
  * softplus(x W_fa W_fb + dt_bias)`` a channel, ``alpha = exp(g)``; ``beta
  = sigmoid(x W_b)`` a head.  The TOKEN RECURRENCE itself (``lax.scan`` over
  positions), state ``S (d, d)`` a head from zero: ``S_t = (I - beta_t k_t
  k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``.
  ``y = (RMSNorm_head(o) * sigmoid(x W_ga W_gb)) W_o``.
* **MLA without rotary** (``mla_use_nope``): ``q = x W_q`` -> heads x (nope
  | rope); ``[c_kv | k_rope] = x W_kva``; ``c = RMSNorm(c_kv)``; ``[k_nope |
  v] = c W_kvb`` a head; ``score = (q_nope k_nope + q_rope k_rope) /
  sqrt(nope + rope)``, nothing rotated; causal softmax; ``o = sum p v``;
  ``W_o``.
* Feed-forward: SwiGLU in the first ``first_k_dense_replace`` layers; then
  ``s = sigmoid(x W_g)`` over the router's whole width, chosen = top-k of
  ``s + b`` (one group), ``w = s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scaling_factor``, ``y = sum over the HELD chosen experts of w_k
  expert_k(x) + shared(x)``: ``experts_held`` names the experts whose
  matrices the stacks carry; what the others would add is left out.

``DT_BIAS_SHIFT`` is the one place where a drawn leaf is shaped
(``layer_weights``; the program's family adds the same number when it loads
the weights): the harness draws ``dt_bias`` about zero, which gives ``g ~
-0.7`` a token and a state that forgets in ten tokens, so that no fault in
carrying, snapshotting or restoring a state could be seen in what is
served; shifted, ``softplus ~ 0.004`` and a state remembers ~250 tokens.

Departures, each for memory alone: attention is taken over blocks of query
rows, the experts run one after another, and weights stay stored in
bfloat16 (the type the configuration states) and are widened where used.

``quant="int8"`` is the control: the same mathematics with every matmul's
operands rounded to int8 (weights per output channel, activations per
row), the nearest precision below the stated bfloat16.  Two planted faults,
not precisions: ``quant="state_dropped"`` zeroes every KDA state each
``STATE_DROP_EVERY`` positions (what a lost snapshot is),
``quant="decay_ignored"`` sets ``alpha = 1``.

Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512
DT_BIAS_SHIFT = -5.5
STATE_DROP_EVERY = 1024
_QKV = ("q", "k", "v")


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


def is_kda(cfg, i: int) -> bool:
    return i + 1 in cfg["linear_attn_config"]["kda_layers"]


# -- KDA ------------------------------------------------------------------
def short_conv(x, w):
    """x (S, D), w (D, K): ``y_t = sum_j w[:, j] x_{t - (K - 1) + j}``."""
    K = w.shape[1]
    S = x.shape[0]
    xs = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x], axis=0)
    return sum(w[:, j].astype(F32) * xs[j:j + S] for j in range(K))


def kda_attention(cfg, w, h, quant):
    """h (S, H) -> (S, H)."""
    lin = cfg["linear_attn_config"]
    nh, d = lin["num_heads"], lin["head_dim"]
    S = h.shape[0]

    def head_input(n):
        y = short_conv(_mm(h, w[f"self_attn.{n}_proj.weight"], quant),
                       w[f"self_attn.{n}_conv1d.weight"])
        return jax.nn.silu(y).reshape(S, nh, d)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q, k, v = (head_input(n) for n in _QKV)
    q, k = unit(q) * d ** -0.5, unit(k)
    f = _mm(_mm(h, w["self_attn.f_a_proj.weight"], quant),
            w["self_attn.f_b_proj.weight"], quant)
    g = -jnp.exp(w["self_attn.A_log"].astype(F32))[:, None] * jax.nn.softplus(
        (f + w["self_attn.dt_bias"].astype(F32)).reshape(S, nh, d))
    alpha = jnp.ones_like(g) if quant == "decay_ignored" else jnp.exp(g)
    beta = jax.nn.sigmoid(_mm(h, w["self_attn.b_proj.weight"], quant))
    keep = jnp.ones((S,), F32)
    if quant == "state_dropped":
        keep = (jnp.arange(S) % STATE_DROP_EVERY != 0).astype(F32)

    def step(state, x):
        q_t, k_t, v_t, a_t, b_t, keep_t = x
        state = a_t[..., None] * (state * keep_t)
        u = b_t[:, None] * (v_t - jnp.einsum("hd,hde->he", k_t, state,
                                             precision=HI))
        state = state + k_t[..., None] * u[:, None, :]
        return state, jnp.einsum("hd,hde->he", q_t, state, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((nh, d, d), F32),
                        (q, k, v, alpha, beta, keep))
    gate = _mm(_mm(h, w["self_attn.g_a_proj.weight"], quant),
               w["self_attn.g_b_proj.weight"], quant).reshape(S, nh, d)
    o = rms_norm(o, w["self_attn.o_norm.weight"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate)
    return _mm(o.reshape(S, nh * d), w["self_attn.o_proj.weight"], quant)


# -- MLA ------------------------------------------------------------------
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


def latent_attention(cfg, w, h, quant):
    """h (S, H) -> (S, H); no position enters (``mla_use_nope``)."""
    S = h.shape[0]
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = _mm(h, w["self_attn.q_proj.weight"], quant).reshape(S, nh, nope + rope)
    kva = _mm(h, w["self_attn.kv_a_proj_with_mqa.weight"], quant)
    c = rms_norm(kva[:, :rank], w["self_attn.kv_a_layernorm.weight"], cfg["rms_norm_eps"])
    kv = _mm(c, w["self_attn.kv_b_proj.weight"], quant).reshape(S, nh, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(kva[:, None, rank:], (S, nh, rope))], -1)
    o = attention(q, k, kv[..., nope:], 1.0 / jnp.sqrt(F32(nope + rope)))
    return _mm(o, w["self_attn.o_proj.weight"], quant)


# -- feed-forward ---------------------------------------------------------
def swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down, quant)


def router(cfg, w, x, quant):
    """x (S, H) -> dense weights (S, E) over the router's whole width: zero
    but for each token's chosen experts."""
    s = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], quant))
    bias = w["mlp.gate.e_score_correction_bias"].astype(F32)
    _, idx = jax.lax.top_k(s + bias[None, :], cfg["num_experts_per_token"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["moe_renormalize"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    chosen = chosen * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(chosen)


def routed_part(cfg, w, x, quant, held=None):
    """What the experts ``held`` (ids in the stacks' order; None: the
    stacks carry every expert) add to each token: every held expert on
    every token, one after another, weighted by the router (zero where it
    was not chosen)."""
    weights = router(cfg, w, x, quant)
    if held is not None:
        weights = weights[:, jnp.asarray(held)]

    def one(y, e):
        gate, up, down, we = e
        return y + we[:, None] * swiglu(x, gate, up, down, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
                         w["mlp.experts.down_proj"], weights.T))
    return y


def shared_part(w, x, quant):
    return swiglu(x, w["mlp.shared_experts.gate_proj.weight"],
                  w["mlp.shared_experts.up_proj.weight"],
                  w["mlp.shared_experts.down_proj.weight"], quant)


def layer(cfg, i, w, x, quant=None):
    """Decoder layer ``i`` of one sequence, x (S, H); ``w`` maps the layer's
    short names to weights; a layer with a router is a sparse one."""
    mm_quant = quant if quant == "int8" else None
    h = rms_norm(x, w["input_layernorm.weight"], cfg["rms_norm_eps"])
    if is_kda(cfg, i):
        x = x + kda_attention(cfg, w, h, quant)
    else:
        x = x + latent_attention(cfg, w, h, mm_quant)
    h = rms_norm(x, w["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    if "mlp.gate.weight" in w:
        return x + routed_part(cfg, w, h, mm_quant, cfg.get("experts_held")) \
            + shared_part(w, h, mm_quant)
    return x + swiglu(h, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
                      w["mlp.down_proj.weight"], mm_quant)


def shift_decay(name: str, value):
    """The drawn ``dt_bias`` with ``DT_BIAS_SHIFT`` added, in the type it is
    stored in; any other leaf as it is (module docstring)."""
    if not name.endswith("self_attn.dt_bias"):
        return value
    return (value.astype(F32) + DT_BIAS_SHIFT).astype(value.dtype)


def layer_weights(weights: dict, i: int) -> dict:
    p = f"model.layers.{i}."
    return {k[len(p):]: shift_decay(k, v) for k, v in weights.items()
            if k.startswith(p)}


def logits(cfg, weights, tokens, quant=None):
    """Logits (S, V) of one sequence ``tokens`` (S,)."""
    x = jnp.take(weights["model.embed_tokens.weight"], tokens, axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(cfg, i, layer_weights(weights, i), x, quant)
    x = rms_norm(x, weights["model.norm.weight"], cfg["rms_norm_eps"])
    return _mm(x, weights["lm_head.weight"], quant if quant == "int8" else None)
