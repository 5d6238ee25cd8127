"""``kimi_linear``: a decoder whose layers mix in two ways — KDA (Kimi
Delta Attention, a gated delta-rule linear attention with a per-channel
decay and a per-sequence state) and latent (MLA) attention without rotary
— over a sparse feed-forward with a shared expert, on the serving path
(Kimi-Linear-48B-A3B-Instruct's ``config.json``, ``model_type:
kimi_linear``; arXiv:2510.26692 section 3).

Layer ``i`` (0-based) is KDA where ``i + 1`` is in
``linear_attn_config.kda_layers`` and MLA where it is in
``full_attn_layers``; the lists stand as published and are read to the
depth.  ``x = RMSNorm(h)``:

* **KDA**, ``H`` heads of ``d_k = d_v = linear_attn_config.head_dim``.
  ``q, k, v = SiLU(conv(x . W_q)), SiLU(conv(x . W_k)), SiLU(conv(x .
  W_v))``; ``conv`` a causal depthwise convolution over positions (kernel
  ``short_conv_kernel_size``, tap ``K - 1`` on the current input, no
  bias); ``q, k`` L2-normalised a head (``x * rsqrt(sum x^2 + 1e-6)``),
  ``q`` times ``d_k^-0.5``.  Log-decay a channel ``g = -exp(A_log[h]) *
  softplus(x . W_fa . W_fb + dt_bias)``, ``alpha = exp(g)``; write strength
  ``beta = sigmoid(x . W_b)`` a head.  State ``S (d_k, d_v)`` a head,
  float32: ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``,
  ``o_t = S_t^T q_t``.  ``y = (RMSNorm_head(o) * sigmoid(x . W_ga . W_gb))
  . W_o``.
* **MLA, no rotary** (``mla_use_nope``): ``deepseek_v3``'s equations and
  code (``mla_project``, ``absorbed_queries``, ``absorbed_output``); the
  ``qk_rope_head_dim`` columns of q and of the shared key enter the score
  as they are projected.  Position reaches the model through the KDA
  layers alone.
* Feed-forward: a SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers, then ``expert_layer.expert_layer``
  under this config's ``router`` with ``held=experts_held``: the stacks
  carry the experts this chip holds (all of them where ``experts_held`` is
  None), the router keeps its published width.

**What a sequence holds has two kinds.**  The MLA layers' latent lives in
one paged pool ``(L_mla, P, page, width)`` as ``deepseek_v3``'s does.  The
KDA layers' state is a fixed-size ENTRY a sequence: ``S (L_kda, N, H,
d_k, d_v)`` float32 and the convolutions' last ``K - 1`` inputs ``(L_kda,
N, K - 1, 3 H d)``; entry ``s < slots`` is decode slot ``s``'s running
state, the entries behind them are the prefix cache's snapshots
(``PagedKVCache``'s state kind).  ``pools = (latent pool, S, conv)`` is
donated to and returned by every program.  A row's entry rides the LAST
column of the page-table operand ``(B, W + 1)``; a decode call's row
``b`` is entry ``b``.  Two forms of the recurrence:

* ``kda_chunk_scan``: a call's positions in chunks of ``KDA_CHUNK``
  against the incoming state, exact: with ``G_t`` the running sum of ``g``
  inside a chunk, ``A_ti = sum_d k_td k_id exp(G_td - G_id)`` (``i < t``),
  ``(I + Diag(beta) A) U = Diag(beta) (V - (exp(G) * K) S_0)``, ``O =
  (exp(G) * Q) S_0 + tril(B) U`` with ``B`` as ``A`` with q for k_t and
  ``i <= t``, ``S_C = Diag(exp(G_C)) S_0 + sum_i Diag(exp(G_C - G_i)) k_i
  u_i^T``.  Every ``exp`` is of a difference taken pairwise (``<= 0``).
* one position a row for ``decode_n``: ``ops/pallas/kda_decode.py``.

A padded position (at or past ``lengths``) has ``g = 0`` and ``beta = 0``
and leaves state and convolution inputs as they were; a call that starts
at position 0 starts from the zero state whatever the entry holds.

What the state does not compose with yet is refused by ``ServingEngine``
in one place (``kv_layout_ = "latent+state"``).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .deepseek_v3 import (_DENSE_KEYS, CallCounts, ShapesUntilLoaded,
                          _pad_columns, absorbed_output, absorbed_queries,
                          expanded_attend, feed_forward, mla_project)
from .expert_layer import (EXPERT_KEYS, ROUTE_COUNTS, ROUTER, ROUTER_BIAS,
                           SHARED_KEYS, Router)
from .llama_decode import (PagedOnlyDense, _logits, _rms,
                           chunked_prefill_shim, decode_scan, emit_fn)

KDA_CHUNK = 64
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_QKV = ("q", "k", "v")
# one device call's counts, in this order (``CallCounts``)
CALL_COUNTS = ("layer_calls",) + ROUTE_COUNTS + (
    "latent_tokens_read", "kda_rows_stepped", "kda_chunk_positions")
_COUNTERS = {
    **{k: CallCounts.counters[k] for k in ("layer_calls",) + ROUTE_COUNTS},
    "latent_tokens_read": ("serving_latent_tokens_read_total",
                           "latent cache positions the decode rows attended "
                           "to, summed over steps and latent layers"),
    "kda_rows_stepped": ("serving_kda_rows_stepped_total",
                         "running states a decode step read and wrote, "
                         "summed over steps and KDA layers"),
    "kda_chunk_positions": ("serving_kda_chunk_positions_total",
                            "prompt positions the chunk form took into a "
                            "state, summed over lane calls and KDA layers")}

_DENSE_REASON = (
    "a latent+state serving factory is paged-only: the dense wave cache "
    "stores per-head K and V and has no state entry a sequence — route "
    "with policy='paged'")

_LINEAR_48B = {
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                   22, 23, 25, 26],
    "num_heads": 32, "short_conv_kernel_size": 4}


@dataclasses.dataclass
class KimiLinearConfig:
    """The published keys as they stand (defaults:
    Kimi-Linear-48B-A3B-Instruct).  ``experts_held``: the ids of the
    experts this chip holds, in the stacks' order (None: all
    ``num_experts``, which stays the router's width)."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 72
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    num_expert_group: int = 1
    topk_group: int = 1
    use_grouped_topk: bool = True
    routed_scaling_factor: float = 2.446
    kv_lora_rank: int = 512
    q_lora_rank: Any = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Any = None
    linear_attn_config: Any = dataclasses.field(
        default_factory=lambda: dict(_LINEAR_48B))
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    experts_held: Any = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                          ("moe_layer_freq", 1), ("hidden_act", "silu"),
                          ("tie_word_embeddings", False),
                          ("mla_use_nope", True)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"kimi_linear: {key}={getattr(self, key)!r} is not "
                    f"computed here (only {want!r})")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention expands one key/value head "
                             "a query head")
        lin = self.linear_attn_config
        kinds = [(i + 1 in lin["kda_layers"], i + 1 in lin["full_attn_layers"])
                 for i in range(self.num_hidden_layers)]
        if any(a == b for a, b in kinds):
            raise ValueError("every layer is one of kda_layers and "
                             "full_attn_layers")
        if self.experts_held is not None:
            self.experts_held = tuple(int(e) for e in self.experts_held)

    qk_head_dim = property(
        lambda self: self.qk_nope_head_dim + self.qk_rope_head_dim)
    latent_width = property(
        lambda self: self.kv_lora_rank + self.qk_rope_head_dim)
    kda_heads = property(lambda self: self.linear_attn_config["num_heads"])
    kda_dim = property(lambda self: self.linear_attn_config["head_dim"])
    conv_kernel = property(
        lambda self: self.linear_attn_config["short_conv_kernel_size"])
    # the two low-rank gates' rank (assumed: the KDA head size)
    gate_rank = kda_dim

    @property
    def router(self) -> Router:
        return Router(k=self.num_experts_per_token,
                      n_experts=self.num_experts,
                      scaling=self.routed_scaling_factor,
                      normalise=self.moe_renormalize,
                      scoring=self.moe_router_activation_func,
                      groups=(self.num_expert_group, self.topk_group))

    @property
    def n_held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else len(self.experts_held)

    def is_kda(self, i: int) -> bool:
        return i + 1 in self.linear_attn_config["kda_layers"]

    def layers_of(self, kda: bool) -> list:
        return [i for i in range(self.num_hidden_layers)
                if self.is_kda(i) == kda]

    def sparse_layer(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    @staticmethod
    def tiny(**over):
        """The CPU tests' size, every ratio kept: 4 heads (KDA 16 x 16;
        MLA nope 16 / rope 8 / v 16, rank 32) over a hidden size of 64,
        five layers KDA KDA MLA KDA MLA, layer 0 dense, then 8 experts (3
        a token) and one shared."""
        cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=176,
                   moe_intermediate_size=24, num_hidden_layers=5,
                   num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                   num_experts=8, num_experts_per_token=3, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   linear_attn_config={
                       "kda_layers": [1, 2, 4], "full_attn_layers": [3, 5],
                       "head_dim": 16, "num_heads": 4,
                       "short_conv_kernel_size": 4},
                   max_position_embeddings=512, dtype=jnp.float32)
        cfg.update(over)
        return KimiLinearConfig(**cfg)


# -- leaves ---------------------------------------------------------------
def layer_leaf_shapes(cfg: KimiLinearConfig, i: int) -> dict:
    """Layer ``i``'s leaves, short name -> shape (linear weights (in,
    out); a convolution's (channels, taps); the held experts stacked)."""
    H = cfg.hidden_size
    shapes = {"input_layernorm.weight": (H,)}
    if cfg.is_kda(i):
        D, r = cfg.kda_heads * cfg.kda_dim, cfg.gate_rank
        for n in _QKV:
            shapes[f"self_attn.{n}_proj.weight"] = (H, D)
            shapes[f"self_attn.{n}_conv1d.weight"] = (D, cfg.conv_kernel)
        shapes.update({
            "self_attn.f_a_proj.weight": (H, r),
            "self_attn.f_b_proj.weight": (r, D),
            "self_attn.dt_bias": (D,),
            "self_attn.A_log": (cfg.kda_heads,),
            "self_attn.b_proj.weight": (H, cfg.kda_heads),
            "self_attn.g_a_proj.weight": (H, r),
            "self_attn.g_b_proj.weight": (r, D),
            "self_attn.o_norm.weight": (cfg.kda_dim,),
            "self_attn.o_proj.weight": (D, H)})
    else:
        nh = cfg.num_attention_heads
        shapes.update({
            "self_attn.q_proj.weight": (H, nh * cfg.qk_head_dim),
            "self_attn.kv_a_proj_with_mqa.weight": (H, cfg.latent_width),
            "self_attn.kv_a_layernorm.weight": (cfg.kv_lora_rank,),
            "self_attn.kv_b_proj.weight":
                (cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim
                                         + cfg.v_head_dim)),
            "self_attn.o_proj.weight": (nh * cfg.v_head_dim, H)})
    shapes["post_attention_layernorm.weight"] = (H,)
    if cfg.sparse_layer(i):
        E, I = cfg.n_held, cfg.moe_intermediate_size
        S = cfg.num_shared_experts * I
        shapes[ROUTER] = (H, cfg.num_experts)
        shapes[ROUTER_BIAS] = (cfg.num_experts,)
        shapes.update(zip(EXPERT_KEYS, ((E, H, I), (E, H, I), (E, I, H))))
        shapes.update(zip(SHARED_KEYS, ((H, S), (H, S), (S, H))))
    else:
        I = cfg.intermediate_size
        shapes.update(zip(_DENSE_KEYS, ((H, I), (H, I), (I, H))))
    return shapes


def leaf_shapes(cfg: KimiLinearConfig) -> dict:
    """Every leaf under its ``state_dict`` name -> shape."""
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, cfg.hidden_size)}
    for i in range(cfg.num_hidden_layers):
        shapes.update({f"model.layers.{i}.{k}": s
                       for k, s in layer_leaf_shapes(cfg, i).items()})
    shapes["model.norm.weight"] = (cfg.hidden_size,)
    shapes["lm_head.weight"] = (cfg.hidden_size, cfg.vocab_size)
    return shapes


# -- KDA: the layer's mathematics -----------------------------------------
def kda_project(cfg, lp, h):
    """h (B, T, H) -> the three projections side by side (B, T, 3 D),
    before their convolution."""
    with jax.named_scope("kda.project"):
        return jnp.concatenate(
            [h @ lp[f"self_attn.{n}_proj.weight"] for n in _QKV], axis=-1)


def kda_conv(cfg, lp, x, taps, n_real):
    """The causal depthwise convolution with its state: x (B, T, 3 D),
    ``taps`` (B, K - 1, 3 D) the inputs before x, ``n_real`` (B,) how many
    of x's positions are real -> (SiLU(conv) (B, T, 3 D) float32, the
    taps after the last real position)."""
    K = cfg.conv_kernel
    with jax.named_scope("kda.conv"):
        T = x.shape[1]
        w = jnp.concatenate([lp[f"self_attn.{n}_conv1d.weight"]
                             for n in _QKV], axis=0).astype(_F32)   # (3D, K)
        xs = jnp.concatenate([taps.astype(x.dtype), x], axis=1)
        xf = xs.astype(_F32)
        y = sum(w[:, j] * xf[:, j:j + T] for j in range(K))
        after = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
            row, n, K - 1, 0))(xs, n_real)
        return jax.nn.silu(y), after.astype(taps.dtype)


def kda_gates(cfg, lp, h, real):
    """-> (g (B, T, nh, dk) float32 log-decay <= 0, beta (B, T, nh)
    float32), both zero at a position that is not ``real`` (B, T)."""
    nh, dk = cfg.kda_heads, cfg.kda_dim
    B, T, _ = h.shape
    with jax.named_scope("kda.gate"):
        f = (h @ lp["self_attn.f_a_proj.weight"]) \
            @ lp["self_attn.f_b_proj.weight"]
        g = -jnp.exp(lp["self_attn.A_log"].astype(_F32))[:, None] \
            * jax.nn.softplus(
                (f.astype(_F32) + lp["self_attn.dt_bias"].astype(_F32)
                 ).reshape(B, T, nh, dk))
        beta = jax.nn.sigmoid(
            (h @ lp["self_attn.b_proj.weight"]).astype(_F32))
        keep = real[..., None]
        return jnp.where(keep[..., None], g, 0.0), jnp.where(keep, beta, 0.0)


def kda_heads_of(cfg, qkv):
    """SiLU(conv) (B, T, 3 D) float32 -> q, k (normalised a head, q
    scaled), v, each (B, T, nh, d)."""
    B, T, _ = qkv.shape
    nh, d = cfg.kda_heads, cfg.kda_dim
    q, k, v = (a.reshape(B, T, nh, d) for a in jnp.split(qkv, 3, axis=-1))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    return unit(q) * d ** -0.5, unit(k), v


def kda_out(cfg, lp, h, o):
    """o (B, T, nh, dv) float32 -> the layer's output (B, T, H): a head's
    RMSNorm, the sigmoid output gate, ``W_o``."""
    B, T, nh, dv = o.shape
    with jax.named_scope("kda.out"):
        gate = ((h @ lp["self_attn.g_a_proj.weight"])
                @ lp["self_attn.g_b_proj.weight"]).astype(_F32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.rms_norm_eps) \
            * lp["self_attn.o_norm.weight"].astype(_F32)
        o = o * jax.nn.sigmoid(gate.reshape(B, T, nh, dv))
        return o.reshape(B, T, nh * dv).astype(h.dtype) \
            @ lp["self_attn.o_proj.weight"]


def kda_chunk(q, k, v, g, beta, S0):
    """One chunk against its incoming state (module docstring): q, k, g
    (B, C, nh, dk), v (B, C, nh, dv), beta (B, C, nh), S0 (B, nh, dk, dv),
    all float32 -> (o (B, C, nh, dv), S_C)."""
    C = q.shape[1]
    G = jnp.cumsum(g, axis=1)
    t = jnp.arange(C)
    seen = t[:, None] >= t[None, :]                        # (t, i): i <= t
    # exp(G_t - G_i), i <= t, else 0: never 1 / exp(G_i)
    E = jnp.exp(jnp.where(seen[None, :, :, None, None],
                          G[:, :, None] - G[:, None, :], -jnp.inf))
    kE = k[:, None] * E                                    # (B, t, i, nh, dk)
    A = jnp.einsum("bthd,btihd->bhti", k, kE, precision=_HI)
    Bm = jnp.einsum("bthd,btihd->bhti", q, kE, precision=_HI)
    gam = jnp.exp(G)
    rhs = beta[..., None] * (v - jnp.einsum(
        "bthd,bhde->bthe", k * gam, S0, precision=_HI))
    M = jnp.where((t[:, None] > t[None, :])[None, None],
                  jnp.swapaxes(beta, 1, 2)[..., None] * A, 0.0) \
        + jnp.eye(C, dtype=_F32)
    U = jax.scipy.linalg.solve_triangular(
        M, jnp.swapaxes(rhs, 1, 2), lower=True, unit_diagonal=True)
    o = jnp.einsum("bthd,bhde->bthe", q * gam, S0, precision=_HI) \
        + jnp.einsum("bhti,bhie->bthe", Bm, U, precision=_HI)
    S = gam[:, -1][..., None] * S0 + jnp.einsum(
        "bihd,bhie->bhde", kE[:, -1], U, precision=_HI)
    return o, S


def kda_chunk_scan(q, k, v, g, beta, S0, chunk: int = KDA_CHUNK):
    """A call's T positions in chunks of ``chunk`` (the last padded with
    inert positions), the state carried from one to the next."""
    B, T = q.shape[:2]
    with jax.named_scope("kda.scan"):
        pad = -T % chunk
        parts = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                 for a in (q, k, v, g, beta)]
        parts = [jnp.moveaxis(a.reshape((B, (T + pad) // chunk, chunk)
                                        + a.shape[2:]), 1, 0) for a in parts]

        def body(S, xs):
            o, S = kda_chunk(*xs, S)
            return S, o
        S, o = jax.lax.scan(body, S0, tuple(parts))
        o = jnp.moveaxis(o, 0, 1).reshape((B, T + pad) + o.shape[3:])
        return o[:, :T], S


def kda_mix(cfg, lp, h, real, n_real, taps, recur):
    """A KDA layer's token mixing over (B, T, H): ``recur(q, k, v, g,
    beta) -> (o (B, T, nh, dv) float32, extra)`` owns the state.  Returns
    (y (B, T, H), the convolution's taps after the call, extra)."""
    qkv, taps = kda_conv(cfg, lp, kda_project(cfg, lp, h), taps, n_real)
    g, beta = kda_gates(cfg, lp, h, real)
    o, extra = recur(*kda_heads_of(cfg, qkv), g, beta)
    return kda_out(cfg, lp, h, o), taps, extra


def layer_math(cfg, lp, x, mix):
    """One layer over (B, T, H): ``mix(h) -> (attention output (B, T, H),
    extra)`` is the layer's KDA or MLA with its cache strategy.  Returns
    (x, extra, counts | None)."""
    h = _rms(x, lp["input_layernorm.weight"], cfg.rms_norm_eps)
    attn, extra = mix(h)
    x = x + attn
    h2 = _rms(x, lp["post_attention_layernorm.weight"], cfg.rms_norm_eps)
    y, counts = feed_forward(cfg, lp, h2)
    return x + y, extra, counts


def zero_state(cfg, B: int, dtype):
    nh, d, K = cfg.kda_heads, cfg.kda_dim, cfg.conv_kernel
    return (jnp.zeros((B, nh, d, d), _F32),
            jnp.zeros((B, K - 1, 3 * nh * d), dtype))


def full_forward(cfg, outer, layers, ids, states=None, lengths=None):
    """Logits (B, S, V) float32 of whole sequences, no cache: KDA in the
    chunk form from ``states`` (one ``(S, taps)`` a KDA layer; None: zero),
    MLA expanded.  With ``states`` given it also returns the states after
    ``lengths`` (default: every) positions, for the tests."""
    B, S = ids.shape
    x = jnp.take(outer["model.embed_tokens.weight"], ids, axis=0)
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    n_real = jnp.full((B,), S, jnp.int32) if lengths is None \
        else jnp.asarray(lengths, jnp.int32)
    real = pos[None, :] < n_real[:, None]
    out_states = []
    for i, lp in enumerate(layers):
        if cfg.is_kda(i):
            S0, taps = zero_state(cfg, B, x.dtype) if states is None \
                else states[len(out_states)]

            def mix(h, lp=lp, S0=S0, taps=taps):
                y, taps2, S1 = kda_mix(
                    cfg, lp, h, real, n_real, taps,
                    lambda q, k, v, g, b: kda_chunk_scan(q, k, v, g, b, S0))
                return y, (S1, taps2)
        else:
            def mix(h, lp=lp):
                return expanded_attend(cfg, lp, mask)(
                    *mla_project(cfg, lp, h, pos))
        x, extra, _ = layer_math(cfg, lp, x, mix)
        if cfg.is_kda(i):
            out_states.append(extra)
    x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
    logits = _logits(cfg, outer, x).astype(_F32)
    return logits if states is None else (logits, out_states)


# -- the model object -----------------------------------------------------
class KimiLinearForCausalLM(ShapesUntilLoaded):
    """The model object ``ServingEngine`` and the benchmark hold, as
    ``DeepseekV3ForCausalLM`` is one (``ShapesUntilLoaded``)."""

    kv_layout_ = "latent+state"  # what ServingEngine's refusals read
    layer_leaf_shapes = staticmethod(layer_leaf_shapes)
    full_forward = staticmethod(full_forward)

    def serving_decode_factory(self, *, scan_layers=True, **build):
        """What ``ServingEngine`` asks a model for: its paged serving
        factory (``state_serving_decode_factory``) from the geometry, the
        number of snapshot entries among it.  ``scan_layers`` changes
        nothing (the layers are unrolled: they differ in kind); every
        other option of the Llama factories must be unset."""
        geometry = ("max_len", "page_size", "n_pool_pages", "batch_capacity",
                    "chunked_prefill", "n_state_snapshots")
        unset = {k: v for k, v in build.items()
                 if k not in geometry and v is not None}
        if unset:
            raise ValueError("a latent+state serving factory takes its "
                             f"geometry alone, not {sorted(unset)}")
        return state_serving_decode_factory(
            self, **{k: build[k] for k in geometry
                     if build.get(k) is not None})


# -- the paged serving factory --------------------------------------------
def state_paged_decode_factory(model: KimiLinearForCausalLM,
                               page_size: int = 64,
                               n_pool_pages: int = 256,
                               n_state_entries: int = 8,
                               chunked_prefill: int | None = None,
                               emit: str = "token",
                               decode_kernel: bool = True):
    """Compiled prefill and decode over a paged latent pool and the
    sequences' state entries, the contract of
    ``llama_paged_decode_factory``: returns ``(outer, layers, pools,
    prefill, decode_step, decode_n)`` with fixed shapes.  ``pools`` is
    ``(latent pool (L_mla, P, page, width), S (L_kda, N, nh, dk, dv)
    float32, taps (L_kda, N, K - 1, 3 nh d))``, donated to and returned by
    every program and updated in place.  ``page_tables`` is ``(B, W + 1)``:
    the latent pages by position, then the row's state entry (a decode
    call's row ``b`` is entry ``b`` and reads no such column).  The
    programs count (``CALL_COUNTS``; ``decode_n.counts``).
    ``decode_kernel=False`` runs the decode step's recurrence in
    jax.numpy (the tests' oracle)."""
    from ...ops.pallas.kda_decode import (kda_decode_step,
                                          kda_decode_step_reference)
    from ...ops.pallas.latent_paged_attention import (
        latent_paged_attention, page_width)

    cfg = model.config
    outer, layers = model.decode_params()
    kda_at = {i: j for j, i in enumerate(cfg.layers_of(True))}
    mla_at = {i: j for j, i in enumerate(cfg.layers_of(False))}
    L_k, L_m = len(kda_at), len(mla_at)
    nh = cfg.num_attention_heads
    rank, width = cfg.kv_lora_rank, page_width(cfg.latent_width)
    dtype = outer["model.embed_tokens.weight"].dtype
    sm_scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    if chunked_prefill is None or chunked_prefill % page_size:
        raise ValueError("chunked_prefill must be a multiple of page_size "
                         f"({page_size}): prefill runs in chunks only")
    _emit = emit_fn(emit)
    tally = CallCounts(CALL_COUNTS, _COUNTERS)
    step_fn = kda_decode_step if decode_kernel else kda_decode_step_reference

    def _latent(lp, j, pool, write, tables, seq_lens, starts):
        """MLA layer ``j`` (of its kind): write the positions' latent in
        place, then the absorbed kernel over the rows' pages, the heads in
        groups of at most 4096 query rows (what the kernel was compiled
        with elsewhere)."""
        def mix(h, pos):
            q_nope, q_rope, latent = mla_project(cfg, lp, h, pos)
            T = q_nope.shape[1]
            new_pool = write(pool, j, _pad_columns(latent, width))
            q = _pad_columns(absorbed_queries(cfg, lp, q_nope, q_rope),
                             width)
            per = max(d for d in range(1, nh + 1)
                      if nh % d == 0 and (d == 1 or d * T <= 4096))
            with jax.named_scope("mla.attend"):
                ctx = jnp.concatenate([
                    latent_paged_attention(
                        q[:, a * T:(a + per) * T], new_pool, j, tables,
                        seq_lens, starts, T, rank, sm_scale)
                    for a in range(0, nh, per)], axis=1)
            return absorbed_output(cfg, lp, ctx, T), new_pool
        return mix

    def _stack(layers, x, pools, kda_mix_at, mla_mix_at):
        """The unrolled stack -> (x, pools, counts (1 + len(ROUTE_COUNTS),):
        expert-layer calls and their ``ROUTE_COUNTS`` summed)."""
        pool, S, taps = pools
        total = jnp.zeros((1 + len(ROUTE_COUNTS),), jnp.int32)
        for i, lp in enumerate(layers):
            if i in kda_at:
                x, (S, taps), counts = layer_math(
                    cfg, lp, x, kda_mix_at(lp, kda_at[i], S, taps))
            else:
                x, pool, counts = layer_math(
                    cfg, lp, x, mla_mix_at(lp, mla_at[i], pool))
            if counts is not None:
                total = total + jnp.concatenate(
                    [jnp.ones((1,), jnp.int32), counts])
        return x, (pool, S, taps), total

    def _decode_step(outer, layers, tok, page_tables, lengths, pools,
                     active):
        """One position a row -> (emission, pools', counts).  ``active``
        (B,): the rows whose state this call may touch."""
        B = tok.shape[0]
        tables = page_tables[:, :-1]
        pos = lengths[:, None]
        real, n_real = active[:, None], active.astype(jnp.int32)

        def write(pool, j, latent):          # (B, 1, width) at each row's end
            pages = jnp.take_along_axis(
                tables, (lengths // page_size)[:, None], 1)[:, 0]
            return pool.at[j, pages, lengths % page_size].set(
                latent[:, 0].astype(pool.dtype))

        def kda_mix_at(lp, j, S, taps):
            def mix(h):
                def recur(q, k, v, g, beta):
                    o, S2 = step_fn(S, j, q[:, 0], k[:, 0], v[:, 0],
                                    jnp.exp(g[:, 0]), beta[:, 0], active)
                    return o[:, None], S2
                y, t2, S2 = kda_mix(cfg, lp, h, real, n_real, taps[j, :B],
                                    recur)
                return y, (S2, jax.lax.dynamic_update_slice(
                    taps, t2[None], (j, 0, 0, 0)))
            return mix

        def mla_mix_at(lp, j, pool):
            mix = _latent(lp, j, pool, write, tables, lengths + 1, lengths)
            return lambda h: mix(h, pos)

        x = jnp.take(outer["model.embed_tokens.weight"], tok, axis=0)[:, None]
        x, pools, counts = _stack(layers, x, pools, kda_mix_at, mla_mix_at)
        x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
        n_act = jnp.sum(active).astype(jnp.int32)
        more = jnp.stack([
            jnp.sum(jnp.where(active, lengths + 1, 0)).astype(jnp.int32)
            * L_m, n_act * L_k, jnp.zeros((), jnp.int32)])
        return (_emit(_logits(cfg, outer, x[:, 0])), pools,
                jnp.concatenate([counts, more]))

    @partial(jax.jit, donate_argnums=(5,))
    def decode_step(outer, layers, tok, page_tables, lengths, pools):
        """-> (emission, pools', counts (len(CALL_COUNTS),)); a row of
        length 0 is an idle slot and its state stands."""
        return _decode_step(outer, layers, tok, page_tables, lengths, pools,
                            lengths > 0)

    @partial(jax.jit, donate_argnums=(6,))
    def _chunk_program(outer, layers, chunk, start, page_tables, lengths,
                       pools, x_last):
        """T = m x C tokens at absolute positions start..start+T-1: writes
        their latent pages, attends to every pool position < start + T,
        takes the real ones (< lengths) into the rows' states (from zero
        where ``start`` is 0), and harvests the hidden state of each
        sequence's (length - 1) row when it falls inside."""
        B, T = chunk.shape
        tables, entry = page_tables[:, :-1], page_tables[:, -1]
        n_real = jnp.clip(lengths - start, 0, T).astype(jnp.int32)
        real = jnp.arange(T)[None, :] < n_real[:, None]
        fresh = jnp.asarray(start == 0)
        starts = jnp.full((B,), start, jnp.int32)

        def write(pool, j, latent):          # whole pages scatter
            npg = T // page_size
            ids = jax.lax.dynamic_slice_in_dim(
                tables, start // page_size, npg, 1).reshape(-1)
            return pool.at[j, ids].set(
                latent.reshape(B * npg, page_size, width).astype(pool.dtype))

        def kda_mix_at(lp, j, S, taps):
            def mix(h):
                S0 = jnp.where(fresh, 0.0, S[j, entry])
                t0 = jnp.where(fresh, 0, taps[j, entry])
                y, t2, S2 = kda_mix(
                    cfg, lp, h, real, n_real, t0,
                    lambda q, k, v, g, b: kda_chunk_scan(q, k, v, g, b, S0))
                return y, (S.at[j, entry].set(S2),
                           taps.at[j, entry].set(t2))
            return mix

        def mla_mix_at(lp, j, pool):
            mix = _latent(lp, j, pool, write, tables, lengths, starts)
            return lambda h: mix(h, start + jnp.arange(T))

        x = jnp.take(outer["model.embed_tokens.weight"], chunk, axis=0)
        x, pools, counts = _stack(layers, x, pools, kda_mix_at, mla_mix_at)
        idx = jnp.clip(lengths - 1 - start, 0, T - 1)
        row = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32),
                                  1)[:, 0]
        hit = ((lengths - 1 >= start) & (lengths - 1 < start + T))[:, None]
        more = jnp.stack([jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                          jnp.sum(n_real) * L_k])
        return (jnp.where(hit, row, x_last), pools,
                jnp.concatenate([counts, more.astype(jnp.int32)]))

    def _prefill_chunk(outer, layers, chunk, start, page_tables, lengths,
                       pools, x_last, lora=None):
        x_last, pools, counts = _chunk_program(
            outer, layers, chunk, start, page_tables, lengths, pools, x_last)
        tally.add("prefill", counts)
        return x_last, pools
    _prefill_chunk._cache_size = _chunk_program._cache_size
    _prefill_chunk.program = _chunk_program      # for ahead-of-time compiles

    @jax.jit
    def _finish_prefill(outer, x_last, grammar=None):
        x = _rms(x_last, outer["model.norm.weight"], cfg.rms_norm_eps)
        return _emit(_logits(cfg, outer, x))

    prefill = chunked_prefill_shim(_prefill_chunk, _finish_prefill,
                                   chunked_prefill, cfg.hidden_size, dtype)

    @partial(jax.jit, donate_argnums=(5,), static_argnums=(6,))
    def _decode_n(outer, layers, tok, page_tables, lengths, pools, n):
        active = lengths > 0      # as the call starts: a scan step adds one
        (emits, counts), tok, pools = decode_scan(
            lambda tok, lens, pools: _decode_step(
                outer, layers, tok, page_tables, lens, pools, active),
            tok, lengths, pools, n)
        return emits, tok, pools, jnp.sum(counts, axis=0)

    def decode_n(outer, layers, tok, page_tables, lengths, pools, n):
        """``n`` decode steps in ONE compiled program; returns (emits (n,
        B, ...), next_tok (B,), pools'). ``pools`` is DONATED."""
        emits, tok, pools, counts = _decode_n(outer, layers, tok,
                                              page_tables, lengths, pools, n)
        tally.add("decode", counts)
        return emits, tok, pools
    decode_n._jit_inner = (_decode_n,)
    decode_n.counts = tally

    def step(outer, layers, tok, page_tables, lengths, pools):
        return decode_step(outer, layers, tok, page_tables, lengths,
                           pools)[:2]

    d, K = cfg.kda_dim, cfg.conv_kernel
    pools = (jnp.zeros((L_m, n_pool_pages, page_size, width), dtype),
             jnp.zeros((L_k, n_state_entries, cfg.kda_heads, d, d), _F32),
             jnp.zeros((L_k, n_state_entries, K - 1, 3 * cfg.kda_heads * d),
                       dtype))
    return outer, layers, pools, prefill, step, decode_n


def state_serving_decode_factory(model: KimiLinearForCausalLM,
                                 max_len: int = 256, page_size: int = 64,
                                 n_pool_pages: int = 256,
                                 batch_capacity: int = 8,
                                 chunked_prefill: int | None = None,
                                 n_state_snapshots: int = 0,
                                 decode_kernel: bool = True):
    """The object ``ServingEngine`` consumes, for a latent pool beside
    state entries: paged only.  Entries ``0 .. batch_capacity - 1`` are the
    decode slots' running states, the ``n_state_snapshots`` behind them the
    prefix cache's; ``state_copy`` moves one entry onto another."""
    cfg = model.config
    paged = state_paged_decode_factory(
        model, page_size=page_size, n_pool_pages=n_pool_pages,
        n_state_entries=batch_capacity + n_state_snapshots,
        chunked_prefill=chunked_prefill, decode_kernel=decode_kernel)
    pool, S, taps = paged[2]

    @partial(jax.jit, donate_argnums=(0,))
    def _copy(pools, src, dst):
        pool, S, taps = pools
        return (pool, S.at[:, dst].set(S[:, src]),
                taps.at[:, dst].set(taps[:, src]))

    class _Serving:
        dense = PagedOnlyDense(_DENSE_REASON)
        paged_parts = paged
        capacity = batch_capacity
        max_len_ = max_len
        page_size_ = page_size
        n_pool_pages_ = n_pool_pages
        n_state_snapshots_ = n_state_snapshots
        chunked_prefill_ = chunked_prefill
        # chunks ONE lane call may span (``deepseek_v3``: the latent
        # kernel's query rows in VMEM; here the heads go in groups)
        chunked_prefill_widest_ = 4
        kv_layout_ = "latent+state"
        # what a sequence holds: a latent page over the MLA layers, one
        # state entry over the KDA layers
        page_bytes_ = {"latent": pool.shape[0] * page_size * pool.shape[-1]
                       * pool.dtype.itemsize}
        state_entry_bytes_ = (S.nbytes + taps.nbytes) // S.shape[1]
        # a latent page's bytes were every layer a latent one
        page_bytes_all_latent_ = page_bytes_["latent"] \
            // pool.shape[0] * cfg.num_hidden_layers
        call_counts = paged[5].counts    # CallCounts: reset() / take()

        @staticmethod
        def state_copy(pools, src: int, dst: int):
            """Entry ``src`` of every KDA layer onto entry ``dst``, in
            place (``pools`` is DONATED): a snapshot taken or restored."""
            return _copy(pools, jnp.int32(src), jnp.int32(dst))

        def pick(self, lengths, capacity=None, shared_prefix=False,
                 expect_churn=False):
            return "paged", paged

    return _Serving()
