"""``laguna``: a decoder whose layers attend in two ways — full (global)
attention and sliding-window attention, with head counts of their own —
over a sparse feed-forward with one shared expert, on the serving path
(Laguna-XS.2's ``config.json``, ``model_type: laguna``).

Layer ``i`` has kind ``layer_types[i]`` and ``n_i =
num_attention_heads_per_layer[i]`` query heads over ``num_key_value_heads``
KV heads of ``head_dim``.  ``x = RMSNorm(h)``:

* ``q = x . W_q`` -> ``n_i`` heads, ``k = x . W_k``, ``v = x . W_v`` -> the
  KV heads; no bias, no per-head q/k norm (assumed: the config names none).
* **Rotary by kind** (``rope_tables``), split-half pairing ``(d, d + r/2)``
  on the first ``r = head_dim * partial_rotary_factor`` dimensions, the
  rest passed through.  A sliding layer rotates the whole head at its own
  theta, unscaled; a full layer rotates half of it under YaRN
  (``yarn_inv_freq``), its ``cos`` and ``sin`` times ``attention_factor``.
* Query head ``g`` reads KV head ``g // (n_i / n_kv)``; ``score = q . k /
  sqrt(head_dim)``; a full layer is causal, a sliding layer lets position
  ``t`` see ``j`` with ``t - sliding_window < j <= t``.
* **The gate** (``attn_gate``; assumed per head, as the sibling config's
  ``gating: "per-head"``): ``g = sigmoid_f32(x . W_g)``, one value a head
  and token, on the attention output before ``o_proj``.
* Feed-forward: a SwiGLU where ``mlp_layer_types[i] == "dense"``, else
  ``expert_layer.expert_layer`` under this config's ``router`` (sigmoid
  scores, the ``num_experts_per_tok`` largest, normalised, times
  ``moe_routed_scaling_factor``, NO selection bias leaf) plus one ungated
  shared SwiGLU of ``shared_expert_intermediate_size``.

**The cache has two kinds of page.**  Global layers' K/V live in pools
``(L_global, n_kv, P_g, page, head_dim)``, window layers' in ``(L_window,
n_kv, P_w, page, head_dim)``, each addressed in place by (layer of its
kind, page) and carried through every program (donated; kv heads an index
of the scatter).  A row's two page tables ride one ``(B, 2 W)`` operand:
the global kind's entries, then the window kind's, both indexed by
position.  Window layers attend through the paged kernel with ``window=``:
its walk starts at the page that holds the first position the row may
see, so the entries behind it are never read and the engine gives those
pages back while the request runs (``PagedKVCache``'s window kind).

The model object keeps every leaf under its ``state_dict`` name as it was
loaded and holds shapes only until ``load_tree``; the layer loop is
unrolled (layers differ in shape).  What a two-kind cache does not
compose with yet is refused by ``ServingEngine`` in one place
(``kv_layout_ = "windowed"``).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .deepseek_v3 import CallCounts, ShapesUntilLoaded
from .expert_layer import (EXPERT_KEYS, ROUTE_COUNTS, ROUTER, SHARED_KEYS,
                           Router, expert_layer)
from .llama_decode import (PagedOnlyDense, _logits, _rms,
                           chunked_prefill_shim, decode_scan, emit_fn)

FULL, SLIDING = "full_attention", "sliding_attention"
GATE = "self_attn.gate_proj.weight"
_DENSE_KEYS = ("mlp.gate_proj.weight", "mlp.up_proj.weight",
               "mlp.down_proj.weight")
# one device call's counts, in this order (``CallCounts``)
CALL_COUNTS = ("layer_calls",) + ROUTE_COUNTS + (
    "kv_tokens_read_global", "kv_tokens_read_window")
_COUNTERS = {
    "layer_calls": ("serving_moe_layer_calls_total",
                    "expert-layer calls counted (device calls x steps x "
                    "layers)"),
    "pairs": ("serving_moe_pairs_total",
              "token-expert pairs routed, summed over calls and layers"),
    "experts_hit": ("serving_moe_experts_hit_total",
                    "experts that received a token, summed over calls and "
                    "layers"),
    "max_expert_pairs": ("serving_moe_max_expert_pairs_total",
                         "the largest expert's pairs, summed over calls and "
                         "layers"),
    "kv_tokens_read_global": ("serving_kv_tokens_read_global_total",
                              "cache positions the rows' walks read in "
                              "global layers, summed over rows and layers"),
    "kv_tokens_read_window": ("serving_kv_tokens_read_window_total",
                              "cache positions the rows' walks read in "
                              "sliding-window layers (at most the window a "
                              "row), summed over rows and layers")}

_DENSE_REASON = (
    "a two-kind (global + window) serving factory is paged-only: the dense "
    "wave cache has neither page kinds nor a window to give pages back "
    "behind — route with policy='paged'")

_ROPE_XS2 = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}


@dataclasses.dataclass
class LagunaConfig:
    """The published keys as they stand (defaults: Laguna-XS.2).  The three
    per-layer lists may be longer than ``num_hidden_layers`` (a cut in
    depth keeps the published lists): the first ``num_hidden_layers``
    entries are the model's."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: Any = True
    sliding_window: int = 512
    rope_parameters: Any = None
    layer_types: Any = None
    mlp_layer_types: Any = None
    num_attention_heads_per_layer: Any = None
    moe_apply_router_weight_on_input: bool = False
    partial_rotary_factor: float = 0.5
    moe_routed_scaling_factor: float = 2.5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.rope_parameters is None:
            self.rope_parameters = _ROPE_XS2
        if self.layer_types is None:
            self.layer_types = [FULL, SLIDING, SLIDING, SLIDING] * (-(-L // 4))
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["dense"] + ["sparse"] * L
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = [
                48 if t == FULL else 64 for t in self.layer_types]
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            if len(getattr(self, key)) < L:
                raise ValueError(f"{key} names {len(getattr(self, key))} "
                                 f"layers, the model has {L}")
            setattr(self, key, list(getattr(self, key))[:L])
        for key, want in (("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("moe_apply_router_weight_on_input", False)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"laguna: {key}={getattr(self, key)!r} is not computed "
                    f"here (only {want!r})")
        if self.gating not in (True, "per-head", "per_head"):
            raise NotImplementedError(
                f"laguna: gating={self.gating!r}: only the per-head output "
                "gate (True, read as the sibling's 'per-head') is computed")
        for kind in set(self.layer_types):
            if kind not in (FULL, SLIDING):
                raise NotImplementedError(f"laguna: layer type {kind!r}")
            if self.rope_parameters[kind]["rope_type"] not in ("default",
                                                               "yarn"):
                raise NotImplementedError(
                    f"laguna: rope_type of {kind}: 'default' or 'yarn'")
        for n in self.num_attention_heads_per_layer:
            if n % self.num_key_value_heads:
                raise ValueError(f"{n} query heads over "
                                 f"{self.num_key_value_heads} KV heads")

    # -- by layer ----------------------------------------------------------
    def heads(self, i: int) -> int:
        return self.num_attention_heads_per_layer[i]

    def window_of(self, i: int):
        """Layer ``i``'s window, None for a full layer."""
        return self.sliding_window if self.layer_types[i] == SLIDING \
            else None

    def sparse_layer(self, i: int) -> bool:
        return self.mlp_layer_types[i] == "sparse"

    def layers_of(self, kind: str) -> list:
        return [i for i, t in enumerate(self.layer_types) if t == kind]

    @property
    def router(self) -> Router:
        """What ``expert_layer.route`` computes here (assumed from the
        geometry and the sibling's ``norm_topk_prob: true``): sigmoid
        scores, the k largest, normalised, scaled; no bias leaf."""
        return Router(k=self.num_experts_per_tok, n_experts=self.num_experts,
                      scaling=self.moe_routed_scaling_factor, normalise=True)

    @staticmethod
    def tiny(**over):
        """The CPU tests' size, every ratio kept: 2 KV heads under 6
        (full) and 8 (sliding) query heads of 16 over a hidden size of 32
        (heads x head = 3 and 4 times it), window 8 (two pages of 4), 5
        layers F S S S F, layer 0 dense, 16 experts (4 a token) of a
        quarter of the hidden size and one shared; the published rotary
        rules."""
        types = [FULL, SLIDING, SLIDING, SLIDING, FULL]
        cfg = dict(vocab_size=256, hidden_size=32, intermediate_size=128,
                   num_hidden_layers=5, num_attention_heads=6,
                   num_key_value_heads=2, head_dim=16,
                   max_position_embeddings=4096, num_experts=16,
                   num_experts_per_tok=4, moe_intermediate_size=8,
                   shared_expert_intermediate_size=8, sliding_window=8,
                   layer_types=types,
                   mlp_layer_types=["dense"] + ["sparse"] * 4,
                   num_attention_heads_per_layer=[
                       6 if t == FULL else 8 for t in types],
                   dtype=jnp.float32)
        cfg.update(over)
        return LagunaConfig(**cfg)


# -- leaves ---------------------------------------------------------------
def layer_leaf_shapes(cfg: LagunaConfig, i: int) -> dict:
    """Layer ``i``'s leaves, short name -> shape (linear weights (in,
    out); a layer's experts stacked over their number)."""
    H, D = cfg.hidden_size, cfg.head_dim
    n, nkv = cfg.heads(i), cfg.num_key_value_heads
    shapes = {
        "input_layernorm.weight": (H,),
        "self_attn.q_proj.weight": (H, n * D),
        "self_attn.k_proj.weight": (H, nkv * D),
        "self_attn.v_proj.weight": (H, nkv * D),
        GATE: (H, n),
        "self_attn.o_proj.weight": (n * D, H),
        "post_attention_layernorm.weight": (H,),
    }
    if cfg.sparse_layer(i):
        E, I = cfg.num_experts, cfg.moe_intermediate_size
        S = cfg.shared_expert_intermediate_size
        shapes[ROUTER] = (H, E)
        shapes.update(zip(EXPERT_KEYS, ((E, H, I), (E, H, I), (E, I, H))))
        shapes.update(zip(SHARED_KEYS, ((H, S), (H, S), (S, H))))
    else:
        I = cfg.intermediate_size
        shapes.update(zip(_DENSE_KEYS, ((H, I), (H, I), (I, H))))
    return shapes


def leaf_shapes(cfg: LagunaConfig) -> dict:
    """Every leaf under its ``state_dict`` name -> shape."""
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, cfg.hidden_size)}
    for i in range(cfg.num_hidden_layers):
        shapes.update({f"model.layers.{i}.{k}": s
                       for k, s in layer_leaf_shapes(cfg, i).items()})
    shapes["model.norm.weight"] = (cfg.hidden_size,)
    shapes["lm_head.weight"] = (cfg.hidden_size, cfg.vocab_size)
    return shapes


# -- the layer's mathematics ----------------------------------------------
def yarn_inv_freq(rp: dict, r: int):
    """The ``r / 2`` rotary frequencies of a YaRN layer: ``transformers``'
    ``_compute_yarn_parameters`` with ``truncate`` on.  Dimension ``d``'s
    frequency is the plain one below ``low``, the plain one over
    ``factor`` above ``high``, and a linear blend between."""
    base, factor = float(rp["rope_theta"]), float(rp["factor"])
    orig = rp["original_max_position_embeddings"]

    def corr(beta):
        return r * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), r - 1)
    d = jnp.arange(r // 2, dtype=jnp.float32)
    plain = base ** (-2.0 * d / r)
    ramp = jnp.clip((d - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_tables(cfg: LagunaConfig, kind: str, pos):
    """-> (cos, sin) float32 of shape ``pos.shape + (r/2,)`` and ``r``,
    the rotated width, for a layer of ``kind``."""
    rp = cfg.rope_parameters[kind]
    r = int(cfg.head_dim * rp.get("partial_rotary_factor", 1))
    if rp["rope_type"] == "yarn":
        inv, scale = yarn_inv_freq(rp, r), float(rp["attention_factor"])
    else:
        inv = float(rp["rope_theta"]) ** (
            -2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
        scale = 1.0
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale, r


def apply_rope(x, cos, sin, r: int):
    """x (..., heads, D) with cos/sin (..., r/2): rotate the pairs ``(d, d
    + r/2)`` of the first ``r`` dimensions (Hugging Face's
    ``rotate_half``), pass the rest through."""
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :r // 2], xf[..., r // 2:r], xf[..., r:]
    c, s = cos[..., None, :], sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)
    return out.astype(x.dtype)


def attn_project(cfg, lp, i, h, pos):
    """h (B, T, H), pos (B, T) -> q (B, T, n_i, D), k, v (B, T, nkv, D),
    q and k after this layer kind's rotary."""
    B, T, _ = h.shape
    D, nkv = cfg.head_dim, cfg.num_key_value_heads
    with jax.named_scope("attn.project"):
        q = (h @ lp["self_attn.q_proj.weight"]).reshape(B, T, cfg.heads(i), D)
        k = (h @ lp["self_attn.k_proj.weight"]).reshape(B, T, nkv, D)
        v = (h @ lp["self_attn.v_proj.weight"]).reshape(B, T, nkv, D)
    with jax.named_scope("attn.rope"):
        cos, sin, r = rope_tables(cfg, cfg.layer_types[i],
                                  jnp.broadcast_to(pos, (B, T)))
        return apply_rope(q, cos, sin, r), apply_rope(k, cos, sin, r), v


def attn_gate(lp, h, o):
    """The per-head output gate (assumed): o (B, T, n, D) times
    ``sigmoid_f32(h . W_g)`` (B, T, n), before ``o_proj``."""
    with jax.named_scope("attn.gate"):
        g = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                                   lp[GATE].astype(jnp.float32)))
        return (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)


def feed_forward(cfg, lp, h):
    """-> (y, the expert layer's counts, or None for a dense layer)."""
    if ROUTER in lp:
        return expert_layer(cfg, lp, h)
    g, u, d = (lp[k] for k in _DENSE_KEYS)
    return (jax.nn.silu(h @ g) * (h @ u)) @ d, None


def layer_math(cfg, lp, i, x, pos, attend):
    """One layer over (B, T, H): ``attend(q, k, v) -> (o (B, T, n_i, D),
    extra)`` owns the cache strategy.  Returns (x, extra, counts | None)."""
    B, T, _ = x.shape
    h = _rms(x, lp["input_layernorm.weight"], cfg.rms_norm_eps)
    o, extra = attend(*attn_project(cfg, lp, i, h, pos))
    o = attn_gate(lp, h, o).reshape(B, T, -1)
    x = x + o @ lp["self_attn.o_proj.weight"]
    h2 = _rms(x, lp["post_attention_layernorm.weight"], cfg.rms_norm_eps)
    y, counts = feed_forward(cfg, lp, h2)
    return x + y, extra, counts


def masked_attend(cfg, i, mask):
    """The equations as published, no cache: float32 softmax under
    ``mask`` (T, T)."""
    def attend(q, k, v):
        B, T, n, D = q.shape
        nkv = cfg.num_key_value_heads
        qg = q.reshape(B, T, nkv, n // nkv, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) \
            / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1).astype(v.dtype)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
        return o.reshape(B, T, n, D), ()
    return attend


def layer_mask(cfg, i, S: int, window_ignored: bool = False):
    """(S, S) bool: causal, and within the window in a sliding layer."""
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    w = cfg.window_of(i)
    if w is not None and not window_ignored:
        mask = mask & (pos[None, :] > pos[:, None] - w)
    return mask


def full_forward(cfg, outer, layers, ids, window_ignored: bool = False):
    """Logits (B, S, V) float32 of whole sequences, no cache.
    ``window_ignored`` is the tests' planted fault: sliding layers attend
    to everything."""
    S = ids.shape[1]
    x = jnp.take(outer["model.embed_tokens.weight"], ids, axis=0)
    pos = jnp.arange(S)[None, :]
    for i, lp in enumerate(layers):
        x, _, _ = layer_math(
            cfg, lp, i, x, pos,
            masked_attend(cfg, i, layer_mask(cfg, i, S, window_ignored)))
    x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
    return _logits(cfg, outer, x).astype(jnp.float32)


# -- the model object -----------------------------------------------------
class LagunaForCausalLM(ShapesUntilLoaded):
    """The model object ``ServingEngine`` and the benchmark hold, as
    ``DeepseekV3ForCausalLM`` is one (``ShapesUntilLoaded``: shapes only
    until ``load_tree``, then the loaded arrays themselves)."""

    kv_layout_ = "windowed"      # what ServingEngine's refusals read
    layer_leaf_shapes = staticmethod(layer_leaf_shapes)
    full_forward = staticmethod(full_forward)

    def serving_decode_factory(self, *, scan_layers=True, **build):
        """What ``ServingEngine`` asks a model for: its paged serving
        factory (``windowed_serving_decode_factory``) from the geometry,
        the window pool's size among it.  ``scan_layers`` changes nothing
        (the layers are unrolled: they differ in shape); every other
        option of the Llama factories must be unset."""
        geometry = ("max_len", "page_size", "n_pool_pages", "batch_capacity",
                    "chunked_prefill", "n_window_pages", "window_slack")
        unset = {k: v for k, v in build.items()
                 if k not in geometry and v is not None}
        if unset:
            raise ValueError("a two-kind serving factory takes its "
                             f"geometry alone, not {sorted(unset)}")
        return windowed_serving_decode_factory(
            self, **{k: build[k] for k in geometry
                     if build.get(k) is not None})


# -- the paged serving factory --------------------------------------------
def windowed_paged_decode_factory(model: LagunaForCausalLM,
                                  page_size: int = 64,
                                  n_pool_pages: int = 256,
                                  n_window_pages: int = 64,
                                  chunked_prefill: int | None = None,
                                  emit: str = "token",
                                  window_ignored: bool = False):
    """Compiled prefill and decode over the two kinds of paged pool, the
    contract of ``llama_paged_decode_factory``: returns ``(outer, layers,
    pools, prefill, decode_step, decode_n)`` with fixed shapes, so churn
    never recompiles.  ``pools`` is ``(k_global, v_global, k_window,
    v_window)``, each ``(layers of the kind, n_kv, pages, page_size,
    head_dim)``, donated to and returned by every program and updated in
    place (scattered by (layer, kv head, page, offset); read by the
    kernel through the page table).  ``page_tables`` is ``(B, 2 W)``: the
    global kind's table, then the window kind's.  ``prefill`` is the
    chunked walk (``chunked_prefill_shim``); every chunk attends through
    the kernel to the pool pages written so far, its own included.  The
    programs count (``CALL_COUNTS``) and the factory keeps each call's
    counts on the device (``decode_n.counts``).  ``window_ignored`` is the
    tests' planted fault: sliding layers attend to everything they hold."""
    from ...ops.pallas.paged_attention import (paged_attention,
                                               paged_prefill_attention)

    cfg = model.config
    outer, layers = model.decode_params()
    nkv, D = cfg.num_key_value_heads, cfg.head_dim
    window = cfg.sliding_window
    dtype = outer["model.embed_tokens.weight"].dtype
    if chunked_prefill is None or chunked_prefill % page_size:
        raise ValueError("chunked_prefill must be a multiple of page_size "
                         f"({page_size}): prefill runs in chunks only")
    if window % page_size:
        raise ValueError(f"sliding_window {window} must be a multiple of "
                         f"page_size {page_size}")
    _emit = emit_fn(emit)
    tally = CallCounts(CALL_COUNTS, _COUNTERS)
    _heads = jnp.arange(nkv)
    # layer i -> (which pools, its index among the layers of its kind)
    place, n_kind = {}, {FULL: 0, SLIDING: 0}
    for i, kind in enumerate(cfg.layer_types):
        place[i] = (0 if kind == FULL else 2, n_kind[kind])
        n_kind[kind] += 1
    L_g, L_w = n_kind[FULL], n_kind[SLIDING]

    def _tables(page_tables):
        W = page_tables.shape[1] // 2
        return page_tables[:, :W], page_tables[:, W:]

    def _layers(layers, x, pos, pools, attend_at):
        """The unrolled stack -> (x, pools, counts (1 + len(ROUTE_COUNTS),):
        expert-layer calls and their ``ROUTE_COUNTS`` summed)."""
        pools = list(pools)
        total = jnp.zeros((1 + len(ROUTE_COUNTS),), jnp.int32)
        for i, lp in enumerate(layers):
            at, j = place[i]
            x, (pools[at], pools[at + 1]), counts = layer_math(
                cfg, lp, i, x, pos,
                attend_at(i, j, pools[at], pools[at + 1],
                          at == 2))
            if counts is not None:
                total = total + jnp.concatenate(
                    [jnp.ones((1,), jnp.int32), counts])
        return x, tuple(pools), total

    def _window(windowed: bool):
        return window if windowed and not window_ignored else None

    @partial(jax.jit, donate_argnums=(5,))
    def decode_step(outer, layers, tok, page_tables, lengths, pools):
        """-> (emission, pools', counts (len(CALL_COUNTS),))."""
        tables = _tables(page_tables)
        at_page = (lengths // page_size)[:, None]
        pages = [jnp.take_along_axis(t, at_page, 1)[:, 0] for t in tables]
        offs = lengths % page_size

        def attend_at(i, j, kp, vp, windowed):
            def attend(q, k, v):             # (B, 1, heads, D)
                at = (j, _heads[None, :], pages[windowed][:, None],
                      offs[:, None])
                kp2 = kp.at[at].set(k[:, 0].astype(kp.dtype))
                vp2 = vp.at[at].set(v[:, 0].astype(vp.dtype))
                with jax.named_scope("attn.window" if windowed
                                     else "attn.global"):
                    ctx = paged_attention(
                        q[:, 0], kp2, vp2, tables[windowed], lengths + 1,
                        layer=j, window=_window(windowed))
                return ctx[:, None], (kp2, vp2)
            return attend

        x = jnp.take(outer["model.embed_tokens.weight"], tok, axis=0)[:, None]
        x, pools, counts = _layers(layers, x, lengths[:, None], pools,
                                   attend_at)
        x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
        # an empty slot rides as a length-0 row: it reads nothing that counts
        seen = jnp.where(lengths > 0, lengths + 1, 0)
        read = jnp.stack([jnp.sum(seen) * L_g,
                          jnp.sum(jnp.minimum(seen, window)) * L_w])
        return (_emit(_logits(cfg, outer, x[:, 0])), pools,
                jnp.concatenate([counts, read.astype(jnp.int32)]))

    @partial(jax.jit, donate_argnums=(6,))
    def _chunk_program(outer, layers, chunk, start, page_tables, lengths,
                       pools, x_last):
        """One C-token chunk at absolute positions start..start+C-1:
        writes its pages in both kinds, attends to every pool position it
        may see, and harvests the hidden state of each sequence's (length
        - 1) row when it falls inside this chunk."""
        B, C = chunk.shape
        npg = C // page_size
        tables = _tables(page_tables)
        ids = [jax.lax.dynamic_slice_in_dim(t, start // page_size, npg, 1)
               for t in tables]

        def attend_at(i, j, kp, vp, windowed):
            def attend(q, k, v):             # (B, C, heads, D)
                at = (j, _heads[None, :, None], ids[windowed][:, None, :])

                def pageify(a):              # -> (B, nkv, npg, page, D)
                    return jnp.swapaxes(a, 1, 2).reshape(
                        B, nkv, npg, page_size, D)
                kp2 = kp.at[at].set(pageify(k).astype(kp.dtype))
                vp2 = vp.at[at].set(pageify(v).astype(vp.dtype))
                with jax.named_scope("attn.window" if windowed
                                     else "attn.global"):
                    ctx = paged_prefill_attention(
                        jnp.swapaxes(q, 1, 2), kp2, vp2, tables[windowed],
                        lengths, start, layer=j, window=_window(windowed))
                return jnp.swapaxes(ctx, 1, 2), (kp2, vp2)
            return attend

        x = jnp.take(outer["model.embed_tokens.weight"], chunk, axis=0)
        x, pools, counts = _layers(layers, x, (start + jnp.arange(C))[None],
                                   pools, attend_at)
        idx = jnp.clip(lengths - 1 - start, 0, C - 1)
        row = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32),
                                  1)[:, 0]
        hit = ((lengths - 1 >= start) & (lengths - 1 < start + C))[:, None]
        end = jnp.minimum(lengths, start + C)
        read = jnp.stack([
            jnp.sum(end) * L_g,
            jnp.sum(end - jnp.maximum(start - window + 1, 0)) * L_w])
        return (jnp.where(hit, row, x_last), pools,
                jnp.concatenate([counts, read.astype(jnp.int32)]))

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
        (emits, counts), tok, pools = decode_scan(
            lambda tok, lens, pools: decode_step(
                outer, layers, tok, page_tables, lens, pools),
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

    def pool(n_layers, n_pages):
        return jnp.zeros((n_layers, nkv, n_pages, page_size, D), dtype)
    pools = (pool(L_g, n_pool_pages), pool(L_g, n_pool_pages),
             pool(L_w, n_window_pages), pool(L_w, n_window_pages))
    return outer, layers, pools, prefill, step, decode_n


def windowed_serving_decode_factory(model: LagunaForCausalLM,
                                    max_len: int = 256, page_size: int = 64,
                                    n_pool_pages: int = 256,
                                    batch_capacity: int = 8,
                                    chunked_prefill: int | None = None,
                                    n_window_pages: int | None = None,
                                    window_slack: int = 1,
                                    window_ignored: bool = False):
    """The object ``ServingEngine`` consumes, for a two-kind cache: paged
    only (``pick`` always answers "paged"; the dense slot is a stub that
    says why).  ``n_window_pages`` defaults to the window pool's floor:
    every slot's ring and the padding page."""
    from ...ops.pallas.paged_attention import window_ring
    cfg = model.config
    if n_window_pages is None:
        n_window_pages = batch_capacity * window_ring(
            cfg.sliding_window, page_size, window_slack) + 1
    paged = windowed_paged_decode_factory(
        model, page_size=page_size, n_pool_pages=n_pool_pages,
        n_window_pages=n_window_pages, chunked_prefill=chunked_prefill,
        window_ignored=window_ignored)
    kv_bytes = 2 * cfg.num_key_value_heads * cfg.head_dim * page_size \
        * jnp.dtype(paged[2][0].dtype).itemsize

    class _Serving:
        dense = PagedOnlyDense(_DENSE_REASON)
        paged_parts = paged
        capacity = batch_capacity
        max_len_ = max_len
        page_size_ = page_size
        n_pool_pages_ = n_pool_pages
        n_window_pages_ = n_window_pages
        window_ = cfg.sliding_window
        chunked_prefill_ = chunked_prefill
        # chunks ONE lane call may span: the kernel holds (heads a KV
        # head) x width query rows a KV head in VMEM and unrolls over
        # them. 8 x 128 = 1024 rows need 33.3 MB (the launcher asks);
        # 3 and 4 chunks compile too (48.4 / 37.0 MB) but Mosaic takes
        # 78 / 128 s over such a program where 2 take 40 (compiled for
        # the chip on a CPU host): a cold start's minutes, so 2
        chunked_prefill_widest_ = 2
        kv_layout_ = "windowed"
        # a page's bytes, K and V over the layers of its kind
        page_bytes_ = {"global": kv_bytes * len(cfg.layers_of(FULL)),
                       "window": kv_bytes * len(cfg.layers_of(SLIDING))}
        call_counts = paged[5].counts    # CallCounts: reset() / take()

        def pick(self, lengths, capacity=None, shared_prefix=False,
                 expect_churn=False):
            return "paged", paged

    return _Serving()
