"""``deepseek_v3``: a decoder with latent (MLA) attention and a sparse
feed-forward with shared experts and sigmoid routing, on the serving path
(Moonlight-16B-A3B's ``config.json``, ``model_type: deepseek_v3``).

Per layer, ``x = RMSNorm(h)``:

* **MLA**, no query compression (``q_lora_rank: null``).  ``q = x . W_q``
  -> heads x (``nope | rope``); ``[c_kv | k_rope] = x . W_kva`` (``rank |
  rope``); ``c = RMSNorm(c_kv)`` (``kv_a_layernorm``); RoPE on ``q_rope``
  per head and on the ONE ``k_rope`` all heads share, over interleaved
  pairs of dimensions ``(2i, 2i + 1)`` at frequency ``theta^(-2i/rope)``
  (the pairing of Hugging Face's ``deepseek_v3``, which de-interleaves q
  and k alike before a split-half rotation: the scores are the same).
  ``[k_nope | v] = c . W_kvb`` per head; ``score = (q_nope . k_nope +
  q_rope . k_rope) / sqrt(nope + rope)``, causal softmax, ``o = sum p v``,
  ``attn = concat(o) . W_o``.
* **The cache holds ``c`` (after its norm) and ``k_rope`` (after RoPE)**:
  ``rank + rope`` values a token and layer whatever the head count, in ONE
  pool ``(L, P, page, width)`` addressed in place by (layer, page), its
  ``width`` the latent's padded to the lane tile (576 -> 640: the kernel's
  docstring says what an unpadded pool costs).
  Every serving program attends in the **absorbed** form through
  ``ops/pallas/latent_paged_attention.py``: ``q_abs_h = q_nope_h .
  W_UK_h^T``, ``score = (q_abs_h . c + q_rope_h . k_rope) * scale``, ``o_h
  = (sum p c) . W_UV_h`` with ``W_UK_h | W_UV_h`` the two halves of
  ``W_kvb``'s head ``h``.  Cached positions are never expanded to K/V, in
  decode (``chunk = 1``) or in chunked prefill (``chunk = C``): at a
  64-token chunk the absorbed form also costs fewer operations than
  expanding the context a chunk (PERF.md section 5).  ``full_forward`` is
  the expanded form without a cache, for the CPU tests.
* **Feed-forward**: a SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers, then ``expert_layer.expert_layer``.

The model object keeps every leaf under its ``state_dict`` name as it was
loaded — the expert stacks above all, which the grouped products read
whole — and the layer loop is unrolled (layers differ in kind, and a
scan would slice the stacks).  As built it holds shapes only
(``jax.ShapeDtypeStruct``): construction makes no weight.

What a latent cache does not compose with yet is refused by
``ServingEngine`` in one place (``kv_layout_ = "latent"``).
"""
from __future__ import annotations

import collections
import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ... import nn
from ...core.tensor import Parameter
from .expert_layer import (EXPERT_KEYS, ROUTE_COUNTS, ROUTER, ROUTER_BIAS,
                           SHARED_KEYS, expert_layer)
from .llama_decode import (PagedOnlyDense, _logits, _rms,
                           chunked_prefill_shim, decode_scan, emit_fn)

_DENSE_KEYS = ("mlp.gate_proj.weight", "mlp.up_proj.weight",
               "mlp.down_proj.weight")
# one device call's counts, in this order (``CallCounts``)
CALL_COUNTS = ("layer_calls",) + ROUTE_COUNTS + ("cached_tokens_read",)

_DENSE_REASON = (
    "a latent-cache serving factory is paged-only: the dense wave cache "
    "stores per-head K and V, which is what a latent cache exists not to "
    "hold — route with policy='paged'")


@dataclasses.dataclass
class DeepseekV3Config:
    """The published keys as they stand (defaults: Moonlight-16B-A3B)."""
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    kv_lora_rank: int = 512
    q_lora_rank: Any = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: Any = None
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                          ("attention_bias", False), ("moe_layer_freq", 1),
                          ("hidden_act", "silu"),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"deepseek_v3: {key}={getattr(self, key)!r} is not "
                    f"computed here (only {want!r})")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention expands one key/value head "
                             "a query head")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values the cache holds a token and layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def sparse_layer(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    @staticmethod
    def tiny(**over):
        """The CPU tests' size, every ratio kept: 4 heads of nope 16 /
        rope 8 / v 16 over a hidden size of 64, rank 32, layer 0 dense,
        then 8 experts (3 a token) and 2 shared."""
        cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=176,
                   moe_intermediate_size=24, num_hidden_layers=3,
                   num_attention_heads=4, num_key_value_heads=4,
                   n_routed_experts=8, n_shared_experts=2,
                   num_experts_per_tok=3, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   max_position_embeddings=512, dtype=jnp.float32)
        cfg.update(over)
        return DeepseekV3Config(**cfg)


# -- leaves ---------------------------------------------------------------
def layer_leaf_shapes(cfg: DeepseekV3Config, i: int) -> dict:
    """Layer ``i``'s leaves, short name -> shape (linear weights (in,
    out); a layer's experts stacked over their number)."""
    H, nh = cfg.hidden_size, cfg.num_attention_heads
    shapes = {
        "input_layernorm.weight": (H,),
        "self_attn.q_proj.weight": (H, nh * cfg.qk_head_dim),
        "self_attn.kv_a_proj_with_mqa.weight": (H, cfg.latent_width),
        "self_attn.kv_a_layernorm.weight": (cfg.kv_lora_rank,),
        "self_attn.kv_b_proj.weight":
            (cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "self_attn.o_proj.weight": (nh * cfg.v_head_dim, H),
        "post_attention_layernorm.weight": (H,),
    }
    if cfg.sparse_layer(i):
        E, I = cfg.n_routed_experts, cfg.moe_intermediate_size
        S = cfg.n_shared_experts * I
        shapes[ROUTER] = (H, E)
        shapes[ROUTER_BIAS] = (E,)
        shapes.update(zip(EXPERT_KEYS, ((E, H, I), (E, H, I), (E, I, H))))
        shapes.update(zip(SHARED_KEYS, ((H, S), (H, S), (S, H))))
    else:
        I = cfg.intermediate_size
        shapes.update(zip(_DENSE_KEYS, ((H, I), (H, I), (I, H))))
    return shapes


def leaf_shapes(cfg: DeepseekV3Config) -> dict:
    """Every leaf under its ``state_dict`` name -> shape."""
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, cfg.hidden_size)}
    for i in range(cfg.num_hidden_layers):
        shapes.update({f"model.layers.{i}.{k}": s
                       for k, s in layer_leaf_shapes(cfg, i).items()})
    shapes["model.norm.weight"] = (cfg.hidden_size,)
    shapes["lm_head.weight"] = (cfg.hidden_size, cfg.vocab_size)
    return shapes


# -- the layer's mathematics ----------------------------------------------
def rope_interleaved(x, pos, theta):
    """Rotate pairs ``(2i, 2i + 1)`` of the last axis by ``pos *
    theta^(-2i/d)``; ``pos`` broadcasts against ``x.shape[:-1]``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x0, x1 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_project(cfg, lp, h, pos):
    """h (B, T, H), pos (B, T) or (T,) -> ``q_nope`` (B, T, nh, nope),
    ``q_rope`` (B, T, nh, rope) after RoPE, and what the cache holds of
    these positions, ``latent`` (B, T, rank + rope).  A config that states
    ``mla_use_nope`` rotates nothing: its ``rope`` columns enter the score
    as they are projected."""
    B, T, _ = h.shape
    nh, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    pos = jnp.broadcast_to(pos, (B, T))
    with jax.named_scope("mla.project"):
        q = (h @ lp["self_attn.q_proj.weight"]).reshape(
            B, T, nh, cfg.qk_head_dim)
        q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], \
            q[..., cfg.qk_nope_head_dim:]
        kva = h @ lp["self_attn.kv_a_proj_with_mqa.weight"]
        c = _rms(kva[..., :rank], lp["self_attn.kv_a_layernorm.weight"],
                 cfg.rms_norm_eps)
        k_rope = kva[..., rank:]
        if not getattr(cfg, "mla_use_nope", False):
            q_rope = rope_interleaved(q_rope, pos[..., None], cfg.rope_theta)
            k_rope = rope_interleaved(k_rope, pos, cfg.rope_theta)
        return q_nope, q_rope, jnp.concatenate([c, k_rope], axis=-1)


def _kvb_heads(cfg, lp):
    """``W_kvb`` as (rank, nh, nope + v): head ``h``'s ``W_UK | W_UV``."""
    return lp["self_attn.kv_b_proj.weight"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def absorbed_queries(cfg, lp, q_nope, q_rope):
    """(B, T, nh, .) -> (B, nh * T, rank + rope), row = head * T + i: each
    head's query taken through ``W_UK^T`` beside its rotary part."""
    B, T, nh, _ = q_nope.shape
    w_uk = _kvb_heads(cfg, lp)[..., :cfg.qk_nope_head_dim]
    q_abs = jnp.einsum("bthd,chd->bhtc", q_nope, w_uk)
    q_cat = jnp.concatenate([q_abs, jnp.swapaxes(q_rope, 1, 2)], axis=-1)
    return q_cat.reshape(B, nh * T, cfg.latent_width)


def _pad_columns(x, width):
    """Zeros after the last axis up to ``width`` (a page's lane padding)."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def absorbed_output(cfg, lp, ctx, T):
    """The kernel's (B, nh * T, rank) -> attention output (B, T, H):
    through ``W_UV`` per head, then ``W_o``."""
    B = ctx.shape[0]
    nh = cfg.num_attention_heads
    w_uv = _kvb_heads(cfg, lp)[..., cfg.qk_nope_head_dim:]
    o = jnp.einsum("bhtc,chd->bthd",
                   ctx.reshape(B, nh, T, cfg.kv_lora_rank), w_uv)
    return o.reshape(B, T, nh * cfg.v_head_dim) \
        @ lp["self_attn.o_proj.weight"]


def feed_forward(cfg, lp, h):
    """-> (y, the expert layer's counts, or None for a dense layer).  A
    config that states ``experts_held`` holds that share of the experts."""
    if ROUTER in lp:
        return expert_layer(cfg, lp, h, getattr(cfg, "experts_held", None))
    g, u, d = (lp[k] for k in _DENSE_KEYS)
    return (jax.nn.silu(h @ g) * (h @ u)) @ d, None


def layer_math(cfg, lp, x, pos, attend):
    """One layer over (B, T, H): ``attend(q_nope, q_rope, latent) ->
    (attention output (B, T, H), extra)`` owns the cache strategy.
    Returns (x, extra, counts | None)."""
    h = _rms(x, lp["input_layernorm.weight"], cfg.rms_norm_eps)
    attn, extra = attend(*mla_project(cfg, lp, h, pos))
    x = x + attn
    h2 = _rms(x, lp["post_attention_layernorm.weight"], cfg.rms_norm_eps)
    y, counts = feed_forward(cfg, lp, h2)
    return x + y, extra, counts


def expanded_attend(cfg, lp, mask):
    """The equations as published, no cache: every position's K and V
    expanded from its latent (``mla.expand``), float32 softmax."""
    def attend(q_nope, q_rope, latent):
        B, T, nh, _ = q_nope.shape
        rank = cfg.kv_lora_rank
        with jax.named_scope("mla.expand"):
            kv = jnp.einsum("btc,chd->bthd", latent[..., :rank],
                            _kvb_heads(cfg, lp))
        k_nope, v = kv[..., :cfg.qk_nope_head_dim], \
            kv[..., cfg.qk_nope_head_dim:]
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
             + jnp.einsum("bqhd,bkd->bhqk", q_rope, latent[..., rank:]))
        s = s.astype(jnp.float32) / math.sqrt(cfg.qk_head_dim)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1).astype(v.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return o.reshape(B, T, -1) @ lp["self_attn.o_proj.weight"], ()
    return attend


def full_forward(cfg, outer, layers, ids):
    """Logits (B, S, V) float32 of whole sequences, expanded, no cache."""
    S = ids.shape[1]
    x = jnp.take(outer["model.embed_tokens.weight"], ids, axis=0)
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    for lp in layers:
        x, _, _ = layer_math(cfg, lp, x, pos, expanded_attend(cfg, lp, mask))
    x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
    return _logits(cfg, outer, x).astype(jnp.float32)


# -- the model object -----------------------------------------------------
class ShapesUntilLoaded(nn.Layer):
    """A model object that holds shapes only until ``load_tree``, for
    ``ServingEngine`` and the benchmark: ``outer`` holds the embedding,
    the final norm and the head under their ``state_dict`` names;
    ``layers[i]`` holds layer ``i``'s leaves under their short names.
    Every leaf is a ``jax.ShapeDtypeStruct`` until ``load_tree`` brings
    its value, and is then the loaded array itself: no stacking, no copy.
    A subclass states its ``layer_leaf_shapes(config, i)`` and its
    ``full_forward(config, outer, layers, ids)``."""

    layer_leaf_shapes = full_forward = None

    def __init__(self, config):
        super().__init__()
        self.config = config
        self._forward = None
        dt = jnp.dtype(config.dtype)
        sds = lambda shape: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
        H = config.hidden_size
        self.outer = {"model.embed_tokens.weight": sds((config.vocab_size, H)),
                      "model.norm.weight": sds((H,)),
                      "lm_head.weight": sds((H, config.vocab_size))}
        self.layers = [{k: sds(s) for k, s in
                        type(self).layer_leaf_shapes(config, i).items()}
                       for i in range(config.num_hidden_layers)]

    def _store(self, name: str):
        """-> (the dict that holds leaf ``name``, its key there)."""
        if name in self.outer:
            return self.outer, name
        head, _, rest = name.partition("model.layers.")
        idx, _, key = rest.partition(".")
        if head or not idx.isdigit() or int(idx) >= len(self.layers) \
                or key not in self.layers[int(idx)]:
            raise KeyError(name)
        return self.layers[int(idx)], key

    def leaf_shapes(self) -> dict:
        """Every leaf under its ``state_dict`` name -> shape."""
        shapes = {"model.embed_tokens.weight":
                  self.outer["model.embed_tokens.weight"].shape}
        for i, lp in enumerate(self.layers):
            shapes.update({f"model.layers.{i}.{k}": v.shape
                           for k, v in lp.items()})
        shapes.update({k: self.outer[k].shape
                       for k in ("model.norm.weight", "lm_head.weight")})
        return shapes

    def _leaves(self):
        return [v for store in (self.outer, *self.layers)
                for v in store.values()]

    def materialized(self) -> bool:
        return all(isinstance(v, jax.Array) for v in self._leaves())

    def load_tree(self, tree) -> None:
        """Take the leaves ``tree`` names (``state_dict`` names) as they
        are; a name the model does not have is ignored, a shape it does
        not expect is an error."""
        for name, value in tree.items():
            try:
                store, key = self._store(name)
            except KeyError:
                continue
            if not isinstance(value, jax.Array):   # a Parameter, a numpy array
                value = jnp.asarray(getattr(value, "_value", value))
            if value.shape != store[key].shape:
                raise ValueError(f"{name}: shape {value.shape}, the model "
                                 f"expects {store[key].shape}")
            store[key] = value

    def drop_weights(self) -> None:
        """Back to shapes only."""
        for store in (self.outer, *self.layers):
            for k, v in store.items():
                store[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)

    def decode_params(self):
        """``(outer, layers)`` as the serving programs take them: the
        model's own arrays."""
        if not self.materialized():
            raise ValueError("the model holds shapes only: load_tree first")
        return dict(self.outer), [dict(lp) for lp in self.layers]

    def state_dict(self, *a, **k):
        if not self.materialized():
            raise ValueError("the model holds shapes only: load_tree first")
        return collections.OrderedDict(
            (name, Parameter(v))
            for name, v in self.tree_flatten_params().items())

    def tree_flatten_params(self):
        """The stored arrays themselves, for barriers and byte counts."""
        out = {}
        for name in self.leaf_shapes():
            store, key = self._store(name)
            out[name] = store[key]
        return out

    def forward(self, input_ids):
        """Logits (B, S, V), float32: ``full_forward``."""
        outer, layers = self.decode_params()
        if not isinstance(input_ids, jax.Array):
            input_ids = getattr(input_ids, "_value", input_ids)
        ids = jnp.asarray(input_ids, jnp.int32)
        if self._forward is None:
            self._forward = jax.jit(partial(type(self).full_forward,
                                            self.config))
        return self._forward(outer, layers, ids)


class DeepseekV3ForCausalLM(ShapesUntilLoaded):
    """The model object ``ServingEngine`` and the benchmark hold
    (``ShapesUntilLoaded``)."""

    kv_layout_ = "latent"        # what ServingEngine's refusals read
    layer_leaf_shapes = staticmethod(layer_leaf_shapes)
    full_forward = staticmethod(full_forward)

    def serving_decode_factory(self, *, scan_layers=True, **build):
        """What ``ServingEngine`` asks a model for: its paged serving
        factory (``latent_serving_decode_factory``) from the geometry.
        ``scan_layers`` changes nothing (the layers are unrolled: they
        differ in kind); every other option of the Llama factories must
        be unset, a latent cache composes with none of them yet."""
        geometry = ("max_len", "page_size", "n_pool_pages", "batch_capacity",
                    "chunked_prefill")
        unset = {k: v for k, v in build.items()
                 if k not in geometry and v is not None}
        if unset:
            raise ValueError("a latent-cache serving factory takes its "
                             f"geometry alone, not {sorted(unset)}")
        return latent_serving_decode_factory(
            self, **{k: build[k] for k in geometry if k in build})


# -- the paged serving factory --------------------------------------------
class CallCounts:
    """The counts of every device call of a run, kept on the device until
    asked for: one ``(len(names),)`` int32 row a program call, in
    call order, each call's layers and steps summed.  ``counters`` names
    the registry counter each count's sum over a run goes to (the engine
    creates them for a factory that has a ``CallCounts``, and for no
    other)."""

    counters = {
        "layer_calls": ("serving_moe_layer_calls_total",
                        "expert-layer calls counted (device calls x steps "
                        "x layers)"),
        "pairs": ("serving_moe_pairs_total",
                  "token-expert pairs routed, summed over calls and layers"),
        "experts_hit": ("serving_moe_experts_hit_total",
                        "experts that received a token, summed over calls "
                        "and layers"),
        "max_expert_pairs": ("serving_moe_max_expert_pairs_total",
                             "the largest expert's pairs, summed over calls "
                             "and layers"),
        "cached_tokens_read": ("serving_mla_cached_tokens_read_total",
                               "latent cache positions the decode rows "
                               "attended to, summed over steps")}

    def __init__(self, names=CALL_COUNTS, counters=None):
        self.names = tuple(names)
        if counters is not None:    # another model's counts and counters
            self.counters = counters
        self._rows: list = []       # (kind, device array)

    def add(self, kind: str, counts):
        self._rows.append((kind, counts))

    def reset(self):
        self._rows.clear()

    def take(self) -> dict:
        """``{"kind": [...], <CALL_COUNTS name>: [...]}``, one entry a
        call, and forget them."""
        rows, self._rows = self._rows, []
        # fetched as they are: stacking them on the device would compile
        # a program for every number of calls
        table = np.asarray(jax.device_get([c for _, c in rows]),
                           np.int64).reshape(-1, len(self.names))
        out = {"kind": [k for k, _ in rows]}
        out.update({name: [int(v) for v in table[:, j]]
                    for j, name in enumerate(self.names)})
        return out


def latent_paged_decode_factory(model: DeepseekV3ForCausalLM,
                                page_size: int = 64,
                                n_pool_pages: int = 256,
                                chunked_prefill: int | None = None,
                                emit: str = "token"):
    """Compiled prefill and decode over a paged LATENT pool, the contract
    of ``llama_paged_decode_factory``: returns ``(outer, layers, pool,
    prefill, decode_step, decode_n)`` with fixed shapes, so churn never
    recompiles.  ``pool`` is one array ``(L, P, page_size, page_width)``
    that every program updates in place (donated; scattered by (layer,
    page, offset), read by the kernel through the page table): no program
    slices, gathers or copies it.  ``prefill`` is the chunked walk
    (``chunked_prefill_shim``); every chunk attends through the kernel to
    the pool pages written so far, its own included.  The programs also
    count (``CALL_COUNTS``): the factory keeps each call's counts on the
    device (``decode_n.counts``, a ``CallCounts``)."""
    from ...ops.pallas.latent_paged_attention import (
        latent_paged_attention, page_width)

    cfg = model.config
    outer, layers = model.decode_params()
    L = cfg.num_hidden_layers
    rank, width = cfg.kv_lora_rank, page_width(cfg.latent_width)
    dtype = outer["model.embed_tokens.weight"].dtype
    sm_scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    if chunked_prefill is None or chunked_prefill % page_size:
        raise ValueError("chunked_prefill must be a multiple of page_size "
                         f"({page_size}): prefill runs in chunks only")
    _emit = emit_fn(emit)
    tally = CallCounts()

    def _attend(lp, i, pool, write, page_tables, seq_lens, starts):
        """Layer ``i``'s cache strategy: write the positions' latent in
        place, then the absorbed kernel over the rows' pages."""
        def attend(q_nope, q_rope, latent):
            T = q_nope.shape[1]
            new_pool = write(pool, i, _pad_columns(latent, width))
            q = _pad_columns(absorbed_queries(cfg, lp, q_nope, q_rope),
                             width)
            with jax.named_scope("mla.attend"):
                ctx = latent_paged_attention(q, new_pool, i, page_tables,
                                             seq_lens, starts, T, rank,
                                             sm_scale)
            return absorbed_output(cfg, lp, ctx, T), new_pool
        return attend

    def _layers(layers, x, pos, pool, write, page_tables, seq_lens, starts):
        """The unrolled stack -> (x, pool, counts (len(ROUTE_COUNTS)+1,):
        expert-layer calls and their ``ROUTE_COUNTS`` summed)."""
        total = jnp.zeros((1 + len(ROUTE_COUNTS),), jnp.int32)
        for i, lp in enumerate(layers):
            x, pool, counts = layer_math(
                cfg, lp, x, pos,
                _attend(lp, i, pool, write, page_tables, seq_lens, starts))
            if counts is not None:
                total = total + jnp.concatenate(
                    [jnp.ones((1,), jnp.int32), counts])
        return x, pool, total

    @partial(jax.jit, donate_argnums=(5,))
    def decode_step(outer, layers, tok, page_tables, lengths, pool):
        """-> (emission, pool', counts (len(CALL_COUNTS),))."""
        def write(pool, i, latent):          # (B, 1, width) at each row's end
            pages = jnp.take_along_axis(
                page_tables, (lengths // page_size)[:, None], 1)[:, 0]
            return pool.at[i, pages, lengths % page_size].set(
                latent[:, 0].astype(pool.dtype))
        x = jnp.take(outer["model.embed_tokens.weight"], tok, axis=0)[:, None]
        x, pool, counts = _layers(layers, x, lengths[:, None], pool, write,
                                  page_tables, lengths + 1, lengths)
        x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
        # an empty slot rides as a length-0 row: it reads nothing that counts
        read = jnp.sum(jnp.where(lengths > 0, lengths + 1, 0)).astype(jnp.int32)
        return (_emit(_logits(cfg, outer, x[:, 0])), pool,
                jnp.concatenate([counts, read[None]]))

    @partial(jax.jit, donate_argnums=(6,))
    def _chunk_program(outer, layers, chunk, start, page_tables, lengths,
                       pool, x_last):
        """One C-token chunk at absolute positions start..start+C-1:
        writes its pages, attends to every pool position < start + C, and
        harvests the hidden state of each sequence's (length - 1) row when
        it falls inside this chunk."""
        B, C = chunk.shape

        def write(pool, i, latent):          # whole pages scatter
            npg = C // page_size
            ids = jax.lax.dynamic_slice_in_dim(
                page_tables, start // page_size, npg, 1).reshape(-1)
            return pool.at[i, ids].set(
                latent.reshape(B * npg, page_size, width).astype(pool.dtype))
        x = jnp.take(outer["model.embed_tokens.weight"], chunk, axis=0)
        x, pool, counts = _layers(
            layers, x, start + jnp.arange(C), pool, write, page_tables,
            lengths, jnp.full((B,), start, jnp.int32))
        idx = jnp.clip(lengths - 1 - start, 0, C - 1)
        row = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32),
                                  1)[:, 0]
        hit = ((lengths - 1 >= start) & (lengths - 1 < start + C))[:, None]
        return (jnp.where(hit, row, x_last), pool,
                jnp.pad(counts, (0, 1)))      # a chunk reads no decode row

    def _prefill_chunk(outer, layers, chunk, start, page_tables, lengths,
                       pool, x_last, lora=None):
        x_last, pool, counts = _chunk_program(
            outer, layers, chunk, start, page_tables, lengths, pool, x_last)
        tally.add("prefill", counts)
        return x_last, pool
    _prefill_chunk._cache_size = _chunk_program._cache_size

    @jax.jit
    def _finish_prefill(outer, x_last, grammar=None):
        x = _rms(x_last, outer["model.norm.weight"], cfg.rms_norm_eps)
        return _emit(_logits(cfg, outer, x))

    prefill = chunked_prefill_shim(_prefill_chunk, _finish_prefill,
                                   chunked_prefill, cfg.hidden_size, dtype)

    @partial(jax.jit, donate_argnums=(5,), static_argnums=(6,))
    def _decode_n(outer, layers, tok, page_tables, lengths, pool, n):
        (emits, counts), tok, pool = decode_scan(
            lambda tok, lens, pool: decode_step(
                outer, layers, tok, page_tables, lens, pool),
            tok, lengths, pool, n)
        return emits, tok, pool, jnp.sum(counts, axis=0)

    def decode_n(outer, layers, tok, page_tables, lengths, pool, n):
        """``n`` decode steps in ONE compiled program; returns (emits (n,
        B, ...), next_tok (B,), pool'). ``pool`` is DONATED."""
        emits, tok, pool, counts = _decode_n(outer, layers, tok,
                                             page_tables, lengths, pool, n)
        tally.add("decode", counts)
        return emits, tok, pool
    decode_n._jit_inner = (_decode_n,)
    decode_n.counts = tally

    def step(outer, layers, tok, page_tables, lengths, pool):
        return decode_step(outer, layers, tok, page_tables, lengths,
                           pool)[:2]

    pool = jnp.zeros((L, n_pool_pages, page_size, width), dtype)
    return outer, layers, pool, prefill, step, decode_n


def latent_serving_decode_factory(model: DeepseekV3ForCausalLM,
                                  max_len: int = 256, page_size: int = 64,
                                  n_pool_pages: int = 256,
                                  batch_capacity: int = 8,
                                  chunked_prefill: int | None = None):
    """The object ``ServingEngine`` consumes, for a latent-cache model:
    paged only (``pick`` always answers "paged"; the dense slot is a stub
    that says why)."""
    paged = latent_paged_decode_factory(
        model, page_size=page_size, n_pool_pages=n_pool_pages,
        chunked_prefill=chunked_prefill)
    class _Serving:
        dense = PagedOnlyDense(_DENSE_REASON)
        paged_parts = paged
        capacity = batch_capacity
        max_len_ = max_len
        page_size_ = page_size
        n_pool_pages_ = n_pool_pages
        chunked_prefill_ = chunked_prefill
        # chunks ONE lane call may span: the kernel holds heads x width
        # query rows in VMEM (16 x 256 = 4096 rows: 21.2 MB, for which
        # the launcher asks); wider than 4 was never compiled for the chip
        chunked_prefill_widest_ = 4
        kv_layout_ = "latent"
        call_counts = paged[5].counts    # CallCounts: reset() / take()

        def pick(self, lengths, capacity=None, shared_prefix=False,
                 expect_churn=False):
            return "paged", paged

    return _Serving()
