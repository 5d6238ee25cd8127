"""Compiled KV-cache generation for Llama.

~ the reference's generative-inference flagship
(fused_multi_transformer_op.cu: stacked weights + in-place KV cache, one
kernel per decode step). TPU-native: prefill captures per-layer K/V into
a (L, B, kv_heads, max_len, head_dim) functional cache; each decode step
is ONE jitted program (lax.scan over the stacked layer weights) that
attends a single query position against the cache and writes its K/V at
`pos` via dynamic_update_slice — O(S) per token instead of the O(S²)
recompute of the eager `LlamaForCausalLM.generate`.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from ...jax_compat import device_put_sharded, make_mesh
from .llama import LlamaConfig, LlamaForCausalLM, apply_rotary
from .llama_functional import _rms, split_params  # noqa: F401 (re-export)
from .llama_functional import stack_layers, unstack_layers  # noqa: F401


def _stack_apply(body, x, stacked, scan_layers: bool = True):
    """Run ``body(carry, per_layer) -> (carry, ys)`` over the leading L
    axis of every leaf in ``stacked`` (the stack_layers convention shared
    with the training path).

    ``scan_layers=True`` lowers the layer body ONCE as a ``lax.scan`` —
    program size is O(1) in depth, which is what lets the two-model
    speculative program compile quickly at real model sizes (the
    unrolled form is ~L x the module text and compile time).
    ``scan_layers=False`` python-unrolls L copies of the body into the
    jaxpr: the parity/debug fallback the scan path is tested token-exact
    against (and the shape a per-layer-heterogeneous model would need).
    """
    if scan_layers:
        return jax.lax.scan(body, x, stacked)
    L = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    ys = []
    for i in range(L):
        x, y = body(x, jax.tree_util.tree_map(lambda a: a[i], stacked))
        ys.append(y)
    return x, jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *ys)


def _stack_carry(body, carry, stacked, scan_layers: bool = True):
    """``_stack_apply`` for a body that keeps STATE and knows its layer:
    ``body(i, carry, per_layer) -> carry`` with ``i`` the layer index (a
    traced int32 under the scan, a Python int unrolled). What rides the
    carry is updated in place by the loop — the paged K/V pools do; a
    scan's ``xs`` are sliced a layer at a time and its ``ys`` stacked
    into a fresh buffer, which for a pool is a copy of the pool."""
    L = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if scan_layers:
        return jax.lax.scan(
            lambda c, xs: (body(xs[0], c, xs[1]), None), carry,
            (jnp.arange(L, dtype=jnp.int32), stacked))[0]
    for i in range(L):
        carry = body(i, carry,
                     jax.tree_util.tree_map(lambda a: a[i], stacked))
    return carry


def _mm(x, w):
    """Matmul against a weight that may be int8-quantized.

    Plain array -> x @ w. Tuple (w_q int8 (in,out), scale f32 (out,)) ->
    the shared int8 GEMM (quantization.int8.int8_matmul): 2x the bf16
    dot rate on v5e-class MXUs and half the weight HBM bytes — decode at
    small batch is weight-bandwidth-bound.
    """
    if not isinstance(w, tuple):
        return x @ w
    from ...quantization.int8 import int8_matmul
    return int8_matmul(x, w[0], w[1])


def _quantize_weights(tree, keys):
    """Per-output-channel int8 for the named (..., in, out) weights:
    value -> (int8 data, f32 scale over the 'in' axis)."""
    from ...quantization.int8 import quantize_stacked_jnp
    out = dict(tree)
    for k in keys:
        if tree.get(k) is not None:
            out[k] = quantize_stacked_jnp(tree[k])
    return out


_PROJ_KEYS = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
              "self_attn.v_proj.weight", "self_attn.o_proj.weight",
              "mlp.gate_proj.weight", "mlp.up_proj.weight",
              "mlp.down_proj.weight")


# --- multi-adapter LoRA (batched multi-model serving) ----------------------

@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    """Multi-adapter LoRA layout for the paged serving decode path:
    a device-resident ADAPTER BANK of ``n_slots`` stacked low-rank
    delta sets over the q/v attention projections (the classic LoRA
    target pair), applied per batch row by slot index — the
    S-LoRA / Punica batched-multi-adapter design riding PR 1's
    weights-as-args invariant: the bank and the per-row index vector
    are jit INPUTS, so one fixed-shape ``decode_n`` program serves any
    mix of adapters and admission churn never recompiles.

    Slot 0 is the reserved IDENTITY (all-zero deltas): ``adapter=None``
    rows are routed through it and their delta is an exact float zero
    — token-for-token the base model. ``rank`` is the low-rank width
    ``r`` (delta = ``(h @ A) @ B * scale``); ``scale`` is the merged
    ``alpha / r`` multiplier applied at serve time."""

    n_slots: int = 4
    rank: int = 4
    scale: float = 1.0

    def __post_init__(self):
        if self.n_slots < 2:
            raise ValueError("LoRAConfig needs n_slots >= 2 (slot 0 "
                             "is the reserved identity)")
        if self.rank < 1:
            raise ValueError("LoRAConfig rank must be >= 1")


def as_lora_config(lora) -> "LoRAConfig | None":
    """Normalize the ``lora=`` argument: None stays None, a
    ``(n_slots, rank)`` tuple becomes a LoRAConfig, a LoRAConfig
    passes through."""
    if lora is None or isinstance(lora, LoRAConfig):
        return lora
    if isinstance(lora, tuple) and len(lora) == 2:
        return LoRAConfig(n_slots=int(lora[0]), rank=int(lora[1]))
    raise ValueError(f"lora {lora!r}: pass None, (n_slots, rank), or "
                     "a LoRAConfig")


LORA_KEYS = ("q_A", "q_B", "v_A", "v_B")


# --- constrained decoding (grammar/JSON-schema guided generation) ----------

@dataclasses.dataclass(frozen=True)
class GrammarConfig:
    """Constrained-decoding layout for the paged serving decode path:
    a device-resident GRAMMAR BANK of ``n_slots * max_states`` packed
    uint32 allow-bitmask rows, indexed per batch row by a flat
    ``slot * max_states + state`` id — the same per-row-state-as-jit-
    data mechanism the adapter bank (PR 12) and the quantized page
    tier (PR 14) ride: the bank and the id vector are jit INPUTS, so
    one fixed-shape ``decode_n`` program serves any mix of schemas
    and grammar churn never recompiles.

    Slot 0 is the reserved ALL-ALLOW identity (every bit set): free
    rows carry flat id 0 and their masked logits are exactly the base
    logits — token-for-token the unconstrained model. ``max_states``
    bounds one automaton's DFA size (compilation refuses larger
    schemas loudly)."""

    n_slots: int = 4
    max_states: int = 64

    def __post_init__(self):
        if self.n_slots < 2:
            raise ValueError("GrammarConfig needs n_slots >= 2 "
                             "(slot 0 is the reserved all-allow "
                             "identity)")
        if self.max_states < 2:
            raise ValueError("GrammarConfig max_states must be >= 2")


def as_grammar_config(grammar) -> "GrammarConfig | None":
    """Normalize the ``grammar=`` argument: None stays None, a
    ``(n_slots, max_states)`` tuple becomes a GrammarConfig, a
    GrammarConfig passes through."""
    if grammar is None or isinstance(grammar, GrammarConfig):
        return grammar
    if isinstance(grammar, tuple) and len(grammar) == 2:
        return GrammarConfig(n_slots=int(grammar[0]),
                             max_states=int(grammar[1]))
    raise ValueError(f"grammar {grammar!r}: pass None, (n_slots, "
                     "max_states), or a GrammarConfig")


def grammar_bank_hooks(vocab_size: int, grammar: "GrammarConfig",
                       tp: "TPConfig | None" = None):
    """The grammar-cache device hooks: ``(init_grammar_bank,
    upload_grammar)``.

    ``init_grammar_bank()`` builds the ``(n_slots * max_states,
    ceil(vocab/32))`` uint32 bank with slot 0's whole block all-ones
    (the all-allow identity every free row indexes at flat id 0) and
    the rest zero until uploaded. Under ``tp`` the bank is placed
    REPLICATED on the mesh (a bank is a few KB — replication costs
    nothing and every shard masks its own logits copy identically).

    ``upload_grammar(bank, slot, compiled)`` writes one compiled
    automaton's packed per-state masks into the slot's block
    (functional ``.at[...].set`` — the returned bank REBINDS), zeroing
    the block's unused tail so a recycled slot can never leak a
    larger predecessor's rows. ``compiled`` is a
    ``serving.grammar.CompiledGrammar``-shaped object (``n_states``,
    ``masks``)."""
    words = (int(vocab_size) + 31) // 32
    ms, ns = grammar.max_states, grammar.n_slots

    def init_grammar_bank():
        bank = np.zeros((ns * ms, words), np.uint32)
        bank[:ms] = np.uint32(0xFFFFFFFF)
        bank = jnp.asarray(bank)
        if tp is not None:
            bank = device_put_sharded(bank, tp.build_mesh())
        return bank

    def upload_grammar(bank, slot, compiled):
        n = int(compiled.n_states)
        if n > ms:
            raise ValueError(f"grammar compiles to {n} states > "
                             f"max_states {ms}")
        masks = np.asarray(compiled.masks, np.uint32)
        if masks.shape != (n, words):
            raise ValueError(f"grammar masks have shape {masks.shape},"
                             f" bank rows want (*, {words}) (vocab "
                             "mismatch?)")
        block = np.zeros((ms, words), np.uint32)
        block[:n] = masks
        return bank.at[slot * ms:(slot + 1) * ms].set(
            jnp.asarray(block))

    return init_grammar_bank, upload_grammar


def _bgmv(h, A, B_, ids):
    """Batched gather matvec (Punica's BGMV): per-row low-rank delta
    ``(h @ A[row]) @ B[row]``. ``h`` (B, T, H); ``A`` (n_slots, H, r);
    ``B_`` (n_slots, r, out); ``ids`` (B,) int slot indices. The
    gather is by row SEGMENT — every row of a same-adapter group reads
    the same bank slice (the engine's admission ordering groups
    adapter-sharers adjacently) — and the whole thing is fixed-shape:
    slot indices are data, so adapter churn never recompiles."""
    Ar = jnp.take(A, ids, axis=0)          # (B, H, r)
    Br = jnp.take(B_, ids, axis=0)         # (B, r, out)
    t = jnp.einsum("bth,bhr->btr", h, Ar)
    return jnp.einsum("btr,bro->bto", t, Br)


def synthesize_lora_deltas(cfg: LlamaConfig, rank: int, seed: int = 0,
                           init_scale: float = 0.02) -> dict:
    """One seeded host-resident LoRA delta set for ``cfg``'s decode
    path, the layout ``llama_paged_decode_factory(lora=...)``'s
    ``upload_adapter`` hook consumes: ``q_A``/``v_A`` (L, H, r) and
    ``q_B``/``v_B`` (L, r, out) numpy float32. Both factors are drawn
    nonzero (unlike training-init LoRA, where B starts at zero — a
    zero delta would make every adapter the base model and parity
    tests vacuous). Deterministic in (cfg, rank, seed)."""
    rng = np.random.default_rng(seed)
    L = cfg.num_hidden_layers
    H = cfg.hidden_size
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = H // nh

    def draw(*shape):
        return (rng.standard_normal(shape) * init_scale).astype(
            np.float32)

    return {"q_A": draw(L, H, rank), "q_B": draw(L, rank, nh * hd),
            "v_A": draw(L, H, rank), "v_B": draw(L, rank, nkv * hd)}


def lora_bank_hooks(cfg: LlamaConfig, lora: "LoRAConfig", dtype,
                    tp: "TPConfig | None" = None):
    """The adapter-cache device hooks for a llama decode path:
    ``(init_adapter_bank, upload_adapter)``.

    ``init_adapter_bank()`` builds the all-zero device bank — per
    LoRA key a ``(L, n_slots, ...)`` array stacked layer-first so it
    scans with the layer weights; slot 0 stays zero forever (the
    identity every ``adapter=None`` row decodes through). Under
    ``tp`` the bank is placed REPLICATED on the mesh (rank is tiny —
    a few KB per adapter — so replication costs nothing and the
    delta add simply reshards into the column-parallel q/v layout).

    ``upload_adapter(bank, slot, deltas)`` is the paced host->device
    upload: a functional ``.at[:, slot].set`` per key (the returned
    bank REBINDS — sharding and every other slot's content
    preserved). ``deltas`` is a ``synthesize_lora_deltas``-shaped
    host tree: ``q_A``/``v_A`` (L, H, r), ``q_B``/``v_B``
    (L, r, out)."""
    L = cfg.num_hidden_layers
    H = cfg.hidden_size
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = H // nh
    r, ns = lora.rank, lora.n_slots
    shapes = {"q_A": (L, ns, H, r), "q_B": (L, ns, r, nh * hd),
              "v_A": (L, ns, H, r), "v_B": (L, ns, r, nkv * hd)}

    def init_adapter_bank():
        bank = {k: jnp.zeros(s, dtype) for k, s in shapes.items()}
        if tp is not None:
            bank = device_put_sharded(bank, tp.build_mesh())
        return bank

    def upload_adapter(bank, slot, deltas):
        for k in LORA_KEYS:
            if k not in deltas:
                raise ValueError(f"adapter delta set missing {k!r} "
                                 f"(needs {LORA_KEYS})")
            want = shapes[k][:1] + shapes[k][2:]
            got = tuple(np.asarray(deltas[k]).shape)
            if got != want:
                raise ValueError(f"adapter delta {k} has shape {got}, "
                                 f"bank slot wants {want} (rank/model "
                                 "mismatch?)")
        return {k: bank[k].at[:, slot].set(
            jnp.asarray(np.asarray(deltas[k]), bank[k].dtype))
            for k in LORA_KEYS}

    return init_adapter_bank, upload_adapter


# --- speculative serving (draft + target over ONE paged pool) --------------

@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Adaptive speculative-decode policy for the serving engine.

    ``n_draft`` is the draft window: each spec round proposes that
    many tokens (one draft walk) and verifies them in ONE batched
    target block — greedy acceptance keeps every emitted token
    EXACTLY the target model's greedy token, so speculation changes
    latency, never content.

    The ADAPTIVE half is per-request + per-run:

    - eligibility (``Policy.spec_route``): a request decodes
      speculatively only when ``priority <= max_priority`` AND its
      deadline is loose (``deadline_ms`` unset or >=
      ``loose_deadline_ms``) — tight/high-priority traffic keeps the
      plain fixed-latency decode path;
    - acceptance floor: the engine EWMAs the measured per-turn
      acceptance (accepted/proposed, ``ewma_alpha``); once at least
      ``min_rounds`` spec TURNS (EWMA samples — a busy turn's eight
      rows are still one sample) are in evidence and the EWMA sits
      below ``accept_floor``, the route LATCHES to plain decode for
      the rest of the run (draft compute that mostly misses is pure
      waste);
    - overload fallback (``overload_fallback``): while a
      page-severity SLO incident delivered through
      ``QoSScheduler.note_incident`` (e.g. a ``BurnRateRule`` firing)
      stays open, spec rows decode plain — draft compute is spent
      exactly when capacity is scarce, so overload is the moment to
      stop spending it. The route re-enables when the incident
      closes.

    Every flip is logged on the virtual clock with the rule that
    fired (``ServeResult.spec_stats["flips"]``)."""

    n_draft: int = 4
    accept_floor: float = 0.35
    ewma_alpha: float = 0.25
    min_rounds: int = 8
    max_priority: int = 0
    loose_deadline_ms: float = 8000.0
    overload_fallback: bool = True

    def __post_init__(self):
        if self.n_draft < 1:
            raise ValueError("SpecConfig n_draft must be >= 1")
        if not 0.0 <= self.accept_floor <= 1.0:
            raise ValueError("accept_floor is an acceptance fraction "
                             "in [0, 1]")
        if not 0.0 < self.ewma_alpha <= 1.0:
            raise ValueError("ewma_alpha must be in (0, 1]")
        if self.min_rounds < 1:
            raise ValueError("min_rounds must be >= 1")
        if self.loose_deadline_ms < 0:
            raise ValueError("loose_deadline_ms must be >= 0")


def as_spec_config(spec) -> "SpecConfig | None":
    """Normalize the ``spec=`` argument: None/False stays off, True
    is the stock SpecConfig (bool checked FIRST — ``True`` is an int
    in python, and silently reading it as ``n_draft=1`` would cripple
    the draft window), an int becomes a SpecConfig with that draft
    window, a SpecConfig passes through."""
    if isinstance(spec, bool):
        return SpecConfig() if spec else None
    if spec is None or isinstance(spec, SpecConfig):
        return spec
    if isinstance(spec, int):
        return SpecConfig(n_draft=spec)
    raise ValueError(f"spec {spec!r}: pass None, True, an int "
                     "n_draft, or a SpecConfig")


def _write_positions(pool_l, kv, page_tables, positions, page_size):
    """kv (B, nkv, T, hd) written at PER-ROW absolute ``positions``
    (B, T) through the page tables — the speculative draft/verify
    write. Unlike ``_write_pages`` (page-aligned) or ``_write_token``
    (one slot), spec blocks start at each row's current length, so
    every (row, t) scatters to its own (page, offset). Positions of
    inactive rows resolve through page-table row 0 into the reserved
    padding page (the same junk-write discipline empty decode slots
    ride)."""
    pages = jnp.take_along_axis(page_tables, positions // page_size, 1)
    offs = positions % page_size
    if isinstance(pool_l, tuple):
        data, sc = pool_l
        qd, s = _q8(kv)
        return (data.at[:, pages, offs].set(
                    jnp.transpose(qd, (1, 0, 2, 3))),
                sc.at[:, pages, offs].set(jnp.transpose(s, (1, 0, 2))))
    return pool_l.at[:, pages, offs].set(
        jnp.transpose(kv, (1, 0, 2, 3)).astype(pool_l.dtype))


def build_spec_step(cfg_t: LlamaConfig, cfg_d: LlamaConfig,
                    page_size: int, scan_layers: bool = True):
    """ONE compiled speculative round over the paged pool, batched
    across decode slots: the draft consumes ``[prev, tok]`` (two
    positions — re-consuming position len-1 rewrites identical K/V
    and guarantees the draft cache has no hole after a
    fully-accepted round, the PR-1 two-token-feed trick) then walks
    ``k-1`` more greedy steps as an in-jit scan; the target verifies
    ``[tok, d_0..d_{k-1}]`` in ONE (k+1)-position block through its
    pool. Per-row positions are data (``lengths``), so rows at
    different depths — and rows routed PLAIN this turn, riding along
    as length-0 page-0 rows — share the one fixed-shape program and
    admission churn never recompiles.

    Acceptance is the branch-free PR-1 arithmetic: ``n`` = length of
    the matching draft prefix, the candidate vector holds accepted
    drafts then the target's correction/bonus token, junk beyond
    ``n`` is overwritten by later rounds (the same
    overwrite-rollback invariant both pools use — K/V written for
    rejected proposals sits beyond the advanced length and the key
    masks never reach it).

    Both models' weights travel as ARGUMENTS (the PR-1
    weights-as-jit-args invariant — a closure capture would inline
    model-sized constants into the module); under TP the caller
    passes target weights sharded and draft weights replicated, and
    the program inherits the arg shardings unchanged.

    Returns a host shim ``spec_step(outer_t, layers_t, outer_d,
    layers_d, prev_tok, tok, page_tables, lengths, pools_t, pools_d,
    k) -> (accepted (B,), cand (B, k+1), pools_t', pools_d')`` whose
    inner jitted program is advertised via ``_jit_inner`` (the PR-4
    convention), so the engine's recompile detector and
    ``jit.compile`` trace instants see spec compiles."""

    def make_block(cfg):
        nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        hd = cfg.hidden_size // nh

        def block(outer, layers, tokens, pos, page_tables, pools):
            """tokens (B, T) at per-row absolute positions ``pos``
            (B, T): write K/V at those slots, attend causally over
            the whole pool, return (logits (B, T, V), pools')."""
            k_pools, v_pools = pools
            B, T = tokens.shape
            W = page_tables.shape[1]
            S = W * page_size
            x = jnp.take(outer["model.embed_tokens.weight"], tokens,
                         axis=0)
            key_ok = jnp.arange(S)[None, None, :] <= pos[:, :, None]
            mask = key_ok[:, None]

            def gather(pool):
                if isinstance(pool, tuple):
                    data, sc = pool
                    g = (data[:, page_tables].astype(jnp.float32)
                         * sc[:, page_tables][..., None])
                else:
                    g = pool[:, page_tables]
                return jnp.swapaxes(g, 0, 1).reshape(B, nkv, S, hd)

            def body(x, per_layer):
                lp, kp_l, vp_l = per_layer

                def attend(q, k, v):
                    kp = _write_positions(kp_l, k, page_tables, pos,
                                          page_size)
                    vp = _write_positions(vp_l, v, page_tables, pos,
                                          page_size)
                    return _attend(cfg, q,
                                   gather(kp).astype(q.dtype),
                                   gather(vp).astype(q.dtype),
                                   mask), (kp, vp)

                x, (kp, vp) = _layer_math(cfg, lp, x, pos, attend)
                return x, (kp, vp)

            x, (k_pools, v_pools) = _stack_apply(
                body, x, (layers, k_pools, v_pools), scan_layers)
            x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
            return _logits(cfg, outer, x), (k_pools, v_pools)

        return block

    block_t = make_block(cfg_t)
    block_d = make_block(cfg_d)

    def _step_body(outer_t, layers_t, outer_d, layers_d, prev_tok,
                   tok, page_tables, lengths, pools_t, pools_d, k):
        B = tok.shape[0]
        lens = lengths
        # draft: consume [prev, tok] at (len-1, len), emit d_0, then
        # walk k-1 more steps (in-jit scan — one traced draft block)
        feed = jnp.stack([prev_tok, tok], 1).astype(jnp.int32)
        pos0 = lens[:, None] + jnp.asarray([-1, 0])[None, :]
        lg, pools_d = block_d(outer_d, layers_d, feed, pos0,
                              page_tables, pools_d)
        cur = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)

        def dstep(carry, i):
            cur, pd = carry
            lg, pd = block_d(outer_d, layers_d, cur[:, None],
                             lens[:, None] + 1 + i, page_tables, pd)
            return (jnp.argmax(lg[:, -1], -1).astype(jnp.int32),
                    pd), cur

        (last_d, pools_d), ds = jax.lax.scan(
            dstep, (cur, pools_d), jnp.arange(k - 1))
        drafts = jnp.concatenate(
            [jnp.swapaxes(ds, 0, 1), last_d[:, None]], 1) \
            if k > 1 else last_d[:, None]                    # (B, k)
        # target verifies [tok, d_0..d_{k-1}] in ONE (k+1)-pos block
        blk = jnp.concatenate([tok[:, None], drafts], 1) \
            .astype(jnp.int32)
        pos_t = lens[:, None] + jnp.arange(k + 1)[None, :]
        lg_t, pools_t = block_t(outer_t, layers_t, blk, pos_t,
                                page_tables, pools_t)
        t = jnp.argmax(lg_t, -1).astype(jnp.int32)       # (B, k+1)
        matches = (drafts == t[:, :k]).astype(jnp.int32)
        n = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
        idx = jnp.arange(k + 1)[None, :]
        dpad = jnp.concatenate(
            [drafts, jnp.zeros((B, 1), jnp.int32)], 1)
        cand = jnp.where(idx < n[:, None], dpad, t)
        return n, cand, pools_t, pools_d

    step = partial(jax.jit, static_argnums=(10,),
                   donate_argnums=(8, 9))(_step_body)

    def spec_step(outer_t, layers_t, outer_d, layers_d, prev_tok,
                  tok, page_tables, lengths, pools_t, pools_d, k):
        return step(outer_t, layers_t, outer_d, layers_d, prev_tok,
                    tok, page_tables, lengths, pools_t, pools_d, k)

    spec_step._jit_inner = (step,)
    return spec_step


# --- tensor parallelism (sharded decode weights + paged pool) --------------

@dataclasses.dataclass(frozen=True)
class TPConfig:
    """Tensor-parallel layout for the serving decode path: a 1-D named
    device mesh, attention heads and MLP hidden dims partitioned over
    ``axis``, everything else (embeddings, norms, lm head, page
    tables) replicated. Threaded into the decode/prefill factories —
    weights and pools are placed ONCE at load (NamedSharding;
    jax_compat.device_put_sharded) and every jitted call inherits the
    arg shardings, so the fixed-shape ``decode_n`` batches still never
    recompile across churn.

    ``hbm_budget_bytes_per_device``: optional per-device byte budget
    for weights + KV pool together; the factory measures the ACTUAL
    per-device resident bytes after placement and refuses loudly
    (MemoryError) when they exceed it — the "a model bigger than one
    chip serves only under TP" check the serving_tp gate exercises.
    """

    mesh_shape: tuple = (2,)
    axis: str = "tp"
    hbm_budget_bytes_per_device: int | None = None

    def __post_init__(self):
        shape = tuple(int(s) for s in self.mesh_shape)
        object.__setattr__(self, "mesh_shape", shape)
        if len(shape) != 1 or shape[0] < 1:
            raise ValueError(f"TPConfig mesh_shape {shape}: tensor "
                             "parallelism is a 1-D mesh (one named "
                             "axis)")

    @property
    def size(self) -> int:
        return self.mesh_shape[0]

    def build_mesh(self):
        return make_mesh(self.mesh_shape, (self.axis,))


def as_tp_config(tp) -> TPConfig | None:
    """Normalize the ``tp=`` argument: None stays None, an int becomes
    a 1-D TPConfig of that many devices, a TPConfig passes through."""
    if tp is None or isinstance(tp, TPConfig):
        return tp
    if isinstance(tp, int):
        return TPConfig(mesh_shape=(tp,))
    raise ValueError(f"tp {tp!r}: pass None, an int degree, or a "
                     "TPConfig")


def tp_layer_specs(axis: str = "tp") -> dict:
    """PartitionSpec args for the STACKED (L, in, out) decode layer
    weights: column-parallel q/k/v and MLP gate/up (output features —
    heads / hidden dims — split over ``axis``), row-parallel o_proj
    and down_proj (input features split; jit inserts the psum over the
    contraction), norms replicated (missing keys -> replicated in
    ``device_put_sharded``). The Megatron layout: one all-reduce per
    attention block, one per MLP, no resharding between them."""
    col = (None, None, axis)
    row = (None, axis, None)
    return {
        "self_attn.q_proj.weight": col,
        "self_attn.k_proj.weight": col,
        "self_attn.v_proj.weight": col,
        "self_attn.o_proj.weight": row,
        "mlp.gate_proj.weight": col,
        "mlp.up_proj.weight": col,
        "mlp.down_proj.weight": row,
    }


def tp_pool_spec(axis: str = "tp") -> tuple:
    """PartitionSpec args for the paged KV pools (L, Hkv, P, page,
    hd): page CONTENT splits by kv head over ``axis``; page ids,
    tables and lengths stay host-side and replicated (trailing dims
    unspecified = replicated, which also covers the int8 scale leaves'
    (L, Hkv, P, page) shape)."""
    return (None, axis)


def paged_kernel_call(kernel, q, kp, vp, *scalars, layer=None, mesh=None,
                      axis=None):
    """``kernel(q, k_pages, v_pages, *scalars, layer=layer)`` with an
    int8 pool's (data, scales) pair unpacked. The pools are the whole
    (L, Hkv, P, page, hd) ones read at ``layer`` (a Python int or the
    layer loop's traced counter), or one layer's pages with ``layer``
    None. A Mosaic call does not lower under GSPMD, so with a ``mesh``
    it runs inside a shard_map: query heads and the pools' kv-head axis
    manual over ``axis`` (``tp_pool_spec``'s layout — each device walks
    its own kv heads' pages), tables / lengths / positions and the
    layer index replicated."""
    quant = isinstance(kp, tuple)
    pools = (kp[0], vp[0], kp[1], vp[1]) if quant else (kp, vp)
    if layer is not None:
        scalars += (jnp.asarray(layer, jnp.int32),)      # rides last

    def call(q, k, v, *rest):
        kw = {}
        if quant:
            kw["k_scales"], kw["v_scales"], *rest = rest
        if layer is not None:
            *rest, kw["layer"] = rest
        return kernel(q, k, v, *rest, **kw)

    if mesh is not None:
        pool_spec = P(axis) if layer is None else P(*tp_pool_spec(axis))
        call = jax.shard_map(
            call, mesh=mesh,
            in_specs=(P(None, axis),) + (pool_spec,) * len(pools)
            + (P(),) * len(scalars),
            out_specs=P(None, axis), check_vma=False)
    return call(q, *pools, *scalars).astype(q.dtype)


def _validate_tp(cfg: LlamaConfig, tp: TPConfig):
    if tp.size > len(jax.devices()):
        raise ValueError(f"tp={tp.size} needs {tp.size} devices, have "
                         f"{len(jax.devices())}")
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    inter = cfg.intermediate_size
    for name, dim in (("attention heads", nh), ("kv heads", nkv),
                      ("mlp intermediate", inter)):
        if dim % tp.size:
            raise ValueError(
                f"tp={tp.size} does not divide {name} ({dim}) — the "
                "head/hidden partition would be ragged")


def tree_device_bytes(tree) -> int:
    """Resident bytes of ``tree``'s leaves on ONE device: a sharded
    leaf contributes one device's shard bytes (computed from the
    sharding's shard shape — metadata only, so a DONATED buffer that
    already died still answers), a replicated or unsharded leaf its
    full size — the per-device HBM footprint the TP capacity claims
    are judged on. Host (numpy) leaves count whole."""
    total = 0
    for a in jax.tree_util.tree_leaves(tree):
        sh = getattr(a, "sharding", None)
        if sh is not None and hasattr(sh, "shard_shape"):
            shard = sh.shard_shape(a.shape)
            total += int(np.prod(shard, dtype=np.int64)) \
                * a.dtype.itemsize
        else:
            total += int(getattr(a, "nbytes", np.asarray(a).nbytes))
    return total


def decode_need_bytes_per_device(outer, layers, pools) -> int:
    """THE per-device residency arithmetic for a decode factory:
    weights + KV pools, one device's share each. The factory's
    ``hbm_budget_bytes_per_device`` refusal, the bench's capacity
    demo and the tests all call THIS — three private copies could
    silently diverge and flip the refuses/serves verdict."""
    return (tree_device_bytes(outer) + tree_device_bytes(layers)
            + tree_device_bytes(pools))


# --- quantized KV page tier (kv_quant serving) ------------------------------

def kv_quant_page_bytes(cfg: "LlamaConfig", page_size: int,
                        dtype) -> tuple:
    """(full_precision, int8+scale) bytes ONE page costs across all
    layers, k+v — the per-page prices ``PagedKVCache.stored_bytes()``
    charges. A quantized slot stores head_dim int8 bytes plus one f32
    per-slot scale (the _q8 codec), so the int8 price is
    ``hd + 4`` bytes per slot vs ``hd * itemsize`` full precision."""
    L = cfg.num_hidden_layers
    nkv = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    slots = L * nkv * page_size
    fp = 2 * slots * hd * np.dtype(dtype).itemsize
    q = 2 * slots * (hd + 4)
    return fp, q


@jax.jit
def compact_kv_pages(pools, mask):
    """Quantize the masked pages of a PRESSURE-tier pool (functional):
    per-slot absmax int8 (``_q8``) written into the int8 arena, tier
    bits set. Fixed shape — ``mask`` is a (P,) bool jit INPUT, so any
    compaction batch reuses the one compiled program and compaction
    churn never recompiles. The full-precision slots of a compacted
    page are left in place but dead: every read goes through the tier
    mask, and the write path clears a page's tier bit in the same
    program that rewrites it."""
    (kf, kq, ks), (vf, vq, vs), tier = pools
    m5 = mask[None, None, :, None, None]
    m4 = mask[None, None, :, None]

    def one(fp, qd0, s0):
        qd, s = _q8(fp)
        return jnp.where(m5, qd, qd0), jnp.where(m4, s, s0)

    kq, ks = one(kf, kq, ks)
    vq, vs = one(vf, vq, vs)
    return (kf, kq, ks), (vf, vq, vs), tier | mask


def export_quant_pages(pools, page_ids):
    """Slice a PRESSURE pool's pages for a disaggregated handoff: both
    arenas AND the per-page tier bits travel, so a mixed-tier chain
    re-materializes (quantized pages re-compact) exactly on import.
    The default engine export (page-axis tree_map) cannot carry the
    1-D tier leaf — this is the factory override it looks for."""
    idx = jnp.asarray(list(page_ids))
    (kf, kq, ks), (vf, vq, vs), tier = pools

    def sl(a):
        return a[:, :, idx]

    return ((sl(kf), sl(kq), sl(ks)), (sl(vf), sl(vq), sl(vs)),
            tier[idx])


def import_quant_pages(pools, page_ids, data):
    """Scatter an exported mixed-tier chain into a PRESSURE pool at
    ``page_ids`` (the importer's freshly allocated pages)."""
    idx = jnp.asarray(list(page_ids))
    (kf, kq, ks), (vf, vq, vs), tier = pools
    (kfd, kqd, ksd), (vfd, vqd, vsd), td = data

    def st(a, d):
        return a.at[:, :, idx].set(d)

    return ((st(kf, kfd), st(kq, kqd), st(ks, ksd)),
            (st(vf, vfd), st(vq, vqd), st(vs, vsd)),
            tier.at[idx].set(td))


# --- heterogeneous-handoff transforms (reshard-on-import) -------------------

def repage_kv_data(data, page_size_from: int, page_size_to: int,
                   n_tokens: int):
    """Re-page an exported KV chain across page geometries: every leaf
    is ``(L, Hkv, n_pages, page_size, *tail)`` (page content ``tail =
    (hd,)``; the int8 scale leaves' ``tail = ()``), tokens packed
    contiguously in chain order — so the transform is flatten the slot
    axis, keep the ``n_tokens`` real positions, pad to the destination
    chain's slot count, refold. Pad slots sit beyond the row's length
    like the slack of a directly-prefilled last page: data slots pad 0,
    per-slot scale leaves pad 1 (the pool-init scale, so the adopted
    chain is indistinguishable from one written in place). PRESSURE
    chains never reach here — their per-page tier bits have no
    token-resolution meaning, so ``handoff_steps`` refuses the pairing
    upstream."""
    n_to = -(-n_tokens // page_size_to)

    def one(a):
        a = np.asarray(a)
        L, H, n, ps = a.shape[:4]
        tail = a.shape[4:]
        if n * ps < n_tokens:
            raise ValueError(
                f"repage: chain carries {n}x{ps} slots but claims "
                f"{n_tokens} tokens")
        flat = a.reshape(L, H, n * ps, *tail)[:, :, :n_tokens]
        pad = n_to * page_size_to - n_tokens
        if pad:
            fill = np.ones if len(tail) == 0 else np.zeros
            flat = np.concatenate(
                [flat, fill((L, H, pad) + tail, a.dtype)], axis=2)
        return flat.reshape(L, H, n_to, page_size_to, *tail)

    return jax.tree_util.tree_map(one, data)


def transcode_kv_data(data, quant_from, quant_to):
    """Transcode an exported FULL-PRECISION chain ``(k, v)`` into the
    destination codec. Runs the SAME ``_q8`` per-slot absmax codec the
    destination's own write path uses (``_cache_write``), so a
    transcoded page is bit-identical to the page a direct int8 engine
    would have written from the same K/V values.

    - ``'int8'``: ``((k_int8, k_scale), (v_int8, v_scale))`` — scales
      stamped per slot over head_dim, the int8 pool leaf structure.
    - ``'pressure'``: both arenas plus an ALL-SET tier mask — the
      imported chain lands parked in the int8 tier (that is what the
      priced transcode bought; the caller mirrors the positions into
      ``quant_pages`` so the importer's byte census prices it), and the
      fp arena keeps the exact source values so a later rewrite/tier
      clear reads them back.

    Quantized sources do not transcode: int8 cannot recover precision
    (→ fp refused) and carries no tier bits (→ pressure refused);
    ``handoff_steps`` refuses those pairings before data ever moves."""
    if quant_from is not None:
        raise ValueError(
            f"transcode: source codec {quant_from!r} is not "
            "transcodable (only full-precision chains re-encode)")
    k, v = data
    k, v = jnp.asarray(k), jnp.asarray(v)
    if quant_to == "int8":
        return _q8(k), _q8(v)
    if quant_to == "pressure":
        kq, ks = _q8(k)
        vq, vs = _q8(v)
        tier = jnp.ones((k.shape[2],), bool)
        return (k, kq, ks), (v, vq, vs), tier
    raise ValueError(f"transcode: unknown destination codec "
                     f"{quant_to!r}")


def shard_decode_params(outer, layers, tp: TPConfig):
    """Place decode weights on the TP mesh ONCE at load: layer
    projections per ``tp_layer_specs``, outer params (embeddings,
    final norm, lm head) replicated. Returns (outer, layers, mesh)."""
    mesh = tp.build_mesh()
    layers = device_put_sharded(layers, mesh, tp_layer_specs(tp.axis))
    outer = device_put_sharded(outer, mesh)
    return outer, layers, mesh


def _proj_qkv(cfg: LlamaConfig, p, h, pos, lora=None):
    """h: (B, T, H); pos: (T,) absolute positions. Returns q,k,v with
    rotary applied — q (B, nh, T, hd), k/v (B, nkv, T, hd).

    ``lora`` (multi-adapter serving only): ``(bank_l, ids, scale)`` —
    this layer's adapter-bank slice (``q_A``/``q_B``/``v_A``/``v_B``,
    each (n_slots, ...)) plus per-row slot indices; the low-rank
    ``_bgmv`` delta lands on q and v BEFORE the head reshape/rotary.
    Slot 0 holds zeros, so identity rows add an exact float 0."""
    B, T, H = h.shape
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = H // nh
    q = _mm(h, p["self_attn.q_proj.weight"])
    k = _mm(h, p["self_attn.k_proj.weight"]).reshape(B, T, nkv, hd)
    v = _mm(h, p["self_attn.v_proj.weight"])
    if lora is not None:
        bank_l, ids, scale = lora
        q = q + _bgmv(h, bank_l["q_A"], bank_l["q_B"], ids) \
            * jnp.asarray(scale, q.dtype)
        v = v + _bgmv(h, bank_l["v_A"], bank_l["v_B"], ids) \
            * jnp.asarray(scale, v.dtype)
    q = q.reshape(B, T, nh, hd)
    v = v.reshape(B, T, nkv, hd)
    q = apply_rotary(q, pos, cfg.rope_theta)
    k = apply_rotary(k, pos, cfg.rope_theta)
    return (jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2))


def _q8(x):
    """Per-(batch, head, slot) absmax int8 quantization over head_dim —
    the KV-cache codec (serving memory halves vs bf16; the dequant
    multiply fuses into the attention matmuls)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), -1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _cache_write(cache, kv, write_at):
    """Write a (B, nkv, T, hd) block at slot ``write_at``; quantized
    caches are (int8 data, f32 scales) tuples."""
    if isinstance(cache, tuple):
        data, sc = cache
        qv, s = _q8(kv)
        data = jax.lax.dynamic_update_slice(data, qv, (0, 0, write_at, 0))
        sc = jax.lax.dynamic_update_slice(sc, s, (0, 0, write_at))
        return (data, sc)
    return jax.lax.dynamic_update_slice(cache, kv, (0, 0, write_at, 0))


def _cache_read(cache, dtype):
    if isinstance(cache, tuple):
        data, sc = cache
        # dequant in f32: casting the scales to bf16 first would stack a
        # second quantization on top of the int8 rounding
        return (data.astype(jnp.float32) * sc[..., None]).astype(dtype)
    return cache


def _attend(cfg, q, k_all, v_all, key_mask):
    """q: (B, nh, T, hd); k/v_all: (B, nkv, S, hd); key_mask (T, S) or
    broadcastable bool."""
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    if nh != nkv:
        k_all = jnp.repeat(k_all, nh // nkv, axis=1)
        v_all = jnp.repeat(v_all, nh // nkv, axis=1)
    hd = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_all) / math.sqrt(hd)
    s = jnp.where(key_mask, s, jnp.finfo(s.dtype).min)
    probs = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v_all)


def _layer_math(cfg, lp, x, pos_vec, attend, lora=None):
    """The shared decoder-layer body (rms -> qkv+rope -> attend ->
    o_proj residual -> mlp residual); ``attend(q, k, v) -> (ctx, extra)``
    owns the cache strategy so the two cache variants below can't
    diverge on the math. ``lora`` is the optional per-layer
    multi-adapter delta (see ``_proj_qkv``)."""
    B, T, H = x.shape
    h = _rms(x, lp["input_layernorm.weight"], cfg.rms_norm_eps)
    q, k, v = _proj_qkv(cfg, lp, h, pos_vec, lora=lora)
    ctx, extra = attend(q, k, v)
    attn = _mm(jnp.swapaxes(ctx, 1, 2).reshape(B, T, H),
               lp["self_attn.o_proj.weight"])
    x = x + attn
    h2 = _rms(x, lp["post_attention_layernorm.weight"], cfg.rms_norm_eps)
    mlp = _mm(jax.nn.silu(_mm(h2, lp["mlp.gate_proj.weight"]))
              * _mm(h2, lp["mlp.up_proj.weight"]),
              lp["mlp.down_proj.weight"])
    return x + mlp, extra


def _layer_step(cfg, lp, x, k_cache, v_cache, pos_vec, key_mask, write_at):
    """One decoder layer over T positions with cache read+write.

    x: (B, T, H); caches (B, nkv, max_len, hd); pos_vec (T,) absolute
    positions; write_at: scalar start index where this block's K/V land.
    Returns (x_out, new_k_cache, new_v_cache).
    """
    def attend(q, k, v):
        kc = _cache_write(k_cache, k, write_at)
        vc = _cache_write(v_cache, v, write_at)
        k_all = _cache_read(kc, q.dtype)
        v_all = _cache_read(vc, q.dtype)
        if isinstance(kc, tuple):
            # overlay the EXACT current block over the dequantized cache:
            # this step's own keys aren't round-tripped (quantization
            # error applies only to the stored past, matching the rolling
            # prefill path)
            k_all = jax.lax.dynamic_update_slice(
                k_all, k.astype(k_all.dtype), (0, 0, write_at, 0))
            v_all = jax.lax.dynamic_update_slice(
                v_all, v.astype(v_all.dtype), (0, 0, write_at, 0))
        return _attend(cfg, q, k_all, v_all, key_mask), (kc, vc)

    x, (kc, vc) = _layer_math(cfg, lp, x, pos_vec, attend)
    return x, kc, vc


def _logits(cfg, outer, x_last):
    head = outer.get("lm_head.weight")
    if head is None:
        # tied embeddings stay unquantized (the same array feeds the
        # token lookup, where int8 would distort every embedding)
        return x_last @ outer["model.embed_tokens.weight"].T
    return _mm(x_last, head)


def _layer_step_rolling_prefill(cfg, lp, x, pos_vec, key_mask, W,
                                quantized=False):
    """Prefill layer for a ROLLING (sliding-window) cache: attention runs
    banded over this block's own K/V, then only the last W positions land
    in the cache, each at slot p % W (~ Mistral's rolling buffer — cache
    memory is O(window), not O(sequence))."""
    B, S0, _ = x.shape

    def attend(q, k, v):
        ctx = _attend(cfg, q, k, v, key_mask)
        if S0 >= W:
            # slot for absolute position p is p % W; the last W positions
            # in order are a cyclic shift of the slot sequence
            kc = jnp.roll(k[:, :, S0 - W:, :], S0 % W, axis=2)
            vc = jnp.roll(v[:, :, S0 - W:, :], S0 % W, axis=2)
        else:
            nkv, hd = k.shape[1], k.shape[-1]
            kc = jnp.zeros((B, nkv, W, hd), k.dtype).at[:, :, :S0].set(k)
            vc = jnp.zeros((B, nkv, W, hd), v.dtype).at[:, :, :S0].set(v)
        if quantized:
            kc, vc = _q8(kc), _q8(vc)
        return ctx, (kc, vc)

    x, (kc, vc) = _layer_math(cfg, lp, x, pos_vec, attend)
    return x, kc, vc


def llama_decode_factory(model: LlamaForCausalLM, max_len: int = 256,
                         kv_cache_dtype: str | None = None,
                         weight_dtype: str | None = None,
                         scan_layers: bool = True):
    """Returns ``generate(tokens, max_new_tokens, key=None,
    temperature=0.0, top_k=0) -> (B, S0+max_new) token array`` running a
    fully jitted prefill + per-token decode with functional KV caches.

    ``scan_layers`` (default True) runs the stacked (L, ...) layer
    weights through ONE ``lax.scan`` layer body; False unrolls the L
    layers into the program (parity/debug fallback — ~L x the HLO,
    identical tokens).

    With ``config.sliding_window`` < max_len the cache is a ROLLING
    buffer of window slots (write at pos % window): memory stays
    O(window) and generation length is unbounded by the cache.

    ``kv_cache_dtype="int8"`` stores the cache quantized (per-slot absmax
    over head_dim): cache memory halves vs bf16 and the dequant fuses
    into the attention matmuls — the serving-memory lever the
    reference's fused_multi_transformer lacks.

    ``weight_dtype="int8"`` additionally quantizes the projection and
    lm-head weights per output channel (~ QuantizationFreezePass +
    fused int8 inference, paddle/fluid/operators/fused/): activations
    quantize dynamically per tensor and the matmuls run int8 x int8 ->
    int32 on the MXU — half the weight HBM traffic, which is what bounds
    small-batch decode. Tied embeddings stay full precision.
    """
    cfg = model.config
    outer, layers = split_params(model)
    if weight_dtype not in (None, "int8"):
        raise ValueError(f"weight_dtype {weight_dtype!r}: use None or "
                         "'int8'")
    if weight_dtype == "int8":
        layers = _quantize_weights(layers, _PROJ_KEYS)
        outer = _quantize_weights(outer, ("lm_head.weight",))
    L = cfg.num_hidden_layers
    nkv = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    window = getattr(cfg, "sliding_window", None)
    rolling = window is not None and window < max_len
    C = window if rolling else max_len  # cache slots
    quantized = kv_cache_dtype == "int8"
    if kv_cache_dtype not in (None, "int8"):
        raise ValueError(f"kv_cache_dtype {kv_cache_dtype!r}: use None "
                         "(model dtype) or 'int8'")

    def init_caches(B, dtype):
        if quantized:
            return (jnp.zeros((L, B, nkv, C, hd), jnp.int8),
                    jnp.ones((L, B, nkv, C), jnp.float32))
        return jnp.zeros((L, B, nkv, C, hd), dtype)

    def _band(S0):
        causal = jnp.tril(jnp.ones((S0, S0), bool))
        if window is not None:
            i = jnp.arange(S0)[:, None]
            j = jnp.arange(S0)[None, :]
            causal &= (i - j) < window
        return causal

    if rolling:
        # rolling prefill PRODUCES the caches (scan ys) — no zero-filled
        # buffers allocated and threaded through as dead inputs
        @jax.jit
        def prefill(outer, layers, tokens):
            B, S0 = tokens.shape
            x = jnp.take(outer["model.embed_tokens.weight"], tokens,
                         axis=0)
            pos_vec = jnp.arange(S0)
            band_mask = _band(S0)  # vs this block's own S0 keys

            def body(x, lp):
                x, kc, vc = _layer_step_rolling_prefill(
                    cfg, lp, x, pos_vec, band_mask, C, quantized)
                return x, (kc, vc)

            x, (k_caches, v_caches) = _stack_apply(body, x, layers,
                                                   scan_layers)
            x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
            return _logits(cfg, outer, x[:, -1]), k_caches, v_caches
    else:
        @partial(jax.jit, donate_argnums=(3, 4))
        def prefill(outer, layers, tokens, k_caches, v_caches):
            B, S0 = tokens.shape
            x = jnp.take(outer["model.embed_tokens.weight"], tokens,
                         axis=0)
            pos_vec = jnp.arange(S0)
            key_mask = jnp.concatenate(
                [_band(S0), jnp.zeros((S0, max_len - S0), bool)], axis=1)

            def body(x, per_layer):
                lp, kc, vc = per_layer
                x, kc, vc = _layer_step(cfg, lp, x, kc, vc, pos_vec,
                                        key_mask, 0)
                return x, (kc, vc)

            x, (k_caches, v_caches) = _stack_apply(
                body, x, (layers, k_caches, v_caches), scan_layers)
            x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
            return _logits(cfg, outer, x[:, -1]), k_caches, v_caches

    # donate the caches: dynamic_update_slice aliases in place instead of
    # copying the whole (L,B,nkv,C,hd) buffers every token
    @partial(jax.jit, donate_argnums=(4, 5))
    def decode_step(outer, layers, token, pos, k_caches, v_caches):
        """token: (B,) int; pos: scalar absolute position of `token`."""
        x = jnp.take(outer["model.embed_tokens.weight"], token[:, None],
                     axis=0)
        pos_vec = jnp.full((1,), pos)
        if rolling:
            # every cache slot already written is within the window by
            # construction (the buffer only ever holds the last C keys)
            key_mask = ((jnp.arange(C) <= pos) | (pos >= C))[None, :]
            write_at = jax.lax.rem(pos, C)
        else:
            key_mask = (jnp.arange(C) <= pos)[None, :]
            write_at = pos

        def body(x, per_layer):
            lp, kc, vc = per_layer
            x, kc, vc = _layer_step(cfg, lp, x, kc, vc, pos_vec,
                                    key_mask, write_at)
            return x, (kc, vc)

        x, (k_caches, v_caches) = _stack_apply(
            body, x, (layers, k_caches, v_caches), scan_layers)
        x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
        return _logits(cfg, outer, x[:, 0]), k_caches, v_caches

    def sample(logits, key, temperature, top_k, top_p):
        if temperature <= 0.0:
            return jnp.argmax(logits, -1)
        logits = logits / temperature
        top_k = min(top_k, logits.shape[-1])  # huge k = no truncation
        if top_k > 0:
            kth = jnp.sort(logits, -1)[:, -top_k][:, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p < 1.0:
            # nucleus: keep the smallest prefix of the sorted
            # distribution whose mass reaches top_p; top_p <= 0 clamps to
            # the minimal nucleus (top-1, i.e. greedy) so the parameter
            # stays monotonic instead of 0.0 meaning "unrestricted"
            p = max(float(top_p), 1e-9)
            srt = jnp.sort(logits, -1)[:, ::-1]
            probs = jax.nn.softmax(srt, -1)
            cum = jnp.cumsum(probs, -1)
            keep = (cum - probs) < p  # mass BEFORE this token
            cutoff = jnp.where(keep, srt, jnp.inf).min(-1, keepdims=True)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(key, logits, -1)

    def generate(tokens, max_new_tokens: int, key=None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id: int | None = None,
                 pad_token_id: int = 0):
        """``eos_token_id`` enables batched early stop: rows that have
        emitted EOS produce ``pad_token_id`` from then on, and the decode
        loop exits once every row has finished."""
        tokens = jnp.asarray(tokens)
        B, S0 = tokens.shape
        if not rolling and S0 + max_new_tokens > max_len:
            # hard error (not assert): past max_len the cache writes
            # would silently clamp and corrupt generations (the rolling
            # window cache has no such limit — it wraps by design)
            raise ValueError(
                f"prompt {S0} + max_new_tokens {max_new_tokens} exceeds "
                f"the factory's max_len {max_len}")
        if key is None:
            key = jax.random.PRNGKey(0)
        dtype = outer["model.embed_tokens.weight"].dtype
        if rolling:
            logits, kc, vc = prefill(outer, layers, tokens)
        else:
            kc = init_caches(B, dtype)
            vc = init_caches(B, dtype)
            logits, kc, vc = prefill(outer, layers, tokens, kc, vc)
        out = [tokens]
        pos = S0
        finished = jnp.zeros((B,), bool)
        for i in range(max_new_tokens):
            key, sub = jax.random.split(key)
            nxt = sample(logits, sub, temperature, top_k, top_p)
            if eos_token_id is not None:
                nxt = jnp.where(finished, pad_token_id, nxt)
                finished = finished | (nxt == eos_token_id)
            out.append(nxt[:, None])
            # all-finished poll every 8 steps: the bool() readback is a
            # host sync that would otherwise serialize the async decode
            # dispatch pipeline on EVERY token;
            # at most 7 wasted padded steps in exchange
            if eos_token_id is not None \
                    and (i % 8 == 7 or i + 1 == max_new_tokens) \
                    and bool(finished.all()):
                break  # every row has emitted EOS
            if i + 1 < max_new_tokens:
                logits, kc, vc = decode_step(outer, layers, nxt,
                                             jnp.asarray(pos), kc, vc)
                pos += 1
        return jnp.concatenate(out, axis=1)

    @partial(jax.jit, static_argnums=(3,))
    def _compiled_greedy(outer, layers, tokens, max_new):
        """prefill + max_new greedy decode steps in ONE program
        (lax.scan): generate()'s python loop pays a per-token host
        dispatch; the in-jit loop has one dispatch per CALL. (How much
        the per-token dispatch costs on a local chip is not measured —
        PERF.md, open questions.)"""
        B, S0 = tokens.shape
        dtype = outer["model.embed_tokens.weight"].dtype
        if rolling:
            logits, kc, vc = prefill(outer, layers, tokens)
        else:
            kc = init_caches(B, dtype)
            vc = init_caches(B, dtype)
            logits, kc, vc = prefill(outer, layers, tokens, kc, vc)

        def step(carry, i):
            logits, kc, vc = carry
            nxt = jnp.argmax(logits, -1)
            logits, kc, vc = decode_step(outer, layers, nxt, S0 + i,
                                         kc, vc)
            return (logits, kc, vc), nxt

        (logits, _, _), toks = jax.lax.scan(
            step, (logits, kc, vc), jnp.arange(max_new - 1))
        last = jnp.argmax(logits, -1)
        gen = jnp.concatenate([jnp.swapaxes(toks, 0, 1),
                               last[:, None]], 1) if max_new > 1 \
            else last[:, None]
        return jnp.concatenate([tokens, gen], axis=1)

    def generate_compiled(tokens, max_new_tokens: int):
        """Greedy-only one-program variant of generate() (same output
        as temperature=0)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        B, S0 = tokens.shape
        if max_new_tokens < 1:
            # match generate(): zero budget returns the prompt alone
            return np.asarray(tokens)
        if not rolling and S0 + max_new_tokens > max_len:
            raise ValueError(
                f"prompt {S0} + max_new_tokens {max_new_tokens} exceeds "
                f"the factory's max_len {max_len}")
        return np.asarray(_compiled_greedy(outer, layers, tokens,
                                           max_new_tokens))

    generate.compiled = generate_compiled
    # program introspection hooks: lower/compile the per-token step or
    # the whole greedy program without running it (program-size parity
    # tests + compile-time rows in tools/spec_decode_bench.py)
    generate._parts = {"outer": outer, "layers": layers,
                       "prefill": prefill, "decode_step": decode_step,
                       "init_caches": init_caches,
                       "compiled_greedy": _compiled_greedy,
                       "scan_layers": scan_layers, "rolling": rolling}
    return generate


def llama_speculative_decode_factory(target: LlamaForCausalLM,
                                     draft: LlamaForCausalLM,
                                     max_len: int = 256,
                                     n_draft: int = 4,
                                     scan_layers: bool = True):
    """Greedy speculative decoding: a small draft model proposes
    ``n_draft`` tokens (ONE jitted program — the autoregressive draft
    walk runs as an in-jit scan, so the whole draft phase costs a single
    host readback); the target model VERIFIES them in ONE batched block
    step (k+1 positions through the cache — matmul-heavy, instead of k+1
    sequential target steps). Accepted-prefix + the target's correction
    token advance the sequence; rejected cache slots are overwritten by
    the next block (the key mask never reaches stale slots beyond the
    write position), so rollback is free. On a fully-accepted round the
    draft hasn't consumed its own last proposal — it is fed as part of
    the next round's block, so the draft cache never holds a hole.

    Greedy acceptance makes the output EXACTLY the target model's greedy
    generation — speculation changes latency, never content. The serving
    analog the reference's fused_multi_transformer stack lacks.

    Both models must share a vocabulary. Batch size 1 per call (the
    accepted-prefix length is data-dependent; batching rows with
    different acceptance lengths needs per-row position bookkeeping —
    future work).

    ``scan_layers`` (default True) runs BOTH models' stacked (L, ...)
    layer weights through one ``lax.scan`` layer body per block — the
    two-model program is the largest HLO in the repo and scan-compression
    is what lets it compile at 0.44B; False unrolls the layers
    (parity/debug fallback, ~L x the program)."""
    if target.config.vocab_size != draft.config.vocab_size:
        raise ValueError("target and draft must share a vocabulary")
    if n_draft < 1:
        raise ValueError("n_draft must be >= 1 (0 would still emit one "
                         "unverified draft per round and desync the draft "
                         "cache)")
    if getattr(target.config, "sliding_window", None) or \
            getattr(draft.config, "sliding_window", None):
        raise ValueError("speculative decoding with sliding_window is "
                         "not supported (rolling slots break the "
                         "overwrite-rollback invariant)")

    def build(model):
        cfg = model.config
        outer, layers = split_params(model)
        L = cfg.num_hidden_layers
        nkv = cfg.num_key_value_heads
        hd = cfg.hidden_size // cfg.num_attention_heads
        dtype = outer["model.embed_tokens.weight"].dtype

        def init(B):
            return (jnp.zeros((L, B, nkv, max_len, hd), dtype),
                    jnp.zeros((L, B, nkv, max_len, hd), dtype))

        def block_body(outer, layers, tokens, k_caches, v_caches, pos0):
            """tokens (B, T) at absolute positions pos0..pos0+T-1; writes
            their K/V at the same slots; returns logits for EVERY
            position (B, T, V)."""
            T = tokens.shape[1]
            x = jnp.take(outer["model.embed_tokens.weight"], tokens,
                         axis=0)
            pos_vec = pos0 + jnp.arange(T)
            key_mask = jnp.arange(max_len)[None, :] <= pos_vec[:, None]

            def body(x, per_layer):
                lp, kc, vc = per_layer
                x, kc, vc = _layer_step(cfg, lp, x, kc, vc, pos_vec,
                                        key_mask, pos0)
                return x, (kc, vc)

            x, (k_caches, v_caches) = _stack_apply(
                body, x, (layers, k_caches, v_caches), scan_layers)
            x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
            return _logits(cfg, outer, x), k_caches, v_caches

        block = partial(jax.jit, donate_argnums=(3, 4))(block_body)
        return outer, layers, init, block_body, block

    outerT, layersT, initT, blockT_body, blockT = build(target)
    outerD, layersD, initD, blockD_body, _ = build(draft)

    @partial(jax.jit, donate_argnums=(3, 4), static_argnums=(5,))
    def draft_round(outer, layers, feed, k_caches, v_caches, k, pos0):
        """Consume the pending ``feed`` block (ends at position pos0 +
        T0 - 1), then greedily draft ``k`` tokens with an in-jit scan —
        the whole draft phase is one program, one readback."""
        T0 = feed.shape[1]
        lg, k_caches, v_caches = blockD_body(outer, layers, feed,
                                             k_caches, v_caches, pos0)
        cur = jnp.argmax(lg[:, -1], -1)  # (B,) — the first draft token

        def step(carry, i):
            cur, kc, vc = carry
            lg, kc, vc = blockD_body(outer, layers, cur[:, None], kc, vc,
                                     pos0 + T0 + i)
            return (jnp.argmax(lg[:, -1], -1), kc, vc), cur

        (last_d, k_caches, v_caches), ds = jax.lax.scan(
            step, (cur, k_caches, v_caches), jnp.arange(k - 1))
        # ds: (k-1, B) of d_0..d_{k-2}; last carry is d_{k-1}
        drafts = jnp.concatenate(
            [jnp.swapaxes(ds, 0, 1), last_d[:, None]], 1) \
            if k > 1 else last_d[:, None]
        return drafts, k_caches, v_caches

    # Both models' weights travel as ARGUMENTS through every jitted
    # spec program, never as closure captures: a closed-over array is
    # embedded in the lowered module as a literal constant, so the
    # two-model program used to carry ~2 model-sizes of inline weight
    # bytes — THE reason the remote compile service hung then broke its
    # pipe at 0.44B while the plain decode (weights as args, ~kB of
    # HLO) compiled in 1.6 s. With args + the scanned layer body the
    # spec module text is size-O(1) in both depth and width.
    _params = (outerT, layersT, outerD, layersD)

    @jax.jit
    def _spec_prefill(params, tokens):
        """Prefill both models; returns the spec loop state."""
        pouterT, playersT, pouterD, playersD = params
        B, S0 = tokens.shape
        kT, vT = initT(B)
        kD, vD = initD(B)
        lgT, kT, vT = blockT_body(pouterT, playersT, tokens, kT, vT, 0)
        last = jnp.argmax(lgT[0, -1], -1).astype(jnp.int32)
        seq = jnp.zeros((max_len,), jnp.int32)
        seq = jax.lax.dynamic_update_slice(seq, tokens[0].astype(
            jnp.int32), (0,))
        seq = seq.at[S0].set(last)
        _, kD, vD = blockD_body(pouterD, playersD, tokens, kD, vD, 0)
        return (jnp.asarray(1, jnp.int32), jnp.asarray(0, jnp.int32),
                jnp.asarray(S0, jnp.int32), last, seq, kT, vT, kD, vD)

    def _spec_round(params, state):
        """One draft/verify/accept round. Greedy acceptance arithmetic
        is branch-free: n = length of the matching draft prefix; the
        candidate vector writes accepted drafts then the target's
        correction; junk beyond n is overwritten by later rounds (the
        same overwrite-rollback invariant the caches use)."""
        pouterT, playersT, pouterD, playersD = params
        produced, rounds, pos, last, seq, kT, vT, kD, vD = state
        k = n_draft
        feed = jax.lax.dynamic_slice(seq, (pos - 1,), (2,))[None]
        lg, kD2, vD2 = blockD_body(pouterD, playersD, feed, kD, vD,
                                   pos - 1)
        cur = jnp.argmax(lg[:, -1], -1)

        # inner draft walk as a scan: one traced draft block instead of
        # k-1 unrolled copies — program size (and with it compile
        # time) stays O(1) in k
        def dstep(carry, i):
            cur, kc, vc = carry
            lg, kc, vc = blockD_body(pouterD, playersD, cur[:, None],
                                     kc, vc, pos + 1 + i)
            return (jnp.argmax(lg[:, -1], -1), kc, vc), cur

        (last_d, kD2, vD2), ds = jax.lax.scan(
            dstep, (cur, kD2, vD2), jnp.arange(k - 1))
        drafts = (jnp.concatenate([jnp.swapaxes(ds, 0, 1),
                                   last_d[:, None]], 1)
                  if k > 1 else last_d[:, None])  # (1, k)
        blk = jnp.concatenate([last[None], drafts[0]])[None]
        lgT, kT2, vT2 = blockT_body(pouterT, playersT,
                                    blk.astype(jnp.int32), kT, vT, pos)
        t = jnp.argmax(lgT[0], -1).astype(jnp.int32)  # (k+1,)
        matches = (drafts[0].astype(jnp.int32) == t[:k]).astype(
            jnp.int32)
        n = jnp.sum(jnp.cumprod(matches))
        idx = jnp.arange(k + 1)
        dpad = jnp.concatenate([drafts[0].astype(jnp.int32),
                                jnp.zeros((1,), jnp.int32)])
        cand = jnp.where(idx < n, dpad, t)
        seq = jax.lax.dynamic_update_slice(seq, cand, (pos + 1,))
        last = jax.lax.dynamic_index_in_dim(t, n, keepdims=False)
        return (produced + n + 1, rounds + 1, pos + n + 1, last,
                seq, kT2, vT2, kD2, vD2)

    @partial(jax.jit, static_argnums=(2,), donate_argnums=(1,))
    def _spec_chunk(params, state, R, max_new):
        """R gated rounds inside ONE lax.scan program (a while_loop
        formulation is semantically identical; the scan form was chosen
        for a compiler this repo no longer runs on — a work-around to
        re-judge, PERF.md open questions). Rounds past max_new
        become no-ops: the fresh state is computed then discarded by a
        scalar select, so output and stats are EXACTLY the while_loop's.
        The host re-dispatches chunks until produced >= max_new — ONE
        dispatch when acceptance is high (R is sized for the accepted
        case), <= k+1 when the draft never matches."""
        def body(state, _):
            new_state = _spec_round(params, state)
            valid = state[0] < max_new
            state = jax.tree_util.tree_map(
                lambda a, b: jnp.where(valid, b, a), state, new_state)
            return state, None

        state, _ = jax.lax.scan(body, state, None, length=R)
        return state

    def _compiled_spec(tokens, max_new):
        state = _spec_prefill(_params, tokens)
        # chunk size caps the compiled program; at high acceptance 128
        # tokens costs ~7 dispatches at R=4 (vs 2 per ROUND for the
        # python loop)
        # R static (scan length, few values); max_new TRACED (only the
        # gating comparison reads it) so one compile serves every
        # generation length; state donated so the KV caches alias
        # across chunk re-dispatches instead of copying
        R = min(4, max(1, -(-max_new // (n_draft + 1))))
        mn = jnp.asarray(max_new, jnp.int32)
        while int(state[0]) < max_new:
            state = _spec_chunk(_params, state, R, mn)
        return state[4], state[0], state[1]

    def generate_compiled(tokens, max_new_tokens: int):
        """One-program speculative decode; same greedy-exact output as
        ``generate`` (stats in .last_stats after each call)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        B, S0 = tokens.shape
        if B != 1:
            raise ValueError("speculative generate supports batch 1")
        if S0 + max_new_tokens + 2 * (n_draft + 1) > max_len:
            raise ValueError(
                f"prompt {S0} + max_new {max_new_tokens} + 2x draft "
                f"window {n_draft + 1} exceeds max_len {max_len}")
        seq, produced, rounds = _compiled_spec(tokens, max_new_tokens)
        seq = np.asarray(seq)
        produced, rounds = int(produced), int(rounds)
        # produced = 1 (prefill token) + sum(n_i + 1): subtract the
        # prefill token AND the per-round correction token so the rate
        # counts only accepted DRAFT proposals
        generate_compiled.last_stats = {
            "rounds": rounds,
            "tokens": min(produced, max_new_tokens),
            "target_steps": 1 + rounds,
            "accept_rate": round(
                (produced - 1 - rounds) / max(1, rounds * n_draft), 4),
        }
        return seq[None, :S0 + max_new_tokens]

    generate_compiled.last_stats = {}
    # PR-4 convention: a python shim driving jitted programs
    # advertises them via _jit_inner, so program-cache-growth
    # detection (engine jit.compile instants, cache_stats consumers)
    # sees spec compiles instead of missing them behind the shim
    generate_compiled._jit_inner = (_spec_prefill, _spec_chunk)

    def generate(tokens, max_new_tokens: int):
        tokens = jnp.asarray(tokens)
        B, S0 = tokens.shape
        if B != 1:
            raise ValueError("speculative generate supports batch 1")
        if S0 + max_new_tokens + n_draft + 1 > max_len:
            raise ValueError(
                f"prompt {S0} + max_new {max_new_tokens} + draft window "
                f"{n_draft + 1} exceeds max_len {max_len}")
        kT, vT = initT(B)
        kD, vD = initD(B)
        logitsT, kT, vT = blockT(outerT, layersT, tokens, kT, vT, 0)
        seq = [int(t) for t in np.asarray(tokens)[0]]
        last = int(np.asarray(jnp.argmax(logitsT[:, -1], -1))[0])
        seq.append(last)
        produced = 1
        pos = S0          # `last` occupies sequence position pos
        pending = seq[S0:]  # tokens the DRAFT has not consumed yet
        # (the draft skipped prefill of nothing: feed it the prompt too)
        _, kD, vD = draft_round(
            outerD, layersD, tokens, kD, vD, 1,
            jnp.asarray(0))  # consumes prompt; 1 throwaway draft token
        rounds = 0
        while produced < max_new_tokens:
            k = min(n_draft, max_new_tokens - produced)
            feed = jnp.asarray([pending], jnp.int32)
            T0 = len(pending)
            drafts_arr, kD, vD = draft_round(
                outerD, layersD, feed, kD, vD, k,
                jnp.asarray(pos - T0 + 1))
            drafts = [int(x) for x in np.asarray(drafts_arr)[0]]
            # ONE target block verifies [last, d0..d_{k-1}]
            blk = jnp.asarray([[last] + drafts], jnp.int32)
            lgT, kT, vT = blockT(outerT, layersT, blk, kT, vT,
                                 jnp.asarray(pos))
            t = [int(x) for x in np.asarray(jnp.argmax(lgT[0], -1))]
            n = 0
            while n < k and drafts[n] == t[n]:
                n += 1
            seq.extend(drafts[:n] + [t[n]])  # accepted + correction/bonus
            produced += n + 1
            pos += n + 1
            last = t[n]
            # the draft consumed [pending, d0..d_{k-2}]; feed it whatever
            # of the accepted sequence it hasn't seen, plus the new last
            pending = ([drafts[k - 1]] if n == k else []) + [last]
            rounds += 1
        out = np.asarray(seq[:S0 + max_new_tokens], np.int32)[None, :]
        generate.last_stats = {
            "rounds": rounds,
            "tokens": min(produced, max_new_tokens),
            "target_steps": 1 + rounds,
        }
        return out

    generate.last_stats = {}
    # one-program-per-chunk variant (host-redispatched lax.scan
    # chunks): identical greedy output, ~max_new/(R*(k+1)) dispatches
    # instead of two per round
    generate.compiled = generate_compiled
    # lower/compile the chunk program without generating (compile-time
    # + program-size measurement at sizes where RUNNING is impractical)
    generate._parts = {"spec_prefill": _spec_prefill,
                       "spec_chunk": _spec_chunk,
                       "params": _params,
                       "scan_layers": scan_layers}
    return generate


# --- paged decode (continuous batching) ------------------------------------

def emit_fn(emit: str):
    """What a paged program hands back for a row's last position:
    ``"token"`` the greedy token, ``"logits"`` the float32 logits (the
    serving loop then owns sampling)."""
    if emit not in ("token", "logits"):
        raise ValueError(f"emit {emit!r}: use 'token' or 'logits'")

    def _emit(logits):
        return jnp.argmax(logits, -1) if emit == "token" \
            else logits.astype(jnp.float32)
    return _emit


def chunked_prefill_shim(prefill_chunk, finish_prefill, C: int,
                         hidden: int, dtype):
    """The chunked-prefill walk every paged factory shares: plain
    python over ONE compiled chunk program
    (``prefill_chunk(outer, layers, chunk, start, page_tables, lengths,
    pools, x_last, lora) -> (x_last, pools)``) and the finishing
    program (``finish_prefill(outer, x_last, grammar)``). The walk is
    the entry outside the serving lane; the lane's own is
    ``prefill_chunked.lane_call``: a span of chunks in one program."""

    def prefill_chunked(outer, layers, tokens, page_tables, lengths,
                        pools, resume_from: int = 0, lora=None,
                        grammar=None):
        """``resume_from`` (a chunk multiple): skip chunks whose pages
        already hold real K/V — the prefix-cache path
        (PagedKVCache.acquire_prefix returns the cached token count;
        pass the MINIMUM across the batch, rounded DOWN to a chunk
        multiple — a larger value would skip chunks that are
        uninitialized for the less-cached sequences). The final chunk
        always runs so the last-position logits exist; its page writes
        rewrite identical content when the tail was cached.
        ``lora``: optional ``(adapter_bank, adapter_ids)`` deltas,
        threaded into every chunk call."""
        B, T = tokens.shape
        if T % C:
            raise ValueError(
                f"chunked prefill: padded prompt length {T} must be a "
                f"multiple of the chunk size {C}")
        if resume_from % C:
            raise ValueError(f"resume_from {resume_from} must be a "
                             f"chunk multiple ({C})")
        resume = min(resume_from, T - C)
        # the shim's three host steps, named in the profiler's trace
        # (free while no session records): each is a dispatch of its
        # own that the device may sit out
        with TraceAnnotation("factory:prefill.slice"):
            x_last = jnp.zeros((B, hidden), dtype)
        for s in range(resume, T, C):  # static count; ONE compiled fn
            with TraceAnnotation("factory:prefill.slice"):
                chunk = tokens[:, s:s + C]
            with TraceAnnotation("factory:prefill.chunk"):
                x_last, pools = prefill_chunk(
                    outer, layers, chunk, s, page_tables, lengths,
                    pools, x_last, lora)
        with TraceAnnotation("factory:prefill.finish"):
            return finish_prefill(outer, x_last, grammar), pools

    x0 = {}     # the zeros a call's carry starts from, made once a batch

    def lane_call(outer, layers, span, start: int, page_tables, lengths,
                  pools, final: bool, lora=None, grammar=None):
        """The serving lane's entry: ONE chunk program over ``span``
        ((B, m x C) token ids, cut by the caller on the host) at the
        absolute positions ``start`` ... ``start + m x C - 1``, ``m``
        whole chunks of one prompt (the program is generic in its
        width; a width compiles once). ``lengths``: the real prompt
        length on the prompt's ``final`` call, the span's end before
        it. Returns ``(first, pools)``; ``first`` is None unless
        ``final``: the finishing program (final norm and the
        whole-vocabulary head) runs for the one call whose last
        position it reads. One dispatch a call, two on the final."""
        B = span.shape[0]
        if B not in x0:
            x0[B] = jnp.zeros((B, hidden), dtype)
        with TraceAnnotation("factory:prefill.chunk"):
            x_last, pools = prefill_chunk(
                outer, layers, span, start, page_tables, lengths, pools,
                x0[B], lora)
        if not final:
            return None, pools
        with TraceAnnotation("factory:prefill.finish"):
            return finish_prefill(outer, x_last, grammar), pools

    # the shim itself is plain python; expose the jitted programs it
    # drives so the serving engine's recompile detector (obs layer:
    # program-cache growth across a call) can watch prefill too
    prefill_chunked._jit_inner = (prefill_chunk, finish_prefill)
    prefill_chunked.lane_call = lane_call
    return prefill_chunked


def decode_scan(step, tok, lengths, pools, n: int):
    """``n`` decode steps as ONE ``lax.scan`` (traced inside the
    caller's jit). ``step(tok, lens, pools) -> (emit, pools, *extra)``;
    the feedback token is the emission, or its greedy argmax where the
    step emits logits. Returns ``((emits, *extras) each stacked (n,
    ...), next_tok (B,), pools')``."""
    def body(carry, _):
        tok, lens, pools = carry
        nxt, pools, *extra = step(tok, lens, pools)
        step_tok = nxt if nxt.ndim == 1 else jnp.argmax(
            nxt, -1).astype(jnp.int32)
        return (step_tok.astype(jnp.int32), lens + 1, pools), \
            (nxt, *extra)
    # int32 up front: with emit="logits" callers derive the seed
    # token themselves (e.g. np.argmax -> int64) and a dtype drift
    # would break the scan carry structure
    (tok, _, pools), ys = jax.lax.scan(
        body, (jnp.asarray(tok, jnp.int32), lengths, pools), None,
        length=n)
    return ys, tok, pools


def llama_paged_decode_factory(model: LlamaForCausalLM,
                               page_size: int = 64,
                               n_pool_pages: int = 256,
                               chunked_prefill: int | None = None,
                               kv_cache_dtype: str | None = None,
                               emit: str = "token",
                               prefill_attention: str = "gather",
                               scan_layers: bool = True,
                               tp: "TPConfig | int | None" = None,
                               lora: "LoRAConfig | tuple | None"
                               = None,
                               kv_quant: str | None = None):
    """Compiled decode over a PAGED KV pool — the continuous-batching
    serving path (ops/pallas/paged_attention.py; the reference's dense
    fused_multi_transformer cache cannot share memory across requests).

    The pools are (L, Hkv, P, page_size, hd), one each for K and V;
    sequences hold page tables (B, pages_per_seq — the caller's table
    width) and real lengths (B,). Every program takes the pools DONATED
    and addresses them in place by (layer, page): they ride the layer
    loop's carry whole (``_stack_carry``), a write is one scatter of the
    new rows at (layer, head, page, offset), the kernels read (layer,
    head, page) blocks out of the whole pool and the dense chunk
    attention gathers the batch's pages of a layer in one gather — no
    program slices a layer out of a pool, stacks one back or copies
    one (tests/test_chip_aot.py holds the chip compiler to that).
    Ragged batches are
    first-class: rotary positions, cache writes and attention masks are
    all per-sequence, so requests at different depths decode together in
    ONE jitted step — admit/evict between steps by editing the tables
    (PagedKVCache does the host bookkeeping).

    Returns (outer, layers, pools, prefill, decode_step):
      pools: (k_pools, v_pools) each (L, Hkv, P, page_size, hd)
      prefill(outer, layers, tokens (B,T), page_tables, lengths, pools)
          -> (next_token (B,), pools')   [prompt K/V written to pages]
      decode_step(outer, layers, tok (B,), page_tables, lengths, pools)
          -> (next_token (B,), pools')   [lengths' = lengths + 1 is the
                                          caller's bookkeeping]

    ``chunked_prefill=C`` (a page multiple): the returned prefill walks
    the prompt in C-token chunks, each attending causally to the pool
    pages written so far — score memory per layer is O(C x table_width
    x page_size) instead of the one-shot O(T^2): the long-prompt
    admission path of serving stacks (vLLM's chunked prefill).

    ``kv_cache_dtype="int8"``: pool pages store the per-slot absmax
    int8 codec (the dense cache's _q8) — serving cache memory halves
    and the Pallas kernel dequantizes in VMEM per page.

    ``kv_quant``: the serving-tier spelling of the pool codec.
    ``"int8"`` is always-int8 — identical storage to
    ``kv_cache_dtype="int8"``. ``"pressure"`` keeps hot pages full
    precision and adds an int8+scale shadow arena plus a (P,) page
    tier mask (all jit inputs): ``compact_kv_pages`` quantizes parked
    pages under byte pressure, reads merge both tiers through ONE
    fixed-shape where(), and the write paths clear a written page's
    tier bit in-program — so compaction churn and page recycling
    never recompile and never read stale int8 content.

    ``emit="logits"``: prefill/decode_step return the last-position
    logits (B, V) instead of greedy tokens, so the serving loop owns
    sampling (temperature/top-k/top-p live with the request, not the
    compiled program — the dense factory's in-jit sampler is the other
    option when the whole loop is compiled).

    ``prefill_attention="kernel"`` (chunked prefill only): attend each
    chunk through the paged_prefill_attention Pallas kernel instead of
    the dense page gather — no (B, nkv, S, hd) gathered temporary, and
    int8 pools stay int8 all the way into VMEM. "gather" remains the
    default until the kernel carries a chip measurement.

    ``scan_layers`` (default True): one scanned layer body over the
    stacked (L, ...) weights, the pools in its carry; False unrolls the
    layers into the program (parity fallback, the same addressing).

    ``tp`` (``TPConfig`` / int degree): shard the decode path over a
    1-D named mesh — attention heads and MLP hidden dims partitioned
    column/row-parallel (``tp_layer_specs``), the KV pools split by kv
    head (``tp_pool_spec``), embeddings/norms replicated. Placement
    happens ONCE here (NamedSharding device_put); the jitted
    prefill/decode programs inherit the arg shardings and GSPMD
    inserts the collectives — except around the paged Pallas kernel,
    which GSPMD cannot partition and which therefore runs shard_mapped
    over the tp axis (``_paged_kernel``). The fixed-shape ``decode_n``
    batches still never recompile across churn. ``tp=None`` builds
    exactly the single-device factory.

    ``lora`` (``LoRAConfig`` / ``(n_slots, rank)``): multi-adapter
    serving. Every prefill/decode callable accepts a trailing
    ``lora=(adapter_bank, adapter_ids)`` argument — the bank is the
    device-resident stack of per-slot low-rank q/v deltas
    (``lora_bank_hooks`` builds and uploads it), ``adapter_ids`` the
    per-row slot indices — applied per row via the batched ``_bgmv``
    gather. Both are jit inputs (the PR-1 weights-as-args invariant),
    so one compiled fixed-shape program serves ANY adapter mix and
    adapter churn never recompiles. Slot 0 is the all-zero identity;
    with ``lora=None`` at the call the programs trace exactly the
    base-model math. Under ``tp`` the bank stays replicated (rank is
    tiny; the delta add reshards into the column-parallel q/v
    layout).
    """
    from ...ops.pallas.paged_attention import (paged_attention,
                                               paged_prefill_attention)

    cfg = model.config
    lora_cfg = as_lora_config(lora)
    lora_scale = lora_cfg.scale if lora_cfg is not None else 1.0
    outer, layers = split_params(model)
    outer = {k: jnp.asarray(v) for k, v in outer.items()}
    layers = {k: jnp.asarray(v) for k, v in layers.items()}
    tp = as_tp_config(tp)
    tp_mesh = None
    if tp is not None:
        _validate_tp(cfg, tp)
        outer, layers, tp_mesh = shard_decode_params(outer, layers, tp)
    L = cfg.num_hidden_layers
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = cfg.hidden_size // nh
    dtype = layers["self_attn.q_proj.weight"].dtype

    quantized = kv_cache_dtype == "int8"
    if kv_cache_dtype not in (None, "int8"):
        raise ValueError(f"kv_cache_dtype {kv_cache_dtype!r}: use None "
                         "(model dtype) or 'int8'")
    if kv_quant not in (None, "int8", "pressure"):
        raise ValueError(f"kv_quant {kv_quant!r}: use None, 'int8' "
                         "(every page stored int8+scale) or 'pressure' "
                         "(parked pages compacted to int8 under byte "
                         "pressure)")
    if kv_quant == "int8":
        # always-int8 IS the existing int8 pool codec, named at the
        # serving tier: one storage path, two spellings
        quantized = True
    pressure = kv_quant == "pressure"
    if pressure:
        if kv_cache_dtype is not None:
            raise ValueError("kv_quant='pressure' owns the pool codec "
                             "— drop kv_cache_dtype")
        if tp is not None:
            raise ValueError(
                "kv_quant='pressure' does not compose with tp= yet: "
                "the (P,) page-tier mask is a whole-pool jit input "
                "with no kv-head axis to shard — use kv_quant='int8' "
                "(scales shard with their kv heads per tp_pool_spec)")
    _emit = emit_fn(emit)
    if prefill_attention not in ("gather", "kernel"):
        raise ValueError(f"prefill_attention {prefill_attention!r}: "
                         "use 'gather' or 'kernel'")

    _paged_kernel = partial(paged_kernel_call, mesh=tp_mesh,
                            axis=tp.axis if tp is not None else None)

    def _gmask(logits, grammar):
        """CONSTRAINED DECODING: mask each row's logits with its
        grammar state's packed allow-bitmask BEFORE the emit argmax.
        ``grammar`` is ``(mask_table, state_ids)`` — a
        ``(rows, ceil(V/32))`` uint32 bank and a (B,) int32 flat-id
        vector, BOTH jit inputs like lora's bank/ids, so one compiled
        program serves any schema mix and grammar churn never
        recompiles. Flat id 0 is the reserved all-allow row: free
        rows' where() keeps every logit, bit-for-bit the base math.
        ``grammar=None`` (the Python-level default) traces the
        identical base program — no mask op exists in it at all."""
        if grammar is None:
            return logits
        table, gids = grammar
        v = logits.shape[-1]
        rows = jnp.take(table, gids, axis=0)       # (B, words)
        word = jnp.arange(v) // 32
        bit = (jnp.arange(v) % 32).astype(jnp.uint32)
        allow = (jnp.take(rows, word, axis=1) >> bit[None, :]) \
            & jnp.uint32(1)
        return jnp.where(allow.astype(bool), logits,
                         jnp.asarray(-jnp.inf, logits.dtype))

    # ONE definition of how the layer loop runs, shared by prefill /
    # decode_step / _prefill_chunk / the ragged chunk (private copies
    # could silently diverge the chunked-prefill path from decode if the
    # lora payload ever grows, e.g. k-proj deltas). What is read a layer
    # at a time (the weights, the optional adapter bank) rides the loop's
    # ``xs``; the pools ride its CARRY whole and are addressed in place
    # by (layer, page) — as ``xs``/``ys`` each call sliced every layer
    # out of them and stacked a fresh pool.
    def _layers_over_pools(x, k_pools, v_pools, layers, lora, pos,
                           attend_at):
        """Every layer's ``_layer_math`` over the carried pools:
        ``attend_at(i, k_pools, v_pools) -> attend`` is the program's
        cache strategy at layer ``i``, its ``attend(q, k, v)`` returning
        ``(ctx, (k_pools', v_pools'))``. -> (x, k_pools, v_pools)."""
        def body(i, carry, per_layer):
            x, k_pools, v_pools = carry
            if lora is None:
                lp, lo = per_layer, None
            else:
                lp, bl = per_layer
                lo = (bl, lora[1], lora_scale)
            x, (k_pools, v_pools) = _layer_math(
                cfg, lp, x, pos, attend_at(i, k_pools, v_pools), lora=lo)
            return x, k_pools, v_pools

        return _stack_carry(
            body, (x, k_pools, v_pools),
            layers if lora is None else (layers, lora[0]), scan_layers)

    # The kv head is an INDEX of every scatter and gather below, like
    # the layer and the page, never a slice: what one index moves is then
    # the pool's minor dims alone, contiguous as the pool lies. (With the
    # heads as a slice XLA:TPU stores the pool heads-minor for the
    # scatter and converts all of it there and back, every layer: the
    # chip compiler's output, tests/test_chip_aot.py.)
    _heads = np.arange(nkv)

    def _gather_pages(pool, i, page_tables):
        """(B, nkv, S, hd): layer ``i``'s pages of the batch in ONE
        gather from the whole pool, dequantizing only that slice —
        never a layer of the pool, never the pool."""
        B, W = page_tables.shape
        at = (i, _heads[None, :, None], page_tables[:, None, :])
        if isinstance(pool, tuple):
            data, sc = pool
            g = data[at].astype(jnp.float32) * sc[at][..., None]
        else:
            g = pool[at]                         # (B, nkv, W, page, hd)
        return g.reshape(B, nkv, W * page_size, hd)

    def init_pools():
        shape = (L, nkv, n_pool_pages, page_size, hd)
        if pressure:
            # two-tier arena: full-precision pages PLUS an int8+scale
            # shadow and a (P,) tier mask saying which arena each page
            # reads from. All jit inputs — compaction flips tier bits,
            # never shapes, so the degradation tier cannot recompile.
            def one():
                return (jnp.zeros(shape, dtype),
                        jnp.zeros(shape, jnp.int8),
                        jnp.ones(shape[:-1], jnp.float32))
            return one(), one(), jnp.zeros((n_pool_pages,), bool)
        if quantized:
            def one():
                return (jnp.zeros(shape, jnp.int8),
                        jnp.ones(shape[:-1], jnp.float32))
            pools = one(), one()
        else:
            pools = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
        if tp_mesh is not None:
            # page CONTENT splits by kv head; the spec's trailing dims
            # (and the int8 scale leaves' 4-D shape) stay replicated
            pools = device_put_sharded(pools, tp_mesh,
                                       tp_pool_spec(tp.axis))
        return pools

    def _tier_clear(pools, written_ids):
        """PRESSURE: the pages this program is about to write get
        fresh full-precision content, so their tier bit dies in the
        SAME program — a recycled page id can never read stale int8
        data (the device-side twin of PagedKVCache dropping a page's
        tier with its id). Rewrites of still-cached tails clear too:
        their fp slots hold identical content."""
        (kf, kq, ks), (vf, vq, vs), tier = pools
        tier = tier.at[written_ids.reshape(-1)].set(False)
        return (kf, kq, ks), (vf, vq, vs), tier

    def _tier_enter(pools):
        """PRESSURE: merge both arenas into ONE full-precision view
        (quantized pages dequantized through the tier mask) so every
        downstream read/write path is the unquantized program — one
        fixed-shape where() per pool, no second attention variant.
        Returns (k_view, v_view, merge_ctx); passthrough otherwise."""
        if not pressure:
            k_pools, v_pools = pools
            return k_pools, v_pools, None
        (kf, kq, ks), (vf, vq, vs), tier = pools
        t = tier[None, None, :, None, None]

        def merge(fp, qd, s):
            return jnp.where(
                t, (qd.astype(jnp.float32) * s[..., None]).astype(
                    fp.dtype), fp)

        return merge(kf, kq, ks), merge(vf, vq, vs), (pools, t)

    def _tier_exit(k_eff, v_eff, ctx):
        """PRESSURE: fold the written merged view back into the
        two-tier pool — quantized pages keep their (authoritative)
        int8 arena and old fp slots, everything else takes the writes.
        Passthrough otherwise."""
        if ctx is None:
            return k_eff, v_eff
        ((kf, kq, ks), (vf, vq, vs), tier), t = ctx
        return ((jnp.where(t, kf, k_eff), kq, ks),
                (jnp.where(t, vf, v_eff), vq, vs), tier)

    # the writes: ONE scatter of the new rows into the carried pool at
    # (layer, head, page, offset) / (layer, head, page id)
    def _write_token(pool, i, kv, pages, offs):
        """kv (B, nkv, 1, hd) written at each row's (page, offset)."""
        at = (i, _heads[None, :], pages[:, None], offs[:, None])
        if isinstance(pool, tuple):
            data, sc = pool
            qd, s = _q8(kv)                              # (B,nkv,1,hd)
            return (data.at[at].set(qd[:, :, 0]),
                    sc.at[at].set(s[:, :, 0]))
        return pool.at[at].set(kv[:, :, 0].astype(pool.dtype))

    def _write_pages(pool, i, kv, ids):
        """kv (B, nkv, C, hd) scattered as whole pages to layer ``i``'s
        page ``ids`` (B, C/page_size): the one page write under the
        chunk, the ragged chunk and the one-shot prompt (their C and
        first position are page multiples)."""
        B, npg = ids.shape
        at = (i, _heads[None, :, None], ids[:, None, :])

        def pageify(a):                  # -> (B, nkv, npg, page, ...)
            return a.reshape((B, nkv, npg, page_size) + a.shape[3:])

        if isinstance(pool, tuple):
            data, sc = pool
            qd, s = _q8(kv)
            return (data.at[at].set(pageify(qd)),
                    sc.at[at].set(pageify(s)))
        return pool.at[at].set(pageify(kv).astype(pool.dtype))

    @partial(jax.jit, donate_argnums=(5,))  # updated where they lie
    def prefill(outer, layers, tokens, page_tables, lengths, pools,
                lora=None, grammar=None):
        """Prompts padded to a page multiple; ``lengths`` are the REAL
        prompt lengths (padding K/V lands in allocated pages but is
        masked by lengths everywhere downstream). ``lora``: optional
        ``(adapter_bank, adapter_ids)`` multi-adapter deltas.
        ``grammar``: optional ``(mask_table, state_ids)`` constrained-
        decoding masks over the FIRST emitted token (each row's id is
        its automaton's start state; free rows pass 0)."""
        B, T = tokens.shape
        if T % page_size:
            raise ValueError(f"prefill length {T} must be a multiple of "
                             f"page_size {page_size} (pad the prompt)")
        ids = page_tables[:, :T // page_size]     # the prompt's pages
        if pressure:
            pools = _tier_clear(pools, ids)
        k_pools, v_pools, _tm = _tier_enter(pools)
        x = jnp.take(outer["model.embed_tokens.weight"], tokens, axis=0)
        pos_vec = jnp.arange(T)
        causal = jnp.tril(jnp.ones((T, T), bool))
        # padding keys never attend: key j valid iff j < len(b)
        key_ok = jnp.arange(T)[None, :] < lengths[:, None]
        mask = causal[None, None] & key_ok[:, None, None, :]

        def attend_at(i, k_pools, v_pools):
            def attend(q, k, v):
                kp = _write_pages(k_pools, i, k, ids)
                vp = _write_pages(v_pools, i, v, ids)
                return _attend(cfg, q, k, v, mask), (kp, vp)
            return attend

        x, k_pools, v_pools = _layers_over_pools(
            x, k_pools, v_pools, layers, lora, pos_vec, attend_at)
        x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
        # each sequence's last REAL position owns the next token
        x_last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), 1)[:, 0]
        out = _emit(_gmask(_logits(cfg, outer, x_last), grammar))
        return out, _tier_exit(k_pools, v_pools, _tm)

    @partial(jax.jit, donate_argnums=(5,))  # no per-token pool copy
    def decode_step(outer, layers, tok, page_tables, lengths, pools,
                    lora=None, grammar=None):
        # each sequence's current end: where this token's K/V land
        pages = jnp.take_along_axis(
            page_tables, (lengths // page_size)[:, None], 1)[:, 0]
        offs = lengths % page_size
        if pressure:
            pools = _tier_clear(pools, pages)
        k_pools, v_pools, _tm = _tier_enter(pools)
        x = jnp.take(outer["model.embed_tokens.weight"], tok,
                     axis=0)[:, None]                    # (B, 1, H)
        pos = lengths[:, None]                           # per-sequence

        def attend_at(i, k_pools, v_pools):
            def attend(q, k, v):
                kp = _write_token(k_pools, i, k, pages, offs)
                vp = _write_token(v_pools, i, v, pages, offs)
                ctx = _paged_kernel(paged_attention, q[:, :, 0], kp, vp,
                                    page_tables, lengths + 1, layer=i)
                return ctx[:, :, None], (kp, vp)
            return attend

        x, k_pools, v_pools = _layers_over_pools(
            x, k_pools, v_pools, layers, lora, pos, attend_at)
        x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
        out = _emit(_gmask(_logits(cfg, outer, x[:, 0]), grammar))
        return out, _tier_exit(k_pools, v_pools, _tm)

    @partial(jax.jit, donate_argnums=(6,))
    def _prefill_chunk(outer, layers, chunk, start, page_tables, lengths,
                       pools, x_last, lora=None):
        """One C-token chunk at absolute positions start..start+C-1:
        writes its pages, attends to every pool position < start+C, and
        harvests the hidden state of each sequence's (length-1) row when
        it falls inside this chunk."""
        C = chunk.shape[1]
        # start and C are page multiples, so whole pages scatter
        ids = jax.lax.dynamic_slice_in_dim(
            page_tables, start // page_size, C // page_size, 1)
        if pressure:
            pools = _tier_clear(pools, ids)
        k_pools, v_pools, _tm = _tier_enter(pools)
        W = page_tables.shape[1]
        S = W * page_size
        x = jnp.take(outer["model.embed_tokens.weight"], chunk, axis=0)
        pos_vec = start + jnp.arange(C)
        # causal over ABSOLUTE key positions, bounded by real length
        key_ok = (jnp.arange(S)[None, None, :]
                  <= (start + jnp.arange(C))[None, :, None]) \
            & (jnp.arange(S)[None, None, :]
               < lengths[:, None, None])
        mask = key_ok[:, None]                       # (B, 1, C, S)

        def attend_at(i, k_pools, v_pools):
            def attend(q, k, v):
                kp = _write_pages(k_pools, i, k, ids)
                vp = _write_pages(v_pools, i, v, ids)
                if prefill_attention == "kernel":
                    ctx = _paged_kernel(paged_prefill_attention, q, kp,
                                        vp, page_tables, lengths, start,
                                        layer=i)
                    return ctx, (kp, vp)
                k_all = _gather_pages(kp, i, page_tables)
                v_all = _gather_pages(vp, i, page_tables)
                return _attend(cfg, q, k_all.astype(q.dtype),
                               v_all.astype(q.dtype), mask), (kp, vp)
            return attend

        x, k_pools, v_pools = _layers_over_pools(
            x, k_pools, v_pools, layers, lora, pos_vec, attend_at)
        # harvest rows whose (length-1) position lives in this chunk
        idx = jnp.clip(lengths - 1 - start, 0, C - 1)
        row = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32),
                                  1)[:, 0]
        hit = ((lengths - 1 >= start)
               & (lengths - 1 < start + C))[:, None]
        x_last = jnp.where(hit, row, x_last)
        return x_last, _tier_exit(k_pools, v_pools, _tm)

    @jax.jit
    def _finish_prefill(outer, x_last, grammar=None):
        x = _rms(x_last, outer["model.norm.weight"], cfg.rms_norm_eps)
        return _emit(_gmask(_logits(cfg, outer, x), grammar))

    prefill_chunked = chunked_prefill_shim(
        _prefill_chunk, _finish_prefill, chunked_prefill,
        cfg.hidden_size, dtype)

    @partial(jax.jit, donate_argnums=(6,))
    def _prefill_chunk_ragged(outer, layers, chunk, starts, page_tables,
                              lengths, pools, x_last, lora=None):
        """One C-token chunk PER ROW at per-row absolute positions
        starts[r]..starts[r]+C-1: a lane's pending chunks ACROSS
        requests fused into one fixed-shape program. ``starts`` rides
        as jit data exactly like decode_n's lengths, so one compiled
        program serves every admission mix. Rows with nothing to run
        point their pages at the reserved padding page 0 and write
        garbage there (the pool convention); their x_last never
        updates because length-1 falls outside the chunk window."""
        C = chunk.shape[1]
        # per-row page ids, gathered where _prefill_chunk takes one
        # shared slice. Duplicate ids across rows (idle rows all point at
        # the reserved page 0; cohort rows rewriting a shared cached page
        # carry identical content) make the scatter order unspecified
        # but the result deterministic.
        col = (starts // page_size)[:, None] \
            + jnp.arange(C // page_size)[None, :]
        ids = jnp.take_along_axis(page_tables, col, 1)
        if pressure:
            pools = _tier_clear(pools, ids)
        k_pools, v_pools, _tm = _tier_enter(pools)
        W = page_tables.shape[1]
        S = W * page_size
        x = jnp.take(outer["model.embed_tokens.weight"], chunk, axis=0)
        pos = starts[:, None] + jnp.arange(C)[None, :]       # (R, C)
        # causal over ABSOLUTE key positions, bounded by real length —
        # the per-chunk mask with a per-row start
        key_ok = (jnp.arange(S)[None, None, :] <= pos[:, :, None]) \
            & (jnp.arange(S)[None, None, :]
               < lengths[:, None, None])
        mask = key_ok[:, None]                       # (R, 1, C, S)

        def attend_at(i, k_pools, v_pools):
            def attend(q, k, v):
                kp = _write_pages(k_pools, i, k, ids)
                vp = _write_pages(v_pools, i, v, ids)
                k_all = _gather_pages(kp, i, page_tables)
                v_all = _gather_pages(vp, i, page_tables)
                return _attend(cfg, q, k_all.astype(q.dtype),
                               v_all.astype(q.dtype), mask), (kp, vp)
            return attend

        x, k_pools, v_pools = _layers_over_pools(
            x, k_pools, v_pools, layers, lora, pos, attend_at)
        idx = jnp.clip(lengths - 1 - starts, 0, C - 1)
        row = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32),
                                  1)[:, 0]
        hit = ((lengths - 1 >= starts)
               & (lengths - 1 < starts + C))[:, None]
        x_last = jnp.where(hit, row, x_last)
        return x_last, _tier_exit(k_pools, v_pools, _tm)

    def prefill_ragged(outer, layers, chunk, starts, page_tables,
                       lengths, pools, lora=None, grammar=None):
        """ONE fused lane dispatch: row r runs the C tokens of
        ``chunk[r]`` at absolute offset ``starts[r]`` against its own
        page table. Returns per-row next-token logits-argmax like
        ``prefill``; only rows whose FINAL chunk this is (length-1
        inside the window) carry a meaningful value — the engine reads
        exactly those rows and ignores the rest."""
        R = chunk.shape[0]
        x_last = jnp.zeros((R, cfg.hidden_size), dtype)
        x_last, pools = _prefill_chunk_ragged(
            outer, layers, chunk, starts, page_tables, lengths, pools,
            x_last, lora)
        return _finish_prefill(outer, x_last, grammar), pools

    prefill_ragged._jit_inner = (_prefill_chunk_ragged, _finish_prefill)

    if chunked_prefill is not None:
        if chunked_prefill % page_size:
            raise ValueError("chunked_prefill must be a multiple of "
                             f"page_size ({page_size})")
        prefill = prefill_chunked
        if prefill_attention != "kernel":
            # the fused program always attends via the gather path;
            # advertising it under kernel-mode prefill would silently
            # mix two numerics in one run, so the engine only sees the
            # ragged entry point when both programs share the math
            prefill_chunked._ragged = prefill_ragged

    @partial(jax.jit, donate_argnums=(5,), static_argnums=(6,))
    def decode_n(outer, layers, tok, page_tables, lengths, pools, n,
                 lora=None, grammar=None):
        """n decode steps in ONE compiled program (lax.scan over the
        step body) — the serving loop's dispatch amortizer: one host
        dispatch per n tokens instead of one per token (the per-step
        dispatch cost on a local chip is not measured yet — PERF.md,
        open questions). With
        emit="logits" the feedback token is greedy argmax; the stacked
        per-step emissions come back as (n, B, ...) so the caller still
        owns post-hoc sampling decisions. Returns
        (emits (n, B, ...), next_tok (B,), pools'); the caller's length
        bookkeeping is lengths' = lengths + n. NOTE: ``pools`` is
        DONATED (like decode_step's) — rebind the returned pools and
        never reuse the argument, or JAX raises a donated-buffer
        error. ``lora``: optional ``(adapter_bank, adapter_ids)``
        multi-adapter deltas — both jit INPUTS, so the ONE compiled
        program serves any adapter mix (the serving_lora recompile
        gate counts exactly this cache staying at one entry).
        ``grammar``: optional ``(mask_table, state_ids)`` constrained-
        decoding masks, the same jit-input discipline. NOTE the DFA
        state advances HOST-side from each emitted token, so the mask
        holds each row's dispatch-time state for all ``n`` scanned
        steps — a wave carrying any constrained row must run ``n=1``
        (the serving engine clamps exactly this; ``n`` is static, so
        the clamp costs at most one extra cache entry, flat in the
        number of schemas)."""
        (emits,), tok, pools = decode_scan(
            lambda tok, lens, pools: decode_step(
                outer, layers, tok, page_tables, lens, pools, lora,
                grammar),
            tok, lengths, pools, n)
        return emits, tok, pools

    pools = init_pools()
    if tp is not None and tp.hbm_budget_bytes_per_device is not None:
        # MEASURED per-device residency after placement (weights +
        # pools) vs the declared budget: a model too big for one
        # device's HBM must refuse loudly here, not OOM mid-serve —
        # and the same model under a wider mesh fits and serves (the
        # serving_tp capacity gate drives exactly this pair)
        need = decode_need_bytes_per_device(outer, layers, pools)
        if need > tp.hbm_budget_bytes_per_device:
            raise MemoryError(
                f"tp={tp.size}: weights + KV pool need {need} bytes "
                f"per device, budget is "
                f"{tp.hbm_budget_bytes_per_device} — widen the mesh "
                "or shrink the pool")
    return outer, layers, pools, prefill, decode_step, decode_n


def route_decode(lengths, capacity: int, shared_prefix: bool = False,
                 expect_churn: bool = False, explain: bool = False):
    """Serving router: pick the decode backend from batch statistics
    (round-4 verdict item 6 — callers previously chose by hand).

    Returns "paged" or "dense". Policy derived from the chip rows in
    PERF.md (records 27/29/34 + the round-5 compiled-decode
    re-measurement, record 37): routing is by batch STRUCTURE, not
    size — the round-4 "small batches -> paged (1.90x)" rule compared
    scan-amortized paged against a per-token-dispatched dense loop;
    with the dense loop compiled (gen.compiled) dense wins every
    uniform shape measured (B=1: 559 vs ~166 tok/s paged-per-seq
    equivalent; B=8: 3237 vs 1685; B=64: 3594 vs 3043 at the best
    page size).

    - shared prompt prefixes -> paged (prefix pages are shared across
      sequences; the dense cache replicates them per slot)
    - admission/eviction churn (continuous batching) -> paged (dense
      slots pin max_len memory for the whole batch lifetime)
    - ragged lengths -> paged (the dense cache masks but still walks
      max-length KV for every row; pages walk only real lengths)
    - severely under-full compiled capacity -> paged (dense pays
      full-capacity compute for empty slots)
    - otherwise (uniform, near-full) -> dense compiled

    ``lengths``: real sequence lengths (any array-like); ``capacity``:
    the batch size the dense cache would be compiled for.

    ``explain=True`` returns ``(backend, rule)`` where ``rule`` names
    the policy clause that fired — the serving engine's decision log
    (paddle_tpu.serving) records it so a workload bench can say WHICH
    routing rule lost when routed trails a fixed policy.
    """
    import numpy as _np

    from ...obs import metrics as _obs_metrics

    def _r(backend, rule):
        # obs counter per (clause, backend): the short label is the
        # rule text up to its parenthesized rationale — stable across
        # wording tweaks inside the parens, low-cardinality by design
        _obs_metrics.counter(
            "route_decode_total", "routing-rule firings by clause",
            rule=rule.split(" (")[0], backend=backend).inc()
        return (backend, rule) if explain else backend

    lens = _np.asarray(lengths)
    if shared_prefix:
        return _r("paged", "shared-prefix (prefix pages shared across "
                           "sequences; dense replicates per slot)")
    if expect_churn:
        return _r("paged", "churn (dense slots pin max_len memory for "
                           "the batch lifetime)")
    B = int(lens.size)
    if B == 0:
        return _r("dense", "empty wave")
    spread = float(lens.max() - lens.min()) / max(1.0, float(lens.max()))
    if spread > 0.25:
        return _r("paged", f"ragged lengths (spread {spread:.2f} > 0.25; "
                           "pages walk only real lengths)")
    if B < capacity // 2:
        return _r("paged", f"under-full (B={B} < capacity {capacity}//2; "
                           "dense pays full-capacity compute)")
    return _r("dense", "uniform near-full wave (dense compiled wins "
                       "every uniform shape measured, PERF record 37)")


class PagedOnlyDense:
    """THE dense-backend stub for paged-only serving factories (the
    TP factory below and ``serving.sim`` share it): exactly enough
    surface for ``ServingEngine.__init__``'s dense introspection —
    the ``rolling`` check and the embed-tokens dtype read — with
    every actual dense call raising ``reason``. One class, so when
    the engine grows a new introspection read there is one stub to
    keep in lockstep, not a copy per paged-only factory."""

    def __init__(self, reason: str):
        def _raise(*a, **k):
            raise NotImplementedError(reason)
        self._raise = _raise
        self._parts = {
            "rolling": False,
            "outer": {"model.embed_tokens.weight":
                      np.zeros((1, 1), np.float32)},
            "init_caches": _raise,
            "prefill": _raise,
            "decode_step": _raise,
        }

    def __call__(self, *a, **k):
        self._raise()


_TP_DENSE_REASON = (
    "a tensor-parallel serving factory is paged-only: the dense "
    "wave cache replicates max_len K/V per slot on ONE device, "
    "which is exactly the residency TP exists to break — route "
    "with policy='paged'")

_PRESSURE_DENSE_REASON = (
    "a kv_quant='pressure' serving factory is paged-only: the "
    "degradation tier compacts PAGES parked in the pool's evictable "
    "LRU, and the dense wave cache has neither pages nor an LRU — "
    "route with policy='paged'")


def llama_serving_decode_factory(model: LlamaForCausalLM,
                                 max_len: int = 256,
                                 page_size: int = 64,
                                 n_pool_pages: int = 256,
                                 kv_cache_dtype: str | None = None,
                                 batch_capacity: int = 8,
                                 scan_layers: bool = True,
                                 chunked_prefill: int | None = None,
                                 tp: "TPConfig | int | None" = None,
                                 lora: "LoRAConfig | tuple | None"
                                 = None,
                                 draft: LlamaForCausalLM | None
                                 = None,
                                 kv_quant: str | None = None,
                                 grammar: "GrammarConfig | tuple | "
                                 "None" = None):
    """Both decode backends behind one object + the router: build once,
    then ``pick(lengths, ...)`` returns ("dense", gen) or
    ("paged", (outer, layers, pools, prefill, decode_step, decode_n))
    per batch. The dense program and the paged pool coexist; routing
    per admission wave is how serving stacks exploit both regimes.

    ``batch_capacity`` is the batch size the dense compiled program is
    expected to serve (gen.compiled specializes per batch shape; this
    is the shape the serving loop pads uniform waves to). It is the
    DEFAULT ``capacity`` for ``pick`` — previously capacity defaulted
    to len(lengths), which made route_decode's under-full check
    (B < capacity//2) unreachable: a 2-request wave against an 8-slot
    compiled program now correctly routes paged."""
    # kv_cache_dtype is the SERVING cache codec: it must reach BOTH
    # backends, or an int8-configured engine would quantize only
    # paged-routed traffic (and int8 rounding can flip a greedy token,
    # breaking cross-backend output parity for no routing reason)
    tp = as_tp_config(tp)
    lora = as_lora_config(lora)
    grammar = as_grammar_config(grammar)
    if kv_quant not in (None, "int8", "pressure"):
        raise ValueError(f"kv_quant {kv_quant!r}: use None, 'int8' or "
                         "'pressure'")
    if kv_quant == "int8":
        if kv_cache_dtype not in (None, "int8"):
            raise ValueError("kv_quant='int8' IS kv_cache_dtype="
                             f"'int8' — {kv_cache_dtype!r} conflicts")
        # the serving cache codec must reach BOTH backends (see the
        # kv_cache_dtype note below), so always-int8 rides it
        kv_cache_dtype = "int8"
    if kv_quant == "pressure":
        if kv_cache_dtype is not None:
            raise ValueError("kv_quant='pressure' owns the pool codec "
                             "— drop kv_cache_dtype")
        if draft is not None:
            raise ValueError(
                "kv_quant='pressure' does not compose with draft= "
                "yet: the draft pool rides the target's page ids but "
                "has no tier mask, so a compacted target page would "
                "desync draft K/V — use kv_quant='int8'")
    if tp is None:
        if kv_quant == "pressure":
            # pressure is PAGED-ONLY: the dense wave cache has no
            # pages to tier
            gen = PagedOnlyDense(_PRESSURE_DENSE_REASON)
        else:
            gen = llama_decode_factory(model, max_len=max_len,
                                       kv_cache_dtype=kv_cache_dtype,
                                       scan_layers=scan_layers)
    else:
        # tensor-parallel serving is PAGED-ONLY: no dense replica is
        # built (see PagedOnlyDense) — the engine coerces its routing
        # to the paged backend
        gen = PagedOnlyDense(_TP_DENSE_REASON)
    paged = llama_paged_decode_factory(model, page_size=page_size,
                                       n_pool_pages=n_pool_pages,
                                       kv_cache_dtype=kv_cache_dtype,
                                       chunked_prefill=chunked_prefill,
                                       scan_layers=scan_layers, tp=tp,
                                       lora=lora, kv_quant=kv_quant)
    lora_hooks = None
    if lora is not None:
        # the adapter-cache device hooks (serving.adapters.AdapterCache
        # consumes them); dtype follows the decode weights
        lora_hooks = lora_bank_hooks(
            model.config, lora,
            paged[1]["self_attn.q_proj.weight"].dtype, tp=tp)
    grammar_hooks = None
    if grammar is not None:
        # the grammar-cache device hooks (serving.grammar.GrammarCache
        # consumes them); under tp the bank replicates on the mesh
        grammar_hooks = grammar_bank_hooks(model.config.vocab_size,
                                           grammar, tp=tp)
    spec_built = None
    if draft is not None:
        # SPECULATIVE serving: the draft model gets its own paged
        # parts over the SAME page geometry — its pool is indexed by
        # the target's page ids, so draft K/V rides the target's
        # PagedKVCache chains (one allocation per request covers
        # both; prefix retention and eviction recycle draft pages in
        # lockstep with target pages). The batched spec round program
        # (draft propose + target verify + branch-free acceptance)
        # comes from build_spec_step.
        if lora is not None:
            raise ValueError(
                "speculative serving does not compose with lora= yet "
                "— the draft has no adapter bank, so a per-row delta "
                "would desync draft proposals from the verified "
                "target (run spec engines single-model)")
        if draft.config.vocab_size != model.config.vocab_size:
            raise ValueError("target and draft must share a "
                             "vocabulary")
        d_outer, d_layers, d_pools, d_prefill, _, _ = \
            llama_paged_decode_factory(
                draft, page_size=page_size, n_pool_pages=n_pool_pages,
                chunked_prefill=chunked_prefill,
                scan_layers=scan_layers)
        if tp is not None:
            # the draft REPLICATES on the target's mesh (no partition
            # specs = every device holds the whole draft): a draft is
            # small by construction, and a replicated draft walk
            # needs zero collectives — only the sharded target verify
            # pays the per-block psums
            mesh = tp.build_mesh()
            d_outer = device_put_sharded(d_outer, mesh)
            d_layers = device_put_sharded(d_layers, mesh)
            d_pools = device_put_sharded(d_pools, mesh)
        spec_built = (d_outer, d_layers, d_pools, d_prefill,
                      build_spec_step(model.config, draft.config,
                                      page_size, scan_layers))

    class _Serving:
        # staticmethod: a bare function class-attribute would BIND as a
        # method and eat the first positional arg (tokens) as self
        dense = staticmethod(gen)
        paged_parts = paged
        capacity = batch_capacity
        # build-config metadata the serving engine reads when handed a
        # prebuilt factory (paddle_tpu.serving.ServingEngine(serving=...))
        max_len_ = max_len
        page_size_ = page_size
        n_pool_pages_ = n_pool_pages
        chunked_prefill_ = chunked_prefill
        # chunks ONE lane call may span (``prefill.lane_call``). None:
        # the factory sets no limit of its own — the gather path holds
        # no block of query rows in VMEM, the engine's budget bounds it
        chunked_prefill_widest_ = None
        # ... and ONE lane program, the widest, that a narrower span
        # rides padded: bound by the weights it reads, the program costs
        # about the same at 64 and at 256 tokens (8.9 / 9.0 / 10.1 / 10.2
        # ms at 1 to 4 chunks on Mistral's 8 layers, TPU v5e), while
        # each further width is a trace and a lowering (0.3 - 0.5 s of
        # every start, the persistent compile cache notwithstanding)
        chunked_prefill_pads_ = True
        tp_ = tp  # TPConfig when the paged path is mesh-sharded
        lora_ = lora  # LoRAConfig when multi-adapter serving is built
        # GrammarConfig when constrained decoding is built, plus the
        # vocabulary size the engine compiles schemas against
        grammar_ = grammar
        grammar_vocab_ = model.config.vocab_size
        # quantized page tier: None | "int8" | "pressure". page_bytes_
        # prices ONE page (full-precision, int8+scale) for the
        # bookkeeper's stored-bytes census; the pressure hooks are the
        # device-side compaction/handoff programs the engine drives.
        kv_quant_ = kv_quant
        page_bytes_ = (kv_quant_page_bytes(
            model.config, page_size,
            paged[1]["self_attn.q_proj.weight"].dtype)
            if kv_quant is not None else None)
        if kv_quant == "pressure":
            compact_pages = staticmethod(compact_kv_pages)
            export_kv_pages = staticmethod(export_quant_pages)
            import_kv_pages = staticmethod(import_quant_pages)
        # (draft outer, layers, pools, chunked prefill, spec_step)
        # when the factory is spec-capable; None otherwise — the
        # engine refuses ServingEngine(spec=...) without it. A tuple,
        # not a callable, so the class attribute never method-binds.
        spec_parts = spec_built
        if getattr(paged[3], "_ragged", None) is not None:
            # the fused ragged-prefill entry point (one program for a
            # whole lane turn); absent when the per-chunk prefill uses
            # kernel attention, so the engine's ragged_prefill= flag
            # fails loudly instead of mixing numerics
            prefill_ragged = staticmethod(paged[3]._ragged)
        if lora_hooks is not None:
            # adapter-cache device hooks (paddle_tpu.serving.adapters)
            init_adapter_bank = staticmethod(lora_hooks[0])
            upload_adapter = staticmethod(lora_hooks[1])
        if grammar_hooks is not None:
            # grammar-cache device hooks (paddle_tpu.serving.grammar)
            init_grammar_bank = staticmethod(grammar_hooks[0])
            upload_grammar = staticmethod(grammar_hooks[1])

        def pick(self, lengths, capacity=None, shared_prefix=False,
                 expect_churn=False):
            if self.tp_ is not None or self.kv_quant_ == "pressure":
                # no dense replica exists on a sharded or
                # pressure-tiered factory
                return "paged", paged
            # read the live attribute (not the factory closure) so
            # callers who adjust serving.capacity see routing follow
            cap = capacity if capacity is not None else self.capacity
            backend = route_decode(lengths, cap, shared_prefix,
                                   expect_churn)
            return backend, (gen if backend == "dense" else paged)

    return _Serving()
