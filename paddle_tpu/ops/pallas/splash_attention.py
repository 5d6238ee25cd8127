"""Block-sparse ("splash") flash attention Pallas TPU kernel.

~ the reference's sparse_attention_op.cu (block-sparse SDD attention over
a CSR pattern) — which computes DENSE scores and masks. Here masked-out
blocks are truly SKIPPED in the forward and dQ walks: a per-q-block list
of live kv-block indices (scalar-prefetched into SMEM) drives the
online-softmax walk, so compute and VMEM traffic scale with the
pattern's density, not O(S^2). Same resident-KV + exp2-domain design as
flash_attention.py.

ONE kernel family serves both MHA and GQA/MQA: queries carry a group
dimension (the G query heads sharing a kv head fold into the matmul M
dimension, K/V stay at their true head count — flash_attention_gqa.py's
layout); plain multi-head attention is the G=1 case. The dK/dV backward
STREAMS q blocks through an innermost grid dimension with VMEM scratch
accumulators (full-sequence q/do residency would be G*Sq*D — over VMEM
at training shapes); dead (q, kv) block pairs skip their compute via a
prefetched block-mask predicate (their DMA still runs — Mosaic fetches
per grid step — so the dkv pass is DMA-dense but compute-sparse).

The block pattern is a (num_q_blocks, num_kv_blocks) bool mask — the
natural TPU granularity (MXU tiles), and the form local/strided/BigBird
patterns compress to. ``causal=True`` applies the elementwise triangle
inside live blocks; ``window`` additionally applies the token-exact
sliding-window band (q_pos - k_pos < window, Mistral semantics).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LN2, LOG2E, NEG_INF
from .lowering import interpret as _interpret

# f32-element budget for one (G*block_q, block_k) score/probability buffer
# (2 MB each); _resolve raises when a grouped config exceeds it
SCORE_ELEMS = 512 * 1024


# see flash_attention_gqa._MAX_ROWS — same v5e scoped-vmem measurement
MAX_ROWS = 2048

# Resident K/V in the fwd/dq kernels grows with Sk (the long8k failure
# mode of the MHA flash kernels); past this frontier the kernels switch
# to STREAMING live kv blocks through an innermost grid dimension whose
# index map reads the prefetched kv_idx table — VMEM drops to O(block)
# and DMA to O(live blocks), i.e. the pattern's density (the resident
# walk DMAs nothing per step but holds all of K/V; the dkv pass was
# always streamed). The fit model is flash_attention_gqa's — one
# definition, recalibrated in one place by tools/long8k_vmem_repro.py.
# None = automatic; tests/benches may force a mode.
from .flash_attention_gqa import _gqa_fits as _resident_fits  # noqa: E402

_FORCE_STREAM = None


def fits_score_budget(groups: int, block_q: int = 128,
                      block_k: int = 128) -> bool:
    """The kernel's VMEM eligibility predicate — ONE definition shared
    with model-level gates (llama's grouped sliding-window path) so the
    bound can't drift between the kernel and its callers. Checks both
    the (G*bq, bk) score-buffer budget and the G*bq row cap (rows-tall
    q/acc/out buffers bound VMEM independently of block_k)."""
    return (groups * block_q * block_k <= SCORE_ELEMS
            and groups * block_q <= MAX_ROWS)


def pick_splash_blocks(Sq: int, Sk: int, groups: int = 1):
    """Largest square block pair (512 -> 256 -> 128) that divides the
    sequences and fits the score/row budgets. Measured on v5e
    (2026-08-01, fwd+bwd chains): at window=2048/S=8192 the 512-block
    banded kernel runs 20.4 ms vs 62.0 ms at 128 blocks — per-block
    overhead dominates the extra boundary density at every window down
    to 256 — so callers building masks should use the coarsest tiling
    the budgets allow, not the finest."""
    for cand in (512, 256, 128):
        if Sq % cand or Sk % cand:
            continue
        bq = bk = cand
        while not fits_score_budget(groups, bq, bk) and bk > 128:
            bk //= 2
        # large groups (MQA) blow the G*bq row cap at any bk: shrink bq
        # (halving preserves divisibility and sublane alignment down to 8)
        while not fits_score_budget(groups, bq, bk) and bq > 8 \
                and (bq // 2) % 8 == 0:
            bq //= 2
        if fits_score_budget(groups, bq, bk):
            return bq, bk
    return 128, 128


def _pattern_tables(block_mask: np.ndarray):
    """Dense (nq, nk) bool -> padded per-q-block kv index lists.

    Returns (kv_idx (nq, max_kv), kv_cnt (nq,)) int32; padding entries
    repeat the last valid index (never walked — counts bound the
    fori_loop)."""
    bm = np.asarray(block_mask, bool)
    nq, _ = bm.shape
    kv_cnt = bm.sum(1).astype(np.int32)
    max_kv = max(1, int(kv_cnt.max()))
    kv_idx = np.zeros((nq, max_kv), np.int32)
    for i in range(nq):
        live = np.flatnonzero(bm[i])
        kv_idx[i, :len(live)] = live
        if len(live):
            kv_idx[i, len(live):] = live[-1]
    return kv_idx, kv_cnt


def banded_block_mask(Sq, Sk, block_q, block_k, window,
                      causal=True) -> np.ndarray:
    """Block mask for sliding-window attention: block (i, j) is live iff
    some (q_pos, k_pos) pair in it satisfies the causal triangle and
    q_pos - k_pos < window (token-exact masking happens in-kernel)."""
    nq, nk = Sq // block_q, Sk // block_k
    bm = np.zeros((nq, nk), bool)
    for i in range(nq):
        q_hi = (i + 1) * block_q - 1
        q_lo = i * block_q
        for j in range(nk):
            k_hi = (j + 1) * block_k - 1
            k_lo = j * block_k
            if causal and k_lo > q_hi:
                continue
            # the block's MINIMUM q_pos - k_pos is q_lo - k_hi; the block
            # is dead only when even that violates the band
            if window is not None and q_lo - k_hi >= window:
                continue
            bm[i, j] = True
    return bm


def _live_mask(qi, kj, rows, block_q, block_k, causal, window,
               q_offset=0):
    """Elementwise live mask for a (G*block_q, block_k) score block: row
    r belongs to query position qi*block_q + (r % block_q) — the group
    index r // block_q shares positions across the G heads. q_offset
    shifts the query frame relative to the keys (ring attention's
    cross-chunk pairs: chunk distance d puts queries d*S_local ahead of
    the held K/V chunk)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
    q_pos = q_offset + qi * block_q + jax.lax.rem(r, block_q)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_k), 1)
    live = jnp.ones((rows, block_k), bool)
    if causal:
        live &= q_pos >= k_pos
    if window is not None:
        live &= (q_pos - k_pos) < window
    return live


def _fwd_kernel(kv_idx, kv_cnt, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                sm_scale, causal, block_q, block_k, window, groups,
                q_offset=0):
    qi = pl.program_id(1)
    G = groups
    D = q_ref.shape[-1]
    rows = G * block_q
    q = q_ref[0].reshape(rows, D)
    q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
    m = jnp.full((rows,), NEG_INF, jnp.float32)
    l = jnp.zeros((rows,), jnp.float32)
    acc = jnp.zeros((rows, D), jnp.float32)

    def body(t, carry):
        m, l, acc = carry
        kj = kv_idx[qi, t]
        k = k_ref[0, pl.dslice(kj * block_k, block_k)]
        v = v_ref[0, pl.dslice(kj * block_k, block_k)]
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or window is not None:
            s = jnp.where(_live_mask(qi, kj, rows, block_q, block_k,
                                     causal, window,
                                     q_offset), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp2(s - m_new[:, None])
        # rows with NO live entry yet (m_new still NEG_INF — e.g. a live
        # block entirely above the causal diagonal): exp2(s - m_new) is
        # exp2(0) = 1 per entry since NEG_INF is finite; zero them so
        # such rows accumulate no bogus mass
        p = jnp.where((m_new > NEG_INF * 0.5)[:, None], p, 0.0)
        alpha = jnp.exp2(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, kv_cnt[qi], body, (m, l, acc))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    # fully-masked rows (no live block, or live blocks fully above the
    # causal diagonal) output 0
    any_mass = l > 0.0
    o_ref[0] = jnp.where(any_mass[:, None], acc / l_safe[:, None],
                         0.0).reshape(G, block_q, D).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(any_mass, LN2 * m + jnp.log(l_safe),
                           NEG_INF).reshape(G, block_q, 1).astype(
        jnp.float32)


def _bwd_dq_kernel(kv_idx, kv_cnt, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, *, sm_scale, causal, block_q,
                   block_k, window, groups, q_offset=0):
    qi = pl.program_id(1)
    G = groups
    D = q_ref.shape[-1]
    rows = G * block_q
    q = q_ref[0].reshape(rows, D)
    q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
    do = do_ref[0].reshape(rows, D)
    lse2 = lse_ref[0].reshape(rows) * LOG2E
    delta = delta_ref[0].reshape(rows)
    dq = jnp.zeros((rows, D), jnp.float32)

    def body(t, dq):
        kj = kv_idx[qi, t]
        k = k_ref[0, pl.dslice(kj * block_k, block_k)]
        v = v_ref[0, pl.dslice(kj * block_k, block_k)]
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or window is not None:
            s = jnp.where(_live_mask(qi, kj, rows, block_q, block_k,
                                     causal, window,
                                     q_offset), s, NEG_INF)
        # masked entries must be 0 regardless of lse: for an all-masked
        # row lse is NEG_INF and s - lse2 would OVERFLOW to +inf
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp2(s - lse2[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + jax.lax.dot_general(ds.astype(k.dtype), k,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, kv_cnt[qi], body, dq)
    dq_ref[0] = dq.reshape(G, block_q, D).astype(dq_ref.dtype)


def _fwd_kernel_stream(kv_idx, kv_cnt, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, m_scr, l_scr, acc_scr, *, sm_scale,
                       causal, block_q, block_k, window, groups, t_max,
                       q_offset=0):
    """Forward with LIVE kv blocks streamed through the innermost grid
    dimension: the k/v BlockSpec index maps read kv_idx[qi, t] from the
    scalar-prefetch channel, so only live blocks are ever DMA'd and VMEM
    holds one block — no resident K/V, no S ceiling. Same online-softmax
    math as `_fwd_kernel`, with the (m, l, acc) carry in VMEM scratch."""
    qi = pl.program_id(1)
    t = pl.program_id(2)
    G = groups
    D = q_ref.shape[-1]
    rows = G * block_q

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(t < kv_cnt[qi])
    def _compute():
        kj = kv_idx[qi, t]
        q = q_ref[0].reshape(rows, D)
        q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or window is not None:
            s = jnp.where(_live_mask(qi, kj, rows, block_q, block_k,
                                     causal, window,
                                     q_offset), s, NEG_INF)
        m = m_scr[...][:, 0]
        l = l_scr[...][:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp2(s - m_new[:, None])
        p = jnp.where((m_new > NEG_INF * 0.5)[:, None], p, 0.0)
        alpha = jnp.exp2(m - m_new)
        l_scr[...] = (alpha * l + jnp.sum(p, axis=1))[:, None]
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]

    @pl.when(t == t_max - 1)
    def _flush():
        m = m_scr[...][:, 0]
        l = l_scr[...][:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        any_mass = l > 0.0
        o_ref[0] = jnp.where(
            any_mass[:, None], acc_scr[...] / l_safe[:, None],
            0.0).reshape(G, block_q, D).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(any_mass, LN2 * m + jnp.log(l_safe),
                               NEG_INF).reshape(G, block_q, 1).astype(
            jnp.float32)


def _bwd_dq_kernel_stream(kv_idx, kv_cnt, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, delta_ref, dq_ref, dq_scr, *,
                          sm_scale, causal, block_q, block_k, window,
                          groups, t_max, q_offset=0):
    qi = pl.program_id(1)
    t = pl.program_id(2)
    G = groups
    D = q_ref.shape[-1]
    rows = G * block_q

    @pl.when(t == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(t < kv_cnt[qi])
    def _compute():
        kj = kv_idx[qi, t]
        q = q_ref[0].reshape(rows, D)
        q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
        do = do_ref[0].reshape(rows, D)
        lse2 = lse_ref[0].reshape(rows) * LOG2E
        delta = delta_ref[0].reshape(rows)
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or window is not None:
            s = jnp.where(_live_mask(qi, kj, rows, block_q, block_k,
                                     causal, window,
                                     q_offset), s, NEG_INF)
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp2(s - lse2[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == t_max - 1)
    def _flush():
        dq_ref[0] = dq_scr[...].reshape(G, block_q, D).astype(
            dq_ref.dtype)


def _bwd_dkv_kernel(bm_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale, causal, block_q, block_k, window, groups,
                    num_q, q_offset=0):
    """dK/dV with q blocks STREAMED through the innermost grid dimension
    (VMEM holds one (G, bq, D) q/do block, not the sequence); compute for
    dead (q, kv) pairs is skipped via the prefetched block-mask
    predicate."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    G = groups
    D = q_ref.shape[-1]
    rows = G * block_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(bm_ref[qi, kj] > 0)
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        k2 = (k.astype(jnp.float32) * (sm_scale * LOG2E)).astype(k.dtype)
        q = q_ref[0].reshape(rows, D)
        do = do_ref[0].reshape(rows, D)
        lse2 = lse_ref[0].reshape(rows) * LOG2E
        delta = delta_ref[0].reshape(rows)
        s = jax.lax.dot_general(q, k2, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or window is not None:
            s = jnp.where(_live_mask(qi, kj, rows, block_q, block_k,
                                     causal, window,
                                     q_offset), s, NEG_INF)
        # same NEG_INF-lse guard as the dq kernel
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp2(s - lse2[:, None]), 0.0)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _flush():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _resolve(q, k, block_mask, sm_scale, block_q, block_k):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    nq, nk = np.asarray(block_mask).shape
    bq = block_q or q.shape[2] // nq
    bk = block_k or k.shape[2] // nk
    if q.shape[2] != nq * bq or k.shape[2] != nk * bk:
        raise ValueError(
            f"splash_attention: block_mask {nq}x{nk} with blocks "
            f"({bq},{bk}) does not tile seqs ({q.shape[2]},{k.shape[2]})")
    if q.shape[1] % max(1, k.shape[1]):
        raise ValueError(
            f"query heads {q.shape[1]} not a multiple of kv heads "
            f"{k.shape[1]}")
    G = q.shape[1] // max(1, k.shape[1])
    if not fits_score_budget(G, bq, bk):
        # rows-tall (G*bq) q/acc/out buffers bound VMEM independently of
        # bk: measured on v5e, rows=4096 exceeds the 16M scoped-vmem
        # limit by ~1M even with the score budget satisfied (see
        # flash_attention_gqa._MAX_ROWS). Splash blocks are pinned by
        # the mask tiling, so the fix is a clear error, not auto-shrink.
        if G * bq > MAX_ROWS:
            raise ValueError(
                f"splash_attention: G*block_q = {G * bq} rows exceeds "
                f"the VMEM row budget ({MAX_ROWS}); use a finer "
                f"block_mask granularity (smaller block_q) or fewer "
                f"query groups")
        raise ValueError(
            f"splash_attention: G*block_q*block_k = {G * bq * bk} f32 "
            f"elements exceeds the VMEM score budget ({SCORE_ELEMS}); "
            f"use a finer block_mask granularity"
            + (" or repeat K/V across fewer query groups" if G > 1
               else ""))
    if _FORCE_STREAM is not None:
        streamed = _FORCE_STREAM
    else:
        streamed = not _resident_fits(G * bq, bk, k.shape[2],
                                      q.shape[-1], q.dtype.itemsize)
    return sm_scale, bq, bk, G, streamed


def _splash_fwd(q, k, v, block_mask, causal, sm_scale, block_q, block_k,
                window=None, q_offset=0):
    sm_scale, bq, bk, G, streamed = _resolve(q, k, block_mask, sm_scale,
                                             block_q, block_k)
    kv_idx, kv_cnt = _pattern_tables(block_mask)
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    Sk = k.shape[2]
    bh = B * Hkv
    qr = q.reshape(bh, G, Sq, D)
    kr = k.reshape(bh, Sk, D)
    vr = v.reshape(bh, Sk, D)
    if streamed:
        t_max = kv_idx.shape[1]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, Sq // bq, t_max),
            in_specs=[
                pl.BlockSpec((1, G, bq, D),
                             lambda b, i, t, idx, cnt: (b, 0, i, 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, i, t, idx, cnt: (b, idx[i, t], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, i, t, idx, cnt: (b, idx[i, t], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, G, bq, D),
                             lambda b, i, t, idx, cnt: (b, 0, i, 0)),
                pl.BlockSpec((1, G, bq, 1),
                             lambda b, i, t, idx, cnt: (b, 0, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, D), jnp.float32),
            ],
        )
        kernel = functools.partial(
            _fwd_kernel_stream, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, window=window, groups=G, t_max=t_max,
            q_offset=q_offset)
        semantics = ("parallel", "parallel", "arbitrary")
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, Sq // bq),
            in_specs=[
                pl.BlockSpec((1, G, bq, D), lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i, *_: (b, 0, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i, *_: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, G, bq, D), lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, G, bq, 1), lambda b, i, *_: (b, 0, i, 0)),
            ],
        )
        kernel = functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
            block_k=bk, window=window, groups=G, q_offset=q_offset)
        semantics = ("parallel", "arbitrary")
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, G, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((bh, G, Sq, 1), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
    )(jnp.asarray(kv_idx), jnp.asarray(kv_cnt), qr, kr, vr)
    out = out.reshape(B, Hq, Sq, D)
    return out, (q, k, v, out, lse.reshape(B, Hq, Sq))


def _splash_bwd(block_mask, causal, sm_scale, block_q, block_k, window,
                q_offset, res, do, *, delta=None):
    # delta: optional precomputed sum(dO*O, -1) as (B,H,Sq) f32 — ring
    # attention calls this once per ring step with the same global
    # (out, dO), so the reduction hoists out of the ring loop
    # (mirrors flash_attention._fa_bwd's delta kwarg)
    q, k, v, out, lse = res
    sm_scale, bq, bk, G, streamed = _resolve(q, k, block_mask, sm_scale,
                                             block_q, block_k)
    kv_idx, kv_cnt = _pattern_tables(block_mask)
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    Sk = k.shape[2]
    bh = B * Hkv
    qr = q.reshape(bh, G, Sq, D)
    kr = k.reshape(bh, Sk, D)
    vr = v.reshape(bh, Sk, D)
    dor = do.reshape(bh, G, Sq, D)
    lser = lse.reshape(bh, G, Sq, 1)
    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
    delta = delta.reshape(bh, G, Sq, 1)

    if streamed:
        t_max = kv_idx.shape[1]
        dq_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, Sq // bq, t_max),
            in_specs=[
                pl.BlockSpec((1, G, bq, D),
                             lambda b, i, t, idx, cnt: (b, 0, i, 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, i, t, idx, cnt: (b, idx[i, t], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, i, t, idx, cnt: (b, idx[i, t], 0)),
                pl.BlockSpec((1, G, bq, D),
                             lambda b, i, t, idx, cnt: (b, 0, i, 0)),
                pl.BlockSpec((1, G, bq, 1),
                             lambda b, i, t, idx, cnt: (b, 0, i, 0)),
                pl.BlockSpec((1, G, bq, 1),
                             lambda b, i, t, idx, cnt: (b, 0, i, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, G, bq, D), lambda b, i, t, idx, cnt: (b, 0, i, 0)),
            scratch_shapes=[pltpu.VMEM((G * bq, D), jnp.float32)],
        )
        dq_kernel = functools.partial(
            _bwd_dq_kernel_stream, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, window=window, groups=G, t_max=t_max,
            q_offset=q_offset)
        dq_semantics = ("parallel", "parallel", "arbitrary")
    else:
        dq_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, Sq // bq),
            in_specs=[
                pl.BlockSpec((1, G, bq, D), lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i, *_: (b, 0, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i, *_: (b, 0, 0)),
                pl.BlockSpec((1, G, bq, D), lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, G, bq, 1), lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, G, bq, 1), lambda b, i, *_: (b, 0, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, G, bq, D),
                                   lambda b, i, *_: (b, 0, i, 0)),
        )
        dq_kernel = functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
            block_k=bk, window=window, groups=G, q_offset=q_offset)
        dq_semantics = ("parallel", "arbitrary")
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((bh, G, Sq, D), q.dtype),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dq_semantics),
    )(jnp.asarray(kv_idx), jnp.asarray(kv_cnt), qr, kr, vr, dor, lser,
      delta)

    num_q = Sq // bq
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, Sk // bk, num_q),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), lambda b, j, i, *_: (b, 0, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i, *_: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i, *_: (b, j, 0)),
            pl.BlockSpec((1, G, bq, D), lambda b, j, i, *_: (b, 0, i, 0)),
            pl.BlockSpec((1, G, bq, 1), lambda b, j, i, *_: (b, 0, i, 0)),
            pl.BlockSpec((1, G, bq, 1), lambda b, j, i, *_: (b, 0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i, *_: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i, *_: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
    )
    bm_i32 = jnp.asarray(np.asarray(block_mask, np.int32))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          window=window, groups=G, num_q=num_q,
                          q_offset=q_offset),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((bh, Sk, D), v.dtype),
        ],
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(bm_i32, qr, kr, vr, dor, lser, delta)

    return (dq.reshape(B, Hq, Sq, D), dk.reshape(B, Hkv, Sk, D),
            dv.reshape(B, Hkv, Sk, D))


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def splash_attention(q, k, v, block_mask, causal=False, sm_scale=None,
                     block_q=None, block_k=None, window=None,
                     q_offset=0):
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) with Hq a multiple of Hkv
    (MHA is Hq == Hkv; GQA/MQA fold the group into the kernel's M dim).
    block_mask: (Sq//block_q, Sk//block_k) bool numpy array (a static
    pattern — it defines the compiled kernel). Equivalent to dense
    attention with masked-out blocks at -inf, but skipped rather than
    computed."""
    out, _ = _splash_fwd(q, k, v, block_mask, causal, sm_scale, block_q,
                         block_k, window, q_offset)
    return out


splash_attention.defvjp(_splash_fwd, _splash_bwd)

# GQA entry point: same kernel family; kept as a named alias so call
# sites read as grouped (and for parity with flash_attention_gqa.py)
grouped_splash_attention = splash_attention
