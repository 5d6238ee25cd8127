"""Flash attention Pallas TPU kernel.

TPU-native replacement for the reference's fused CUDA attention
(paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h — which
materializes the full O(s^2) score matrix). This kernel implements the
online-softmax streaming algorithm: scores never leave VMEM, HBM traffic is
O(s*d), and the MXU sees back-to-back (bq x d)@(d x bk) and (bq x bk)@(bk x d)
matmuls.

Design notes (measured on v5e at B=8, H=12, S=2048, D=128, bf16):
- K/V stay RESIDENT in VMEM for the whole kv walk (full-seq BlockSpec) and
  the walk is a fori_loop — measured faster (337ms train step) than
  streaming kv blocks through an innermost grid dimension with scratch
  accumulators (366ms): resident K/V costs zero DMA inside the loop. The
  resident footprint grows with S, and the chip showed the 512x512-block
  kernels overflow the 16M scoped-vmem budget at S=8192 (21M) — so
  `_resolve_blocks` runs a fit model that shrinks blocks as S grows and
  switches to the grid-streamed kernel variants (O(block) VMEM at any S)
  past the resident frontier. Multi-chip long context should still shard
  over the 'sep' mesh axis (ring attention); streaming is the single-chip
  escape hatch.
- Matmul operands stay in their storage dtype (bf16 runs the MXU at full
  rate; f32 at half), accumulating in f32 via preferred_element_type.
- Softmax runs in the exp2 domain with sm_scale*log2e folded into q (or k)
  once per kernel invocation; lse is stored in the natural-log domain.
- Masking every live block measured faster than lax.cond diagonal-only
  masking (cond defeats Mosaic's loop pipelining).

Layout: (batch, heads, seq, head_dim). Forward saves per-row logsumexp for
the backward pass; backward recomputes block scores (flash-style) to form
dQ/dK/dV without the s^2 buffer.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import interpret as _interpret

DEFAULT_BLOCK_Q = None  # auto: largest of 512/256/128 dividing the seq
DEFAULT_BLOCK_K = None
NEG_INF = -1e30
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


def flash_eligible(seq_len: int, head_dim: int, dtype) -> bool:
    """The one shape/dtype gate for every flash-attention entry point
    (model layers, Ulysses, ring — they must never diverge): kernel
    supports 128-multiple sequences >= 256 and MXU-tiled head dims,
    under the FLAGS_use_flash_attention switch."""
    from ...core import flags as _flags
    return (bool(_flags.get_flag("use_flash_attention"))
            and seq_len >= 256 and seq_len % 128 == 0
            and head_dim in (64, 128, 256)
            and dtype in (jnp.float32, jnp.bfloat16))


def _pick_block(seq_len: int) -> int:
    # Measured on v5e at (B8,H12,S2048,D128) fwd+bwd: 512 blocks run 11.6ms
    # vs 18.4ms at the MXU-tile minimum of 128 — bigger blocks amortize the
    # grid/loop overhead and keep the MXU busy; 1024 is no faster and eats
    # VMEM headroom.
    for cand in (512, 256, 128):
        if seq_len % cand == 0:
            return cand
    # Correctness fallback for non-128-multiple sequences: the block MUST
    # divide seq_len (grid steps would otherwise skip output rows / kv
    # positions) and stay sublane-aligned for Mosaic (multiple of 8) —
    # including seq_len <= 128, where returning seq_len verbatim would hand
    # Mosaic an unaligned sublane count (e.g. S=100).
    for cand in range(min(128, seq_len), 7, -1):
        if seq_len % cand == 0 and cand % 8 == 0:
            return cand
    raise ValueError(
        f"flash_attention: no sublane-aligned block divides seq_len="
        f"{seq_len}; pad the sequence to a multiple of 128")


# Scoped-VMEM fit model, calibrated on chip (v5e, 16M scoped limit).
# Chip facts driving the coefficients (tools/long8k_vmem_repro.py,
# 2026-08-01 window, D=128 bf16):
#   fwd  resident 512x512 @ S=8192  COMPILES      -> fwd temps <= ~7M
#   f+b  resident 512x512 @ S=8192  FAILS @17.00M -> ~16M + ~1M
#   f+b  resident 256x256 @ S=8192  FAILS @16.50M -> ~16M + ~0.5M
#   fwd  resident 256     @ S=16384 FAILS @16.50M -> resident alone 16M
#   f+b  streamed 512x512 @ S=8192  COMPILES
# The backward failures sit at ~2x the bf16 resident bytes plus a small
# block term: the dk/dv kernel's full-length operands (Q, dO) are ALSO
# materialized as f32 compute copies (2 x Sres x D x 4B), which the
# round-3 model missed — its coef-13 block term was calibrated against
# what was actually this S-scaled backward failure. Forward temps are
# block-sized only (s/p/exp2/acc/iota ~ a few (bq,bk) f32 buffers).
_SCOPED_VMEM = 16 * 2**20
_TEMP_COEF = 6        # fwd/dq: (bq,bk) f32-buffer equivalents, safe side
_BWD_TEMP_COEF = 2    # dk/dv block temps (chip: ~1-2 buffer equivalents)
_FIT_MARGIN = 2**20


def _resident_fits(bq, bk, Sres, D, itemsize=2, bwd=False) -> bool:
    # Sres: the longest sequence any resident-mode kernel holds full-length
    # in VMEM — Sk for the forward/dq kernels (K+V resident), and
    # max(Sq, Sk) on the backward path (the dk/dv kernel keeps Q+dO
    # resident at Sq)
    resident = 2 * 2 * Sres * D * itemsize  # 2 tensors, double-buffered
    if bwd:
        # full-length f32 compute copies of the resident pair (chip-
        # calibrated: the 17.00M/16.50M failures above)
        resident += 2 * Sres * D * 4
        temps = _BWD_TEMP_COEF * bq * bk * 4
    else:
        temps = _TEMP_COEF * bq * bk * 4
    return resident + temps + _FIT_MARGIN <= _SCOPED_VMEM


def _stream_fits(bq, bk, D, itemsize=2) -> bool:
    # streamed path: no resident K/V; scratch acc/m/l + double-buffered
    # q/k/v/o block streams + the same f32 temporaries
    scratch = bq * D * 4 + 2 * bq * 4
    streams = 2 * 2 * (2 * bq + 2 * bk) * D * itemsize
    temps = _TEMP_COEF * bq * bk * 4
    return scratch + streams + temps + _FIT_MARGIN <= _SCOPED_VMEM


# Canonical block-pair preference, best-first from the v5e fwd+bwd
# measurements at S=2048/D=128 (512x512 = 11.6ms, 256x512 = 13.6ms,
# 256x256 = 15.1ms, 128x128 = 18.4ms). autotune._FA_BLOCKS derives from
# this list so the tuner and the resolver can never disagree.
MEASURED_BLOCK_ORDER = ((512, 512), (256, 512), (512, 256), (256, 256),
                        (128, 512), (512, 128), (128, 128))
_PAIR_ORDER = MEASURED_BLOCK_ORDER[:-1] + ((128, 256), (256, 128),
                                           (128, 128))
# Backward-kernel preference, from the on-chip 3x3 sweep at S=2048/D=128
# (tools/flash_bwd_sweep.py, 2026-08-01): 1024x512 measured fastest
# (13.51 ms/fwd+bwd vs 13.68 at 512x512); taller dq blocks amortize the
# full-length kv walk. Tried first when S divides; everything after
# falls back to the shared order.
_BWD_PAIR_ORDER = ((1024, 512),) + _PAIR_ORDER


def _resolve_blocks(Sq, Sk, block_q, block_k, D=128, itemsize=2,
                    stream=None, bwd=False):
    """Pick (block_q, block_k, streamed). Explicit blocks are honored
    verbatim (sweeps/experiments own the consequences); auto-pick walks
    the measured-fast pairs largest-first and returns the first that
    fits the scoped-VMEM model with K/V resident, else falls back to the
    grid-streamed kernels (unbounded S at O(block) VMEM). ``stream``
    True/False forces the mode; None decides from the fit model.
    ``bwd`` widens the resident term to max(Sq, Sk): the dk/dv kernel
    keeps Q+dO resident at Sq where the forward keeps K+V at Sk."""
    Sres = max(Sq, Sk) if bwd else Sk
    if block_q and block_k:
        if stream is None:
            stream = not _resident_fits(block_q, block_k, Sres, D,
                                        itemsize, bwd)
        return block_q, block_k, stream
    seen = set()
    cands = []
    for bq, bk in (_BWD_PAIR_ORDER if bwd else _PAIR_ORDER):
        cq, ck = block_q or bq, block_k or bk
        if (cq, ck) in seen or Sq % cq or Sk % ck:
            continue
        seen.add((cq, ck))
        cands.append((cq, ck))
    if stream:
        for cq, ck in cands:
            if _stream_fits(cq, ck, D, itemsize):
                return cq, ck, True
        # forced streaming with no fitting 128-multiple pair: divisor
        # blocks are <=128 and always stream-fit
        return (block_q or _pick_block(Sq), block_k or _pick_block(Sk),
                True)
    for cq, ck in cands:
        if _resident_fits(cq, ck, Sres, D, itemsize, bwd):
            return cq, ck, False
    if stream is None:
        for cq, ck in cands:
            if _stream_fits(cq, ck, D, itemsize):
                return cq, ck, True
    # no 128-multiple pair divides S: divisor-search blocks are <=128.
    # They may still not make RESIDENT K/V fit (odd does not imply
    # tiny) — honor the fit model and stream when it says no, unless
    # the caller forced resident and owns the compile outcome.
    cq = block_q or _pick_block(Sq)
    ck = block_k or _pick_block(Sk)
    if stream is False and cands:
        return cands[0][0], cands[0][1], False
    if stream is False:
        return cq, ck, False
    return cq, ck, not _resident_fits(cq, ck, Sres, D, itemsize, bwd)


def _mask_causal(s, qi, kj, block_q, block_k):
    """NEG_INF-mask score entries above the causal diagonal for the
    (qi, kj) block pair — shared by all six kernel variants."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_q, block_k, kv_len):
    qi = pl.program_id(1)
    q = q_ref[0]  # (block_q, d)
    # fold sm_scale*log2e into q once: scores leave the MXU already in the
    # exp2 domain with no per-block rescale
    q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)

    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)

    num_kv = kv_len // block_k
    if causal:
        # only blocks at or before the diagonal contribute
        num_live = jnp.minimum(((qi + 1) * block_q + block_k - 1) // block_k,
                               num_kv)
    else:
        num_live = num_kv

    def body(kj, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(kj * block_k, block_k)]
        v = v_ref[0, pl.dslice(kj * block_k, block_k)]
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _mask_causal(s, qi, kj, block_q, block_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_live, body, (m, l, acc))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse is saved in the natural-log domain (bwd converts back)
    lse_ref[0] = (LN2 * m + jnp.log(l_safe))[:, None].astype(jnp.float32)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, sm_scale, causal, block_q, block_k, kv_len):
    qi = pl.program_id(1)
    q = q_ref[0]
    q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
    do = do_ref[0]
    lse2 = lse_ref[0, :, 0] * LOG2E  # exp2-domain logsumexp
    delta = delta_ref[0, :, 0]
    dq = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    num_kv = kv_len // block_k
    if causal:
        num_live = jnp.minimum(((qi + 1) * block_q + block_k - 1) // block_k,
                               num_kv)
    else:
        num_live = num_kv

    def body(kj, dq):
        k = k_ref[0, pl.dslice(kj * block_k, block_k)]
        v = v_ref[0, pl.dslice(kj * block_k, block_k)]
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _mask_causal(s, qi, kj, block_q, block_k)
        p = jnp.exp2(s - lse2[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + jax.lax.dot_general(ds.astype(k.dtype), k,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_live, body, dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale, causal, block_q, block_k,
                    q_len):
    kj = pl.program_id(1)
    k = k_ref[0]  # (block_k, d)
    v = v_ref[0]
    # fold sm_scale*log2e into k once (dk accumulation uses unscaled q)
    k2 = (k.astype(jnp.float32) * (sm_scale * LOG2E)).astype(k.dtype)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    num_q = q_len // block_q
    if causal:
        first_live = (kj * block_k) // block_q
    else:
        first_live = 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(qi * block_q, block_q)]
        do = do_ref[0, pl.dslice(qi * block_q, block_q)]
        lse2 = lse_ref[0, pl.dslice(qi * block_q, block_q), 0] * LOG2E
        delta = delta_ref[0, pl.dslice(qi * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k2, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _mask_causal(s, qi, kj, block_q, block_k)
        p = jnp.exp2(s - lse2[:, None])  # (bq, bk)
        dv_new = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_new = dk + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(first_live, num_q, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---- grid-streamed variants (long sequences) ----
#
# Beyond the resident-KV frontier (~14k at D=128: double-buffered K+V
# alone approach the 16M scoped-vmem limit) K/V blocks stream through an
# innermost grid dimension and the online-softmax state (m, l, acc)
# lives in VMEM scratch across grid steps — O(block) VMEM at any S.
# Measured 8% slower than resident at S=2048 (PERF.md round-2
# ablations), so the resolver only picks streaming when resident can't
# compile. Same math as the resident kernels; dead causal blocks skip
# compute via pl.when (the DMA still runs — acceptable for a fallback
# whose alternative is failing to compile).


def _fwd_kernel_stream(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                       acc_scr, *, sm_scale, causal, block_q, block_k,
                       num_kv):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _compute():
        q = q_ref[0]
        q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _mask_causal(s, qi, kj, block_q, block_k)
        m = m_scr[...][:, 0]
        l = l_scr[...][:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]
        l_scr[...] = l_new[:, None]

    if causal:
        pl.when((qi + 1) * block_q > kj * block_k)(_compute)
    else:
        _compute()

    @pl.when(kj == num_kv - 1)
    def _flush():
        l = l_scr[...][:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (LN2 * m_scr[...][:, 0] + jnp.log(l_safe))[
            :, None].astype(jnp.float32)


def _bwd_dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_scr, *, sm_scale, causal, block_q,
                          block_k, num_kv):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _compute():
        q = q_ref[0]
        q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
        do = do_ref[0]
        lse2 = lse_ref[0, :, 0] * LOG2E
        delta = delta_ref[0, :, 0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _mask_causal(s, qi, kj, block_q, block_k)
        p = jnp.exp2(s - lse2[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((qi + 1) * block_q > kj * block_k)(_compute)
    else:
        _compute()

    @pl.when(kj == num_kv - 1)
    def _flush():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                           sm_scale, causal, block_q, block_k, num_q):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        k2 = (k.astype(jnp.float32) * (sm_scale * LOG2E)).astype(k.dtype)
        q = q_ref[0]
        do = do_ref[0]
        lse2 = lse_ref[0, :, 0] * LOG2E
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(q, k2, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _mask_causal(s, qi, kj, block_q, block_k)
        p = jnp.exp2(s - lse2[:, None])
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((qi + 1) * block_q > kj * block_k)(_compute)
    else:
        _compute()

    @pl.when(qi == num_q - 1)
    def _flush():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_fwd_stream(q, k, v, causal, sm_scale, block_q, block_k):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"flash_attention blocks ({block_q},{block_k}) must divide "
            f"seq lens ({Sq},{Sk}); pass block_q/block_k=None to auto-pick")
    bh = B * H
    qr = q.reshape(bh, Sq, D)
    kr = k.reshape(bh, Sk, D)
    vr = v.reshape(bh, Sk, D)
    num_kv = Sk // block_k
    out, lse = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_fwd_kernel_stream, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          num_kv=num_kv),
        grid=(bh, Sq // block_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, t: (b, t, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, t: (b, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, t: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((bh, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, D), lse[..., 0].reshape(B, H, Sq)


def _flash_bwd_stream(q, k, v, out, lse, do, causal, sm_scale, block_q,
                      block_k):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"flash_attention backward blocks ({block_q},{block_k}) must "
            f"divide seq lens ({Sq},{Sk})")
    bh = B * H
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, Sq, 1)
    qr = q.reshape(bh, Sq, D)
    kr = k.reshape(bh, Sk, D)
    vr = v.reshape(bh, Sk, D)
    dor = do.reshape(bh, Sq, D)
    lser = lse.reshape(bh, Sq, 1)
    num_kv = Sk // block_k
    num_q = Sq // block_q

    dq = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_bwd_dq_kernel_stream, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          num_kv=num_kv),
        grid=(bh, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, t: (b, t, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, t: (b, t, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, t: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, t: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr, dor, lser, delta)

    dk, dv = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_bwd_dkv_kernel_stream, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          num_q=num_q),
        grid=(bh, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((bh, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr, dor, lser, delta)

    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"flash_attention blocks ({block_q},{block_k}) must divide "
            f"seq lens ({Sq},{Sk}); pass block_q/block_k=None to auto-pick")
    bh = B * H
    qr = q.reshape(bh, Sq, D)
    kr = k.reshape(bh, Sk, D)
    vr = v.reshape(bh, Sk, D)
    grid = (bh, Sq // block_q)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, kv_len=Sk)
    out, lse = functools.partial(pl.pallas_call, interpret=_interpret())(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((bh, Sq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, D), lse[..., 0].reshape(B, H, Sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, causal=False, sm_scale=None,
                block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                bwd_block_q=None, bwd_block_k=None, stream=None):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k, streamed = _resolve_blocks(
        q.shape[2], k.shape[2], block_q, block_k, q.shape[-1],
        q.dtype.itemsize, stream)
    fwd = _flash_fwd_stream if streamed else _flash_fwd
    out, _ = fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out


# When AUTO resolution lands in streamed mode for a causal self-attention,
# optionally route through the splash kernels with a lower-triangular
# block mask instead of the hand-written streamed variants. The theory
# (splash's prefetched kv_idx tables elide dead-block DMA, ~2x saved in
# the fwd/dQ walks) LOST on chip: at S=16384 the plain streamed kernels
# measure 48.3 ms/fwd+bwd vs 97.4 ms through splash-tril
# (tools/seq_attn_bench.py, 2026-08-01) — splash's per-block overhead
# (128/256 tiles, table machinery) outweighs the halved DMA, so the
# route is OFF. Kept as a switch so future splash block-size tuning can
# re-measure against the same yardstick.
CAUSAL_STREAM_VIA_SPLASH = False


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    bwd_block_q=None, bwd_block_k=None, stream=None):
    """q/k/v: (batch, heads, seq, head_dim). Returns same shape as q.

    ``bwd_block_q``/``bwd_block_k`` tile the two backward kernels
    independently of the forward (None = same as forward). The backward
    walks the opposite operand full-length per block (dq walks K/V,
    dk/dv walks Q), so its VMEM/pipelining optimum need not match the
    forward's — tools/flash_bwd_sweep.py measures the grid on chip.

    ``stream`` selects the K/V-streaming kernels (None = automatic:
    resident K/V while the scoped-VMEM fit model allows it, streaming
    beyond — long sequences where double-buffered resident K/V would
    blow the 16M scoped-vmem limit that interpret-mode tests can't see).
    The forward and backward resolve independently: at S=8192 the
    forward stays resident while the backward streams. Auto-streamed
    causal self-attention can route through splash-tril via
    CAUSAL_STREAM_VIA_SPLASH, but that route measured 2x slower on chip
    and is off (see the toggle's comment).
    """
    auto = (block_q is None and block_k is None and bwd_block_q is None
            and bwd_block_k is None and stream is None)
    if auto and causal and CAUSAL_STREAM_VIA_SPLASH \
            and q.shape[2] == k.shape[2] and q.shape[2] % 256 == 0:
        _, _, streamed = _resolve_blocks(
            q.shape[2], k.shape[2], None, None, q.shape[-1],
            q.dtype.itemsize)
        if streamed:
            import numpy as _np

            from .splash_attention import splash_attention
            bq = bk = 256
            n = q.shape[2] // bq
            bm = _np.tril(_np.ones((n, n), bool))
            return splash_attention(q, k, v, bm, True, sm_scale, bq, bk)
    return _flash_core(q, k, v, causal, sm_scale, block_q, block_k,
                       bwd_block_q, bwd_block_k, stream)


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k,
            bwd_block_q, bwd_block_k, stream=None):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k, streamed = _resolve_blocks(
        q.shape[2], k.shape[2], block_q, block_k, q.shape[-1],
        q.dtype.itemsize, stream)
    fwd = _flash_fwd_stream if streamed else _flash_fwd
    out, lse = fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, bwd_block_q, bwd_block_k,
            stream, res, do, *, delta=None):
    # delta: optional precomputed sum(dO*O, -1) as (B,H,Sq) f32 — ring
    # attention calls this once per ring step with the SAME (dO, O), so
    # it hoists the reduction out of its scan instead of recomputing it
    # n times (advisor round-4 finding)
    q, k, v, out, lse = res
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k, streamed = _resolve_blocks(
        q.shape[2], k.shape[2],
        bwd_block_q or block_q, bwd_block_k or block_k, q.shape[-1],
        q.dtype.itemsize, stream, bwd=True)
    # explicit bwd blocks skip the fwd path's validation; a non-dividing
    # block would silently leave output rows unwritten (grid truncation)
    if q.shape[2] % block_q or k.shape[2] % block_k:
        raise ValueError(
            f"flash_attention backward blocks ({block_q}, {block_k}) must "
            f"divide seq lens ({q.shape[2]}, {k.shape[2]})")
    if streamed:
        return _flash_bwd_stream(q, k, v, out, lse, do, causal, sm_scale,
                                 block_q, block_k)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bh = B * H
    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
    delta = delta.reshape(bh, Sq, 1)
    qr = q.reshape(bh, Sq, D)
    kr = k.reshape(bh, Sk, D)
    vr = v.reshape(bh, Sk, D)
    dor = do.reshape(bh, Sq, D)
    lser = lse.reshape(bh, Sq, 1)

    dq = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=Sk),
        grid=(bh, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, Sq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qr, kr, vr, dor, lser, delta)

    dk, dv = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, q_len=Sq),
        grid=(bh, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Sq, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Sq, 1), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((bh, Sk, D), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qr, kr, vr, dor, lser, delta)

    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


_flash_core.defvjp(_fa_fwd, _fa_bwd)
