"""Grouped-query (GQA/MQA) flash attention Pallas TPU kernel.

The reference has no GQA-aware fused attention (fused_attention_op.cu
predates GQA); the portable fallback repeats K/V across query groups
(jnp.repeat) which multiplies K/V HBM traffic and VMEM residency by
n_groups. This kernel keeps K/V at their true head count: each grid
program processes ALL G query heads that share one kv head, flattening
the group into the matmul M dimension — the MXU sees a (G*bq, d)@(d, bk)
score matmul (bigger, not more, calls) and K/V are fetched once per kv
head instead of once per query head.

Layouts: q (B, G*Hkv, S, D) with head order grouped by kv head
(h = kv_head * G + g — jnp.repeat convention); k/v (B, Hkv, S, D).
Same resident-KV fori-walk + exp2-domain design as flash_attention.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LN2, LOG2E, NEG_INF, _pick_block
from .lowering import interpret as _interpret


# f32-element budget for ONE (G*block_q, block_k) score/probability buffer
# (2 MB each; the kernel holds score + p + acc + resident K/V in VMEM).
_SCORE_ELEMS = 512 * 1024
# Row cap for the G*block_q dimension: q/q2/acc/out buffers are rows-tall
# regardless of block_k, so the score budget alone can't bound them.
# Measured on v5e: rows=4096 (MQA G=32, bq=128, bk=128) exceeds the 16M
# scoped-vmem limit by 912K even with the score budget satisfied.
_MAX_ROWS = 2048
# Resident K/V grows with Sk (the long8k chip failure mode of the MHA
# kernels); the GQA temp coefficient is bounded by the round-3 chip
# evidence — rows=1024 x bk=512 at S=2048 COMPILED (2M resident +
# C*rows*bk*4 <= 16M gives C <= 6.8). 6 is the provisional value;
# tools/long8k_vmem_repro.py's GQA section re-measures the frontier.
_GQA_TEMP_COEF = 6
_GQA_VMEM = 16 * 2**20 - 2**20  # scoped limit less margin


def _gqa_fits(rows, bk, Sk, D, itemsize):
    resident = 2 * 2 * Sk * D * itemsize  # K+V per kv head, double-buffered
    return resident + _GQA_TEMP_COEF * rows * bk * 4 <= _GQA_VMEM


class ResidentOverflowError(ValueError):
    """No reachable block pair fits resident K/V in scoped VMEM —
    grouped_flash_attention auto-delegates to coarse-tile splash
    streaming on this, other ValueErrors (bad shapes etc.) propagate."""


def _gqa_resolve_blocks(Sq, Sk, G, block_q, block_k, D=128, itemsize=2):
    """Group-aware block pick: score/probability buffers are (G*block_q,
    block_k) f32, so the JOINT product G*block_q*block_k is bounded — a
    per-axis cap alone lets rows grow unboundedly with G (MQA G=32 at the
    512 default block_k would put ~16 MB of f32 score buffers in VMEM and
    fail Mosaic compilation). Auto-picked blocks shrink (block_k first,
    then block_q down to the 8-sublane floor) until the product fits;
    user-pinned blocks are honored as given."""
    user_q, user_k = block_q is not None, block_k is not None
    if block_q is None:
        cap = max(128, 1024 // G)
        for cand in (512, 256, 128):
            if cand <= cap and Sq % cand == 0:
                block_q = cand
                break
        else:
            block_q = min(_pick_block(Sq), cap)
    # plain per-axis pick only: the group-aware caps below own the VMEM
    # bound for these kernels (the MHA resolver's resident-fit model is
    # calibrated for the non-grouped kernels and a hardcoded D/itemsize)
    bq = block_q or _pick_block(Sq)
    bk = block_k or _pick_block(Sk)
    # halving preserves divisibility (bk | Sk implies bk/2 | Sk)
    while G * bq > _MAX_ROWS and not user_q and bq > 8 \
            and (bq // 2) % 8 == 0:
        bq //= 2
    while G * bq * bk > _SCORE_ELEMS and not user_k and bk > 128:
        bk //= 2
    while G * bq * bk > _SCORE_ELEMS and not user_q and bq > 8 \
            and (bq // 2) % 8 == 0:
        bq //= 2
    # long-Sk resident term (auto blocks only): shrink until the resident
    # K/V plus temp buffers fit scoped VMEM
    while not _gqa_fits(G * bq, bk, Sk, D, itemsize) and not user_k \
            and bk > 128:
        bk //= 2
    while not _gqa_fits(G * bq, bk, Sk, D, itemsize) and not user_q \
            and bq > 8 and (bq // 2) % 8 == 0:
        bq //= 2
    if not (user_q or user_k) and not _gqa_fits(G * bq, bk, Sk, D,
                                                itemsize):
        # either resident K/V alone exceeds scoped VMEM (no block choice
        # can compile) or the shrink loops stalled on divisibility /
        # sublane alignment short of a fitting pair — both end in an
        # opaque Mosaic compile failure, so raise the typed error here.
        # grouped_flash_attention's public entry catches it and
        # delegates to the coarse-tile K/V-streaming splash kernels;
        # direct core callers see the message below.
        raise ResidentOverflowError(
            f"grouped_flash_attention: resident K/V at Sk={Sk} "
            f"(D={D}, {itemsize}B) cannot fit the 16M scoped-VMEM "
            f"budget at any block size; shard the sequence (ring "
            f"attention / 'sep' axis) or use splash/flash streaming "
            f"for single-chip sequences this long")
    return bq, bk


def _pos_grids(rows, block_k, qi, kj, block_q):
    """(q_pos, k_pos) grids for a (G*bq, bk) score block: row r belongs to
    query position qi*bq + (r % bq) — the group index g = r // bq shares
    positions across the G heads."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
    q_pos = qi * block_q + jax.lax.rem(r, block_q)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_k), 1)
    return q_pos, k_pos


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_q, block_k, kv_len, groups):
    qi = pl.program_id(1)
    G = groups
    D = q_ref.shape[-1]
    rows = G * block_q
    q = q_ref[0].reshape(rows, D)  # (G, bq, D) -> (G*bq, D)
    q2 = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)

    m = jnp.full((rows,), NEG_INF, jnp.float32)
    l = jnp.zeros((rows,), jnp.float32)
    acc = jnp.zeros((rows, D), jnp.float32)

    num_kv = kv_len // block_k
    if causal:
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
            q_pos, k_pos = _pos_grids(rows, block_k, qi, kj, block_q)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
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
    o_ref[0] = (acc / l_safe[:, None]).reshape(G, block_q, D).astype(
        o_ref.dtype)
    lse_ref[0] = (LN2 * m + jnp.log(l_safe)).reshape(G, block_q, 1).astype(
        jnp.float32)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, sm_scale, causal, block_q, block_k, kv_len, groups):
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
            q_pos, k_pos = _pos_grids(rows, block_k, qi, kj, block_q)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp2(s - lse2[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + jax.lax.dot_general(ds.astype(k.dtype), k,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_live, body, dq)
    dq_ref[0] = dq.reshape(G, block_q, D).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    block_q, block_k, num_q, groups):
    """Unlike the MHA kernel (full q/do resident in VMEM — fine at
    rows=block_q), the grouped q/do blocks are G-times taller, so the q
    walk streams through the innermost GRID dimension with dk/dv in VMEM
    scratch; Mosaic double-buffers the next q/do block DMA."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    G = groups
    D = q_ref.shape[-1]
    rows = G * block_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    live = (qi * block_q + block_q - 1 >= kj * block_k) if causal else True

    @pl.when(live)
    def _compute():
        k = k_ref[0]  # (block_k, D)
        v = v_ref[0]
        k2 = (k.astype(jnp.float32) * (sm_scale * LOG2E)).astype(k.dtype)
        q = q_ref[0].reshape(rows, D)
        do = do_ref[0].reshape(rows, D)
        lse2 = lse_ref[0].reshape(rows) * LOG2E
        delta = delta_ref[0].reshape(rows)
        s = jax.lax.dot_general(q, k2, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos, k_pos = _pos_grids(rows, block_k, qi, kj, block_q)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp2(s - lse2[:, None])  # (G*bq, bk)
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


def _shapes(q, k):
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hkv}")
    return B, Hq, Hkv, Hq // Hkv, Sq, D


def _gqa_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k):
    B, Hq, Hkv, G, Sq, D = _shapes(q, k)
    Sk = k.shape[2]
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"grouped_flash_attention blocks ({block_q},{block_k}) must "
            f"divide seq lens ({Sq},{Sk})")
    bh = B * Hkv
    # head order: h = kv*G + g (jnp.repeat convention)
    qr = q.reshape(bh, G, Sq, D)
    kr = k.reshape(bh, Sk, D)
    vr = v.reshape(bh, Sk, D)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, kv_len=Sk,
                               groups=G)
    out, lse = functools.partial(pl.pallas_call, interpret=_interpret())(
        kernel,
        grid=(bh, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, G, block_q, D), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, G, block_q, D), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, block_q, 1), lambda b, i: (b, 0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, G, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((bh, G, Sq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qr, kr, vr)
    return (out.reshape(B, Hq, Sq, D),
            lse.reshape(B, Hq, Sq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _grouped_flash_core(q, k, v, causal=False, sm_scale=None,
                        block_q=None, block_k=None):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    G = q.shape[1] // max(1, k.shape[1])
    block_q, block_k = _gqa_resolve_blocks(q.shape[2], k.shape[2], G,
                                           block_q, block_k,
                                           q.shape[-1], q.dtype.itemsize)
    out, _ = _gqa_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k)
    return out


def grouped_flash_attention(q, k, v, causal=False, sm_scale=None,
                            block_q=None, block_k=None):
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) with Hq = G*Hkv. Equivalent to
    flash_attention over jnp.repeat(k/v, G, axis=1) without the repeat.

    Past the resident-K/V VMEM frontier (auto blocks only) the call
    delegates to the K/V-STREAMING splash kernels at the true kv-head
    count with coarse (pick_splash_blocks) tiles — so GQA long-context
    works on one chip instead of failing to compile. Block size decides
    this race: at the round-3 128-tiles splash lost to repeat+flash
    (46.2 vs 34.0 ms at S=16384/G=4), at 512-tiles it wins while moving
    G x less K/V (28.2 vs 34.0 ms; 18.6 vs 20.0 at S=8192 —
    tools/gqa_xlong_bench.py, 2026-08-01)."""
    G = q.shape[1] // max(1, k.shape[1])
    if block_q is None and block_k is None:
        try:
            bq, bk = _gqa_resolve_blocks(q.shape[2], k.shape[2], G, None,
                                         None, q.shape[-1],
                                         q.dtype.itemsize)
            # pass the resolved blocks through — the core (and its vjp)
            # would otherwise re-run the identical resolution
            return _grouped_flash_core(q, k, v, causal, sm_scale, bq, bk)
        except ResidentOverflowError:
            import numpy as _np

            from .splash_attention import (pick_splash_blocks,
                                           splash_attention)
            bq, bk = pick_splash_blocks(q.shape[2], k.shape[2], G)
            nq, nk = q.shape[2] // bq, k.shape[2] // bk
            # full causal = lower-triangular block mask (the token-exact
            # triangle applies in-kernel); non-causal or mismatched
            # tilings use the dense mask — still streamed, just no
            # block skipping
            if causal and nq == nk:
                bm = _np.tril(_np.ones((nq, nk), bool))
            else:
                bm = _np.ones((nq, nk), bool)
            return splash_attention(q, k, v, bm, causal, sm_scale, bq, bk)
    return _grouped_flash_core(q, k, v, causal, sm_scale, block_q,
                               block_k)


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    G = q.shape[1] // max(1, k.shape[1])
    block_q, block_k = _gqa_resolve_blocks(q.shape[2], k.shape[2], G,
                                           block_q, block_k,
                                           q.shape[-1], q.dtype.itemsize)
    out, lse = _gqa_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, res, do):
    q, k, v, out, lse = res
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    G0 = q.shape[1] // max(1, k.shape[1])
    block_q, block_k = _gqa_resolve_blocks(q.shape[2], k.shape[2], G0,
                                           block_q, block_k,
                                           q.shape[-1], q.dtype.itemsize)
    B, Hq, Hkv, G, Sq, D = _shapes(q, k)
    Sk = k.shape[2]
    bh = B * Hkv
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, G, Sq, 1)
    qr = q.reshape(bh, G, Sq, D)
    kr = k.reshape(bh, Sk, D)
    vr = v.reshape(bh, Sk, D)
    dor = do.reshape(bh, G, Sq, D)
    lser = lse.reshape(bh, G, Sq, 1)

    dq = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=Sk,
                          groups=G),
        grid=(bh, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, G, block_q, D), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, G, block_q, D), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, block_q, 1), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, block_q, 1), lambda b, i: (b, 0, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, block_q, D), lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, G, Sq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qr, kr, vr, dor, lser, delta)

    num_q = Sq // block_q
    dk, dv = functools.partial(pl.pallas_call, interpret=_interpret())(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=num_q,
                          groups=G),
        grid=(bh, Sk // block_k, num_q),
        in_specs=[
            pl.BlockSpec((1, G, block_q, D), lambda b, j, i: (b, 0, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, G, block_q, D), lambda b, j, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, block_q, 1), lambda b, j, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, block_q, 1), lambda b, j, i: (b, 0, i, 0)),
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

    return (dq.reshape(B, Hq, Sq, D), dk.reshape(B, Hkv, Sk, D),
            dv.reshape(B, Hkv, Sk, D))


_grouped_flash_core.defvjp(_fa_fwd, _fa_bwd)
