"""Paged attention over a LATENT cache, in the absorbed form (MLA).

A latent page holds, for each of its ``page_size`` positions, the
compressed key/value ``c`` (``rank`` values, after its norm) and the one
rotary key ``k_rope`` all heads share (``rope`` values, after RoPE):
``rank + rope`` values a token and layer, whatever the number of heads.
The per-head keys and values are never expanded: with
``q_abs_h = q_nope_h . W_UK_h^T`` (``rank`` wide) the score of head ``h``
against a cached position is ``(q_abs_h . c + q_rope_h . k_rope) *
sm_scale`` and its output ``(sum p . c)``, which the caller takes through
``W_UV_h`` afterwards.  So the kernel is multi-query attention with ONE
key/value head of width ``rank + rope`` whose values are the key's first
``rank`` columns.

TPU mapping, as ``paged_attention.py``: the page table rides the scalar
prefetch channel, the grid is ``(row, page)`` over the table's whole width
(pages beyond a row's causal horizon or length skip their compute; their
block index repeats, so their DMA is not issued again), online softmax
with VMEM scratch over the page axis.  All ``heads * chunk`` query rows of
a sequence run against each page in one program, so a page is fetched
once a row whatever the head count.  Decode is ``chunk = 1``.

The pool is ONE operand ``(L, P, page_size, width)`` addressed in place by
``(layer, page)``: the layer index is part of the block index, so no layer
slice of the pool is ever made.  ``width`` is ``rank + rope`` rounded up
to the 128-lane tile (``page_width``: 576 -> 640 at the published widths)
with zeros in the columns past ``rank + rope`` of pages and queries
alike, so ONE matrix product over the whole width gives the score.  The
padding is what keeps the pool where it lies: XLA:TPU stores an array
whose minor dimension is no tile multiple in a layout of its own choosing
(for ``(7, 3617, 64, 576)``: pages minor-most), and every program that
scatters into it or hands it to this kernel then converts the whole pool
to row-major and back, two pool-sized copies a call (seen in the chip
compiler's output for the unpadded pool, PERF.md section 6).

API:
  latent_paged_attention(q, pool, layer, page_tables, seq_lens, starts,
                         chunk, rank, sm_scale)
    q           (B, heads * chunk, width)         row = head * chunk + i
    pool        (L, P, page_size, width)
    page_tables (B, pages_per_seq), seq_lens (B,), starts (B,)
    -> (B, heads * chunk, rank)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF
from .lowering import interpret as _interpret

LANES = 128


def page_width(latent_width: int) -> int:
    """Columns a latent page holds a position: the latent's own, rounded
    up to the lane tile (module docstring)."""
    return -(-latent_width // LANES) * LANES


def _latent_kernel(st_ref, pt_ref, sl_ref, q_ref, kv_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, sm_scale, page_size, chunk,
                   rank):
    """One program per (sequence, page): ``heads * chunk`` query rows
    accumulate online softmax over the page axis.  Row r sits at absolute
    position ``st_ref[b] + (r % chunk)``; masking is causal over absolute
    positions and bounded by the sequence's length."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = sl_ref[b]
    start = st_ref[b]
    base = j * page_size
    live = (base <= start + chunk - 1) & (base < seq_len)

    @pl.when(live)
    def _compute():
        kv = kv_ref[0, 0]                          # (page_size, width)
        c = kv[:, :rank]                           # the values
        s = jax.lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                           # (rows, page_size)
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        row_pos = start + jax.lax.rem(rows, chunk)
        col_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (col_pos <= row_pos) & (col_pos < seq_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


def latent_paged_attention(q, pool, layer: int, page_tables, seq_lens,
                           starts, chunk: int, rank: int, sm_scale: float):
    """Shapes in the module docstring.  ``layer`` is a Python int (the
    layer loop is unrolled): it is part of the page operand's block index.
    Non-differentiable by design — a serving kernel."""
    B, rows, width = q.shape
    L, P, page_size, width_p = pool.shape
    if width != width_p:
        raise ValueError(f"latent width mismatch: q {width} vs pages "
                         f"{width_p}")
    if not 0 < rank < width:
        raise ValueError(f"rank {rank} must lie inside the page's {width} "
                         "columns")
    if rows % chunk:
        raise ValueError(f"{rows} query rows are not heads x chunk "
                         f"({chunk})")
    n_pages = page_tables.shape[1]
    layer = int(layer)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, rows, width),
                         lambda b, j, st, pt, sl: (b, 0, 0)),
            pl.BlockSpec((1, 1, page_size, width),
                         lambda b, j, st, pt, sl: (layer, pt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, rank),
                               lambda b, j, st, pt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, sm_scale=sm_scale,
                          page_size=page_size, chunk=chunk, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, rank), q.dtype),
        interpret=_interpret(),
        name="latent_paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # 16 heads x a lane call of 3 or 4 pages (3072 / 4096 rows)
            # need 18.0 / 21.2 MB of VMEM, over Mosaic's scoped 16
            **({"vmem_limit_bytes": 32 << 20} if rows > 2048 else {})),
    )(jnp.asarray(starts, jnp.int32).reshape(B),
      jnp.asarray(page_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32), q, pool)


def latent_paged_attention_reference(q, pool, layer, page_tables, seq_lens,
                                     starts, chunk, rank, sm_scale):
    """Dense jnp oracle: gathers the rows' pages, masks, exact softmax in
    float32."""
    B, rows, width = q.shape
    page_size = pool.shape[2]
    S = page_tables.shape[1] * page_size
    kv = pool[layer][page_tables].reshape(B, S, width).astype(jnp.float32)
    s = jnp.einsum("brw,bsw->brs", q.astype(jnp.float32), kv) * sm_scale
    row_pos = (jnp.asarray(starts)[:, None]
               + jnp.arange(rows)[None, :] % chunk)
    col = jnp.arange(S)
    mask = ((col[None, None, :] <= row_pos[:, :, None])
            & (col[None, None, :] < jnp.asarray(seq_lens)[:, None, None]))
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.where(mask, jax.nn.softmax(s, -1), 0.0)
    return jnp.einsum("brs,bsc->brc", p, kv[..., :rank]).astype(q.dtype)
