"""Paged KV-cache decode attention — the vLLM-style serving kernel.

The reference's generative path (fused_multi_transformer_op.cu) allocates
a DENSE (B, H, max_len, D) cache per batch slot: memory scales with
max_len whatever the actual lengths, and sequences cannot share a pool.
Paged attention stores K/V in fixed-size PAGES drawn from one global
pool; each sequence holds a page table of indices, so cache memory
tracks the sum of real lengths and slots are reused across requests —
the design that makes continuous batching work.

TPU mapping: one program a sequence, and its work is the pages the
sequence HAS. The page table, the lengths and the layer ride the
scalar-prefetch channel (pltpu.PrefetchScalarGridSpec); the pools stay
in HBM as whole operands, and the kernel walks a row's live pages in
blocks of ``BLOCK_PAGES``: it copies a block's pages itself
(pltpu.make_async_copy, one strided copy a page carrying every kv head)
into one of two VMEM buffers, the next block in flight while this one is
multiplied, and the next sequence's first block started before this
program ends — an idle slot (length 1 on the reserved page 0) costs one
page, a row of 5 pages one block, a row that fills its table
ceil(W / BLOCK_PAGES). (The grid used to carry the table's width as an
axis: 16 x 8 x 66 steps a layer whatever the rows held.) The arithmetic
is an online softmax over the blocks with VMEM scratch carrying
(m, l, acc) per kv head. GQA: all G query heads sharing a kv head run in
one product, so each page is fetched ONCE.

The pools of a whole model are ONE operand each, (L, Hkv, P, page_size,
D), addressed in place by (layer, page): a copy's source is
``pool.at[layer, :, page_tables[b, j]]``, so the layer may be a Python
int or a traced scalar (a scanned layer loop's counter) and no layer
slice of a pool is ever made. A (Hkv, P, page_size, D) pool is the
one-layer case of the same call.

API:
  paged_attention(q, k_pages, v_pages, page_tables, seq_lens, layer=None)
    q           (B, Hq, D)            one decode position per sequence
    k/v_pages   (L, Hkv, P, page_size, D) whole pools + ``layer``, or
                (Hkv, P, page_size, D) one layer's pages (``layer`` None)
    page_tables (B, pages_per_seq)    page ids (padding ids are masked)
    seq_lens    (B,)                  real lengths -> (B, Hq, D)
  paged_prefill_attention(q (B, Hq, C, D), ..., q_start, layer=None)
    the same pools, a C-token query chunk -> (B, Hq, C, D)
  k_scales / v_scales (int8 pools): the pools' shape less D; the launcher
    (``_paged_call``) slices the one layer's and pads them to rows of 128
    lanes, and the kernel copies the rows of the pages it walks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs import ledger as obs_ledger
from .flash_attention import NEG_INF
from .lowering import interpret as _interpret


# Pages a walk copies and multiplies as one block. The launcher's own
# constant, not an option: 8 pages of 64 tokens x 8 kv heads is 1 MB a
# buffer in bf16 (K and V double-buffered: 4 MB of the 16 MB of VMEM).
BLOCK_PAGES = 8


def _paged_kernel(st_ref, pt_ref, sl_ref, ly_ref, q_ref, k_hbm, v_hbm,
                  *rest, sm_scale, page_size, chunk, block_pages,
                  quantized=False, window=None):
    """ONE program per sequence, shared by decode and chunked prefill:
    all the device's kv heads, and for each its (G*chunk) query rows,
    walk the pages the sequence HAS in blocks of ``block_pages``. The
    pools stay in HBM; a block's pages are copied into one of two VMEM
    buffers (one strided copy a page carries every head), the next block
    in flight while this one is multiplied, and the next sequence's
    first block started before this program ends. Online softmax over
    the blocks with (m, l, acc) in VMEM scratch. Row r sits at absolute
    position st_ref[b] + (r % chunk); masking is causal over absolute
    positions AND bounded by seq_len — decode is simply the chunk=1 case
    with st = seq_len - 1. ``quantized``: int8 pools whose per-slot f32
    scales ride the same copies as (page_size,) rows and scale the
    scores and the probabilities, which is where a row of them fits.
    The grid is sequential: buffers, semaphores and the buffer's parity
    (``slot_ref``) pass from one program to the next.

    ``window`` (static; None = causal over everything, and then the
    traced program is the one it was before the argument existed): a row
    at position t sees the keys j with t - window < j <= t. The walk then
    has a LOWER bound too: it starts at the page that holds the first
    position the program's first row may see, max(0, st - window + 1),
    masks what lies before each row's own bound, and walks to the row's
    last page - ``window / page_size + 1`` pages for a decode row
    whatever its context. The table is indexed by position as without a
    window (entry j holds positions j * page_size ...): entries behind
    the window are never read, so the host may leave them dead (0) once
    it has given those pages back."""
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems,
         slot_ref, m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    n_seqs = pl.num_programs(0)
    _, heads, rows, head_dim = q_ref.shape
    width = pt_ref.shape[1]
    layer = ly_ref[0]

    def pages_of(b):
        """Pages sequence ``b``'s rows attend to: at least one, so that
        every program waits for exactly the block its predecessor
        started; an empty row reads its table's first entry, masked."""
        live = jnp.minimum(sl_ref[b], st_ref[b] + chunk)
        return jnp.clip(pl.cdiv(live, page_size), 1, width)

    def first_page(b):
        """The table entry a windowed walk of sequence ``b`` starts at."""
        first = jnp.maximum(st_ref[b] - (window - 1), 0)
        return jnp.minimum(first // page_size, pages_of(b) - 1)

    pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    scales = ((ks_hbm, ks_buf), (vs_hbm, vs_buf)) if quantized else ()

    def block_copies(b, j, slot, act):
        """``act`` (start or wait) on every copy of block ``j`` of
        sequence ``b`` into buffer ``slot``: a page's K and V (and their
        scales), every kv head in one strided copy."""
        left = pages_of(b) - j * block_pages
        if window is not None:
            first = first_page(b)
            left = left - first

        def page_copies(i):
            if window is None:
                page = pt_ref[b, j * block_pages + i]
            else:
                page = pt_ref[b, first + j * block_pages + i]
            for which, (hbm, buf) in enumerate(pools):
                act(pltpu.make_async_copy(
                    hbm.at[layer, :, page], buf.at[slot, :, i],
                    sems.at[which, slot]))
            for which, (hbm, buf) in enumerate(scales):
                act(pltpu.make_async_copy(
                    hbm.at[:, page], buf.at[slot, :, i],
                    sems.at[which, slot]))

        page_copies(0)                     # a block has at least a page
        for i in range(1, block_pages):
            pl.when(i < left)(functools.partial(page_copies, i))

    def start(b, j, slot):
        block_copies(b, j, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _first():
        # pages a block does not copy keep what the buffer held: V (and
        # the scales) must be finite there, since 0 x NaN is NaN
        slot_ref[0] = 0
        v_buf[...] = jnp.zeros_like(v_buf)
        if quantized:
            ks_buf[...] = jnp.zeros_like(ks_buf)
            vs_buf[...] = jnp.zeros_like(vs_buf)
        start(0, 0, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    seq_len = sl_ref[b]
    q_start = st_ref[b]
    walked = pages_of(b)
    if window is not None:
        walked = walked - first_page(b)
    n_blocks = pl.cdiv(walked, block_pages)
    slot0 = slot_ref[0]
    shape = (block_pages, rows, page_size)
    # key position inside a block, and each row's absolute position
    col_in = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * page_size
              + jax.lax.broadcasted_iota(jnp.int32, shape, 2))
    row_pos = q_start + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, shape, 1), chunk)

    def walk(j, _):
        slot = jax.lax.rem(slot0 + j, 2)

        @pl.when(j + 1 < n_blocks)
        def _next_block():
            start(b, j + 1, 1 - slot)

        @pl.when((j + 1 == n_blocks) & (b + 1 < n_seqs))
        def _next_sequence():
            start(b + 1, 0, 1 - slot)

        block_copies(b, j, slot, lambda c: c.wait())
        col_pos = j * (block_pages * page_size) + col_in
        if window is not None:
            col_pos = col_pos + first_page(b) * page_size
        mask = (col_pos <= row_pos) & (col_pos < seq_len)
        if window is not None:
            mask = mask & (col_pos > row_pos - window)
        for h in range(heads):
            q = jnp.broadcast_to(q_ref[0, h].astype(jnp.float32)[None],
                                 (block_pages, rows, head_dim))
            s = jax.lax.dot_general(
                q, k_buf[slot, h].astype(jnp.float32),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            if quantized:
                s = s * ks_buf[slot, h, :, :page_size][:, None]
            s = jnp.where(mask, s * sm_scale, NEG_INF)

            m_prev = m_scr[h]
            m_new = jnp.maximum(
                m_prev, jnp.max(jnp.max(s, 2, keepdims=True), 0))
            p = jnp.exp(s - m_new[None])
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(
                jnp.sum(p, 2, keepdims=True), 0)
            if quantized:
                p = p * vs_buf[slot, h, :, :page_size][:, None]
            acc_scr[h] = acc_scr[h] * alpha + jnp.sum(jax.lax.dot_general(
                p, v_buf[slot, h].astype(jnp.float32),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32), 0)
            m_scr[h] = m_new

    jax.lax.fori_loop(0, n_blocks, walk, None)
    slot_ref[0] = jax.lax.rem(slot0 + n_blocks, 2)
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)).astype(
        o_ref.dtype)


def _paged_call(q4, k_pages, v_pages, page_tables, seq_lens, starts,
                chunk, sm_scale, k_scales, v_scales, layer, window=None):
    """Shared launcher: q4 (B, Hkv, G*chunk, D) -> same shape out. The
    pools are (L, Hkv, P, page_size, D) read at ``layer`` (int or traced
    scalar); 4-D pools are lifted to the L = 1 pool they are. The pools
    go in whole and stay in HBM, an int8 pool's scales as the one layer's
    rows: the kernel copies the pages it walks."""
    quantized = k_scales is not None or v_scales is not None
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("int8 pools need BOTH k_scales and v_scales")
    if k_pages.ndim == 4:
        if layer is not None:
            raise ValueError("a layer index needs the whole "
                             "(L, Hkv, P, page_size, D) pools")
        k_pages, v_pages = k_pages[None], v_pages[None]
        layer = 0
    elif layer is None:
        raise ValueError("(L, Hkv, P, page_size, D) pools need the layer "
                         "to read")
    B, Hkv, rows, D = q4.shape
    _, _, P, page_size, Dk = k_pages.shape
    if D != Dk:
        raise ValueError(f"head_dim mismatch: q {D} vs pages {Dk}")

    row_spec = pl.BlockSpec((1, Hkv, rows, D),
                            lambda b, st, pt, sl, ly: (b, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    args = [q4, k_pages, v_pages]
    buffers = [pltpu.VMEM((2, Hkv, BLOCK_PAGES, page_size, D), p.dtype)
               for p in (k_pages, v_pages)]
    if quantized:
        # a copy out of HBM takes whole rows of 128 lanes, and a page's
        # scales are page_size wide: ONE layer's scales are sliced out and
        # padded to such rows (2 MB at 8 x 513 x 64, where the pool's would
        # be pool-sized), and the kernel copies the rows of the pages it
        # walks
        lanes = -(-page_size // 128) * 128
        if k_scales.ndim == 4:
            k_scales, v_scales = k_scales[layer], v_scales[layer]
        args += [jnp.pad(s.astype(jnp.float32),
                         ((0, 0), (0, 0), (0, lanes - page_size)))
                 for s in (k_scales, v_scales)]
        buffers += [pltpu.VMEM((2, Hkv, BLOCK_PAGES, lanes),
                               jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[row_spec] + [in_hbm] * (len(args) - 1),
        out_specs=row_spec,
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((2, 2)),       # (K | V, buffer)
            pltpu.SMEM((1,), jnp.int32),           # the buffer's parity
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),
            pltpu.VMEM((Hkv, rows, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, sm_scale=sm_scale,
                          page_size=page_size, chunk=chunk,
                          block_pages=BLOCK_PAGES, quantized=quantized,
                          **({} if window is None
                             else {"window": int(window)})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, D), q4.dtype),
        interpret=_interpret(),
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a 64-token chunk under 8 query heads a kv head (512 rows)
            # needs 18.7 MB of VMEM, over Mosaic's scoped default of 16;
            # a lane call of several pages more (1024 rows 33.3 MB, 1536
            # rows 48.4): 32 MB for every 768 rows
            **({"vmem_limit_bytes": (32 << 20) * -(-rows // 768)}
               if rows > 384 else {})),
    )(jnp.asarray(starts, jnp.int32).reshape(B),
      jnp.asarray(page_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *args)


def paged_attention(q, k_pages, v_pages, page_tables, seq_lens,
                    sm_scale=None, k_scales=None, v_scales=None,
                    layer=None, window=None):
    """Decode-step attention over a paged KV pool (shapes in the module
    docstring). ``window`` (static): the row sees its last ``window``
    positions alone, itself among them, and the walk starts at the page
    that holds the first of them (``_paged_kernel``). ``k_scales``/``v_scales`` (the pools' shape less D)
    switch the int8-pool path: pages are int8 and dequantized in VMEM
    per block. ``layer`` names the layer of a 5-D pool to read.
    Non-differentiable by design — a serving kernel. Internally the
    chunk=1 case of the shared paged kernel with start = seq_len - 1."""
    B, Hq, D = q.shape
    Hkv = k_pages.shape[-4]
    if Hq % Hkv:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads "
                         f"{Hkv}")
    G = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    sl = jnp.asarray(seq_lens, jnp.int32)
    out = _paged_call(q.reshape(B, Hkv, G, D), k_pages, v_pages,
                      page_tables, sl, jnp.maximum(sl - 1, 0), 1,
                      sm_scale, k_scales, v_scales, layer, window)
    return out.reshape(B, Hq, D)


def paged_attention_reference(q, k_pages, v_pages, page_tables, seq_lens,
                              sm_scale=None):
    """Dense jnp oracle (gathers pages, masks, exact softmax)."""
    B, Hq, D = q.shape
    Hkv, P, page_size, _ = k_pages.shape
    G = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    n_pages = page_tables.shape[1]
    S = n_pages * page_size
    # (B, Hkv, S, D) gathered caches
    k = k_pages[:, page_tables].transpose(1, 0, 2, 3, 4).reshape(
        B, Hkv, S, D)
    v = v_pages[:, page_tables].transpose(1, 0, 2, 3, 4).reshape(
        B, Hkv, S, D)
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg,
                   k.astype(jnp.float32)) * sm_scale
    mask = jnp.arange(S)[None, :] < jnp.asarray(seq_lens)[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


def window_ring(window: int, page_size: int, slack: int = 1) -> int:
    """The most window-kind pages a row holds: the window, the page a
    decode call of ``slack`` steps may cross into, the partial page at
    the window's far end."""
    return -(-(window + slack) // page_size) + 1


class _WindowKind:
    """The book's SECOND kind of page: what a model's sliding-window
    layers hold of a sequence (``PagedKVCache(window_pages=, window=)``).

    A window layer's row at position t reads the keys j with t - window
    < j <= t, so the pages wholly behind that are given back WHILE the
    request runs. A sequence's table is indexed by position like the
    global kind's (entry j holds positions j * page_size ...; the kernel
    starts its walk at the first live entry and never reads one behind
    it), with 0 where a page was given back: a row holds at most ``ring``
    pages of this kind however long it runs. The kind has its own free
    list, refcounts and evictable LRU; a page published for prefix
    sharing is keyed by the GLOBAL page that holds the same tokens (the
    global chain stays the one chain over token pages) and parks with
    its key when its last holder lets go, so a later hit finds the
    window's pages beside the chain's. Page 0 is the reserved padding
    page, as in the global kind."""

    def __init__(self, n_pages: int, page_size: int, window: int,
                 slack: int = 1):
        if window % page_size:
            raise ValueError(f"window {window} must be a multiple of "
                             f"page_size {page_size}")
        self.n_pages = n_pages
        self.page_size = page_size
        self.window = window
        self.ring = window_ring(window, page_size, slack)
        self.reset()
        self.released = 0           # pages given back behind the window
        self.cut = 0                # prefix hits shortened or lost here

    def reset(self):
        self._free = list(range(self.n_pages - 1, 0, -1))
        self.tables: dict = {}      # seq -> [page | 0] by position
        self._refs: dict = {}       # page -> holders
        self._evictable: dict = {}  # parked page -> True; insertion = LRU
        self._key: dict = {}        # page -> the global page it is keyed by
        self._by_key: dict = {}     # global page -> page

    def populations(self):
        return len(self._refs), len(self._evictable), len(self._free)

    def _take(self) -> int:
        """One page off the free list, the least recently parked page
        reclaimed (its key dropped) when the list is dry."""
        if not self._free:
            if not self._evictable:
                raise MemoryError(
                    "window pages exhausted: every page is held by a "
                    "running row (the pool is smaller than slots x ring)")
            p = next(iter(self._evictable))
            del self._evictable[p]
            self._by_key.pop(self._key.pop(p), None)
            self._free.append(p)
        p = self._free.pop()
        self._refs[p] = 1
        return p

    def _let_go(self, p: int):
        rc = self._refs.get(p, 1) - 1
        if rc > 0:
            self._refs[p] = rc
            return
        self._refs.pop(p, None)
        if p in self._key:
            self._evictable[p] = True       # parked, key live
        else:
            self._free.append(p)

    def extend(self, seq_id, n_tokens: int):
        """Pages for every position < n_tokens that has none yet."""
        table = self.tables.setdefault(seq_id, [])
        for _ in range(-(-n_tokens // self.page_size) - len(table)):
            table.append(self._take())
        return table

    def release(self, seq_id, next_pos: int) -> int:
        """Give back ``seq_id``'s pages no position >= ``next_pos`` can
        see: page j is dead once its last position lies at or before
        ``next_pos - window``."""
        table = self.tables.get(seq_id, ())
        dead = min(max(0, (next_pos - self.window + 1) // self.page_size),
                   len(table))
        n = 0
        for j in range(dead - 1, -1, -1):     # behind them all is 0 already
            if table[j] == 0:
                break
            self._let_go(table[j])
            table[j] = 0
            n += 1
        self.released += n
        return n

    def free(self, seq_id):
        for p in self.tables.pop(seq_id, ()):
            if p:
                self._let_go(p)

    def publish(self, seq_id, index: int, global_page: int):
        """Key ``seq_id``'s page at ``index`` by the global page of the
        same tokens, unless that key or this page is published."""
        table = self.tables.get(seq_id, ())
        p = table[index] if index < len(table) else 0
        if p and p not in self._key and global_page not in self._by_key:
            self._key[p] = global_page
            self._by_key[global_page] = p

    def unkey(self, global_page: int):
        """The global page lost its identity: the page keyed by it can
        serve no prefix again (parked: freed; held: freed when let go)."""
        p = self._by_key.pop(global_page, None)
        if p is None:
            return
        self._key.pop(p, None)
        if p in self._evictable:
            del self._evictable[p]
            self._free.append(p)

    def covered(self, chain, n: int) -> bool:
        """Are the pages that positions >= n * page_size may see of the
        first n chain pages all held or parked?"""
        back = self.window // self.page_size
        return all(g in self._by_key for g in chain[max(0, n - back):n])

    def share(self, seq_id, chain, n: int):
        """Take the covering pages of an ``n``-page hit into ``seq_id``'s
        table (parked ones revived), dead entries before them."""
        back = self.window // self.page_size
        table = self.tables.setdefault(seq_id, [])
        for j in range(n):
            p = self._by_key[chain[j]] if j >= n - back else 0
            if p:
                self._evictable.pop(p, None)
                self._refs[p] = self._refs.get(p, 0) + 1
            table.append(p)


class _StateKind:
    """The book's THIRD kind: what a model's recurrent (linear-attention)
    layers hold of a sequence (``PagedKVCache(state_slots=,
    state_snapshots=)``) — not pages but one fixed-size ENTRY, with no
    table and no growth.

    Entries ``0 .. slots - 1`` are the decode slots' running states (slot
    ``s`` computes in entry ``s``: nothing to allocate). The
    ``n_snapshots`` entries behind them belong to the prefix cache. A
    latent page says what the keys at its positions were; it does not say
    what the state was, and a state cannot be rebuilt from pages without
    re-running every position under it. So a prefix hit is valid only at
    a position where a SNAPSHOT stands: a copy of a running entry, taken
    at a page edge and keyed — like the window kind's pages — by the
    (global) page that ends at that position. A snapshot is pinned while
    a row resumes from it, otherwise the least recently used one is
    overwritten when a new one is wanted, and it is dropped when its page
    loses its identity (the chain is the one chain over token pages)."""

    def __init__(self, slots: int, n_snapshots: int):
        self.slots = slots
        self.n_snapshots = n_snapshots
        self.reset()
        self.taken = 0              # snapshots taken
        self.evicted = 0            # snapshots overwritten for a new one
        self.cut = 0                # prefix hits shortened or lost here
        self.cut_tokens = 0         # matched tokens recomputed for it
        self.matched_tokens = 0     # tokens the chain could have served

    def reset(self):
        self._free = list(range(self.slots + self.n_snapshots - 1,
                                self.slots - 1, -1))
        self._by_key: dict = {}     # global page -> entry; insertion = LRU
        self._key: dict = {}        # entry -> the page it is keyed by
        self._pins: dict = {}       # entry -> rows resuming from it

    def populations(self):
        """(pinned, unpinned, free) snapshot entries."""
        return (len(self._pins), len(self._key) - len(self._pins),
                len(self._free))

    def deepest(self, chain, cap: int) -> int:
        """The most pages ``n <= cap`` of ``chain`` at whose end a
        snapshot stands (0: the zero state stands at the start)."""
        for n in range(cap, 0, -1):
            if chain[n - 1] in self._by_key:
                return n
        return 0

    def pin(self, page: int) -> int:
        """The entry keyed by ``page``, most recently used from now and
        not to be overwritten until ``unpin``."""
        entry = self._by_key.pop(page)
        self._by_key[page] = entry
        self._pins[entry] = self._pins.get(entry, 0) + 1
        return entry

    def unpin(self, entry: int):
        n = self._pins.get(entry, 1) - 1
        if n > 0:
            self._pins[entry] = n
        else:
            self._pins.pop(entry, None)

    def take(self, page: int):
        """An entry for a new snapshot keyed by ``page``: a free one, else
        the least recently used that no row is resuming from. None where
        ``page`` has its snapshot already (touched) or every entry is
        pinned."""
        if page in self._by_key:
            self._by_key[page] = self._by_key.pop(page)
            return None
        if self._free:
            entry = self._free.pop()
        else:
            old = next((p for p, e in self._by_key.items()
                        if e not in self._pins), None)
            if old is None:
                return None
            entry = self._by_key.pop(old)
            del self._key[entry]
            self.evicted += 1
        self._by_key[page] = entry
        self._key[entry] = page
        self.taken += 1
        return entry

    def unkey(self, page: int):
        """The page lost its identity: its snapshot can serve no prefix
        again (a pinned one is being copied from in program order, and
        the copy was dispatched before anything could overwrite it)."""
        entry = self._by_key.pop(page, None)
        if entry is not None:
            del self._key[entry]
            self._pins.pop(entry, None)
            self._free.append(entry)


class PagedKVCache:
    """Host-side page-pool bookkeeping for serving loops: a free list of
    pages plus per-sequence tables (~ vLLM's BlockManager). Device data
    stays functional — ``write`` returns the updated pools."""

    def __init__(self, n_pages: int, page_size: int, kv_heads: int,
                 head_dim: int, dtype=jnp.bfloat16,
                 window_pages: int | None = None,
                 window: int | None = None, window_slack: int = 1,
                 state_slots: int | None = None, state_snapshots: int = 0):
        # a second kind of page (``_WindowKind``) for a model whose
        # sliding-window layers keep a pool of their own; None: every
        # statement below runs as it did before the kind existed
        self._win = None if window_pages is None else _WindowKind(
            window_pages, page_size, window, window_slack)
        # a third kind (``_StateKind``): a state entry a sequence and the
        # prefix cache's snapshots, for a model with recurrent layers
        self._state = None if state_slots is None else _StateKind(
            state_slots, state_snapshots)
        self._pub: dict = {}        # seq -> (pages published, last page)
        self._cut_of: dict = {}     # seq -> its hit was cut (for rollback)
        self._kind_bytes: dict | None = None
        self.page_size = page_size
        self.k_pages = jnp.zeros((kv_heads, n_pages, page_size, head_dim),
                                 dtype)
        self.v_pages = jnp.zeros_like(self.k_pages)
        self._free = list(range(n_pages - 1, 0, -1))  # page 0 = padding
        self.tables: dict = {}
        self.lengths: dict = {}
        # prefix cache (~ vLLM automatic prefix caching / SGLang
        # RadixAttention, flattened to exact-match chain hashing): FULL
        # pages of identical token prefixes are shared across sequences.
        # Key = (parent_page_or_0, tuple of page tokens) -> page id;
        # refcounts keep shared pages alive while anyone holds them.
        # RETENTION: a published page whose refcount hits 0 does NOT
        # return to the free list — it parks in the evictable LRU pool,
        # key intact, so a later identical prefix revives it for free.
        # allocate() reclaims from the LRU leaf-first only once the
        # free list runs dry (a parent page never dies before its
        # children — the chain invariant that keeps recycled page ids
        # from ever matching stale child keys).
        self._prefix: dict = {}
        self._refs: dict = {}       # page id -> holders (resident set)
        self._page_key: dict = {}   # page id -> its prefix key
        self._children: dict = {}   # page id -> keys with it as parent
        self._evictable: dict = {}  # page id -> True; insertion = LRU
        self._stats = {"hit_tokens": 0, "lookup_tokens": 0,
                       "evictions": 0, "compactions": 0}
        # quantized-tier overlay (kv_quant serving): page ids whose
        # device content is stored int8+scale. Strictly a subset of
        # resident|evictable — the resident+evictable+free census is
        # untouched; a page's tier dies with its id (eviction, an
        # unpublished free, purge) so a recycled id never reads stale
        # int8 data.
        self._quant: set = set()
        self._kv_quant: str | None = None
        self._page_bytes: tuple | None = None  # (fp, int8+scale) /page
        self._byte_budget: int | None = None
        self._compact_cb = None
        # host-DRAM offload tier (hostmem serving): pages the eviction
        # scan would recycle spill their content to a byte-budgeted
        # HostArena instead of dying, keyed by FULL token prefix
        # (root..page) so a spilled chain's identity survives device
        # page-id recycling. _spilled maps that key -> True for every
        # page this bookkeeper parked in the arena; spill/page-in data
        # movement is the engine's (the _spill_cb / page_in import_cb
        # closures price it on the virtual clock — this bookkeeper
        # never touches device arrays). None/empty when unarmed: the
        # resident+evictable+free census and every stat dict stay
        # byte-identical to the pre-hostmem engine.
        self._arena = None
        self._spill_cb = None
        self._host_page_bytes: tuple | None = None  # (fp, q) /page
        self._spilled: dict = {}
        self._spill_stats = {"spills": 0, "pageins": 0,
                             "spill_refusals": 0}
        # pool generation: purge() bumps it. Content written under an
        # earlier epoch is unreachable after a purge (every key dropped,
        # every page back on the free list), so a restarted replica
        # over this bookkeeper can never serve pre-crash pages; the
        # tag makes "which generation is this pool" checkable.
        self.epoch = 0
        # per-device pool residency, NOTED by the serving engine when
        # its factory pools are mesh-sharded (this bookkeeper's own
        # arrays are 1-element stand-ins there); None = never noted,
        # and cache_stats stays byte-identical to the unsharded shape
        self._pool_bytes: tuple | None = None

    def note_pool_bytes(self, total_bytes: int,
                        per_device_bytes: int | None = None):
        """Record the REAL pool's byte footprint (the serving factory
        owns the device arrays; this bookkeeper owns the accounting):
        ``cache_stats()`` then reports ``bytes_per_device`` — the
        number the tensor-parallel capacity claims are gated on. With
        ``per_device_bytes`` omitted the pool is unsharded (one device
        holds everything)."""
        total = int(total_bytes)
        self._pool_bytes = (total, int(per_device_bytes)
                            if per_device_bytes is not None else total)

    # --- quantized page tier (kv_quant serving) ------------------------

    def note_kv_quant(self, mode: str, fp_bytes_per_page: int | None = None,
                      q_bytes_per_page: int | None = None,
                      byte_budget: int | None = None, compact_cb=None):
        """Arm the quantized-tier accounting. ``mode`` is ``"int8"``
        (every occupied page already stored int8+scale by the factory)
        or ``"pressure"`` (full-precision hot pages; parked pages are
        compacted to int8 under byte pressure). The per-page byte costs
        let ``stored_bytes()`` price the pool as actually stored;
        ``byte_budget`` (pressure only) makes ``allocate()`` reclaim
        bytes by compacting the evictable LRU — oldest first, prefix
        keys intact — BEFORE giving up with MemoryError.
        ``compact_cb(page_ids)`` is the device-side compaction hook the
        engine installs (this bookkeeper never touches device data)."""
        if mode not in ("int8", "pressure"):
            raise ValueError(f"note_kv_quant: unknown mode {mode!r}")
        self._kv_quant = mode
        if fp_bytes_per_page is not None:
            self._page_bytes = (int(fp_bytes_per_page),
                                int(q_bytes_per_page))
        self._byte_budget = int(byte_budget) \
            if byte_budget is not None else None
        self._compact_cb = compact_cb

    def quantized_pages(self) -> set:
        return set(self._quant)

    def mark_quantized(self, page_ids):
        """Record that ``page_ids`` are now stored int8 (e.g. after a
        disaggregated import of a mixed-tier chain). Pages must be
        occupied — a free page has no content to have a tier."""
        for p in page_ids:
            if p not in self._refs and p not in self._evictable:
                raise ValueError(
                    f"mark_quantized: page {p} is not occupied")
            self._quant.add(p)

    def compact_candidates(self):
        """Evictable pages not yet quantized, oldest-parked first —
        the order compaction spends them (mirrors the eviction LRU,
        except nothing is forgotten: keys and census stay intact)."""
        return [p for p in self._evictable if p not in self._quant]

    def compact_evictable(self, max_pages: int | None = None) -> list:
        """Compact up to ``max_pages`` (default: all) evictable
        full-precision pages to int8, oldest first: the device hook
        runs first (so a failure there leaves the tier unmarked), then
        the pages join the quantized tier. Returns the page ids
        compacted. Census is untouched — the pages stay evictable,
        keys live, revivable by the same prefixes."""
        cands = self.compact_candidates()
        if max_pages is not None:
            cands = cands[:max_pages]
        if cands:
            if self._compact_cb is not None:
                self._compact_cb(list(cands))
            self._quant.update(cands)
            self._stats["compactions"] += len(cands)
        return cands

    def stored_bytes(self) -> int | None:
        """Bytes the OCCUPIED pages (resident + evictable) actually
        cost as stored: quantized pages at int8+scale size, the rest
        at full precision. None until note_kv_quant supplied per-page
        costs. This is the dynamic pressure signal — admission grows
        it, compaction shrinks it, eviction zeroes a page's share."""
        if self._page_bytes is None:
            return None
        fp, q = self._page_bytes
        occupied = len(self._refs) + len(self._evictable)
        n_q = len(self._quant)
        if self._kv_quant == "int8":
            return occupied * q
        return (occupied - n_q) * fp + n_q * q

    # --- host-DRAM offload tier (hostmem serving) ----------------------

    def note_hostmem(self, arena, spill_cb,
                     fp_bytes_per_page: int,
                     q_bytes_per_page: int | None = None):
        """Arm the host-arena spill tier. ``arena`` is a
        ``serving.hostmem.HostArena``; ``spill_cb(page_id, quant)``
        is the engine's export closure — it copies the page's device
        content host-side (priced as one ``kv_pageout`` on the
        virtual clock) and returns the opaque data blob the arena
        stores. Per-page byte prices charge the arena budget: a page
        sitting in the int8 tier spills at ``q_bytes_per_page``
        (the kv_quant_page_bytes arithmetic carried across the tier
        boundary), everything else at ``fp_bytes_per_page``."""
        self._arena = arena
        self._spill_cb = spill_cb
        q = int(q_bytes_per_page) if q_bytes_per_page is not None \
            else int(fp_bytes_per_page)
        self._host_page_bytes = (int(fp_bytes_per_page), q)

    def _spill_key(self, p) -> tuple | None:
        """Page ``p``'s FULL token prefix (root..p, a page multiple of
        tokens), reconstructed by walking parent keys — the identity a
        spilled page keeps after its device id recycles. None for an
        unpublished page or a broken walk (nothing to spill under)."""
        parts = []
        while p != 0:
            key = self._page_key.get(p)
            if key is None:
                return None
            parts.append(key[1])
            p = key[0]
        toks: tuple = ()
        for seg in reversed(parts):
            toks += seg
        return toks

    def _try_spill(self, p):
        """Park evicted page ``p``'s content in the host arena before
        its device id recycles. Refusal (arena budget exhausted, or an
        unpublished page with no prefix identity) is silent: the page
        simply dies exactly as it did pre-hostmem. A key the arena
        already holds is NOT re-copied — same token prefix, same K/V
        content."""
        key = self._spill_key(p)
        if key is None:
            return
        if key in self._spilled:
            if key in self._arena:
                return  # identical content already parked host-side
            del self._spilled[key]  # arena LRU reclaimed it since —
            # fall through and re-spill the fresh copy
        quant = p in self._quant
        fp, q = self._host_page_bytes
        try:
            data = self._spill_cb(p, quant)
            self._arena.put(key, data, q if quant else fp,
                            quant=quant, epoch=self.epoch)
        except MemoryError:
            self._spill_stats["spill_refusals"] += 1
            return
        self._spilled[key] = True
        self._spill_stats["spills"] += 1

    def _prune_spilled(self):
        """Drop bookkeeping for keys the arena's own LRU reclaimed
        behind our back (the arena owes the bookkeeper no callback;
        reconciliation is lazy, before any read of ``_spilled``)."""
        gone = [k for k in self._spilled if k not in self._arena]
        for k in gone:
            del self._spilled[k]

    def spilled_extension(self, tokens, start: int) -> list:
        """The spilled keys that would EXTEND ``tokens``' resident
        chain past ``start`` cached tokens (a page multiple), in chain
        order — the admission probe for a priced page-in. Stops at the
        first hole: a mid-chain gap means the earlier pages' K/V is
        gone and everything past it would be wrong-context."""
        out = []
        n = int(start)
        ps = self.page_size
        while n + ps <= len(tokens):
            key = tuple(int(t) for t in tokens[:n + ps])
            if key not in self._spilled \
                    or self._arena.peek(key) is None:
                break
            out.append(key)
            n += ps
        return out

    def page_in(self, seq_id, tokens, start: int, import_cb) -> int:
        """Restore the spilled extension of ``tokens[:start]`` into
        ``seq_id``'s chain: per spilled page, take one device page
        (free list first, eviction — which may itself spill — when
        dry), hand it to ``import_cb(page_id, entry)`` (the engine's
        scatter closure, priced as one ``kv_pagein``), then publish it
        resident under ``seq_id`` exactly as if a prefill had written
        and registered it. Stops — cleanly, partial restores are
        valid prefixes — when the pool cannot yield a page. Returns
        tokens paged in (``lengths[seq_id]`` advanced past them, so
        the prefill resumes beyond the restored prefix). Call between
        ``acquire_prefix`` and ``allocate``; ``rollback_acquire``
        stays exact because restored tokens are counted as hits."""
        table = self.tables.get(seq_id)
        if table is None:
            raise KeyError(f"page_in: unknown sequence {seq_id!r}")
        ps = self.page_size
        n = int(start)
        restored = 0
        for key in self.spilled_extension(tokens, n):
            if not self._free and not self._evictable:
                break
            if not self._free:
                self._evict_lru()  # may itself SPILL, which may evict
                # arena LRU entries — re-probe the key below
            if not self._free:
                break
            entry = self._arena.peek(key)
            if entry is None or entry.epoch != self.epoch:
                break  # evicted arena-side just now, or pre-purge
                # content that must never serve
            p = self._free.pop()
            entry = self._arena.take(key)
            self._spilled.pop(key, None)
            import_cb(p, entry)
            self._refs[p] = 1
            table.append(p)
            parent = table[-2] if len(table) >= 2 else 0
            pkey = (parent, key[n:n + ps])
            self._prefix[pkey] = p
            self._page_key[p] = pkey
            self._children.setdefault(parent, set()).add(pkey)
            if entry.quant:
                self._quant.add(p)
            n += ps
            restored += ps
            self._spill_stats["pageins"] += 1
        if restored:
            self._stats["hit_tokens"] += restored
            self.lengths[seq_id] = n
        return restored

    def spill_chain(self, seq_id, tokens, owner: str) -> list:
        """Preemption-as-swap: park ``seq_id``'s live chain content in
        the arena PINNED under ``owner`` (the rid — a preempted
        request's only K/V copy must survive arbitrary spill traffic
        until it pages back in). Spills every FULL page covered by
        ``lengths[seq_id]`` positions of ``tokens`` (prompt + emitted
        history; the trailing partial page re-prefills on resume).
        ALL-OR-NOTHING: if the arena refuses any page, every put/pin
        this call made is rolled back and [] returns — the caller
        then declines to preempt. Returns the pinned keys on success.
        Pages stay allocated; the caller frees the sequence after."""
        table = self.tables.get(seq_id)
        if table is None:
            raise KeyError(f"spill_chain: unknown sequence "
                           f"{seq_id!r}")
        ps = self.page_size
        n_full = min(int(self.lengths.get(seq_id, 0)) // ps,
                     len(table))
        fp, q = self._host_page_bytes
        put_keys, pinned_keys = [], []
        try:
            for i in range(n_full):
                key = tuple(int(t) for t in tokens[:(i + 1) * ps])
                p = table[i]
                quant = p in self._quant
                if key in self._spilled:
                    e = self._arena.peek(key)
                    if e is not None and e.owner is None:
                        self._arena.pin(key, owner)
                        pinned_keys.append(key)
                    continue  # already parked (or pinned elsewhere —
                    # equally protected); content is identical
                data = self._spill_cb(p, quant)
                self._arena.put(key, data, q if quant else fp,
                                quant=quant, epoch=self.epoch,
                                pin=owner)
                self._spilled[key] = True
                self._spill_stats["spills"] += 1
                put_keys.append(key)
        except MemoryError:
            self._spill_stats["spill_refusals"] += 1
            for key in put_keys:
                self._arena.drop(key)
                self._spilled.pop(key, None)
                self._spill_stats["spills"] -= 1
            for key in pinned_keys:
                self._arena.unpin(key)
            return []
        return put_keys + pinned_keys

    def drop_spilled_owner(self, owner: str) -> int:
        """A preempted request was shed while requeued: its pinned
        chain will never page back in — release the arena bytes and
        forget the keys. Returns entries dropped."""
        dropped = [k for k in list(self._spilled)
                   if (e := self._arena.peek(k)) is not None
                   and e.owner == owner]
        for k in dropped:
            self._arena.drop(k)
            del self._spilled[k]
        return len(dropped)

    def unpin_spilled_owner(self, owner: str):
        """Demote ``owner``'s still-pinned keys to the arena LRU (a
        restored request consumed the keys it needed; leftovers —
        shared-prefix pages that matched resident instead — go back
        to being ordinary spilled cache)."""
        for k in list(self._spilled):
            e = self._arena.peek(k)
            if e is not None and e.owner == owner:
                self._arena.unpin(k)

    def allocate(self, seq_id, n_tokens: int):
        """Reserve pages so ``seq_id`` can hold n_tokens total. The
        free list is spent first; evictable LRU pages are reclaimed
        leaf-first only when it dries. MemoryError fires only when
        free + evictable together cannot cover the need (and mutates
        nothing, so a caller can free()/requeue safely)."""
        table = self.tables.setdefault(seq_id, [])
        need = -(-n_tokens // self.page_size) - len(table)
        if need > len(self._free) + len(self._evictable):
            raise MemoryError(
                f"paged cache exhausted: need {need} pages, "
                f"{len(self._free)} free + {len(self._evictable)} "
                f"evictable")
        if need > 0 and self._byte_budget is not None \
                and self._kv_quant == "pressure":
            # byte-budget admission: new pages land full precision; if
            # that would breach the budget, reclaim bytes by compacting
            # parked LRU pages to int8 FIRST (compaction before
            # shedding — nothing is forgotten). Feasibility is checked
            # before any mutation so MemoryError still mutates nothing.
            # (Conservative: page-count evictions the loop below may do
            # would free more bytes, but refusing early is deterministic
            # and never over-admits.)
            fp, q = self._page_bytes
            projected = self.stored_bytes() + need * fp
            over = projected - self._byte_budget
            if over > 0:
                save = fp - q
                n_compact = -(-over // save) if save > 0 else 0
                cands = self.compact_candidates()
                if save <= 0 or n_compact > len(cands):
                    raise MemoryError(
                        f"paged cache byte budget exhausted: "
                        f"{projected} stored bytes projected > "
                        f"{self._byte_budget} budget and only "
                        f"{len(cands)} compactable pages")
                self.compact_evictable(max_pages=n_compact)
        for _ in range(max(0, need)):
            if not self._free:
                self._evict_lru()
            p = self._free.pop()
            self._refs[p] = 1
            table.append(p)
        return table

    def _evict_lru(self):
        """Reclaim ONE evictable page onto the free list: the least-
        recently-parked page with no LIVE child key (leaf-first). The
        chain invariant — an acquirer always holds a page's parents,
        so refs(parent) >= refs(child) — means an evictable page's
        children are evictable too: a leaf always exists and parents
        are never reclaimed before their children."""
        for p in self._evictable:
            kids = self._children.get(p)
            if kids and any(k in self._prefix for k in kids):
                continue  # still a parent of live keys: not a leaf
            del self._evictable[p]
            if self._arena is not None:
                self._try_spill(p)  # park content host-side BEFORE the
                # prefix identity (and the device id) dies below
            self._drop_keys(p)
            self._quant.discard(p)  # tier dies with the id: a recycled
            # page must never read stale int8 content
            self._stats["evictions"] += 1
            self._free.append(p)
            return
        raise MemoryError("no evictable leaf page")  # unreachable by
        # the chain invariant (kept as a loud guard, not a code path)

    def _drop_keys(self, p):
        """Forget page ``p``'s prefix identity before its id recycles:
        its own key, its membership in the parent's child set, and —
        the wrong-context-KV hazard — every key chained THROUGH it
        (a future sequence must never match stale children under the
        recycled id and share unrelated K/V)."""
        for kind in (self._win, self._state):
            if kind is not None:
                kind.unkey(p)
        key = self._page_key.pop(p, None)
        if key is not None:
            self._prefix.pop(key, None)
            sibs = self._children.get(key[0])
            if sibs is not None:
                sibs.discard(key)
                if not sibs:
                    self._children.pop(key[0], None)
        for ck in self._children.pop(p, ()):
            page_c = self._prefix.pop(ck, None)
            if page_c is not None \
                    and self._page_key.get(page_c) == ck:
                self._page_key.pop(page_c, None)
                for kind in (self._win, self._state):
                    if kind is not None:
                        kind.unkey(page_c)

    def acquire_prefix(self, seq_id, tokens) -> int:
        """Match ``tokens`` against cached FULL prompt pages; matched
        pages are SHARED into seq_id's table (refcounted) and the
        number of cached tokens (a page multiple) is returned — the
        prefill can resume past them (for a BATCHED prefill, resume at
        the MINIMUM cached count across the batch). Call BEFORE
        allocate(); if allocate() then raises MemoryError, call
        free(seq_id) before retrying or requeueing, or the shared
        refcounts leak."""
        if seq_id in self.tables:
            raise ValueError(
                f"acquire_prefix: {seq_id!r} already holds pages — "
                "free() it first (e.g. after a failed allocate)")
        table = self.tables.setdefault(seq_id, [])
        n = 0
        pages = self._chain(tokens)
        if self._win is not None:
            chain, hit, cap = self._two_kind_hit(tokens)
            pages = chain[:hit]
            if hit < cap:
                self._win.cut += 1
                self._cut_of[seq_id] = True
        elif self._state is not None:
            chain, hit, cap = self._state_hit(tokens)
            pages = chain[:hit]
            st = self._state
            st.matched_tokens += cap * self.page_size
            st.cut_tokens += (cap - hit) * self.page_size
            st.cut += hit < cap
            self._cut_of[seq_id] = (cap, hit)
        for page in pages:
            if page in self._evictable:
                del self._evictable[page]  # revival: LRU -> resident
            self._refs[page] = self._refs.get(page, 0) + 1
            table.append(page)
            n += self.page_size
        self._stats["hit_tokens"] += n
        self._stats["lookup_tokens"] += \
            (len(tokens) // self.page_size) * self.page_size
        # write()/decode append after the cached prefix, never inside it
        self.lengths[seq_id] = n
        if self._win is not None:
            self._win.share(seq_id, chain, hit)
        if self._win is not None or self._state is not None:
            self._pub[seq_id] = (hit, pages[-1] if pages else 0)
        return n

    def rollback_acquire(self, seq_id, tokens):
        """Leak-proof admit rollback for acquire_prefix -> failed
        allocate: free ``seq_id`` (shared refs released, revived pages
        re-parked evictable) AND unwind the hit/lookup stats the
        acquire recorded — a rolled-back admit was never served from
        cache, and double counting would inflate hit_rate exactly
        under the pool pressure blocked waves retry in. Valid only
        while the table still holds ONLY acquired pages (allocate
        failed without mutating)."""
        n_cached = len(self.tables.get(seq_id, ())) * self.page_size
        if self._win is not None and self._cut_of.pop(seq_id, False):
            self._win.cut -= 1      # the retry will count it again
        if self._state is not None and seq_id in self._cut_of:
            cap, hit = self._cut_of.pop(seq_id)    # the retry counts again
            st = self._state
            st.matched_tokens -= cap * self.page_size
            st.cut_tokens -= (cap - hit) * self.page_size
            st.cut -= hit < cap
        self.free(seq_id)
        self._stats["hit_tokens"] -= n_cached
        self._stats["lookup_tokens"] -= \
            (len(tokens) // self.page_size) * self.page_size

    def _chain(self, tokens):
        """Walk the published chain for ``tokens`` from the root,
        yielding each matched page — the ONE matcher under both
        acquire_prefix and match_prefix, so acquisition and admission
        pricing can never disagree on what the cache serves."""
        parent = 0
        n = 0
        ps = self.page_size
        while n + ps <= len(tokens):
            page = self._prefix.get(
                (parent, tuple(int(t) for t in tokens[n:n + ps])))
            if page is None:
                return
            yield page
            parent = page
            n += ps

    def match_prefix(self, tokens) -> int:
        """Non-acquiring probe: how many leading tokens of ``tokens``
        the cache could serve right now (a page multiple). No refcount,
        LRU, or stats mutation — safe for a scheduler to call per
        admission turn to price prefill work before committing."""
        if self._win is not None:
            return self._two_kind_hit(tokens)[1] * self.page_size
        if self._state is not None:
            return self._state_hit(tokens)[1] * self.page_size
        return sum(self.page_size for _ in self._chain(tokens))

    def _state_hit(self, tokens):
        """-> (the matched chain, pages a book with a state kind can serve
        of it, pages it could were a snapshot at every page): the deepest
        position under the matched chain at which a snapshot stands. A hit
        never takes the whole prompt (the final chunk always runs)."""
        chain = list(self._chain(tokens))
        cap = min(len(chain), (len(tokens) - 1) // self.page_size)
        return chain, self._state.deepest(chain, cap), cap

    # --- the state kind (books of a model with recurrent layers) --------

    def state_resume(self, seq_id):
        """The snapshot entry ``seq_id``'s acquired prefix ends on, pinned
        until ``state_resumed`` (None: it starts from the zero state)."""
        n, last = self._pub.get(seq_id, (0, 0))
        return self._state.pin(last) if n else None

    def state_resumed(self, entry: int):
        """The copy out of ``entry`` is dispatched: it may be overwritten
        (later, in program order)."""
        self._state.unpin(entry)

    def state_snapshot(self, seq_id, tokens, n_tokens: int):
        """``seq_id``'s running state stands at ``n_tokens`` (a page's
        edge inside its prompt): publish its pages up to there and hand
        out the entry to copy the state to, or None where that position
        has its snapshot already, the chain was evicted under the
        sequence, or every entry is pinned."""
        self.publish_upto(seq_id, tokens, n_tokens)
        n, last = self._pub.get(seq_id, (None, 0))
        if n is None or n * self.page_size != n_tokens or not last:
            return None
        return self._state.take(last)

    def _two_kind_hit(self, tokens):
        """-> (the matched global chain, pages a two-kind book can serve
        of it, pages it could were no window page gone): the longest hit
        whose window-kind pages — those covering the ``window`` positions
        before its end — are all still held or parked. A hit never takes
        the whole prompt (the final chunk always runs, and re-running it
        inside the hit would need one page more behind the window), and
        never resumes with window pages missing: a window layer's keys
        cannot be rebuilt without re-running every layer under it."""
        chain = list(self._chain(tokens))
        cap = min(len(chain), (len(tokens) - 1) // self.page_size)
        hit = cap
        while hit > 0 and not self._win.covered(chain, hit):
            hit -= 1
        return chain, hit, cap

    # --- the window kind (two-kind books only) --------------------------

    def window_table(self, seq_id):
        """``seq_id``'s window-kind pages by position, 0 where dead."""
        return self._win.tables.get(seq_id, ())

    def window_extend(self, seq_id, n_tokens: int):
        """Window-kind pages for every position < ``n_tokens``: called
        before the chunk or decode call that writes them. It cannot fail
        on a pool of at least ``slots x ring`` pages (the engine's
        floor): a row holds at most ``ring``, the rest is free or
        parked."""
        return self._win.extend(seq_id, n_tokens)

    def window_release(self, seq_id, next_pos: int) -> int:
        """Give back the window-kind pages wholly behind ``next_pos -
        window`` (``next_pos``: the lowest position still to attend);
        a published one parks with its key. Returns pages given back."""
        return self._win.release(seq_id, next_pos)

    def publish_upto(self, seq_id, tokens, n_tokens: int):
        """Two kinds: publish ``seq_id``'s full prompt pages below
        ``n_tokens`` (they hold real K/V), both kinds, continuing where
        the last call stopped. A window page has to be published BEFORE
        it is given back, so the engine calls this after every chunk
        where a one-kind book registers once, at the prompt's end."""
        table = self.tables.get(seq_id, [])
        i, parent = self._pub.get(seq_id, (0, 0))
        ps = self.page_size
        while i is not None and (i + 1) * ps <= min(n_tokens, len(tokens)):
            if parent and parent not in self._page_key:
                # the chain this sequence followed was evicted under it
                # (its own next page was not yet a live child): nothing
                # further of it can be published
                i = None
                break
            key = (parent, tuple(int(t) for t in tokens[i * ps:(i + 1)
                                                        * ps]))
            if self._prefix.get(key) is None:
                page = table[i]
                self._prefix[key] = page
                self._page_key[page] = key
                self._children.setdefault(parent, set()).add(key)
            parent = self._prefix[key]
            if self._win is not None:
                self._win.publish(seq_id, i, parent)
            i += 1
        self._pub[seq_id] = (i, parent)

    def note_kind_bytes(self, by_kind: dict):
        """A page's bytes by kind (``{"global": b, "window": b}``: each
        over the layers of its kind), so that what a request will hold
        can be priced: ``footprint_bytes``."""
        self._kind_bytes = {k: int(v) for k, v in by_kind.items()}

    def footprint_bytes(self, n_tokens: int) -> int | None:
        """Bytes a request of ``n_tokens`` will hold at its fullest:
        every page in the global kind, at most the ring in the window
        kind."""
        if self._kind_bytes is None:
            return None
        pages = -(-n_tokens // self.page_size)
        return pages * self._kind_bytes["global"] \
            + min(pages, self._win.ring) * self._kind_bytes["window"]

    def populations_by_kind(self) -> dict:
        return {"global": self.populations(),
                "window": self._win.populations()}

    def register_prefix(self, seq_id, tokens):
        """Publish seq_id's FULL prompt pages (now holding real K/V) for
        sharing. Call after the prompt's prefill wrote its pages."""
        if self._win is not None or self._state is not None:
            return self.publish_upto(seq_id, tokens, len(tokens))
        table = self.tables.get(seq_id, [])
        parent = 0
        ps = self.page_size
        for i in range(len(tokens) // ps):
            key = (parent, tuple(int(t) for t in tokens[i * ps:(i + 1)
                                                        * ps]))
            page = table[i]
            existing = self._prefix.get(key)
            if existing is None:
                self._prefix[key] = page
                self._page_key[page] = key
                # root (parent == 0) keys are tracked too: _children is
                # the leaf test's reverse index as well as the stale-key
                # invalidator, so EVERY published key must sit under its
                # parent (page 0 is never recycled, but its child set
                # must shrink as root keys die or it leaks forever)
                self._children.setdefault(parent, set()).add(key)
            parent = self._prefix[key]

    def write(self, seq_id, k_new, v_new):
        """Append (Hkv, T, D) keys/values for seq_id; returns the
        updated (k_pages, v_pages) pool arrays (also stored on self —
        each update is a functional dynamic slice per page)."""
        T = k_new.shape[1]
        start = self.lengths.get(seq_id, 0)
        self.allocate(seq_id, start + T)
        table = self.tables[seq_id]
        ps = self.page_size
        written = 0
        while written < T:
            pos = start + written
            page = table[pos // ps]
            off = pos % ps
            n = min(ps - off, T - written)  # chunk ends at a page edge
            self.k_pages = jax.lax.dynamic_update_slice(
                self.k_pages, k_new[:, None, written:written + n].astype(
                    self.k_pages.dtype), (0, page, off, 0))
            self.v_pages = jax.lax.dynamic_update_slice(
                self.v_pages, v_new[:, None, written:written + n].astype(
                    self.v_pages.dtype), (0, page, off, 0))
            written += n
        self.lengths[seq_id] = start + T
        return self.k_pages, self.v_pages

    def free(self, seq_id):
        if self._win is not None:
            self._win.free(seq_id)
        if self._win is not None or self._state is not None:
            self._pub.pop(seq_id, None)
            self._cut_of.pop(seq_id, None)
        for p in self.tables.pop(seq_id, []):
            rc = self._refs.get(p, 1) - 1
            if rc <= 0:
                self._refs.pop(p, None)
                if p in self._page_key:
                    # retention: a PUBLISHED page outlives its last
                    # holder — park it in the evictable LRU pool with
                    # its key live, so a recurring prefix revives it
                    # instead of re-prefilling; allocate() reclaims it
                    # leaf-first only under free-list pressure
                    self._evictable[p] = True
                else:
                    self._drop_keys(p)  # stale-chain invalidation for
                    # the recycled id (unpublished pages normally have
                    # no keys; kept defensive)
                    self._quant.discard(p)
                    self._free.append(p)
            else:
                self._refs[p] = rc
        self.lengths.pop(seq_id, None)

    def purge(self):
        """Crash/abort teardown: the pool is GONE, not drained. Every
        sequence's pages are released, every RETAINED (evictable) page
        is reclaimed and every prefix key dropped — unlike ``free()``,
        nothing survives into the retention LRU, because a crashed
        replica's K/V content cannot be trusted — and the pool's
        ``epoch`` is bumped so no later sequence can ever be served
        pages written before the purge. Leaves the census balanced:
        0 resident, 0 evictable, every usable page free. (No per-page
        ``_drop_keys`` walk: the whole key space is wiped below.)"""
        n_pages = int(self.k_pages.shape[1])
        self.tables.clear()
        self.lengths.clear()
        self._refs.clear()
        self._evictable.clear()
        self._prefix.clear()
        self._page_key.clear()
        self._children.clear()
        self._quant.clear()  # both tiers go: pre-purge int8 content is
        # as untrusted as the full-precision pages
        self._free = list(range(n_pages - 1, 0, -1))
        for kind in (self._win, self._state):
            if kind is not None:
                kind.reset()
                self._pub.clear()
                self._cut_of.clear()
        if self._arena is not None:
            # the host tier dies with the pool: pre-purge spilled
            # content is exactly as untrusted as pre-purge device
            # pages (the epoch guard below would refuse it anyway —
            # dropping keeps the arena census honest about capacity)
            for key in self._spilled:
                self._arena.drop(key)
            self._spilled.clear()
        self.epoch += 1

    def export_chain(self, seq_id, n_tokens: int):
        """The page ids holding ``seq_id``'s first ``n_tokens``
        tokens, in chain order — what a disaggregated serving handoff
        exports (the pages beyond — decode slack the allocation
        reserved — stay behind and are freed with the sequence).
        Raises on an unknown sequence or a chain shorter than the
        asked-for tokens: exporting a hole would hand the importer
        unrelated K/V."""
        table = self.tables.get(seq_id)
        if table is None:
            raise KeyError(f"export_chain: unknown sequence "
                           f"{seq_id!r}")
        need = -(-int(n_tokens) // self.page_size)
        if need > len(table):
            raise ValueError(
                f"export_chain: {seq_id!r} holds {len(table)} pages, "
                f"{need} needed for {n_tokens} tokens")
        return list(table[:need])

    def populations(self) -> Tuple[int, int, int]:
        """The census populations (resident, evictable, free) — the
        counts ``census_ok`` balances against capacity and the cost
        ledger's occupancy sampler integrates per turn."""
        return len(self._refs), len(self._evictable), len(self._free)

    def page_holders(self) -> Dict[int, List[str]]:
        """page -> sorted holder seq_ids, from the live tables — the
        attribution view of the resident tier (a shared prefix page
        lists every sharer; refcounts mirror these memberships, which
        the ledger's occupancy audit cross-checks)."""
        holders: Dict[int, List[str]] = {}
        for sid in sorted(self.tables):
            for p in self.tables[sid]:
                holders.setdefault(p, []).append(sid)
        return holders

    def census_ok(self) -> bool:
        """The accounting invariant in one place: every usable page
        (page 0 is reserved padding) is exactly one of resident /
        evictable / free. The serving engine samples this each turn;
        the serving_prefix bench gate fails if it ever broke."""
        balanced = obs_ledger.census_balanced(
            int(self.k_pages.shape[1]) - 1, *self.populations())
        # the quantized tier is an overlay, never a fourth state: every
        # quantized page must still be occupied
        tier_ok = obs_ledger.overlay_contained(
            self._quant, self._refs, self._evictable)
        if self._arena is not None:
            # the host tier extends the census: spilled is a distinct
            # state (spill != leak, like retention != leak) — after
            # reconciling arena-side LRU deaths, every spilled key
            # must be live in the arena, and the arena's own
            # pinned+evictable+free conservation must hold
            self._prune_spilled()
            if not self._arena.census_ok():
                return False
            if any(k not in self._arena for k in self._spilled):
                return False
        if self._win is not None:
            balanced = balanced and obs_ledger.census_balanced(
                self._win.n_pages - 1, *self._win.populations())
        if self._state is not None:
            # every snapshot entry is pinned, parked or free, and every
            # parked or pinned one is keyed by a page that has its identity
            st = self._state
            balanced = balanced and obs_ledger.census_balanced(
                st.n_snapshots, *st.populations()) \
                and obs_ledger.overlay_contained(st._by_key, self._page_key)
        return balanced and tier_ok

    def cache_stats(self) -> dict:
        """Prefix-cache accounting: cumulative hit/lookup tokens and
        evictions plus the live page census. The census satisfies
        ``resident + evictable + free == n_pages - 1`` at all times
        (page 0 is the reserved padding page) — the invariant the
        serving bench gate checks."""
        hit = self._stats["hit_tokens"]
        lookup = self._stats["lookup_tokens"]
        out = {
            "n_pages": int(self.k_pages.shape[1]) - 1,
            "resident_pages": len(self._refs),
            "evictable_pages": len(self._evictable),
            "free_pages": len(self._free),
            "hit_tokens": hit,
            "lookup_tokens": lookup,
            "hit_rate": round(hit / lookup, 4) if lookup else 0.0,
            "evictions": self._stats["evictions"],
        }
        if self._win is not None:
            # populations by kind ONLY when there is more than one: every
            # one-kind run's dict stays byte-identical
            out["kinds"] = {
                kind: dict(zip(("resident_pages", "evictable_pages",
                                "free_pages"), pops))
                for kind, pops in self.populations_by_kind().items()}
            out["kinds"]["window"]["n_pages"] = self._win.n_pages - 1
            out["kinds"]["global"]["n_pages"] = out["n_pages"]
            out["window_pages_released"] = self._win.released
            out["prefix_hits_cut_by_window"] = self._win.cut
            if self._kind_bytes is not None:
                out["page_bytes"] = dict(self._kind_bytes)
        if self._state is not None:
            # the state kind's own accounting ONLY where there is one
            st = self._state
            out["state"] = dict(
                zip(("pinned_snapshots", "parked_snapshots",
                     "free_snapshots"), st.populations()),
                n_snapshots=st.n_snapshots, slots=st.slots,
                state_snapshots_taken=st.taken,
                state_snapshots_evicted=st.evicted,
                prefix_hits_cut_by_snapshot=st.cut,
                prefix_tokens_cut_by_snapshot=st.cut_tokens,
                prefix_tokens_matched=st.matched_tokens)
        if self._pool_bytes is not None:
            # only when noted (a sharded serving pool): unsharded runs
            # keep the pre-TP dict byte-for-byte
            out["bytes_total"] = self._pool_bytes[0]
            out["bytes_per_device"] = self._pool_bytes[1]
        if self._kv_quant is not None:
            # kv_quant census bucket — present only when the tier is
            # armed (kv_quant=None keeps the dict byte-identical).
            # always-int8 stores every occupied page quantized; pressure
            # counts the compacted overlay.
            occupied = len(self._refs) + len(self._evictable)
            out["quantized_pages"] = (occupied
                                      if self._kv_quant == "int8"
                                      else len(self._quant))
            out["compactions"] = self._stats["compactions"]
            sb = self.stored_bytes()
            if sb is not None:
                out["stored_bytes"] = sb
        if self._arena is not None:
            # hostmem census bucket — present only when the tier is
            # armed (hostmem=None keeps the dict byte-identical)
            self._prune_spilled()
            out["spilled_pages"] = len(self._spilled)
            out["spills"] = self._spill_stats["spills"]
            out["pageins"] = self._spill_stats["pageins"]
            out["spill_refusals"] = self._spill_stats["spill_refusals"]
        return out

    def batch_views(self, seq_ids):
        """(page_tables (B, max_pages), seq_lens (B,)) padded with the
        reserved page 0."""
        import numpy as np
        tables = [self.tables[s] for s in seq_ids]
        width = max((len(t) for t in tables), default=1)
        pt = np.zeros((len(seq_ids), width), np.int32)
        for i, t in enumerate(tables):
            pt[i, :len(t)] = t
        sl = np.asarray([self.lengths[s] for s in seq_ids], np.int32)
        return jnp.asarray(pt), jnp.asarray(sl)


# --- prefill over pages (chunked-prefill attention) ------------------------

def paged_prefill_attention(q, k_pages, v_pages, page_tables, seq_lens,
                            q_start, sm_scale=None, k_scales=None,
                            v_scales=None, layer=None, window=None):
    """Causal attention of a C-token query chunk against the paged pool
    (the chunk's own K/V must already be written to its pages).

    q (B, Hq, C, D); pools as in paged_attention; q_start: scalar
    absolute position of the chunk's first token (shared across the
    left-aligned batch). Returns (B, Hq, C, D). The chunk=C case of the
    shared paged kernel; pages entirely beyond start+C or the sequence
    length are skipped, and with a ``window`` (static) those wholly
    before ``q_start - window + 1`` too: each query row is bounded by
    its own ``t - window < j <= t``.
    """
    B, Hq, C, D = q.shape
    Hkv = k_pages.shape[-4]
    if Hq % Hkv:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads "
                         f"{Hkv}")
    G = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    starts = jnp.full((B,), q_start, jnp.int32)
    out = _paged_call(q.reshape(B, Hkv, G * C, D), k_pages, v_pages,
                      page_tables, jnp.asarray(seq_lens, jnp.int32),
                      starts, C, sm_scale, k_scales, v_scales, layer,
                      window)
    return out.reshape(B, Hq, C, D)
