"""The paged kernel's walk with a LOWER bound (a sliding-window row), and
``PagedKVCache``'s second kind of page (window layers' pages, given back
behind the window while a request runs)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.paged_attention import (PagedKVCache,
                                                   paged_attention,
                                                   paged_prefill_attention)

PS, D, HKV, P, W, WINDOW = 4, 16, 2, 48, 12, 8


def oracle(q, kp, vp, pt, lens, starts, window):
    """q (B, Hq, C, D) over pools (Hkv, P, ps, D): gather, mask, softmax."""
    B, Hq, C, _ = q.shape
    G = Hq // HKV
    S = pt.shape[1] * PS
    k = kp[:, pt].transpose(1, 0, 2, 3, 4).reshape(B, HKV, S, D)
    v = vp[:, pt].transpose(1, 0, 2, 3, 4).reshape(B, HKV, S, D)
    s = jnp.einsum("bhgcd,bhsd->bhgcs", q.reshape(B, HKV, G, C, D), k) / math.sqrt(D)
    pos = (starts[:, None] + jnp.arange(C)[None])[..., None]
    j = jnp.arange(S)[None, None]
    ok = (j <= pos) & (j < lens[:, None, None]) & (j > pos - window)
    p = jax.nn.softmax(jnp.where(ok[:, None, None], s, -1e30), -1)
    return jnp.einsum("bhgcs,bhsd->bhgcd", p, v).reshape(B, Hq, C, D)


def pools(rng):
    kp = jnp.asarray(rng.normal(size=(HKV, P, PS, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(HKV, P, PS, D)), jnp.float32)
    # page 0 is where dead table entries point: poisoned, so that reading
    # one shows
    return kp.at[:, 0].set(1e4), vp.at[:, 0].set(1e4)


@pytest.mark.parametrize("group", [4, 6, 8])
def test_decode_walk_with_a_lower_bound_matches_jnp(group):
    """Lengths whose bound t - 7 lies inside a page (13, 30), on a page's
    edge (8: bound 0; 12: bound 4; 48: bound 40) and before the start (1,
    5); the entries behind the window are DEAD (0) and must not be read."""
    rng = np.random.default_rng(group)
    kp, vp = pools(rng)
    lens = np.array([1, 5, 8, 9, 12, 13, 30, 48], np.int32)
    pt = np.zeros((len(lens), W), np.int32)
    nxt = 1
    for b, n in enumerate(lens):
        for j in range(max(0, n - WINDOW) // PS, -(-n // PS)):
            pt[b, j], nxt = nxt, nxt + 1
    q = jnp.asarray(rng.normal(size=(len(lens), group * HKV, D)), jnp.float32)
    out = paged_attention(q, kp, vp, jnp.asarray(pt), jnp.asarray(lens), window=WINDOW)
    ref = oracle(q[:, :, None], kp, vp, jnp.asarray(pt), jnp.asarray(lens),
                 jnp.asarray(lens - 1), WINDOW)[:, :, 0]
    assert float(jnp.abs(ref).max()) < 10          # the oracle saw no poison either
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    # without the bound the same rows read further back: the argument matters
    full = paged_attention(q, kp, vp, jnp.asarray(pt), jnp.asarray(lens))
    assert float(jnp.abs(full[6] - out[6]).max()) > 1e-2


@pytest.mark.parametrize("group", [4, 6, 8])
def test_chunk_walk_bounds_each_query_row_alike(group):
    rng = np.random.default_rng(10 + group)
    kp, vp = pools(rng)
    C = PS
    for start, length in ((0, 3), (8, 12), (12, 15), (24, 28), (40, 44)):
        pt = np.zeros((1, W), np.int32)
        for j in range(max(0, start - WINDOW + 1) // PS, (start + C) // PS):
            pt[0, j] = 1 + j
        q = jnp.asarray(rng.normal(size=(1, group * HKV, C, D)), jnp.float32)
        out = paged_prefill_attention(q, kp, vp, jnp.asarray(pt),
                                      jnp.asarray([length], jnp.int32), start, window=WINDOW)
        ref = oracle(q, kp, vp, jnp.asarray(pt), jnp.asarray([length]),
                     jnp.asarray([start]), WINDOW)
        live = length - start
        np.testing.assert_allclose(np.asarray(out)[:, :, :live], np.asarray(ref)[:, :, :live],
                                   atol=2e-6)


def test_without_a_window_the_traced_program_is_the_one_it_was():
    """``window`` is static: at None the kernel's jaxpr is, to the letter,
    the one traced without the argument (the Mistral cells' decode and
    chunk programs lower to the same kernel body), and a window changes it."""
    S = jax.ShapeDtypeStruct
    q, kp = S((16, 32, 128), jnp.bfloat16), S((8, 8, 513, 64, 128), jnp.bfloat16)
    pt, sl = S((16, 66), jnp.int32), S((16,), jnp.int32)

    def decode(**kw):
        return str(jax.make_jaxpr(lambda q, k, v, pt, sl: paged_attention(
            q, k, v, pt, sl, layer=3, **kw))(q, kp, kp, pt, sl))
    assert decode() == decode(window=None) != decode(window=512)
    assert "window" not in decode()
    qc, pt1, sl1 = S((1, 32, 64, 128), jnp.bfloat16), S((1, 66), jnp.int32), S((1,), jnp.int32)

    def chunk(**kw):
        return str(jax.make_jaxpr(lambda q, k, v, pt, sl: paged_prefill_attention(
            q, k, v, pt, sl, 128, layer=3, **kw))(qc, kp, kp, pt1, sl1))
    assert chunk() == chunk(window=None) != chunk(window=512)


# --- the book's two kinds of page ------------------------------------------

def book(n_window=25, n_global=64):
    return PagedKVCache(n_global, PS, kv_heads=1, head_dim=1,
                        window_pages=n_window, window=WINDOW, window_slack=1)


def census(b):
    kinds = b.populations_by_kind()
    assert sum(kinds["global"]) == b.k_pages.shape[1] - 1 and b.census_ok()
    return kinds["window"]


def prefill(b, sid, tokens, upto=None):
    """What the engine does a chunk: extend, (compute), publish, give back."""
    n = len(tokens) if upto is None else upto
    start = b.lengths.get(sid, 0)
    for k in range(start // PS, -(-n // PS)):
        b.window_extend(sid, (k + 1) * PS)
        assert sum(p != 0 for p in b.window_table(sid)) <= b._win.ring
        b.publish_upto(sid, tokens, min((k + 1) * PS, n))
        b.window_release(sid, min((k + 1) * PS, n))
    b.lengths[sid] = n


def test_pages_behind_the_window_come_back_and_the_census_holds_a_kind():
    b = book(n_global=128)
    assert b._win.ring == WINDOW // PS + 2 == 4
    toks = list(range(100, 100 + 40 * WINDOW))           # a row of 40 windows
    b.allocate("s", len(toks) + 8)
    free0 = census(b)[2]
    held = []
    for k in range(len(toks) // PS):
        b.window_extend("s", (k + 1) * PS)
        held.append(sum(p != 0 for p in b.window_table("s")))
        b.window_release("s", (k + 1) * PS)
        r, e, f = census(b)
        assert r == sum(p != 0 for p in b.window_table("s")) and r + e + f == 24
    assert max(held) <= b._win.ring and held[-1] == 3    # 2 window pages + the chunk's own
    # decode on: one position a step
    for t in range(len(toks), len(toks) + 8):
        b.window_extend("s", t + 1)
        assert sum(p != 0 for p in b.window_table("s")) <= b._win.ring
        b.window_release("s", t + 1)
    table = b.window_table("s")
    live = sum(p != 0 for p in table)
    assert len(table) == len(b.tables["s"]) and 2 <= live <= 3
    assert table[:-live] == [0] * (len(table) - live) and all(table[-live:])
    assert b.cache_stats()["window_pages_released"] == len(table) - live
    b.free("s")
    assert census(b) == (0, 0, free0)                    # nothing was published: all free


def test_a_prefix_hit_needs_both_kinds_and_is_cut_when_window_pages_are_gone():
    b = book()
    prefix = list(range(1, 25))                          # 6 pages
    a = prefix + [90, 91, 92, 93, 94]
    b.acquire_prefix("a", a)
    b.allocate("a", len(a) + 4)
    prefill(b, "a", a)
    b.register_prefix("a", a)
    # the window's pages of the prefix's end were published before they
    # were given back, and park with their keys
    assert census(b)[1] >= 2
    c = prefix + [70, 71, 72]
    assert b.match_prefix(c) == 24
    assert b.acquire_prefix("c", c) == 24
    wt = b.window_table("c")
    assert wt[:4] == [0] * 4 and all(wt[4:6]) and len(wt) == 6     # [n - 8, n) alone
    assert b.cache_stats()["prefix_hits_cut_by_window"] == 0
    b.allocate("c", len(c) + 4)
    prefill(b, "c", c)
    b.free("a"), b.free("c")
    census(b)
    # a hit never takes the whole prompt: the final chunk always runs
    assert b.match_prefix(prefix) == 20
    # evict the parked window pages: the global chain still matches 6 pages,
    # the window kind covers none of it -> the hit is lost, never resumed
    # with window pages missing
    for i in range(6):
        b.window_extend(f"x{i}", 4 * PS)
    assert census(b)[1] == 0
    assert len(list(b._chain(c))) == 6 and b.match_prefix(c) == 0
    assert b.acquire_prefix("d", c) == 0 and b.tables["d"] == [] and not b.window_table("d")
    assert b.cache_stats()["prefix_hits_cut_by_window"] == 1
    b.rollback_acquire("d", c)                           # a requeue counts it once
    assert b.cache_stats()["prefix_hits_cut_by_window"] == 0
    for i in range(6):
        b.free(f"x{i}")
    census(b)


def test_a_hit_is_cut_back_to_what_the_window_kind_still_covers():
    b = book()
    long = list(range(1, 41))                            # 10 pages
    b.acquire_prefix("a", long + [99])
    b.allocate("a", 48)
    prefill(b, "a", long + [99])
    b.free("a")
    assert b.match_prefix(long + [98]) == 40
    # drop the window page keyed by the chain's LAST page: hits of 10 and
    # of 9 pages both need it; 8 pages need pages 6 and 7 alone
    chain = list(b._chain(long))
    b._win.unkey(chain[9])
    b._win.unkey(chain[8])
    assert b.match_prefix(long + [98]) == 32
    assert b.acquire_prefix("b", long + [98]) == 32
    assert b.cache_stats()["prefix_hits_cut_by_window"] == 1
    assert [bool(p) for p in b.window_table("b")] == [False] * 6 + [True] * 2
    # a global page that loses its identity takes its window page's key along
    b.free("b")
    g = chain[7]
    w = b._win._by_key[g]
    b._drop_keys(g)
    assert g not in b._win._by_key and w not in b._win._key
    census(b)
    b.purge()
    assert census(b) == (0, 0, 24) and b.match_prefix(long) == 0


def test_a_one_kind_book_reports_what_it_reported():
    b = PagedKVCache(16, PS, kv_heads=1, head_dim=1)
    b.acquire_prefix("s", list(range(9)))
    b.allocate("s", 12)
    b.register_prefix("s", list(range(9)))
    assert list(b.cache_stats()) == ["n_pages", "resident_pages", "evictable_pages",
                                     "free_pages", "hit_tokens", "lookup_tokens", "hit_rate",
                                     "evictions"]
    assert b.match_prefix(list(range(9))) == 8           # the whole prompt may hit here
    two = book()
    stats = two.cache_stats()
    assert list(stats)[8:] == ["kinds", "window_pages_released", "prefix_hits_cut_by_window"]
    assert stats["kinds"]["window"] == {"resident_pages": 0, "evictable_pages": 0,
                                        "free_pages": 24, "n_pages": 24}
    two.note_kind_bytes({"global": 100, "window": 10})
    assert two.footprint_bytes(10 * PS) == 10 * 100 + 4 * 10      # the ring, not the length
    with pytest.raises(MemoryError, match="window pages exhausted"):
        for i in range(7):
            two.window_extend(f"s{i}", 4 * PS)
