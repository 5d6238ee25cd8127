"""Paged KV-cache decode attention (ops/pallas/paged_attention.py):
kernel-vs-oracle parity in interpret mode + the PagedKVCache pool
bookkeeping a serving loop relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.paged_attention import (PagedKVCache,
                                                   paged_attention,
                                                   paged_attention_reference)


def _setup(rng, B=2, Hq=4, Hkv=2, D=16, P=9, page_size=8, n_pages=3):
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(0, 1, (Hkv, P, page_size, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(0, 1, (Hkv, P, page_size, D)),
                     jnp.float32)
    pt = jnp.asarray(rng.choice(np.arange(1, P), (B, n_pages),
                                replace=False), jnp.int32)
    return q, kp, vp, pt


def test_kernel_matches_oracle_ragged_lengths():
    rng = np.random.default_rng(0)
    q, kp, vp, pt = _setup(rng)
    # ragged: mid-page end, exact page edge
    sl = jnp.asarray([13, 16], jnp.int32)
    got = paged_attention(q, kp, vp, pt, sl)
    want = paged_attention_reference(q, kp, vp, pt, sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_jits_and_single_token():
    rng = np.random.default_rng(1)
    q, kp, vp, pt = _setup(rng)
    sl = jnp.asarray([1, 5], jnp.int32)
    f = jax.jit(paged_attention)
    got = f(q, kp, vp, pt, sl)
    want = paged_attention_reference(q, kp, vp, pt, sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_mqa_group():
    rng = np.random.default_rng(2)
    q, kp, vp, pt = _setup(rng, Hq=6, Hkv=1)
    sl = jnp.asarray([20, 9], jnp.int32)
    got = paged_attention(q, kp, vp, pt, sl)
    want = paged_attention_reference(q, kp, vp, pt, sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_cache_serving_loop():
    """Pool bookkeeping end-to-end: prefill two sequences, decode-append,
    free one, reuse its pages for a third — attention over the pool
    matches a dense oracle at every step."""
    rng = np.random.default_rng(3)
    Hkv, D, ps = 2, 16, 8
    cache = PagedKVCache(n_pages=8, page_size=ps, kv_heads=Hkv,
                         head_dim=D, dtype=jnp.float32)

    dense = {}

    def append(sid, T):
        k = jnp.asarray(rng.normal(0, 1, (Hkv, T, D)), jnp.float32)
        v = jnp.asarray(rng.normal(0, 1, (Hkv, T, D)), jnp.float32)
        cache.write(sid, k, v)
        pk, pv = dense.get(sid, (jnp.zeros((Hkv, 0, D)),
                                 jnp.zeros((Hkv, 0, D))))
        dense[sid] = (jnp.concatenate([pk, k], 1),
                      jnp.concatenate([pv, v], 1))

    append("a", 11)   # 2 pages, mid-page end
    append("b", 8)    # exactly 1 page
    append("a", 3)    # decode appends continue page 2

    q = jnp.asarray(rng.normal(0, 1, (2, 4, D)), jnp.float32)
    pt, sl = cache.batch_views(["a", "b"])
    got = paged_attention(q, cache.k_pages, cache.v_pages, pt, sl)
    for i, sid in enumerate(["a", "b"]):
        k, v = dense[sid]
        G = 4 // Hkv
        qg = q[i].reshape(Hkv, G, D)
        s = jnp.einsum("hgd,hsd->hgs", qg, k) / np.sqrt(D)
        want = jnp.einsum("hgs,hsd->hgd", jax.nn.softmax(s, -1),
                          v).reshape(4, D)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    # free + reuse
    pages_a = set(cache.tables["a"])
    cache.free("a")
    append("c", 30)   # needs 4 pages; must reuse a's
    assert pages_a & set(cache.tables["c"])
    with pytest.raises(MemoryError):
        append("c", 100)


def test_pool_exhaustion_and_padding_page():
    cache = PagedKVCache(n_pages=3, page_size=4, kv_heads=1, head_dim=8)
    # page 0 is reserved for table padding: only 2 usable pages
    cache.allocate("s", 8)
    with pytest.raises(MemoryError):
        cache.allocate("s", 12)


def test_int8_pool_matches_dequant_oracle():
    """int8 pages + per-slot scales: the kernel's in-VMEM dequant must
    match the dense oracle run over the dequantized pool."""
    rng = np.random.default_rng(7)
    B, Hq, Hkv, D, P, ps, n = 2, 4, 2, 16, 9, 8, 3
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, D)), jnp.float32)
    kf = rng.normal(0, 1, (Hkv, P, ps, D)).astype(np.float32)
    vf = rng.normal(0, 1, (Hkv, P, ps, D)).astype(np.float32)

    def quant(x):
        scale = np.maximum(np.abs(x).max(-1), 1e-8) / 127.0
        qd = np.clip(np.round(x / scale[..., None]), -127, 127)
        return qd.astype(np.int8), scale.astype(np.float32)

    kq, ks = quant(kf)
    vq, vs = quant(vf)
    pt = jnp.asarray(rng.choice(np.arange(1, P), (B, n), replace=False),
                     jnp.int32)
    sl = jnp.asarray([13, 16], jnp.int32)
    got = paged_attention(q, jnp.asarray(kq), jnp.asarray(vq), pt, sl,
                          k_scales=jnp.asarray(ks),
                          v_scales=jnp.asarray(vs))
    want = paged_attention_reference(
        q, jnp.asarray(kq.astype(np.float32) * ks[..., None]),
        jnp.asarray(vq.astype(np.float32) * vs[..., None]), pt, sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="BOTH"):
        paged_attention(q, jnp.asarray(kq), jnp.asarray(vq), pt, sl,
                        k_scales=jnp.asarray(ks))


def test_prefill_kernel_matches_dense_gather():
    """paged_prefill_attention (chunk queries x pages, absolute-position
    causal) vs the dense gather+softmax oracle, fp and int8 pools."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_prefill_attention)
    rng = np.random.default_rng(11)
    B, Hq, Hkv, C, D, P, ps, W = 2, 4, 2, 8, 16, 9, 8, 3
    start = 8  # second page
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, C, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(0, 1, (Hkv, P, ps, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(0, 1, (Hkv, P, ps, D)), jnp.float32)
    pt = jnp.asarray(rng.choice(np.arange(1, P), (B, W), replace=False),
                     jnp.int32)
    sl = jnp.asarray([start + C, start + 5], jnp.int32)

    got = paged_prefill_attention(q, kp, vp, pt, sl, start)

    # dense oracle
    S = W * ps
    k = jnp.swapaxes(kp[:, pt], 0, 1).reshape(B, Hkv, S, D)
    v = jnp.swapaxes(vp[:, pt], 0, 1).reshape(B, Hkv, S, D)
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, C, D)
    s = jnp.einsum("bhgcd,bhsd->bhgcs", qg, k) / np.sqrt(D)
    col = jnp.arange(S)[None, None, None, None, :]
    row = start + jnp.arange(C)[None, None, None, :, None]
    mask = (col <= row) & (col < jnp.asarray(sl)[:, None, None, None,
                                                 None])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bhgcs,bhsd->bhgcd", p, v).reshape(B, Hq, C, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # int8 pool path agrees with its own dequantized oracle
    def quant(x):
        x = np.asarray(x)
        sc = np.maximum(np.abs(x).max(-1), 1e-8) / 127.0
        qd = np.clip(np.round(x / sc[..., None]), -127, 127)
        return jnp.asarray(qd.astype(np.int8)), jnp.asarray(
            sc.astype(np.float32))
    kq, ks = quant(kp)
    vq, vs = quant(vp)
    got8 = paged_prefill_attention(q, kq, vq, pt, sl, start,
                                   k_scales=ks, v_scales=vs)
    want8 = paged_prefill_attention(
        q, kq.astype(jnp.float32) * ks[..., None],
        vq.astype(jnp.float32) * vs[..., None], pt, sl, start)
    np.testing.assert_allclose(np.asarray(got8), np.asarray(want8),
                               rtol=2e-5, atol=2e-5)


def test_prefix_cache_child_keys_die_with_parent():
    """Recycled page ids must never resurrect prefix chains. Under
    retention, freeing the last holder PARKS published pages in the
    evictable LRU (keys live, chains still matchable); only EVICTION
    recycles an id, and it takes every key chained through the page
    with it (the wrong-context-KV hazard) — children always before
    parents. A partially-failed admit recovers via free() + retry."""
    ps = 4
    cache = PagedKVCache(n_pages=8, page_size=ps, kv_heads=1, head_dim=8)
    X = list(range(10, 10 + ps))
    Y = list(range(20, 20 + ps))
    Z = list(range(30, 30 + ps))

    # A publishes X+Y; B publishes X+Z uncached-overlapping (collides on X)
    assert cache.acquire_prefix("A", X + Y) == 0
    cache.allocate("A", 2 * ps)
    cache.register_prefix("A", X + Y)
    assert cache.acquire_prefix("B", X + Z) == ps  # shares A's X page
    cache.allocate("B", 2 * ps)
    cache.register_prefix("B", X + Z)
    pX = cache.tables["A"][0]
    assert cache.tables["B"][0] == pX and cache._refs[pX] == 2

    # free both: the published pages are RETAINED (evictable), not
    # dropped — both chains still match for free
    cache.free("A")
    cache.free("B")
    assert pX in cache._evictable and pX not in cache._free
    assert cache.match_prefix(X + Y) == 2 * ps
    assert cache.match_prefix(X + Z) == 2 * ps

    # allocation pressure reclaims leaf-first: 7 usable pages, 3
    # evictable (X, Y-child, Z-child); taking 6 evicts the two LEAVES,
    # X survives as the most valuable (still-parenting) page
    cache.allocate("C", 6 * ps)
    assert cache.match_prefix(X + Y) == ps  # children gone...
    assert cache.match_prefix(X + Z) == ps
    assert cache.match_prefix(X) == ps      # ...parent still cached
    cache.free("C")

    # full pressure recycles X too; a new sequence publishing W under
    # X's recycled id must NOT make stale (X -> Y/Z) chains matchable
    cache.allocate("C", 7 * ps)
    assert cache.match_prefix(X) == 0
    cache.free("C")
    W = list(range(40, 40 + ps))
    assert cache.acquire_prefix("C", W) == 0
    cache.allocate("C", ps)
    cache.register_prefix("C", W)
    assert cache.acquire_prefix("D", W + Z) == ps  # only W matches
    # lengths bookkeeping: write() appends AFTER the cached prefix
    assert cache.lengths["D"] == ps

    # recovery contract: failed allocate -> free -> retry works
    cache.free("D")
    assert cache.acquire_prefix("D", W + Z) == ps
    with pytest.raises(MemoryError):
        cache.allocate("D", 100 * ps)
    cache.free("D")
    assert cache.acquire_prefix("D", W + Z) == ps  # no assert, no leak
    cache.free("D")
    # census invariant held throughout
    s = cache.cache_stats()
    assert s["resident_pages"] + s["evictable_pages"] \
        + s["free_pages"] == s["n_pages"]


# --- the whole pools addressed by (layer, page) ----------------------------

def _q8_np(x):
    x = np.asarray(x, np.float32)
    sc = np.maximum(np.abs(x).max(-1), 1e-8) / 127.0
    qd = np.clip(np.round(x / sc[..., None]), -127, 127)
    return jnp.asarray(qd.astype(np.int8)), jnp.asarray(
        sc.astype(np.float32))


@pytest.mark.parametrize("traced", [False, True],
                         ids=["int_layer", "traced_layer"])
@pytest.mark.parametrize("codec", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_whole_pool_read_at_a_layer(kind, codec, traced):
    """The kernels given the (L, Hkv, P, page, D) pools and a layer index
    — a Python int or a traced scalar — read that layer's pages: equal to
    the oracle there, and bit-identical to the 4-D call on ``pool[layer]``
    (the one-layer case of the same kernel)."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_prefill_attention)
    rng = np.random.default_rng(23)
    L, B, Hq, Hkv, D, P, ps, W, C = 3, 2, 4, 2, 16, 9, 8, 3, 8
    layer, start = 2, 8
    dt = jnp.bfloat16 if codec == "bf16" else jnp.float32
    kp = jnp.asarray(rng.normal(0, 1, (L, Hkv, P, ps, D)), dt)
    vp = jnp.asarray(rng.normal(0, 1, (L, Hkv, P, ps, D)), dt)
    pt = jnp.asarray(rng.choice(np.arange(1, P), (B, W), replace=False),
                     jnp.int32)
    if kind == "decode":
        q = jnp.asarray(rng.normal(0, 1, (B, Hq, D)), dt)
        sl = jnp.asarray([13, 16], jnp.int32)

        def kernel(k, v, **kw):
            return paged_attention(q, k, v, pt, sl, **kw)
    else:
        q = jnp.asarray(rng.normal(0, 1, (B, Hq, C, D)), dt)
        sl = jnp.asarray([start + C, start + 5], jnp.int32)

        def kernel(k, v, **kw):
            return paged_prefill_attention(q, k, v, pt, sl, start, **kw)

    scales = {}
    kf, vf = kp, vp                      # what the oracle reads
    if codec == "int8":
        (kp, ks), (vp, vs) = _q8_np(kp), _q8_np(vp)
        scales = dict(k_scales=ks, v_scales=vs)
        kf = kp.astype(jnp.float32) * ks[..., None]
        vf = vp.astype(jnp.float32) * vs[..., None]

    if traced:
        got = jax.jit(lambda i: kernel(kp, vp, layer=i, **scales))(
            jnp.int32(layer))
    else:
        got = kernel(kp, vp, layer=layer, **scales)
    one_layer = kernel(kp[layer], vp[layer],
                       **{n: s[layer] for n, s in scales.items()})
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(one_layer, np.float32))

    tol = 2e-2 if codec == "bf16" else 2e-5
    rows = [None] if kind == "decode" else range(C)
    for c in rows:                       # a chunk row = a decode position
        qc = q if c is None else q[:, :, c]
        lens = sl if c is None else jnp.minimum(sl, start + c + 1)
        want = paged_attention_reference(qc, kf[layer], vf[layer], pt,
                                         lens)
        np.testing.assert_allclose(
            np.asarray(got if c is None else got[:, :, c], np.float32),
            np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_layer_index_and_pool_rank_must_agree():
    rng = np.random.default_rng(29)
    q, kp, vp, pt = _setup(rng)
    sl = jnp.asarray([5, 9], jnp.int32)
    with pytest.raises(ValueError, match="whole"):
        paged_attention(q, kp, vp, pt, sl, layer=0)
    with pytest.raises(ValueError, match="need the layer"):
        paged_attention(q, kp[None], vp[None], pt, sl)


# --- the walk: the pages a row has, a block at a time ------------------------

@pytest.mark.parametrize("hkv", [1, 2], ids=["one_kv_head", "two_kv_heads"])
@pytest.mark.parametrize("codec", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_walk_visits_the_pages_a_row_has(kind, codec, hkv):
    """One batch with every shape of row the walk must serve, against the
    oracle, through the whole pools at a TRACED layer: idle slots (length
    1 on the reserved page 0, as ``_decode_batch`` leaves them) beside
    long rows; lengths on a page's edge, one past it, and inside the first
    page; live pages that are no multiple of the block; a row that fills
    its whole table (three blocks, the last one short); the kv heads a
    ``tp`` shard sees. The prefill entry runs the same walk with a taller
    query block, bounded by ``min(seq_len, start + chunk)``."""
    import importlib
    mod = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    rng = np.random.default_rng(31)
    ps, W, L, D, G, layer = 8, 19, 2, 16, 2, 1
    B = mod.BLOCK_PAGES
    assert W % B and W > 2 * B
    lens = [1, ps, ps + 1, 2 * ps, 1, 3, (B + 3) * ps - 3, B * ps,
            B * ps + 1, W * ps, 1]
    n = len(lens)
    P = 1 + sum(-(-ln // ps) for ln in lens)
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16,
          "int8": jnp.float32}[codec]
    kp = jnp.asarray(rng.normal(0, 1, (L, hkv, P, ps, D)), dt)
    vp = jnp.asarray(rng.normal(0, 1, (L, hkv, P, ps, D)), dt)
    pt = np.zeros((n, W), np.int32)       # idle rows: page 0 throughout
    free = list(rng.permutation(np.arange(1, P)))
    for i, ln in enumerate(lens):
        if ln > 1:
            pt[i, :-(-ln // ps)] = [free.pop() for _ in range(-(-ln // ps))]
    pt, sl = jnp.asarray(pt), jnp.asarray(lens, jnp.int32)

    scales, kf, vf = {}, kp, vp
    if codec == "int8":
        (kp, ks), (vp, vs) = _q8_np(kp), _q8_np(vp)
        scales = dict(k_scales=ks, v_scales=vs)
        kf = kp.astype(jnp.float32) * ks[..., None]
        vf = vp.astype(jnp.float32) * vs[..., None]
    tol = 2e-2 if codec == "bf16" else 2e-5

    if kind == "decode":
        q = jnp.asarray(rng.normal(0, 1, (n, hkv * G, D)), dt)
        got = jax.jit(lambda i: mod.paged_attention(
            q, kp, vp, pt, sl, layer=i, **scales))(jnp.int32(layer))
        want = paged_attention_reference(q, kf[layer], vf[layer], pt, sl)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        return
    C, start = ps, B * ps                 # the chunk opens the second block
    q = jnp.asarray(rng.normal(0, 1, (n, hkv * G, C, D)), dt)
    got = jax.jit(lambda i: mod.paged_prefill_attention(
        q, kp, vp, pt, sl, start, layer=i, **scales))(jnp.int32(layer))
    for c in range(C):
        want = paged_attention_reference(
            q[:, :, c], kf[layer], vf[layer], pt,
            jnp.minimum(sl, start + c + 1))
        np.testing.assert_allclose(np.asarray(got[:, :, c], np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


def test_an_empty_row_reads_nothing_and_returns_zeros():
    """Length 0 (no position to attend to): the walk still takes its one
    block, everything in it masked, and the row comes out 0, not NaN —
    whatever the buffer held from the row before."""
    rng = np.random.default_rng(37)
    q, kp, vp, pt = _setup(rng, B=3, P=10)
    sl = jnp.asarray([17, 0, 24], jnp.int32)
    got = np.asarray(paged_attention(q, kp * jnp.inf, vp, pt, sl * 0))
    assert not got.any()
    got = np.asarray(paged_attention(q, kp, vp, pt, sl))
    want = np.asarray(paged_attention_reference(q, kp, vp, pt, sl))
    assert not got[1].any()
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], rtol=2e-5,
                               atol=2e-5)
