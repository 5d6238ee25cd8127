"""llama_paged_decode_factory: compiled continuous-batching decode over
the paged KV pool must reproduce the eager model's greedy tokens — per
sequence, at RAGGED lengths in one batch."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.nlp.llama_decode import llama_paged_decode_factory
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache

PS = 8  # page size


def _greedy_eager(model, prompt, n):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n)
    return np.asarray(out.numpy())[0, len(prompt):]


def test_paged_decode_matches_eager_ragged():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    outer, layers, pools, prefill, decode_step, _ = \
        llama_paged_decode_factory(model, page_size=PS, n_pool_pages=16)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, 5).tolist(),
               rng.integers(1, 64, 3).tolist()]
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    T = PS  # pad prompts to one page
    toks = np.zeros((2, T), np.int64)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p

    # host page bookkeeping: 3 pages per sequence (room for 19 tokens)
    book = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2, head_dim=8)
    for i in range(2):
        book.allocate(i, 3 * PS)
    pt = jnp.asarray(np.stack([book.tables[0], book.tables[1]]),
                     jnp.int32)

    N = 6
    nxt, pools = prefill(outer, layers, jnp.asarray(toks), pt,
                         jnp.asarray(lengths), pools)
    got = [np.asarray(nxt)]
    lens = jnp.asarray(lengths)
    for _ in range(N - 1):
        nxt, pools = decode_step(outer, layers, nxt, pt, lens, pools)
        lens = lens + 1
        got.append(np.asarray(nxt))
    got = np.stack(got, 1)  # (B, N)

    for i, p in enumerate(prompts):
        want = _greedy_eager(model, p, N)
        np.testing.assert_array_equal(
            got[i], want, err_msg=f"sequence {i}")


def test_paged_decode_crosses_page_boundary():
    """Decode past a page edge: token PS lands in the second page and
    attention still sees the whole history."""
    paddle.seed(1)
    cfg = LlamaConfig.tiny(vocab=32, hidden=32, layers=1, heads=2,
                           kv_heads=1)
    model = LlamaForCausalLM(cfg)
    outer, layers, pools, prefill, decode_step, _ = \
        llama_paged_decode_factory(model, page_size=PS, n_pool_pages=8)
    prompt = list(range(1, PS))  # length 7: boundary hits mid-decode
    book = PagedKVCache(n_pages=8, page_size=PS, kv_heads=1, head_dim=16)
    book.allocate(0, 2 * PS)
    pt = jnp.asarray([book.tables[0]], jnp.int32)
    toks = jnp.asarray(np.asarray(prompt + [0])[None])
    lens = jnp.asarray([len(prompt)], jnp.int32)

    N = 5  # positions 7..11 — crosses into page 2 at position 8
    nxt, pools = prefill(outer, layers, toks, pt, lens, pools)
    got = [int(nxt[0])]
    for _ in range(N - 1):
        nxt, pools = decode_step(outer, layers, nxt, pt, lens, pools)
        lens = lens + 1
        got.append(int(nxt[0]))

    want = _greedy_eager(model, prompt, N)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_chunked_prefill_matches_oneshot():
    """Chunked prefill (C-token chunks attending through the pool) must
    produce the same next token and the same subsequent decode stream as
    the one-shot prefill."""
    paddle.seed(2)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    from paddle_tpu.models.nlp.llama_decode import (
        llama_paged_decode_factory as factory)
    o1, l1, pools1, prefill1, decode1, *_ = factory(model, page_size=PS,
                                                n_pool_pages=16)
    o2, l2, pools2, prefill2, decode2, *_ = factory(model, page_size=PS,
                                                n_pool_pages=16,
                                                chunked_prefill=PS)
    # chunk = 2 pages: exercises the multi-page scatter (npg > 1)
    o3, l3, pools3, prefill3, decode3, *_ = factory(model, page_size=PS,
                                                n_pool_pages=16,
                                                chunked_prefill=2 * PS)

    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 64, 14).tolist(),
               rng.integers(1, 64, 9).tolist()]
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    T = 2 * PS  # two chunks
    toks = np.zeros((2, T), np.int64)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    book = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2, head_dim=8)
    for i in range(2):
        book.allocate(i, 3 * PS)
    pt = jnp.asarray(np.stack([book.tables[0], book.tables[1]]),
                     jnp.int32)

    n1, pools1 = prefill1(o1, l1, jnp.asarray(toks), pt, lengths, pools1)
    n2, pools2 = prefill2(o2, l2, jnp.asarray(toks), pt, lengths, pools2)
    n3, pools3 = prefill3(o3, l3, jnp.asarray(toks), pt, lengths, pools3)
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n3))
    lens = lengths
    for _ in range(4):
        n1, pools1 = decode1(o1, l1, n1, pt, lens, pools1)
        n2, pools2 = decode2(o2, l2, n2, pt, lens, pools2)
        lens = lens + 1
        np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))


def test_int8_pool_decode_close_to_fp():
    """kv_cache_dtype='int8' on the paged path: greedy tokens match the
    fp pools on a short horizon (the dense cache's int8 bar) and the
    pools really store int8."""
    paddle.seed(5)
    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    from paddle_tpu.models.nlp.llama_decode import (
        llama_paged_decode_factory as factory)
    mk = lambda **kw: factory(model, page_size=PS, n_pool_pages=16, **kw)
    o1, l1, pools_f, pre_f, dec_f, *_ = mk()
    o2, l2, pools_q, pre_q, dec_q, *_ = mk(kv_cache_dtype="int8")
    assert pools_q[0][0].dtype == jnp.int8

    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 64, 6).tolist(),
               rng.integers(1, 64, 4).tolist()]
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    toks = np.zeros((2, PS), np.int64)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    book = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2,
                        head_dim=16)
    for i in range(2):
        book.allocate(i, 2 * PS)
    pt = jnp.asarray(np.stack([book.tables[0], book.tables[1]]),
                     jnp.int32)

    nf, pools_f = pre_f(o1, l1, jnp.asarray(toks), pt, lengths, pools_f)
    nq, pools_q = pre_q(o2, l2, jnp.asarray(toks), pt, lengths, pools_q)
    np.testing.assert_array_equal(np.asarray(nf), np.asarray(nq))
    lens = lengths
    for _ in range(5):
        nf, pools_f = dec_f(o1, l1, nf, pt, lens, pools_f)
        nq, pools_q = dec_q(o2, l2, nq, pt, lens, pools_q)
        lens = lens + 1
        np.testing.assert_array_equal(np.asarray(nf), np.asarray(nq))


def test_emit_logits_mode():
    """emit='logits': the serving loop owns sampling; argmax over the
    emitted logits must reproduce the token-mode stream."""
    paddle.seed(6)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    from paddle_tpu.models.nlp.llama_decode import (
        llama_paged_decode_factory as factory)
    o1, l1, p1, pre_t, dec_t, *_ = factory(model, page_size=PS,
                                       n_pool_pages=16)
    o2, l2, p2, pre_l, dec_l, *_ = factory(model, page_size=PS,
                                       n_pool_pages=16, emit="logits")

    rng = np.random.default_rng(6)
    toks = np.zeros((1, PS), np.int64)
    toks[0, :5] = rng.integers(1, 64, 5)
    lens = jnp.asarray([5], jnp.int32)
    book = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2, head_dim=8)
    book.allocate(0, 2 * PS)
    pt = jnp.asarray([book.tables[0]], jnp.int32)

    nt, p1 = pre_t(o1, l1, jnp.asarray(toks), pt, lens, p1)
    lg, p2 = pre_l(o2, l2, jnp.asarray(toks), pt, lens, p2)
    assert lg.shape == (1, 64)
    assert int(np.argmax(np.asarray(lg), -1)[0]) == int(nt[0])
    for _ in range(3):
        nt, p1 = dec_t(o1, l1, nt, pt, lens, p1)
        tok_from_logits = jnp.argmax(lg, -1)
        lg, p2 = dec_l(o2, l2, tok_from_logits, pt, lens, p2)
        lens = lens + 1
        assert int(np.argmax(np.asarray(lg), -1)[0]) == int(nt[0])


def test_prefill_kernel_mode_matches_gather():
    """prefill_attention='kernel' routes chunk attention through the
    Pallas paged prefill kernel; the token stream must equal the
    gather path, fp and int8."""
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    from paddle_tpu.models.nlp.llama_decode import (
        llama_paged_decode_factory as factory)
    for kv_dtype in (None, "int8"):
        mk = lambda pa: factory(model, page_size=PS, n_pool_pages=16,
                                chunked_prefill=PS,
                                kv_cache_dtype=kv_dtype,
                                prefill_attention=pa)
        o1, l1, p1, pre_g, dec_g, *_ = mk("gather")
        o2, l2, p2, pre_k, dec_k, *_ = mk("kernel")
        rng = np.random.default_rng(8)
        toks = np.zeros((2, 2 * PS), np.int64)
        toks[0, :11] = rng.integers(1, 64, 11)
        toks[1, :14] = rng.integers(1, 64, 14)
        lens = jnp.asarray([11, 14], jnp.int32)
        book = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2,
                            head_dim=8)
        for i in range(2):
            book.allocate(i, 3 * PS)
        pt = jnp.asarray(np.stack([book.tables[0], book.tables[1]]),
                         jnp.int32)
        ng, p1 = pre_g(o1, l1, jnp.asarray(toks), pt, lens, p1)
        nk, p2 = pre_k(o2, l2, jnp.asarray(toks), pt, lens, p2)
        np.testing.assert_array_equal(np.asarray(ng), np.asarray(nk))
        cur = lens
        for _ in range(3):
            ng, p1 = dec_g(o1, l1, ng, pt, cur, p1)
            nk, p2 = dec_k(o2, l2, nk, pt, cur, p2)
            cur = cur + 1
            np.testing.assert_array_equal(np.asarray(ng), np.asarray(nk))


def test_prefix_cache_reuses_pages_and_skips_chunks():
    """vLLM-style prefix caching: a second request sharing a full-page
    prompt prefix acquires the cached pages (refcounted) and resumes
    prefill past them — tokens equal the uncached run exactly."""
    paddle.seed(9)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    from paddle_tpu.models.nlp.llama_decode import (
        llama_paged_decode_factory as factory)
    o, l, pools, prefill, decode, *_ = factory(model, page_size=PS,
                                           n_pool_pages=16,
                                           chunked_prefill=PS)
    rng = np.random.default_rng(10)
    shared = rng.integers(1, 64, PS).tolist()        # one full page
    tailA = rng.integers(1, 64, 3).tolist()
    tailB = rng.integers(1, 64, 5).tolist()
    book = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2, head_dim=8)

    def run(sid, prompt, resume):
        T = 2 * PS
        toks = np.zeros((1, T), np.int64)
        toks[0, :len(prompt)] = prompt
        book.allocate(sid, 3 * PS)
        pt = jnp.asarray([book.tables[sid]], jnp.int32)
        lens = jnp.asarray([len(prompt)], jnp.int32)
        book.lengths[sid] = len(prompt)
        nxt, p = prefill(o, l, jnp.asarray(toks), pt, lens,
                         pools_box[0], resume_from=resume)
        pools_box[0] = p
        out = [int(nxt[0])]
        cur = lens
        for _ in range(3):
            nxt, pools_box[0] = decode(o, l, nxt, pt, cur, pools_box[0])
            cur = cur + 1
            out.append(int(nxt[0]))
        return out

    pools_box = [pools]

    # request A: no cache; publish its prompt pages
    promptA = shared + tailA
    nc = book.acquire_prefix("A", promptA)
    assert nc == 0
    outA = run("A", promptA, resume=0)
    book.register_prefix("A", promptA)

    # request B: same first page — acquire + resume past it
    promptB = shared + tailB
    ncB = book.acquire_prefix("B", promptB)
    assert ncB == PS
    assert book.tables["B"][0] == book.tables["A"][0]  # SHARED page
    assert book._refs[book.tables["A"][0]] == 2
    outB = run("B", promptB, resume=ncB)

    # oracle: B uncached in a fresh book/pools
    o2, l2, pools2, prefill2, decode2, *_ = factory(model, page_size=PS,
                                                n_pool_pages=16,
                                                chunked_prefill=PS)
    book2 = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2,
                         head_dim=8)
    pools_box2 = [pools2]

    def run2(prompt):
        T = 2 * PS
        toks = np.zeros((1, T), np.int64)
        toks[0, :len(prompt)] = prompt
        book2.allocate("x", 3 * PS)
        pt = jnp.asarray([book2.tables["x"]], jnp.int32)
        lens = jnp.asarray([len(prompt)], jnp.int32)
        nxt, pools_box2[0] = prefill2(o2, l2, jnp.asarray(toks), pt,
                                      lens, pools_box2[0])
        out = [int(nxt[0])]
        cur = lens
        for _ in range(3):
            nxt, pools_box2[0] = decode2(o2, l2, nxt, pt, cur,
                                         pools_box2[0])
            cur = cur + 1
            out.append(int(nxt[0]))
        return out

    np.testing.assert_array_equal(outB, run2(promptB))

    # freeing A keeps the shared page alive for B; freeing B parks the
    # published page in the evictable LRU (retention) — it stays
    # matchable until allocation pressure reclaims it
    page = book.tables["A"][0]
    book.free("A")
    assert book._refs[page] == 1 and page not in book._free
    book.free("B")
    assert page in book._evictable and page not in book._free
    assert book.match_prefix(shared) == PS


def test_fixed_shape_batching_never_recompiles():
    """The serving property the paged design promises: one compiled
    decode executable serves every mix of live/pad slots — page tables
    and lengths are data, not shapes (pad slots: length 0, page 0)."""
    paddle.seed(11)
    cfg = LlamaConfig.tiny(vocab=32, hidden=32, layers=1, heads=2,
                           kv_heads=1)
    model = LlamaForCausalLM(cfg)
    from paddle_tpu.models.nlp.llama_decode import (
        llama_paged_decode_factory as factory)
    o, l, pools, prefill, decode, *_ = factory(model, page_size=PS,
                                           n_pool_pages=8)
    B, W = 2, 2
    toks = jnp.asarray(np.ones((B, PS), np.int64))
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([5, 3], jnp.int32)
    nxt, pools = prefill(o, l, toks, pt, lens, pools)
    mixes = [  # (page_tables, lengths, tokens) — shapes identical
        (pt, lens, nxt),
        (jnp.asarray([[1, 2], [0, 0]], jnp.int32),
         jnp.asarray([6, 0], jnp.int32), nxt),          # slot 1 empty
        (jnp.asarray([[5, 6], [3, 4]], jnp.int32),
         jnp.asarray([1, 7], jnp.int32), nxt),          # new request
    ]
    for ptx, lnx, tok in mixes:
        out, pools = decode(o, l, tok, ptx, lnx, pools)
        assert np.isfinite(np.asarray(out)).all() or True  # int tokens
    assert decode._cache_size() == 1, decode._cache_size()


def test_decode_n_matches_per_step_loop():
    """The factory's scan-amortized decode_n (n steps in ONE compiled
    program — the serving loop's dispatch amortizer) must emit exactly
    the per-step decode_step tokens and leave identical pools."""
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    outer, layers, pools, prefill, decode_step, decode_n = \
        llama_paged_decode_factory(model, page_size=PS, n_pool_pages=16)

    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 64, 5).tolist(),
               rng.integers(1, 64, 3).tolist()]
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    toks = np.zeros((2, PS), np.int64)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    book = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2, head_dim=8)
    for i in range(2):
        book.allocate(i, 3 * PS)
    pt = jnp.asarray(np.stack([book.tables[0], book.tables[1]]),
                     jnp.int32)

    N = 5
    nxt, pools = prefill(outer, layers, jnp.asarray(toks), pt,
                         jnp.asarray(lengths), pools)

    # per-step reference (fresh pools for the scan run: deep-copy now)
    import jax
    pools_scan = jax.tree.map(jnp.copy, pools)
    ref_nxt, lens = nxt, jnp.asarray(lengths)
    ref = []
    pools_ref = pools
    for _ in range(N):
        ref_nxt, pools_ref = decode_step(outer, layers, ref_nxt, pt,
                                         lens, pools_ref)
        lens = lens + 1
        ref.append(np.asarray(ref_nxt))
    ref = np.stack(ref, 0)  # (N, B)

    emits, last, pools_scan = decode_n(outer, layers, nxt, pt,
                                       jnp.asarray(lengths), pools_scan,
                                       N)
    np.testing.assert_array_equal(np.asarray(emits), ref)
    np.testing.assert_array_equal(np.asarray(last), ref[-1])
    for a, b in zip(jax.tree.leaves(pools_scan),
                    jax.tree.leaves(pools_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_decode_n_logits_mode_greedy_feedback():
    """decode_n with emit="logits": per-step logits stack to (N, B, V),
    the greedy-argmax feedback reproduces token-mode output, and an
    int64 seed token (np.argmax default) doesn't break the scan carry."""
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 64, 6).tolist()
    lengths = jnp.asarray(np.asarray([len(prompt)], np.int32))
    toks = np.zeros((1, PS), np.int64)
    toks[0, :len(prompt)] = prompt

    def fresh_table():
        book = PagedKVCache(n_pages=16, page_size=PS, kv_heads=2,
                            head_dim=8)
        book.allocate(0, 3 * PS)
        return jnp.asarray(np.stack([book.tables[0]]), jnp.int32)

    # token mode reference
    outer, layers, pools, prefill, _, decode_n = \
        llama_paged_decode_factory(model, page_size=PS, n_pool_pages=16)
    pt = fresh_table()
    tok0, pools = prefill(outer, layers, jnp.asarray(toks), pt, lengths,
                          pools)
    tok0_np = np.asarray(tok0)
    emits_t, last_t, _ = decode_n(outer, layers, tok0, pt, lengths,
                                  pools, 4)

    # logits mode: caller-side greedy, int64 seed on purpose
    outer, layers, pools, prefill, _, decode_n = \
        llama_paged_decode_factory(model, page_size=PS, n_pool_pages=16,
                                   emit="logits")
    pt = fresh_table()
    logits0, pools = prefill(outer, layers, jnp.asarray(toks), pt,
                             lengths, pools)
    tok0_l = np.argmax(np.asarray(logits0), -1)
    assert tok0_l.dtype == np.int64
    np.testing.assert_array_equal(tok0_l.astype(np.int32), tok0_np)
    emits_l, last_l, _ = decode_n(outer, layers, jnp.asarray(tok0_l),
                                  pt, lengths, pools, 4)

    assert np.asarray(emits_l).shape == (4, 1, 64)
    np.testing.assert_array_equal(np.argmax(np.asarray(emits_l), -1),
                                  np.asarray(emits_t))
    np.testing.assert_array_equal(np.asarray(last_l),
                                  np.asarray(last_t))


# --- the pools ride the layer loop's carry, never its xs / ys ---------------

def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    import jax
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("codec", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("program", ["decode_n", "decode_step",
                                     "prefill_chunk", "ragged_chunk",
                                     "oneshot_prefill"])
def test_no_scan_slices_or_stacks_the_pools(program, codec):
    """A scan slices every layer out of its ``xs`` and stacks its ``ys``
    into a fresh buffer: a pool there is a copy of the pool a call,
    whatever is donated (the 1.08 GB the serving cells copied twice a
    call). So in every paged program's jaxpr the pools — and a layer's
    pages of them — appear among no scan's scanned inputs or stacked
    outputs; they ride a carry, which the loop updates in place."""
    import jax
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=3, heads=4,
                           kv_heads=2)
    n_pages, B, W = 23, 2, 4
    outer, layers, pools, prefill, decode_step, decode_n = \
        llama_paged_decode_factory(
            LlamaForCausalLM(cfg), page_size=PS, n_pool_pages=n_pages,
            kv_cache_dtype=codec,
            chunked_prefill=None if program == "oneshot_prefill" else PS)
    pt = jnp.zeros((B, W), jnp.int32)
    lens = jnp.ones((B,), jnp.int32)
    tok = jnp.zeros((B,), jnp.int32)
    chunk = jnp.zeros((B, PS), jnp.int32)
    x_last = jnp.zeros((B, cfg.hidden_size), jnp.float32)
    if program == "decode_n":
        jaxpr = jax.make_jaxpr(decode_n, static_argnums=(6,))(
            outer, layers, tok, pt, lens, pools, 3)
    elif program == "decode_step":
        jaxpr = jax.make_jaxpr(decode_step)(outer, layers, tok, pt, lens,
                                            pools)
    elif program == "prefill_chunk":
        jaxpr = jax.make_jaxpr(prefill._jit_inner[0])(
            outer, layers, chunk, 0, pt, lens, pools, x_last)
    elif program == "ragged_chunk":
        jaxpr = jax.make_jaxpr(prefill._ragged._jit_inner[0])(
            outer, layers, chunk, jnp.zeros((B,), jnp.int32), pt, lens,
            pools, x_last)
    else:
        jaxpr = jax.make_jaxpr(prefill)(outer, layers, chunk, pt, lens,
                                        pools)

    page_dims = {(2, n_pages, PS, 8), (2, n_pages, PS)}   # data, scales

    def of_the_pools(v):
        shape = tuple(v.aval.shape)
        return shape[-4:] in page_dims or shape[-3:] in page_dims

    scans = list(_scans(jaxpr.jaxpr))
    assert scans                                   # the layer loop is one
    carried = 0
    for eqn in scans:
        fixed = eqn.params["num_consts"] + eqn.params["num_carry"]
        xs, ys = eqn.invars[fixed:], eqn.outvars[eqn.params["num_carry"]:]
        assert not [v.aval for v in xs if of_the_pools(v)], "pools in xs"
        assert not [v.aval for v in ys if of_the_pools(v)], "pools in ys"
        carried += sum(map(of_the_pools,
                           eqn.invars[eqn.params["num_consts"]:fixed]))
    assert carried >= len(jax.tree_util.tree_leaves(pools))
