"""Serving-path chip bench: paged vs dense decode + speculative speedup.

Chip-queue item complementing ladder_bench config 6 (dense compiled
decode). Same 0.44B-ish model; measures on the real chip:
  1. dense decode_step tokens/sec at B=8 (the ladder's serving shape)
  2. paged decode_step tokens/sec at the same shape (fp and int8
     pools) — the continuous-batching price/win vs the dense cache
  3. greedy speculative decoding wall-clock vs plain decode at equal
     output (draft = 2-layer slice config), with acceptance stats

Run: python tools/serving_bench.py
"""
from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import (LlamaConfig, LlamaForCausalLM,
                                       llama_paged_decode_factory)
    from paddle_tpu.models.nlp.llama_decode import (
        llama_decode_factory, llama_speculative_decode_factory)
    from paddle_tpu.ops.pallas.paged_attention import PagedKVCache

    on_tpu = jax.devices()[0].platform != "cpu"
    paddle.seed(0)
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                          intermediate_size=4096, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=12,
                          max_position_embeddings=2048,
                          dtype=jnp.bfloat16)
        B, prompt_len, new, ps = 8, 128, 128, 64
    else:
        cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                               kv_heads=2)
        B, prompt_len, new, ps = 2, 8, 8, 8
    model = LlamaForCausalLM(cfg)
    model.eval()
    if on_tpu:
        model.to(dtype="bfloat16")
    rng = np.random.default_rng(0)
    prompt = np.asarray(rng.integers(1, cfg.vocab_size, (B, prompt_len)),
                        np.int32)

    def emit(rec):
        rec["device"] = str(jax.devices()[0])
        print(json.dumps(rec), flush=True)

    # 1. dense decode (the ladder baseline, re-measured side by side).
    # gen() runs prefill + decode inside one call; the paged rows below
    # time decode ONLY — so the decode-only dense time is isolated by
    # differencing a full run against a 1-token run (both warmed).
    gen = llama_decode_factory(model, max_len=prompt_len + new)
    out = gen(jnp.asarray(prompt), max_new_tokens=new)
    _ = np.asarray(out)          # host readback sync (and compile)
    _ = np.asarray(gen(jnp.asarray(prompt), max_new_tokens=1))
    reps = 3 if on_tpu else 1

    def timed(n_tok):
        t0 = time.perf_counter()
        for _ in range(reps):
            o = gen(jnp.asarray(prompt), max_new_tokens=n_tok)
        _ = np.asarray(o)
        return (time.perf_counter() - t0) / reps

    dense_full_dt = timed(new)
    dense_one_dt = timed(1)
    dense_dt = dense_full_dt - dense_one_dt  # decode-only, new-1 steps
    dense_per_tok = dense_dt / max(1, new - 1)
    emit({"bench": "dense_decode", "B": B, "new": new,
          "tokens_per_sec": round(B * new / dense_full_dt, 1),
          "decode_only_tokens_per_sec": round(B / dense_per_tok, 1),
          "prefill_plus_1_s": round(dense_one_dt, 3)})

    # one-program greedy loop (round-5): the python loop above pays a
    # per-token host dispatch; this one pays one per call
    _ = gen.compiled(np.asarray(prompt), new)
    t0 = time.perf_counter()
    for _ in range(reps):
        _ = gen.compiled(np.asarray(prompt), new)
    dt_c = (time.perf_counter() - t0) / reps
    emit({"bench": "dense_decode_compiled", "B": B, "new": new,
          "tokens_per_sec": round(B * new / dt_c, 1),
          "vs_python_loop": round(dense_full_dt / dt_c, 2)})

    # 2. paged decode at the same shape (fp + int8 pools)
    npages_seq = -(-(prompt_len + new) // ps)
    pool_pages = B * npages_seq + 2
    for kv_dtype in (None, "int8"):
        o, l, pools, prefill, step, decode_n = llama_paged_decode_factory(
            model, page_size=ps, n_pool_pages=pool_pages,
            kv_cache_dtype=kv_dtype)
        book = PagedKVCache(pool_pages, ps,
                            cfg.num_key_value_heads,
                            cfg.hidden_size // cfg.num_attention_heads)
        for b in range(B):
            book.allocate(b, npages_seq * ps)
            book.lengths[b] = prompt_len
        pt, lens = book.batch_views(list(range(B)))
        T = ps * (-(-prompt_len // ps))
        toks = np.zeros((B, T), np.int64)
        toks[:, :prompt_len] = prompt
        nxt, pools = prefill(o, l, jnp.asarray(toks), pt, lens, pools)

        # (a) scan-amortized: all `new` steps inside ONE jit — the
        # factory's decode_n — measures the kernels; the per-step python
        # loop below adds one host dispatch per token. decode_n donates
        # its pools arg: thread the returned pools forward.
        _, nxt2, pools = decode_n(o, l, nxt, pt, lens, pools, new)
        _ = np.asarray(nxt2)
        t0 = time.perf_counter()
        _, nxt2, pools = decode_n(o, l, nxt, pt, lens, pools, new)
        _ = np.asarray(nxt2)
        dt_amort = time.perf_counter() - t0
        # vs dense DECODE-ONLY per-token time (prefill excluded on both
        # sides — the window-2 row compared against prefill+decode and
        # overstated the paged win)
        vs_dense = (dense_per_tok * new) / dt_amort
        emit({"bench": f"paged_decode_{kv_dtype or 'fp'}_amortized",
              "B": B, "new": new, "page_size": ps,
              "tokens_per_sec": round(B * new / dt_amort, 1),
              "vs_dense_decode_only": round(vs_dense, 3)})

        # (b) per-step loop (kept to quantify the per-token dispatch
        # cost next to the amortized number). decode_n's
        # trace does NOT warm decode_step's own jit cache — warm one
        # step first or its compile lands in dispatch_floor_ms.
        nxt, pools = step(o, l, nxt, pt, lens, pools)
        cur = lens + 1
        _ = np.asarray(nxt)
        t0 = time.perf_counter()
        for _ in range(new - 1):
            nxt, pools = step(o, l, nxt, pt, cur, pools)
            cur = cur + 1
        _ = np.asarray(nxt)
        dt = (time.perf_counter() - t0) / max(1, new - 1)
        emit({"bench": f"paged_decode_{kv_dtype or 'fp'}_per_step", "B": B,
              "new": new, "page_size": ps,
              "tokens_per_sec": round(B / dt, 1),
              "dispatch_floor_ms": round(
                  (dt - dt_amort / new) * 1e3, 2),
              "vs_dense_decode_only": round(
                  dense_per_tok / dt, 3)})

    # 3. speculative vs plain at equal (greedy) output, B=1
    draft_cfg = LlamaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size // 2,
        intermediate_size=cfg.intermediate_size // 2,
        num_hidden_layers=max(2, cfg.num_hidden_layers // 6),
        num_attention_heads=max(2, cfg.num_attention_heads // 2),
        num_key_value_heads=max(2, cfg.num_key_value_heads // 2),
        max_position_embeddings=cfg.max_position_embeddings,
        dtype=cfg.dtype) if on_tpu else LlamaConfig.tiny(
        vocab=97, hidden=16, layers=1, heads=2, kv_heads=1)
    draft = LlamaForCausalLM(draft_cfg)
    draft.eval()
    if on_tpu:
        draft.to(dtype="bfloat16")
    p1 = prompt[:1]
    out_plain = gen(jnp.asarray(p1), max_new_tokens=new)
    _ = np.asarray(out_plain)
    t0 = time.perf_counter()
    out_plain = gen(jnp.asarray(p1), max_new_tokens=new)
    _ = np.asarray(out_plain)
    plain_dt = time.perf_counter() - t0

    # Two drafts bracket the speculative mechanism: draft == target
    # gives 100% acceptance (the mechanical upper bound — what the
    # machinery costs when proposals are perfect), while the RANDOMLY
    # INITIALIZED half-size draft is the adversarial lower bound (~0
    # acceptance: untrained draft and target agree almost never, so
    # every round pays draft+verify for one emitted token — a
    # measurement artifact of random weights, not the mechanism;
    # trained draft/target pairs sit between the brackets).
    for tag, d in (("draft=target", model), ("random_half_draft", draft)):
        spec = llama_speculative_decode_factory(
            model, d, max_len=prompt_len + new + 8, n_draft=4)
        out_spec = np.asarray(spec(p1, max_new_tokens=new))  # warm
        t0 = time.perf_counter()
        out_spec = np.asarray(spec(p1, max_new_tokens=new))
        spec_dt = time.perf_counter() - t0
        match = bool((out_spec[:, :out_plain.shape[1]]
                      == np.asarray(out_plain)).all())
        emit({"bench": f"speculative_vs_plain[{tag}]", "new": new,
              "plain_s": round(plain_dt, 3), "spec_s": round(spec_dt, 3),
              "speedup": round(plain_dt / spec_dt, 2),
              "output_identical": match,
              "stats": getattr(spec, "last_stats", {})})


if __name__ == "__main__" and "b64" not in sys.argv:
    main()


def b64_ablation():
    """Round-4 verdict item 6b: the uniform-B=64 paged-vs-dense gap
    (2093 vs 3474 tok/s at page_size=64) ablated over page_size, to
    establish whether 0.6x dense is fundamental or a tile-size artifact.
    Dense baseline re-measured in the same process."""
    import os

    import jax
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import (LlamaConfig, LlamaForCausalLM,
                                       llama_paged_decode_factory)
    from paddle_tpu.models.nlp.llama_decode import llama_decode_factory
    from paddle_tpu.ops.pallas.paged_attention import PagedKVCache

    on_tpu = jax.devices()[0].platform != "cpu"
    paddle.seed(0)
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                          intermediate_size=4096, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=12,
                          max_position_embeddings=2048,
                          dtype=jnp.bfloat16)
        B, prompt_len, new = 64, 128, 128
        sizes = (256,) if "ps256" in sys.argv else (32, 64, 128)
    else:
        cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                               kv_heads=2)
        B, prompt_len, new = 4, 8, 8
        sizes = (8,)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if on_tpu:
        model.to(dtype="bfloat16")
    rng = np.random.default_rng(0)
    prompt = np.asarray(rng.integers(1, cfg.vocab_size, (B, prompt_len)),
                        np.int32)

    def emit(rec):
        rec["device"] = str(jax.devices()[0])
        print(json.dumps(rec), flush=True)

    # dense decode-only baseline (differenced, as in main())
    gen = llama_decode_factory(model, max_len=prompt_len + new)
    _ = np.asarray(gen(jnp.asarray(prompt), max_new_tokens=new))
    _ = np.asarray(gen(jnp.asarray(prompt), max_new_tokens=1))
    reps = 3 if on_tpu else 1

    def timed(n_tok):
        t0 = time.perf_counter()
        for _ in range(reps):
            o = gen(jnp.asarray(prompt), max_new_tokens=n_tok)
        _ = np.asarray(o)
        return (time.perf_counter() - t0) / reps

    dense_per_tok = (timed(new) - timed(1)) / max(1, new - 1)
    dense_tps = B / dense_per_tok
    emit({"bench": "b64_dense_decode_only", "B": B,
          "tokens_per_sec": round(dense_tps, 1)})

    for ps in sizes:
        npages_seq = -(-(prompt_len + new) // ps)
        pool_pages = B * npages_seq + 2
        try:
            o, l, pools, prefill, step, decode_n = \
                llama_paged_decode_factory(model, page_size=ps,
                                           n_pool_pages=pool_pages)
            book = PagedKVCache(pool_pages, ps, cfg.num_key_value_heads,
                                cfg.hidden_size
                                // cfg.num_attention_heads)
            for b in range(B):
                book.allocate(b, npages_seq * ps)
                book.lengths[b] = prompt_len
            pt, lens = book.batch_views(list(range(B)))
            T = ps * (-(-prompt_len // ps))
            toks = np.zeros((B, T), np.int64)
            toks[:, :prompt_len] = prompt
            nxt, pools = prefill(o, l, jnp.asarray(toks), pt, lens,
                                 pools)
            _, nxt2, pools = decode_n(o, l, nxt, pt, lens, pools, new)
            _ = np.asarray(nxt2)
            t0 = time.perf_counter()
            _, nxt2, pools = decode_n(o, l, nxt, pt, lens, pools, new)
            _ = np.asarray(nxt2)
            dt = time.perf_counter() - t0
            emit({"bench": "b64_paged_amortized", "B": B,
                  "page_size": ps, "new": new,
                  "tokens_per_sec": round(B * new / dt, 1),
                  "vs_dense_decode_only": round(
                      (B * new / dt) / dense_tps, 3)})
        except Exception as e:  # noqa: BLE001 — a failing size is a row
            emit({"bench": "b64_paged_amortized", "page_size": ps,
                  "error": repr(e)[-300:]})


if __name__ == "__main__" and "b64" in sys.argv:
    b64_ablation()
    sys.exit(0)
