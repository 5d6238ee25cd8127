"""Speculative decoding with a REAL (trained) draft — chip bench.

Round-4 verdict item 7: the spec-decode rows were mechanism-only
(draft=target accepted perfectly yet measured 0.33x plain because every
round paid 2 host dispatches; a random draft accepts
~0). This bench closes both gaps:

  1. the compiled speculative loop (generate.compiled — the whole
     draft/verify/accept cycle as host-redispatched lax.scan chunks,
     a handful of dispatches per call, same greedy-exact output), and
  2. a draft that genuinely approximates the target: both models train
     on a deterministic synthetic task (fixed random permutation
     next-token map over a 256-id sub-vocabulary) until the mapping is
     learned, so the 9x-smaller draft proposes what the target would
     emit and acceptance is earned, not assumed.

Emits one JSON line per row. Run:
  python tools/spec_decode_bench.py

Modes:
  (default)        train target+draft, measure python-loop / compiled
                   plain / compiled spec; emits the canonical
                   "spec_vs_plain_compiled" summary row that
                   tools/bench_gate.py serving gates.
  --small          the 23M/6M pair (fast chip sanity scale).
  --compile-044b   build the 0.44B target + 46M draft (untrained) and
                   measure COMPILE time + module size of the plain and
                   speculative programs under scan_layers=True, plus the
                   unrolled-layers module size for the L x comparison.
                   The spec program carries weights as jit ARGUMENTS
                   (not closure constants), so its module is ~100 KB at
                   any model size — this is the row that shows the
                   0.44B spec program compiling (round-5 it hung the
                   remote compiler >35 min carrying ~1 GB of inline
                   weight constants).
  --no-compiled    escape hatch: skip the compiled loops and measure
                   the python loop only (the scan-layers + args program
                   is expected to compile everywhere).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SUB_V = 256  # task sub-vocabulary (ids 1..256): memorizable quickly


def _task_batch(rng, perm, B, S):
    """Sequences following next = perm[cur] (ids offset by 1 to avoid
    token 0). Returns (tokens, labels) position-aligned for the train
    factories (callers of the task shift by construction here)."""
    starts = rng.integers(0, SUB_V, B)
    seq = np.empty((B, S + 1), np.int64)
    seq[:, 0] = starts
    for t in range(S):
        seq[:, t + 1] = perm[seq[:, t]]
    seq += 1
    return seq[:, :-1], seq[:, 1:]


def _train(model, mesh, perm, steps, B, S, lr, label):
    import jax.numpy as jnp

    from paddle_tpu.models.nlp.llama import llama_train_step_factory
    params, opt, step, _ = llama_train_step_factory(
        model, mesh, learning_rate=lr, remat=False)
    rng = np.random.default_rng(0)
    loss = None
    t0 = time.perf_counter()
    for i in range(steps):
        tok, lab = _task_batch(rng, perm, B, S)
        params, opt, loss = step(params, opt, jnp.asarray(tok, jnp.int32),
                                 jnp.asarray(lab, jnp.int32))
    lv = float(loss)
    # write the trained weights back into the model for the decode
    # factories (they read model.state_dict())
    model.load_tree({k: v for k, v in params.items()})
    return lv, time.perf_counter() - t0


def main():
    import jax
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama_decode import (
        llama_decode_factory, llama_speculative_decode_factory)

    on_tpu = jax.devices()[0].platform != "cpu"
    paddle.seed(0)
    if on_tpu:
        tgt_cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                              intermediate_size=4096,
                              num_hidden_layers=12,
                              num_attention_heads=12,
                              num_key_value_heads=12,
                              max_position_embeddings=2048,
                              dtype=jnp.bfloat16)
        drf_cfg = LlamaConfig(vocab_size=32000, hidden_size=512,
                              intermediate_size=1408,
                              num_hidden_layers=4,
                              num_attention_heads=8,
                              num_key_value_heads=8,
                              max_position_embeddings=2048,
                              dtype=jnp.bfloat16)
        steps_t, steps_d, B, S = 150, 300, 16, 256
        prompt_len, new = 32, 128
        drafts = (4, 8)
    else:
        tgt_cfg = LlamaConfig.tiny(vocab=300, hidden=64, layers=2,
                                   heads=4)
        drf_cfg = LlamaConfig.tiny(vocab=300, hidden=32, layers=1,
                                   heads=2)
        steps_t, steps_d, B, S = 60, 60, 8, 32
        prompt_len, new = 8, 16
        drafts = (4,)

    rng = np.random.default_rng(7)
    perm = rng.permutation(SUB_V)

    def emit(rec):
        rec["device"] = str(jax.devices()[0])
        print(json.dumps(rec), flush=True)

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    target = LlamaForCausalLM(tgt_cfg)
    draft = LlamaForCausalLM(drf_cfg)
    if on_tpu:
        target.to(dtype="bfloat16")
        draft.to(dtype="bfloat16")
    lt, tt = _train(target, mesh, perm, steps_t, B, S, 3e-4, "target")
    ld, td = _train(draft, mesh, perm, steps_d, B, S, 1e-3, "draft")
    n_t = sum(int(np.prod(p.shape)) for p in
              target.state_dict().values())
    n_d = sum(int(np.prod(p.shape)) for p in draft.state_dict().values())
    emit({"bench": "spec_distill_train", "target_loss": round(lt, 4),
          "draft_loss": round(ld, 4), "target_params": n_t,
          "draft_params": n_d,
          "size_ratio": round(n_t / n_d, 1),
          "train_s": round(tt + td, 1)})
    target.eval()
    draft.eval()

    # task-distribution prompt
    ptok, _ = _task_batch(np.random.default_rng(99), perm, 1,
                          prompt_len)
    prompt = ptok[:, :prompt_len].astype(np.int32)

    max_len = prompt_len + new + 32
    gen = llama_decode_factory(target, max_len=max_len)
    plain = np.asarray(gen(jnp.asarray(prompt), max_new_tokens=new))
    reps = 3 if on_tpu else 1
    t0 = time.perf_counter()
    for _ in range(reps):
        plain = np.asarray(gen(jnp.asarray(prompt), max_new_tokens=new))
    plain_dt = (time.perf_counter() - t0) / reps
    emit({"bench": "spec_plain_decode", "new": new,
          "s": round(plain_dt, 3),
          "tokens_per_sec": round(new / plain_dt, 1)})

    # --no-compiled must skip EVERY compiled loop (the plain baseline
    # compiles the same class of program as the spec loop)
    skip_compiled = "--no-compiled" in sys.argv
    if not skip_compiled:
        # compiled plain (gen.compiled): the FAIR baseline for compiled
        # spec — both loops then sit on the same dispatch floor. First
        # call = compile + run; steady state measured after.
        t0 = time.perf_counter()
        plain_c = gen.compiled(prompt, new)
        plain_compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            plain_c = gen.compiled(prompt, new)
        plain_c_dt = (time.perf_counter() - t0) / reps
        emit({"bench": "plain_compiled", "new": new,
              "compile_s": round(plain_compile_s, 2),
              "s": round(plain_c_dt, 3),
              "tokens_per_sec": round(new / plain_c_dt, 1),
              "vs_python_loop": round(plain_dt / plain_c_dt, 2),
              "matches_python": bool((plain_c == plain).all())})

    for nd in drafts:
        spec = llama_speculative_decode_factory(target, draft,
                                                max_len=max_len,
                                                n_draft=nd)
        if skip_compiled:
            # explicit escape hatch only: with weights passed as jit
            # arguments (module ~100 KB at any size) + scanned layers,
            # the spec program is expected to compile everywhere the
            # plain scan does — the round-5 hang was the closure-
            # constant module, not the model
            emit({"bench": "spec_compiled_distilled", "n_draft": nd,
                  "skipped": "--no-compiled passed"})
        else:
            try:
                t0 = time.perf_counter()
                out = spec.compiled(prompt, max_new_tokens=new)
                spec_compile_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = spec.compiled(prompt, max_new_tokens=new)
                dt = (time.perf_counter() - t0) / reps
                matches = bool((out[:, :plain.shape[1]] == plain).all())
                emit({"bench": "spec_compiled_distilled", "n_draft": nd,
                      "new": new, "s": round(dt, 3),
                      "compile_s": round(spec_compile_s, 2),
                      "speedup_vs_plain": round(plain_dt / dt, 2),
                      "output_matches_plain": matches,
                      "stats": spec.compiled.last_stats})
                # the canonical serving row bench_gate.py gates
                emit({"bench": "spec_vs_plain_compiled", "n_draft": nd,
                      "new": new,
                      "plain_tok_s": round(new / plain_c_dt, 1),
                      "spec_tok_s": round(new / dt, 1),
                      "ratio": round(plain_c_dt / dt, 3),
                      "compile_s_plain": round(plain_compile_s, 2),
                      "compile_s_spec": round(spec_compile_s, 2),
                      "output_matches_plain": matches,
                      "stats": spec.compiled.last_stats})
                continue
            except Exception as e:  # noqa: BLE001 — a compile
                # failure is a real failure mode; fall through to the
                # python loop so the ACCEPTANCE evidence still lands,
                # and emit the summary row with the error so the
                # serving gate FAILS instead of silently skipping
                emit({"bench": "spec_compiled_distilled", "n_draft": nd,
                      "error": repr(e)[-250:]})
                emit({"bench": "spec_vs_plain_compiled", "n_draft": nd,
                      "error": repr(e)[-250:]})
        out = spec(prompt, max_new_tokens=new)
        t0 = time.perf_counter()
        out = spec(prompt, max_new_tokens=new)
        dt = time.perf_counter() - t0
        emit({"bench": "spec_python_loop_distilled", "n_draft": nd,
              "new": new, "s": round(dt, 3),
              "speedup_vs_plain": round(plain_dt / dt, 2),
              "output_matches_plain": bool(
                  (out[:, :plain.shape[1]] == plain).all()),
              "stats": spec.last_stats,
              "note": "two host dispatches per round; "
                      "acceptance is the distillation evidence"})


_MODES = ("--small", "--compile-044b")

if __name__ == "__main__" and not any(m in sys.argv for m in _MODES):
    main()


def small_mode():
    """--small: a 4-layer scale that compiles in well under a minute.
    Both
    decode loops are compiled here — plain gen.compiled (greedy
    lax.scan) vs spec generate.compiled (scan chunks) — so the
    comparison has no dispatch-floor asymmetry, and both models are
    TRAINED so acceptance is earned."""
    import os

    import jax
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama_decode import (
        llama_decode_factory, llama_speculative_decode_factory)

    on_tpu = jax.devices()[0].platform != "cpu"
    paddle.seed(0)
    tgt_cfg = LlamaConfig(vocab_size=32000, hidden_size=512,
                          intermediate_size=1408, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024,
                          dtype=jnp.bfloat16)
    drf_cfg = LlamaConfig(vocab_size=32000, hidden_size=256,
                          intermediate_size=704, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=1024,
                          dtype=jnp.bfloat16)
    steps_t, steps_d, B, S = (200, 300, 16, 256) if on_tpu \
        else (30, 30, 8, 32)
    prompt_len, new = (32, 128) if on_tpu else (8, 16)

    rng = np.random.default_rng(7)
    perm = rng.permutation(SUB_V)

    def emit(rec):
        rec["device"] = str(jax.devices()[0])
        print(json.dumps(rec), flush=True)

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    target = LlamaForCausalLM(tgt_cfg)
    draft = LlamaForCausalLM(drf_cfg)
    if on_tpu:
        target.to(dtype="bfloat16")
        draft.to(dtype="bfloat16")
    lt, _ = _train(target, mesh, perm, steps_t, B, S, 1e-3, "target")
    ld, _ = _train(draft, mesh, perm, steps_d, B, S, 1e-3, "draft")
    n_t = sum(int(np.prod(p.shape)) for p in
              target.state_dict().values())
    n_d = sum(int(np.prod(p.shape)) for p in draft.state_dict().values())
    emit({"bench": "spec_small_train", "target_loss": round(lt, 4),
          "draft_loss": round(ld, 4), "size_ratio": round(n_t / n_d, 1)})
    target.eval()
    draft.eval()

    ptok, _ = _task_batch(np.random.default_rng(99), perm, 1, prompt_len)
    prompt = ptok[:, :prompt_len].astype(np.int32)
    max_len = prompt_len + new + 32
    reps = 5 if on_tpu else 1

    gen = llama_decode_factory(target, max_len=max_len)
    plain_py = np.asarray(gen(jnp.asarray(prompt), max_new_tokens=new))
    t0 = time.perf_counter()
    for _ in range(reps):
        plain_py = np.asarray(gen(jnp.asarray(prompt),
                                  max_new_tokens=new))
    py_dt = (time.perf_counter() - t0) / reps
    emit({"bench": "small_plain_python_loop", "s": round(py_dt, 3),
          "tokens_per_sec": round(new / py_dt, 1)})

    t0 = time.perf_counter()
    plain_c = gen.compiled(prompt, new)
    plain_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        plain_c = gen.compiled(prompt, new)
    c_dt = (time.perf_counter() - t0) / reps
    emit({"bench": "small_plain_compiled", "s": round(c_dt, 3),
          "compile_s": round(plain_compile_s, 2),
          "tokens_per_sec": round(new / c_dt, 1),
          "vs_python_loop": round(py_dt / c_dt, 2),
          "matches_python": bool((plain_c == plain_py).all())})

    for nd in ((4, 8) if on_tpu else (4,)):
        spec = llama_speculative_decode_factory(target, draft,
                                                max_len=max_len,
                                                n_draft=nd)
        t0 = time.perf_counter()
        out = spec.compiled(prompt, max_new_tokens=new)
        spec_compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            out = spec.compiled(prompt, max_new_tokens=new)
        dt = (time.perf_counter() - t0) / reps
        matches = bool((out[:, :plain_py.shape[1]] == plain_py).all())
        emit({"bench": "small_spec_compiled", "n_draft": nd,
              "s": round(dt, 3),
              "compile_s": round(spec_compile_s, 2),
              "speedup_vs_plain_compiled": round(c_dt / dt, 2),
              "speedup_vs_plain_python": round(py_dt / dt, 2),
              "output_matches_plain": matches,
              "stats": spec.compiled.last_stats})
        emit({"bench": "spec_vs_plain_compiled", "n_draft": nd,
              "new": new, "plain_tok_s": round(new / c_dt, 1),
              "spec_tok_s": round(new / dt, 1),
              "ratio": round(c_dt / dt, 3),
              "compile_s_plain": round(plain_compile_s, 2),
              "compile_s_spec": round(spec_compile_s, 2),
              "output_matches_plain": matches,
              "stats": spec.compiled.last_stats})


if __name__ == "__main__" and "--small" in sys.argv:
    small_mode()
    sys.exit(0)


def compile_044b():
    """--compile-044b: does the speculative program COMPILE at 0.44B?

    Builds the 0.44B target + 46M draft (untrained — weights do not
    affect compile time), AOT-lowers and compiles the plain compiled
    greedy program and the spec prefill/chunk programs under
    scan_layers=True, and reports module text sizes for the scanned vs
    unrolled layer bodies. Runs anywhere (CPU included): the claim is
    about program size and compile time, not throughput. The round-5
    hang was never the model — the spec programs closed over both
    models' weights, which lower as INLINE LITERALS (~1 GB of module
    for 0.44B bf16 x 2); weights now travel as jit arguments and the
    chunk module is ~100 KB at any model size.
    """
    import jax
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama_decode import (
        llama_decode_factory, llama_speculative_decode_factory)

    def emit(rec):
        rec["device"] = str(jax.devices()[0])
        print(json.dumps(rec), flush=True)

    tgt_cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                          intermediate_size=4096, num_hidden_layers=12,
                          num_attention_heads=12,
                          num_key_value_heads=12,
                          max_position_embeddings=2048,
                          dtype=jnp.bfloat16)
    drf_cfg = LlamaConfig(vocab_size=32000, hidden_size=512,
                          intermediate_size=1408, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=2048,
                          dtype=jnp.bfloat16)
    paddle.seed(0)
    t0 = time.perf_counter()
    target = LlamaForCausalLM(tgt_cfg)
    draft = LlamaForCausalLM(drf_cfg)
    target.to(dtype="bfloat16")
    draft.to(dtype="bfloat16")
    target.eval()
    draft.eval()
    build_s = time.perf_counter() - t0
    n_t = sum(int(np.prod(p.shape)) for p in
              target.state_dict().values())
    n_d = sum(int(np.prod(p.shape)) for p in draft.state_dict().values())
    emit({"bench": "compile_044b_models", "target_params": n_t,
          "draft_params": n_d, "size_ratio": round(n_t / n_d, 1),
          "build_s": round(build_s, 1)})

    prompt_len, new, n_draft = 32, 128, 4
    max_len = prompt_len + new + 32
    tokens = jnp.asarray(np.ones((1, prompt_len), np.int32))

    # plain compiled greedy (the round-5 1.6 s reference point):
    # weights as args; scanned layer body
    gen = llama_decode_factory(target, max_len=max_len)
    p = gen._parts
    t0 = time.perf_counter()
    low = p["compiled_greedy"].lower(p["outer"], p["layers"], tokens,
                                     new)
    lower_s = time.perf_counter() - t0
    nbytes = len(low.as_text())
    t0 = time.perf_counter()
    low.compile()
    emit({"bench": "plain_compiled_044b_aot", "module_bytes": nbytes,
          "lower_s": round(lower_s, 2),
          "compile_s": round(time.perf_counter() - t0, 2)})

    # speculative prefill + chunk programs (scan layer body, weights
    # as args) — the programs that never compiled before this change
    spec = llama_speculative_decode_factory(target, draft,
                                            max_len=max_len,
                                            n_draft=n_draft)
    sp = spec._parts
    t0 = time.perf_counter()
    low_p = sp["spec_prefill"].lower(sp["params"], tokens)
    state_avals = jax.eval_shape(sp["spec_prefill"], sp["params"],
                                 tokens)
    low_c = sp["spec_chunk"].lower(sp["params"], state_avals, 4,
                                   jnp.asarray(new, jnp.int32))
    lower_s = time.perf_counter() - t0
    pb, cb = len(low_p.as_text()), len(low_c.as_text())
    t0 = time.perf_counter()
    low_p.compile()
    prefill_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    low_c.compile()
    chunk_s = time.perf_counter() - t0
    emit({"bench": "spec_compiled_044b_aot", "n_draft": n_draft,
          "prefill_module_bytes": pb, "chunk_module_bytes": cb,
          "lower_s": round(lower_s, 2),
          "compile_s_prefill": round(prefill_s, 2),
          "compile_s_chunk": round(chunk_s, 2),
          "note": "weights as jit args (no inline constants) + "
                  "lax.scan layer body"})

    # unrolled-layers comparison: module size only (the L x text blowup
    # the scan body avoids; compiling the unrolled form proves nothing
    # more and is slow)
    spec_u = llama_speculative_decode_factory(target, draft,
                                              max_len=max_len,
                                              n_draft=n_draft,
                                              scan_layers=False)
    su = spec_u._parts
    low_cu = su["spec_chunk"].lower(su["params"], state_avals, 4,
                                    jnp.asarray(new, jnp.int32))
    ub = len(low_cu.as_text())
    emit({"bench": "spec_unrolled_044b_module",
          "chunk_module_bytes": ub, "vs_scan": round(ub / cb, 2)})

    # end-to-end: the compiled spec loop actually RUNS at 0.44B (short
    # horizon — throughput at this scale belongs to the chip, not here)
    run_new = 8
    t0 = time.perf_counter()
    out = spec.compiled(np.ones((1, prompt_len), np.int32),
                        max_new_tokens=run_new)
    emit({"bench": "spec_compiled_044b_run", "new": run_new,
          "first_call_s": round(time.perf_counter() - t0, 2),
          "out_shape": list(np.asarray(out).shape),
          "stats": spec.compiled.last_stats})


if __name__ == "__main__" and "--compile-044b" in sys.argv:
    compile_044b()
    sys.exit(0)
