"""Amortized chip benches for the sequence-parallel attention family.

VERDICT r3 item 3: ring attention, Ulysses, and splash density scaling
had CPU-correctness tests only. This tool scan-chains ITERS fwd+bwd
iterations inside ONE jit (the flash_bwd_sweep.py pattern) so per-layer
cost is measurable, and reports each variant as a fraction of dense
flash-attention throughput at equal shapes.

Rows at the bench shape (B=8, H=12, S=2048, D=128, bf16):
  - flash dense causal (the yardstick)
  - ring attention on a 1-device 'sep' mesh (machinery overhead vs flash;
    the multi-chip claim is comm-overlap, which one chip cannot measure —
    this row bounds the non-comm overhead)
  - Ulysses on a 1-device 'sep' mesh (same purpose)
  - splash banded at window S, S/2, S/4, S/8 (density scaling curve: the
    reference's sparse_attention_op.cu pays dense compute at any
    sparsity; splash cost should track density)
Long-context rows (B=2, S=8192): flash vs ring vs splash window 2048.

Run: python tools/seq_attn_bench.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ITERS = 8


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.splash_attention import (banded_block_mask,
                                                        splash_attention)
    from paddle_tpu.parallel.ring_attention import ring_attention
    from paddle_tpu.parallel.ulysses import ulysses_attention

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("sep",))

    def bench(fn, q, k, v, repeats=3):
        """min ms per fwd+bwd over a scan chain of ITERS grads."""
        g = jax.grad(lambda a, b, c: fn(a, b, c).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))

        def many(q, k, v):
            def body(carry, _):
                cq, ck, cv = carry
                dq, dk, dv = g(cq, ck, cv)
                # all three grads feed the carry or XLA DCEs the dkv pass
                return ((cq + (1e-6 * dq).astype(cq.dtype),
                         ck + (1e-6 * dk).astype(ck.dtype),
                         cv + (1e-6 * dv).astype(cv.dtype)), None)
            (cq, _, _), _ = jax.lax.scan(body, (q, k, v), None, length=ITERS)
            return cq

        f = jax.jit(many)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = f(q, k, v)
            float(out[0, 0, 0, 0])  # host readback = the only real sync
            times.append(time.perf_counter() - t0)
        return min(times[1:]) / ITERS * 1e3, round(times[0], 1)

    def make_qkv(B, H, S, D, dtype):
        rng = np.random.default_rng(0)
        return tuple(jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
                     for _ in range(3))

    if on_tpu:
        # xlong sits past the resident-KV frontier: flash/splash resolve
        # to the round-4 grid-streamed kernels (the single-chip path the
        # resident design could not compile at all)
        shapes = [("bench", 8, 12, 2048, 128, jnp.bfloat16),
                  ("long", 2, 12, 8192, 128, jnp.bfloat16),
                  ("xlong", 1, 12, 16384, 128, jnp.bfloat16)]
    else:
        shapes = [("bench", 1, 2, 512, 64, jnp.float32)]

    rows = []

    def emit(rec):
        rec["device"] = str(dev)
        rows.append(rec)
        print(json.dumps(rec), flush=True)

    def bench_or_record(tag, variant, fn, q, k, v, **extra):
        """One infeasible variant (e.g. a Mosaic scoped-VMEM overflow)
        must record a row and let the sweep continue, not kill the
        whole run (the 8k resident row once died at 17M and took the
        streamed/xlong rows with it)."""
        try:
            ms, comp = bench(fn, q, k, v)
        except Exception as e:  # noqa: BLE001 — record and move on
            lines = [ln for ln in str(e).splitlines() if ln.strip()]
            msg = lines[-1][:200] if lines else repr(e)[:200]
            emit({"shape": tag, "variant": variant,
                  "S": q.shape[2], "B": q.shape[0],
                  "infeasible": msg, **extra})
            return None
        return ms, comp

    for tag, B, H, S, D, dtype in shapes:
        q, k, v = make_qkv(B, H, S, D, dtype)

        def frac(ms, flash_ms):
            return round(flash_ms / ms, 3) if flash_ms else None

        r = bench_or_record(tag, "flash_dense",
                            lambda a, b, c: flash_attention(a, b, c, True),
                            q, k, v)
        flash_ms = None
        if r:
            flash_ms, comp = r
            emit({"shape": tag, "variant": "flash_dense", "S": S, "B": B,
                  "ms": round(flash_ms, 3), "compile_s": comp})

        if tag in ("long", "xlong"):
            # auto resolution (fwd resident + streamed bwd at 8k; fully
            # streamed at 16k — splash-tril routing is OFF after losing
            # this head-to-head 97.4 vs 48.3 ms) vs forced full
            # streaming at the same shape
            r = bench_or_record(tag, "flash_streamed",
                                lambda a, b, c: flash_attention(
                                    a, b, c, True, None, None, None, None,
                                    None, True), q, k, v)
            if r:
                ms, comp = r
                emit({"shape": tag, "variant": "flash_streamed", "S": S,
                      "B": B, "ms": round(ms, 3), "compile_s": comp,
                      "frac_of_flash": frac(ms, flash_ms)})

        r = bench_or_record(tag, "ring_p1",
                            lambda a, b, c: ring_attention(
                                a, b, c, mesh, "sep", True), q, k, v)
        if r:
            ms, comp = r
            emit({"shape": tag, "variant": "ring_p1", "S": S, "B": B,
                  "ms": round(ms, 3), "compile_s": comp,
                  "frac_of_flash": frac(ms, flash_ms)})

        if tag == "bench":
            r = bench_or_record(tag, "ulysses_p1",
                                lambda a, b, c: ulysses_attention(
                                    a, b, c, mesh, "sep", True), q, k, v)
            if r:
                ms, comp = r
                emit({"shape": tag, "variant": "ulysses_p1", "S": S,
                      "B": B, "ms": round(ms, 3), "compile_s": comp,
                      "frac_of_flash": frac(ms, flash_ms)})
            windows = (S, S // 2, S // 4, S // 8)
        else:
            windows = (2048,)

        from paddle_tpu.ops.pallas.splash_attention import \
            pick_splash_blocks
        for w in windows:
            # coarse tiles, as the model's sliding-window path picks
            # them (512-tile banded splash measured 3x the 128-tile
            # kernel — PERF.md round 4)
            sbq, sbk = pick_splash_blocks(S, S)
            bm = banded_block_mask(S, S, sbq, sbk, w)
            density = round(float(bm.mean()), 3)
            r = bench_or_record(tag, f"splash_w{w}",
                                lambda a, b, c, bm=bm, w=w: splash_attention(
                                    a, b, c, bm, True, None, sbq, sbk, w),
                                q, k, v, density=density, blocks=sbq)
            if r:
                ms, comp = r
                emit({"shape": tag, "variant": f"splash_w{w}", "S": S,
                      "B": B, "density": density, "blocks": sbq,
                      "ms": round(ms, 3), "compile_s": comp,
                      "frac_of_flash": frac(ms, flash_ms)})

        if tag == "xlong":
            # full-causal tril splash vs flash streamed at the same
            # shape: table streaming skips dead-block DMA (tril halves
            # it), flash streaming DMAs every block — the winner should
            # own the long-S causal auto route
            sbq, sbk = pick_splash_blocks(S, S)
            bm = np.tril(np.ones((S // sbq, S // sbk), bool))
            r = bench_or_record(tag, "splash_tril_full",
                                lambda a, b, c, bm=bm: splash_attention(
                                    a, b, c, bm, True, None, sbq, sbk),
                                q, k, v)
            if r:
                ms, comp = r
                emit({"shape": tag, "variant": "splash_tril_full", "S": S,
                      "B": B, "ms": round(ms, 3), "compile_s": comp,
                      "frac_of_flash": frac(ms, flash_ms)})

    with open("/tmp/seq_attn_bench.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
