"""GQA past-frontier A/B: splash-streaming delegation vs repeat+flash.

At S=16384 a GQA config cannot hold resident K/V (ResidentOverflowError)
and `grouped_flash_attention` auto-delegates to the K/V-streaming splash
kernels at the TRUE kv-head count (G-times less K/V DMA). Window-3
measured the splash family ~2x slower per computed block than the plain
streamed flash kernels — which, after jnp.repeat to full heads, pay
G-times MORE DMA. This tool measures the head-to-head (fwd+bwd scan
chains, the seq_attn_bench pattern) so the delegation routes on data:

  a) grouped_flash_attention auto  (-> splash streaming, true kv count)
  b) jnp.repeat(G) + flash_attention auto (-> plain streamed, G x DMA)

Run: python tools/gqa_xlong_bench.py
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

    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.flash_attention_gqa import (
        grouped_flash_attention)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    if on_tpu:
        shapes = [(1, 8, 2, 16384, 128), (2, 8, 2, 8192, 128)]
    else:
        shapes = [(1, 4, 2, 512, 64)]

    def bench(fn, q, k, v, repeats=3):
        g = jax.grad(lambda a, b, c: fn(a, b, c).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))

        def many(q, k, v):
            def body(carry, _):
                cq, ck, cv = carry
                dq, dk, dv = g(cq, ck, cv)
                return ((cq + (1e-6 * dq).astype(cq.dtype),
                         ck + (1e-6 * dk).astype(ck.dtype),
                         cv + (1e-6 * dv).astype(cv.dtype)), None)
            (cq, _, _), _ = jax.lax.scan(body, (q, k, v), None,
                                         length=ITERS)
            return cq
        f = jax.jit(many)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = f(q, k, v)
            float(out[0, 0, 0, 0])
            times.append(time.perf_counter() - t0)
        return min(times[1:]) / ITERS * 1e3, round(times[0], 1)

    for B, Hq, Hkv, S, D in shapes:
        G = Hq // Hkv
        rng = np.random.default_rng(0)
        dt = jnp.bfloat16 if on_tpu else jnp.float32
        q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), dt)
        k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dt)
        v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dt)

        # grouped_flash_attention's overflow delegation routes to
        # COARSE-TILE splash (pick_splash_blocks — see
        # flash_attention_gqa.py:326), so "grouped_auto" already covers
        # that path past the resident frontier. "grouped_splash" here
        # reconstructs the PRE-SWITCH fixed 128-tile splash config so
        # the round-3 A/B that justified the coarse-tile switch (128
        # tiles lost to repeat+flash) stays reproducible.
        from paddle_tpu.ops.pallas.splash_attention import splash_attention

        def grouped_splash(a, b, c):
            bq = bk = 128
            bm = np.tril(np.ones((S // bq, S // bk), bool))
            return splash_attention(a, b, c, bm, True, None, bq, bk)

        for tag, fn in (
            ("grouped_auto",
             lambda a, b, c: grouped_flash_attention(a, b, c, True)),
            ("grouped_splash", grouped_splash),
            ("repeat_flash",
             lambda a, b, c: flash_attention(
                 a, jnp.repeat(b, G, axis=1), jnp.repeat(c, G, axis=1),
                 True)),
        ):
            try:
                ms, comp = bench(fn, q, k, v)
                rec = {"S": S, "B": B, "G": G, "variant": tag,
                       "ms": round(ms, 3), "compile_s": comp,
                       "device": str(dev)}
            except Exception as e:  # noqa: BLE001 — record and continue
                lines = [x for x in str(e).splitlines() if x.strip()]
                rec = {"S": S, "B": B, "G": G, "variant": tag,
                       "infeasible": (lines[-1] if lines else repr(e))[:200],
                       "device": str(dev)}
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
