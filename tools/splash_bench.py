"""Splash (block-sparse / sliding-window) attention chip benchmark.

Queue item (PERF.md): per-call fwd+bwd time vs window size at the bench
shape — compute should scale with pattern density (window/S), unlike the
reference's sparse_attention_op.cu which pays dense compute at any
sparsity. Also times grouped (GQA) splash vs the repeat-K/V fallback.

Run on the chip:
  python tools/splash_bench.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.sync import hard_sync
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.splash_attention import (banded_block_mask,
                                                        splash_attention)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    if on_tpu:
        B, H, S, D = 8, 12, 2048, 128
        dtype = jnp.bfloat16
        iters = 20
    else:
        B, H, S, D = 1, 2, 512, 64
        dtype = jnp.float32
        iters = 2

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)

    def timed(fn, kk=None, vv=None):
        kk = k if kk is None else kk
        vv = v if vv is None else vv
        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))
        out = g(q, kk, vv)
        hard_sync(out[0])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(q, kk, vv)
        hard_sync(out[0])
        return (time.perf_counter() - t0) / iters * 1e3

    dense_ms = timed(lambda a, b, c: flash_attention(a, b, c, True))
    rows = [{"variant": "flash_dense_causal", "ms": round(dense_ms, 2)}]
    for window in (S, S // 2, S // 4, S // 8):
        bm = banded_block_mask(S, S, 128, 128, window)
        ms = timed(lambda a, b, c, bm=bm, w=window: splash_attention(
            a, b, c, bm, True, None, 128, 128, w))
        rows.append({"variant": f"splash_window_{window}",
                     "density": round(float(bm.mean()), 3),
                     "ms": round(ms, 2)})

    # grouped (GQA) vs repeat-K/V at a windowed pattern: the grouped
    # kernel reads K/V once per kv head instead of once per query head
    Hkv = max(1, H // 4)
    G = H // Hkv
    kg = k[:, :Hkv]
    vg = v[:, :Hkv]
    bm = banded_block_mask(S, S, 128, 128, S // 4)

    grouped_ms = timed(lambda a, b, c: splash_attention(
        a, b, c, bm, True, None, 128, 128, S // 4), kg, vg)
    repeat_ms = timed(lambda a, b, c: splash_attention(
        a, jnp.repeat(b, G, axis=1), jnp.repeat(c, G, axis=1), bm, True,
        None, 128, 128, S // 4), kg, vg)
    rows.append({"variant": f"grouped_splash_G{G}",
                 "ms": round(grouped_ms, 2)})
    rows.append({"variant": f"repeat_kv_splash_G{G}",
                 "ms": round(repeat_ms, 2)})
    for r in rows:
        r["device"] = str(dev)
        print(json.dumps(r))
    with open("/tmp/splash_bench.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
