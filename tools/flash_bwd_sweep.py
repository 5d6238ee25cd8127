"""Backward-block sweep for the flash attention kernels, on chip.

The forward sweep settled on 512x512 (PERF.md round-2 table); the two
backward kernels (dq walks resident K/V; dk/dv walks resident Q) have
their own VMEM/pipelining tradeoff and until now inherited the forward
blocks. Times ONE jitted fwd+bwd at the bench shape per (bq, bk) pair
with host-readback sync, min over 3 repeats.

  python tools/flash_bwd_sweep.py
"""
import itertools
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

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B, H, S, D = 8, 12, 2048, 128
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, H, S, D)), jnp.bfloat16)
               for _ in range(3))

    ITERS = 8  # chained grads inside ONE jit: amortizes the per-call
    #            dispatch cost that would otherwise blur the deltas
    #            between block configs

    results = []
    for bq, bk in itertools.product((256, 512, 1024), (256, 512, 1024)):

        def loss(q, k, v):
            # stream=False pins the resident kernels: the sweep compares
            # bwd block tilings of ONE mode (auto-routing would silently
            # switch modes per block pair and corrupt the comparison)
            return flash_attention(q, k, v, True, None, 512, 512,
                                   bq, bk, False).astype(jnp.float32).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))

        def many(q, k, v):
            def body(c, _):
                cq, ck, cv = c
                dq, dk, dv = g(cq, ck, cv)
                # ALL three grads feed the carry: dk/dv must stay live or
                # XLA dead-code-eliminates the dkv kernel and the sweep
                # times only fwd+dq
                return ((cq + (1e-6 * dq).astype(cq.dtype),
                         ck + (1e-6 * dk).astype(ck.dtype),
                         cv + (1e-6 * dv).astype(cv.dtype)), None)
            (cq, _, _), _ = jax.lax.scan(body, (q, k, v), None,
                                         length=ITERS)
            return cq

        f = jax.jit(many)
        try:
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                out = f(q, k, v)
                float(out[0, 0, 0, 0])  # host readback = real sync
                times.append(time.perf_counter() - t0)
            rec = {"bwd_bq": bq, "bwd_bk": bk,
                   "ms_per_fwdbwd": round(min(times[1:]) / ITERS * 1e3, 2),
                   "compile_s": round(times[0], 1)}
        except Exception as e:  # noqa: BLE001 — sweep keeps going
            rec = {"bwd_bq": bq, "bwd_bk": bk, "error": repr(e)[-200:]}
        results.append(rec)
        print(json.dumps(rec), flush=True)
    best = min((r for r in results if "ms_per_fwdbwd" in r),
               key=lambda r: r["ms_per_fwdbwd"], default=None)
    print(json.dumps({"best": best}))


if __name__ == "__main__":
    main()
