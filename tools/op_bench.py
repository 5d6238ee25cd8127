"""Per-op latency benchmark harness.

~ tools/ci_op_benchmark.sh + paddle/fluid/operators/benchmark/op_tester.cc
(+ check_op_benchmark_result.py): measure registered ops on canonical
shapes, write a JSON report, and compare against a stored baseline with a
relative-regression gate — the per-op CI gate of the reference.

Usage:
  python tools/op_bench.py --out /tmp/ops.json            # measure
  python tools/op_bench.py --out new.json --baseline old.json --gate 1.15
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


CASES = [
    # (op path under paddle_tpu, args builder, name)
    ("matmul", lambda p, np: (p.randn([1024, 1024]), p.randn([1024, 1024]))),
    ("add", lambda p, np: (p.randn([4096, 1024]), p.randn([4096, 1024]))),
    ("softmax", lambda p, np: (p.randn([256, 4096]),)),
    ("exp", lambda p, np: (p.randn([4096, 1024]),)),
    ("sum", lambda p, np: (p.randn([4096, 1024]),)),
    ("transpose", lambda p, np: (p.randn([512, 512, 16]), [2, 0, 1])),
    ("tanh", lambda p, np: (p.randn([4096, 1024]),)),
    ("mean", lambda p, np: (p.randn([4096, 1024]),)),
]


def time_op(fn, args, iters=20, warmup=3):
    from paddle_tpu.core.sync import hard_sync
    for _ in range(warmup):
        out = fn(*args)
    hard_sync(out._value if hasattr(out, "_value") else out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    hard_sync(out._value if hasattr(out, "_value") else out)
    return (time.perf_counter() - t0) / iters


def eager_vs_jit(sizes=(16, 256, 2048), iters=50):
    """Eager per-op dispatch overhead vs jit (SURVEY §3.1 hot-loop
    concern): the same 5-op chain runs (a) through the eager dispatcher
    (one apply_op per op: AMP hook, tape record, registry lookup) and
    (b) as one jax.jit program. The per-op overhead is the eager-minus-
    jit gap divided by the op count; at small sizes this is pure host
    dispatch cost, at large sizes compute dominates and the gap vanishes.
    """
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.sync import hard_sync

    rows = []
    for n in sizes:
        x = paddle.randn([n, n])
        xv = x._value

        def chain_eager(t):
            return paddle.sum(paddle.tanh(t * 2.0 + 1.0) * t)

        def chain_jnp(v):
            return jnp.sum(jnp.tanh(v * 2.0 + 1.0) * v)

        jitted = jax.jit(chain_jnp)
        e = time_op(chain_eager, (x,), iters=iters)
        j = time_op(jitted, (xv,), iters=iters)
        n_ops = 5  # mul, add, tanh, mul, sum
        rows.append({"size": n, "eager_us": e * 1e6, "jit_us": j * 1e6,
                     "per_op_overhead_us": (e - j) * 1e6 / n_ops,
                     "ratio": e / max(j, 1e-12)})
        print(f"n={n:5d}  eager {e * 1e6:9.1f}us  jit {j * 1e6:9.1f}us  "
              f"per-op overhead {(e - j) * 1e6 / n_ops:7.2f}us  "
              f"ratio {e / max(j, 1e-12):5.2f}x")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/op_bench.json")
    ap.add_argument("--eager-vs-jit", action="store_true",
                    help="measure eager dispatch overhead vs jit and exit")
    ap.add_argument("--baseline", default=None)
    ap.add_argument("--gate", type=float, default=1.2,
                    help="fail if new/old latency ratio exceeds this")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    if args.eager_vs_jit:
        rows = eager_vs_jit()
        with open(args.out, "w") as f:
            json.dump({"eager_vs_jit": rows}, f, indent=1)
        print(f"wrote {args.out}")
        return

    import numpy as np
    import paddle_tpu as paddle

    report = {}
    for name, build in CASES:
        fn = getattr(paddle, name)
        case_args = build(paddle, np)
        dt = time_op(fn, case_args, iters=args.iters)
        report[name] = {"latency_ms": dt * 1e3}
        print(f"{name:12s} {dt * 1e3:10.4f} ms")

    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {args.out}")

    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        regressions = []
        for name, entry in report.items():
            if name in base:
                ratio = entry["latency_ms"] / max(
                    1e-9, base[name]["latency_ms"])
                flag = " REGRESSION" if ratio > args.gate else ""
                print(f"{name:12s} ratio {ratio:6.3f}{flag}")
                if ratio > args.gate:
                    regressions.append(name)
        if regressions:
            print(f"FAILED gate ({args.gate}x): {regressions}")
            sys.exit(1)
        print("op benchmark gate passed")


if __name__ == "__main__":
    main()
