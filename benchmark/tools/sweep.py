#!/usr/bin/env python3
"""The sweep that fixed the serving cells' rates: one engine, one traffic
mix offered at several rates, each for ``--seconds``.

    python3 benchmark/tools/sweep.py --workload serve_chat_steady --rates 2,2.5,3,3.5 --seconds 30

The highest rate at which the queue does not grow through the window (late
arrivals wait no longer than early ones, and the drain after the last
arrival stays short) is the knee; ``chat_steady`` takes four fifths of it,
``prefill_burst`` five fourths.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from benchmark.harness import device, serve, traffic
    from benchmark.harness.spec import Spec
    spec = Spec(HERE)
    cell = spec.cell(args.workload)
    device.enable_cache(HERE.parent)
    devices = device.require_chips(cell["chips"])
    family, config, mix = cell["family"], cell["config_spec"], cell["traffic_spec"]
    model = config["model"]
    t0 = time.perf_counter()
    eng = serve.build_engine(family, config, args.seed)
    eng.run(serve.to_requests(traffic.warmup_requests(mix, model["vocab_size"], eng.chunk_C)))
    print(f"set-up {time.perf_counter() - t0:.1f}s", flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        reqs = traffic.serve_requests(dict(mix, rate_per_s=rate), args.seconds, args.seed,
                                      model["vocab_size"])
        res = eng.run(serve.to_requests(reqs))
        obs = serve.observe(reqs, res, eng.bench_clock, family, model, None)
        rows = sorted(obs["requests"], key=lambda r: r["arrival"])
        ttft = np.array([r["token_times"][0] - r["arrival"] for r in rows])
        third = max(1, len(rows) // 3)
        last_due = rows[-1]["arrival"]
        spans = obs["spans"]
        print("sweep " + json.dumps({
            "rate_per_s": rate, "requests": len(rows), "window_s": obs["window_s"],
            "drain_s": obs["first_due_s"] + obs["window_s"] - last_due,
            "tok_s": sum(len(r["token_times"]) for r in rows) / obs["window_s"],
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "ttft_first_third_ms": 1e3 * float(ttft[:third].mean()),
            "ttft_last_third_ms": 1e3 * float(ttft[-third:].mean()),
            "decode_ms": 1e3 * float(np.median([b - a for k, a, b, _ in spans if k == "decode"])),
            "prefill_s": float(sum(b - a for k, a, b, _ in spans if k == "prefill")),
            "decode_s": float(sum(b - a for k, a, b, _ in spans if k == "decode"))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
