#!/usr/bin/env python3
"""What ONE lane call costs at each width it may take, on the chip.

    python3 tools/lane_widths.py --workload serve_latent_moe_docqa --seed 7

Builds a serving cell's engine as ``benchmark/run.py`` does (weights from
the seed, the configuration's geometry) and times ``prefill.lane_call``
alone: for each width 1 ... ``_lane_widest`` a span of exactly ``width x
chunk`` tokens at ``--start`` positions into a prompt, ``--calls`` calls
after ``--warm``, each waited for (the engine compiled its own programs
while it was built; where its factory pads, ``chunked_prefill_pads_``, the
narrower widths compile here, inside ``--warm``).  Prints the median milliseconds a call and a 64-token chunk, and what
the prompt's final call adds (the finishing program: final norm and the
whole-vocabulary head).  Chip only (exit 2 without one); the result goes to
``chiprun_out/lane_widths/<workload>.<seed>.json`` too.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--start", type=int, default=2048)
    ap.add_argument("--warm", type=int, default=3)
    ap.add_argument("--calls", type=int, default=12)
    args = ap.parse_args()

    import jax
    from benchmark.harness import device, serve
    from benchmark.harness.spec import Spec
    spec = Spec(REPO / "benchmark")
    cell = spec.cell(args.workload)
    device.enable_cache(REPO)
    device.require_chips(cell["chips"])
    t0 = time.perf_counter()
    marks = device.Marks(t0, jax.devices())
    eng = serve.build_engine(cell["family"], cell["config_spec"], args.seed, None, marks)
    built_s = time.perf_counter() - t0
    C, widest = eng.chunk_C, eng._lane_widest
    start = args.start // C * C
    rng = np.random.default_rng(args.seed)
    vocab = cell["config_spec"]["model"]["vocab_size"]
    # one row's tables: a page a position, the window kind's (if any) behind
    pt = np.zeros((1, eng._table_cols), np.int32)
    n_pages = (start + widest * C) // eng.page_size + 1
    pt[0, :n_pages] = 1 + np.arange(n_pages) % (eng.n_pool_pages - 1)
    if eng.window is not None:
        pt[0, eng.W:eng.W + n_pages] = 1 + np.arange(n_pages) % (eng.n_window_pages - 1)
    arr, lane_call = eng._arr, eng._p_prefill.lane_call
    rows = []
    for w in range(1, widest + 1):
        span = rng.integers(0, vocab, (1, w * C)).astype(np.int32)
        ms = {}
        for final in (False, True):
            lens = np.asarray([start + w * C], np.int32)
            took = []
            for i in range(args.warm + args.calls):
                a = time.perf_counter()
                out, eng._pools = lane_call(eng._p_outer, eng._p_layers, arr(span), start,
                                            arr(pt), arr(lens), eng._pools, final)
                jax.block_until_ready((out, eng._pools))
                if i >= args.warm:
                    took.append(time.perf_counter() - a)
            ms[final] = 1e3 * float(np.median(took))
        rows.append({"width": w, "tokens": w * C, "call_ms": ms[False],
                     "ms_per_chunk": ms[False] / w, "final_call_ms": ms[True],
                     "tokens_per_s": w * C / ms[False] * 1e3})
        print("lane_width " + json.dumps(rows[-1]), flush=True)
    if eng._call_counts is not None:
        eng._call_counts.reset()        # these calls are no run's
    out = {"workload": args.workload, "seed": args.seed, "start": start, "chunk": C,
           "widest": widest, "engine_built_s": built_s,
           "set_up_parts_s": marks.parts, "widths": rows}
    path = REPO / "chiprun_out" / "lane_widths" / f"{args.workload}.{args.seed}.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=1, default=str))
    print("lane_widths " + json.dumps({k: out[k] for k in ("workload", "widest",
                                                           "engine_built_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
