#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (PERF.md, section 2).

    python3 benchmark/tools/readings.py train --workload train_s4096 --seeds 1,2,3
    python3 benchmark/tools/readings.py train --workload train_s4096 --seeds 4,5,6 --side control
    python3 benchmark/tools/readings.py train --workload train_s4096 --seeds 7,8,9 --side half_batch
    python3 benchmark/tools/readings.py serve --workload serve_chat_steady --seed 4 --seconds 12

``train`` reads every seed in one process (set-up is the long part): the
program's first three steps, or the control's (the reference at int8 put
in the program's place), or a planted fault's, each against the plain
reference.  ``serve`` runs a short window at the cell's own load and reads
the program's gaps and the control's over the same sample.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("kind", choices=("train", "serve"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--side", default="program",
                    choices=("program", "control", "half_batch", "state_unchanged"))
    args = ap.parse_args()

    from benchmark.harness import device, serve, train, train_check
    from benchmark.harness.spec import Spec
    spec = Spec(HERE)
    cell = spec.cell(args.workload)
    device.enable_cache(HERE.parent)
    devices = device.require_chips(cell["chips"])
    family, model, job = cell["family"], cell["config_spec"]["model"], cell["traffic_spec"]

    if args.kind == "serve":
        out = serve.run(spec, cell, args.seed, args.seconds, False, devices,
                        device.CompileCounter(), time.perf_counter(), control="int8")
        print("readings " + json.dumps({
            "seed": args.seed, "program": {k: v["value"] for k, v in out["checks"].rows.items()},
            "control": out["info"]["control"], "tokens": out["info"]["tokens_compared"]}),
            flush=True)
        return 0

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        if args.side == "control":
            mesh = train.build_mesh(devices, job)
            first = train_check.reference_first_steps(
                family, model, job, seed, mesh, quant="int8", keep_first_moment=True)
        else:
            fault = None if args.side == "program" else args.side
            trainer = train.Trainer(family, cell["config_spec"], job, seed, devices, fault)
            mesh = trainer.mesh
            first = train_check.first_steps(trainer, train.CHECK_STEPS, True)
            del trainer
        gc.collect()
        numbers, extra = train_check.compare(family, model, job, seed, first, mesh)
        print("readings " + json.dumps({
            "seed": seed, "side": args.side, "numbers": numbers,
            "worst": extra["worst_leaves"], "left_out": extra["left_out_of_change"],
            "seconds": time.perf_counter() - t0,
            "memory_peak_bytes": device.memory_peak_bytes(devices)}), flush=True)
        del first
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
