#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints information lines, then each number compared beside its limit on
standard error, and as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``) and, last, ``checks``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def run_cell(spec, name: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, **kw) -> dict:
    """Drive one cell and return the result line's object.  Set-up leaves out
    the one call that brings the chips up (``chip_start``: the machine's
    runtime, 5.6 to 12.4 s from run to run on one machine, PERF.md section 2):
    it counts the imports before that call and everything after it, and
    ``start_to_chip_s`` prints both parts of what lies before the chips."""
    import jax
    from benchmark.harness import device, serve, train
    from benchmark.harness.trace import breakdown

    cell = spec.cell(name)
    device.enable_cache(spec.root.parent)
    t_imported = time.perf_counter()
    if require_chip:
        devices = device.require_chips(cell["chips"])
    else:
        devices = jax.devices()[:cell["chips"]]
    t_chips = time.perf_counter()
    counter = device.CompileCounter()
    kind = cell["traffic_spec"]["kind"]
    runner = {"serve_open_loop": serve.run, "train_packed": train.run}[kind]
    t_setup = t_chips - (t_imported - T_PROCESS)     # set-up's origin: as if the chips came up at once
    out = runner(spec, cell, seed, seconds, trace, devices, counter, t_setup, **kw)

    obs = out["obs"]
    entries = spec.per_layer(name) if trace else spec.end_to_end(name)
    metrics = spec.read_metrics(entries, obs)
    if obs.get("machine_stops"):    # what a reader that leaves them out reads with them in
        with_stops = spec.read_metrics(entries, dict(obs, machine_stops=[]))
        out["info"]["metrics_with_stops"] = {k: v["value"] for k, v in with_stops.items()
                                             if metrics.get(k) != v}
    info = dict(out["info"], workload=name, seed=seed, seconds=seconds,
                not_compared=out["checks"].not_compared,
                start_to_chip_s={"total": t_chips - T_PROCESS,
                                 "python_imports": t_imported - T_PROCESS,
                                 "chip_start": t_chips - t_imported})
    print("info " + json.dumps(info), flush=True)
    dev = dict(device.device_info(devices), memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": out["checks"].correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    if trace:
        reduced = obs.get("device_trace")
        if reduced is None:
            raise RuntimeError("the traced run left no device trace to read")
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = breakdown(reduced)
    result["checks"] = out["checks"].rows
    out["checks"].print()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness.device import NoChip
    from benchmark.harness.spec import Spec, SpecError
    try:
        spec = Spec(HERE)
        result = run_cell(spec, args.workload, args.seed, args.seconds, bool(args.trace))
    except (NoChip, SpecError, ModuleNotFoundError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
