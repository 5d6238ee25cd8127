#!/usr/bin/env python3
"""What a serving cell's limits read when the reference itself is made
wrong in a named way (PERF.md, section 2).

    python3 benchmark/tools/fault_readings.py --workload serve_window_moe_codemix \
        --seed 4 --seconds 12 --control window_ignored
    python3 benchmark/tools/fault_readings.py --workload serve_window_moe_codemix \
        --seed 5 --seconds 12 --fault token_altered

``tools/readings.py serve`` reads the control ``int8``; this reads any
``quant`` the cell's family's ``reference_programs`` knows, over the same
sample of one short window at the cell's own load: at every sampled
position the token judged is the one the altered reference puts first, its
gap taken against the plain reference.  ``window_ignored`` (the ``laguna``
family) is the planted fault in which sliding layers attend to everything
before them.  ``--fault token_altered`` is the harness's own planted fault
(one served token of the longest request changed where it is produced):
``program`` then holds what the limits read under it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control")
    ap.add_argument("--fault", choices=("token_altered",),
                    help="the harness's planted fault in what was served")
    args = ap.parse_args()

    from benchmark.harness import device, serve
    from benchmark.harness.spec import Spec
    spec = Spec(HERE)
    cell = spec.cell(args.workload)
    device.enable_cache(HERE.parent)
    devices = device.require_chips(cell["chips"])
    out = serve.run(spec, cell, args.seed, args.seconds, False, devices,
                    device.CompileCounter(), time.perf_counter(), control=args.control,
                    fault=args.fault)
    print("readings " + json.dumps({
        "seed": args.seed, "control_name": args.control, "fault": args.fault,
        "program": {k: v["value"] for k, v in out["checks"].rows.items()},
        "control": out["info"].get("control"), "tokens": out["info"]["tokens_compared"],
        "requests": out["info"]["requests_compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
