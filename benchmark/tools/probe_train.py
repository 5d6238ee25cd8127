#!/usr/bin/env python3
"""A training cell's window under another loop, for PERF.md's look at the
long steps: ``--run-ahead 0`` stops the host at every step (as PR 24's loop
did), ``--no-freeze`` leaves the interpreter's collector on set-up's
objects, ``--no-optional`` leaves out the comparison that keeps the first
moment (the window's rate has to agree with and without it)."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--run-ahead", type=int, default=2)
    ap.add_argument("--no-freeze", action="store_true")
    ap.add_argument("--no-optional", action="store_true")
    args = ap.parse_args()
    from benchmark.harness.spec import Spec
    from benchmark.run import run_cell
    out = run_cell(Spec(HERE), args.workload, args.seed, args.seconds, False,
                   run_ahead=args.run_ahead, freeze=not args.no_freeze,
                   optional_checks=not args.no_optional)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
