"""Project the BASELINE #4 north star (Llama-3-8B, >=40% MFU, v5p-64)
from single-chip measurements + the analytic comm model.

One real chip cannot run the pod; what it CAN pin down is the compute
term — the achieved fraction of peak at exactly the per-chip shard
shapes an 8B TP-sliced layer puts on each chip (tools/mfu_scale.py
tp_shard row, falling back to the 0.44B headline MFU from
PERF_LAST_TPU.json). The ICI terms (TP allreduces, DP gradient
allreduce, pipeline p2p + bubble) come from the same CostModel the
planner ranks plans with (distributed/auto_parallel/cost_model.py),
so the projection and the planner cannot drift apart.

    projected_mfu = step_flops / (n_chips * peak * t_step)
    t_step = (t_compute / measured_eff + t_tp) / (1 - bubble)
             + t_dp + t_p2p

Prints one JSON line; cites which measurement fed measured_eff.
Run: python tools/pod_projection.py
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measured_efficiency():
    """(eff, source): achieved fraction of peak on the real chip."""
    # best: the TP-shard-shaped row from the chip queue, preferring the
    # adamw variant (round-4 verdict item 2: the projected plan trains
    # with adamw + ZeRO-sliced moments, so the sgd-measured efficiency
    # omitted real per-step moment traffic). The repo-rooted file is
    # authoritative (the round-4 runner's --out); /tmp is only a
    # fallback for the runner's default path — a stale /tmp file must
    # never shadow a fresh repo file. Within a file, the LAST row wins
    # (the runner appends across re-runs).
    for cq in (os.path.join(REPO, "CHIP_QUEUE_RESULTS.jsonl"),
               "/tmp/chip_queue_results.jsonl"):
        if not os.path.exists(cq):
            continue
        latest = {}
        with open(cq) as f:
            for ln in f:
                try:
                    rec = json.loads(ln)
                except json.JSONDecodeError:
                    continue
                if rec.get("name", "").startswith("mfu_scale_tp_shard"):
                    for row in rec.get("results", []):
                        if "compute_mfu" in row:
                            latest[rec["name"]] = float(row["compute_mfu"])
        if "mfu_scale_tp_shard_adamw" in latest:
            return latest["mfu_scale_tp_shard_adamw"], (
                "mfu_scale.py tp_shard_adamw (8B TP=8 per-chip shapes, "
                "zero-sliced bf16-moment adamw, measured; "
                f"{os.path.basename(cq)})")
        if "mfu_scale_tp_shard" in latest:
            return latest["mfu_scale_tp_shard"], (
                "mfu_scale.py tp_shard (8B TP=8 per-chip "
                f"shapes, measured, SGD-ONLY; {os.path.basename(cq)})")
    # fallback: the commit-keyed headline measurement
    rec_path = os.path.join(REPO, "PERF_LAST_TPU.json")
    if os.path.exists(rec_path):
        with open(rec_path) as f:
            rec = json.load(f)
        if "mfu" in rec:
            return (float(rec["mfu"]),
                    f"PERF_LAST_TPU.json headline "
                    f"({rec.get('config', '?')}, "
                    f"commit {rec.get('measured_at_commit', '?')})")
    from paddle_tpu.distributed.auto_parallel import CostModel
    return (CostModel.DEFAULT_EFF,
            "cost-model default (NO chip measurement found)")


def main():
    from paddle_tpu.distributed.auto_parallel import (Cluster, ModelSpec,
                                                      Planner)

    eff, source = measured_efficiency()

    # Llama-3-8B pretraining shape at S=8192 on a v5p-64 slice
    model = ModelSpec(n_layers=32, hidden=4096, intermediate=14336,
                      vocab=128256, seq=8192, global_batch=128)
    cluster = Cluster(n_devices=64)  # v5p defaults in DeviceSpec
    planner = Planner(cluster, model)
    best = planner.best()
    est = best.cost  # the planner already ran the cost model

    # compute term from first principles with the MEASURED efficiency
    # (recomputing rather than rescaling est["compute"] keeps this
    # independent of the cost model's internal eff constant)
    peak = cluster.device.peak_flops

    def project(eff_x, ici_scale):
        t_compute = model.step_flops() / (cluster.n_devices * peak * eff_x)
        # same term structure as CostModel.estimate (tp + sep ride
        # inside the bubble with compute; dp grad sync and pp p2p
        # outside) so planner and projection cannot drift apart
        t_step = ((t_compute + (est["tp_comm"]
                                + est.get("sep_comm", 0.0)) / ici_scale)
                  / (1 - est["bubble"])
                  + est["dp_comm"] / ici_scale
                  + est["pp_p2p"] / ici_scale)
        return (model.step_flops() / (cluster.n_devices * peak * t_step),
                t_step)

    mfu, t_step = project(eff, 1.0)
    tok_per_chip = model.global_batch * model.seq / t_step \
        / cluster.n_devices
    t_compute = model.step_flops() / (cluster.n_devices * peak * eff)

    # sensitivity band (round-4 verdict item 2): the ICI terms are
    # cost-model-only (one chip cannot measure collectives) and the
    # efficiency transfers from a same-shaped but not identical run —
    # so publish the corners, not just the center. Pessimistic corner:
    # ICI half as fast as modeled AND eff 5pt lower; optimistic: 2x ICI,
    # +5pt eff.
    mfu_pess, _ = project(max(eff - 0.05, 0.05), 0.5)
    mfu_opt, _ = project(min(eff + 0.05, 1.0), 2.0)

    print(json.dumps({
        "target": "llama3-8b v5p-64 (BASELINE #4)",
        "plan": {"dp": best.dp, "mp": best.mp, "pp": best.pp,
                 "sep": getattr(best, "sep", 1)},
        "measured_eff": round(eff, 4),
        "eff_source": source,
        "step_ms": round(t_step * 1e3, 1),
        "projected_mfu": round(mfu, 4),
        "band": {
            "pessimistic_mfu": round(mfu_pess, 4),
            "optimistic_mfu": round(mfu_opt, 4),
            "corners": "eff -/+5pt x ICI bandwidth 0.5x/2x",
            "pessimistic_meets_40pct": bool(mfu_pess >= 0.40),
        },
        "tokens_per_sec_per_chip": round(tok_per_chip, 1),
        "meets_40pct": bool(mfu >= 0.40),
        "terms_ms": {
            "compute": round(t_compute * 1e3, 1),
            "tp_comm": round(est["tp_comm"] * 1e3, 1),
            "sep_comm": round(est.get("sep_comm", 0.0) * 1e3, 1),
            "dp_comm": round(est["dp_comm"] * 1e3, 1),
            "pp_p2p": round(est["pp_p2p"] * 1e3, 1),
            "bubble_frac": round(est["bubble"], 3),
        },
        "memory_gb_per_chip": round(est["memory_bytes"] / 1e9, 1),
    }))


if __name__ == "__main__":
    main()
