"""Benchmark regression gate (~ reference tools/ci_op_benchmark.sh:1 +
check_op_benchmark_result.py:1 + ci_model_benchmark.sh:37-60 discipline).

Compares a fresh chip measurement against the commit-stamped last
recorded row and FAILS (exit 1) on >threshold regression, so a round
cannot silently ship a slower build. Modes:

  python tools/bench_gate.py check <fresh.json>   # compare a bench.py
      output file (or '-' for stdin) against PERF_LAST_TPU.json
  python tools/bench_gate.py serving <fresh.jsonl> [--stamp]
  python tools/bench_gate.py obs <fresh.jsonl>
      # gate the OBSERVABILITY rows (tools/serving_workload_bench.py
      # --obs-overhead / --trace-out / --slo / --cost). Four
      # families, judged by whichever is present (all that are;
      # combined verdict printed last):
      #  - obs_overhead: engine wall time with obs merged but tracing
      #    OFF must stay within 2% of the no-obs baseline arm measured
      #    in the same process — instrumentation has to be free when
      #    nobody is looking.
      #  - obs_trace: a --trace-out run's span accounting must
      #    balance: every opened request root closed, events present.
      #  - obs_slo: on the seeded chaos trace, the SLO watchdog must
      #    detect every injected crash/stall as an incident exactly
      #    once with ZERO fault-free false positives, incident JSONL
      #    + postmortem bundles byte-identical across replays, engine
      #    outputs/slot-logs/metrics untouched by the monitor, and
      #    (when the obs_overhead row carries a monitor arm) the
      #    monitor-on wall tax <= 2% over no-obs.
      #  - obs_cost: the resource-attribution ledger must conserve
      #    exactly (sum(attributed) + idle == elapsed per engine
      #    book, page-turns == pool-occupancy integral), attribute
      #    every unit, keep ledger-off/on streams identical, account
      #    exactly once across the chaos crash+failover, and (when
      #    the obs_overhead row carries a ledger arm) cost <= 2%
      #    wall tax over no-obs.
      # gate the SERVING rows. Two canonical families, judged by
      # whichever is present (both when both are):
      #  - spec_vs_plain_compiled (tools/spec_decode_bench.py):
      #    spec-compiled vs compiled-plain decode throughput; a
      #    recorded spec compile failure also FAILS here (the claim is
      #    gated either way, not anecdotal). --stamp records the fresh
      #    row as the new baseline (PERF_LAST_SERVING.json) after a
      #    pass.
      #  - serving_workload (tools/serving_workload_bench.py): the
      #    routed policy must hold >= (1 - threshold) x the best FIXED
      #    policy's tokens/sec on the mixed trace, and the policies'
      #    greedy outputs must agree; a missing routed/fixed row FAILs
      #    with a clean record (graceful, never a traceback).
      #  - serving_qos (tools/serving_workload_bench.py --qos): under
      #    the 2x-overload multi-tenant trace, the QoS scheduler's
      #    goodput (tokens from SLO-met requests only) must reach
      #    >= 1.15x the FIFO baseline's, tight-deadline-cohort SLO
      #    attainment must hold >= 0.9, and the rows' aggregates must
      #    prove shed requests were never counted as SLO hits
      #    (deadline_hits <= completed, shed + completed == arrived).
      #  - serving_prefix (tools/serving_workload_bench.py --prefix):
      #    on the recurring-system-prompt trace, automatic prefix
      #    caching must save >= 30% prefill tokens and improve round-2
      #    TTFT p50 >= 1.3x vs the cache-off arm, with byte-identical
      #    greedy tokens and the pool census invariant (resident +
      #    evictable + free == pool size) held at every engine turn.
      #  - serving_cluster (tools/serving_workload_bench.py --cluster):
      #    on the ~10^5-request multi-replica overload trace,
      #    prefix_aware placement must reach >= 1.15x round_robin's
      #    aggregate goodput with Jain fairness held and strictly more
      #    prefill saved; greedy streams must agree across placements
      #    and the single-engine oracle; per-tenant request
      #    conservation (completed + shed == arrived) must hold
      #    cluster-wide AND across the mid-trace drain+join arm, with
      #    the drained replica's pool census balanced at removal.
      #  - serving_chaos (tools/serving_workload_bench.py --chaos):
      #    under the seeded crash+stall+decode-error schedule, zero
      #    requests lost or duplicated (census conservation at every
      #    membership change), completed streams token-identical to
      #    the fault-free replay, goodput >= 0.80x fault-free.
      #  - serving_disagg (tools/serving_workload_bench.py --disagg):
      #    on the prefill-heavy burst trace, the async prefill lane's
      #    TPOT p95 must be >= 1.3x better than the interleaved loop
      #    with TTFT p50 held, token-identical streams across the
      #    lane and both cluster arms, and the disaggregated
      #    cluster's KV-handoff census balanced (every exported chain
      #    imported or reclaimed exactly once).
      #  - serving_hetero (tools/serving_workload_bench.py --hetero):
      #    wide-fp-prefill -> narrow-int8-decode streams token-
      #    identical to the twin fleet, both censuses balanced with
      #    zero failed, the hetero arm resharded on both the page
      #    AND codec axes while the twin arm resharded on none, and
      #    hetero completions >= twin.
      #  - serving_autoscale (tools/serving_workload_bench.py
      #    --autoscale): on the diurnal and flash-crowd traces, the
      #    autoscaled fleet's goodput must be >= a static fleet sized
      #    to the diurnal peak with replica-hours STRICTLY below it,
      #    zero join->drain oscillation inside the hysteresis window,
      #    >= 1 join and >= 1 drain actually taken per trace, the
      #    action log byte-identical across two seeded replays, >= 1
      #    incident closed "action_taken", request conservation on
      #    every arm, and autoscale-off byte-identity (a monitored
      #    router without an autoscaler replays exactly like a plain
      #    one).
      #  - serving_tp (tools/serving_workload_bench.py --tp): the
      #    mesh-sharded decode path must produce greedy streams
      #    bit-equal to the TP=1 engine on the mixed trace (real
      #    tiny-llama factory AND the sim bookkeeping arm), per-device
      #    pool bytes at TP=2 must be <= 0.55x of TP=1 at equal total
      #    capacity, and the capacity demo must hold: a model over the
      #    per-device HBM budget refuses at TP=1 and serves under TP.
      #  - serving_spec (tools/serving_workload_bench.py --spec): on
      #    the mixed churn trace, the adaptive speculative route must
      #    reach >= 1.0x plain decode's tokens/sec with FULL greedy
      #    parity on every stream (speculation changes latency, never
      #    content); the overload arm's BurnRateRule incident —
      #    delivered through QoSScheduler.note_incident — must flip
      #    the route plain and back, with the flip timeline
      #    byte-identical across two seeded replays and censuses
      #    intact on every arm.
      #  - serving_quant (tools/serving_workload_bench.py --kv-quant):
      #    the always-int8 KV pool must measure <= 0.55x the fp
      #    pool's per-device bytes at equal page count, reach >= 1.0x
      #    fp tokens/sec at an EQUAL byte budget (capacity converts
      #    to throughput), hold teacher-forced logits within 5% of
      #    fp, serve the HBM-budget pair the fp build refuses, keep
      #    the kv_quant=None arm free of quant machinery, and the
      #    sim pressure arm must compact parked pages identically
      #    across two seeded replays with token parity and the pool
      #    census intact.
      #  - serving_hostmem (tools/serving_workload_bench.py
      #    --hostmem): on the multi-turn session trace at one fixed
      #    HBM page budget, effective capacity (HBM pages + peak
      #    arena pages) must reach >= 3x the HBM budget, round-2
      #    TTFT p50 must beat the recompute arm by at least the
      #    priced mean kv_pagein transfer cost, every preempted/
      #    swapped stream must match the sim oracle exactly (zero
      #    diverged, >= 1 preempt and restore), the hostmem engine's
      #    shed count must sit STRICTLY below the shed-only
      #    engine's, pool AND arena censuses must hold on every
      #    armed arm, and the hostmem=None arm must stay
      #    byte-identical with no hostmem keys.
      #  - serving_grammar (tools/serving_workload_bench.py
      #    --grammar): on the seeded Zipf-schema trace every
      #    completed constrained stream must detokenize to JSON its
      #    schema validates (parse_frac == 1.0), free rows must stay
      #    byte-identical to the unconstrained baseline on the
      #    common length, constrained goodput must reach >= 0.95x
      #    the budget-matched unconstrained run, the decode
      #    program-cache must stay flat in schema count, and the
      #    grammar cache's resident+evictable+free census must hold.

The training gate compares the LEGACY row when present (fixed MHA
config — stable across rounds) and falls back to the headline value; a
config change that renames rows therefore can't masquerade as a
speedup.
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
THRESHOLD = 0.05  # fail on >5% MFU regression


def _legacy_mfu(detail: dict, fallback: float) -> float:
    row = detail.get("legacy_mha_config")
    if isinstance(row, dict) and "mfu" in row:
        return float(row["mfu"])
    return fallback


def load_baseline():
    rec_path = os.path.join(REPO, "PERF_LAST_TPU.json")
    if not os.path.exists(rec_path):
        return None
    with open(rec_path) as f:
        return json.load(f)


def check(fresh: dict, last: dict | None) -> int:
    if last is None:
        print(json.dumps({"gate": "skip",
                          "reason": "no PERF_LAST_TPU.json baseline"}))
        return 0
    last_legacy = _legacy_mfu(last, float(last.get("mfu", 0.0)))
    detail = fresh.get("detail", {})
    fresh_head = float(fresh.get("value", 0.0))
    fresh_legacy = _legacy_mfu(detail, fresh_head)
    ratio = fresh_legacy / last_legacy if last_legacy else 1.0
    rec = {
        "gate": "pass" if ratio >= 1.0 - THRESHOLD else "FAIL",
        "fresh_legacy_mfu": round(fresh_legacy, 4),
        "last_legacy_mfu": round(last_legacy, 4),
        "fresh_headline_mfu": round(fresh_head, 4),
        "ratio": round(ratio, 4),
        "threshold": THRESHOLD,
        "baseline_commit": last.get("measured_at_commit", "?"),
    }
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


SERVING_BASELINE = "PERF_LAST_SERVING.json"


def _serving_baseline_path():
    # env override so tests (and out-of-tree CI) can isolate the
    # stamped baseline from the repo-root file
    return os.environ.get("BENCH_GATE_SERVING_BASELINE",
                          os.path.join(REPO, SERVING_BASELINE))


def load_serving_baseline():
    path = _serving_baseline_path()
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _json_lines(text: str) -> list:
    out = []
    for ln in text.splitlines():
        if ln.startswith("{"):
            try:
                out.append(json.loads(ln))
            except json.JSONDecodeError:
                pass
    return out


def check_serving_workload(rows: list) -> int:
    """Gate the trace-replay rows from tools/serving_workload_bench.py:
    routed tokens/sec must hold >= (1 - THRESHOLD) x the best fixed
    policy's, and the three policies' greedy outputs must agree. The
    routed-vs-fixed claim has no stamped baseline — the fixed arms ARE
    the baseline, re-measured in the same run on the same trace."""
    wl = [r for r in rows if r.get("bench") == "serving_workload"]
    routed = [r for r in wl if r.get("policy") == "routed"]
    fixed = [r for r in wl if r.get("policy") in ("dense", "paged")]
    if not routed:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_workload rows carry no "
                                    "routed-policy row (run tools/"
                                    "serving_workload_bench.py with "
                                    "routed in --policies)"}))
        return 1
    if not fixed:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_workload rows carry no "
                                    "fixed-policy (dense/paged) row to "
                                    "compare routed against"}))
        return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_workload_summary"]
    if any(r.get("outputs_match") is False for r in summaries):
        print(json.dumps({"gate": "FAIL",
                          "reason": "policies produced DIVERGING greedy "
                                    "outputs on the same trace "
                                    "(correctness, not routing)"}))
        return 1
    rtps = float(routed[0].get("tokens_per_sec") or 0.0)
    best = max(fixed, key=lambda r: float(r.get("tokens_per_sec") or 0.0))
    btps = float(best.get("tokens_per_sec") or 0.0)
    if btps <= 0 or rtps <= 0:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_workload rows carry no "
                                    "tokens_per_sec (empty trace?)"}))
        return 1
    ratio = rtps / btps
    rec = {
        "gate": "pass" if ratio >= 1.0 - THRESHOLD else "FAIL",
        "routed_tokens_per_sec": round(rtps, 4),
        "best_fixed_policy": best.get("policy"),
        "best_fixed_tokens_per_sec": round(btps, 4),
        "routed_vs_best_fixed": round(ratio, 4),
        "threshold": THRESHOLD,
        "device": routed[0].get("device", "?"),
    }
    if rec["gate"] == "FAIL":
        rec["reason"] = (f"routed loses the mixed trace to "
                         f"{best.get('policy')} by {1 - ratio:.1%} — see "
                         "the serving_workload_diagnosis row for the "
                         "routing rule to re-measure")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


QOS_GOODPUT_FLOOR = 1.15   # qos goodput must beat fifo by >= 15%
QOS_TIGHT_SLO_FLOOR = 0.90  # tight-deadline cohort attainment floor


def check_serving_qos(rows: list) -> int:
    """Gate the overload rows from serving_workload_bench.py --qos:
    the QoS scheduler earns its keep only if goodput under 2x overload
    beats FIFO by >= QOS_GOODPUT_FLOOR while the tight-deadline cohort
    still attains >= QOS_TIGHT_SLO_FLOOR. Like the workload family,
    FIFO is the baseline re-measured in the same run on the same trace
    — no stamped file. The shed-accounting invariant is checked from
    the aggregates: a shed request must appear in `shed`, never in
    `deadline_hits` (hits <= completed and shed + completed ==
    arrived would both break if sheds were counted as served)."""
    qr = [r for r in rows if r.get("bench") == "serving_qos"]
    by = {r.get("scheduler"): r for r in qr}
    fifo, qos = by.get("fifo"), by.get("qos")
    if fifo is None or qos is None:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_qos rows need BOTH a fifo "
                                    "and a qos scheduler row (run "
                                    "tools/serving_workload_bench.py "
                                    "--qos)"}))
        return 1
    for r in (fifo, qos):
        hits = int(r.get("deadline_hits") or 0)
        completed = int(r.get("completed") or 0)
        shed = int(r.get("shed") or 0)
        arrived = int(r.get("arrived") or 0)
        if hits > completed or shed + completed != arrived:
            print(json.dumps({
                "gate": "FAIL", "scheduler": r.get("scheduler"),
                "reason": f"shed accounting broken: deadline_hits "
                          f"{hits} / completed {completed} / shed "
                          f"{shed} / arrived {arrived} — a shed "
                          f"request may have been counted as an SLO "
                          f"hit"}))
            return 1
    ftps = float(fifo.get("goodput_tokens_per_sec") or 0.0)
    qtps = float(qos.get("goodput_tokens_per_sec") or 0.0)
    if ftps <= 0 or qtps <= 0:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_qos rows carry no "
                                    "goodput_tokens_per_sec (no "
                                    "deadlines in the trace?)"}))
        return 1
    ratio = qtps / ftps
    tight = qos.get("slo_tight_attained")
    rec = {
        "gate": "pass",
        "qos_goodput_tokens_per_sec": round(qtps, 4),
        "fifo_goodput_tokens_per_sec": round(ftps, 4),
        "qos_vs_fifo_goodput": round(ratio, 4),
        "goodput_floor": QOS_GOODPUT_FLOOR,
        "slo_tight_attained": tight,
        "tight_floor": QOS_TIGHT_SLO_FLOOR,
        "shed_rate": qos.get("shed_rate"),
        "overload": qos.get("overload"),
        "device": qos.get("device", "?"),
    }
    if ratio < QOS_GOODPUT_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"qos goodput only {ratio:.3f}x fifo under "
                         f"overload (floor {QOS_GOODPUT_FLOOR}) — the "
                         "scheduler is not earning its shed rate")
    elif int(qos.get("tight_requests") or 0) > 0 and (
            tight is None or float(tight) < QOS_TIGHT_SLO_FLOOR):
        rec["gate"] = "FAIL"
        rec["reason"] = (f"tight-deadline cohort attained {tight} < "
                         f"{QOS_TIGHT_SLO_FLOOR} under qos — goodput "
                         "was bought by abandoning the interactive "
                         "cohort")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


PREFIX_SAVED_FLOOR = 0.30     # prefill tokens saved by cache-on
PREFIX_TTFT2_FLOOR = 1.30     # round-2 TTFT p50 improvement floor


def check_serving_prefix(rows: list) -> int:
    """Gate the prefix-cache rows from serving_workload_bench.py
    --prefix: on the recurring-system-prompt trace (fixed clock,
    per-chunk prefill pricing) the cache-on arm must save >=
    PREFIX_SAVED_FLOOR of the cache-off arm's prefill tokens AND
    improve round-2 TTFT p50 by >= PREFIX_TTFT2_FLOOR, with byte-
    identical greedy tokens per request, and BOTH arms' pool census
    must have held resident + evictable + free == pool size at every
    engine turn (the refcount/LRU accounting invariant). Cache-off is
    the baseline re-measured in the same run — no stamped file."""
    pr = [r for r in rows if r.get("bench") == "serving_prefix"]
    by = {r.get("cache"): r for r in pr}
    off, on = by.get("off"), by.get("on")
    if off is None or on is None:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_prefix rows need BOTH a "
                                    "cache-off and a cache-on arm (run "
                                    "tools/serving_workload_bench.py "
                                    "--prefix)"}))
        return 1
    for r in (off, on):
        cs = r.get("cache_stats") or {}
        counted = (cs.get("resident_pages", -1)
                   + cs.get("evictable_pages", 0)
                   + cs.get("free_pages", 0))
        if cs.get("invariant_ok") is not True \
                or counted != cs.get("n_pages"):
            print(json.dumps({
                "gate": "FAIL", "cache": r.get("cache"),
                "reason": f"refcount/LRU accounting broken: resident+"
                          f"evictable+free == {counted} vs pool "
                          f"{cs.get('n_pages')} (invariant_ok="
                          f"{cs.get('invariant_ok')}) — pages leaked "
                          f"or double-counted"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_prefix_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_prefix_summary row — "
                                    "cached-vs-uncached token parity "
                                    "is UNVERIFIED (rerun tools/"
                                    "serving_workload_bench.py "
                                    "--prefix end to end)"}))
        return 1
    if any(r.get("outputs_match") is not True for r in summaries):
        print(json.dumps({"gate": "FAIL",
                          "reason": "cache-on produced DIVERGING greedy "
                                    "tokens vs cache-off on the same "
                                    "trace (correctness, not savings)"}))
        return 1
    p_off = float(off.get("prefill_tokens") or 0.0)
    p_on = float(on.get("prefill_tokens") or 0.0)
    t_off = off.get("ttft_round2_p50")
    t_on = on.get("ttft_round2_p50")
    if p_off <= 0 or not t_off or not t_on:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_prefix rows carry no "
                                    "prefill_tokens / ttft_round2_p50 "
                                    "(empty trace or single round?)"}))
        return 1
    saved = 1.0 - p_on / p_off
    imp = float(t_off) / float(t_on)
    rec = {
        "gate": "pass",
        "prefill_tokens_saved_frac": round(saved, 4),
        "saved_floor": PREFIX_SAVED_FLOOR,
        "ttft_round2_improvement": round(imp, 4),
        "ttft2_floor": PREFIX_TTFT2_FLOOR,
        "hit_rate": (on.get("cache_stats") or {}).get("hit_rate"),
        "evictions": (on.get("cache_stats") or {}).get("evictions"),
        "device": on.get("device", "?"),
    }
    if saved < PREFIX_SAVED_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"cache-on saved only {saved:.1%} of prefill "
                         f"tokens (floor {PREFIX_SAVED_FLOOR:.0%}) — "
                         "retention is not serving the recurring "
                         "prefixes")
    elif imp < PREFIX_TTFT2_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"round-2 TTFT p50 improved only {imp:.3f}x "
                         f"(floor {PREFIX_TTFT2_FLOOR}) — the saved "
                         "prefill is not reaching time-to-first-token")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


CLUSTER_GOODPUT_FLOOR = 1.15  # prefix_aware vs round_robin goodput


def check_serving_cluster(rows: list) -> int:
    """Gate the multi-replica rows from serving_workload_bench.py
    --cluster: on the ~10^5-request overload trace (fixed clock, sim
    replicas) prefix_aware placement must reach >=
    CLUSTER_GOODPUT_FLOOR x round_robin's aggregate goodput WITHOUT
    trading fairness away (Jain >= round_robin's) and with strictly
    more prefill tokens saved; greedy streams must agree across all
    placements and the single-engine oracle; every placement's census
    must conserve requests (completed + shed == arrived per tenant, no
    rid lost or duplicated) with the pool invariant held; and the
    drain+join arm must conserve across the mid-trace lifecycle with
    the drained replica's census balanced at removal. round_robin is
    the baseline re-measured in the same run — no stamped file."""
    cr = [r for r in rows if r.get("bench") == "serving_cluster"]
    by = {r.get("placement"): r for r in cr}
    rr, pa = by.get("round_robin"), by.get("prefix_aware")
    if rr is None or pa is None:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_cluster rows need BOTH a "
                                    "round_robin and a prefix_aware "
                                    "placement row (run tools/serving_"
                                    "workload_bench.py --cluster)"}))
        return 1
    for r in cr:
        if r.get("conserved") is not True \
                or r.get("pool_census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "placement": r.get("placement"),
                "reason": "cluster census broken: conserved="
                          f"{r.get('conserved')} pool_census_ok="
                          f"{r.get('pool_census_ok')} — a request was "
                          "lost/duplicated or pages leaked"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_cluster_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_cluster_summary row — "
                                    "cross-placement/oracle token "
                                    "parity is UNVERIFIED (rerun the "
                                    "--cluster arm end to end)"}))
        return 1
    s = summaries[-1]
    if s.get("parity_ok") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "placements produced DIVERGING "
                                    "greedy streams vs each other or "
                                    "the single-engine oracle "
                                    "(correctness, not placement)",
                          "parity_vs_oracle":
                          s.get("parity_vs_oracle")}))
        return 1
    life = [r for r in rows
            if r.get("bench") == "serving_cluster_lifecycle"]
    if not life:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_cluster_lifecycle row "
                                    "— the drain/join conservation "
                                    "invariant is UNVERIFIED"}))
        return 1
    lf = life[-1]
    if not (lf.get("conserved") is True
            and lf.get("removal_census_ok") is True
            and lf.get("pool_census_ok") is True
            and int(lf.get("requeued") or 0) >= 1
            and lf.get("parity_vs_oracle") is True):
        print(json.dumps({
            "gate": "FAIL",
            "reason": "drain/join invariant broken: conserved="
                      f"{lf.get('conserved')} removal_census_ok="
                      f"{lf.get('removal_census_ok')} requeued="
                      f"{lf.get('requeued')} parity="
                      f"{lf.get('parity_vs_oracle')} (requeued must "
                      "be >= 1 or the drain never exercised the "
                      "requeue path)",
            "lost": lf.get("lost"),
            "duplicated": lf.get("duplicated")}))
        return 1
    tr_rows = [r for r in rows
               if r.get("bench") == "serving_cluster_trace"]
    if tr_rows:
        reps = tr_rows[-1].get("replicas") or []
        idle = [r.get("replica") for r in reps
                if not (r.get("slot_busy_frac") or 0) > 0
                or not (r.get("requests") or 0) > 0]
        if not reps or idle:
            print(json.dumps({
                "gate": "FAIL",
                "reason": f"per-replica trace evidence broken: "
                          f"replicas {idle or 'MISSING'} show zero "
                          "slot occupancy or zero requests in the "
                          "chrome trace"}))
            return 1
    rr_g = float(rr.get("goodput_tokens_per_sec") or 0.0)
    pa_g = float(pa.get("goodput_tokens_per_sec") or 0.0)
    if rr_g <= 0 or pa_g <= 0:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_cluster rows carry no "
                                    "goodput_tokens_per_sec (no "
                                    "deadlines in the trace?)"}))
        return 1
    ratio = pa_g / rr_g
    jain_rr = rr.get("fairness_jain")
    jain_pa = pa.get("fairness_jain")
    saved_rr = int(rr.get("prefill_tokens_saved") or 0)
    saved_pa = int(pa.get("prefill_tokens_saved") or 0)
    rec = {
        "gate": "pass",
        "prefix_vs_round_robin_goodput": round(ratio, 4),
        "goodput_floor": CLUSTER_GOODPUT_FLOOR,
        "fairness_jain_round_robin": jain_rr,
        "fairness_jain_prefix_aware": jain_pa,
        "prefill_saved_round_robin": saved_rr,
        "prefill_saved_prefix_aware": saved_pa,
        "requests": rr.get("arrived"),
        "replicas": rr.get("replicas"),
        "requeued_in_lifecycle": lf.get("requeued"),
    }
    if ratio < CLUSTER_GOODPUT_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"prefix_aware goodput only {ratio:.3f}x "
                         f"round_robin (floor {CLUSTER_GOODPUT_FLOOR})"
                         " — placement is not converting prefix "
                         "locality into goodput")
    elif jain_rr is not None and (jain_pa is None
                                  or float(jain_pa)
                                  < float(jain_rr) - 1e-9):
        rec["gate"] = "FAIL"
        rec["reason"] = (f"prefix_aware Jain fairness {jain_pa} fell "
                         f"below round_robin's {jain_rr} — goodput "
                         "was bought by starving a tenant")
    elif saved_pa <= saved_rr:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"prefix_aware saved {saved_pa} prefill "
                         f"tokens vs round_robin's {saved_rr} — "
                         "sharers are not being co-placed with their "
                         "prefixes")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


DISAGG_TPOT_FLOOR = 1.30   # lane TPOT p95 improvement floor
DISAGG_TTFT_HOLD = 1.02    # lane TTFT p50 may drift <= 2% ("no worse")


def check_serving_disagg(rows: list) -> int:
    """Gate the disaggregation rows from serving_workload_bench.py
    --disagg: on the prefill-heavy burst trace (fixed unit-cost
    clock) the async prefill lane's TPOT p95 must be >=
    DISAGG_TPOT_FLOOR x better than the interleaved loop's while TTFT
    p50 holds (<= DISAGG_TTFT_HOLD x — "no worse", with a 2% guard
    band), every arm's greedy streams must be token-identical
    (in-engine lane AND both cluster arms vs the interleaved
    baseline), and the cluster KV-handoff census must balance: every
    exported chain imported or reclaimed exactly once, with at least
    one handoff actually exercised (a disagg gate that moved no KV
    gates nothing). The interleaved arm is the baseline re-measured
    in the same run — no stamped file."""
    dr = [r for r in rows if r.get("bench") == "serving_disagg"]
    by = {r.get("arm"): r for r in dr}
    il, ln = by.get("interleaved"), by.get("async_lane")
    if il is None or ln is None:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_disagg rows need BOTH an "
                                    "interleaved and an async_lane "
                                    "arm (run tools/serving_workload_"
                                    "bench.py --disagg)"}))
        return 1
    for r in dr:
        if r.get("census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "pool census broken under the prefill lane "
                          "— pages leaked or double-counted"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_disagg_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_disagg_summary row — "
                                    "lane-vs-interleaved token parity "
                                    "is UNVERIFIED (rerun the "
                                    "--disagg arm end to end)"}))
        return 1
    s = summaries[-1]
    if s.get("outputs_match") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "the async lane produced "
                                    "DIVERGING greedy tokens vs the "
                                    "interleaved loop on the same "
                                    "trace (correctness, not "
                                    "latency)"}))
        return 1
    if s.get("cluster_parity_ok") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "a cluster arm's streams diverged "
                                    "from the interleaved baseline — "
                                    "the KV handoff is corrupting "
                                    "chains"}))
        return 1
    cl = [r for r in rows
          if r.get("bench") == "serving_disagg_cluster"]
    dis_cl = [r for r in cl if r.get("arm") == "cluster_disagg"]
    if not dis_cl:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no cluster_disagg row — the "
                                    "handoff census is UNVERIFIED"}))
        return 1
    for r in cl:
        if r.get("conserved") is not True \
                or r.get("pool_census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "cluster census broken: conserved="
                          f"{r.get('conserved')} pool_census_ok="
                          f"{r.get('pool_census_ok')}"}))
            return 1
    ho = dis_cl[-1].get("handoffs") or {}
    if not int(ho.get("exported") or 0) \
            or ho.get("balanced") is not True \
            or int(ho.get("failed") or 0):
        print(json.dumps({"gate": "FAIL",
                          "reason": f"KV handoff census: exported="
                                    f"{ho.get('exported')} balanced="
                                    f"{ho.get('balanced')} failed="
                                    f"{ho.get('failed')} — every "
                                    "exported chain must be imported "
                                    "or reclaimed exactly once, at "
                                    "least one must have moved, and "
                                    "none may fail ('balanced' alone "
                                    "would count failures as "
                                    "success)",
                          "handoffs": ho}))
        return 1
    # intersection-only parity would let dropped requests vanish from
    # the comparison: the disagg cluster must COMPLETE what the
    # interleaved baseline completed
    if int(dis_cl[-1].get("completed") or 0) \
            != int(il.get("completed") or 0):
        print(json.dumps({"gate": "FAIL",
                          "reason": f"cluster_disagg completed "
                                    f"{dis_cl[-1].get('completed')} "
                                    f"requests vs the interleaved "
                                    f"baseline's "
                                    f"{il.get('completed')} — "
                                    "requests were dropped, not "
                                    "just re-placed"}))
        return 1
    tpot_imp = s.get("tpot_p95_improvement")
    ttft_ratio = s.get("ttft_p50_ratio")
    rec = {
        "gate": "pass",
        "tpot_p95_improvement": tpot_imp,
        "tpot_floor": DISAGG_TPOT_FLOOR,
        "ttft_p50_ratio": ttft_ratio,
        "ttft_hold": DISAGG_TTFT_HOLD,
        "handoffs": ho,
        "parity_compared": s.get("parity_compared"),
        "prefill_chunk_budget": s.get("prefill_chunk_budget"),
        "device": il.get("device", "?"),
    }
    if tpot_imp is None or float(tpot_imp) < DISAGG_TPOT_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"async-lane TPOT p95 only {tpot_imp}x "
                         f"better than interleaved (floor "
                         f"{DISAGG_TPOT_FLOOR}) — decode is still "
                         "stalling behind prefill")
    elif ttft_ratio is None or float(ttft_ratio) > DISAGG_TTFT_HOLD:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"async-lane TTFT p50 is {ttft_ratio}x the "
                         f"interleaved loop's (hold "
                         f"{DISAGG_TTFT_HOLD}) — TPOT was bought by "
                         "stalling first tokens")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


def check_serving_hetero(rows: list) -> int:
    """Gate the heterogeneous-fleet rows from
    serving_workload_bench.py --hetero: the wide-fp-prefill ->
    narrow-int8-decode cluster's greedy streams must be
    token-identical to the twin (equal-geometry) fleet's on the same
    trace, BOTH handoff censuses must balance with ZERO failed (a
    transform that drops chains is not a transform), the hetero arm
    must actually reshard on BOTH mismatch axes (page geometry AND
    codec — a hetero gate that transformed nothing gates nothing)
    while the twin arm resharded on NONE (the absence regression:
    equal-geometry imports must never open a transform span), and
    the hetero fleet must complete no fewer requests than the twin
    fleet. The twin arm is the baseline re-measured in the same run
    — no stamped file."""
    hr = [r for r in rows if r.get("bench") == "serving_hetero"]
    by = {r.get("arm"): r for r in hr}
    tw, he = by.get("twin"), by.get("hetero")
    if tw is None or he is None:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_hetero rows need BOTH a "
                                    "twin and a hetero arm (run "
                                    "tools/serving_workload_bench.py "
                                    "--hetero)"}))
        return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_hetero_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_hetero_summary row — "
                                    "hetero-vs-twin token parity is "
                                    "UNVERIFIED (rerun the --hetero "
                                    "arm end to end)"}))
        return 1
    s = summaries[-1]
    if s.get("outputs_match") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "the heterogeneous fleet produced "
                                    "DIVERGING greedy tokens vs the "
                                    "twin fleet on the same trace — "
                                    "a reshard/repage/transcode step "
                                    "is corrupting chains"}))
        return 1
    for r in (tw, he):
        if r.get("conserved") is not True \
                or r.get("pool_census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "cluster census broken: conserved="
                          f"{r.get('conserved')} pool_census_ok="
                          f"{r.get('pool_census_ok')}"}))
            return 1
        ho = r.get("handoffs") or {}
        if not int(ho.get("exported") or 0) \
                or ho.get("balanced") is not True \
                or int(ho.get("failed") or 0):
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": f"KV handoff census: exported="
                          f"{ho.get('exported')} balanced="
                          f"{ho.get('balanced')} failed="
                          f"{ho.get('failed')} — every exported "
                          "chain must be imported or reclaimed "
                          "exactly once, at least one must have "
                          "moved, and none may fail",
                "handoffs": ho}))
            return 1
    het_rs = he.get("resharded") or {}
    if not (int(het_rs.get("page") or 0)
            and int(het_rs.get("codec") or 0)):
        print(json.dumps({"gate": "FAIL",
                          "reason": "the hetero arm resharded "
                                    f"{het_rs} — a heterogeneous "
                                    "fleet that never ran a "
                                    "kv_repage AND a kv_transcode "
                                    "transform gated nothing"}))
        return 1
    if tw.get("resharded"):
        print(json.dumps({"gate": "FAIL",
                          "reason": "the TWIN arm resharded "
                                    f"{tw.get('resharded')} — "
                                    "equal-geometry imports must "
                                    "never open a transform span "
                                    "(the absence regression)"}))
        return 1
    if int(he.get("completed") or 0) < int(tw.get("completed") or 0):
        print(json.dumps({"gate": "FAIL",
                          "reason": f"hetero completed "
                                    f"{he.get('completed')} requests "
                                    f"vs the twin fleet's "
                                    f"{tw.get('completed')} — priced "
                                    "transforms must trade latency, "
                                    "not completions"}))
        return 1
    rec = {
        "gate": "pass",
        "hetero_resharded": het_rs,
        "hetero_transform_price": he.get("transform_price_total"),
        "twin_completed": tw.get("completed"),
        "hetero_completed": he.get("completed"),
        "handoffs": he.get("handoffs"),
        "device": he.get("device", "?"),
    }
    print(json.dumps(rec))
    return 0


RAGGED_TTFT_FLOOR = 2.0    # burst-cohort TTFT p95 improvement floor
RAGGED_STARVE_SLACK = 1.05  # ragged worst-case TTFT vs per-chunk


def check_serving_ragged(rows: list) -> int:
    """Gate the ragged batched-prefill rows from
    serving_workload_bench.py --ragged: greedy streams must be
    token-identical to per-chunk prefill on EVERY trace (mixed churn,
    prefill-heavy, admission-burst), the burst cohort's TTFT p95 must
    be >= RAGGED_TTFT_FLOOR x better at equal prefill_chunk_budget,
    the real tiny-llama ragged program cache must stay FLAT across
    admission mixes (a fused prefill that recompiles per mix has no
    claim), the lane-starvation aging bound must hold (ragged
    worst-case TTFT within RAGGED_STARVE_SLACK of per-chunk on every
    trace — fusing must not age anyone out), and the fixed clock must
    be byte-identical with dispatch_ahead on. The per-chunk arm is
    the baseline re-measured in the same run — no stamped file."""
    rr = [r for r in rows if r.get("bench") == "serving_ragged"]
    by = {(r.get("trace"), r.get("arm")): r for r in rr}
    if ("admission_burst", "per_chunk") not in by \
            or ("admission_burst", "ragged") not in by:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_ragged rows need BOTH a "
                                    "per_chunk and a ragged arm on "
                                    "the admission_burst trace (run "
                                    "tools/serving_workload_bench.py "
                                    "--ragged)"}))
        return 1
    for r in rr:
        if r.get("census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "trace": r.get("trace"),
                "arm": r.get("arm"),
                "reason": "pool census broken under the ragged lane "
                          "— pages leaked or double-counted"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_ragged_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_ragged_summary row — "
                                    "ragged-vs-per-chunk token parity "
                                    "is UNVERIFIED (rerun the "
                                    "--ragged arm end to end)"}))
        return 1
    s = summaries[-1]
    if s.get("outputs_match") is not True:
        print(json.dumps({"gate": "FAIL",
                          "parity_by_trace": s.get("parity_by_trace"),
                          "reason": "the ragged lane produced "
                                    "DIVERGING greedy tokens vs "
                                    "per-chunk prefill on the same "
                                    "trace (correctness, not "
                                    "latency)"}))
        return 1
    if s.get("program_cache_flat") is not True:
        print(json.dumps({"gate": "FAIL",
                          "cache_calls": s.get("program_cache_calls"),
                          "reason": "ragged prefill RECOMPILED across "
                                    "admission mixes — the fused "
                                    "shape is leaking trace data into "
                                    "jit statics"}))
        return 1
    if s.get("starvation_ok") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "lane-starvation aging bound "
                                    "broken: some request's ragged "
                                    "TTFT exceeds its per-chunk TTFT "
                                    f"by > {RAGGED_STARVE_SLACK}x — "
                                    "fusing is aging rows out"}))
        return 1
    if s.get("dispatch_ahead_parity_ok") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "dispatch_ahead=True changed "
                                    "fixed-clock outputs — the "
                                    "overlap is supposed to be a "
                                    "measured-clock optimization "
                                    "only"}))
        return 1
    imp = s.get("burst_ttft_p95_improvement")
    rec = {
        "gate": "pass",
        "burst_ttft_p95_improvement": imp,
        "ttft_floor": RAGGED_TTFT_FLOOR,
        "burst_ttft_p95_per_chunk": s.get("burst_ttft_p95_per_chunk"),
        "burst_ttft_p95_ragged": s.get("burst_ttft_p95_ragged"),
        "program_cache_calls": s.get("program_cache_calls"),
        "prefill_chunk_budget": s.get("prefill_chunk_budget"),
        "device": s.get("device", "?"),
    }
    if imp is None or float(imp) < RAGGED_TTFT_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"burst TTFT p95 only {imp}x better than "
                         f"per-chunk (floor {RAGGED_TTFT_FLOOR}) — "
                         "the fused program is not amortizing the "
                         "admission spike")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


TP_BYTES_CEIL = 0.55  # per-device pool bytes at TP=2 vs TP=1 (the
# >= 1.8x-reduction floor, expressed as the ratio the row carries)


def check_serving_tp(rows: list) -> int:
    """Gate the tensor-parallel rows from serving_workload_bench.py
    --tp: greedy token parity (TP=2 — and TP=4 when the backend had 4
    devices — bit-equal to the TP=1 engine on the mixed trace, real
    factory AND sim arm), per-device pool bytes at TP=2 <=
    TP_BYTES_CEIL x TP=1 at equal total capacity, the pool census
    invariant held on every arm, and the capacity demo (an over-budget
    model refuses at TP=1, serves under TP). A single-device image
    produces no JSON at all — the caller's no-JSON handling reads
    that as FAIL, which is the honest verdict: the claim was not
    checked."""
    tr = [r for r in rows if r.get("bench") == "serving_tp"]
    by = {r.get("arm"): r for r in tr}
    if "tp1" not in by or "tp2" not in by:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_tp rows need BOTH a tp1 "
                                    "and a tp2 arm (run tools/"
                                    "serving_workload_bench.py --tp "
                                    "on a multi-device backend)"}))
        return 1
    for r in tr:
        if r.get("census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "pool census broken under the sharded "
                          "engine — pages leaked or double-counted"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_tp_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_tp_summary row — "
                                    "TP-vs-TP1 token parity is "
                                    "UNVERIFIED (rerun the --tp arm "
                                    "end to end)"}))
        return 1
    s = summaries[-1]
    for key, what in (("parity_tp2", "TP=2"),
                      ("sim_parity", "the sim TP arm")):
        if s.get(key) is not True:
            print(json.dumps({"gate": "FAIL",
                              "reason": f"{what} produced DIVERGING "
                                        "greedy tokens vs the TP=1 "
                                        "engine on the same trace "
                                        "(correctness, not layout)"}))
            return 1
    if "tp4" in by and s.get("parity_tp4") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "a tp4 arm ran but its streams "
                                    "diverged from TP=1 (or the "
                                    "summary never compared them)"}))
        return 1
    caps = [r for r in rows
            if r.get("bench") == "serving_tp_capacity"]
    if not caps or caps[-1].get("tp1_refused") is not True \
            or caps[-1].get("tp2_served") is not True:
        c = caps[-1] if caps else {}
        print(json.dumps({"gate": "FAIL",
                          "reason": "capacity demo failed: a model "
                                    "over the per-device budget must "
                                    "REFUSE at TP=1 (got "
                                    f"refused={c.get('tp1_refused')}) "
                                    "and SERVE with parity under TP "
                                    f"(got served={c.get('tp2_served')})"
                          }))
        return 1
    ratio = s.get("pool_bytes_ratio_tp2")
    rec = {
        "gate": "pass",
        "pool_bytes_ratio_tp2": ratio,
        "bytes_ceil": TP_BYTES_CEIL,
        "bytes_reduction_tp2": s.get("bytes_reduction_tp2"),
        "tp_degrees": s.get("tp_degrees"),
        "parity_tp2": True,
        "parity_tp4": s.get("parity_tp4"),
        "capacity_demo": "tp1 refused / tp2 served",
        "device": by["tp1"].get("device", "?"),
    }
    if ratio is None or float(ratio) > TP_BYTES_CEIL:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"per-device pool bytes at TP=2 are {ratio}x "
                         f"TP=1 (ceiling {TP_BYTES_CEIL}) — the pool "
                         "did not actually shard")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


CHAOS_GOODPUT_FLOOR = 0.80  # goodput under faults vs fault-free


def check_serving_chaos(rows: list) -> int:
    """Gate the fault-tolerance rows from serving_workload_bench.py
    --chaos: on the ~10^5-request sim trace under the seeded
    crash+stall+decode-error schedule, ZERO requests may be lost or
    duplicated (census conservation held at every membership change —
    the crashed replica's pool must census to zero resident pages at
    removal), every completed stream must be token-identical to the
    fault-free replay (failed-over requests resume from their salvaged
    prefix and must not diverge), and goodput under faults must hold
    >= CHAOS_GOODPUT_FLOOR x the fault-free run's. The schedule must
    actually have crashed a replica and retried work (a chaos gate
    that injected nothing proves nothing). Fault-free is the baseline
    re-measured in the same run — no stamped file."""
    cr = [r for r in rows if r.get("bench") == "serving_chaos"]
    by = {r.get("arm"): r for r in cr}
    ff, ch = by.get("fault_free"), by.get("chaos")
    if ff is None or ch is None:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_chaos rows need BOTH a "
                                    "fault_free and a chaos arm (run "
                                    "tools/serving_workload_bench.py "
                                    "--chaos)"}))
        return 1
    for r in (ff, ch):
        if r.get("conserved") is not True \
                or r.get("pool_census_ok") is not True \
                or r.get("removal_census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "chaos census broken: conserved="
                          f"{r.get('conserved')} pool_census_ok="
                          f"{r.get('pool_census_ok')} "
                          "removal_census_ok="
                          f"{r.get('removal_census_ok')} — a request "
                          "was lost/duplicated or a dead replica's "
                          "pages leaked",
                "lost": r.get("lost"),
                "duplicated": r.get("duplicated")}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_chaos_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_chaos_summary row — "
                                    "chaos-vs-fault-free token parity "
                                    "is UNVERIFIED (rerun the --chaos "
                                    "arm end to end)"}))
        return 1
    s = summaries[-1]
    if s.get("lost") or s.get("duplicated"):
        print(json.dumps({"gate": "FAIL",
                          "reason": "requests lost or duplicated "
                                    "across the crash",
                          "lost": s.get("lost"),
                          "duplicated": s.get("duplicated")}))
        return 1
    if s.get("membership_census_ok") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "membership-change census broken: "
                                    "a removed (crashed or drained) "
                                    "replica's pool did not balance "
                                    "at removal"}))
        return 1
    if s.get("parity_ok") is not True \
            or not int(s.get("parity_compared") or 0):
        print(json.dumps({"gate": "FAIL",
                          "reason": "completed streams DIVERGED from "
                                    "the fault-free replay (resume-"
                                    "from-prefix is redoing work "
                                    "wrong), or nothing was compared",
                          "parity_compared": s.get("parity_compared")}))
        return 1
    if s.get("resumed_truncated_unexplained"):
        # prefix parity held, but a salvage-resumed stream came back
        # SHORTER than fault-free with no deadline/cancel/degradation
        # on its record — a resume-budget bug, not a policy truncation
        print(json.dumps({"gate": "FAIL",
                          "reason": "resumed stream(s) shorter than "
                                    "fault-free with nothing on the "
                                    "record to explain it — the "
                                    "resume-from-prefix budget "
                                    "arithmetic is dropping tokens",
                          "rids": s.get(
                              "resumed_truncated_unexplained")}))
        return 1
    if int(s.get("crashes") or 0) < 1 or int(s.get("retried") or 0) < 1:
        print(json.dumps({"gate": "FAIL",
                          "reason": f"the schedule crashed "
                                    f"{s.get('crashes')} replicas and "
                                    f"retried {s.get('retried')} "
                                    "requests — a chaos run that "
                                    "injects nothing gates nothing"}))
        return 1
    ratio = s.get("chaos_vs_fault_free_goodput")
    rec = {
        "gate": "pass",
        "chaos_vs_fault_free_goodput": ratio,
        "goodput_floor": CHAOS_GOODPUT_FLOOR,
        "crashes": s.get("crashes"), "stalls": s.get("stalls"),
        "decode_errors": s.get("decode_errors"),
        "failovers": s.get("failovers"),
        "retried": s.get("retried"), "failed": s.get("failed"),
        "resumed_with_salvage": s.get("resumed_with_salvage"),
        "parity_compared": s.get("parity_compared"),
        "requests": s.get("requests"), "replicas": s.get("replicas"),
        "device": ch.get("device", "?"),
    }
    if ratio is None or float(ratio) < CHAOS_GOODPUT_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"goodput under faults only {ratio} x "
                         f"fault-free (floor {CHAOS_GOODPUT_FLOOR}) — "
                         "failover is losing more than the crashed "
                         "capacity")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


LORA_GOODPUT_FLOOR = 1.2  # multiplexed vs one-model-per-replica split


def check_serving_lora(rows: list) -> int:
    """Gate the multi-model LoRA rows from serving_workload_bench.py
    --lora: on the seeded Zipf-adapter trace at EQUAL replica count,
    the multiplexed fleet (every replica serves every adapter through
    one fixed-shape batch; adapter-aware placement with hot-adapter
    replication) must reach >= LORA_GOODPUT_FLOOR x the
    one-model-per-replica split's goodput, every multiplexed stream
    must be bit-equal to the split's dedicated single-adapter engine
    on the common length (per-adapter greedy parity — the correctness
    claim), and the census must hold on BOTH arms: requests conserved,
    pool pages balanced, and the adapter cache's
    resident+evictable+free slot invariant sampled every turn. The
    split baseline is re-measured in the same run — no stamped
    file. A missing-JSON input is the caller's no-JSON FAIL: the
    claim was not checked."""
    lr = [r for r in rows if r.get("bench") == "serving_lora"]
    by = {r.get("arm"): r for r in lr}
    if "multiplexed" not in by or "split" not in by:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_lora rows need BOTH a "
                                    "multiplexed and a split arm (run "
                                    "tools/serving_workload_bench.py "
                                    "--lora)"}))
        return 1
    for r in lr:
        if r.get("conserved") is not True \
                or r.get("pool_census_ok") is not True \
                or r.get("adapter_census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "lora census broken: conserved="
                          f"{r.get('conserved')} pool_census_ok="
                          f"{r.get('pool_census_ok')} "
                          "adapter_census_ok="
                          f"{r.get('adapter_census_ok')} — a request "
                          "was lost/duplicated, pool pages leaked, or "
                          "an adapter slot escaped the "
                          "resident+evictable+free census"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_lora_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_lora_summary row — "
                                    "the goodput/parity claims are "
                                    "UNVERIFIED (rerun the --lora arm "
                                    "end to end)"}))
        return 1
    s = summaries[-1]
    if s.get("parity_ok") is not True \
            or not int(s.get("parity_compared") or 0):
        print(json.dumps({"gate": "FAIL",
                          "reason": "multiplexed streams DIVERGED "
                                    "from the dedicated "
                                    "single-adapter engines (the "
                                    "batched delta application is "
                                    "mixing adapters across rows), "
                                    "or nothing was compared",
                          "parity_compared": s.get("parity_compared")
                          }))
        return 1
    if s.get("adapter_census_ok") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "adapter-cache census broken in "
                                    "the summary — a pin leaked or a "
                                    "slot was double-counted"}))
        return 1
    ratio = s.get("multiplexed_vs_split_goodput")
    rec = {
        "gate": "pass",
        "multiplexed_vs_split_goodput": ratio,
        "goodput_floor": LORA_GOODPUT_FLOOR,
        "adapters": s.get("adapters"), "replicas": s.get("replicas"),
        "requests": s.get("requests"),
        "adapter_hit_rate_multiplexed":
        s.get("adapter_hit_rate_multiplexed"),
        "adapter_uploads_multiplexed":
        s.get("adapter_uploads_multiplexed"),
        "parity_compared": s.get("parity_compared"),
        "device": by["multiplexed"].get("device", "?"),
    }
    if ratio is None or float(ratio) < LORA_GOODPUT_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"multiplexed goodput only {ratio}x the "
                         f"one-model-per-replica split (floor "
                         f"{LORA_GOODPUT_FLOOR}) — adapter "
                         "multiplexing is not recovering the "
                         "capacity the split strands on cold "
                         "replicas")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


GRAMMAR_GOODPUT_FLOOR = 0.95  # constrained vs unconstrained goodput


def check_serving_grammar(rows: list) -> int:
    """Gate the constrained-decoding rows from
    serving_workload_bench.py --grammar: on the seeded Zipf-schema
    trace every COMPLETED constrained stream must detokenize to JSON
    its schema validates (parse_frac == 1.0 — no partial credit),
    the free rows of the constrained run must be byte-identical to
    the unconstrained baseline on the common stream length (the mask
    never leaks across rows of the shared batch), constrained
    goodput must stay >= GRAMMAR_GOODPUT_FLOOR x the budget-matched
    unconstrained run (the mask is jit data; only the per-schema
    grammar_compile units are priced), the distinct-static-decode-
    length program count must stay flat vs the free arm (schemas are
    data, not programs), and the census must hold on both arms:
    requests conserved, pool pages balanced, and the grammar cache's
    resident+evictable+free slot invariant sampled every turn. The
    free baseline is re-measured in the same run — no stamped file.
    A missing-JSON input is the caller's no-JSON FAIL: the claim was
    not checked."""
    gr = [r for r in rows if r.get("bench") == "serving_grammar"]
    by = {r.get("arm"): r for r in gr}
    if "constrained" not in by or "free" not in by:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_grammar rows need BOTH "
                                    "a constrained and a free arm "
                                    "(run tools/"
                                    "serving_workload_bench.py "
                                    "--grammar)"}))
        return 1
    for r in gr:
        if r.get("conserved") is not True \
                or r.get("pool_census_ok") is not True \
                or (r.get("arm") == "constrained"
                    and r.get("grammar_census_ok") is not True):
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "grammar census broken: conserved="
                          f"{r.get('conserved')} pool_census_ok="
                          f"{r.get('pool_census_ok')} "
                          "grammar_census_ok="
                          f"{r.get('grammar_census_ok')} — a request "
                          "was lost/duplicated, pool pages leaked, or "
                          "a grammar slot escaped the "
                          "resident+evictable+free census"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_grammar_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_grammar_summary row "
                                    "— the parse/parity/goodput "
                                    "claims are UNVERIFIED (rerun "
                                    "the --grammar arm end to end)"}))
        return 1
    s = summaries[-1]
    pf = s.get("constrained_parse_frac")
    if pf != 1.0 or not int(s.get("constrained_checked") or 0):
        print(json.dumps({"gate": "FAIL",
                          "reason": "a completed constrained stream "
                                    "failed to parse/validate "
                                    "against its schema (the "
                                    "allow-mask admitted a token the "
                                    "DFA forbids), or nothing was "
                                    "checked",
                          "constrained_parse_frac": pf,
                          "constrained_checked":
                          s.get("constrained_checked")}))
        return 1
    if s.get("free_parity_ok") is not True \
            or not int(s.get("free_parity_compared") or 0):
        print(json.dumps({"gate": "FAIL",
                          "reason": "free rows DIVERGED from the "
                                    "unconstrained baseline (the "
                                    "grammar mask leaked into "
                                    "all-allow rows of the shared "
                                    "batch), or nothing was compared",
                          "free_parity_compared":
                          s.get("free_parity_compared")}))
        return 1
    if int(s.get("decode_programs_constrained") or 0) > \
            int(s.get("decode_programs_free") or 0) + 1:
        print(json.dumps({"gate": "FAIL",
                          "reason": "constrained arm compiled more "
                                    "decode programs than "
                                    "free-arm + 1 — schemas are "
                                    "leaking into static jit keys "
                                    "instead of riding the mask "
                                    "bank as data",
                          "decode_programs_constrained":
                          s.get("decode_programs_constrained"),
                          "decode_programs_free":
                          s.get("decode_programs_free")}))
        return 1
    if s.get("grammar_census_ok") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "grammar-cache census broken in "
                                    "the summary — a pin leaked or a "
                                    "slot was double-counted"}))
        return 1
    ratio = s.get("constrained_vs_free_goodput")
    rec = {
        "gate": "pass",
        "constrained_vs_free_goodput": ratio,
        "goodput_floor": GRAMMAR_GOODPUT_FLOOR,
        "schemas": s.get("schemas"), "requests": s.get("requests"),
        "constrained_parse_frac": pf,
        "constrained_checked": s.get("constrained_checked"),
        "free_parity_compared": s.get("free_parity_compared"),
        "grammar_compiles": s.get("grammar_compiles"),
        "tokens_masked_frac": s.get("tokens_masked_frac"),
        "device": by["constrained"].get("device", "?"),
    }
    if ratio is None or float(ratio) < GRAMMAR_GOODPUT_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"constrained goodput only {ratio}x the "
                         f"budget-matched unconstrained run (floor "
                         f"{GRAMMAR_GOODPUT_FLOOR}) — the mask "
                         "machinery is costing decode throughput "
                         "beyond the priced per-schema compiles")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


SPEC_TPS_FLOOR = 1.0  # adaptive-spec vs plain decode tokens/sec


def check_serving_spec(rows: list) -> int:
    """Gate the speculative-serving rows from
    serving_workload_bench.py --spec: on the mixed churn trace the
    adaptive route must reach >= SPEC_TPS_FLOOR x plain decode's
    tokens/sec with FULL greedy parity on every stream — equal
    output dicts, not just compared prefixes: speculation changes
    latency, never content — and the overload arm must show the
    fallback actually closing the loop: >= 1 flip to plain while the
    BurnRateRule incident is open, >= 1 re-enable after it closes,
    the whole flip timeline byte-identical across two seeded
    replays, and the pool census intact on every arm. The plain
    baseline is re-measured in the same run — no stamped file. A
    missing-JSON input is the caller's no-JSON FAIL: the claim was
    not checked."""
    sr = [r for r in rows if r.get("bench") == "serving_spec"]
    by = {r.get("arm"): r for r in sr}
    if "plain" not in by or "adaptive_spec" not in by:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_spec rows need BOTH a "
                                    "plain and an adaptive_spec arm "
                                    "(run tools/serving_workload_"
                                    "bench.py --spec)"}))
        return 1
    over = [r for r in rows
            if r.get("bench") == "serving_spec_overload"]
    for r in sr + over:
        if r.get("census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm", "overload"),
                "reason": "pool census broken under the spec route "
                          "— a verify-window page escaped the "
                          "resident+evictable+free invariant"}))
            return 1
    if not over:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_spec_overload row — "
                                    "the fallback claim is "
                                    "UNVERIFIED (rerun the --spec "
                                    "arm end to end)"}))
        return 1
    o = over[-1]
    if not int(o.get("fallback_flips") or 0) \
            or not int(o.get("reenable_flips") or 0):
        print(json.dumps({
            "gate": "FAIL",
            "reason": "the overload arm never flipped the route "
                      f"(fallback={o.get('fallback_flips')} "
                      f"reenable={o.get('reenable_flips')}) — the "
                      "BurnRateRule incident is not reaching "
                      "QoSScheduler.note_incident, or the surge is "
                      "not burning"}))
        return 1
    if o.get("flips_deterministic") is not True:
        print(json.dumps({
            "gate": "FAIL",
            "reason": "route flips diverged across two seeded "
                      "replays — the adaptive gate is reading "
                      "nondeterministic state"}))
        return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_spec_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_spec_summary row — "
                                    "the throughput/parity claims "
                                    "are UNVERIFIED (rerun the "
                                    "--spec arm end to end)"}))
        return 1
    s = summaries[-1]
    if s.get("outputs_match") is not True \
            or not int(s.get("parity_compared") or 0):
        print(json.dumps({
            "gate": "FAIL",
            "reason": "adaptive-spec streams DIVERGED from plain "
                      "decode (verification must make every token "
                      "the target's greedy token), or nothing was "
                      "compared",
            "parity_compared": s.get("parity_compared")}))
        return 1
    ratio = s.get("spec_vs_plain_tokens_per_sec")
    rec = {
        "gate": "pass",
        "spec_vs_plain_tokens_per_sec": ratio,
        "tps_floor": SPEC_TPS_FLOOR,
        "acceptance_rate": s.get("acceptance_rate"),
        "n_draft": s.get("n_draft"),
        "requests": s.get("requests"),
        "parity_compared": s.get("parity_compared"),
        "fallback_flips": o.get("fallback_flips"),
        "reenable_flips": o.get("reenable_flips"),
        "device": by["adaptive_spec"].get("device", "?"),
    }
    if ratio is None or float(ratio) < SPEC_TPS_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"adaptive-spec only {ratio}x plain "
                         f"decode's tokens/sec (floor "
                         f"{SPEC_TPS_FLOOR}) — the draft window is "
                         "not paying for its verify blocks on this "
                         "trace")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


QUANT_BYTES_CEIL = 0.55     # int8 / fp pool bytes per device
QUANT_TPS_FLOOR = 1.0       # int8 vs fp tokens/sec at EQUAL pool bytes
QUANT_REL_ERR_CEIL = 0.05   # teacher-forced logit error vs fp


def check_serving_quant(rows: list) -> int:
    """Gate the quantized paged-KV rows from serving_workload_bench.py
    --kv-quant: the always-int8 pool must measure <= QUANT_BYTES_CEIL
    x the fp pool's per-device bytes at equal page count, win (>=
    QUANT_TPS_FLOOR x) on tokens/sec at an EQUAL byte budget (the
    capacity it bought must convert to throughput, not just a smaller
    census), hold teacher-forced logits within QUANT_REL_ERR_CEIL of
    fp, serve the HBM-budget pair the fp build refuses, keep the
    kv_quant=None row free of any kv_quant machinery, and the sim
    pressure arm must compact pages deterministically across two
    seeded replays with token parity and the pool census intact on
    every arm. A missing-JSON input is the caller's no-JSON FAIL: the
    claim was not checked."""
    qr = [r for r in rows if r.get("bench") == "serving_quant"]
    by = {r.get("arm"): r for r in qr}
    need = ("fp", "int8", "fp_fixed_bytes", "int8_fixed_bytes")
    missing = [a for a in need if a not in by]
    if missing:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_quant rows missing arms "
                                    f"{missing} (run tools/serving_"
                                    "workload_bench.py --kv-quant)"}))
        return 1
    for r in qr:
        if r.get("census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "pool census broken under kv_quant — a "
                          "quantized page escaped the resident+"
                          "evictable+free invariant"}))
            return 1
    if "kv_quant" in by["fp"] or "kv_quant" in by["fp_fixed_bytes"]:
        print(json.dumps({
            "gate": "FAIL",
            "reason": "the kv_quant=None arm carries kv_quant report "
                      "keys — the off mode is no longer inert (PR-5 "
                      "presence convention broken)"}))
        return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_quant_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_quant_summary row — "
                                    "the byte/throughput/accuracy "
                                    "claims are UNVERIFIED (rerun "
                                    "the --kv-quant arm end to "
                                    "end)"}))
        return 1
    s = summaries[-1]
    press = [r for r in rows
             if r.get("bench") == "serving_quant_pressure"]
    rec = {
        "gate": "pass",
        "bytes_ratio": s.get("bytes_ratio"),
        "bytes_ceil": QUANT_BYTES_CEIL,
        "capacity_gain": s.get("capacity_gain"),
        "tps_ratio_fixed_bytes": s.get("tps_ratio_fixed_bytes"),
        "tps_floor": QUANT_TPS_FLOOR,
        "logit_rel_err": s.get("logit_rel_err"),
        "rel_err_ceil": QUANT_REL_ERR_CEIL,
        "pressure_pages_compacted": s.get("pressure_pages_compacted"),
        "device": by["int8"].get("device", "?"),
    }
    ratio = s.get("bytes_ratio")
    if ratio is None or float(ratio) > QUANT_BYTES_CEIL:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"int8 pool measures {ratio}x the fp pool's "
                         f"per-device bytes (ceiling "
                         f"{QUANT_BYTES_CEIL}) — the quantized tier "
                         "is not actually smaller")
    tps = s.get("tps_ratio_fixed_bytes")
    if rec["gate"] == "pass" \
            and (tps is None or float(tps) < QUANT_TPS_FLOOR):
        rec["gate"] = "FAIL"
        rec["reason"] = (f"int8 only reaches {tps}x fp tokens/sec at "
                         f"equal pool bytes (floor {QUANT_TPS_FLOOR})"
                         " — the extra pages are not converting to "
                         "throughput")
    err = s.get("logit_rel_err")
    if rec["gate"] == "pass" \
            and (err is None or float(err) > QUANT_REL_ERR_CEIL):
        rec["gate"] = "FAIL"
        rec["reason"] = (f"teacher-forced logit error {err} exceeds "
                         f"{QUANT_REL_ERR_CEIL} — the int8 cache is "
                         "not faithful enough to serve")
    if rec["gate"] == "pass" and s.get("none_identity") is not True:
        rec["gate"] = "FAIL"
        rec["reason"] = ("kv_quant=None replay diverged or grew "
                         "kv_quant state — the off mode must stay "
                         "byte-identical")
    if rec["gate"] == "pass" \
            and (s.get("capacity_fp_refused") is not True
                 or s.get("capacity_int8_served") is not True):
        rec["gate"] = "FAIL"
        rec["reason"] = ("capacity pair broken (fp_refused="
                         f"{s.get('capacity_fp_refused')} int8_served"
                         f"={s.get('capacity_int8_served')}) — the "
                         "over-budget model must refuse at fp and "
                         "serve under kv_quant='int8'")
    if rec["gate"] == "pass":
        if not press:
            rec["gate"] = "FAIL"
            rec["reason"] = ("no serving_quant_pressure row — the "
                             "compact-under-pressure claim is "
                             "UNVERIFIED")
        else:
            p = press[-1]
            if p.get("deterministic") is not True \
                    or p.get("token_parity_vs_plain") is not True \
                    or not int(p.get("pages_compacted") or 0) \
                    or p.get("census_ok") is not True:
                rec["gate"] = "FAIL"
                rec["reason"] = (
                    "pressure arm broken (deterministic="
                    f"{p.get('deterministic')} parity="
                    f"{p.get('token_parity_vs_plain')} "
                    f"pages_compacted={p.get('pages_compacted')} "
                    f"census_ok={p.get('census_ok')}) — the "
                    "ThresholdRule incident must flip compaction "
                    "identically on two seeded replays without "
                    "touching tokens")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


HOSTMEM_CAPACITY_FLOOR = 3.0  # (HBM + peak arena pages) / HBM pages


def check_serving_hostmem(rows: list) -> int:
    """Gate the KV-memory-hierarchy rows from serving_workload_bench
    .py --hostmem: effective capacity (HBM pages + peak arena pages)
    >= HOSTMEM_CAPACITY_FLOOR x the HBM page budget, round-2 TTFT p50
    beating the recompute arm by at least the priced mean kv_pagein
    transfer cost per round-2 request (the swap must PAY, not just
    work), token parity between the hostmem and recompute arms, ZERO
    preempted/swapped streams diverging from the sim oracle with the
    preempt rung actually exercised (>= 1 preempt, >= 1 restore),
    the hostmem engine shedding STRICTLY fewer requests than the
    shed-only engine at the same deadline overload, pool and arena
    censuses intact on every arm, and the hostmem=None arm carrying
    no hostmem machinery (PR-5 presence convention). A missing-JSON
    input is the caller's no-JSON FAIL: the claim was not checked."""
    hr = [r for r in rows if r.get("bench") == "serving_hostmem"]
    by = {r.get("arm"): r for r in hr}
    need = ("recompute", "hostmem", "swap_overload", "shed_only",
            "shed_hostmem")
    missing = [a for a in need if a not in by]
    if missing:
        print(json.dumps({"gate": "FAIL",
                          "reason": "serving_hostmem rows missing "
                                    f"arms {missing} (run tools/"
                                    "serving_workload_bench.py "
                                    "--hostmem)"}))
        return 1
    for r in hr:
        if r.get("census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": r.get("arm"),
                "reason": "pool census broken under hostmem — a "
                          "spilled page escaped the resident+"
                          "evictable+spilled+free invariant"}))
            return 1
    for arm in ("hostmem", "swap_overload", "shed_hostmem"):
        if by[arm].get("arena_census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "arm": arm,
                "reason": "host arena census broken — a budgeted "
                          "byte escaped the pinned+evictable+free "
                          "invariant"}))
            return 1
    for arm in ("recompute", "shed_only"):
        if any(k in by[arm] for k in ("kv_pageouts", "kv_pageins",
                                      "preemptions",
                                      "preempt_restores",
                                      "arena_census_ok")):
            print(json.dumps({
                "gate": "FAIL", "arm": arm,
                "reason": "the hostmem=None arm carries hostmem "
                          "report keys — the off mode is no longer "
                          "inert (PR-5 presence convention broken)"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_hostmem_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_hostmem_summary row "
                                    "— the capacity/TTFT/parity/shed "
                                    "claims are UNVERIFIED (rerun "
                                    "the --hostmem arm end to end)"}))
        return 1
    s = summaries[-1]
    rec = {
        "gate": "pass",
        "capacity_ratio": s.get("capacity_ratio"),
        "capacity_floor": HOSTMEM_CAPACITY_FLOOR,
        "ttft2_margin": s.get("ttft2_margin"),
        "transfer_cost_per_round2": s.get("transfer_cost_per_round2"),
        "preempts": s.get("preempts"),
        "restores": s.get("restores"),
        "diverged": s.get("diverged"),
        "shed_only": s.get("shed_only"),
        "shed_hostmem": s.get("shed_hostmem"),
        "device": by["hostmem"].get("device", "?"),
    }
    cap = s.get("capacity_ratio")
    if cap is None or float(cap) < HOSTMEM_CAPACITY_FLOOR:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"effective capacity only {cap}x the HBM "
                         f"page budget (floor "
                         f"{HOSTMEM_CAPACITY_FLOOR}) — the arena is "
                         "not actually multiplying capacity")
    margin = s.get("ttft2_margin")
    cost = s.get("transfer_cost_per_round2")
    if rec["gate"] == "pass" \
            and (margin is None or cost is None
                 or float(margin) < float(cost)):
        rec["gate"] = "FAIL"
        rec["reason"] = (f"round-2 TTFT margin {margin} is below the "
                         f"priced transfer cost {cost} — paging the "
                         "session back in does not beat recomputing "
                         "it")
    if rec["gate"] == "pass" and s.get("token_parity") is not True:
        rec["gate"] = "FAIL"
        rec["reason"] = ("hostmem outputs diverge from the recompute "
                         "arm — spill/page-in changed token content")
    if rec["gate"] == "pass" and s.get("none_identity") is not True:
        rec["gate"] = "FAIL"
        rec["reason"] = ("hostmem=None replay diverged or grew "
                         "hostmem state — the off mode must stay "
                         "byte-identical")
    if rec["gate"] == "pass" \
            and (not int(s.get("preempts") or 0)
                 or not int(s.get("restores") or 0)
                 or int(s.get("diverged") or 0) != 0
                 or s.get("diverged") is None):
        rec["gate"] = "FAIL"
        rec["reason"] = (f"swap parity broken (preempts="
                         f"{s.get('preempts')} restores="
                         f"{s.get('restores')} diverged="
                         f"{s.get('diverged')}) — the preempt rung "
                         "must fire and every swapped stream must "
                         "match the oracle exactly")
    if rec["gate"] == "pass" \
            and (s.get("shed_only") is None
                 or s.get("shed_hostmem") is None
                 or not int(s.get("shed_only") or 0)
                 or int(s.get("shed_hostmem"))
                 >= int(s.get("shed_only"))):
        rec["gate"] = "FAIL"
        rec["reason"] = (f"shed rate not strictly below "
                         f"(shed_only={s.get('shed_only')} "
                         f"shed_hostmem={s.get('shed_hostmem')}) — "
                         "preempt-as-swap must beat shed-only at the "
                         "same overload")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


AUTOSCALE_GOODPUT_FLOOR = 1.0   # autoscaled vs static-peak goodput
AUTOSCALE_KINDS = ("diurnal", "flash")


def check_serving_autoscale(rows: list) -> int:
    """Gate the elastic-autoscaling rows from serving_workload_bench.py
    --autoscale: on BOTH workload shapes (diurnal day + flash crowd,
    fixed clock, sim replicas) the autoscaled fleet must reach >=
    AUTOSCALE_GOODPUT_FLOOR x the static peak-sized fleet's goodput
    with replica-hours STRICTLY below it, take >= 1 join and >= 1
    drain (a loop that never acts proves nothing), show ZERO
    join->drain oscillation inside the hysteresis window, close >= 1
    incident with resolution action_taken, write a byte-identical
    action log on a second seeded replay, and conserve every arm's
    request census; autoscale-off must be byte-identical to a plain
    router. The static fleet is the baseline re-measured in the same
    run — no stamped file."""
    ar = [r for r in rows if r.get("bench") == "serving_autoscale"]
    by = {(r.get("trace_kind"), r.get("arm")): r for r in ar}
    for kind in AUTOSCALE_KINDS:
        if (kind, "static_peak") not in by \
                or (kind, "autoscaled") not in by:
            print(json.dumps({
                "gate": "FAIL",
                "reason": f"serving_autoscale rows need BOTH a "
                          f"static_peak and an autoscaled arm for the "
                          f"{kind} trace (run tools/serving_workload_"
                          "bench.py --autoscale)"}))
            return 1
    for r in ar:
        if r.get("conserved") is not True \
                or r.get("pool_census_ok") is not True \
                or r.get("removal_census_ok") is not True:
            print(json.dumps({
                "gate": "FAIL", "trace_kind": r.get("trace_kind"),
                "arm": r.get("arm"),
                "reason": "autoscale census broken: conserved="
                          f"{r.get('conserved')} pool_census_ok="
                          f"{r.get('pool_census_ok')} "
                          "removal_census_ok="
                          f"{r.get('removal_census_ok')} — a request "
                          "was lost/duplicated across membership "
                          "churn or a drained replica's pages "
                          "leaked"}))
            return 1
    summaries = [r for r in rows
                 if r.get("bench") == "serving_autoscale_summary"]
    if not summaries:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no serving_autoscale_summary row "
                                    "— the goodput/hours/oscillation "
                                    "claims are UNVERIFIED (rerun the "
                                    "--autoscale arm end to end)"}))
        return 1
    s = summaries[-1]
    if s.get("action_log_deterministic") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "two seeded replays produced "
                                    "DIFFERENT action logs — the "
                                    "control plane is not "
                                    "deterministic (a non-virtual "
                                    "input leaked into a decision)"}))
        return 1
    if s.get("off_identity") is not True:
        print(json.dumps({"gate": "FAIL",
                          "reason": "autoscale=None is NOT "
                                    "byte-identical to a plain router "
                                    "— the inert path mutated "
                                    "behavior"}))
        return 1
    rec = {"gate": "pass", "goodput_floor": AUTOSCALE_GOODPUT_FLOOR,
           "hysteresis_window": s.get("hysteresis_window"),
           "requests": s.get("requests"),
           "static_replicas": s.get("static_replicas"),
           "device": "sim"}
    for kind in AUTOSCALE_KINDS:
        g = s.get(f"{kind}_goodput_ratio")
        h = s.get(f"{kind}_hours_ratio")
        osc = s.get(f"{kind}_oscillations")
        rec[f"{kind}_goodput_ratio"] = g
        rec[f"{kind}_hours_ratio"] = h
        rec[f"{kind}_joins"] = s.get(f"{kind}_joins")
        rec[f"{kind}_drains"] = s.get(f"{kind}_drains")
        rec[f"{kind}_oscillations"] = osc
        if g is None or float(g) < AUTOSCALE_GOODPUT_FLOOR:
            rec["gate"] = "FAIL"
            rec["reason"] = (f"{kind}: autoscaled goodput only {g}x "
                             f"the static peak-sized fleet's (floor "
                             f"{AUTOSCALE_GOODPUT_FLOOR}) — elasticity "
                             "is losing more goodput to reaction lag "
                             "than it recovers at the peak")
        elif h is None or float(h) >= 1.0:
            rec["gate"] = "FAIL"
            rec["reason"] = (f"{kind}: autoscaled replica-hours {h}x "
                             "the static fleet's — not strictly "
                             "below, so the goodput was bought with "
                             "MORE capacity, not elasticity")
        elif osc is None or int(osc) != 0:
            rec["gate"] = "FAIL"
            rec["reason"] = (f"{kind}: {osc} join->drain "
                             "oscillation(s) inside the hysteresis "
                             "window — the cooldown/hysteresis "
                             "machinery is not holding")
        elif int(s.get(f"{kind}_joins") or 0) < 1 \
                or int(s.get(f"{kind}_drains") or 0) < 1:
            rec["gate"] = "FAIL"
            rec["reason"] = (f"{kind}: joins="
                             f"{s.get(f'{kind}_joins')} drains="
                             f"{s.get(f'{kind}_drains')} — the loop "
                             "never exercised both directions, so "
                             "the elasticity claim is vacuous")
        elif int(s.get(f"{kind}_actions_taken") or 0) < 1:
            rec["gate"] = "FAIL"
            rec["reason"] = (f"{kind}: no incident closed with "
                             "resolution action_taken — the detect->"
                             "act loop never attributed an action to "
                             "the incident that triggered it")
        if rec["gate"] == "FAIL":
            break
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


OBS_OFF_OVERHEAD_MAX = 0.02  # tracing-off tax allowed over no-obs


def check_obs_overhead(rows: list) -> int:
    """Gate the obs_overhead row (serving_workload_bench.py
    --obs-overhead): the tracing-OFF replay's wall time must stay
    within OBS_OFF_OVERHEAD_MAX of the no-obs baseline arm from the
    SAME process — the observability layer must cost nothing while
    disabled. The tracing-ON wall rides along for the record but is
    not gated (recording spans is allowed to cost; turning them off
    must not)."""
    rs = [r for r in rows if r.get("bench") == "obs_overhead"]
    if not rs:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no obs_overhead row in input "
                                    "(run tools/serving_workload_"
                                    "bench.py --obs-overhead)"}))
        return 1
    r = rs[-1]
    noobs = float(r.get("noobs_wall_s") or 0.0)
    off = float(r.get("off_wall_s") or 0.0)
    if noobs <= 0 or off <= 0:
        print(json.dumps({"gate": "FAIL",
                          "reason": "obs_overhead row carries no wall "
                                    "measurements"}))
        return 1
    if r.get("tokens_match") is False:
        print(json.dumps({"gate": "FAIL",
                          "reason": "obs arms generated DIVERGING "
                                    "token counts — instrumentation "
                                    "changed behavior, not just "
                                    "cost"}))
        return 1
    overhead = off / noobs - 1.0
    rec = {
        "gate": "pass" if overhead <= OBS_OFF_OVERHEAD_MAX else "FAIL",
        "overhead_off": round(overhead, 4),
        "max_overhead_off": OBS_OFF_OVERHEAD_MAX,
        "noobs_wall_s": round(noobs, 6),
        "off_wall_s": round(off, 6),
        "on_wall_s": r.get("on_wall_s"),
        "overhead_on": r.get("overhead_on"),
        "trace_events": r.get("trace_events"),
        "device": r.get("device", "?"),
    }
    if rec["gate"] == "FAIL":
        rec["reason"] = (f"tracing-off wall {off:.4f}s is "
                         f"{overhead:.1%} over the no-obs baseline "
                         f"{noobs:.4f}s (max "
                         f"{OBS_OFF_OVERHEAD_MAX:.0%}) — the disabled "
                         "path is not free")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


def check_obs_trace(rows: list) -> int:
    """Gate the obs_trace span-accounting row (a --trace-out run):
    spans were recorded and every opened request root closed — a
    dangling root means a request left the engine untracked."""
    rs = [r for r in rows if r.get("bench") == "obs_trace"]
    if not rs:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no obs_trace row in input (run "
                                    "tools/serving_workload_bench.py "
                                    "with --trace-out)"}))
        return 1
    r = rs[-1]
    unclosed = r.get("unclosed_roots") or []
    rec = {
        "gate": "pass",
        "events": r.get("events"),
        "roots_open": r.get("roots_open"),
        "roots_closed": r.get("roots_closed"),
        "recompiles": r.get("recompiles"),
        "path": r.get("path"),
    }
    if not r.get("events"):
        rec["gate"] = "FAIL"
        rec["reason"] = "trace recorded zero events"
    elif unclosed:
        rec["gate"] = "FAIL"
        rec["reason"] = (f"{len(unclosed)} request root span(s) never "
                         f"closed: {unclosed[:5]}")
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


OBS_SLO_OVERHEAD_MAX = 0.02  # monitor-on tax allowed over no-obs


def check_obs_slo(rows: list) -> int:
    """Gate the obs_slo family (serving_workload_bench.py --slo): on
    the seeded chaos trace the SLO watchdog must detect every injected
    crash and stall as an incident EXACTLY once, fire NOTHING on the
    fault-free replay, produce byte-identical incident JSONL and
    postmortem bundles across two monitored runs (modulo paths), and
    leave engine outputs / slot logs / metrics records byte-identical
    to the monitor-off replay. When the input also carries an
    obs_overhead row with a monitor arm (``overhead_slo``), that tax
    is gated <= OBS_SLO_OVERHEAD_MAX alongside the tracing-off gate."""
    rs = [r for r in rows if r.get("bench") == "obs_slo_summary"]
    if not rs:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no obs_slo_summary row in input "
                                    "(run tools/serving_workload_"
                                    "bench.py --slo)"}))
        return 1
    r = rs[-1]
    reasons = []
    if not r.get("detected_exactly_once"):
        reasons.append(
            f"crash/stall detection not exactly-once: "
            f"{r.get('crash_incidents')}/{r.get('crashes_injected')} "
            f"crashes, "
            f"{r.get('stall_incidents')}/{r.get('stalls_injected')} "
            "stalls")
    if r.get("fault_free_incidents", 1) != 0:
        reasons.append(f"{r.get('fault_free_incidents')} "
                       "false-positive incident(s) on the fault-free "
                       "replay")
    if not r.get("incidents_total"):
        reasons.append("the chaos replay fired ZERO incidents — the "
                       "watchdog is not watching")
    if r.get("incidents_loaded") != r.get("incidents_total"):
        reasons.append("incident JSONL did not round-trip "
                       f"({r.get('incidents_loaded')} loaded of "
                       f"{r.get('incidents_total')})")
    if not r.get("incidents_byte_identical"):
        reasons.append("two monitored replays produced DIFFERENT "
                       "incident JSONL bytes")
    if not r.get("bundles_byte_identical"):
        reasons.append("postmortem bundles diverged across replays "
                       f"(first diff: {r.get('bundle_first_diff')})")
    elif r.get("incidents_total") \
            and not r.get("bundle_files_compared"):
        # two EMPTY bundle trees compare equal — with incidents fired
        # that means the flight recorder wrote nothing, and the
        # byte-identity clause silently tested nothing
        reasons.append("incidents fired but zero bundle files were "
                       "written/compared — the flight recorder is "
                       "not recording")
    for key in ("outputs_identical", "slot_logs_identical",
                "metrics_records_identical",
                "cluster_report_identical"):
        if not r.get(key):
            reasons.append(f"{key} is false — the monitor changed "
                           "the system it watches")
    overhead_slo = None
    for o in rows:
        if o.get("bench") == "obs_overhead" \
                and o.get("overhead_slo") is not None:
            overhead_slo = float(o["overhead_slo"])
    if overhead_slo is not None \
            and overhead_slo > OBS_SLO_OVERHEAD_MAX:
        reasons.append(f"monitor-on wall {overhead_slo:.1%} over the "
                       f"no-obs baseline (max "
                       f"{OBS_SLO_OVERHEAD_MAX:.0%})")
    rec = {
        "gate": "pass" if not reasons else "FAIL",
        "crashes": f"{r.get('crash_incidents')}/"
                   f"{r.get('crashes_injected')}",
        "stalls": f"{r.get('stall_incidents')}/"
                  f"{r.get('stalls_injected')}",
        "incidents_total": r.get("incidents_total"),
        "fault_free_incidents": r.get("fault_free_incidents"),
        "byte_identical": bool(r.get("incidents_byte_identical")
                               and r.get("bundles_byte_identical")),
        "monitor_transparent": bool(
            r.get("outputs_identical")
            and r.get("slot_logs_identical")
            and r.get("metrics_records_identical")),
        "overhead_slo": overhead_slo,
        "by_kind": r.get("by_kind"),
        "device": r.get("device", "?"),
    }
    if reasons:
        rec["reason"] = "; ".join(reasons)
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


OBS_LEDGER_OVERHEAD_MAX = 0.02  # ledger-on tax allowed over no-obs


def check_obs_cost(rows: list) -> int:
    """Gate the obs_cost family (serving_workload_bench.py --cost):
    the resource-attribution ledger must conserve EXACTLY on every
    armed arm — per engine book ``sum(attributed) + idle == elapsed``
    on the fixed virtual clock, per-request page-turns equal to the
    per-turn pool-occupancy integral — attribute every priced unit
    (zero unattributed), leave the off-arm token streams identical to
    ledger-on (a bookkeeper that changes the books it keeps is
    disqualified), and account EXACTLY ONCE across the chaos arm's
    crash + failover (every served rid ledgered, at most one terminal
    outcome per request). When the input also carries an obs_overhead
    row with a ledger arm (``overhead_ledger``), that tax is gated
    <= OBS_LEDGER_OVERHEAD_MAX alongside the tracing-off gate."""
    rs = [r for r in rows if r.get("bench") == "obs_cost_summary"]
    if not rs:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no obs_cost_summary row in input "
                                    "(run tools/serving_workload_"
                                    "bench.py --cost)"}))
        return 1
    r = rs[-1]
    reasons = []
    for arm in ("on", "chaos"):
        if not r.get(f"{arm}_conserved_ok"):
            reasons.append(f"{arm} arm broke unit conservation: "
                           "sum(attributed) + idle != elapsed on "
                           "some engine book")
        if not r.get(f"{arm}_occupancy_ok"):
            reasons.append(f"{arm} arm broke occupancy conservation: "
                           "per-request page-turns != per-turn "
                           "pool-occupancy integral")
        if r.get(f"{arm}_unattributed_units", 1) != 0:
            reasons.append(
                f"{arm} arm left "
                f"{r.get(f'{arm}_unattributed_units')} units "
                "unattributed — every priced unit must carry an "
                "owner")
        if not r.get(f"{arm}_audit_ok"):
            reasons.append(f"{arm} arm audit_ok is false")
    if not r.get("off_on_identical"):
        reasons.append("ledger-on token streams differ from "
                       "ledger-off — the ledger changed the system "
                       "it accounts")
    if not r.get("chaos_exactly_once"):
        reasons.append(
            "chaos accounting not exactly-once: "
            f"unledgered={r.get('chaos_unledgered')} "
            f"multi_terminal={r.get('chaos_multi_terminal')}")
    if not r.get("chaos_parity_ok"):
        reasons.append("chaos completed-stream parity vs ledger-off "
                       "failed — the failover replay diverged")
    overhead_ledger = None
    for o in rows:
        if o.get("bench") == "obs_overhead" \
                and o.get("overhead_ledger") is not None:
            overhead_ledger = float(o["overhead_ledger"])
    if overhead_ledger is not None \
            and overhead_ledger > OBS_LEDGER_OVERHEAD_MAX:
        reasons.append(f"ledger-on wall {overhead_ledger:.1%} over "
                       f"the no-obs baseline (max "
                       f"{OBS_LEDGER_OVERHEAD_MAX:.0%})")
    rec = {
        "gate": "pass" if not reasons else "FAIL",
        "requests": r.get("requests"),
        "conserved": bool(r.get("on_conserved_ok")
                          and r.get("chaos_conserved_ok")),
        "occupancy": bool(r.get("on_occupancy_ok")
                          and r.get("chaos_occupancy_ok")),
        "unattributed_units": r.get("on_unattributed_units"),
        "off_on_identical": r.get("off_on_identical"),
        "chaos_exactly_once": r.get("chaos_exactly_once"),
        "chaos_parity_compared": r.get("chaos_parity_compared"),
        "overhead_ledger": overhead_ledger,
        "device": r.get("device", "?"),
    }
    if reasons:
        rec["reason"] = "; ".join(reasons)
    print(json.dumps(rec))
    return 0 if rec["gate"] == "pass" else 1


def check_obs(rows: list) -> int:
    """The obs gate: judge whichever observability families the input
    carries (all that are); several families present -> the
    LAST record printed carries the combined verdict, matching the
    serving gate's convention."""
    fam_rcs: dict = {}
    if any(r.get("bench") == "obs_overhead" for r in rows):
        fam_rcs["overhead"] = check_obs_overhead(rows)
    if any(r.get("bench") == "obs_trace" for r in rows):
        fam_rcs["trace"] = check_obs_trace(rows)
    if any(r.get("bench", "").startswith("obs_slo") for r in rows):
        fam_rcs["slo"] = check_obs_slo(rows)
    if any(r.get("bench", "").startswith("obs_cost") for r in rows):
        fam_rcs["cost"] = check_obs_cost(rows)
    if not fam_rcs:
        print(json.dumps({"gate": "FAIL",
                          "reason": "no obs_overhead, obs_trace, "
                                    "obs_slo or obs_cost row in "
                                    "input (run tools/"
                                    "serving_workload_bench.py "
                                    "--obs-overhead, --trace-out, "
                                    "--slo or --cost)"}))
        return 1
    if len(fam_rcs) == 1:
        return next(iter(fam_rcs.values()))
    rc = max(fam_rcs.values())
    combined = {"gate": "pass" if rc == 0 else "FAIL",
                "combined": True}
    for k, v in fam_rcs.items():
        combined[f"{k}_gate"] = "pass" if v == 0 else "FAIL"
    print(json.dumps(combined))
    return rc


def check_serving(rows: list, last: dict | None, stamp: bool) -> int:
    """Gate the serving rows: the spec-compiled vs compiled-plain row
    (tools/spec_decode_bench.py), the workload-replay rows
    (tools/serving_workload_bench.py), the QoS overload rows (--qos),
    the prefix-cache rows (--prefix), the multi-replica cluster rows
    (--cluster) and/or the fault-tolerance rows (--chaos) — whichever
    families the input carries; every family present must pass. FAILs
    on: no canonical row at all, a recorded compile failure, output
    divergence, a >threshold regression, a sub-floor qos-vs-fifo
    goodput ratio, broken shed accounting, sub-floor prefix savings /
    TTFT improvement, a broken refcount/LRU census, a sub-floor
    prefix-aware-vs-round-robin cluster goodput ratio, a broken
    cluster/drain-join request-conservation census, a lost/duplicated
    /diverging request across a crash, sub-floor goodput under
    faults, a sub-floor multiplexed-vs-split lora goodput ratio /
    adapter-parity break (--lora), a constrained stream whose text
    fails its schema / a grammar mask leaking into free rows / a
    sub-floor constrained-vs-free goodput ratio (--grammar), or a
    spec route that is slower than plain / breaks greedy parity /
    never flips under overload (--spec) — so the serving claims can
    only change deliberately."""
    fam_rcs: dict = {}
    if any(r.get("bench", "").startswith("serving_workload")
           for r in rows):
        fam_rcs["workload"] = check_serving_workload(rows)
    if any(r.get("bench", "").startswith("serving_qos") for r in rows):
        fam_rcs["qos"] = check_serving_qos(rows)
    if any(r.get("bench", "").startswith("serving_prefix")
           for r in rows):
        fam_rcs["prefix"] = check_serving_prefix(rows)
    if any(r.get("bench", "").startswith("serving_cluster")
           for r in rows):
        fam_rcs["cluster"] = check_serving_cluster(rows)
    if any(r.get("bench", "").startswith("serving_chaos")
           for r in rows):
        fam_rcs["chaos"] = check_serving_chaos(rows)
    if any(r.get("bench", "").startswith("serving_disagg")
           for r in rows):
        fam_rcs["disagg"] = check_serving_disagg(rows)
    if any(r.get("bench", "").startswith("serving_hetero")
           for r in rows):
        fam_rcs["hetero"] = check_serving_hetero(rows)
    if any(r.get("bench", "").startswith("serving_ragged")
           for r in rows):
        fam_rcs["ragged"] = check_serving_ragged(rows)
    if any(r.get("bench", "").startswith("serving_autoscale")
           for r in rows):
        fam_rcs["autoscale"] = check_serving_autoscale(rows)
    if any(r.get("bench", "").startswith("serving_tp") for r in rows):
        fam_rcs["tp"] = check_serving_tp(rows)
    if any(r.get("bench", "").startswith("serving_lora")
           for r in rows):
        fam_rcs["lora"] = check_serving_lora(rows)
    if any(r.get("bench", "").startswith("serving_grammar")
           for r in rows):
        fam_rcs["grammar"] = check_serving_grammar(rows)
    if any(r.get("bench", "").startswith("serving_spec")
           for r in rows):
        fam_rcs["spec"] = check_serving_spec(rows)
    if any(r.get("bench", "").startswith("serving_quant")
           for r in rows):
        fam_rcs["quant"] = check_serving_quant(rows)
    if any(r.get("bench", "").startswith("serving_hostmem")
           for r in rows):
        fam_rcs["hostmem"] = check_serving_hostmem(rows)
    summary = [r for r in rows
               if r.get("bench") == "spec_vs_plain_compiled"]
    if not summary:
        if len(fam_rcs) == 1:
            return next(iter(fam_rcs.values()))  # that gate decides
        if fam_rcs:
            rc = max(fam_rcs.values())
            combined = {"gate": "pass" if rc == 0 else "FAIL",
                        "combined": True}
            for k, v in fam_rcs.items():
                combined[f"{k}_gate"] = "pass" if v == 0 else "FAIL"
            print(json.dumps(combined))
            return rc
        print(json.dumps({"gate": "FAIL",
                          "reason": "no spec_vs_plain_compiled, "
                                    "serving_workload or serving_qos "
                                    "row in input (run tools/"
                                    "spec_decode_bench.py or tools/"
                                    "serving_workload_bench.py "
                                    "[--qos])"}))
        return 1
    errors = [r for r in summary if "error" in r]
    ok = [r for r in summary if "ratio" in r]
    if not ok:
        rec = {"gate": "FAIL",
               "reason": ("spec compiled loop failed to compile/run "
                          "(reproduced failure)" if errors else
                          "spec row carries no ratio (compiled loop "
                          "skipped?)")}
        if errors:
            rec["error"] = str(errors[0].get("error"))[-250:]
        print(json.dumps(rec))
        return 1
    # a divergence on ANY row fails — not just the best-ratio one
    # (the correctness backstop must not be maskable by a faster row)
    diverged = [r for r in ok
                if r.get("output_matches_plain") is False]
    if diverged:
        print(json.dumps({"gate": "FAIL",
                          "reason": "spec output diverged from plain "
                                    "greedy",
                          "n_draft": diverged[0].get("n_draft")}))
        return 1
    best = max(ok, key=lambda r: float(r["ratio"]))
    fresh_ratio = float(best["ratio"])
    rec = {
        "gate": "pass",
        "fresh_spec_vs_plain": round(fresh_ratio, 4),
        "n_draft": best.get("n_draft"),
        "compile_s_spec": best.get("compile_s_spec"),
        "device": best.get("device", "?"),
    }
    if last is None:
        rec["baseline"] = "none (skip regression compare)"
    else:
        base_ratio = float(last.get("ratio", 0.0))
        rec["last_spec_vs_plain"] = round(base_ratio, 4)
        rec["baseline_device"] = last.get("device", "?")
        if base_ratio and fresh_ratio < base_ratio * (1.0 - THRESHOLD):
            rec["gate"] = "FAIL"
            rec["reason"] = (f"spec/plain ratio regressed "
                             f"{fresh_ratio:.3f} < {base_ratio:.3f} "
                             f"- {THRESHOLD:.0%}")
    print(json.dumps(rec))
    spec_rc = 0 if rec["gate"] == "pass" else 1
    rc = max([spec_rc, *fam_rcs.values()])
    if fam_rcs:
        # several families ran: the LAST record must carry the combined
        # verdict — consumers read the final JSON line, and a passing
        # spec record must not mask a failed workload/qos gate there
        combined = {"gate": "pass" if rc == 0 else "FAIL",
                    "combined": True,
                    "spec_gate": "pass" if spec_rc == 0 else "FAIL"}
        for k, v in fam_rcs.items():
            combined[f"{k}_gate"] = "pass" if v == 0 else "FAIL"
        print(json.dumps(combined))
    # stamp only when the COMBINED gate passes: a failing workload
    # family must not mutate the spec baseline on its way out (a rerun
    # would then compare against the freshly stamped row)
    if rc == 0 and stamp:
        path = _serving_baseline_path()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(best, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)
        print(json.dumps({"gate_note": f"stamped {SERVING_BASELINE}"}))
    return rc


def main() -> int:
    mode = sys.argv[1] if len(sys.argv) > 1 else None
    if mode == "check":
        baseline = load_baseline()
        src = sys.argv[2] if len(sys.argv) > 2 else "-"
        text = sys.stdin.read() if src == "-" else open(src).read()
        # bench.py prints one JSON line (possibly after warnings); no
        # JSON line at all is a FAIL record, not a bare IndexError
        # (round-5 advice #3)
        lines = [ln for ln in text.splitlines() if ln.startswith("{")]
        if not lines:
            print(json.dumps({"gate": "FAIL",
                              "reason": "input contains no JSON line "
                                        "(bench produced no row)"}))
            return 1
        return check(json.loads(lines[-1]), baseline)
    if mode == "serving":
        # first non-flag operand is the source; "--stamp" may appear
        # before or after it
        stamp = "--stamp" in sys.argv
        operands = [a for a in sys.argv[2:] if not a.startswith("--")]
        src = operands[0] if operands else "-"
        text = sys.stdin.read() if src == "-" else open(src).read()
        return check_serving(_json_lines(text), load_serving_baseline(),
                             stamp)
    if mode == "obs":
        operands = [a for a in sys.argv[2:] if not a.startswith("--")]
        src = operands[0] if operands else "-"
        text = sys.stdin.read() if src == "-" else open(src).read()
        return check_obs(_json_lines(text))
    raise SystemExit("mode: check <file|-> | "
                     "serving <file|-> [--stamp] | obs <file|->")


if __name__ == "__main__":
    sys.exit(main())
