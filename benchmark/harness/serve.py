"""A serving cell: the engine under open-loop traffic on a wall clock."""
from __future__ import annotations

import gc
import time

import jax

from . import device, serve_check, traffic, weights as W
from .checks import Checks
from .clock import WallClock
from .gclog import GcLog
from .trace import TraceWindow, custom_calls, serving_stretch


class NoTrace(RuntimeError):
    pass


def build_engine(family, config: dict, seed: int, trace_window=None, marks=None):
    from paddle_tpu.serving import ServingEngine

    class BenchEngine(ServingEngine):
        """The engine with the benchmark's wall clock in its clock seam."""
        bench_clock = None

        def _make_clock(self, label="engine"):
            self.bench_clock = WallClock(trace_window)
            return self.bench_clock

    mark = (lambda name: None) if marks is None else marks.add
    args = dict(config["engine"])
    net = family.serving_program(config["model"], args)
    mark("program_model_object")
    family.load_weights(net, W.make_weights(family, config["model"], seed))
    jax.block_until_ready(net.tree_flatten_params())
    mark("weights_from_seed")
    eng = BenchEngine(net, clock="measured", **args)
    mark("engine")
    return eng


def to_requests(reqs: list[dict]):
    from paddle_tpu.serving.workload import Request
    return [Request(rid=r["rid"], arrival=r["arrival"], prompt=r["prompt"],
                    max_new_tokens=r["max_new_tokens"],
                    prefix_group=r["prefix_group"]) for r in reqs]


def observe(reqs, res, clock, family, model, peak) -> dict:
    """What the window did, in plain lists: every reader works from this.
    ``engine_record`` hands on, untouched, what the engine kept for that
    request beyond tokens and stamps (``ServeResult.request_records``, where
    an engine has it): a family whose step does not yield one token a
    sequence counts its passes and lines its tokens up from it."""
    records = getattr(res, "request_records", None) or {}
    rows = []
    for r in reqs:
        rec = res.metrics._req.get(r["rid"])
        out = res.outputs.get(r["rid"], [])
        stamps = list(rec.token_times) if rec is not None else []
        done = (rec is not None and rec.finish is not None and not rec.evicted
                and len(out) == r["max_new_tokens"])
        rows.append({"rid": r["rid"], "arrival": r["arrival"],
                     "prompt_len": len(r["prompt"]), "want": r["max_new_tokens"],
                     "admit": None if rec is None else rec.admit,
                     "token_times": stamps, "done": bool(done),
                     "cached": int(res.prefix_cached.get(r["rid"], 0)),
                     "output": [int(t) for t in out],
                     "engine_record": records.get(r["rid"])})
    first_due = min(r["arrival"] for r in rows)
    last_token = max((t for r in rows for t in r["token_times"]), default=first_due)
    window_s = last_token - first_due
    work = 0.0
    for r in rows:
        if not r["token_times"]:
            continue
        for part in family.request_flops(model, r):
            work += part
    return {"kind": "serve", "requests": rows, "spans": list(clock.spans),
            "first_due_s": first_due, "window_s": window_s,
            "oversleep_s": list(clock.oversleep_s),
            "engine_dev_wall_s": clock.dev_wall, "slept_s": clock.slept_s,
            "overhead": res.overhead, "prefill_tokens": res.prefill_tokens,
            "model_flops": work, "peak": peak, "chips": 1, "model": model}


def stalls(clock, gc_log, top=5) -> dict:
    """Where a window lost time in one piece: the calls that ran longest over
    the median of their kind and size, the longest stretches of host time
    between two calls (waits for an arrival taken out), and the collector."""
    spans = [(kind, a, b, units if isinstance(units, (int, float, type(None))) else str(units))
             for kind, a, b, units in clock.spans]
    sizes = {}
    for kind, a, b, units in spans:
        sizes.setdefault((kind, units), []).append(b - a)
    median = {k: sorted(v)[len(v) // 2] for k, v in sizes.items()}
    calls = sorted(((b - a - median[(kind, units)], kind, units, b - a, a)
                    for kind, a, b, units in spans), reverse=True)[:top]
    between = []
    for (_, _, end, _), (kind, start, _, _) in zip(clock.spans, clock.spans[1:]):
        slept = sum(min(b, start) - max(a, end) for a, b in clock.sleeps
                    if a < start and b > end)
        between.append((start - end - slept, kind, end))
    between = sorted(between, reverse=True)[:top]
    ms = lambda s: round(1e3 * s, 3)
    return {"calls_over_median": [[k, u, ms(d), ms(x), round(at, 3)] for x, k, u, d, at in calls],
            "host_between_calls": [[k, ms(d), round(at, 3)] for d, k, at in between],
            "gc": dict(gc_log.summary(), longest=[
                [g, ms(d), round(at - clock.t0, 3)]
                for g, d, at in sorted(gc_log.events, key=lambda e: -e[1])[:top]])}


def run(spec, cell, seed, seconds, trace, devices, counter, t_setup,
        control=None, fault=None) -> dict:
    family, config, mix = cell["family"], cell["config_spec"], cell["traffic_spec"]
    model = config["model"]
    vocab = model["vocab_size"]
    marks = device.Marks(t_setup, devices)
    marks.add("python_imports")
    tw = TraceWindow(spec.root.parent / ".bench_trace") if trace else None
    eng = build_engine(family, config, seed, tw, marks)
    warm = traffic.warmup_requests(mix, vocab, eng.chunk_C)
    eng.run(to_requests(warm))
    marks.add("warm_up")
    reqs = traffic.serve_requests(mix, seconds, seed, vocab)
    trace_reqs = to_requests(reqs)
    gc.collect()
    marks.add("traffic")
    compiles_before = counter.count
    setup_s = time.perf_counter() - t_setup

    if tw is not None:
        tw.place(*serving_stretch([r["arrival"] for r in reqs], int(mix.get("burst", 1)),
                                  seconds))
        tw.arm()
    gc_log = GcLog()
    res = eng.run(trace_reqs)
    gc_log.close()
    if tw is not None:
        tw.finish()
    compiles_in_window = counter.count - compiles_before
    clock = eng.bench_clock
    peak = spec.peak(devices[0].device_kind) if devices[0].platform == "tpu" else None
    obs = observe(reqs, res, clock, family, model, peak)
    if fault == "token_altered":    # tests only: a served token changed where it is produced
        victim = max(obs["requests"], key=lambda r: r["prompt_len"] + len(r["output"]))
        victim["output"][len(victim["output"]) // 2] ^= 1
    obs["setup_s"] = setup_s
    memory_peak = device.memory_peak_bytes(devices)
    dense_waves = sum(d.get("backend") != "paged" for d in res.decisions)
    census_broken = 0 if res.cache_stats.get("invariant_ok") else 1

    stalled = stalls(clock, gc_log)
    del eng, res, trace_reqs
    gc.collect()
    t_ref = time.perf_counter()
    sample = serve_check.pick_sample(obs["requests"], reqs, seed, mix)
    out_rows = int(mix["output"]["max"])
    pad_to = family.pad_length(mix)
    gaps = serve_check.served_gaps(family, model, seed, sample, pad_to, out_rows=out_rows)
    checks = Checks(cell["limits"])
    checks.add("served_gap_max", gaps["max"])
    checks.add("served_gap_mean", gaps["mean"])
    checks.add("requests_unfinished", sum(not r["done"] for r in obs["requests"]))
    checks.add("compiles_in_window", compiles_in_window)
    checks.add("dense_waves", dense_waves)
    checks.add("pool_census_broken", census_broken)
    info = {"traffic": traffic.summary(reqs), "window_s": obs["window_s"],
            "samples": {"requests": len(reqs), "token_gaps": sum(
                max(len(r["token_times"]) - 1, 0) for r in obs["requests"])},
            "setup_s": setup_s, "setup_parts": marks.parts,
            "memory_peak_at": marks.peak_bytes,
            "reference_s": time.perf_counter() - t_ref,
            "tokens_compared": gaps["tokens"], "requests_compared": len(sample),
            "ref_logit_absmax": gaps["ref_absmax"],
            "compiles_before_window": compiles_before,
            "warmup_requests": len(warm),
            "oversleep_max_ms": 1e3 * max(obs["oversleep_s"], default=0.0),
            "wall_minus_engine_s": obs["window_s"] - obs["engine_dev_wall_s"],
            "stalls": stalled}
    if control is not None:     # readings for the limits, never in a benchmark run
        lower = serve_check.served_gaps(family, model, seed, sample, pad_to, control, out_rows)
        info["control"] = {"served_gap_max": lower["max"], "served_gap_mean": lower["mean"]}
    if tw is not None:
        t_red = time.perf_counter()
        obs["device_trace"] = tw.reduce()
        if obs["device_trace"] is None:
            raise NoTrace(no_trace_message(tw, clock, reqs))
        obs["trace_interval"] = tw.interval
        info["trace_reduce_s"] = time.perf_counter() - t_red
        info["trace_costs"] = tw.costs
        info["trace_stretch"] = {"placed": [tw.start_at, tw.stop_at],
                                 "ran": [x if x != float("inf") else None for x in tw.interval],
                                 "arrival_wait_s": obs["device_trace"]["arrival_wait_s"]}
        info["custom_calls"] = custom_calls(obs["device_trace"])
    return {"obs": obs, "checks": checks, "info": info,
            "attempted": len(reqs),
            "failed": sum(not r["done"] for r in obs["requests"]),
            "memory_peak_bytes": memory_peak}


def no_trace_message(tw, clock, reqs) -> str:
    """Why a traced stretch caught no device operation, with what places
    it: the stretch, the calls' times and the arrivals' times."""
    r3 = lambda xs: [round(x, 3) for x in xs]
    starts = [a for _, a, _, _ in clock.spans]
    ran = None if tw.interval is None else r3(x for x in tw.interval if x is not None)
    return ("the traced stretch caught no device operation: placed at "
            f"{r3([tw.start_at, tw.stop_at])} s of the window, profiler ran over {ran}; "
            f"{len(starts)} calls, the first at {r3(starts[:3])} and the last at "
            f"{r3(starts[-3:])} s; arrivals due at {r3(sorted({r['arrival'] for r in reqs}))} s")
