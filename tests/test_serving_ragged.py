"""Ragged batched prefill + the dispatch-ahead engine turn, and their
satellites.

Deterministic sim-backed tests (fixed clock) for: flags-off
byte-identity, ragged-vs-per-chunk greedy token parity on the mixed
churn / prefill-heavy / admission-burst traces (sim AND the real tiny
model), the fused program's cache flatness across admission mixes,
``EngineClock.timed`` pricing parity (a fused dispatch of k chunks
prices exactly k sequential chunk calls on BOTH fixed-cost models),
the burst-TTFT acceptance floor, composition with the QoS scheduler /
LoRA adapters / disaggregated prefill-role clusters, dispatch-ahead
fixed-clock byte-identity plus the measured-clock
``ServeResult.overhead`` decomposition, the construction-time
refusals, ``synthesize_admission_burst_trace``, the ``trace_report``
ragged/ahead rows, and the ``serving_ragged`` bench-gate family.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (ClusterRouter, EngineClock, Request,
                                ServingEngine, QoSScheduler,
                                load_trace, make_sim_serving,
                                save_trace,
                                synthesize_admission_burst_trace,
                                synthesize_prefill_heavy_trace,
                                synthesize_trace)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 101
COSTS = {"prefill_unit": 1.0, "decode": 1.0}


def _sim_engine(budget=None, slots=8, chunk=4, max_len=96, extra=16,
                costs=COSTS, **kw):
    return ServingEngine(
        serving=make_sim_serving(
            max_len=max_len, page_size=8, slots=slots, vocab=VOCAB,
            n_pool_pages=slots * (max_len // 8) + 1 + extra),
        slots=slots, policy="paged", clock="fixed",
        fixed_costs=costs, decode_chunk=chunk,
        prefill_chunk_budget=budget, **kw)


def _mixed_trace(seed=0, n=24):
    return synthesize_trace(
        seed=seed, n_requests=n, arrival="poisson",
        mean_interarrival=2.0, prompt_len=(6, 40), output_len=(4, 20),
        vocab_size=VOCAB, shared_prefix_frac=0.3, prefix_len=16,
        churn_frac=0.2, rid_prefix="m")


def _burst_trace(seed=0, **kw):
    kw.setdefault("n_bursts", 2)
    kw.setdefault("burst_size", 6)
    kw.setdefault("n_background", 4)
    return synthesize_admission_burst_trace(seed=seed,
                                            vocab_size=VOCAB, **kw)


# --- EngineClock: fused pricing parity --------------------------------------

def test_timed_cost_list_sums():
    """A ragged dispatch passes a LIST of per-chunk costs and is
    charged their sum — so k chunks fused into one program price
    exactly k sequential chunk calls under flat per-call splitting
    (the PR-8 lane convention), never re-multiplied or discounted."""
    clk = EngineClock("fixed", {"prefill": 3.0})
    clk.timed("prefill", lambda: None, cost=[1.5, 1.5, 3.0])
    assert clk.now() == 6.0
    clk.timed("prefill", lambda: None, cost=0.5)  # scalar unchanged
    assert clk.now() == 6.5
    # the same chunks run as sequential calls: identical total
    seq = EngineClock("fixed", {"prefill": 3.0})
    for c in (1.5, 1.5, 3.0, 0.5):
        seq.timed("prefill", lambda: None, cost=c)
    assert seq.now() == clk.now()


def test_timed_units_parity_both_models():
    """Per-unit model: one call at units=k equals k calls at
    units=1. Flat model: the list-cost path carries the split."""
    fused = EngineClock("fixed", {"prefill_unit": 0.5})
    fused.timed("prefill", lambda: None, units=4)
    seq = EngineClock("fixed", {"prefill_unit": 0.5})
    for _ in range(4):
        seq.timed("prefill", lambda: None, units=1)
    assert fused.now() == seq.now() == 2.0


def test_measured_clock_accumulates_dev_wall():
    clk = EngineClock("measured")
    assert clk.dev_wall == 0.0
    clk.timed("decode", lambda: np.zeros(4))
    assert clk.dev_wall > 0.0
    assert clk.dev_wall == pytest.approx(clk.now())


def test_engine_pricing_parity_single_row():
    """A lane of ONE request makes the fused dispatch degenerate to
    the per-chunk call (k=1), so the full timeline — not just the
    streams — must be identical on BOTH fixed-cost models."""
    trace = [Request(rid="p", arrival=0.0,
                     prompt=tuple(range(1, 20)), max_new_tokens=6)]
    for costs in (COSTS, {"prefill": 3.0, "decode": 1.0}):
        a = _sim_engine(2, costs=costs).run(trace)
        b = _sim_engine(2, costs=costs, ragged_prefill=True).run(trace)
        assert a.outputs == b.outputs
        assert a.report() == b.report(), costs


# --- flags off: byte identity -----------------------------------------------

def test_flags_off_byte_identity():
    """ragged_prefill=False / dispatch_ahead=False is the SAME engine
    as not passing the flags: outputs, slot logs, and records."""
    trace = _mixed_trace()
    base = _sim_engine(2).run(trace)
    off = _sim_engine(2, ragged_prefill=False,
                      dispatch_ahead=False).run(trace)
    assert off.outputs == base.outputs
    assert off.slot_log == base.slot_log
    assert off.report() == base.report()
    assert off.overhead is None  # fixed clock: no decomposition


# --- ragged parity ----------------------------------------------------------

def test_ragged_parity_all_traces():
    """Fusing changes WHEN chunks run, never WHAT they compute:
    greedy streams bit-equal to per-chunk on every gate trace, pool
    census held, no page leaked."""
    for name, trace in (
            ("mixed_churn", _mixed_trace()),
            ("prefill_heavy", synthesize_prefill_heavy_trace(
                seed=0, n_short=24, n_long=8, vocab_size=VOCAB)),
            ("admission_burst", _burst_trace())):
        base = _sim_engine(2).run(trace)
        res = _sim_engine(2, ragged_prefill=True).run(trace)
        assert res.outputs == base.outputs, name
        assert res.cache_stats["invariant_ok"] is True
        assert res.pages_free_end == res.pages_total
        assert res.report()["completed"] \
            == base.report()["completed"], name


def test_ragged_determinism():
    trace = _burst_trace(seed=3)
    a = _sim_engine(2, ragged_prefill=True).run(trace)
    b = _sim_engine(2, ragged_prefill=True).run(trace)
    assert a.outputs == b.outputs
    assert a.slot_log == b.slot_log
    assert a.report() == b.report()


def test_ragged_burst_ttft_floor():
    """The acceptance number: on the admission-burst trace with
    decode priced 4x a prefill chunk (each serialized chunk turn
    pays for the active decode batch), the burst cohort's TTFT p95
    is >= 2x better at EQUAL prefill_chunk_budget — the spike's
    chunks drain budget fused dispatches per turn instead of budget
    chunks per turn."""
    costs = {"prefill_unit": 1.0, "decode": 4.0}
    trace = synthesize_admission_burst_trace(
        seed=0, n_bursts=3, burst_size=8, n_background=6,
        vocab_size=VOCAB)

    def burst_p95(res):
        xs = [res.metrics.request(r.rid)["ttft"] for r in trace
              if r.rid.rsplit(".", 1)[-1].startswith("x")]
        return float(np.percentile([x for x in xs if x is not None],
                                   95))
    pc = _sim_engine(2, slots=16, costs=costs).run(trace)
    rg = _sim_engine(2, slots=16, costs=costs,
                     ragged_prefill=True).run(trace)
    assert rg.outputs == pc.outputs
    assert burst_p95(pc) / burst_p95(rg) >= 2.0, (burst_p95(pc),
                                                  burst_p95(rg))


def test_ragged_starvation_bound():
    """Every lane entry rides every fused dispatch, so no request can
    age out: ragged worst-case TTFT is no worse than per-chunk's on
    the adversarial prefill-heavy trace."""
    trace = synthesize_prefill_heavy_trace(seed=0, n_short=24,
                                           n_long=8,
                                           vocab_size=VOCAB)

    def ttft_max(res):
        xs = [res.metrics.request(r.rid)["ttft"] for r in trace]
        return max(x for x in xs if x is not None)
    pc = _sim_engine(2).run(trace)
    rg = _sim_engine(2, ragged_prefill=True).run(trace)
    assert ttft_max(rg) <= ttft_max(pc) * 1.05 + 1e-9


def test_ragged_program_cache_flat():
    """The fused shape is (slots, chunk) with per-row starts/lengths
    as jit DATA: two different admission mixes through the REAL
    ragged program must not add a compile entry."""
    from paddle_tpu.serving.engine import _jit_cache_size
    srv, _ = _real_factory()
    eng = ServingEngine(serving=srv, slots=4, policy="paged",
                        clock="fixed", fixed_costs=COSTS,
                        decode_chunk=4, prefill_chunk_budget=2,
                        ragged_prefill=True)
    sizes = []
    for k in range(2):
        eng.run(synthesize_trace(
            seed=5 + k, n_requests=6, arrival="poisson",
            mean_interarrival=1.0 + k, prompt_len=(2, 20),
            output_len=(2, 6), vocab_size=97, rid_prefix=f"m{k}"))
        sizes.append(_jit_cache_size(eng._p_prefill_ragged))
    assert sizes[0] == sizes[1], sizes


# --- composition ------------------------------------------------------------

def test_ragged_qos_composition():
    """The QoS loop drives the ragged lane: feasibility pricing sees
    the same committed-chunk backlog, and every completed stream is
    still the sim oracle's greedy stream."""
    sim = make_sim_serving(max_len=96, page_size=8, slots=8,
                           vocab=VOCAB)
    trace = _burst_trace(seed=1)
    res = _sim_engine(2, scheduler=QoSScheduler(),
                      ragged_prefill=True).run(trace)
    assert res.cache_stats["invariant_ok"] is True
    by_rid = {r.rid: r for r in trace}
    checked = 0
    for rid, toks in res.outputs.items():
        if not toks:
            continue
        exp = sim.expected_stream(by_rid[rid].prompt, len(toks))
        assert list(toks) == list(exp), rid
        checked += 1
    assert checked > 0


def test_ragged_lora_composition():
    """Per-row adapter ids ride the fused batch exactly like they
    ride decode_n: multiplexed ragged streams bit-equal to the
    per-chunk multiplexed engine."""
    from paddle_tpu.serving import (AdapterStore,
                                    synthesize_zipf_adapter_trace)
    store = AdapterStore({f"a{k}": {"salt": 7919 * (k + 1)}
                          for k in range(3)})

    def eng(ragged):
        return ServingEngine(
            serving=make_sim_serving(max_len=64, page_size=8,
                                     slots=8, vocab=509,
                                     lora_slots=3),
            slots=8, policy="paged", clock="fixed",
            fixed_costs=COSTS, decode_chunk=4,
            prefill_chunk_budget=2, adapters=store,
            ragged_prefill=ragged)
    trace = synthesize_zipf_adapter_trace(seed=0, n_requests=40,
                                          n_adapters=3,
                                          base_frac=0.2)
    base = eng(False).run(trace)
    res = eng(True).run(trace)
    assert res.outputs == base.outputs
    assert res.adapter_stats["invariant_ok"]


def test_ragged_disagg_cluster_handoffs():
    """A ragged prefill-role session exports each finished row's
    KVHandoff individually even when several rows finish in ONE
    fused dispatch: exactly-once census, streams equal the lone
    per-chunk engine."""
    trace = [Request(rid=f"d{i}", arrival=0.0,
                     prompt=tuple(range(1 + i, 12 + i)),
                     max_new_tokens=4) for i in range(6)]

    def spawn(name):
        return _sim_engine(2, slots=8, ragged_prefill=True)
    res = ClusterRouter(spawn, 2, placement="disaggregated",
                        roles={"r0": "prefill", "r1": "decode"},
                        kv_transfer_unit=0.05).run(trace)
    cen = res.census()
    assert cen["conserved"] and cen["handoffs"]["balanced"]
    assert cen["handoffs"]["exported"] == len(trace)
    lone = _sim_engine(2, slots=8).run(trace)
    assert res.outputs() == lone.outputs


# --- dispatch-ahead ---------------------------------------------------------

def test_dispatch_ahead_fixed_clock_identity():
    """Overlap is a measured-clock optimization: the fixed clock
    prices the same work, so outputs, slot logs, and records are
    byte-identical with the flag on — with or without the lane, and
    with ragged on top."""
    trace = _mixed_trace()
    for kw in ({"budget": None}, {"budget": 2},
               {"budget": 2, "ragged_prefill": True}):
        budget = kw.pop("budget")
        base = _sim_engine(budget, **kw).run(trace)
        on = _sim_engine(budget, dispatch_ahead=True, **kw).run(trace)
        assert on.outputs == base.outputs, kw
        assert on.slot_log == base.slot_log, kw
        assert on.report() == base.report(), kw
        assert on.overhead is None


def test_dispatch_ahead_stash_actually_serves(tmp_path):
    """The flag is not a no-op: on a steady decode roster the stash
    serves real turns — decode spans tagged ahead=true appear in the
    trace, and the streams still match flag-off."""
    from paddle_tpu import obs
    trace = [Request(rid=f"s{i}", arrival=0.0,
                     prompt=tuple(range(1, 6)), max_new_tokens=12)
             for i in range(4)]
    tr = obs.Tracer()
    res = _sim_engine(2, dispatch_ahead=True, trace=tr).run(trace)
    served = [e for e in tr.events if e.get("ph") == "X"
              and e.get("name") == "decode"
              and e.get("args", {}).get("ahead")]
    assert served, "no decode turn was served from the stash"
    assert res.outputs == _sim_engine(2).run(trace).outputs


def test_dispatch_ahead_measured_overhead_row():
    """The measured clock decomposes the run: ServeResult.overhead
    carries run/device wall and engine_host_frac in [0, 1]; fixed
    clocks and save_log never see it."""
    trace = [Request(rid=f"o{i}", arrival=0.0,
                     prompt=tuple(range(1, 8)), max_new_tokens=6)
             for i in range(3)]

    def eng(ahead):
        return ServingEngine(
            serving=make_sim_serving(max_len=96, page_size=8,
                                     slots=8, vocab=VOCAB),
            slots=8, policy="paged", clock="measured",
            decode_chunk=4, dispatch_ahead=ahead)
    for ahead in (False, True):
        ov = eng(ahead).run(trace).overhead
        assert set(ov) >= {"run_wall_s", "device_wall_s",
                           "engine_host_frac"}
        assert 0.0 <= ov["engine_host_frac"] <= 1.0
        assert ov["device_wall_s"] <= ov["run_wall_s"]


def test_dispatch_ahead_refusals():
    from paddle_tpu.models.nlp.llama_decode import SpecConfig
    with pytest.raises(ValueError, match="dispatch_ahead"):
        ServingEngine(
            serving=make_sim_serving(max_len=96, page_size=8,
                                     slots=8, vocab=VOCAB,
                                     spec_accept=0.9),
            slots=8, policy="paged", clock="fixed",
            fixed_costs=COSTS, decode_chunk=4,
            prefill_chunk_budget=2, spec=SpecConfig(),
            dispatch_ahead=True)
    with pytest.raises(ValueError, match="dispatch_ahead"):
        ServingEngine(
            serving=make_sim_serving(max_len=96, page_size=8,
                                     slots=8, vocab=VOCAB,
                                     kv_quant="pressure"),
            slots=8, policy="paged", clock="fixed",
            fixed_costs=COSTS, decode_chunk=4,
            kv_quant="pressure", dispatch_ahead=True)


def test_ragged_refusals():
    with pytest.raises(ValueError, match="prefill_chunk_budget"):
        _sim_engine(None, ragged_prefill=True)
    srv = make_sim_serving(max_len=96, page_size=8, slots=8,
                           vocab=VOCAB)
    del srv.prefill_ragged  # a factory that never advertised it
    with pytest.raises(ValueError, match="prefill_ragged"):
        ServingEngine(serving=srv, slots=8, policy="paged",
                      clock="fixed", fixed_costs=COSTS,
                      decode_chunk=4, prefill_chunk_budget=2,
                      ragged_prefill=True)


# --- real tiny model --------------------------------------------------------

def _real_factory():
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama_decode import (
        llama_serving_decode_factory)
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    model.eval()
    srv = llama_serving_decode_factory(model, max_len=48, page_size=8,
                                       n_pool_pages=25,
                                       batch_capacity=4,
                                       chunked_prefill=8)
    return srv, model


def _real_trace(n=8):
    return synthesize_trace(seed=5, n_requests=n, arrival="poisson",
                            mean_interarrival=2.0, prompt_len=(4, 20),
                            output_len=(3, 8), vocab_size=97,
                            shared_prefix_frac=0.25)


def test_real_model_ragged_and_ahead_parity():
    """The fused ragged program drives the REAL jitted factory to
    bit-equal greedy streams, and dispatch-ahead keeps the real
    fixed-clock run byte-identical."""
    trace = _real_trace()

    def eng(**kw):
        srv, _ = _real_factory()
        return ServingEngine(serving=srv, slots=4, policy="paged",
                             clock="fixed", fixed_costs=COSTS,
                             decode_chunk=4, prefill_chunk_budget=2,
                             **kw)
    base = eng().run(trace)
    rg = eng(ragged_prefill=True).run(trace)
    assert rg.outputs == base.outputs
    ah = eng(dispatch_ahead=True).run(trace)
    assert ah.outputs == base.outputs
    assert ah.slot_log == base.slot_log
    both = eng(ragged_prefill=True, dispatch_ahead=True).run(trace)
    assert both.outputs == base.outputs


def test_real_factory_without_chunking_refuses_ragged():
    """A factory built without chunked_prefill has no ragged program
    to advertise — construction must refuse up-front (the standing
    chunked-prefill requirement fires first), not crash mid-replay."""
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama_decode import (
        llama_serving_decode_factory)
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    model.eval()
    srv = llama_serving_decode_factory(model, max_len=48, page_size=8,
                                       n_pool_pages=25,
                                       batch_capacity=4)
    with pytest.raises(ValueError, match="chunked-prefill"):
        ServingEngine(serving=srv, slots=4, policy="paged",
                      clock="fixed", fixed_costs=COSTS,
                      decode_chunk=4, prefill_chunk_budget=2,
                      ragged_prefill=True)


# --- the admission-burst synthesizer ----------------------------------------

def test_burst_trace_shape_and_determinism():
    trace = synthesize_admission_burst_trace(seed=0, n_bursts=2,
                                             burst_size=5,
                                             n_background=3)
    burst = [r for r in trace if r.rid.endswith(".x5")]
    bg = [r for r in trace if r.rid.endswith(".bg")]
    assert len(burst) == 10 and len(bg) == 3
    assert len(trace) == 13
    # every burst's arrivals are SYNCHRONIZED — that is the shape
    by_b = {}
    for r in burst:
        by_b.setdefault(r.rid.split(".")[0], set()).add(r.arrival)
    assert all(len(v) == 1 for v in by_b.values())
    assert [r.rid for r in trace] \
        == [r.rid for r in sorted(trace,
                                  key=lambda r: (r.arrival, r.rid))]
    again = synthesize_admission_burst_trace(seed=0, n_bursts=2,
                                             burst_size=5,
                                             n_background=3)
    assert trace == again
    other = synthesize_admission_burst_trace(seed=1, n_bursts=2,
                                             burst_size=5,
                                             n_background=3)
    assert trace != other
    with pytest.raises(ValueError):
        synthesize_admission_burst_trace(n_bursts=0)


def test_burst_trace_jsonl_roundtrip(tmp_path):
    trace = _burst_trace(seed=2)
    p = str(tmp_path / "burst.jsonl")
    save_trace(p, trace)
    assert load_trace(p) == trace


# --- trace_report rows ------------------------------------------------------

def test_trace_report_ragged_and_ahead_rows(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from trace_report import (ahead_summary,
                              load_trace as load_chrome,
                              ragged_summary)
    from paddle_tpu import obs
    trace = _burst_trace(seed=1)

    def run(path, **kw):
        tr = obs.Tracer()
        _sim_engine(2, trace=tr, **kw).run(trace)
        tr.export(path)
        return load_chrome(path)
    legacy = run(str(tmp_path / "legacy.json"))
    assert ragged_summary(legacy) is None  # absent: byte-identical
    assert ahead_summary(legacy) is None
    evts = run(str(tmp_path / "on.json"), ragged_prefill=True,
               dispatch_ahead=True)
    rg = ragged_summary(evts)
    assert rg["fused_calls"] >= 1
    assert rg["rows_fused"] >= rg["fused_calls"]
    assert rg["max_rows_per_call"] >= 2  # the burst DID fuse
    ah = ahead_summary(evts)
    assert ah["ahead_served"] >= 1
    assert 0.0 < ah["ahead_frac"] <= 1.0
    # --json: new rows present, global row still LAST
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "trace_report.py"),
         str(tmp_path / "on.json"), "--json"],
        capture_output=True, text=True)
    rows = [json.loads(ln) for ln in out.stdout.splitlines()]
    benches = [r.get("bench") for r in rows]
    assert "trace_report_ragged" in benches
    assert "trace_report_ahead" in benches
    assert benches[-1] == "trace_report"
    out0 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "trace_report.py"),
         str(tmp_path / "legacy.json"), "--json"],
        capture_output=True, text=True)
    benches0 = [json.loads(ln).get("bench")
                for ln in out0.stdout.splitlines()]
    assert "trace_report_ragged" not in benches0
    assert "trace_report_ahead" not in benches0


# --- bench_gate: the serving_ragged family ----------------------------------

def _gate(text, tmp_path):
    p = tmp_path / "rows.jsonl"
    p.write_text(text)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"),
         "serving", str(p)], capture_output=True, text=True)
    recs = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    return r.returncode, recs


def _ragged_row(trace, arm, census=True):
    return json.dumps({"bench": "serving_ragged", "trace": trace,
                       "arm": arm, "device": "sim",
                       "census_ok": census, "ttft_max": 10.0})


def _ragged_summary_row(**kw):
    row = {"bench": "serving_ragged_summary", "device": "sim",
           "outputs_match": True, "program_cache_flat": True,
           "starvation_ok": True, "dispatch_ahead_parity_ok": True,
           "burst_ttft_p95_per_chunk": 90.0,
           "burst_ttft_p95_ragged": 40.0,
           "burst_ttft_p95_improvement": 2.25,
           "program_cache_calls": [2, 2],
           "prefill_chunk_budget": 2}
    row.update(kw)
    return json.dumps(row)


def test_bench_gate_serving_ragged_family(tmp_path):
    base = [_ragged_row("admission_burst", "per_chunk"),
            _ragged_row("admission_burst", "ragged"),
            _ragged_row("mixed_churn", "per_chunk"),
            _ragged_row("mixed_churn", "ragged")]
    rc, recs = _gate("\n".join(base + [_ragged_summary_row()]) + "\n",
                     tmp_path)
    assert rc == 0 and recs[-1]["gate"] == "pass"
    assert recs[-1]["burst_ttft_p95_improvement"] == 2.25

    # missing arm -> FAIL naming the bench command
    rc, recs = _gate(_ragged_row("admission_burst", "per_chunk")
                     + "\n", tmp_path)
    assert rc == 1 and "--ragged" in recs[-1]["reason"]

    # no summary row -> parity UNVERIFIED
    rc, recs = _gate("\n".join(base) + "\n", tmp_path)
    assert rc == 1 and "UNVERIFIED" in recs[-1]["reason"]

    # broken census on any arm -> FAIL
    rows = base[:-1] + [_ragged_row("mixed_churn", "ragged",
                                    census=False),
                        _ragged_summary_row()]
    rc, recs = _gate("\n".join(rows) + "\n", tmp_path)
    assert rc == 1 and "census" in recs[-1]["reason"]

    for kw, needle in (
            ({"outputs_match": False}, "DIVERGING"),
            ({"program_cache_flat": False,
              "program_cache_calls": [2, 3]}, "RECOMPILED"),
            ({"starvation_ok": False}, "aging"),
            ({"dispatch_ahead_parity_ok": False}, "dispatch_ahead"),
            ({"burst_ttft_p95_improvement": 1.4}, "floor 2.0")):
        rc, recs = _gate(
            "\n".join(base + [_ragged_summary_row(**kw)]) + "\n",
            tmp_path)
        assert rc == 1, kw
        assert needle in recs[-1]["reason"], (kw, recs[-1])


def test_ragged_bench_arm_end_to_end(tmp_path):
    """The --ragged arm emits gateable rows and the gate passes on
    the real thing, not just on fakes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "serving_workload_bench.py"),
         "--ragged", "--cpu"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-800:]
    rows = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    summ = [x for x in rows
            if x["bench"] == "serving_ragged_summary"]
    assert len(summ) == 1
    assert summ[0]["outputs_match"] is True
    assert summ[0]["burst_ttft_p95_improvement"] >= 2.0
    rc, recs = _gate(r.stdout, tmp_path)
    assert rc == 0 and recs[-1]["gate"] == "pass"
