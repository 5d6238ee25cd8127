"""paddle_tpu.serving.ServingEngine: the continuous-batching engine.

Deterministic replay tests (fixed-cost clock): exact completion order
and slot occupancy from a seeded trace, shared-prefix page reuse,
mid-stream eviction (churn), routed-policy decision logging, dense-wave
parity with the compiled generate loop, and cross-policy greedy-token
parity on one mixed trace.
"""
import collections
import hashlib
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.serving import (FixedPolicy, QoSScheduler, Request,
                                ServingEngine, SpecConfig,
                                make_sim_serving, merge_traces,
                                synthesize_overload_trace,
                                synthesize_trace)

REPLAYS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "serving_replays.json")


def _build_srv():
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama_decode import (
        llama_serving_decode_factory)
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    model.eval()
    srv = llama_serving_decode_factory(model, max_len=48, page_size=8,
                                       n_pool_pages=25, batch_capacity=4,
                                       chunked_prefill=8)
    return srv, model, cfg


@pytest.fixture(scope="module")
def srv_model():
    """One model + serving factory for every engine in this module, so
    the compiled programs (paged prefill/decode_n, dense shapes) are
    shared across tests."""
    return _build_srv()


def _engine(srv, policy="paged", **kw):
    kw.setdefault("clock", "fixed")
    return ServingEngine(serving=srv, slots=4, policy=policy, **kw)


def _req(rid, arrival, prompt, budget, **kw):
    return Request(rid=rid, arrival=arrival, prompt=tuple(prompt),
                   max_new_tokens=budget, **kw)


def test_completion_order_and_slot_occupancy(srv_model):
    """Seeded trace -> EXACT completion order and slot assignment.
    Budgets 2/4/6/8 admitted together complete shortest-first; the
    late-arriving 1-token request reuses the first freed slot."""
    srv, _, _ = srv_model
    rng = np.random.default_rng(5)
    prompts = [tuple(int(t) for t in rng.integers(1, 97, 6))
               for _ in range(5)]
    trace = [
        _req("A", 0.0, prompts[0], 2),
        _req("B", 0.0, prompts[1], 4),
        _req("C", 0.0, prompts[2], 6),
        _req("D", 0.0, prompts[3], 8),
        _req("E", 5.0, prompts[4], 1),
    ]
    eng = _engine(srv, "paged")
    res = eng.run(trace)
    finish_order = sorted(
        res.outputs, key=lambda rid: (
            res.metrics.request(rid)["finish"], rid))
    assert finish_order == ["A", "E", "B", "C", "D"], (
        finish_order, {r: res.metrics.request(r)["finish"]
                       for r in res.outputs})
    acquires = [(rid, slot) for _, ev, rid, slot in res.slot_log
                if ev == "acquire"]
    assert acquires == [("A", 0), ("B", 1), ("C", 2), ("D", 3),
                        ("E", 0)], acquires  # E reuses A's freed slot
    assert {r: len(o) for r, o in res.outputs.items()} == \
        {"A": 2, "B": 4, "C": 6, "D": 8, "E": 1}
    assert res.pages_free_end == res.pages_total  # no page leaks
    # bit-identical replay
    res2 = _engine(srv, "paged").run(trace)
    assert res2.outputs == res.outputs
    assert res2.slot_log == res.slot_log
    assert res2.report() == res.report()


def test_shared_prefix_pages_are_reused(srv_model):
    """Second request in a prefix group hits the pool's prefix cache
    for the full shared pages and still decodes the same tokens as an
    isolated dense generate."""
    import jax.numpy as jnp
    srv, _, _ = srv_model
    rng = np.random.default_rng(7)
    prefix = tuple(int(t) for t in rng.integers(1, 97, 16))  # 2 pages
    tails = [tuple(int(t) for t in rng.integers(1, 97, 3))
             for _ in range(2)]
    # r1 arrives AFTER r0's prefill registered the shared pages but
    # while r0 is still decoding: prefix pages stay alive exactly as
    # long as a holder references them (free() drops dead prefix
    # chains so recycled page ids can never serve stale K/V)
    trace = [
        _req("r0", 0.0, prefix + tails[0], 8, prefix_group=0),
        _req("r1", 3.0, prefix + tails[1], 4, prefix_group=0),
    ]
    res = _engine(srv, "paged").run(trace)
    assert res.prefix_cached == {"r0": 0, "r1": 16}
    assert res.pages_free_end == res.pages_total
    # parity: each request's stream equals the dense compiled greedy
    for rid, prompt, budget in (("r0", prefix + tails[0], 8),
                                ("r1", prefix + tails[1], 4)):
        want = np.asarray(srv.dense(
            jnp.asarray([prompt]),
            max_new_tokens=budget))[0, len(prompt):]
        assert res.outputs[rid] == [int(t) for t in want], rid


def test_eviction_churn_frees_pages(srv_model):
    """cancel_after evicts mid-stream: the canceled request stops at
    its cancel point (marked evicted), its pages return to the pool,
    and the surviving requests complete their full budgets."""
    srv, _, _ = srv_model
    rng = np.random.default_rng(9)
    prompts = [tuple(int(t) for t in rng.integers(1, 97, 7))
               for _ in range(3)]
    trace = [
        _req("keep0", 0.0, prompts[0], 6),
        _req("gone", 0.0, prompts[1], 8, cancel_after=2),
        _req("keep1", 0.0, prompts[2], 5),
    ]
    res = _engine(srv, "paged").run(trace)
    assert len(res.outputs["gone"]) == 2
    assert res.metrics.request("gone")["evicted"] is True
    assert len(res.outputs["keep0"]) == 6
    assert len(res.outputs["keep1"]) == 5
    assert res.metrics.request("keep0")["evicted"] is False
    assert res.pages_free_end == res.pages_total
    rep = res.report()
    assert rep["completed"] == 3 and rep["evicted"] == 1


def test_routed_policy_logs_decisions(srv_model):
    """A uniform full wave routes dense (with the rule named); a later
    ragged wave routes paged; a wave arriving while paged rows stream
    joins the active batch."""
    srv, _, _ = srv_model
    rng = np.random.default_rng(11)
    uniform = [_req(f"u{i}", 0.0,
                    tuple(int(t) for t in rng.integers(1, 97, 8)), 3)
               for i in range(4)]
    ragged = [_req(f"g{i}", 50.0 + i * 0.0001,
                   tuple(int(t) for t in rng.integers(1, 97, 4 + 5 * i)),
                   6) for i in range(3)]
    late = [_req("late", 52.0,
                 tuple(int(t) for t in rng.integers(1, 97, 8)), 3)]
    res = _engine(srv, "routed").run(uniform + ragged + late)
    assert res.policy == "routed"
    assert res.decisions[0]["backend"] == "dense"
    assert "uniform" in res.decisions[0]["rule"]
    ragged_waves = [d for d in res.decisions if d["backend"] == "paged"]
    assert ragged_waves and "ragged" in ragged_waves[0]["rule"]
    join = [d for d in res.decisions
            if "join-active-batch" in d["rule"]]
    assert join, res.decisions  # the late wave joined the paged batch
    assert res.report()["completed"] == 8


def test_dense_wave_matches_compiled_generate(srv_model):
    """The dense wave path is the SAME computation as the dense
    factory's generate(): one uniform wave's streams equal the batched
    greedy output token-for-token."""
    import jax.numpy as jnp
    srv, _, _ = srv_model
    rng = np.random.default_rng(13)
    prompts = np.asarray(rng.integers(1, 97, (4, 9)), np.int32)
    trace = [_req(f"d{i}", 0.0, tuple(int(t) for t in prompts[i]), 5)
             for i in range(4)]
    res = _engine(srv, "dense").run(trace)
    assert all(d["backend"] == "dense" for d in res.decisions)
    want = np.asarray(srv.dense(jnp.asarray(prompts), max_new_tokens=5))
    for i in range(4):
        assert res.outputs[f"d{i}"] == [int(t) for t in want[i, 9:]], i


def test_cross_policy_token_parity(srv_model):
    """One mixed trace through routed / dense-only / paged-only: every
    request's greedy tokens agree across all three policies."""
    srv, _, cfg = srv_model
    ragged = synthesize_trace(seed=3, n_requests=5, arrival="poisson",
                              mean_interarrival=0.5, prompt_len=(4, 14),
                              output_len=(3, 6), vocab_size=97,
                              churn_frac=0.3, rid_prefix="r")
    burst = synthesize_trace(seed=9, n_requests=4, arrival="bursty",
                             burst_size=4, mean_interarrival=0.7,
                             prompt_len=(8, 12), output_len=(3, 5),
                             vocab_size=97, rid_prefix="b")
    trace = merge_traces(ragged, burst)
    outs = {}
    for pol in ("routed", "dense", "paged"):
        res = _engine(srv, pol).run(trace)
        outs[pol] = res.outputs
        assert res.report()["completed"] == len(trace), pol
        assert res.pages_free_end == res.pages_total, pol
    assert outs["routed"] == outs["dense"] == outs["paged"]


def test_admission_shares_batching_config(srv_model):
    """The engine's admission defaults ARE inference.BatchingConfig —
    one knob surface for both batchers."""
    from paddle_tpu.inference import BatchingConfig, DynamicBatcher
    srv, _, _ = srv_model
    eng = _engine(srv, "paged")
    assert isinstance(eng.admission, BatchingConfig)
    dflt = BatchingConfig()
    assert (eng.admission.max_batch, eng.admission.max_delay_ms) == \
        (dflt.max_batch, dflt.max_delay_ms)
    # and the batcher accepts the same object (no predictor run needed)
    cfgd = BatchingConfig(max_batch=7, max_delay_ms=11.0)
    eng2 = _engine(srv, "paged", admission=cfgd)
    assert eng2.admission.max_batch == 7
    assert eng2.admission.max_delay == pytest.approx(0.011)
    assert DynamicBatcher  # the same config type drives both batchers


def test_engine_validation_errors(srv_model):
    srv, model, _ = srv_model
    eng = _engine(srv, "paged")
    over = [_req("x", 0.0, tuple(range(1, 33)), 40)]  # footprint > 48
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.run(over)
    with pytest.raises(ValueError, match="clock"):
        ServingEngine(serving=srv, clock="hourglass")
    with pytest.raises(ValueError, match="backend"):
        FixedPolicy("quantum")
    with pytest.raises(ValueError, match="chunked"):
        from paddle_tpu.models.nlp.llama_decode import (
            llama_serving_decode_factory)
        plain = llama_serving_decode_factory(model, max_len=48,
                                             page_size=8,
                                             n_pool_pages=25)
        ServingEngine(serving=plain)


# ---------------------------------------------------------------------------
# the replays recorded before ServingEngine.run became a session replay
# ---------------------------------------------------------------------------
# Seven small replays on the fixed clock that between them took every
# branch of the two loops `run()` had (FIFO, and `_run_scheduled` for a
# scheduler), recorded at the parent of the PR that deleted them
# (`python tests/test_serving_engine.py` rewrites the file from whatever
# tree it runs in; a PR that means to change a replay re-records and
# says why).

_SIM_COSTS = {"prefill_unit": 1.0, "decode": 1.0, "spec_decode": 1.25,
              "spec_prefill": 0.25, "kv_pageout": 2.0, "kv_pagein": 2.0}


def _sim(slots, **kw):
    kw.setdefault("n_pool_pages", slots * 8 + 1)
    return make_sim_serving(max_len=64, page_size=8, slots=slots,
                            vocab=211, chunked_prefill=8, **kw)


def _sim_engine(sim, slots, **kw):
    return ServingEngine(serving=sim, slots=slots, policy="paged",
                         clock="fixed", fixed_costs=dict(_SIM_COSTS),
                         trace=obs.Tracer(), **kw)


def _fifo_lane(srv):
    """FIFO, the async prefill lane on, a shared prefix: admission
    reserves, the lane prefills under its budget, a cohort hits the
    prefix cache."""
    trace = synthesize_trace(
        seed=4, n_requests=14, arrival="poisson",
        mean_interarrival=1.5, prompt_len=(6, 30), output_len=(3, 9),
        vocab_size=211, shared_prefix_frac=0.5, prefix_len=16,
        rid_prefix="l")
    return _sim_engine(_sim(4), 4, prefill_chunk_budget=2), trace


def _fifo_churn(srv):
    """FIFO, no lane (prefill inside admission), `cancel_after` churn,
    more requests than slots, idle gaps between arrivals; the cost
    ledger samples occupancy once a turn, so it counts the turns."""
    trace = synthesize_trace(
        seed=6, n_requests=16, arrival="poisson",
        mean_interarrival=2.5, prompt_len=(4, 20), output_len=(4, 12),
        vocab_size=211, churn_frac=0.4, rid_prefix="c")
    return _sim_engine(_sim(3), 3, prefill_chunk_budget=None,
                       decode_chunk=2, ledger=True), trace


def _qos_overload(srv):
    """A scheduler under 2.5x overload with a queue bound: sheds at
    enqueue and at selection, clamps budgets through a degrade tier,
    times out running rows and lane entries."""
    trace = synthesize_overload_trace(
        seed=2, n_requests=48, service_tokens_per_unit=4.0,
        overload=2.5, prompt_len=(4, 24), output_len=(4, 14),
        vocab_size=211, tight_slack=1.6)
    sched = QoSScheduler(
        tenant_weights={"intl": 2.0, "std": 1.0, "bulk": 0.5},
        max_queue=6, headroom=1.0)
    return _sim_engine(_sim(4), 4, scheduler=sched,
                       prefill_chunk_budget=1), trace


def _qos_preempt(srv):
    """A scheduler over a host arena with one slot: a high-priority
    arrival swaps the running row out, runs, and the row resumes."""
    trace = [Request(rid="lo", prompt=tuple(range(10, 26)),
                     max_new_tokens=30, arrival=0.0, tenant="t0",
                     priority=0),
             Request(rid="hi", prompt=tuple(range(40, 56)),
                     max_new_tokens=8, arrival=20.0, tenant="t1",
                     priority=9),
             Request(rid="mid", prompt=tuple(range(60, 70)),
                     max_new_tokens=6, arrival=21.0, tenant="t1",
                     priority=5, deadline_ms=9000.0)]
    return _sim_engine(_sim(1, n_pool_pages=12), 1,
                       scheduler=QoSScheduler(), hostmem=1 << 20), trace


def _routed_dense(srv):
    """The routed policy on the real model: a uniform wave goes dense,
    a ragged one paged, a late one joins the paged batch."""
    rng = np.random.default_rng(11)
    pk = lambda n: tuple(int(t) for t in rng.integers(1, 97, n))
    trace = [_req(f"u{i}", 0.0, pk(8), 3) for i in range(4)] \
        + [_req(f"g{i}", 50.0 + i * 0.0001, pk(4 + 5 * i), 6)
           for i in range(3)] + [_req("late", 52.0, pk(8), 3)]
    return _engine(srv, "routed", trace=obs.Tracer()), trace


def _qos_dense(srv):
    """A dense wave under a scheduler: the second equal-length group
    starts past a row's deadline, and the row times out mid-wave."""
    rng = np.random.default_rng(41)
    pk = lambda n: tuple(int(t) for t in rng.integers(1, 97, n))
    trace = [_req("longrun", 0.0, pk(6), 12),
             _req("misses", 0.0, pk(8), 4, deadline_ms=9000.0)]
    return _engine(srv, "dense", trace=obs.Tracer(),
                   scheduler=QoSScheduler(headroom=1.0)), trace


def _spec(srv):
    """The speculative route with churn: spec and plain rows in one
    batch, accepted drafts, the prefill of the draft's cache."""
    trace = synthesize_trace(
        seed=1, n_requests=20, arrival="poisson", mean_interarrival=0.6,
        prompt_len=(4, 16), output_len=(8, 20), vocab_size=211,
        shared_prefix_frac=0.3, prefix_len=8, churn_frac=0.2,
        rid_prefix="m")
    return _sim_engine(_sim(4, n_pool_pages=4 * 8 + 1 + 16,
                            spec_accept=0.8), 4,
                       spec=SpecConfig(n_draft=4), decode_chunk=1,
                       expect_churn=True), trace


_REPLAY_CASES = {f.__name__[1:]: f for f in (
    _fifo_lane, _fifo_churn, _qos_overload, _qos_preempt, _routed_dense,
    _qos_dense, _spec)}
# the real model's token VALUES are float arithmetic, so the record
# keeps their counts (no `eos_token_id`: a value steers nothing); the
# values are held to the dense generate by the tests above
_REAL_MODEL = ("routed_dense", "qos_dense")


def _snapshot(name, res):
    """What a replay is held to: every field of the result that a
    fixed clock makes exact."""
    # events by track NAME, the `jit` track left out: its `jit.compile`
    # instants (and the track ids after it) say what this process had
    # compiled before the replay, which is the test order's doing and
    # not the loop's
    evts = res.trace.to_chrome()["traceEvents"]
    track = {e["tid"]: e["args"]["name"] for e in evts
             if e["name"] == "thread_name"}
    evts = [dict(e, tid=track[e["tid"]]) for e in evts
            if e["ph"] != "M" and track[e["tid"]] != "jit"]
    for e in evts:      # the one wall-clock attr of a trace
        e.get("args", {}).pop("wall_s", None)
    names = collections.Counter(e["name"] for e in evts)
    outputs = res.outputs if name not in _REAL_MODEL else \
        {rid: len(out) for rid, out in res.outputs.items()}
    snap = {
        "policy": res.policy, "scheduler": res.scheduler,
        "outputs": outputs, "decisions": res.decisions,
        "slot_log": res.slot_log, "shed": res.shed,
        "prefix_cached": res.prefix_cached,
        "requests": res.metrics.request_rows(),
        "report": res.report(), "pages_total": res.pages_total,
        "pages_free_end": res.pages_free_end,
        "prefill_tokens": res.prefill_tokens,
        "cache_stats": res.cache_stats, "spec_stats": res.spec_stats,
        "hostmem_stats": res.hostmem_stats,
        "pages_spilled": res.pages_spilled, "overhead": res.overhead,
        "cost_stats": res.cost_stats,
        "trace_events": names,
        "trace_sha256": hashlib.sha256(
            json.dumps(evts, sort_keys=True).encode()).hexdigest()}
    return json.loads(json.dumps(snap))     # tuples as JSON has them


def _replay(name, srv):
    eng, trace = _REPLAY_CASES[name](srv)
    return _snapshot(name, eng.run(trace))


@pytest.mark.parametrize("name", sorted(_REPLAY_CASES))
def test_run_reproduces_the_replays_recorded_before_it_was_a_session(
        srv_model, name):
    """`ServingEngine.run` through an `EngineSession` gives, field for
    field, what `run()` and `_run_scheduled()` gave. No field is
    excepted: `scheduler` read "fifo" on the FIFO loop already, and a
    replay keeps the loops' queue-depth cadence because its idle wait
    and its arrival intake sit inside the turn."""
    with open(REPLAYS) as f:
        want = json.load(f)[name]
    got = _replay(name, srv_model[0])
    assert set(got) == set(want)
    for field in want:
        assert got[field] == want[field], field


if __name__ == "__main__":
    srv = _build_srv()[0]
    os.makedirs(os.path.dirname(REPLAYS), exist_ok=True)
    with open(REPLAYS, "w") as f:
        json.dump({name: _replay(name, srv)
                   for name in sorted(_REPLAY_CASES)}, f, indent=0,
                  sort_keys=True)
        f.write("\n")
