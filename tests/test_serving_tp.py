"""Tensor-parallel sharded serving: decode weights + paged KV pool
over a named mesh, and its satellites.

The claims, tested on the forced 8-device CPU mesh (tests/conftest.py):
greedy streams at TP=2 and TP=4 bit-equal to the TP=1 engine on a
mixed trace (real tiny-llama factory AND the sim bookkeeping arm),
``tp=None`` byte-identical to the pre-TP engine (outputs, slot logs,
metrics records, registry contents, cache_stats shape), the fixed-
shape decode_n program still compiling ONCE across churn under
sharding, per-device pool bytes halving at TP=2 (cache_stats
``bytes_per_device`` + the ``serving_pool_bytes_per_device`` gauge +
an SLO ``ThresholdRule`` watching the streamed signal), the
over-HBM-budget capacity refusal (TP=1 refuses loudly, TP=2 serves),
KV handoffs composing with TP (same-degree pools adopt, mismatched
degrees are accounted FAILED), ``trace_report`` tp rows (absent for
unsharded traces), the jax_compat mesh/sharding bridge helpers, and
the ``serving_tp`` bench-gate family.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jax_compat import (device_put_sharded, make_mesh,
                                   named_sharding)
from paddle_tpu.models.nlp.llama_decode import (
    TPConfig, as_tp_config, decode_need_bytes_per_device,
    tree_device_bytes)
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs.slo import ThresholdRule
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache
from paddle_tpu.serving import (ClusterRouter, Request, ServingEngine,
                                make_sim_serving, synthesize_trace)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 97
COSTS = {"prefill_unit": 1.0, "decode": 1.0}


# --- jax_compat mesh/sharding helpers ---------------------------------------

def test_jax_compat_mesh_helpers():
    """make_mesh / named_sharding / device_put_sharded on the forced
    8-device CPU mesh: AUTO axes (jax.make_mesh's Explicit default turns
    every sharded contraction in the TP programs into a type error),
    replication puts a full copy per device, per-leaf specs shard,
    missing dict keys replicate."""
    mesh = make_mesh((2,), ("tp",))
    assert tuple(mesh.axis_names) == ("tp",)
    assert mesh.devices.size == 2
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
    assert TPConfig((2,)).build_mesh().axis_types == mesh.axis_types
    sh = named_sharding(mesh, None, "tp")
    assert sh.mesh.axis_names == mesh.axis_names
    assert tuple(sh.spec) == (None, "tp")

    x = np.arange(32, dtype=np.float32).reshape(4, 8)
    rep = device_put_sharded(x, mesh)            # replicated
    assert all(s.data.shape == (4, 8) for s in rep.addressable_shards)
    tree = {"a": x, "b": x.copy()}
    out = device_put_sharded(tree, mesh, {"a": (None, "tp")})
    a_shards = out["a"].addressable_shards
    assert all(s.data.shape == (4, 4) for s in a_shards)  # split
    assert all(s.data.shape == (4, 8)
               for s in out["b"].addressable_shards)      # replicated
    np.testing.assert_array_equal(np.asarray(out["a"]), x)
    # per-device byte census: sharded leaf counts one device's share,
    # replicated leaf counts whole
    assert tree_device_bytes({"a": out["a"]}) == x.nbytes // 2
    assert tree_device_bytes({"b": out["b"]}) == x.nbytes
    # a spec naming no leaf would silently replicate a renamed weight:
    # it must refuse loudly instead
    with pytest.raises(ValueError, match="no tree leaf"):
        device_put_sharded(tree, mesh, {"zz": (None, "tp")})


def test_tp_config_validation():
    assert as_tp_config(None) is None
    assert as_tp_config(2) == TPConfig((2,))
    assert as_tp_config(TPConfig((4,))).size == 4
    with pytest.raises(ValueError, match="1-D"):
        TPConfig((2, 2))
    with pytest.raises(ValueError):
        as_tp_config("wide")


# --- real tiny-llama factory fixtures ---------------------------------------

@pytest.fixture(scope="module")
def tp_model():
    """kv_heads=4 so TP=2 AND TP=4 divide every head partition."""
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4,
                           kv_heads=4)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model, cfg


def _factory(model, tp=None, **kw):
    from paddle_tpu.models.nlp.llama_decode import (
        llama_serving_decode_factory)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 8)
    kw.setdefault("n_pool_pages", 25)
    kw.setdefault("batch_capacity", 4)
    kw.setdefault("chunked_prefill", 8)
    return llama_serving_decode_factory(model, tp=tp, **kw)


@pytest.fixture(scope="module")
def srv_by_tp(tp_model):
    """One factory per degree, shared across this module's engines so
    the sharded programs compile once."""
    model, _ = tp_model
    return {1: _factory(model), 2: _factory(model, tp=TPConfig((2,))),
            4: _factory(model, tp=4)}


def _trace(seed=3, n=10):
    return synthesize_trace(
        seed=seed, n_requests=n, vocab_size=VOCAB, prompt_len=(5, 14),
        output_len=(3, 8), shared_prefix_frac=0.3, prefix_len=16,
        churn_frac=0.2, rid_prefix="tp")


def _engine(srv, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("policy", "paged")
    kw.setdefault("clock", "fixed")
    return ServingEngine(serving=srv, **kw)


def test_tp_validation_against_model(tp_model):
    """A degree that does not divide the head partitions refuses at
    build, naming the ragged dimension."""
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    model2 = LlamaForCausalLM(cfg)
    model2.eval()
    with pytest.raises(ValueError, match="kv heads"):
        _factory(model2, tp=4)
    model, _ = tp_model
    with pytest.raises(ValueError, match="devices"):
        _factory(model, tp=16)


# --- greedy parity + byte-identity ------------------------------------------

def test_engine_tp_parity_real_factory(srv_by_tp):
    """TP=2 and TP=4 streams bit-equal to the TP=1 engine on the
    mixed trace (shared prefixes + churn), with identical slot logs,
    decisions and metrics records — sharding changes residency, not
    one observable byte of serving behavior."""
    trace = _trace()
    res = {d: _engine(srv_by_tp[d]).run(trace) for d in (1, 2, 4)}
    for d in (2, 4):
        assert res[d].outputs == res[1].outputs, f"tp{d} diverged"
        assert res[d].slot_log == res[1].slot_log
        assert res[d].decisions == res[1].decisions
        assert res[d].metrics.request_rows() == \
            res[1].metrics.request_rows()
    # per-device pool residency halves per doubling; totals are equal
    b1 = res[1].cache_stats
    assert "bytes_per_device" not in b1  # unsharded: pre-TP shape
    b2, b4 = res[2].cache_stats, res[4].cache_stats
    assert b2["bytes_total"] == b4["bytes_total"]
    assert b2["bytes_per_device"] == b2["bytes_total"] // 2
    assert b4["bytes_per_device"] == b4["bytes_total"] // 4


def test_tp_pool_sharding_survives_decode(srv_by_tp):
    """The donated pools come back from prefill/decode_n still
    sharded on the kv-head axis — placement happens once at load, not
    per call (resident-sharded activations, no recompile, no gather
    creep)."""
    eng = _engine(srv_by_tp[2])
    eng.run(_trace(seed=5, n=4))
    for leaf in jax.tree_util.tree_leaves(eng._pools):
        spec = tuple(leaf.sharding.spec)
        assert len(spec) >= 2 and spec[1] == "tp", spec


def test_tp_decode_never_recompiles_across_churn(srv_by_tp):
    """The fixed-shape decode_n batches still never recompile across
    admission/eviction churn when sharded: ONE decode_n cache entry
    after a churny trace."""
    eng = _engine(srv_by_tp[2])
    eng.run(_trace(seed=7, n=8))
    assert eng._p_decode_n._cache_size() == 1


def test_tp_none_registry_and_policy_untouched(srv_by_tp):
    """tp=None leaves no TP trace: no pool-bytes gauge in the
    registry, cache_stats in the pre-TP shape, routed policy intact.
    A TP engine coerces routed->paged and refuses dense outright."""
    obs_metrics.REGISTRY.reset()
    eng1 = _engine(srv_by_tp[1], policy="routed")
    eng1.run(_trace(seed=9, n=4))
    assert "serving_pool_bytes_per_device" \
        not in obs_metrics.REGISTRY.expose_text()
    assert eng1.policy.name == "routed"
    eng2 = _engine(srv_by_tp[2], policy="routed")
    assert eng2.policy.name == "paged"  # coerced: no dense replica
    assert "serving_pool_bytes_per_device" \
        in obs_metrics.REGISTRY.expose_text()
    # POLICY INSTANCES coerce/refuse like their string spellings — a
    # RoutedPolicy object must not sneak a dense wave to the stub
    from paddle_tpu.serving import FixedPolicy, RoutedPolicy
    assert _engine(srv_by_tp[2],
                   policy=RoutedPolicy()).policy.name == "paged"
    with pytest.raises(ValueError, match="dense"):
        _engine(srv_by_tp[2], policy="dense")
    with pytest.raises(ValueError, match="dense"):
        _engine(srv_by_tp[2], policy=FixedPolicy("dense"))
    with pytest.raises(ValueError, match="conflicts"):
        _engine(srv_by_tp[2], tp=TPConfig((4,)))
    with pytest.raises(ValueError, match="conflicts"):
        _engine(srv_by_tp[1], tp=2)  # unsharded factory can't reshard


def test_engine_tp_parity_sim():
    """The sim bookkeeping arm: tp=2 vs tp=1 byte-identical outputs,
    slot logs and records at a few hundred requests, per-device bytes
    = total / degree (the head-split arithmetic)."""
    trace = synthesize_trace(
        seed=11, n_requests=300, vocab_size=509, prompt_len=(6, 24),
        output_len=(4, 12), shared_prefix_frac=0.25, prefix_len=16,
        churn_frac=0.15, rid_prefix="s")

    def run(tp):
        eng = ServingEngine(
            serving=make_sim_serving(max_len=64, page_size=8, slots=8,
                                     vocab=509, tp=tp),
            slots=8, policy="paged", clock="fixed", fixed_costs=COSTS,
            decode_chunk=4)
        return eng, eng.run(trace)

    e1, r1 = run(None)
    e2, r2 = run(TPConfig((2,)))
    assert r2.outputs == r1.outputs
    assert r2.slot_log == r1.slot_log
    assert r2.metrics.request_rows() == r1.metrics.request_rows()
    assert e1.pool_bytes_per_device() is None
    total = np.asarray(e2._pools).nbytes
    assert e2.pool_bytes_per_device() == total // 2
    assert r2.cache_stats["bytes_per_device"] == total // 2
    assert "bytes_per_device" not in r1.cache_stats


# --- bytes census, gauge, SLO watch -----------------------------------------

def test_kvcache_note_pool_bytes_unit():
    book = PagedKVCache(9, 8, kv_heads=1, head_dim=1)
    assert "bytes_per_device" not in book.cache_stats()
    book.note_pool_bytes(1000)
    assert book.cache_stats()["bytes_per_device"] == 1000
    assert book.cache_stats()["bytes_total"] == 1000
    book.note_pool_bytes(1000, 250)
    assert book.cache_stats()["bytes_per_device"] == 250


def test_slo_threshold_watches_pool_bytes():
    """A ThresholdRule on the streamed pool_bytes_per_device signal
    fires on a sharded engine (the engine streams the census at run
    start) and never on an unsharded one (the signal does not
    exist)."""
    rule = ThresholdRule(name="pool_pressure",
                         signal="pool_bytes_per_device", bound=1.0,
                         op=">=")
    trace = _sim_trace_small()
    res = _sim_tp_engine(TPConfig((2,)), slo=[rule]).run(trace)
    assert res.incidents and \
        res.incidents[0].rule == "pool_pressure"
    res1 = _sim_tp_engine(None, slo=[rule]).run(trace)
    assert res1.incidents == []


def _sim_trace_small():
    return synthesize_trace(seed=13, n_requests=6, vocab_size=509,
                            prompt_len=(6, 14), output_len=(3, 6),
                            rid_prefix="w")


def _sim_tp_engine(tp, slots=4, **kw):
    return ServingEngine(
        serving=make_sim_serving(max_len=64, page_size=8, slots=slots,
                                 vocab=509, tp=tp),
        slots=slots, policy="paged", clock="fixed", fixed_costs=COSTS,
        decode_chunk=2, **kw)


# --- capacity: a model bigger than one device's budget ----------------------

def test_capacity_budget_refuses_tp1_serves_tp2(tp_model, srv_by_tp):
    """Per-device HBM budget between the TP=1 and TP=2 footprints: the
    unsharded placement REFUSES loudly (MemoryError naming the need
    and budget), the TP=2 placement fits and serves with parity."""
    model, _ = tp_model

    def need(srv):
        # the factory's own refusal arithmetic — one source of truth
        return decode_need_bytes_per_device(*srv.paged_parts[:3])

    n1, n2 = need(srv_by_tp[1]), need(srv_by_tp[2])
    assert n2 < n1
    budget = (n1 + n2) // 2
    with pytest.raises(MemoryError, match="budget"):
        _factory(model, tp=TPConfig(
            (1,), hbm_budget_bytes_per_device=budget))
    srv = _factory(model, tp=TPConfig(
        (2,), hbm_budget_bytes_per_device=budget))
    trace = _trace(seed=15, n=3)
    res = _engine(srv).run(trace)
    ref = _engine(srv_by_tp[1]).run(trace)
    assert res.outputs == ref.outputs


# --- KV handoffs compose with TP --------------------------------------------

def _sim_cluster_engine(tp, page_size=8, slots=8):
    return ServingEngine(
        serving=make_sim_serving(max_len=96, page_size=page_size,
                                 slots=slots, vocab=101, tp=tp),
        slots=slots, policy="paged", clock="fixed", fixed_costs=COSTS,
        decode_chunk=4, prefill_chunk_budget=2)


def test_handoff_composes_with_tp():
    """Disaggregated placement over SAME-degree sharded pools: every
    chain exported/imported exactly once, streams identical to a lone
    sharded engine — TP composes with the PR-8 handoff."""
    trace = [Request(rid=f"h{i}", arrival=float(i),
                     prompt=tuple(range(1, 12 + i)), max_new_tokens=5)
             for i in range(6)]
    res = ClusterRouter(
        lambda name: _sim_cluster_engine(TPConfig((2,))), 2,
        placement="disaggregated",
        roles={"r0": "prefill", "r1": "decode"},
        kv_transfer_unit=0.05).run(trace)
    cen = res.census()
    assert cen["conserved"] and cen["pool_census_ok"]
    assert cen["handoffs"]["exported"] == len(trace)
    assert cen["handoffs"]["balanced"]
    assert cen["handoffs"].get("failed", 0) == 0
    lone = _sim_cluster_engine(TPConfig((2,))).run(trace)
    assert res.outputs() == lone.outputs


def test_handoff_composes_with_tp_real_pools(tp_model, srv_by_tp):
    """The REAL factory's head-sharded pools move through
    export/import bit-intact: a 1-prefill + 1-decode cluster over two
    tp=2 factories (separate pools per replica, same mesh width)
    agrees token-for-token with a lone sharded engine — the PR-8
    page-axis gather/scatter generalizes to NamedSharding arrays."""
    model, _ = tp_model
    srv_a = _factory(model, tp=TPConfig((2,)))
    srv_b = _factory(model, tp=TPConfig((2,)))
    trace = synthesize_trace(
        seed=21, n_requests=4, arrival="poisson", mean_interarrival=4.0,
        prompt_len=(5, 14), output_len=(3, 5), vocab_size=VOCAB,
        rid_prefix="rh")

    def spawn(name):
        srv = {"r0": srv_a, "r1": srv_b}[name]
        return ServingEngine(serving=srv, slots=4, policy="paged",
                             clock="fixed", fixed_costs=COSTS,
                             decode_chunk=2, prefill_chunk_budget=2)
    res = ClusterRouter(
        spawn, 2, placement="disaggregated",
        roles={"r0": "prefill", "r1": "decode"},
        kv_transfer_unit=0.1).run(trace)
    cen = res.census()
    assert cen["conserved"] and cen["handoffs"]["balanced"]
    assert cen["handoffs"]["exported"] == len(trace)
    assert cen["handoffs"].get("failed", 0) == 0
    lone = _engine(srv_by_tp[2]).run(trace)
    assert res.outputs() == lone.outputs


def test_publish_exports_pool_bytes_gauge_only_when_sharded():
    """publish() lands the per-device pool gauge ONLY for sharded
    runs — an unsharded replay's registry is byte-identical."""
    from paddle_tpu.obs.metrics import MetricsRegistry
    trace = _sim_trace_small()
    res2 = _sim_tp_engine(TPConfig((2,))).run(trace)
    reg = MetricsRegistry()
    res2.metrics.publish(registry=reg)
    txt = reg.expose_text()
    assert "serving_pool_bytes_per_device" in txt
    res1 = _sim_tp_engine(None).run(trace)
    reg1 = MetricsRegistry()
    res1.metrics.publish(registry=reg1)
    assert "serving_pool_bytes_per_device" \
        not in reg1.expose_text()


def test_handoff_reshards_mismatched_tp_degree():
    """A decode worker on a DIFFERENT tp degree adopts a head-sharded
    chain through the priced kv_reshard transform (PR 20): the import
    gathers to the canonical layout on the importer's clock instead
    of accounting the handoff FAILED — streams identical to a
    same-degree fleet, census balanced with the tp axis counted."""
    def spawn(name):
        return _sim_cluster_engine(TPConfig((2,)) if name == "r0"
                                   else None)
    trace = [Request(rid=f"g{i}", arrival=float(i),
                     prompt=tuple(range(1, 10)), max_new_tokens=4)
             for i in range(3)]
    res = ClusterRouter(spawn, 2, placement="disaggregated",
                        roles={"r0": "prefill", "r1": "decode"},
                        kv_transfer_unit=0.05).run(trace)
    cen = res.census()
    assert cen["conserved"], cen
    assert cen["handoffs"]["failed"] == 0
    assert cen["handoffs"]["imported"] == len(trace)
    assert res.handoffs.get("resharded", {}).get("tp") == len(trace)
    twin = ClusterRouter(
        lambda name: _sim_cluster_engine(None), 2,
        placement="disaggregated",
        roles={"r0": "prefill", "r1": "decode"},
        kv_transfer_unit=0.05).run(trace)
    tokens = lambda r: sorted(  # noqa: E731
        (rid, tuple(toks))
        for res_ in r.results.values()
        for rid, toks in res_.outputs.items())
    assert tokens(res) == tokens(twin)


# --- trace_report tp rows ---------------------------------------------------

def test_trace_report_tp_rows(srv_by_tp, tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from trace_report import load_trace as load_chrome, tp_summary
    path = str(tmp_path / "tp_trace.json")
    eng = ServingEngine(serving=srv_by_tp[2], slots=4, policy="paged",
                        clock="fixed", trace=path)
    eng.run(_trace(seed=17, n=4))
    evts = load_chrome(path)
    row = tp_summary(evts)
    assert row is not None and row["tp"] == 2
    assert row["prefill_spans"] > 0 and row["decode_spans"] > 0
    assert row["tagged_spans"] >= row["prefill_spans"] \
        + row["decode_spans"]
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "trace_report.py"), path,
         "--json"], capture_output=True, text=True)
    kinds = [json.loads(ln)["bench"]
             for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert "trace_report_tp" in kinds
    assert kinds[-1] == "trace_report"  # global row still LAST


def test_trace_report_unsharded_has_no_tp_row(srv_by_tp, tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from trace_report import load_trace as load_chrome, tp_summary
    path = str(tmp_path / "plain_trace.json")
    ServingEngine(serving=srv_by_tp[1], slots=4, policy="paged",
                  clock="fixed", trace=path).run(_trace(seed=19, n=3))
    evts = load_chrome(path)
    assert tp_summary(evts) is None
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "trace_report.py"), path],
        capture_output=True, text=True)
    assert "tensor parallel" not in out.stdout


# --- the serving_tp bench-gate family ---------------------------------------

def _gate(text, tmp_path):
    p = tmp_path / "rows.jsonl"
    p.write_text(text)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"),
         "serving", str(p)], capture_output=True, text=True)
    recs = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    return r.returncode, recs


def _tp_row(arm, tp, census=True, per_dev=1000):
    return json.dumps({"bench": "serving_tp", "arm": arm, "tp": tp,
                       "device": "cpu", "census_ok": census,
                       "pool_bytes_per_device": per_dev})


def _tp_cap(refused=True, served=True):
    return json.dumps({"bench": "serving_tp_capacity",
                       "tp1_refused": refused, "tp2_served": served})


def _tp_sum(p2=True, p4=True, sim=True, ratio=0.5):
    return json.dumps({"bench": "serving_tp_summary",
                       "parity_tp2": p2, "parity_tp4": p4,
                       "sim_parity": sim, "tp_degrees": [2, 4],
                       "pool_bytes_ratio_tp2": ratio,
                       "bytes_reduction_tp2": round(1.0 / ratio, 4)
                       if ratio else None})


def test_bench_gate_serving_tp_family(tmp_path):
    base = [_tp_row("tp1", 1, per_dev=2000),
            _tp_row("tp2", 2, per_dev=1000),
            _tp_row("tp4", 4, per_dev=500), _tp_cap()]

    rc, recs = _gate("\n".join(base + [_tp_sum()]) + "\n", tmp_path)
    assert rc == 0 and recs[-1]["gate"] == "pass"

    # TP=2 divergence is correctness
    rc, recs = _gate("\n".join(base + [_tp_sum(p2=False)]) + "\n",
                     tmp_path)
    assert rc == 1 and "DIVERGING" in recs[-1]["reason"]

    # sim-arm divergence FAILs too
    rc, recs = _gate("\n".join(base + [_tp_sum(sim=False)]) + "\n",
                     tmp_path)
    assert rc == 1 and "sim" in recs[-1]["reason"]

    # a tp4 arm present but unverified/diverged FAILs
    rc, recs = _gate("\n".join(base + [_tp_sum(p4=None)]) + "\n",
                     tmp_path)
    assert rc == 1 and "tp4" in recs[-1]["reason"]

    # a pool that did not actually shard FAILs on the byte ceiling
    rc, recs = _gate("\n".join(base + [_tp_sum(ratio=0.97)]) + "\n",
                     tmp_path)
    assert rc == 1 and "0.55" in json.dumps(recs[-1])

    # capacity demo must hold both halves
    rows = base[:3] + [_tp_cap(refused=False)]
    rc, recs = _gate("\n".join(rows + [_tp_sum()]) + "\n", tmp_path)
    assert rc == 1 and "REFUSE" in recs[-1]["reason"]
    rows = base[:3] + [_tp_cap(served=False)]
    rc, recs = _gate("\n".join(rows + [_tp_sum()]) + "\n", tmp_path)
    assert rc == 1 and "SERVE" in recs[-1]["reason"]

    # broken pool census FAILs naming the arm
    rows = [base[0], _tp_row("tp2", 2, census=False), base[3]]
    rc, recs = _gate("\n".join(rows + [_tp_sum()]) + "\n", tmp_path)
    assert rc == 1 and recs[-1]["arm"] == "tp2"

    # a missing arm FAILs gracefully
    rc, recs = _gate(base[0] + "\n", tmp_path)
    assert rc == 1 and "tp2" in recs[-1]["reason"]

    # no summary row -> parity UNVERIFIED
    rc, recs = _gate("\n".join(base) + "\n", tmp_path)
    assert rc == 1 and "UNVERIFIED" in recs[-1]["reason"]


@pytest.mark.slow
def test_bench_tp_single_device_graceful_no_json():
    """On a single-device image the --tp arm prints NO JSON row and
    exits 1 — bench_gate's no-JSON handling reads that as FAIL (the
    claim was not checked, not vacuously passed)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "serving_workload_bench.py"),
         "--cpu", "--tp"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 1
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
    assert "devices" in r.stdout
