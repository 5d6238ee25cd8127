"""The reduction from a profiler trace to tables, on a small recorded trace
(a serving stretch on one TPU v5e, taken by PR 24's harness)."""
from pathlib import Path

import pytest

from benchmark.harness import trace

RECORDED = Path(__file__).parent / "data" / "serve_small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_xplane(str(RECORDED))


def test_busy_window_and_programs(reduced):
    assert reduced["chips_traced"] == 1
    assert reduced["busy_s"] == pytest.approx(0.694062, abs=1e-5)
    assert reduced["window_s"] == pytest.approx(0.756422, abs=1e-5)
    assert reduced["modules"]["jit_decode_n"]["count"] == 21
    assert reduced["modules"]["jit__prefill_chunk"]["count"] == 15


def test_operations_carry_program_kind_and_shape(reduced):
    top = reduced["ops"][0]
    assert top["name"] == "jit_decode_n/closed_call.24 custom-call bf16[16,8,4,128]"
    assert top["count"] == 168 and top["seconds"] == pytest.approx(0.083804, abs=1e-5)
    assert not any(" while" in o["name"].split("/")[1][:20] for o in reduced["ops"])
    total = sum(o["seconds"] for o in reduced["ops"])
    assert total == pytest.approx(reduced["busy_s"], rel=0.02)   # leaves do not overlap


def test_idle_gaps_are_named_by_the_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) <= {"in decode call", "in prefill call", "host between calls"}
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    b = trace.breakdown(reduced)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10


def test_union_counts_overlap_once():
    busy, gaps = trace._union([(0, 10), (2, 5), (12, 15), (15, 16)])
    assert busy == 14 and gaps == [(10, 12)]


def test_readers_on_the_recorded_trace(reduced):
    from benchmark.harness.spec import Spec
    spec = Spec()
    obs = {"kind": "serve", "device_trace": reduced, "peak": spec.peak("TPU v5 lite"),
           "model": spec.cell("serve_chat_steady")["config_spec"]["model"],
           "trace_interval": (0.0, 1.0), "chips": 1,
           "requests": [{"prompt_len": 500, "token_times": [0.1, 0.2, 0.3]}]}
    assert spec.reader("device_idle_share.steady")(obs) == pytest.approx(8.244, abs=1e-2)
    # two decode tokens at contexts 501 and 502, 8 layers, K and V, 8 x 128 x 2 bytes
    least = 2 * 1003 * 8 * 128 * 2 * 8 / 819e9
    assert spec.reader("paged_attn_roofline.steady")(obs) == \
        pytest.approx(100 * least / 0.083804, rel=1e-3)
    assert spec.reader("paged_attn_roofline.steady")(dict(obs, device_trace=None)) is None
