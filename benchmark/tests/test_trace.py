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


def test_arrival_waits_are_named_and_left_out_of_the_idle_share():
    calls = [(0, 10, "prefill"), (40, 50, "decode")]
    waits = [(12, 30), (60, 70)]
    gaps = [(2, 4), (10, 40), (50, 65)]
    named = trace.name_gaps(gaps, calls, waits)
    assert named == {"in prefill call": 2, trace.WAITING: 18 + 5,
                     "host between calls": 12 + 10}
    assert trace.name_gaps(gaps, calls, []) == {"in prefill call": 2, "host between calls": 45}
    from benchmark.harness.spec import Spec
    idle = Spec().reader("device_idle_share.burst")
    t = {"busy_s": 3.0, "window_s": 4.0}
    assert idle({"device_trace": t}) == pytest.approx(25.0)
    # a faster program waits for the next burst instead: not lazier for it
    assert idle({"device_trace": dict(t, busy_s=1.5, window_s=4.0, arrival_wait_s=2.0)}) == \
        pytest.approx(25.0)
    assert idle({"device_trace": dict(t, busy_s=0.0, window_s=4.0, arrival_wait_s=4.0)}) is None


def test_the_stretch_is_placed_by_the_schedule():
    bursts = [0.5, 0.5, 9.1, 9.1, 27.6, 27.6]
    assert trace.serving_stretch(bursts, 2, 40.0) == (27.6, 31.6)
    assert trace.serving_stretch([0.1, 20.0, 38.2, 39.9], 1, 40.0) == (36.0, 40.0)
    assert trace.serving_stretch([0.1, 20.0, 33.0], 1, 40.0) == (33.0, 40.0)


class _Profiler:
    """Stands in for JAX's profiler: records when it was started and stopped."""

    def __init__(self, tw, clock):
        self.calls = []
        tw.start = lambda: (self.calls.append(("start", clock.now())),
                            setattr(tw, "active", True))
        tw.stop = lambda: (self.calls.append(("stop", clock.now())),
                           tw.__dict__.update(active=False, done=True))


def _drive(clock, arrivals, calls_each=3, call_s=0.004):
    """A toy engine: waits for each arrival, then makes a few short calls,
    and so finishes every request well before the next one is due."""
    import time
    for due in arrivals:
        clock.advance_to(due)
        for _ in range(calls_each):
            clock.timed("prefill", lambda: time.sleep(call_s))


@pytest.mark.parametrize("burst,arrivals,seconds", [
    (8, [0.05] * 8 + [0.25] * 8 + [0.55] * 8, 1.0),       # all in long before seconds - 4
    (1, [0.05, 0.2, 0.45, 0.8, 0.9], 1.0)])
def test_a_fast_engine_still_gets_its_stretch_traced(tmp_path, burst, arrivals, seconds):
    from benchmark.harness.clock import WallClock
    start_at, stop_at = trace.serving_stretch(arrivals, burst, seconds, trace_seconds=0.2)
    tw = trace.TraceWindow(tmp_path / "trace")
    tw.place(start_at, stop_at)
    clock = WallClock(tw)
    profiler = _Profiler(tw, clock)
    tw.arm()
    _drive(clock, sorted(set(arrivals)))
    tw.finish()
    assert [c[0] for c in profiler.calls] == ["start", "stop"]
    started, stopped = profiler.calls[0][1], profiler.calls[1][1]
    assert start_at <= started < start_at + 0.05 and started < stopped
    assert tw.interval[0] == pytest.approx(started, abs=0.01)
    if burst == 1:
        return
    # the parent placed every stretch at the last trace_seconds of the arrivals' span:
    # this engine makes no call there, the profiler never ran and reduce() found no file
    old = trace.TraceWindow(tmp_path / "old")
    old.place(seconds - 0.2, seconds)
    old_clock = WallClock(old)
    old_profiler = _Profiler(old, old_clock)
    old.arm()
    _drive(old_clock, sorted(set(arrivals)))
    old.finish()
    assert old_profiler.calls == [] and old.reduce() is None
