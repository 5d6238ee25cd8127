"""The sentinel sees the whole machine stop and never the program's own
pauses; the token-gap readers leave out exactly the gaps that span a stop."""
import os
import signal
import time

import numpy as np
import pytest

from benchmark.harness import sentinel
from benchmark.metrics.readers import serve_latency


def test_a_stopped_sentinel_reports_one_stop():
    with sentinel.Sentinel() as watch:
        time.sleep(0.05)
        os.kill(watch._proc.pid, signal.SIGSTOP)
        try:
            time.sleep(0.15)
        finally:
            os.kill(watch._proc.pid, signal.SIGCONT)
        time.sleep(0.05)
    assert len(watch.stops) == 1
    (a, b), = watch.stops
    assert 0.140 <= b - a <= 0.400
    assert watch._proc.poll() is not None


def test_a_pause_of_this_process_is_no_stop():
    with sentinel.Sentinel() as watch:
        t = time.perf_counter()
        while time.perf_counter() - t < 0.15:     # holds the interpreter lock throughout
            pass
        time.sleep(0.05)
    assert watch.stops == [] and watch.wakeups > 50


def test_stops_land_on_the_window_clock():
    stops = [(10.0, 10.1), (20.0, 20.2), (31.0, 31.1)]
    assert sentinel.on_clock(stops, 10.5, 0.0, 20.0) == pytest.approx([(9.5, 9.7)])
    s = sentinel.summary([(1.0, 1.1), (2.0, 2.3)])
    assert s["count"] == 2 and s["longest_ms"] == pytest.approx(300.0)
    assert s["at"][0] == [2.0, 300.0]


def _obs(token_times, stops):
    return {"kind": "serve", "machine_stops": stops,
            "requests": [{"token_times": t} for t in token_times]}


def _old_gaps(obs):
    """The readers' gaps before stops were counted out: every gap."""
    return [b - a for r in obs["requests"] for a, b in zip(r["token_times"], r["token_times"][1:])]


def test_the_gap_across_a_stop_is_left_out_and_its_neighbours_kept():
    times = [[0.00, 0.01, 0.02, 0.15, 0.16, 0.17], [0.2, 0.5], [0.005, 0.3]]
    obs = _obs(times, [(0.03, 0.14)])
    gaps = serve_latency._gaps(obs)
    assert gaps == pytest.approx([0.01, 0.01, 0.01, 0.01, 0.3])
    assert serve_latency._gaps(_obs(times, [(0.02, 0.15)])) == pytest.approx(
        [0.01, 0.01, 0.01, 0.01, 0.3])        # touching ends: only the gap inside


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_no_stops_read_as_before(seed):
    rng = np.random.default_rng(seed)
    times = [list(np.cumsum(rng.exponential(0.01, size=int(n))))
             for n in rng.integers(2, 80, size=30)]
    for stops in ([], None):
        obs = _obs(times, stops)
        gaps = sorted(_old_gaps(obs))
        k = max(1, int(round(0.05 * len(gaps))))
        assert serve_latency.gap_tail_mean(obs, {"share": 0.05}) == 1e3 * float(np.mean(gaps[-k:]))
        assert serve_latency.gap_percentile(obs, {"q": 95}) == 1e3 * float(np.percentile(gaps, 95))


def test_a_serving_run_counts_out_the_gaps_across_a_planted_stop(tiny_spec, capsys, monkeypatch):
    """The whole run on the CPU, with a stop of a second planted 0.5 s after
    the sentinel started: the stop is on the window's clock, gaps across it
    are left out and counted, and ``metrics_with_stops`` reads them in."""
    import json
    from benchmark.harness import serve
    from benchmark.run import run_cell

    class Planted(sentinel.Sentinel):
        def close(self):
            super().close()
            self.stops = [(self.t_start + 0.5, self.t_start + 1.5)]
            return self.stops

        def __init__(self):
            super().__init__()
            self.t_start = time.perf_counter()

    monkeypatch.setattr(serve.sentinel, "Sentinel", Planted)
    out = run_cell(tiny_spec, "tiny_serve", 2147484002, 2.0, False, require_chip=False)
    info = json.loads(next(l for l in capsys.readouterr().out.splitlines()
                           if l.startswith("info "))[5:])
    assert out["correct"] and info["stalls"]["machine_stops"]["count"] == 1
    assert info["stalls"]["machine_stops"]["longest_ms"] == pytest.approx(1000.0)
    assert 0 < info["samples"]["gaps_left_out"] < info["samples"]["token_gaps"]
    assert set(info["metrics_with_stops"]) <= set(out["metrics"])
    assert info["metrics_with_stops"]["itl_tail5_mean_ms"] != out["metrics"]["itl_tail5_mean_ms"]["value"]
