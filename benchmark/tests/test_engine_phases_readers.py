"""The readers of the engine's own accounting (``obs["overhead"]``): on a
made-up accounting, on the three-key accounting of a program that does not
count phases, and on BENCHMARK.json's entries for them."""
import json

import pytest
from conftest import REPO

from benchmark.harness.spec import Spec

NEW = {"engine_phase_host_share.steady": 3.0, "engine_phase_host_share.burst": 3.0,
       "admit_host_share.steady": 0.75, "emit_host_share.steady": 1.25,
       "lane_host_share.burst": 0.5, "decode_occupancy.steady": 62.5,
       "call_dispatch_share.steady": 2.75, "call_dispatch_share.burst": 2.75,
       "dispatch_excess_share.steady": 1.0, "wait_excess_share.steady": 5.0}


def _obs(overhead):
    return {"kind": "serve", "window_s": 40.0, "overhead": overhead}


def _accounting():
    phases = {"intake": 0.1, "admit": 0.2, "decode.build": 0.1, "decode.emit": 0.3,
              "finish": 0.1, "lane.pick": 0.05, "lane.complete": 0.15, "tail": 0.1,
              "idle_wait": 7.0}
    return {"run_wall_s": 41.0, "device_wall_s": 30.0, "engine_host_frac": 0.27,
            "turns": 4, "slots": 16, "unaccounted_s": 0.1, "idle_wait_s": 7.0,
            "phases": {k: {"n": 4, "self_s": v, "max_s": v} for k, v in phases.items()},
            "calls": {"decode": {"n": 4, "rows": 40, "start_s": [0.0, 1.0, 2.0, 3.0],
                                 "seam_s": [0.0] * 4, "dispatch_s": [0.1, 0.1, 0.1, 0.3],
                                 "wait_s": [1.0, 1.0, 1.0, 3.0]},
                      "prefill": {"n": 3, "rows": 3, "start_s": [0.5, 1.5, 2.5],
                                  "seam_s": [0.0] * 3, "dispatch_s": [0.1, 0.1, 0.3],
                                  "wait_s": [0.5, 0.5, 0.5]}}}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_a_made_up_accounting(name):
    value = Spec().reader(name)(_obs(_accounting()))
    assert value == pytest.approx(NEW[name])


@pytest.mark.parametrize("overhead", [
    None, {"run_wall_s": 41.0, "device_wall_s": 30.0, "engine_host_frac": 0.27}])
def test_nothing_to_read_gives_nothing(overhead):
    """The parent's engine counts no phases: each metric is left out of the
    line, none raises and none reads 0."""
    spec = Spec()
    entries = [m for m in spec.bench["per_layer"] if m["name"] in NEW]
    assert len(entries) == len(NEW)
    assert spec.read_metrics(entries, _obs(overhead)) == {}
    assert all(spec.reader(name)({"kind": "train", "window_s": 40.0}) is None for name in NEW)


def test_entries_are_appended_and_name_their_cells():
    per_layer = json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]
    names = [m["name"] for m in per_layer]
    assert sorted(names[-len(NEW):]) == sorted(NEW)     # after everything that was there
    for m in per_layer[-len(NEW):]:
        cell = "serve_prefill_burst" if m["name"].endswith(".burst") else "serve_chat_steady"
        assert m["workloads"] == [cell]
        assert m["source"] == ("program_counter" if m["name"].startswith("decode_occupancy")
                               else "program_span")
        assert m["layer"] in ("serving engine", "decode factories")
    spec = Spec()
    steady = {m["name"] for m in spec.per_layer("serve_chat_steady")}
    burst = {m["name"] for m in spec.per_layer("serve_prefill_burst")}
    assert len(steady & set(NEW)) == 7 and len(burst & set(NEW)) == 3
    assert not set(NEW) & {m["name"] for m in spec.per_layer("train_s4096")}
