"""The harness end to end on the CPU at toy sizes: it skips only the look
for a chip.  Sound runs come out correct; the timed path broken underneath
comes out not correct; the control in the program's place fails a number."""
import json

import pytest

from benchmark.run import run_cell


def _run(spec, name, **kw):
    return run_cell(spec, name, 2147484001, 2.0, False, require_chip=False, **kw)


def test_serve_cell_runs_and_is_correct(tiny_spec, capsys):
    out = _run(tiny_spec, "tiny_serve")
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 12
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_tail5_mean_ms", "setup_s"}
    assert list(out)[-1] == "checks" and out["device"]["platform"] == "cpu"
    captured = capsys.readouterr()
    assert "check served_gap_max: value" in captured.err and "limit 0.35 ok" in captured.err
    info = next(l for l in captured.out.splitlines() if l.startswith("info "))
    stalls = json.loads(info[5:])["stalls"]
    assert {"calls_over_median", "host_between_calls", "gc", "machine_stops"} <= set(stalls)
    assert stalls["calls_over_median"] and {"collections", "full", "longest_ms"} <= set(stalls["gc"])
    assert {"count", "longest_ms", "total_ms", "at"} <= set(stalls["machine_stops"])
    assert "gaps_left_out" in json.loads(info[5:])["samples"]
    json.dumps(out)


def test_serve_cell_with_a_token_altered_is_not_correct(tiny_spec):
    out = _run(tiny_spec, "tiny_serve", fault="token_altered")
    assert not out["correct"] and not out["checks"]["served_gap_max"]["ok"]


def test_train_cell_runs_and_is_correct(tiny_spec, capsys):
    out = _run(tiny_spec, "tiny_train")
    assert out["correct"] and out["attempted"] >= 3
    assert set(out["metrics"]) == {"train_tok_s_chip", "setup_s"}
    info = next(l for l in capsys.readouterr().out.splitlines() if l.startswith("info "))
    steps = json.loads(info[5:])["steps"]
    assert {"count", "mean_ms", "p50_ms", "max_ms", "over_1.05_p50", "lost_s", "gc",
            "machine_stops"} <= set(steps)


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "change_norm_gap_worst_leaf"),
    ("half_batch", "grad_norm_gap_worst_leaf"),
])
def test_train_cell_with_the_step_broken_is_not_correct(tiny_spec, fault, number):
    out = _run(tiny_spec, "tiny_train", fault=fault)
    assert not out["correct"] and not out["checks"][number]["ok"]


def test_train_control_fails_a_number(tiny_spec):
    import jax
    from benchmark.harness import train, train_check
    cell = tiny_spec.cell("tiny_train")
    family, model, job = cell["family"], cell["config_spec"]["model"], cell["traffic_spec"]
    mesh = train.build_mesh(jax.devices()[:1], job)
    first = train_check.reference_first_steps(family, model, job, 11, mesh, quant="int8",
                                              keep_first_moment=True)
    numbers, _ = train_check.compare(family, model, job, 11, first, mesh)
    assert numbers["grad_diff_worst_leaf"] > cell["limits"]["grad_diff_worst_leaf"]
    sound = train_check.reference_first_steps(family, model, job, 11, mesh,
                                              keep_first_moment=True)
    numbers, _ = train_check.compare(family, model, job, 11, sound, mesh)
    assert all(numbers[k] <= cell["limits"][k] for k in numbers if k in cell["limits"])


def test_sharded_train_cell_runs_on_four_devices(tiny_spec):
    out = _run(tiny_spec, "tiny_train4")
    assert out["correct"] and out["device"]["count"] == 4


def test_no_chip_no_result():
    import subprocess
    import sys
    from conftest import REPO
    r = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "train_s4096",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0 and not r.stdout.strip()
