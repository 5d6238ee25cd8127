"""Cheap guards for the chip bring-up rules: nothing on the serve / train /
bench route may pick the CPU, a default peak or a moving cache directory
on its own. (What needs a chip is chip_smoke.py's; what needs the chip's
compilers is tests/test_chip_aot.py's.)"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    from paddle_tpu.core import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: the helper sets nothing, jax reads the env
        monkeypatch.setenv(cc.ENV_VAR, "/somewhere/else")
        assert cc.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        # unset: one fixed git-ignored directory inside the checkout
        monkeypatch.delenv(cc.ENV_VAR)
        first = cc.enable_compile_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert cc.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:  # keep this suite's CPU programs out of the checkout
        jax.config.update("jax_compilation_cache_dir", before)
    assert cc.cache_entries(os.path.join(REPO, "no_such_dir")) == 0


def test_peak_table_is_keyed_by_device_kind():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench.peak_for("TPU v5 lite") == 197e12
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(KeyError, match="no published bf16 peak"):
            bench.peak_for(kind)


def test_set_device_tpu_without_a_chip_raises():
    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.set_device("tpu")
    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.TPUPlace(0).jax_device
    assert paddle.set_device("cpu").jax_device.platform == "cpu"


def test_chip_entry_points_refuse_to_run_without_a_chip():
    """``python chip_smoke.py`` / ``python bench.py`` with no accelerator:
    non-zero exit, no result line, no model built."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = {name: subprocess.Popen(
        [sys.executable, os.path.join(REPO, name)], env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for name in ("chip_smoke.py", "bench.py")}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=120)
        assert proc.returncode != 0, name
        assert "no accelerator" in err, (name, err[-500:])
        assert not any(ln.startswith("{") for ln in out.splitlines()), \
            (name, out)


def test_chip_smoke_last_line_is_the_contract_object(monkeypatch, tmp_path,
                                                     capsys):
    """The driver reads the LAST stdout line: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``). Everything else — phases,
    versions, ``"claim": null`` — goes on the ``summary`` line before it."""
    import json
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ran = {"status": "pass", "device": dev, "versions": {"jax": "x"},
           "cache": {"dir": "d"}}
    reports = {p: dict(ran) for p in chip_smoke.PHASES[:-1]}
    reports["multichip"] = {"status": "not_run", "reason": "one chip"}
    assert chip_smoke._summarize(reports, False, 1.0) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": dev}
    summary = json.loads(lines[-2].removeprefix("summary "))
    assert summary["phases"]["multichip"] == "not_run: one chip"
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    # a failed phase: exit non-zero, ok false, the same two keys
    reports["train"] = dict(ran, status="fail", error="boom")
    assert chip_smoke._summarize(reports, False, 1.0) == 1
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last == {"ok": False, "device": dev}


def test_pallas_attention_names_the_mesh_it_cannot_split_over():
    from jax.sharding import Mesh

    from paddle_tpu.parallel.pallas_sharding import (PallasShardingError,
                                                     shard_map_attention)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("model",))
    q = jax.ShapeDtypeStruct((2, 6, 256, 64), np.float32)  # 6 heads / 4
    with pytest.raises(PallasShardingError, match=r"'model' \(size 4\)"):
        jax.eval_shape(lambda q: shard_map_attention(
            lambda a, b, c: a, q, q, q, mesh=mesh), q)


def test_launcher_refuses_local_ranks_on_a_tpu_host(monkeypatch):
    import importlib
    launch = importlib.import_module("paddle_tpu.distributed.launch.main")
    args = launch._parse_args(["--nproc_per_node", "2", "train.py"])
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    with pytest.raises(SystemExit, match="one process at a time"):
        launch.build_pod(args)
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 0)
    assert len(launch.build_pod(args)) == 2
    # pinned to the CPU backend, device nodes do not matter
    monkeypatch.undo()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch.local_tpu_chips() == 0
