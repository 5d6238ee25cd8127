"""Finds a cell's files by the names BENCHMARK.json gives.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric is a file of its own under the benchmark's root; adding
one edits no file that is there.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]          # benchmark/
REPO = ROOT.parent


class SpecError(ValueError):
    pass


def load_module(path: Path):
    """A family, a reference or a metric reader is a file of its own, loaded
    by its path, so that a root elsewhere (a test's, a later PR's) brings
    its own."""
    path = Path(path).resolve()
    key = f"_bench_file_{abs(hash(str(path)))}"
    if key not in sys.modules:
        if not path.is_file():
            raise SpecError(f"missing benchmark file: {path}")
        loaded = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(loaded)
        sys.modules[key] = mod
        try:
            loaded.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


def reference_module(family_file: str, name: str):
    """``benchmark/reference/<name>.py`` of the root that holds the family's
    own file: how a family names its plain reference."""
    return load_module(Path(family_file).resolve().parents[1] / "reference" / f"{name}.py")


def _load(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


class Spec:
    """BENCHMARK.json plus the per-name files under ``root``."""

    def __init__(self, root: Path = ROOT, benchmark_json: Path | None = None):
        self.root = Path(root)
        self.bench = _load(benchmark_json or self.root.parent / "BENCHMARK.json")
        self.peaks = _load(self.root / "peaks.json")

    # -- cells ------------------------------------------------------------
    def cell(self, name: str) -> dict:
        cell = _load(self.root / "workloads" / f"{name}.json")
        cell["name"] = name
        path = self.root / "configs" / f"{cell['config']}.json"
        config = _load(path)
        if "family" not in config:
            raise SpecError(f"{path} names no family")
        family = self.family(config["family"])
        missing = [k for k in family.MODEL_KEYS if k not in config]
        if missing:
            raise SpecError(f"{path} lacks {missing}, which family {config['family']!r} reads")
        config["model"] = {k: config[k] for k in family.MODEL_KEYS}
        cell["config_spec"] = config
        cell["family"] = family
        cell["traffic_spec"] = _load(self.root / "traffic" / f"{cell['traffic']}.json")
        return cell

    def family(self, name: str):
        """All that depends on the model's shape: ``families/<name>.py``
        (benchmark/README.md lists what it has to give)."""
        return load_module(self.root / "families" / f"{name}.py")

    # -- metrics ----------------------------------------------------------
    def _reports(self, entry: dict, cell: str, e2e_of_cell: set | None) -> bool:
        if "workloads" in entry:
            return cell in entry["workloads"]
        return e2e_of_cell is None or entry["moves"] in e2e_of_cell

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.bench["end_to_end"]
                if self._reports(m, cell, None)]

    def per_layer(self, cell: str) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.bench["per_layer"]
                if self._reports(m, cell, e2e)]

    def metric_file(self, name: str) -> dict:
        return _load(self.root / "metrics" / f"{name}.json")

    def reader(self, name: str):
        """The metric's reader: ``reader(obs, params) -> float | None``."""
        spec = self.metric_file(name)
        mod_name, _, fn_name = spec["reader"].partition(":")
        fn = getattr(load_module(self.root / "metrics" / "readers" / f"{mod_name}.py"), fn_name)
        params = spec.get("params", {})
        return lambda obs: fn(obs, params)

    def read_metrics(self, entries: list[dict], obs: dict) -> dict:
        out = {}
        for m in entries:
            value = self.reader(m["name"])(obs)
            if value is not None and math.isfinite(value):
                # nothing to read (or nothing finite: every request failed):
                # left out, never 0
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def peak(self, device_kind: str) -> dict:
        try:
            return self.peaks["chips"][device_kind]
        except KeyError:
            raise SpecError(f"device kind {device_kind!r} is not in "
                            f"{self.root / 'peaks.json'}") from None
