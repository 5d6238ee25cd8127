"""BENCHMARK.json against the contract's limits and the files it names."""
import json
import re

from conftest import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
ROOT = REPO / "benchmark"


def test_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and "\n" not in w["why"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in BENCH["end_to_end"])


def test_every_named_file_exists_and_agrees():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        body = json.loads((REPO / c["file"]).read_text())
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        for key, published in body["published"].items():
            assert key in c["reduced"] and body[key] != published
    for w in BENCH["workloads"]:
        cell = json.loads((ROOT / "workloads" / f"{w['name']}.json").read_text())
        assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} == \
            {k: w[k] for k in ("config", "traffic", "chips", "why")}
        assert w["config"] in configs
        assert (ROOT / "traffic" / f"{w['traffic']}.json").is_file()
        assert cell["limits"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        spec = json.loads((ROOT / "metrics" / f"{m['name']}.json").read_text())
        mod = spec["reader"].split(":")[0]
        assert (ROOT / "metrics" / "readers" / f"{mod}.py").is_file()


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    from benchmark.harness.spec import Spec
    spec = Spec()
    cells = [w["name"] for w in BENCH["workloads"]]
    layers = set()
    for m in BENCH["per_layer"]:
        layers.add(m["layer"])
        for cell in m.get("workloads", cells):
            assert m["moves"] in {e["name"] for e in spec.end_to_end(cell)}, (m["name"], cell)
    for cell in cells:
        e2e = {e["name"] for e in spec.end_to_end(cell)}
        assert "setup_s" in e2e and len(e2e) >= 2 and spec.per_layer(cell)
        mfu = [m for m in spec.per_layer(cell) if "mfu" in m["name"]]
        for m in spec.per_layer(cell):
            if m["name"].split(".")[0].endswith("_roofline"):
                assert any(x["moves"] == m["moves"] for x in mfu), m["name"]


def test_a_cell_added_as_files_is_found(tiny_spec):
    cell = tiny_spec.cell("tiny_serve")
    assert cell["config_spec"]["model"]["hidden_size"] == 256
    assert {m["name"] for m in tiny_spec.end_to_end("tiny_serve")} == \
        {"ttft_p95_ms", "itl_tail5_mean_ms", "setup_s"}
    assert "collective_exposed_share.train4" in \
        {m["name"] for m in tiny_spec.per_layer("tiny_train4")}
