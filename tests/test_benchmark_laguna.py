"""``benchmark/tests/test_laguna.py``'s cases from the tier-1 command (which
reads ``tests/`` alone): the ``laguna`` family's seam checks, its readers on
a hand-made ``obs`` and the cell ``serve_window_moe_codemix`` at toy size on
the CPU, collected as they are with the benchmark tests' own fixtures, the
way ``test_benchmark_family.py`` collects ``test_family.py``'s."""
import importlib.util
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
BENCH_TESTS = REPO / "benchmark" / "tests"


def _load(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ours = sys.modules.get("conftest")
try:
    sys.modules["conftest"] = _bench_conftest = _load("benchmark_tests_conftest_laguna",
                                                      BENCH_TESTS / "conftest.py")
    _laguna = _load("benchmark_tests_test_laguna", BENCH_TESTS / "test_laguna.py")
finally:
    if _ours is not None:
        sys.modules["conftest"] = _ours
    else:
        del sys.modules["conftest"]

tiny_root = _bench_conftest.tiny_root
tiny_spec = _bench_conftest.tiny_spec
toy_codemix_spec = _laguna.toy_codemix_spec
globals().update({k: v for k, v in vars(_laguna).items() if k.startswith("test_")})


def test_the_configuration_keeps_every_published_key_but_its_depth():
    """``test_laguna.py``'s case of this name with its entries found by
    their names: it holds them to be the last of ``BENCHMARK.json``'s lists,
    and a later configuration has to append behind them."""
    import json
    cfg = json.loads((REPO / "benchmark/configs/laguna-xs.2-serve-l5.json").read_text())
    published, cell = _laguna.PUBLISHED, _laguna.CELL
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 40}
    assert {k: cfg[k] for k in published} == dict(published, num_hidden_layers=5)
    assert cfg["family"] == "laguna" and all(cfg.get(k) for k in ("stands_for", "note"))
    assert {"gating", "router", "router_bias", "qk_norm", "shared_expert_gate",
            "torch_dtype"} <= set(cfg["assumed"])
    assert cfg["engine"] == {"slots": 32, "max_len": 8768, "page_size": 64,
                             "n_pool_pages": 5409, "n_window_pages": 513, "policy": "paged",
                             "prefill_chunk_budget": 4}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-xs.2-serve-l5")
    assert entry["source"] == cfg["source"]
    names = [w["name"] for w in bench["workloads"]]
    at = names.index(cell)
    assert names[at:at + 2] == [cell, "serve_decode_heavy"]
    assert all(w["chips"] == 1 for w in bench["workloads"][at:at + 2])
    tok_s = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    at = tok_s["workloads"].index(cell)
    assert tok_s["workloads"][at:at + 2] == [cell, "serve_decode_heavy"] and tok_s["bound"] == 0.05
