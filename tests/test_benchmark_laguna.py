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
