"""``benchmark/tests/test_kimi_linear.py``'s cases from the tier-1 command
(which reads ``tests/`` alone): the ``kimi_linear`` family's seam checks, its
readers on a hand-made ``obs`` and the cell ``serve_linear_latent_agent`` at
toy size on the CPU, collected as they are with the benchmark tests' own
fixtures, the way ``test_benchmark_laguna.py`` collects ``test_laguna.py``'s."""
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
    sys.modules["conftest"] = _bench_conftest = _load("benchmark_tests_conftest_kimi",
                                                      BENCH_TESTS / "conftest.py")
    _kimi = _load("benchmark_tests_test_kimi_linear", BENCH_TESTS / "test_kimi_linear.py")
finally:
    if _ours is not None:
        sys.modules["conftest"] = _ours
    else:
        del sys.modules["conftest"]

tiny_root = _bench_conftest.tiny_root
tiny_spec = _bench_conftest.tiny_spec
toy_agent_spec = _kimi.toy_agent_spec
globals().update({k: v for k, v in vars(_kimi).items() if k.startswith("test_")})
