"""Test config: force CPU backend with 8 virtual devices.

Mirrors the reference test strategy (SURVEY.md §4): numpy-oracle op tests on
CPU; distributed parity over a virtual device mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8 is the gloo analog).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the suite's fixtures need the 8 virtual CPU devices: pin the platform
# even when the caller forgot JAX_PLATFORMS=cpu (on a chip host jax would
# otherwise take the chip)
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    yield


def _free_port() -> int:
    """A port currently free on localhost (bind-to-0 probe). Avoids
    collisions between concurrently running suites/processes that the old
    hard-coded ports suffered."""
    import socket
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture
def free_port():
    return _free_port()


@pytest.fixture
def free_port_factory():
    return _free_port


# --- dist-test retry + quarantine discipline ------------------------------
# ~ reference dist_test.sh (retry loop around multi-process tests) and
# tools/get_quick_disable_lt.py (quarantine list fetched before the run).
# Multi-process rendezvous tests are load-sensitive by nature; marked
# tests get bounded reruns, and tests/quarantine.txt names node-id
# substrings to skip outright (one per line, '#' comments).

def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "dist_retry(n=1): rerun a load-sensitive multi-process test up to "
        "n extra times on failure (~ dist_test.sh retry discipline)")


def pytest_collection_modifyitems(config, items):
    import os

    # 1) quarantine: node-id substrings in quarantine.txt skip outright
    qpath = os.path.join(os.path.dirname(__file__), "quarantine.txt")
    patterns = []
    if os.path.exists(qpath):
        with open(qpath) as f:
            # node-id substring, optional trailing '# issue-ref' comment
            patterns = [ln.split("#")[0].strip() for ln in f
                        if ln.split("#")[0].strip()]
    if patterns:
        skip = pytest.mark.skip(
            reason="quarantined (tests/quarantine.txt)")
        for item in items:
            if any(p in item.nodeid for p in patterns):
                item.add_marker(skip)

    # 2) duration-based slow marking (round-4 verdict item 10): node
    # ids measured >= 8s in the full-suite --durations run live in
    # tests/slow_tests.txt; they get the `slow` marker in addition to
    # the file-level pytestmark on the multi-process/e2e modules, so
    # `-m "not slow"` is a genuinely fast lane on this 1-core host
    lpath = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
    if os.path.exists(lpath):
        with open(lpath) as f:
            slow_ids = {ln.strip() for ln in f
                        if ln.strip() and not ln.startswith("#")}
        for item in items:
            if item.nodeid in slow_ids:
                item.add_marker(pytest.mark.slow)


def pytest_runtest_protocol(item, nextitem):
    m = item.get_closest_marker("dist_retry")
    if m is None:
        return None
    retries = int(m.kwargs.get("n", m.args[0] if m.args else 1))
    from _pytest.runner import runtestprotocol
    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    for attempt in range(retries + 1):
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
        if not any(r.failed for r in reports) or attempt == retries:
            for r in reports:
                item.ihook.pytest_runtest_logreport(report=r)
            break
        import warnings
        warnings.warn(f"dist_retry: {item.nodeid} failed attempt "
                      f"{attempt + 1}/{retries + 1}, retrying")
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True

