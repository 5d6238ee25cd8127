"""Same-host multi-process cluster bring-up test.

~ the reference's TestDistBase pillar (unittests/test_dist_base.py:782 /
test_parallel_dygraph_dataparallel.py:152 run_mnist_2gpu, which shells out
to the launcher itself): spawn real trainer processes via
``python -m paddle_tpu.distributed.launch``, validate the PADDLE_* env
contract, and exchange data cross-process through the C++ TCPStore
rendezvous — the full SURVEY.md §3.5 bring-up path without TPUs.
"""
import pytest

pytestmark = pytest.mark.slow  # multi-process/e2e: full-suite lane only
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


TRAINER = textwrap.dedent("""
    import json
    import os
    import sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    rank = int(os.environ["PADDLE_GLOBAL_RANK"])
    world = int(os.environ["PADDLE_WORLD_SIZE"])
    local = int(os.environ["PADDLE_LOCAL_RANK"])
    master = os.environ["PADDLE_MASTER"]
    endpoints = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
    assert len(endpoints) == world

    # cross-process barrier + KV exchange over the TCPStore rendezvous
    from paddle_tpu.distributed.store import TCPStore
    host, port = master.split(":")
    store = TCPStore(host, int(port) + 17, is_master=(rank == 0),
                     world_size=world)
    store.set(f"hello_{rank}", str(rank * 100))
    # every rank waits for every other rank's key (barrier-by-wait)
    got = {}
    for r in range(world):
        store.wait(f"hello_{r}")
        got[r] = int(store.get(f"hello_{r}"))
    out = {"rank": rank, "world": world, "local": local, "got": got}
    with open(os.path.join(os.environ["TEST_OUT_DIR"],
                           f"rank{rank}.json"), "w") as f:
        json.dump(out, f)
""")


def test_launch_two_ranks_rendezvous(tmp_path):
    script = tmp_path / "trainer.py"
    script.write_text(TRAINER)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["TEST_OUT_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", str(script)],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=110)
    assert proc.returncode == 0, proc.stdout + proc.stderr

    import json
    results = {}
    for r in range(2):
        p = tmp_path / f"rank{r}.json"
        assert p.exists(), f"rank {r} wrote no result: {proc.stdout}"
        results[r] = json.loads(p.read_text())
    for r in range(2):
        assert results[r]["world"] == 2
        assert results[r]["got"] == {"0": 0, "1": 100}
