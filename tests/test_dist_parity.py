"""Multi-process DP training parity — the TestDistBase pillar.

~ reference unittests/test_dist_base.py:782 (check_with_place :1457): spawn
trainer processes on localhost via the launch CLI, feed identical data, and
assert per-step loss parity between the 1-process run and the 2-process
data-parallel run. Grad sync fires from backward() through the
DataParallel post-backward hook (the EagerReducer analog) — if grads don't
sync, the parameter trajectories diverge and this test fails.
"""
import pytest

pytestmark = pytest.mark.slow  # multi-process/e2e: full-suite lane only
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAINER = textwrap.dedent("""
    import json
    import os
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")

    rank = int(os.environ.get("PADDLE_GLOBAL_RANK", "0"))
    world = int(os.environ.get("PADDLE_WORLD_SIZE", "1"))
    if world > 1:
        # own port for the jax coordinator (launcher KV uses PADDLE_MASTER)
        host, port = os.environ["PADDLE_MASTER"].split(":")
        os.environ["PADDLE_MASTER"] = f"{host}:{int(port) + 31}"

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    dist.init_parallel_env()
    fleet.init(is_collective=True)

    paddle.seed(42)  # identical init on every rank
    model = paddle.nn.Sequential(
        paddle.nn.Linear(16, 32), paddle.nn.ReLU(),
        paddle.nn.Linear(32, 4))
    model = fleet.distributed_model(model)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    rng = np.random.default_rng(7)  # identical data stream on every rank
    losses = []
    B = 8
    xb0 = rng.standard_normal((B, 16)).astype(np.float32)
    yb0 = rng.standard_normal((B, 4)).astype(np.float32)
    for step in range(4):
        xb, yb = xb0, yb0  # fixed batch: loss must strictly decrease
        lo, hi = rank * B // world, (rank + 1) * B // world
        x = paddle.to_tensor(xb[lo:hi])
        y = paddle.to_tensor(yb[lo:hi])
        loss = paddle.nn.functional.mse_loss(model(x), y)
        loss.backward()   # DP hook syncs grads here
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))

    out = os.environ["TEST_OUT_DIR"]
    with open(os.path.join(out, f"loss_rank{rank}.json"), "w") as f:
        json.dump(losses, f)
""")


def _run(tmp_path, nproc):
    script = tmp_path / "trainer.py"
    script.write_text(TRAINER)
    out = tmp_path / f"np{nproc}"
    out.mkdir()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["TEST_OUT_DIR"] = str(out)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_GLOBAL_RANK", None)
    env.pop("PADDLE_WORLD_SIZE", None)
    if nproc == 1:
        proc = subprocess.run([sys.executable, str(script)],
                              cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=240)
    else:
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", str(nproc), str(script)],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=240)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    losses = []
    for r in range(nproc):
        p = out / f"loss_rank{r}.json"
        assert p.exists(), f"rank {r} wrote no losses: {proc.stdout}\n{proc.stderr}"
        losses.append(json.loads(p.read_text()))
    return np.asarray(losses)  # (nproc, steps)


def test_dp_two_proc_loss_parity(tmp_path):
    single = _run(tmp_path, 1)[0]           # (steps,)
    two = _run(tmp_path, 2)                 # (2, steps)
    # mean of the per-rank half-batch losses == full-batch loss, per step,
    # IF the gradient averaging keeps the parameter trajectories identical
    np.testing.assert_allclose(two.mean(axis=0), single, rtol=1e-5,
                               atol=1e-6)
    # and training must actually progress
    assert single[-1] < single[0]


SPARSE_TRAINER = textwrap.dedent("""
    import json
    import os
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")

    rank = int(os.environ.get("PADDLE_GLOBAL_RANK", "0"))
    world = int(os.environ.get("PADDLE_WORLD_SIZE", "1"))
    if world > 1:
        host, port = os.environ["PADDLE_MASTER"].split(":")
        os.environ["PADDLE_MASTER"] = f"{host}:{int(port) + 43}"

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    dist.init_parallel_env()
    fleet.init(is_collective=True)

    paddle.seed(21)
    emb = paddle.nn.Embedding(16, 8, sparse=True)
    head = paddle.nn.Linear(8, 1)
    model = paddle.nn.Sequential(emb, head)
    model = fleet.distributed_model(model)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    rng = np.random.default_rng(3)
    B = 8
    ids0 = rng.integers(0, 16, B).astype(np.int64)
    y0 = rng.standard_normal((B, 1)).astype(np.float32)
    losses = []
    for step in range(4):
        lo, hi = rank * B // world, (rank + 1) * B // world
        x = paddle.to_tensor(ids0[lo:hi])
        y = paddle.to_tensor(y0[lo:hi])
        loss = paddle.nn.functional.mse_loss(model(x), y)
        loss.backward()   # sparse grad must sync across ranks here
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))

    out = os.environ["TEST_OUT_DIR"]
    with open(os.path.join(out, f"loss_rank{rank}.json"), "w") as f:
        json.dump(losses, f)
""")


def test_sparse_embedding_dp_parity(tmp_path):
    """SelectedRows grads must sync across DP ranks (allgather-average) —
    a silently-unsynced sparse embedding diverges per rank and fails the
    loss-parity identity."""
    def run(nproc):
        script = tmp_path / "sparse_trainer.py"
        script.write_text(SPARSE_TRAINER)
        out = tmp_path / f"sp{nproc}"
        out.mkdir()
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        env["TEST_OUT_DIR"] = str(out)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PADDLE_GLOBAL_RANK", None)
        env.pop("PADDLE_WORLD_SIZE", None)
        if nproc == 1:
            proc = subprocess.run([sys.executable, str(script)],
                                  cwd=REPO, env=env,
                                  capture_output=True, text=True,
                                  timeout=240)
        else:
            proc = subprocess.run(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--nproc_per_node", str(nproc), str(script)],
                cwd=REPO, env=env, capture_output=True, text=True,
                timeout=240)
        assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
        return np.asarray([
            json.loads((out / f"loss_rank{r}.json").read_text())
            for r in range(nproc)])

    single = run(1)[0]
    two = run(2)
    np.testing.assert_allclose(two.mean(axis=0), single, rtol=1e-4,
                               atol=1e-6)
    assert single[-1] < single[0]
