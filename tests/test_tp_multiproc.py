"""Multi-process tensor-parallel training parity.

~ reference hybrid TP tests (test_parallel_dygraph_mp_layers.py over
spawned ranks): a 2-process mesh shards a 2-layer MLP column/row-wise
over the 'model' axis (GSPMD inserts the mp allreduce the reference's
RowParallelLinear does by hand); per-step losses must match the dense
single-process oracle.
"""
import pytest

pytestmark = pytest.mark.slow  # multi-process/e2e: full-suite lane only
import json
import os
import subprocess
import sys
import textwrap

import numpy as np

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
        host, port = os.environ["PADDLE_MASTER"].split(":")
        os.environ["PADDLE_MASTER"] = f"{host}:{int(port) + 37}"

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    n_dev = world if world > 1 else 1
    devs = np.asarray(jax.devices()[:n_dev])
    mesh = Mesh(devs, ("model",))

    # identical init everywhere
    rng = np.random.default_rng(11)
    W1 = jnp.asarray(rng.standard_normal((16, 32)) * 0.1, jnp.float32)
    W2 = jnp.asarray(rng.standard_normal((32, 4)) * 0.1, jnp.float32)
    # Megatron layout: W1 column-sharded, W2 row-sharded over 'model'
    W1 = jax.device_put(W1, NamedSharding(mesh, P(None, "model")))
    W2 = jax.device_put(W2, NamedSharding(mesh, P("model", None)))
    repl = NamedSharding(mesh, P())

    def loss_fn(params, x, y):
        W1, W2 = params
        h = jax.nn.relu(x @ W1)
        pred = h @ W2      # GSPMD inserts the row-parallel allreduce
        return jnp.mean((pred - y) ** 2)

    @jax.jit
    def step(params, x, y):
        l, g = jax.value_and_grad(loss_fn)(params, x, y)
        return l, [p - 0.1 * gp for p, gp in zip(params, g)]

    params = [W1, W2]
    data = np.random.default_rng(5)
    x = jax.device_put(jnp.asarray(
        data.standard_normal((8, 16)), jnp.float32), repl)
    y = jax.device_put(jnp.asarray(
        data.standard_normal((8, 4)), jnp.float32), repl)
    losses = []
    for _ in range(4):
        l, params = step(params, x, y)
        losses.append(float(np.asarray(jax.device_get(l))))

    out = os.environ["TEST_OUT_DIR"]
    with open(os.path.join(out, f"loss_rank{rank}.json"), "w") as f:
        json.dump(losses, f)
""")


def _run(tmp_path, nproc):
    script = tmp_path / "tp_trainer.py"
    script.write_text(TRAINER)
    out = tmp_path / f"np{nproc}"
    out.mkdir()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["TEST_OUT_DIR"] = str(out)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_GLOBAL_RANK", None)
    env.pop("PADDLE_WORLD_SIZE", None)
    env.pop("XLA_FLAGS", None)  # exactly one local CPU device per process
    if nproc == 1:
        proc = subprocess.run([sys.executable, str(script)],
                              cwd=REPO, env=env,
                              capture_output=True, text=True, timeout=300)
    else:
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", str(nproc), str(script)],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    losses = []
    for r in range(nproc):
        p = out / f"loss_rank{r}.json"
        assert p.exists(), \
            f"rank {r} wrote no losses: {proc.stdout}\n{proc.stderr}"
        losses.append(json.loads(p.read_text()))
    return np.asarray(losses)


@pytest.mark.dist_retry(n=1)
def test_tp_two_proc_loss_parity(tmp_path):
    single = _run(tmp_path, 1)[0]
    two = _run(tmp_path, 2)
    # every rank sees the replicated global loss; must equal the dense
    # single-process trajectory step for step
    np.testing.assert_allclose(two[0], two[1], rtol=1e-6)
    np.testing.assert_allclose(two[0], single, rtol=1e-4, atol=1e-6)
    assert single[-1] < single[0]
