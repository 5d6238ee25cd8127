"""Multi-process compiled-mesh execution: ONE jitted hybrid-parallel train
step spanning processes.

~ reference test_dist_base.py:1327 (spawned-rank dist tests): 2 processes x
4 local CPU devices rendezvous via ``init_parallel_env`` (the launch CLI
provides the PADDLE_MASTER/rank env contract) into ONE global 8-device mesh
{'data':2,'sep':2,'model':2}, then run the REAL ``llama_train_step_factory``
program — the untested seam between the single-process virtual-mesh dryrun
and a real pod is exactly this cross-process GSPMD execution (the factory's
device_put of host params onto a partly non-addressable mesh, collectives
crossing the process boundary).

Losses must be identical on every rank (replicated output) and match the
single-process 8-virtual-device oracle step for step.
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
        # the launch master's TCPStore owns PADDLE_MASTER's port; the jax
        # coordinator needs its own
        host, port = os.environ["PADDLE_MASTER"].split(":")
        os.environ["PADDLE_MASTER"] = f"{host}:{int(port) + 53}"

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    assert jax.process_count() == world or world == 1
    devs = np.asarray(jax.devices()[:8])
    mesh = Mesh(devs.reshape(2, 2, 2), ("data", "sep", "model"))

    paddle.seed(0)
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama import llama_train_step_factory
    cfg = LlamaConfig.tiny(vocab=256, hidden=64, layers=2, heads=4,
                           kv_heads=2)
    model = LlamaForCausalLM(cfg)
    params, opt_state, step, _ = llama_train_step_factory(
        model, mesh, learning_rate=1e-3, remat=True)

    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(np.asarray(jax.device_get(loss))))

    out = os.environ["TEST_OUT_DIR"]
    with open(os.path.join(out, f"loss_rank{rank}.json"), "w") as f:
        json.dump(losses, f)
""")


def _trainer_env(out_dir, n_local_devices):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["TEST_OUT_DIR"] = str(out_dir)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_GLOBAL_RANK", None)
    env.pop("PADDLE_WORLD_SIZE", None)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_local_devices}"
    return env


def _run(tmp_path, nproc):
    script = tmp_path / "mesh_trainer.py"
    script.write_text(TRAINER)
    out = tmp_path / f"np{nproc}"
    out.mkdir()
    # every process contributes 8//nproc local devices to the global mesh
    env = _trainer_env(out, 8 // nproc)
    if nproc == 1:
        proc = subprocess.run([sys.executable, str(script)],
                              cwd=REPO, env=env,
                              capture_output=True, text=True, timeout=600)
    else:
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", str(nproc), str(script)],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    losses = []
    for r in range(nproc):
        p = out / f"loss_rank{r}.json"
        assert p.exists(), \
            f"rank {r} wrote no losses: {proc.stdout}\n{proc.stderr}"
        losses.append(json.loads(p.read_text()))
    return np.asarray(losses)


@pytest.mark.dist_retry(n=1)
def test_two_process_global_mesh_train_step(tmp_path):
    single = _run(tmp_path, 1)[0]
    two = _run(tmp_path, 2)
    np.testing.assert_allclose(two[0], two[1], rtol=1e-6)
    np.testing.assert_allclose(two[0], single, rtol=1e-4, atol=1e-6)
    assert single[-1] < single[0], "loss did not decrease"


@pytest.mark.dist_retry(n=1)
def test_two_node_launch_httpmaster_rendezvous(tmp_path):
    """The --nnodes > 1 path: two launch pods (node_rank 0/1) rendezvous
    through HTTPMaster.sync_peers, each contributing one trainer to ONE
    jax.distributed global mesh (~ the reference's multi-node launch
    contract, launch/controllers/collective.py + controllers/master.py).
    """
    import subprocess
    import time as _time
    script = tmp_path / "mesh_trainer.py"
    src = TRAINER.replace("jax.devices()[:8]", "jax.devices()[:2]") \
                 .replace("devs.reshape(2, 2, 2)", "devs.reshape(1, 1, 2)")
    assert "reshape(1, 1, 2)" in src
    script.write_text(src)
    out = tmp_path / "nodes"
    out.mkdir()
    env = _trainer_env(out, 1)
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    master = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    pods = []
    try:
        for nr in (0, 1):
            pods.append(subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--master", master, "--nnodes", "2",
                 "--node_rank", str(nr),
                 "--nproc_per_node", "1", str(script)],
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True))
            _time.sleep(0.5)  # node 0 binds the HTTP master first
        outs = [p.communicate(timeout=600) for p in pods]
    finally:
        for p in pods:
            if p.poll() is None:
                p.kill()
    for p, (so, se) in zip(pods, outs):
        assert p.returncode == 0, so + "\n" + se
    losses = []
    for r in range(2):
        f = out / f"loss_rank{r}.json"
        assert f.exists(), (outs[0][0], outs[0][1], outs[1][1])
        losses.append(json.loads(f.read_text()))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    assert losses[0][-1] < losses[0][0]
