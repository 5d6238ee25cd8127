"""Multi-process eager pipeline parallelism parity.

~ reference test strategy for PP (unittests launched via the launcher,
SURVEY.md §4): 2 stage processes, each building only ITS PipelineLayer
segment, exchanging activations/grads over TCPStore p2p in 1F1B order —
loss trajectory must match the single-process full-model run exactly.
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

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers import (
        pp_layers as PPL)
    LayerDesc, PipelineLayer = PPL.LayerDesc, PPL.PipelineLayer

    world = int(os.environ.get("PADDLE_WORLD_SIZE", "1"))
    rank = int(os.environ.get("PADDLE_GLOBAL_RANK", "0"))

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": world}
    strategy.pipeline_configs = {"micro_batch_size": 4,
                                 "accumulate_steps": 2}
    fleet.init(is_collective=True, strategy=strategy)

    def loss_fn(out, label):
        return paddle.nn.functional.mse_loss(out, label)

    paddle.seed(123)  # same init everywhere; each rank keeps its segment
    descs = [LayerDesc(nn.Linear, 16, 32),
             LayerDesc(nn.Tanh),
             LayerDesc(nn.Linear, 32, 32),
             LayerDesc(nn.Tanh),
             LayerDesc(nn.Linear, 32, 4)]
    model = PipelineLayer(descs, num_stages=world, loss_fn=loss_fn)
    model = fleet.distributed_model(model)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=model.parameters())
    opt = fleet.distributed_optimizer(opt)

    rng = np.random.default_rng(5)
    x = paddle.to_tensor(rng.standard_normal((8, 16)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
    losses = []
    for step in range(4):
        if world > 1:
            loss = model.train_batch((x, y), opt)
        else:
            # single-process oracle: same micro-batching, full stack
            n = 2
            total = 0.0
            for i in range(n):
                xm = x[i * 4:(i + 1) * 4]
                ym = y[i * 4:(i + 1) * 4]
                out = model.forward_full(xm)
                l = loss_fn(out, ym) * (1.0 / n)
                l.backward()
                total += float(l.numpy()) * n
            opt.step()
            opt.clear_grad()
            loss = total / n
        losses.append(float(loss if isinstance(loss, float)
                            else loss.numpy()))

    out_dir = os.environ["TEST_OUT_DIR"]
    with open(os.path.join(out_dir, f"pp_rank{rank}.json"), "w") as f:
        json.dump(losses, f)
""")


def _run(tmp_path, nproc):
    script = tmp_path / "pp_trainer.py"
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
    losses = {}
    for r in range(nproc):
        p = out / f"pp_rank{r}.json"
        assert p.exists(), f"rank {r} wrote nothing: {proc.stdout}\n{proc.stderr}"
        losses[r] = json.loads(p.read_text())
    return losses


@pytest.mark.dist_retry(n=1)
def test_pp_two_stage_loss_parity(tmp_path):
    single = np.asarray(_run(tmp_path, 1)[0])
    multi = _run(tmp_path, 2)
    # every stage reports the broadcast final loss; both must equal the
    # single-process oracle per step
    np.testing.assert_allclose(np.asarray(multi[0]), single, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(multi[1]), single, rtol=1e-5,
                               atol=1e-6)
    assert single[-1] < single[0]
