"""Fault injection: crash mid-training -> elastic relaunch -> resume.

~ the reference's failure story (SURVEY.md §5: launcher watches children,
ElasticManager relaunches, checkpoints ride fs) — which the reference
itself never tests end-to-end (its tests kill processes ad hoc). Here the
full loop runs: the trainer hard-crashes (os._exit(1)) at a chosen epoch,
the launch CLI's elastic watch relaunches the pod, and train_epoch_range
resumes from the last durable checkpoint, skipping completed epochs.
"""
import pytest

pytestmark = pytest.mark.slow  # multi-process/e2e: full-suite lane only
import json
import os
import subprocess
import sys
import textwrap

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
    from paddle_tpu.incubate.checkpoint import train_epoch_range

    out_dir = os.environ["TEST_OUT_DIR"]
    crash_at = int(os.environ.get("CRASH_AT_EPOCH", "-1"))

    paddle.seed(5)
    m = nn.Linear(8, 2)
    opt = paddle.optimizer.Adam(parameters=m.parameters(),
                                learning_rate=0.05)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))

    log_path = os.path.join(out_dir, "epochs.jsonl")
    for epoch in train_epoch_range(6, model=m, optimizer=opt):
        loss = (m(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        with open(log_path, "a") as f:
            f.write(json.dumps({"epoch": epoch, "pid": os.getpid(),
                                "loss": float(loss.numpy())}) + "\\n")
        if epoch == crash_at and not os.path.exists(
                os.path.join(out_dir, "crashed")):
            open(os.path.join(out_dir, "crashed"), "w").close()
            os._exit(1)  # hard crash: no cleanup, no final checkpoint
""")


def test_crash_relaunch_resume(tmp_path):
    script = tmp_path / "trainer.py"
    script.write_text(TRAINER)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["TEST_OUT_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_AUTO_CHECKPOINT_DIR"] = str(tmp_path / "ckpt")
    env["PADDLE_JOB_ID"] = "fault_job"
    env["CRASH_AT_EPOCH"] = "2"
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--elastic_level", "1",
         "--max_restart", "2", str(script)],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "elastic restart" in proc.stderr
    lines = [json.loads(ln) for ln in
             (tmp_path / "epochs.jsonl").read_text().splitlines()]
    epochs = [ln["epoch"] for ln in lines]
    pids = {ln["pid"] for ln in lines}
    # first life ran 0,1,2 then crashed AT the yield of epoch 2 (its
    # checkpoint never landed); the relaunched life re-runs 2..5
    assert epochs == [0, 1, 2, 2, 3, 4, 5], epochs
    assert len(pids) == 2  # two distinct trainer processes
    # state carried across the crash: epoch-2 rerun starts from the
    # epoch-1 checkpoint, so its loss matches the first attempt's
    first_e2 = [ln for ln in lines if ln["epoch"] == 2][0]
    second_e2 = [ln for ln in lines if ln["epoch"] == 2][1]
    assert abs(first_e2["loss"] - second_e2["loss"]) < 1e-6
    # and training progressed monotonically after resume
    assert lines[-1]["loss"] < lines[0]["loss"]
