"""Elastic scale e2e: membership change -> pod relaunch with rewritten
rank envs -> resume from auto-checkpoint.

~ reference elastic/manager.py:34 (--np min:max) + :130 (rank-env rewrite
on scale events). A pod launched with ``--np 1:2`` trains while a second
node joins the TCPStore membership registry (scale UP: trainers relaunch
with PADDLE_WORLD_SIZE=2) and later dies (heartbeat stops -> scale DOWN:
back to world 1). Training progress rides the auto-checkpoint across every
relaunch. Collective execution across the processes is covered separately
by test_multihost_mesh.py; this test validates the launcher's elastic
contract: watch -> terminate -> env rewrite -> relaunch -> resume.
"""
import pytest

pytestmark = pytest.mark.slow  # multi-process/e2e: full-suite lane only
import json
import os
import subprocess
import sys
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAINER = textwrap.dedent("""
    import json
    import os
    import sys
    import time
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.incubate.checkpoint import train_epoch_range

    out_dir = os.environ["TEST_OUT_DIR"]
    paddle.seed(5)
    m = nn.Linear(8, 2)
    opt = paddle.optimizer.Adam(parameters=m.parameters(),
                                learning_rate=0.05)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))

    log_path = os.path.join(out_dir, "epochs.jsonl")
    for epoch in train_epoch_range(28, model=m, optimizer=opt):
        loss = (m(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        with open(log_path, "a") as f:
            f.write(json.dumps({
                "epoch": epoch, "pid": os.getpid(),
                "world": int(os.environ["PADDLE_WORLD_SIZE"]),
                "rank": int(os.environ["PADDLE_GLOBAL_RANK"]),
            }) + "\\n")
        time.sleep(0.7)
""")

# a second "node": registers in the membership store, heartbeats for a
# while, then exits abruptly (no deregistration — death is detected by
# heartbeat expiry, like a real node failure)
PEER = textwrap.dedent("""
    import os
    import sys
    import time
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.distributed.fleet.elastic import ElasticManager

    store = TCPStore("127.0.0.1", int(sys.argv[1]), is_master=False)
    mgr = ElasticManager(store, "zz-nodeB", (1, 2),
                         heartbeat_interval=0.5, dead_after=3.0)
    mgr.start()
    time.sleep(float(sys.argv[2]))
    os._exit(0)
""")


@pytest.mark.dist_retry(n=2)
def test_scale_up_down_relaunch_resume(tmp_path):
    # n=2: the 0.5s-heartbeat/3s-dead-after membership loop is the most
    # load-sensitive e2e in the suite — observed failing (twice in a
    # row) only when a full parallel pytest run shared this 1-core host
    script = tmp_path / "trainer.py"
    script.write_text(TRAINER)
    peer = tmp_path / "peer.py"
    peer.write_text(PEER)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["TEST_OUT_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_AUTO_CHECKPOINT_DIR"] = str(tmp_path / "ckpt")
    env["PADDLE_JOB_ID"] = "elastic_scale_job"
    master_port = 34815
    pod = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{master_port}",
         "--nproc_per_node", "1", "--elastic_level", "1",
         "--np", "1:2", "--elastic_node_id", "aa-nodeA", str(script)],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        log = tmp_path / "epochs.jsonl"
        deadline = time.time() + 60
        while time.time() < deadline:
            if log.exists() and len(log.read_text().splitlines()) >= 2:
                break
            time.sleep(0.3)
        assert log.exists(), "trainer never produced epochs"

        # scale UP: nodeB joins the membership store; kill it only once
        # the relaunched world-2 trainer has actually logged an epoch
        # (event-driven, not sleep-tuned — this host has one CPU core and
        # relaunch latency varies with load)
        peer_proc = subprocess.Popen(
            [sys.executable, str(peer), str(master_port + 7), "120.0"],
            cwd=REPO, env=env)
        try:
            deadline = time.time() + 90
            while time.time() < deadline:
                lines = [json.loads(ln) for ln in
                         log.read_text().splitlines()]
                if any(ln["world"] == 2 for ln in lines):
                    break
                time.sleep(0.4)
            else:
                raise AssertionError("never observed a world=2 epoch")
        finally:
            peer_proc.kill()  # abrupt death -> heartbeat expiry

        # scale DOWN is as load-sensitive as scale UP: wait (event-driven)
        # for the post-death world=1 relaunch to log an epoch before the
        # trainer's epoch budget can run out at world=2 — the failure
        # mode observed under a full parallel suite on this 1-core host
        deadline = time.time() + 90
        while time.time() < deadline:
            lines = [json.loads(ln) for ln in log.read_text().splitlines()]
            after_up = lines[max(i for i, ln in enumerate(lines)
                                 if ln["world"] == 2):]
            if any(ln["world"] == 1 for ln in after_up):
                break
            if pod.poll() is not None:
                break  # pod already finished; asserts below judge the log
            time.sleep(0.4)

        out, err = pod.communicate(timeout=180)
        assert pod.returncode == 0, out + "\n" + err
    finally:
        if pod.poll() is None:
            pod.kill()

    lines = [json.loads(ln) for ln in
             (tmp_path / "epochs.jsonl").read_text().splitlines()]
    worlds = [ln["world"] for ln in lines]
    epochs = [ln["epoch"] for ln in lines]
    pids = {ln["pid"] for ln in lines}
    assert "elastic scale" in err, err
    # membership changes rewrote the world size: 1 -> 2 (join) -> 1 (death)
    assert 2 in worlds, f"never scaled up: {worlds}"
    assert worlds[0] == 1 and worlds[-1] == 1, worlds
    assert len(pids) >= 3, "expected a relaunch per scale event"
    # auto-checkpoint resume: epochs never regress by more than the one
    # in-flight epoch, and the run completes all 28
    for a, b in zip(epochs, epochs[1:]):
        assert b >= a - 1, f"lost progress: {epochs}"
    assert epochs[-1] == 27, epochs
    # rank stays the sorted-membership index of nodeA ("aa-" < "zz-")
    assert all(ln["rank"] == 0 for ln in lines)
