"""Heterogeneous staged PS trainer + PS concurrency.

~ heter_pipeline_trainer.cc (CPU section colocated with the PS streams
micro-batches to an accelerator section over a stage channel) and the
brpc PS service's many-workers contract (one handler thread per
connection, table/memory_sparse_table.cc).
"""
import pytest

pytestmark = pytest.mark.slow  # multi-process/e2e: full-suite lane only
import json
import os
import socket
import subprocess
import sys
import textwrap
import threading

import numpy as np

from paddle_tpu.distributed.ps import PSClient, PSServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


HETER_WORKER = textwrap.dedent("""
    import json
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.distributed.fleet.heter import HeterSection, StageChannel

    port, out_path = int(sys.argv[1]), sys.argv[2]
    ch = StageChannel(port=port, listen=True)

    # dense stage: pooled embedding rows -> linear head, MSE loss; the
    # whole step is ONE jitted function returning updated params + the
    # gradient w.r.t. the embedding rows (sent back for the sparse push)
    def loss_fn(params, rows, labels):
        w, b = params
        pooled = rows.reshape(labels.shape[0], -1, rows.shape[-1]).mean(1)
        pred = pooled @ w + b
        return jnp.mean((pred - labels) ** 2)

    @jax.jit
    def train_step_inner(params, rows, labels):
        def wrapped(p, r):
            return loss_fn(p, r, labels)
        loss = wrapped(params, rows)
        gp, gr = jax.grad(wrapped, argnums=(0, 1))(params, rows)
        new_params = [p - 0.1 * g for p, g in zip(params, gp)]
        return new_params, loss, gr

    def train_step(params, rows, dense_x, labels):
        rows = jnp.asarray(rows)
        labels = jnp.asarray(labels)
        return train_step_inner(params, rows, labels)

    rng = np.random.default_rng(3)
    params = [jnp.asarray(rng.standard_normal((8, 1)) * 0.1, jnp.float32),
              jnp.zeros((1,), jnp.float32)]
    section = HeterSection(ch, train_step, params)
    steps = section.serve()
    with open(out_path, "w") as f:
        json.dump({"steps": steps}, f)
""")

CPU_WORKER = textwrap.dedent("""
    import json
    import sys
    import time
    import numpy as np
    from paddle_tpu.distributed.fleet.heter import CpuSection, StageChannel
    from paddle_tpu.distributed.ps import PSClient

    ps_port, stage_port, out_path = (int(sys.argv[1]), int(sys.argv[2]),
                                     sys.argv[3])
    ps = PSClient(server_addr=f"127.0.0.1:{ps_port}")
    deadline = time.time() + 30
    ch = None
    while ch is None:
        try:
            ch = StageChannel(port=stage_port)
        except OSError:
            if time.time() > deadline:
                raise
            time.sleep(0.2)
    sec = CpuSection(ps, ch, window=2)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, size=(16, 4, 3))       # 16 batches x B4 x 3
    labels = (ids.mean(-1) * 0.01).astype(np.float32)[..., None]

    epoch_losses = []
    for epoch in range(4):
        losses = sec.run_epoch(
            (ids[i].reshape(-1), None, labels[i]) for i in range(16))
        epoch_losses.append(float(np.mean(losses)))
    sec.finish()
    with open(out_path, "w") as f:
        json.dump({"epoch_losses": epoch_losses,
                   "table_size": int(ps.table_size())}, f)
    ps.close()
""")


@pytest.mark.dist_retry(n=1)
def test_heter_pipeline_three_processes(tmp_path):
    server = PSServer(port=0)
    server.add_sparse_table(0, dim=8, lr=0.05, rule="adagrad")
    stage_port = _free_port()
    heter_out = tmp_path / "heter.json"
    cpu_out = tmp_path / "cpu.json"
    hw = tmp_path / "heter_worker.py"
    hw.write_text(HETER_WORKER)
    cw = tmp_path / "cpu_worker.py"
    cw.write_text(CPU_WORKER)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO
    env.pop("XLA_FLAGS", None)
    try:
        heter = subprocess.Popen(
            [sys.executable, str(hw), str(stage_port), str(heter_out)],
            cwd=REPO, env=env)
        cpu = subprocess.Popen(
            [sys.executable, str(cw), str(server.port), str(stage_port),
             str(cpu_out)],
            cwd=REPO, env=env)
        assert cpu.wait(timeout=180) == 0
        assert heter.wait(timeout=60) == 0
    finally:
        for p in (heter, cpu):
            if p.poll() is None:
                p.kill()
        server.stop()

    hres = json.loads(heter_out.read_text())
    cres = json.loads(cpu_out.read_text())
    assert hres["steps"] == 4 * 16
    losses = cres["epoch_losses"]
    assert losses[-1] < losses[0] * 0.7, losses
    assert cres["table_size"] > 0  # sparse rows created + updated on the PS


@pytest.mark.dist_retry(n=1)
def test_ps_concurrent_trainers_large_table():
    """Many trainer connections hammering one sparse table concurrently
    (~ the brpc server's one-thread-per-worker contract); rows must stay
    finite and every worker's pushes must land."""
    server = PSServer(port=0)
    table = server.add_sparse_table(0, dim=32, lr=0.01, rule="adagrad")
    n_workers, n_iters = 4, 30
    errs = []

    def worker(widx):
        try:
            c = PSClient(server_addr=f"127.0.0.1:{server.port}")
            rng = np.random.default_rng(widx)
            for i in range(n_iters):
                # overlapping id ranges force rule-state contention
                ids = rng.integers(0, 5000, size=256)
                rows = c.pull_sparse(ids)
                assert rows.shape == (256, 32)
                c.push_sparse(ids, 0.01 * rng.standard_normal(rows.shape))
            c.close()
        except Exception as e:  # noqa: BLE001 — surfaced in main thread
            errs.append((widx, repr(e)))

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    server.stop()
    assert not errs, errs
    assert table.size() > 1000
    vals = np.stack(list(table._rows.values()))
    assert np.isfinite(vals).all()


class TestSSDSparseTable:
    """Disk-backed sparse table (~ ssd_sparse_table.cc with sqlite in the
    rocksdb role): rows must survive LRU eviction round trips bit-exact,
    the memory budget must hold, and the RPC path must serve it."""

    @pytest.mark.dist_retry(n=1)
    def test_eviction_roundtrip_matches_in_memory_oracle(self, tmp_path):
        from paddle_tpu.distributed.ps import SparseTable, SSDSparseTable
        oracle = SparseTable(dim=8, lr=0.05, rule="adagrad", seed=3)
        ssd = SSDSparseTable(dim=8, path=str(tmp_path / "t.db"),
                             mem_rows=16, lr=0.05, rule="adagrad", seed=3)
        rng = np.random.RandomState(0)
        for it in range(6):
            # 200 ids over a 500-key space with a 16-row budget: every
            # iteration faults most rows through disk
            ids = rng.randint(0, 500, size=200)
            a = oracle.pull(ids)
            b = ssd.pull(ids)
            np.testing.assert_array_equal(a, b)
            g = rng.randn(200, 8).astype(np.float32) * 0.1
            oracle.push(ids, g)
            ssd.push(ids, g)
            assert len(ssd._rows) <= 16
        ids = np.arange(500)
        np.testing.assert_allclose(oracle.pull(ids), ssd.pull(ids),
                                   rtol=1e-6)
        assert ssd.size() == oracle.size() == 500

    @pytest.mark.dist_retry(n=1)
    def test_save_load_and_rpc(self, tmp_path):
        from paddle_tpu.distributed.ps import (PSClient, PSServer,
                                               SparseTable)
        server = PSServer(port=0)
        server.add_ssd_sparse_table(0, dim=4, path=str(tmp_path / "s.db"),
                                    mem_rows=8, lr=0.1)
        c = PSClient(server_addr=f"127.0.0.1:{server.port}")
        ids = np.arange(64)
        rows = c.pull_sparse(ids)
        c.push_sparse(ids, np.ones((64, 4), np.float32))
        after = c.pull_sparse(ids)
        np.testing.assert_allclose(after, rows - 0.1, atol=1e-6)
        assert c.table_size() == 64
        c.save(str(tmp_path / "snap.pkl"))
        c.close()
        server.stop()
        # snapshot loads into a plain in-memory table (same wire format)
        t2 = SparseTable(dim=4)
        t2.load(str(tmp_path / "snap.pkl"))
        np.testing.assert_allclose(t2.pull(ids), after, atol=1e-6)

    @pytest.mark.dist_retry(n=1)
    def test_load_replaces_disk_state(self, tmp_path):
        # regression: stale pre-load rows must not resurrect from disk
        from paddle_tpu.distributed.ps import SSDSparseTable
        t = SSDSparseTable(dim=4, path=str(tmp_path / "r.db"), mem_rows=8,
                           lr=0.1, seed=0)
        t.pull(np.arange(100))  # 92 rows evicted to disk
        t.push(np.arange(100), np.ones((100, 4), np.float32))
        snap = SSDSparseTable(dim=4, path=str(tmp_path / "r2.db"),
                              mem_rows=8, lr=0.1, seed=1)
        snap.pull(np.arange(10))
        snap.save(str(tmp_path / "snap.pkl"))
        t.load(str(tmp_path / "snap.pkl"))
        assert t.size() == 10
        assert len(t._rows) <= 8  # budget holds after load
        np.testing.assert_array_equal(t.pull([3]), snap.pull([3]))
