"""Runtime reshard (VERDICT r3 item 7a): live-array layout moves.

~ auto_parallel/reshard.py:603 Resharder — here GSPMD emits the
collectives. Single-process cases run on the 8-virtual-device CPU mesh;
the cross-process case spawns a 2-process jax.distributed global mesh
(the test_multihost_mesh.py pattern) and reshards a global array from
row-shard to replicated, checking every process's addressable shards.
"""
import pytest

pytestmark = pytest.mark.slow  # multi-process/e2e: full-suite lane only
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed import reshard, reshard_like

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _devs(n):
    return np.asarray(jax.devices()[:n])


def test_same_mesh_respec():
    mesh = Mesh(_devs(8), ("x",))
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    a = jax.device_put(x, NamedSharding(mesh, P("x", None)))
    b = reshard(a, mesh, P(None, "x"))
    assert b.sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, "x")), b.ndim)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(x))


def test_cross_mesh_move():
    m1 = Mesh(_devs(8), ("x",))
    m2 = Mesh(_devs(8).reshape(2, 4), ("a", "b"))
    x = jnp.arange(8 * 12, dtype=jnp.float32).reshape(8, 12)
    a = jax.device_put(x, NamedSharding(m1, P("x", None)))
    b = reshard(a, m2, P("a", "b"))
    assert b.sharding.mesh.axis_names == ("a", "b")
    np.testing.assert_array_equal(np.asarray(b), np.asarray(x))
    # shard shape: (8/2, 12/4)
    assert b.addressable_shards[0].data.shape == (4, 3)


def test_reshard_tensor_wrapper_and_noop():
    mesh = Mesh(_devs(4), ("x",))
    t = paddle.to_tensor(
        np.arange(16, dtype=np.float32).reshape(4, 4))
    out = reshard(t, mesh, P("x", None))
    assert hasattr(out, "_value")
    want = NamedSharding(mesh, P("x", None))
    assert out._value.sharding.is_equivalent_to(want, 2)
    # already-there fast path returns the same object
    again = reshard(out, mesh, P("x", None))
    assert again is out


def test_reshard_like():
    mesh = Mesh(_devs(8), ("x",))
    ref = jax.device_put(jnp.zeros((8, 4)), NamedSharding(mesh, P("x")))
    x = jnp.ones((8, 4))
    out = reshard_like(x, ref)
    assert out.sharding.is_equivalent_to(ref.sharding, 2)


def test_reshard_under_jit_is_constraint():
    mesh = Mesh(_devs(8), ("x",))

    @jax.jit
    def f(a):
        with mesh:
            return reshard(a * 2, mesh, P("x", None))

    out = f(jnp.ones((8, 8)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((8, 8)))


_WORKER = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=sys.argv[1],
                           num_processes=2, process_id=int(sys.argv[2]))
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from paddle_tpu.distributed.reshard import reshard

devs = np.asarray(jax.devices())          # 4 per process = 8 global
mesh = Mesh(devs, ("x",))
rank = int(sys.argv[2])
# build a global row-sharded array from process-local shards
global_shape = (8, 8)
sharding = NamedSharding(mesh, P("x", None))
order = list(devs.flat)
local = [jax.device_put(
            np.full((1, 8), order.index(d), np.float32), d)
         for d in jax.local_devices()]
arr = jax.make_array_from_single_device_arrays(global_shape, sharding,
                                               local)
out = reshard(arr, mesh, P(None, "x"))    # row-shard -> col-shard
rows = {}
for s in out.addressable_shards:
    rows[str(s.index)] = np.asarray(s.data).tolist()
path = os.path.join(sys.argv[3], f"shards_{rank}.json")
with open(path, "w") as f:
    json.dump(rows, f)
"""


@pytest.mark.dist_retry(n=1)
def test_cross_process_reshard(tmp_path, free_port):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    addr = f"127.0.0.1:{free_port}"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_PLATFORMS", None)
    procs = [subprocess.Popen(
        [sys.executable, str(script), addr, str(r), str(tmp_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for r in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, out + "\n" + err

    # expected global array: row i is full of the owning device id
    # (process 0 owns rows 0-3 = ids 0-3, process 1 rows 4-7)
    want = np.repeat(np.arange(8, dtype=np.float32)[:, None], 8, axis=1)
    cols = {}
    for r in range(2):
        rows = json.loads((tmp_path / f"shards_{r}.json").read_text())
        for idx, data in rows.items():
            # idx like "(slice(None, None, None), slice(2, 3, None))"
            start = int(idx.split("slice(")[2].split(",")[0])
            cols[start] = np.asarray(data)
    # after the reshard every shard holds ALL 8 rows of its column strip
    assert len(cols) == 8, sorted(cols)
    full = np.concatenate([cols[c] for c in sorted(cols)], axis=1)
    np.testing.assert_array_equal(full, want)
