"""Rehearsal compiles of the cells' kernels at the cells' shapes for
``v5e:2x2`` with the real compiler and no chip, so that a later PR sees a
shape that no longer fits without chip time.  The topology is described
inside a fixture, in this one file of the benchmark's tests (the repo's
own ``tests/test_chip_aot.py`` does the same for the smoke shapes; this PR
may add no file there).  Whole step programs are in the slow lane.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r}")
    return topo.devices


@pytest.fixture(scope="module")
def one(v5e):
    return SingleDeviceSharding(v5e[0])


def _cell(name):
    from benchmark.harness.spec import Spec
    return Spec().cell(name)


def _compile(fn, *args):
    from paddle_tpu.ops.pallas.lowering import lower_for_chip
    with lower_for_chip():
        return jax.jit(fn).lower(*args).compile()


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def test_gqa_flash_forward_and_backward_at_the_train_cells_shape(one):
    from paddle_tpu.ops.pallas.flash_attention_gqa import grouped_flash_attention
    cell = _cell("train_s4096")
    m, job = cell["config_spec"]["model"], cell["traffic_spec"]
    q = jax.ShapeDtypeStruct((job["batch"], m["num_attention_heads"], job["seq"],
                              m["head_dim"]), BF16, sharding=one)
    kv = jax.ShapeDtypeStruct((job["batch"], m["num_key_value_heads"], job["seq"],
                               m["head_dim"]), BF16, sharding=one)

    def loss(q, k, v):
        out = grouped_flash_attention(q, k, v, True, m["head_dim"] ** -0.5)
        return jnp.sum(out.astype(jnp.float32))
    assert _mosaic_calls(_compile(jax.grad(loss, (0, 1, 2)), q, kv, kv)) == 3


def test_paged_decode_kernel_at_the_serve_cells_shape(one):
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    cell = _cell("serve_chat_steady")
    m, eng = cell["config_spec"]["model"], cell["config_spec"]["engine"]
    width = eng["max_len"] // eng["page_size"]
    pool = jax.ShapeDtypeStruct((m["num_key_value_heads"], eng["n_pool_pages"],
                                 eng["page_size"], m["head_dim"]), BF16, sharding=one)
    c = _compile(paged_attention,
                 jax.ShapeDtypeStruct((eng["slots"], m["num_attention_heads"], m["head_dim"]),
                                      BF16, sharding=one),
                 pool, pool,
                 jax.ShapeDtypeStruct((eng["slots"], width), jnp.int32, sharding=one),
                 jax.ShapeDtypeStruct((eng["slots"],), jnp.int32, sharding=one))
    assert _mosaic_calls(c) == 1


def test_fused_loss_at_the_train_cells_shape(one):
    from paddle_tpu.ops.pallas.fused_ce import causal_lm_loss
    cell = _cell("train_s4096")
    m, job = cell["config_spec"]["model"], cell["traffic_spec"]
    c = _compile(jax.grad(causal_lm_loss),
                 jax.ShapeDtypeStruct((job["batch"], job["seq"], m["vocab_size"]), BF16,
                                      sharding=one),
                 jax.ShapeDtypeStruct((job["batch"], job["seq"]), jnp.int32, sharding=one))
    assert _mosaic_calls(c) == 2
