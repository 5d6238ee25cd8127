"""Chip-less compile check: AOT-compile for the TPU v5e topology what
chip_smoke.py runs, from a host that has no chip.

On the CPU backend every Pallas kernel runs in interpret mode — ordinary
HLO — so the rest of the suite cannot see a Mosaic refusal (a kernel
under GSPMD, a VMEM overflow, an unsupported layout). Compiling against
``get_topology_desc("v5e:2x2")`` inside ``lower_for_chip()`` takes the
chip branches and runs the real Mosaic + XLA:TPU compilers. Nothing
executes: this proves "it compiles", not "it is right" — that is
chip_smoke.py's job on the chip.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import paddle_tpu as paddle
from paddle_tpu.jax_compat import make_mesh
from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas.lowering import lower_for_chip

pytestmark = pytest.mark.slow

BF16 = jnp.bfloat16
# chip_smoke.py's shapes: the 0.44B width, the engine's pool geometry
NH, HD, V, S = 12, 128, 32000, 2048
SLOTS, PAGE, WIDTH = 8, 64, 16
POOL = SLOTS * WIDTH + 1


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this host
        pytest.skip(f"no TPU compile-only topology here: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **jit_kw):
    with lower_for_chip():
        return jax.jit(fn, **jit_kw).lower(*args).compile()


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _full_width_model(kv_heads, layers=2):
    cfg = LlamaConfig(vocab_size=V, hidden_size=NH * HD,
                      intermediate_size=4096, num_hidden_layers=layers,
                      num_attention_heads=NH, num_key_value_heads=kv_heads,
                      max_position_embeddings=S, dtype=BF16)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    return model


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), tree)


def test_kernels_compile_at_smoke_shapes(v5e):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.flash_attention_gqa import (
        grouped_flash_attention)
    from paddle_tpu.ops.pallas.fused_ce import causal_lm_loss
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    one = SingleDeviceSharding(v5e[0])

    pool = _sds((NH, POOL, PAGE, HD), BF16, one)
    c = _compile(paged_attention, _sds((SLOTS, NH, HD), BF16, one), pool,
                 pool, _sds((SLOTS, WIDTH), jnp.int32, one),
                 _sds((SLOTS,), jnp.int32, one))
    assert _mosaic_calls(c) == 1

    for fn, kvh in ((flash_attention, NH), (grouped_flash_attention, 4)):
        def loss(q, k, v, fn=fn):
            return jnp.sum(fn(q, k, v, True, HD ** -0.5).astype(jnp.float32))
        q = _sds((2, NH, S, HD), BF16, one)
        kv = _sds((2, kvh, S, HD), BF16, one)
        c = _compile(jax.grad(loss, (0, 1, 2)), q, kv, kv)
        assert _mosaic_calls(c) == 3      # fwd, dq, dk/dv

    c = _compile(jax.grad(causal_lm_loss), _sds((2, S, V), BF16, one),
                 _sds((2, S), jnp.int32, one))
    assert _mosaic_calls(c) == 2          # fwd, bwd


def test_oversize_resident_flash_block_is_refused(v5e):
    """The negative control: this probe must be able to fail. Resident
    (non-streamed) K/V at S=16384 over more than one head does not fit
    the 16M scoped VMEM, and the chip's own compiler says so."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    one = SingleDeviceSharding(v5e[0])
    x = _sds((2, NH, 16384, HD), BF16, one)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(lambda q, k, v: flash_attention(
            q, k, v, True, None, 256, 256, stream=False), x, x, x)


def test_train_step_compiles_full_width(v5e):
    """bench.py's best row (GQA kv=4, bf16 moments, no remat) at full
    width, two layers deep, B=8 x S=2048."""
    from paddle_tpu.models.nlp.llama import llama_train_step_factory
    one = SingleDeviceSharding(v5e[0])
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with lower_for_chip():
        params, opt_state, step, _ = llama_train_step_factory(
            _full_width_model(kv_heads=4), mesh, remat=False,
            accum_dtype=BF16)
    tok = _sds((8, S), jnp.int32, one)
    # the factory's jit is bound to its (CPU) mesh shardings: compile the
    # same step function for the chip instead
    c = _compile(step.__wrapped__, _on(params, one), _on(opt_state, one),
                 tok, tok, donate_argnums=(0, 1))
    assert _mosaic_calls(c) == 3 * 2 + 2  # flash fwd/dq/dkdv per layer + CE


def test_paged_decode_step_compiles_full_width(v5e):
    from paddle_tpu.models.nlp.llama_decode import llama_paged_decode_factory
    one = SingleDeviceSharding(v5e[0])
    outer, layers, pools, _, decode_step, decode_n = \
        llama_paged_decode_factory(_full_width_model(kv_heads=NH),
                                   page_size=PAGE, n_pool_pages=POOL,
                                   chunked_prefill=PAGE)
    i32 = lambda *s: _sds(s, jnp.int32, one)  # noqa: E731
    args = (_on(outer, one), _on(layers, one), i32(SLOTS),
            i32(SLOTS, WIDTH), i32(SLOTS), _on(pools, one))
    with lower_for_chip():
        assert _mosaic_calls(decode_step.lower(*args).compile()) >= 1
        assert _mosaic_calls(decode_n.lower(*args, 8).compile()) >= 1


# serve_latent_moe_docqa's shapes: 32 slots x 97 pages of 64 positions,
# 16 heads against a latent page of 576 values padded to 640 columns,
# of which the first 512 are the values; a pool of 3617 pages x 7 layers
LATENT = dict(slots=32, table=97, page=64, heads=16, width=640, rank=512,
              pool=3617, layers=7)


@pytest.mark.parametrize("chunk,rows", [(1, LATENT["slots"]), (64, 1), (256, 1)])
def test_latent_paged_kernel_compiles_at_the_cells_shapes(v5e, chunk, rows):
    """Decode (chunk 1, every slot a row), a 64-token prefill chunk of one
    request and the lane's widest call (4 chunks: 4096 query rows, 21.2 MB
    of VMEM, which the launcher asks for), each against the whole pool
    addressed by (layer, page)."""
    from paddle_tpu.ops.pallas.latent_paged_attention import (
        latent_paged_attention, page_width)
    one = SingleDeviceSharding(v5e[0])
    g = LATENT
    assert page_width(576) == g["width"]
    args = (_sds((rows, g["heads"] * chunk, g["width"]), BF16, one),
            _sds((g["layers"], g["pool"], g["page"], g["width"]), BF16, one),
            _sds((rows, g["table"]), jnp.int32, one),
            _sds((rows,), jnp.int32, one), _sds((rows,), jnp.int32, one))
    c = _compile(lambda q, pool, pt, sl, st: latent_paged_attention(
        q, pool, 3, pt, sl, st, chunk, g["rank"], 192 ** -0.5), *args)
    assert _mosaic_calls(c) == 1


def test_latent_decode_program_keeps_the_pool_in_place(v5e):
    """The whole decode program of a 3-layer cut at the published widths:
    the pool is donated and aliased, and nothing of its size is made
    beside it (an unpadded 576-column pool was converted to another layout
    and back, two pool-sized temporaries a call)."""
    from paddle_tpu.models.nlp import deepseek_v3 as M
    one = SingleDeviceSharding(v5e[0])
    g = LATENT
    net = M.DeepseekV3ForCausalLM(M.DeepseekV3Config(num_hidden_layers=3))
    net.decode_params = lambda: (dict(net.outer),          # shapes in place of arrays
                                 [dict(lp) for lp in net.layers])
    outer, layers, _, _, _, decode_n = M.latent_paged_decode_factory(
        net, page_size=g["page"], n_pool_pages=3, chunked_prefill=g["page"])
    pool = _sds((3, g["pool"], g["page"], g["width"]), BF16, one)
    i32 = lambda *s: _sds(s, jnp.int32, one)  # noqa: E731
    with lower_for_chip():
        c = decode_n._jit_inner[0].lower(
            _on(outer, one), _on(layers, one), i32(g["slots"]),
            i32(g["slots"], g["table"]), i32(g["slots"]), pool, 1).compile()
    kernels = [ln for ln in c.as_text().splitlines()
               if "custom-call(" in ln and "%latent_paged_attention" in ln.split("=")[0]]
    assert len(kernels) == 3                                # one a layer
    stats = c.memory_analysis()
    pool_bytes = 3 * g["pool"] * g["page"] * g["width"] * 2
    assert stats.alias_size_in_bytes >= pool_bytes
    assert stats.temp_size_in_bytes < pool_bytes // 8


# the Mistral serving cells' geometry (serve_chat_steady, serve_prefill_burst):
# 16 slots x 66 pages of 64 positions, 8 KV heads x 128 under 32 query
# heads, a pool of 513 pages x 8 layers = 0.54 GB each for K and V
SERVE = dict(slots=16, table=66, page=64, heads=32, kv_heads=8, pool=513,
             layers=8, hidden=4096, inter=14336, vocab=32768)


@pytest.mark.parametrize("tp", [1, 4], ids=["one_chip", "tp4"])
@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_paged_walk_compiles_at_the_serve_cells_geometry(v5e, codec, tp):
    """The decode kernel's walk over the whole pools at a traced layer, at
    the cells' geometry: on one chip (all 8 kv heads a program) and under
    the four-chip ``shard_map`` (2 a device). The pools go in whole and
    stay in HBM (no operand of a pool's size is made beside them); an int8
    pool's scales are one layer's, sliced and padded to rows of 128 lanes
    outside the kernel."""
    from paddle_tpu.models.nlp.llama_decode import paged_kernel_call
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    g = SERVE
    mesh = Mesh(np.asarray(v5e[:tp]), ("tp",))
    ns = lambda *names: NamedSharding(mesh, P(*names))  # noqa: E731
    shape = (g["layers"], g["kv_heads"], g["pool"], g["page"])
    data = _sds(shape + (HD,), jnp.int8 if codec == "int8" else BF16,
                ns(None, "tp"))
    pool = (data, _sds(shape, jnp.float32, ns(None, "tp"))) \
        if codec == "int8" else data
    c = _compile(
        lambda q, kp, vp, pt, sl, i: paged_kernel_call(
            paged_attention, q, kp, vp, pt, sl, layer=i, mesh=mesh,
            axis="tp"),
        _sds((g["slots"], g["heads"], HD), BF16, ns(None, "tp")), pool, pool,
        _sds((g["slots"], g["table"]), jnp.int32, ns()),
        _sds((g["slots"],), jnp.int32, ns()), _sds((), jnp.int32, ns()))
    assert _mosaic_calls(c) == 1
    assert "%paged_attention" in c.as_text()        # the kernel's own name
    layer_bytes = int(np.prod(shape[1:])) * HD * data.dtype.itemsize // tp
    assert c.memory_analysis().temp_size_in_bytes < layer_bytes // 2


def test_llama_paged_programs_keep_the_pools_in_place(v5e):
    """``decode_n`` (n = 1) and ``_prefill_chunk`` at the serving cells'
    geometry: both pools are donated and aliased, nothing of a pool's or a
    layer's pages' size is made beside them, and the kernel is ONE call in
    the scanned layer body, fed the whole pools. (As a scan's xs / ys the
    pools were sliced a layer at a time and stacked into fresh ones: 1.48
    / 1.18 GB of temporaries and 21 / 19 pool-shaped results; with the kv
    heads a SLICE of the scatter XLA:TPU stored the pools heads-minor and
    converted all of them there and back, every layer.)"""
    from paddle_tpu.models.nlp.llama_decode import llama_paged_decode_factory
    one = SingleDeviceSharding(v5e[0])
    g = SERVE
    H, KV = g["hidden"], g["kv_heads"] * HD
    # the programs take their widths from their arguments: a one-layer
    # model of the cell's head geometry builds them, the cell's shapes
    # (8 layers, the real MLP and vocabulary) are what is compiled
    cfg = LlamaConfig(vocab_size=64, hidden_size=H, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=g["heads"],
                      num_key_value_heads=g["kv_heads"], rope_theta=1e6,
                      max_position_embeddings=g["table"] * g["page"],
                      dtype=BF16)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    outer, layers, _, prefill, _, decode_n = llama_paged_decode_factory(
        model, page_size=g["page"], n_pool_pages=3,
        chunked_prefill=g["page"])
    bf = lambda *s: _sds(s, BF16, one)  # noqa: E731
    i32 = lambda *s: _sds(s, jnp.int32, one)  # noqa: E731
    L, I, V = g["layers"], g["inter"], g["vocab"]
    wide = {"q_proj": (H, H), "k_proj": (H, KV), "v_proj": (H, KV),
            "o_proj": (H, H), "gate_proj": (H, I), "up_proj": (H, I),
            "down_proj": (I, H)}                  # the norms are (H,)
    layers_s = {k: bf(L, *wide.get(k.split(".")[-2], (H,)))
                for k in layers}
    outer_s = {k: bf(*{"model.embed_tokens.weight": (V, H),
                       "lm_head.weight": (H, V)}.get(k, (H,)))
               for k in outer}
    pool = bf(L, g["kv_heads"], g["pool"], g["page"], HD)
    pool_bytes = 2 * L * g["kv_heads"] * g["pool"] * g["page"] * HD * 2
    B, W = g["slots"], g["table"]
    with lower_for_chip():
        programs = {
            "decode_n": decode_n.lower(
                outer_s, layers_s, i32(B), i32(B, W), i32(B), (pool, pool),
                1).compile(),
            "_prefill_chunk": prefill._jit_inner[0].lower(
                outer_s, layers_s, i32(1, g["page"]), i32(), i32(1, W),
                i32(1), (pool, pool), bf(1, H)).compile()}
    assert _mosaic_calls(programs["decode_n"]) == 1     # the scanned body's
    moved = re.compile(
        r"= \w+\[[\d,]*%d,%d,%d\]\S* (copy|copy-start|copy-done|"
        r"dynamic-slice|dynamic-update-slice)\(" % (g["pool"], g["page"], HD))
    for name, c in programs.items():
        stats = c.memory_analysis()
        assert stats.alias_size_in_bytes >= pool_bytes, name
        assert stats.temp_size_in_bytes < pool_bytes // 8, (
            name, stats.temp_size_in_bytes)
        assert not [ln for ln in c.as_text().splitlines()
                    if moved.search(ln)], name


@pytest.mark.parametrize("whole", [False, True],
                         ids=["one_layer", "pool_and_layer"])
def test_tp_sharded_paged_call_on_four_chips(v5e, whole):
    """The paged kernel under tp=4: kv heads manual over the tp axis
    (``paged_kernel_call``), for one layer's pages and for the whole pools
    read at a traced layer (``tp_pool_spec``'s layout, the index
    replicated); the bare sharded call is what JAX refuses."""
    from paddle_tpu.models.nlp.llama_decode import paged_kernel_call
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    mesh = Mesh(np.asarray(v5e), ("tp",))
    ns = lambda *names: NamedSharding(mesh, P(*names))  # noqa: E731
    pool = _sds((2, NH, POOL, PAGE, HD), BF16, ns(None, "tp")) if whole \
        else _sds((NH, POOL, PAGE, HD), BF16, ns("tp"))
    args = (_sds((SLOTS, NH, HD), BF16, ns(None, "tp")), pool, pool,
            _sds((SLOTS, WIDTH), jnp.int32, ns()),
            _sds((SLOTS,), jnp.int32, ns()))
    if whole:
        c = _compile(lambda *a: paged_kernel_call(
            paged_attention, *a[:-1], layer=a[-1], mesh=mesh, axis="tp"),
            *args, _sds((), jnp.int32, ns()))
    else:
        c = _compile(lambda *a: paged_kernel_call(paged_attention, *a,
                                                  mesh=mesh, axis="tp"),
                     *args)
    assert _mosaic_calls(c) == 1
    with pytest.raises(NotImplementedError, match="automatically partition"):
        _compile(lambda *a: paged_attention(*a, layer=1 if whole else None),
                 *args)


@pytest.mark.parametrize("shape,names", [((2, 2), ("data", "model")),
                                         ((4,), ("data",))])
def test_multi_device_train_step_lowers_for_tpu(shape, names):
    """The Mosaic-under-GSPMD refusal is raised while LOWERING for the TPU
    platform, so a train step on a CPU mesh of the four-chip shapes shows
    it without a topology: every flash / fused-CE call must sit inside an
    all-manual shard_map."""
    from paddle_tpu.models.nlp.llama import llama_train_step_factory
    cfg = LlamaConfig(vocab_size=512, hidden_size=256, intermediate_size=512,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=512,
                      dtype=BF16)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    with lower_for_chip():
        params, opt_state, step, batch_sh = llama_train_step_factory(
            model, make_mesh(shape, names), remat=False, accum_dtype=BF16)
        tok = jax.device_put(np.zeros((4, 256), np.int32), batch_sh)
        text = step.trace(params, opt_state, tok, tok).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3 * 2


def test_4d_train_step_without_pipe_axis_lowers_for_tpu():
    """The 4-D factory's no-pipe branch runs its stage body under plain
    GSPMD: the flash kernel must be handed the mesh explicitly."""
    from paddle_tpu.models.nlp.llama_functional import (
        llama_4d_train_step_factory)
    cfg = LlamaConfig(vocab_size=512, hidden_size=256, intermediate_size=512,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=512,
                      dtype=BF16)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    mesh = make_mesh((2, 2), ("data", "model"))
    with lower_for_chip():
        params, opt_state, step = llama_4d_train_step_factory(
            model, mesh, n_microbatches=1, remat=False)
        tok = jax.device_put(np.zeros((4, 256), np.int32),
                             NamedSharding(mesh, P("data")))
        text = step.trace(params, opt_state, tok, tok).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3


# Laguna-XS.2's serving cell (serve_window_moe_codemix): 32 slots x 137
# pages of 64 positions in each of two tables, 8 KV heads x 128 under 48
# (full) and 64 (sliding, window 512) query heads, a global pool of 5409
# pages x 2 layers and a window pool of 513 pages x 3 layers
WINDOWED = dict(slots=32, table=137, page=64, kv_heads=8, window=512,
                pools={"global": (2, 5409), "window": (3, 513)})


@pytest.mark.parametrize("heads,kind", [(48, "global"), (64, "window")])
@pytest.mark.parametrize("chunk,rows", [(1, WINDOWED["slots"]), (64, 1), (128, 1)])
def test_windowed_paged_kernel_compiles_at_the_cells_shapes(v5e, chunk, rows,
                                                            heads, kind):
    """Query groups of 6 and 8 a KV head, decode (every slot a row), a
    64-token chunk (384 and 512 rows a KV head: the second needs more than
    Mosaic's scoped default of VMEM, which the launcher asks for) and the
    lane's widest call (2 chunks: 768 and 1024 rows, the second 33.3 MB),
    with and without the walk's lower bound."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_prefill_attention)
    one = SingleDeviceSharding(v5e[0])
    g = WINDOWED
    layers, pages = g["pools"][kind]
    window = g["window"] if kind == "window" else None
    pool = _sds((layers, g["kv_heads"], pages, g["page"], HD), BF16, one)
    pt, sl = _sds((rows, g["table"]), jnp.int32, one), _sds((rows,), jnp.int32, one)
    if chunk == 1:
        c = _compile(lambda q, k, v, pt, sl: paged_attention(
            q, k, v, pt, sl, layer=1, window=window),
            _sds((rows, heads, HD), BF16, one), pool, pool, pt, sl)
    else:
        c = _compile(lambda q, k, v, pt, sl, st: paged_prefill_attention(
            q, k, v, pt, sl, st, layer=1, window=window),
            _sds((rows, heads, chunk, HD), BF16, one), pool, pool, pt, sl,
            _sds((), jnp.int32, one))
    assert _mosaic_calls(c) == 1 and "%paged_attention" in c.as_text()


def test_windowed_programs_keep_both_pools_in_place(v5e):
    """The decode and chunk programs of the five-layer cut at the published
    widths: the four pools are donated and aliased, nothing of a pool's size
    is made beside them, and each layer calls the kernel once."""
    from paddle_tpu.models.nlp import laguna as M
    one = SingleDeviceSharding(v5e[0])
    g = WINDOWED
    net = M.LagunaForCausalLM(M.LagunaConfig(num_hidden_layers=5))
    net.decode_params = lambda: (dict(net.outer),          # shapes in place of arrays
                                 [dict(lp) for lp in net.layers])
    outer, layers, _, prefill, _, decode_n = M.windowed_paged_decode_factory(
        net, page_size=g["page"], n_pool_pages=3, n_window_pages=3,
        chunked_prefill=g["page"])
    pools = tuple(_sds((n, g["kv_heads"], p, g["page"], HD), BF16, one)
                  for n, p in (g["pools"]["global"], g["pools"]["window"])
                  for _ in range(2))
    pool_bytes = sum(int(np.prod(p.shape)) * 2 for p in pools)
    i32 = lambda *s: _sds(s, jnp.int32, one)  # noqa: E731
    with lower_for_chip():
        dec = decode_n._jit_inner[0].lower(
            _on(outer, one), _on(layers, one), i32(g["slots"]),
            i32(g["slots"], 2 * g["table"]), i32(g["slots"]), pools, 1).compile()
        chunk = prefill._jit_inner[0].program.lower(
            _on(outer, one), _on(layers, one), i32(1, g["page"]), i32(),
            i32(1, 2 * g["table"]), i32(1), pools,
            _sds((1, 2048), BF16, one)).compile()
    for c in (dec, chunk):
        kernels = [ln for ln in c.as_text().splitlines()
                   if "custom-call(" in ln and "%paged_attention" in ln.split("=")[0]]
        assert len(kernels) == 5                            # one a layer
        stats = c.memory_analysis()
        assert stats.alias_size_in_bytes >= pool_bytes
        assert stats.temp_size_in_bytes < pool_bytes // 8


# the linear-attention cell's geometry (serve_linear_latent_agent): 64 slots
# and 96 snapshot entries of 7 KDA layers x (32, 128, 128) float32 + the
# convolutions' 3 x 12288 inputs; 2 latent layers over 8193 pages, 281
# table entries and the state entry's column
STATE = dict(slots=64, entries=160, table=281, page=64, heads=32, d=128,
             kda_layers=7, mla_layers=2, pool=8193, width=640)


def test_kda_decode_kernel_compiles_at_the_cells_shapes(v5e):
    """Every slot a row against the whole state operand addressed by
    (layer, row): aliased, nothing of its size beside it."""
    from paddle_tpu.ops.pallas.kda_decode import kda_decode_step
    one = SingleDeviceSharding(v5e[0])
    g = STATE
    f32 = lambda *s: _sds(s, jnp.float32, one)  # noqa: E731
    vec = f32(g["slots"], g["heads"], g["d"])
    c = _compile(lambda S, q, k, v, a, b, act: kda_decode_step(S, 3, q, k, v, a, b, act),
                 f32(g["kda_layers"], g["entries"], g["heads"], g["d"], g["d"]),
                 vec, vec, vec, vec, f32(g["slots"], g["heads"]),
                 _sds((g["slots"],), jnp.bool_, one), donate_argnums=(0,))
    assert _mosaic_calls(c) == 1 and "%kda_decode_step" in c.as_text()
    stats = c.memory_analysis()
    state_bytes = g["kda_layers"] * g["entries"] * g["heads"] * g["d"] * g["d"] * 4
    assert stats.alias_size_in_bytes >= state_bytes
    assert stats.temp_size_in_bytes < state_bytes // 64


def test_state_programs_keep_the_pool_and_the_states_in_place(v5e):
    """The decode program and the lane's widest and narrowest calls of the
    nine-layer cut at the published widths with a chip's 64 of 256 experts:
    the latent pool, the states and the convolutions' inputs are donated and
    aliased, nothing of their size is made beside them, a KDA layer steps
    through the kernel once and a latent layer attends once a head group
    (a held-expert map made as a traced scatter aborted this compiler)."""
    from paddle_tpu.models.nlp import kimi_linear as M
    one = SingleDeviceSharding(v5e[0])
    g = STATE
    net = M.KimiLinearForCausalLM(M.KimiLinearConfig(
        num_hidden_layers=9, vocab_size=40960, experts_held=tuple(range(64))))
    net.decode_params = lambda: (dict(net.outer),          # shapes in place of arrays
                                 [dict(lp) for lp in net.layers])
    outer, layers, _, prefill, _, decode_n = M.state_paged_decode_factory(
        net, page_size=g["page"], n_pool_pages=3, n_state_entries=2,
        chunked_prefill=g["page"])
    pools = (_sds((g["mla_layers"], g["pool"], g["page"], g["width"]), BF16, one),
             _sds((g["kda_layers"], g["entries"], g["heads"], g["d"], g["d"]),
                  jnp.float32, one),
             _sds((g["kda_layers"], g["entries"], 3, 3 * g["heads"] * g["d"]), BF16, one))
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    i32 = lambda *s: _sds(s, jnp.int32, one)  # noqa: E731
    with lower_for_chip():
        dec = decode_n._jit_inner[0].lower(
            _on(outer, one), _on(layers, one), i32(g["slots"]),
            i32(g["slots"], g["table"] + 1), i32(g["slots"]), pools, 1).compile()
        calls = {w: prefill._jit_inner[0].program.lower(
            _on(outer, one), _on(layers, one), i32(1, w * g["page"]), i32(),
            i32(1, g["table"] + 1), i32(1), pools,
            _sds((1, 2304), BF16, one)).compile() for w in (1, 4)}

    def kernels(c, name):
        return [ln for ln in c.as_text().splitlines()
                if "custom-call(" in ln and f"%{name}" in ln.split("=")[0]]
    assert len(kernels(dec, "kda_decode_step")) == g["kda_layers"]
    assert len(kernels(dec, "latent_paged_attention")) == g["mla_layers"]
    assert len(kernels(calls[1], "latent_paged_attention")) == g["mla_layers"]
    assert len(kernels(calls[4], "latent_paged_attention")) == 2 * g["mla_layers"]
    for c in (dec, *calls.values()):
        stats = c.memory_analysis()
        assert stats.alias_size_in_bytes >= pool_bytes
        assert stats.temp_size_in_bytes < pool_bytes // 8
