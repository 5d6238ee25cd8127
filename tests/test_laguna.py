"""``laguna`` on the serving path at the tiny size (``LagunaConfig.tiny``:
2 KV heads under 6 and 8 query heads of 16, window 8 over pages of 4, five
layers F S S S F, layer 0 dense, 16 experts top-4 + 1 shared, partial
rotary under YaRN on the full layers), float32, on the CPU.

What is compared is what ``benchmark/harness/serve_check.py`` compares on
the chip: every served token's logit in the plain reference
(``benchmark/reference/laguna.py``, which imports nothing of the program)
against that reference's best logit at the same position, and — with the
factory handing out logits — the logits themselves.

Tolerances, each with its reason.  ``GAP`` 2e-4: program and reference both
compute in float32 and differ by the order of their sums (the kernel's
online softmax over pages, the grouped expert products); at logits of order
0.3 that is 1e-6..1e-5, and greedy decoding serves the best token, so a
sound run's gap is that rounding.  The int8 control and the planted fault
``window_ignored`` move logits by 1e-2 and more and must fail it.
``LOGITS`` 5e-4 absolute on logits of order 0.3, for the same reason.
"""
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.nlp import laguna as L
from paddle_tpu.models.nlp.laguna import LagunaConfig, LagunaForCausalLM
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.workload import Request

REPO = Path(__file__).resolve().parents[1]
GAP, LOGITS = 2e-4, 5e-4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "laguna_plain_reference", REPO / "benchmark/reference/laguna.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _reference()


def draw(cfg, seed=0):
    """Seeded weights: gains near one, matrices wide enough that logits are
    of order 0.3 and the router's choices are not ties."""
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i, (name, shape) in enumerate(L.leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if len(shape) == 1:
            tree[name] = 1.0 + 0.1 * jax.random.normal(k, shape)
        else:
            tree[name] = jax.random.normal(k, shape) / math.sqrt(shape[-2])
    return tree


def model_dict(cfg):
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_key_value_heads", "head_dim", "rms_norm_eps", "num_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "sliding_window", "rope_parameters",
            "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
            "moe_routed_scaling_factor")
    return {k: getattr(cfg, k) for k in keys}


@pytest.fixture(scope="module")
def tiny():
    cfg = LagunaConfig.tiny()
    net = LagunaForCausalLM(cfg)
    weights = draw(cfg)
    net.load_tree(weights)
    return cfg, net, weights


def engine(net, **over):
    args = dict(slots=4, max_len=96, page_size=4, n_pool_pages=120,
                policy="paged", prefill_chunk_budget=2, clock="fixed",
                n_window_pages=4 * 4 + 1 + 8)
    args.update(over)
    return ServingEngine(net, **args)


def gaps(cfg, weights, reqs, outputs, quant=None):
    """Each served token's gap under the reference's best, per request."""
    md = model_dict(cfg)
    out = {}
    for r in reqs:
        served = outputs[r.rid]
        seq = jnp.asarray(list(r.prompt) + served, jnp.int32)
        ref = np.asarray(R.logits(md, weights, seq, quant))
        p = len(r.prompt)
        rows = ref[p - 1:p - 1 + len(served)]
        out[r.rid] = rows.max(-1) - rows[np.arange(len(served)), served]
    return out


def _requests(rng):
    prefix = list(rng.integers(0, 256, 24))
    mk = lambda rid, at, prompt, n: Request(  # noqa: E731
        rid=rid, arrival=at, prompt=tuple(int(t) for t in prompt),
        max_new_tokens=n, prefix_group=None)
    return [mk("a", 0.0, prefix + list(rng.integers(0, 256, 13)), 12),
            mk("b", 0.0, rng.integers(0, 256, 50), 20),      # 6 windows long
            mk("c", 30.0, prefix + list(rng.integers(0, 256, 7)), 9),
            mk("d", 30.5, rng.integers(0, 256, 5), 30)]     # admitted mid-decode


@pytest.fixture(scope="module")
def served(tiny):
    cfg, net, weights = tiny
    reqs = _requests(np.random.default_rng(0))
    res = engine(net).run(reqs)
    return reqs, res


def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(tiny, served):
    cfg, net, weights = tiny
    reqs, res = served
    assert {r.rid: len(res.outputs[r.rid]) for r in reqs} == {"a": 12, "b": 20, "c": 9, "d": 30}
    # contexts several windows long, a resume from the prefix cache, an
    # admission while others decode
    assert res.prefix_cached == {"a": 0, "b": 0, "c": 24, "d": 0}
    admit = {rid: res.metrics._req[rid].admit for rid in "cd"}
    assert admit["d"] > admit["c"] and res.metrics._req["c"].finish > admit["d"]
    got = gaps(cfg, weights, reqs, res.outputs)
    assert max(float(g.max()) for g in got.values()) < GAP
    assert res.cache_stats["invariant_ok"]
    assert all(d.get("backend") == "paged" for d in res.decisions)


def test_the_int8_control_fails_the_same_tolerance(tiny, served):
    cfg, net, weights = tiny
    reqs, res = served
    md = model_dict(cfg)
    worst = 0.0
    for r in reqs:
        seq = jnp.asarray(list(r.prompt) + res.outputs[r.rid], jnp.int32)
        plain = np.asarray(R.logits(md, weights, seq))
        lower = np.asarray(R.logits(md, weights, seq, "int8"))
        p, n = len(r.prompt), len(res.outputs[r.rid])
        rows, judged = plain[p - 1:p - 1 + n], lower[p - 1:p - 1 + n].argmax(-1)
        worst = max(worst, float((rows.max(-1) - rows[np.arange(n), judged]).max()))
    assert worst > 10 * GAP


def test_the_planted_fault_window_ignored_fails_the_same_tolerance(tiny):
    cfg, net, weights = tiny
    reqs = _requests(np.random.default_rng(0))
    serving = L.windowed_serving_decode_factory(
        net, max_len=96, page_size=4, n_pool_pages=120, batch_capacity=4,
        chunked_prefill=4, n_window_pages=25, window_ignored=True)
    res = ServingEngine(serving=serving, slots=4, policy="paged",
                        prefill_chunk_budget=2, clock="fixed").run(reqs)
    got = gaps(cfg, weights, reqs, res.outputs)
    assert max(float(g.max()) for g in got.values()) > 10 * GAP
    # and the reference with the same fault planted reads as wrong as that
    md = model_dict(cfg)
    seq = jnp.asarray(list(reqs[1].prompt), jnp.int32)
    sound, fault = (np.asarray(R.logits(md, weights, seq, q)) for q in (None, "window_ignored"))
    assert np.abs(sound - fault)[9:].max() > 100 * LOGITS
    assert np.abs(sound - fault)[:8].max() < LOGITS     # nothing lies behind the first window


def test_the_programs_logits_are_the_references(tiny):
    """Chunked prefill, then decode steps, with the factory handing out
    logits: against the reference and against ``full_forward``."""
    cfg, net, weights = tiny
    outer, layers, pools, prefill, step, _ = L.windowed_paged_decode_factory(
        net, page_size=4, n_pool_pages=40, n_window_pages=40, chunked_prefill=4,
        emit="logits")
    rng = np.random.default_rng(3)
    seq = rng.integers(0, 256, 41)
    P, W = 33, 16
    toks = np.zeros((1, 36), np.int32)
    toks[0, :P] = seq[:P]
    pt = np.zeros((1, 2 * W), np.int32)
    pt[0, :12] = np.arange(1, 13)
    pt[0, W:W + 12] = np.arange(20, 32)
    first, pools = prefill(outer, layers, jnp.asarray(toks), jnp.asarray(pt),
                           jnp.asarray([P], jnp.int32), pools)
    rows = [np.asarray(first)[0]]
    for t in range(P, 40):
        out, pools = step(outer, layers, jnp.asarray(seq[t:t + 1], jnp.int32), jnp.asarray(pt),
                          jnp.asarray([t], jnp.int32), pools)
        rows.append(np.asarray(out)[0])
    ref = np.asarray(R.logits(model_dict(cfg), weights, jnp.asarray(seq[:40], jnp.int32)))
    assert np.abs(ref).max() > 0.1
    assert np.abs(np.stack(rows) - ref[P - 1:40]).max() < LOGITS
    full = np.asarray(net.forward(jnp.asarray(seq[None, :40], jnp.int32)))[0]
    assert np.abs(full - ref).max() < LOGITS


def test_yarn_frequencies_against_numbers_written_out_by_hand():
    """The published settings (theta 500000, factor 64, original 4096,
    beta_fast 64, beta_slow 1 over the 64 rotated dimensions): low 5, high
    16; below 5 the plain frequency, from 16 on the plain one over 64,
    between them a ramp of elevenths."""
    rp = LagunaConfig().rope_parameters["full_attention"]
    corr = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(500000))  # noqa: E731
    assert (math.floor(corr(64)), math.ceil(corr(1))) == (5, 16)
    inv = np.asarray(L.yarn_inv_freq(rp, 64))
    plain = 500000.0 ** (-2 * np.arange(32) / 64)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[9], plain[9] * ((4 / 11) / 64 + 7 / 11), rtol=1e-6)
    # by hand: d = 0 -> 1; d = 5 -> 500000^(-10/64) = exp(-2.05037) = 0.128687; d = 16 -> 500000^(-0.5)/64
    np.testing.assert_allclose(inv[[0, 5, 16]], [1.0, 0.1286874, 2.2097087e-05], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(R.yarn_inv_freq(rp, 64)), inv, rtol=1e-7)
    assert rp["attention_factor"] == pytest.approx(0.1 * math.log(64) + 1)
    # a sliding layer rotates the whole head plainly; a full layer half of it
    cfg = LagunaConfig.tiny()
    cos, sin, r = L.rope_tables(cfg, L.SLIDING, jnp.arange(3))
    assert r == 16 and float(cos[0, 0]) == 1.0
    cos, sin, r = L.rope_tables(cfg, L.FULL, jnp.arange(3))
    assert r == 8 and float(cos[0, 0]) == pytest.approx(rp["attention_factor"])
    x = jnp.arange(32, dtype=jnp.float32).reshape(1, 2, 16)      # (T=1, heads, D)
    out = L.apply_rope(x, cos[1:2], sin[1:2], r)
    np.testing.assert_array_equal(np.asarray(out[..., 8:]), np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(out[..., :8]), np.asarray(x[..., :8]))


def test_the_gate_scales_each_heads_output_before_o_proj(tiny):
    cfg, net, weights = tiny
    lp = {L.GATE: jnp.zeros((32, 6)).at[:, 2].set(100.0)}
    h = jnp.ones((1, 3, 32))
    o = jnp.ones((1, 3, 6, 16))
    out = np.asarray(L.attn_gate(lp, h, o))
    np.testing.assert_allclose(out[..., 2, :], 1.0)           # sigmoid(3200) = 1
    np.testing.assert_allclose(np.delete(out, 2, axis=2), 0.5)    # sigmoid(0)
    # the reference's gate is the same function of the same leaf
    ref = R.gate({"self_attn.gate_proj.weight": lp[L.GATE]}, h[0], o[0], None)
    np.testing.assert_allclose(np.asarray(ref), out[0], rtol=1e-6)


def test_router_parity_and_moonlights_router_unchanged(tiny):
    from paddle_tpu.models.nlp import expert_layer as E
    from paddle_tpu.models.nlp.deepseek_v3 import DeepseekV3Config
    cfg, net, weights = tiny
    x = jax.random.normal(jax.random.PRNGKey(5), (7, 32))
    w_r = weights["model.layers.2.mlp.gate.weight"]
    w, idx = E.route(cfg, w_r, None, x)
    s = np.asarray(jax.nn.sigmoid(x @ w_r))
    top = np.argsort(-s, axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(top, -1))
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)     # normalised, scaled
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w), chosen / chosen.sum(-1, keepdims=True) * 2.5,
                               rtol=1e-5)
    dense = np.asarray(R.router(model_dict(cfg), {"mlp.gate.weight": w_r}, x, None))
    np.testing.assert_allclose(np.take_along_axis(dense, np.asarray(idx), -1), np.asarray(w),
                               rtol=1e-5)
    assert "mlp.gate.e_score_correction_bias" not in L.layer_leaf_shapes(cfg, 2)   # no bias leaf
    # Moonlight's: its settings by its own names, its bias moving the choice alone
    ds = DeepseekV3Config.tiny()
    assert E.router_of(ds) == E.Router(k=3, n_experts=8, scaling=2.446, normalise=True,
                                       scoring="sigmoid", groups=(1, 1))
    xr = jax.random.normal(jax.random.PRNGKey(6), (5, 64))
    wr = jax.random.normal(jax.random.PRNGKey(7), (64, 8))
    bias = jnp.zeros((8,)).at[3].set(10.0)
    w0, i0 = E.route(ds, wr, jnp.zeros((8,)), xr)
    w1, i1 = E.route(ds, wr, bias, xr)
    assert (np.asarray(i1) == 3).any(-1).all()
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 2.446, rtol=1e-5)
    nb, ni = E.route(ds, wr, None, xr)
    assert np.array_equal(np.asarray(ni), np.asarray(i0))
    np.testing.assert_allclose(np.asarray(nb), np.asarray(w0), rtol=1e-6)
    with pytest.raises(NotImplementedError, match="one group"):
        E.route(DeepseekV3Config.tiny(n_group=2), wr, None, xr)
    with pytest.raises(NotImplementedError, match="noaux_tc"):
        E.route(DeepseekV3Config.tiny(topk_method="greedy"), wr, None, xr)


def test_decode_n_compiles_once_across_churn_and_the_counts_ride(tiny):
    cfg, net, weights = tiny
    eng = engine(net)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=f"r{i}", arrival=0.7 * i,
                    prompt=tuple(int(t) for t in rng.integers(0, 256, int(n))),
                    max_new_tokens=int(m), prefix_group=None)
            for i, (n, m) in enumerate(zip(rng.integers(3, 60, 9), rng.integers(2, 25, 9)))]
    res = eng.run(reqs)
    assert all(len(res.outputs[r.rid]) == r.max_new_tokens for r in reqs)
    assert eng._p_decode_n._jit_inner[0]._cache_size() == 1
    chunk_program, finish = eng._p_prefill._jit_inner    # a program a width of the lane's call
    assert chunk_program._cache_size() == eng._lane_widest == 2 and finish._cache_size() == 1
    eng2 = engine(net, clock="measured")
    res = eng2.run(reqs)
    ov = res.overhead
    counts = ov["model_counts"]
    assert set(counts) == {"kind"} | set(L.CALL_COUNTS)
    dec = [i for i, k in enumerate(counts["kind"]) if k == "decode"]
    assert dec and all(counts["kv_tokens_read_window"][i] <= counts["kv_tokens_read_global"][i] * 3 // 2
                       for i in dec)
    # a row's walk in a window layer reads at most the window: 3 window
    # layers x 4 rows x 8 positions a decode step
    assert max(counts["kv_tokens_read_window"][i] for i in dec) <= 3 * 4 * 8
    assert ov["kv_pages_held"]["turns"] > 0 and ov["window_pages_released"] > 0
    assert ov["kv_page_bytes"] == {"global": 2 * 2 * 2 * 16 * 4 * 4, "window": 3 * 2 * 2 * 16 * 4 * 4}
    held = ov["kv_pages_held"]
    assert held["window"] < held["global"] == ov["kv_pages_if_all_global"]
    assert "window.release" in ov["phases"]
    assert res.cache_stats["kinds"]["window"]["resident_pages"] == 0


def test_what_a_two_kind_cache_refuses(tiny):
    cfg, net, weights = tiny
    for kw in (dict(tp=2), dict(kv_quant="int8"), dict(kv_cache_dtype="int8"),
               dict(hostmem=1 << 20), dict(dispatch_ahead=True), dict(ragged_prefill=True),
               dict(spec=2), dict(grammar={}), dict(prefill_chunk_budget=None)):
        with pytest.raises(ValueError, match="two-kind"):
            engine(net, **kw)
    with pytest.raises(ValueError, match="two-kind|dense"):
        engine(net, policy="dense")
    with pytest.raises(ValueError, match="slots x ring"):
        engine(net, n_window_pages=4 * 4)
    eng = engine(net)
    with pytest.raises(NotImplementedError, match="paged-only"):
        eng.serving.dense()
    assert eng.serving.kv_layout_ == "windowed" and eng.n_window_pages == 25
    assert engine(net, n_window_pages=None).n_window_pages == 4 * 4 + 1     # slots x ring + 1


def test_the_counters_and_keys_are_absent_on_other_models():
    """A Llama run and a latent run keep the registry, ``overhead`` and
    ``cache_stats`` they had: nothing of the two-kind cache appears."""
    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM
    from paddle_tpu.obs import metrics as obs_metrics
    ours = ("window_pages_released", "prefix_hits_cut_by_window", "kv_pages_held",
            "kv_pages_if_all_global", "kv_page_bytes")
    before = {n for n in obs_metrics.REGISTRY.snapshot() if "window" in n or "kv_pages" in n
              or "kv_tokens" in n} if hasattr(obs_metrics.REGISTRY, "snapshot") else set()
    paddle.seed(0)
    llama = LlamaForCausalLM(LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                                         num_hidden_layers=2, num_attention_heads=4,
                                         num_key_value_heads=2, max_position_embeddings=64))
    ds_cfg = DeepseekV3Config.tiny()
    from paddle_tpu.models.nlp import deepseek_v3 as D
    ds = DeepseekV3ForCausalLM(ds_cfg)
    key = jax.random.PRNGKey(0)
    ds.load_tree({n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), s)
                  for i, (n, s) in enumerate(D.leaf_shapes(ds_cfg).items())})
    req = [Request(rid="x", arrival=0.0, prompt=tuple(range(1, 20)), max_new_tokens=4,
                   prefix_group=None)]
    for model, kw in ((llama, {}), (ds, {})):
        eng = ServingEngine(model, slots=2, max_len=32, page_size=4, policy="paged",
                            prefill_chunk_budget=2, clock="measured", **kw)
        assert eng.window is None and eng._ctr_window is None
        res = eng.run(req)
        assert not set(ours) & set(res.overhead) and "kinds" not in res.cache_stats
        assert "window.release" not in res.overhead["phases"]
        with pytest.raises(ValueError, match="one kind"):
            ServingEngine(model, slots=2, max_len=32, page_size=4, policy="paged",
                          n_window_pages=9)
    if hasattr(obs_metrics.REGISTRY, "snapshot"):
        after = {n for n in obs_metrics.REGISTRY.snapshot() if "window" in n or "kv_pages" in n
                 or "kv_tokens" in n}
        assert after == before


def test_a_hit_cut_for_want_of_window_pages_still_serves_the_references_tokens(tiny):
    """A window pool with no room to retain (slots x ring + 1): by the time
    the prefix comes back its parked window pages were taken by the rows in
    between, the global chain alone would match, and the hit is cut — the
    request prefills from the start and serves what the reference serves."""
    cfg, net, weights = tiny
    rng = np.random.default_rng(7)
    prefix = [int(t) for t in rng.integers(0, 256, 24)]
    mk = lambda rid, at, prompt, n: Request(  # noqa: E731
        rid=rid, arrival=at, prompt=tuple(int(t) for t in prompt),
        max_new_tokens=n, prefix_group=None)
    reqs = [mk("a", 0.0, prefix + [1, 2, 3], 4)]
    reqs += [mk(f"x{i}", 20.0 + i, rng.integers(0, 256, 40), 6) for i in range(4)]
    reqs += [mk("c", 60.0, prefix + [9, 8, 7, 6, 5], 8)]
    res = engine(net, n_window_pages=4 * 4 + 1).run(reqs)
    assert res.prefix_cached["c"] == 0 and res.cache_stats["prefix_hits_cut_by_window"] == 1
    assert res.cache_stats["hit_tokens"] == 0 and res.cache_stats["invariant_ok"]
    got = gaps(cfg, weights, reqs, res.outputs)
    assert max(float(g.max()) for g in got.values()) < GAP
    # with room to retain, the same trace resumes from the cache
    roomy = engine(net, n_window_pages=96).run(reqs)
    assert roomy.prefix_cached["c"] == 24 and roomy.outputs == res.outputs
    assert roomy.cache_stats["prefix_hits_cut_by_window"] == 0
