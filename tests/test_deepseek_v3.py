"""``deepseek_v3`` on the serving path (Moonlight-16B-A3B's shape at toy
widths that keep every ratio: 4 heads of nope 16 / rope 8 / v 16, rank 32,
8 experts top-3, 2 shared, layer 0 dense): the latent paged kernel, the
drop-free expert layer, the model through ``ServingEngine`` against the
benchmark's plain reference, the refusals and the counters.

Tolerances.  The toy model is float32, as the reference is, so the program
and the reference differ by reassociation alone (absorbed against expanded
products, online against whole softmax, grouped against dense expert
products): logits agree to ``TOL = 2e-4`` at logits of magnitude ~1
(measured here: under 3e-5).  The reference's int8 control, the nearest
precision below what the configuration states, moves the same logits by
1e-2 and more, which every comparison below would fail: it is checked to.
"""
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.nlp import deepseek_v3 as M
from paddle_tpu.models.nlp import expert_layer as X
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.ops.pallas.latent_paged_attention import (
    latent_paged_attention, latent_paged_attention_reference, page_width)
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.workload import Request

TOL = 2e-4
PAGE = 8
REPO = Path(__file__).resolve().parents[1]


def _reference():
    """benchmark/reference/deepseek_v3.py, by its path: it imports nothing
    of the program, and the tests import nothing else of the benchmark."""
    path = REPO / "benchmark" / "reference" / "deepseek_v3.py"
    spec = importlib.util.spec_from_file_location("_ref_deepseek_v3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _reference()


def ref_cfg(cfg: M.DeepseekV3Config) -> dict:
    return {k: getattr(cfg, k) for k in (
        "num_hidden_layers", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
        "rope_theta", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor")}


def seeded_weights(cfg, seed=0):
    """Matrices at fan-in scale (so logits are of order one), gains near
    one, the router's bias small against its scores."""
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i, (name, shape) in enumerate(M.leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith("e_score_correction_bias"):
            tree[name] = 0.05 * jax.random.normal(k, shape)
        elif len(shape) == 1:
            tree[name] = 1.0 + 0.1 * jax.random.normal(k, shape)
        else:
            tree[name] = jax.random.normal(k, shape) / math.sqrt(shape[-2])
    return tree


@pytest.fixture(scope="module")
def net():
    cfg = M.DeepseekV3Config.tiny()
    model = M.DeepseekV3ForCausalLM(cfg)
    model.eval()
    model.load_tree(seeded_weights(cfg))
    return model


@pytest.fixture(scope="module")
def weights(net):
    return {k: v._value for k, v in net.state_dict().items()}


_LOGITS = {}


def ref_logits(net, weights, tokens, quant=None):
    """One pass of the reference a sequence and ``quant`` in the file (the
    served-token gaps and the int8 control share it)."""
    key = id(weights), tuple(int(t) for t in tokens), quant
    if key not in _LOGITS:      # the weights ride along: their id stays theirs
        _LOGITS[key] = weights, R.logits(ref_cfg(net.config), weights,
                                         jnp.asarray(key[1], jnp.int32), quant)
    return _LOGITS[key][1]


# One factory a geometry in this file: engines that differ in the clock or
# the policy alone share its compiled programs (``serving=``), built by the
# first engine that needs them.  Engines on one factory run one after another
# (the live pools ride on it, and what a run leaves there no later run reads).
_SERVING = {}


def engine(net, own=False, **kw):
    """An engine on the file's factory of its geometry; ``own``: on a
    factory of its own (a test that counts compiles)."""
    args = dict(slots=4, max_len=128, page_size=PAGE, policy="paged",
                prefill_chunk_budget=2, clock="fixed")
    args.update(kw)
    key = net, repr(sorted(dict(args, clock=None, policy=None).items()))
    if own or key not in _SERVING:
        eng = ServingEngine(net, **args)
        if not own:
            _SERVING[key] = eng.serving
        return eng
    return ServingEngine(serving=_SERVING[key], **args)


def requests(n=6, seed=0, lo=5, hi=60, new=6, gap=0.1):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", arrival=gap * i,
                    prompt=tuple(int(t) for t in rng.integers(
                        0, 256, size=int(rng.integers(lo, hi)))),
                    max_new_tokens=new, prefix_group=None) for i in range(n)]


# -- the model object -------------------------------------------------------
def test_the_model_is_built_with_placeholder_leaves():
    cfg = M.DeepseekV3Config.tiny()
    model = M.DeepseekV3ForCausalLM(cfg)
    assert not model.materialized()
    assert all(isinstance(v, jax.ShapeDtypeStruct) for v in model._leaves())
    with pytest.raises(ValueError, match="shapes only"):
        model.state_dict()
    tree = seeded_weights(cfg)
    model.load_tree(dict(tree, **{"not.a.leaf": jnp.zeros(3)}))
    assert model.materialized()
    assert list(model.state_dict()) == list(M.leaf_shapes(cfg)) == list(tree)
    kept, given = (model.layers[1][X.EXPERT_KEYS[0]],
                   tree["model.layers.1." + X.EXPERT_KEYS[0]])
    assert kept.unsafe_buffer_pointer() == given.unsafe_buffer_pointer()   # no copy
    with pytest.raises(ValueError, match="expects"):
        model.load_tree({"model.norm.weight": jnp.zeros(3)})
    model.drop_weights()
    assert not model.materialized()


def test_the_published_size_states_its_widths():
    cfg = M.DeepseekV3Config(num_hidden_layers=7)
    shapes = M.leaf_shapes(cfg)
    assert cfg.qk_head_dim == 192 and cfg.latent_width == 576 and page_width(576) == 640
    assert shapes["model.layers.0.self_attn.q_proj.weight"] == (2048, 16 * 192)
    assert shapes["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"] == (2048, 576)
    assert shapes["model.layers.0.self_attn.kv_b_proj.weight"] == (512, 16 * 256)
    assert shapes["model.layers.0.mlp.gate_proj.weight"] == (2048, 11264)
    assert shapes["model.layers.6.mlp.experts.down_proj"] == (64, 1408, 2048)
    assert shapes["model.layers.6.mlp.shared_experts.up_proj.weight"] == (2048, 2816)
    assert sum(math.prod(s) for s in shapes.values()) == 4_263_151_488   # 8.53 GB in bf16


@pytest.mark.parametrize("key,value", [("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn"}),
                                       ("attention_bias", True), ("moe_layer_freq", 2)])
def test_what_the_module_does_not_compute_is_refused(key, value):
    with pytest.raises(NotImplementedError, match=key):
        M.DeepseekV3Config.tiny(**{key: value})


# -- rotary pairing ---------------------------------------------------------
def test_rotary_pairs_interleaved_dimensions_as_the_reference_does():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(6, 3, 8)), jnp.float32)
    pos = jnp.arange(6) + 5
    got = M.rope_interleaved(x, pos[:, None], 50000.0)
    np.testing.assert_allclose(got, R.rotary(x, pos, 50000.0), atol=1e-6)
    # Hugging Face's deepseek_v3 gathers [evens | odds] and rotates halves:
    # the same rotation in another order of columns, so scores agree
    d = x.shape[-1]
    perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    inv = 1.0 / (50000.0 ** (np.arange(0, d, 2) / d))
    ang = np.asarray(pos, np.float32)[:, None, None] * inv
    xh = np.asarray(x)[..., perm]
    x1, x2 = xh[..., :d // 2], xh[..., d // 2:]
    hf = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                         x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    np.testing.assert_allclose(np.asarray(got)[..., perm], hf, atol=1e-5)


# -- the kernel -------------------------------------------------------------
@pytest.mark.parametrize("chunk", [1, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_latent_kernel_matches_jnp_in_interpret_mode(chunk, dtype, tol):
    """float32: reassociation of the online softmax alone.  bfloat16: the
    kernel rounds the probabilities to the pages' type before the value
    product (one part in 256 of values of order one)."""
    rng = np.random.default_rng(0)
    B, H, rank, width, P, W, L = 3, 4, 32, 128, 20, 5, 2
    q = np.zeros((B, H * chunk, width), np.float32)
    q[..., :40] = rng.normal(size=(B, H * chunk, 40))
    pool = np.zeros((L, P, PAGE, width), np.float32)
    pool[..., :40] = rng.normal(size=(L, P, PAGE, 40))
    pt = jnp.asarray(rng.integers(1, P, size=(B, W)), jnp.int32)
    lens = jnp.asarray([7, 33, 18], jnp.int32)
    starts = lens - chunk
    args = (jnp.asarray(q, dtype), jnp.asarray(pool, dtype))
    for layer in range(L):
        got = latent_paged_attention(*args, layer, pt, lens, starts, chunk, rank, 0.2)
        want = latent_paged_attention_reference(*args, layer, pt, lens, starts, chunk,
                                                rank, 0.2)
        assert got.shape == (B, H * chunk, rank) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)


# -- the expert layer -------------------------------------------------------
def _layer(net, i=1):
    return net.layers[i]


def test_router_bias_moves_the_choice_and_never_the_weight(net):
    cfg, lp = net.config, dict(_layer(net))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(16, cfg.hidden_size)),
                    jnp.float32)
    s = jax.nn.sigmoid(x @ lp[X.ROUTER])
    w0, idx0 = X.route(cfg, lp[X.ROUTER], jnp.zeros(8), x)
    # no bias: the k largest scores, normalised to sum 1, times the factor
    np.testing.assert_array_equal(np.sort(idx0, -1), np.sort(jax.lax.top_k(s, 3)[1], -1))
    np.testing.assert_allclose(w0.sum(-1), cfg.routed_scaling_factor, rtol=1e-6)
    # a bias that lifts expert 5 into every token's choice
    bias = jnp.zeros(8).at[5].set(10.0)
    w1, idx1 = X.route(cfg, lp[X.ROUTER], bias, x)
    assert bool(jnp.all(jnp.any(idx1 == 5, -1))) and not bool(jnp.all(jnp.any(idx0 == 5, -1)))
    chosen = jnp.take_along_axis(s, idx1, -1)        # weights from s alone, not s + b
    np.testing.assert_allclose(
        w1, chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scaling_factor, rtol=1e-6)
    # and the reference's router says the same
    dense = R.router(ref_cfg(cfg), dict(lp, **{X.ROUTER_BIAS: bias}), x, None)
    np.testing.assert_allclose(jnp.take_along_axis(dense, idx1, -1), w1, rtol=1e-5)
    assert int((dense != 0).sum()) == 16 * 3


def test_no_pair_is_dropped_when_every_token_goes_to_three_experts(net):
    cfg, lp = net.config, dict(_layer(net))
    lp[X.ROUTER_BIAS] = jnp.zeros(8).at[jnp.asarray([1, 4, 6])].set(10.0)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(40, cfg.hidden_size)),
                    jnp.float32)
    y, counts = X.routed_part(cfg, lp, x)
    assert [int(c) for c in counts] == [120, 3, 40]          # pairs, experts hit, largest
    want = R.sparse_feed_forward(ref_cfg(cfg), lp, x, None) \
        - R.swiglu(x, *(lp[k] for k in X.SHARED_KEYS), None)
    np.testing.assert_allclose(y, want, atol=TOL)


def test_two_halves_and_the_shared_expert_once_are_the_whole_layer(net):
    """The chip's-share cut: disjoint sets of held experts, each computing
    its own part over the full router, and what every chip computes alike
    counted once."""
    cfg, lp = net.config, _layer(net, 2)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(24, cfg.hidden_size)),
                    jnp.float32)
    whole, counts = X.expert_layer(cfg, lp, x[None])
    parts, pairs = [], 0
    for held in ([0, 2, 5, 7], [1, 3, 4, 6]):
        share = dict(lp, **{k: lp[k][jnp.asarray(held)] for k in X.EXPERT_KEYS})
        y, c = X.routed_part(cfg, share, x, held=held)
        parts.append(y)
        pairs += int(c[0])
        np.testing.assert_allclose(
            y + R.swiglu(x, *(lp[k] for k in X.SHARED_KEYS), None),
            R.sparse_feed_forward(ref_cfg(cfg), share, x, None, held=held), atol=TOL)
    assert pairs == int(counts[0]) == 24 * 3
    np.testing.assert_allclose(parts[0] + parts[1] + X.shared_part(lp, x), whole[0],
                               atol=TOL)
    with pytest.raises(ValueError, match="say which"):
        X.routed_part(cfg, dict(lp, **{k: lp[k][:4] for k in X.EXPERT_KEYS}), x)


# -- program against reference ---------------------------------------------
def test_expanded_forward_matches_the_reference_and_int8_does_not(net, weights):
    tokens = np.random.default_rng(5).integers(0, 256, size=64)
    got = net(jnp.asarray(tokens[None]))[0]
    want = ref_logits(net, weights, tokens)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL)
    control = ref_logits(net, weights, tokens, quant="int8")
    assert float(jnp.max(jnp.abs(control - want))) > 50 * TOL


def test_absorbed_prefill_and_decode_through_the_cache_match_the_reference(net, weights):
    """Chunked prefill, then decode steps, through the latent pool and the
    kernel (absorbed), logits against the reference's full forward
    (expanded, no cache) at every emitted position."""
    outer, layers, pool, prefill, step, decode_n = M.latent_paged_decode_factory(
        net, page_size=PAGE, n_pool_pages=40, chunked_prefill=PAGE, emit="logits")
    rng = np.random.default_rng(6)
    book = PagedKVCache(40, PAGE, kv_heads=1, head_dim=1)
    prompts = [rng.integers(0, 256, size=n) for n in (21, 9)]
    T = 24
    toks = np.zeros((2, T), np.int32)
    for b, p in enumerate(prompts):
        book.allocate(b, T + 8)
        toks[b, :len(p)] = p
    pt = np.zeros((2, 5), np.int32)
    for b in range(2):
        pt[b, :len(book.tables[b])] = book.tables[b]
    lens = np.asarray([len(p) for p in prompts], np.int32)
    first, pool = prefill(outer, layers, jnp.asarray(toks), jnp.asarray(pt),
                          jnp.asarray(lens), pool)
    seqs = [list(p) for p in prompts]
    for b in range(2):
        want = ref_logits(net, weights, np.pad(seqs[b], (0, 32 - len(seqs[b]))))
        np.testing.assert_allclose(first[b], want[len(seqs[b]) - 1], atol=TOL)
    tok = jnp.argmax(first, -1).astype(jnp.int32)
    for _ in range(4):
        for b in range(2):
            seqs[b].append(int(tok[b]))
        emits, tok, pool = decode_n(outer, layers, tok, jnp.asarray(pt),
                                    jnp.asarray(lens), pool, 1)
        lens = lens + 1
        for b in range(2):
            want = ref_logits(net, weights, np.pad(seqs[b], (0, 32 - len(seqs[b]))))
            np.testing.assert_allclose(emits[0, b], want[len(seqs[b]) - 1], atol=TOL)
    counts = decode_n.counts.take()
    assert counts["kind"] == ["prefill"] * 3 + ["decode"] * 4
    assert counts["layer_calls"] == [2] * 7                    # two expert layers a call
    assert counts["pairs"] == [2 * 2 * PAGE * 3] * 3 + [2 * 2 * 3] * 4
    assert counts["cached_tokens_read"] == [0, 0, 0, 32, 34, 36, 38]


def served_gaps(net, weights, res, reqs, quant=None):
    """The harness's comparison: the reference's best logit less its logit
    of the served token, at every served position (``quant``: the tokens
    the control would have served instead)."""
    gaps = []
    for r in reqs:
        out = res.outputs[r.rid]
        seq = list(r.prompt) + out
        ref = ref_logits(net, weights, np.pad(seq, (0, 128 - len(seq))))
        rows = np.arange(len(r.prompt) - 1, len(seq) - 1)
        judged = jnp.asarray(out)
        if quant is not None:
            low = ref_logits(net, weights, np.pad(seq, (0, 128 - len(seq))), quant)
            judged = jnp.argmax(low[rows], -1)
        gaps.append(np.asarray(jnp.max(ref[rows], -1) - ref[rows, judged]))
    return np.concatenate(gaps)


def test_the_engine_serves_what_the_reference_puts_first(net, weights):
    """Through ``run``, the lane, the prefix cache and ``decode_n``: a
    document asked about twice (the second ask resumed from retained latent
    pages), requests admitted while others decode, more requests than
    slots.  Every served token's reference logit is the reference's best to
    float32 reassociation."""
    rng = np.random.default_rng(7)
    doc = tuple(int(t) for t in rng.integers(0, 256, size=40))
    reqs = requests(7, seed=8, new=8, gap=0.4)
    for i, at in ((1, 0.0), (5, 4.0)):
        own = tuple(int(t) for t in rng.integers(0, 256, size=6 + i))
        reqs[i] = Request(rid=f"doc{i}", arrival=at, prompt=doc + own,
                          max_new_tokens=8, prefix_group=0)
    eng = engine(net, own=True)
    res = eng.run(reqs)
    assert all(len(res.outputs[r.rid]) == 8 for r in reqs)
    assert res.prefix_cached["doc5"] == 40 and res.prefix_cached["doc1"] == 0
    admits = sorted(res.metrics._req[r.rid].admit for r in reqs)
    firsts = sorted(res.metrics._req[r.rid].token_times[0] for r in reqs)
    assert admits[-1] > firsts[0]                       # admitted mid-decode
    assert served_gaps(net, weights, res, reqs).max() <= TOL
    assert served_gaps(net, weights, res, reqs, quant="int8").max() > 50 * TOL
    # fixed shapes: churn never compiled a program (the chunk program has
    # one a width of the lane's call, each compiled when the engine was built)
    chunk_program, finish = eng._p_prefill._jit_inner
    assert eng._p_decode_n._jit_inner[0]._cache_size() == 1
    assert chunk_program._cache_size() == eng._lane_widest == 2
    assert finish._cache_size() == 1
    res2 = engine(net, slots=2).run(reqs)               # another batch shape, same tokens
    assert res2.outputs == res.outputs


def test_the_pool_holds_the_padded_latent_and_the_book_is_told(net):
    eng = engine(net)
    cfg = net.config
    width = page_width(cfg.latent_width)
    assert cfg.latent_width == 40 and width == 128
    assert eng._pools.shape == (cfg.num_hidden_layers, eng.n_pool_pages, PAGE, width)
    res = eng.run(requests(3))
    want = eng.n_pool_pages * PAGE * cfg.num_hidden_layers * width * 4
    assert res.cache_stats["bytes_total"] == want == res.cache_stats["bytes_per_device"]


# -- counters ---------------------------------------------------------------
MOE_COUNTERS = ("serving_moe_pairs_total", "serving_moe_experts_hit_total",
                "serving_moe_max_expert_pairs_total", "serving_moe_layer_calls_total",
                "serving_mla_cached_tokens_read_total")


def test_counters_exist_for_this_model_alone(net):
    obs_metrics.REGISTRY.reset()
    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64)
    llama = LlamaForCausalLM(cfg)
    llama.eval()
    small = [Request(rid=f"l{i}", arrival=0.1 * i, prompt=tuple(range(3, 12 + i)),
                     max_new_tokens=3, prefix_group=None) for i in range(3)]
    plain = ServingEngine(llama, slots=2, max_len=32, page_size=8, policy="paged",
                          prefill_chunk_budget=2, clock="measured").run(small)
    text = obs_metrics.REGISTRY.expose_text()
    assert not any(name in text for name in MOE_COUNTERS)
    assert "serving_pool_bytes_per_device" not in text
    assert "model_counts" not in plain.overhead and "bytes_total" not in plain.cache_stats

    res = engine(net, clock="measured").run(requests(5, new=5))
    text = obs_metrics.REGISTRY.expose_text()
    assert all(name in text for name in MOE_COUNTERS)
    counts = res.overhead["model_counts"]
    n = len(counts["kind"])
    assert set(counts) == {"kind", *M.CALL_COUNTS} and n == len(counts["pairs"]) > 0
    calls = res.overhead["calls"]
    assert counts["kind"].count("decode") == calls["decode"]["n"]
    assert counts["kind"].count("prefill") == calls["prefill"]["n"]
    widths = []
    for kind, layer_calls, pairs, hit, largest, read in zip(
            counts["kind"], *(counts[k] for k in M.CALL_COUNTS)):
        rows = 4                                        # the slots
        if kind == "prefill":
            rows = pairs // (2 * 3)                     # a call's positions: whole chunks
            assert rows % PAGE == 0
            widths.append(rows // PAGE)
        assert layer_calls == 2 and pairs == 2 * rows * 3
        assert 3 * 2 <= hit <= 8 * 2 and pairs / 16 <= largest <= 2 * rows
        assert (read > 0) == (kind == "decode")
    by_width = res.overhead["lane_calls_by_width"]
    assert {w: widths.count(w) for w in set(widths)} == by_width and set(by_width) <= {1, 2}
    assert obs_metrics.REGISTRY.counter("serving_moe_pairs_total").value \
        == sum(counts["pairs"])


# -- the refusals -----------------------------------------------------------
@pytest.mark.parametrize("option", [
    {"tp": 2}, {"kv_quant": "int8"}, {"kv_quant": "pressure"},
    {"kv_cache_dtype": "int8"}, {"hostmem": 1 << 20}, {"lora": (2, 4)},
    {"adapters": {}}, {"spec": 2}, {"grammar_config": (2, 8)},
    {"dispatch_ahead": True}])
def test_what_a_latent_cache_does_not_compose_with_is_refused_by_name(net, option):
    with pytest.raises(ValueError, match="latent .* cache holds one page operand") as e:
        engine(net, **option)
    assert str(e.value).endswith(next(iter(option)))        # named, after the one message


def test_handoff_dense_routing_and_ragged_prefill_are_refused(net):
    eng = engine(net, policy="routed")          # coerced: there is no dense replica
    assert eng.policy.name == "paged"
    with pytest.raises(ValueError, match="latent .* cache"):
        eng.export_kv_pages([1, 2])
    with pytest.raises(ValueError, match="latent .* cache"):
        eng.import_kv_pages([1], None)
    with pytest.raises(ValueError, match="dense"):
        engine(net, policy="dense")
    with pytest.raises(ValueError, match="prefill_ragged"):
        engine(net, ragged_prefill=True)
    prebuilt = net.serving_decode_factory(max_len=128, page_size=PAGE, n_pool_pages=33,
                                          batch_capacity=2, chunked_prefill=PAGE)
    with pytest.raises(ValueError, match="latent .* cache"):
        ServingEngine(serving=prebuilt, slots=2, kv_quant="int8")
    out = ServingEngine(serving=prebuilt, slots=2, policy="paged", clock="fixed",
                        prefill_chunk_budget=2).run(requests(2))
    assert all(len(v) == 6 for v in out.outputs.values())


def test_every_model_answers_the_one_call_the_engine_makes(net):
    """The engine names no factory: a Llama model answers
    ``serving_decode_factory`` with the Llama factories, this model with
    its own from the geometry alone, and an object with no answer is
    refused before anything is built."""
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama_decode import llama_serving_decode_factory
    llama = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=64))
    llama.eval()
    build = dict(max_len=32, page_size=8, n_pool_pages=9, batch_capacity=2, chunked_prefill=8)
    ours, theirs = llama.serving_decode_factory(**build), \
        llama_serving_decode_factory(llama, **build)
    assert type(ours).__name__ == type(theirs).__name__ and ours.max_len_ == 32
    with pytest.raises(ValueError, match=r"geometry alone, not \['kv_quant', 'tp'\]"):
        net.serving_decode_factory(tp=2, kv_quant="int8", lora=None, **build)
    assert net.serving_decode_factory(scan_layers=False, tp=None, **build).kv_layout_ == "latent"
    with pytest.raises(TypeError, match="brings no serving factory"):
        ServingEngine(object(), slots=2, max_len=32, page_size=8)
