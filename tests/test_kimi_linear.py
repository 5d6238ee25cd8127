"""``kimi_linear`` on the serving path at the tiny size
(``KimiLinearConfig.tiny``: 4 heads — KDA 16 x 16, MLA nope 16 / rope 8 /
v 16, rank 32 — five layers KDA KDA MLA KDA MLA, layer 0 dense, 8 experts
top-3 + 1 shared), float32, on the CPU.

What is compared is what ``benchmark/harness/serve_check.py`` compares on
the chip: every served token's logit in the plain reference
(``benchmark/reference/kimi_linear.py``: the token recurrence itself, which
imports nothing of the program) against that reference's best logit at the
same position, and — with the factory handing out logits — the logits
themselves.

Tolerances, each with its reason.  ``LOGITS`` 2e-4 absolute on logits of
order 1: program and reference both compute in float32 and differ by the
order of their sums (the chunk form's triangular solve against the token
recurrence, the kernel's online softmax over pages, the grouped expert
products), 1e-6..1e-5 here.  ``GAP`` 1e-4 for the same reason: greedy
decoding serves the best token, so a sound run's gap is that rounding.
The int8 control and the planted faults ``state_dropped`` and
``decay_ignored`` move logits by 1e-2 and more and must fail it.
"""
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.nlp import expert_layer as X
from paddle_tpu.models.nlp import kimi_linear as M
from paddle_tpu.models.nlp.kimi_linear import (KimiLinearConfig,
                                               KimiLinearForCausalLM)
from paddle_tpu.ops.pallas.kda_decode import (kda_decode_step,
                                              kda_decode_step_reference)
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.workload import Request

REPO = Path(__file__).resolve().parents[1]
GAP, LOGITS = 1e-4, 2e-4
PAGE = 16


def _reference():
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_plain_reference", REPO / "benchmark/reference/kimi_linear.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _reference()


def draw(cfg, seed=0):
    """Seeded weights: gains near one, matrices wide enough that logits are
    of order 1 and the router's choices are not ties; ``dt_bias`` about -4
    (less the reference's shift, which the loader and the reference add),
    so that a state remembers some tens of tokens."""
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i, (name, shape) in enumerate(M.leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith("dt_bias"):
            tree[name] = -4.0 - R.DT_BIAS_SHIFT + 0.3 * jax.random.normal(k, shape)
        elif name.endswith(("A_log", "e_score_correction_bias")):
            tree[name] = 0.1 * jax.random.normal(k, shape)
        elif len(shape) == 1:
            tree[name] = 1.0 + 0.1 * jax.random.normal(k, shape)
        else:
            tree[name] = jax.random.normal(k, shape) / math.sqrt(shape[-2])
    return tree


def model_dict(cfg, **over):
    keys = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rms_norm_eps", "linear_attn_config", "num_experts_per_token",
            "moe_renormalize", "routed_scaling_factor", "first_k_dense_replace")
    return dict({k: getattr(cfg, k) for k in keys},
                experts_held=cfg.experts_held, **over)


def loaded(cfg, weights):
    net = KimiLinearForCausalLM(cfg)
    net.load_tree({k: R.shift_decay(k, v) for k, v in weights.items()})
    return net


@pytest.fixture(scope="module")
def tiny():
    cfg = KimiLinearConfig.tiny()
    weights = draw(cfg)
    return cfg, loaded(cfg, weights), weights


_LOGITS = {}


def ref_logits(cfg, weights, tokens, quant=None):
    """One pass of the reference a sequence and ``quant`` in the file (the
    planted fault's period, which a test may set, is part of the key).  Each
    pass runs at 128 positions, so that it reuses the operations the first
    one compiled: the reference is causal, and a row sees nothing of the
    zeros after the sequence."""
    key = id(weights), tuple(int(t) for t in tokens), quant, R.STATE_DROP_EVERY
    if key not in _LOGITS:      # the weights ride along: their id stays theirs
        padded = np.zeros(128, np.int32)
        padded[:len(tokens)] = key[1]
        _LOGITS[key] = weights, np.asarray(R.logits(
            model_dict(cfg), weights, jnp.asarray(padded), quant))[:len(tokens)]
    return _LOGITS[key][1]


def tokens_of(n, seed=1, vocab=256):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab))


# -- the two forms of the recurrence ---------------------------------------
def _kda_inputs(B, T, nh, d, seed=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, nh, d))
    k = jax.random.normal(ks[1], (B, T, nh, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, nh, d))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, nh, d)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, nh)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, nh, d, d))


def _token_recurrence(xs, S0):
    def step(S, x):
        q, k, v, g, b = x
        S = jnp.exp(g)[..., None] * S
        u = b[..., None] * (v - jnp.einsum("bhd,bhde->bhe", k, S))
        S = S + k[..., None] * u[..., None, :]
        return S, jnp.einsum("bhd,bhde->bhe", q, S)
    S, o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0) for a in xs))
    return jnp.moveaxis(o, 0, 1), S


@pytest.mark.parametrize("cuts", [(150,), (64, 86), (16, 48, 1, 85), (100, 50),
                                  (3, 64, 64, 19)])
def test_the_chunk_form_is_the_token_recurrence_however_the_calls_split(cuts):
    """A sequence of 150 positions run as calls of any lengths, the state
    carried from one to the next, gives the recurrence's outputs and final
    state."""
    xs, S0 = _kda_inputs(2, 150, 4, 16)
    want_o, want_S = _token_recurrence(xs, S0)
    at, S, outs = 0, S0, []
    for n in cuts:
        o, S = M.kda_chunk_scan(*(a[:, at:at + n] for a in xs), S)
        outs.append(o)
        at += n
    np.testing.assert_allclose(np.concatenate(outs, 1), want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


@pytest.mark.parametrize("n_real", [0, 1, 37, 64])
def test_a_padded_position_leaves_the_state_as_it_was(n_real):
    """g = 0 and beta = 0 at the positions past ``n_real``: the state after
    a 64-position call is the state after its real positions."""
    (q, k, v, g, beta), S0 = _kda_inputs(1, 64, 4, 16, seed=3)
    real = (jnp.arange(64) < n_real)[None, :, None]
    g, beta = jnp.where(real[..., None], g, 0.0), jnp.where(real, beta, 0.0)
    _, S = M.kda_chunk_scan(q, k, v, g, beta, S0)
    _, want = _token_recurrence(tuple(a[:, :n_real] for a in (q, k, v, g, beta)), S0)
    np.testing.assert_allclose(S, want, atol=2e-5)


@pytest.mark.parametrize("shape", [(2, 5, 4, 16, 16, 4), (2, 4, 8, 128, 128, 3)],
                         ids=["tiny", "published_head"])
def test_the_decode_kernel_is_its_oracle_and_an_idle_row_stands(shape):
    L, N, H, dk, dv, B = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    S = jax.random.normal(ks[0], (L, N, H, dk, dv), jnp.float32)
    q, k = (jax.random.normal(ks[i], (B, H, dk)) for i in (1, 2))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (B, H, dv))
    alpha = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    active = jnp.arange(B) != 1
    o, S1 = kda_decode_step(S, 1, q, k, v, alpha, beta, active)
    want_o, want_S = kda_decode_step_reference(S, 1, q, k, v, alpha, beta, active)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(S1, want_S, rtol=1e-5, atol=2e-5)
    assert bool((S1[1, 1] == S[1, 1]).all()) and bool((S1[0] == S[0]).all())
    assert bool((S1[1, B:] == S[1, B:]).all())          # entries behind the rows


# -- the programs against the full forward and the plain reference ---------
def test_the_full_forward_is_the_plain_references_logits(tiny):
    cfg, net, weights = tiny
    toks = tokens_of(100)
    got = np.asarray(net.forward(jnp.asarray(toks)[None]))[0]
    np.testing.assert_allclose(got, ref_logits(cfg, weights, toks), atol=LOGITS)


_PROGRAMS = {}


def _factory(net, **kw):
    """The programs of one build, compiled once in the file, and pools of
    their own for every caller (a call donates them): zeros, as built."""
    args = dict(page_size=PAGE, n_pool_pages=40, n_state_entries=5,
                chunked_prefill=PAGE, emit="logits")
    args.update(kw)
    key = net, repr(sorted(args.items()))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = M.state_paged_decode_factory(net, **args)
        assert not any(a.any() for a in jax.tree_util.tree_leaves(_PROGRAMS[key][2]))
    parts = _PROGRAMS[key]
    return (*parts[:2], jax.tree_util.tree_map(jnp.zeros_like, parts[2]), *parts[3:])


@pytest.mark.parametrize("calls", [(2, 1, 1), (4,), (1, 1, 1, 1), (3, 1)])
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "jnp_step"])
def test_lane_calls_then_decode_n_are_the_references_logits(tiny, calls, kernel):
    """A prompt of 50 tokens prefilled as lane calls of any widths into
    slot 1's entry, then teacher-forced decode steps beside two idle slots:
    the first token's and every step's logits are the plain reference's,
    and the idle slots' states stand."""
    cfg, net, weights = tiny
    toks = tokens_of(70, seed=4)
    want = ref_logits(cfg, weights, toks)
    outer, layers, pools, prefill, step, _ = _factory(net, decode_kernel=kernel)
    W, plen = 256 // PAGE, 50
    pt = np.zeros((1, W + 1), np.int32)
    pt[0, :6], pt[0, -1] = [3, 4, 5, 6, 7, 8], 1
    padded = np.zeros((1, 64), np.int32)
    padded[0, :plen] = toks[:plen]
    at = 0
    for w in calls:
        end = at + w * PAGE
        final = end == 64
        first, pools = prefill.lane_call(
            outer, layers, jnp.asarray(padded[:, at:end]), at, jnp.asarray(pt),
            jnp.asarray([plen if final else end]), pools, final)
        at = end
    np.testing.assert_allclose(np.asarray(first)[0], want[plen - 1], atol=LOGITS)
    tok, lens = np.zeros(3, np.int32), np.zeros(3, np.int32)
    ptd = np.zeros((3, W + 1), np.int32)
    ptd[1] = pt[0]
    idle = np.asarray(pools[1][:, [0, 2]])
    for t in range(plen, 62):
        tok[1], lens[1] = toks[t], t
        logits, pools = step(outer, layers, jnp.asarray(tok), jnp.asarray(ptd),
                             jnp.asarray(lens), pools)
        np.testing.assert_allclose(np.asarray(logits)[1], want[t], atol=LOGITS)
    assert (np.asarray(pools[1][:, [0, 2]]) == idle).all()


def test_decode_n_steps_an_idle_row_never(tiny):
    """``decode_n`` adds one to every row's length a step: a row idle as
    the call starts must stay idle through its steps (a slot whose prompt
    the lane is still running keeps its state)."""
    cfg, net, _ = tiny
    outer, layers, pools, prefill, _, decode_n = _factory(net, emit="token")
    pools = (pools[0], pools[1] + 1.0, pools[2] + 1)
    W = 256 // PAGE
    pt = np.zeros((3, W + 1), np.int32)
    pt[1, :2] = [3, 4]
    lens = np.asarray([0, 5, 0], np.int32)
    before = np.asarray(pools[1])
    _, _, pools = decode_n(outer, layers, jnp.zeros(3, jnp.int32), jnp.asarray(pt),
                           jnp.asarray(lens), pools, 4)
    after = np.asarray(pools[1])
    assert (after[:, [0, 2, 3, 4]] == before[:, [0, 2, 3, 4]]).all()
    assert not (after[:, 1] == before[:, 1]).all()
    counts = decode_n.counts.take()
    assert counts["kind"] == ["decode"] and counts["kda_rows_stepped"] == [4 * 3]
    assert counts["latent_tokens_read"] == [2 * (6 + 7 + 8 + 9)]


# -- through ServingEngine --------------------------------------------------
# One factory a geometry in this file: engines that differ in the clock or
# the prefix cache alone share its compiled programs (``serving=``), built by
# the first engine that needs them.  Engines on one factory run one after
# another (the live pools ride on it, and what a run leaves there no later
# run reads).
_SERVING = {}


def _engine(net, **kw):
    args = dict(slots=3, max_len=256, page_size=PAGE, n_pool_pages=60, policy="paged",
                prefill_chunk_budget=4, n_state_snapshots=4, state_snapshot_every=32,
                clock="fixed")
    args.update(kw)
    key = net, repr(sorted(dict(args, clock=None, prefix_cache=None).items()))
    if key not in _SERVING:
        eng = ServingEngine(net, **args)
        _SERVING[key] = eng.serving
        return eng
    return ServingEngine(serving=_SERVING[key], **args)


def _req(i, prompt, arrival=0.0, n=12):
    return Request(rid=f"r{i}", arrival=arrival, prompt=list(prompt), max_new_tokens=n,
                   prefix_group=None)


def _gaps(cfg, weights, prompt, out):
    ref = ref_logits(cfg, weights, list(prompt) + list(out))
    rows = ref[len(prompt) - 1:len(prompt) - 1 + len(out)]
    return rows.max(-1) - rows[np.arange(len(out)), out]


@pytest.fixture(scope="module")
def served(tiny):
    """Four requests through one engine with the prefix cache: r1 shares 70
    tokens with r0 (a snapshot stands at 64), r3 repeats r0 (one stands at
    its last full page, 80), r2 shares nothing."""
    cfg, net, weights = tiny
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 256, 70).tolist()
    prompts = [shared + rng.integers(0, 256, 21).tolist(),
               shared + rng.integers(0, 256, 9).tolist(),
               rng.integers(0, 256, 40).tolist()]
    prompts.append(prompts[0])
    res = _engine(net).run([_req(i, p, 50.0 * (i > 0) + 50.0 * (i > 2))
                            for i, p in enumerate(prompts)])
    return prompts, res


def test_the_engine_serves_the_plain_references_best_tokens(tiny, served):
    cfg, _, weights = tiny
    prompts, res = served
    for i, p in enumerate(prompts):
        assert len(res.outputs[f"r{i}"]) == 12
        assert _gaps(cfg, weights, p, res.outputs[f"r{i}"]).max() < GAP


def test_a_hit_resumes_from_the_deepest_snapshot_and_serves_the_cold_runs_tokens(tiny, served):
    _, net, _ = tiny
    prompts, res = served
    assert res.prefix_cached == {"r0": 0, "r1": 64, "r2": 0, "r3": 80}
    state = res.cache_stats["state"]
    assert state["state_snapshots_taken"] == 4 and state["prefix_hits_cut_by_snapshot"] == 0
    assert res.cache_stats["invariant_ok"]
    for i, p in enumerate(prompts[:3]):
        cold = _engine(net, prefix_cache=False).run([_req(i, p)])
        assert cold.outputs[f"r{i}"] == res.outputs[f"r{i}"]
        assert "state" in cold.cache_stats and \
            cold.cache_stats["state"]["state_snapshots_taken"] == 0
    assert res.outputs["r3"] == res.outputs["r0"]


def test_a_hit_between_snapshots_recomputes_and_counts(tiny):
    """One snapshot entry: the second request's snapshots overwrite the
    first's, whose pages stay.  The first prompt met again matches five
    pages and finds no snapshot under them: it starts at 0, serves the same
    tokens, and the cut is counted."""
    _, net, _ = tiny
    rng = np.random.default_rng(5)
    a, b = rng.integers(0, 256, 91).tolist(), rng.integers(0, 256, 40).tolist()
    eng = _engine(net, n_state_snapshots=1)
    res = eng.run([_req(0, a), _req(1, b, 50.0), _req(2, a, 100.0)])
    assert res.prefix_cached == {"r0": 0, "r1": 0, "r2": 0}
    assert res.outputs["r2"] == res.outputs["r0"]
    state = res.cache_stats["state"]
    assert state["prefix_hits_cut_by_snapshot"] == 1
    assert state["prefix_tokens_matched"] == state["prefix_tokens_cut_by_snapshot"] == 80
    assert state["state_snapshots_evicted"] >= 2 and res.cache_stats["invariant_ok"]


def test_the_engines_spans_and_counters_of_the_state_kind(tiny, served):
    _, net, _ = tiny
    prompts, _ = served
    res = _engine(net, clock="measured").run(
        [_req(i, p, 0.2 * i) for i, p in enumerate(prompts)])
    ov = res.overhead
    assert ov["phases"]["state.snapshot"]["n"] >= 4 and ov["phases"]["state.restore"]["n"] >= 1
    assert ov["state_snapshots_taken"] >= 3 and ov["prefix_hits_cut_by_snapshot"] == 0
    held, pb = ov["kv_pages_held"], ov["kv_page_bytes"]
    assert held["turns"] > 0 and held["latent"] > 0 and held["state"] > 0
    # 2 latent layers x 16 positions x 128 columns of float32; 3 KDA layers
    assert pb["latent"] == 2 * 16 * 128 * 4 and pb["latent_all_layers"] == 5 * 16 * 128 * 4
    assert pb["state"] == 3 * (4 * 4 * 16 * 16 + 4 * 3 * 3 * 64)
    counts = ov["model_counts"]
    assert set(M.CALL_COUNTS) | {"kind"} == set(counts)
    chunk = [n for k, n in zip(counts["kind"], counts["kda_chunk_positions"]) if k == "prefill"]
    # every prompt position not resumed past went into 3 KDA layers' states
    cached = sum(res.prefix_cached.values())
    assert sum(chunk) == 3 * (sum(len(p) for p in prompts) - cached)
    assert sum(counts["kda_rows_stepped"]) > 0 and sum(counts["latent_tokens_read"]) > 0


# -- the refusals -----------------------------------------------------------
@pytest.mark.parametrize("option", [
    dict(tp=2), dict(kv_quant="int8"), dict(kv_cache_dtype="int8"), dict(spec=True),
    dict(dispatch_ahead=True), dict(ragged_prefill=True), dict(prefill_chunk_budget=None),
    dict(lora=(2, 4))])
def test_what_the_state_does_not_compose_with_is_refused_by_name(tiny, option):
    _, net, _ = tiny
    with pytest.raises(ValueError, match="latent\\+state cache") as err:
        _engine(net, **option)
    named = "prefill_outside_the_lane" if "prefill_chunk_budget" in option else next(iter(option))
    assert named in str(err.value)


def test_the_layouts_share_one_refusal_table_and_keep_their_messages(tiny):
    from paddle_tpu.serving import engine as E
    assert set(E._LAYOUT_REFUSES) == {"latent", "windowed", "latent+state"}
    assert E._LAYOUT_REFUSES["latent"][0] is E._LATENT_REFUSES
    assert E._LAYOUT_REFUSES["windowed"][0] is E._WINDOWED_REFUSES
    E._refuse_layout("head_major", tp=2)               # refuses nothing
    E._refuse_layout("latent", tp=None)
    for layout in E._LAYOUT_REFUSES:
        with pytest.raises(ValueError, match="kv_handoff_export"):
            E._refuse_layout(layout, kv_handoff_export=True)
    _, net, _ = tiny
    with pytest.raises(ValueError, match="this model keeps no state entry"):
        from paddle_tpu.models.nlp.deepseek_v3 import (DeepseekV3Config,
                                                       DeepseekV3ForCausalLM)
        ServingEngine(DeepseekV3ForCausalLM(DeepseekV3Config.tiny()), slots=2,
                      max_len=64, page_size=16, n_state_snapshots=2)
    eng = _engine(net)
    with pytest.raises(ValueError, match="latent\\+state cache"):
        eng.export_kv_pages([1])


# -- the book's state kind --------------------------------------------------
def _book(snapshots=2):
    return PagedKVCache(20, 4, kv_heads=1, head_dim=1, state_slots=3,
                        state_snapshots=snapshots)


def test_the_books_snapshots_are_keyed_pinned_recycled_and_dropped_with_their_chain():
    book, toks = _book(), list(range(100, 117))
    assert book.acquire_prefix("a", toks) == 0 and book.state_resume("a") is None
    book.allocate("a", 17)
    assert book.state_snapshot("a", toks, 8) == 3        # the first entry behind the slots
    assert book.state_snapshot("a", toks, 8) is None     # it has its snapshot: touched
    assert book.state_snapshot("a", toks, 16) == 4
    assert book.census_ok() and book.cache_stats()["state"]["free_snapshots"] == 0
    # a second sequence: 4 pages match, a hit never takes the whole prompt
    assert book.match_prefix(toks) == 16
    assert book.acquire_prefix("b", toks) == 16
    entry = book.state_resume("b")
    assert entry == 4 and book.cache_stats()["state"]["pinned_snapshots"] == 1
    # with one pinned, a new snapshot overwrites the other (least recently used)
    other = list(range(200, 209))
    book.acquire_prefix("c", other)
    book.allocate("c", 9)
    assert book.state_snapshot("c", other, 8) == 3 and book._state.evicted == 1
    book.state_resumed(entry)
    assert book.cache_stats()["state"]["pinned_snapshots"] == 0 and book.census_ok()
    # a hit deeper than any snapshot is cut back to the deepest one and counted
    assert book.match_prefix(toks[:13]) == 0             # cap 3 pages: the one at 8 is gone
    assert book.acquire_prefix("d", toks[:13]) == 0
    st = book.cache_stats()["state"]
    assert st["prefix_hits_cut_by_snapshot"] == 1 and st["prefix_tokens_cut_by_snapshot"] == 12
    book.rollback_acquire("d", toks[:13])
    assert book.cache_stats()["state"]["prefix_hits_cut_by_snapshot"] == 0
    # the chain evicted under pressure: its snapshots go with their pages
    for s in "abc":
        book.free(s)
    book.allocate("big", 19 * 4)
    assert book._state.populations() == (0, 0, 2) and book.census_ok()
    book.purge()
    assert book.census_ok() and book.match_prefix(toks) == 0


def test_a_book_without_the_kind_is_what_it_was():
    book = PagedKVCache(20, 4, kv_heads=1, head_dim=1)
    assert book._state is None and "state" not in book.cache_stats()
    toks = list(range(17))
    book.acquire_prefix("a", toks)
    book.allocate("a", 17)
    book.register_prefix("a", toks)
    assert book.match_prefix(toks) == 16 and book.census_ok()


# -- a chip's share of the experts -----------------------------------------
def test_the_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The routed results of ``held`` = each quarter of the experts in
    turn, and the shared expert once, add up to the plain reference's uncut
    layer of this model (every expert held)."""
    cfg = KimiLinearConfig.tiny(num_experts=16, num_experts_per_token=4)
    whole = {k: v for k, v in draw(cfg, seed=3).items() if k.startswith("model.layers.1.")}
    lp = {k[len("model.layers.1."):]: v for k, v in whole.items()}
    x = jax.random.normal(jax.random.PRNGKey(9), (40, cfg.hidden_size))
    total, pairs = X.shared_part(lp, x).astype(jnp.float32), 0
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        part = dict(lp, **{k: lp[k][4 * share:4 * share + 4] for k in X.EXPERT_KEYS})
        y, counts = X.routed_part(cfg, part, x, held=held)
        want = R.routed_part(model_dict(cfg), part, x, None, held=held)
        np.testing.assert_allclose(y, want, atol=2e-5)
        total, pairs = total + y, pairs + int(counts[0])
    assert pairs == 40 * 4                     # every pair is computed on one chip
    uncut = R.routed_part(model_dict(cfg), lp, x, None) + R.shared_part(lp, x, None)
    np.testing.assert_allclose(total, uncut, atol=5e-5)


def test_the_model_with_a_share_of_the_experts_is_the_reference_with_that_share():
    cfg = KimiLinearConfig.tiny(num_experts=16, num_experts_per_token=4,
                                experts_held=(4, 5, 6, 7))
    weights = draw(cfg, seed=6)
    assert weights["model.layers.1.mlp.experts.gate_proj"].shape[0] == 4
    assert weights["model.layers.1.mlp.gate.weight"].shape[1] == 16
    toks = tokens_of(48, seed=8)
    got = np.asarray(loaded(cfg, weights).forward(jnp.asarray(toks)[None]))[0]
    np.testing.assert_allclose(got, ref_logits(cfg, weights, toks), atol=LOGITS)


# -- the reference's control and its planted faults ------------------------
@pytest.mark.parametrize("quant", ["int8", "state_dropped", "decay_ignored"])
def test_the_control_and_the_planted_faults_fail_the_tolerances(tiny, quant, monkeypatch):
    """What the altered reference puts first is far from the plain
    reference's best: by more than ``GAP`` somewhere, as a lower precision
    or a lost or undecayed state must be."""
    cfg, _, weights = tiny
    monkeypatch.setattr(R, "STATE_DROP_EVERY", 32)
    toks = tokens_of(120, seed=11)
    plain = ref_logits(cfg, weights, toks)
    other = ref_logits(cfg, weights, toks, quant)
    assert np.abs(other - plain).max() > 50 * LOGITS
    judged = other.argmax(-1)
    gaps = plain.max(-1) - plain[np.arange(len(toks)), judged]
    assert gaps.max() > 10 * GAP
    if quant == "state_dropped":       # nothing is dropped before position 32
        np.testing.assert_allclose(other[:32], plain[:32], atol=1e-6)
