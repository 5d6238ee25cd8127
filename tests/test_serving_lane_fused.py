"""The prefill lane's fused call: the chunks that consecutive picks would
hand ONE request run as ONE program call (``prefill.lane_call``), up to the
turn's budget and the widest call the factory states.

What is held here, on the CPU at toy sizes: the call changes the tiling of a
prompt and nothing else.  For each of the three factories (the Llama gather
path, the latent-cache expert model, the two-kind window model) an engine
with a budget of 4 serves the tokens, in the (request, chunk) order and at
the virtual times, of the same engine held to one chunk a call; every width
is compiled when the engine is built (where the factory states that it
pads, the widest alone, and a narrower span rides it padded: the tokens are
still those of one chunk a call); one clock span and one entry of the
factory's own counts stand for one program call; the two-kind cache's pages
are published before they are given back; and the counters say how often
the call engaged.
"""
import math

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.nlp import deepseek_v3 as D
from paddle_tpu.models.nlp import laguna as L
from paddle_tpu.serving import (EngineClock, Request, ServingEngine,
                                make_sim_serving)
from paddle_tpu.serving.engine import _jit_cache_size

COSTS = {"prefill_unit": 0.3, "decode": 0.1}     # no binary fractions: sums round
LIMIT = ServingEngine._LANE_STARVE_LIMIT


def _seeded(shapes, seed=0):
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i, (name, shape) in enumerate(shapes.items()):
        k = jax.random.fold_in(key, i)
        if name.endswith("e_score_correction_bias"):
            tree[name] = 0.05 * jax.random.normal(k, shape)
        elif len(shape) == 1:
            tree[name] = 1.0 + 0.1 * jax.random.normal(k, shape)
        else:
            tree[name] = jax.random.normal(k, shape) / math.sqrt(shape[-2])
    return tree


def _llama():
    paddle.seed(0)
    net = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256))
    net.eval()
    return net


def _latent():
    cfg = D.DeepseekV3Config.tiny()
    net = D.DeepseekV3ForCausalLM(cfg)
    net.eval()
    net.load_tree(_seeded(D.leaf_shapes(cfg)))
    return net


def _windowed():
    cfg = L.LagunaConfig.tiny()
    net = L.LagunaForCausalLM(cfg)
    net.load_tree(_seeded(L.leaf_shapes(cfg)))
    return net


# factory -> (model, page size, chunks of the long prompt, the widest call
# the engine arrives at under a budget of 4, further engine arguments)
FACTORIES = {
    "llama": (_llama, 4, 30, 4, {}),
    "latent": (_latent, 8, 30, 4, {}),
    "windowed": (_windowed, 4, 18, 2, {"n_window_pages": 8 * 6 + 1 + 24}),
}


# the two-kind model's cases are collected by
# test_serving_lane_fused_windowed.py (a file of their own: another worker)
@pytest.fixture(scope="module", params=["latent", "llama"])
def factory(request):
    build, page, long_chunks, widest, kw = FACTORIES[request.param]
    return request.param, build(), page, long_chunks, widest, kw


# One factory a geometry and model: engines that differ in the clock, its
# costs or the tracer alone share its compiled programs (``serving=``), built
# by the first engine that needs them.  Engines on one factory run one after
# another (the live pools ride on it, and what a run leaves there no later
# run reads).
_SERVING = {}


def _engine(net, page, own=False, **kw):
    """An engine on the factory of its model and geometry; ``own``: on a
    factory of its own (a test that counts compiles)."""
    args = dict(slots=8, max_len=page * 48, page_size=page, policy="paged",
                prefill_chunk_budget=4, clock="fixed", fixed_costs=COSTS)
    args.update(kw)
    key = net, repr(sorted(dict(args, clock=None, fixed_costs=None, trace=None).items()))
    if own or key not in _SERVING:
        eng = ServingEngine(net, **args)
        if not own:
            _SERVING[key] = eng.serving
        return eng
    return ServingEngine(serving=_SERVING[key], **args)


def _trace(page, long_chunks, seed=3):
    """A long prompt first (the lane's oldest entry, passed over until the
    aging rule hands it a chunk), prompts of 1, 2, 3 and 4 chunks beside it
    and after it (every remainder a call can take), and a prompt asked
    again once its pages are published (the prefix-cache resume)."""
    rng = np.random.default_rng(seed)

    def mk(rid, at, n_tokens, new=4, head=()):
        own = tuple(int(t) for t in rng.integers(1, 250, n_tokens - len(head)))
        return Request(rid=rid, arrival=at, prompt=tuple(head) + own,
                       max_new_tokens=new, prefix_group=None)
    doc = tuple(int(t) for t in rng.integers(1, 250, 3 * page))
    reqs = [mk("a_long", 0.0, long_chunks * page - 1)]
    # beside the long prompt: 1 + 2 + 3 + 4 + 3 + 2 = 15 chunks > the limit
    reqs += [mk(f"s{i}", 0.0, n * page - (i % 3), new=3)
             for i, n in enumerate((1, 2, 3, 4, 3, 2))]
    reqs += [mk("doc1", 0.0, 4 * page + 2, head=doc)]
    # alone in the lane, one remainder each
    reqs += [mk(f"t{n}", 40.0 + 9.0 * n, n * page - 1) for n in (1, 2, 3, 4)]
    reqs += [mk("doc2", 90.0, 5 * page - 1, head=doc)]
    return reqs


def _order(res):
    """The (request, chunk) sequence the lane computed, and its calls."""
    calls = [(e["args"]["rid"], e["args"]["chunk"], e["args"]["width"])
             for e in res.trace.to_chrome()["traceEvents"]
             if e["name"] == "prefill" and e.get("ph") == "X"]
    return [(rid, k + i) for rid, k, w in calls for i in range(w)], calls


@pytest.fixture(scope="module")
def pair(factory):
    """The same trace through a budget of 4 and through the same engine held
    to one chunk a call."""
    name, net, page, long_chunks, widest, kw = factory
    reqs = _trace(page, long_chunks)
    fused = _engine(net, page, trace=obs.Tracer(), **kw)
    assert fused._lane_widest == widest
    single = _engine(net, page, trace=obs.Tracer(), **kw)
    single._lane_widest = 1
    return reqs, fused.run(reqs), single.run(reqs), widest, page


def test_a_budget_of_four_serves_the_tokens_of_one_chunk_a_call(pair):
    reqs, fused, single, widest, page = pair
    assert all(len(fused.outputs[r.rid]) == r.max_new_tokens for r in reqs)
    assert fused.outputs == single.outputs
    assert fused.prefix_cached == single.prefix_cached
    assert fused.prefix_cached["doc2"] >= 2 * page      # the resume ran
    assert fused.prefill_tokens == single.prefill_tokens
    assert fused.cache_stats["invariant_ok"]


def test_the_request_chunk_order_is_unchanged_and_the_aging_rule_trips(pair):
    reqs, fused, single, widest, page = pair
    seq, calls = _order(fused)
    seq1, calls1 = _order(single)
    assert seq == seq1
    assert all(w == 1 for _, _, w in calls1)
    widths = {w for _, _, w in calls}
    assert widths == set(range(1, widest + 1))           # every width ran
    # the long prompt got chunks while shorter ones stood in the lane:
    # its first chunk comes after exactly LIMIT chunks of others, and
    # before the last of the short prompts
    first_long = next(i for i, (rid, _) in enumerate(seq) if rid == "a_long")
    assert first_long == LIMIT
    last_short = max(i for i, (rid, _) in enumerate(seq) if rid.startswith("s"))
    assert first_long < last_short
    # a call never runs past the pick at which the oldest's turn comes: the
    # chunks before the long prompt's first are LIMIT whatever the widths
    assert sum(w for rid, _, w in calls[:[c[0] for c in calls].index("a_long")]) == LIMIT


def test_the_virtual_time_of_the_trace_is_unchanged(pair):
    """To the bit: a call of w chunks is charged its w chunks one after
    another (0.3 a chunk: sums that round)."""
    reqs, fused, single, widest, page = pair
    assert fused.metrics.request_rows() == single.metrics.request_rows()
    assert fused.report() == single.report()
    assert fused.slot_log == single.slot_log


@pytest.mark.parametrize("costs", [{"prefill": 1.7, "decode": 0.1},
                                   {"prefill_unit": 0.3, "decode": 0.1}],
                         ids=["flat", "unit"])
def test_fixed_clock_pricing_is_the_chunk_a_call_loops_on_the_sim(costs):
    """The flat per-call cost is split over a prompt's chunks (1.7 / 7 and
    the like), the unit cost charged a chunk: both as the loop charged."""
    def run(widest):
        eng = ServingEngine(
            serving=make_sim_serving(max_len=256, page_size=8, slots=8, vocab=101),
            slots=8, policy="paged", clock="fixed", fixed_costs=costs,
            prefill_chunk_budget=4)
        assert eng._lane_widest == 4                     # the sim states no limit
        eng._lane_widest = widest
        return eng.run(_trace(8, 24, seed=5))
    fused, single = run(4), run(1)
    assert fused.outputs == single.outputs
    assert fused.metrics.request_rows() == single.metrics.request_rows()
    assert fused.report() == single.report()


def test_every_width_is_compiled_when_the_engine_is_built(factory):
    """A first run of one-chunk prompts compiles the decode program; a run
    with every remainder then compiles nothing."""
    name, net, page, long_chunks, widest, kw = factory
    eng = _engine(net, page, own=True, **kw)
    chunk_program, finish = eng._p_prefill._jit_inner
    # a program a width, or (the Llama gather path states that it pads) the
    # widest alone, which narrower spans ride padded
    programs = 1 if name == "llama" else widest
    assert eng._lane_widths == ((widest,) if name == "llama"
                                else tuple(range(1, widest + 1)))
    assert chunk_program._cache_size() == programs and finish._cache_size() == 1
    rng = np.random.default_rng(0)
    eng.run([Request(rid=f"w{i}", arrival=0.0, max_new_tokens=3, prefix_group=None,
                     prompt=tuple(int(t) for t in rng.integers(1, 250, page - 1)))
             for i in range(2)])
    before = eng._ctr_compiles.value
    res = eng.run(_trace(page, long_chunks))
    assert eng._ctr_compiles.value == before
    assert chunk_program._cache_size() == programs and finish._cache_size() == 1
    assert all(len(out) > 0 for out in res.outputs.values())


def test_a_second_engine_on_a_prebuilt_factory_compiles_nothing():
    """What the serving tests build on (``_engine``): a factory passed as
    ``serving=`` shares its compiled programs, so that an engine on it adds
    no entry to the ``decode_n`` or lane programs' caches, neither while it
    is built (every lane width is called then) nor while it runs."""
    net, page = _llama(), 4
    reqs = _trace(page, 30)
    first = _engine(net, page, own=True)
    res = first.run(reqs)

    def sizes(eng):
        return [_jit_cache_size(p) for p in (eng._p_decode_n, *eng._p_prefill._jit_inner)]
    built = sizes(first)
    second = ServingEngine(serving=first.serving, slots=8, policy="paged",
                           prefill_chunk_budget=4, clock="fixed", fixed_costs=COSTS)
    assert sizes(second) == built
    compiles = second._ctr_compiles.value
    assert second.run(reqs).outputs == res.outputs
    assert sizes(second) == built and second._ctr_compiles.value == compiles


def test_a_padded_span_at_the_tables_end_lands_on_the_padding_page():
    """The padding factory's one program is 4 chunks wide.  A prompt that
    fills its table to the last page but one ends on a call of ONE chunk at
    page 14 of 16: the padded span's pages 16 and 17 are not the table's.
    The lane's table is widened by as many columns of the padding page, so
    the program's page slice is not clamped onto pages 12 to 15 (which
    would put the chunk's keys where the prompt's earlier ones are)."""
    net, page = _llama(), 4
    rng = np.random.default_rng(9)
    mk = lambda rid, n: Request(  # noqa: E731
        rid=rid, arrival=0.0, max_new_tokens=3, prefix_group=None,
        prompt=tuple(int(t) for t in rng.integers(1, 250, n)))
    reqs = [mk("a", 15 * page - 2), mk("b", 2 * page - 1)]   # b first: 2 + 2, then 4 + 4 + 4 + 1

    def run(widest):
        eng = _engine(net, page, max_len=16 * page, slots=2, trace=obs.Tracer())
        assert eng._lane_widths == (4,) and eng._lane_pad_cols == 3
        eng._lane_widest = widest
        return eng.run(reqs)
    fused, single = run(4), run(1)
    calls = _order(fused)[1]
    assert calls[-1] == ("a", 14, 1)                      # pages 14 ... 17 in the program
    assert fused.outputs == single.outputs
    assert all(len(fused.outputs[r.rid]) == 3 for r in reqs)


class _SpanClock(EngineClock):
    """A measured clock that keeps a span a call, as the benchmark's does:
    (kind, units)."""

    def __init__(self):
        super().__init__("measured")
        self.spans = []

    def timed(self, kind, fn, units=None, cost=None):
        out = super().timed(kind, fn, units, cost)
        self.spans.append((kind, units))
        return out


def test_one_span_and_one_count_a_program_call(factory):
    """What the benchmark's readers line up: the clock's ``prefill`` and
    ``decode`` spans against the entries of ``overhead["model_counts"]``, a
    ``prefill`` span of ``units`` u being u entries.  A fused call is one
    span of ``units`` 1 and, where the factory counts, one entry."""
    name, net, page, long_chunks, widest, kw = factory
    clock = _SpanClock()
    eng = _engine(net, page, clock=clock, fixed_costs=None, **kw)
    ov = eng.run(_trace(page, long_chunks)).overhead
    spans = [(k, u) for k, u in clock.spans if k in ("prefill", "decode")]
    assert all(u == 1 for k, u in spans if k == "prefill")
    assert [k for k, _ in spans].count("prefill") == ov["lane_calls"] \
        == ov["calls"]["prefill"]["n"]
    want = sorted(w for w, n in ov["lane_calls_by_width"].items() for _ in range(n))
    assert ov["lane_chunks"] == sum(want) and set(want) == set(range(1, widest + 1))
    if name == "llama":
        assert "model_counts" not in ov                  # it keeps no counts
        return
    counts = ov["model_counts"]
    assert [k for k, _ in spans] == counts["kind"]
    # a call's pairs are its positions': the widths in the counts themselves
    per_chunk = page * net.config.num_experts_per_tok    # pairs a chunk and expert layer
    got = sorted(pairs // (per_chunk * layers) for kind, pairs, layers
                 in zip(counts["kind"], counts["pairs"], counts["layer_calls"])
                 if kind == "prefill")
    assert got == want


@pytest.mark.parametrize("budget", [1, 2, 3, 4])
def test_the_counters_are_what_the_prompts_lengths_say(budget):
    """Prompts that meet no other in the lane: one of n chunks is
    ceil(n / budget) calls, and ``lane_chunks / lane_calls`` follows."""
    chunks = [1, 2, 3, 4, 5, 7, 8, 11]
    rng = np.random.default_rng(2)
    reqs = [Request(rid=f"r{i}", arrival=1000.0 * i, max_new_tokens=2, prefix_group=None,
                    prompt=tuple(int(t) for t in rng.integers(1, 100, 8 * n - 3)))
            for i, n in enumerate(chunks)]
    eng = ServingEngine(
        serving=make_sim_serving(max_len=128, page_size=8, slots=4, vocab=101),
        slots=4, policy="paged", clock="measured", prefill_chunk_budget=budget,
        prefix_cache=False)
    ov = eng.run(reqs).overhead
    want = {}
    for n in chunks:
        for w in [budget] * (n // budget) + [n % budget] * bool(n % budget):
            want[w] = want.get(w, 0) + 1
    assert ov["lane_calls_by_width"] == dict(sorted(want.items()))
    assert ov["lane_chunks"] == sum(chunks) and ov["lane_calls"] == sum(want.values())
    assert ov["lane_calls"] == ov["calls"]["prefill"]["n"]
    if budget == 4:
        assert ov["lane_chunks"] / ov["lane_calls"] == 41 / 13


def test_the_counters_are_absent_without_the_lane_and_on_fixed_clocks():
    reqs = [Request(rid="x", arrival=0.0, prompt=tuple(range(1, 20)), max_new_tokens=2,
                    prefix_group=None)]
    sim = lambda: make_sim_serving(max_len=64, page_size=8, slots=2, vocab=101)  # noqa: E731
    res = ServingEngine(serving=sim(), slots=2, policy="paged", clock="measured").run(reqs)
    assert not {"lane_calls", "lane_chunks", "lane_calls_by_width"} & set(res.overhead)
    res = ServingEngine(serving=sim(), slots=2, policy="paged", clock="fixed",
                        prefill_chunk_budget=2).run(reqs)
    assert res.overhead is None
