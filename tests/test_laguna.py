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


# One factory a geometry in this file: engines that differ in the clock alone
# share its compiled programs (``serving=``), built by the first engine that
# needs them.  Engines on one factory run one after another (the live pools
# ride on it, and what a run leaves there no later run reads).
_SERVING = {}


def engine(net, own=False, **over):
    """An engine on the file's factory of its geometry; ``own``: on a
    factory of its own (a test that counts compiles)."""
    args = dict(slots=4, max_len=96, page_size=4, n_pool_pages=120,
                policy="paged", prefill_chunk_budget=2, clock="fixed",
                n_window_pages=4 * 4 + 1 + 8)
    args.update(over)

    def key(n_window_pages):
        return net, repr(sorted(dict(args, clock=None, n_window_pages=n_window_pages).items()))
    if own or key(args["n_window_pages"]) not in _SERVING:
        eng = ServingEngine(net, **args)
        if not own:     # under the pool size asked for and the one taken (None: the floor)
            _SERVING[key(args["n_window_pages"])] = _SERVING[key(eng.n_window_pages)] = eng.serving
        return eng
    return ServingEngine(serving=_SERVING[key(args["n_window_pages"])], **args)


_LOGITS = {}


def ref_logits(cfg, weights, tokens, quant=None):
    """The reference's logits over ``tokens``, one pass a sequence and
    ``quant`` in the file (the gap checks and the int8 control share it).
    Each pass runs at the engines' ``max_len``, so that it reuses the
    operations the first one compiled: the reference is causal, and a row
    sees nothing of the zeros after the sequence."""
    key = id(weights), tuple(int(t) for t in tokens), quant
    if key not in _LOGITS:      # the weights ride along: their id stays theirs
        padded = np.zeros(96, np.int32)
        padded[:len(tokens)] = key[1]
        _LOGITS[key] = weights, np.asarray(R.logits(
            model_dict(cfg), weights, jnp.asarray(padded), quant))[:len(tokens)]
    return _LOGITS[key][1]


def gaps(cfg, weights, reqs, outputs, quant=None):
    """Each served token's gap under the reference's best, per request."""
    out = {}
    for r in reqs:
        served = outputs[r.rid]
        ref = ref_logits(cfg, weights, list(r.prompt) + served, quant)
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
    worst = 0.0
    for r in reqs:
        seq = list(r.prompt) + res.outputs[r.rid]
        plain = ref_logits(cfg, weights, seq)
        lower = ref_logits(cfg, weights, seq, "int8")
        p, n = len(r.prompt), len(res.outputs[r.rid])
        rows, judged = plain[p - 1:p - 1 + n], lower[p - 1:p - 1 + n].argmax(-1)
        worst = max(worst, float((rows.max(-1) - rows[np.arange(n), judged]).max()))
    assert worst > 10 * GAP














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
