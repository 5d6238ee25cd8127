"""``laguna``'s serving counts at the tiny size, beside ``test_laguna.py``
(a file of their own, so that another worker runs them): one ``decode_n``
program across churn on a factory of the test's own, the counts that ride
with its calls, and the counters and keys other models lack.
"""
import jax
import numpy as np
import pytest

from paddle_tpu.models.nlp import laguna as L
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.workload import Request

from test_laguna import engine, tiny  # noqa: F401  (the fixture is collected by name)


def test_decode_n_compiles_once_across_churn_and_the_counts_ride(tiny):
    cfg, net, weights = tiny
    eng = engine(net, own=True)
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
    eng2 = ServingEngine(serving=eng.serving, slots=4, policy="paged",
                         prefill_chunk_budget=2, clock="measured")
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
