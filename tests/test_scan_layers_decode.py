"""Scan-over-stacked-layers decode programs (round-5 verdict item 3).

The decode factories run one ``lax.scan`` layer body over stacked
(L, ...) weights; ``scan_layers=False`` unrolls the layers. Both paths
must be TOKEN-EXACT equal (same math, different program structure), the
scan program must be materially smaller, and — the 0.44B compile fix —
the speculative programs must carry weights as jit ARGUMENTS, never as
closure constants inlined into the lowered module.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.nlp.llama_decode import (
    llama_decode_factory, llama_speculative_decode_factory)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=4, heads=4,
                           kv_heads=2)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(0).integers(0, 97, (2, 6)).astype(
        np.int32)


def test_stack_unstack_roundtrip(model):
    from paddle_tpu.models.nlp.llama_functional import (
        split_params, stack_layers, unstack_layers)
    _, layers = split_params(model)
    per = unstack_layers(layers)
    assert len(per) == model.config.num_hidden_layers
    back = stack_layers(per)
    for k in layers:
        np.testing.assert_array_equal(np.asarray(layers[k]),
                                      np.asarray(back[k]))


class TestDenseParity:
    def test_generate_and_compiled_token_exact(self, model, prompt):
        gen_s = llama_decode_factory(model, max_len=48, scan_layers=True)
        gen_u = llama_decode_factory(model, max_len=48,
                                     scan_layers=False)
        a = np.asarray(gen_s(jnp.asarray(prompt), max_new_tokens=12))
        b = np.asarray(gen_u(jnp.asarray(prompt), max_new_tokens=12))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(gen_s.compiled(prompt, 12),
                                      gen_u.compiled(prompt, 12))

    def test_int8_cache_parity(self, model, prompt):
        gen_s = llama_decode_factory(model, max_len=48,
                                     kv_cache_dtype="int8")
        gen_u = llama_decode_factory(model, max_len=48,
                                     kv_cache_dtype="int8",
                                     scan_layers=False)
        a = np.asarray(gen_s(jnp.asarray(prompt), max_new_tokens=10))
        b = np.asarray(gen_u(jnp.asarray(prompt), max_new_tokens=10))
        np.testing.assert_array_equal(a, b)

    def test_scan_program_smaller(self, model):
        """The whole point of the stacking: the unrolled decode step
        lowers every layer's body; the scan variant lowers ONE."""
        sizes = {}
        for flag in (True, False):
            gen = llama_decode_factory(model, max_len=32,
                                       scan_layers=flag)
            p = gen._parts
            tok = jnp.zeros((1,), jnp.int32)
            kc = p["init_caches"](1, jnp.float32)
            vc = p["init_caches"](1, jnp.float32)
            low = p["decode_step"].lower(p["outer"], p["layers"], tok,
                                         jnp.asarray(4), kc, vc)
            sizes[flag] = len(low.as_text())
        # at L=4 the layer part dominates: unrolled must be well over
        # the scan size (exact ratio drifts with jax versions)
        assert sizes[False] > 1.5 * sizes[True], sizes


class TestSpeculativeParity:
    def _models(self):
        paddle.seed(31)
        t = LlamaForCausalLM(LlamaConfig.tiny(
            vocab=97, hidden=64, layers=3, heads=4, kv_heads=2))
        t.eval()
        paddle.seed(32)
        d = LlamaForCausalLM(LlamaConfig.tiny(
            vocab=97, hidden=32, layers=1, heads=2, kv_heads=2))
        d.eval()
        return t, d

    def test_compiled_spec_scan_vs_unrolled_vs_oracle(self):
        t, d = self._models()
        prompt = np.asarray(
            np.random.default_rng(2).integers(0, 97, (1, 6)), np.int32)
        oracle = np.asarray(llama_decode_factory(t, max_len=64)(
            prompt, max_new_tokens=20))
        spec_s = llama_speculative_decode_factory(t, d, max_len=64,
                                                  n_draft=4)
        spec_u = llama_speculative_decode_factory(t, d, max_len=64,
                                                  n_draft=4,
                                                  scan_layers=False)
        got_s = spec_s.compiled(prompt, max_new_tokens=20)
        got_u = spec_u.compiled(prompt, max_new_tokens=20)
        np.testing.assert_array_equal(got_s, got_u)
        # greedy spec == the target's greedy generation, both paths
        np.testing.assert_array_equal(got_s, oracle)

    def test_spec_module_carries_no_weight_constants(self):
        """THE 0.44B compile fix: weights travel as jit arguments.  A
        closed-over array lowers as an inline literal, so the two-model
        module used to scale with model bytes (~1 GB at 0.44B — what
        actually broke the remote compile service); as arguments the
        module stays ~100 KB at ANY model size. Pin the property by
        asserting the lowered module text is a small fraction of the
        weight bytes it would otherwise embed."""
        t, d = self._models()
        spec = llama_speculative_decode_factory(t, d, max_len=64,
                                                n_draft=4)
        sp = spec._parts
        tokens = jnp.zeros((1, 6), jnp.int32)
        state = jax.eval_shape(sp["spec_prefill"], sp["params"], tokens)
        low = sp["spec_chunk"].lower(sp["params"], state, 4,
                                     jnp.asarray(20, jnp.int32))
        module_bytes = len(low.as_text())
        weight_bytes = sum(
            leaf.size * leaf.dtype.itemsize for leaf in
            jax.tree_util.tree_leaves(sp["params"]))
        # inline f32 literals render at >2 text bytes per weight byte;
        # an argument-passing module is untouched by model size
        assert module_bytes < weight_bytes / 2, (module_bytes,
                                                 weight_bytes)


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scanned", "unrolled"])
def test_stack_carry_counts_layers_and_keeps_state(scan_layers):
    """The carrying form of the layer loop: the body is told its layer
    (a traced counter under the scan, a Python int unrolled), reads its
    slice of the stacked operands, and what it returns is the next
    layer's state — nothing is stacked."""
    from paddle_tpu.models.nlp.llama_decode import _stack_carry
    w = jnp.arange(12.0).reshape(4, 3)
    seen = []

    def body(i, carry, per_layer):
        seen.append(i)
        x, book = carry
        return x + per_layer["w"].sum(), book.at[i].set(x)

    x, book = jax.jit(lambda w: _stack_carry(
        body, (jnp.float32(1.0), jnp.zeros((4,))), {"w": w},
        scan_layers))(w)
    assert float(x) == 1.0 + float(w.sum())
    np.testing.assert_array_equal(np.asarray(book), [1., 4., 16., 37.])
    if scan_layers:
        assert len(seen) == 1 and isinstance(seen[0], jax.core.Tracer)
    else:
        assert seen == [0, 1, 2, 3]
