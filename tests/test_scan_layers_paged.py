"""The paged decode factories' two forms of the layer loop (scan over
stacked layers, and unrolled), beside ``test_scan_layers_decode.py`` (a file
of their own, so that another worker runs them: each case builds both forms
of a paged factory, with the kernel interpreted on the CPU).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.nlp.llama_decode import llama_paged_decode_factory

from test_scan_layers_decode import model, prompt  # noqa: F401  (fixtures are collected by name)


class TestPagedParity:
    @pytest.mark.parametrize("build", [
        dict(chunked_prefill=8),
        dict(chunked_prefill=8, prefill_attention="kernel"),
        dict(chunked_prefill=8, kv_cache_dtype="int8"),
        dict(kv_quant="pressure"),
    ], ids=["chunked", "chunked_kernel", "chunked_int8", "pressure"])
    def test_pool_contents_scan_vs_unrolled(self, model, prompt, build):
        """Both forms of the layer loop address the carried pools by
        (layer, page): after a prefill and a ``decode_n`` the tokens AND
        every pool leaf are equal, bit for bit."""
        outs = {}
        for flag in (True, False):
            outer, layers, pools, prefill, _, decode_n = \
                llama_paged_decode_factory(model, page_size=8,
                                           n_pool_pages=32,
                                           scan_layers=flag, **build)
            pt = jnp.asarray(
                np.arange(1, 9, dtype=np.int32).reshape(2, 4))
            lens = jnp.asarray([6, 5], jnp.int32)
            tok = jnp.asarray(np.pad(prompt, ((0, 0), (0, 2))))
            nxt, pools = prefill(outer, layers, tok, pt, lens, pools)
            emits, _, pools = decode_n(outer, layers, nxt, pt, lens,
                                       pools, 4)
            outs[flag] = [np.asarray(nxt), np.asarray(emits)] + [
                np.asarray(a) for a in jax.tree_util.tree_leaves(pools)]
        for a, b in zip(outs[True], outs[False]):
            np.testing.assert_array_equal(a, b)

    def test_prefill_decode_token_exact(self, model, prompt):
        outs = {}
        for flag in (True, False):
            parts = llama_paged_decode_factory(
                model, page_size=8, n_pool_pages=32, scan_layers=flag)
            outer, layers, pools, prefill, step, _ = parts
            pt = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4))
            lens = jnp.asarray([6, 6], jnp.int32)
            tok = jnp.asarray(
                np.pad(prompt, ((0, 0), (0, 2))))  # pad to page multiple
            nxt, pools = prefill(outer, layers, tok, pt, lens, pools)
            toks = [np.asarray(nxt)]
            for i in range(4):
                nxt, pools = step(outer, layers, nxt, pt, lens + i,
                                  pools)
                toks.append(np.asarray(nxt))
            outs[flag] = np.stack(toks)
        np.testing.assert_array_equal(outs[True], outs[False])
