"""``laguna``'s programs and functions at the tiny size, beside
``test_laguna.py`` (a file of their own, so that another worker runs them:
the interpreted kernel makes each of these factories cost tens of seconds on
the CPU): the planted fault ``window_ignored`` through the engine, the
factory's logits against the reference, YaRN, the gate and the router.
Tolerances as ``test_laguna.py`` states them.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.nlp import laguna as L
from paddle_tpu.models.nlp.laguna import LagunaConfig
from paddle_tpu.serving import ServingEngine

from test_laguna import (  # noqa: F401  (the fixture is collected by name)
    GAP, LOGITS, R, _requests, gaps, model_dict, ref_logits, tiny)


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
    sound, fault = (ref_logits(cfg, weights, reqs[1].prompt, q) for q in (None, "window_ignored"))
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
    ref = ref_logits(cfg, weights, seq[:40])
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
