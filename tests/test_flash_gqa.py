"""Grouped-query flash attention: kernel oracle + Llama integration.

Exceeds the reference (fused_attention_op.cu predates GQA): K/V stay at
their true head count — no jnp.repeat HBM/VMEM blowup on the flash path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import flags as _flags
from paddle_tpu.ops.pallas.flash_attention_gqa import grouped_flash_attention


def _dense_ref(q, k, v, causal, groups):
    D = q.shape[-1]
    kk = jnp.repeat(k, groups, axis=1)
    vv = jnp.repeat(v, groups, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(D)
    if causal:
        S = q.shape[2]
        s = jnp.where(np.tril(np.ones((S, S), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vv)


class TestGroupedFlashAttention:
    @pytest.mark.parametrize("hq,hkv,causal", [(4, 2, True), (8, 2, False),
                                               (4, 1, True)])
    def test_matches_dense_repeat(self, hq, hkv, causal):
        rng = np.random.default_rng(0)
        S, D = 256, 64
        q = jnp.asarray(rng.standard_normal((2, hq, S, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, hkv, S, D)), jnp.float32)
        out = grouped_flash_attention(q, k, v, causal)
        ref = _dense_ref(q, k, v, causal, hq // hkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_dense_repeat(self):
        rng = np.random.default_rng(1)
        S, D, hq, hkv = 256, 64, 4, 2
        q = jnp.asarray(rng.standard_normal((1, hq, S, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, hkv, S, D)), jnp.float32)
        g = jax.grad(lambda *a: grouped_flash_attention(*a, True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: _dense_ref(*a, True, 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        # dk/dv keep the true kv head count
        assert g[1].shape == (1, hkv, S, D)

    def test_head_count_mismatch_raises(self):
        q = jnp.zeros((1, 3, 128, 64))
        k = jnp.zeros((1, 2, 128, 64))
        with pytest.raises(ValueError):
            grouped_flash_attention(q, k, k)


class TestLlamaGQAFlashPath:
    def test_llama_logits_flash_vs_dense(self):
        from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        cfg = LlamaConfig.tiny(vocab=97, hidden=256, layers=2, heads=4,
                               kv_heads=2)
        m = LlamaForCausalLM(cfg)
        m.eval()
        tok = paddle.to_tensor(np.random.default_rng(0).integers(
            0, 97, (2, 256)).astype(np.int32))
        old = _flags.get_flag("use_flash_attention")
        try:
            _flags.set_flags({"use_flash_attention": True})
            flash = m(tok).numpy()
            _flags.set_flags({"use_flash_attention": False})
            dense = m(tok).numpy()
        finally:
            _flags.set_flags({"use_flash_attention": old})
        np.testing.assert_allclose(flash, dense, rtol=2e-4, atol=2e-4)


class TestRingAttentionGQA:
    def test_ring_gqa_matches_dense(self):
        from jax.sharding import Mesh
        from paddle_tpu.parallel.ring_attention import ring_attention
        rng = np.random.default_rng(3)
        B, Hq, Hkv, S, D = 2, 4, 2, 64, 16
        q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("sep",))
        out = ring_attention(q, k, v, mesh, axis="sep", causal=True)
        ref = _dense_ref(q, k, v, True, Hq // Hkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_ring_gqa_grads(self):
        from jax.sharding import Mesh
        from paddle_tpu.parallel.ring_attention import ring_attention
        rng = np.random.default_rng(4)
        B, Hq, Hkv, S, D = 1, 4, 1, 32, 8
        q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("sep",))
        g = jax.grad(lambda *a: jnp.sum(
            ring_attention(*a, mesh, axis="sep", causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(_dense_ref(*a, True, 4) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        assert g[1].shape == (B, Hkv, S, D)


class TestFlashUnderTensorParallel:
    @pytest.mark.parametrize("kv_heads", [4, 2])
    def test_no_allgather_around_pallas_call(self, kv_heads):
        """GSPMD can't partition a Pallas custom call: without the
        shard_map wrap, TP meshes all-gather full Q/K/V around every
        flash call (measured 27MB/step on this tiny config). The wrap
        must eliminate every all-gather and keep loss parity with the
        single-device step."""
        import re
        from jax.sharding import Mesh
        from paddle_tpu.core import flags as _flags
        from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.nlp.llama import llama_train_step_factory
        import paddle_tpu as paddle

        old = _flags.get_flag("use_flash_attention")
        _flags.set_flags({"use_flash_attention": True})
        try:
            cfg = LlamaConfig.tiny(vocab=128, hidden=256, layers=1,
                                   heads=4, kv_heads=kv_heads)
            rng = np.random.default_rng(0)
            tok = jnp.asarray(rng.integers(0, 128, (4, 256)), jnp.int32)

            paddle.seed(0)
            m1 = LlamaForCausalLM(cfg)
            mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("data",))
            p1, o1, step1, _ = llama_train_step_factory(m1, mesh1,
                                                        remat=False)
            _, _, ref_loss = step1(p1, o1, tok, tok)

            paddle.seed(0)
            m = LlamaForCausalLM(cfg)
            mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                        ("data", "model"))
            params, opt, step, _ = llama_train_step_factory(m, mesh,
                                                            remat=False)
            compiled = step.lower(params, opt, tok, tok).compile()
            _, _, loss = compiled(params, opt, tok, tok)
            np.testing.assert_allclose(float(loss), float(ref_loss),
                                       rtol=2e-5)
            hlo = compiled.as_text()
            n = sum(1 for line in hlo.splitlines()
                    if re.search(r"=\s+\w+\[[\d,]*\]\S*\s+all-gather",
                                 line))
            assert n == 0, f"{n} all-gathers around the flash call"
        finally:
            _flags.set_flags({"use_flash_attention": old})


class TestShardMappedFusedCE:
    def test_fused_ce_data_sep_manual_matches_dense(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.ops.pallas.fused_ce import causal_lm_loss
        rng = np.random.default_rng(0)
        B, S, V = 4, 32, 128
        logits = jnp.asarray(rng.normal(0, 1, (B, S, V)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "sep"))
        manual = ["data", "sep"]

        def _fused(lg, lb):
            loss = causal_lm_loss(lg, lb)
            for a in manual:
                loss = jax.lax.pmean(loss, a)
            return loss

        fn = jax.shard_map(_fused, mesh=mesh,
                           in_specs=(P("data", "sep", None),
                                     P("data", "sep")),
                           out_specs=P(), check_vma=False,
                           axis_names=frozenset(manual))
        dense = jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(logits, -1), labels[..., None], -1)[..., 0])
        np.testing.assert_allclose(float(fn(logits, labels)), float(dense),
                                   rtol=1e-6)
        g1 = jax.grad(lambda lg: fn(lg, labels))(logits)
        g2 = jax.grad(lambda lg: jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(lg, -1),
            labels[..., None], -1)[..., 0]))(logits)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-6)


class TestFlashInPipelineFactory:
    def test_4d_factory_flash_nested_shard_map_parity(self):
        """Inside the 4D factory's partial-manual pipeline the 'model'
        axis is AUTO — the stage body must nest a shard_map around the
        Pallas flash call (GSPMD would all-gather Q/K/V per microbatch
        otherwise) and match the dense path exactly."""
        from jax.sharding import Mesh
        import paddle_tpu as paddle
        from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.nlp import llama_functional as LF

        rng = np.random.default_rng(0)
        tok = jnp.asarray(rng.integers(0, 128, (4, 256)), jnp.int32)
        losses = {}
        from paddle_tpu.parallel import pallas_sharding as PS
        for force in (False, True):
            LF._FORCE_FLASH_FOR_TESTS = force
            PS.ENGAGED["flag"] = False
            try:
                paddle.seed(0)
                # kv_heads=2 exercises the grouped (GQA) kernel branch
                cfg = LlamaConfig.tiny(vocab=128, hidden=256, layers=4,
                                       heads=4, kv_heads=2)
                m = LlamaForCausalLM(cfg)
                mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(
                    1, 2, 2, 2), ("data", "pipe", "sharding", "model"))
                p, o, step = LF.llama_4d_train_step_factory(
                    m, mesh, n_microbatches=2, remat=False)
                p, o, loss = step(p, o, tok, tok)
                # second step covers the backward through the nested
                # shard_map: a wrong dQ/dK/dV would diverge the params
                p, o, loss2 = step(p, o, tok, tok)
                losses[force] = (float(loss), float(loss2))
                if force:
                    assert PS.ENGAGED["flag"], \
                        "nested shard_map branch did not engage"
            finally:
                LF._FORCE_FLASH_FOR_TESTS = False
        np.testing.assert_allclose(losses[True], losses[False], rtol=2e-5)


class TestSdpaUnderMesh:
    def test_sdpa_flash_model_axis_manual(self):
        """scaled_dot_product_attention's flash path must shard_map over
        an AUTO 'model' mesh axis (GSPMD can't partition Pallas) and
        match the plain call exactly."""
        from jax.sharding import Mesh
        import paddle_tpu.nn.functional as F
        from paddle_tpu.core.tensor import Tensor

        from paddle_tpu.parallel import pallas_sharding as PS
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
        rng = np.random.default_rng(0)
        q = rng.standard_normal((2, 256, 4, 64)).astype(np.float32)

        def run(qv):
            out = F.scaled_dot_product_attention(
                Tensor(qv), Tensor(qv), Tensor(qv), is_causal=True,
                use_pallas=True)
            return out._value

        PS.ENGAGED["flag"] = False
        with jax.sharding.set_mesh(mesh):
            sharded = jax.jit(run)(jnp.asarray(q))
        assert PS.ENGAGED["flag"], "manual shard_map path did not engage"
        plain = run(jnp.asarray(q))
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(plain),
                                   atol=2e-5)


class TestGQALongContextDelegation:
    """Past the resident-K/V frontier, grouped_flash_attention delegates
    to the K/V-streaming splash kernels (full causal block mask) instead
    of failing to compile. Must be bit-exact vs the grouped core."""

    @pytest.mark.parametrize("G,S", [(2, 256), (4, 512), (8, 512)])
    def test_delegation_matches_core(self, G, S, monkeypatch):
        # G=4/8 at 512-divisible S are the realistic Llama-3 delegation
        # configs: naive 512x512 splash blocks would be REJECTED by the
        # score/row budgets — the wrapper must shrink group-aware
        import importlib
        ga = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention_gqa")
        rng = np.random.default_rng(11)
        q = jnp.asarray(rng.standard_normal((1, 2 * G, S, 64)),
                        jnp.float32)
        kv = jnp.asarray(rng.standard_normal((1, 2, S, 64)), jnp.float32)

        def run():
            f = lambda a, b, c: ga.grouped_flash_attention(a, b, c, True)
            out, vjp = jax.vjp(f, q, kv, kv)
            return (out, *vjp(out))

        ref = run()

        def reject(*a, **k):
            raise ga.ResidentOverflowError("test-forced")
        monkeypatch.setattr(ga, "_gqa_resolve_blocks", reject)
        got = run()
        for a, b in zip(ref, got):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    def test_pinned_blocks_do_not_delegate(self, monkeypatch):
        import importlib
        ga = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention_gqa")
        rng = np.random.default_rng(12)
        q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
        kv = jnp.asarray(rng.standard_normal((1, 2, 256, 64)),
                         jnp.float32)
        called = []
        orig = ga._grouped_flash_core

        def spy(*a, **k):
            called.append(1)
            return orig(*a, **k)
        monkeypatch.setattr(ga, "_grouped_flash_core", spy)
        ga.grouped_flash_attention(q, kv, kv, True, None, 128, 128)
        assert called  # pinned blocks go straight to the core kernel

    def test_resolver_raises_typed_error_at_extreme_s(self):
        import importlib
        ga = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention_gqa")
        with pytest.raises(ga.ResidentOverflowError):
            ga._gqa_resolve_blocks(16384, 16384, 4, None, None)
