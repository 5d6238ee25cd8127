"""First-compile + timing check of the Pallas kernels that CPU interpret
mode cannot validate (Mosaic compilation, VMEM budgets): grouped GQA/MQA
flash attention fwd+bwd (streamed-dkv backward) and the splash
block-sparse kernel. Run on the real chip:

    python tools/kernel_chip_check.py

Prints one JSON line per check: numerics vs the jnp.repeat + dense oracle
(computed on-chip in f32) and per-call ms.
"""
import json
import math
import time

import numpy as np


def _sync_time(fn, *args, n=10):
    import jax

    _sync = jax.block_until_ready

    out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _i in range(n):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / n * 1000, out


def _dense_ref(q, k, v, causal, G):
    import jax.numpy as jnp
    kf = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / math.sqrt(q.shape[-1])
    if causal:
        S = q.shape[2]
        s = jnp.where(np.tril(np.ones((S, S), bool)), s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf)


def gqa_check(B, Hkv, G, S, D, causal=True):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention_gqa import (
        grouped_flash_attention)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Hkv * G, S, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.bfloat16)

    fwd = jax.jit(lambda a, b, c: grouped_flash_attention(a, b, c, causal))
    ms_fwd, out = _sync_time(fwd, q, k, v)
    ref = _dense_ref(q, k, v, causal, G)
    err_fwd = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))

    def loss(a, b, c):
        return (grouped_flash_attention(a, b, c, causal)
                .astype(jnp.float32) ** 2).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def gradq(a, b, c):
        return grad(a, b, c)[0]

    ms_bwd, _ = _sync_time(gradq, q, k, v)
    # oracle grads in f32 via the dense path
    def loss_ref(a, b, c):
        return (_dense_ref(a, b, c, causal, G) ** 2).sum()
    gq, gk, gv = grad(q, k, v)
    rq, rk, rv = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    # bf16 grads accumulate over S positions (and G heads for dk/dv), so
    # absolute error scales with the grad magnitude — gate on RELATIVE
    # error per tensor (max|diff| / max|ref|)
    def rel(a, r):
        d = float(jnp.max(jnp.abs(a.astype(jnp.float32) - r)))
        return d / max(1e-6, float(jnp.max(jnp.abs(r))))
    err_bwd = max(rel(gq, rq), rel(gk, rk), rel(gv, rv))
    ok = bool(err_fwd < 0.05 and err_bwd < 0.02)
    print(json.dumps({
        "check": f"gqa B{B} Hkv{Hkv} G{G} S{S} D{D} causal={causal}",
        "fwd_ms": round(ms_fwd, 3), "bwd_ms": round(ms_bwd, 3),
        "max_err_fwd": round(err_fwd, 5),
        "rel_err_bwd": round(err_bwd, 5),
        "ok": ok,
    }))
    return ok


def splash_check(B, H, S, D, density):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.splash_attention import splash_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    bq = bk = 256
    nq, nk = S // bq, S // bk
    # causal-ish banded pattern at the requested density
    bm = np.zeros((nq, nk), bool)
    for i in range(nq):
        w = max(1, int(round(density * (i + 1))))
        bm[i, max(0, i + 1 - w):i + 1] = True
    fn = jax.jit(lambda a, b, c: splash_attention(a, b, c, bm, True, None,
                                                  bq, bk))
    ms, out = _sync_time(fn, q, k, v)
    ok = bool(jnp.isfinite(out.astype(jnp.float32)).all())
    print(json.dumps({
        "check": f"splash B{B} H{H} S{S} D{D} density={density}",
        "ms": round(ms, 3),
        "blocks_live": int(bm.sum()), "blocks_total": int(bm.size),
        "finite": ok,
    }))
    return ok


def splash_qoffset_check(B, H, Sloc, D, window, dist):
    """Shifted-query-frame splash (ring-window chunk pair at distance
    `dist`) vs a dense f32 oracle on real Mosaic — validates the
    q_offset kernels the window x sep ring composes from."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.splash_attention import splash_attention

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((B, H, Sloc, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, Sloc, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, Sloc, D)), jnp.bfloat16)
    off = dist * Sloc
    bq = bk = 128
    nq, nk = Sloc // bq, Sloc // bk
    bm = np.zeros((nq, nk), bool)
    for i in range(nq):
        for j in range(nk):
            bm[i, j] = (off + i * bq - (j + 1) * bk + 1) < window
    causal = dist == 0
    fn = jax.jit(lambda a, b, c: splash_attention(
        a, b, c, bm, causal, None, bq, bk, window, off))
    ms, out = _sync_time(fn, q, k, v)
    # dense oracle
    qp = off + np.arange(Sloc)[:, None]
    kp = np.arange(Sloc)[None, :]
    live = (qp - kp < window)
    if causal:
        live &= qp >= kp
    s = np.einsum("bhqd,bhkd->bhqk",
                  np.asarray(q, np.float32), np.asarray(k, np.float32)) \
        / np.sqrt(D)
    s = np.where(live, s, -1e30)
    m = s.max(-1, keepdims=True)
    p = np.where(live, np.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    ref = np.where(l > 0,
                   np.einsum("bhqk,bhkd->bhqd", p,
                             np.asarray(v, np.float32))
                   / np.maximum(l, 1e-30), 0.0)
    err = float(np.max(np.abs(np.asarray(out, np.float32) - ref)))
    ok = err < 0.05  # bf16 inputs
    print(json.dumps({
        "check": f"splash_qoffset dist={dist} w={window} Sloc={Sloc}",
        "ms": round(ms, 3), "max_err": round(err, 4), "ok": ok,
    }))
    return ok


def paged_check(B, Hq, Hkv, D, page_size, n_pages_per_seq, pool_pages):
    """Real-Mosaic compile + numerics of the paged decode kernel (the
    scalar-prefetch page gather is exactly what interpret mode cannot
    validate), plus per-call ms at a serving-ish shape."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal(
        (Hkv, pool_pages, page_size, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal(
        (Hkv, pool_pages, page_size, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.integers(1, pool_pages,
                                  (B, n_pages_per_seq)), jnp.int32)
    sl = jnp.asarray(rng.integers(page_size,
                                  n_pages_per_seq * page_size + 1,
                                  (B,)), jnp.int32)
    # amortize the per-call dispatch cost: chain ITERS decode steps
    # inside ONE jit (the flash_bwd_sweep pattern) — the carry perturbs
    # q so XLA cannot collapse the chain
    ITERS = 32

    def chained(q, kp, vp, pt, sl):
        def body(carry, _):
            o = paged_attention(carry, kp, vp, pt, sl)
            return carry + (1e-6 * o).astype(carry.dtype), o
        out, ys = jax.lax.scan(body, q, None, length=ITERS)
        # ys[0] is the UNperturbed first call: numerics come from the
        # same executable as the timing (one Mosaic compile, not two)
        return out, ys[0]

    fn = jax.jit(chained)
    ms_total, (_, out) = _sync_time(fn, q, kp, vp, pt, sl, n=3)
    ms = ms_total / ITERS

    # int8 pool variant through the same Mosaic path (dequant in VMEM)
    kq = jnp.clip(jnp.round(kp.astype(jnp.float32) * 16), -127,
                  127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vp.astype(jnp.float32) * 16), -127,
                  127).astype(jnp.int8)
    sc = jnp.full(kp.shape[:-1], 1 / 16, jnp.float32)
    out8 = jax.jit(lambda *a: paged_attention(
        a[0], a[1], a[2], a[3], a[4], k_scales=sc, v_scales=sc))(
        q, kq, vq, pt, sl)
    _ = np.asarray(out8.ravel()[0])
    int8_finite = bool(jnp.isfinite(out8.astype(jnp.float32)).all())

    # chunked-prefill kernel (chunk queries x pages) at a 256-token
    # chunk, checked against a dense gather oracle — finite-but-wrong
    # page gathers under real Mosaic must not pass
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_prefill_attention)
    C = 256
    start = 256
    qc = jnp.asarray(rng.standard_normal((B, Hq, C, D)), jnp.bfloat16)
    outp = jax.jit(lambda *a: paged_prefill_attention(*a))(
        qc, kp, vp, pt, sl, start)
    _ = np.asarray(outp.ravel()[0])
    W = pt.shape[1]
    S = W * page_size
    G = Hq // Hkv
    kg = jnp.swapaxes(kp[:, pt], 0, 1).reshape(B, Hkv, S, D)
    vg = jnp.swapaxes(vp[:, pt], 0, 1).reshape(B, Hkv, S, D)
    qg = qc.reshape(B, Hkv, G, C, D).astype(jnp.float32)
    sc_ = jnp.einsum("bhgcd,bhsd->bhgcs", qg,
                     kg.astype(jnp.float32)) / math.sqrt(D)
    col = jnp.arange(S)[None, None, None, None, :]
    row = start + jnp.arange(C)[None, None, None, :, None]
    msk = (col <= row) & (col < sl[:, None, None, None, None])
    sc_ = jnp.where(msk, sc_, -1e30)
    pr = jax.nn.softmax(sc_, -1)
    refp = jnp.einsum("bhgcs,bhsd->bhgcd", pr,
                      vg.astype(jnp.float32)).reshape(B, Hq, C, D)
    perr = float(jnp.max(jnp.abs(outp.astype(jnp.float32) - refp)))
    prefill_finite = perr < 0.05
    ref = paged_attention_reference(q.astype(jnp.float32),
                                    kp.astype(jnp.float32),
                                    vp.astype(jnp.float32), pt, sl)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    ok = err < 0.05  # bf16 kernel vs f32 oracle
    print(json.dumps({
        "check": f"paged B{B} Hq{Hq}/kv{Hkv} D{D} ps{page_size} "
                 f"pages{n_pages_per_seq}",
        "ms": round(ms, 3), "max_err": round(err, 4),
        "int8_finite": int8_finite, "prefill_ok": prefill_finite,
        "prefill_max_err": round(perr, 4),
        "ok": ok and int8_finite and prefill_finite,
    }))
    return ok and int8_finite and prefill_finite


def flash_stream_check(B, H, S, D):
    """Real-Mosaic compile + run of the round-4 grid-streamed flash
    kernels (fwd + both bwd passes) against the resident kernels at the
    same shape/blocks — interpret mode already proves bit-exactness, so
    on chip the bar is: compiles, runs, and stays within bf16 noise."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)),
                           jnp.bfloat16) for _ in range(3))

    def make(mode):
        f = jax.jit(lambda a, b, c: flash_attention(
            a, b, c, True, None, 256, 256, None, None, mode))
        g = jax.jit(jax.grad(
            lambda a, b, c: flash_attention(
                a, b, c, True, None, 256, 256, None, None,
                mode).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        return f, g

    f_s, g_s = make(True)
    out_s, grads_s = f_s(q, k, v), g_s(q, k, v)  # compile once
    # time the grad alone: jax.grad recomputes its own forward, so
    # adding f_s would double-count one forward pass
    ms, _ = _sync_time(g_s, q, k, v)
    f_r, g_r = make(False)
    out_r, grads_r = f_r(q, k, v), g_r(q, k, v)
    err = float(jnp.max(jnp.abs(out_s.astype(jnp.float32) -
                                out_r.astype(jnp.float32))))
    gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                     b.astype(jnp.float32))))
               for a, b in zip(grads_s, grads_r))
    ok = err < 0.02 and gerr < 0.05
    print(json.dumps({
        "check": f"flash_streamed B{B} H{H} S{S} D{D}",
        "ms_grad": round(ms, 3),  # one jax.grad call = fwd+bwd
        "max_err": round(err, 4),
        "max_grad_err": round(gerr, 4), "ok": ok}))
    return ok


def ring_flash_check(B, H, S, D, n_dev=1):
    """Real-Mosaic run of the flash-engine ring (custom VJP: per-chunk
    flash fwd partials + global-lse flash bwd) against the dense f32
    oracle — fwd values and grads. seq_attn_bench times this path; this
    check owns its NUMERICS on hardware."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.parallel.ring_attention import ring_attention

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)),
                           jnp.bfloat16) for _ in range(3))
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("sep",))

    def loss_ring(a, b, c):
        return jnp.sum(ring_attention(
            a, b, c, mesh, "sep", True).astype(jnp.float32) ** 2)

    out = ring_attention(q, k, v, mesh, "sep", True)
    grads = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    ref = _dense_ref(q, k, v, True, 1)

    def loss_ref(a, b, c):
        return jnp.sum(_dense_ref(a, b, c, True, 1).astype(
            jnp.float32) ** 2)
    gref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    gerr = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                              b.astype(jnp.float32)))) /
        max(1e-6, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        for a, b in zip(grads, gref))
    ok = err < 0.02 and gerr < 0.05
    print(json.dumps({
        "check": f"ring_flash B{B} H{H} S{S} D{D} p{n_dev}",
        "max_err": round(err, 4), "rel_grad_err": round(gerr, 4),
        "ok": ok}))
    return ok


def splash_stream_check(B, H, S, D, density):
    """Streamed-splash (table-driven K/V streaming) vs resident splash
    on chip at the same mask."""
    import importlib

    import jax
    import jax.numpy as jnp
    sp = importlib.import_module("paddle_tpu.ops.pallas.splash_attention")

    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)),
                           jnp.bfloat16) for _ in range(3))
    nq = S // 128
    bm = np.tril(np.ones((nq, nq), bool))
    if density < 1.0:
        w = max(1, int(nq * density))
        for i in range(nq):
            bm[i, :max(0, i - w)] = False

    def make(force):
        # _FORCE_STREAM is read at TRACE time: set it, trace via one
        # call, then restore
        sp._FORCE_STREAM = force
        try:
            f = jax.jit(lambda a, b, c: sp.splash_attention(
                a, b, c, bm, True, None, 128, 128))
            out = f(q, k, v)
        finally:
            sp._FORCE_STREAM = None
        return f, out

    f_s, out_s = make(True)
    ms, _ = _sync_time(f_s, q, k, v)
    _, out_r = make(False)
    err = float(jnp.max(jnp.abs(out_s.astype(jnp.float32) -
                                out_r.astype(jnp.float32))))
    ok = err < 0.02
    print(json.dumps({
        "check": f"splash_streamed B{B} H{H} S{S} D{D} density={density}",
        "ms_fwd": round(ms, 3), "max_err": round(err, 4), "ok": ok}))
    return ok


if __name__ == "__main__":
    import sys

    import jax
    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "platform": dev.platform}))
    results = []
    # round-4 streamed kernels: first real-Mosaic compile — guarded so a
    # failure reports instead of aborting the established checks
    for name, check in (("flash_streamed",
                         lambda: flash_stream_check(2, 4, 2048, 128)),
                        ("splash_streamed",
                         lambda: splash_stream_check(2, 4, 2048, 128,
                                                     0.5)),
                        ("ring_flash",
                         lambda: ring_flash_check(2, 4, 2048, 128))):
        try:
            results.append(check())
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"check": name, "error": repr(e)[-300:]}))
            results.append(False)
    # bench-adjacent GQA shape (Llama-3-8B-style grouping) + MQA stress
    results.append(gqa_check(B=4, Hkv=4, G=4, S=2048, D=128))
    results.append(gqa_check(B=2, Hkv=2, G=8, S=2048, D=128))
    # MQA — the VMEM stress case
    results.append(gqa_check(B=1, Hkv=1, G=32, S=2048, D=128))
    results.append(gqa_check(B=4, Hkv=4, G=4, S=1024, D=64, causal=False))
    for den in (0.25, 0.5, 1.0):
        results.append(splash_check(B=4, H=8, S=2048, D=128, density=den))
    # shifted-frame (ring-window) splash: diag + cross-chunk pair
    for dist in (0, 1):
        results.append(splash_qoffset_check(B=2, H=4, Sloc=1024, D=128,
                                            window=768, dist=dist))
    # LAST + guarded: a paged-kernel failure must not hide the
    # established checks' rows
    try:
        results.append(paged_check(B=8, Hq=32, Hkv=8, D=128,
                                   page_size=64, n_pages_per_seq=128,
                                   pool_pages=1024))
    except Exception as e:  # noqa: BLE001 — report, don't abort
        print(json.dumps({"check": "paged", "error": repr(e)[-300:]}))
        results.append(False)
    sys.exit(0 if all(results) else 1)
