"""MFU-vs-scale measurements arguing the 8B/40% north star (BASELINE #4).

VERDICT r3 item 2: the MFU story was one point (0.44B/S2048/0.766).
This tool adds the missing axes on the one real chip:

  ladder   — largest model trainable fully in HBM with bf16 adamw
             moments + remat="dots": tries descending configs, reports
             step_ms/MFU for the first that fits and OOM records for the
             rest. (The >2B regime previously required pinned-host
             moment offload at 0.105 MFU — this row shows the in-HBM
             frontier instead.)
  tp_shard — the per-chip compute of Llama-3-8B sliced TP=8 (BASELINE
             config 4's per-chip shard): hand-built scan over 32 layers
             of the sliced matmul shapes (q 4096->512, kv 4096->128,
             o 512->4096, ffn 4096->1792->4096, vocab shard 16032) with
             GQA flash attention at S=8192, fwd+bwd, remat per layer.
             One chip cannot measure ICI collectives; this row bounds
             the compute term of the pod MFU projection (comm term comes
             from parallel/cost_model).

Run: python tools/mfu_scale.py ladder
     python tools/mfu_scale.py tp_shard
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PEAK = 197e12  # v5e bf16

# ladder rungs: (layers, hidden, inter, heads, kv) descending ~2.4B ->
# ~1.0B; GQA kv=4 keeps the KV projections from dominating the HBM
# budget. Window-2 chip fact: every rung >= 1.5B at B=4 OOMs in HLO
# temps (bf16 params+moments alone are ~9.3 GB at 1.5B; grads +
# fused-CE temps push past 15.75 GB), so the ladder descends far enough
# to bracket the true in-HBM frontier instead of reporting only OOMs.
LADDER = [(32, 2560, 6912, 20, 4),   # ~2.36B
          (26, 2560, 6912, 20, 4),   # ~1.95B
          (20, 2560, 6912, 20, 4),   # ~1.54B
          (16, 2560, 6912, 20, 4),   # ~1.26B
          (24, 2048, 5504, 16, 4),   # ~1.19B
          (12, 2560, 6912, 20, 4)]   # ~0.99B


def run_ladder(only: int | None = None, B_override: int | None = None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama import llama_train_step_factory

    on_tpu = jax.devices()[0].platform != "cpu"
    ladder = list(LADDER) if on_tpu else [(2, 64, 128, 4, 2)]
    B, S = (4, 2048) if on_tpu else (1, 128)
    if B_override is not None:
        B = B_override

    def try_rung(L, h, inter, heads, kv):
        # all device buffers (params/moments/compiled step) are locals of
        # this frame: an OOM unwinds the frame and frees them before the
        # next rung allocates
        cfg = LlamaConfig(vocab_size=32000, hidden_size=h,
                          intermediate_size=inter, num_hidden_layers=L,
                          num_attention_heads=heads, num_key_value_heads=kv,
                          max_position_embeddings=2048, dtype=jnp.bfloat16)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        model.to(dtype="bfloat16")
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        params, opt_state, step, _ = llama_train_step_factory(
            model, mesh, learning_rate=1e-4, remat="dots",
            accum_dtype=jnp.bfloat16)
        n_params = sum(int(np.prod(v.shape)) for v in params.values())
        rng = np.random.default_rng(0)
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                          jnp.int32)
        lab = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                          jnp.int32)
        loss = None
        t0 = time.perf_counter()
        for _ in range(2):
            params, opt_state, loss = step(params, opt_state, tok, lab)
        float(loss)
        compile_s = time.perf_counter() - t0
        steps = 10 if on_tpu else 2
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, tok, lab)
        lv = float(loss)
        dt = (time.perf_counter() - t0) / steps
        flops = 6 * n_params * B * S + 12 * L * h * S * B * S
        return {"mode": "ladder", "params_b": round(n_params / 1e9, 3),
                "layers": L, "hidden": h, "B": B, "S": S,
                "moments": "bf16", "remat": "dots",
                "step_ms": round(dt * 1e3, 1),
                "mfu": round(flops / dt / PEAK, 4),
                "loss": lv, "compile_s": round(compile_s, 1),
                "device": str(jax.devices()[0])}

    import gc
    if only is not None:
        ladder = ladder[only:only + 1]
    for L, h, inter, heads, kv in ladder:
        try:
            print(json.dumps(try_rung(L, h, inter, heads, kv)), flush=True)
            return  # largest fitting config measured — done
        except Exception as e:  # noqa: BLE001 — OOM is a data point
            msg = repr(e)
            oom = "RESOURCE_EXHAUSTED" in msg or "memory" in msg.lower()
            print(json.dumps({"mode": "ladder", "layers": L, "hidden": h,
                              "oom": oom, "error": msg[-200:]}), flush=True)
            gc.collect()


def run_ladder_subproc():
    """Window-2 chip fact: after one rung OOMs, every later rung in the
    SAME process reports RESOURCE_EXHAUSTED even at sizes that fit cold
    (device memory from the failed attempt is not reclaimed by the
    runtime). So the driver mode runs each rung in a fresh subprocess
    (fresh TPU client, clean HBM) and stops at the first success."""
    import subprocess
    for idx in range(len(LADDER)):
        # B=4 for MFU quality; a B=2 retry probes whether the rung fits
        # at all (the frontier is 2-D in (params, batch)). Both in fresh
        # subprocesses: an OOM poisons the TPU client's HBM accounting
        # for the rest of its process (window-2 chip fact).
        for B in (4, 2):
            try:
                r = subprocess.run(
                    [sys.executable, __file__, "ladder_rung", str(idx),
                     str(B)],
                    capture_output=True, text=True, timeout=900)
            except subprocess.TimeoutExpired:
                print(json.dumps({"mode": "ladder", "rung": idx, "B": B,
                                  "error": "timeout after 900s"}),
                      flush=True)
                continue
            wrote = False
            fit = False
            for line in r.stdout.splitlines():
                if line.startswith("{"):
                    print(line, flush=True)
                    wrote = True
                    try:
                        fit = fit or "step_ms" in json.loads(line)
                    except ValueError:
                        pass
            if not wrote:
                print(json.dumps({"mode": "ladder", "rung": idx, "B": B,
                                  "error": (r.stderr or "")[-200:]}),
                      flush=True)
            if fit:
                return  # largest fitting config measured


def run_tp_shard(optimizer: str = "sgd", zero_dp: int = 8):
    """optimizer="adamw": the round-4 verdict item 2 fix — the projected
    v5p-64 plan trains with adamw + ZeRO-sliced moments, so the measured
    per-chip efficiency must include the sliced adamw update's HBM
    traffic, not sgd's. Each chip holds bf16 moments for a 1/zero_dp
    slice of its shard and updates only that slice (the rest arrives by
    all-gather on the pod — ICI term, cost model's job). zero_dp=8 over
    the TP=8-shaped ~1.03B shard gives a ~129M-param slice, matching the
    dp=32/mp=2 plan's 4B/32 = 125M slice per chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.flash_attention_gqa import (
        grouped_flash_attention)

    on_tpu = jax.devices()[0].platform != "cpu"
    if on_tpu:
        B, S, L = 1, 8192, 32
        H, HKV, D, HID, INTER, VOC = 4, 1, 128, 4096, 1792, 16032
        dtype = jnp.bfloat16
    else:
        B, S, L = 1, 256, 2
        H, HKV, D, HID, INTER, VOC = 2, 1, 32, 64, 96, 128
        dtype = jnp.float32

    rng = np.random.default_rng(0)

    def w(*shape):
        return jnp.asarray(
            rng.standard_normal(shape) * (0.02), dtype)

    # stacked per-layer weights so one lax.scan covers all 32 layers
    ws = {
        "wq": w(L, HID, H * D), "wk": w(L, HID, HKV * D),
        "wv": w(L, HID, HKV * D), "wo": w(L, H * D, HID),
        "wg": w(L, HID, INTER), "wu": w(L, HID, INTER),
        "wd": w(L, INTER, HID),
    }
    emb = w(VOC, HID)
    head = w(HID, VOC)

    def rms(x):
        v = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x.astype(jnp.float32) * jax.lax.rsqrt(v + 1e-5)).astype(
            x.dtype)

    def layer(x, lw):
        def body(x, lw):
            h0 = rms(x)
            q = (h0 @ lw["wq"]).reshape(B, S, H, D).transpose(0, 2, 1, 3)
            k = (h0 @ lw["wk"]).reshape(B, S, HKV, D).transpose(0, 2, 1, 3)
            v = (h0 @ lw["wv"]).reshape(B, S, HKV, D).transpose(0, 2, 1, 3)
            a = grouped_flash_attention(q, k, v, True)
            a = a.transpose(0, 2, 1, 3).reshape(B, S, H * D)
            x = x + (a @ lw["wo"]).astype(x.dtype)
            h1 = rms(x)
            f = (jax.nn.silu((h1 @ lw["wg"]).astype(jnp.float32)).astype(
                x.dtype) * (h1 @ lw["wu"])) @ lw["wd"]
            return x + f.astype(x.dtype)
        return jax.checkpoint(body)(x, lw)

    def loss_fn(ws, emb, head, ids, labels):
        x = emb[ids]
        def scan_body(x, lw):
            return layer(x, lw), None
        x, _ = jax.lax.scan(scan_body, x, ws)
        logits = (rms(x) @ head).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None],
                                             -1))

    ids = jnp.asarray(rng.integers(0, VOC, (B, S)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, VOC, (B, S)), jnp.int32)

    if optimizer == "adamw":
        # ZeRO-sliced adamw: bf16 moments for the leading 1/zero_dp of
        # each tensor's flat elements; only that slice of the param is
        # updated locally. Slice choice is irrelevant to cost — the HBM
        # traffic (read g + m + v + p slice, write m + v + p slice) only
        # depends on the element count.
        def slice_len(v):
            return max(1, int(np.prod(v.shape)) // zero_dp)

        moments = {
            "m_ws": {k: jnp.zeros((slice_len(v),), jnp.bfloat16)
                     for k, v in ws.items()},
            "v_ws": {k: jnp.zeros((slice_len(v),), jnp.bfloat16)
                     for k, v in ws.items()},
            "m_emb": jnp.zeros((slice_len(emb),), jnp.bfloat16),
            "v_emb": jnp.zeros((slice_len(emb),), jnp.bfloat16),
            "m_head": jnp.zeros((slice_len(head),), jnp.bfloat16),
            "v_head": jnp.zeros((slice_len(head),), jnp.bfloat16),
            "t": jnp.zeros((), jnp.float32),
        }

        def adamw_slice(p, g, m, v, t, lr=1e-4, b1=0.9, b2=0.95,
                        eps=1e-8, wd=0.01):
            k = m.shape[0]
            shape = p.shape
            pf = p.reshape(-1)
            gf = g.reshape(-1)[:k].astype(jnp.float32)
            mf = m.astype(jnp.float32)
            vf = v.astype(jnp.float32)
            mf = b1 * mf + (1 - b1) * gf
            vf = b2 * vf + (1 - b2) * gf * gf
            mhat = mf / (1 - b1 ** t)
            vhat = vf / (1 - b2 ** t)
            ps = pf[:k].astype(jnp.float32)
            ps = ps - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * ps)
            pf = pf.at[:k].set(ps.astype(pf.dtype))
            return (pf.reshape(shape), mf.astype(jnp.bfloat16),
                    vf.astype(jnp.bfloat16))

        @jax.jit
        def train(state):
            ws, emb, head, mom = state
            g = jax.grad(loss_fn, argnums=(0, 1, 2))(ws, emb, head, ids,
                                                     labels)
            t = mom["t"] + 1.0
            new_ws, new_m, new_v = {}, {}, {}
            for k, v in ws.items():
                new_ws[k], new_m[k], new_v[k] = adamw_slice(
                    v, g[0][k], mom["m_ws"][k], mom["v_ws"][k], t)
            emb2, me, ve = adamw_slice(emb, g[1], mom["m_emb"],
                                       mom["v_emb"], t)
            head2, mh, vh = adamw_slice(head, g[2], mom["m_head"],
                                        mom["v_head"], t)
            return new_ws, emb2, head2, {
                "m_ws": new_m, "v_ws": new_v, "m_emb": me, "v_emb": ve,
                "m_head": mh, "v_head": vh, "t": t}

        state = (ws, emb, head, moments)
    else:
        @jax.jit
        def train(state):
            ws, emb, head = state
            g = jax.grad(loss_fn, argnums=(0, 1, 2))(ws, emb, head, ids,
                                                     labels)
            lr = 1e-6
            new_ws = {k: (v - lr * g[0][k].astype(jnp.float32)).astype(
                v.dtype) for k, v in ws.items()}
            return (new_ws, (emb - lr * g[1].astype(jnp.float32)).astype(
                emb.dtype), (head - lr * g[2].astype(jnp.float32)).astype(
                head.dtype))

        state = (ws, emb, head)

    # one shared timing scaffold for both optimizers — the sgd-vs-adamw
    # comparison is only valid if the measurement discipline is identical
    t0 = time.perf_counter()
    state = train(state)
    float(state[1][0, 0])  # emb readback = sync
    compile_s = time.perf_counter() - t0
    steps = 8 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(steps):
        state = train(state)
    float(state[1][0, 0])
    dt = (time.perf_counter() - t0) / steps
    ws = state[0]
    emb, head = state[1], state[2]

    n_params = sum(int(np.prod(v.shape)) for v in ws.values()) + \
        int(np.prod(emb.shape)) + int(np.prod(head.shape))
    tok = B * S
    # attention flops at the sliced head count: fwd 2*2*B*H*S^2*D, x3 bwd
    attn = 12 * L * H * S * S * D * B
    flops = 6 * n_params * tok + attn
    rec = {"mode": f"tp_shard_{optimizer}" if optimizer != "sgd"
           else "tp_shard",
           "what": ("llama3-8b TP=8 per-chip shard shapes, fwd+bwd+"
                    + (f"zero-sliced adamw (bf16 moments, dp={zero_dp})"
                       if optimizer == "adamw" else "sgd")),
           "shard_params_b": round(n_params / 1e9, 3),
           "B": B, "S": S, "layers": L,
           "step_ms": round(dt * 1e3, 1),
           "compute_mfu": round(flops / dt / PEAK, 4),
           "compile_s": round(compile_s, 1),
           "note": "compute term only; ICI comm term from cost model",
           "device": str(jax.devices()[0])}
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "ladder"
    if mode == "ladder":
        run_ladder_subproc()
    elif mode == "ladder_rung":
        run_ladder(only=int(sys.argv[2]),
                   B_override=int(sys.argv[3]) if len(sys.argv) > 3
                   else None)
    elif mode == "tp_shard":
        run_tp_shard()
    elif mode == "tp_shard_adamw":
        run_tp_shard("adamw",
                     zero_dp=int(sys.argv[2]) if len(sys.argv) > 2 else 8)
    else:
        raise SystemExit(
            "mode: ladder | ladder_rung <i> | tp_shard | "
            "tp_shard_adamw [zero_dp]")
