"""Benchmark: flagship Llama training step on one real TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric = model FLOPs utilization (MFU) of a causal-LM training step
(fwd+bwd+adamw, bf16 params, Pallas flash attention, fused CE).
vs_baseline = MFU / 0.40 — the north-star ladder target is >=40% MFU
(BASELINE.md config 4). The reference publishes no numbers (BASELINE.md),
so the MFU ceiling is the honest yardstick.

Runs in ONE process (a chip belongs to one process at a time) and only
on an accelerator: with no chip, an unknown device kind, or any failure
it exits non-zero and prints no metric.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

# bf16 peak FLOP/s per chip, keyed by jax's ``device_kind``. A device that
# is not here is an error, not a default.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


def peak_for(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published bf16 peak for device_kind {device_kind!r}; add "
            "it to bench.PEAK_BF16_FLOPS with its source") from None


def bench_config(kv_heads: int):
    """The ~0.44B-param Llama slice that fits one v5e with adam moments."""
    import jax.numpy as jnp

    from paddle_tpu.models.nlp import LlamaConfig
    return LlamaConfig(vocab_size=32000, hidden_size=1536,
                       intermediate_size=4096, num_hidden_layers=12,
                       num_attention_heads=12,
                       num_key_value_heads=kv_heads,
                       max_position_embeddings=2048, dtype=jnp.bfloat16)


def run_config(dev, kv_heads, accum_dtype, time_budget_s):
    """Measure one training config on ``dev``; returns (mfu, row_dict)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import LlamaForCausalLM
    from paddle_tpu.models.nlp.llama import llama_train_step_factory

    cfg = bench_config(kv_heads)
    B, S = 8, 2048
    steps, warmup = 30, 3

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    mesh = Mesh(np.asarray([dev]), ("data",))
    # remat off: activations for the 0.44B config fit v5e HBM; measured
    # 0.554 vs 0.424 MFU against full-checkpoint remat (pre-round figure,
    # PERF.md). Larger configs flip remat="dots"/True.
    params, opt_state, step, _ = llama_train_step_factory(
        model, mesh, learning_rate=1e-4, remat=False,
        accum_dtype=jnp.dtype(accum_dtype))

    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)

    def timed_run(n):
        nonlocal params, opt_state
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            params, opt_state, loss = step(params, opt_state, tokens, labels)
        jax.block_until_ready(loss)
        return time.perf_counter() - t0, float(loss)

    timed_run(warmup)  # compile + warm

    def measure_once():
        # two-point measurement cancels the fixed per-run overhead
        small_n = max(2, steps // 5)
        t_small, _ = timed_run(small_n)
        t_big, loss_val = timed_run(steps)
        d = (t_big - t_small) / (steps - small_n)
        if d <= 0:  # overhead-dominated; fall back to the big run
            d = t_big / steps
        return d, loss_val

    # min over up to three passes: compile is already paid, so extra
    # passes are cheap, and the min is the machine's capability rather
    # than one pass's worst moment
    t_start = time.perf_counter()
    dt, loss = measure_once()
    passes = 1
    while passes < 3 and time.perf_counter() - t_start <= time_budget_s:
        d2, l2 = measure_once()
        passes += 1
        if d2 < dt:
            dt, loss = d2, l2

    tokens_per_step = B * S
    # standard 6ND causal-LM training FLOPs + attention term
    attn_flops = (12 * cfg.num_hidden_layers * cfg.hidden_size * S
                  * tokens_per_step)
    flops_per_step = 6 * n_params * tokens_per_step + attn_flops
    mfu = (flops_per_step / dt) / peak_for(dev.device_kind)
    row = {
        "mfu": round(mfu, 4),
        "tokens_per_sec_per_chip": round(tokens_per_step / dt, 1),
        "step_ms": round(dt * 1000, 2),
        "params": n_params,
        "batch": B, "seq": S,
        "kv_heads": cfg.num_key_value_heads,
        "moments_dtype": str(accum_dtype),
        "loss": float(loss),
        "passes": passes,
    }
    return mfu, row


def main():
    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit("bench.py: jax found no accelerator (platform "
                         "'cpu'); this benchmark only runs on a chip")
    peak_for(dev.device_kind)  # unknown device: fail before measuring
    enable_compile_cache()

    # Two rows: "legacy" = the fixed MHA/f32-moments config every prior
    # round benched (round-over-round comparability); "best" = GQA kv=4 +
    # bf16 adamw moments (Llama-3-realistic). The headline value is the
    # BEST row; both rows ride in detail.
    _, row_legacy = run_config(dev, kv_heads=12, accum_dtype="float32",
                               time_budget_s=250)
    mfu, row_best = run_config(dev, kv_heads=4, accum_dtype="bfloat16",
                               time_budget_s=250)
    print(json.dumps({
        "metric": "llama_train_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / 0.40, 4),
        "detail": {
            "best_config": row_best,
            "legacy_mha_config": row_legacy,
            "tokens_per_sec_per_chip": row_best["tokens_per_sec_per_chip"],
            "step_ms": row_best["step_ms"],
            "params": row_best["params"],
            "batch": row_best["batch"], "seq": row_best["seq"],
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "loss": row_best["loss"],
        },
    }))


if __name__ == "__main__":
    import signal

    def _on_alarm(signum, frame):
        raise TimeoutError("bench watchdog expired (1500s)")

    signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(1500)
    main()  # any failure: traceback, non-zero exit, no metric line
