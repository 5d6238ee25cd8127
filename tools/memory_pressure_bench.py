"""Memory-pressure config on one chip: ~1.9B-param Llama, remat="dots",
adamw moments offloaded to pinned host memory.

~ group_sharded_stage3.py:58 (offload) + the reference's large-model
single-GPU recipes: f32 moments are 8 B/param, so >~1.5B params cannot
hold params+grads+moments in 15.75 GB of v5e HBM — the moments move to
pinned host memory (XLA streams them around the jitted update) and
activations are rematerialized under the "dots" policy.

Run on the chip:
  python tools/memory_pressure_bench.py
Writes /tmp/memory_pressure.json and prints a PERF.md-ready row.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(tiny: bool = False, variant: str = "dots-b2"):
    import jax
    if tiny:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.nlp.llama import llama_train_step_factory

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    remat = "dots"
    if tiny or not on_tpu:
        cfg = LlamaConfig.tiny(vocab=512, hidden=128, layers=2, heads=4)
        B, S, steps = 2, 128, 2
    else:
        # 1.75B params: 3.26G bf16 params + grads on device; 13.04G of
        # f32 moments live in pinned host memory.
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2560,
                          intermediate_size=6912, num_hidden_layers=20,
                          num_attention_heads=20, num_key_value_heads=20,
                          max_position_embeddings=2048,
                          dtype=jnp.bfloat16)
        # Measured (2026-07-31): full remat at B=4 compiles to 16.30G
        # (grads + B=4 working set) and OOMs a 15.75G v5e; "dots" at
        # B=2 compiles to 11.2G device total and runs. Keep full-b4
        # selectable for bigger-HBM chips.
        if variant == "full-b4":
            remat, B, S, steps = True, 4, 2048, 8
        else:
            remat, B, S, steps = "dots", 2, 2048, 8

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    params, opt_state, step, _ = llama_train_step_factory(
        model, mesh, learning_rate=1e-4, remat=remat,
        offload_moments=True)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())

    mk = {k: a.sharding.memory_kind for k, a in opt_state["m"].items()}
    assert all(v == "pinned_host" for v in mk.values()), mk

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)

    # AOT-compile and call the executable directly: the compiled
    # object's memory_analysis() is the row's device/host byte split
    if on_tpu and not tiny:
        compiled = step.lower(params, opt_state, tokens, labels).compile()
        ma = compiled.memory_analysis()
        print(json.dumps({"device_args_gib": round(
            ma.argument_size_in_bytes / 2**30, 2),
            "device_temp_gib": round(ma.temp_size_in_bytes / 2**30, 2),
            "host_moments_gib": round(
                ma.host_argument_size_in_bytes / 2**30, 2)}))
        step = compiled

    # compile + warm
    params, opt_state, loss = step(params, opt_state, tokens, labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
    lv = float(loss)
    dt = (time.perf_counter() - t0) / steps

    stats = dev.memory_stats() or {}
    hbm_peak = stats.get("peak_bytes_in_use", 0) / 2**30
    hbm_limit = stats.get("bytes_limit", 0) / 2**30
    flops = 6 * n_params * B * S + \
        12 * cfg.num_hidden_layers * cfg.hidden_size * S * B * S
    peak = 197e12 if on_tpu else 1e12
    mfu = flops / dt / peak
    out = {
        "params": n_params, "batch": B, "seq": S,
        "step_ms": round(dt * 1e3, 1), "mfu": round(mfu, 4),
        "loss": lv, "device": str(dev),
        "hbm_peak_gib": round(hbm_peak, 2),
        "hbm_limit_gib": round(hbm_limit, 2),
        "moments_memory_kind": "pinned_host",
        "remat": remat if isinstance(remat, str) else "full",
    }
    print(json.dumps(out))
    with open("/tmp/memory_pressure.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(tiny="--tiny" in sys.argv,
         variant="full-b4" if {"full-b4", "--full-b4"} & set(sys.argv)
         else "dots-b2")
