"""Map the flash-attention scoped-VMEM feasibility frontier (compile-only).

The long8k chip run exposed a Mosaic scoped-vmem overflow (21M > 16M) at
S=8192 with the auto-picked 512x512 blocks: the resident-KV design's f32
compute blocks + double-buffered streams outgrow the 16M scoped budget as
S grows, which interpret-mode tests can never catch. This tool
lower()+compile()s each kernel (fwd / bwd-dq / bwd-dkv, via jax.vjp so
the two bwd kernels compile in one pass) separately per (S, bq, bk)
combo — Mosaic's scoped-vmem check fires at compile time, so the chip is
only needed as a compile target. Prints one JSON line per combo.

Run: python tools/long8k_vmem_repro.py
"""
from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B, H, D = 2, 12, 128
    rng = np.random.default_rng(0)

    def probe(fwd, phase, *args):
        """jit-lower-compile fwd (or its fwd+bwd vjp) and parse a Mosaic
        scoped-allocation overflow out of the failure, if any."""
        def fwdbwd(*a):
            out, vjp = jax.vjp(fwd, *a)
            return vjp(out)

        fn = fwd if phase == "fwd" else fwdbwd
        try:
            jax.jit(fn).lower(*args).compile()
            return {"ok": True}
        except Exception as e:  # noqa: BLE001
            m = re.search(r"Scoped allocation with size ([0-9.]+[KMG]) ",
                          str(e))
            return {"ok": False,
                    "scoped": m.group(1) if m else str(e)[:120]}

    def compile_one(S, bq, bk, phase, stream):
        q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
        return probe(
            lambda a, b, c: flash_attention(a, b, c, True, None, bq, bk,
                                            bq, bk, stream),
            phase, q, q, q)

    # decision-critical combos only (~25 compile-only probes, not the
    # full cartesian grid):
    # S=8192 maps the failure frontier, 16384 validates streaming where
    # resident cannot fit, 2048@512 re-confirms the known-good headline
    flash_grid = [
        (2048, 512, False), (2048, 512, True),
        (8192, 512, False), (8192, 256, False), (8192, 512, True),
        (16384, 256, False), (16384, 512, True), (16384, 256, True),
        (32768, 256, True),
    ]
    for S, blk, stream in flash_grid:
        for phase in ("fwd", "fwdbwd"):
            r = compile_one(S, blk, blk, phase, stream)
            print(json.dumps(
                {"S": S, "block": blk, "phase": phase,
                 "stream": stream, **r}), flush=True)

    # GQA frontier: same resident-K/V exposure, rows = G*bq. Gates the
    # queued mfu_scale tp_shard row (G=4, S=8192).
    from paddle_tpu.ops.pallas.flash_attention_gqa import (
        grouped_flash_attention)

    def compile_gqa(S, G, bq, bk, phase):
        q = jnp.asarray(rng.standard_normal((1, 4 * G, S, D)),
                        jnp.bfloat16)
        kv = jnp.asarray(rng.standard_normal((1, 4, S, D)), jnp.bfloat16)
        return probe(
            lambda a, b, c: grouped_flash_attention(a, b, c, True, None,
                                                    bq, bk),
            phase, q, kv, kv)

    gqa_grid = [
        (8192, 4, 256, 256),   # the resolver's tp_shard pick — must pass
        (8192, 4, 256, 512),   # one step larger: how much margin exists
        (8192, 8, 128, 256),
        (2048, 4, 256, 512),   # round-3 known-good (calibration anchor)
    ]
    for S, G, bq, bk in gqa_grid:
        for phase in ("fwd", "fwdbwd"):
            r = compile_gqa(S, G, bq, bk, phase)
            print(json.dumps(
                {"kernel": "gqa", "S": S, "G": G, "bq": bq,
                 "bk": bk, "phase": phase, **r}), flush=True)

    # splash banded frontier at long S (gates seq_attn_bench long rows)
    from paddle_tpu.ops.pallas.splash_attention import (
        banded_block_mask, splash_attention)

    def compile_splash(S, blk, window, phase):
        q = jnp.asarray(rng.standard_normal((1, 4, S, D)), jnp.bfloat16)
        bm = banded_block_mask(S, S, blk, blk, window, causal=True)
        return probe(
            lambda a, b, c: splash_attention(a, b, c, bm, True, None,
                                             blk, blk, window),
            phase, q, q, q)

    for S, window, blk in ((8192, 2048, 256), (16384, 2048, 256)):
        for phase in ("fwd", "fwdbwd"):
            r = compile_splash(S, blk, window, phase)
            print(json.dumps(
                {"kernel": "splash", "S": S, "window": window,
                 "block": blk, "phase": phase, **r}), flush=True)


if __name__ == "__main__":
    main()
