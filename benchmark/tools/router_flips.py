#!/usr/bin/env python3
"""Where a routed model's served-token gaps come from, and the reading of a
planted fault, at a cell's own size (``deepseek_v3`` family).

    python3 benchmark/tools/router_flips.py --workload serve_latent_moe_docqa \
        --seed 5 --seconds 40 --out chiprun_out/flips_5.json

One process: the cell's window with ``fault="token_altered"`` (one served
token of the longest request changed after it was served: the harness's
own planted fault, read by the harness's own comparison), then, on the
sample the harness drew and with the fault undone, one pass per sampled
request through

* the plain reference (float32), which judges: at every served position its
  logits, in every sparse layer its chosen experts and the margin between
  the last chosen and the first not chosen score (``s + b``);
* the program's own layer mathematics in the program's precision (bfloat16
  weights and activations, ``layer_math`` with the expanded attention of
  ``full_forward`` taken in blocks of query rows: **no absorbed product, no
  kernel, no cache**), teacher-forced on the same tokens: its chosen experts
  and its best token;
* the control (the reference at int8): the same two.

A position is *flipped* for a side when any sparse layer's chosen set
differs from the reference's.  The line ``flips {...}`` sets the gaps
against that: the served tokens' gaps by the reference's smallest margin,
each side's best token's gap at flipped and at unflipped positions.  Every
position's numbers go to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))

Q_ROWS = 512            # query rows a block of the program-side attention


def reference_pass(family, model, quant):
    """-> ``run(weights, tokens, rows)`` giving (logits (rows, V), chosen
    (layers, rows, k), margin (layers, rows)) of the sparse layers, by the
    reference's own functions: ``R.layer`` gives the next hidden state, the
    probe computes the router's input again."""
    import jax
    import jax.numpy as jnp
    R, k = family.R, model["num_experts_per_tok"]
    eps = model["rms_norm_eps"]

    @jax.jit
    def probe(w, x, pos, rows):
        nxt = R.layer(model, w, x, pos, quant)
        if "mlp.gate.weight" not in w:
            return nxt, None
        h = R.rms_norm(x, w["input_layernorm.weight"], eps)
        mid = x + R.latent_attention(model, w, h, pos, quant)
        h = R.rms_norm(jnp.take(mid, rows, axis=0), w["post_attention_layernorm.weight"], eps)
        s = jax.nn.sigmoid(R._mm(h, w["mlp.gate.weight"], quant)) \
            + w["mlp.gate.e_score_correction_bias"].astype(R.F32)[None, :]
        top, idx = jax.lax.top_k(s, k + 1)
        return nxt, (idx[:, :k], top[:, k - 1] - top[:, k])

    embed, _, head = family.reference_programs(model, quant)

    def run(weights, tokens, rows):
        x = embed(weights["model.embed_tokens.weight"], tokens)
        pos = jnp.arange(tokens.shape[0])
        chosen, margin = [], []
        for i in range(model["num_hidden_layers"]):
            x, routed = probe(R.layer_weights(weights, i), x, pos, rows)
            if routed is not None:
                chosen.append(routed[0])
                margin.append(routed[1])
        logits = head(weights["model.norm.weight"], weights["lm_head.weight"], x, rows)
        return logits, jnp.stack(chosen), jnp.stack(margin)
    return run


def program_pass(net):
    """-> ``run(tokens, rows)`` giving (logits (rows, V) float32, chosen
    (layers, rows, k)): the program's ``layer_math`` in its own precision
    over the whole sequence at once."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.nlp import deepseek_v3 as P
    from paddle_tpu.models.nlp.expert_layer import ROUTER, ROUTER_BIAS, route
    cfg = net.config
    outer, layers = net.decode_params()

    @jax.jit
    def layer(lp, x, rows):
        S = x.shape[1]

        def attend(q_nope, q_rope, latent):
            def block(i):
                cut = partial(jax.lax.dynamic_slice_in_dim, start_index=i * Q_ROWS,
                              slice_size=Q_ROWS, axis=1)
                mask = (i * Q_ROWS + jnp.arange(Q_ROWS))[:, None] >= jnp.arange(S)[None, :]
                return P.expanded_attend(cfg, lp, mask)(cut(q_nope), cut(q_rope), latent)[0][0]
            attn = jax.lax.map(block, jnp.arange(S // Q_ROWS)).reshape(1, S, -1)
            return attn, attn
        nxt, attn, _ = P.layer_math(cfg, lp, x, jnp.arange(S), attend)
        if ROUTER not in lp:
            return nxt, None
        h = P._rms(jnp.take(x + attn, rows, axis=1)[0],
                   lp["post_attention_layernorm.weight"], cfg.rms_norm_eps)
        return nxt, route(cfg, lp[ROUTER], lp[ROUTER_BIAS], h)[1]

    @jax.jit
    def head(outer, x, rows):
        x = P._rms(jnp.take(x, rows, axis=1)[0], outer["model.norm.weight"], cfg.rms_norm_eps)
        return P._logits(cfg, outer, x).astype(jnp.float32)

    def run(tokens, rows):
        x = jnp.take(outer["model.embed_tokens.weight"], tokens, axis=0)[None]
        chosen = []
        for lp in layers:
            x, idx = layer(lp, x, rows)
            if idx is not None:
                chosen.append(idx)
        return head(outer, x, rows), jnp.stack(chosen)
    return run


def flipped_layers(chosen, ref_chosen) -> np.ndarray:
    """(layers, rows, k) twice -> (rows,) sparse layers whose chosen SET differs."""
    a, b = np.sort(np.asarray(chosen), -1), np.sort(np.asarray(ref_chosen), -1)
    return (a != b).any(-1).sum(0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", metavar="DIR",
                    help="no chip: run on what JAX has, with the benchmark directory DIR (a toy copy)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from benchmark.harness import device, serve, serve_check, traffic, weights as W
    from benchmark.harness.spec import Spec
    spec = Spec(Path(args.rehearse or HERE))
    cell = spec.cell(args.workload)
    device.enable_cache(HERE.parent)
    devices = jax.devices()[:1] if args.rehearse else device.require_chips(cell["chips"])
    family, config, mix = cell["family"], cell["config_spec"], cell["traffic_spec"]
    model = config["model"]

    out = serve.run(spec, cell, args.seed, args.seconds, False, devices,
                    device.CompileCounter(), time.perf_counter(), fault="token_altered")
    fault = {k: v["value"] for k, v in out["checks"].rows.items() if k.startswith("served_gap")}
    rows_done = out["obs"]["requests"]
    victim = max(rows_done, key=lambda r: r["prompt_len"] + len(r["output"]))
    victim["output"][len(victim["output"]) // 2] ^= 1           # the fault undone
    reqs = traffic.serve_requests(mix, args.seconds, args.seed, model["vocab_size"])
    sample = serve_check.pick_sample(rows_done, reqs, args.seed, mix)

    weights = W.make_weights(family, model, args.seed)
    pad_to, out_rows = family.pad_length(mix), int(mix["output"]["max"])
    pad_to = -(-pad_to // Q_ROWS) * Q_ROWS
    net = family.serving_program(model, {"max_len": pad_to})
    net.load_tree(weights)
    plain = reference_pass(family, model, None)
    control = reference_pass(family, model, "int8")
    program = program_pass(net)
    per = {k: [] for k in ("rid", "k", "served_gap", "margin_min", "program_flips",
                           "program_gap", "program_serves_same", "control_flips",
                           "control_gap")}
    for s in sample:
        n, p = len(s["output"]), len(s["prompt"])
        seq = np.zeros(pad_to, np.int32)
        seq[:p + n] = list(s["prompt"]) + list(s["output"])
        at = np.zeros(out_rows, np.int32)
        at[:n] = np.arange(p - 1, p - 1 + n)
        tokens, rows = jnp.asarray(seq), jnp.asarray(at)
        ref, ref_chosen, margin = plain(weights, tokens, rows)
        gap_of = lambda judged: np.asarray(            # noqa: E731
            jnp.max(ref, -1) - jnp.take_along_axis(ref, judged[:, None], -1)[:, 0])[:n]
        served = jnp.asarray(np.pad(np.asarray(s["output"], np.int32), (0, out_rows - n)))
        prog, prog_chosen = program(tokens, rows)
        ctl, ctl_chosen, _ = control(weights, tokens, rows)
        per["rid"] += [s["rid"]] * n
        per["k"] += list(range(n))
        per["served_gap"] += gap_of(served).tolist()
        per["margin_min"] += np.asarray(jnp.min(margin, 0))[:n].tolist()
        per["program_flips"] += flipped_layers(prog_chosen, ref_chosen)[:n].tolist()
        per["program_gap"] += gap_of(jnp.argmax(prog, -1)).tolist()
        per["program_serves_same"] += np.asarray(jnp.argmax(prog, -1) == served)[:n].tolist()
        per["control_flips"] += flipped_layers(ctl_chosen, ref_chosen)[:n].tolist()
        per["control_gap"] += gap_of(jnp.argmax(ctl, -1)).tolist()

    a = {k: np.asarray(v) for k, v in per.items()}

    def split(gap, flips):
        f = flips > 0
        stat = lambda g: None if not g.size else {     # noqa: E731
            "positions": int(g.size), "mean": float(g.mean()), "max": float(g.max()),
            "over_0.3": int((g > 0.3).sum())}
        return {"flipped": stat(gap[f]), "unflipped": stat(gap[~f])}

    by_margin = {}
    for lo, hi in ((0.0, 0.002), (0.002, 0.005), (0.005, 0.01), (0.01, 0.02), (0.02, 9.0)):
        g = a["served_gap"][(a["margin_min"] >= lo) & (a["margin_min"] < hi)]
        by_margin[f"{lo}-{hi}"] = {"positions": int(g.size),
                                   "mean": float(g.mean()) if g.size else None,
                                   "max": float(g.max()) if g.size else None,
                                   "over_0.3": int((g > 0.3).sum())}
    summary = {
        "seed": args.seed, "tokens": int(a["served_gap"].size),
        "fault_token_altered": fault,
        "sound": {"served_gap_max": float(a["served_gap"].max()),
                  "served_gap_mean": float(a["served_gap"].mean())},
        "served_gap_by_reference_margin": by_margin,
        "served_gap_by_program_side_flip": split(a["served_gap"], a["program_flips"]),
        "program_side": dict(split(a["program_gap"], a["program_flips"]),
                             serves_same_token=float(a["program_serves_same"].mean())),
        "control": split(a["control_gap"], a["control_flips"]),
        "memory_peak_bytes": device.memory_peak_bytes(devices)}
    print("flips " + json.dumps(summary), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps({"summary": summary, "positions": per}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
