"""Functional Llama: scan-over-layers + pipeline-parallel training.

The nn.Layer Llama (llama.py) is the eager/API surface; this module is the
scaled execution form:
  * layer params STACKED along a leading axis; the decoder stack runs as
    ``lax.scan`` over layer params — one compiled layer body regardless of
    depth (fast compiles, natural remat granularity), and the stacking is
    exactly what pipeline parallelism needs.
  * ``llama_pp_train_step_factory``: dp x pp training. Decoder layers are
    split into `pipe` stages (leading axis sharded over the 'pipe' mesh
    axis); microbatches flow through parallel.pipeline_apply (shard_map +
    ppermute), embedding/norm/lm-head run replicated outside the rotation.
    This is the compiled replacement for the reference's 1F1B runtime
    (SURVEY.md §2.2 pipeline rows) composed with data parallelism.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...ops.pallas.lowering import lowering_for_chip
from .llama import LlamaConfig, LlamaForCausalLM, apply_rotary

LAYER_KEYS = [
    "input_layernorm.weight",
    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
    "self_attn.v_proj.weight", "self_attn.o_proj.weight",
    "post_attention_layernorm.weight",
    "mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight",
]


def stack_layers(per_layer: list) -> Dict[str, jax.Array]:
    """List of L per-layer param dicts -> one dict of (L, ...) stacked
    leaves. THE stacking convention: train (scan-over-layers forward),
    pipeline stage splitting, and the decode factories all consume this
    layout, so a weight tree round-trips between them with no reshapes."""
    keys = per_layer[0].keys()
    return {k: jnp.stack([p[k] for p in per_layer]) for k in keys}


def unstack_layers(stacked: Dict[str, jax.Array]) -> list:
    """Inverse of stack_layers: (L, ...) leaves -> list of L dicts."""
    L = next(iter(stacked.values())).shape[0]
    return [{k: v[i] for k, v in stacked.items()} for i in range(L)]


def split_params(model: LlamaForCausalLM):
    """model state_dict -> (outer_params, stacked_layer_params)."""
    sd = {k: v._value for k, v in model.state_dict().items()}
    L = model.config.num_hidden_layers
    per_layer = [{key: sd.pop(f"model.layers.{i}.{key}")
                  for key in LAYER_KEYS} for i in range(L)]
    return sd, stack_layers(per_layer)


def merge_params(model: LlamaForCausalLM, outer, layers):
    sd = dict(outer)
    for i, lp in enumerate(unstack_layers(layers)):
        for key, leaf in lp.items():
            sd[f"model.layers.{i}.{key}"] = leaf
    model.load_tree(sd)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


_FORCE_FLASH_FOR_TESTS = False  # CPU interpret-mode flash in the factories


def layer_forward(cfg: LlamaConfig, p: Dict[str, jax.Array], x,
                  attn_mesh=None):
    """One decoder layer over its param dict (pure). ``attn_mesh``: the
    mesh to shard_map the flash kernel over when the caller runs under
    plain GSPMD (None = the context mesh of an enclosing shard_map)."""
    B, S, H = x.shape
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = H // nh
    h = _rms(x, p["input_layernorm.weight"], cfg.rms_norm_eps)
    q = (h @ p["self_attn.q_proj.weight"]).reshape(B, S, nh, hd)
    k = (h @ p["self_attn.k_proj.weight"]).reshape(B, S, nkv, hd)
    v = (h @ p["self_attn.v_proj.weight"]).reshape(B, S, nkv, hd)
    pos = jnp.arange(S)
    q = apply_rotary(q, pos, cfg.rope_theta)
    k = apply_rotary(k, pos, cfg.rope_theta)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)  # (B, nkv, S, hd) — true kv head count
    vt = jnp.swapaxes(v, 1, 2)
    use_flash = (S >= 256 and S % 128 == 0 and hd in (64, 128, 256)
                 and qt.dtype in (jnp.float32, jnp.bfloat16)
                 and (lowering_for_chip() or _FORCE_FLASH_FOR_TESTS))
    if use_flash:
        # GQA configs keep K/V at nkv heads (grouped kernel — no repeat
        # blowup through HBM)
        if nh != nkv:
            from ...ops.pallas.flash_attention_gqa import (
                grouped_flash_attention as _fa)
        else:
            from ...ops.pallas.flash_attention import flash_attention as _fa
        # GSPMD can't partition a Pallas call: when this stage body runs
        # with a >1 AUTO 'model' axis (the 4D factory's partial-manual
        # pipeline), the shared wrapper nests a shard_map so heads go
        # manual instead of all-gathering Q/K/V per microbatch
        from ...parallel.pallas_sharding import shard_map_attention
        ctx = shard_map_attention(lambda a, b, c: _fa(a, b, c, True),
                                  qt, kt, vt, mesh=attn_mesh)
    else:
        if nh != nkv:
            kt = jnp.repeat(kt, nh // nkv, axis=1)
            vt = jnp.repeat(vt, nh // nkv, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal, s, jnp.finfo(s.dtype).min)
        probs = jax.nn.softmax(s.astype(jnp.float32), -1).astype(qt.dtype)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    attn = jnp.swapaxes(ctx, 1, 2).reshape(B, S, H) \
        @ p["self_attn.o_proj.weight"]
    x = x + attn
    h2 = _rms(x, p["post_attention_layernorm.weight"], cfg.rms_norm_eps)
    mlp = (jax.nn.silu(h2 @ p["mlp.gate_proj.weight"])
           * (h2 @ p["mlp.up_proj.weight"])) @ p["mlp.down_proj.weight"]
    return x + mlp


def forward(cfg: LlamaConfig, outer, layers, tokens, remat=True):
    """Full causal-LM forward with lax.scan over stacked layers."""
    x = jnp.take(outer["model.embed_tokens.weight"], tokens, axis=0)

    body = (lambda carry, lp: (layer_forward(cfg, lp, carry), None))
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, layers)
    x = _rms(x, outer["model.norm.weight"], cfg.rms_norm_eps)
    head = outer.get("lm_head.weight")
    if head is None:
        return x @ outer["model.embed_tokens.weight"].T
    return x @ head


def _ce(logits, labels):
    """Causal-LM CE: Pallas fused softmax-xent on TPU (no (N,V) softmax
    HBM round-trip), dense log_softmax on CPU."""
    if lowering_for_chip():
        from ...ops.pallas.fused_ce import causal_lm_loss
        return causal_lm_loss(logits, labels)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    return jnp.mean(-jnp.take_along_axis(logp, labels[..., None], -1)[..., 0])


def loss_fn(cfg, outer, layers, tokens, labels, remat=True):
    logits = forward(cfg, outer, layers, tokens, remat)
    return _ce(logits, labels)


def llama_pp_train_step_factory(model: LlamaForCausalLM, mesh: Mesh,
                                n_microbatches: int = 2,
                                learning_rate=1e-4, weight_decay=0.01,
                                beta1=0.9, beta2=0.95, eps=1e-8,
                                remat: bool = True, n_virtual: int = 1):
    """dp x pp compiled training step.

    mesh axes: 'pipe' (required) and optionally 'data'. Decoder layers are
    evenly split over stages; stage leaf shape (n_stages, L/stage, ...).
    n_virtual > 1 switches to the breadth-first interleaved schedule
    (pipeline_apply_interleaved): layers lay out as (V, P, L/(P*V), ...)
    with round-robin stage placement, shrinking the pipeline bubble by V.
    Returns (params, opt_state, step_fn).
    """
    from ...parallel.pipeline import (pipeline_apply,
                                      pipeline_apply_interleaved)

    cfg = model.config
    n_stages = mesh.shape["pipe"]
    data_axis = "data" if "data" in mesh.axis_names else None
    L = cfg.num_hidden_layers
    V = n_virtual
    assert L % (n_stages * V) == 0, (L, n_stages, V)
    per = L // (n_stages * V)

    outer, layers = split_params(model)
    if V > 1:
        # (L, ...) -> (V, P, per, ...): [v, d] holds global stage v*P + d,
        # i.e. decoder layers (v*P + d)*per ... +per
        layers = jax.tree.map(
            lambda a: jnp.array(a, copy=True).reshape(
                (V, n_stages, per) + a.shape[1:]), layers)
        pipe_spec = P(None, "pipe")
    else:
        # reshape stacked layers (L, ...) -> (n_stages, per, ...)
        layers = jax.tree.map(
            lambda a: jnp.array(a, copy=True).reshape(
                (n_stages, per) + a.shape[1:]), layers)
        pipe_spec = P("pipe")
    outer = {k: jnp.array(v, copy=True) for k, v in outer.items()}

    rep = NamedSharding(mesh, P())
    pipe_sh = {k: NamedSharding(mesh, pipe_spec)
               for k in layers}
    outer_sh = {k: rep for k in outer}
    outer = {k: jax.device_put(v, rep) for k, v in outer.items()}
    layers = {k: jax.device_put(v, pipe_sh[k]) for k, v in layers.items()}

    params = {"outer": outer, "layers": layers}
    shardings = {"outer": outer_sh, "layers": pipe_sh}
    moments_sh = shardings

    def zeros_like_tree(tree, sh):
        return {k: jax.device_put(jnp.zeros(v.shape, jnp.float32), sh[k])
                for k, v in tree.items()}

    opt_state = {
        # committed to the mesh: an uncommitted scalar aval mismatches
        # the jit output's and recompiles the step (see make_adamw_state)
        "step": jax.device_put(jnp.zeros((), jnp.int32), rep),
        "m": {"outer": zeros_like_tree(outer, outer_sh),
              "layers": zeros_like_tree(layers, pipe_sh)},
        "v": {"outer": zeros_like_tree(outer, outer_sh),
              "layers": zeros_like_tree(layers, pipe_sh)},
    }

    def stage_fn(stage_params, x):
        body = lambda carry, lp: (layer_forward(cfg, lp, carry), None)
        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    def pipe_loss(params, tokens, labels):
        emb = jnp.take(params["outer"]["model.embed_tokens.weight"], tokens,
                       axis=0)
        if V > 1:
            h = pipeline_apply_interleaved(
                stage_fn, params["layers"], emb, mesh, n_microbatches,
                n_virtual=V, remat=remat, data_axis=data_axis,
                params_layout="vp")
        else:
            h = pipeline_apply(stage_fn, params["layers"], emb, mesh,
                               n_microbatches, remat=remat,
                               data_axis=data_axis)
        h = _rms(h, params["outer"]["model.norm.weight"], cfg.rms_norm_eps)
        head = params["outer"].get("lm_head.weight")
        logits = (h @ (head if head is not None
                       else params["outer"]["model.embed_tokens.weight"].T))
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return jnp.mean(
            -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0])

    def train_step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(pipe_loss)(params, tokens, labels)
        step = opt_state["step"] + 1
        t = step.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m2 = beta1 * m + (1 - beta1) * g
            v2 = beta2 * v + (1 - beta2) * jnp.square(g)
            mhat = m2 / (1 - beta1 ** t)
            vhat = v2 / (1 - beta2 ** t)
            delta = mhat / (jnp.sqrt(vhat) + eps) \
                + weight_decay * p.astype(jnp.float32)
            return ((p.astype(jnp.float32)
                     - learning_rate * delta).astype(p.dtype), m2, v2)

        new_p = {"outer": {}, "layers": {}}
        new_m = {"outer": {}, "layers": {}}
        new_v = {"outer": {}, "layers": {}}
        for grp in ("outer", "layers"):
            for k in params[grp]:
                new_p[grp][k], new_m[grp][k], new_v[grp][k] = upd(
                    params[grp][k], grads[grp][k],
                    opt_state["m"][grp][k], opt_state["v"][grp][k])
        return new_p, {"step": step, "m": new_m, "v": new_v}, loss

    batch_sh = NamedSharding(mesh, P(data_axis) if data_axis else P())
    jitted = jax.jit(
        train_step,
        in_shardings=({"outer": outer_sh, "layers": pipe_sh},
                      {"step": rep,
                       "m": {"outer": outer_sh, "layers": pipe_sh},
                       "v": {"outer": outer_sh, "layers": pipe_sh}},
                      batch_sh, batch_sh),
        donate_argnums=(0, 1))
    return params, opt_state, jitted


# ---------------------------------------------------------------------------
# Full 4D composition: data x sharding x model x pipe in ONE program
# ---------------------------------------------------------------------------

# TP layout of the stacked layer leaves (n_stages, per_stage, in, out):
# column-parallel projections shard the output dim over 'model',
# row-parallel shard the input dim (~ mp_layers.py ColumnParallelLinear:97 /
# RowParallelLinear:170 expressed as GSPMD specs)
_COL_KEYS = {"self_attn.q_proj.weight", "self_attn.k_proj.weight",
             "self_attn.v_proj.weight", "mlp.gate_proj.weight",
             "mlp.up_proj.weight"}
_ROW_KEYS = {"self_attn.o_proj.weight", "mlp.down_proj.weight"}


def llama_4d_train_step_factory(model: LlamaForCausalLM, mesh: Mesh,
                                n_microbatches: int = 2,
                                learning_rate=1e-4, weight_decay=0.01,
                                beta1=0.9, beta2=0.95, eps=1e-8,
                                remat: bool = True, n_virtual: int = 1):
    """ONE jitted train step over data x sharding x model x pipe.

    ~ the reference's 4D HybridCommunicateGroup axes
    (fleet/base/topology.py:52 ["data","pipe","sharding","model"]) — but
    composed by GSPMD in a single XLA program rather than four comm-group
    runtimes: 'pipe' rotates stages via ppermute inside a partial-manual
    shard_map, 'model' partitions the stage matmuls (TP), 'data' shards the
    microbatch, and 'sharding' holds the ZeRO-sharded adamw moments.
    Mesh axes absent (or size 1) degrade gracefully.
    """
    cfg = model.config
    # absent axes degrade to size 1 (the docstring contract): a planner
    # mesh may carry only the axes its plan actually uses
    n_stages = mesh.shape.get("pipe", 1)
    have = {a for a in mesh.axis_names if mesh.shape[a] > 1}
    data_axis = "data" if "data" in mesh.axis_names else None
    mdl = "model" if "model" in have else None
    L = cfg.num_hidden_layers
    V = n_virtual
    assert L % (n_stages * V) == 0, (L, n_stages, V)
    per = L // (n_stages * V)

    outer, layers = split_params(model)
    pipe_name = "pipe" if "pipe" in mesh.axis_names else None
    if pipe_name is None and n_microbatches > 1:
        # microbatching is a pipeline concept: without a pipe axis the
        # batch runs in one shot (use gradient_merge for accumulation),
        # so peak activation memory is NOT bounded by n_microbatches
        import warnings
        warnings.warn(
            "llama_4d_train_step_factory: mesh has no 'pipe' axis — "
            f"n_microbatches={n_microbatches} is ignored (full-batch "
            "step)", stacklevel=2)
    if V > 1:
        # (L, ...) -> (V, P, per, ...): [v, d] = global stage v*P + d
        # (breadth-first interleaved placement)
        layers = jax.tree.map(
            lambda a: jnp.array(a, copy=True).reshape(
                (V, n_stages, per) + a.shape[1:]), layers)
        pipe_prefix = [None, pipe_name]
    else:
        layers = jax.tree.map(
            lambda a: jnp.array(a, copy=True).reshape(
                (n_stages, per) + a.shape[1:]), layers)
        pipe_prefix = [pipe_name]
    outer = {k: jnp.array(v, copy=True) for k, v in outer.items()}

    def layer_spec(key, shape):
        spec = list(pipe_prefix) + [None] * (len(shape) - len(pipe_prefix))
        if mdl and key in _COL_KEYS and shape[-1] % mesh.shape[mdl] == 0:
            spec[-1] = mdl
        elif mdl and key in _ROW_KEYS and shape[-2] % mesh.shape[mdl] == 0:
            spec[-2] = mdl
        return P(*spec)

    def outer_spec(key, shape):
        if mdl and key == "model.embed_tokens.weight" \
                and shape[0] % mesh.shape[mdl] == 0:
            return P(mdl, None)   # vocab-parallel (~ VocabParallelEmbedding)
        if mdl and key == "lm_head.weight" \
                and shape[-1] % mesh.shape[mdl] == 0:
            return P(None, mdl)
        return P()

    def zero_spec(base: P, shape):
        """Moment layout: param spec + 'sharding' on the largest free,
        divisible dim (ZeRO over the 'sharding' axis)."""
        spec = list(base) + [None] * (len(shape) - len(base))
        if "sharding" in have:
            n = mesh.shape["sharding"]
            for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
                if spec[i] is None and shape[i] % n == 0 and shape[i] >= n:
                    spec[i] = "sharding"
                    break
        return P(*spec)

    layer_sh = {k: NamedSharding(mesh, layer_spec(k, v.shape))
                for k, v in layers.items()}
    outer_sh = {k: NamedSharding(mesh, outer_spec(k, v.shape))
                for k, v in outer.items()}
    layer_msh = {k: NamedSharding(mesh, zero_spec(layer_sh[k].spec, v.shape))
                 for k, v in layers.items()}
    outer_msh = {k: NamedSharding(mesh, zero_spec(outer_sh[k].spec, v.shape))
                 for k, v in outer.items()}

    outer = {k: jax.device_put(v, outer_sh[k]) for k, v in outer.items()}
    layers = {k: jax.device_put(v, layer_sh[k]) for k, v in layers.items()}
    params = {"outer": outer, "layers": layers}

    def zeros_tree(tree, sh):
        return {k: jax.device_put(jnp.zeros(v.shape, jnp.float32), sh[k])
                for k, v in tree.items()}

    rep = NamedSharding(mesh, P())
    opt_state = {
        "step": jax.device_put(jnp.zeros((), jnp.int32), rep),
        "m": {"outer": zeros_tree(outer, outer_msh),
              "layers": zeros_tree(layers, layer_msh)},
        "v": {"outer": zeros_tree(outer, outer_msh),
              "layers": zeros_tree(layers, layer_msh)},
    }

    def stage_fn(stage_params, x, attn_mesh=None):
        body = lambda carry, lp: (
            layer_forward(cfg, lp, carry, attn_mesh), None)
        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    auto = {a for a in ("model", "sharding") if a in mesh.axis_names}

    def pipe_loss(params, tokens, labels):
        emb = jnp.take(params["outer"]["model.embed_tokens.weight"], tokens,
                       axis=0)
        from ...parallel.pipeline import (pipeline_apply,
                                          pipeline_apply_interleaved)
        if pipe_name is None:
            # no pipe axis on the planner's mesh: run the single stage
            # in place (GSPMD still applies data/model/sharding layouts);
            # remat must survive the degradation — the pipe branches get
            # it inside pipeline_apply. Microbatching is a pipeline
            # concept: without a pipe axis the batch runs in one shot
            # (use gradient_merge for accumulation), so warn when the
            # caller asked for it.
            assert V == 1, "virtual stages need a 'pipe' mesh axis"
            stage0 = jax.tree.map(lambda a: a[0], params["layers"])
            # no enclosing shard_map here: hand the flash kernel the mesh
            # (a Mosaic call cannot lower under plain GSPMD)
            fn = partial(stage_fn, attn_mesh=mesh)
            fn = jax.checkpoint(fn) if remat else fn
            h = fn(stage0, emb)
        elif V > 1:
            h = pipeline_apply_interleaved(
                stage_fn, params["layers"], emb, mesh, n_microbatches,
                n_virtual=V, remat=remat, data_axis=data_axis,
                auto_axes=auto, params_layout="vp")
        else:
            h = pipeline_apply(stage_fn, params["layers"], emb, mesh,
                               n_microbatches, remat=remat,
                               data_axis=data_axis, auto_axes=auto)
        h = _rms(h, params["outer"]["model.norm.weight"], cfg.rms_norm_eps)
        head = params["outer"].get("lm_head.weight")
        logits = (h @ (head if head is not None
                       else params["outer"]["model.embed_tokens.weight"].T))
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return jnp.mean(
            -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0])

    def train_step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(pipe_loss)(params, tokens, labels)
        step = opt_state["step"] + 1
        t = step.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m2 = beta1 * m + (1 - beta1) * g
            v2 = beta2 * v + (1 - beta2) * jnp.square(g)
            mhat = m2 / (1 - beta1 ** t)
            vhat = v2 / (1 - beta2 ** t)
            delta = mhat / (jnp.sqrt(vhat) + eps) \
                + weight_decay * p.astype(jnp.float32)
            return ((p.astype(jnp.float32)
                     - learning_rate * delta).astype(p.dtype), m2, v2)

        new_p = {"outer": {}, "layers": {}}
        new_m = {"outer": {}, "layers": {}}
        new_v = {"outer": {}, "layers": {}}
        for grp in ("outer", "layers"):
            for k in params[grp]:
                new_p[grp][k], new_m[grp][k], new_v[grp][k] = upd(
                    params[grp][k], grads[grp][k],
                    opt_state["m"][grp][k], opt_state["v"][grp][k])
        return new_p, {"step": step, "m": new_m, "v": new_v}, loss

    batch_sh = NamedSharding(mesh, P(data_axis) if data_axis else P())
    param_sh = {"outer": outer_sh, "layers": layer_sh}
    mom_sh = {"outer": outer_msh, "layers": layer_msh}
    jitted = jax.jit(
        train_step,
        in_shardings=(param_sh,
                      {"step": rep, "m": mom_sh, "v": mom_sh},
                      batch_sh, batch_sh),
        out_shardings=(param_sh,
                       {"step": rep, "m": mom_sh, "v": mom_sh},
                       rep),
        donate_argnums=(0, 1))
    return params, opt_state, jitted
