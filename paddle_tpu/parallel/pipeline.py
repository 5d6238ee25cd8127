"""Compiled pipeline parallelism.

Replaces the reference's pipeline machinery (SURVEY.md §2.2): the dygraph
1F1B loop (fleet/meta_parallel/pipeline_parallel.py:81), NCCL p2p protocol
(pp_utils/p2p_communication.py:217), static SectionWorker
(framework/section_worker.cc) and the fleet_executor actor runtime
(distributed/fleet_executor/carrier.h:49).

TPU-native form: ONE SPMD program. Stage parameters are stacked along a
leading axis sharded over the 'pipe' mesh axis; a lax.scan steps the
software pipeline; jax.lax.ppermute rotates activations stage->stage over
ICI. Backward is jax.grad of the scan (ppermute transposes to the reverse
rotation), with jax.checkpoint on the stage body bounding activation
memory — the compiled equivalent of 1F1B's schedule-managed buffers.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def stack_stage_params(per_stage_params):
    """[stage_tree_0, ...] -> one tree with leading stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                   n_microbatches: int, axis: str = "pipe",
                   remat: bool = True, data_axis: str | None = None,
                   auto_axes=None, shard_input: bool = False):
    """Run ``stage_fn`` as a pipeline over mesh axis ``axis``.

    stage_fn(stage_params, activation) -> activation (same shape) — the body
    of ONE stage (e.g. a block of decoder layers).
    stacked_params: pytree, each leaf (n_stages, ...), sharded over `axis`.
    x: (batch, ...) global input; it is split into n_microbatches along
    batch inside the program.
    Returns y: (batch, ...) output of the last stage, replicated.

    Schedule: classic GPipe fill/steady/drain (n_micro + n_stages - 1
    ticks). Stage s at tick t computes micro (t - s). 1F1B's memory profile
    comes from remat + scan rather than schedule interleaving; the compiled
    program overlaps ppermute with the next tick's compute via XLA's
    latency-hiding scheduler.

    shard_input=True (requires n_microbatches % n_stages == 0): the
    microbatch buffer is sharded over the pipe axis instead of replicated
    — each stage stores M/P micros and the tick's micro is routed to
    stage 0 by a masked psum (one mb of comm per tick). Cuts the input
    buffer's per-stage memory by P at the cost of ~2x the final
    broadcast's comm volume spread over ticks.
    """
    n_stages = mesh.shape[axis]
    if shard_input and n_microbatches % n_stages != 0:
        raise ValueError(
            f"shard_input needs n_microbatches ({n_microbatches}) "
            f"divisible by n_stages ({n_stages})")
    body = jax.checkpoint(stage_fn) if remat else stage_fn

    def spmd(params, xm):
        # params: (1, ...) local stage slice; xm: microbatches — either
        # (M, mb, ...) replicated or (M/P, mb, ...) pipe-sharded
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        M = n_microbatches
        local_m = xm.shape[0]
        ticks = M + n_stages - 1
        state = jnp.zeros_like(xm[0])          # current activation buffer
        out_shape = (M,) + xm.shape[1:]
        outputs = jnp.zeros(out_shape, xm.dtype)  # last stage writes here

        def fetch_micro(xm, t):
            if not shard_input:
                mb_idx = jnp.clip(t, 0, M - 1)
                return jax.lax.dynamic_index_in_dim(xm, mb_idx, 0,
                                                    keepdims=False)
            # owner stage holds micro t at local index t % (M/P); route it
            # to everyone with a masked psum (stage 0 consumes)
            owner = jnp.clip(t, 0, M - 1) // local_m
            local_idx = jnp.clip(t, 0, M - 1) % local_m
            mine = jax.lax.dynamic_index_in_dim(xm, local_idx, 0,
                                                keepdims=False)
            return jax.lax.psum(
                jnp.where(stage == owner, 1.0, 0.0).astype(mine.dtype)
                * mine, axis)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (if in range) else keeps buffer
            injected = jax.lax.select(
                jnp.logical_and(stage == 0, t < M),
                fetch_micro(xm, t),
                state)
            out = body(params, injected)
            # last stage records micro (t - (n_stages-1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            write = jnp.logical_and(stage == n_stages - 1,
                                    t >= n_stages - 1)
            outputs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, out_idx, 0),
                lambda o: o, outputs)
            # rotate activations forward one stage over ICI
            nxt = jax.lax.ppermute(
                out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                       jnp.arange(ticks))
        # everyone returns the last stage's outputs (broadcast over axis)
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, 1.0, 0.0) * outputs, axis)
        return outputs

    B = x.shape[0]
    mb = B // n_microbatches
    xm = x.reshape((n_microbatches, mb) + x.shape[1:])
    return _launch(spmd, stacked_params, xm, mesh, axis, data_axis,
                   auto_axes, shard_input, B, stage_leading_spec=P(axis))


def pipeline_apply_interleaved(stage_fn: Callable, stacked_params, x,
                               mesh: Mesh, n_microbatches: int,
                               n_virtual: int, axis: str = "pipe",
                               remat: bool = True,
                               data_axis: str | None = None,
                               auto_axes=None,
                               params_layout: str = "stacked"):
    """Breadth-first interleaved pipeline (virtual pipeline stages).

    Exceeds both the GPipe schedule above and the reference's 1F1B (which
    carries a comment that interleaving is NOT implemented,
    pipeline_parallel.py:84): global stage s = v*P + d lives on device
    s % P as virtual chunk v = s // P (Megatron-style round-robin
    placement), and micro m's stage s runs at tick

        t(m, s) = (m // P)*P*V + s + (m % P)

    which satisfies the hop dependency t(m, s) = t(m, s-1) + 1 under a
    uniform +1 ring rotation — INCLUDING the wrap from device P-1 back to
    device 0 (the activation re-enters one tick later as chunk v+1, so no
    inter-chunk buffering exists at all). Every device does exactly one
    stage-computation per tick for the whole M*V working window: the only
    bubble is the ring skew, (P-1)/(M*V + P - 1) — a factor V smaller
    than GPipe's (P-1)/(M + P - 1).

    stacked_params: pytree with leading axis Sg = P*V in global stage
    order (params_layout="stacked"), or already laid out as (V, P, ...)
    with axis 1 sharded over `axis` (params_layout="vp" — what a train
    step should keep between iterations to avoid relayout). Requires
    n_microbatches % P == 0.
    """
    n_stages = mesh.shape[axis]
    V = n_virtual
    if V < 1:
        raise ValueError("n_virtual must be >= 1")
    if n_microbatches % n_stages != 0:
        raise ValueError(
            f"interleaved schedule needs n_microbatches "
            f"({n_microbatches}) divisible by n_stages ({n_stages})")
    lead = jax.tree_util.tree_leaves(stacked_params)[0].shape
    if params_layout == "vp":
        if lead[0] != V or lead[1] != n_stages:
            raise ValueError(
                f"vp-layout params lead with {lead[:2]}, expected "
                f"({V}, {n_stages})")
        params_vp = stacked_params
    else:
        if lead[0] != n_stages * V:
            raise ValueError(
                f"stacked params carry {lead[0]} stages, expected "
                f"n_stages*n_virtual = {n_stages * V}")
        # (Sg, ...) -> (V, P, ...): element [v, d] is global stage v*P + d
        params_vp = jax.tree.map(
            lambda l: l.reshape((V, n_stages) + l.shape[1:]), stacked_params)
    body = jax.checkpoint(stage_fn) if remat else stage_fn

    def spmd(params, xm):
        # params leaf: (V, 1, ...) local slice -> (V, ...)
        params = jax.tree.map(lambda p: p[:, 0], params)
        d = jax.lax.axis_index(axis)
        P_ = n_stages
        M = n_microbatches
        PV = P_ * V
        work = M * V
        ticks = work + P_ - 1
        state = jnp.zeros_like(xm[0])
        outputs = jnp.zeros((M,) + xm.shape[1:], xm.dtype)

        def tick(carry, t):
            state, outputs = carry
            u = t - d
            valid = jnp.logical_and(u >= 0, u < work)
            uc = jnp.clip(u, 0, work - 1)
            g = uc // PV
            v = (uc % PV) // P_
            r = uc % P_
            m = g * P_ + r
            inject = jnp.logical_and(jnp.logical_and(d == 0, v == 0), valid)
            x_in = jax.lax.select(
                inject,
                jax.lax.dynamic_index_in_dim(xm, m, 0, keepdims=False),
                state)
            pv = jax.tree.map(
                lambda l: jax.lax.dynamic_index_in_dim(l, v, 0,
                                                       keepdims=False),
                params)
            out = body(pv, x_in)
            emit = jnp.logical_and(
                jnp.logical_and(d == P_ - 1, v == V - 1), valid)
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, out, m, 0),
                lambda o: o, outputs)
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % P_) for i in range(P_)])
            return (nxt, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                       jnp.arange(ticks))
        outputs = jax.lax.psum(
            jnp.where(d == P_ - 1, 1.0, 0.0) * outputs, axis)
        return outputs

    B = x.shape[0]
    mb = B // n_microbatches
    xm = x.reshape((n_microbatches, mb) + x.shape[1:])
    return _launch(spmd, params_vp, xm, mesh, axis, data_axis, auto_axes,
                   False, B, stage_leading_spec=P(None, axis))


def _launch(spmd, params, xm, mesh, axis, data_axis, auto_axes,
            shard_input, B, stage_leading_spec):

    # batch (microbatch dim 1) may additionally shard over a data axis —
    # each data shard runs its own pipeline instance over the same stages
    in_axis0 = axis if shard_input else None
    x_spec = P(in_axis0, data_axis)
    out_spec = P(None, data_axis) if data_axis else P()
    in_specs = (jax.tree.map(lambda _: stage_leading_spec, params), x_spec)
    kw = {}
    if auto_axes:
        # partial-manual shard_map: 'pipe'/'data' rotate explicitly, the
        # listed axes (e.g. 'model' for TP, 'sharding' for ZeRO) stay with
        # GSPMD — the compiler partitions the stage body's matmuls from the
        # incoming param shardings (4D composition in ONE program)
        kw["axis_names"] = frozenset(
            a for a in mesh.axis_names if a not in auto_axes)
    fn = jax.shard_map(spmd, mesh=mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False, **kw)
    y = fn(params, xm)
    return y.reshape((B,) + y.shape[2:])
