"""Ulysses (DeepSpeed-style) sequence parallelism: head-scatter all-to-all.

Sister strategy to ring attention (parallel/ring_attention.py) for the
'sep' axis — the reference has neither (SURVEY.md §0/§5). Where the ring
rotates KV chunks P times over ICI, Ulysses does TWO all-to-alls total:

    in : (B, H,   S/P, D) sequence-sharded
    a2a: (B, H/P, S,   D) head-sharded     <- full sequence per device
    ... exact local attention over the full sequence ...
    a2a: (B, H,   S/P, D) sequence-sharded again

Comm volume is O(2·B·S·H·D/P) regardless of sequence length, vs the
ring's P·(KV volume); Ulysses wins when H >= P and attention is dense;
the ring wins when H < P or memory forbids holding the full sequence.
Exposing both lets the topology/planner pick per config.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _local_attention(q, k, v, causal: bool, sm_scale: float):
    """Attention on local (B, h, S, D) blocks. After the all-to-all each
    device holds the FULL sequence for its head shard, so this is plain
    attention — route through the flash kernel when shapes allow (chip:
    the dense-einsum path measured 0.47x flash throughput and O(S^2)
    memory, tools/seq_attn_bench.py), exact dense softmax otherwise."""
    from ..ops.pallas.flash_attention import flash_attention, flash_eligible
    if flash_eligible(q.shape[2], q.shape[-1], q.dtype):
        return flash_attention(q, k, v, causal, sm_scale)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sep",
                      causal: bool = True, sm_scale=None):
    """q/k/v: GLOBAL (batch, heads, seq, head_dim); the seq dim is sharded
    over mesh axis ``axis`` on entry and exit; internally heads are
    sharded instead (two lax.all_to_all hops). Heads must divide the axis
    size. Differentiable (shard_map of pure jnp ops)."""
    B, H, S, D = q.shape
    n = mesh.shape[axis]
    if H % n != 0:
        raise ValueError(f"heads {H} not divisible by '{axis}' size {n}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)

    def local(ql, kl, vl):
        # local blocks arrive (B, H, S/P, D); exchange seq-shards for
        # head-shards: concat seq along axis 2, split heads along axis 1
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh = seq_to_heads(ql)          # (B, H/P, S, D)
        kh = seq_to_heads(kl)
        vh = seq_to_heads(vl)
        oh = _local_attention(qh, kh, vh, causal, sm_scale)
        return heads_to_seq(oh)        # (B, H, S/P, D)

    spec = P(None, None, axis, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    sh = NamedSharding(mesh, spec)
    with mesh:
        return fn(jax.device_put(q, sh), jax.device_put(k, sh),
                  jax.device_put(v, sh))
