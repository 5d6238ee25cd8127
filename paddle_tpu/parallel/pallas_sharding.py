"""Manual sharding wrapper for Pallas attention kernels.

A Mosaic custom call cannot be partitioned by GSPMD at all: on a chip,
lowering one under a mesh of more than one device raises unless EVERY
mesh axis is manual at the call ("Mosaic kernels cannot be
automatically partitioned"). Every flash-attention call site therefore
routes through ``shard_map_attention``: all free mesh axes go manual,
heads split over the 'model' axis and batch over 'data'; any other axis
holds replicas. (On the CPU backend the kernels are interpreted into
ordinary HLO and would partition, which is why only a chip compile —
tests/test_chip_aot.py — can see a violation of this rule.)

One implementation for the three call-site families (LlamaAttention,
llama_functional.layer_forward inside the partial-manual pipeline, and
the public nn.functional.scaled_dot_product_attention) so guards cannot
drift.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

# test hook: set True whenever a wrapped (manual) kernel launch is traced
ENGAGED = {"flag": False}


class PallasShardingError(ValueError):
    """A Pallas kernel sits under a mesh it cannot be split over."""


def shard_map_attention(fn, q, k, v, mesh=None, head_axis: str = "model",
                        batch_axis: str = "data"):
    """Run ``fn(q, k, v)`` (layout (B, H, S, D); k/v may carry fewer heads
    — GQA) per device: heads manual over ``head_axis``, batch manual over
    ``batch_axis``, replicated over every other free axis.

    mesh=None takes the context abstract mesh (``jax.sharding.set_mesh``
    scopes and enclosing shard_map regions — only its AUTO axes are still
    free there); a concrete mesh is used as given (the train-step
    factories pass theirs). With no mesh, or a one-device mesh, this is a
    plain ``fn(q, k, v)``. A head or batch dimension that its >1 axis
    does not divide raises ``PallasShardingError``.
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
        free = tuple(mesh.auto_axes)
    else:
        free = tuple(mesh.axis_names)
    if math.prod(mesh.shape[a] for a in free) <= 1:
        return fn(q, k, v)

    def split(axis, what, *dims):
        if axis not in free or mesh.shape[axis] <= 1:
            return None
        if any(d % mesh.shape[axis] for d in dims):
            raise PallasShardingError(
                f"Pallas attention under mesh {dict(mesh.shape)}: axis "
                f"{axis!r} (size {mesh.shape[axis]}) does not divide the "
                f"{what} of q{tuple(q.shape)} / k{tuple(k.shape)}; a "
                "Mosaic kernel cannot be partitioned automatically")
        return axis

    h_ax = split(head_axis, "head counts", q.shape[1], k.shape[1])
    b_ax = split(batch_axis, "batch", q.shape[0])
    spec = P(b_ax, h_ax, None, None)
    out = jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                        out_specs=spec, check_vma=False,
                        axis_names=frozenset(free))(q, k, v)
    ENGAGED["flag"] = True  # after the call: a tracing failure above must
    #                         not leave the marker set
    return out
