"""Mesh and sharding helpers over the installed jax (0.9).

Everything the installed jax spells natively is called natively at the
call site (``jax.shard_map``, ``jax.sharding.set_mesh`` /
``get_abstract_mesh``, ``pltpu.CompilerParams``); what stays here is
the repo's own policy: meshes are built with AUTO axes, and trees are
placed on a mesh by PartitionSpec args.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names):
    """A device mesh over the first prod(shape) local devices with
    every axis ``AxisType.Auto``. ``jax.make_mesh`` defaults to
    Explicit axes, under which sharded contractions are type errors
    instead of GSPMD-inserted collectives — the serving and training
    factories all rely on GSPMD (Pallas calls go manual through
    ``shard_map`` on top of it)."""
    shape = tuple(int(s) for s in shape)
    return jax.make_mesh(shape, tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape))


def named_sharding(mesh, *names):
    """``NamedSharding(mesh, PartitionSpec(*names))`` in one call.
    ``names`` entries are mesh axis names or None (replicated dim); no
    names at all = fully replicated over the mesh."""
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*names))


def device_put_sharded(tree, mesh, specs=None):
    """Place every leaf of ``tree`` on ``mesh`` under ``specs``:

    - ``specs=None``: every leaf replicated (the activation-staging
      case — a host batch must live on ALL mesh devices before a
      sharded-weight program can consume it without an implicit
      default-device transfer);
    - a single PartitionSpec-args tuple: every leaf gets it;
    - a dict keyed like ``tree`` (flat param dicts): per-leaf spec
      tuples, missing keys replicated.
    """
    def _sh(spec):
        return named_sharding(mesh, *spec) if spec else \
            named_sharding(mesh)

    if isinstance(tree, dict) and isinstance(specs, dict):
        unknown = set(specs) - set(tree)
        if unknown:
            # a spec naming no leaf is a silent replication bug in the
            # making (a renamed weight key would quietly lose its
            # sharding and bloat every device) — refuse loudly instead
            raise ValueError(f"device_put_sharded: spec keys "
                             f"{sorted(unknown)} name no tree leaf")
        return {k: jax.device_put(v, _sh(specs.get(k)))
                for k, v in tree.items()}
    sh = _sh(tuple(specs) if specs else ())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh),
                                  tree)
