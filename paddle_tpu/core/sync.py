"""Device synchronization helpers.

``jax.block_until_ready`` is a real barrier on the local chip and on the
CPU backend (chip_smoke.py's train phase checks it: a host read-back
after the barrier adds nothing), so ``hard_sync`` is exactly that.
"""
from __future__ import annotations

import jax


def hard_sync(value) -> None:
    """Block until every array in the pytree is materialized on device."""
    jax.block_until_ready(value)


def is_ready(value) -> bool:
    """Non-blocking readiness poll over a pytree (True when unknowable)."""
    for leaf in jax.tree_util.tree_leaves(value):
        probe = getattr(leaf, "is_ready", None)
        if probe is not None and not probe():
            return False
    return True
