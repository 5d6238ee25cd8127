"""The one decision of how Pallas kernels lower: Mosaic for a chip,
interpret mode for the CPU backend.

Every kernel module asks ``interpret()``; the model code that forks on
"is this program for an accelerator" (fused CE, in-jit moment offload,
flash in the functional layers) asks ``lowering_for_chip()``. On the CPU
backend kernels run interpreted — ordinary HLO, which is what the test
suite needs. ``lower_for_chip()`` is for ahead-of-time compilation
against a TPU topology from a host that has no chip: inside it the same
traces take the chip branches, so a Mosaic refusal shows without
spending chip time. It never runs anything.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

_FOR_CHIP = contextvars.ContextVar("paddle_tpu_lower_for_chip",
                                   default=False)


def lowering_for_chip() -> bool:
    """True when the program being traced is meant for an accelerator:
    the default backend is one, or an AOT ``lower_for_chip()`` scope is
    open."""
    return _FOR_CHIP.get() or jax.default_backend() != "cpu"


def interpret() -> bool:
    """``pallas_call(interpret=...)`` for every kernel in this package."""
    return not lowering_for_chip()


@contextlib.contextmanager
def lower_for_chip():
    """Trace chip branches on a chip-less host (AOT compile checks)."""
    token = _FOR_CHIP.set(True)
    try:
        yield
    finally:
        _FOR_CHIP.reset(token)
