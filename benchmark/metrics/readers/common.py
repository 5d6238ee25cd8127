"""Readers every kind of cell shares."""
from __future__ import annotations


def setup_s(obs, params):
    return obs["setup_s"]


def mfu(obs, params):
    """Model operations of the window (benchmark/flops.py) over window x
    chips x the chip's bf16 peak."""
    if obs.get("peak") is None or obs["window_s"] <= 0:
        return None
    return (100.0 * obs["model_flops"]
            / (obs["window_s"] * obs["chips"] * obs["peak"]["bf16_flops_per_s"]))
