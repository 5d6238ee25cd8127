"""Readers of a training window's steps."""
from __future__ import annotations


def tokens_per_s_chip(obs, params):
    """Tokens of all steps completed in the window over the whole window
    (first enqueue to the last step's barrier), over chips."""
    if obs["kind"] != "train" or obs["window_s"] <= 0:
        return None
    return obs["steps"] * obs["tokens_per_step"] / obs["window_s"] / obs["chips"]


def step_p50_ms(obs, params):
    return obs["step_stats"].get("p50_ms") if obs["kind"] == "train" else None


def excess_share(obs, params):
    """Seconds of steps beyond the median step, over the window: the stalls."""
    if obs["kind"] != "train" or "excess_s" not in obs["step_stats"]:
        return None
    return 100.0 * obs["step_stats"]["excess_s"] / obs["window_s"]
