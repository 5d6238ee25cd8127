"""A training cell: the compiled step its configuration's family builds.

Set-up builds one object, the compiled step with its state, drives it from
the seed through its first steps on the window's own call and feed, and
hands that same object to the window.  The window runs as a training job
does: the host stays at most ``RUN_AHEAD`` steps ahead of the device, reads
nothing back, and stops the clock at one barrier on the last step.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from . import device, train_check, weights as W
from .checks import Checks
from .gclog import GcLog
from .trace import TraceWindow, custom_calls

RUN_AHEAD = 2
CHECK_STEPS = 3
TRACED_STEPS = 6


def build_mesh(devices, job: dict):
    from jax.sharding import Mesh
    shape = job.get("mesh", {"data": 1})
    n = int(np.prod(list(shape.values())))
    if n != len(devices):
        raise ValueError(f"the job's mesh {shape} needs {n} chips, the cell gives {len(devices)}")
    return Mesh(np.asarray(devices).reshape(tuple(shape.values())), tuple(shape))


class Trainer:
    """The compiled step, its state and its feed: one object for set-up's
    first steps and for the window."""

    def __init__(self, family, config: dict, job: dict, seed: int, devices, fault=None,
                 marks=None):
        self.family, self.model, self.job, self.seed = family, config["model"], job, seed
        self.mesh = build_mesh(devices, job)
        mark = (lambda name: None) if marks is None else marks.add
        net = family.training_program(self.model, job)
        mark("program_model_object")
        shardings = family.param_shardings(net, self.mesh)
        family.load_weights(net, W.make_weights(family, self.model, seed, shardings))
        jax.block_until_ready(net.tree_flatten_params())
        mark("weights_from_seed")
        self.params, self.opt, self.step, batch_sh = family.train_step(net, self.mesh, job)
        family.drop_weights(net)        # the step holds its own copy
        mark("step_factory")
        tokens, labels = W.make_batches(
            seed, job["batch"], job["seq"], self.model["vocab_size"], batch_sh)
        self.batches = list(zip(tokens, labels))
        mark("batches")
        self.n = 0
        self.fault = fault

    def advance(self):
        """Enqueue one step; returns its loss, still on the device."""
        tokens, labels = self.batches[self.n % len(self.batches)]
        self.n += 1
        if self.fault == "state_unchanged":
            _, _, loss = self.step(_copy(self.params), _copy(self.opt), tokens, labels)
            return loss
        if self.fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens = jnp.concatenate([tokens[:half], tokens[:half]])
            labels = jnp.concatenate([labels[:half], labels[:half]])
        self.params, self.opt, loss = self.step(self.params, self.opt, tokens, labels)
        return loss


def _copy(tree):
    return jax.tree_util.tree_map(lambda a: a + jnp.zeros((), a.dtype), tree)


def step_stats(done_at: list[float], t_first: float) -> dict:
    """What the steps did, from their completion times."""
    ends = np.asarray(done_at)
    gaps = np.diff(np.concatenate([[t_first], ends]))[1:]   # the first holds the fill
    if gaps.size == 0:
        return {"count": int(ends.size)}
    p50 = float(np.median(gaps))
    slow = gaps[gaps > 1.05 * p50]
    where = np.flatnonzero(gaps > 1.05 * p50) + 1       # step number in the window
    return {"count": int(ends.size), "mean_ms": 1e3 * float(gaps.mean()),
            "p50_ms": 1e3 * p50, "max_ms": 1e3 * float(gaps.max()),
            "over_1.05_p50": int(slow.size),
            "lost_s": float((slow - p50).sum()),
            "excess_s": float(np.clip(gaps - p50, 0, None).sum()),
            "long_steps": [[int(i), round(1e3 * float(gaps[i - 1]), 1)] for i in where[:8]]}


def window(trainer: Trainer, seconds: float, run_ahead: int = RUN_AHEAD):
    """Steps for ``seconds``; the host never waits on the newest steps."""
    pending, done_at, losses = deque(), [], []
    t0 = time.perf_counter()
    while True:
        pending.append(trainer.advance())
        if len(pending) > run_ahead:
            loss = pending.popleft()
            loss.block_until_ready()
            done_at.append(time.perf_counter())
            losses.append(loss)
            if done_at[-1] - t0 >= seconds:
                break
    for loss in pending:
        loss.block_until_ready()
        done_at.append(time.perf_counter())
        losses.append(loss)
    return t0, done_at, losses


def traced_window(trainer: Trainer, tw: TraceWindow, steps: int):
    """A short stretch with a barrier after every step, under the profiler."""
    tw.arm()
    tw.start()
    t0 = time.perf_counter()
    done_at, losses = [], []
    for i in range(steps):
        with jax.profiler.StepTraceAnnotation("bench:step", step_num=i):
            with jax.profiler.TraceAnnotation("bench:step"):
                loss = trainer.advance()
                loss.block_until_ready()
        done_at.append(time.perf_counter())
        losses.append(loss)
    tw.finish()
    return t0, done_at, losses


def run(spec, cell, seed, seconds, trace, devices, counter, t_setup,
        fault=None, optional_checks=True, run_ahead=RUN_AHEAD, freeze=True) -> dict:
    family, config, job = cell["family"], cell["config_spec"], cell["traffic_spec"]
    model = config["model"]
    marks = device.Marks(t_setup, devices)
    marks.add("python_imports")
    trainer = Trainer(family, config, job, seed, devices, fault, marks)
    first = train_check.first_steps(trainer, CHECK_STEPS, optional_checks, marks)
    for _ in range(RUN_AHEAD + 1):      # step 4 onward: the state as the window finds it
        trainer.advance().block_until_ready()
    gc.collect()
    if freeze:          # what set-up left behind is not garbage: keep the collector off it
        gc.freeze()
    marks.add("steps_to_window")
    compiles_before = counter.count
    setup_s = time.perf_counter() - t_setup

    gc_log = GcLog()
    if trace:
        tw = TraceWindow(spec.root.parent / ".bench_trace")
        t0, done_at, losses = traced_window(trainer, tw, TRACED_STEPS)
    else:
        t0, done_at, losses = window(trainer, seconds, run_ahead)
    gc_log.close()
    compiles_in_window = counter.count - compiles_before
    window_s = done_at[-1] - t0
    losses = [float(x) for x in jax.device_get(losses)]
    memory_peak = device.memory_peak_bytes(devices)
    chips = len(devices)
    peak = spec.peak(devices[0].device_kind) if devices[0].platform == "tpu" else None
    stats = step_stats(done_at, t0)
    stats["gc"] = gc_log.summary()
    obs = {"kind": "train", "steps": len(done_at), "window_s": window_s,
           "step_ends_s": [t - t0 for t in done_at], "step_stats": stats,
           "tokens_per_step": job["batch"] * job["seq"], "chips": chips,
           "model_flops": len(done_at) * family.train_step_flops(model, job["batch"],
                                                                 job["seq"]),
           "peak": peak, "setup_s": setup_s, "model": model, "job": job}

    mesh = trainer.mesh
    del trainer
    gc.unfreeze()
    gc.collect()
    t_ref = time.perf_counter()
    numbers, extra = train_check.compare(family, model, job, seed, first, mesh)
    checks = Checks(cell["limits"])
    for name, value in numbers.items():
        checks.add(name, value)
    checks.add("loss_not_finite", sum(not np.isfinite(x) for x in losses + first["losses"]))
    checks.add("compiles_in_window", compiles_in_window)
    info = {"window_s": window_s, "setup_s": setup_s, "setup_parts": marks.parts,
            "memory_peak_at": marks.peak_bytes,
            "reference_s": time.perf_counter() - t_ref, "steps": stats,
            "losses_first": first["losses"], "ref_losses": extra["ref_losses"],
            "last_loss": losses[-1], "worst_leaves": extra["worst_leaves"],
            "left_out_of_change": extra["left_out_of_change"],
            "compiles_before_window": compiles_before}
    if trace:
        obs["device_trace"] = tw.reduce()
        if obs["device_trace"]:
            info["custom_calls"] = custom_calls(obs["device_trace"])
    return {"obs": obs, "checks": checks, "info": info, "attempted": len(done_at),
            "failed": sum(not np.isfinite(x) for x in losses),
            "memory_peak_bytes": memory_peak}
