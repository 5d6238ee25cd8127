"""The chip: finding it, the compile cache, compile counting, memory."""
from __future__ import annotations

import os
from pathlib import Path

import jax


class NoChip(RuntimeError):
    pass


def enable_cache(checkout: Path) -> str:
    """JAX's persistent cache at a fixed place inside the checkout (the
    path is part of the cache's key), unless the machine names one."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = str(checkout / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def require_chips(n: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no accelerator: JAX reports platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX reports {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Counts programs lowered in this process (a new shape lowers even
    when the persistent cache then supplies the binary)."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class Marks:
    """Seconds between named points of set-up, and the fullest chip's memory
    peak at each, for the information line: a process's peak never falls
    again, so the marks say which part set it."""

    def __init__(self, t0: float, devices=()):
        self.last, self.parts, self.peak_bytes, self.devices = t0, {}, {}, devices

    def add(self, name: str):
        import time
        now = time.perf_counter()
        self.parts[name] = round(self.parts.get(name, 0.0) + now - self.last, 3)
        self.last = now
        if self.devices:
            self.peak_bytes[name] = memory_peak_bytes(self.devices)
