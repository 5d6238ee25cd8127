"""Runtime kernel autotuning with a persistent cache.

~ paddle/phi/kernels/autotune/ (AutoTuneBase auto_tune_base.h:48: time every
candidate once, pick the fastest; AutoTuneCache cache.h:144 keyed by op +
shape/dtype signature; switch_autotune.cc flag gating).

TPU shape: candidates are whole jitted callables (e.g. a Pallas kernel at
several block sizes) — each is compiled + timed on the real arguments the
first time a (op, signature) key is seen; the winner is cached for the
process and exportable/importable like the reference's cache file.
"""
from __future__ import annotations

import json
import time
import warnings
from typing import Any, Callable, Dict, Sequence

import jax

from ..core import flags as _flags

_flags.define_flag("use_autotune", False, "enable runtime kernel autotune")


class AutoTuneCache:
    """(op, signature) -> chosen candidate index (+ timings for report)."""

    def __init__(self):
        self._cache: Dict[tuple, int] = {}
        self._timings: Dict[tuple, list] = {}
        self.hits = 0
        self.misses = 0

    def key(self, op: str, args, tag: str = "") -> tuple:
        """tag fingerprints the candidate list: persisted entries store a
        bare index, so a reordered/extended candidate set must produce a
        different key (stale imported entries are then simply unmatched)."""
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in args
                    if hasattr(a, "shape"))
        return (op, sig, tag)

    def get(self, key):
        if key in self._cache:
            self.hits += 1
            return self._cache[key]
        self.misses += 1
        return None

    def peek(self, key):
        """Lookup without touching the hit/miss statistics (for passive
        probes like the jit-trace path that never trigger a tune)."""
        return self._cache.get(key)

    def put(self, key, idx, timings=None):
        self._cache[key] = idx
        if timings is not None:
            self._timings[key] = timings

    def report(self):
        return {"hits": self.hits, "misses": self.misses,
                "size": len(self._cache)}

    def export(self, path: str):
        payload = {json.dumps(list(k)): v for k, v in self._cache.items()}
        with open(path, "w") as f:
            json.dump(payload, f)

    def load(self, path: str):
        def canon(x):
            return (tuple(canon(i) for i in x) if isinstance(x, list)
                    else x)

        with open(path) as f:
            payload = json.load(f)
        for k, v in payload.items():
            parts = json.loads(k)
            op, sig = parts[0], canon(parts[1])
            tag = parts[2] if len(parts) > 2 else ""
            self._cache[(op, sig, tag)] = v


_CACHE = AutoTuneCache()


def cache() -> AutoTuneCache:
    return _CACHE


def enable_autotune():
    _flags.set_flags({"use_autotune": True})


def disable_autotune():
    _flags.set_flags({"use_autotune": False})


def autotune_enabled() -> bool:
    return bool(_flags.get_flag("use_autotune"))


def _time_once(fn: Callable, args, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def autotune(op: str, candidates: Sequence[Callable], args,
             default: int = 0, tag: str = "") -> Callable:
    """Pick the fastest candidate for these argument shapes.

    Off (the default, like FLAGS_use_autotune): returns candidates[default].
    On: first call per (op, signature) times each candidate on the real
    args; later calls hit the cache. Pass a `tag` identifying the candidate
    set so persisted indices never dereference a different list.
    """
    if not autotune_enabled() or len(candidates) == 1:
        return candidates[default]
    key = _CACHE.key(op, args, tag)
    idx = _CACHE.get(key)
    if idx is not None:
        return candidates[idx]
    timings, errors = [], []
    for i, cand in enumerate(candidates):
        try:
            timings.append(_time_once(cand, args))
        except Exception as e:  # noqa: BLE001 — a candidate that cannot
            # compile or run loses the race, but never silently
            warnings.warn(f"autotune({op}): candidate {i} failed: {e!r}",
                          stacklevel=2)
            timings.append(float("inf"))
            errors.append(e)
    if len(errors) == len(candidates):
        raise RuntimeError(
            f"autotune({op}): every candidate failed") from errors[0]
    best = min(range(len(timings)), key=timings.__getitem__)
    _CACHE.put(key, best, timings)
    return candidates[best]


# ---- tuned flash attention -------------------------------------------------

# The canonical measured best-first ordering lives next to the kernels;
# sharing it keeps the tuner's candidate order and the resolver's
# auto-pick from ever diverging.
from .pallas.flash_attention import MEASURED_BLOCK_ORDER as _FA_BLOCKS


def tuned_flash_attention(q, k, v, causal=False, sm_scale=None):
    """Flash attention with autotuned (block_q, block_k).

    Candidates are block configs that divide the sequence lengths. Timing
    happens only on concrete (eager) calls; under a jit trace the cached
    choice for this signature is used (falling back to the default blocks),
    so the tune is race-free with compilation."""
    from .pallas.flash_attention import flash_attention
    Sq, Sk = q.shape[2], k.shape[2]
    configs = [(bq, bk) for bq, bk in _FA_BLOCKS
               if Sq % bq == 0 and Sk % bk == 0]
    if not configs:
        configs = [(None, None)]  # auto-pick divisor blocks in the kernel

    def make(bq, bk):
        def run(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal, sm_scale, bq, bk)
        return run

    cands = [make(bq, bk) for bq, bk in configs]
    tag = str(configs)
    if isinstance(q, jax.core.Tracer):
        idx = _CACHE.peek(
            _CACHE.key("flash_attention", (q, k, v), tag)) or 0
        return cands[idx](q, k, v)
    chosen = autotune("flash_attention", cands, (q, k, v), tag=tag)
    return chosen(q, k, v)
