"""Device / place abstraction.

TPU-native equivalent of the reference's ``phi::Place`` hierarchy
(paddle/phi/common/place.h) and ``paddle.device.set_device``
(python/paddle/device/__init__.py). A Place is a thin view over a
``jax.Device``; there are no streams to manage — XLA owns scheduling.
"""
from __future__ import annotations

import functools
import threading

import jax

_state = threading.local()


class Place:
    """Base place. Mirrors phi::Place (paddle/phi/common/place.h)."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    @property
    def jax_device(self) -> jax.Device:
        devs = _devices_of_type(self.device_type)
        if self.device_id >= len(devs):
            raise ValueError(
                f"device {self.device_type}:{self.device_id} out of range "
                f"({len(devs)} present)")
        return devs[self.device_id]


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPlace(TPUPlace):
    """Accelerator-place API-compat alias (~ paddle.CUDAPlace): on this
    framework the accelerator is the TPU, so CUDAPlace(i) denotes device i
    of the default accelerator platform."""


class CUDAPinnedPlace(CPUPlace):
    """~ paddle.CUDAPinnedPlace — host memory; jax manages pinned staging
    buffers itself, so this is the CPU place."""


class NPUPlace(TPUPlace):
    """~ paddle.NPUPlace API-compat alias (custom accelerator slot)."""


class XPUPlace(TPUPlace):
    """~ paddle.XPUPlace API-compat alias."""


@functools.lru_cache(maxsize=None)
def _devices_of_type(kind: str):
    if kind == "cpu":
        return jax.devices("cpu")
    # "tpu" names the default accelerator platform; with none present
    # there is no such device — never the CPU under the chip's name
    accel = [d for d in jax.devices() if d.platform != "cpu"]
    if not accel:
        raise RuntimeError(
            f"no accelerator device: jax sees only "
            f"{jax.devices()[0].platform!r} (place type {kind!r})")
    return accel


def _parse(device: str) -> Place:
    device = device.lower()
    if ":" in device:
        kind, idx = device.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("cpu",):
        return CPUPlace()
    if kind in ("tpu", "xla", "gpu"):  # accept 'gpu' for script compat
        return TPUPlace(idx)
    raise ValueError(f"unknown device {device!r}")


def set_device(device) -> Place:
    """paddle.device.set_device equivalent. Raises when the named device
    does not exist (``set_device("tpu")`` on a host with no chip)."""
    place = device if isinstance(device, Place) else _parse(device)
    place.jax_device  # resolve now: a missing device fails here, loudly
    _state.place = place
    return place


def get_device() -> str:
    p = _current_expected_place()
    return f"{p.device_type}:{p.device_id}"


def _current_expected_place() -> Place:
    p = getattr(_state, "place", None)
    if p is None:
        accel = [d for d in jax.devices() if d.platform != "cpu"]
        p = TPUPlace(0) if accel else CPUPlace()
        _state.place = p
    return p


def is_compiled_with_tpu() -> bool:
    return any(d.platform != "cpu" for d in jax.devices())


def device_count() -> int:
    return len(_devices_of_type(_current_expected_place().device_type))
