"""Device / Place abstraction.

TPU-native analogue of Paddle's Place hierarchy (reference:
paddle/phi/common/place.h:23-185 — AllocationType, CPUPlace:109, GPUPlace:117)
and framework::InitDevices (paddle/fluid/platform/init.cc). On TPU there is no
vendor-SDK zoo: JAX/PJRT owns device enumeration, so a Place is a typed handle
to a `jax.Device` plus the `paddle.set_device` / `get_device` API
(reference: python/paddle/device/__init__.py).
"""
from __future__ import annotations

import threading

import jax


class Place:
    """Typed device identity. Wraps a jax.Device."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    @property
    def jax_device(self):
        devs = _devices_of_type(self.device_type)
        if not devs:
            raise RuntimeError(f"no {self.device_type} devices visible to JAX")
        if not 0 <= self._device_id < len(devs):
            raise ValueError(
                f"{self!r}: only {len(devs)} {self.device_type} device(s) "
                "visible to JAX")
        return devs[self._device_id]

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.device_type, self._device_id))


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    """The native accelerator Place (replaces reference GPUPlace/CUDAPlace)."""

    device_type = "tpu"


class CUDAPinnedPlace(CPUPlace):
    """Compatibility alias — on TPU pinned host memory is just host memory."""


class CUDAPlace(TPUPlace):
    """Compatibility alias (reference: phi/common/place.h:117 GPUPlace):
    scripts written for the reference's accelerator land on this build's
    accelerator. Device-id semantics carry over unchanged."""


class NPUPlace(TPUPlace):
    """Compatibility alias (reference: place.h:146 NPUPlace)."""


class XPUPlace(TPUPlace):
    """Compatibility alias (reference: place.h XPUPlace)."""


class MLUPlace(TPUPlace):
    """Compatibility alias (reference: place.h MLUPlace)."""


class IPUPlace(TPUPlace):
    """Compatibility alias (reference: place.h IPUPlace)."""


class CustomPlace(TPUPlace):
    """Compatibility alias (reference: place.h:185 CustomPlace)."""

    def __init__(self, device_type="tpu", device_id=0):
        super().__init__(device_id)


def _devices_of_type(kind: str):
    """The ``cpu`` or ``tpu`` platform's devices; ``[]`` when JAX has no such
    backend here. Never a stand-in from another platform."""
    try:
        return jax.devices(kind)
    except RuntimeError:
        return []


_state = threading.local()


def _default_place() -> Place:
    backend = jax.default_backend()
    if backend == "tpu":
        return TPUPlace(0)
    if backend == "cpu":  # the explicit test mode (JAX_PLATFORMS=cpu)
        return CPUPlace(0)
    raise RuntimeError(
        f"unsupported JAX backend {backend!r}: paddle_tpu runs on tpu or cpu")


def set_device(device) -> Place:
    """paddle.set_device — accepts 'cpu', 'tpu', 'tpu:0', or a Place."""
    if isinstance(device, Place):
        place = device
    else:
        s = str(device).lower()
        # accept reference spellings and map them onto the accelerator
        s = s.replace("gpu", "tpu").replace("xpu", "tpu").replace("npu", "tpu")
        if ":" in s:
            kind, _, idx = s.partition(":")
            idx = int(idx)
        else:
            kind, idx = s, 0
        if kind == "cpu":
            place = CPUPlace(idx)
        elif kind == "tpu":
            place = TPUPlace(idx)
        else:
            raise ValueError(f"unknown device {device!r}")
    _state.place = place
    return place


def get_device() -> str:
    p = _expected_place()
    return f"{p.device_type}:{p.get_device_id()}"


def _expected_place() -> Place:
    p = getattr(_state, "place", None)
    if p is None:
        p = _default_place()
        _state.place = p
    return p


def _set_expected_place(place: Place):
    _state.place = place


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return bool(_devices_of_type("tpu"))


def device_count() -> int:
    return len(_devices_of_type(_expected_place().device_type))
