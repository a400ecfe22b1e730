"""Device ("place") abstraction.

TPU-native re-design of the reference's Place variant
(reference: paddle/fluid/platform/place.h:26 CPUPlace, :37 CUDAPlace,
:103 ``Place`` boost::variant) and the DeviceContextPool
(paddle/fluid/platform/device_context.h:691).

On TPU there are no per-device streams or handle pools to manage — XLA
owns scheduling — so a Place is simply a binding to a ``jax.Device``.
A process-global "expected place" (mirroring the reference's
``_current_expected_place``) decides where new tensors materialise.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "XPUPlace", "CUDAPlace",
    "CUDAPinnedPlace", "set_device", "get_device", "device_count",
    "is_compiled_with_cuda", "is_compiled_with_xpu", "is_compiled_with_tpu",
]


class Place:
    """Base class of all places. Wraps a jax.Device."""

    _kind = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    # -- jax binding -------------------------------------------------------
    def jax_device(self) -> jax.Device:
        """The bound device.  A place whose platform is absent, or whose
        index is past the device count, is an error — never another
        device (a ``TPUPlace`` quietly meaning the host CPU, or
        ``TPUPlace(3)`` meaning chip 0, hides exactly the failures a
        bring-up has to see)."""
        devs = self._devices()
        if not 0 <= self._device_id < len(devs):
            raise ValueError(
                f"{self!r}: device index {self._device_id} out of range "
                f"— this process sees {len(devs)} {self._kind} "
                f"device(s) (jax.devices() = {jax.devices()})")
        return devs[self._device_id]

    def _devices(self):
        """The devices this kind of place indexes into."""
        return jax.devices()

    def get_device_id(self) -> int:
        return self._device_id

    def __repr__(self):
        return f"{type(self).__name__}({self._device_id})"

    def __eq__(self, other):
        return type(self) is type(other) and self._device_id == other._device_id

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def is_cpu_place(self):
        return isinstance(self, CPUPlace)

    def is_tpu_place(self):
        return isinstance(self, TPUPlace)

    def is_gpu_place(self):
        return isinstance(self, CUDAPlace)


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def _devices(self):
        return jax.devices("cpu")   # present beside any accelerator

    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    """The accelerator place. ``TPUPlace(n)`` <=> ``jax.devices()[n]``."""

    _kind = "tpu"

    def _devices(self):
        return [d for d in jax.devices() if d.platform != "cpu"]


class XPUPlace(TPUPlace):
    """Compat alias: the reference's Baidu-Kunlun place maps to the accelerator."""


class CUDAPlace(TPUPlace):
    """Compat alias so reference scripts using CUDAPlace(n) run unchanged."""


class CUDAPinnedPlace(CPUPlace):
    """Pinned host memory has no TPU analog; host arrays are already staged."""

    def __init__(self):
        Place.__init__(self, 0)


_expected_place: Optional[Place] = None


def _default_place() -> Place:
    global _expected_place
    if _expected_place is None:
        accel = [d for d in jax.devices() if d.platform != "cpu"]
        _expected_place = TPUPlace(0) if accel else CPUPlace()
    return _expected_place


_user_set_device = False


def _explicitly_set() -> bool:
    """True once the user called set_device — then new tensors commit to
    that place instead of staying uncommitted."""
    return _user_set_device


def set_device(device) -> Place:
    """paddle.set_device('tpu:0' | 'cpu' | 'gpu:0' | Place)."""
    global _expected_place, _user_set_device
    _user_set_device = True
    if isinstance(device, Place):
        _expected_place = device
        return device
    s = str(device).lower()
    if s.startswith("cpu"):
        _expected_place = CPUPlace()
    elif s.startswith(("tpu", "gpu", "xpu", "npu", "cuda")):
        idx = int(s.split(":")[1]) if ":" in s else 0
        _expected_place = TPUPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    return _expected_place


def get_device() -> str:
    p = _default_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"tpu:{p.get_device_id()}"


def device_count() -> int:
    return len(jax.devices())


def get_cudnn_version():
    """None: this build has no CUDA/cuDNN (parity: paddle.get_cudnn_version
    returns None when not compiled with CUDA)."""
    return None


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())
