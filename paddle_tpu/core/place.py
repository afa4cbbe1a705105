"""Places: device handles for the TPU-native runtime.

Parity: reference Place variant (/root/reference/paddle/fluid/platform/
place.h:79) with CPUPlace/CUDAPlace/CUDAPinnedPlace. Here the accelerator
place is TPUPlace backed by a PJRT device obtained from JAX; CPUPlace maps
to the host platform. DeviceContextPool's role (per-device streams,
device_context.h:243) is subsumed by PJRT/JAX's async dispatch — a Place
just resolves to a jax.Device.

A place names ONE platform and never resolves to another: TPUPlace on a
host where JAX found no TPU raises, it does not hand back a CPU device.
"""
from __future__ import annotations

import os

import jax


def _local_devices(platform):
    """(LOCAL devices of *platform*, None), or ((), the RuntimeError
    JAX raised) when this process has no such backend — the error
    carries the runtime's own reason (e.g. why libtpu failed to
    initialise). A Place is a per-process device handle (like the
    reference's CUDAPlace(dev_id) per trainer process); under
    jax.distributed another process's device is not addressable."""
    try:
        return tuple(jax.local_devices(backend=platform)), None
    except RuntimeError as err:
        return (), err


class Place:
    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def jax_device(self):
        raise NotImplementedError

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    def jax_device(self):
        # host places are logical (reference CPU_NUM may exceed the
        # host devices JAX exposes), so the id wraps
        devs, err = _local_devices("cpu")
        if not devs:
            raise RuntimeError(
                f"{self!r}: JAX has no 'cpu' platform in this process "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): "
                f"{err}") from err
        return devs[self.device_id % len(devs)]


class TPUPlace(Place):
    """First-class accelerator place (north-star: fluid.TPUPlace(0)):
    chip ``device_id`` of this process's TPU backend, or an error."""

    def jax_device(self):
        devs, err = _local_devices("tpu")
        if not devs:
            raise RuntimeError(
                f"{self!r}: JAX has no 'tpu' platform in this process; "
                f"it found {sorted({d.platform for d in jax.devices()})} "
                f"(default backend {jax.default_backend()!r}). JAX "
                f"said: {err}") from err
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: this process has {len(devs)} local TPU "
                f"device(s); valid device_id is 0..{len(devs) - 1}")
        return devs[self.device_id]


# Alias so code written against the reference's GPU naming keeps working.
CUDAPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    """Reference CUDAPinnedPlace (page-locked host staging memory).
    TPU transfers stage through the PJRT runtime's own pinned buffers,
    so this is host memory by another name — kept for API parity."""


def cpu_places(device_count=None):
    """Reference fluid.cpu_places: CPU_NUM CPUPlaces."""
    n = device_count or int(os.environ.get("CPU_NUM", 1))
    return [CPUPlace(i) for i in range(n)]


def cuda_places(device_ids=None):
    """Reference fluid.cuda_places — here: one place per local TPU chip,
    exactly as many as JAX reports (none on a host without a TPU)."""
    if device_ids is not None:
        return [TPUPlace(int(i)) for i in device_ids]
    return [TPUPlace(i) for i in range(len(_local_devices("tpu")[0]))]


tpu_places = cuda_places


def cuda_pinned_places(device_count=None):
    n = device_count or int(os.environ.get("CPU_NUM", 1))
    return [CUDAPinnedPlace(i) for i in range(n)]


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_place() -> Place:
    """Follows JAX's default backend: TPUPlace(0) where JAX runs on a
    TPU, CPUPlace(0) otherwise."""
    return TPUPlace(0) if is_compiled_with_tpu() else CPUPlace(0)
