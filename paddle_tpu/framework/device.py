"""Device ("Place") management.

TPU-native re-design of the reference's Place / DeviceContext machinery
(reference: paddle/fluid/platform/place.h:26-103 CPUPlace/CUDAPlace/...,
paddle/fluid/platform/device_context.h:61 DeviceContextPool,
python/paddle/device ``set_device``/``get_device``).

On TPU there are no per-device streams/handles to manage — the XLA runtime
owns contexts and buffers — so a Place reduces to a (kind, index) pair that
maps to a ``jax.Device``.  ``set_device`` installs the jax default device;
jit-compiled functions place outputs by sharding, not by Place.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from .errors import InvalidArgumentError, UnavailableError

__all__ = [
    "Place",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "set_device",
    "get_device",
    "device_count",
    "is_compiled_with_tpu",
    "is_compiled_with_cuda",
    "get_jax_device",
    "memory_stats",
    "XPUPlace",
    "on_tpu",
    "peak_bf16_tflops",
]


@dataclasses.dataclass(frozen=True)
class Place:
    """Device identity: kind ('cpu'|'tpu'|'gpu') + index.

    Parity: platform::Place (place.h:26); unlike the reference this is not a
    boost::variant — one dataclass covers all kinds.
    """

    kind: str
    index: int = 0

    def __str__(self):
        return f"{self.kind}:{self.index}"

    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if _kind_of(d) == self.kind]
        if not devs:
            # fall back to cpu backend (always present)
            if self.kind == "cpu":
                devs = jax.devices("cpu")
            else:
                raise UnavailableError(
                    f"No {self.kind} devices available; jax.devices()={jax.devices()}"
                )
        if self.index >= len(devs):
            raise InvalidArgumentError(
                f"Device index {self.index} out of range for {self.kind} "
                f"({len(devs)} available)"
            )
        return devs[self.index]


def CPUPlace(index: int = 0) -> Place:
    return Place("cpu", index)


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


def CUDAPlace(index: int = 0) -> Place:
    """Parity alias: maps to 'gpu' backend if jax has one."""
    return Place("gpu", index)


def XPUPlace(index: int = 0) -> Place:
    """Parity with the reference's Kunlun XPUPlace (place.h:62): on this
    framework every accelerator is reached through XLA, so XPU maps to the
    default accelerator kind."""
    return Place(_default_accel_kind(), index)


def _kind_of(d: jax.Device) -> str:
    plat = d.platform.lower()
    if plat == "tpu":
        return "tpu"
    if plat in ("gpu", "cuda", "rocm"):
        return "gpu"
    return "cpu"


def on_tpu() -> bool:
    """The one answer to "does this process compile for a TPU?".  Every
    Pallas ``interpret=`` switch and every ``*_eligible`` kernel gate asks
    here (through the module, ``device.on_tpu()``, so the compile-only
    tests can steer all of them from one place)."""
    return jax.default_backend() == "tpu"


#: Peak dense bf16 matmul rate per chip in TFLOP/s, keyed by
#: ``jax.Device.device_kind``.  Source: Google Cloud documentation, "TPU
#: v5e" system architecture (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
#: A kind that is not listed has no peak here and steptrace reports no
#: MFU — there is no default.
PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
}


def peak_bf16_tflops() -> Optional[float]:
    """Peak bf16 TFLOP/s of the first device's kind from
    :data:`PEAK_BF16_TFLOPS`, or None for a kind the table does not list."""
    return PEAK_BF16_TFLOPS.get(jax.devices()[0].device_kind)


def _default_accel_kind() -> str:
    for d in jax.devices():
        k = _kind_of(d)
        if k != "cpu":
            return k
    return "cpu"


_current_place: Optional[Place] = None


def set_device(device) -> Place:
    """Parity: ``paddle.set_device('tpu')`` / ``paddle.set_device('cpu')``.

    Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:1' or a Place. Installs the matching
    jax default device so eager ops land there.
    """
    global _current_place
    if isinstance(device, Place):
        place = device
    else:
        s = str(device).lower()
        if ":" in s:
            kind, idx = s.split(":", 1)
            place = Place(kind, int(idx))
        else:
            place = Place(s, 0)
    jdev = place.jax_device()
    jax.config.update("jax_default_device", jdev)
    _current_place = place
    return place


def get_device() -> str:
    """Parity: ``paddle.get_device`` — returns e.g. 'tpu:0'."""
    global _current_place
    if _current_place is None:
        d = jax.devices()[0]
        _current_place = Place(_kind_of(d), 0)
    return str(_current_place)


def get_jax_device() -> jax.Device:
    """The jax.Device eager ops currently target."""
    global _current_place
    if _current_place is None:
        get_device()
    return _current_place.jax_device()


def device_count(kind: Optional[str] = None) -> int:
    """Number of visible devices of ``kind`` (default: current kind)."""
    kind = kind or (_current_place.kind if _current_place else _default_accel_kind())
    return len([d for d in jax.devices() if _kind_of(d) == kind]) or (
        len(jax.devices("cpu")) if kind == "cpu" else 0
    )


def is_compiled_with_tpu() -> bool:
    """True when a TPU backend is visible (parity shape: is_compiled_with_cuda)."""
    return any(_kind_of(d) == "tpu" for d in jax.devices())


def is_compiled_with_cuda() -> bool:
    return any(_kind_of(d) == "gpu" for d in jax.devices())


def memory_stats(place=None) -> dict:
    """Allocator statistics of one device (``peak_bytes_in_use``,
    ``bytes_in_use``, ``bytes_limit``, ...) as reported by the backend.

    ``place`` is a :class:`Place`, a ``jax.Device``, or None (the current
    device).  Backends without allocator introspection (the CPU backend
    returns None from ``Device.memory_stats()``) yield ``{}`` — callers
    treat missing keys as "unreported", so the observability HBM gauges
    simply read 0 off-TPU."""
    if place is None:
        dev = get_jax_device()
    elif isinstance(place, Place):
        dev = place.jax_device()
    else:
        dev = place
    try:
        stats = dev.memory_stats()
    except Exception:
        return {}
    return dict(stats) if stats else {}
