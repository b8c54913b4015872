"""Device abstraction, analog of the reference's heat/core/devices.py.

The reference binds each array to a torch device ("cpu"/"gpu",
devices.py:17-134) and moves local tensors explicitly.  In this framework
placement is governed by the communication mesh (every array lives sharded
or replicated across the mesh's devices), so :class:`Device` is descriptive
metadata for API parity: it records which platform the array's mesh lives
on.  ``cpu``/``tpu``/``gpu`` globals plus ``get_device``/``use_device``/
``sanitize_device`` mirror devices.py:137-199.
"""

from __future__ import annotations

from typing import Optional, Union

import jax

__all__ = ["Device", "cpu", "get_device", "sanitize_device", "use_device"]


class Device:
    """Represents the platform an array's devices belong to.

    Analog of ``heat.core.devices.Device`` (devices.py:17-134), minus the
    torch-device plumbing (XLA owns placement).
    """

    def __init__(self, device_type: str, device_id: int = 0):
        self.__device_type = str(device_type)
        self.__device_id = int(device_id)

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def device_id(self) -> int:
        return self.__device_id

    def __repr__(self) -> str:
        return f"device({str(self)!r})"

    def __str__(self) -> str:
        return f"{self.device_type}:{self.device_id}"

    def __eq__(self, other) -> bool:
        if isinstance(other, Device):
            return self.device_type == other.device_type and self.device_id == other.device_id
        if isinstance(other, str):
            return str(self) == other or self.device_type == other
        return NotImplemented

    def __hash__(self) -> int:
        return hash(str(self))


cpu = Device("cpu")
"""The host CPU device (devices.py:107)."""

# Accelerator registration mirrors the dynamic gpu registration in
# devices.py:110-134, but is LAZY: querying ``jax.default_backend()``
# initializes the XLA backend, which must not happen at import time or the
# multi-process bootstrap (``heat_tpu.parallel.init``) could no longer run
# first.  The registry resolves on first device lookup instead.
__registry = {"cpu": cpu}
__default_device: Optional[Device] = None


def _ensure_registry() -> Device:
    global __default_device
    if __default_device is None:
        try:  # pragma: no cover - depends on runtime platform
            platform = jax.default_backend()
        except Exception:  # lint: allow H501(backend probe falls back to cpu)
            platform = "cpu"
        if platform not in __registry:
            accel = Device(platform)
            __registry[platform] = accel
            if platform in ("cuda", "rocm"):
                __registry.setdefault("gpu", accel)
        __default_device = __registry[platform]
    return __default_device


def __getattr__(name: str):
    # PEP 562 lazy module attributes: ``devices.tpu`` / ``devices.gpu``
    # resolve after the registry exists (mirroring the conditional globals
    # in the reference's devices.py:110-134)
    if name in ("tpu", "gpu"):
        _ensure_registry()
        if name in __registry:
            return __registry[name]
        raise AttributeError(f"no {name!r} device on this platform")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def get_device() -> Device:
    """Current default device (devices.py:137)."""
    return _ensure_registry()


def sanitize_device(device: Optional[Union[str, Device]]) -> Device:
    """Validate ``device`` or return the default (devices.py:144)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    _ensure_registry()
    name = str(device).split(":")[0].strip().lower()
    if name in __registry:
        return __registry[name]
    raise ValueError(f"Unknown device, must be one of {sorted(__registry)}, got {device!r}")


def use_device(device: Optional[Union[str, Device]] = None) -> None:
    """Set the default device (devices.py:171)."""
    global __default_device
    __default_device = sanitize_device(device)
