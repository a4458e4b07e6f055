"""Device/Place layer.

Reference parity: phi::Place + DeviceContextPool + paddle.set_device
(reference: paddle/phi/common/place.h, paddle/phi/core/device_context.cc —
unverified, mount empty). On TPU there is no per-stream context to manage: XLA
owns scheduling. This layer is therefore a thin selection mechanism that
routes creation ops (and jit compilation) onto a chosen jax.Device, plus the
CustomDevice-style "fake backend" trick for CI (the analog of the reference's
custom_cpu plugin test backend, test/custom_runtime/ — unverified): a process
that was PUT on the CPU (``JAX_PLATFORMS=cpu``) serves ``tpu`` places from its
host CPU devices. That backend is chosen, never discovered — a ``tpu`` place
in a process that was not put on the CPU and finds no chip is an error.
"""
from __future__ import annotations

import threading

import jax


class Place:
    """Device identity, paddle.CPUPlace()/TPUPlace(id) analog."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0):
    return Place("tpu", device_id)


class _DeviceState(threading.local):
    def __init__(self):
        self.place = None  # lazily resolved


_STATE = _DeviceState()


def explicitly_on_cpu() -> bool:
    """Whether this process was put on the CPU on purpose
    (``JAX_PLATFORMS=cpu`` in the environment, or the same through
    ``jax.config``) — how the tests and the ``make`` gates run."""
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def _accelerator_devices():
    devs = jax.devices()
    accel = [d for d in devs if d.platform != "cpu"]
    return accel


def _indexed(devs, place):
    if not 0 <= place.device_id < len(devs):
        raise ValueError(
            f"{place!r}: this process has {len(devs)} "
            f"{devs[0].platform} device(s)"
        )
    return devs[place.device_id]


def _default_place() -> Place:
    if _accelerator_devices():
        return Place("tpu", 0)
    return Place("cpu", 0)


def set_device(device) -> Place:
    """paddle.set_device parity. Accepts 'cpu', 'tpu', 'tpu:1', Place."""
    if isinstance(device, Place):
        _STATE.place = device
        return device
    if not isinstance(device, str):
        raise TypeError(f"set_device expects str or Place, got {type(device)}")
    dev = device.lower()
    # The reference's gpu place maps to the accelerator here so that
    # reference scripts run unmodified ("gpu" -> the TPU chip).
    if dev.startswith("gpu"):
        dev = "tpu" + dev[3:]
    if ":" in dev:
        kind, _, idx = dev.partition(":")
        place = Place(kind, int(idx))
    else:
        place = Place(dev, 0)
    if place.device_type not in ("cpu", "tpu"):
        raise ValueError(f"unknown device {device!r}; expected cpu/tpu[:i]")
    # Steer jax's default device so eager computation stays on the chosen
    # backend (otherwise ops on freshly created arrays bounce to whatever
    # backend is jax's global default, one transfer per op).
    jax.config.update("jax_default_device", jax_device(place))
    _STATE.place = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    if _STATE.place is None:
        _STATE.place = _default_place()
    return _STATE.place


def jax_device(place: Place | None = None):
    """Resolve a Place to a concrete jax.Device (local)."""
    p = place or current_place()
    if p.device_type == "cpu":
        cpus = [d for d in jax.devices() if d.platform == "cpu"]
        if not cpus:
            # jax can always materialize host CPU devices
            cpus = jax.devices("cpu")
        return _indexed(cpus, p)
    accel = _accelerator_devices()
    if accel:
        return _indexed(accel, p)
    if not explicitly_on_cpu():
        raise RuntimeError(
            f"{p!r}: jax found no accelerator "
            f"(devices: {jax.devices()}). Set JAX_PLATFORMS=cpu to run "
            "on the host CPU on purpose."
        )
    # fake-backend mode, chosen by JAX_PLATFORMS=cpu: the process's CPU
    # devices stand in for the chips, index for index
    return jax_device(Place("cpu", p.device_id))


def is_compiled_with_cuda() -> bool:  # reference API parity
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    """Local visible device count for the current place kind."""
    p = current_place()
    if p.device_type == "cpu":
        return len([d for d in jax.devices() if d.platform == "cpu"]) or 1
    return len(_accelerator_devices()) or 1


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    # XLA plays CINN's role by design (SURVEY §7); the flag answers the
    # reference question "is a tensor compiler available" truthfully
    return True


def is_compiled_with_distribute() -> bool:
    return True


def CUDAPlace(device_id: int = 0):
    """Reference scripts constructing CUDAPlace run on the accelerator
    this build targets (TPU) — same role, same API shape."""
    return TPUPlace(device_id)


def XPUPlace(device_id: int = 0):
    return TPUPlace(device_id)


def CUDAPinnedPlace():
    return Place("cpu", 0)


def CustomPlace(device_type: str, device_id: int = 0):
    return Place(str(device_type), int(device_id))
