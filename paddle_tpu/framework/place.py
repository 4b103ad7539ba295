"""Device identity ("Place") for the TPU-native framework.

Role parity: reference paddle/fluid/platform/place.h (CPUPlace:26,
CUDAPlace:37, XPUPlace:62, variant Place:103).  Here a Place is a small
Python value object that resolves to a concrete ``jax.Device``; there are no
streams or device contexts — XLA/PJRT owns scheduling and memory, which is
the TPU-native replacement for the reference's DeviceContext/allocator
stack (device_context.h:61, memory/allocation/*).
"""
from __future__ import annotations

import functools


class Place:
    """Base device identity."""

    device_id: int = 0

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        raise NotImplementedError


class CPUPlace(Place):
    def __init__(self):
        self.device_id = 0

    def jax_device(self):
        import jax

        return jax.devices("cpu")[0]


class TPUPlace(Place):
    """An accelerator chip visible to JAX.

    On a real TPU host this is one chip; in CPU-simulation test runs
    (``--xla_force_host_platform_device_count=N``) it is one virtual device.
    """

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def jax_device(self):
        devs = accelerator_devices()
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"TPUPlace({self.device_id}) out of range: {len(devs)} device(s) visible"
            )
        return devs[self.device_id]


class CUDAPlace(TPUPlace):
    """Compatibility alias: reference scripts that pin CUDAPlace(i) run on
    the accelerator chip i of this framework instead."""


class CUDAPinnedPlace(CPUPlace):
    """Compatibility alias; host memory staging is PJRT's job here."""

    def __init__(self):
        super().__init__()


@functools.lru_cache(maxsize=None)
def accelerator_devices():
    """All non-CPU jax devices, else CPU devices (simulation mode)."""
    import jax

    devs = [d for d in jax.devices() if d.platform != "cpu"]
    return tuple(devs) if devs else tuple(jax.devices())


def is_compiled_with_cuda() -> bool:  # API parity helper
    return False


def is_compiled_with_tpu() -> bool:
    import jax

    return any(d.platform != "cpu" for d in jax.devices())


_pinned_place: Place | None = None  # set by set_device


def _default_place() -> Place:
    import jax

    if _pinned_place is not None:
        return _pinned_place
    if any(d.platform != "cpu" for d in jax.devices()):
        return TPUPlace(0)
    return CPUPlace()


def set_device(device: str) -> Place:
    """Pin the process to a device (reference paddle.set_device,
    python/paddle/device.py).

    ``set_device("cpu")`` pins the live jax platform config so ONLY the
    CPU backend initializes: a process that selects the CPU this way
    never opens the chip (which belongs to one process at a time).
    ``set_device("tpu")`` (or the "gpu" compat alias) restores
    accelerator-first selection.
    Already-initialized backends are cleared so the new selection takes
    effect mid-process (existing arrays keep referencing their original
    client and stay readable).  Returns the corresponding Place, which
    also becomes the default place.
    """
    import jax

    global _pinned_place
    d = device.split(":")[0].lower()
    idx = int(device.split(":")[1]) if ":" in device else 0
    if d == "cpu":
        place: Place = CPUPlace()
        want = "cpu"
    elif d in ("tpu", "gpu", "xpu", "npu"):
        place = TPUPlace(idx)
        want = None  # accelerator-first
    else:
        raise ValueError(
            f"unknown device {device!r}; expected cpu/tpu/gpu")
    if jax.config.jax_platforms != want:
        jax.config.update("jax_platforms", want)
        # a config update after backend init is otherwise a silent
        # no-op; clearing rebuilds backends under the new selection.
        # Same-platform calls (incl. index-only changes) skip this —
        # clearing drops every jit cache and re-inits the backend.
        try:
            from jax.extend.backend import clear_backends

            clear_backends()
        except Exception:
            pass
        accelerator_devices.cache_clear()
    _pinned_place = place
    return place


def get_device() -> str:
    """Reference paddle.get_device: 'cpu' or 'tpu:<id>'."""
    p = _default_place()
    return "cpu" if isinstance(p, CPUPlace) else f"tpu:{p.device_id}"
