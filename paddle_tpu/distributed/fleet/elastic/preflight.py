"""Device preflight with a deadline: probe the backend IN-PROCESS.

A chip belongs to one process at a time.  Every caller of the preflight
(the elastic supervisor before each attempt, the disagg autoscaler from a
live server) either already holds the chip or is about to, so a
probe in a CHILD process could never load the TPU library and would read
every healthy device as dead.  The probe therefore runs in the calling
process, on a daemon thread under a deadline: a tiny jit dispatch
(compile + execute one add) that exercises init, compile and dispatch.
A backend that wedges leaves that thread hung, but the caller gets its
verdict at the deadline and stays free to report and exit.

The verdict is structured, not a string soup:

- ``ok``            the probe returned; ``platform`` is set.
- ``init_timeout``  the probe exceeded ``FLAGS_elastic_preflight_timeout_s``.
- ``compile_error`` the probe raised; ``diag`` carries the exception.

Failures retry with exponential backoff (``FLAGS_elastic_backoff_s *
2^k``) up to ``attempts`` — a transiently-held chip recovers without
burning the supervisor's restart budget.  Every attempt lands in the
flight recorder (``elastic/preflight``) and the ``elastic_preflight_*``
metric family.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from ....framework import flags as _flags
from . import chaos as _chaos

__all__ = ["PreflightVerdict", "preflight_device", "default_probe",
           "PREFLIGHT_OK", "PREFLIGHT_INIT_TIMEOUT",
           "PREFLIGHT_COMPILE_ERROR"]

PREFLIGHT_OK = "ok"
PREFLIGHT_INIT_TIMEOUT = "init_timeout"
PREFLIGHT_COMPILE_ERROR = "compile_error"

def default_probe() -> str:
    """init + compile + dispatch on this process's own backend; returns
    the platform jax reports."""
    import jax
    import jax.numpy as jnp

    jax.jit(lambda v: v + 1)(jnp.zeros((8,), jnp.float32)) \
        .block_until_ready()
    return jax.devices()[0].platform


class PreflightVerdict:
    """Structured outcome of :func:`preflight_device`."""

    __slots__ = ("ok", "verdict", "platform", "diag", "attempts",
                 "elapsed_s")

    def __init__(self, verdict: str, platform: Optional[str] = None,
                 diag: str = "", attempts: int = 1,
                 elapsed_s: float = 0.0):
        self.verdict = verdict
        self.ok = verdict == PREFLIGHT_OK
        self.platform = platform
        self.diag = diag
        self.attempts = int(attempts)
        self.elapsed_s = float(elapsed_s)

    def to_dict(self) -> dict:
        return {"verdict": self.verdict, "ok": self.ok,
                "platform": self.platform, "diag": self.diag,
                "attempts": self.attempts,
                "elapsed_s": round(self.elapsed_s, 3)}

    def __repr__(self) -> str:  # readable in failure records
        return (f"PreflightVerdict({self.verdict!r}, "
                f"platform={self.platform!r}, attempts={self.attempts})")


def _one_probe(probe: Callable[[], str],
               timeout_s: float) -> PreflightVerdict:
    f = _chaos.take("preflight_init_timeout")
    if f is not None:
        return PreflightVerdict(
            PREFLIGHT_INIT_TIMEOUT,
            diag=f"chaos: injected preflight init timeout ({timeout_s}s)")
    box: dict = {}

    def work():
        try:
            box["platform"] = probe()
        except Exception as e:  # noqa: BLE001 - becomes the verdict
            box["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=work, daemon=True,
                         name="elastic-preflight")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        return PreflightVerdict(
            PREFLIGHT_INIT_TIMEOUT,
            diag=f"device init did not complete within {timeout_s}s")
    if "platform" not in box:
        return PreflightVerdict(
            PREFLIGHT_COMPILE_ERROR,
            diag=f"probe raised {box.get('error', 'and left no result')}"
            [-2000:])
    return PreflightVerdict(PREFLIGHT_OK, platform=str(box["platform"]))


def preflight_device(attempts: int = 2,
                     timeout_s: Optional[float] = None,
                     backoff_s: Optional[float] = None,
                     probe: Optional[Callable[[], str]] = None,
                     sleep_fn: Callable[[float], None] = time.sleep
                     ) -> PreflightVerdict:
    """Probe the device up to ``attempts`` times with exponential
    backoff; returns the first ``ok`` verdict, else the last failure.
    ``timeout_s`` / ``backoff_s`` default from
    ``FLAGS_elastic_preflight_timeout_s`` / ``FLAGS_elastic_backoff_s``.
    Never raises — a preflight that cannot even run is a failed
    verdict, not an exception."""
    from ....monitor import stat_add
    from ....observe import flight as _flight

    timeout_s = float(_flags.flag("elastic_preflight_timeout_s")
                      if timeout_s is None else timeout_s)
    backoff_s = float(_flags.flag("elastic_backoff_s")
                      if backoff_s is None else backoff_s)
    probe = probe or default_probe
    attempts = max(int(attempts), 1)
    t0 = time.perf_counter()
    v = PreflightVerdict(PREFLIGHT_COMPILE_ERROR, diag="no attempts made",
                         attempts=0)
    for i in range(attempts):
        try:
            v = _one_probe(probe, timeout_s)
        except Exception as e:  # noqa: BLE001 - the thread could not start
            v = PreflightVerdict(
                PREFLIGHT_COMPILE_ERROR,
                diag=f"probe could not run: {type(e).__name__}: {e}")
        v.attempts = i + 1
        v.elapsed_s = time.perf_counter() - t0
        stat_add("elastic_preflight_attempts")
        stat_add(f"elastic_preflight_{v.verdict}")
        _flight.record("elastic/preflight", attempt=i + 1,
                       verdict=v.verdict, platform=v.platform,
                       diag=(v.diag or "")[:300],
                       elapsed_s=round(v.elapsed_s, 3))
        if v.ok:
            return v
        if i + 1 < attempts:
            stat_add("elastic_preflight_retries")
            sleep_fn(backoff_s * (2 ** i))
    return v
