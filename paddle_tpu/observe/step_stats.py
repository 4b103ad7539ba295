"""Per-step telemetry: step-time distribution, throughput, MFU.

Role parity: the reference scatters this across ``STAT_ADD`` counters,
the benchmark flag's per-op timing, and out-of-tree scripts; here the
Executor feeds ONE ``StepTimer`` per process from ``_dispatch`` — every
``run``/``run_steps`` call records wall time, step count, example
count, the compiled program's static FLOPs (hapi/model_stat.py
accounting over the program IR) and allreduce payload bytes (the PR 2
fused-bucket accounting, re-derived from the post-pass op stream).

Out the other end:
- ``step_time_seconds`` histogram (p50/p95/p99 via observe/histogram,
  exported to ``/stats``, ``/metrics``, and ``export_stats()``),
- ``summary()``: examples/sec, compile-vs-execute wall split,
  allreduce bytes/step, and an **MFU estimate** =
  achieved FLOP/s ÷ ``FLAGS_device_peak_tflops`` — the single number
  that says how far from "as fast as the hardware allows" a step is.

Timing honesty: jax arrays are async, so a run's wall time is dispatch
time unless something blocks.  Under pipelined dispatch
(``FLAGS_max_inflight_steps`` > 0, the default) the Executor records
each step at its window-DRAIN point with the inter-drain wall time — in
a steady loop drains fire once per dispatch (backpressure), so the
recorded number is the training loop's true per-step period, input wait
included.  ``summary()`` drains every live Executor first so it only
reports completed steps.  ``FLAGS_benchmark`` forces an immediate drain
per call (the reference meaning of that flag); multi-step ``run_steps``
calls amortize the launch so their per-step number is accurate either
way.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from . import device_peaks as _peaks
from .histogram import histogram, stat_time

__all__ = ["STEP_TIME_HISTOGRAM", "StepTimer", "step_timer",
           "reset_step_stats", "mfu_estimate"]

STEP_TIME_HISTOGRAM = "step_time_seconds"


def mfu_estimate(flops_per_step: float, step_time_s: float,
                 peak_tflops: Optional[float] = None) -> float:
    """Model FLOPs utilization: achieved / peak.  ``peak_tflops``
    defaults to ``FLAGS_device_peak_tflops``, and that to the live
    device's row in ``observe/device_peaks.py``; with neither there is
    nothing to divide by and asking is an error."""
    if step_time_s <= 0.0 or flops_per_step <= 0.0:
        return 0.0
    peak = _peaks.peak_tflops(peak_tflops)
    if peak is None:
        raise ValueError(
            "no peak FLOP/s for this device: it is not in "
            "observe/device_peaks.py and FLAGS_device_peak_tflops is "
            "unset; an MFU needs one of the two")
    return (flops_per_step / step_time_s) / (peak * 1e12)


class StepTimer:
    """Accumulates per-run telemetry; one instance per process (the
    Executor feeds the module singleton; tests may build their own)."""

    def __init__(self, hist_name: str = STEP_TIME_HISTOGRAM):
        self._lock = threading.Lock()
        self._hist_name = hist_name
        histogram(hist_name)  # pre-register: /metrics shows the (empty)
        self._zero()          # histogram before the first step runs

    def _zero(self):
        self.runs = 0
        self.steps = 0
        self.examples = 0
        self.compiles = 0
        self.compile_time = 0.0
        self.execute_time = 0.0
        self.flops = 0.0
        self.allreduce_bytes = 0

    # -- feeding (Executor._dispatch) ------------------------------------
    def record_run(self, duration_s: float, steps: int = 1,
                   examples: int = 0, compiled: bool = False,
                   flops_per_step: float = 0.0,
                   allreduce_bytes_per_step: int = 0) -> None:
        steps = max(int(steps), 1)
        with self._lock:
            self.runs += 1
            if compiled:
                # first call traces + XLA-compiles + executes: charge it
                # all to the compile side so steady-state numbers stay
                # clean (the split IS the compile-storm detector)
                self.compiles += 1
                self.compile_time += duration_s
            else:
                self.execute_time += duration_s
                self.steps += steps
                self.examples += int(examples)
                self.flops += flops_per_step * steps
                self.allreduce_bytes += int(allreduce_bytes_per_step) * steps
        if not compiled:
            stat_time(self._hist_name, duration_s / steps)

    # -- reading ---------------------------------------------------------
    def summary(self, peak_tflops: Optional[float] = None) -> Dict:
        # pipelined dispatch moves per-step accounting to window-drain
        # points: a summary is a read point, so quiesce every live
        # Executor first — the numbers then reflect completed steps
        # only.  raise_errors=False: a step failure hit here is PARKED
        # on its window and re-raised at the next raising drain point
        # (next dispatch, handle read, drain/close, ckpt snapshot) —
        # telemetry never raises, but it never eats the error either
        try:
            from ..framework.executor import drain_all as _drain_all

            _drain_all(raise_errors=False)
        except ImportError:  # pragma: no cover - partial installs
            pass
        with self._lock:
            runs, steps, examples = self.runs, self.steps, self.examples
            compiles = self.compiles
            ct, et = self.compile_time, self.execute_time
            flops, ar_bytes = self.flops, self.allreduce_bytes
        out = {
            "runs": runs,
            "steps": steps,
            "compiles": compiles,
            "compile_time_s": round(ct, 6),
            "execute_time_s": round(et, 6),
            "step_time_s": histogram(self._hist_name).summary(),
        }
        # XLA introspection (observe/xla_stats.py): the AOT-measured
        # trace+compile wall times and the newest executable's size —
        # compile_time_s above is the first-CALL wall split, this is
        # the compiler's own bill (ROADMAP item 5's acceptance metric)
        ch = histogram("compile_seconds")
        if ch.count:
            out["xla_compile_seconds"] = ch.summary()
        from ..monitor import stat_get

        size = stat_get("executable_size_bytes")
        if size:
            out["executable_size_bytes"] = size
        if et > 0.0 and steps:
            out["steps_per_sec"] = round(steps / et, 3)
            if examples:
                out["examples_per_sec"] = round(examples / et, 3)
            out["allreduce_bytes_per_step"] = ar_bytes // steps
            if flops:
                out["flops_per_step"] = int(flops / steps)
                peak = _peaks.peak_tflops(peak_tflops)
                if peak is not None:
                    # significant digits, not decimal places: a toy
                    # model's 1e-6 MFU must not round to a dead zero
                    out["mfu"] = float(
                        f"{mfu_estimate(flops / steps, et / steps, peak):.4g}")
                else:
                    # no flag and no table row for this device: there
                    # is no denominator — null, not a misleading 0.0
                    out["mfu"] = None
        return out

    def reset(self) -> None:
        with self._lock:
            self._zero()
        histogram(self._hist_name).reset()


_STEP_TIMER = StepTimer()


def step_timer() -> StepTimer:
    return _STEP_TIMER


def reset_step_stats() -> None:
    _STEP_TIMER.reset()
