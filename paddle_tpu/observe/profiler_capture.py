"""Anomaly-triggered + continuous ``jax.profiler`` capture.

The phase-attribution engine (``observe/phases.py``) says where a step
went on *average*; this module answers "what happened at 03:12 when
p99 doubled" — automatically, with the evidence already on disk when a
human looks.

- **Anomaly trigger** (``FLAGS_prof_trigger_ratio``): every drained
  step's wall time feeds a rolling-median baseline; a step exceeding
  ``ratio x baseline`` — or any ``slo_burn_rate_*_ppm`` gauge past its
  budget (PR 12) — fires ONE bounded capture: a ``jax.profiler`` trace
  window of at most ``FLAGS_prof_capture_s`` seconds plus a phase
  snapshot, dumped as a postmortem bundle (``phases.json`` section,
  rendered by ``python -m tools.postmortem``).  The trigger then
  latches until the step time drops back under the threshold, and a
  ``FLAGS_prof_cooldown_s`` quiet period follows every capture, so one
  episode produces one bundle, not one per step — and the capture's
  own overhead can never re-trigger it.
- **Continuous mode** (``FLAGS_prof_continuous_s``): a daemon thread
  captures one bounded window every N seconds (duty cycle
  ``capture_s / continuous_s``) into a 2-deep rotating directory set —
  the always-on-fleet profiling mode, without bundles.

A trace that cannot start (another ``jax.profiler`` trace is already
live in the process) counts ``prof_trace_unavailable`` and the phase
snapshot still lands.  Trace
directories are summarized best-effort (file count/bytes + event count
where the chrome-trace JSON is readable) — parsing failures degrade to
the raw listing, never to a lost capture.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Dict, List, Optional

from ..framework import flags as _flags
from ..monitor import stat_add, stat_set

__all__ = ["CaptureEngine", "capture_engine", "on_step_drained",
           "maybe_start_continuous", "stop_continuous", "parse_trace_dir",
           "reset_capture"]

BASELINE_WINDOW = 64   # rolling step-time samples behind the median
BASELINE_WARMUP = 8    # steps before the trigger may fire


def _start_trace(log_dir: str) -> bool:
    """Begin a ``jax.profiler`` trace; False when one is already live
    (the profiler allows one per process)."""
    import jax

    try:
        jax.profiler.start_trace(log_dir)
    except RuntimeError:
        return False
    return True


def _stop_trace() -> None:
    import jax

    try:
        jax.profiler.stop_trace()
    except RuntimeError:  # the other owner stopped it first
        pass


def _burning_slo() -> Optional[str]:
    """Name of the first SLO objective burning past budget
    (``slo_burn_rate_<name>_ppm`` > 1e6), or None."""
    from ..monitor import StatRegistry

    for name, value in StatRegistry.instance().export():
        if name.startswith("slo_burn_rate_") and name.endswith("_ppm") \
                and value > 1_000_000:
            return name
    return None


def parse_trace_dir(directory: str) -> Dict:
    """Best-effort summary of a ``jax.profiler`` trace directory:
    file count + total bytes always; trace-event count where a
    ``*.trace.json(.gz)`` is present and parseable (the CPU backend's
    host-only traces are; some TPU runtimes emit only protobufs —
    those still count as captured files)."""
    out: Dict = {"dir": directory, "files": 0, "bytes": 0}
    try:
        paths: List[str] = []
        for root, _dirs, files in os.walk(directory):
            for f in files:
                paths.append(os.path.join(root, f))
        out["files"] = len(paths)
        out["bytes"] = sum(os.path.getsize(p) for p in paths)
        events = 0
        for p in paths:
            if p.endswith(".trace.json.gz") or p.endswith(".trace.json"):
                try:
                    if p.endswith(".gz"):
                        import gzip

                        with gzip.open(p, "rt") as f:
                            doc = json.load(f)
                    else:
                        with open(p) as f:
                            doc = json.load(f)
                    events += len(doc.get("traceEvents", []))
                except Exception:  # noqa: BLE001 - summary only
                    continue
        if events:
            out["trace_events"] = events
    except OSError:
        pass
    return out


class CaptureEngine:
    """Rolling baseline + latched anomaly capture + continuous mode;
    one instance per process (the executor drain feeds the module
    singleton)."""

    def __init__(self, window: int = BASELINE_WINDOW,
                 warmup: int = BASELINE_WARMUP):
        self._lock = threading.Lock()
        self._samples = collections.deque(maxlen=int(window))
        self.warmup = int(warmup)
        self._latched = False
        self._last_burn_check = 0.0
        self._burning = False
        self._last_capture_t = 0.0
        self._capture_thread: Optional[threading.Thread] = None
        self._continuous_thread: Optional[threading.Thread] = None
        self._continuous_stop = threading.Event()
        self.captures = 0
        self.bundles: List[str] = []

    # -- baseline + trigger (executor drain path) ------------------------
    def _baseline(self) -> float:
        s = sorted(self._samples)
        return s[len(s) // 2] if s else 0.0

    def on_step(self, wall_s: float, compiled: bool = False) -> None:
        """Feed one drained step; fires at most one capture per
        anomaly episode.  First-call (compile) steps never feed the
        baseline — a compile is not a regression."""
        ratio = float(_flags.flag("prof_trigger_ratio") or 0.0)
        if ratio <= 0.0 or compiled:
            return
        wall = max(float(wall_s), 0.0)
        # the SLO-burn probe walks the stat registry: throttle it to
        # ~1/s so the trigger path stays amortized-free per step
        now = time.monotonic()
        burn = None
        if now - self._last_burn_check >= 1.0:
            self._last_burn_check = now
            burn = _burning_slo()
            self._burning = burn is not None
        fire: Optional[str] = None
        cooldown = float(_flags.flag("prof_cooldown_s") or 0.0)
        with self._lock:
            base = self._baseline()
            armed = len(self._samples) >= self.warmup
            spiking = armed and base > 0.0 and wall > ratio * base
            if not spiking:
                # a spiking step never joins the baseline: the anomaly
                # must not drag its own detector upward
                self._samples.append(wall)
            capturing = self._capture_thread is not None \
                and self._capture_thread.is_alive()
            cooling = now - self._last_capture_t < cooldown \
                and self._last_capture_t > 0.0
            if (spiking or burn is not None) and not self._latched \
                    and not capturing and not cooling:
                self._latched = True
                self._last_capture_t = now
                fire = (f"step_time {wall * 1e3:.1f}ms > {ratio:g}x "
                        f"baseline {base * 1e3:.1f}ms") if spiking \
                    else f"slo_burn {burn}"
            elif self._latched and not spiking and not self._burning:
                self._latched = False  # episode over: re-arm
        if fire is not None:
            self._start_capture(fire)

    # -- one bounded capture ---------------------------------------------
    def _start_capture(self, trigger: str) -> None:
        stat_add("prof_captures_triggered")
        t = threading.Thread(target=self._capture, args=(trigger,),
                             name="prof-capture", daemon=True)
        with self._lock:
            self._capture_thread = t
        t.start()

    def _capture(self, trigger: str) -> None:
        from . import flight as _flight
        from . import health as _health

        capture_s = max(float(_flags.flag("prof_capture_s") or 0.0), 0.0)
        base = _flags.flag("postmortem_dir") or "postmortem"
        trace_dir = os.path.join(
            str(base), f"prof_{time.strftime('%Y%m%d_%H%M%S')}_"
                       f"{os.getpid()}")
        started = False
        try:
            os.makedirs(trace_dir, exist_ok=True)
            started = _start_trace(trace_dir)
        except OSError:
            pass
        if not started:
            stat_add("prof_trace_unavailable")
        _flight.record("prof/capture_start", trigger=trigger,
                       trace=started, capture_s=capture_s)
        if started:
            # the bound: stop no matter what after capture_s
            time.sleep(capture_s)
            _stop_trace()
        profiler = parse_trace_dir(trace_dir) if started else \
            {"unavailable": True}
        try:
            bundle = _health.dump_postmortem(
                "step_time_anomaly",
                extra={"trigger": trigger, "profiler": profiler,
                       "prof_capture_s": capture_s})
        except Exception:  # noqa: BLE001 - capture must not kill callers
            bundle = None
        with self._lock:
            self.captures += 1
            if bundle:
                self.bundles.append(bundle)
        stat_add("prof_captures")
        stat_set("prof_capture_latched", 1)
        _flight.record("prof/capture_done", trigger=trigger,
                       bundle=bundle or "")

    def wait(self, timeout: float = 30.0) -> bool:
        """Join the in-flight capture thread (tests/bench); returns
        whether it finished."""
        with self._lock:
            t = self._capture_thread
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    # -- continuous low-duty-cycle mode ----------------------------------
    def start_continuous(self) -> bool:
        """Start the continuous-profiling daemon when
        ``FLAGS_prof_continuous_s`` > 0; idempotent."""
        period = float(_flags.flag("prof_continuous_s") or 0.0)
        if period <= 0.0:
            return False
        with self._lock:
            if self._continuous_thread is not None \
                    and self._continuous_thread.is_alive():
                return True
            self._continuous_stop.clear()
            self._continuous_thread = threading.Thread(
                target=self._continuous_loop, args=(period,),
                name="prof-continuous", daemon=True)
            self._continuous_thread.start()
        return True

    def _continuous_loop(self, period: float) -> None:
        from . import flight as _flight

        base = _flags.flag("postmortem_dir") or "postmortem"
        root = os.path.join(str(base), "prof_continuous")
        n = 0
        while not self._continuous_stop.wait(period):
            capture_s = max(float(_flags.flag("prof_capture_s") or 0.0),
                            0.0)
            # 2-deep rotation: slot index alternates, so disk usage is
            # bounded at two windows no matter how long the fleet runs
            trace_dir = os.path.join(root, f"window_{n % 2}")
            n += 1
            try:
                import shutil

                shutil.rmtree(trace_dir, ignore_errors=True)
                os.makedirs(trace_dir, exist_ok=True)
            except OSError:
                continue
            if not _start_trace(trace_dir):
                stat_add("prof_trace_unavailable")
                continue
            time.sleep(capture_s)
            _stop_trace()
            stat_add("prof_continuous_captures")
            _flight.record("prof/continuous_window",
                           **parse_trace_dir(trace_dir))

    def stop_continuous(self) -> None:
        self._continuous_stop.set()
        with self._lock:
            t, self._continuous_thread = self._continuous_thread, None
        if t is not None:
            t.join(timeout=5)

    def reset(self) -> None:
        self.stop_continuous()
        self.wait(timeout=5)
        with self._lock:
            self._samples.clear()
            self._latched = False
            self._last_burn_check = 0.0
            self._burning = False
            self._last_capture_t = 0.0
            self.captures = 0
            self.bundles = []
        stat_set("prof_capture_latched", 0)


_ENGINE = CaptureEngine()


def capture_engine() -> CaptureEngine:
    return _ENGINE


def on_step_drained(wall_s: float, compiled: bool = False) -> None:
    """Drain-path hook (framework/executor.py): never raises."""
    try:
        _ENGINE.on_step(wall_s, compiled=compiled)
    except Exception:  # noqa: BLE001 - observer only
        stat_add("prof_trigger_errors")


def maybe_start_continuous() -> bool:
    """Auto-start hook (Executor construction): the continuous daemon
    when ``FLAGS_prof_continuous_s`` > 0, else nothing."""
    try:
        return _ENGINE.start_continuous()
    except Exception:  # noqa: BLE001
        return False


def stop_continuous() -> None:
    _ENGINE.stop_continuous()


def reset_capture() -> None:
    _ENGINE.reset()
