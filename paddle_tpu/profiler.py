"""Profiler: RecordEvent-style annotations + trace capture over jax.profiler.

Role parity: reference ``python/paddle/fluid/profiler.py`` (``profiler``
context manager :255, ``start_profiler`` :131, ``stop_profiler`` :198) and
the C++ ``RecordEvent`` scoped annotations (platform/profiler.cc:53).
TPU-native redesign: instead of CUPTI device tracing + a custom
profiler.proto, capture goes through ``jax.profiler`` — the trace contains
every XLA executable launch and on-device op, viewable in
TensorBoard/Perfetto (replaces tools/timeline.py's chrome://tracing dump).
``RecordEvent`` is a span of ``paddle_tpu.observe``'s tracer, the one
span API: it appears on the host timeline alongside device ops when an
XLA capture is live (the tracer opens the ``TraceAnnotation``), and in
the ring buffer whenever ``FLAGS_enable_tracer`` is set — once in each.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Optional

from .observe import tracer as _otracer

_state = {"running": False, "dir": None, "t0": None}


class RecordEvent:
    """Scoped host-side annotation (reference platform/profiler.cc:53).

    Usable as a context manager, via explicit begin()/end(), or as a
    function decorator (``@RecordEvent("serving/batch")`` wraps every
    call of the function in its own span).  A name over
    ``observe.begin()/end()``: shows up as a named span on the profiler
    timeline when a capture is active AND in the observe tracer's ring
    buffer when ``FLAGS_enable_tracer`` is set; costs ~nothing when
    neither is running.  The open-span stacks are the tracer's, per
    thread, so one instance may be shared across threads or re-entered.
    """

    def __init__(self, name: str):
        self.name = name

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with RecordEvent(self.name):
                return fn(*args, **kwargs)

        return wrapped

    def begin(self):
        _otracer.begin(self.name)

    def end(self):
        _otracer.end()

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def start_profiler(state: str = "All", tracer_option: str = "Default",
                   profile_path: Optional[str] = None):
    """Begin a trace capture (reference fluid/profiler.py:131).

    ``state``/``tracer_option`` are accepted for API parity; XLA traces
    host + device unconditionally (there is no CPU-only tracer to pick).
    """
    import jax

    if _state["running"]:
        raise RuntimeError("profiler is already running")
    out = profile_path or os.environ.get("PADDLE_TPU_PROFILE_DIR",
                                         "/tmp/paddle_tpu_profile")
    os.makedirs(out, exist_ok=True)
    _state.update(running=True, dir=out, t0=time.perf_counter())
    try:
        jax.profiler.start_trace(out)
    except Exception:
        # a failed capture must not wedge the "already running" check
        # for the rest of the process
        _state.update(running=False, dir=None, t0=None)
        raise


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: Optional[str] = None) -> str:
    """End the capture and return the trace directory (reference
    fluid/profiler.py:198).  ``sorted_key`` is parity-only: aggregation
    and sorting happen in TensorBoard/Perfetto over the dumped trace, not
    in-process."""
    import jax

    if not _state["running"]:
        raise RuntimeError("profiler is not running")
    out = _state["dir"]
    jax.profiler.stop_trace()
    # full reset (not just the running bit): a later start must never
    # see this capture's dir/t0
    _state.update(running=False, dir=None, t0=None)
    return out


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None, tracer_option: str = "Default"):
    """Context manager parity with ``fluid.profiler.profiler`` (:255)::

        with profiler(profile_path="/tmp/trace"):
            exe.run(main, feed=..., fetch_list=[loss])
    """
    start_profiler(state, tracer_option, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):  # pragma: no cover - trivial
    """Reference API shim: CUDA-specific; on TPU this is the same XLA
    trace capture (kept so fluid scripts run unchanged)."""
    with profiler():
        yield
