"""In-process span tracer: always-available, low-overhead host timeline.

Role parity: the reference's CUPTI ``DeviceTracer`` + ``RecordEvent``
host annotations feeding ``profiler.proto`` (platform/device_tracer.cc,
platform/profiler.cc:53).  TPU-native framing: XLA owns the device
timeline (``jax.profiler`` captures it when asked), but a heavyweight
XLA capture is the wrong tool for "where did THIS step's milliseconds
go" in a serving process at 3am — so this tracer records *host-side*
named spans into a bounded in-memory ring buffer, always compiled in,
gated by ``FLAGS_enable_tracer``, and exportable at any moment as
Chrome trace-event JSON (``observe/timeline.py``) without restarting or
re-running anything.

One span API, two sinks.  ``span()``/``begin()``/``end()`` ALSO open a
``jax.profiler.TraceAnnotation`` of the same name and attributes,
unconditionally: while any ``jax.profiler`` session runs (the
benchmark's ``--trace 1`` window, ``profiler.start_profiler``,
``observe/profiler_capture``) every span site lands on the host plane of
that trace, on the device trace's clock, its attributes as the event's
stats; with no session open the annotation is a sub-microsecond no-op.
``FLAGS_enable_tracer`` gates the ring buffer only.

Design constraints:
- **Disabled cost ~ zero**: ``span()`` with the flag off and no
  profiler session is one flag lookup and one un-recorded annotation
  (about a microsecond) — no clock read, no lock.
- **Enabled cost is bounded**: finished spans land in a
  ``deque(maxlen=capacity)`` (old spans fall off; a long-lived server
  cannot leak), two ``perf_counter`` calls + one lock per span.
- **Thread-correct nesting**: the open-span stack is thread-local, so
  concurrent serving clients / executor callers each get a properly
  nested lane, keyed by thread id in the export.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation as _Annotation

from ..framework import flags as _flags

__all__ = ["SpanRecord", "Tracer", "get_tracer", "enabled", "enable",
           "disable", "span", "begin", "end", "snapshot", "clear",
           "open_spans", "NULL_SPAN"]

DEFAULT_CAPACITY = 65536

# perf_counter origin for the whole process: every span timestamp is
# relative to this, so spans from different threads share one timeline
_EPOCH = time.perf_counter()


class SpanRecord(NamedTuple):
    """One finished span (times are seconds since the tracer epoch)."""

    name: str
    t_begin: float
    t_end: float
    tid: int
    thread_name: str
    depth: int          # 0 = top-level on its thread
    parent: Optional[str]
    args: Optional[dict]

    @property
    def duration(self) -> float:
        return self.t_end - self.t_begin


class Tracer:
    """Ring buffer of finished spans + per-thread open-span stacks."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        import collections

        self._buf = collections.deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._local = threading.local()
        self._dropped = 0
        self.pid = os.getpid()

    # -- recording -------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def begin(self, name: Optional[str], args: Optional[dict] = None) -> None:
        self._stack().append((name, time.perf_counter() - _EPOCH, args))

    def end(self) -> None:
        st = self._stack()
        if not st:  # unbalanced end(): drop silently (never raise in
            return  # instrumentation paths)
        t1 = time.perf_counter() - _EPOCH
        name, t0, args = st.pop()
        th = threading.current_thread()
        # only spans begun with the buffer on are on this stack, so a
        # span begun while it was off is invisible to depth and parent
        depth = len(st)
        parent = st[-1][0] if st else None
        self._append(SpanRecord(name, t0, t1, th.ident or 0, th.name,
                                depth, parent, args))

    def record(self, name: str, t_begin: float, t_end: float,
               parent: Optional[str] = None,
               args: Optional[dict] = None) -> None:
        """A span whose two ends are already known (seconds since the
        tracer epoch), on the calling thread's lane: what reports a
        phase only once it is over (a program's birth,
        ``xla_stats``)."""
        th = threading.current_thread()
        self._append(SpanRecord(name, t_begin, t_end, th.ident or 0,
                                th.name, len(self._stack()), parent, args))

    def _append(self, rec: SpanRecord) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._dropped += 1
            self._buf.append(rec)

    def set_args(self, **kwargs) -> None:
        """Attach/extend args on the INNERMOST open span of this thread
        (e.g. byte counts known only after the span body ran)."""
        st = self._stack()
        if not st:  # no open span
            return
        name, t0, args = st[-1]
        merged = dict(args or {})
        merged.update(kwargs)
        st[-1] = (name, t0, merged)

    # -- reading ---------------------------------------------------------
    def snapshot(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._buf)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def enabled() -> bool:
    """Single source of truth is ``FLAGS_enable_tracer`` (so
    ``paddle_tpu.set_flags`` and the env var both just work)."""
    return bool(_flags.flag("enable_tracer"))


def recording() -> bool:
    """Whether a span opened now reaches a sink: a ``jax.profiler``
    session runs or the ring buffer is on.  For a site whose span
    attributes cost a clock read a token to gather."""
    return _Annotation.is_enabled() or enabled()


def enable() -> None:
    _flags.set_flags({"enable_tracer": True})


def disable() -> None:
    _flags.set_flags({"enable_tracer": False})


class _Span:
    """Context manager over one begin()/end() pair."""

    __slots__ = ("_name", "_args")

    def __init__(self, name, args):
        self._name = name
        self._args = args

    def __enter__(self):
        _begin(self._name, self._args)
        return self

    def __exit__(self, *exc):
        end()
        return False


class _NullSpan:
    """Shared no-op context manager (see ``NULL_SPAN``)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()

# the shared no-op, exported for instrumentation sites that need an
# "either a span or nothing" slot (e.g. the Executor's first-call
# compile wrapper) without growing their own null context manager
NULL_SPAN = _NULL


# per-thread LIFO of the open spans: (profiler annotation, whether the
# span is also in the ring buffer, name, attributes).  begin() decides
# both sinks once, so a pair stays balanced when FLAGS_enable_tracer
# flips between the calls; name and attributes ride along whichever
# sinks take the span, so that a compile can name the span it ran under
# (``open_spans``) in a process that traces nothing
_local = threading.local()


def _open_spans() -> list:
    try:
        return _local.spans
    except AttributeError:
        st = _local.spans = []
        return st


def open_spans() -> List[Tuple[str, dict]]:
    """``(name, attributes)`` of the calling thread's open spans,
    outermost first, with the ring buffer on or off."""
    return [(name, attrs) for _, _, name, attrs in _open_spans()]


def span(name: str, **attrs):
    """``with observe.span("executor/run", bytes=n):`` — an event of
    the profiler's trace while a ``jax.profiler`` session runs, a
    ring-buffer record while ``FLAGS_enable_tracer`` is set."""
    return _Span(name, attrs)


def begin(name: str, **attrs) -> None:
    """Explicit begin/end pair (what ``span()`` and
    ``profiler.RecordEvent`` are built on).  The caller must guarantee
    LIFO order per thread.  The profiler annotation always opens; the
    ring-buffer record is gated by ``FLAGS_enable_tracer``, read here
    and not again at end()."""
    _begin(name, attrs)


def _begin(name: str, attrs: dict) -> None:
    annotation = _Annotation(name, **attrs)
    annotation.__enter__()
    in_ring = bool(_flags.flag("enable_tracer"))
    if in_ring:
        _TRACER.begin(name, attrs or None)
    _open_spans().append((annotation, in_ring, name, attrs))


def end() -> None:
    st = _open_spans()
    if not st:  # unbalanced end(): drop silently (never raise in
        return  # instrumentation paths)
    annotation, in_ring, _, _ = st.pop()
    if in_ring:
        _TRACER.end()
    annotation.__exit__(None, None, None)


def set_span_args(**kwargs) -> None:
    """Attach args known only after the span body ran (byte counts) to
    the innermost open span of this thread, in both sinks."""
    st = _open_spans()
    if not st:
        return
    annotation, in_ring, _, _ = st[-1]
    annotation.set_metadata(**kwargs)
    if in_ring:
        _TRACER.set_args(**kwargs)


def snapshot() -> List[SpanRecord]:
    return _TRACER.snapshot()


def clear() -> None:
    _TRACER.clear()
