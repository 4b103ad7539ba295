"""XLA introspection: compile telemetry, HBM accounting, budget gate.

PR 7 opened model sizes a single chip's HBM cannot hold, and the
framework was blind on both axes that matter there: how long XLA took
to compile the program (ROADMAP item 5's linear blow-up at depth) and
how many bytes of HBM the program will need per chip — discovered, if
at all, via an opaque RESOURCE_EXHAUSTED after dispatch.  This module
is the reference's ``memory_optimize``/profiler role (SURVEY L1/L11)
rebuilt on what jax actually exposes:

- **Compile telemetry** — of EVERY program the process compiles, not
  the Executor's alone.  Listeners on ``jax.monitoring`` (registered
  once when ``paddle_tpu.observe`` is imported) assemble one *birth
  record* a compiled program, per thread: its trace, its lowering, and
  its backend compile or its load from the persistent cache, hit or
  miss, and the span it ran under (:func:`program_births`,
  :func:`births_summary`; counters ``xla_program_births``,
  ``xla_cache_hits``, ``xla_cache_misses``; histograms
  ``xla_trace_seconds``, ``xla_lower_seconds``,
  ``xla_backend_compile_seconds``, ``xla_cache_load_seconds``; a flight
  event ``xla/program_born`` a miss; ring-buffer spans ``xla/*`` under
  ``FLAGS_enable_tracer``).  The serving engine's lazy ``jax.jit``s,
  the weights' jit and eager operations are in it like the Executor's
  entries; a listener runs while a program compiles and never on a
  step.  The Executor besides AOT-lowers every fresh entry
  (``jit_fn.lower(...).compile()``) and hands the compiled executable
  to :func:`on_compile`: wall time into the ``compile_seconds``
  histogram (its split is the record's ``birth``), executable size +
  HLO module stats as ``/metrics`` gauges, an ``executor/compile_done``
  flight event with the duration, and an optional optimized-HLO dump
  (``FLAGS_hlo_dump_dir``).
- **HBM accounting** — ``compiled.memory_analysis()`` (per-chip under
  SPMD, since the analyzed module is the partitioned per-device
  program) becomes a footprint
  breakdown (arguments / outputs / temporaries / generated code), and
  the :class:`~..framework.passes.TPShardingPlan` + scope var sizes
  join into a top-N per-var attribution table — the thing that says
  *what to shard next*.  ``hbm_required_bytes`` rides ``/metrics``;
  live ``device.memory_stats()`` (``hbm_free_bytes``) rides the
  heartbeat thread (observe/health.py) onto ``/metrics/cluster``.
- **Pre-dispatch budget gate** — when the predicted footprint exceeds
  ``FLAGS_hbm_budget_fraction`` × device memory, the compile raises
  :class:`MemoryBudgetError` *before* the first dispatch, with the
  attribution table in the message; the same data lands in the
  ``memory.json`` section of postmortem bundles.

Everything here is capability-skipped, never fatal: a jax without
``memory_analysis`` records what it can and moves on — only the budget
gate (explicitly armed via the flag) may raise.
"""
from __future__ import annotations

import collections
import functools
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..framework import flags as _flags
from . import flight as _flight
from . import tracer as _tracer
from .histogram import stat_time

__all__ = ["COMPILE_SECONDS_HISTOGRAM", "MemoryBudgetError",
           "memory_breakdown", "cost_flops", "var_attribution",
           "format_attribution", "device_memory_stats",
           "device_hbm_capacity", "record_device_memory",
           "check_hbm_budget", "on_compile", "compile_records",
           "last_compile", "memory_report", "clear_compile_records",
           "listen_for_births", "program_births", "births_summary"]

logger = logging.getLogger(__name__)

COMPILE_SECONDS_HISTOGRAM = "compile_seconds"

# how many vars the attribution table keeps (the error message shows 3)
TOP_N_VARS = 10

# bounded ring of compile records: memory.json in postmortem bundles
# reads it, /metrics gauges reflect the newest entry
_RECORDS: "collections.deque[dict]" = collections.deque(maxlen=32)
_LOCK = threading.Lock()
_HLO_SEQ = 0

# set once the jax backend is definitionally in use (the Executor's
# first compile; same reasoning as flight.record_device_topology):
# before that, jax.local_devices() ITSELF performs backend init — on a
# dead TPU that is the 240s hang the health plane exists to survive,
# so the heartbeat's device-memory sampling must not be the first call
_BACKEND_IN_USE = False


def mark_backend_in_use() -> None:
    """The Executor calls this at its first compile — the one point
    where probing jax devices cannot introduce a device-init that was
    not already being paid."""
    global _BACKEND_IN_USE

    _BACKEND_IN_USE = True


class MemoryBudgetError(RuntimeError):
    """Predicted per-chip HBM footprint exceeds the configured budget
    (``FLAGS_hbm_budget_fraction`` × device memory).  Raised BEFORE the
    executable is dispatched, with the per-var attribution table
    attached (``.attribution``) and its top rows in the message."""

    def __init__(self, message: str, required_bytes: int = 0,
                 budget_bytes: int = 0, capacity_bytes: int = 0,
                 attribution: Optional[Sequence[dict]] = None):
        super().__init__(message)
        self.required_bytes = int(required_bytes)
        self.budget_bytes = int(budget_bytes)
        self.capacity_bytes = int(capacity_bytes)
        self.attribution = list(attribution or [])


def _mb(nbytes) -> float:
    return round(int(nbytes or 0) / 2 ** 20, 2)


# ---------------------------------------------------------------------------
# compiled-executable readings
# ---------------------------------------------------------------------------


def memory_breakdown(compiled) -> Optional[Dict[str, int]]:
    """Per-chip footprint breakdown from ``compiled.memory_analysis()``
    or None where the backend reports none.  ``total_bytes`` is the predicted
    live-at-once HBM need: arguments + outputs + temporaries +
    generated code, minus the aliased (donated-in-place) bytes that
    would otherwise count twice."""
    m = compiled.memory_analysis()
    if m is None:
        return None

    def _get(attr):
        return max(int(getattr(m, attr) or 0), 0)

    args = _get("argument_size_in_bytes")
    outs = _get("output_size_in_bytes")
    temps = _get("temp_size_in_bytes")
    code = _get("generated_code_size_in_bytes")
    alias = _get("alias_size_in_bytes")
    return {
        "arguments_bytes": args,
        "outputs_bytes": outs,
        "temporaries_bytes": temps,
        "generated_code_bytes": code,
        "aliased_bytes": alias,
        "total_bytes": max(args + outs + temps + code - alias, 0),
    }


def cost_flops(compiled) -> Optional[float]:
    """FLOPs of one executable call per ``compiled.cost_analysis()``
    (per-chip under SPMD), or None when unavailable."""
    c = compiled.cost_analysis()
    f = float((c or {}).get("flops") or 0.0)
    return f if f > 0.0 else None


# ---------------------------------------------------------------------------
# per-var attribution: TPShardingPlan x scope var sizes
# ---------------------------------------------------------------------------


def var_attribution(entries: Sequence[Tuple], plan=None, mesh=None,
                    top_n: int = TOP_N_VARS) -> List[dict]:
    """Join var sizes with the sharding plan into the top-N per-chip
    attribution table.

    ``entries`` are ``(name, shape, dtype_str, kind)`` tuples (kind:
    ``"state"`` for scope vars, ``"feed"`` for inputs).  With a
    :class:`~..framework.passes.TPShardingPlan`, per-chip bytes divide
    by :meth:`~..framework.passes.TPShardingPlan.shard_divisor` and the
    spec string names the layout; without one everything is replicated
    (feeds are counted unsharded either way — a conservative bound, and
    params dominate the footprints this table exists to explain)."""
    rows: List[dict] = []
    for name, shape, dtype, kind in entries:
        try:
            itemsize = np.dtype(dtype).itemsize
        except TypeError:
            continue
        n = 1
        for s in shape:
            n *= max(int(s), 1)
        nbytes = n * itemsize
        if plan is not None:
            div = plan.shard_divisor(name, mesh)
            spec = plan.spec_str(name)
        else:
            div, spec = 1, "replicated"
        rows.append({
            "name": str(name),
            "kind": str(kind),
            "dtype": str(dtype),
            "shape": [int(s) for s in shape],
            "global_bytes": int(nbytes),
            "per_chip_bytes": int(nbytes // div),
            "spec": spec,
        })
    rows.sort(key=lambda r: (-r["per_chip_bytes"], r["name"]))
    return rows[:max(int(top_n), 1)]


def format_attribution(rows: Sequence[dict], limit: Optional[int] = None
                       ) -> str:
    """Render attribution rows as an aligned text table (error messages
    and logs; the postmortem CLI has its own pure-stdlib renderer)."""
    rows = list(rows)[:limit] if limit else list(rows)
    if not rows:
        return "  (no per-var attribution available)"
    width = max(len(r["name"]) for r in rows)
    out = [f"  {'var':<{width}}  {'per-chip MB':>12}  {'global MB':>10}  "
           f"{'kind':<5}  spec"]
    for r in rows:
        out.append(
            f"  {r['name']:<{width}}  {_mb(r['per_chip_bytes']):>12}  "
            f"{_mb(r['global_bytes']):>10}  {r['kind']:<5}  {r['spec']}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# live device memory (heartbeat thread -> /metrics + /metrics/cluster)
# ---------------------------------------------------------------------------


def device_memory_stats(device=None) -> Optional[dict]:
    """Live ``device.memory_stats()`` as a plain dict, or None where
    the backend has none (CPU)."""
    if device is None:
        import jax

        device = jax.local_devices()[0]
    ms = device.memory_stats()
    return dict(ms) if ms else None


def device_hbm_capacity(device=None) -> Optional[int]:
    """Per-device memory capacity in bytes for the budget gate:
    ``FLAGS_hbm_bytes_per_device`` when set, else the device's reported
    ``bytes_limit``, else None (gate capability-skips)."""
    override = int(_flags.flag("hbm_bytes_per_device"))
    if override > 0:
        return override
    ms = device_memory_stats(device)
    if ms:
        try:
            limit = int(ms.get("bytes_limit", 0))
        except (TypeError, ValueError):
            limit = 0
        if limit > 0:
            return limit
    return None


def record_device_memory(devices=None) -> dict:
    """One live HBM sample across the local devices, mirrored to
    ``/metrics`` gauges (``hbm_free_bytes`` = the MIN free — the chip
    that OOMs first — plus ``hbm_used_bytes``/``hbm_limit_bytes``) and
    returned as heartbeat payload fields for ``/metrics/cluster``.
    Returns {} where no device reports memory stats (CPU backend):
    the capability skip, not an error.  With no explicit ``devices``,
    nothing is probed until :func:`mark_backend_in_use` — the heartbeat
    thread calls this, and ``jax.local_devices()`` on a backend nobody
    initialized yet IS the device-init hang the health plane must
    survive (the PR 6 topology-probe rule)."""
    if devices is None:
        if not _BACKEND_IN_USE:
            return {}
        try:
            import jax

            devices = jax.local_devices()
        except Exception:  # noqa: BLE001 - a dead backend is not a crash
            return {}
    free = used = limit = None
    for d in devices:
        ms = device_memory_stats(d)
        if not ms:
            continue
        try:
            d_limit = int(ms.get("bytes_limit", 0))
            d_used = int(ms.get("bytes_in_use", 0))
        except (TypeError, ValueError):
            continue
        if d_limit <= 0:
            continue
        d_free = max(d_limit - d_used, 0)
        free = d_free if free is None else min(free, d_free)
        used = d_used if used is None else max(used, d_used)
        limit = d_limit if limit is None else max(limit, d_limit)
    if free is None:
        return {}
    from ..monitor import stat_set

    stat_set("hbm_free_bytes", free)
    stat_set("hbm_used_bytes", used)
    stat_set("hbm_limit_bytes", limit)
    return {"hbm_free_bytes": free, "hbm_used_bytes": used,
            "hbm_limit_bytes": limit}


# ---------------------------------------------------------------------------
# the pre-dispatch budget gate
# ---------------------------------------------------------------------------


def check_hbm_budget(required_bytes: int,
                     attribution: Sequence[dict] = (),
                     device=None, fingerprint: str = "") -> dict:
    """Judge a predicted per-chip footprint against the configured
    budget.  Returns a verdict record (``disabled`` / ``skipped`` /
    ``pass``); raises :class:`MemoryBudgetError` on rejection — the
    caller (Executor first-dispatch introspection) has NOT launched the
    executable yet, so the failure is a report, not a dead device."""
    from ..monitor import stat_add

    fraction = float(_flags.flag("hbm_budget_fraction"))
    if fraction <= 0.0:
        return {"verdict": "disabled"}
    capacity = device_hbm_capacity(device)
    if capacity is None:
        # no way to know this device's memory: skip LOUDLY (counter +
        # flight event) rather than pretend the program fits
        stat_add("hbm_budget_gate_skipped")
        _flight.record("xla/hbm_budget_skipped",
                       reason="device memory capacity unknown "
                              "(no memory_stats and no "
                              "FLAGS_hbm_bytes_per_device)")
        return {"verdict": "skipped", "fraction": fraction}
    budget = int(fraction * capacity)
    rec = {"fraction": fraction, "capacity_bytes": int(capacity),
           "budget_bytes": budget, "required_bytes": int(required_bytes)}
    if int(required_bytes) <= budget:
        stat_add("hbm_budget_gate_passed")
        rec["verdict"] = "pass"
        return rec
    stat_add("hbm_budget_gate_rejections")
    top = list(attribution)[:3]
    _flight.record("xla/hbm_budget_reject", fingerprint=fingerprint[:16],
                   required_bytes=int(required_bytes),
                   budget_bytes=budget, capacity_bytes=int(capacity),
                   top_vars=[r.get("name") for r in top])
    raise MemoryBudgetError(
        f"predicted per-chip HBM footprint {_mb(required_bytes)} MB "
        f"exceeds the budget {_mb(budget)} MB "
        f"(FLAGS_hbm_budget_fraction={fraction} x {_mb(capacity)} MB "
        f"device memory); rejected BEFORE dispatch.  Largest per-chip "
        f"allocations:\n"
        + format_attribution(attribution, limit=TOP_N_VARS)
        + "\nShard the top vars (DistributedStrategy.tensor_parallel "
          "partition_rules), shrink the batch, or raise "
          "FLAGS_hbm_budget_fraction.  Full breakdown: memory.json in "
          "the postmortem bundle / observe.xla_stats.memory_report().",
        required_bytes=int(required_bytes), budget_bytes=budget,
        capacity_bytes=int(capacity), attribution=attribution)


# ---------------------------------------------------------------------------
# the per-compile entry point (Executor._introspect_first_compile)
# ---------------------------------------------------------------------------


def _dump_hlo(hlo_text: Optional[str], fingerprint: str) -> Optional[str]:
    """FLAGS_hlo_dump_dir: save the optimized HLO module text beside
    the postmortem bundles; returns the path or None.  Best-effort — a
    full disk must not fail a compile."""
    global _HLO_SEQ

    d = _flags.flag("hlo_dump_dir")
    if not d or not hlo_text:
        return None
    try:
        os.makedirs(d, exist_ok=True)
        with _LOCK:
            _HLO_SEQ += 1
            seq = _HLO_SEQ
        path = os.path.join(
            d, f"hlo_{fingerprint[:16] or 'unknown'}_{seq:03d}.txt")
        with open(path, "w") as f:
            f.write(hlo_text)
        return path
    except OSError:
        return None


def on_compile(compiled, *, fingerprint: str = "", seconds: float = 0.0,
               size_entries: Sequence[Tuple] = (), plan=None, mesh=None,
               n_steps: int = 1, program_flops: float = 0.0,
               device=None) -> dict:
    """Record one Executor compile: telemetry, HBM accounting, and the
    budget gate (which may raise :class:`MemoryBudgetError` — the ONLY
    exception this function lets escape, and only when the gate is
    armed).  Returns the compile record (also kept in the bounded ring
    behind :func:`compile_records`/``memory.json``); the caller reads
    ``xla_flops_per_step`` off it for the MFU cross-check."""
    from ..monitor import stat_add, stat_set

    stat_time(COMPILE_SECONDS_HISTOGRAM, max(float(seconds), 0.0))

    rec: dict = {
        "ts": time.time(),
        "fingerprint": str(fingerprint)[:16],
        "compile_seconds": round(float(seconds), 6),
        "n_steps": int(n_steps),
    }
    # what compile_seconds timed: the calling thread's newest birth, if
    # it lies inside the seconds just measured (the caller compiled on
    # this thread and came straight here)
    born, _birth_local.last = getattr(_birth_local, "last", None), None
    if born is not None and born["t_begin"] >= _now() - float(seconds) - 1.0:
        rec["birth"] = {k: born[k] for k in (
            "seq", "program", "trace_s", "lower_s", "backend_s", "cache",
            "cache_load_s", "compile_saved_s")}
    if mesh is not None:
        try:
            rec["mesh"] = {str(a): int(mesh.shape[a])
                           for a in mesh.axis_names}
        except Exception:  # noqa: BLE001 - telemetry only
            pass

    # -- executable size + HLO module stats --------------------------------
    breakdown = memory_breakdown(compiled)
    exec_size = 0
    if breakdown:
        exec_size = breakdown["generated_code_bytes"]
    if exec_size <= 0:
        exec_size = int(compiled.runtime_executable()
                        .size_of_generated_code_in_bytes())
    # the optimized-HLO text is rendered ONLY when something needs it —
    # a dump dir, or a backend that reports no code size (the text
    # length is then the honest proxy for "how big did this program
    # get", the ROADMAP item 5 blow-up signal).  For a large model the
    # text is tens of MB of string; unconditional as_text() on the
    # first-dispatch path would tax exactly the workloads this PR
    # exists to observe.
    hlo_text = None
    if exec_size <= 0 or _flags.flag("hlo_dump_dir"):
        hlo_text = compiled.as_text()
    if exec_size <= 0 and hlo_text:
        exec_size = len(hlo_text)
        rec["executable_size_is_hlo_text"] = True
    rec["executable_size_bytes"] = int(exec_size)
    stat_set("executable_size_bytes", int(exec_size))
    if hlo_text:
        rec["hlo_text_bytes"] = len(hlo_text)
        rec["hlo_ops"] = hlo_text.count(" = ")
        stat_set("executable_hlo_bytes", len(hlo_text))
        stat_set("executable_hlo_ops", rec["hlo_ops"])
        hlo_path = _dump_hlo(hlo_text, str(fingerprint))
        if hlo_path:
            rec["hlo_dump_path"] = hlo_path

    # -- HBM accounting ----------------------------------------------------
    attribution = var_attribution(size_entries, plan=plan, mesh=mesh)
    rec["attribution"] = attribution
    required = 0
    if breakdown is None:
        stat_add("xla_memory_analysis_unavailable")
    else:
        rec["memory"] = breakdown
        required = breakdown["total_bytes"]
        stat_set("hbm_required_bytes", required)

    # -- MFU honesty cross-check -------------------------------------------
    # hapi/model_stat.py program_flops vs XLA's own count.  Only where
    # the two count the SAME thing: single-step (a run_steps scan's
    # cost analysis may or may not fold the trip count depending on the
    # XLA version) and single-device (on a mesh the analyzed module is
    # the per-chip partition while the IR estimate is global/mp — they
    # disagree by design, not by mispricing).
    if int(n_steps) == 1 and mesh is None:
        xla = cost_flops(compiled)
        if xla is not None:
            rec["xla_flops"] = xla
            if program_flops and program_flops > 0.0:
                ratio = xla / float(program_flops)
                rec["flops_ratio_xla_over_ir"] = round(ratio, 4)
                if ratio > 2.0 or ratio < 0.5:
                    # the hand-rolled IR count misprices fused ops (and
                    # on sharded meshes counts global, not per-chip,
                    # work): XLA's number wins the MFU denominator
                    stat_add("mfu_flops_mismatch")
                    rec["flops_source"] = "xla"
                    rec["xla_flops_per_step"] = xla
            else:
                # no IR estimate at all: XLA is the only source
                rec["flops_source"] = "xla"
                rec["xla_flops_per_step"] = xla

    _flight.record("executor/compile_done",
                   fingerprint=rec["fingerprint"],
                   seconds=rec["compile_seconds"],
                   executable_size_bytes=rec["executable_size_bytes"],
                   hbm_required_bytes=required,
                   n_steps=int(n_steps))

    # the budget verdict is computed BEFORE the record is published:
    # once appended, rec is shared with concurrent memory_report()
    # readers (the stall watchdog's dump thread), and a post-append
    # key insert would race their serialization — while a REJECTED
    # compile must still land in the ring with its full numbers
    # (memory.json in the failure's postmortem shows the why)
    budget_exc = None
    if breakdown is not None:
        try:
            rec["budget"] = check_hbm_budget(
                required, attribution, device=device,
                fingerprint=str(fingerprint))
        except MemoryBudgetError as e:
            # the rejection's numbers matter MOST in memory.json: keep
            # the full verdict off the exception, not a stub
            rec["budget"] = {
                "verdict": "rejected",
                "fraction": float(_flags.flag("hbm_budget_fraction")),
                "required_bytes": e.required_bytes,
                "budget_bytes": e.budget_bytes,
                "capacity_bytes": e.capacity_bytes,
            }
            budget_exc = e
    with _LOCK:
        _RECORDS.append(rec)
    if budget_exc is not None:
        raise budget_exc
    return rec


# ---------------------------------------------------------------------------
# the birth log: one record a compiled program, from jax.monitoring
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

# a benchmark cell's set-up is 6-30 programs (a step, a prefill a bucket,
# the weights' jit, the check's reference, a few one-operation eager
# ones); past this many the ring forgets, births_summary() counts on
BIRTHS_CAPACITY = 1024
# a thread's traces wait for their lowering by function name, the newest
# of a name: a large program's lowering traces hundreds of small jitted
# functions AFTER the program's own trace has ended, so the newest N
# would lose it.  Names that no lowering claims (eval_shape, make_jaxpr)
# are dropped together past this many
_PENDING_TRACES = 1024
# the span attributes that say WHICH step or prefill compiled
_UNDER_ATTRS = ("iter", "step", "bucket")

_BIRTHS: "collections.deque[dict]" = collections.deque(
    maxlen=BIRTHS_CAPACITY)
_BIRTH_TOTALS: Dict[str, Dict[str, float]] = {}
_BIRTH_SEQ = 0
_LAST_BIRTH_END: Optional[float] = None
_LISTENING = False
# jax reports a compile's phases on the compiling thread, each as it
# ends: a thread's pending traces, its program between lowering and
# backend, and its newest finished birth (what on_compile attaches)
_birth_local = threading.local()


def _now() -> float:
    return time.perf_counter() - _tracer._EPOCH


def _split_fun_name(fun_name) -> Tuple[str, str]:
    """jax's ``jit(step)`` as (the program's name as its XLA module and a
    device trace have it, ``jit_step``; the traced function's, ``step``)."""
    name = str(fun_name or "")
    api, paren, rest = name.partition("(")
    if paren and rest.endswith(")"):
        return f"{api}_{rest[:-1]}", rest[:-1]
    return name, name


def _under() -> Tuple[Optional[str], dict]:
    """The open span a birth is filed under: the innermost one of the
    calling thread that says which step or prefill it is (an engine
    program's first call lies inside ``executor/persistent``, which
    names neither, inside ``serving/step_dispatch``, which does); where
    none does, the innermost one; ``(None, {})`` outside every span."""
    spans = _tracer.open_spans()
    for name, attrs in reversed(spans):
        found = {k: attrs[k] for k in _UNDER_ATTRS if k in attrs}
        if found:
            return name, found
    return (spans[-1][0], {}) if spans else (None, {})


def _never_raising(listener):
    """jax calls a listener inside the compile it reports: a fault of
    the log must not become a fault of the program's compile."""
    @functools.wraps(listener)
    def guarded(*args, **kw):
        try:
            listener(*args, **kw)
        except Exception:  # noqa: BLE001 - telemetry, inside jax's compile
            from ..monitor import stat_add

            stat_add("xla_birth_log_errors")
            logger.debug("birth log listener failed", exc_info=True)
    return guarded


@_never_raising
def _on_event(event: str, **_kw) -> None:
    born = getattr(_birth_local, "open", None)
    if born is None:
        return
    if event == _CACHE_REQUEST_EVENT:
        # jax makes the request with no cache directory too, and then
        # finds nothing: that is no miss a warmer cache would cure
        import jax

        if jax.config.jax_compilation_cache_dir:
            born["cache"] = "miss"  # until a hit says otherwise
    elif event == _CACHE_HIT_EVENT:
        born["cache"] = "hit"


@_never_raising
def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _TRACE_EVENT:
        traces = getattr(_birth_local, "traces", None)
        if traces is None or len(traces) >= _PENDING_TRACES:
            traces = _birth_local.traces = {}
        traces[kw.get("fun_name")] = (float(duration), _now())
    elif event == _LOWER_EVENT:
        _open_birth(*_split_fun_name(kw.get("fun_name")), float(duration))
    elif event == _BACKEND_EVENT:
        _close_birth(_split_fun_name(kw.get("fun_name"))[0],
                     float(duration))
    elif event in (_CACHE_LOAD_EVENT, _CACHE_SAVED_EVENT):
        born = getattr(_birth_local, "open", None)
        if born is not None:
            key = "cache_load_s" if event == _CACHE_LOAD_EVENT \
                else "compile_saved_s"
            born[key] = float(duration)


def _new_birth(program: str, t_begin: float, lower_s: float = 0.0,
               t_lowered: Optional[float] = None) -> dict:
    return {"program": program, "trace_s": 0.0, "lower_s": lower_s,
            "t_begin": t_begin, "t_lowered": t_lowered, "cache": "off",
            "cache_load_s": None, "compile_saved_s": None}


def _open_birth(program: str, traced: str, lower_s: float) -> None:
    """A lowering ended: the program's trace is the newest pending one
    of its function's name (the traces of the jitted functions it calls
    lie inside it, those its lowering made lie inside ``lower_s``: they
    are dropped, not added)."""
    t_lowered = _now()
    born = _new_birth(program, t_lowered - lower_s, lower_s, t_lowered)
    trace = (getattr(_birth_local, "traces", None) or {}).get(traced)
    if trace is not None:
        born["trace_s"], t_traced = trace
        born["t_begin"] = min(born["t_begin"], t_traced - born["trace_s"])
    _birth_local.traces = None
    _birth_local.open = born


def _close_birth(program: str, backend_s: float) -> None:
    global _BIRTH_SEQ, _LAST_BIRTH_END

    from ..monitor import stat_add

    t_end = _now()
    born, _birth_local.open = getattr(_birth_local, "open", None), None
    if born is None or born["program"] != program:
        # compiled from a lowering made elsewhere: what is known is here
        born = _new_birth(program, t_end - backend_s)
    t_lowered = born.pop("t_lowered")
    under, under_attrs = _under()
    th = threading.current_thread()
    born.update(backend_s=backend_s, t_end=t_end, thread=th.name,
                under=under, under_attrs=under_attrs)
    cache = born["cache"]
    with _LOCK:
        _BIRTH_SEQ += 1
        born["seq"] = _BIRTH_SEQ
        _LAST_BIRTH_END = t_end
        tot = _BIRTH_TOTALS.setdefault(cache, {
            "births": 0, "trace_s": 0.0, "lower_s": 0.0,
            "backend_s": 0.0, "cache_load_s": 0.0})
        tot["births"] += 1
        for key in ("trace_s", "lower_s", "backend_s", "cache_load_s"):
            tot[key] += born[key] or 0.0
        _BIRTHS.append(born)
    _birth_local.last = born

    stat_add("xla_program_births")
    stat_time("xla_trace_seconds", born["trace_s"])
    stat_time("xla_lower_seconds", born["lower_s"])
    if cache == "hit":
        stat_add("xla_cache_hits")
        stat_time("xla_cache_load_seconds", born["cache_load_s"])
    else:
        stat_time("xla_backend_compile_seconds", backend_s)
    if cache == "miss":
        # in a serving process a miss after start-up is a recompile
        stat_add("xla_cache_misses")
        _flight.record("xla/program_born", program=program,
                       birth=born["seq"], thread=th.name, under=under,
                       **under_attrs, trace_s=round(born["trace_s"], 6),
                       lower_s=round(born["lower_s"], 6),
                       backend_s=round(backend_s, 6))
    if _tracer.enabled():
        tr = _tracer.get_tracer()
        args = {"program": program, "birth": born["seq"], **under_attrs}
        if born["trace_s"]:
            tr.record("xla/trace", born["t_begin"],
                      born["t_begin"] + born["trace_s"], under, args)
        if t_lowered is not None:
            tr.record("xla/lower", t_lowered - born["lower_s"], t_lowered,
                      under, args)
        tr.record("xla/cache_load" if cache == "hit"
                  else "xla/backend_compile", t_end - backend_s, t_end,
                  under, dict(args, cache=cache))


def listen_for_births() -> None:
    """Register the birth log's listeners on ``jax.monitoring``: once a
    process, however often it is called (``paddle_tpu.observe`` calls
    it when it is imported, which precedes every compile)."""
    global _LISTENING

    with _LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def program_births() -> List[dict]:
    """The newest ``BIRTHS_CAPACITY`` birth records, oldest first.  One
    a compiled program: ``seq``, ``program`` (jax's ``fun_name``
    ``jit(step)`` as the XLA module and a device trace name it,
    ``jit_step``), ``thread``, ``t_begin`` and ``t_end`` (seconds on the
    tracer's epoch: the ring buffer's timeline), ``trace_s`` (the
    program's own trace; the traces of the jitted functions it calls lie
    inside it), ``lower_s``, ``backend_s`` (the backend's compile, or on
    a hit the load that took its place), ``cache`` (``hit`` | ``miss`` |
    ``off`` where the process has no cache directory or jax asked no
    cache), ``cache_load_s`` and
    ``compile_saved_s`` (a hit's, else None), and ``under`` with
    ``under_attrs``: the span that caused it (:func:`_under`) and which
    of ``iter`` / ``step`` / ``bucket`` that span carries."""
    with _LOCK:
        return [dict(b) for b in _BIRTHS]


def births_summary() -> dict:
    """Counts and sums of every birth since the process started, by
    ``cache`` (the ring forgets, these do not), and when the newest
    ended."""
    with _LOCK:
        by_cache = {k: dict(v) for k, v in _BIRTH_TOTALS.items()}
        last = _LAST_BIRTH_END
    return {
        "births": sum(int(v["births"]) for v in by_cache.values()),
        "cache_hits": int(by_cache.get("hit", {}).get("births", 0)),
        "cache_misses": int(by_cache.get("miss", {}).get("births", 0)),
        "by_cache": by_cache,
        "last_birth_t_end": last,
        "since_last_birth_s": None if last is None else _now() - last,
    }


# ---------------------------------------------------------------------------
# reading back (postmortem memory.json, tests, dashboards)
# ---------------------------------------------------------------------------


def compile_records() -> List[dict]:
    with _LOCK:
        return list(_RECORDS)


def last_compile() -> Optional[dict]:
    with _LOCK:
        return _RECORDS[-1] if _RECORDS else None


def clear_compile_records() -> None:
    with _LOCK:
        _RECORDS.clear()


def memory_report(probe_devices: bool = False) -> dict:
    """The ``memory.json`` postmortem section: every recorded compile
    (footprint breakdown + attribution + budget verdicts) plus the
    heartbeat's CACHED hbm gauges.  Pure data — ``tools/postmortem.py``
    renders it without importing the framework.

    Live device probing is opt-in (``probe_devices=True``): the dump
    path fires exactly when a device call is hung, and a
    ``memory_stats()`` against the same wedged PJRT runtime would hang
    the watchdog thread mid-bundle — the per-section error capture
    handles exceptions, not hangs.  The cached gauges (last heartbeat
    sample) are the safe default."""
    from ..monitor import stat_get

    report: dict = {"ts": time.time(), "compiles": compile_records()}
    gauges = {k: stat_get(k) for k in
              ("hbm_free_bytes", "hbm_used_bytes", "hbm_limit_bytes")}
    if any(gauges.values()):
        report["hbm_gauges"] = gauges
    devices = []
    if probe_devices and _BACKEND_IN_USE:
        try:
            import jax

            for d in jax.local_devices():
                ms = device_memory_stats(d)
                if ms:
                    devices.append({"device": str(d), **ms})
        except Exception:  # noqa: BLE001 - a dead backend still reports
            pass
    report["device_memory"] = devices
    return report
