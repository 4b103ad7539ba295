"""``paddle_tpu.observe`` — always-on in-process telemetry.

The reference ships a full observability stack (CUPTI ``DeviceTracer``
→ ``profiler.proto`` → ``tools/timeline.py``, plus ``StatRegistry``
counters); the TPU-native port previously covered only the thin ends.
This package is the middle:

- ``tracer``     — the one span API, fed by the Executor phases, graph
  passes, collective lowerings, the serving batch lifecycle, the decode
  engine's loop phases, and every ``profiler.RecordEvent``.  Each span
  is a ``jax.profiler.TraceAnnotation`` (an event of whatever profiler
  trace is running, on the device trace's clock) and, under
  ``FLAGS_enable_tracer``, a record of the host-side ring buffer.
- ``timeline``   — Chrome trace-event JSON export of that buffer
  (Perfetto/chrome://tracing), plus a
  ``python -m paddle_tpu.observe.timeline`` CLI.
- ``histogram``  — log-bucketed ``stat_time`` latency histograms with
  p50/p95/p99, and the Prometheus text exposition behind the fleet KV
  HTTP server's ``/metrics`` route.
- ``step_stats`` — ``StepTimer``: step-time distribution, examples/sec,
  compile-vs-execute split, allreduce bytes/step, and the MFU estimate
  (FLOPs from ``hapi/model_stat.py`` over the program IR).
- ``flight``     — always-on bounded ring of structured lifecycle
  events (run metadata, executor dispatch/drain, ckpt save/restore,
  serving start/stop), gated by ``FLAGS_flight_recorder``, with an
  optional JSONL file sink (``FLAGS_flight_recorder_file``).
- ``health``     — stall watchdog (``FLAGS_stall_timeout_s``) dumping
  postmortem bundles (all-thread stacks, Chrome trace, metrics
  snapshot, flight tail, flags), crash/atexit hooks, and cluster-wide
  health telemetry (per-rank heartbeats over the fleet KV server +
  the aggregated ``/metrics/cluster`` route on rank 0).
- ``request_trace`` — Dapper-style per-request serving timelines:
  trace ids minted at submit, structured lifecycle events (enqueue,
  admission, prefill chunks, decode steps, CoW copies, speculative
  rounds, terminal outcome), head-sampling
  (``FLAGS_request_trace_sample``) with tail retention of every SLO
  violator and abnormal ending; served on ``/debug/requests`` +
  ``/debug/request/<id>``, exported to Chrome trace JSON, embedded in
  postmortem bundles as ``requests.json``.
- ``slo``        — declarative objectives (ttft p99 / tpot p50 /
  error rate) evaluated on rolling multi-windows (SRE-workbook burn
  rates): ``slo_burn_rate_*`` / ``slo_budget_remaining_*`` gauges and
  the ``decode_goodput_rps`` metric (completions meeting ALL
  objectives per second).
- ``phases``     — step-phase attribution: decomposes each drained
  step's wall time into compute / exposed-collective / host-blocked /
  input-wait buckets, backed by an HLO cost model (deterministic
  *predicted* fractions on backends without device tracing) and a
  per-collective ledger keyed by FuseAllReducePass bucket /
  collective-matmul chunk identity (``comm_exposed_seconds`` vs
  ``comm_hidden_seconds`` per collective).
- ``profiler_capture`` — anomaly-triggered + continuous
  ``jax.profiler`` capture: step-time spikes past
  ``FLAGS_prof_trigger_ratio`` x rolling baseline (or an SLO burn-rate
  trip) fire one bounded trace window + phase snapshot into a
  postmortem bundle; ``FLAGS_prof_continuous_s`` runs a low-duty-cycle
  always-on mode with 2-deep directory rotation.
- ``metrics_catalog`` — the authoritative name → (type, unit,
  subsystem) catalog behind ``METRICS.md``; a tier-1 drift gate keeps
  every ``/metrics`` series documented.
- ``xla_stats``  — XLA introspection: the birth log of every program
  the process compiles (``program_births``, ``births_summary``: trace,
  lowering, backend compile or cache load, hit or miss, and the span
  that caused it, from ``jax.monitoring`` listeners registered when
  this package is imported), the Executor's per-compile wall time
  (``compile_seconds``), executable size, per-chip HBM footprint from
  ``compiled.memory_analysis()`` joined with the tensor-parallel
  sharding plan into a per-var attribution table, live
  ``device.memory_stats()`` on the heartbeat, and the pre-dispatch
  memory budget gate (``FLAGS_hbm_budget_fraction`` →
  :class:`~.xla_stats.MemoryBudgetError` before dispatch).
"""
from . import (flight, health, metrics_catalog, phases, profiler_capture,
               request_trace, slo, xla_stats)
from .flight import FlightRecorder, get_flight_recorder
from .phases import (PhaseEngine, PhasePlan, build_phase_plan,
                     collective_inventory, phase_engine, phases_report,
                     reset_phases)
from .profiler_capture import (CaptureEngine, capture_engine,
                               parse_trace_dir, reset_capture)
from .request_trace import (RequestTrace, TraceStore,
                            export_request_chrome_trace, get_trace_store)
from .slo import Objective, SLOEngine, get_slo_engine
from .health import (HealthReporter, StallWatchdog, cluster_health,
                     dump_postmortem, executor_progress,
                     install_crash_handler, serve_cluster_health,
                     start_watchdog, stop_watchdog)
from .histogram import (Histogram, HistogramRegistry, export_histograms,
                        histogram, prometheus_text, stat_time)
from .step_stats import (StepTimer, mfu_estimate, reset_step_stats,
                         step_timer)
from .xla_stats import (MemoryBudgetError, births_summary,
                        check_hbm_budget, device_memory_stats,
                        memory_breakdown, memory_report, program_births,
                        var_attribution)
from .tracer import (SpanRecord, Tracer, begin, clear, disable, enable,
                     enabled, end, get_tracer, set_span_args, snapshot,
                     span)
from .timeline import chrome_trace, export_chrome_trace

# compile telemetry covers every program of the process from here on
xla_stats.listen_for_births()

__all__ = [
    # tracer
    "SpanRecord", "Tracer", "get_tracer", "enabled", "enable", "disable",
    "span", "begin", "end", "set_span_args", "snapshot", "clear",
    # timeline
    "chrome_trace", "export_chrome_trace",
    # histograms
    "Histogram", "HistogramRegistry", "histogram", "stat_time",
    "export_histograms", "prometheus_text",
    # step telemetry
    "StepTimer", "step_timer", "reset_step_stats", "mfu_estimate",
    # flight recorder
    "flight", "FlightRecorder", "get_flight_recorder",
    # health plane
    "health", "StallWatchdog", "HealthReporter", "executor_progress",
    "dump_postmortem", "start_watchdog", "stop_watchdog",
    "install_crash_handler", "cluster_health", "serve_cluster_health",
    # XLA introspection
    "xla_stats", "MemoryBudgetError", "memory_breakdown",
    "var_attribution", "check_hbm_budget", "device_memory_stats",
    "memory_report", "program_births", "births_summary",
    # phase attribution + profiler capture + metrics catalog
    "phases", "PhasePlan", "PhaseEngine", "build_phase_plan",
    "collective_inventory", "phase_engine", "phases_report",
    "reset_phases", "profiler_capture", "CaptureEngine",
    "capture_engine", "parse_trace_dir", "reset_capture",
    "metrics_catalog",
    # per-request tracing + SLO plane
    "request_trace", "RequestTrace", "TraceStore", "get_trace_store",
    "export_request_chrome_trace", "slo", "Objective", "SLOEngine",
    "get_slo_engine",
]
