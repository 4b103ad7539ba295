"""Tier-1 config: the FLAGS_* registry (reference platform/flags.cc +
global_value_getter_setter.cc, python paddle.set_flags/get_flags).

Flags initialize from FLAGS_<name> environment variables (reference gflags
env behavior) and are mutable at runtime via set_flags.  SURVEY §5 keeps
the reference's 3-tier config shape: this module is tier 1; BuildStrategy/
ExecutionStrategy are tier 2; DistributedStrategy proto is tier 3.
"""
from __future__ import annotations

import os
from typing import Dict

_TRUTHY = {"1", "true", "True", "TRUE", "yes", "on"}


def _parse(raw: str, default):
    if isinstance(default, bool):
        return raw in _TRUTHY
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


class _Flag:
    __slots__ = ("name", "value", "default", "help")

    def __init__(self, name, default, help_=""):
        self.name = name
        self.default = default
        self.help = help_
        raw = os.environ.get("FLAGS_" + name)
        self.value = _parse(raw, default) if raw is not None else default


_REGISTRY: Dict[str, _Flag] = {}


_LOWERING_FLAGS: set = set()  # flags read at trace time (key compiles)


def lowering_key() -> tuple:
    """State of every flag that affects op lowering — folded into the
    Executor compile-cache key so flipping any of them re-lowers
    instead of silently reusing a stale compiled program."""
    return tuple(sorted(
        (n, _REGISTRY[n].value) for n in _LOWERING_FLAGS))


def define_flag(name: str, default, help_: str = "",
                affects_lowering: bool = False):
    if name in _REGISTRY:
        raise KeyError(f"flag {name!r} already defined")
    _REGISTRY[name] = _Flag(name, default, help_)
    if affects_lowering:
        _LOWERING_FLAGS.add(name)


def get_flags(flags):
    """paddle.get_flags parity: str or list -> {name: value}."""
    names = [flags] if isinstance(flags, str) else list(flags)
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag {n!r}")
        out[n] = _REGISTRY[key].value
    return out


def set_flags(flags: Dict):
    """paddle.set_flags parity: {FLAGS_name or name: value}."""
    for n, v in flags.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag {n!r}")
        f = _REGISTRY[key]
        f.value = _parse(v, f.default) if isinstance(v, str) else type(f.default)(v)


def flag(name: str):
    """Internal fast accessor."""
    return _REGISTRY[name].value


def flags_snapshot() -> Dict:
    """Current value of EVERY registered flag (flight-recorder run
    metadata + postmortem bundles: the config a failure ran under is
    half the diagnosis)."""
    return {n: f.value for n, f in sorted(_REGISTRY.items())}


# ---- the registry (reference platform/flags.cc equivalents that are
# meaningful under XLA; memory/GC/cudnn knobs are N/A by design) ----------
define_flag("check_nan_inf", False,
            "scan every op output for NaN/Inf after each executor run "
            "(reference operator.cc:1129 + nan_inf_utils_detail)")
define_flag("benchmark", False, "sync + time each executor call")
define_flag("paddle_num_threads", 1, "host-side intra-op threads (XLA-owned)")
define_flag("use_tpu", True, "prefer the TPU backend when available")
define_flag("eager_delete_tensor_gb", 0.0, "N/A under XLA (kept for parity)")
define_flag("allocator_strategy", "xla", "memory is PJRT/XLA-owned")
define_flag("cpu_deterministic", False,
            "force deterministic reductions on CPU runs")
define_flag("seed", 0, "global random seed override (0 = program seed)")
define_flag("flash_attention", "auto",
            "fused attention kernel engagement: 'auto' (flash only when "
            "the score tensor would threaten HBM), 'always', 'never'. "
            "Also gates the FlashAttentionPass graph rewrite of unfused "
            "matmul/softmax chains ('never' = no rewrite, bitwise "
            "restore; 'auto' rewrites on TPU backends only)",
            affects_lowering=True)
define_flag("fuse_passes", True,
            "enable the graph-pass pipeline (framework/passes.py): fused "
            "bucketed gradient allreduce, redundant-cast elimination, "
            "dead-op elimination — applied before lowering; "
            "affects_lowering so flipping it re-keys the compile cache",
            affects_lowering=True)
define_flag("enable_tracer", False,
            "record host-side spans (executor phases, per-pass, "
            "per-collective, serving batch lifecycle) into the in-process "
            "ring buffer (paddle_tpu.observe); export any time with "
            "observe.export_chrome_trace() — independent of jax.profiler "
            "captures (reference FLAGS_enable_rpc_profiler / DeviceTracer "
            "role, CUPTI replaced by a pure-host ring buffer)")
define_flag("ckpt_async_save", True,
            "CheckpointManager default (paddle_tpu.ckpt): hand "
            "serialization + shard writes to the background writer "
            "thread so save() blocks only for the device->host snapshot")
define_flag("ckpt_keep_n", 5,
            "checkpoint retention default: keep the N newest committed "
            "steps (0 = keep everything); keep_every_n_steps multiples "
            "survive GC regardless")
define_flag("ckpt_fsync", True,
            "fsync shard/manifest files and directories at commit — the "
            "atomicity guarantee against power loss; disable only for "
            "tests/benchmarks on throwaway dirs")
define_flag("ckpt_verify_restore", True,
            "verify the SHA-256 of every shard against the manifest "
            "before restoring (off: existence+size checks only)")
define_flag("device_peak_tflops", 0.0,
            "per-chip peak TFLOP/s the MFU estimate divides by "
            "(observe/step_stats.py).  0 = unset: the live device's "
            "published bf16 peak from observe/device_peaks.py "
            "(keyed by device_kind; \"TPU v5 lite\" = 197).  A device "
            "that is not in that table has no MFU (null in summaries, "
            "an error from mfu_estimate) - never another chip's number")
define_flag("max_inflight_steps", 2,
            "pipelined step dispatch (framework/executor.py): Executor."
            "run returns a lazy StepHandle and up to this many steps may "
            "be in flight on the device before dispatch backpressures "
            "(drains the oldest step).  0 = legacy synchronous fetch "
            "(every run blocks on device->host transfer of its fetch "
            "list).  NaN-scan, FLAGS_benchmark sync, and StepTimer "
            "accounting all happen at window-drain points; "
            "FLAGS_benchmark / FLAGS_check_nan_inf force an immediate "
            "drain per step so their semantics stay per-call")
define_flag("flight_recorder", True,
            "record structured lifecycle events (run metadata, executor "
            "dispatch/drain, ckpt save/restore, serving start/stop) into "
            "the bounded in-process flight-recorder ring "
            "(paddle_tpu.observe.flight); ~µs per event, read back by "
            "postmortem bundles and observe.flight.tail()")
define_flag("flight_recorder_file", "",
            "optional always-on JSONL sink for flight-recorder events: "
            "every event is appended + flushed to this path, so a "
            "process that dies without running any handler still leaves "
            "its event tail on disk; empty = ring buffer only")
define_flag("stall_timeout_s", 0.0,
            "stall watchdog (paddle_tpu.observe.health): when > 0, a "
            "daemon thread samples executor progress (steps dispatched "
            "vs drained, in-flight window age) and dumps a postmortem "
            "bundle (all-thread stacks, Chrome trace, metrics snapshot, "
            "flight-recorder tail, flags) after this many seconds of "
            "no-progress with work pending; 0 = disabled")
define_flag("postmortem_dir", "postmortem",
            "directory postmortem bundles are written under (stall "
            "watchdog, crash hook, bench failure records); each dump is "
            "its own bundle_<ts>_<pid>_<reason> subdirectory — read one "
            "with: python -m tools.postmortem <dir>")
define_flag("heartbeat_interval_s", 10.0,
            "cluster health telemetry (observe/health.py): period of "
            "each rank's HealthReporter heartbeat PUT to the fleet KV "
            "HTTP server; a rank is reported dead on /metrics/cluster "
            "after 3 missed intervals")
define_flag("xla_introspect", True,
            "XLA compile introspection (paddle_tpu.observe.xla_stats): "
            "every Executor compile is AOT-lowered so its wall time "
            "(compile_seconds histogram), executable size, and per-chip "
            "HBM footprint (compiled.memory_analysis) are recorded "
            "BEFORE the first dispatch — the footprint feeds the "
            "FLAGS_hbm_budget_fraction gate.  Capability-guarded: a jax "
            "without AOT stages falls back to the lazy first-call "
            "compile with the telemetry skipped")
define_flag("hbm_budget_fraction", 0.0,
            "pre-dispatch memory budget gate: when > 0, a program whose "
            "predicted per-chip HBM footprint (from "
            "compiled.memory_analysis after lowering) exceeds this "
            "fraction of the device's memory is rejected with a "
            "MemoryBudgetError naming the largest vars and their "
            "sharding specs — a readable report instead of an opaque "
            "RESOURCE_EXHAUSTED mid-step.  0 = gate disabled")
define_flag("hbm_bytes_per_device", 0,
            "explicit per-device HBM capacity in bytes for the budget "
            "gate; 0 = probe device.memory_stats()['bytes_limit'] "
            "(unavailable on the CPU backend, where the gate then "
            "capability-skips unless this override is set)")
define_flag("hlo_dump_dir", "",
            "save each compile's optimized HLO module text under this "
            "directory (hlo_<fingerprint>_<n>.txt) beside the "
            "postmortem bundles; empty = disabled")
define_flag("layer_scan", False,
            "scan-over-layers compile-time optimization (framework/"
            "passes.py LayerScanPass): detect maximal runs of isomorphic "
            "repeated op segments (the forward/backward/optimizer "
            "regions a repeated-layer model builder emits), stack their "
            "per-layer weights on a leading num_layers axis, and lower "
            "each run to ONE jax.lax.scan — trace+compile time and "
            "executable size become ~constant in depth instead of "
            "linear, with bitwise-identical step numerics.  Also "
            "enabled per-program by DistributedStrategy."
            "recompute_configs={'scan_layers': N}; non-matching "
            "programs are left untouched (pass_layer_scan_skipped "
            "counters name why)",
            affects_lowering=True)
define_flag("layer_scan_min_layers", 4,
            "minimum isomorphic segment repeat count before "
            "LayerScanPass rewrites a run (shorter runs gain nothing "
            "and shallow nets keep their unrolled executables); "
            "recompute_configs={'scan_layers': N} overrides per program",
            affects_lowering=True)
define_flag("layer_scan_policy", "",
            "XLA rematerialization policy wrapped around the layer_scan "
            "body via jax.checkpoint: '' (no wrap), 'nothing_saveable', "
            "'dots_saveable', or 'save_anything' (= jax "
            "everything_saveable) — extends the program-level "
            "recompute_barrier support to XLA remat choices per scanned "
            "block",
            affects_lowering=True)
define_flag("layer_scan_unroll", 1,
            "lax.scan unroll= factor for layer_scan regions (>1 trades "
            "compile time back for per-step dispatch overhead on very "
            "cheap bodies)",
            affects_lowering=True)
define_flag("decode_slots", 8,
            "decode engine (paddle_tpu.serving.decode): fixed slot-batch "
            "capacity of one DecodeEngine replica — the number of "
            "requests decoding JOINTLY in each compiled step; new "
            "requests claim free slots at step boundaries (continuous "
            "batching), finished/expired slots free immediately")
define_flag("decode_max_seq_len", 256,
            "decode engine: per-slot sequence capacity (prompt + "
            "generated), and the width of the paged KV cache's per-slot "
            "page table; must be a multiple of FLAGS_decode_page_size")
define_flag("decode_page_size", 16,
            "decode engine: positions per KV-cache page "
            "(serving/kv_cache.py) — pages are the allocation grain, "
            "reserved at admission and freed the moment a request "
            "finishes; also the per-grid-step DMA size of the Pallas "
            "paged decode-attention kernel")
define_flag("decode_max_new_tokens", 64,
            "decode engine: default generation budget when a request "
            "does not pass max_new_tokens; admission reserves cache "
            "pages for prompt + this many positions")
define_flag("decode_prefix_cache", True,
            "decode engine: share KV-cache pages across requests whose "
            "prompts open with the same token prefix "
            "(serving/kv_cache.py PrefixIndex) — admission skips both "
            "the HBM reservation AND the prefill compute for hit "
            "pages, with refcounts + copy-on-write at the first "
            "divergent token; finished requests register their pages "
            "for future hits (evicted LRU under pool pressure)")
define_flag("decode_prefill_chunk_pages", 0,
            "decode engine: chunked prefill — a prompt longer than "
            "this many cache pages fills them across SEVERAL step "
            "boundaries instead of stalling the whole slot batch on "
            "one long prefill dispatch (protects ttft_ms_p99 for the "
            "slots already decoding); 0 = off (one prefill dispatch "
            "per request)")
define_flag("request_trace_sample", 1.0,
            "per-request tracing (paddle_tpu.observe.request_trace): "
            "head-sampling fraction of NORMAL completions whose full "
            "timeline is retained in the bounded finished-trace ring "
            "(deterministic exact rate).  Recording itself is always on "
            "and ~free (one monotonic read + a tuple append per "
            "lifecycle event); tail retention keeps every SLO violator "
            "and abnormal ending (deadline/abandoned/rejected/error) "
            "REGARDLESS of this flag — 0 retains only the traces you'd "
            "page on")
define_flag("request_trace_ring", 512,
            "capacity of the retained finished-trace ring "
            "(request_trace.TraceStore); oldest retained traces fall "
            "off — in-flight timelines are unaffected")
define_flag("slo_ttft_p99_ms", 0.0,
            "SLO objective (paddle_tpu.observe.slo): time-to-first-"
            "token p99 target in ms — a request whose ttft exceeds it "
            "(or that dies before first token) burns the 1% error "
            "budget; 0 = objective disabled.  Burn-rate/budget gauges "
            "ride /metrics as slo_burn_rate_ttft_p99_ppm / "
            "slo_budget_remaining_ttft_p99_ppm")
define_flag("slo_tpot_p50_ms", 0.0,
            "SLO objective: per-request MEAN time-per-output-token p50 "
            "target in ms (budget 50%); 0 = disabled")
define_flag("slo_error_rate_ppm", 10000,
            "SLO objective: allowed fraction of requests ending in any "
            "outcome other than 'completed', in parts-per-million "
            "(default 10000 = 1%); 0 = disabled.  Always-on by default "
            "so decode_goodput_rps and the burn gauges exist out of "
            "the box")
define_flag("slo_windows_s", "60,300",
            "comma-separated rolling window lengths (seconds) for the "
            "multi-window burn-rate evaluation (SRE-workbook style: "
            "short window catches fast burn, long window slow bleed); "
            "goodput is measured over the shortest window")
define_flag("weight_quant", "",
            "post-training weight-only quantization "
            "(slim/quantization.py PostTrainingWeightQuantPass): rewrite "
            "matmul-family weights to a compact carrier + per-output-"
            "channel scales lowered through the dequant-fused "
            "ops/quant_ops.dequant_matmul kernel.  '' = off; 'int8' = "
            "symmetric int8; 'fp8_e4m3' = float8 e4m3 (jnp.float8_e4m3fn).  "
            "Per-program override: slim.quantization.mark_weight_quant",
            affects_lowering=True)
define_flag("elastic_max_restarts", 3,
            "elastic training supervisor (distributed/fleet/elastic): "
            "restart budget — how many times ElasticSupervisor.run may "
            "restart (in place) or re-shard (after a dead rank) "
            "following a classified failure before raising a terminal "
            "ElasticTerminated with the full restart history (the "
            "terminal path: tests/test_elastic.py, "
            "test_restart_budget_exhaustion_is_terminal_not_a_hang)")
define_flag("elastic_preflight_timeout_s", 240.0,
            "deadline for ONE device preflight probe "
            "(fleet.elastic.preflight_device: a tiny jit dispatch on a "
            "daemon thread of the calling process, which holds or is "
            "about to hold the chip); past it the caller gets a "
            "structured init_timeout verdict, retried with backoff")
define_flag("elastic_backoff_s", 10.0,
            "base backoff between elastic restart/preflight attempts; "
            "attempt k sleeps backoff * 2^(k-1) — exponential, so a "
            "transiently-held chip (an orphaned worker still being "
            "reaped) gets time to come back without burning the "
            "restart budget in seconds")
define_flag("decode_kv_quant", False,
            "decode engine: store KV-cache pages int8 with a parallel "
            "per-page scale pool (serving/kv_cache.py) — scales are "
            "per position-in-page per head, written by the SAME step "
            "that writes the page bytes, so stored content is "
            "write-once and order-independent (speculative decode "
            "stays bitwise-equal to its own non-speculative quantized "
            "run).  Roughly halves bytes per page vs bf16, so a fixed "
            "pool byte budget holds ~2x the pages -> ~2x decode slots; "
            "attention dequantizes pages inline in both the reference "
            "and Pallas paths")
define_flag("pp_degree", 0,
            "default pipeline-parallel degree for shapeless mesh "
            "building: parallel_env.init_parallel_env() called with "
            "NEITHER mesh_shape NOR axis_names factors the visible "
            "devices into a (dp, pp) named mesh with this many "
            "pipeline stages (0 = no pipeline axis; a non-divisor "
            "device count is rejected loudly).  The stage COUNT a "
            "program runs with is always the mesh's 'pp' axis size — "
            "this flag only sizes meshes built without an explicit "
            "shape, and an explicit axis_names argument wins over it; "
            "3-axis (dp, mp, pp) meshes are built with an explicit "
            "mesh_shape")
define_flag("overlap_grad_allreduce", True,
            "stretch FuseAllReducePass buckets across the layer-scan "
            "boundary (framework/passes.py): a bucket holding a stacked "
            "grad-carrier allreduce (the LayerScanPass pulled-out "
            "collective carrying num_layers x per-layer bytes) closes "
            "at its producing backward segment instead of being dragged "
            "to the last collective of the whole backward — the bulk "
            "grad payload dispatches as soon as the backward scan "
            "finishes and overlaps the remaining (unrolled edge-layer) "
            "backward compute.  Off = one greedy bucket stream anchored "
            "at its last member (the pre-overlap sequential schedule, "
            "the bench A/B baseline)",
            affects_lowering=True)
define_flag("collective_matmul_chunks", 0,
            "latency-hiding collective matmul (ops/collective_matmul."
            "py): decompose each tensor-parallel ROW-PARALLEL matmul + "
            "mp partial-sum reduce (the ops ShardingPropagationPass "
            "anchored as contracted) into this many output-row chunks — "
            "chunk k's reduce overlaps chunk k+1's matmul on hardware "
            "with async collectives (Wang et al., ASPLOS 2023).  "
            "Applies to the GSPMD tensor-parallel path AND the manual "
            "pipeline×mp path; a shape not divisible by the chunk count "
            "(x its sharded mesh axes) falls back to the unchunked "
            "lowering, counted collective_matmul_fallback.  0/1 = off; "
            "pure-jnp semantics, so CPU tier-1 runs stay exact",
            affects_lowering=True)
define_flag("ep_degree", 0,
            "default expert-parallel degree for shapeless mesh "
            "building: parallel_env.init_parallel_env() called with "
            "NEITHER mesh_shape NOR axis_names factors the visible "
            "devices into a (dp, ep) named mesh — or (dp, ep, pp) when "
            "FLAGS_pp_degree also asks for stages — with this many "
            "expert shards (0 = no ep axis; a non-divisor device "
            "count, or an ep x pp product exceeding the visible "
            "devices, is rejected loudly at carve time with the axis "
            "named).  The expert-parallel degree a program runs with "
            "is always the mesh's 'ep' axis size — this flag only "
            "sizes meshes built without an explicit shape, and an "
            "explicit axis_names argument wins over it")
define_flag("moe_alltoall_chunks", 0,
            "latency-hiding MoE all-to-all (ops/moe_ops.py): slice the "
            "expert-parallel dispatch/combine all-to-all and the "
            "expert FFN einsums into this many CAPACITY-axis chunks — "
            "chunk k's all-to-all overlaps chunk k+1's expert compute "
            "(the collective-matmul chunking idiom generalized to "
            "all-to-all).  Chunk outputs are CONCATENATED and combined "
            "once, so chunked and sequential schedules stay bitwise-"
            "identical; a capacity not divisible by the chunk count "
            "falls back to the unchunked lowering, counted "
            "moe_alltoall_fallback.  0/1 = off; pure-jnp semantics, "
            "so CPU tier-1 runs stay exact",
            affects_lowering=True)
define_flag("decode_spec_k", 0,
            "decode engine: speculative decoding window — a draft "
            "model (DecodeEngine(draft_model=, draft_weights=)) "
            "proposes this many tokens per round and the target model "
            "verifies them in ONE batched step; greedy output stays "
            "bitwise-identical to non-speculative decode (rejected "
            "proposals fall back to the target's own token); 0 = off, "
            "ignored unless a draft model is configured")
define_flag("phase_attribution", True,
            "step-phase attribution (paddle_tpu.observe.phases): "
            "decompose each drained step's wall time into compute / "
            "exposed-collective / host-blocked / input-wait buckets "
            "(phase_*_seconds_micro gauges + the per-collective "
            "exposed-vs-hidden ledger on /stats and /metrics).  Pure "
            "observer: never affects lowering or numerics — the "
            "measured split comes from timestamps the drain path "
            "already takes, the predicted split from the compile-time "
            "cost model (deterministic on CPU/tier-1)")
define_flag("phase_interconnect_gbps", 100.0,
            "assumed per-chip interconnect bandwidth (GB/s) for the "
            "phase-attribution cost model's predicted collective "
            "times (observe/phases.py) — TPU v4/v5e ICI-class default; "
            "set to your fabric's number for honest predicted "
            "comm fractions.  Prediction only: measured phases and "
            "step numerics never read it")
define_flag("prof_trigger_ratio", 0.0,
            "anomaly-triggered profiling (observe/profiler_capture): "
            "when a drained step's wall time exceeds this ratio x the "
            "rolling step-time baseline (or an slo_burn_rate_* gauge "
            "trips past its budget), capture ONE bounded jax.profiler "
            "trace window + phase snapshot into a postmortem bundle "
            "(phases.json section), then latch until the step time "
            "drops back under the threshold; 0 = disabled")
define_flag("prof_cooldown_s", 60.0,
            "minimum seconds between two anomaly-triggered captures "
            "(observe/profiler_capture): after one bundle is written "
            "the trigger stays quiet for this long even if the episode "
            "re-trips — a sustained regression produces one bundle per "
            "cooldown window, not one per step; the capture itself "
            "perturbs step times, so this also keeps the observer from "
            "triggering on its own overhead")
define_flag("prof_capture_s", 2.0,
            "bound (seconds) of one anomaly/continuous profiler "
            "capture window — the trace is stopped after this long no "
            "matter what, so a capture can never become the overhead "
            "it is meant to explain")
define_flag("prof_continuous_s", 0.0,
            "continuous low-duty-cycle profiling: every this many "
            "seconds, capture one FLAGS_prof_capture_s trace window "
            "(duty cycle = capture_s / continuous_s) — the always-on "
            "fleet profiling mode; 0 = disabled.  Captures are "
            "capability-skipped (prof_trace_unavailable counted) on "
            "backends without jax.profiler trace support")
define_flag("flight_recorder_max_mb", 0.0,
            "size-based rotation for the FLAGS_flight_recorder_file "
            "JSONL sink: when the active segment exceeds this many MB "
            "it is rotated to <path>.1 (one previous segment kept, so "
            "the post-crash tail always spans >= this much history); "
            "0 = unbounded (the pre-rotation behavior)")
define_flag("disagg_prefill_replicas", 1,
            "disaggregated serving (paddle_tpu.serving.disagg): "
            "replicas in the PREFILL set of a DisaggServer — they run "
            "only (chunked) prefill + first-token sampling, then hand "
            "the request's KV pages off to a decode replica; the "
            "DistServe/Mooncake split that stops long prefills from "
            "stealing decode step time")
define_flag("disagg_decode_replicas", 1,
            "disaggregated serving: replicas in the DECODE set — they "
            "admit requests by INSTALLING migrated KV pages (no "
            "prefill compute) and emit from the first decode step; "
            "tokens stay bitwise-equal to a local prefill because the "
            "migrated admission reuses the full-prefix-hit contract "
            "(lengths start at prompt-1, same fold_in(key, 0) "
            "sampling)")
define_flag("disagg_migrate_host_bounce", False,
            "disaggregated serving: force KV-page migration through "
            "host memory (np.asarray out / device_put in) even when "
            "prefill and decode replicas share a process/backend — "
            "the cross-host transport path, also the A/B knob for "
            "measuring migration overhead; off = device-to-device "
            "pool-slice copy when possible")
define_flag("disagg_handoff_timeout_s", 120.0,
            "disaggregated serving: how long the router waits for a "
            "prefill replica to finish one request's prefill leg "
            "before treating the replica as failed and re-dispatching "
            "the request (counted disagg_redispatches_total)")
define_flag("disagg_redispatch_retries", 2,
            "disaggregated serving: how many times the router "
            "re-dispatches one request after a prefill-replica "
            "failure (death, timeout, lost payload) before failing "
            "the request to the client; each retry picks a surviving "
            "replica, so a killed replica drops zero requests while "
            "any prefill capacity remains")
define_flag("disagg_autoscale_interval_s", 1.0,
            "disagg autoscaler: seconds between policy ticks of the "
            "background Autoscaler thread (Autoscaler.serve_forever); "
            "each tick reads SLO burn + queue depths and may re-role "
            "at most one replica")
define_flag("disagg_autoscale_cooldown_s", 30.0,
            "disagg autoscaler: minimum seconds between two re-roles "
            "— the anti-flap floor; a trigger firing inside the "
            "window is counted (autoscale_cooldown_skips_total) and "
            "dropped, never queued")
define_flag("disagg_autoscale_burn_high", 1.0,
            "disagg autoscaler: ttft-objective SLO burn rate at/above "
            "which a decode replica is re-roled into the prefill set "
            "(prefill capacity is what ttft burn starves); paired "
            "with disagg_autoscale_burn_low as hysteresis so the two "
            "thresholds can never chase each other")
define_flag("disagg_autoscale_burn_low", 0.25,
            "disagg autoscaler: ttft burn rate at/below which the "
            "prefill side is considered healthy enough to GIVE UP a "
            "replica to the decode set (only then does decode queue "
            "pressure trigger a prefill->decode re-role) — the lower "
            "half of the hysteresis band")
define_flag("disagg_autoscale_queue_high", 4,
            "disagg autoscaler: mean decode-replica queue depth "
            "at/above which (with prefill burn under burn_low) a "
            "prefill replica is re-roled into the decode set")
