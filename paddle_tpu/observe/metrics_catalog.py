"""Authoritative metrics catalog: every ``/metrics`` series documented.

The stat plane grew organically (executor counters, pass stats, serving
outcomes, SLO burn gauges, phase attribution...) and the only inventory
was grep.  This module is the registry of record: an ordered list of
prefix rules mapping a series name (namespace stripped) to its type,
unit convention, and owning subsystem.  Two consumers:

- ``METRICS.md`` is *generated* from these rules
  (``python -m paddle_tpu.observe.metrics_catalog --write``); the
  checked-in copy is a drift gate — tier-1 fails when the file and the
  rules disagree.
- ``tests/test_metrics_catalog.py`` scrapes a clean-process
  ``prometheus_text()`` and asserts every exported series matches a
  rule, so a PR adding a stat without a catalog row fails loudly.

Units are suffix-encoded by convention (the registry stores ints only,
PR 4): ``_seconds`` (histogram, float seconds), ``_seconds_micro``
(gauge, integer microseconds), ``_bytes``, ``_ppm`` (parts-per-million
of a ratio), ``_ms``, ``_rps``; bare names are event/object counts.
``unit_of`` resolves a concrete name's unit from its suffix.

Matching is first-rule-wins over the authoring order below, with exact
rules (``exact=True``) checked as whole-name equality and prefix rules
as ``startswith``.
"""
from __future__ import annotations

import sys
from typing import List, NamedTuple, Optional

__all__ = ["Rule", "RULES", "lookup", "unit_of", "catalog_markdown",
           "check_file", "write_file", "main"]


class Rule(NamedTuple):
    prefix: str       # name prefix (or whole name when exact=True)
    type: str         # "gauge" | "histogram" (counters export as gauges)
    subsystem: str    # owning module / plane
    description: str  # one line: what the family measures
    exact: bool = False


# Ordered: exact histogram names first (several share a prefix with
# gauge families), then gauge/counter families grouped by subsystem.
RULES = (
    # -- latency histograms (HistogramRegistry, stat_time) ---------------
    Rule("step_time_seconds", "histogram", "step_stats",
         "Per-step wall time distribution (drained, post-compile)",
         exact=True),
    Rule("compile_seconds", "histogram", "xla_stats",
         "Program compile wall time per cache-miss", exact=True),
    Rule("xla_compile_seconds", "histogram", "xla_stats",
         "XLA-side compile time where introspection exposes it",
         exact=True),
    Rule("xla_trace_seconds", "histogram", "xla_stats",
         "Birth log: a compiled program's own jaxpr trace (the traces of "
         "the jitted functions it calls lie inside it), every program of "
         "the process; host work no compile cache removes: a tail here "
         "is set-up time a warm start still pays", exact=True),
    Rule("xla_lower_seconds", "histogram", "xla_stats",
         "Birth log: a program's lowering to an MLIR module; with "
         "`xla_trace_seconds` what a warm start spends before it can "
         "ask the cache", exact=True),
    Rule("xla_backend_compile_seconds", "histogram", "xla_stats",
         "Birth log: a backend compile that no cache hit replaced (a "
         "miss, or no cache): the cold part of set-up; a sample after "
         "start-up is a recompile", exact=True),
    Rule("xla_cache_load_seconds", "histogram", "xla_stats",
         "Birth log: a persistent-cache hit's retrieval (read + "
         "deserialize_executable); grows with what a cached program "
         "holds, so watch it when kernels are added", exact=True),
    Rule("input_wait_seconds", "histogram", "io",
         "Executor blocked waiting on the input pipeline", exact=True),
    Rule("fetch_sync_seconds", "histogram", "io",
         "Host-blocking fetch/device-sync sections", exact=True),
    Rule("ckpt_save_blocking_seconds", "histogram", "checkpoint",
         "Train-loop time blocked by a checkpoint save", exact=True),
    Rule("ckpt_write_seconds", "histogram", "checkpoint",
         "Checkpoint shard write+fsync time", exact=True),
    Rule("serving_latency_seconds", "histogram", "serving",
         "End-to-end serving request latency", exact=True),
    Rule("decode_request_latency_seconds", "histogram", "serving",
         "Decode-engine request latency (submit to terminal)",
         exact=True),
    Rule("decode_prefill_seconds", "histogram", "serving",
         "One prefill dispatch from its arguments through the sync: "
         "until the sampled token is on the host (per request or "
         "chunk; a chunk that samples no token has no sync and ends "
         "with its dispatch)", exact=True),
    Rule("decode_step_seconds", "histogram", "serving",
         "One batched decode step, dispatch + sync: from the hand-over "
         "until its tokens are on the host, a whole-prompt prefill "
         "handed over ahead of it in the same iteration included (a "
         "speculative round: draft burst + verify).  A joint step is "
         "handed over while the one before it is in flight "
         "(`decode_steps_ahead`) and read an iteration later, so the "
         "interval also holds the wait behind that step and the host's "
         "work beside it: consecutive observations overlap, and their "
         "sum passes the wall time", exact=True),
    Rule("decode_turnaround_seconds", "histogram", "serving",
         "Where the engine loop leaves the device waiting for the "
         "host: from a joint step's tokens on the host until the next "
         "joint step's hand-over begins (delivery, reaping, the next "
         "step's arguments, the admission, the prefills handed over "
         "ahead), observed ONLY at a hand-over with no joint step in "
         "flight, never across an idle wait.  A step handed over behind "
         "one in flight (`decode_steps_ahead` of `decode_steps`) waits "
         "for nothing the host does and observes nothing, so the sum's "
         "rate, seconds a second, is the share of the time the device "
         "waits for the host between joint steps", exact=True),
    Rule("ttft_seconds", "histogram", "slo",
         "Time to first token (SLO input): submit until the prefill's "
         "sampled token has been read back and is handed to the caller, "
         "on every prefill path", exact=True),
    Rule("tpot_seconds", "histogram", "slo",
         "Time per output token (SLO input)", exact=True),
    Rule("emb_lookup_seconds", "histogram", "embedding",
         "Sharded-embedding lookup (gather+alltoall)", exact=True),
    Rule("migrate_seconds", "histogram", "disagg",
         "One KV-page migration install (gather->scatter)",
         exact=True),
    # -- executor / compile plane ---------------------------------------
    Rule("executor_", "gauge", "executor",
         "Dispatch/drain/cache counters of the Executor hot path"),
    Rule("executable_", "gauge", "xla_stats",
         "Compiled-executable size and HLO op counts"),
    Rule("remat_", "gauge", "executor",
         "Rematerialization policy availability/fallbacks"),
    Rule("mfu_", "gauge", "step_stats",
         "Model-FLOPs-utilization estimate bookkeeping"),
    Rule("h2d_", "gauge", "io",
         "Host-to-device transfer bytes (feed path)"),
    # -- graph passes / parallelism -------------------------------------
    Rule("pass_", "gauge", "passes",
         "Graph-pass effect counters (fusion, scan, DCE, quant, TP)"),
    Rule("pipeline_", "gauge", "pipeline",
         "Pipeline-parallel scan/segment counters"),
    Rule("pp_", "gauge", "pipeline",
         "Pipeline-parallel schedule stats (stages, bubble fraction)"),
    Rule("tp_", "gauge", "tensor_parallel",
         "Tensor-parallel constraint/fallback counters"),
    Rule("collective_matmul_", "gauge", "tensor_parallel",
         "Collective-matmul chunking engagement/fallbacks"),
    Rule("ep_", "gauge", "expert_parallel",
         "Expert-parallel ('ep' axis) mesh/plan bookkeeping"),
    Rule("moe_local_assignments", "gauge", "expert_parallel",
         "(live row, chosen expert) pairs whose expert THIS chip holds "
         "(`ops/moe_ops.py` `moe_share_*`), summed over a joint decode "
         "step's layers: what the held share computes.  Rides the "
         "step's one read-back behind the tokens"),
    Rule("moe_experts_hit", "gauge", "expert_parallel",
         "Held experts some live row of a joint decode step chose, "
         "summed over its layers: the expert weights the step cannot "
         "avoid reading.  Rides the step's one read-back"),
    Rule("moe_grouped_pairs", "gauge", "expert_parallel",
         "(live row, held expert) pairs that calls of `moe_share_ffn` "
         "computed in the grouped form (`ops/pallas_moe_grouped.py`: "
         "the pairs sorted by expert, a row meets only the experts it "
         "chose), summed over a whole-prompt prefill's layers and read "
         "back with the prefill's token.  0 over a routed model's "
         "prefills means the form never engaged"),
    Rule("moe_grouped_rows_dense", "gauge", "expert_parallel",
         "Rows x held experts of those same calls: the pairs the dense "
         "form (every row through every held expert) would have "
         "computed.  `moe_grouped_pairs` over this is the share of the "
         "old multiply-adds that is left"),
    Rule("moe_grouped_extra_passes", "gauge", "expert_parallel",
         "Passes over the grouped form's sorted buffer beyond a call's "
         "first: a call whose rows chose more pairs than the buffer "
         "holds (two a row) fills and walks it again, reading the held "
         "experts' weights once more each time; nothing is dropped and "
         "no other form takes over.  0 under any routing near uniform"),
    Rule("moe_grouped_layout_updates", "gauge", "expert_parallel",
         "Updates the ONE scatter of the grouped form's pair layout "
         "walks a call (`ops/pallas_moe_grouped.py` `layout_pass`; the "
         "chip walks them one by one, dropped ones too): rows x "
         "min(top_k, n_held), a row's candidates, where the call is "
         "told `top_k`, rows x held experts where not.  Set when a "
         "call is traced, so it reads the newest traced shape; until "
         "PR 55 two scatters walked rows x held experts each"),
    Rule("moe_hit_form_calls", "gauge", "expert_parallel",
         "Calls of `moe_share_ffn` in a joint decode step that took the "
         "hit form (`ops/pallas_moe_hit.py`: one kernel over the held "
         "experts some live row chose; engaged by `moe_ops.hit_rule` "
         "from the step's static shape and the model's `top_k` / "
         "`num_experts`), one an expert layer a step.  Rides the step's "
         "one read-back; 0 where the step keeps the dense form, which "
         "then does not read it back at all"),
    Rule("moe_experts_skipped", "gauge", "expert_parallel",
         "Held experts whose weights those calls did not read, summed "
         "over a joint decode step's layers: with `moe_experts_hit` it "
         "adds up to held experts x expert layers a step"),
    Rule("moe_", "gauge", "expert_parallel",
         "Mixture-of-experts routing: expert balance and drop "
         "fractions (ppm), routed-FFN engagement, all-to-all "
         "chunking engagement/fallbacks"),
    Rule("flash_attention_", "gauge", "kernels",
         "Flash-attention kernel engagement"),
    Rule("quant_", "gauge", "quantization",
         "Quantization engagement and quality deltas"),
    # -- phase attribution / profiling (this PR) ------------------------
    Rule("phase_", "gauge", "phases",
         "Step-phase attribution: per-bucket seconds/fractions and "
         "predicted compute/comm split"),
    Rule("comm_", "gauge", "phases",
         "Collective ledger: exposed vs hidden communication time"),
    Rule("prof_", "gauge", "profiler_capture",
         "Anomaly-triggered / continuous profiler capture counters"),
    # -- observability plane --------------------------------------------
    Rule("flight_", "gauge", "flight",
         "Flight-recorder sink bookkeeping (rotations)"),
    Rule("watchdog_", "gauge", "health",
         "Stall-watchdog trips"),
    Rule("postmortem_", "gauge", "health",
         "Postmortem bundles written"),
    Rule("health_", "gauge", "health",
         "Heartbeat delivery failures/blackholes"),
    Rule("cluster_", "gauge", "health",
         "Rank-0 aggregated cluster health (skew, stragglers, HBM)"),
    Rule("hbm_", "gauge", "xla_stats",
         "HBM budget gate and live device memory"),
    Rule("xla_program_births", "gauge", "xla_stats",
         "Programs compiled or loaded from the compile cache since the "
         "process started, all threads (`xla_stats.program_births()` "
         "holds each one's record): flat in a healthy serving process "
         "after warm-up", exact=True),
    Rule("xla_cache_hits", "gauge", "xla_stats",
         "Births that a persistent-cache hit served: a warm start has "
         "`xla_cache_hits` = `xla_program_births` less the uncached",
         exact=True),
    Rule("xla_cache_misses", "gauge", "xla_stats",
         "Births that asked the persistent cache and compiled: each "
         "leaves an `xla/program_born` flight event naming the span it "
         "ran under; nonzero after start-up means a shape or a source "
         "line the cache has not seen", exact=True),
    Rule("xla_", "gauge", "xla_stats",
         "XLA introspection availability/fallback counters"),
    Rule("slo_", "gauge", "slo",
         "SLO burn rates and remaining error budget per objective"),
    Rule("request_trace", "gauge", "request_trace",
         "Per-request trace store occupancy/retention"),
    # -- training-side subsystems ---------------------------------------
    Rule("ckpt_", "gauge", "checkpoint",
         "Checkpoint save/restore/GC outcomes and bytes"),
    Rule("elastic_", "gauge", "elastic",
         "Elastic restart/reshard lifecycle counters"),
    Rule("chaos_", "gauge", "elastic",
         "Chaos fault injection arming/firing"),
    Rule("emb_", "gauge", "embedding",
         "Sharded-embedding traffic and placement stats"),
    # -- serving ---------------------------------------------------------
    Rule("decode_h2d_", "gauge", "serving",
         "Host arrays the engine's argument builders hand to the device "
         "(`_uploads`: count, `_bytes`), counted where they are handed "
         "over: ONE packed int32 array a joint decode step and a "
         "whole-prompt prefill, one a field for the rows and "
         "speculative builders; the weights are not in it"),
    Rule("decode_attn_blocks_", "gauge", "serving",
         "Blocks of page-table entries the paged-attention kernel meets "
         "a layer, added once a joint decode step from the lengths the "
         "engine holds: `_live` the blocks its loop runs (those holding "
         "a position some row attends; one for a dead slot), `_walked` "
         "every block of every slot's table, which a fixed grid would "
         "walk.  Their ratio is the share of the table that is work"),
    Rule("decode_prefill_keys_", "gauge", "serving",
         "Cache positions a whole-prompt prefill's attention meets a "
         "head, summed over the model's layers that have keys, added "
         "once a prefill from the numbers the host holds: `_attended` "
         "what the form that runs walks (the flash kernel: its row "
         "blocks that hold a real row times the key blocks each visits; "
         "else the bucket's rows times the positions each row's softmax "
         "spans: the bucket, in a window layer the keys a block of "
         "query rows reaches through the window), `_live` those a "
         "prompt row can see (length x (length + 1) / 2; in a window "
         "layer its last `window`).  Their ratio is the share of the "
         "attention that is work; the rest is the blocks the diagonal "
         "or the window's edge crosses and the bucket's padding"),
    Rule("decode_prefill_compiles", "gauge", "serving",
         "jit FUNCTIONS the engine made for a prefill bucket or a "
         "multi-row step (one a new `(bucket, model, quantized)` or "
         "`(rows, slots, model)` key), not compiles: whether such a "
         "function's first call compiled or loaded, and for how long, is "
         "its birth (`xla_program_births`, `xla_cache_misses`, under "
         "`serving/prefill_dispatch` with its `bucket`)", exact=True),
    Rule("decode_prefill_attn_", "gauge", "serving",
         "Layer-calls of a whole-prompt prefill's attention by the form "
         "they ran in, added once a prefill: `_flash` the Pallas flash "
         "kernel (`ops/pallas_prompt_attention.py`, where kernels are "
         "asked for and its rule takes the shape), `_blocks` plain jnp "
         "in blocks of query rows (or, for a model that stays bitwise "
         "with its cached prefix, over the whole bucket)"),
    Rule("decode_prefill_scan_", "gauge", "serving",
         "How a model's recurrent layers take a prompt in a whole-prompt "
         "prefill, counted in the program and read back with the "
         "prefill's token, summed over the recurrent layers: `_steps` "
         "the iterations of the scan over the prompt, `_tokens` the real "
         "tokens they took (padding rows are never scanned).  Their "
         "ratio is the tokens an iteration takes: 1 where the model "
         "hands the one-token update alone, near the chunk's length "
         "where it hands the rule's chunk form"),
    Rule("decode_steps_", "gauge", "serving",
         "Joint decode steps whose sampler does more than an argmax, "
         "added once a step from the knobs the engine hands over: "
         "`_drawn` the steps in which a live slot has temperature > 0 "
         "(the categorical draw runs), `_filtered` those in which such "
         "a slot also has top_k > 0 or top_p < 1 (the vocabulary's sort "
         "runs).  Either over `decode_steps` is the share of steps that "
         "pay for it; every other step takes the argmax alone.  `_slow` "
         "the joint steps whose hand-over and read-back together kept "
         "the host longer than 0.5 s, the program's first run (a compile or a load from the "
         "compile cache) apart: each leaves a `serving/slow_step` event "
         "in the flight recorder (iteration, step, live slots, prefills "
         "ahead, the hand-over's and the read-back's begin and end).  "
         "`_ahead` the joint steps handed over while the joint step "
         "before them was in flight, its tokens not yet on the host "
         "(the next token comes from the device): over `decode_steps` "
         "the share of steps the host's path ran beside the device's"),
    Rule("decode_rows_discarded", "gauge", "serving",
         "Rows of a joint decode step that were nobody's at its "
         "delivery: the slot no longer held the request the row was "
         "computed for (an end token in the step before, a deadline "
         "reap, an abort or a failed read-back released it while the "
         "step was in flight)"),
    Rule("decode_state_bytes", "gauge", "serving",
         "Device bytes of the slot-indexed slabs that hold the state of "
         "a model's recurrent layers (linear attention: a matrix a head "
         "and a convolution tail), all slots; 0 for a model whose "
         "layers all keep keys"),
    Rule("decode_prefix_bypassed", "gauge", "serving",
         "Admissions of an engine whose prefix cache was asked for and "
         "left out because the model keeps recurrent state or a ring of "
         "a window layer's last positions (a shared page of keys says "
         "nothing about either), or a latent page (what would read a "
         "hit's suffix through the pages is not built for its rows): "
         "every request admitted fresh"),
    Rule("decode_window_pages_", "gauge", "serving",
         "The rings of a model's window layers (a slot keeps ceil(window "
         "/ page) + 1 pages a layer, however long its request), over all "
         "window layers, set or added once a joint decode step: `_held` "
         "(gauge) the ring pages that hold a position of a live request, "
         "never more than slots x layers x ring; `_recycled` the pages a "
         "step's tokens overwrote because they had slid out of the "
         "window"),
    Rule("decode_window_blocks_walked", "gauge", "serving",
         "Blocks of ring entries the window layers' paged-attention "
         "kernel walks a layer (one or two a live slot: from the block "
         "that holds the window's first position), added once a joint "
         "decode step from the lengths the engine holds"),
    Rule("decode_window_positions_live", "gauge", "serving",
         "Positions inside some live row's window a layer (min(length, "
         "window) a slot), added once a joint decode step; over "
         "`decode_window_blocks_walked` x a block's positions it is the "
         "share of what the window kernel reads that is attended"),
    Rule("decode_window_rows", "gauge", "serving",
         "Whether the window caps anything, added once a joint decode "
         "step from the lengths the engine holds: `decode_window_rows` "
         "the live rows, `_capped` those whose length exceeds the window "
         "(their attention reads the window, not the context).  Their "
         "ratio is the share of the step's rows for which a window layer "
         "is cheaper than a global one"),
    Rule("decode_window_bytes", "gauge", "serving",
         "Device bytes of the window layers' ring pools, all slots: no "
         "term in max_seq_len; 0 for a model without window layers"),
    Rule("decode_prefill_conv_rows", "gauge", "serving",
         "Real prompt rows that went through the convolution layers' "
         "prompt form (one call a layer for the whole bucket), a layer, "
         "read back with a whole-prompt prefill's token: over "
         "`decode_prefill_scan_steps` it is the rows a call took"),
    Rule("decode_latent_bytes", "gauge", "serving",
         "Device bytes of the pool of a model with latent attention: ONE "
         "row a position a layer for keys and values, at whole lane "
         "tiles, every page of every layer; 0 for a model that caches K "
         "and V"),
    Rule("decode_cache_layers", "gauge", "serving",
         "Depth of the engine's page pools: the layers of K and V a "
         "token leaves in the cache, what the model declares as "
         "`cache_layers` (a stack its tokens pass through several times "
         "on the same weights keeps K and V of every pass), else one a "
         "weight layer that has keys"),
    Rule("decode_kv_pool_bytes", "gauge", "serving",
         "Device bytes of the page pools, every cache layer and page, K "
         "and V (scale pools included when quantized; the one pool of a "
         "latent cache): over the positions of a pool layer it is what "
         "a cached token costs"),
    Rule("decode_loop_passes", "gauge", "serving",
         "Passes of its stack a looped model's `forward` ran "
         "(`serving/looped_lm.py` adds `loops`), read back with the "
         "tokens of a joint decode step and of a whole-prompt prefill: "
         "over `decode_steps` + `decode_prefills` it is the passes a "
         "token takes (a multi-row program's are counted and dropped)"),
    Rule("decode_loop_exit_mass", "gauge", "serving",
         "Where a looped model's exit gate would let a joint decode "
         "step's live rows leave, in THOUSANDTHS of a pass: the sum over "
         "the rows of sum_t p_t (t + 1), p_t = lambda_t prod_{j<t} (1 - "
         "lambda_j) with the rest on the last pass.  Over the live rows "
         "x 1000 it is the expected pass of exit; at the published "
         "threshold of 1 it decides nothing (every row runs every pass): "
         "a number to watch, limits nothing"),
    Rule("decode_latent_positions_live", "gauge", "serving",
         "Cached rows the live slots' tokens attend a layer in a model "
         "with latent attention (a slot's length, the token itself "
         "counted), added once a joint decode step from the lengths the "
         "engine holds: times the row's published bytes and the layers "
         "it is what the latent kernel has to read"),
    Rule("decode_latent_blocks_walked", "gauge", "serving",
         "Blocks of page-table entries the latent kernel's loop runs a "
         "layer (those holding a position a live slot attends), added "
         "once a joint decode step; `decode_latent_positions_live` over "
         "it x a block's positions is the share of what it copies that "
         "is attended"),
    Rule("decode_index_bytes", "gauge", "serving",
         "Device bytes of the index keys' pool of a model whose attention "
         "reads the positions a learned indexer selects: ONE key a "
         "position a layer in a third pool behind the K/V pools' page "
         "ids, at whole lane tiles, every page of every layer; 0 for a "
         "model that keeps none"),
    Rule("decode_index_positions_scored", "gauge", "serving",
         "Cached index keys the live slots' queries score a layer (a "
         "slot's length, the token itself counted), added once a joint "
         "decode step from the lengths the engine holds: times the key's "
         "published bytes and the layers it is what the indexer has to "
         "read"),
    Rule("decode_index_positions_selected", "gauge", "serving",
         "Positions the live slots attend a layer after the indexer's "
         "selection (min(a slot's length, `index_topk`)), added once a "
         "joint decode step: over `decode_index_positions_scored` it is "
         "the share of a context a step reads K and V of"),
    Rule("decode_kv_joint_rows", "gauge", "serving",
         "1 where a position's K and V lie side by side in ONE pool row "
         "(`serving/kv_cache.py` `CacheConfig.joint`: one unquantized "
         "K/V head whose keys and values are whole lane tiles wide; the "
         "paged kernel then starts one copy a page, not two), 0 where "
         "the cache keeps two pools or a latent row; read from the "
         "cache's shape when the engine is built.  "
         "`decode_kv_pool_row_lanes` then reads keys + values"),
    Rule("decode_attn_feed_bits", "gauge", "serving",
         "Width of the K and V operands the paged attention kernel's "
         "matmuls take, read from the pools' dtype when the engine "
         "starts: 16 = bfloat16 blocks go to the MXU as they lie in the "
         "pool and the float32 query and probabilities ride as three "
         "groups of bfloat16 rows; 32 = float32 (and int8, dequantized) "
         "blocks, float32 operands on both sides"),
    Rule("decode_", "gauge", "serving",
         "Decode-engine lifecycle, paging, speculation, goodput"),
    Rule("serving_", "gauge", "serving",
         "Batching server lifecycle and queue occupancy"),
    Rule("prefill_", "gauge", "serving",
         "Chunked-prefill padding/live token accounting"),
    Rule("spec_", "gauge", "serving",
         "Speculative-decoding acceptance rates"),
    # -- disaggregated serving (serving/disagg.py) ------------------------
    Rule("migrate_", "gauge", "disagg",
         "KV-page migration traffic (pages/bytes, device vs "
         "host-bounce transport)"),
    Rule("disagg_", "gauge", "disagg",
         "Disagg router lifecycle: handoffs, re-dispatches, replica "
         "deaths, role-set sizes"),
    Rule("autoscale_", "gauge", "disagg",
         "SLO-driven re-roling: re-roles, cooldown skips, preflight "
         "failures, observed burn/queue signals"),
)

_UNIT_SUFFIXES = (
    ("_seconds_micro", "microseconds (int)"),
    ("_us_total", "microseconds (int)"),
    ("_seconds", "seconds"),
    ("_bytes", "bytes"),
    ("_ppm", "parts-per-million"),
    ("_micro", "micro-units (int, value x 1e6)"),
    ("_ms", "milliseconds"),
    ("_rps", "requests/second"),
)


def unit_of(name: str) -> str:
    """Unit of a concrete series name by suffix convention."""
    for suffix, unit in _UNIT_SUFFIXES:
        if name.endswith(suffix):
            return unit
    return "count"


def lookup(name: str) -> Optional[Rule]:
    """First rule matching ``name`` (namespace already stripped), or
    None — an undocumented series."""
    for r in RULES:
        if (name == r.prefix) if r.exact else name.startswith(r.prefix):
            return r
    return None


def catalog_markdown() -> str:
    """Deterministic METRICS.md body rendered from ``RULES``."""
    lines = [
        "# Metrics catalog",
        "",
        "Generated by `python -m paddle_tpu.observe.metrics_catalog "
        "--write` — do not edit by hand; tier-1 "
        "(`tests/test_metrics_catalog.py`) fails on drift and on any "
        "`/metrics` series without a row here.",
        "",
        "Series are exported under the `paddle_tpu_` namespace. "
        "`Match` is a name prefix unless marked `(exact)`. Units are "
        "suffix-encoded per name: `_seconds` (float, histograms), "
        "`_seconds_micro`/`_micro` (integer micro-units), `_bytes`, "
        "`_ppm` (parts-per-million), `_rps`; bare names are counts. "
        "Counters export with Prometheus type `gauge` because the "
        "registry is resettable.",
        "",
        "| Match | Type | Subsystem | Description |",
        "|---|---|---|---|",
    ]
    for r in RULES:
        match = f"`{r.prefix}`" + (" (exact)" if r.exact else "*")
        lines.append(
            f"| {match} | {r.type} | {r.subsystem} | {r.description} |")
    return "\n".join(lines) + "\n"


def write_file(path: str) -> str:
    with open(path, "w") as f:
        f.write(catalog_markdown())
    return path


def check_file(path: str) -> bool:
    """True when the checked-in catalog matches the rules."""
    try:
        with open(path) as f:
            return f.read() == catalog_markdown()
    except OSError:
        return False


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import os

    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.observe.metrics_catalog",
        description="Generate/verify METRICS.md from the catalog rules")
    p.add_argument("--write", action="store_true",
                   help="(re)write METRICS.md")
    p.add_argument("--check", action="store_true",
                   help="exit 1 when METRICS.md drifted from the rules")
    p.add_argument("--path", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "METRICS.md"))
    args = p.parse_args(argv)
    if args.write:
        print(write_file(args.path))
        return 0
    if args.check:
        if check_file(args.path):
            print("METRICS.md: up to date")
            return 0
        print("METRICS.md: DRIFTED — regenerate with --write",
              file=sys.stderr)
        return 1
    print(catalog_markdown(), end="")
    return 0


if __name__ == "__main__":  # pragma: no cover - CLI
    sys.exit(main())
