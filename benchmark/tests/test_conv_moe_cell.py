"""The convolution/attention cell rehearsed on the CPU at a tiny size
(widths cut HERE, never in the benchmark's files): the loader resolves
it, its kind runs it through the real server, the check fails what it
must (controls of ``conv_moe_controls.py``, tails of another size), the
operation and byte counts agree with hand counts and every reader the
cell brings returns a value - the trace's from a recorded fragment of
the chip's own events, since a CPU run has no device plane - and None
where the program has nothing to read."""
import copy
import json
import math
import os

import pytest

from benchmark import flops_conv_moe as fc
from benchmark import run as bench_run
from benchmark.readers import conv_moe, hybrid_moe
from benchmark.tests import conv_moe_controls as controls
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "lfm2_8b_a1b.extract_closed_c128"
KINDS = ["recurrent", "recurrent", "attention", "recurrent"]
TINY = {"model": dict(vocab_size=97, d_model=32, layer_kinds=KINDS,
                      num_heads=4, num_kv_heads=2, head_dim=8,
                      ffn_dim=48, dense_layers=1, num_experts=8, top_k=2,
                      held_experts=[0, 8], expert_dim=16, dtype="float32",
                      max_seq_len=4096),
        "serving": dict(slots=4, max_seq_len=64, num_pages=None,
                        page_size=8, cache_dtype="float32")}
SERVE = dict(spec_overrides={
    "traffic": {"callers": 4, "prompt_len": [8, 30], "reply_len": [4, 30],
                "pool": 8, "stagger_s": 0.3},
    "serve": {"fill_s": 0.6},
    "check": {"prompt_len": [20, 30], "new_tokens": 8, "pad": 48,
              "logit_rms_rtol": 1e-5, "route_eps": 1e-6,
              "reroute_share": 0.0}})
NEW = {"conv_ms_per_step.serve", "dense_ffn_step_ms.serve",
       "dense_ffn_prompt_ms.serve",
       "conv_prompt_ms.serve", "moe_prefill_ms.serve",
       "moe_prefill_roofline", "moe_pairs_per_row.serve",
       "prompt_attn_ms.serve", "prefill_device_share.serve"}
JOINED = {"jit_step_ms.serve", "jit_prefill_ms.serve",
          "moe_ffn_ms_per_step.serve", "moe_experts_roofline",
          "routed_experts_hit_share.serve", "full_attn_ms_per_step.serve",
          "full_attn_roofline.serve",
          "prefill_keys_live_share.serve", "kv_bytes_per_token.serve",
          "slot_occupancy.serve", "engine_host_ms_per_step.serve",
          "h2d_uploads_per_step.serve", "engine_unspanned_share.serve",
          "deliver_emit_ms_per_step.serve",
          "steps_in_flight_at_dispatch.serve", "caller_itl_p99_ms.serve",
          "caller_ttft_p90_ms.serve", "setup_births_s",
          "setup_trace_lower_s", "setup_backend_compile_s",
          "setup_cache_load_s", "setup_cache_misses"}


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(rh.TINY, "lfm2_8b_a1b", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_conv_moe"
    assert cell["workload"]["chips"] == 1
    assert {e["name"] for e, _, _ in cell["per_layer"]} == NEW | JOINED
    assert [e["name"] for e in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    # depth alone is cut: every width, expert and vocabulary row stays
    assert config["reduced"] == ["num_hidden_layers"] \
        == cell["config_entry"]["reduced"]
    assert config["published"]["num_hidden_layers"] == 24
    assert config["layer_types"] == config["published"]["layer_types"][:12]
    m = config["model"]
    assert (m["vocab_size"], m["d_model"], m["num_heads"],
            m["num_kv_heads"], m["conv_kernel"], m["ffn_dim"],
            m["dense_layers"], m["num_experts"], m["top_k"],
            m["expert_dim"], m["rope_theta"], m["rms_eps"],
            len(m["layer_kinds"]), m["max_seq_len"]) == (
        config["vocab_size"], config["hidden_size"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["conv_L_cache"], config["intermediate_size"],
        config["num_dense_layers"], config["num_experts"],
        config["num_experts_per_tok"], config["moe_intermediate_size"],
        config["rope_theta"], config["norm_eps"],
        config["num_hidden_layers"], config["max_position_embeddings"])
    assert m["head_dim"] * m["num_heads"] == m["d_model"]
    assert m["held_experts"] == [0, m["num_experts"]]
    assert m["layer_kinds"] == [
        "attention" if k == "full_attention" else "recurrent"
        for k in config["layer_types"]]
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    model = cell["model"].make_model(config)
    assert model.held_experts == tuple(range(32)) and model.tie_head
    # 3 attention layers x (K, V) x 8 heads x 64 lanes x 2 B
    assert cell["model"].kv_bytes_per_token(config) == 6144 \
        == fc.kv_bytes_per_token(3, 8, 64, "bfloat16")
    sv, t = config["serving"], cell["spec"]["traffic"]
    assert sv["num_pages"] == sv["slots"] * 161 + 1 == 20609
    assert sv["max_seq_len"] == t["prompt_len"][1] + t["reply_len"][1]
    mem = config["memory"]
    assert mem["kv_pages_bytes"] == 20609 * 16 * 6144 == 2025947136
    assert mem["conv_tail_bytes"] == 18874368 \
        == fc.conv_tail_bytes(128, 9, 3, 2048)
    assert mem["resident_bytes"] == mem["weights_bytes"] \
        + mem["kv_pages_bytes"] + mem["conv_tail_bytes"]
    # the traffic of the issue, letter for letter
    assert (t["callers"], t["prompt_len"], t["reply_len"], t["pool"],
            t["stagger_s"], cell["spec"]["serve"]["fill_s"],
            cell["spec"]["trace_seconds"]) == (
        128, [1024, 2048], [128, 512], 128, 8.0, 24.0, 4)
    # every prompt of the pool falls in the ONE bucket of 2,048
    from paddle_tpu.serving.buckets import prefill_bucket_grid

    grid = prefill_bucket_grid(sv["max_seq_len"], 16)
    sizes = cell["traffic"].size_pool(t)
    assert {next(b for b in grid if b >= p) for p, _ in sizes} == {2048}
    chk = cell["spec"]["check"]
    assert chk["requests"] == 2 and chk["new_tokens"] == 8
    assert chk["prompt_len"] == [1536, 2048]
    assert chk["pad"] >= 2048 + 8 - 1


def test_the_files_hold_the_catalog_entrys_numbers():
    """Every number of the published config under its own key, but the
    depth (and the list that follows it)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "LFM2-8B-A1B")
    config = bench_run.resolve_cell(ROOT, CELL)["config"]
    assert config["source"] == entry["source_url"]
    assert sorted(k for k, v in entry["config"].items()
                  if config.get(k) != v) == ["layer_types",
                                             "num_hidden_layers"]
    assert config["published"]["layer_types"] \
        == entry["config"]["layer_types"]


def test_the_built_model_is_the_size_the_file_says():
    import jax

    cell = bench_run.resolve_cell(ROOT, CELL)
    model = cell["model"].make_model(cell["config"])
    shapes = jax.tree_util.tree_leaves(
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    count = lambda s: math.prod(s.shape)  # noqa: E731
    assert sum(map(count, shapes)) == 3928728256 \
        == cell["config"]["parameters"]["built"]
    assert sum(count(s) * s.dtype.itemsize for s in shapes) \
        == cell["config"]["parameters"]["bytes"] \
        == cell["config"]["memory"]["weights_bytes"]
    # the issue's arithmetic, a piece at a time
    conv, attn = 4 * 2048 ** 2 + 3 * 2048, 2048 * 5120 + 128
    dense, experts = 3 * 2048 * 7168, 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert 9 * conv + 3 * attn + 12 * 4096 + 2 * dense + 10 * experts \
        + 65536 * 2048 + 2048 == 3928728256


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    assert chk["worst_route_gap"] == 0 and chk["rerouted_share"] == 0
    # 3 convolution layers x 4 slots x 2 rows x 32 lanes x 4 B
    assert chk["state_bytes"] == chk["state_bytes_float32"] == 3072
    assert min(chk["prompt_lens"]) >= 20 and chk["positions"] == 8
    assert result["failed"] == 0 and result["attempted"] > 5
    serve = result["sources"]["serve"]
    c = serve["counters"]
    stepped = c["decode_tokens_total"] - c["decode_prefills"]
    # every expert held, top-2, three expert layers
    assert c["moe_local_assignments"] == 2 * stepped * 3
    # an admission and its prefill may fall on two sides of an edge
    assert abs(c["decode_prefix_bypassed"] - c["decode_prefills"]) <= 4
    assert c["decode_prefill_scan_steps"] == 3 * c["decode_prefills"]
    assert c["decode_prefill_conv_rows"] == c["decode_prefill_scan_tokens"]
    assert c["moe_grouped_extra_passes"] == 0
    assert serve["gauges"] == {
        "decode_kv_pool_bytes": 33 * 8 * 2 * 2 * 8 * 4}
    assert serve["kv_pool_positions"] == 33 * 8
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "moe_pairs_per_row.serve",
            "kv_bytes_per_token.serve", "routed_experts_hit_share.serve",
            "prefill_keys_live_share.serve", "caller_itl_p99_ms.serve",
            "caller_ttft_p90_ms.serve"} <= set(got)
    assert got["moe_pairs_per_row.serve"]["value"] == 2.0
    assert got["kv_bytes_per_token.serve"]["value"] == 2 * 2 * 8 * 4
    assert 0 < got["routed_experts_hit_share.serve"]["value"] <= 100


def _served_model(monkeypatch, change_model=None, change_weights=None):
    """The kind run with the SERVED model or weights changed (the
    reference keeps the configuration's)."""
    real_resolve = bench_run.resolve_cell

    def resolve(root, name):
        c = real_resolve(root, name)
        build, ref_logits = c["model"].build, c["model"].reference_logits
        kept = {}

        def changed(config, seed):
            model, weights = build(config, seed)
            kept["weights"] = weights
            if change_model:
                change_model(model)
            return model, change_weights(weights) if change_weights \
                else weights

        c["model"].build = changed
        c["model"].reference_logits = lambda config, weights, *a, **kw: \
            ref_logits(config, kept["weights"], *a, **kw)
        return c

    monkeypatch.setattr(rh.bench_run, "resolve_cell", resolve)


def test_the_controls_are_the_issues_eleven_the_contracts_and_the_served():
    assert list(controls.CONTROLS) == [
        "served", "tail_zeroed_at_every_step", "taps_reversed",
        "no_b_gate", "no_c_gate", "bias_in_the_weights_too",
        "weights_not_renormalised", "top_3", "no_qk_norm", "rope_base_1e4",
        "bf16_tail", "all_bf16", "reference_in_bf16"]


@pytest.mark.parametrize("name", [
    "tail_zeroed_at_every_step", "taps_reversed", "no_b_gate", "no_c_gate",
    "bias_in_the_weights_too", "weights_not_renormalised", "top_3",
    "no_qk_norm", "rope_base_1e4"])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        tiny, monkeypatch, name):
    change_model, change_weights, patch = controls.CONTROLS[name]
    _served_model(monkeypatch, change_model, change_weights)
    undo = patch() if patch else None
    try:
        _, result = rehearse(CELL, 0.3, **SERVE)
    finally:
        if undo:
            undo()
    chk = result["checks"]
    assert not result["correct"]
    if name == "top_3":
        assert chk["worst_route_gap"] == 1.0
    else:
        assert chk["worst_logit_rms_rel_err"] > 1e-3
    # the tails keep their size: only logits and routing tell these
    assert chk["state_bytes"] == chk["state_bytes_float32"]


def test_the_check_fails_a_bfloat16_tail_by_its_bytes(tiny, monkeypatch):
    change_model, _, _ = controls.CONTROLS["bf16_tail"]
    _served_model(monkeypatch, change_model)
    _, result = rehearse(CELL, 0.3, **SERVE)
    chk = result["checks"]
    assert not result["correct"]
    assert chk["state_bytes"] * 2 == chk["state_bytes_float32"]


@pytest.mark.parametrize("most, correct", [(2.0, True), (0.2, False),
                                           (None, True)])
def test_the_check_holds_the_error_to_the_stated_precisions(
        tiny, monkeypatch, most, correct):
    """A bfloat16 model: the served RMS error over that of the reference
    with bfloat16 operands is formed (near 1 at real widths: the workload
    file; anywhere between 0.5 and 2 at 32 lanes) and held to
    ``rms_over_stated_max`` where the file gives one."""
    rh.TINY["lfm2_8b_a1b"]["model"]["dtype"] = "bfloat16"
    rh.TINY["lfm2_8b_a1b"]["serving"]["cache_dtype"] = "bfloat16"
    spec = copy.deepcopy(SERVE["spec_overrides"])
    spec["check"].update(logit_rms_rtol=1.0, route_eps=1.0,
                         reroute_share=1.0, rms_over_stated_max=most)
    _, result = rehearse(CELL, 0.3, spec_overrides=spec)
    chk = result["checks"]
    assert result["correct"] is correct, chk
    assert chk["stated_logit_rms_rel_err"] > 1e-3
    assert 0.5 < chk["logit_rms_over_stated"] < 2.0
    assert chk["logit_rms_over_stated"] == pytest.approx(
        chk["worst_logit_rms_rel_err"] / chk["stated_logit_rms_rel_err"])
    assert chk["rms_over_stated_max"] == most


def test_the_counts_against_hand_counts():
    assert fc.kv_bytes_per_token(3, 8, 64, "bfloat16") == 6144
    assert fc.kv_bytes_per_token(3, 8, 64) == 12288
    assert fc.conv_tail_bytes(128, 9, 3, 2048) == 18874368
    # a pair: three products of 2,048 x 1,792, two operations a
    # multiply-add = 22.0 MFLOP; a prompt of 2,048 rows x 4 pairs x 10
    # layers 1.80 TFLOP, 9.2 ms at 197 TFLOP/s
    assert fc.grouped_pair_flops(1, 2048, 1792) == 22020096
    assert fc.grouped_pair_flops(2048 * 4 * 10, 2048, 1792) \
        == 1803886264320


# a step's and a prefill's events as the chip named them (my chip run,
# PR 54; operands and layouts shortened).  In the step: the one
# in-projection that reads W_in from HBM and one that reads a copy the
# compiler prefetched (its asynchronous slice names the weight, takes no
# time of its own and computes nothing), the taps, a slab's copy, the
# tails' shift, and what is NOT the convolution's: an expert's gate
# matmul, the paged kernel, an out-projection fused with the residual
# add over a prefetched copy; a dense layer's gate-and-up and down
# matmuls over prefetched copies (and one prefetch's start, which names
# the weight).  In the prefill: the dense layer's three matmuls, the
# in-projection, the engine's one-iteration loop that carries a slot's tail (its body
# nested in it), the out-projection over the loop's result, the loop
# over the grouped experts' passes with the two kernels inside, and the
# blocked attention's three fusions over the plane of float32 scores.
_IN_HBM = ("%fusion.228 = f32[128,6144]{1,0:T(8,128)S(1)} fusion("
           "bf16[2048,6144]{1,0:T(8,128)(2,1)} "
           "%weights__layers___1___conv_w_in__.1, f32[128,2048]{1,0} "
           "%get-tuple-element.190), kind=kOutput, calls=%fused_computation")
_IN_COPY = ("%fusion.229 = f32[128,6144]{1,0:T(8,128)S(1)} fusion("
            "bf16[2048,6144]{1,0:T(8,128)(2,1)S(1)} %custom-call.57, "
            "f32[128,2048]{1,0} %get-tuple-element.200), kind=kOutput")
_PREFETCH = ("%slice-start.24 = ((bf16[2048,6144]{1,0}), bf16[512,6144]"
             "{1,0:S(1)}, s32[]{:S(2)}) async-start(bf16[2048,6144]{1,0} "
             "%weights__layers___0___conv_w_in__.1), calls=%async_comp.24")
_TAPS = ("%fusion.466 = (f32[1,2048]{1,0}, f32[1,2048]{1,0}, f32[1,2048]"
         "{1,0}) fusion(f32[3,2048]{1,0:T(4,128)} "
         "%weights__layers___0___conv_taps__.1), kind=kLoop")
_SLAB = ("%copy.602 = f32[128,2,2048]{2,1,0:T(2,128)S(1)} copy("
         "f32[128,2,2048]{2,1,0:T(2,128)} %state_10_.1)")
_SHIFT = ("%fusion.96 = (f32[128,2,2048]{2,1,0:T(2,128)}, f32[128,2,2048]"
          "{2,1,0:T(2,128)}) fusion(f32[128,2,2048]{2,1,0:T(2,128)S(1)} "
          "%custom-call.64, f32[128,1,2048]{2,1,0} %copy.324, pred[128]{0} "
          "%copy-done.36), kind=kLoop")
_MOE_GATE = ("%fusion.224 = f32[128,57344]{1,0} fusion(bf16[2048,57344]"
             "{1,0} %weights__layers___2___moe_w_gate__.1, f32[128,2048]"
             "{1,0} %get-tuple-element.196), kind=kOutput")
_PAGED = ("%paged_attention.3 = f32[128,4,512]{2,1,0} custom-call(s32[1]{0}"
          " %bitcast.217, bf16[3,20609,16,512]{3,2,1,0} %state_0_.1), "
          'custom_call_target="tpu_custom_call"')
_OUT = ("%multiply_reduce_fusion.5 = (f32[128]{0}, f32[128,2048]{1,0}) "
        "fusion(f32[128,2048]{1,0} %get-tuple-element.190, bf16[2048,2048]"
        "{1,0:S(1)} %custom-call.70, f32[128,2048]{1,0} %fusion.300), "
        "kind=kOutput")
_FFN_PREFETCH = ("%slice-start.8 = ((bf16[2048,7168]{1,0}), bf16[512,7168]"
                 "{1,0:S(1)}, s32[]{:S(2)}) async-start(bf16[2048,7168]{1,0} "
                 "%weights__layers___0___ffn_w_up__.1), calls=%async_comp.8")
_FFN_UP = ("%fusion.226 = f32[128,7168]{1,0:T(8,128)S(1)} fusion("
           "bf16[2048,7168]{1,0:S(1)} %copy-done, f32[128,2048]{1,0} "
           "%get-tuple-element.188), kind=kOutput, calls=%fused_computation")
_FFN_DOWN = ("%multiply_reduce_fusion.25 = (f32[128]{0}, f32[128,2048]{1,0})"
             " fusion(f32[128,2048]{1,0} %get-tuple-element.188, "
             "bf16[7168,2048]{1,0:S(1)} %custom-call.51, bf16[2048,7168]"
             "{1,0:S(1)} %custom-call.52, f32[128,7168]{1,0} %fusion.226), "
             "kind=kOutput")
_P_FFN_GATE = ("%fusion.649 = f32[2048,7168]{1,0:T(8,128)} fusion("
               "bf16[2048,7168]{1,0:S(1)} %copy-done, f32[2048,2048]{1,0} "
               "%get-tuple-element.2641), kind=kOutput")
_P_FFN_UP = ("%fusion.559 = bf16[2048,7168]{1,0} fusion(bf16[2048,7168]"
             "{1,0:S(1)} %custom-call.315, f32[2048,7168]{1,0} %fusion.649),"
             " kind=kOutput")
_P_FFN_DOWN = ("%multiply_reduce_fusion.25 = (f32[2048]{0}, f32[2048,2048]"
               "{1,0}) fusion(f32[2048,2048]{1,0} %copy-done.181, "
               "bf16[2048,7168]{1,0} %fusion.559, bf16[7168,2048]{1,0:S(1)} "
               "%custom-call.316), kind=kOutput")
_P_IN = ("%fusion.600 = f32[2048,6144]{1,0:T(8,128)S(1)} fusion("
         "bf16[2048,6144]{1,0} %weights__layers___0___conv_w_in__.1, "
         "bf16[2048,2048]{1,0:S(1)} %fusion.60), kind=kOutput")
_P_LOOP = ("%while.158 = (s32[]{:T(128)}, f32[1,2,2048]{2,1,0:T(2,128)S(1)},"
           " bf16[2048,2048]{1,0:T(8,128)(2,1)}, s32[]{:T(128)}, "
           "f32[2048,6144]{1,0}) while(%tuple.1), condition=%c, body=%b")
_P_BODY = ("%fusion.700 = bf16[2048,2048]{1,0} fusion(f32[2048,6144]{1,0} "
           "%get-tuple-element.9, f32[3,2048]{1,0} %get-tuple-element.10), "
           "kind=kLoop")
_P_OUT = ("%multiply_reduce_fusion.26 = (f32[2048]{0}, f32[2048,2048]{1,0})"
          " fusion(f32[2048,2048]{1,0} %get-tuple-element.2219, "
          "bf16[2048,2048]{1,0:T(8,128)(2,1)} %while.77, bf16[2048,2048]"
          "{1,0:S(1)} %custom-call.324), kind=kOutput")
_P_PASSES = ("%while.169 = (s32[]{:T(128)}, f32[2048,2048]{1,0:T(8,128)"
             "S(1)}, s32[]{:T(128)}, s32[32]{0}) while(%tuple.9), "
             "condition=%c, body=%b")
_P_GATE_UP = ("%moe_grouped_gate_up.3 = bf16[16384,1792]{1,0} custom-call("
              "s32[64]{0} %a, bf16[16384,2048]{1,0} %b), "
              'custom_call_target="tpu_custom_call"')
_P_DOWN = ("%moe_grouped_down.3 = f32[2048,2048]{1,0} custom-call(s32[64]"
           '{0} %a, bf16[16384,1792]{1,0} %b), custom_call_target='
           '"tpu_custom_call"')
_P_SCORES = ("%fusion.739 = (f32[8,4,2048]{2,1,0}, f32[8,4,2048,2048]"
             "{2,3,1,0:T(8,128)}) fusion(bf16[2048,8,4,64]{0,3,2,1} "
             "%bitcast.1805, pred[2048,2048]{0,1} %custom-call.307), "
             "kind=kOutput")
_P_SUMS = ("%fusion.551 = f32[8,4,2048]{2,1,0} fusion(f32[8,4,2048,2048]"
           "{2,3,1,0:T(8,128)} %get-tuple-element.2685, f32[8,4,2048]{2,1,0}"
           " %get-tuple-element.2684), kind=kLoop")
_P_PV = ("%fusion.563 = bf16[8,64,4,2048]{3,1,2,0} fusion(bf16[2048,8,64,1]"
         "{0,2,3,1} %bitcast.1859, f32[8,4,2048,2048]{2,3,1,0:T(8,128)} "
         "%get-tuple-element.2649), kind=kOutput")


def _view():
    """Two runs of ``jit_step`` around one of ``jit_prefill``, times by
    hand (seconds)."""
    ops = []
    for t0 in (0.0, 0.06):
        ops += [(t0, t0, _PREFETCH),
                (t0 + 0.0000, t0 + 0.0001, _TAPS),
                (t0 + 0.0001, t0 + 0.0002, _SLAB),
                (t0 + 0.0002, t0 + 0.0006, _IN_HBM),
                (t0 + 0.0006, t0 + 0.0008, _IN_COPY),
                (t0 + 0.0008, t0 + 0.0009, _SHIFT),
                (t0 + 0.0009, t0 + 0.0012, _OUT),
                (t0 + 0.0012, t0 + 0.0012, _FFN_PREFETCH),
                (t0 + 0.0013, t0 + 0.00132, _FFN_UP),
                (t0 + 0.0014, t0 + 0.00144, _FFN_DOWN),
                (t0 + 0.0020, t0 + 0.0115, _MOE_GATE),
                (t0 + 0.0115, t0 + 0.0135, _PAGED)]
    p0 = 0.02
    ops += [(p0, p0 + 0.0003, _P_IN),
            (p0 + 0.0003, p0 + 0.0005, _P_LOOP),
            (p0 + 0.0003, p0 + 0.0005, _P_BODY),
            (p0 + 0.0005, p0 + 0.0006, _P_OUT),
            (p0 + 0.0006, p0 + 0.0006, _FFN_PREFETCH),
            (p0 + 0.0006, p0 + 0.0009, _P_FFN_GATE),
            (p0 + 0.0040, p0 + 0.00435, _P_FFN_UP),
            (p0 + 0.0202, p0 + 0.02055, _P_FFN_DOWN),
            (p0 + 0.0010, p0 + 0.0018, _P_SCORES),
            (p0 + 0.0018, p0 + 0.0025, _P_SUMS),
            (p0 + 0.0025, p0 + 0.0032, _P_PV),
            (p0 + 0.0040, p0 + 0.0202, _P_PASSES),
            (p0 + 0.0041, p0 + 0.0141, _P_GATE_UP),
            (p0 + 0.0141, p0 + 0.0201, _P_DOWN)]
    ops.sort(key=lambda e: (e[0], -e[1]))
    return {"runs": {"jit_step": [(0.0, 0.014), (0.06, 0.074)],
                     "jit_prefill": [(0.02, 0.06)]}, "ops": ops}


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.028, "count": 2},
                              "jit_prefill": {"total_s": 0.04, "count": 1}}},
        "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
        "config": config, "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 257,
                               "decode_prefills": 1,
                               "moe_local_assignments": 4 * 256 * 10,
                               "moe_experts_hit": 640,
                               "moe_grouped_pairs": 4 * 1800 * 10,
                               "decode_prefill_keys_live": 3,
                               "decode_prefill_keys_attended": 12},
                  "slots": 128, "page_size": 16,
                  "kv_bytes_per_token": 6144,
                  "decode_contexts": [1600] * 256,
                  "kv_pool_positions": 20609 * 16,
                  "gauges": {"decode_kv_pool_bytes": 2025947136},
                  "caller_ms": {"ttft_p90": 85.0, "itl_p99": 112.0}},
    }


def _with_view(monkeypatch, cell, view):
    """Every copy of ``readers/hybrid_moe.py`` the readers reach (the
    package's own, and those the loader made from the files) sees
    ``view``."""
    mods = {id(hybrid_moe.__dict__): hybrid_moe.__dict__}
    for _, _, reader in cell["per_layer"]:
        g = reader.__globals__
        for mod in (g, getattr(g.get("hybrid_moe"), "__dict__", None)):
            if mod and "ops_in_runs" in mod and "view" in mod:
                mods[id(mod)] = mod
    for mod in mods.values():
        monkeypatch.setitem(mod, "view", lambda s: view)


def test_the_trace_readers_read_a_recorded_fragment(monkeypatch):
    cell = bench_run.resolve_cell(ROOT, CELL)
    _with_view(monkeypatch, cell, _view())
    got = {k: v["value"] for k, v in bench_run.layer_metrics(
        cell, _sources(cell["config"])).items()}
    assert set(got) >= NEW
    assert got["jit_step_ms.serve"] == pytest.approx(14.0)
    assert got["jit_prefill_ms.serve"] == pytest.approx(40.0)
    assert got["prefill_device_share.serve"] == pytest.approx(
        100 * 0.04 / 0.068)
    # taps, slab copy, both in-projections, the shift: not the fused
    # out-projection, not the experts, not the kernel
    assert got["conv_ms_per_step.serve"] == pytest.approx(0.9)
    # the gate-and-up matmul over a prefetched copy 0.02 and the down
    # matmul 0.04, a step; 0.3 + 0.35 + 0.35 a prompt: the prefetches'
    # own events, which name the weights, are not in it
    assert got["dense_ffn_step_ms.serve"] == pytest.approx(0.06)
    assert got["dense_ffn_prompt_ms.serve"] == pytest.approx(1.0)
    # in-projection 0.3, the loop 0.2 (its body is inside it), the
    # out-projection 0.1
    assert got["conv_prompt_ms.serve"] == pytest.approx(0.6)
    assert got["prompt_attn_ms.serve"] == pytest.approx(2.2)
    assert got["moe_prefill_ms.serve"] == pytest.approx(16.0)
    # 1,800 real rows x 4 pairs x 10 layers x 22.0 MFLOP = 1.585 TFLOP
    assert got["moe_prefill_roofline"] == pytest.approx(
        100 * (72000 * 22020096 / 197e12) / 16e-3)
    assert got["moe_pairs_per_row.serve"] == 4.0
    assert got["moe_ffn_ms_per_step.serve"] == pytest.approx(9.5)
    # 320 experts hit a step x 3 x 2,048 x 1,792 x 2 B = 7.05 GB
    assert got["moe_experts_roofline"] == pytest.approx(
        100 * (320 * 22020096 / 819e9) / 9.5e-3)
    assert got["full_attn_ms_per_step.serve"] == pytest.approx(2.0)
    assert got["kv_bytes_per_token.serve"] == 6144
    assert got["routed_experts_hit_share.serve"] == 100.0
    assert got["prefill_keys_live_share.serve"] == 25.0
    for name, value in got.items():
        if name.endswith("_roofline") or "_roofline." in name:
            assert 0 < value <= 100, (name, value)


def test_the_new_readers_read_nothing_where_the_program_lacks_them(
        monkeypatch):
    """The parent of this PR: no such counters or gauges, a trace without
    such operations; a run without a trace; another configuration's
    sizes."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    bare = dict(_sources(cell["config"]), trace=None)
    bare["serve"] = {"slots": 128, "counters": {
        "decode_steps": 2, "decode_tokens_total": 257,
        "decode_prefills": 1}}
    got = bench_run.layer_metrics(cell, bare)
    assert not NEW & set(got) and "kv_bytes_per_token.serve" not in got
    # a trace whose programs have none of these operations
    view = _view()
    view["ops"] = [e for e in view["ops"] if e[2] in (_MOE_GATE, _PAGED)]
    _with_view(monkeypatch, cell, view)
    got = bench_run.layer_metrics(cell, dict(
        _sources(cell["config"]), serve=bare["serve"]))
    assert NEW & set(got) == {"prefill_device_share.serve"}
    solar = bench_run.resolve_cell(
        ROOT, "solar_open2_250b.chat_closed_c128")["config"]
    other = _sources(solar)
    for name, params in (
            ("dense_ffn_ms", {"module": "jit_step"}),
            ("moe_prefill_roofline", {"pattern": "x",
                                      "module": "jit_prefill"}),
            ("moe_pairs_per_row", {}),
            ("prefill_device_share", {"prefill": "jit_prefill",
                                      "step": "jit_step"})):
        assert getattr(conv_moe, name)(other, params) is None
