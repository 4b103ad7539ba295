"""The state-space hybrid's cell rehearsed on the CPU at a tiny size
(widths cut HERE, never in the benchmark's files; the kernels in
interpret mode): the loader resolves it, its kind runs it through the
real server with the check that holds the state's bytes, the check fails
what it must (the controls of ``mamba_controls.py``), the flops and
bytes functions agree with hand counts and every reader the cell brings
returns a value - the trace's from a synthetic parsed trace, since a CPU
run has no device plane."""
import copy
import json
import math
import os

import pytest

from benchmark import flops_mamba as fm
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe
from benchmark.tests import mamba_controls as controls
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "jamba2_3b.reason_closed_c256"
KINDS = ["recurrent", "recurrent", "attention", "recurrent"]
TINY = {"model": dict(vocab_size=97, d_model=32, layer_kinds=KINDS,
                      d_inner=128, d_state=8, d_conv=4, dt_rank=6,
                      num_heads=4, num_kv_heads=1, head_dim=8, ffn_dim=40,
                      dtype="float32"),
        "serving": dict(slots=4, max_seq_len=256, num_pages=None,
                        page_size=8, cache_dtype="float32",
                        use_pallas="always", interpret=True)}
SERVE = dict(spec_overrides={
    "traffic": {"callers": 4, "prompt_len": [8, 40], "reply_len": [4, 40],
                "pool": 8, "stagger_s": 0.3},
    "serve": {"fill_s": 0.6},
    "check": {"prompt_len": [70, 100], "new_tokens": 12, "pad": 128,
              "logit_rms_rtol": 1e-5}})
NEW = {"ssm_step_ms.serve", "ssm_step_roofline", "ssm_prefill_ms.serve",
       "ssm_prefill_roofline", "ssm_proj_ms_per_step.serve",
       "ssm_proj_roofline.serve"}
JOINED = {"slot_occupancy.serve", "engine_host_ms_per_step.serve",
          "h2d_uploads_per_step.serve", "engine_unspanned_share.serve",
          "deliver_emit_ms_per_step.serve",
          "steps_in_flight_at_dispatch.serve", "jit_step_ms.serve",
          "jit_prefill_ms.serve", "caller_itl_p99_ms.serve",
          "caller_ttft_p90_ms.serve", "full_attn_ms_per_step.serve",
          "full_attn_roofline.serve", "dense_ffn_ms_per_step.serve",
          "prefill_keys_live_share.serve",
          "prefill_tokens_per_scan_step.serve", "kv_bytes_per_token.serve",
          "setup_births_s", "setup_trace_lower_s", "setup_backend_compile_s",
          "setup_cache_load_s", "setup_cache_misses"}
# accepted metrics the issue names whose readers, unedited, read nothing
# of this configuration (PERF.md section 7 says why, one by one)
NOT_JOINED = {"state_bytes_per_slot.serve", "dense_ffn_roofline.serve",
              "prefill_device_share.serve", "conv_ms_per_step.serve"}


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(rh.TINY, "jamba2_3b", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_ssm"
    assert cell["workload"]["chips"] == 1
    names = {e["name"] for e, _, _ in cell["per_layer"]}
    assert names == NEW | JOINED and not names & NOT_JOINED
    # every metric this PR brings lists its cells, and this one alone
    assert all(e["workloads"] == [CELL] for e, _, _ in cell["per_layer"]
               if e["name"] in NEW)
    assert [e["name"] for e in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    # the traffic of the issue, letter for letter
    assert cell["spec"]["traffic"] == {
        "generator": "closed_loop", "callers": 256,
        "prompt_len": [320, 512], "reply_len": [1024, 3072], "pool": 256,
        "stagger_s": 8.0}
    assert cell["spec"]["serve"]["fill_s"] == 48.0
    assert cell["spec"]["trace_seconds"] == 4
    # one bucket: every prompt of the pool falls into 512
    lens = [p for p, _ in cell["traffic"].size_pool(cell["spec"]["traffic"])]
    assert 320 <= min(lens) and max(lens) <= 512 and len(lens) == 256
    chk = cell["spec"]["check"]
    assert (chk["requests"], chk["prompt_len"], chk["new_tokens"]) == (
        2, [480, 510], 136)
    assert chk["pad"] >= 510 + 135
    # every key as published, nothing reduced
    assert config["reduced"] == []
    m = config["model"]
    assert (m["d_model"], m["ffn_dim"], m["d_state"], m["d_conv"],
            m["dt_rank"], m["num_heads"], m["num_kv_heads"], m["rms_eps"],
            m["vocab_size"], len(m["layer_kinds"])) == (
        config["hidden_size"], config["intermediate_size"],
        config["mamba_d_state"], config["mamba_d_conv"],
        config["mamba_dt_rank"], config["num_attention_heads"],
        config["num_key_value_heads"], config["rms_norm_eps"],
        config["vocab_size"], config["num_hidden_layers"])
    assert m["d_inner"] == config["mamba_expand"] * config["hidden_size"]
    assert m["head_dim"] * m["num_heads"] == config["hidden_size"]
    # layers 7 and 21 attention, counted from 0: 13 to 1, not 7 to 1
    assert [i for i, k in enumerate(m["layer_kinds"]) if k == "attention"] \
        == [i for i in range(28) if i % config["attn_layer_period"]
            == config["attn_layer_offset"]] == [7, 21]
    assert config["num_experts"] == 1 and config["tie_word_embeddings"]
    # 2 attention layers x ONE K/V head x (128 + 128) lanes, bf16
    assert cell["model"].kv_bytes_per_token(config) == 1024
    sv = config["serving"]
    assert sv["slots"] == 256 and sv["max_seq_len"] == 4096
    assert sv["num_pages"] == sv["slots"] * (sv["max_seq_len"] // 16 + 1) \
        + 1 == 65793
    model = cell["model"].make_model(config)
    assert model.recurrent_state["ssm"][0] == (16, 5120)
    assert model.recurrent_state["conv"][0] == (3 * 5120,)
    assert model.prefill_chunks_per_call(512) == 8
    assert set(config["assumed"]) >= {
        "layer_order", "inner_norms", "dt_bias", "attention", "positions",
        "experts", "initial_distributions", "unread_keys"}
    assert config["deployment"]["chips"] == 1


def test_the_files_hold_the_catalog_entrys_numbers():
    """Every number of the published config under its own key: nothing
    is reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "AI21-Jamba2-3B")
    config = bench_run.resolve_cell(ROOT, CELL)["config"]
    assert config["source"] == entry["source_url"]
    assert not [k for k, v in entry["config"].items()
                if config.get(k, "absent") != v]
    entry_manifest = next(
        c for c in bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
        ["configs"] if c["name"] == "jamba2_3b")
    assert entry_manifest["reduced"] == [] \
        and entry_manifest["source"] == entry["source_url"]


def test_the_built_model_is_the_size_the_file_says():
    import jax

    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    model = cell["model"].make_model(config)
    shapes = jax.tree_util.tree_leaves(
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    count = lambda s: math.prod(s.shape)  # noqa: E731
    assert sum(map(count, shapes)) == config["parameters"]["built"]
    assert sum(count(s) * s.dtype.itemsize for s in shapes) \
        == config["parameters"]["bytes"]
    assert 3.02e9 < config["parameters"]["built"] < 3.04e9
    # the sizes the cell is argued from, as the files state them
    m, sv, mem = config["model"], config["serving"], config["memory"]
    assert fm.ssm_slot_bytes(16, 5120, 4) == 389120
    assert mem["state_bytes"] == sv["slots"] * 26 * 389120 == 2589982720
    assert mem["kv_pages_bytes"] == 2 * sv["num_pages"] * 16 * 256 * 2
    assert mem["weights_bytes"] == config["parameters"]["bytes"]
    assert mem["resident_bytes"] == mem["weights_bytes"] \
        + mem["kv_pages_bytes"] + mem["state_bytes"]
    assert m["layer_kinds"].count("recurrent") == 26


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert chk["worst_logit_rel_err"] < 1e-4
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    # 4 slots x 3 recurrent layers x (8 x 128 + 3 x 128) float32
    assert chk["state_bytes"] == chk["state_bytes_float32"] \
        == 4 * 3 * (8 * 128 + 3 * 128) * 4
    assert min(chk["prompt_lens"]) >= 70 and chk["positions"] == 12
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["decode_prefix_bypassed"] > 0
    # the kernels take these widths: a step counts its live rows a layer
    assert c["ssm_kernel_rows"] == 3 * (
        c["decode_tokens_total"] - c["decode_prefills"])
    assert c["decode_prefill_scan_tokens"] >= 3 * 8 * c["decode_prefills"]
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "prefill_keys_live_share.serve",
            "kv_bytes_per_token.serve", "prefill_tokens_per_scan_step.serve",
            "caller_itl_p99_ms.serve", "caller_ttft_p90_ms.serve"} <= set(got)
    # 1 attention layer x K and V of one head of 8 float32 lanes
    assert got["kv_bytes_per_token.serve"]["value"] == 2 * 8 * 4
    # prompts of 8-40 tokens in tiles of 64: all of a prompt in one
    assert 8 <= got["prefill_tokens_per_scan_step.serve"]["value"] <= 40


@pytest.mark.parametrize("name", [n for n in controls.CONTROLS
                                  if n != "served"])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        tiny, name):
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = copy.deepcopy(cell["config"])
    for key, val in rh.TINY["jamba2_3b"].items():
        config[key].update(val)
    spec = copy.deepcopy(cell["spec"])
    spec["check"].update(SERVE["spec_overrides"]["check"])
    ok, chk = controls.run_control(dict(cell, config=config, spec=spec),
                                   config, name, 3, 2)
    assert not ok
    if name == "state_in_bf16":
        # the size is a limit of its own: the matrices' half
        assert chk["state_bytes"] < chk["state_bytes_float32"]
    else:
        assert chk["state_bytes"] == chk["state_bytes_float32"]
    assert chk["worst_logit_rms_rel_err"] > 1e-4


def test_the_flops_and_bytes_functions_against_hand_counts():
    # one slot of one layer: 16 x 5,120 and three rows of 5,120, float32
    assert fm.ssm_slot_bytes(16, 5120, 4) == (81920 + 15360) * 4 == 389120
    # a step of 256 live slots, 26 layers: each state in and out
    assert fm.ssm_state_bytes(256, 26, 16, 5120, 4) \
        == 256 * 26 * 2 * 389120 == 5179965440
    # a token of a layer: 81,920 entries, six operations and one
    # exponential each
    assert fm.ssm_scan_ops(1, 16, 5120) == 491520
    assert fm.ssm_scan_exps(1, 16, 5120) == 81920
    assert fm.VECTOR_F32_OPS_PER_S == 6.144e12
    assert fm.TRANSCENDENTAL_F32_PER_S == 1.536e12
    # the vector unit binds: 0.080 us against 0.053 us a token-layer
    assert fm.ssm_scan_least_s(1, 16, 5120) == pytest.approx(
        491520 / 6.144e12) == pytest.approx(8.0e-8)
    assert 81920 / 1.536e12 == pytest.approx(5.33e-8, rel=1e-2)
    # a 416-token prompt's 26 layers: 0.87 ms
    assert fm.ssm_scan_least_s(416 * 26, 16, 5120) == pytest.approx(
        0.865e-3, rel=1e-2)
    sizes = fm.ssm_proj_params(2560, 5120, 16, 160)
    assert sizes == {"in": 26214400, "x": 983040, "dt": 819200,
                     "out": 13107200}
    # 26 layers' four projections in bf16: 2.14 GB a step
    assert 26 * sum(sizes.values()) * 2 == 2138439680


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.03, "count": 1}}},
        "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
        "config": config, "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 514,
                               "decode_prefills": 2,
                               "ssm_kernel_rows": 26 * 512,
                               "decode_prefill_keys_live": 30,
                               "decode_prefill_keys_attended": 40,
                               "decode_prefill_scan_steps": 2 * 26 * 7,
                               "decode_prefill_scan_tokens": 2 * 26 * 416},
                  "slots": 256, "page_size": 16, "kv_bytes_per_token": 1024,
                  "decode_contexts": [2000] * 512,
                  "kv_pool_positions": 65793 * 16,
                  "gauges": {"decode_kv_pool_bytes": 2 * 65793 * 16 * 512,
                             "decode_state_bytes": 2589982720},
                  "caller_ms": {"ttft_p90": 340.0, "itl_p99": 340.0}},
    }


def test_the_trace_readers_read_a_synthetic_trace(monkeypatch):
    """Two runs of ``jit_step`` and one of ``jit_prefill``; each pattern
    takes its own events and only those inside its module."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    update = ("%ssm_state_update.2 = (f32[256,5120], f32[256,16,5120]) "
              "custom-call(%f, %dt, %u, %b, %c, %a, %state_2_.1), "
              'custom_call_target="tpu_custom_call"')
    conv = ("%ssm_conv_update.3 = (f32[256,5120], f32[256,15360]) "
            "custom-call(%l, %state_3_.1, %u, %taps, %bias), "
            'custom_call_target="tpu_custom_call"')
    scan = ("%ssm_prompt_scan.8 = (f32[512,5120], f32[1,16,5120]) "
            "custom-call(%n, %dt, %x, %b, %c, %a, %s), "
            'custom_call_target="tpu_custom_call"')
    paged = ('%paged_attention.3 = f32[256,20,128] custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    w_in = ("%fusion.12 = f32[256,10240] fusion(%fusion.100, "
            "%weights__layers___0___ssm_w_in__.1), kind=kOutput")
    w_out = ("%fusion.13 = f32[256,2560] fusion(%fusion.101, "
             "%weights__layers___0___ssm_w_out__.1), kind=kOutput")
    prefetch = ("%copy-start.4 = (bf16[160,5120], bf16[160,5120], u32[]) "
                "copy-start(%weights__layers___0___ssm_w_dt__.1)")
    dense = ("%fusion.191 = f32[256,8192] fusion(%fusion.100, "
             "%weights__layers___0___ffn_w_up__.1), kind=kOutput")
    step = lambda t: [  # noqa: E731
        (t + 0.001, t + 0.002, conv), (t + 0.002, t + 0.003, w_in),
        (t + 0.003, t + 0.0032, prefetch),
        (t + 0.004, t + 0.010, update), (t + 0.010, t + 0.0105, w_out),
        (t + 0.011, t + 0.012, paged), (t + 0.012, t + 0.016, dense)]
    view = {"runs": {"jit_step": [(0.0, 0.02), (0.15, 0.17)],
                     "jit_prefill": [(0.02, 0.05)]},
            "ops": step(0.0) + [(0.03, 0.034, scan), (0.035, 0.036, w_in)]
            + step(0.15)}
    monkeypatch.setattr(hybrid_moe, "view", lambda s: view)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__ == "_bench_readers_hybrid_moe":
            monkeypatch.setitem(reader.__globals__, "view", lambda s: view)
    got = bench_run.layer_metrics(cell, _sources(cell["config"]))
    assert got["jit_step_ms.serve"]["value"] == pytest.approx(20.0)
    assert got["jit_prefill_ms.serve"]["value"] == pytest.approx(30.0)
    # both kernels that pass over the slabs
    assert got["ssm_step_ms.serve"]["value"] == pytest.approx(7.0)
    assert got["ssm_prefill_ms.serve"]["value"] == pytest.approx(4.0)
    # the matmuls and the prefetch's own event, the step's alone
    assert got["ssm_proj_ms_per_step.serve"]["value"] == pytest.approx(1.7)
    assert got["dense_ffn_ms_per_step.serve"]["value"] == pytest.approx(4.0)
    assert got["full_attn_ms_per_step.serve"]["value"] == pytest.approx(1.0)
    # 256 live slots x 26 layers x 2 x 389,120 B over 819 GB/s
    assert got["ssm_step_roofline"]["value"] == pytest.approx(
        100 * 5179965440 / 819e9 / 7e-3)
    # one prefill run in the trace, 416 tokens x 26 layers a prefill
    assert got["ssm_prefill_roofline"]["value"] == pytest.approx(
        100 * 416 * 26 * 491520 / 6.144e12 / 4e-3)
    # W_in and W_out of layer 0 are read by a matmul; W_dt is prefetched
    # (its copy's event is timed and its bytes are not counted)
    assert got["ssm_proj_roofline.serve"]["value"] == pytest.approx(
        100 * (26214400 + 13107200) * 2 / 819e9 / 1.7e-3)
    assert got["kv_bytes_per_token.serve"]["value"] == 1024
    assert got["prefill_tokens_per_scan_step.serve"]["value"] \
        == pytest.approx(416 / 7)
    assert got["prefill_keys_live_share.serve"]["value"] == 75.0
    assert all(0 < v["value"] <= 100 for k, v in got.items()
               if "roofline" in k)


def test_the_new_readers_read_nothing_where_the_program_lacks_them():
    """The parent of this PR (no such model: the cell fails before any
    reader runs) and the other cells' configurations: no reader of this
    PR raises, each returns None."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    sources = dict(_sources(cell["config"]), trace=None)
    sources["serve"] = {"slots": 256, "counters": {
        "decode_steps": 2, "decode_tokens_total": 514, "decode_prefills": 2}}
    got = bench_run.layer_metrics(cell, sources)
    assert not [k for k in got if k in NEW or "roofline" in k]
    for other in ("olmo_hybrid_7b.docqa_closed_c32",
                  "kimi_linear_48b.agent_closed_c128",
                  "gpt2_medium.chat_closed_c32"):
        config = bench_run.resolve_cell(ROOT, other)["config"]
        theirs = dict(_sources(config), trace=None)
        for _, _, reader in cell["per_layer"]:
            if reader.__module__.endswith("readers_mamba"):
                assert reader(theirs, {"pattern": "x",
                                       "module": "jit_step"}) is None
