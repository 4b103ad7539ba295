"""The dense hybrid cell rehearsed on the CPU at a tiny size (widths cut
HERE, never in the benchmark's files): the loader resolves it, its kind
runs it through the real server with the plain check, the check fails
what it must (the controls of ``gated_delta_controls.py``), the flops
and bytes functions agree with hand counts and every reader the cell
brings returns a value - the trace's from a synthetic parsed trace,
since a CPU run has no device plane."""
import copy
import json
import os

import pytest

from benchmark import flops_gated_delta as fg
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe
from benchmark.tests import gated_delta_controls as controls
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "olmo_hybrid_7b.docqa_closed_c32"
KINDS = ["recurrent", "recurrent", "recurrent", "attention"]
TINY = {"model": dict(vocab_size=97, d_model=32, layer_kinds=KINDS,
                      num_heads=3, head_dim=8, lin_heads=3, lin_key_dim=6,
                      lin_value_dim=12, conv_kernel=4, ffn_dim=40,
                      dtype="float32"),
        "serving": dict(slots=4, max_seq_len=256, num_pages=None,
                        page_size=8, cache_dtype="float32",
                        use_pallas="always", interpret=True)}
SERVE = dict(spec_overrides={
    "traffic": {"callers": 4, "prompt_len": [8, 150], "reply_len": [4, 40],
                "pool": 8, "stagger_s": 0.3},
    "serve": {"fill_s": 0.6},
    "check": {"prompt_len": [70, 150], "new_tokens": 12, "pad": 168,
              "logit_rms_rtol": 2e-5}})


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(rh.TINY, "olmo_hybrid_7b", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_recurrent"
    names = {e["name"] for e, _, _ in cell["per_layer"]}
    assert {"gdn_ms_per_step.serve", "gdn_state_roofline",
            "gdn_prefill_ms.serve", "gdn_prefill_roofline",
            "dense_ffn_ms_per_step.serve", "dense_ffn_roofline.serve",
            "prefill_tokens_per_scan_step.serve",
            "full_attn_ms_per_step.serve", "full_attn_roofline.serve",
            "prefill_keys_live_share.serve", "jit_step_ms.serve",
            "jit_prefill_ms.serve", "slot_occupancy.serve",
            "caller_itl_p99_ms.serve"} <= names
    # what reads another model's layers, and what reads null since the
    # step ahead, stays off the cell
    assert not {"kda_state_roofline", "moe_experts_roofline",
                "window_attn_roofline.serve", "decode_attn_roofline",
                "idle_under_sync_ms_per_step.serve",
                "clock_align_slack_ms.serve"} & names
    assert config["reduced"] == ["num_hidden_layers"]
    m = config["model"]
    assert (m["vocab_size"], m["d_model"], m["num_heads"], m["lin_heads"],
            m["lin_key_dim"], m["lin_value_dim"], m["conv_kernel"],
            m["ffn_dim"], m["rms_eps"]) == (
        config["vocab_size"], config["hidden_size"],
        config["num_attention_heads"], config["linear_num_key_heads"],
        config["linear_key_head_dim"], config["linear_value_head_dim"],
        config["linear_conv_kernel_dim"], config["intermediate_size"],
        config["rms_norm_eps"])
    assert config["num_key_value_heads"] == config["num_attention_heads"] \
        == config["linear_num_value_heads"] == 30
    assert m["head_dim"] * m["num_heads"] == m["d_model"]
    n = config["num_hidden_layers"]
    assert len(m["layer_kinds"]) == n == 8 \
        == config["published"]["num_hidden_layers"] \
        // config["deployment"]["chips"]
    assert m["layer_kinds"] == [
        "recurrent" if k == "linear_attention" else "attention"
        for k in config["layer_types"][:n]]
    assert config["linear_allow_neg_eigval"] \
        and not config["tie_word_embeddings"] \
        and config["rope_parameters"] == {"rope_theta": None}
    # 2 full layers x 30 heads x (128 + 128) lanes, bf16
    assert cell["model"].kv_bytes_per_token(config) == 30720
    sv, tr = config["serving"], cell["spec"]["traffic"]
    assert sv["num_pages"] == sv["slots"] * 353 + 1
    assert sv["slots"] == tr["callers"] == tr["pool"] == 32
    assert sv["max_seq_len"] == 5632 == tr["prompt_len"][1] \
        + tr["reply_len"][1]
    # the memory the file reckons is what the shapes give
    mem = config["memory"]
    assert mem["kv_pages_bytes"] == 11297 * 16 * 30720
    assert mem["state_bytes"] == 32 * 6 * (30 * 96 * 192 + 3 * 11520) * 4
    assert mem["weights_bytes"] == config["parameters"]["bytes"]
    assert mem["resident_bytes"] == mem["weights_bytes"] \
        + mem["kv_pages_bytes"] + mem["state_bytes"]
    assert 0.65 < mem["resident_bytes"] / 16e9 < 0.7
    # a prefill, then at least 128 decode steps; all inside the pad
    chk = cell["spec"]["check"]
    assert chk["new_tokens"] - 1 >= 128
    assert chk["prompt_len"][1] + chk["new_tokens"] - 1 <= chk["pad"]
    assert tr["prompt_len"][0] <= chk["prompt_len"][0] \
        and chk["prompt_len"][1] <= tr["prompt_len"][1]


def test_the_files_hold_the_catalog_entrys_numbers():
    """Every number of the published config under its own key, but for
    the one that ``reduced`` names."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Olmo-Hybrid-7B")
    config = bench_run.resolve_cell(ROOT, CELL)["config"]
    assert config["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"])


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert 0 < chk["worst_logit_rms_rel_err"] < 2e-5
    # 4 slots x 3 layers x (3 x 6 x 12 + 3 x 72) x 4 B
    assert chk["state_bytes"] == chk["state_bytes_float32"] \
        == 4 * 3 * (216 + 216) * 4
    assert min(chk["prompt_lens"]) >= 70 and chk["positions"] == 12
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["decode_prefix_bypassed"] > 0
    assert 0 < c["decode_prefill_scan_steps"] \
        < c["decode_prefill_scan_tokens"]
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "prefill_tokens_per_scan_step.serve",
            "prefill_keys_live_share.serve", "caller_itl_p99_ms.serve",
            "caller_ttft_p90_ms.serve"} <= set(got)
    assert 1 < got["prefill_tokens_per_scan_step.serve"]["value"] <= 64


def _served_model(monkeypatch, change):
    """The kind run with the SERVED model changed (the reference keeps
    the configuration's)."""
    real_resolve = bench_run.resolve_cell

    def resolve(root, name):
        c = real_resolve(root, name)
        make = c["model"].make_model

        def changed(config):
            model = make(config)
            change(model)
            return model

        c["model"].make_model = changed
        return c

    monkeypatch.setattr(rh.bench_run, "resolve_cell", resolve)


@pytest.mark.parametrize("name", ["beta_without_the_2", "no_qk_norm",
                                  "no_decay", "tail_dropped"])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        tiny, monkeypatch, name):
    _served_model(monkeypatch, controls.CONTROLS[name])
    _, result = rehearse(CELL, 0.3, **SERVE)
    assert not result["correct"]
    assert result["checks"]["worst_logit_rms_rel_err"] > 1e-3


def test_the_check_fails_a_state_in_half_the_bytes(tiny, monkeypatch):
    _served_model(monkeypatch, controls.CONTROLS["bf16_state"])
    _, result = rehearse(CELL, 0.3, **SERVE)
    chk = result["checks"]
    assert not result["correct"]
    # the matrices in two bytes, the convolution tails still in four
    assert chk["state_bytes"] == 4 * 3 * (216 * 2 + 216 * 4)
    assert chk["state_bytes"] < chk["state_bytes_float32"]
    assert chk["worst_logit_rms_rel_err"] > 2e-5


def test_the_flops_and_bytes_functions_against_hand_counts():
    # 32 live slots x 6 layers x 30 x 96 x 192 x 4 B, there and back
    assert fg.gdn_state_bytes(32, 6, 30, 96, 192) \
        == 32 * 6 * 2 * 2211840 == 849346560
    assert fg.dense_ffn_bytes(8, 3840, 11008, "bfloat16") \
        == 8 * 3 * 3840 * 11008 * 2 == 2028994560
    # one chunk of 4 rows, one head, d_k 2, d_v 3: K K^T and Q K^T 2 x 32,
    # the inverse (2 pairs of 1x1: 4; 1 pair of 2x2: 16) 20, T [V | K] 80,
    # [Q; W] S 48, P U 48, K^T U 24 multiply-adds
    assert fg.gdn_chunk_flops(1, 4, 1, 2, 3) == 2 * (64 + 20 + 80 + 48
                                                     + 48 + 24)
    # rows in (2 + 2 + 3 a row) and out (3), the state there and back
    assert fg.gdn_chunk_bytes(1, 4, 1, 2, 3) == (4 * 7 + 4 * 3 + 12) * 4
    # the cell's chunk: 64 rows x 30 heads, 96 on 192
    assert 380e6 < fg.gdn_chunk_flops(1, 64, 30, 96, 192) < 385e6


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.5, "count": 2}}},
        "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
        "config": config, "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 65,
                               "decode_prefills": 1,
                               "decode_prefill_keys_live": 3,
                               "decode_prefill_keys_attended": 8,
                               "decode_prefill_scan_steps": 6 * 60,
                               "decode_prefill_scan_tokens": 6 * 3800},
                  "slots": 32, "page_size": 16, "kv_bytes_per_token": 30720,
                  "decode_contexts": [4000] * 64,
                  "caller_ms": {"ttft_p90": 900.0, "itl_p99": 300.0}},
    }


def test_the_trace_readers_read_a_synthetic_trace(monkeypatch):
    """Two runs of ``jit_step`` and two of ``jit_prefill``; each pattern
    takes its own events and only those inside its own program."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    full = ('%paged_attention.3 = f32[32,1,3840] custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    gdn = ("%fusion.77 = (f32[32,30,96,192], f32[32,30,192]) fusion("
           "%state_4_.1, %fusion.70), kind=kLoop")
    ffn = ("%fusion.201 = f32[32,3840] fusion(%fusion.100, "
           "%weights__layers___1___ffn_w_up__.1, "
           "%weights__layers___1___ffn_w_down__.1), kind=kOutput")
    ahead = ("%copy-start.3 = (bf16[3840,11008]{1,0:S(1)}, bf16[3840,11008], "
             "u32[]) copy-start(%weights__layers___0___ffn_w_gate__.1)")
    loop = ("%while.9 = (s32[]{:T(128)}, f32[1,30,96,192]{3,2,1,0:T(8,128)}, "
            "f32[1,34560]{1,0}, f32[4096,30,192]{2,1,0}) while(%tuple.4), "
            "condition=%cond.1, body=%body.1")
    blocks = ("%while.11 = (s32[]{:T(128)}, f32[4,1024,30,1,128]{4,3,2,1,0}) "
              "while(%tuple.8), condition=%cond.2, body=%body.2")
    view = {"runs": {"jit_step": [(0.0, 0.02), (0.05, 0.07)],
                     "jit_prefill": [(0.02, 0.05), (0.07, 0.10)]},
            "ops": [(0.001, 0.007, full), (0.007, 0.011, gdn),
                    (0.012, 0.018, ffn),
                    (0.0525, 0.0525, ahead),    # moves bytes, reads nothing
                    (0.021, 0.031, loop), (0.032, 0.040, blocks),
                    (0.041, 0.044, ffn),        # the prefill's: not a step's
                    (0.051, 0.057, full), (0.057, 0.061, gdn),
                    (0.062, 0.068, ffn),
                    (0.071, 0.081, loop)]}
    monkeypatch.setattr(hybrid_moe, "view", lambda s: view)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__ == "_bench_readers_hybrid_moe":
            monkeypatch.setitem(reader.__globals__, "view", lambda s: view)
    got = bench_run.layer_metrics(cell, _sources(cell["config"]))
    assert got["jit_step_ms.serve"]["value"] == pytest.approx(20.0)
    assert got["full_attn_ms_per_step.serve"]["value"] == pytest.approx(6.0)
    assert got["gdn_ms_per_step.serve"]["value"] == pytest.approx(4.0)
    assert got["dense_ffn_ms_per_step.serve"]["value"] == pytest.approx(6.0)
    assert got["gdn_prefill_ms.serve"]["value"] == pytest.approx(10.0)
    # 32 live slots a step: 0.85 GB at 819 GB/s over 4 ms
    assert got["gdn_state_roofline"]["value"] == pytest.approx(
        100 * 849346560 / 819e9 / 4e-3)
    # the matched events that COMPUTE name two matrices: those two are
    # the bytes (the prefetch of a third names it and is no read)
    assert got["dense_ffn_roofline.serve"]["value"] == pytest.approx(
        100 * 2 * 3840 * 11008 * 2 / 819e9 / 6e-3)
    # 360 chunk-layers a prefill, two prefill runs traced, 20 ms of loops
    chunks = 2 * 360
    least = max(fg.gdn_chunk_flops(chunks, 64, 30, 96, 192) / 197e12,
                fg.gdn_chunk_bytes(chunks, 64, 30, 96, 192) / 819e9)
    assert got["gdn_prefill_roofline"]["value"] == pytest.approx(
        100 * least / 20e-3)
    assert got["prefill_tokens_per_scan_step.serve"]["value"] \
        == pytest.approx(3800 / 60)
    # 32 tokens a step at context 4,000: 250 pages x 16 x 30,720 B
    assert got["full_attn_roofline.serve"]["value"] == pytest.approx(
        100 * 32 * 250 * 16 * 30720 / 819e9 / 6e-3)
    assert got["prefill_keys_live_share.serve"]["value"] == 37.5
    assert all(0 < v["value"] <= 100 for k, v in got.items()
               if "roofline" in k)


def test_the_new_readers_read_nothing_where_the_program_lacks_them():
    """The parent of this PR: no such counters, no trace, a
    configuration without these keys."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    sources = dict(_sources(cell["config"]), trace=None)
    sources["serve"]["counters"] = {"decode_steps": 2,
                                    "decode_tokens_total": 65,
                                    "decode_prefills": 1}
    got = bench_run.layer_metrics(cell, sources)
    assert not [k for k in got if "roofline" in k or "gdn" in k
                or "ffn" in k or "scan" in k or "keys" in k]
    sources["serve"]["counters"].update(decode_prefill_scan_steps=0,
                                        decode_prefill_scan_tokens=0)
    assert "prefill_tokens_per_scan_step.serve" not in \
        bench_run.layer_metrics(cell, sources)
    solar = bench_run.resolve_cell(
        ROOT, "solar_open2_250b.chat_closed_c128")["config"]
    other = dict(_sources(solar), trace=None)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__.endswith("gated_delta") \
                and "roofline" in reader.__name__:
            assert reader(other, {"pattern": "x", "module": "jit_step",
                                  "chunk": 64}) is None
