"""The parallel-block cell rehearsed on the CPU at a tiny size (widths
cut HERE, never in the benchmark's files): the loader resolves it, its
kind runs it through the real server with the check that follows the
served routing, the check fails what it must (rotary in the global
layer, half-split pairing, shared experts summed, a window off by one, a
ring as long as the sequence), the bytes function agrees with a hand
count and every reader the cell brings returns a value - the trace's
from a synthetic parsed trace, since a CPU run has no device plane."""
import copy
import json
import os

import pytest

from benchmark import flops_parallel_moe as fp
from benchmark import flops_window_moe as fw
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe
from benchmark.tests import parallel_moe_controls as controls
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "command_a_plus.rag_closed_c48"
KINDS = ["window", "window", "window", "attention"]
TINY = {"model": dict(vocab_size=97, d_model=32, layer_kinds=KINDS,
                      num_heads=8, num_kv_heads=2, window_kv_heads=2,
                      head_dim=12, v_head_dim=12, window=20, num_experts=16,
                      top_k=4, held_experts=[0, 5], expert_dim=16,
                      shared_experts=4, shared_dim=16, dtype="float32"),
        "serving": dict(slots=4, max_seq_len=128, num_pages=None,
                        page_size=8, cache_dtype="float32",
                        use_pallas="always", interpret=True)}
SERVE = dict(spec_overrides={
    "traffic": {"callers": 4, "prompt_len": [8, 40], "reply_len": [4, 40],
                "pool": 8, "stagger_s": 0.3},
    "serve": {"fill_s": 0.6},
    "check": {"prompt_len": [40, 60], "new_tokens": 12, "pad": 80,
              "logit_rms_rtol": 1e-5, "route_eps": 1e-6,
              "reroute_share": 0.0}})


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(rh.TINY, "command_a_plus", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_window"
    names = {e["name"] for e, _, _ in cell["per_layer"]}
    assert {"window_attn_roofline.serve", "full_attn_roofline.serve",
            "window_attn_ms_per_step.serve", "full_attn_ms_per_step.serve",
            "window_positions_live_share.serve",
            "routed_experts_hit_share.serve", "moe_experts_roofline",
            "jit_step_ms.serve", "h2d_uploads_per_step.serve",
            "shared_ffn_ms_per_step.serve", "shared_ffn_roofline.serve",
            "window_capped_share.serve",
            "prefill_keys_live_share.serve"} <= names
    # what reads another model's layers, and what reads null since the
    # step ahead, stays off the cell
    assert not {"kda_state_roofline", "experts_hit_share.serve",
                "paged_attn_roofline.serve", "decode_attn_roofline",
                "idle_under_sync_ms_per_step.serve",
                "clock_align_slack_ms.serve"} & names
    # every width as published; depth, experts held and vocabulary cut
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    m = config["model"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"],
            m["window_kv_heads"], m["head_dim"], m["v_head_dim"],
            m["window"], m["expert_dim"], m["shared_dim"],
            m["shared_experts"], m["top_k"], m["num_experts"],
            m["rope_theta"], m["norm_eps"], m["logit_scale"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["num_key_value_heads"],
        config["head_dim"], config["head_dim"], config["sliding_window"],
        config["intermediate_size"], config["intermediate_size"],
        config["num_shared_experts"], config["num_experts_per_tok"],
        config["published"]["num_experts"], config["rope_theta"],
        config["layer_norm_eps"], config["logit_scale"])
    n = config["num_hidden_layers"]
    assert len(m["layer_kinds"]) == n == config["layer_switch"] == 4
    assert m["layer_kinds"] == [
        "window" if k == "sliding_attention" else "attention"
        for k in config["layer_types"][:n]]
    assert m["dense_layers"] == config["first_k_dense_replace"] == 0
    assert config["tie_word_embeddings"] and config["use_parallel_block"] \
        and not config["use_qk_norm"]
    lo, hi = m["held_experts"]
    assert hi - lo == config["num_experts"] == 128 // 16
    assert m["vocab_size"] == config["vocab_size"] == 262144 // 8
    # 1 global layer x 8 heads x (128 + 128) lanes, bf16
    assert cell["model"].kv_bytes_per_token(config) == 4096
    sv = config["serving"]
    assert sv["num_pages"] == sv["slots"] * 385 + 1
    assert sv["max_seq_len"] == 6144 \
        == cell["spec"]["traffic"]["prompt_len"][1] \
        + cell["spec"]["traffic"]["reply_len"][1]
    # the memory the file reckons is what the shapes give
    mem = config["memory"]
    assert mem["window_rings_bytes"] == 3 * (48 * 257 + 1) * 65536
    assert mem["global_pages_bytes"] == 18481 * 65536
    assert mem["weights_bytes"] == config["parameters"]["bytes"]
    assert 0.6 < mem["resident_bytes"] / 16e9 < 0.65
    # the check's requests cross the ring's first overwrite in decode
    chk = cell["spec"]["check"]
    assert chk["prompt_len"][0] + chk["new_tokens"] > 257 * 16
    assert chk["prompt_len"][1] + chk["new_tokens"] - 1 <= chk["pad"]


def test_the_files_hold_the_catalog_entrys_numbers():
    """Every number of the published config under its own key, but for
    the three that ``reduced`` names."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "command-a-plus-05-2026")
    config = bench_run.resolve_cell(ROOT, CELL)["config"]
    assert config["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"])


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert chk["worst_logit_rel_err"] < 1e-4 and chk["worst_route_gap"] == 0
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    # 3 window layers x (4 slots x 4 pages + trash) x 8 x 2 x (12 + 12) x 4 B
    assert chk["window_bytes"] == chk["window_bytes_owed"] \
        == 3 * 17 * 8 * 2 * 24 * 4
    assert min(chk["prompt_lens"]) >= 40 and chk["positions"] == 12
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["moe_experts_hit"] > 0 and c["decode_prefix_bypassed"] > 0
    assert c["decode_window_rows"] >= c["decode_steps"]
    assert 0 < c["decode_window_rows_capped"] < c["decode_window_rows"]
    assert 0 < c["decode_prefill_keys_live"] \
        < c["decode_prefill_keys_attended"]
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "routed_experts_hit_share.serve",
            "window_positions_live_share.serve",
            "window_capped_share.serve", "prefill_keys_live_share.serve",
            "caller_itl_p99_ms.serve", "caller_ttft_p90_ms.serve"} <= set(got)
    for name in ("routed_experts_hit_share.serve",
                 "window_capped_share.serve",
                 "prefill_keys_live_share.serve"):
        assert 0 < got[name]["value"] < 100, name


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


@pytest.mark.parametrize("change", [
    lambda m: setattr(m, "window", 19), lambda m: setattr(m, "window", 21),
    controls._rotate_global, controls._half_split, controls._sum_shared,
    lambda m: setattr(m, "logit_scale", 1.1)],
    ids=["window_19", "window_21", "rotary_in_the_global_layer",
         "half_split_pairing", "shared_experts_summed", "logit_scale"])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        tiny, monkeypatch, change):
    _served_model(monkeypatch, change)
    _, result = rehearse(CELL, 0.3, **SERVE)
    assert not result["correct"]
    assert result["checks"]["worst_logit_rms_rel_err"] > 1e-3


def test_the_check_fails_a_window_layer_that_keeps_every_position(
        tiny, monkeypatch):
    """A window as long as the sequence: the logits are another model's
    and the rings' bytes follow ``max_seq_len``."""
    _served_model(monkeypatch, lambda m: setattr(m, "window", 128))
    _, result = rehearse(CELL, 0.3, **SERVE)
    chk = result["checks"]
    assert not result["correct"]
    assert chk["window_bytes"] > 4 * chk["window_bytes_owed"]
    assert chk["worst_logit_rms_rel_err"] > 1e-3


def test_the_bytes_functions_against_hand_counts():
    # four layers x four shared experts x three 4096 x 4096 matrices, bf16
    assert fp.shared_expert_bytes(4, 4096, 4, 4096, "bfloat16") \
        == 4 * 3 * 4096 * 16384 * 2 == 1610612736
    assert fp.shared_expert_bytes(1, 32, 4, 16, "float32") == 4 * 3 * 512 * 4
    # the published rows, bf16: 8 heads x (128 + 128) lanes x 2 B a layer
    assert fw.kv_bytes_per_token(1, 8, 128, 128, "bfloat16") == 4096
    assert fw.kv_bytes_per_token(3, 8, 128, 128, "bfloat16") == 12288
    # a window of 4,096 over pages of 16: 256 pages where it starts on a
    # page's edge, 257 where it does not, fewer while the context is short
    assert [fw.window_pages(n, 16, 4096) for n in
            (1, 3100, 4096, 4097, 4112, 5000, 6144)] == [
        1, 194, 256, 257, 256, 257, 256]


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.5, "count": 1}}},
        "peaks": {"hbm_gbps": 819.0}, "config": config,
        "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 100,
                               "decode_prefills": 4, "moe_experts_hit": 60,
                               "moe_local_assignments": 48,
                               "decode_window_positions_live": 2 * 48 * 4000,
                               "decode_window_blocks_walked": 2 * 48 * 33,
                               "decode_window_rows": 96,
                               "decode_window_rows_capped": 60,
                               "decode_prefill_keys_live": 3,
                               "decode_prefill_keys_attended": 8},
                  "slots": 48, "page_size": 16, "kv_bytes_per_token": 4096,
                  "decode_contexts": [5000] * 96,
                  "caller_ms": {"ttft_p90": 900.0, "itl_p99": 300.0}},
    }


def test_the_trace_readers_read_a_synthetic_trace(monkeypatch):
    """Two runs of ``jit_step`` and one of ``jit_prefill``; each pattern
    takes its own events and only those inside a step."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    full = ('%paged_attention.3 = f32[48,16,1024] custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    window = ('%paged_attention_window.5 = f32[48,16,1024] custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    moe = ("%fusion.189 = f32[48,32768] fusion(%fusion.100, "
           "%weights__layers___1___moe_w_gate__.1), kind=kOutput")
    shared = ("%fusion.201 = f32[48,16384] fusion(%fusion.100, "
              "%weights__layers___1___shared_w_up__.1), kind=kOutput")
    view = {"runs": {"jit_step": [(0.0, 0.02), (0.05, 0.07)],
                     "jit_prefill": [(0.02, 0.05)]},
            "ops": [(0.001, 0.003, full), (0.003, 0.007, window),
                    (0.010, 0.014, moe), (0.014, 0.017, shared),
                    (0.03, 0.04, shared),       # the prefill's: not a step's
                    (0.051, 0.053, full), (0.053, 0.057, window),
                    (0.060, 0.064, moe), (0.064, 0.067, shared)]}
    # the loader runs each reader file as a module of its own; the new
    # file's readers reach the trace through the package's module
    monkeypatch.setattr(hybrid_moe, "view", lambda s: view)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__ == "_bench_readers_hybrid_moe":
            monkeypatch.setitem(reader.__globals__, "view", lambda s: view)
    got = bench_run.layer_metrics(cell, _sources(cell["config"]))
    assert got["jit_step_ms.serve"]["value"] == pytest.approx(20.0)
    assert got["full_attn_ms_per_step.serve"]["value"] == pytest.approx(2.0)
    assert got["window_attn_ms_per_step.serve"]["value"] == pytest.approx(4.0)
    assert got["moe_ffn_ms_per_step.serve"]["value"] == pytest.approx(4.0)
    assert got["shared_ffn_ms_per_step.serve"]["value"] == pytest.approx(3.0)
    # 1.61 GB of shared experts a step at 819 GB/s over 3 ms
    assert got["shared_ffn_roofline.serve"]["value"] == pytest.approx(
        100 * 1610612736 / 819e9 / 3e-3)
    # 48 tokens a step at context 5,000: 313 pages x 16 x 4,096 B global,
    # 257 pages x 16 x 12,288 B in the rings
    assert got["full_attn_roofline.serve"]["value"] == pytest.approx(
        100 * 48 * 313 * 16 * 4096 / 819e9 / 2e-3)
    assert got["window_attn_roofline.serve"]["value"] == pytest.approx(
        100 * 48 * 257 * 16 * 12288 / 819e9 / 4e-3)
    assert got["window_positions_live_share.serve"]["value"] \
        == pytest.approx(100 * 4000 / (33 * 128))
    assert got["window_capped_share.serve"]["value"] == 62.5
    assert got["prefill_keys_live_share.serve"]["value"] == 37.5
    # 30 of 8 x 4 expert-layers hit a step x 100.7 MB
    assert got["routed_experts_hit_share.serve"]["value"] == pytest.approx(
        100 * 30 / 32)
    assert got["moe_experts_roofline"]["value"] == pytest.approx(
        100 * 30 * 3 * 4096 * 4096 * 2 / 819e9 / 4e-3)
    assert all(0 < v["value"] <= 100 for k, v in got.items()
               if "roofline" in k)


def test_the_new_readers_read_nothing_where_the_program_lacks_them():
    """The parent of this PR: no such counters, no operand by that name,
    a configuration without these keys."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    sources = dict(_sources(cell["config"]), trace=None)
    sources["serve"]["counters"] = {"decode_steps": 2,
                                    "decode_tokens_total": 100,
                                    "decode_prefills": 4}
    got = bench_run.layer_metrics(cell, sources)
    assert not [k for k in got if "roofline" in k or "window" in k
                or "experts" in k or "shared" in k or "keys" in k]
    # counters that exist and never moved (a parent that knows the name
    # and not the mechanism) read nothing either
    sources["serve"]["counters"].update(
        decode_window_rows=0, decode_window_rows_capped=0,
        decode_prefill_keys_live=0, decode_prefill_keys_attended=0)
    got = bench_run.layer_metrics(cell, sources)
    assert "window_capped_share.serve" not in got \
        and "prefill_keys_live_share.serve" not in got
    mimo = bench_run.resolve_cell(
        ROOT, "mimo_v2_5.reason_closed_c128")["config"]
    other = dict(_sources(mimo), trace=None)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__.endswith("parallel_moe") \
                and "roofline" in reader.__name__:
            assert reader(other, {"pattern": "x",
                                  "module": "jit_step"}) is None
