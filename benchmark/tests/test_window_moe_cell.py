"""The window/global-attention cell rehearsed on the CPU at a tiny size
(widths cut HERE, never in the benchmark's files): the loader resolves
it, its kind runs it through the real server with the check that follows
the served routing, the check fails what it must (a window off by one,
no sink, swapped rotary bases, no value scale, a ring as long as the
sequence), the bytes functions agree with hand counts and every reader
the cell brings returns a value - the trace's from a synthetic parsed
trace, since a CPU run has no device plane."""
import copy
import json
import os

import pytest

from benchmark import flops_window_moe as fw
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "mimo_v2_5.reason_closed_c128"
KINDS = ["attention", "window", "window", "attention", "window"]
TINY = {"model": dict(vocab_size=97, d_model=32, layer_kinds=KINDS,
                      num_heads=8, num_kv_heads=2, window_kv_heads=4,
                      head_dim=12, v_head_dim=8, rotary_dim=4, window=20,
                      dense_dim=48, num_experts=16, top_k=4,
                      held_experts=[0, 5], expert_dim=16, dtype="float32"),
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
    monkeypatch.setitem(rh.TINY, "mimo_v2_5", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_window"
    names = {e["name"] for e, _, _ in cell["per_layer"]}
    assert {"window_attn_roofline.serve", "full_attn_roofline.serve",
            "window_attn_ms_per_step.serve", "full_attn_ms_per_step.serve",
            "window_positions_live_share.serve",
            "routed_experts_hit_share.serve", "moe_experts_roofline",
            "jit_step_ms.serve", "h2d_uploads_per_step.serve"} <= names
    # what reads another model's layers stays off the cell
    assert not {"kda_state_roofline", "experts_hit_share.serve",
                "paged_attn_roofline.serve", "decode_attn_roofline"} & names
    # every width as published; depth, experts held and vocabulary cut
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    m = config["model"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"],
            m["window_kv_heads"], m["head_dim"], m["v_head_dim"],
            m["window"], m["dense_dim"], m["expert_dim"], m["top_k"],
            m["num_experts"], m["rope_theta"], m["window_rope_theta"],
            m["value_scale"], m["rms_eps"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["swa_num_key_value_heads"],
        config["head_dim"], config["v_head_dim"], config["sliding_window"],
        config["intermediate_size"], config["moe_intermediate_size"],
        config["num_experts_per_tok"],
        config["published"]["n_routed_experts"], config["rope_theta"],
        config["swa_rope_theta"], config["attention_value_scale"],
        config["layernorm_epsilon"])
    assert m["rotary_dim"] == int(
        config["head_dim"] * config["partial_rotary_factor"]) == 64
    n = config["num_hidden_layers"]
    assert len(m["layer_kinds"]) == n == 7
    assert m["layer_kinds"] == [
        "window" if k else "attention"
        for k in config["hybrid_layer_pattern"][:n]]
    assert m["dense_layers"] == config["moe_layer_freq"][:n].count(0) == 1
    lo, hi = m["held_experts"]
    assert hi - lo == config["n_routed_experts"] == 256 // 16
    assert m["vocab_size"] == config["vocab_size"] == 152576 // 8
    # 2 global layers x 4 heads x (192 + 128) lanes, bf16
    assert cell["model"].kv_bytes_per_token(config) == 5120
    sv = config["serving"]
    assert sv["num_pages"] == sv["slots"] * 225 + 1


def test_the_files_hold_the_catalog_entrys_numbers():
    """Every number of the published config under its own key, but for
    the three that ``reduced`` names."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "MiMo-V2.5")
    config = bench_run.resolve_cell(ROOT, CELL)["config"]
    assert config["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items()
                     if config.get(k) != v)
    assert differs == sorted(config["reduced"])


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert chk["worst_logit_rel_err"] < 1e-4 and chk["worst_route_gap"] == 0
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    # 3 window layers x (4 slots x 4 pages + trash) x 8 x 4 x (12 + 8) x 4 B
    assert chk["window_bytes"] == chk["window_bytes_owed"] \
        == 3 * 17 * 8 * 4 * 20 * 4
    assert min(chk["prompt_lens"]) >= 40 and chk["positions"] == 12
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["moe_experts_hit"] > 0 and c["decode_prefix_bypassed"] > 0
    assert c["decode_window_positions_live"] > 0
    assert c["decode_window_blocks_walked"] >= c["decode_steps"]
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "routed_experts_hit_share.serve",
            "window_positions_live_share.serve",
            "caller_itl_p99_ms.serve", "caller_ttft_p90_ms.serve"} <= set(got)
    assert 0 < got["routed_experts_hit_share.serve"]["value"] <= 100
    assert 0 < got["window_positions_live_share.serve"]["value"] <= 100


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


def _swap_bases(model):
    model.rope_theta, model.window_rope_theta = (
        model.window_rope_theta, model.rope_theta)


@pytest.mark.parametrize("change", [
    lambda m: setattr(m, "window", 19), lambda m: setattr(m, "window", 21),
    _swap_bases, lambda m: setattr(m, "value_scale", 1.0),
    lambda m: setattr(m, "rotary_dim", 6)],
    ids=["window_19", "window_21", "bases_swapped", "no_value_scale",
         "rotary_6_lanes"])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        tiny, monkeypatch, change):
    _served_model(monkeypatch, change)
    _, result = rehearse(CELL, 0.3, **SERVE)
    assert not result["correct"]
    assert result["checks"]["worst_logit_rms_rel_err"] > 1e-3


def test_the_check_fails_a_model_served_without_its_sinks(
        tiny, monkeypatch):
    from paddle_tpu.ops import pallas_decode_attention as pda

    real_step, real_prefill = (pda.paged_decode_attention,
                               pda.grouped_causal_attention)
    monkeypatch.setattr(
        pda, "paged_decode_attention",
        lambda *a, sinks=None, **kw: real_step(*a, **kw))
    monkeypatch.setattr(
        pda, "grouped_causal_attention",
        lambda *a, sinks=None, **kw: real_prefill(*a, **kw))
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
    # the published rows, bf16: a global position 4 x (192 + 128) x 2 B a
    # layer, a window position 8 x 320 x 2 B a layer
    assert fw.kv_bytes_per_token(2, 4, 192, 128, "bfloat16") == 5120
    assert fw.kv_bytes_per_token(5, 8, 192, 128, "bfloat16") == 25600
    # a window of 128 over pages of 16: 8 pages where it starts on a
    # page's edge, 9 where it does not, fewer while the context is short
    assert [fw.window_pages(n, 16, 128) for n in
            (1, 16, 17, 128, 129, 144, 150, 3584)] == [
        1, 1, 2, 8, 9, 8, 9, 8]
    assert fw.window_attention_bytes([150, 3584], 16, 128, 25600) \
        == (9 + 8) * 16 * 25600


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.09, "count": 1}}},
        "peaks": {"hbm_gbps": 819.0}, "config": config,
        "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 260,
                               "decode_prefills": 4, "moe_experts_hit": 180,
                               "moe_local_assignments": 128,
                               "decode_window_positions_live": 2 * 128 * 128,
                               "decode_window_blocks_walked": 2 * 128 * 2},
                  "slots": 128, "page_size": 16, "kv_bytes_per_token": 5120,
                  "decode_contexts": [1500] * 256,
                  "caller_ms": {"ttft_p90": 110.0, "itl_p99": 170.0}},
    }


def test_the_trace_readers_read_a_synthetic_trace(monkeypatch):
    """Two runs of ``jit_step`` and one of ``jit_prefill``; each pattern
    takes its own kernel's events and only those inside a step."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    full = ('%paged_attention.3 = f32[128,16,512] custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    window = ('%paged_attention_window.5 = f32[128,8,1024] custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    moe = ("%fusion.189 = f32[128,32768] fusion(%fusion.100, "
           "%weights__layers___1___moe_w_gate__.1), kind=kOutput")
    view = {"runs": {"jit_step": [(0.0, 0.02), (0.05, 0.07)],
                     "jit_prefill": [(0.02, 0.05)]},
            "ops": [(0.001, 0.003, full), (0.003, 0.004, window),
                    (0.010, 0.016, moe),
                    (0.03, 0.04, moe),          # the prefill's: not a step's
                    (0.051, 0.053, full), (0.053, 0.054, window),
                    (0.060, 0.066, moe)]}
    # the loader runs each reader file as a module of its own; the new
    # file's readers reach the trace through the package's module
    monkeypatch.setattr(hybrid_moe, "view", lambda s: view)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__ == "_bench_readers_hybrid_moe":
            monkeypatch.setitem(reader.__globals__, "view", lambda s: view)
    got = bench_run.layer_metrics(cell, _sources(cell["config"]))
    assert got["jit_step_ms.serve"]["value"] == pytest.approx(20.0)
    assert got["jit_prefill_ms.serve"]["value"] == pytest.approx(90.0)
    assert got["full_attn_ms_per_step.serve"]["value"] == pytest.approx(2.0)
    assert got["window_attn_ms_per_step.serve"]["value"] == pytest.approx(1.0)
    assert got["moe_ffn_ms_per_step.serve"]["value"] == pytest.approx(6.0)
    # 128 tokens a step at context 1,500: 94 pages x 16 x 5,120 B global,
    # 9 pages x 16 x 25,600 B in the rings
    assert got["full_attn_roofline.serve"]["value"] == pytest.approx(
        100 * 128 * 94 * 16 * 5120 / 819e9 / 2e-3)
    assert got["window_attn_roofline.serve"]["value"] == pytest.approx(
        100 * 128 * 9 * 16 * 25600 / 819e9 / 1e-3)
    assert got["window_positions_live_share.serve"]["value"] == 50.0
    # 90 of 16 x 6 expert-layers hit a step x 50.3 MB
    assert got["routed_experts_hit_share.serve"]["value"] == pytest.approx(
        100 * 90 / 96)
    assert got["moe_experts_roofline"]["value"] == pytest.approx(
        100 * 90 * 3 * 4096 * 2048 * 2 / 819e9 / 6e-3)
    assert all(0 < v["value"] <= 100 for k, v in got.items()
               if "roofline" in k)


def test_the_new_readers_read_nothing_where_the_program_lacks_them():
    """The parent of the PR that added them: no window counters, no
    kernel by that name, a configuration without these keys."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    sources = dict(_sources(cell["config"]), trace=None)
    sources["serve"]["counters"] = {"decode_steps": 2,
                                    "decode_tokens_total": 260,
                                    "decode_prefills": 4}
    got = bench_run.layer_metrics(cell, sources)
    assert not [k for k in got if "roofline" in k or "window" in k
                or "experts" in k]
    solar = bench_run.resolve_cell(
        ROOT, "solar_open2_250b.chat_closed_c128")["config"]
    other = dict(_sources(solar), serve=sources["serve"])
    for _, _, reader in cell["per_layer"]:
        if reader.__module__.endswith("window_moe"):
            assert reader(dict(other, trace=None), {
                "pattern": "x", "module": "jit_step",
                "block_positions": 128}) is None
