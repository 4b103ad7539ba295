"""The latent-attention cell rehearsed on the CPU at a tiny size (widths
cut HERE, never in the benchmark's files): the loader resolves it, its
kind runs it through the real server with the check that follows the
served routing, the check fails what it must (the controls of
``latent_moe_controls.py``, a cache of the wrong size), the flops and
bytes functions agree with hand counts and every reader the cell brings
returns a value - the trace's from a synthetic parsed trace, since a CPU
run has no device plane."""
import copy
import json
import math
import os

import pytest

from benchmark import flops_latent_moe as fl
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe
from benchmark.tests import latent_moe_controls as controls
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "kimi_k2_5.agent_closed_c64"
TINY = {"model": dict(vocab_size=97, d_model=32, num_layers=3,
                      layer_kinds=["attention"] * 3, dense_layers=1,
                      num_heads=4, q_rank=24, kv_rank=16, nope_dim=8,
                      rope_dim=8, v_dim=8, rope_orig_len=64, dense_dim=48,
                      num_experts=16, top_k=4, held_experts=[0, 5],
                      expert_dim=16, shared_dim=16, dtype="float32"),
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
    monkeypatch.setitem(rh.TINY, "kimi_k2_5", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_latent"
    assert cell["workload"]["chips"] == 1
    names = {e["name"] for e, _, _ in cell["per_layer"]}
    assert {"latent_attn_ms_per_step.serve", "latent_attn_roofline.serve",
            "latent_proj_ms_per_step.serve", "latent_proj_roofline.serve",
            "latent_row_bytes.serve", "moe_ffn_ms_per_step.serve",
            "moe_experts_roofline", "routed_experts_hit_share.serve",
            "prefill_keys_live_share.serve", "shared_ffn_ms_per_step.serve",
            "dense_ffn_ms_per_step.serve", "jit_step_ms.serve",
            "jit_prefill_ms.serve", "slot_occupancy.serve",
            "engine_host_ms_per_step.serve", "h2d_uploads_per_step.serve",
            "engine_unspanned_share.serve", "deliver_emit_ms_per_step.serve",
            "steps_in_flight_at_dispatch.serve", "caller_itl_p99_ms.serve",
            "caller_ttft_p90_ms.serve"} == names
    assert [e["name"] for e in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    # every width as published; depth, experts held and vocabulary cut
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    m, y = config["model"], config["rope_scaling"]
    assert (m["d_model"], m["num_heads"], m["q_rank"], m["kv_rank"],
            m["nope_dim"], m["rope_dim"], m["v_dim"], m["dense_dim"],
            m["expert_dim"], m["shared_dim"], m["top_k"], m["num_experts"],
            m["rope_theta"], m["routed_scale"], m["rms_eps"],
            m["dense_layers"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["q_lora_rank"], config["kv_lora_rank"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["v_head_dim"], config["intermediate_size"],
        config["moe_intermediate_size"],
        config["moe_intermediate_size"] * config["n_shared_experts"],
        config["num_experts_per_tok"],
        config["published"]["n_routed_experts"], config["rope_theta"],
        config["routed_scaling_factor"], config["rms_norm_eps"],
        config["first_k_dense_replace"])
    assert (m["rope_factor"], m["rope_orig_len"], m["rope_beta_fast"],
            m["rope_beta_slow"], m["rope_mscale"],
            m["rope_mscale_all_dim"]) == (
        y["factor"], y["original_max_position_embeddings"], y["beta_fast"],
        y["beta_slow"], y["mscale"], y["mscale_all_dim"])
    assert m["num_layers"] == len(m["layer_kinds"]) \
        == config["num_hidden_layers"] == 5
    lo, hi = m["held_experts"]
    assert hi - lo == config["n_routed_experts"] == 384 // 32
    assert m["vocab_size"] == config["vocab_size"] == 163840 // 8
    # 5 layers x (512 + 64) lanes, bf16: the published row
    assert cell["model"].kv_bytes_per_token(config) == 5 * 1152
    sv = config["serving"]
    assert sv["num_pages"] == sv["slots"] * 641 + 1
    model = cell["model"].make_model(config)
    assert model.softmax_scale == pytest.approx(0.14468, rel=1e-4)
    assert model.rope_mscale == 1.0
    # the blend: the fast pairs turn as published, the slow ones a 64th
    f = [50000.0 ** (-2.0 * j / 64) for j in range(32)]
    assert model.rope_freqs[:9] == pytest.approx(f[:9])
    assert model.rope_freqs[20:] == pytest.approx([x / 64 for x in f[20:]])
    assert f[14] / 64 < model.rope_freqs[14] < f[14]


def test_the_files_hold_the_catalog_entrys_numbers():
    """Every number of the published config under its own key, but for
    the three that ``reduced`` names."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Kimi-K2.5")
    config = bench_run.resolve_cell(ROOT, CELL)["config"]
    assert config["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items()
                     if config.get(k) != v)
    assert differs == sorted(config["reduced"])


def test_the_built_model_is_the_size_the_file_says():
    import jax

    cell = bench_run.resolve_cell(ROOT, CELL)
    model = cell["model"].make_model(cell["config"])
    shapes = jax.tree_util.tree_leaves(
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    count = lambda s: math.prod(s.shape)  # noqa: E731
    assert sum(map(count, shapes)) == cell["config"]["parameters"]["built"]
    assert sum(count(s) * s.dtype.itemsize for s in shapes) \
        == cell["config"]["parameters"]["bytes"]


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert chk["worst_logit_rel_err"] < 1e-4 and chk["worst_route_gap"] == 0
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    # 3 layers x (4 slots x 16 pages + trash) x 8 rows of one lane tile
    assert chk["latent_bytes"] == chk["latent_bytes_owed"] \
        == 3 * 65 * 8 * 128 * 4
    assert chk["latent_row_bytes"] == 512
    assert chk["latent_row_bytes_published"] == (16 + 8) * 4
    assert min(chk["prompt_lens"]) >= 40 and chk["positions"] == 12
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["moe_experts_hit"] > 0 and c["decode_prefix_bypassed"] > 0
    assert c["decode_latent_positions_live"] > c["decode_steps"]
    assert c["decode_latent_blocks_walked"] >= c["decode_steps"]
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "routed_experts_hit_share.serve",
            "prefill_keys_live_share.serve", "latent_row_bytes.serve",
            "caller_itl_p99_ms.serve", "caller_ttft_p90_ms.serve"} <= set(got)
    assert 0 < got["routed_experts_hit_share.serve"]["value"] <= 100
    assert got["latent_row_bytes.serve"]["value"] == 512


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


@pytest.mark.parametrize("name", [
    "no_mscale", "rope_key_normed", "half_split_pairing", "plain_theta",
    "bias_in_the_weights", "no_routed_scaling", "latent_in_8_bits"])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        tiny, monkeypatch, name):
    change, patch = controls.CONTROLS[name]
    if change:
        _served_model(monkeypatch, change)
    undo = patch() if patch else None
    try:
        _, result = rehearse(CELL, 0.3, **SERVE)
    finally:
        if undo:
            undo()
    assert not result["correct"]
    assert result["checks"]["worst_logit_rms_rel_err"] > 1e-3


def test_the_check_fails_a_cache_of_another_size(tiny, monkeypatch):
    """Rows padded past whole lane tiles (or expanded K/V, or 8 bits):
    the logits are right and the size is not."""
    from paddle_tpu.serving import kv_cache

    monkeypatch.setattr(
        kv_cache.CacheConfig, "row_lanes",
        property(lambda self: 256 if self.latent
                 else self.num_heads * self.head_dim))
    _, result = rehearse(CELL, 0.3, **SERVE)
    chk = result["checks"]
    assert not result["correct"]
    assert chk["worst_logit_rms_rel_err"] < 1e-5
    assert chk["latent_bytes"] == 2 * chk["latent_bytes_owed"]


def test_the_flops_and_bytes_functions_against_hand_counts():
    # the published row, bf16
    assert fl.latent_row_bytes(512, 64, "bfloat16") == 1152
    # 64 slots at 8,000 positions, 5 layers: 512,000 rows of 1,152 B a
    # layer, and a slot's 64 x 576 query and 64 x 512 context in float32
    assert fl.latent_attention_bytes(512000, 64, 5, 64, 512, 64) \
        == 5 * (512000 * 1152 + 64 * 64 * (576 + 512) * 4)
    # 2 x 64 heads x (576 score + 512 value lanes) a position a layer
    assert fl.latent_attention_flops(512000, 5, 64, 512, 64) \
        == 2 * 64 * 1088 * 512000 * 5
    # 121 FLOP/B: under the v5e's ridge of 240, so bytes bound it
    assert fl.latent_attention_flops(1, 1, 64, 512, 64) / 1152 \
        == pytest.approx(120.9, abs=0.1)
    # q_a 11.01 M, q_b 18.87 M, kv_a 4.13 M, kv_b 8.39 M, o 58.72 M
    assert fl.latent_projection_bytes(1, 7168, 64, 1536, 512, 128, 64,
                                      128) == 2 * 101122048
    assert fl.latent_projection_params(7168, 64, 1536, 512, 128, 64, 128) \
        == {"wq_a": 11010048, "wq_b": 18874368, "wkv_a": 4128768,
            "w_uk": 4194304, "w_uv": 4194304, "wo": 58720256}
    assert 2 * 101122048 == pytest.approx(202.2e6, rel=1e-3)


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.6, "count": 2}}},
        "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
        "config": config, "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 130,
                               "decode_prefills": 2, "moe_experts_hit": 60,
                               "moe_local_assignments": 32,
                               "decode_latent_positions_live": 2 * 512000,
                               "decode_latent_blocks_walked": 2 * 4000,
                               "decode_prefill_keys_live": 30,
                               "decode_prefill_keys_attended": 40},
                  "slots": 64, "page_size": 16, "kv_bytes_per_token": 5760,
                  "decode_contexts": [8000] * 128,
                  "latent_pool_rows": 5 * 41025 * 16,
                  "caller_ms": {"ttft_p90": 340.0, "itl_p99": 340.0}},
    }


def test_the_trace_readers_read_a_synthetic_trace(monkeypatch):
    """Two runs of ``jit_step`` and one of ``jit_prefill``; each pattern
    takes its own kernel's events and only those inside a step, and the
    other cells' attention patterns do not read the latent kernel."""
    import re

    from paddle_tpu.monitor import stat_set

    cell = bench_run.resolve_cell(ROOT, CELL)
    latent = ('%paged_attention_latent.3 = f32[64,64,512] custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    proj = ("%fusion.12 = f32[64,64,512] fusion(%p, "
            "%weights__layers___1___w_uk__.1), kind=kOutput")
    out = ("%fusion.13 = f32[64,7168] fusion(%p, "
           "%weights__layers___1___wo__.1), kind=kOutput")
    moe = ("%fusion.189 = f32[64,24576] fusion(%fusion.100, "
           "%weights__layers___1___moe_w_gate__.1), kind=kOutput")
    shared = ("%fusion.190 = f32[64,2048] fusion(%fusion.100, "
              "%weights__layers___1___shared_w_up__.1), kind=kOutput")
    dense = ("%fusion.191 = f32[64,18432] fusion(%fusion.100, "
             "%weights__layers___0___ffn_w_up__.1), kind=kOutput")
    view = {"runs": {"jit_step": [(0.0, 0.02), (0.05, 0.07)],
                     "jit_prefill": [(0.02, 0.05)]},
            "ops": [(0.001, 0.006, latent), (0.006, 0.007, proj),
                    (0.007, 0.008, out), (0.010, 0.016, moe),
                    (0.016, 0.017, shared), (0.017, 0.019, dense),
                    (0.03, 0.04, moe),          # the prefill's: not a step's
                    (0.051, 0.056, latent), (0.056, 0.057, proj),
                    (0.057, 0.058, out), (0.060, 0.066, moe),
                    (0.066, 0.067, shared), (0.067, 0.069, dense)]}
    monkeypatch.setattr(hybrid_moe, "view", lambda s: view)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__ == "_bench_readers_hybrid_moe":
            monkeypatch.setitem(reader.__globals__, "view", lambda s: view)
    stat_set("decode_latent_bytes", 5 * 41025 * 16 * 1280)
    got = bench_run.layer_metrics(cell, _sources(cell["config"]))
    assert got["jit_step_ms.serve"]["value"] == pytest.approx(20.0)
    assert got["latent_attn_ms_per_step.serve"]["value"] \
        == pytest.approx(5.0)
    assert got["latent_proj_ms_per_step.serve"]["value"] \
        == pytest.approx(2.0)
    assert got["moe_ffn_ms_per_step.serve"]["value"] == pytest.approx(6.0)
    assert got["shared_ffn_ms_per_step.serve"]["value"] == pytest.approx(1.0)
    assert got["dense_ffn_ms_per_step.serve"]["value"] == pytest.approx(2.0)
    # 512,000 rows of 1,152 B x 5 layers and 64 slots' queries and
    # contexts: bytes bound it (3.6 ms against 1.8 ms of FLOPs)
    need = 5 * (512000 * 1152 + 64 * 64 * 1088 * 4)
    assert need / 819e9 > 2 * 64 * 1088 * 512000 * 5 / 197e12
    assert got["latent_attn_roofline.serve"]["value"] == pytest.approx(
        100 * need / 819e9 / 5e-3)
    # what the matched events name: layer 1's W_UK and W_o, once
    assert got["latent_proj_roofline.serve"]["value"] == pytest.approx(
        100 * 2 * (512 * 64 * 128 + 8192 * 7168) / 819e9 / 2e-3)
    assert got["latent_row_bytes.serve"]["value"] == 1280
    assert got["routed_experts_hit_share.serve"]["value"] == pytest.approx(
        100 * 30 / 48)
    assert got["prefill_keys_live_share.serve"]["value"] == 75.0
    assert all(0 < v["value"] <= 100 for k, v in got.items()
               if "roofline" in k)
    # no accepted attention metric reads the latent kernel as its own
    for name in ("paged_attn_ms_per_step.serve",
                 "full_attn_ms_per_step.serve",
                 "window_attn_ms_per_step.serve"):
        mfile = bench_run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        pats = list(mfile.get("kernels", {}).values()) + [
            mfile["params"].get("pattern")]
        assert not any(p and re.search(p, latent) for p in pats), name


def test_the_new_readers_read_nothing_where_the_program_lacks_them():
    """The parent of this PR: no latent counters, no kernel by that
    name, a configuration without these keys."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    sources = dict(_sources(cell["config"]), trace=None)
    sources["serve"] = {"slots": 64, "counters": {
        "decode_steps": 2, "decode_tokens_total": 130, "decode_prefills": 2}}
    got = bench_run.layer_metrics(cell, sources)
    assert not [k for k in got if "latent" in k or "roofline" in k]
    mimo = bench_run.resolve_cell(
        ROOT, "mimo_v2_5.reason_closed_c128")["config"]
    other = dict(_sources(mimo), trace=None)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__.endswith("latent_moe"):
            assert reader(other, {"pattern": "x",
                                  "module": "jit_step"}) is None
