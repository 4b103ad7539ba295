"""The linear/latent hybrid's cell rehearsed on the CPU at a tiny size
(widths cut HERE, never in the benchmark's files): the loader resolves
it, its kind runs it through the real server with the check that follows
the served routing and holds BOTH sizes, the check fails what it must
(the controls of ``linear_latent_controls.py``, a cache of the wrong
size), the flops and bytes functions agree with hand counts and every
reader the cell brings returns a value - the trace's from a synthetic
parsed trace, since a CPU run has no device plane."""
import copy
import json
import math
import os

import pytest

from benchmark import flops_linear_latent as fl
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe
from benchmark.tests import linear_latent_controls as controls
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "kimi_linear_48b.agent_closed_c128"
KINDS = ["recurrent", "recurrent", "attention", "recurrent", "attention"]
TINY = {"model": dict(vocab_size=97, d_model=32, layer_kinds=KINDS,
                      dense_layers=1, lin_heads=2, lin_head_dim=8,
                      conv_kernel=4, gate_rank=8, num_heads=4, kv_rank=16,
                      nope_dim=8, rope_dim=8, v_dim=8, dense_dim=48,
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
NEW = {"kda_step_ms.serve", "kda_step_roofline", "kda_prefill_ms.serve",
       "kda_prefill_roofline", "latent_step_ms.serve",
       "latent_step_roofline.serve", "state_bytes_per_slot.serve"}
JOINED = {"slot_occupancy.serve", "engine_host_ms_per_step.serve",
          "h2d_uploads_per_step.serve", "engine_unspanned_share.serve",
          "deliver_emit_ms_per_step.serve",
          "steps_in_flight_at_dispatch.serve", "jit_step_ms.serve",
          "jit_prefill_ms.serve", "caller_itl_p99_ms.serve",
          "caller_ttft_p90_ms.serve", "moe_ffn_ms_per_step.serve",
          "moe_experts_roofline", "routed_experts_hit_share.serve",
          "moe_prefill_ms.serve", "prefill_keys_live_share.serve",
          "dense_ffn_ms_per_step.serve",
          "setup_births_s", "setup_trace_lower_s", "setup_backend_compile_s",
          "setup_cache_load_s", "setup_cache_misses",
          "prefill_tokens_per_scan_step.serve", "latent_row_bytes.serve",
          "kv_bytes_per_token.serve"}


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(rh.TINY, "kimi_linear_48b", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_linear_latent"
    assert cell["workload"]["chips"] == 1
    assert {e["name"] for e, _, _ in cell["per_layer"]} == NEW | JOINED
    # every metric this PR brings lists its cells, and this one alone
    assert all(e["workloads"] == [CELL] for e, _, _ in cell["per_layer"]
               if e["name"] in NEW)
    assert [e["name"] for e in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    # the traffic of the issue, letter for letter
    assert cell["spec"]["traffic"] == {
        "generator": "closed_loop", "callers": 128,
        "prompt_len": [3072, 4096], "reply_len": [1024, 2048], "pool": 128,
        "stagger_s": 8.0}
    assert cell["spec"]["serve"]["fill_s"] == 48.0
    assert cell["spec"]["trace_seconds"] == 4
    chk = cell["spec"]["check"]
    assert (chk["requests"], chk["prompt_len"], chk["new_tokens"]) == (
        2, [3900, 4090], 136)
    assert chk["pad"] % 128 == 0 and chk["pad"] >= 4090 + 135
    # every width as published; depth, experts held and vocabulary cut
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    m, lin = config["model"], config["linear_attn_config"]
    assert (m["d_model"], m["num_heads"], m["kv_rank"], m["nope_dim"],
            m["rope_dim"], m["v_dim"], m["dense_dim"], m["expert_dim"],
            m["shared_dim"], m["top_k"], m["num_experts"],
            m["routed_scale"], m["rms_eps"], m["dense_layers"],
            m["lin_heads"], m["lin_head_dim"], m["conv_kernel"],
            m["gate_rank"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["kv_lora_rank"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"],
        config["intermediate_size"], config["moe_intermediate_size"],
        config["moe_intermediate_size"] * config["num_shared_experts"],
        config["num_experts_per_token"], config["published"]["num_experts"],
        config["routed_scaling_factor"], config["rms_norm_eps"],
        config["first_k_dense_replace"], lin["num_heads"], lin["head_dim"],
        lin["short_conv_kernel_size"], lin["head_dim"])
    assert config["q_lora_rank"] is None and config["mla_use_nope"] is True
    # layers 1-8 of the published lists, counted from 1
    assert len(m["layer_kinds"]) == config["num_hidden_layers"] == 8
    assert [i + 1 for i, k in enumerate(m["layer_kinds"])
            if k == "attention"] == [
        l for l in lin["full_attn_layers"] if l <= 8] == [4, 8]
    assert [i + 1 for i, k in enumerate(m["layer_kinds"])
            if k == "recurrent"] == [l for l in lin["kda_layers"] if l <= 8]
    lo, hi = m["held_experts"]
    assert hi - lo == config["num_experts"] == 256 // 8
    assert m["vocab_size"] == config["vocab_size"] == 163840 // 8
    # 2 latent layers x (512 + 64) lanes, bf16: the published row
    assert cell["model"].kv_bytes_per_token(config) == 2 * 1152 == 2304
    sv = config["serving"]
    assert sv["num_pages"] == sv["slots"] * (sv["max_seq_len"] // 16 + 1) + 1
    model = cell["model"].make_model(config)
    assert model.softmax_scale == 192 ** -0.5 and model.beta_scale == 1.0
    assert model._rotary(None) is None
    assert set(config["assumed"]) >= {
        "router", "beta", "low_rank_gates", "conv_activation",
        "l2_norm_eps", "decay_init", "shared_key", "softmax_scale",
        "shared_expert", "layer_lists"}
    d = config["deployment"]
    assert d["chips_sharing_a_layer"] == 8 and "stage 0" in d["pipeline"]


def test_the_files_hold_the_catalog_entrys_numbers():
    """Every number of the published config under its own key, but for
    the three that ``reduced`` names; nested groups whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Kimi-Linear-48B-A3B-Instruct")
    config = bench_run.resolve_cell(ROOT, CELL)["config"]
    assert config["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"])
    assert {k: config["published"][k] for k in config["reduced"]} \
        == {k: entry["config"][k] for k in config["reduced"]}


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
    # the two sizes the cut is argued from, as the files state them
    m, sv = config["model"], config["serving"]
    assert fl.state_slot_bytes(1, 32, 128, 4) == 2244608
    assert sv["slots"] * fl.state_slot_bytes(
        m["layer_kinds"].count("recurrent"), m["lin_heads"],
        m["lin_head_dim"], m["conv_kernel"]) == 1723858944
    assert 2 * sv["num_pages"] * 16 * 640 * 2 == 2018549760


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert chk["worst_logit_rel_err"] < 1e-4 and chk["worst_route_gap"] == 0
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    # 2 latent layers x (4 slots x 16 pages + trash) x 8 rows of one tile
    assert chk["latent_bytes"] == chk["latent_bytes_owed"] \
        == 2 * 65 * 8 * 128 * 4
    assert chk["latent_row_bytes"] == 512
    assert chk["latent_row_bytes_published"] == (16 + 8) * 4
    # 4 slots x 3 recurrent layers x (2 x 8 x 8 + 3 x 3 x 16) float32
    assert chk["state_bytes"] == chk["state_bytes_float32"] \
        == 4 * 3 * (2 * 8 * 8 + 3 * 3 * 16) * 4
    assert min(chk["prompt_lens"]) >= 40 and chk["positions"] == 12
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["moe_experts_hit"] > 0 and c["decode_prefix_bypassed"] > 0
    assert c["decode_latent_positions_live"] > c["decode_steps"]
    assert c["decode_prefill_scan_tokens"] >= 3 * 8 * c["decode_prefills"]
    assert c["kda_kernel_rows"] == 0        # toy widths: the XLA form
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "routed_experts_hit_share.serve",
            "prefill_keys_live_share.serve", "latent_row_bytes.serve",
            "kv_bytes_per_token.serve", "state_bytes_per_slot.serve",
            "prefill_tokens_per_scan_step.serve",
            "caller_itl_p99_ms.serve", "caller_ttft_p90_ms.serve"} <= set(got)
    assert 0 < got["routed_experts_hit_share.serve"]["value"] <= 100
    assert got["latent_row_bytes.serve"]["value"] == 512
    assert got["kv_bytes_per_token.serve"]["value"] == 2 * 512
    assert got["state_bytes_per_slot.serve"]["value"] \
        == 3 * (2 * 8 * 8 + 3 * 3 * 16) * 4
    assert got["prefill_tokens_per_scan_step.serve"]["value"] == 1.0


def _served_model(monkeypatch, change, reweigh=None):
    """The kind run with the SERVED model changed (the reference keeps
    the configuration's model and weights)."""
    real_resolve = bench_run.resolve_cell

    def resolve(root, name):
        c = real_resolve(root, name)
        build, reference = c["model"].build, c["model"].reference_logits

        def changed(config, seed):
            model, weights = build(config, seed)
            change(model)
            if reweigh is None:
                return model, weights
            served = reweigh(model, weights, seed)
            kept[id(served["layers"][0])] = weights
            return model, served

        def with_own_weights(config, weights, tokens, routing=None):
            own = kept.get(id(weights["layers"][0]), weights)
            return reference(config, own, tokens, routing=routing)

        kept = {}
        c["model"].build = changed
        c["model"].reference_logits = with_own_weights
        return c

    monkeypatch.setattr(rh.bench_run, "resolve_cell", resolve)


@pytest.mark.parametrize("name", [
    n for n in controls.CONTROLS
    if n not in ("served", "bf16_router") and n not in controls.SERVING])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        tiny, monkeypatch, name):
    change, patch = controls.CONTROLS[name]
    if change:
        _served_model(monkeypatch, change, controls.REWEIGH.get(name))
    undo = patch() if patch else None
    try:
        _, result = rehearse(CELL, 0.3, **SERVE)
    finally:
        if undo:
            undo()
    chk = result["checks"]
    assert not result["correct"]
    if name == "state_in_bf16":
        # the size is a limit of its own: the matrices' half
        assert chk["state_bytes"] < chk["state_bytes_float32"]
        assert chk["latent_bytes"] == chk["latent_bytes_owed"]
    else:
        assert chk["state_bytes"] == chk["state_bytes_float32"]
        assert chk["worst_logit_rms_rel_err"] > 1e-3


def test_the_check_fails_a_pool_of_eight_bit_rows_by_its_size(tiny):
    """The control that really holds 8-bit rows, through the controls'
    own entry: the configuration's dtype owes twice the bytes."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = copy.deepcopy(cell["config"])
    for key, val in rh.TINY["kimi_linear_48b"].items():
        config[key].update(val)
    config["serving"].update(cache_dtype="bfloat16", interpret=False)
    spec = copy.deepcopy(cell["spec"])
    spec["check"].update(SERVE["spec_overrides"]["check"])
    tiny_cell = dict(cell, config=config, spec=spec)
    ok, chk = controls.run_control(tiny_cell, config, "latent_pool_fp8",
                                   3, 4)
    assert not ok
    assert chk["latent_bytes"] * 2 == chk["latent_bytes_owed"]
    assert chk["state_bytes"] == chk["state_bytes_float32"]


def test_the_check_fails_a_latent_pool_of_another_size(tiny, monkeypatch):
    """Rows padded past whole lane tiles (or expanded K/V, or 8 bits):
    the logits and the state's size are right and the pool's is not."""
    from paddle_tpu.serving import kv_cache

    monkeypatch.setattr(
        kv_cache.CacheConfig, "row_lanes",
        property(lambda self: 256 if self.latent
                 else self.num_heads * self.head_dim))
    _, result = rehearse(CELL, 0.3, **SERVE)
    chk = result["checks"]
    assert not result["correct"]
    assert chk["worst_logit_rms_rel_err"] < 1e-5
    assert chk["state_bytes"] == chk["state_bytes_float32"]
    assert chk["latent_bytes"] == 2 * chk["latent_bytes_owed"]


def test_the_flops_and_bytes_functions_against_hand_counts():
    # one slot of one layer: 32 matrices of 128 x 128 and three rows of
    # three convolutions' 4,096 inputs, float32
    assert fl.state_slot_bytes(1, 32, 128, 4) \
        == 32 * 128 * 128 * 4 + 3 * 12288 * 4 == 2244608
    assert fl.state_slot_bytes(6, 32, 128, 4) == 13467648
    # a step of 128 live slots, 6 layers: each matrix in and out
    assert fl.kda_state_bytes(128, 6, 32, 128) \
        == 128 * 6 * 2 * 32 * 128 * 128 * 4 == 3221225472
    # a token a head: 7 d^2 multiplies and adds; a 3,600-token prompt's
    # six layers 79.3 G, 12.9 ms at the vector unit's 6.14 T/s
    assert fl.kda_token_ops(1, 1, 128) == 7 * 128 * 128 == 114688
    assert fl.kda_token_ops(3600 * 6, 32, 128) == 79272345600
    assert fl.VECTOR_F32_OPS_PER_S == 6.144e12
    assert fl.kda_token_ops(3600 * 6, 32, 128) / fl.VECTOR_F32_OPS_PER_S \
        == pytest.approx(12.9e-3, rel=1e-2)
    # the published row, bf16; 128 slots at 4,900 positions, 2 layers
    assert fl.latent_row_bytes(512, 64, "bfloat16") == 1152
    assert fl.latent_attention_bytes(627200, 128, 2, 32, 512, 64) \
        == 2 * (627200 * 1152 + 128 * 32 * (576 + 512) * 4)
    assert fl.latent_attention_flops(627200, 2, 32, 512, 64) \
        == 2 * 32 * 1088 * 627200 * 2
    # 60 FLOP/B at 32 heads (121 at the sibling's 64): bytes bound it
    assert fl.latent_attention_flops(1, 1, 32, 512, 64) / 1152 \
        == pytest.approx(60.4, abs=0.1)


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.1, "count": 1}}},
        "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
        "config": config, "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 258,
                               "decode_prefills": 2, "moe_experts_hit": 440,
                               "moe_local_assignments": 256,
                               "kda_kernel_rows": 6 * 256,
                               "decode_latent_positions_live": 2 * 627200,
                               "decode_latent_blocks_walked": 2 * 640,
                               "decode_prefill_keys_live": 30,
                               "decode_prefill_keys_attended": 40,
                               "decode_prefill_scan_steps": 2 * 6 * 57,
                               "decode_prefill_scan_tokens": 2 * 6 * 3600,
                               "moe_grouped_pairs": 2 * 7 * 3600},
                  "slots": 128, "page_size": 16, "kv_bytes_per_token": 2304,
                  "decode_contexts": [4900] * 256,
                  "latent_pool_rows": 2 * 49281 * 16,
                  "kv_pool_positions": 49281 * 16,
                  "gauges": {"decode_kv_pool_bytes": 2018549760,
                             "decode_state_bytes": 1723858944,
                             "decode_latent_bytes": 2018549760},
                  "caller_ms": {"ttft_p90": 340.0, "itl_p99": 340.0}},
    }


def test_the_trace_readers_read_a_synthetic_trace(monkeypatch):
    """Two runs of ``jit_step`` and one of ``jit_prefill``; each pattern
    takes its own events and only those inside its module; the slabs are
    found from operand 1, which the accepted pattern would lose."""
    import re

    from paddle_tpu.monitor import stat_set

    cell = bench_run.resolve_cell(ROOT, CELL)
    latent = ('%paged_attention_latent.3 = f32[128,32,512] custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    first = ("%kda_state_update.1 = (f32[128,32,128], f32[128,32,128,128]) "
             "custom-call(%n, %c, %r, %state_1_.1), "
             'custom_call_target="tpu_custom_call"')
    later = first.replace("%kda_state_update.1", "%kda_state_update.2") \
        .replace("%state_1_.1", "%state_3_.1")
    tail = ("%fusion.7 = f32[128,36864] fusion(%state_2_.1, %u), "
            "kind=kLoop")
    pool = ("%fusion.8 = bf16[2,49281,16,640] fusion(%state_0_.1, %rows), "
            "kind=kLoop")
    loop = ("%while.4 = (s32[], f32[1,32,128,128], f32[1,36864], "
            "f32[4096,32,128]) while(%tuple.9), condition=%c, body=%b")
    moe = ("%fusion.189 = f32[128,32768] fusion(%fusion.100, "
           "%weights__layers___1___moe_w_gate__.1), kind=kOutput")
    grouped = ('%moe_grouped_gate_up.2 = bf16[8192,1024] custom-call(%x), '
               'custom_call_target="tpu_custom_call"')
    shared = ("%fusion.190 = f32[128,1024] fusion(%fusion.100, "
              "%weights__layers___1___shared_w_up__.1), kind=kOutput")
    dense = ("%fusion.191 = f32[128,9216] fusion(%fusion.100, "
             "%weights__layers___0___ffn_w_up__.1), kind=kOutput")
    step = lambda t: [  # noqa: E731
        (t + 0.001, t + 0.003, first), (t + 0.003, t + 0.005, later),
        (t + 0.005, t + 0.0055, tail), (t + 0.0055, t + 0.006, pool),
        (t + 0.006, t + 0.008, latent), (t + 0.010, t + 0.014, moe),
        (t + 0.014, t + 0.015, shared), (t + 0.015, t + 0.017, dense)]
    view = {"runs": {"jit_step": [(0.0, 0.02), (0.15, 0.17)],
                     "jit_prefill": [(0.02, 0.12)]},
            "ops": step(0.0) + [
                (0.03, 0.10, loop),             # spans the kernel's calls
                (0.04, 0.05, first.replace("f32[128,", "f32[1,")),
                (0.10, 0.11, grouped), (0.11, 0.115, moe)] + step(0.15)}
    monkeypatch.setattr(hybrid_moe, "view", lambda s: view)
    for _, _, reader in cell["per_layer"]:
        if reader.__module__ == "_bench_readers_hybrid_moe":
            monkeypatch.setitem(reader.__globals__, "view", lambda s: view)
    stat_set("decode_latent_bytes", 2 * 49281 * 16 * 1280)
    got = bench_run.layer_metrics(cell, _sources(cell["config"]))
    assert got["jit_step_ms.serve"]["value"] == pytest.approx(20.0)
    assert got["jit_prefill_ms.serve"]["value"] == pytest.approx(100.0)
    # both kernel calls and the tail's fusion, not the pool's
    assert got["kda_step_ms.serve"]["value"] == pytest.approx(4.5)
    assert got["latent_step_ms.serve"]["value"] == pytest.approx(2.0)
    assert got["kda_prefill_ms.serve"]["value"] == pytest.approx(70.0)
    assert got["moe_ffn_ms_per_step.serve"]["value"] == pytest.approx(4.0)
    assert got["moe_prefill_ms.serve"]["value"] == pytest.approx(10.0)
    assert got["dense_ffn_ms_per_step.serve"]["value"] == pytest.approx(2.0)
    # 128 live slots x 6 layers x 2 x 2,097,152 B over 819 GB/s
    assert got["kda_step_roofline"]["value"] == pytest.approx(
        100 * 3221225472 / 819e9 / 4.5e-3)
    # one prefill run in the trace, 3,600 tokens x 6 layers a prefill
    assert got["kda_prefill_roofline"]["value"] == pytest.approx(
        100 * 79272345600 / 6.144e12 / 70e-3)
    need = 2 * (627200 * 1152 + 128 * 32 * 1088 * 4)
    assert need / 819e9 > 2 * 32 * 1088 * 627200 * 2 / 197e12
    assert got["latent_step_roofline.serve"]["value"] == pytest.approx(
        100 * need / 819e9 / 2e-3)
    assert got["latent_row_bytes.serve"]["value"] == 1280
    assert got["kv_bytes_per_token.serve"]["value"] == 2560
    assert got["state_bytes_per_slot.serve"]["value"] == 6 * 2244608
    assert got["prefill_tokens_per_scan_step.serve"]["value"] \
        == pytest.approx(3600 / 57)
    assert got["routed_experts_hit_share.serve"]["value"] == pytest.approx(
        100 * 220 / (32 * 7))
    assert got["prefill_keys_live_share.serve"]["value"] == 75.0
    assert all(0 < v["value"] <= 100 for k, v in got.items()
               if "roofline" in k)
    # the accepted pattern counts slabs from operand 2 (after a V pool):
    # it would lose this model's first layer, and take its pool for none
    old = re.compile(bench_run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "kda_ms_per_step.serve.json"))["params"]["pattern"])
    new = re.compile(bench_run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "kda_step_ms.serve.json"))["params"]["pattern"])
    assert not old.search(first) and old.search(later)
    assert new.search(first) and new.search(later) and new.search(tail) \
        and not new.search(pool) and not new.search(latent)


def test_the_new_readers_read_nothing_where_the_program_lacks_them():
    """The parent of this PR (no such model: the cell fails before any
    reader runs) and the other cells' configurations: no reader of this
    PR raises, each returns None."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    sources = dict(_sources(cell["config"]), trace=None)
    sources["serve"] = {"slots": 128, "counters": {
        "decode_steps": 2, "decode_tokens_total": 258, "decode_prefills": 2}}
    got = bench_run.layer_metrics(cell, sources)
    assert not [k for k in got if k in NEW or "roofline" in k]
    for other in ("solar_open2_250b.chat_closed_c128",
                  "kimi_k2_5.agent_closed_c64",
                  "gpt2_medium.chat_closed_c32"):
        config = bench_run.resolve_cell(ROOT, other)["config"]
        theirs = dict(_sources(config), trace=None)
        for _, _, reader in cell["per_layer"]:
            if reader.__module__.endswith("linear_latent"):
                assert reader(theirs, {"pattern": "x",
                                       "module": "jit_step"}) is None
