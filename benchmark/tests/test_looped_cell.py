"""The looped cell rehearsed on the CPU at a tiny size (widths cut HERE,
never in the benchmark's files): the loader resolves it, its kind runs it
through the real server, the check fails what it must (the controls of
``looped_controls.py``, pools of another size), the bytes functions agree
with hand counts and every reader the cell brings returns a value - the
trace's from a recorded fragment of the chip's own events, since a CPU
run has no device plane - and None where the program has nothing to
read."""
import copy
import json
import math
import os

import pytest

from benchmark import flops_looped as fl
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe, looped
from benchmark.tests import looped_controls as controls
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "ouro_2_6b.mathqa_closed_c16"
TINY = {"model": dict(vocab_size=97, d_model=32, num_layers=3, loops=4,
                      num_heads=2, head_dim=16, ffn_dim=48,
                      dtype="float32"),
        "serving": dict(slots=4, max_seq_len=64, num_pages=None,
                        page_size=8, cache_dtype="float32",
                        use_pallas="always", interpret=True)}
SERVE = dict(spec_overrides={
    "traffic": {"callers": 4, "prompt_len": [8, 30], "reply_len": [4, 30],
                "pool": 8, "stagger_s": 0.3},
    "serve": {"fill_s": 0.6},
    "check": {"prompt_len": [20, 30], "new_tokens": 12, "pad": 48,
              "logit_rms_rtol": 1e-5}})


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(rh.TINY, "ouro_2_6b", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_looped"
    assert cell["workload"]["chips"] == 1
    names = {e["name"] for e, _, _ in cell["per_layer"]}
    assert {"loop_pass_ms.serve", "loop_weights_roofline.serve",
            "loop_attn_roofline.serve", "loop_attn_ms_per_step.serve",
            "loop_small_ops_ms_per_step.serve",
            "loop_passes_per_token.serve", "kv_bytes_per_token.serve",
            "jit_step_ms.serve", "jit_prefill_ms.serve",
            "slot_occupancy.serve", "engine_host_ms_per_step.serve",
            "h2d_uploads_per_step.serve", "engine_unspanned_share.serve",
            "deliver_emit_ms_per_step.serve",
            "steps_in_flight_at_dispatch.serve", "caller_itl_p99_ms.serve",
            "caller_ttft_p90_ms.serve"} == names
    assert [e["name"] for e in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    # nothing cut: every width, the depth and the vocabulary as published
    assert config["reduced"] == []
    m = config["model"]
    assert (m["vocab_size"], m["d_model"], m["num_layers"], m["loops"],
            m["num_heads"], m["head_dim"], m["ffn_dim"], m["rope_theta"],
            m["rms_eps"]) == (
        config["vocab_size"], config["hidden_size"],
        config["num_hidden_layers"], config["total_ut_steps"],
        config["num_attention_heads"], config["head_dim"],
        config["intermediate_size"], config["rope_theta"],
        config["rms_norm_eps"])
    assert config["num_key_value_heads"] == m["num_heads"]
    model = cell["model"].make_model(config)
    assert model.cache_layers == 192
    assert model.max_seq_len == config["max_position_embeddings"]
    # 192 cache layers x 16 heads x 128 x (K, V) x 2 B
    assert cell["model"].kv_bytes_per_token(config) == 1572864 \
        == fl.kv_bytes_per_token(48, 4, 16, 128)
    sv, t = config["serving"], cell["spec"]["traffic"]
    assert sv["num_pages"] == sv["slots"] * 21 + 1 == 337
    assert sv["max_seq_len"] == t["prompt_len"][1] + t["reply_len"][1]
    assert config["memory"]["kv_pages_bytes"] == 337 * 16 * 1572864 \
        == 8480882688
    # the traffic of the issue, letter for letter
    assert (t["callers"], t["prompt_len"], t["reply_len"], t["pool"],
            t["stagger_s"], cell["spec"]["serve"]["fill_s"]) == (
        16, [64, 128], [96, 192], 32, 3.0, 10.0)


def test_the_files_hold_the_catalog_entrys_numbers():
    """Every number of the published config under its own key: nothing
    is reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Ouro-2.6B")
    config = bench_run.resolve_cell(ROOT, CELL)["config"]
    assert config["source"] == entry["source_url"]
    assert [k for k, v in entry["config"].items()
            if config.get(k) != v] == []


def test_the_built_model_is_the_size_the_file_says():
    import jax

    cell = bench_run.resolve_cell(ROOT, CELL)
    model = cell["model"].make_model(cell["config"])
    shapes = jax.tree_util.tree_leaves(
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    count = lambda s: math.prod(s.shape)  # noqa: E731
    assert sum(map(count, shapes)) == 2667974657 \
        == cell["config"]["parameters"]["built"]
    assert sum(count(s) * s.dtype.itemsize for s in shapes) \
        == cell["config"]["parameters"]["bytes"] \
        == cell["config"]["memory"]["weights_bytes"]


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    # 12 cache layers x (4 slots x 8 pages + trash) x 8 x (K, V) x 32 x 4
    assert chk["kv_pool_bytes"] == chk["kv_pool_bytes_owed"] \
        == 12 * 33 * 8 * 2 * 32 * 4
    assert min(chk["prompt_lens"]) >= 20 and chk["positions"] == 12
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["decode_loop_passes"] == 4 * (c["decode_steps"]
                                           + c["decode_prefills"])
    # a row would leave between the first pass and the last
    live = c["decode_tokens_total"] - c["decode_prefills"]
    assert 1000 * live < c["decode_loop_exit_mass"] < 4000 * live
    assert c["decode_prefix_pages_hit"] == 0        # random prompts
    assert result["sources"]["serve"]["gauges"] == {
        "decode_kv_pool_bytes": chk["kv_pool_bytes"],
        "decode_cache_layers": 12}
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "loop_passes_per_token.serve",
            "kv_bytes_per_token.serve", "caller_itl_p99_ms.serve",
            "caller_ttft_p90_ms.serve"} <= set(got)
    assert got["loop_passes_per_token.serve"]["value"] == 4.0
    assert got["kv_bytes_per_token.serve"]["value"] == 12 * 2 * 32 * 4


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


def test_the_controls_are_the_issues_six_and_the_served_model():
    assert list(controls.CONTROLS) == [
        "served", "three_passes", "no_norm_between_passes",
        "passes_share_kv", "no_output_norms", "rope_base_1e4",
        "int8_pages"]


@pytest.mark.parametrize("name", [
    "three_passes", "no_norm_between_passes", "passes_share_kv",
    "no_output_norms", "rope_base_1e4"])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        tiny, monkeypatch, name):
    change, knobs = controls.CONTROLS[name]
    assert not knobs
    _served_model(monkeypatch, change)
    _, result = rehearse(CELL, 0.3, **SERVE)
    chk = result["checks"]
    assert not result["correct"]
    assert chk["worst_logit_rms_rel_err"] > 1e-2
    # the pools keep their size: only the logits tell these
    assert chk["kv_pool_bytes"] == chk["kv_pool_bytes_owed"]


def test_the_check_fails_pages_in_eight_bits_by_their_bytes(tiny,
                                                            monkeypatch):
    """int8 pages with a scale a head a position: the logits move by
    what 8 bits cost and the pools are not the size the passes owe."""
    _, knobs = controls.CONTROLS["int8_pages"]
    monkeypatch.setitem(rh.TINY["ouro_2_6b"], "serving",
                        dict(TINY["serving"], **knobs))
    _, result = rehearse(CELL, 0.3, **SERVE)
    chk = result["checks"]
    assert not result["correct"]
    # 32 + 2 x 4 bytes a position a pool where float32 takes 128
    assert chk["kv_pool_bytes"] * 128 == chk["kv_pool_bytes_owed"] * 40
    assert 1e-4 < chk["worst_logit_rms_rel_err"] < 0.2


def test_the_check_fails_a_cache_that_keeps_one_pass(tiny, monkeypatch):
    """A model that declares a quarter of the cache layers (the family's
    reduced cache as a deployment would size it) fails by the bytes
    before any logit is read."""
    def shrink(model):
        model.cache_layers = model.num_layers
        model._cache_layer = lambda t, l: l

    _served_model(monkeypatch, shrink)
    _, result = rehearse(CELL, 0.3, **SERVE)
    chk = result["checks"]
    assert not result["correct"]
    assert chk["kv_pool_bytes"] * 4 == chk["kv_pool_bytes_owed"]


def test_the_bytes_functions_against_hand_counts():
    assert fl.layer_matrix_params(2048, 16, 128, 5632) == {
        "wq": 4194304, "wk": 4194304, "wv": 4194304, "wo": 4194304,
        "ffn_w_gate": 11534336, "ffn_w_up": 11534336,
        "ffn_w_down": 11534336}
    # a layer's matrices 51,380,224 elements; four reads of 48 layers
    # and one of the head, bf16: 19.93 GB, 24.3 ms at 819 GB/s
    assert fl.step_weight_bytes(48, 4, 2048, 16, 128, 5632, 49152) \
        == 2 * (4 * 48 * 51380224 + 2048 * 49152) == 19931332608
    assert fl.step_weight_bytes(48, 1, 2048, 16, 128, 5632, 49152,
                                "float32") \
        == 4 * (48 * 51380224 + 2048 * 49152)
    assert fl.kv_bytes_per_token(48, 4, 16, 128) == 1572864
    assert fl.kv_bytes_per_token(48, 1, 16, 128, "float32") == 786432


# a step's events as the chip named them (my chip run, PR 50; operands
# shortened): the loop over the passes, a pass's loop over the layers,
# and inside it the matmuls that cut their layer out of a stack, the
# copies of wq / wk into fast memory, the matmuls that read those copies,
# the kernel, a norm; outside the loops the head
_POOL = "bf16[192,337,16,2048]{3,2,1,0}"
_OUTER = f"%while.34 = (s32[], f32[16,2048], {_POOL}, {_POOL}, " \
    "bf16[48,5632,2048]{2,1,0}) while(%tuple.154), condition=%c, body=%b"
_INNER = f"%while.35 = (s32[], f32[16,2048], {_POOL}, {_POOL}, s32[48], " \
    "bf16[48,5632,2048]{2,1,0}) while(%tuple.146), condition=%c, body=%b"
_DOWN = ("%multiply_reduce_fusion.23 = (f32[16]{0}, f32[16,2048]{1,0}) "
         "fusion(bf16[48,5632,2048]{2,1,0} %get-tuple-element.1555, s32[] "
         "%get-tuple-element.1521, f32[16,5632]{1,0} %fusion.154, "
         "bf16[48,2048,5632]{2,1,0} %get-tuple-element.1557), kind=kOutput")
_GATE = ("%fusion.154 = f32[16,5632]{1,0} fusion(bf16[48,2048,5632]{2,1,0} "
         "%get-tuple-element.1556, s32[] %get-tuple-element.1521, "
         "f32[16,2048]{1,0} %get-tuple-element.1484), kind=kOutput")
_WQ_COPY = ("%constant_dynamic-slice_fusion.21 = bf16[1,2048,2048]{2,1,0} "
            "fusion(bf16[48,2048,2048]{2,1,0} %get-tuple-element.1564, "
            "s32[] %get-tuple-element.1521), kind=kLoop")
_Q = ("%fusion.149 = f32[16,16,128]{2,0,1} fusion(bf16[16,128,2048]{2,1,0} "
      "%bitcast.229, bf16[16,2048]{1,0} %multiply_convert_fusion.17), "
      "kind=kOutput")
_ATTN = ("%paged_attention.6 = f32[16,1,2048]{2,1,0} custom-call(s32[1]{0} "
         f"%bitcast.217, s32[320]{{0}} %get-tuple-element.1574, {_POOL} "
         '%get-tuple-element.1523), custom_call_target="tpu_custom_call"')
_NORM = ("%multiply_convert_fusion.17 = bf16[16,2048]{1,0} fusion("
         "f32[16,2048]{1,0} %get-tuple-element.1522), kind=kLoop")
_HEAD = ("%fusion.73 = f32[16,49152]{1,0} fusion(bf16[2048,49152]{1,0} "
         "%weights__lm_head__.1, f32[16,2048]{1,0} %while.25), "
         "kind=kOutput")


def _view():
    """Two runs of ``jit_step`` (two passes of one layer each, so that
    the arithmetic is by hand) around one of ``jit_prefill``."""
    ops = []
    for t0 in (0.0, 0.05):
        ops.append((t0 + 0.001, t0 + 0.019, _OUTER))
        for p0 in (t0 + 0.001, t0 + 0.010):
            ops += [(p0, p0 + 0.008, _INNER),
                    (p0, p0 + 0.001, _WQ_COPY),
                    (p0 + 0.001, p0 + 0.0015, _Q),
                    (p0 + 0.0015, p0 + 0.0035, _ATTN),
                    (p0 + 0.0035, p0 + 0.004, _NORM),
                    (p0 + 0.004, p0 + 0.006, _GATE),
                    (p0 + 0.006, p0 + 0.008, _DOWN)]
        ops.append((t0 + 0.019, t0 + 0.020, _HEAD))
    ops.append((0.03, 0.04, _GATE))             # the prefill's: no step's
    ops.sort(key=lambda e: (e[0], -e[1]))
    return {"runs": {"jit_step": [(0.0, 0.02), (0.05, 0.07)],
                     "jit_prefill": [(0.02, 0.05)]}, "ops": ops}


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.03, "count": 1}}},
        "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
        "config": config, "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 33,
                               "decode_prefills": 1,
                               "decode_loop_passes": 12},
                  "slots": 16, "page_size": 16,
                  "kv_bytes_per_token": 1572864,
                  "decode_contexts": [160] * 32,
                  "kv_pool_positions": 337 * 16,
                  "gauges": {"decode_kv_pool_bytes": 8480882688,
                             "decode_cache_layers": 192},
                  "caller_ms": {"ttft_p90": 78.0, "itl_p99": 118.0}},
    }


def test_the_trace_readers_read_a_recorded_fragment(monkeypatch):
    cell = bench_run.resolve_cell(ROOT, CELL)
    view = _view()
    # every copy of ``readers/hybrid_moe.py`` the readers reach (the
    # package's own, and the one the loader made from the file), once
    mods = {id(hybrid_moe.__dict__): hybrid_moe.__dict__}
    for _, _, reader in cell["per_layer"]:
        if reader.__module__.endswith("hybrid_moe"):
            mods[id(reader.__globals__)] = reader.__globals__
    for mod in mods.values():
        monkeypatch.setitem(mod, "view", lambda s: view)
    got = bench_run.layer_metrics(cell, _sources(cell["config"]))
    assert got["jit_step_ms.serve"]["value"] == pytest.approx(20.0)
    # the inner loop is the pass; the outer one holds it and is not one
    assert got["loop_pass_ms.serve"]["value"] == pytest.approx(8.0)
    assert got["loop_attn_ms_per_step.serve"]["value"] \
        == pytest.approx(4.0)
    # what READS a matrix: the copy of wq, gate, down (+ up), the head;
    # not the matmul over the copy, not the loops that carry the stacks
    read_s = 2 * (0.001 + 0.002 + 0.002) + 0.001
    need = 2 * (4 * 48 * 51380224 + 2048 * 49152)
    assert got["loop_weights_roofline.serve"]["value"] == pytest.approx(
        100 * need / 819e9 / read_s)
    # the rest of the leaves: q over the copy and the norm, two passes
    assert got["loop_small_ops_ms_per_step.serve"]["value"] \
        == pytest.approx(2 * (0.5 + 0.5))
    # 32 rows at context 160: 10 pages of 16 x 1,572,864 B each, a step
    assert got["loop_attn_roofline.serve"]["value"] == pytest.approx(
        100 * (32 * 160 * 1572864 / 2) / 819e9 / 4e-3)
    assert got["loop_passes_per_token.serve"]["value"] == 4.0
    assert got["kv_bytes_per_token.serve"]["value"] == 1572864
    pat = looped.reads_a_matrix(cell["config"]["model"])
    assert [bool(pat.search(n)) for n in (
        _DOWN, _GATE, _WQ_COPY, _HEAD, _Q, _ATTN, _NORM)] \
        == [True] * 4 + [False] * 3


def test_the_new_readers_read_nothing_where_the_program_lacks_them():
    """The parent of this PR: no loop counters or gauges, no trace; and
    another configuration's sizes."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    sources = dict(_sources(cell["config"]), trace=None)
    sources["serve"] = {"slots": 16, "counters": {
        "decode_steps": 2, "decode_tokens_total": 33, "decode_prefills": 1}}
    got = bench_run.layer_metrics(cell, sources)
    assert not [k for k in got if "loop" in k or "kv_bytes" in k]
    olmo = bench_run.resolve_cell(
        ROOT, "olmo_hybrid_7b.docqa_closed_c32")["config"]
    other = dict(_sources(olmo), trace=None)
    for name in ("loop_pass_ms", "loop_weights_roofline",
                 "loop_small_ops_ms_per_step"):
        assert getattr(looped, name)(other, {
            "pattern": "x", "module": "jit_step"}) is None
