"""The indexed-attention cell rehearsed on the CPU at a tiny size (widths
cut HERE, never in the benchmark's files): the loader resolves it, its
kind runs it through the real server with the check that follows the
served routing AND the served selections, the check fails what it must
(the controls of ``indexed_moe_controls.py``, pools of the wrong size),
the bytes functions agree with hand counts and every reader the cell
brings returns a value - the trace's from a synthetic parsed trace,
since a CPU run has no device plane."""
import copy
import json
import math
import os

import pytest

from benchmark import flops_indexed_moe as fi
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe
from benchmark.readers import indexed_moe as readers
from benchmark.tests import indexed_moe_controls as controls
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "keye_vl2_30b.vreason_closed_c16"
TINY = {"model": dict(vocab_size=97, d_model=32, num_layers=2,
                      layer_kinds=["attention"] * 2, num_heads=4,
                      num_kv_heads=2, head_dim=8, index_heads=3,
                      index_dim=6, index_topk=12, index_block=4,
                      num_experts=16, top_k=4, held_experts=[0, 5],
                      expert_dim=16, rope_theta=1e4, dtype="float32"),
        "serving": dict(slots=4, max_seq_len=128, num_pages=None,
                        page_size=8, cache_dtype="float32")}
SERVE = dict(spec_overrides={
    "traffic": {"callers": 4, "prompt_len": [8, 40], "reply_len": [4, 40],
                "pool": 8, "stagger_s": 0.3},
    "serve": {"fill_s": 0.6},
    "check": {"prompt_len": [40, 60], "new_tokens": 12, "pad": 80,
              "logit_rms_rtol": 1e-5, "route_eps": 1e-6,
              "reroute_share": 0.0, "select_eps": 1e-6,
              "reselect_share": 0.0}})


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(rh.TINY, "keye_vl2_30b", copy.deepcopy(TINY))


@pytest.fixture
def rehearsed(tiny, monkeypatch):
    """``rehearse`` with the reference's row block cut to the tiny pad."""
    real = bench_run.resolve_cell

    def resolve(root, name):
        c = real(root, name)
        c["model"]._REFERENCE_BLOCK = 8
        return c

    monkeypatch.setattr(rh.bench_run, "resolve_cell", resolve)
    return lambda seconds=0.3: rehearse(CELL, seconds, **SERVE)


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    assert cell["chips"] == 1 and cell["spec"]["kind"] == "serve_indexed"
    assert cell["config"]["builder"] == "indexed_moe_lm"
    names = {e["name"] for e, _, _ in cell["per_layer"]}
    assert {"index_score_ms_per_step.serve", "index_select_ms_per_step.serve",
            "indexer_roofline.serve", "sparse_attn_ms_per_step.serve",
            "sparse_attn_roofline.serve", "selected_share_of_context.serve",
            "index_row_bytes.serve", "moe_experts_roofline",
            "jit_step_ms.serve"} <= names
    # no window of this cell holds a prefill or a first token (a request
    # outlasts set-up and window together): what reads one is not joined
    assert not names & {"jit_prefill_ms.serve", "moe_prefill_ms.serve",
                        "prefill_keys_live_share.serve",
                        "caller_ttft_p90_ms.serve"}
    # what reads every live row's bytes would read a sparse kernel at
    # over 100 %: not joined
    assert not {n for n in names if n.startswith("full_attn")}
    assert [m["name"] for m in cell["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    t = cell["spec"]["traffic"]
    assert (t["callers"], t["prompt_len"], t["reply_len"], t["pool"],
            t["stagger_s"]) == (16, [6144, 8192], [8192, 16384], 16, 16.0)
    assert cell["spec"]["serve"]["fill_s"] == 56.0
    assert cell["spec"]["trace_seconds"] == 4


def test_the_files_hold_the_catalog_entrys_numbers():
    cell = bench_run.resolve_cell(ROOT, CELL)
    cfg, entry = cell["config"], cell["config_entry"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 48
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_local_experts"], cfg["vocab_size"]) == (
        8, 16, 16, 18992)
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    sa, m = cfg["sa_config"], cfg["model"]
    assert (m["index_heads"], m["index_dim"], m["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert m["index_block"] == sa["q_chunk_size"] == sa["kv_chunk_size"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["expert_dim"], m["top_k"], m["num_experts"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["moe_intermediate_size"], cfg["num_experts_per_tok"], 128)
    assert m["rope_theta"] == cfg["rope_theta"] == 1e7
    s = cfg["serving"]
    assert s["num_pages"] == s["slots"] * (s["max_seq_len"] // 16 + 1) + 1
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert len(entry["why"]) <= 200 and len(cell["workload"]["why"]) <= 200


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


def test_the_kind_runs_the_cell_and_its_counter_readers_read(rehearsed):
    bench, result = rehearsed(2.0)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert chk["worst_logit_rel_err"] < 1e-4 and chk["worst_route_gap"] == 0
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    assert chk["worst_select_gap"] == 0 and chk["positions_reselected"] == 0
    assert chk["positions_selected"] > 0
    # 2 layers x (4 slots x 16 pages + trash) x 8 rows of one lane tile
    assert chk["index_bytes"] == chk["index_bytes_owed"] \
        == 2 * 65 * 8 * 128 * 4
    assert chk["kv_pool_bytes"] == chk["kv_pool_bytes_owed"] \
        == 2 * 65 * 8 * 2 * 16 * 4
    assert min(chk["prompt_lens"]) >= 40 and chk["positions"] == 12
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["moe_experts_hit"] > 0 and c["decode_prefix_bypassed"] > 0
    assert 0 < c["decode_index_positions_selected"] \
        <= c["decode_index_positions_scored"]
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "routed_experts_hit_share.serve",
            "index_row_bytes.serve", "selected_share_of_context.serve",
            "kv_bytes_per_token.serve", "caller_itl_p99_ms.serve"} <= set(got)
    assert 0 < got["routed_experts_hit_share.serve"]["value"] <= 100
    assert got["index_row_bytes.serve"]["value"] == 512
    # K and V of 2 heads of 8 and the stored index row, float32, 2 layers
    assert got["kv_bytes_per_token.serve"]["value"] == 2 * (128 + 512)
    assert 0 < got["selected_share_of_context.serve"]["value"] <= 100


def _served_model(monkeypatch, change):
    """The kind run with the SERVED model changed (the reference keeps
    the configuration's)."""
    real_resolve = rh.bench_run.resolve_cell

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
    n for n in controls.CONTROLS if n not in ("served", "bf16_router")])
def test_the_check_fails_a_served_model_that_is_not_the_references(
        rehearsed, monkeypatch, name):
    change, patch = controls.CONTROLS[name]
    # the tiny model's own sizes: 12 positions kept, theta 1e4
    if name == "topk_1024":
        change = lambda m: setattr(m, "index_topk", 6)  # noqa: E731
    if name == "theta_1e4":
        change = lambda m: setattr(m, "rope_theta", 1e2)  # noqa: E731
    if change:
        _served_model(monkeypatch, change)
    undo = patch() if patch else None
    try:
        _, result = rehearsed()
    finally:
        if undo:
            undo()
    assert not result["correct"], name
    chk = result["checks"]
    assert chk["worst_logit_rms_rel_err"] > 1e-5 \
        or chk["positions_reselected"] > 0 or chk["worst_select_gap"] > 0


def test_the_check_fails_pools_of_another_size(rehearsed, monkeypatch):
    """An index row padded past a whole lane tile (or a pool in 8 bits):
    the logits are right and the size is not."""
    from paddle_tpu.serving import kv_cache

    monkeypatch.setattr(kv_cache.IndexSpec, "row_lanes",
                        property(lambda self: 256))
    _, result = rehearsed()
    chk = result["checks"]
    assert not result["correct"]
    assert chk["worst_logit_rms_rel_err"] < 1e-5
    assert chk["index_bytes"] == 2 * chk["index_bytes_owed"]


def test_the_bytes_functions_against_hand_counts():
    # the published rows, bf16: 64 lanes of a key; K and V of 4 x 128
    assert fi.index_row_bytes(64, "bfloat16") == 128
    assert fi.kv_row_bytes(4, 128, "bfloat16") == 2048
    assert fi.position_bytes(4, 128, 64, "bfloat16") == 2176
    # 16 slots at 15,000 positions, 8 layers: 0.25 GB of keys a step
    assert fi.indexer_bytes(16 * 15000, 8, 64) == 16 * 15000 * 8 * 128 \
        == 245760000
    # ... and 16 x 2,048 selected rows of 2,048 B a layer: 0.54 GB
    assert fi.sparse_attention_bytes(16 * 2048, 8, 4, 128) \
        == 16 * 8 * 2048 * 2048 == 536870912


def _sources(config, spec):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.6, "count": 1}}},
        "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
        "config": config, "spec": spec,
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 34,
                               "decode_prefills": 2, "moe_experts_hit": 160,
                               "moe_local_assignments": 32,
                               "decode_index_positions_scored":
                                   2 * 16 * 15000,
                               "decode_index_positions_selected":
                                   2 * 16 * 2048,
                               "decode_prefill_keys_live": 30,
                               "decode_prefill_keys_attended": 40},
                  "slots": 16, "page_size": 16, "kv_bytes_per_token": 17408,
                  "decode_contexts": [15000] * 32,
                  "kv_pool_positions": 24593 * 16,
                  "index_pool_rows": 8 * 24593 * 16,
                  "gauges": {"decode_index_bytes": 805863424,
                             "decode_kv_pool_bytes": 6446907392 + 805863424},
                  "caller_ms": {"ttft_p90": 340.0, "itl_p99": 340.0}},
    }


def test_the_trace_readers_read_a_synthetic_trace(monkeypatch):
    """Two runs of ``jit_step`` and one of ``jit_prefill``; each pattern
    takes its own operations' events and only those inside a step, and
    both rooflines come out of the counters' bytes."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    gather = ("%fusion.4 = bf16[24576,16,128]{2,1,0} fusion(bf16[8,24593,"
              "16,128]{3,2,1,0} %state_2_.1, s32[24576]{0} %clamp)")
    score = ("%fusion.44 = f32[16,24576]{1,0} fusion(bf16[16,24576,128]"
             "{2,1,0} %bitcast.7, f32[16,16,64]{2,1,0} %fusion.195)")
    where = ("%fusion.65 = f32[16,24576]{1,0} fusion(f32[16,24576]{1,0} "
             "%fusion.44, s32[16]{0} %gte.42)")
    sort = ("%sort.1 = (f32[16,24576]{1,0}, s32[16,24576]{1,0}) sort(f32[16,"
            "24576]{1,0} %fusion.65, s32[16,24576]{1,0} %iota.1)")
    rows = ("%fusion.9 = bf16[32768,512]{1,0} fusion(bf16[3147904,512]{1,0} "
            "%bitcast.18, s32[32768]{0} %clamp.1)")
    attend = ("%fusion.12 = f32[16,32,2048]{2,1,0} fusion(bf16[16,2048,512]"
              "{2,1,0} %bitcast.3, bf16[16,32,512]{2,1,0} %convert.1)")
    other = "%fusion.99 = f32[16,2048]{1,0} fusion(f32[16,2048]{1,0} %x)"
    ms = 1e-3
    ops, t = [], 0.0
    for run in range(2):                    # two steps, 10 ms each
        base = run * 0.010
        for name, dur in ((gather, 0.5), (score, 0.3), (where, 0.01),
                          (sort, 0.44), (rows, 0.9), (attend, 0.1),
                          (other, 0.2)):
            ops.append((base + t, base + t + dur * ms, name))
            t += dur * ms
        t = 0.0
    ops.append((0.040, 0.040 + 2 * ms, score))      # a prefill's: not a step
    view = {"runs": {"jit_step": [(0.0, 0.010), (0.010, 0.020)],
                     "jit_prefill": [(0.030, 0.050)]},
            "ops": sorted(ops)}
    monkeypatch.setattr(hybrid_moe, "view", lambda sources: view)
    sources = _sources(cell["config"], cell["spec"])
    got = bench_run.layer_metrics(cell, sources)
    assert got["index_score_ms_per_step.serve"]["value"] \
        == pytest.approx(0.8)
    assert got["index_select_ms_per_step.serve"]["value"] \
        == pytest.approx(0.45)
    assert got["sparse_attn_ms_per_step.serve"]["value"] \
        == pytest.approx(1.0)
    # 245.76 MB of keys over 819 GB/s = 0.300 ms of 1.25 ms
    assert got["indexer_roofline.serve"]["value"] == pytest.approx(
        100 * 245760000 / 819e9 / 1.25e-3, rel=1e-6)
    # 536.9 MB of selected rows = 0.656 ms of 1.0 ms
    assert got["sparse_attn_roofline.serve"]["value"] == pytest.approx(
        100 * 536870912 / 819e9 / 1.0e-3, rel=1e-6)
    assert got["selected_share_of_context.serve"]["value"] \
        == pytest.approx(100 * 2048 / 15000)
    assert got["index_row_bytes.serve"]["value"] == 256
    assert got["kv_bytes_per_token.serve"]["value"] == 8 * (2048 + 256)


def test_the_new_readers_read_nothing_where_the_program_lacks_them(
        monkeypatch):
    """The parent's program (no such counters, gauges or operations) and
    a configuration without an indexer: every new reader returns None
    and raises nothing."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    monkeypatch.setattr(hybrid_moe, "view", lambda sources: {
        "runs": {"jit_step": [(0.0, 0.01)]},
        "ops": [(0.001, 0.002, "%fusion.1 = f32[16,2048]{1,0} fusion()")]})
    bare = _sources(cell["config"], cell["spec"])
    bare["serve"]["counters"] = {"decode_steps": 2}
    bare["serve"].pop("gauges")
    other = _sources({"model": {"num_layers": 2}, "serving": {}},
                     cell["spec"])
    new = [(e, f, r) for e, f, r in cell["per_layer"]
           if f["reader"].startswith("indexed_moe:")]
    assert len(new) == 7
    for sources in (bare, other):
        for entry, mfile, reader in new:
            assert reader(sources, mfile.get("params", {})) is None, \
                entry["name"]


def test_the_recorded_selections_unpack_to_the_served_masks():
    import numpy as np

    from paddle_tpu.ops import indexed_attention as ixa

    kind = bench_run.resolve_cell(ROOT, CELL)["kind"]
    rng = np.random.RandomState(0)
    mask = rng.rand(5, 2, 16) < 0.5
    mask[0] = False                             # a row under topk
    steps = [np.asarray([[0, 3, 5, -1], [1, 2, -1, -1]])]
    sel = kind.selections_of([np.asarray(ixa.pack_bits(mask))] + steps,
                             5, 6, 8, 2)
    assert len(sel) == 2 and sel[0].shape == (8, 8)
    assert sel[0][0].tolist() == [True] + [False] * 7   # every live one
    for t in range(1, 5):
        np.testing.assert_array_equal(
            sel[1][t, :t + 1], mask[t, 1, :t + 1])
        assert not sel[1][t, t + 1:].any()
    assert np.flatnonzero(sel[0][5]).tolist() == [0, 3, 5]
    assert np.flatnonzero(sel[1][5]).tolist() == [1, 2]
    assert np.flatnonzero(sel[0][7]).tolist() == [0]    # padding rows
