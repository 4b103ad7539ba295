"""The hybrid linear/softmax-attention cell rehearsed on the CPU at a
tiny size (widths cut HERE, never in the benchmark's files): the loader
resolves it, its kind runs it through the real server with the check
that follows the served routing, and every reader it brings returns a
value - the trace's from a synthetic parsed trace, since a CPU run has
no device plane."""
import copy

import pytest

from benchmark import flops_hybrid_moe
from benchmark import run as bench_run
from benchmark.readers import hybrid_moe
from benchmark.tests import rehearsal as rh
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

CELL = "solar_open2_250b.chat_closed_c128"
TINY = {"model": dict(vocab_size=97, d_model=32, num_heads=4, num_kv_heads=2,
                      head_dim=8, lin_heads=2, lin_head_dim=8, gate_rank=4,
                      num_experts=16, top_k=4, held_experts=[0, 5],
                      expert_dim=16, shared_dim=16, dtype="float32"),
        "serving": dict(slots=4, max_seq_len=128, num_pages=None,
                        cache_dtype="float32", use_pallas="always",
                        interpret=True)}
SERVE = dict(spec_overrides={
    "traffic": {"callers": 4, "prompt_len": [8, 40], "reply_len": [4, 12],
                "pool": 8, "stagger_s": 0.3},
    "serve": {"fill_s": 0.6},
    "check": {"prompt_len": [20, 30], "pad": 48, "logit_rms_rtol": 1e-5,
              "route_eps": 1e-6, "reroute_share": 0.0}})


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(rh.TINY, "solar_open2_250b", copy.deepcopy(TINY))


def test_the_loader_resolves_the_cell_and_its_configuration():
    cell = bench_run.resolve_cell(ROOT, CELL)
    config = cell["config"]
    assert cell["spec"]["kind"] == "serve_routed"
    names = {e["name"] for e, _, _ in cell["per_layer"]}
    assert {"kda_state_roofline", "moe_experts_roofline",
            "jit_step_ms.serve", "h2d_uploads_per_step.serve"} <= names
    assert "decode_attn_roofline" not in names
    # every width as published; depth, experts held and vocabulary cut
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    m = config["model"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["lin_heads"], m["lin_head_dim"], m["conv_kernel"],
            m["num_experts"], m["top_k"], m["expert_dim"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["linear_attn_config"]["num_heads"],
        config["linear_attn_config"]["head_dim"],
        config["linear_attn_config"]["short_conv_kernel_size"],
        config["published"]["n_routed_experts"],
        config["num_experts_per_tok"], config["moe_intermediate_size"])
    lo, hi = m["held_experts"]
    assert hi - lo == config["n_routed_experts"] == 40
    assert m["vocab_size"] == config["vocab_size"] == 196608 // 8
    assert len(m["layer_kinds"]) == config["num_hidden_layers"] == 4
    assert cell["model"].kv_bytes_per_token(config) == 4096
    sv = config["serving"]
    assert sv["num_pages"] == sv["slots"] * 97 + 1


def test_the_kind_runs_the_cell_and_its_counter_readers_read(tiny):
    bench, result = rehearse(CELL, 2.0, **SERVE)
    assert result["correct"], result["checks"]
    chk = result["checks"]
    assert chk["worst_logit_rel_err"] < 1e-4 and chk["worst_route_gap"] == 0
    assert 0 < chk["worst_logit_rms_rel_err"] < 1e-5
    assert result["failed"] == 0 and result["attempted"] > 5
    c = result["sources"]["serve"]["counters"]
    assert c["moe_experts_hit"] > 0 and c["decode_prefix_bypassed"] > 0
    assert c["moe_experts_hit"] <= c["moe_local_assignments"]
    sources = dict(result["sources"], peaks=CPU_PEAKS, config=bench.config,
                   spec=bench.spec)
    got = bench_run.layer_metrics(bench.cell, sources)
    assert {"slot_occupancy.serve", "experts_hit_share.serve",
            "caller_itl_p99_ms.serve", "caller_ttft_p90_ms.serve"} <= set(got)
    assert 0 < got["experts_hit_share.serve"]["value"] <= 100
    # the callers' tails, per-layer readings here: what the window line's
    # ladder of the client's clock holds
    tails = result["sources"]["serve"]["caller_ms"]
    assert got["caller_itl_p99_ms.serve"]["value"] == tails["itl_p99"] > 0
    assert got["caller_ttft_p90_ms.serve"]["value"] == tails["ttft_p90"] > 0


def test_the_check_fails_a_router_the_reference_does_not_bear_out(
        tiny, monkeypatch):
    """A served router that ranks by other scores than the reference's
    picks experts far below the reference's k-th score: the gap fails the
    check, whatever the logits."""
    from paddle_tpu.ops import moe_ops

    real = moe_ops.moe_share_route

    def skewed(h, router_w, router_bias, **kw):
        return real(h, router_w[:, ::-1], router_bias, **kw)

    monkeypatch.setattr(moe_ops, "moe_share_route", skewed)
    _, result = rehearse(CELL, 0.5, **SERVE)
    assert not result["correct"]
    assert result["checks"]["worst_route_gap"] > 0.01
    assert result["checks"]["rerouted_share"] > 0.5


def test_the_check_fails_a_recurrent_state_kept_in_bfloat16(
        tiny, monkeypatch):
    """Half the bytes: the logits would not tell (the workload file has
    the chip's readings), the state's size does."""
    import jax.numpy as jnp

    real_resolve = bench_run.resolve_cell

    def resolve(root, name):
        c = real_resolve(root, name)
        make = c["model"].make_model

        def make_bf16(config):
            model = make(config)
            model.recurrent_state = {
                n: (shape, jnp.bfloat16 if n == "s" else dt)
                for n, (shape, dt) in model.recurrent_state.items()}
            return model

        c["model"].make_model = make_bf16
        return c

    monkeypatch.setattr(rh.bench_run, "resolve_cell", resolve)
    _, result = rehearse(CELL, 0.5, **dict(SERVE, spec_overrides=dict(
        SERVE["spec_overrides"], check=dict(
            SERVE["spec_overrides"]["check"], logit_rms_rtol=0.5))))
    chk = result["checks"]
    assert not result["correct"]
    assert chk["state_bytes"] < chk["state_bytes_float32"]
    assert chk["worst_logit_rel_err"] < 0.5


def _sources(config):
    return {
        "trace": {"modules": {"jit_step": {"total_s": 0.04, "count": 2},
                              "jit_prefill": {"total_s": 0.03, "count": 1}}},
        "peaks": {"hbm_gbps": 819.0}, "config": config,
        "spec": {"name": CELL},
        "serve": {"counters": {"decode_steps": 2, "decode_tokens_total": 260,
                               "decode_prefills": 4, "moe_experts_hit": 300,
                               "moe_local_assignments": 900},
                  "slots": 128, "page_size": 16, "kv_bytes_per_token": 4096,
                  "decode_contexts": [500] * 256,
                  "caller_ms": {"ttft_p90": 110.0, "itl_p99": 170.0}},
    }


def test_the_trace_readers_read_a_synthetic_trace(monkeypatch):
    """Two runs of ``jit_step`` and one of ``jit_prefill``; each pattern
    takes its own events and only those that start inside a step."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    kda = ("%add_select_fusion = (f32[128,64,128,128]) fusion(%state_6_.1, "
           "%custom-call.50, %state_4_.1, %state_2_.1), kind=kLoop")
    moe = ("%fusion.189 = f32[128,51200] fusion(%fusion.100, "
           "%weights__layers___0___moe_w_gate__.1), kind=kOutput")
    paged = ('%paged_attention.3 = f32[128,8,1024] custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    pools = "%scatter.1 = bf16[1,12417,16,1024] scatter(%state_0_.1, %b)"
    view = {"runs": {"jit_step": [(0.0, 0.02), (0.05, 0.07)],
                     "jit_prefill": [(0.02, 0.05)]},
            "ops": [(0.001, 0.009, kda), (0.010, 0.016, moe),
                    (0.016, 0.017, paged), (0.017, 0.018, pools),
                    (0.03, 0.04, moe),          # the prefill's: not a step's
                    (0.051, 0.059, kda), (0.060, 0.066, moe),
                    (0.066, 0.067, paged)]}
    # the loader runs each reader file as a module of its own
    for _, _, reader in cell["per_layer"]:
        if reader.__module__.endswith("hybrid_moe"):
            monkeypatch.setitem(reader.__globals__, "view", lambda s: view)
    got = bench_run.layer_metrics(cell, _sources(cell["config"]))
    assert got["jit_step_ms.serve"]["value"] == pytest.approx(20.0)
    assert got["jit_prefill_ms.serve"]["value"] == pytest.approx(30.0)
    assert got["caller_itl_p99_ms.serve"]["value"] == 170.0
    assert got["caller_ttft_p90_ms.serve"]["value"] == 110.0
    assert got["kda_ms_per_step.serve"]["value"] == pytest.approx(8.0)
    assert got["moe_ffn_ms_per_step.serve"]["value"] == pytest.approx(6.0)
    # 128 live slots x 3 layers x 2 x 4.19 MB over 819 GB/s = 3.93 ms
    assert flops_hybrid_moe.kda_state_bytes(1, 3, 64, 128) == 6 * 4194304
    assert got["kda_state_roofline"]["value"] == pytest.approx(
        100 * 128 * 6 * 4194304 / 819e9 / 8e-3)
    # 150 experts hit a step x 31.5 MB over 819 GB/s = 5.76 ms
    assert got["moe_experts_roofline"]["value"] == pytest.approx(
        100 * 150 * 3 * 4096 * 1280 * 2 / 819e9 / 6e-3)
    assert got["paged_attn_roofline.serve"]["value"] == pytest.approx(
        100 * (256 * 512 * 4096 / 2) / 819e9 / 1e-3)
    assert got["experts_hit_share.serve"]["value"] == pytest.approx(
        100 * 150 / 160)
    assert all(v["value"] <= 100 for k, v in got.items() if "roofline" in k)


def test_the_trace_readers_read_nothing_without_a_trace():
    cell = bench_run.resolve_cell(ROOT, CELL)
    sources = dict(_sources(cell["config"]), trace=None)
    got = bench_run.layer_metrics(cell, sources)
    assert not [k for k in got if "roofline" in k
                or ("_ms" in k and not k.startswith("caller_"))]
    # a program without these operations (the parent): a trace, no match
    assert hybrid_moe.ops_in_runs({"runs": {"jit_step": [(0, 1)]},
                                   "ops": [(0.1, 0.2, "%x = f32[] add()")]},
                                  "%state_2_", "jit_step")[0] == 0.0
