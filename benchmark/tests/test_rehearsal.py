"""Both kinds of cell rehearsed on the CPU at a tiny size, the training
kind also on four virtual devices, and the command's refusals.  What
this pins is control flow, arguments and the correctness checks - never
a time: the numbers a rehearsal prints are thrown away."""
import json
import os
import subprocess
import sys

from benchmark import run as bench_run
from benchmark.tests.overlay import apply_overlay
from benchmark.tests.rehearsal import CPU_PEAKS, ROOT, rehearse

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRAIN = dict(spec_overrides={"traffic": {"batch_per_chip": 8},
                             "train": {"steps_per_call": 3}})
SERVE = dict(spec_overrides={
    "traffic": {"callers": 4, "prompt_len": [8, 40], "reply_len": [4, 12],
                "pool": 8, "stagger_s": 0.3},
    "serve": {"fill_s": 0.6},
    "check": {"prompt_len": [20, 30], "pad": 48, "logit_rtol": 1e-4}})


def _metrics(bench, result):
    sources = dict(result["sources"], peaks=CPU_PEAKS,
                   config=bench.config, spec=bench.spec)
    return bench_run.layer_metrics(bench.cell, sources)


def test_training_kind_on_one_device():
    bench, result = rehearse("bert_base.pretrain_b256_s128", 1.0, **TRAIN)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == result["sources"]["train"]["steps"] > 0
    assert result["end_to_end"]["train_samples_s_chip"] > 0
    assert bench.setup_s > 0 and bench.compiles_in_window == 0
    chk = result["checks"]
    assert chk["loss_rel_err"] < chk["loss_rtol"]
    assert chk["encoder_out_rel_err"] < chk["probe_rtol"]
    assert chk["loss_fell"]
    # untraced, only the host-clock reader has something to read
    assert set(_metrics(bench, result)) == {"enqueue_ms_per_call.train"}


def test_training_check_fails_a_reference_that_skips_a_layer(monkeypatch):
    """The probe is what tells a broken layer from bf16 rounding.  At
    the tiny width a layer's branches are a hundredth of the residual
    (read here: 1.1e-2 with a layer skipped against 3.4e-3 without), so
    the bar is set between the two; at the real width the chip read
    0.38 against 0.009."""
    cell = bench_run.resolve_cell(ROOT, "bert_base.pretrain_b256_s128")
    real = cell["model"].reference
    def skewed(config, weights, batch):
        return real(config, weights, batch, skip_layer=1)

    import benchmark.tests.rehearsal as rh

    real_resolve = bench_run.resolve_cell

    def resolve(root, name):
        c = real_resolve(root, name)
        c["model"].reference = skewed
        return c

    monkeypatch.setattr(rh.bench_run, "resolve_cell", resolve)
    spec = dict(TRAIN["spec_overrides"], check={"probe_rtol": 0.007})
    _, result = rehearse("bert_base.pretrain_b256_s128", 0.5,
                         spec_overrides=spec)
    assert not result["correct"]
    assert result["checks"]["encoder_out_rel_err"] > 0.007


def test_training_kind_on_four_virtual_devices(tmp_path):
    """The four-chip cell is a workload file and a manifest entry."""
    import jax

    assert len(jax.devices()) >= 4
    root = apply_overlay(ROOT, os.path.join(DATA, "overlay_dp4"),
                         str(tmp_path))
    bench, result = rehearse("bert_base.pretrain_dp4_b1024", 1.0,
                             n_devices=4, root=root, **TRAIN)
    assert result["correct"], result["checks"]
    assert result["sources"]["train"]["chips"] == 4
    assert result["info"]["feed_devices"] == 4


def test_serving_kind():
    bench, result = rehearse("gpt2_medium.chat_closed_c32", 2.0, **SERVE)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 10
    e2e = result["end_to_end"]
    # every end-to-end metric the manifest names for the cell but set-up
    wanted = {m["name"] for m in bench.cell["end_to_end"]} - {"setup_s"}
    assert set(e2e) == wanted and all(v > 0 for v in e2e.values())
    assert {"ttft_p90_ms", "itl_p99_ms"} <= wanted
    c = result["sources"]["serve"]["counters"]
    # the client's clock and the program's counter saw the same tokens
    assert c["decode_tokens_total"] == round(
        e2e["serve_tok_s"] * result["sources"]["serve"]["span_s"])
    assert bench.compiles_in_window == 0
    got = _metrics(bench, result)
    assert {"slot_occupancy.serve", "decode_step_ms.serve"} <= set(got)
    assert 0 < got["slot_occupancy.serve"]["value"] <= 100


def test_serving_check_fails_wrong_logits(monkeypatch):
    cell = bench_run.resolve_cell(ROOT, "gpt2_medium.chat_closed_c32")
    real = cell["model"].reference_logits
    real_resolve = bench_run.resolve_cell

    def resolve(root, name):
        c = real_resolve(root, name)
        c["model"].reference_logits = \
            lambda config, w, t: real(config, w, t) * 1.2
        return c

    import benchmark.tests.rehearsal as rh

    monkeypatch.setattr(rh.bench_run, "resolve_cell", resolve)
    _, result = rehearse("gpt2_medium.chat_closed_c32", 0.5, **SERVE)
    assert not result["correct"]
    assert result["checks"]["worst_logit_rel_err"] > 0.1


def _run_command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_run_without_a_tpu():
    proc = _run_command(ROOT, "--workload", "bert_base.pretrain_b256_s128",
                        "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout and "needs a TPU" in proc.stderr


def test_the_command_fails_where_only_the_benchmark_is(tmp_path):
    """A directory with BENCHMARK.json and ``paths`` alone has no
    program to measure: non-zero, no result."""
    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(str(tmp_path), "--workload",
                        manifest["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


def test_nothing_under_benchmark_touches_a_device_at_import():
    code = """
import importlib, json, pkgutil
import benchmark
for m in pkgutil.walk_packages(benchmark.__path__, "benchmark."):
    if ".tests" not in m.name and not m.name.endswith("rehearse_compile"):
        importlib.import_module(m.name)
from jax._src import xla_bridge
print(json.dumps(sorted(xla_bridge._backends)))
"""
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == []
