"""A new configuration, cell and per-layer metric come as files and
manifest entries only: laid over a copy of the benchmark, the loader of
``run.py`` resolves the cell - and the kind runs it - with no edit to a
file that is there."""
import os

import pytest

from benchmark import run as bench_run
from benchmark.tests.overlay import apply_overlay
from benchmark.tests.rehearsal import ROOT, rehearse

OVERLAY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "overlay_new_cell")


def test_the_loader_resolves_a_cell_added_as_files(tmp_path):
    root = apply_overlay(ROOT, OVERLAY, str(tmp_path))
    cell = bench_run.resolve_cell(root, "toy_lm.chat_closed_c4")
    assert cell["config"]["model"]["n_embd"] == 32
    assert cell["spec"]["traffic"]["callers"] == 4
    names = [entry["name"] for entry, _, _ in cell["per_layer"]]
    assert names == ["steps_per_request.serve"]
    # the reader came from the overlay's own file
    reader = cell["per_layer"][0][2]
    assert reader.__module__ == "_bench_readers_toy"
    # and the cells that were there still resolve, unchanged
    old = bench_run.resolve_cell(root, "gpt2_medium.chat_closed_c32")
    assert "steps_per_request.serve" not in [
        e["name"] for e, _, _ in old["per_layer"]]


def test_the_added_cell_runs_and_its_metric_is_read(tmp_path):
    root = apply_overlay(ROOT, OVERLAY, str(tmp_path))
    bench, result = rehearse("toy_lm.chat_closed_c4", 1.0, root=root)
    assert result["correct"], result["checks"]
    got = bench_run.layer_metrics(bench.cell, result["sources"])
    assert got["steps_per_request.serve"]["unit"] == "steps"
    assert got["steps_per_request.serve"]["value"] > 0


def test_an_overlay_may_not_replace_a_file(tmp_path):
    bad = tmp_path / "overlay"
    (bad / "benchmark").mkdir(parents=True)
    (bad / "benchmark" / "flops.py").write_text("# an edit\n")
    (bad / "BENCHMARK.add.json").write_text("{}")
    with pytest.raises(FileExistsError, match="flops.py"):
        apply_overlay(ROOT, str(bad), str(tmp_path / "out"))


def test_a_missing_piece_is_named(tmp_path):
    root = apply_overlay(ROOT, OVERLAY, str(tmp_path))
    os.remove(os.path.join(root, "benchmark", "readers", "toy.py"))
    with pytest.raises(bench_run.BenchmarkError, match="toy"):
        bench_run.resolve_cell(root, "toy_lm.chat_closed_c4")
    with pytest.raises(bench_run.BenchmarkError, match="no workload"):
        bench_run.resolve_cell(root, "nothing.here")
