"""BENCHMARK.json against the contract, as far as a test can hold it."""
import json
import os
import re

import pytest

from benchmark import run as bench_run
from benchmark.tests.rehearsal import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_shape_of_the_file(manifest):
    assert set(manifest) == KEYS
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(manifest["command"]) <= 32
    assert all(_line(w) for w in manifest["command"])
    assert 1 <= manifest["run_seconds"] <= 51
    for path in manifest["paths"]:
        assert PATH.match(path) and not path.startswith("/") \
            and ".." not in path
        assert os.path.isdir(os.path.join(ROOT, path))


def test_names_units_and_lines(manifest):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group in ("end_to_end", "per_layer"),
                    entry["name"]) not in seen
            seen.add((group in ("end_to_end", "per_layer"), entry["name"]))
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_cells_configs_and_four_chip_share(manifest):
    cells = manifest["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in manifest["end_to_end"])


def test_every_cell_resolves_and_reports_what_its_metrics_move(manifest):
    for w in manifest["workloads"]:
        cell = bench_run.resolve_cell(ROOT, w["name"])
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"], w["name"]
        for entry, mfile, reader in cell["per_layer"]:
            assert entry["moves"] in e2e, (w["name"], entry["name"])
            assert mfile["unit"] == entry["unit"]
            assert callable(reader)
        # the configuration's file names its source and what was cut
        config = cell["config"]
        assert config["source"] == cell["config_entry"]["source"]
        assert config["reduced"] == cell["config_entry"]["reduced"]
        assert "departures" in config and "assumed" in config
        for key in ("kind", "who", "why", "traffic", "check"):
            assert key in cell["spec"], (w["name"], key)


def test_files_under_paths_are_named_from_name_characters(manifest):
    for path in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), ROOT)
                assert PATH.match(rel), rel
