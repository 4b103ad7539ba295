"""readers/engine.py on a small trace recorded on the chip (one TPU v5
lite, PR 26; ``record_engine_trace.py``): a toy ``DecodeEngine`` (2
layers, 4 slots) serves two requests of three tokens inside
``bench/window``.  Read by hand first (``trace_reduce.py --describe``):
the engine thread is the "python" line that holds the ``serving/*``
spans, 27 of them in two iterations - lock_wait, admit (2 admitted), two
prefills (args, dispatch, sync, deliver each), reap, one decode step
(cow, args, dispatch, sync, deliver); then lock_wait, admit, reap and a
second step - and a last lock_wait and admit before the thread goes idle.  The spans' attributes are the
events' stats; the kernel's device events are named
``%paged_attention.<n> = ... custom-call(...)``.

The file is kept gzipped (1.3 MB raw, most of it the device events'
HLO text) and unpacked where ``run.py`` would have written it."""
import gzip
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark import trace_reduce as tr
from benchmark.readers import engine
from benchmark.tests.rehearsal import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "gpt2_medium.chat_closed_c32"
NEW = ("step_args_ms.serve", "step_sync_ms.serve",
       "engine_host_ms_per_step.serve", "h2d_uploads_per_step.serve",
       "engine_unspanned_share.serve", "prefill_host_ms.serve",
       "paged_attn_ms_per_step.serve")
PREFILL = ["serving/prefill_args", "serving/prefill_dispatch",
           "serving/prefill_sync", "serving/prefill_deliver"]
STEP = ["serving/step_cow", "serving/step_args", "serving/step_dispatch",
        "serving/step_sync", "serving/step_deliver"]


def _lay_out(root, trace_file, cell=CELL):
    """Put a trace where ``run.py`` writes a cell's: returns its path."""
    out = os.path.join(root, ".bench_runs", cell, "trace", "plugins",
                       "profile", "2026_09_27_11_58_00")
    os.makedirs(out)
    path = os.path.join(out, "tiny.xplane.pb")
    opener = gzip.open if trace_file.endswith(".gz") else open
    with opener(os.path.join(DATA, trace_file), "rb") as f, \
            open(path, "wb") as g:
        g.write(f.read())
    return path


@pytest.fixture
def readers():
    """{metric: (reader, params, kernels)} of the new metrics, as the
    manifest and the metric files give them.  ``run.py`` loads the
    reader's module from its file; here the function is taken from the
    imported module of the same file, so that a test can point its
    ``ROOT`` at a directory of its own."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    out = {}
    for entry, mfile, reader in cell["per_layer"]:
        if entry["name"] in NEW:
            module, _, fn = mfile["reader"].partition(":")
            assert module == "engine" and reader.__name__ == fn
            out[entry["name"]] = (getattr(engine, fn),
                                  mfile.get("params", {}),
                                  mfile.get("kernels", {}))
    return out


@pytest.fixture
def sources(tmp_path, monkeypatch, readers):
    """What ``run.py`` hands a reader after a traced run whose trace is
    the recorded one: the reduction with every metric's kernel patterns,
    the window's counters, the cell's name."""
    path = _lay_out(str(tmp_path), "tiny_engine_trace.xplane.pb.gz")
    monkeypatch.setattr(engine, "ROOT", str(tmp_path))
    kernels = {"paged_attention": 'custom_call_target="tpu_custom_call"'}
    for _, _, k in readers.values():
        kernels.update(k)
    return {
        "trace": tr.reduce(path, kernels=kernels),
        "spec": {"name": CELL},
        "serve": {"counters": {
            "decode_steps": 2,
            "decode_prefill_seconds": {"count": 2, "sum": 0.0052}}},
    }


def test_the_manifest_lists_the_new_metrics_for_the_serving_cell(readers):
    assert set(readers) == set(NEW)
    train = bench_run.resolve_cell(ROOT, "bert_base.pretrain_b256_s128")
    assert not {e["name"] for e, _, _ in train["per_layer"]} & set(NEW)


def test_the_engine_thread_and_its_two_iterations(sources):
    v = engine.view(sources)
    assert v["window"] == pytest.approx((0.043108729, 0.064728349))
    (rows,) = v["threads"]              # one engine thread
    names = [r[2] for r in rows]
    head = ["serving/lock_wait", "serving/admit"]
    assert names == (head + PREFILL + PREFILL + ["serving/reap"] + STEP
                     + head + ["serving/reap"] + STEP + head)
    # leaves: each ends before the next begins
    assert all(a[1] <= b[0] for a, b in zip(rows, rows[1:]))
    assert rows[1][3] == {"admitted": 2, "queued": 0}
    assert rows[2][3] == {"slot": 0, "bucket": 16, "req": "decode-000003",
                          "uploads": 6, "upload_bytes": 112}
    assert [r[3]["step"] for r in rows if r[2] == "serving/step_sync"] \
        == [4, 5]
    # parsed once a process
    assert engine.view(sources) is v


def test_the_new_metrics_on_the_recorded_trace(sources, readers):
    got = {name: reader(sources, params)
           for name, (reader, params, _) in readers.items()}
    # serving/step_args: 3.822 and 3.074 ms
    assert got["step_args_ms.serve"] == pytest.approx(3.447985, rel=1e-5)
    assert got["step_sync_ms.serve"] == pytest.approx(0.918000, rel=1e-5)
    # every span but the dispatches and syncs, 10.875 ms, over 2 steps
    assert got["engine_host_ms_per_step.serve"] == \
        pytest.approx(5.437395, rel=1e-5)
    # (6 + 6 + 11 + 11) uploads over 2 step dispatches
    assert got["h2d_uploads_per_step.serve"] == 17.0
    # from the first span's start to the last one's end (18.58 ms; the
    # toy engine's idle wait after it is cut by the trace's end and left
    # no event) 0.63 ms lie between spans
    assert got["engine_unspanned_share.serve"] == \
        pytest.approx(3.39598, rel=1e-4)
    assert got["prefill_host_ms.serve"] == pytest.approx(2.6)
    # 4 kernel events (2 layers x 2 steps), 23.958 us, over 2 runs
    assert got["paged_attn_ms_per_step.serve"] == \
        pytest.approx(0.011979, rel=1e-4)
    # the kernel named by its own name is the one "every tpu_custom_call"
    # finds in this cell
    kernel_s = sources["trace"]["kernel_s"]
    assert kernel_s["paged_attention_by_name"] == \
        pytest.approx(kernel_s["paged_attention"], rel=1e-9)


def test_nested_spans_are_counted_once(sources, readers):
    """A span inside a phase (``serving/migrate_export`` inside a
    deliver) adds nothing to the host's time."""
    v = engine.view(sources)
    reader, params, _ = readers["engine_host_ms_per_step.serve"]
    before = reader(sources, params)
    a, b, _, _ = next(r for r in v["threads"][0]
                      if r[2] == "serving/step_deliver")
    v["threads"][0].append((a + 1e-6, b - 1e-6, "serving/migrate_export", {}))
    assert reader(sources, params) == pytest.approx(before)


def test_a_program_without_the_spans_reads_nothing(tmp_path, monkeypatch,
                                                   readers):
    """The parent of the PR that added the spans: its trace has a window
    and device events and no ``serving/*`` span.  Every reader of a span
    returns None and none raises; the histogram is the parent's own."""
    path = _lay_out(str(tmp_path), "tiny_tpu_trace.xplane.pb")
    monkeypatch.setattr(engine, "ROOT", str(tmp_path))
    kernels = {}
    for _, _, k in readers.values():
        kernels.update(k)
    sources = {"trace": tr.reduce(path, kernels=kernels),
               "spec": {"name": CELL},
               "serve": {"counters": {
                   "decode_steps": 3,
                   "decode_prefill_seconds": {"count": 4, "sum": 0.0088}}}}
    got = {name: reader(sources, params)
           for name, (reader, params, _) in readers.items()}
    assert got.pop("prefill_host_ms.serve") == pytest.approx(2.2)
    assert set(got.values()) == {None}
    # and a run without a trace (--trace 0) reads nothing at all
    bare = {"serve": sources["serve"], "spec": sources["spec"]}
    for name, (reader, params, _) in readers.items():
        if name != "prefill_host_ms.serve":
            assert reader(bare, params) is None


def test_a_traced_rehearsal_reads_its_own_trace(tmp_path, monkeypatch):
    """End to end on the CPU at a tiny size: the serving kind under a
    profiler session that ``Bench.window`` opens, the program's spans
    written by the real engine, the readers finding the trace where
    ``run.py`` laid it.  Pins control flow and counts, never a time."""
    from benchmark.tests.overlay import apply_overlay
    from benchmark.tests.rehearsal import rehearse
    from benchmark.tests.test_rehearsal import SERVE

    empty = tmp_path / "overlay"
    empty.mkdir()
    (empty / "BENCHMARK.add.json").write_text("{}")
    root = apply_overlay(ROOT, str(empty), str(tmp_path / "checkout"))
    bench, result = rehearse(CELL, 1.0, trace=True, root=root, **SERVE)
    assert result["correct"], result["checks"]
    # the CPU's trace has no device plane, so the reduction is not made;
    # the span readers need only that the run was traced
    sources = dict(result["sources"], trace={"modules": {}},
                   spec=bench.spec, config=bench.config)
    got = bench_run.layer_metrics(bench.cell, sources)
    for name in NEW[:6]:
        assert name in got, (name, sorted(got))
    assert "paged_attn_ms_per_step.serve" not in got
    steps = result["sources"]["serve"]["counters"]["decode_steps"]
    assert steps > 0
    # 11 arrays a step plus the prefills' 6 each
    assert 11.0 <= got["h2d_uploads_per_step.serve"]["value"] <= 17.0
    assert got["engine_unspanned_share.serve"]["value"] < 20.0
    assert got["step_args_ms.serve"]["value"] > 0
    # through the sync now: a prefill's host time is at least its sync's
    assert got["prefill_host_ms.serve"]["value"] > 0
    assert json.dumps(got)              # plain numbers, as the line needs
