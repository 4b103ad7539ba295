"""Profiler subsystem: trace capture + RecordEvent annotations.

Reference parity: python/paddle/fluid/profiler.py:131/:198/:255 and the
RecordEvent scoped annotations (platform/profiler.cc:53).
"""
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.framework.program import Program, program_guard


def _tiny_run(tmp_scope):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", [4])
        y = layers.fc(x, size=2)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=tmp_scope)
    return exe.run(main, feed={"x": np.ones((3, 4), "float32")},
                   fetch_list=[y], scope=tmp_scope)


def test_profiler_context_manager_captures_trace(tmp_path):
    out = str(tmp_path / "trace")
    scope = pt.framework.Scope()
    with profiler.profiler(profile_path=out):
        with profiler.RecordEvent("tiny_step"):
            _tiny_run(scope)
    # jax dumps plugins/profile/<date>/*.xplane.pb under the trace dir
    found = []
    for root, _dirs, files in os.walk(out):
        found.extend(f for f in files if f.endswith((".xplane.pb", ".json.gz",
                                                     ".trace.json.gz")))
    assert found, f"no trace artifacts written under {out}"


def test_start_stop_and_double_start_rejected(tmp_path):
    out = str(tmp_path / "trace2")
    profiler.start_profiler(profile_path=out)
    with pytest.raises(RuntimeError):
        profiler.start_profiler(profile_path=out)
    assert profiler.stop_profiler() == out
    with pytest.raises(RuntimeError):
        profiler.stop_profiler()


def test_record_event_without_capture_is_noop():
    with profiler.RecordEvent("outside_capture"):
        pass


def test_stop_profiler_resets_dir_and_t0(tmp_path):
    out = str(tmp_path / "trace3")
    profiler.start_profiler(profile_path=out)
    assert profiler._state["dir"] == out
    assert profiler._state["t0"] is not None
    assert profiler.stop_profiler() == out
    # full state reset: a later capture must never see this one's
    # dir/t0 (previously they leaked until process exit)
    assert profiler._state == {"running": False, "dir": None, "t0": None}


def test_failed_start_does_not_wedge_running_check(tmp_path, monkeypatch):
    """A start_trace failure must roll the state back so the process
    can still profile later (previously the pre-set 'running' flag — or
    a partially-updated dir — wedged every subsequent start)."""
    import jax

    def boom(*a, **k):
        raise RuntimeError("synthetic capture failure")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="synthetic"):
        profiler.start_profiler(profile_path=str(tmp_path / "bad"))
    assert profiler._state == {"running": False, "dir": None, "t0": None}
    monkeypatch.undo()
    # the profiler still works after the failure
    out = str(tmp_path / "good")
    profiler.start_profiler(profile_path=out)
    assert profiler.stop_profiler() == out


def test_record_event_dual_feeds_observe_tracer():
    """RecordEvent spans land in the observe ring buffer when
    FLAGS_enable_tracer is set — no XLA capture needed."""
    from paddle_tpu import observe

    observe.clear()
    observe.enable()
    try:
        with profiler.RecordEvent("outer_evt"):
            with profiler.RecordEvent("inner_evt"):
                pass
    finally:
        observe.disable()
    recs = {r.name: r for r in observe.snapshot()}
    assert recs["inner_evt"].parent == "outer_evt"
    assert recs["inner_evt"].depth == 1
    observe.clear()


def test_record_event_spans_nest_under_concurrent_threads():
    import threading

    from paddle_tpu import observe

    observe.clear()
    observe.enable()
    try:
        barrier = threading.Barrier(2)

        def work(tag):
            barrier.wait()
            with profiler.RecordEvent(f"{tag}_outer"):
                with profiler.RecordEvent(f"{tag}_inner"):
                    pass

        ts = [threading.Thread(target=work, args=(f"w{i}",))
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        observe.disable()
    recs = {r.name: r for r in observe.snapshot()}
    for tag in ("w0", "w1"):
        assert recs[f"{tag}_inner"].parent == f"{tag}_outer"
    assert recs["w0_outer"].tid != recs["w1_outer"].tid
    observe.clear()


def test_shared_record_event_is_reentrant_and_thread_safe():
    """ONE RecordEvent instance used via the explicit begin()/end() API
    reentrantly and from multiple threads: every pair must record its
    own span with correct nesting (per-call state, not per-instance)."""
    import threading

    from paddle_tpu import observe

    observe.clear()
    observe.enable()
    ev = profiler.RecordEvent("shared")
    try:
        ev.begin()
        ev.begin()  # reentrant on one thread
        ev.end()
        ev.end()
        barrier = threading.Barrier(2)

        def work():
            barrier.wait()
            for _ in range(10):
                ev.begin()
                ev.end()

        ts = [threading.Thread(target=work) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        observe.disable()
    recs = [r for r in observe.snapshot() if r.name == "shared"]
    assert len(recs) == 22  # 2 reentrant + 20 threaded, none lost
    inner = [r for r in recs if r.depth == 1]
    assert len(inner) == 1 and inner[0].parent == "shared"
    observe.clear()


def test_exported_timeline_is_schema_valid_chrome_trace(tmp_path):
    """Tracer-driven Executor run -> export -> valid Chrome trace JSON
    (the tools/timeline.py parity path, no CUPTI/XLA capture)."""
    import json

    from paddle_tpu import observe

    observe.clear()
    observe.enable()
    try:
        scope = pt.framework.Scope()
        _tiny_run(scope)
    finally:
        observe.disable()
    path = str(tmp_path / "host_trace.json")
    observe.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    assert {"executor/run", "executor/lowering"} <= {e["name"] for e in xs}
    for e in xs:
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    observe.clear()


def test_tracer_disabled_run_overhead_is_negligible():
    """ISSUE acceptance: tracer off => the instrumented Executor.run
    path costs ~nothing extra, as counts no loaded CPU moves: a warm
    run with the tracer off leaves NO span in the ring buffer and none
    open, and what the same run opens with the tracer on (each a flag
    read and a null profiler annotation when it is off) stays a
    handful."""
    from paddle_tpu import observe
    from paddle_tpu.observe import tracer

    scope = pt.framework.Scope()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        y = layers.fc(layers.data("x", [4]), size=2)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)

    def warm_runs(n):
        observe.clear()
        for _ in range(n):
            exe.run(main, feed={"x": np.ones((3, 4), "float32")},
                    fetch_list=[y], scope=scope)
        return len(observe.snapshot())

    observe.disable()
    warm_runs(1)                        # compiled: the runs below are warm
    assert warm_runs(20) == 0 and tracer.open_spans() == []
    observe.enable()
    try:
        a_run = warm_runs(20) / 20
    finally:
        observe.disable()
        observe.clear()
    assert 1 <= a_run <= 8, a_run
