"""Observability layer (paddle_tpu.observe): span tracer + Chrome-trace
export, log-bucketed latency histograms, Prometheus /metrics exposition,
and the Executor-fed step telemetry (StepTimer/MFU).

Reference parity: DeviceTracer -> profiler.proto -> tools/timeline.py
(SURVEY L11) and StatRegistry runtime counters, rebuilt TPU-native as an
in-process ring buffer + text exposition (no CUPTI, no proto hop).
"""
import json
import math
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, observe
from paddle_tpu.framework.program import Program, program_guard
from paddle_tpu.monitor import export_stats, stat_add, stat_reset
from paddle_tpu.observe.histogram import BUCKET_BOUNDS, Histogram


@pytest.fixture
def tracer_on():
    observe.clear()
    observe.enable()
    yield
    observe.disable()
    observe.clear()


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_count_sum_max_exact(self):
        h = Histogram("t")
        for v in (0.001, 0.002, 0.004, 0.1):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(0.107)
        assert h.max == 0.1

    def test_quantiles_within_bucket_resolution(self):
        h = Histogram("t")
        vals = [0.001] * 50 + [0.010] * 45 + [0.500] * 5
        for v in vals:
            h.observe(v)
        # log2 buckets: the estimate must land within one bucket (2x)
        # of the true quantile, and never above the exact max
        assert h.percentile(50) <= 0.002048  # bucket containing 1ms
        assert 0.008 <= h.percentile(95) <= 0.02
        assert h.percentile(99) <= h.max == 0.5

    def test_negative_and_nan_dropped(self):
        h = Histogram("t")
        h.observe(-1.0)
        h.observe(float("nan"))
        assert h.count == 0

    def test_out_of_range_goes_to_inf_bucket(self):
        h = Histogram("t")
        h.observe(1e9)  # way past the last finite bound
        rows = h.cumulative_buckets()
        assert rows[-1] == (math.inf, 1)
        assert rows[-2][1] == 0  # not in any finite bucket

    def test_bucket_bounds_are_log2_from_1us(self):
        assert BUCKET_BOUNDS[0] == 1e-6
        for lo, hi in zip(BUCKET_BOUNDS, BUCKET_BOUNDS[1:]):
            assert hi == pytest.approx(2 * lo)

    def test_stat_time_rides_export_stats(self):
        observe.histogram("obs_test_seconds").reset()
        from paddle_tpu.monitor import stat_time

        stat_time("obs_test_seconds", 0.25)
        stat_time("obs_test_seconds", 0.25)
        snap = dict(export_stats())
        assert snap["obs_test_seconds_count"] == 2
        assert snap["obs_test_seconds_max"] == pytest.approx(0.25)
        names = [n for n, _ in export_stats()]
        assert names == sorted(names)  # still one sorted snapshot


class TestPrometheus:
    def _parse(self, text):
        """Minimal exposition-format parser: name{labels} value."""
        metrics = {}
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            name_part, value = line.rsplit(" ", 1)
            metrics[name_part] = float(value)
        return metrics

    def test_counters_and_histogram_render(self):
        stat_reset()
        observe.histogram("step_time_seconds").reset()
        stat_add("executor_run", 7)
        observe.stat_time("step_time_seconds", 0.004)
        observe.stat_time("step_time_seconds", 0.016)
        text = observe.prometheus_text()
        m = self._parse(text)
        assert m["paddle_tpu_executor_run"] == 7
        assert m["paddle_tpu_step_time_seconds_count"] == 2
        assert m["paddle_tpu_step_time_seconds_sum"] == pytest.approx(0.02)
        # cumulative buckets: monotone, +Inf == count
        buckets = [(k, v) for k, v in m.items()
                   if k.startswith("paddle_tpu_step_time_seconds_bucket")]
        assert buckets, text
        counts = [v for _, v in buckets]
        assert counts == sorted(counts)
        assert m['paddle_tpu_step_time_seconds_bucket{le="+Inf"}'] == 2
        assert "# TYPE paddle_tpu_step_time_seconds histogram" in text

    def test_name_sanitization(self):
        observe.stat_time("weird name-with.chars_seconds", 0.001)
        text = observe.prometheus_text()
        assert "paddle_tpu_weird_name_with_chars_seconds_count" in text

    def test_metrics_route_over_real_http(self):
        from paddle_tpu.distributed.fleet.utils.http_server import KVServer

        observe.stat_time("step_time_seconds", 0.008)
        kv = KVServer(0)
        kv.start()
        try:
            url = f"http://127.0.0.1:{kv.port}/metrics"
            with urllib.request.urlopen(url, timeout=10) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                body = r.read().decode()
        finally:
            kv.stop()
        assert "paddle_tpu_step_time_seconds_bucket{" in body
        self._parse(body)  # parses clean


# ---------------------------------------------------------------------------
# tracer + timeline
# ---------------------------------------------------------------------------


class TestTracer:
    def test_disabled_span_is_noop(self):
        observe.disable()
        observe.clear()
        with observe.span("should_not_record"):
            pass
        assert observe.snapshot() == []

    def test_disabled_overhead_near_zero(self, monkeypatch):
        """ISSUE acceptance: tracer off => near-zero per-span cost, as
        counts no loaded CPU moves: of 10k disabled spans NONE reaches
        the ring buffer's ``begin`` or ``end`` (its two clock reads and
        its record), none is recorded and none is left open."""
        from paddle_tpu.observe import tracer

        observe.disable()
        observe.clear()
        ring_calls = []
        for name in ("begin", "end", "record"):
            monkeypatch.setattr(tracer._TRACER, name,
                                lambda *a, _n=name: ring_calls.append(_n))
        for _ in range(10_000):
            with observe.span("off", bytes=1):
                pass
        assert ring_calls == [] and observe.snapshot() == []
        assert tracer.open_spans() == [] and not tracer.recording()

    def test_nesting_and_args(self, tracer_on):
        with observe.span("outer", phase="x"):
            with observe.span("inner", bytes=128):
                pass
        recs = {r.name: r for r in observe.snapshot()}
        assert recs["inner"].depth == 1
        assert recs["inner"].parent == "outer"
        assert recs["inner"].args == {"bytes": 128}
        assert recs["outer"].depth == 0 and recs["outer"].parent is None
        assert recs["outer"].t_begin <= recs["inner"].t_begin
        assert recs["inner"].t_end <= recs["outer"].t_end

    def test_concurrent_threads_nest_independently(self, tracer_on):
        """Each thread gets its own parent stack: sibling threads never
        corrupt each other's nesting."""
        barrier = threading.Barrier(2)

        def work(tag):
            barrier.wait()
            for _ in range(20):
                with observe.span(f"{tag}/outer"):
                    with observe.span(f"{tag}/inner"):
                        pass

        ts = [threading.Thread(target=work, args=(f"t{i}",))
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        recs = observe.snapshot()
        assert len(recs) == 80
        for r in recs:
            tag = r.name.split("/", 1)[0]
            if r.name.endswith("/inner"):
                assert r.depth == 1 and r.parent == f"{tag}/outer"
            else:
                assert r.depth == 0 and r.parent is None
        # spans of different tags come from different threads
        tids = {r.name.split("/", 1)[0]: r.tid for r in recs}
        assert tids["t0"] != tids["t1"]

    def test_explicit_begin_end_respects_flag_and_stays_balanced(self):
        """The ring buffer of module-level begin()/end() is gated like
        span()'s; a begin made while disabled decides so for its end()
        too, so nesting stays correct even when the flag flips
        mid-pair."""
        observe.clear()
        observe.disable()
        observe.begin("off")
        observe.end()
        assert observe.snapshot() == []
        observe.begin("off2")  # disabled: not in the buffer
        observe.enable()
        try:
            with observe.span("live"):  # nested "under" it
                pass
        finally:
            observe.end()  # closes "off2", records nothing
            observe.disable()
        recs = observe.snapshot()
        assert [r.name for r in recs] == ["live"]
        assert recs[0].depth == 0 and recs[0].parent is None
        observe.clear()

    def test_ring_buffer_bounds_memory(self):
        t = observe.Tracer(capacity=8)
        for i in range(20):
            t.begin(f"s{i}")
            t.end()
        assert len(t.snapshot()) == 8
        assert t.dropped == 12
        assert t.snapshot()[-1].name == "s19"

    def test_chrome_trace_schema(self, tracer_on, tmp_path):
        with observe.span("a", k=1):
            with observe.span("b"):
                pass
        path = str(tmp_path / "trace.json")
        observe.export_chrome_trace(path)
        with open(path) as f:
            doc = json.load(f)  # schema-valid JSON
        evs = doc["traceEvents"]
        assert isinstance(evs, list)
        xs = [e for e in evs if e.get("ph") == "X"]
        assert {e["name"] for e in xs} == {"a", "b"}
        for e in xs:
            for field in ("name", "ph", "ts", "dur", "pid", "tid"):
                assert field in e, e
            assert e["dur"] >= 0
        # thread metadata present so Perfetto labels the lane
        assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
                   for e in evs)
        # nesting is containment on the shared lane
        a = next(e for e in xs if e["name"] == "a")
        b = next(e for e in xs if e["name"] == "b")
        assert a["tid"] == b["tid"]
        assert a["ts"] <= b["ts"]
        assert b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 1e-3


# ---------------------------------------------------------------------------
# executor integration (8-device mesh, acceptance scenario)
# ---------------------------------------------------------------------------


def _fleet_mlp():
    """2-layer MLP transpiled for 8-way data parallelism: its backward
    carries transpiler-marked c_allreduce_sum ops the fuse pass buckets."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.initializer import ConstantInitializer
    from paddle_tpu.optimizer import MomentumOptimizer
    from paddle_tpu.param_attr import ParamAttr

    main, startup = Program(), Program()
    main.random_seed = 1
    with program_guard(main, startup):
        x = layers.data("x", [8])
        y = layers.data("y", [1])
        h = layers.fc(x, 16, act="relu", param_attr=ParamAttr(
            initializer=ConstantInitializer(0.1)), bias_attr=False)
        pred = layers.fc(h, 1, param_attr=ParamAttr(
            initializer=ConstantInitializer(0.2)), bias_attr=False)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = MomentumOptimizer(0.05, 0.9)
        fleet.init(is_collective=True)
        fleet.distributed_optimizer(opt)
        fleet.minimize(loss)
    return main, startup, loss


class TestExecutorTelemetry:
    def test_mesh_run_produces_phase_and_collective_spans(self, tracer_on,
                                                          tmp_path):
        """ISSUE acceptance: Executor.run on the 8-device mesh with the
        tracer enabled -> Chrome trace with nested pass-pipeline /
        lowering / compile / execute spans AND per-collective spans
        carrying byte counts."""
        from paddle_tpu.distributed.parallel_env import (init_parallel_env,
                                                         reset_mesh)

        mesh = init_parallel_env()
        try:
            main, startup, loss = _fleet_mlp()
            scope = pt.framework.Scope()
            exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
            exe.run(startup, scope=scope)
            X = np.random.RandomState(0).randn(16, 8).astype("f4")
            Y = np.ones((16, 1), "f4")
            feed = {"x": X, "y": Y}
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            out = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            # pipelined dispatch: the executor/fetch span fires when the
            # fetches are actually READ (StepHandle materialization)
            np.asarray(out[0])
        finally:
            reset_mesh()

        recs = observe.snapshot()
        names = {r.name for r in recs}
        for phase in ("executor/run", "executor/pass_pipeline",
                      "executor/analysis", "executor/compile",
                      "executor/lowering", "executor/execute",
                      "executor/fetch"):
            assert phase in names, sorted(names)
        # per-pass span under the pipeline (fuse pass bucketed 2 grads)
        assert "pass/fuse_allreduce" in names
        # collective spans carry bytes + dtype
        colls = [r for r in recs if r.name.startswith("collective/")]
        assert any(r.name == "collective/c_allreduce_sum" for r in colls)
        for r in colls:
            if r.name == "collective/c_allreduce_sum":
                assert r.args and r.args["bytes"] > 0
                assert "float32" in r.args["dtype"]
        # nesting: lowering under compile, compile under run
        by_name = {r.name: r for r in recs}
        assert by_name["executor/lowering"].depth \
            > by_name["executor/compile"].depth
        assert by_name["executor/compile"].parent == "executor/run"
        # second run is a cache hit: an execute span at depth 1
        execs = [r for r in recs if r.name == "executor/execute"]
        assert any(r.parent == "executor/run" for r in execs)

        # the whole thing exports as schema-valid Chrome trace JSON
        path = str(tmp_path / "mesh_trace.json")
        observe.export_chrome_trace(path)
        with open(path) as f:
            doc = json.load(f)
        assert any(e.get("name") == "collective/c_allreduce_sum"
                   and e.get("args", {}).get("bytes", 0) > 0
                   for e in doc["traceEvents"])

    def test_step_timer_feeds_histogram_and_mfu_accounting(self):
        observe.reset_step_stats()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data("x", [4])
            y = layers.fc(x, 2, bias_attr=False)
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.framework.Scope()
        exe.run(startup, scope=scope)
        feed = {"x": np.ones((3, 4), "f4")}
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[y], scope=scope)
        s = observe.step_timer().summary()
        assert s["compiles"] >= 1
        assert s["steps"] >= 2  # non-compile runs
        assert s["step_time_s"]["count"] == s["steps"]
        assert s["step_time_s"]["p50"] > 0
        assert s["examples_per_sec"] > 0
        # fc(3x4 -> 2): matmul flops counted per step, batch-scaled
        assert s["flops_per_step"] >= 2 * 3 * 2 * 4
        assert "paddle_tpu_step_time_seconds_bucket{" \
            in observe.prometheus_text()

    def test_step_timer_counts_allreduce_bytes(self):
        from paddle_tpu.distributed.parallel_env import (init_parallel_env,
                                                         reset_mesh)

        observe.reset_step_stats()
        mesh = init_parallel_env()
        try:
            main, startup, loss = _fleet_mlp()
            scope = pt.framework.Scope()
            exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
            exe.run(startup, scope=scope)
            feed = {"x": np.zeros((16, 8), "f4"),
                    "y": np.zeros((16, 1), "f4")}
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        finally:
            reset_mesh()
        s = observe.step_timer().summary()
        # grads: 8x16 + 16x1 floats = 144 * 4 bytes reduced per step
        assert s["allreduce_bytes_per_step"] == 144 * 4

    def test_mfu_estimate_math(self):
        # 1 TFLOP in 0.1s = 10 TFLOP/s; at a 100-TFLOP/s peak -> 0.1
        assert observe.mfu_estimate(1e12, 0.1, peak_tflops=100.0) \
            == pytest.approx(0.1)
        assert observe.mfu_estimate(0.0, 0.1, peak_tflops=100.0) == 0.0
        assert observe.mfu_estimate(1e12, 0.0, peak_tflops=100.0) == 0.0

    def test_peak_comes_from_the_device_table_not_a_default(self):
        """FLAGS_device_peak_tflops unset: the live device's published
        peak from the one table; a device that is not in it (the CPU
        here) has NO peak - null in summaries, an error when asked."""
        from types import SimpleNamespace

        from paddle_tpu.observe import device_peaks

        v5e = device_peaks.device_peak(
            SimpleNamespace(device_kind="TPU v5 lite"))
        assert (v5e["bf16_tflops"], v5e["hbm_gbps"], v5e["hbm_gb"]) \
            == (197.0, 819.0, 16.0) and "TPU v5e" in v5e["source"]
        pt.set_flags({"FLAGS_device_peak_tflops": 0.0})
        assert device_peaks.device_peak() is None  # cpu: not in the table
        assert device_peaks.peak_tflops() is None
        with pytest.raises(ValueError, match="device_peaks"):
            observe.mfu_estimate(1e12, 0.1)
        pt.set_flags({"FLAGS_device_peak_tflops": 123.0})
        try:
            assert device_peaks.peak_tflops() == 123.0  # the flag wins
            assert device_peaks.peak_tflops(50.0) == 50.0  # an argument more
        finally:
            pt.set_flags({"FLAGS_device_peak_tflops": 0.0})


# ---------------------------------------------------------------------------
# serving lifecycle + hapi callback
# ---------------------------------------------------------------------------


class TestServingTelemetry:
    def test_batch_lifecycle_spans_and_latency_histogram(self, tracer_on,
                                                         tmp_path):
        import shutil
        import tempfile

        from paddle_tpu import serving
        from paddle_tpu.fluid import io as fluid_io
        from paddle_tpu.framework import unique_name
        from paddle_tpu.framework.place import _default_place
        from paddle_tpu.framework.scope import _switch_scope

        observe.histogram("serving_latency_seconds").reset()
        d = tempfile.mkdtemp(prefix="observe_serving_")
        try:
            main, startup = Program(), Program()
            with unique_name.guard(), program_guard(main, startup):
                x = layers.data("x", [4])
                out = layers.fc(x, 2, bias_attr=False)
            sc = pt.framework.Scope()
            exe = pt.Executor(_default_place())
            exe.run(startup, scope=sc)
            old = _switch_scope(sc)
            try:
                fluid_io.save_inference_model(d, ["x"], [out], exe, main)
            finally:
                _switch_scope(old)

            srv = serving.Server(d, serving.ServingConfig(
                batch_sizes=(1, 2, 4), batch_window_ms=1.0))
            srv.start()
            try:
                srv.infer({"x": np.ones((1, 4), "f4")})
                srv.infer({"x": np.ones((2, 4), "f4")})
            finally:
                srv.stop(drain=True)
        finally:
            shutil.rmtree(d, ignore_errors=True)

        names = {r.name for r in observe.snapshot()}
        for phase in ("serving/enqueue", "serving/coalesce", "serving/pad",
                      "serving/execute", "serving/reply"):
            assert phase in names, sorted(names)
        h = observe.histogram("serving_latency_seconds").summary()
        assert h["count"] == 2
        assert h["p50"] > 0
        # latency quantiles reach the /stats payload
        snap = dict(export_stats())
        assert snap["serving_latency_seconds_count"] == 2


class TestBenchmarkCallback:
    def test_fit_records_step_histogram_and_summary(self, capsys):
        import paddle_tpu.optimizer as optim
        from paddle_tpu import nn
        from paddle_tpu.hapi import BenchmarkCallback
        from paddle_tpu.hapi.model import InputSpec

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 1)

            def forward(self, x):
                return self.fc(x)

        model = pt.Model(Net(), inputs=[InputSpec([None, 4], "float32", "x")],
                         labels=[InputSpec([None, 1], "float32", "y")])
        model.prepare(optim.Adam(0.01, parameters=model.parameters()),
                      nn.MSELoss())
        X = np.random.RandomState(0).randn(16, 4).astype("f4")
        Y = np.ones((16, 1), "f4")
        cb = BenchmarkCallback(batch_size=8)
        model.fit(list(zip(X, Y)), batch_size=8, epochs=2, verbose=0,
                  callbacks=[cb])
        s = cb.last_summary
        assert s is not None
        assert s["steps"] > 0
        assert s["step_time_s"]["count"] == s["steps"]
        assert s["steps_per_sec"] > 0
        assert s["examples_per_sec"] > 0
        assert "[bench]" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# timeline CLI (satellite: dump a trace from any run, no code changes)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_timeline_cli_traces_a_script(tmp_path):
    import os
    import subprocess
    import sys

    script = tmp_path / "tiny.py"
    script.write_text(
        "import numpy as np\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu import layers\n"
        "from paddle_tpu.framework.program import Program, program_guard\n"
        "main, startup = Program(), Program()\n"
        "with program_guard(main, startup):\n"
        "    x = layers.data('x', [4])\n"
        "    y = layers.fc(x, 2)\n"
        "exe = pt.Executor(pt.CPUPlace())\n"
        "scope = pt.framework.Scope()\n"
        "exe.run(startup, scope=scope)\n"
        "exe.run(main, feed={'x': np.ones((3, 4), 'f4')},\n"
        "        fetch_list=[y], scope=scope)\n")
    out = tmp_path / "trace.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observe.timeline",
         str(out), str(script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)
    assert r.returncode == 0, r.stderr[-2000:]
    with open(out) as f:
        doc = json.load(f)
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "executor/run" in names
    assert "executor/lowering" in names
