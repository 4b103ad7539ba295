"""Flight recorder, stall watchdog, postmortem bundles, cluster health.

The device-failure diagnosability plane (paddle_tpu/observe/flight.py +
health.py): bounded structured event ring with run metadata and
lifecycle events, a watchdog that converts a hung device call into a
readable postmortem bundle, per-rank heartbeats over the real fleet KV
HTTP server with rank-0 aggregation (straggler skew, liveness), and the
``python -m tools.postmortem`` bundle reader.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, observe
from paddle_tpu.framework.program import Program, program_guard
from paddle_tpu.monitor import stat_get
from paddle_tpu.observe import flight, health

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_plane():
    """Each test starts with an empty flight ring, no watchdog, no
    crash hook, and the default flags."""
    flight.clear_events()
    yield
    health.stop_watchdog()
    health.uninstall_crash_handler()
    pt.set_flags({"FLAGS_flight_recorder": True,
                  "FLAGS_flight_recorder_file": "",
                  "FLAGS_flight_recorder_max_mb": 0.0,
                  "FLAGS_stall_timeout_s": 0.0,
                  "FLAGS_device_peak_tflops": 275.0})
    flight.clear_events()


def _tiny_step(exe=None, scope=None):
    """One fc program + a ready (exe, scope, run) triple."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", [4])
        y = layers.fc(x, 2, bias_attr=False)
    exe = exe or pt.Executor(pt.CPUPlace())
    scope = scope or pt.framework.Scope()
    exe.run(startup, scope=scope)

    def run():
        return exe.run(main, feed={"x": np.ones((3, 4), "f4")},
                       fetch_list=[y], scope=scope)

    return exe, scope, run


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_record_order_seq_and_fields(self):
        flight.record("test/a", k=1)
        flight.record("test/b", s="x", arr=(1, 2))
        evs = flight.snapshot_events()
        assert [e["event"] for e in evs] == ["test/a", "test/b"]
        assert evs[0]["k"] == 1 and evs[1]["arr"] == [1, 2]
        assert evs[1]["seq"] == evs[0]["seq"] + 1
        assert evs[0]["ts"] <= evs[1]["ts"]

    def test_flag_gates_recording(self):
        pt.set_flags({"FLAGS_flight_recorder": False})
        assert flight.record("test/off") is None
        assert flight.snapshot_events() == []
        pt.set_flags({"FLAGS_flight_recorder": True})
        assert flight.record("test/on") is not None

    def test_ring_is_bounded(self):
        r = flight.FlightRecorder(capacity=8)
        for i in range(20):
            r.record("e", i=i)
        evs = r.snapshot()
        assert len(evs) == 8
        assert [e["i"] for e in evs] == list(range(12, 20))
        assert r.dropped == 12

    def test_unserializable_field_degrades_to_repr(self):
        flight.record("test/obj", obj=object())
        ev = flight.snapshot_events()[-1]
        assert "object object at" in ev["obj"]
        json.dumps(ev)  # the ring only ever holds JSON-able events

    def test_file_sink_appends_flushed_jsonl(self, tmp_path):
        p = str(tmp_path / "fr" / "events.jsonl")
        pt.set_flags({"FLAGS_flight_recorder_file": p})
        flight.record("test/sink", n=1)
        flight.record("test/sink", n=2)
        # flushed per event: readable NOW, without any shutdown hook
        lines = [json.loads(l) for l in open(p).read().splitlines()]
        assert [e["n"] for e in lines] == [1, 2]
        pt.set_flags({"FLAGS_flight_recorder_file": ""})
        flight.record("test/sink", n=3)
        assert len(open(p).read().splitlines()) == 2  # sink detached

    def test_file_sink_rotates_at_size_cap_and_tail_survives(
            self, tmp_path):
        """FLAGS_flight_recorder_max_mb: the active segment rotates to
        <path>.1 at the cap and a reader concatenating .1 + active —
        the post-SIGKILL recovery path, no shutdown hook involved —
        sees an unbroken, parseable event history spanning the
        rotation."""
        p = str(tmp_path / "fr" / "events.jsonl")
        before = stat_get("flight_sink_rotations")
        pt.set_flags({"FLAGS_flight_recorder_file": p,
                      "FLAGS_flight_recorder_max_mb": 0.002})  # ~2 KB
        pad = "x" * 64
        for i in range(200):  # ~130 bytes/line >> 2 KB: many rotations
            flight.record("test/rot", i=i, pad=pad)
        # no close/flush call: every line was already flushed at write
        assert os.path.isfile(p) and os.path.isfile(p + ".1")
        assert os.path.getsize(p + ".1") >= 2 * 1024
        assert stat_get("flight_sink_rotations") > before
        events = []
        for seg in (p + ".1", p):  # rotated first, then active
            for line in open(seg).read().splitlines():
                events.append(json.loads(line))  # every line parses
        idx = [e["i"] for e in events if e["event"] == "test/rot"]
        # contiguous tail ending at the last event: rotation dropped
        # only history OLDER than the kept two segments
        assert idx == list(range(idx[0], 200))
        assert len(idx) >= 20  # spans at least one rotation boundary

    def test_run_metadata_once_and_content(self):
        ev = flight.record_run_metadata()
        assert ev is not None
        assert ev["event"] == "run/metadata"
        assert ev["jax_version"]
        assert ev["pid"] == os.getpid()
        assert "flags" in ev and "max_inflight_steps" in ev["flags"]
        assert flight.record_run_metadata() is None  # once per process
        assert flight.record_run_metadata(force=True) is not None

    def test_executor_feeds_lifecycle_events(self):
        _, _, run = _tiny_step()
        run().numpy()
        run().numpy()
        names = [e["event"] for e in flight.snapshot_events()]
        assert "run/metadata" in names
        assert "executor/created" in names
        assert "run/devices" in names
        assert "executor/compile" in names
        assert names.count("executor/dispatch") >= 3  # startup + 2 steps
        dev = next(e for e in flight.snapshot_events()
                   if e["event"] == "run/devices")
        assert dev["platform"] == "cpu" and dev["device_count"] == 8

    def test_a_record_is_one_ring_entry_and_nothing_when_off(self):
        """What a call costs, as counts no loaded CPU moves: one
        record a call (consecutive ``seq``), a ring that never holds
        more than its capacity however many calls were made, no sink
        write with no sink set; and with the flag off no record, no
        ``seq``, nothing in the ring."""
        n = flight.DEFAULT_CAPACITY + 100
        recs = [flight.record("test/overhead", i=i) for i in range(n)]
        first = recs[0]["seq"]
        assert [r["seq"] for r in recs] == list(range(first, first + n))
        held = flight.snapshot_events()
        assert len(held) == flight.DEFAULT_CAPACITY
        assert [e["i"] for e in held] == list(range(100, n))
        assert flight._RECORDER._sink is None
        pt.set_flags({"FLAGS_flight_recorder": False})
        assert [flight.record("test/off", i=i) for i in range(10)] \
            == [None] * 10
        assert flight.snapshot_events() == held
        pt.set_flags({"FLAGS_flight_recorder": True})
        assert flight.record("test/on")["seq"] == first + n

    def test_dump_writes_jsonl(self, tmp_path):
        flight.record("test/d", x=1)
        p = flight.dump(str(tmp_path / "tail.jsonl"))
        rows = [json.loads(l) for l in open(p).read().splitlines()]
        assert rows[-1]["event"] == "test/d"


# ---------------------------------------------------------------------------
# ckpt lifecycle events
# ---------------------------------------------------------------------------


class TestCkptFlightEvents:
    def test_save_commit_restore_events(self, tmp_path):
        from paddle_tpu.ckpt import CheckpointManager

        m = CheckpointManager(str(tmp_path), async_save=False)
        m.save(3, state={"w": np.ones((4,), "f4")})
        m.restore()
        m.close()
        names = [e["event"] for e in flight.snapshot_events()]
        assert "ckpt/save" in names
        assert "ckpt/commit" in names
        assert "ckpt/restore" in names
        commit = next(e for e in flight.snapshot_events()
                      if e["event"] == "ckpt/commit")
        assert commit["step"] == 3 and commit["bytes"] == 16


# ---------------------------------------------------------------------------
# postmortem bundles
# ---------------------------------------------------------------------------

BUNDLE_FILES = ("meta.json", "stacks.txt", "trace.json", "metrics.prom",
                "flight.jsonl", "flags.json", "requests.json")


class TestPostmortem:
    def test_bundle_is_complete(self, tmp_path):
        observe.enable()
        try:
            with observe.span("test/pm"):
                pass
        finally:
            observe.disable()
        flight.record("test/before_dump", k=1)
        b = health.dump_postmortem("unit", directory=str(tmp_path),
                                   extra={"why": "test"})
        for f in BUNDLE_FILES:
            assert os.path.isfile(os.path.join(b, f)), f
        meta = json.load(open(os.path.join(b, "meta.json")))
        assert meta["reason"] == "unit"
        assert meta["pid"] == os.getpid()
        assert meta["extra"] == {"why": "test"}
        assert "dispatched" in meta["progress"]
        assert meta["section_errors"] == {}
        stacks = open(os.path.join(b, "stacks.txt")).read()
        assert "MainThread" in stacks and "test_bundle_is_complete" in stacks
        trace = json.load(open(os.path.join(b, "trace.json")))
        assert any(e.get("name") == "test/pm"
                   for e in trace["traceEvents"])
        prom = open(os.path.join(b, "metrics.prom")).read()
        assert "paddle_tpu_" in prom
        fl = [json.loads(l) for l in
              open(os.path.join(b, "flight.jsonl")).read().splitlines()]
        assert any(e["event"] == "test/before_dump" for e in fl)
        flags = json.load(open(os.path.join(b, "flags.json")))
        assert "stall_timeout_s" in flags
        # the dump itself is a flight event + a counter
        assert any(e["event"] == "postmortem/dump"
                   for e in flight.snapshot_events())

    def test_two_dumps_same_second_get_distinct_dirs(self, tmp_path):
        b1 = health.dump_postmortem("dup", directory=str(tmp_path))
        b2 = health.dump_postmortem("dup", directory=str(tmp_path))
        assert b1 != b2 and os.path.isdir(b1) and os.path.isdir(b2)

    def test_crash_handler_dumps_and_chains(self, tmp_path):
        seen = []
        prev = sys.excepthook
        sys.excepthook = lambda *a: seen.append(a)
        try:
            health.install_crash_handler(directory=str(tmp_path))
            try:
                raise ValueError("boom-for-bundle")
            except ValueError:
                sys.excepthook(*sys.exc_info())
        finally:
            health.uninstall_crash_handler()
            sys.excepthook = prev
        assert len(seen) == 1  # chained to the previous hook
        bundles = [d for d in os.listdir(tmp_path)
                   if d.startswith("bundle_")]
        assert len(bundles) == 1
        meta = json.load(open(tmp_path / bundles[0] / "meta.json"))
        assert meta["reason"] == "crash"
        assert meta["exception"]["type"] == "ValueError"
        assert "boom-for-bundle" in meta["exception"]["value"]
        # faulthandler armed for fatal signals in the same dir
        assert any(d.startswith("fatal_") for d in os.listdir(tmp_path))


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------


class _HungDeviceCall:
    """A mocked never-completing device call: jax.block_until_ready
    duck-calls .block_until_ready(), which parks on an Event."""

    def __init__(self, release: threading.Event):
        self._release = release

    def block_until_ready(self):
        self._release.wait(timeout=60)
        return self


class TestStallWatchdog:
    def test_hung_step_trips_within_timeout_and_bundle_is_complete(
            self, tmp_path):
        """Chaos test: a deliberately hung step (mocked never-completing
        device call) must trip the watchdog within the stall timeout and
        leave a complete postmortem bundle."""
        from paddle_tpu.framework.executor import _InflightStep

        exe, _, run = _tiny_step()
        run().numpy()  # healthy baseline step
        exe.drain()
        base_drained = stat_get("executor_steps_drained")

        release = threading.Event()
        entry = _InflightStep(
            sync_refs=(_HungDeviceCall(release),), nan_flags=None,
            nan_ops=(), t_dispatch=time.perf_counter(), steps=1,
            examples=0, compiled=False, flops_per_step=0.0,
            allreduce_bytes=0)
        exe._window.push(entry)
        drainer = threading.Thread(target=exe.drain,
                                   name="hung-train-loop", daemon=True)
        drainer.start()

        timeout = 0.6
        wd = health.StallWatchdog(timeout_s=timeout, poll_s=0.1,
                                  directory=str(tmp_path))
        t0 = time.perf_counter()
        wd.start()
        try:
            deadline = time.time() + 15
            while not wd.bundles and time.time() < deadline:
                time.sleep(0.05)
            tripped_after = time.perf_counter() - t0
            assert wd.bundles, "watchdog never tripped on the hung step"
            # fires once the no-progress window exceeds the timeout —
            # within timeout + a few polls of slack, not minutes later
            assert tripped_after < timeout + 2.0
            b = wd.bundles[0]
            for f in BUNDLE_FILES:
                assert os.path.isfile(os.path.join(b, f)), f
            meta = json.load(open(os.path.join(b, "meta.json")))
            assert meta["reason"] == "stall"
            assert meta["progress"]["inflight"] >= 1
            assert meta["progress"]["drained"] == base_drained
            # the hung thread is IN the stack dump, named, inside the
            # mocked device call
            stacks = open(os.path.join(b, "stacks.txt")).read()
            assert "hung-train-loop" in stacks
            assert "block_until_ready" in stacks
            # latched: a continuing stall produces no second bundle
            time.sleep(3 * wd.poll_s + timeout)
            assert len(wd.bundles) == 1
            assert stat_get("watchdog_stalls") >= 1
            assert any(e["event"] == "health/stall"
                       for e in flight.snapshot_events())
        finally:
            release.set()
            drainer.join(timeout=10)
            wd.stop()
        assert not drainer.is_alive()
        assert stat_get("executor_steps_drained") == base_drained + 1

    def test_no_trip_while_progressing_or_idle(self, tmp_path):
        state = {"drained": 0}

        def progress():
            state["drained"] += 1  # every poll sees fresh progress
            return {"dispatched": state["drained"] + 1,
                    "drained": state["drained"], "inflight": 1,
                    "oldest_inflight_age_s": 0.01}

        wd = health.StallWatchdog(timeout_s=0.2, poll_s=0.05,
                                  directory=str(tmp_path),
                                  progress_fn=progress)
        wd.start()
        time.sleep(0.6)
        wd.stop()
        assert wd.bundles == []
        # idle (nothing pending) never trips either
        wd2 = health.StallWatchdog(
            timeout_s=0.2, poll_s=0.05, directory=str(tmp_path),
            progress_fn=lambda: {"dispatched": 5, "drained": 5,
                                 "inflight": 0,
                                 "oldest_inflight_age_s": None})
        wd2.start()
        time.sleep(0.6)
        wd2.stop()
        assert wd2.bundles == []

    def test_rearms_after_progress_resumes(self, tmp_path):
        state = {"drained": 0, "stuck": True}

        def progress():
            if not state["stuck"]:
                state["drained"] += 1
            return {"dispatched": state["drained"] + 1,
                    "drained": state["drained"], "inflight": 1,
                    "oldest_inflight_age_s": None}

        wd = health.StallWatchdog(timeout_s=0.2, poll_s=0.05,
                                  directory=str(tmp_path),
                                  progress_fn=progress)
        wd.start()
        try:
            deadline = time.time() + 10
            while len(wd.bundles) < 1 and time.time() < deadline:
                time.sleep(0.05)
            assert len(wd.bundles) == 1
            state["stuck"] = False  # progress resumes -> re-arm
            time.sleep(0.3)
            state["stuck"] = True   # second stall
            deadline = time.time() + 10
            while len(wd.bundles) < 2 and time.time() < deadline:
                time.sleep(0.05)
            assert len(wd.bundles) == 2
        finally:
            wd.stop()

    def test_ready_but_unread_entry_is_idle_not_a_stall(self, tmp_path):
        """A dispatched step whose fetch buffers are device-complete
        but unread (interactive pause, slow consumer) must read as an
        idle host, not a hung device."""
        _, _, run = _tiny_step()
        run().numpy()
        h = run()  # dispatched, never read: entry stays in the window
        deadline = time.time() + 10
        while (health.executor_progress()["oldest_ready"] is not True
               and time.time() < deadline):
            time.sleep(0.02)
        p = health.executor_progress()
        assert p["inflight"] >= 1 and p["oldest_ready"] is True
        wd = health.StallWatchdog(timeout_s=0.2, poll_s=0.05,
                                  directory=str(tmp_path))
        wd.start()
        time.sleep(0.7)
        wd.stop()
        assert wd.bundles == []
        h.numpy()  # now read it; the window drains

    def test_compile_grace_scales_the_timeout(self, tmp_path):
        """Pending work + frozen counters during an in-flight compile
        only trips once compile_grace * timeout is exceeded — a long
        XLA compile is not a stall, a compile hung far past it is."""

        def progress():
            return {"dispatched": 1, "drained": 0, "inflight": 1,
                    "oldest_inflight_age_s": 99.0, "oldest_ready": None,
                    "compiling": True, "compile_age_s": 99.0}

        wd = health.StallWatchdog(timeout_s=0.2, poll_s=0.05,
                                  compile_grace=1000.0,
                                  directory=str(tmp_path),
                                  progress_fn=progress)
        wd.start()
        time.sleep(0.7)  # far past timeout_s, far under the grace
        wd.stop()
        assert wd.bundles == []
        wd2 = health.StallWatchdog(timeout_s=0.2, poll_s=0.05,
                                   compile_grace=2.0,
                                   directory=str(tmp_path),
                                   progress_fn=progress)
        wd2.start()
        deadline = time.time() + 10
        while not wd2.bundles and time.time() < deadline:
            time.sleep(0.05)
        wd2.stop()
        assert len(wd2.bundles) == 1  # hung compile IS the failure

    def test_executor_marks_active_compile(self):
        from paddle_tpu.framework.executor import _ACTIVE_COMPILES

        seen = {}
        orig = health.executor_progress

        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data("x", [6])
            y = layers.fc(x, 3, bias_attr=False)
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.framework.Scope()
        exe.run(startup, scope=scope)
        # sample the marker from a sibling thread while the first call
        # (trace+compile) runs on the main thread
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                if _ACTIVE_COMPILES:
                    seen["during"] = orig()
                time.sleep(0.001)

        t = threading.Thread(target=sampler, daemon=True)
        t.start()
        exe.run(main, feed={"x": np.ones((2, 6), "f4")},
                fetch_list=[y], scope=scope).numpy()
        stop.set()
        t.join()
        exe.drain()
        assert seen, "sampler never saw the active-compile marker"
        assert seen["during"]["compiling"] is True
        assert seen["during"]["compile_age_s"] >= 0.0
        assert health.executor_progress()["compiling"] is False

    def test_idle_executor_cannot_mask_another_executors_hang(self):
        """oldest_ready is judged PER WINDOW: a second executor with a
        device-complete-but-unread entry must not hide a hung entry in
        the first one."""
        from paddle_tpu.framework.executor import _InflightStep

        _, _, run_a = _tiny_step()
        run_a().numpy()
        h = run_a()  # executor A: ready-but-unread entry in the window
        deadline = time.time() + 10
        while (health.executor_progress()["oldest_ready"] is not True
               and time.time() < deadline):
            time.sleep(0.02)
        assert health.executor_progress()["oldest_ready"] is True

        exe_b = pt.Executor(pt.CPUPlace())  # executor B: hung entry
        release = threading.Event()
        exe_b._window.push(_InflightStep(
            (_HungDeviceCall(release),), None, (), time.perf_counter(),
            1, 0, False, 0.0, 0))
        try:
            p = health.executor_progress()
            assert p["inflight"] >= 2
            assert p["oldest_ready"] is False  # B's hang wins
        finally:
            release.set()
            exe_b._window._entries.clear()
            h.numpy()

    def test_flag_gates_auto_start(self):
        assert health.maybe_start_watchdog() is None  # 0.0 = disabled
        pt.set_flags({"FLAGS_stall_timeout_s": 30.0})
        try:
            wd = health.maybe_start_watchdog()
            assert wd is not None and wd.running
            assert wd.timeout_s == 30.0
            # Executor construction is the auto-start hook
            assert health.get_watchdog() is wd
            assert health.start_watchdog() is wd  # singleton
        finally:
            health.stop_watchdog()
            pt.set_flags({"FLAGS_stall_timeout_s": 0.0})

    def test_requires_positive_timeout(self):
        with pytest.raises(ValueError):
            health.StallWatchdog(timeout_s=0.0)


# ---------------------------------------------------------------------------
# cluster health over the real fleet KV HTTP server
# ---------------------------------------------------------------------------


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


class TestClusterHealth:
    def test_two_rank_heartbeats_and_straggler_skew_over_real_http(self):
        """Acceptance: a 2-rank run over the real KV HTTP server shows
        per-rank heartbeats and a nonzero straggler-skew gauge on
        /metrics/cluster when one rank is artificially slowed."""
        from paddle_tpu.distributed.fleet.utils.http_server import KVServer

        srv = KVServer(0)
        srv.start()
        try:
            health.serve_cluster_health(srv, world_size=2)
            ep = f"127.0.0.1:{srv.port}"
            # rank 1 is artificially 3x slower than rank 0
            r0 = health.HealthReporter(
                ep, rank=0, world_size=2, interval_s=5.0,
                stats_fn=lambda: {"step_time_p50_s": 0.1,
                                  "drained": 10, "dispatched": 10})
            r1 = health.HealthReporter(
                ep, rank=1, world_size=2, interval_s=5.0,
                stats_fn=lambda: {"step_time_p50_s": 0.3,
                                  "drained": 7, "dispatched": 8})
            assert r0.publish_once() and r1.publish_once()

            doc = _get_json(f"http://{ep}/metrics/cluster")
            assert doc["world_size"] == 2
            assert doc["alive_ranks"] == 2 and doc["dead_ranks"] == []
            assert set(doc["ranks"]) == {"0", "1"}
            for r in ("0", "1"):
                assert doc["ranks"][r]["last_heartbeat_age_s"] < 5.0
                assert doc["ranks"][r]["alive"] is True
            assert doc["ranks"]["1"]["step_time_p50_s"] == 0.3
            # straggler gauge: (0.3 - 0.1) / 0.1 = 2.0
            assert doc["step_time_skew"] == pytest.approx(2.0)
            assert doc["straggler_rank"] == 1

            # the liveness/skew gauges are mirrored onto plain /metrics
            with urllib.request.urlopen(
                    f"http://{ep}/metrics", timeout=10) as resp:
                prom = resp.read().decode()
            assert "paddle_tpu_cluster_ranks_alive 2" in prom
            assert "paddle_tpu_cluster_step_time_skew_ppm 2000000" in prom
        finally:
            srv.stop()

    def test_dead_rank_detection(self):
        now = time.time()
        kv = {
            "health/rank/0": json.dumps(
                {"rank": 0, "ts": now, "interval_s": 1.0}).encode(),
            "health/rank/1": json.dumps(
                {"rank": 1, "ts": now - 100.0,
                 "interval_s": 1.0}).encode(),
            "unrelated/key": b"junk",
            "health/rank/bogus": b"not json",
        }
        doc = health.cluster_health(kv, world_size=3, now=now)
        assert doc["alive_ranks"] == 1
        assert doc["dead_ranks"] == [1, 2]  # stale beat + never beat
        assert doc["ranks"]["1"]["alive"] is False
        assert doc["ranks"]["1"]["last_heartbeat_age_s"] == \
            pytest.approx(100.0, abs=1.0)
        assert doc["step_time_skew"] == 0.0  # <2 timed ranks: no skew

    def test_reporter_thread_beats_periodically_with_default_stats(self):
        from paddle_tpu.distributed.fleet.utils.http_server import KVServer

        srv = KVServer(0)
        srv.start()
        try:
            r = health.HealthReporter(f"127.0.0.1:{srv.port}", rank=0,
                                      interval_s=0.1)
            r.start()
            time.sleep(0.45)
            r.stop()
            assert r.beats >= 2  # immediate first beat + periodic
            snap = srv.kv_snapshot(health.HEALTH_KEY_PREFIX)
            payload = json.loads(snap["health/rank/0"].decode())
            assert payload["pid"] == os.getpid()
            # default stats: executor progress counters ride along
            assert "dispatched" in payload and "drained" in payload
        finally:
            srv.stop()

    def test_reporter_survives_unreachable_server(self):
        r = health.HealthReporter("127.0.0.1:9", rank=0, interval_s=5.0,
                                  timeout_s=0.5)
        assert r.publish_once() is False
        assert r.failures == 1
        assert stat_get("health_heartbeat_failures") >= 1

    def test_restarted_rank_rejoins_alive_with_bumped_epoch(self):
        """ISSUE 14 satellite: a dead-listed rank that RESUMES
        heartbeating re-enters alive_ranks and clears from dead_ranks,
        and its restart (new pid, dispatched counter reset) bumps a
        MONOTONIC rank-epoch — so the supervisor can tell a restarted
        rank from a straggler whose counters 'went backwards'."""
        book = {}
        now = time.time()

        def hb(rank, ts, pid, disp, p50):
            return json.dumps(
                {"rank": rank, "ts": ts, "interval_s": 1.0, "pid": pid,
                 "dispatched": disp, "drained": disp,
                 "step_time_p50_s": p50}).encode()

        # scrape 1: rank 1's beat is stale -> dead-listed
        kv = {"health/rank/0": hb(0, now, 100, 50, 0.1),
              "health/rank/1": hb(1, now - 100.0, 200, 40, 0.1)}
        d1 = health.cluster_health(kv, world_size=2, now=now, book=book)
        assert d1["dead_ranks"] == [1]
        assert d1["rank_epochs"] == {"0": 0, "1": 0}

        # scrape 2: rank 1 restarted — fresh pid, counters reset, live
        # beat.  It must REJOIN alive, leave dead_ranks, and bump its
        # epoch; its reset step-time must NOT enter the skew gauge.
        kv = {"health/rank/0": hb(0, now + 1, 100, 60, 0.1),
              "health/rank/1": hb(1, now + 1, 201, 2, 9.9)}
        d2 = health.cluster_health(kv, world_size=2, now=now + 1,
                                   book=book)
        assert d2["dead_ranks"] == [] and d2["alive_ranks"] == 2
        assert d2["ranks"]["1"]["epoch"] == 1
        assert d2["ranks"]["1"]["restarted"] is True
        assert d2["rank_epochs"]["1"] == 1
        assert d2["step_time_skew"] == 0.0  # restarted rank excluded
        assert stat_get("cluster_rank_restarts") >= 1

        # scrape 2b: the restarted rank has NOT dispatched a step yet
        # (counters unchanged) — the exclusion must be STICKY, not a
        # single-scrape flag, or the cold p50 pollutes the skew gauge
        # one scrape after detection
        kv = {"health/rank/0": hb(0, now + 1.5, 100, 65, 0.1),
              "health/rank/1": hb(1, now + 1.5, 201, 2, 9.9)}
        d2b = health.cluster_health(kv, world_size=2, now=now + 1.5,
                                    book=book)
        assert d2b["ranks"]["1"]["epoch"] == 1  # no double bump
        assert d2b["ranks"]["1"]["restarted"] is True
        assert d2b["step_time_skew"] == 0.0

        # scrape 3: the restarted rank's counters move FORWARD again —
        # no further bump, and it re-enters the skew computation
        kv = {"health/rank/0": hb(0, now + 2, 100, 70, 0.1),
              "health/rank/1": hb(1, now + 2, 201, 12, 0.3)}
        d3 = health.cluster_health(kv, world_size=2, now=now + 2,
                                   book=book)
        assert d3["ranks"]["1"]["epoch"] == 1
        assert "restarted" not in d3["ranks"]["1"]
        assert d3["step_time_skew"] == pytest.approx(2.0)

    def test_counter_regression_alone_bumps_epoch(self):
        """A rank whose cumulative dispatched counter went backwards
        restarted even if its pid looks unchanged (pid reuse / missing
        pid field): the epoch must still bump exactly once."""
        book = {}
        now = time.time()

        def hb(disp):
            return json.dumps({"rank": 0, "ts": now, "interval_s": 1.0,
                               "dispatched": disp}).encode()

        for disp, want_epoch in ((30, 0), (31, 0), (4, 1), (5, 1)):
            doc = health.cluster_health(
                {"health/rank/0": hb(disp)}, world_size=1, now=now,
                book=book)
            assert doc["rank_epochs"]["0"] == want_epoch, disp

    def test_heartbeat_blackhole_chaos_dead_lists_then_recovers(self):
        """fleet.elastic.chaos 'heartbeat_blackhole' drops a live
        rank's beats (the injected dead-rank path); clearing the fault
        lets the next beat through."""
        from paddle_tpu.distributed.fleet.elastic import chaos

        from paddle_tpu.distributed.fleet.utils.http_server import \
            KVServer

        srv = KVServer(0)
        srv.start()
        try:
            r = health.HealthReporter(f"127.0.0.1:{srv.port}", rank=0,
                                      interval_s=5.0)
            chaos.inject("heartbeat_blackhole", rank=0, count=-1)
            try:
                assert r.publish_once() is False
                assert r.publish_once() is False
                assert srv.kv_snapshot(health.HEALTH_KEY_PREFIX) == {}
                assert stat_get("health_heartbeat_blackholed") >= 2
            finally:
                chaos.clear()
            assert r.publish_once() is True
            assert "health/rank/0" in srv.kv_snapshot(
                health.HEALTH_KEY_PREFIX)
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# /metrics scrape thread-safety under live recording (satellite)
# ---------------------------------------------------------------------------


class TestConcurrentScrape:
    def test_scrape_while_training_thread_records(self):
        """Concurrent /metrics scrapes over real HTTP while StepTimer +
        histograms + counters are being fed from a 'training' thread:
        every scrape must return 200 with well-formed exposition."""
        from paddle_tpu.distributed.fleet.utils.http_server import KVServer
        from paddle_tpu.monitor import stat_add, stat_time

        srv = KVServer(0)
        srv.start()
        stop = threading.Event()
        errors = []

        def trainer():
            timer = observe.StepTimer("concurrent_scrape_seconds")
            i = 0
            while not stop.is_set():
                i += 1
                stat_time("concurrent_scrape_seconds", 1e-4 * (i % 7 + 1))
                timer.record_run(1e-3, steps=1, examples=4,
                                 compiled=(i == 1))
                stat_add("concurrent_scrape_ops")
                flight.record("test/scrape_step", i=i)

        def scraper():
            url = f"http://127.0.0.1:{srv.port}/metrics"
            for _ in range(25):
                try:
                    with urllib.request.urlopen(url, timeout=10) as r:
                        assert r.status == 200
                        body = r.read().decode()
                    # well-formed: every sample line is "name value"
                    for ln in body.splitlines():
                        if ln and not ln.startswith("#"):
                            float(ln.rsplit(" ", 1)[1])
                    assert "concurrent_scrape_seconds_bucket" in body
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        tr = threading.Thread(target=trainer, daemon=True)
        scrapers = [threading.Thread(target=scraper) for _ in range(4)]
        tr.start()
        for s in scrapers:
            s.start()
        for s in scrapers:
            s.join()
        stop.set()
        tr.join(timeout=10)
        srv.stop()
        assert errors == []


# ---------------------------------------------------------------------------
# StepTimer MFU guard (satellite)
# ---------------------------------------------------------------------------


class TestMFUGuard:
    def test_mfu_is_null_when_peak_unset(self):
        t = observe.StepTimer("mfu_guard_seconds")
        t.record_run(0.01, steps=1, examples=1, compiled=True)
        t.record_run(0.01, steps=1, examples=1, flops_per_step=1e9)
        pt.set_flags({"FLAGS_device_peak_tflops": 0.0})
        s = t.summary()
        assert "mfu" in s and s["mfu"] is None
        assert s["flops_per_step"] > 0  # the numerator still reports
        json.dumps(s)  # null, not NaN/inf: stays JSON-clean
        # explicit peak overrides the dead flag
        assert t.summary(peak_tflops=100.0)["mfu"] > 0
        pt.set_flags({"FLAGS_device_peak_tflops": 275.0})
        assert t.summary()["mfu"] > 0

    def test_benchmark_callback_survives_null_mfu(self, capsys):
        """on_train_end formats the MFU — a null one (peak unset) must
        print 'no MFU' gracefully, not TypeError on the format spec."""
        from paddle_tpu.hapi.callbacks import BenchmarkCallback

        cb = BenchmarkCallback(batch_size=4, flops_per_step=1e9,
                               log_freq=0)
        cb.on_train_begin()
        for i in range(3):
            cb.on_train_batch_begin(i)
            time.sleep(0.001)
            cb.on_train_batch_end(i)
        pt.set_flags({"FLAGS_device_peak_tflops": 0.0})
        cb.on_train_end()  # crashed with TypeError before the guard
        assert cb.last_summary["mfu"] is None
        out = capsys.readouterr().out
        assert "[bench]" in out and "MFU" not in out
        pt.set_flags({"FLAGS_device_peak_tflops": 275.0})
        cb.on_train_end()
        assert "MFU" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# tools/postmortem.py CLI (satellite)
# ---------------------------------------------------------------------------


class TestPostmortemCLI:
    def _bundle(self, tmp_path):
        flight.record("test/cli", marker="xyz")
        # a retained violator so the bundle's requests.json section is
        # populated (observe/request_trace.py)
        from paddle_tpu.observe import request_trace as rt

        store = rt.get_trace_store()
        tr = store.start("decode", replica="replica-cli")
        tr.event("admit", slot=0)
        store.finish(tr, outcome="deadline", reason="cli smoke",
                     violations=["ttft_p99"], latency_s=0.5)
        return health.dump_postmortem("cli_smoke",
                                      directory=str(tmp_path))

    def test_in_process_render_and_latest_selection(self, tmp_path,
                                                    capsys):
        from tools import postmortem as pm

        b = self._bundle(tmp_path)
        assert pm.main([str(b)]) == 0
        out = capsys.readouterr().out
        assert "cli_smoke" in out and "flight recorder" in out
        # a parent dir resolves to its newest bundle
        assert pm.resolve_bundle(str(tmp_path)) == b
        assert pm.main([str(tmp_path), "--stacks"]) == 0
        assert "MainThread" in capsys.readouterr().out
        assert pm.main([str(tmp_path / "nope")]) == 2

    def test_python_dash_m_smoke(self, tmp_path):
        b = self._bundle(tmp_path)
        r = subprocess.run(
            [sys.executable, "-m", "tools.postmortem", b],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "postmortem bundle" in r.stdout
        assert "cli_smoke" in r.stdout
        # the requests.json section renders: violator row + its SLO
        # violation, plus the reqtrace pointer
        assert "violators" in r.stdout
        assert "ttft_p99" in r.stdout
        assert "tools.reqtrace" in r.stdout
