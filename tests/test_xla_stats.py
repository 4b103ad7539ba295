"""XLA introspection (paddle_tpu.observe.xla_stats): compile telemetry,
HBM accounting, and the pre-dispatch memory budget gate.

Reference parity: the memory_optimize/profiler role (SURVEY L1/L11) —
here rebuilt on jax's AOT stages (``jit(f).lower(...).compile()`` →
``memory_analysis()``/``cost_analysis()``), so an over-budget program
fails BEFORE dispatch with a per-var attribution table instead of an
opaque RESOURCE_EXHAUSTED after it.
"""
import io
import json
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, observe
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.passes import TPShardingPlan
from paddle_tpu.framework.program import Program, program_guard
from paddle_tpu.monitor import stat_get, stat_reset
from paddle_tpu.observe import flight, health, xla_stats
from paddle_tpu.observe.xla_stats import MemoryBudgetError
from paddle_tpu.optimizer import MomentumOptimizer


@pytest.fixture
def restore_flags():
    """Tests flip the gate/introspection flags; always restore."""
    yield
    pt.set_flags({"FLAGS_hbm_budget_fraction": 0.0,
                  "FLAGS_hbm_bytes_per_device": 0,
                  "FLAGS_xla_introspect": True,
                  "FLAGS_hlo_dump_dir": ""})


def _train_program(seed=3):
    """fc -> fc, MSE, Momentum: parameters + velocity slots in scope."""
    main, startup = Program(), Program()
    main.random_seed = seed
    with unique_name.guard(), program_guard(main, startup):
        x = layers.data("x", [8])
        y = layers.data("y", [1])
        h = layers.fc(x, 16, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        MomentumOptimizer(0.05, 0.9).minimize(loss)
    return main, startup, loss


def _feed(batch=16):
    rs = np.random.RandomState(0)
    X = rs.randn(batch, 8).astype("f4")
    return {"x": X, "y": X.sum(1, keepdims=True).astype("f4") * 0.3}


def _fresh_executor(main_startup=None):
    main, startup, loss = main_startup or _train_program()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    return exe, scope, main, loss


# ---------------------------------------------------------------------------
# mocked compiled objects (the unit half: no XLA required)
# ---------------------------------------------------------------------------


class _FakeMemStats:
    argument_size_in_bytes = 1000
    output_size_in_bytes = 500
    temp_size_in_bytes = 300
    generated_code_size_in_bytes = 0
    alias_size_in_bytes = 200


class _FakeCompiled:
    """Duck-typed jax AOT Compiled: enough surface for on_compile."""

    def __init__(self, mem=_FakeMemStats(), flops=None,
                 text="HloModule fake\n  %a = f32[] add(x, y)\n"):
        self._mem = mem
        self._flops = flops
        self._text = text

    def memory_analysis(self):
        return self._mem

    def cost_analysis(self):
        return {} if self._flops is None else {"flops": self._flops}

    def runtime_executable(self):
        from types import SimpleNamespace

        return SimpleNamespace(size_of_generated_code_in_bytes=lambda: 0)

    def as_text(self):
        return self._text


class TestMemoryBreakdown:
    def test_breakdown_fields_and_total(self):
        b = xla_stats.memory_breakdown(_FakeCompiled())
        assert b["arguments_bytes"] == 1000
        assert b["outputs_bytes"] == 500
        assert b["temporaries_bytes"] == 300
        assert b["aliased_bytes"] == 200
        # total = args + outs + temps + code - aliased
        assert b["total_bytes"] == 1000 + 500 + 300 + 0 - 200

    def test_backend_without_memory_analysis_is_none(self):
        assert xla_stats.memory_breakdown(_FakeCompiled(mem=None)) is None


# ---------------------------------------------------------------------------
# attribution: TPShardingPlan x var sizes
# ---------------------------------------------------------------------------


def _mesh_2x4():
    import jax

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return jax.sharding.Mesh(devs, ("dp", "mp"))


class TestAttribution:
    def test_sorted_and_truncated(self):
        entries = [(f"v{i}", (i + 1, 4), "float32", "state")
                   for i in range(12)]
        rows = xla_stats.var_attribution(entries, top_n=5)
        assert len(rows) == 5
        assert rows[0]["name"] == "v11"  # biggest first
        sizes = [r["per_chip_bytes"] for r in rows]
        assert sizes == sorted(sizes, reverse=True)
        assert rows[0]["global_bytes"] == 12 * 4 * 4

    def test_plan_join_divides_sharded_vars(self):
        mesh = _mesh_2x4()
        plan = TPShardingPlan(
            {"w": (None, "mp"), "b": (), "z": ("dp", "mp")}, mp_degree=4)
        entries = [("w", (64, 64), "float32", "state"),
                   ("b", (64,), "float32", "state"),
                   ("z", (64, 64), "float32", "state")]
        rows = {r["name"]: r
                for r in xla_stats.var_attribution(entries, plan, mesh)}
        assert rows["w"]["per_chip_bytes"] == 64 * 64 * 4 // 4
        assert rows["w"]["spec"] == "P(None, 'mp')"
        assert rows["b"]["per_chip_bytes"] == 64 * 4  # replicated
        assert rows["b"]["spec"] == "replicated"
        assert rows["z"]["per_chip_bytes"] == 64 * 64 * 4 // 8  # dp*mp
        # plan helpers directly (the passes.py join surface)
        assert plan.shard_divisor("z", mesh) == 8
        assert plan.shard_divisor("unknown", mesh) == 1
        assert plan.spec_str("unknown") == "replicated"

    def test_format_is_aligned_text(self):
        rows = xla_stats.var_attribution(
            [("weight", (1024, 1024), "float32", "state")])
        txt = xla_stats.format_attribution(rows)
        assert "weight" in txt and "per-chip MB" in txt
        assert "4.0" in txt  # 1024*1024*4 = 4MB


# ---------------------------------------------------------------------------
# the budget gate (unit: explicit capacity override, no device probing)
# ---------------------------------------------------------------------------


class TestBudgetGate:
    def test_disabled_by_default(self, restore_flags):
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.0})
        v = xla_stats.check_hbm_budget(10 ** 15)
        assert v["verdict"] == "disabled"

    def test_skips_loudly_without_capacity(self, restore_flags):
        # CPU devices report no memory_stats and no override is set:
        # the gate cannot judge — it must skip with a counter, never
        # guess, and NEVER pass the program silently as "fits"
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.9,
                      "FLAGS_hbm_bytes_per_device": 0})
        stat_reset("hbm_budget_gate_skipped")
        v = xla_stats.check_hbm_budget(10 ** 15)
        assert v["verdict"] == "skipped"
        assert stat_get("hbm_budget_gate_skipped") == 1

    def test_under_budget_passes(self, restore_flags):
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.5,
                      "FLAGS_hbm_bytes_per_device": 1000})
        stat_reset("hbm_budget_gate_passed")
        v = xla_stats.check_hbm_budget(400)
        assert v["verdict"] == "pass"
        assert v["budget_bytes"] == 500
        assert stat_get("hbm_budget_gate_passed") == 1

    def test_over_budget_raises_with_attribution(self, restore_flags):
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.5,
                      "FLAGS_hbm_bytes_per_device": 1000})
        rows = xla_stats.var_attribution(
            [("big.w_0", (100, 100), "float32", "state"),
             ("mid.w_0", (10, 10), "float32", "state"),
             ("tiny.b_0", (4,), "float32", "state"),
             ("x", (16, 8), "float32", "feed")])
        stat_reset("hbm_budget_gate_rejections")
        with pytest.raises(MemoryBudgetError) as ei:
            xla_stats.check_hbm_budget(900, rows, fingerprint="abcd1234")
        e = ei.value
        msg = str(e)
        # the top-3 largest vars and their specs are IN the error
        assert "big.w_0" in msg and "mid.w_0" in msg and "tiny.b_0" in msg
        assert "replicated" in msg
        assert "BEFORE dispatch" in msg
        assert e.required_bytes == 900 and e.budget_bytes == 500
        assert e.attribution[0]["name"] == "big.w_0"
        assert stat_get("hbm_budget_gate_rejections") == 1
        # the rejection left a flight event naming the top vars
        ev = [r for r in flight.tail(20)
              if r["event"] == "xla/hbm_budget_reject"]
        assert ev and ev[-1]["top_vars"][0] == "big.w_0"


# ---------------------------------------------------------------------------
# on_compile (mocked compiled): record, gauges, mfu cross-check
# ---------------------------------------------------------------------------


class TestOnCompileMocked:
    def test_record_gauges_and_flight_event(self):
        xla_stats.clear_compile_records()
        observe.histogram("compile_seconds").reset()
        rec = xla_stats.on_compile(
            _FakeCompiled(), fingerprint="deadbeefcafe", seconds=0.25,
            size_entries=[("w", (32, 32), "float32", "state")])
        assert rec["compile_seconds"] == 0.25
        assert observe.histogram("compile_seconds").count == 1
        assert rec["memory"]["total_bytes"] == 1600
        assert stat_get("hbm_required_bytes") == 1600
        # CPU-style zero code size falls back to the HLO text length
        assert rec["executable_size_bytes"] == len(
            _FakeCompiled().as_text())
        assert rec["executable_size_is_hlo_text"] is True
        assert rec["hlo_ops"] == 1
        assert rec["attribution"][0]["name"] == "w"
        assert xla_stats.last_compile() is rec
        ev = [r for r in flight.tail(10)
              if r["event"] == "executor/compile_done"]
        assert ev and ev[-1]["fingerprint"] == "deadbeefcafe"
        assert ev[-1]["seconds"] == 0.25
        assert ev[-1]["hbm_required_bytes"] == 1600

    def test_capability_skip_without_memory_analysis(self, restore_flags):
        # a backend that reports no memory_analysis: telemetry
        # that exists is still recorded, the counter says why the HBM
        # half is missing, and an ARMED gate does not fire (it cannot
        # judge what it cannot see — the skip path, not a crash)
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.9,
                      "FLAGS_hbm_bytes_per_device": 1})
        stat_reset("xla_memory_analysis_unavailable")
        rec = xla_stats.on_compile(
            _FakeCompiled(mem=None), seconds=0.1)
        assert "memory" not in rec
        assert stat_get("xla_memory_analysis_unavailable") == 1

    def test_mfu_mismatch_prefers_xla(self):
        stat_reset("mfu_flops_mismatch")
        rec = xla_stats.on_compile(
            _FakeCompiled(flops=1000.0), seconds=0.0,
            program_flops=100.0)  # 10x apart: the IR count mispriced
        assert rec["flops_source"] == "xla"
        assert rec["xla_flops_per_step"] == 1000.0
        assert stat_get("mfu_flops_mismatch") == 1

    def test_mfu_within_2x_keeps_ir_count(self):
        stat_reset("mfu_flops_mismatch")
        rec = xla_stats.on_compile(
            _FakeCompiled(flops=150.0), seconds=0.0, program_flops=100.0)
        assert "xla_flops_per_step" not in rec
        assert rec["flops_ratio_xla_over_ir"] == 1.5
        assert stat_get("mfu_flops_mismatch") == 0

    def test_no_cross_check_for_scans_or_meshes(self):
        rec = xla_stats.on_compile(
            _FakeCompiled(flops=1000.0), seconds=0.0,
            program_flops=1.0, n_steps=10)
        assert "xla_flops_per_step" not in rec
        rec = xla_stats.on_compile(
            _FakeCompiled(flops=1000.0), seconds=0.0,
            program_flops=1.0, mesh=_mesh_2x4())
        assert "xla_flops_per_step" not in rec


# ---------------------------------------------------------------------------
# Executor integration (the tentpole end-to-end, real XLA)
# ---------------------------------------------------------------------------


class TestExecutorIntrospection:
    def test_compile_telemetry_end_to_end(self):
        xla_stats.clear_compile_records()
        observe.histogram("compile_seconds").reset()
        exe, scope, main, loss = _fresh_executor()
        out = exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert np.isfinite(out[0]).all()
        # startup + train = two compiles, both measured
        assert observe.histogram("compile_seconds").count >= 2
        recs = xla_stats.compile_records()
        assert len(recs) >= 2
        train = recs[-1]
        assert train["memory"]["total_bytes"] > 0
        assert stat_get("hbm_required_bytes") > 0
        assert stat_get("executable_size_bytes") > 0
        names = [r["name"] for r in train["attribution"]]
        assert "fc_0.w_0" in names  # scope state joined in
        assert any(r["kind"] == "feed" for r in train["attribution"])
        assert any(r["event"] == "executor/compile_done"
                   for r in flight.tail(20))
        # the AOT executable replaced the lazy callable (paid once)
        assert any(getattr(e.fn, "__name__", "") == "run_compiled"
                   for e in exe._cache.values())
        # StepTimer surfaces the compiler's own bill
        s = observe.step_timer().summary()
        assert s["xla_compile_seconds"]["count"] >= 2
        assert s["executable_size_bytes"] > 0

    def test_budget_gate_rejects_before_dispatch(
            self, restore_flags):
        exe, scope, main, loss = _fresh_executor()
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.5,
                      "FLAGS_hbm_bytes_per_device": 1024})
        d0 = stat_get("executor_steps_dispatched")
        with pytest.raises(MemoryBudgetError) as ei:
            exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        # NOTHING dispatched: the rejection is a report, not a dead chip
        assert stat_get("executor_steps_dispatched") == d0
        assert "fc_0.w_0" in str(ei.value)  # largest var named
        # the rejected compile still left its record for memory.json
        assert xla_stats.last_compile()["budget"]["verdict"] == "rejected"
        # widening the budget lets the same cached entry run
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.0})
        out = exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert np.isfinite(out[0]).all()

    def test_flag_gates_introspection_off(self, restore_flags):
        pt.set_flags({"FLAGS_xla_introspect": False})
        xla_stats.clear_compile_records()
        exe, scope, main, loss = _fresh_executor()
        out = exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert np.isfinite(out[0]).all()
        assert xla_stats.compile_records() == []

    def test_capability_skip_runs_unintrospected(self, restore_flags,
                                                 monkeypatch):
        # a backend that reports no memory analysis for REAL compiled
        # objects: the run must proceed, counted, with the armed gate
        # skipping (capacity known, footprint unknowable)
        monkeypatch.setattr(xla_stats, "memory_breakdown",
                            lambda compiled: None)
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.9,
                      "FLAGS_hbm_bytes_per_device": 1})
        stat_reset("xla_memory_analysis_unavailable")
        exe, scope, main, loss = _fresh_executor()
        out = exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert np.isfinite(out[0]).all()
        assert stat_get("xla_memory_analysis_unavailable") >= 1

    def test_introspection_parity(self, restore_flags):
        """Same program, same seed: losses bitwise-equal with the AOT
        introspection path on vs off (the compiled executable must be
        the same computation the lazy path would have traced)."""
        losses = {}
        for flag in (True, False):
            pt.set_flags({"FLAGS_xla_introspect": flag})
            exe, scope, main, loss = _fresh_executor(_train_program(7))
            vals = []
            for _ in range(3):
                out = exe.run(main, feed=_feed(), fetch_list=[loss],
                              scope=scope)
                vals.append(np.asarray(out[0]).copy())
            exe.drain()
            losses[flag] = np.concatenate(vals)
        np.testing.assert_array_equal(losses[True], losses[False])

    def test_hlo_dump_dir(self, restore_flags, tmp_path):
        d = tmp_path / "hlo"
        pt.set_flags({"FLAGS_hlo_dump_dir": str(d)})
        exe, scope, main, loss = _fresh_executor()
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        exe.drain()
        dumps = sorted(d.glob("hlo_*.txt"))
        assert dumps, "no optimized-HLO dumps written"
        assert dumps[0].stat().st_size > 0
        assert xla_stats.last_compile().get("hlo_dump_path")


# ---------------------------------------------------------------------------
# live HBM telemetry: heartbeat fields + cluster aggregation
# ---------------------------------------------------------------------------


class _FakeDevice:
    def __init__(self, limit, used):
        self._s = {"bytes_limit": limit, "bytes_in_use": used}

    def memory_stats(self):
        return self._s


class TestDeviceMemoryTelemetry:
    def test_record_device_memory_gauges_min_free(self):
        devs = [_FakeDevice(1000, 100), _FakeDevice(1000, 700)]
        out = xla_stats.record_device_memory(devs)
        # min free across chips: the one that OOMs first
        assert out["hbm_free_bytes"] == 300
        assert out["hbm_used_bytes"] == 700
        assert out["hbm_limit_bytes"] == 1000
        assert stat_get("hbm_free_bytes") == 300
        assert stat_get("hbm_used_bytes") == 700

    def test_cpu_devices_capability_skip(self):
        import jax

        # the CPU backend has no memory stats: {} — never a crash, and
        # the heartbeat payload simply omits the hbm fields
        assert xla_stats.record_device_memory(jax.local_devices()) == {}

    def test_no_device_probe_before_backend_in_use(self, monkeypatch):
        """The heartbeat thread samples through the default path; until
        the Executor's first compile marks the backend in use, it must
        not touch jax at all — jax.local_devices() on an uninitialized
        (possibly dead) backend IS the 240s device-init hang the
        health plane exists to survive (the PR 6 topology rule)."""
        monkeypatch.setattr(xla_stats, "_BACKEND_IN_USE", False)

        def boom(device=None):  # any probe here is the bug
            raise AssertionError("device probed before backend in use")

        monkeypatch.setattr(xla_stats, "device_memory_stats", boom)
        assert xla_stats.record_device_memory() == {}
        # explicit devices (tests, supervisors) still bypass the gate
        monkeypatch.setattr(xla_stats, "device_memory_stats",
                            lambda d=None: {"bytes_limit": 10,
                                            "bytes_in_use": 4})
        assert xla_stats.record_device_memory(
            [object()])["hbm_free_bytes"] == 6

    def test_memory_report_never_probes_by_default(self, monkeypatch):
        """dump_postmortem fires exactly when a device call is hung: the
        memory.json section must read the cached heartbeat gauges, not
        re-probe the wedged PJRT runtime."""
        monkeypatch.setattr(xla_stats, "_BACKEND_IN_USE", True)
        monkeypatch.setattr(
            xla_stats, "device_memory_stats",
            lambda d=None: (_ for _ in ()).throw(
                AssertionError("live probe on the dump path")))
        from paddle_tpu.monitor import stat_set

        stat_set("hbm_free_bytes", 777)
        rep = xla_stats.memory_report()
        assert rep["device_memory"] == []
        assert rep["hbm_gauges"]["hbm_free_bytes"] == 777
        stat_set("hbm_free_bytes", 0)

    def test_heartbeat_payload_carries_hbm_fields(self, monkeypatch):
        monkeypatch.setattr(
            xla_stats, "record_device_memory",
            lambda devices=None: {"hbm_free_bytes": 123,
                                  "hbm_used_bytes": 7,
                                  "hbm_limit_bytes": 130})
        stats = health._default_rank_stats()
        assert stats["hbm_free_bytes"] == 123

    def test_cluster_health_min_free_across_ranks(self):
        import time as _time

        now = _time.time()
        kv = {
            "health/rank/0": json.dumps(
                {"rank": 0, "ts": now, "interval_s": 10.0,
                 "hbm_free_bytes": 5000}),
            "health/rank/1": json.dumps(
                {"rank": 1, "ts": now, "interval_s": 10.0,
                 "hbm_free_bytes": 2000}),
        }
        out = health.cluster_health(kv, world_size=2, now=now)
        assert out["min_hbm_free_bytes"] == 2000
        assert out["min_hbm_free_rank"] == 1
        assert stat_get("cluster_min_hbm_free_bytes") == 2000
        # a fleet without hbm reporters (CPU) omits the key
        for v in kv:
            kv[v] = json.dumps({"rank": 0, "ts": now, "interval_s": 10.0})
        out = health.cluster_health(kv, world_size=2, now=now)
        assert "min_hbm_free_bytes" not in out


# ---------------------------------------------------------------------------
# memory.json: postmortem bundle section + pure-stdlib CLI rendering
# ---------------------------------------------------------------------------


class TestMemoryJsonBundle:
    def _bundle_with_record(self, tmp_path, restore=None):
        xla_stats.clear_compile_records()
        pt.set_flags({"FLAGS_hbm_budget_fraction": 0.5,
                      "FLAGS_hbm_bytes_per_device": 1000})
        try:
            xla_stats.on_compile(
                _FakeCompiled(), fingerprint="feedface", seconds=0.5,
                size_entries=[("giant.w_0", (128, 128), "float32",
                               "state")])
        except MemoryBudgetError:
            pass  # 1600 > 500: the rejection is part of the fixture
        finally:
            pt.set_flags({"FLAGS_hbm_budget_fraction": 0.0,
                          "FLAGS_hbm_bytes_per_device": 0})
        return health.dump_postmortem("memtest", directory=str(tmp_path))

    def test_bundle_has_memory_section(self, tmp_path):
        bundle = self._bundle_with_record(tmp_path)
        with open(f"{bundle}/memory.json") as f:
            mem = json.load(f)
        assert mem["compiles"], "compile records missing from bundle"
        last = mem["compiles"][-1]
        assert last["memory"]["total_bytes"] == 1600
        assert last["budget"]["verdict"] == "rejected"
        # the rejection keeps its numbers (they matter MOST here)
        assert last["budget"]["required_bytes"] == 1600
        assert last["budget"]["budget_bytes"] == 500
        assert last["budget"]["capacity_bytes"] == 1000
        assert last["attribution"][0]["name"] == "giant.w_0"
        with open(f"{bundle}/meta.json") as f:
            meta = json.load(f)
        assert "memory.json" not in meta.get("section_errors", {})

    def test_postmortem_cli_renders_memory(self, tmp_path):
        from tools import postmortem

        bundle = self._bundle_with_record(tmp_path)
        buf = io.StringIO()
        assert postmortem.render(bundle, out=buf) == 0
        txt = buf.getvalue()
        assert "xla compiles recorded" in txt
        assert "giant.w_0" in txt
        assert "per-chip footprint" in txt
        assert "budget gate: rejected" in txt
        assert "memory.json" in txt  # listed among the bundle files


# ---------------------------------------------------------------------------
# /metrics well-formedness with the new gauges under concurrent scrape
# ---------------------------------------------------------------------------


class TestConcurrentScrapeWithXlaGauges:
    def test_scrape_while_compiles_record(self):
        """4 scrapers x 25 GETs over real HTTP while a thread feeds
        compile records (compile_seconds histogram + hbm/executable
        gauges): every exposition must stay well-formed and carry the
        new series — including the phase-attribution and profiler-
        capture gauges."""
        from paddle_tpu.distributed.fleet.utils.http_server import KVServer
        from paddle_tpu.monitor import stat_set
        from paddle_tpu.observe import phases as phases_mod

        # seed one record so the first scrape already sees the series
        xla_stats.on_compile(_FakeCompiled(), seconds=0.01)
        phases_mod.reset_phases()
        phases_mod.phase_engine().on_step_drained(
            wall_s=0.01, sync_s=0.005, host_s=0.001)
        stat_set("prof_capture_latched", 0)
        srv = KVServer(0)
        srv.start()
        stop = threading.Event()
        errors = []

        def compiler():
            i = 0
            while not stop.is_set():
                i += 1
                xla_stats.on_compile(
                    _FakeCompiled(flops=float(i)), seconds=1e-4 * i,
                    fingerprint=f"fp{i}",
                    size_entries=[("w", (i % 7 + 1, 8), "float32",
                                   "state")])

        def scraper():
            url = f"http://127.0.0.1:{srv.port}/metrics"
            for _ in range(25):
                try:
                    with urllib.request.urlopen(url, timeout=10) as r:
                        assert r.status == 200
                        body = r.read().decode()
                    for ln in body.splitlines():
                        if ln and not ln.startswith("#"):
                            float(ln.rsplit(" ", 1)[1])
                    assert "paddle_tpu_compile_seconds_bucket" in body
                    assert "paddle_tpu_hbm_required_bytes" in body
                    assert "paddle_tpu_executable_size_bytes" in body
                    assert "paddle_tpu_phase_compute_seconds_micro" \
                        in body
                    assert "paddle_tpu_phase_compute_fraction_ppm" \
                        in body
                    assert "paddle_tpu_prof_capture_latched" in body
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        t = threading.Thread(target=compiler, daemon=True)
        scrapers = [threading.Thread(target=scraper) for _ in range(4)]
        t.start()
        for s in scrapers:
            s.start()
        for s in scrapers:
            s.join()
        stop.set()
        t.join(timeout=10)
        srv.stop()
        assert errors == []
