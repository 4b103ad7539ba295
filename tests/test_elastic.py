"""fleet.elastic — the preemption-proof training supervisor (ISSUE 14).

Acceptance oracle: a chaos-injected rank kill mid-step re-shards onto
the smaller topology via the supervisor and the FULL trajectory
(losses + final params) is bitwise the uninterrupted run's — the
extension of ``test_ckpt.test_async_crash_resume_bitwise_parity`` to
topology loss.  Every other classified failure path (preflight
init-timeout/compile-error, watchdog stall, torn checkpoint, dead-rank
detection, poison step, budget exhaustion) is pinned here too, all
driven through ``elastic.chaos`` — the paths run every suite, not only
when real hardware dies.
"""
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.ckpt import CheckpointManager
from paddle_tpu.distributed.fleet import elastic
from paddle_tpu.distributed.fleet.elastic import chaos
from paddle_tpu.framework.scope import Scope
from paddle_tpu.monitor import stat_get


@pytest.fixture(autouse=True)
def _chaos_and_postmortem(tmp_path):
    """Every test starts with a disarmed armory and its own postmortem
    dir (supervisor bundles must not litter the repo).  ckpt fsync is
    off per its own flag doc (throwaway dirs; torn-save coverage here
    uses fault injection, not real crashes) — the suite runs near the
    tier-1 budget and these tests save every step."""
    chaos.clear()
    old = pt.get_flags(["FLAGS_postmortem_dir", "FLAGS_ckpt_fsync"])
    pt.set_flags({"FLAGS_postmortem_dir": str(tmp_path / "postmortem"),
                  "FLAGS_ckpt_fsync": False})
    yield
    chaos.clear()
    pt.set_flags(old)


# ---------------------------------------------------------------------------
# preflight: in-process probe under a deadline + structured verdicts
# ---------------------------------------------------------------------------


class TestPreflight:
    def test_ok_probe_reports_platform(self):
        v = elastic.preflight_device(
            attempts=1, timeout_s=30, probe=lambda: "cpu")
        assert v.ok and v.verdict == "ok"
        assert v.platform == "cpu" and v.attempts == 1
        assert v.to_dict()["verdict"] == "ok"

    def test_init_timeout_bounded_with_exponential_backoff(self):
        """A probe that never finishes init cannot hang the caller:
        the deadline converts it to a structured init_timeout, and
        retries back off exponentially."""
        import threading

        sleeps = []
        release = threading.Event()
        try:
            v = elastic.preflight_device(
                attempts=3, timeout_s=0.3, backoff_s=0.5,
                probe=lambda: release.wait(60), sleep_fn=sleeps.append)
        finally:
            release.set()  # let the three wedged probe threads go
        assert not v.ok and v.verdict == "init_timeout"
        assert v.attempts == 3
        assert sleeps == [0.5, 1.0]  # backoff * 2^k, no sleep after last
        assert "did not complete" in v.diag

    @staticmethod
    def _kaboom():
        raise RuntimeError("XLA kaboom")

    def test_compile_error_carries_the_exception(self):
        v = elastic.preflight_device(
            attempts=1, timeout_s=30, probe=self._kaboom)
        assert v.verdict == "compile_error" and not v.ok
        assert "kaboom" in v.diag and "RuntimeError" in v.diag

    def test_chaos_injected_timeout_then_recovers(self):
        """The r04/r05 failure on demand: one injected init-timeout,
        then the retry succeeds — the probe does not run for the
        injected attempt."""
        chaos.inject("preflight_init_timeout", count=1)
        sleeps = []
        before = stat_get("elastic_preflight_init_timeout")
        v = elastic.preflight_device(
            attempts=2, timeout_s=5, backoff_s=0.1,
            probe=lambda: "cpu", sleep_fn=sleeps.append)
        assert v.ok and v.attempts == 2 and sleeps == [0.1]
        assert stat_get("elastic_preflight_init_timeout") == before + 1
        assert chaos.armed() == []  # consumed


# ---------------------------------------------------------------------------
# supervisor over a pure-host toy program (fast classification paths)
# ---------------------------------------------------------------------------


class _Toy:
    """Deterministic 'training': the state is one float accumulating
    the batches; checkpointable via the state()/load_state() half of
    the program protocol."""

    def __init__(self):
        self.w = 0.0

    def step(self, batch):
        self.w += float(batch)
        return self.w

    def state(self):
        return {"w": np.asarray([self.w], dtype="f8")}

    def load_state(self, state):
        self.w = float(np.asarray(state["w"]).ravel()[0])


_BATCHES = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
_CUMSUM = [1.0, 3.0, 6.0, 10.0, 15.0, 21.0]


def _sup(**kw):
    kw.setdefault("preflight", False)
    kw.setdefault("backoff_s", 0.0)
    return elastic.ElasticSupervisor(**kw)


class TestSupervisor:
    def test_plain_run_ok(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        r = _sup(world_size=1).run(lambda topo: _Toy(), manager=mgr,
                                   loader=_BATCHES, total_steps=6)
        mgr.close()
        assert r.status == "ok" and r.restarts == 0 and r.reshards == 0
        assert r.losses == _CUMSUM and r.final_step == 6

    def test_kill_rank_reshards_and_resumes(self, tmp_path):
        """kill_rank_mid_step -> topology_change -> world 2 -> 1,
        restore from the latest intact step, fast-forward the
        iterator, continue: the trajectory matches the uninterrupted
        one and the failure left a postmortem bundle + history."""
        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        chaos.inject("kill_rank_mid_step", rank=1, at_step=4)
        r = _sup(world_size=2).run(lambda topo: _Toy(), manager=mgr,
                                   loader=_BATCHES, total_steps=6)
        mgr.close()
        assert r.status == "recovered"
        assert r.restarts == 1 and r.reshards == 1
        assert r.final_world_size == 1
        assert r.losses == _CUMSUM
        h = r.history[0]
        assert h["kind"] == "topology_change" and h["step"] == 4
        assert h["dead_ranks"] == [1]
        bundles = os.listdir(tmp_path / "postmortem")
        assert any("elastic_topology_change" in b for b in bundles)

    def test_preflight_timeout_and_rank_kill_in_one_run(self, tmp_path):
        """The supervisor's own preflight (on by default, the real
        in-process probe) with one injected init-timeout, then a rank
        killed mid-step: the run retries the probe, re-shards, and ends
        with every step's real number — not the 0.0 of a dead round."""
        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        chaos.inject("preflight_init_timeout", count=1)
        chaos.inject("kill_rank_mid_step", rank=1, at_step=4)
        r = elastic.ElasticSupervisor(
            world_size=2, preflight_attempts=2, preflight_timeout_s=60.0,
            backoff_s=0.0).run(lambda topo: _Toy(), manager=mgr,
                               loader=_BATCHES, total_steps=6)
        mgr.close()
        assert r.status == "recovered"
        assert r.preflight_retries == 1
        assert r.restarts == 1 and r.reshards == 1
        assert r.final_world_size == 1 and r.losses == _CUMSUM
        assert chaos.armed() == []

    def test_train_fn_sees_shrunken_topology(self, tmp_path):
        worlds = []

        def train_fn(topo):
            worlds.append((topo.world_size, tuple(topo.ranks)))
            return _Toy()

        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        chaos.inject("kill_rank_mid_step", rank=1, at_step=2)
        _sup(world_size=3).run(train_fn, manager=mgr, loader=_BATCHES,
                               total_steps=4)
        mgr.close()
        assert worlds == [(3, (0, 1, 2)), (2, (0, 2))]

    def test_poison_step_terminates_loudly(self, tmp_path):
        """The same step failing identically twice is poison: replay
        cannot help, so the supervisor terminates with the history —
        it must NOT burn the whole restart budget first."""

        class Bad(_Toy):
            def step(self, batch):
                if float(batch) == 3.0:
                    raise ValueError("deterministic step bug")
                return super().step(batch)

        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        with pytest.raises(elastic.ElasticTerminated,
                           match="poison") as ei:
            _sup(world_size=1, max_restarts=10).run(
                lambda topo: Bad(), manager=mgr, loader=_BATCHES,
                total_steps=6)
        mgr.close()
        assert len(ei.value.history) == 2  # first + identical repeat
        assert all(h["step"] == 3 for h in ei.value.history)

    def test_restart_budget_exhaustion_is_terminal_not_a_hang(self):
        """Distinct transient failures every attempt: the budget bounds
        them and the terminal error names it — never a silent hang."""
        n = [0]

        def train_fn(topo):
            n[0] += 1

            def step(i, batch):
                raise RuntimeError(f"flaky device episode {n[0]}")

            return step

        with pytest.raises(elastic.ElasticTerminated,
                           match="budget") as ei:
            _sup(world_size=1, max_restarts=2).run(
                train_fn, total_steps=3)
        assert len(ei.value.history) == 3  # initial + 2 restarts

    def test_dead_rank_detection_from_cluster_plane(self, tmp_path):
        """The health plane dead-lists rank 1 while the loop runs: the
        supervisor notices via its cluster poll, classifies
        topology_change, and re-shards without any exception from the
        train step itself."""
        seen = {"steps": 0}

        class Counting(_Toy):
            def step(self, batch):
                seen["steps"] += 1
                return super().step(batch)

        def cluster_fn():
            return {"dead_ranks": [1] if seen["steps"] >= 2 else []}

        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        r = _sup(world_size=2, cluster_fn=cluster_fn,
                 cluster_poll_s=0.0).run(
            lambda topo: Counting(), manager=mgr, loader=_BATCHES,
            total_steps=5)
        mgr.close()
        assert r.reshards == 1 and r.final_world_size == 1
        assert r.history[0]["kind"] == "topology_change"
        assert r.history[0]["dead_ranks"] == [1]
        assert r.losses == _CUMSUM[:5]

    def test_watchdog_stall_dumps_bundle_and_restarts_in_place(
            self, tmp_path):
        """hang_device_call holds the step window past the watchdog
        timeout: the PR 6 watchdog trips (bundle dumped), the attempt
        is classified transient, and the restart completes the run."""
        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        chaos.inject("hang_device_call", at_step=3, seconds=0.7)
        r = _sup(world_size=1, watchdog_timeout_s=0.15).run(
            lambda topo: _Toy(), manager=mgr, loader=_BATCHES,
            total_steps=5)
        mgr.close()
        assert r.status == "recovered" and r.restarts == 1
        assert r.reshards == 0  # restart IN PLACE: same world
        assert r.history[0]["kind"] == "transient"
        assert "StallDetected" in r.history[0]["error"]
        assert r.losses == _CUMSUM[:5]
        bundles = os.listdir(tmp_path / "postmortem")
        # one bundle from the watchdog trip itself + one from the
        # supervisor's failure record
        assert any(b.startswith("bundle_") and "stall" in b
                   for b in bundles)

    def test_torn_checkpoint_falls_back_and_recovers(self, tmp_path):
        """torn_checkpoint kills the writer pre-commit at step 4: the
        save fails (transient), restore falls back to intact step 3,
        and the replay commits a clean step 4..6."""
        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        chaos.inject("torn_checkpoint", at_step=4)
        r = _sup(world_size=1).run(lambda topo: _Toy(), manager=mgr,
                                   loader=_BATCHES, total_steps=6)
        assert r.status == "recovered" and r.restarts == 1
        assert "TornCheckpoint" in r.history[0]["error"]
        assert r.losses == _CUMSUM
        assert mgr.latest_intact_step() == 6
        mgr.close()

    def test_no_manager_runs_unsupervised_checkpointing(self):
        r = _sup(world_size=1).run(lambda topo: _Toy(),
                                   loader=_BATCHES, total_steps=4)
        assert r.losses == _CUMSUM[:4] and r.status == "ok"

    def test_stateless_program_with_manager_skips_saves(self, tmp_path):
        """A bare callable has nothing to checkpoint: the supervisor
        must run it (saves skipped) rather than crash the first save
        and read the crash as a poison step."""
        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        r = _sup(world_size=1).run(
            lambda topo: (lambda i, batch: float(batch)),
            manager=mgr, loader=_BATCHES, total_steps=3)
        assert r.losses == _BATCHES[:3] and r.status == "ok"
        assert mgr.all_steps() == []  # nothing was saved
        mgr.close()

    def test_caller_fault_hook_chained_and_restored(self, tmp_path):
        """The supervisor chains the chaos ckpt hook in FRONT of a
        caller-installed one (both fire) and restores the caller's
        when the run ends."""
        mgr = CheckpointManager(str(tmp_path / "c"), keep_n=0,
                                async_save=False)
        phases = []

        def user_hook(phase, step):
            phases.append((phase, step))

        mgr.set_fault_hook(user_hook)
        r = _sup(world_size=1).run(lambda topo: _Toy(), manager=mgr,
                                   loader=_BATCHES, total_steps=2)
        assert r.status == "ok"
        assert ("pre_commit", 1) in phases  # caller's hook still fired
        assert mgr._fault_hook is user_hook  # and was restored
        mgr.close()

    def test_classify_failure_table(self):
        assert elastic.classify_failure(
            chaos.RankKilled(2)) == "topology_change"
        assert elastic.classify_failure(
            RuntimeError("x"), dead_ranks=[1]) == "topology_change"
        assert elastic.classify_failure(
            RuntimeError("x")) == "transient"
        assert elastic.classify_failure(
            RuntimeError("x"), repeat=True) == "poison_step"
        from paddle_tpu.observe.xla_stats import MemoryBudgetError

        assert elastic.classify_failure(
            MemoryBudgetError("too big")) == "poison_step"
        # a budget refusal is poison even on its FIRST occurrence
        assert elastic.is_device_failure(RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory".lower()))
        assert not elastic.is_device_failure(KeyError("shape"))


# ---------------------------------------------------------------------------
# THE acceptance test: rank kill mid-step -> re-shard -> bitwise parity
# (extends test_ckpt.test_async_crash_resume_bitwise_parity to topology
# loss: same full-state model — params, Momentum slots, LR schedule,
# RNG/dropout, AMP loss-scale counters, iterator position)
# ---------------------------------------------------------------------------


def _full_train_fn():
    """Supervisor-protocol wrapper around test_ckpt's full-state model
    (fc -> dropout -> fc, Momentum + StepDecay + dynamic loss scaling):
    a fresh build per (re)start, exactly like a restarted process."""
    from test_ckpt import _build_full_model

    def train_fn(topo):
        main, startup, loss, sched = _build_full_model()
        sc = Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=sc)

        class Prog:
            scope = sc
            components = {"lr_sched": sched}

            def step(self, batch):
                bx, by = batch
                out = exe.run(main, feed={"x": bx, "y": by},
                              fetch_list=[loss], scope=sc)
                sched.step()
                return float(np.asarray(out[0]).ravel()[0])

            def params(self):
                return {n: np.asarray(sc.get_var(n))
                        for n in sc.local_var_names()
                        if hasattr(sc.get_var(n), "dtype")}

        return Prog()

    return train_fn


def _full_loader():
    from paddle_tpu.io import DataLoader, TensorDataset

    rs = np.random.RandomState(0)
    X = rs.randn(32, 8).astype("f4")
    Y = (X.sum(1, keepdims=True) * 0.3).astype("f4")
    return DataLoader(TensorDataset([X, Y]), batch_size=8,
                      shuffle=False)


def test_chaos_rank_kill_reshards_bitwise(tmp_path):
    """ISSUE 14 acceptance: chaos kills rank 1 mid-step 5 of a 2-rank
    run; the supervisor classifies topology_change, re-shards to the
    surviving world (1), restores the latest intact async checkpoint,
    fast-forwards the ResumableIterator, and continues — the full loss
    trajectory AND final state (params + optimizer slots + LR step +
    RNG + loss-scale) are bitwise the uninterrupted run's."""
    # oracle: uninterrupted supervised run at the surviving world size
    mo = CheckpointManager(str(tmp_path / "oracle"), keep_n=0,
                           async_save=True)
    ro = _sup(world_size=1).run(_full_train_fn(), manager=mo,
                                loader=_full_loader(), total_steps=7)
    mo.close()
    assert ro.status == "ok" and len(ro.losses) == 7
    oracle_params = ro.train.params()

    # chaos run: rank 1 dies mid-step 5
    chaos.inject("kill_rank_mid_step", rank=1, at_step=5)
    mc = CheckpointManager(str(tmp_path / "chaos"), keep_n=0,
                           async_save=True)
    before = stat_get("elastic_reshards")
    rc = _sup(world_size=2).run(_full_train_fn(), manager=mc,
                                loader=_full_loader(), total_steps=7)
    mc.close()
    assert rc.status == "recovered"
    assert rc.restarts == 1 and rc.reshards == 1
    assert rc.final_world_size == 1
    assert stat_get("elastic_reshards") == before + 1
    # the restart resumed from a committed step, not from scratch
    assert rc.history[0]["kind"] == "topology_change"

    # losses bitwise (replayed steps overwrote their first emission)
    np.testing.assert_array_equal(rc.losses, ro.losses)
    # final state bitwise across every state family
    chaos_params = rc.train.params()
    assert sorted(chaos_params) == sorted(oracle_params)
    for n in oracle_params:
        np.testing.assert_array_equal(chaos_params[n], oracle_params[n],
                                      err_msg=n)
