"""Scan-over-layers (framework/passes.py LayerScanPass + ops/layer_scan.py).

Oracles: the scanned program must be BITWISE equal to the unrolled one
— per-step losses, parameters, AND optimizer slots, including the
dropout RNG stream — while trace+compile time and executable HLO op
count collapse from linear-in-depth to ~constant.  The acceptance
number (48 deep, >=5x compile drop) is asserted here via the
``compile_seconds`` histogram the Executor feeds, and checkpoints stay
per-layer so resume is elastic across the scan flag.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import passes as passes_mod
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.program import Program, program_guard
from paddle_tpu.initializer import ConstantInitializer, NormalInitializer
from paddle_tpu.optimizer import MomentumOptimizer
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.monitor import stat_get, stat_reset, stat_set

# mesh8 / mesh_dp_mp fixtures: shared in tests/conftest.py

SKIP_REASONS = (
    "no_repeats", "stack_align", "rename_conflict", "input_classify",
    "output_classify", "shared_written", "outside_write",
    "family_mismatch", "tp_spec_mismatch", "ys_conflict", "var_missing",
)


def _reset_scan_stats():
    for k in ("pass_layer_scan_segments", "pass_layer_scan_layers",
              "pass_layer_scan_skipped"):
        stat_reset(k)
    for r in SKIP_REASONS:
        stat_reset("pass_layer_scan_skipped_" + r)


@pytest.fixture(autouse=True)
def _scan_flag_reset():
    yield
    pt.set_flags({"FLAGS_layer_scan": False,
                  "FLAGS_layer_scan_min_layers": 4,
                  "FLAGS_layer_scan_policy": "",
                  "FLAGS_layer_scan_unroll": 1})


def _build_mlp(n_layers=6, width=16, in_dim=8, dropout=0.1,
               fleet_strategy=None, ffn=0, optimizer=None):
    """Repeated-layer MLP; with ``ffn`` a 2-sublayer (expand/contract)
    transformer-ffn-shaped block."""
    from paddle_tpu.distributed import fleet

    main, startup = Program(), Program()
    main.random_seed = 7
    with unique_name.guard(), program_guard(main, startup):
        x = layers.data("x", [in_dim])
        y = layers.data("y", [1])
        h = x
        for i in range(n_layers):
            if ffn:
                h1 = layers.fc(h, ffn, act="relu", name=f"blk{i}_ffn1",
                               param_attr=ParamAttr(
                                   initializer=NormalInitializer(0.0, 0.05)))
                h = layers.fc(h1, width, name=f"blk{i}_ffn2",
                              param_attr=ParamAttr(
                                  initializer=ConstantInitializer(0.02)),
                              bias_attr=False)
            else:
                h = layers.fc(h, width, act="relu", param_attr=ParamAttr(
                    name=f"blk{i}.w",
                    initializer=ConstantInitializer(0.02 * (i + 1))),
                    bias_attr=ParamAttr(name=f"blk{i}.b",
                                        initializer=ConstantInitializer(0.0)))
            if dropout:
                h = layers.dropout(h, dropout_prob=dropout)
        pred = layers.fc(h, 1, param_attr=ParamAttr(
            name="head.w", initializer=ConstantInitializer(0.1)),
            bias_attr=False)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = optimizer or MomentumOptimizer(0.05, 0.9)
        if fleet_strategy is not None:
            fleet.init(is_collective=True, strategy=fleet_strategy)
            fleet.distributed_optimizer(opt)
            fleet.minimize(loss)
        else:
            opt.minimize(loss)
    return main, startup, loss


def _train(main, startup, loss, X, Y, steps=4, mesh=None, scope=None,
           exe=None, run_startup=True):
    if scope is None:
        scope = pt.framework.Scope()
    if exe is None:
        exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
    if run_startup:
        exe.run(startup, scope=scope)
    losses = [float(np.asarray(
        exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                scope=scope)[0]).item()) for _ in range(steps)]
    return losses, scope, exe


def _state(scope):
    """Per-layer params + optimizer slots as host arrays (reads through
    StackedParamRef views on a scanned scope)."""
    return {n: np.asarray(scope.get_var(n)).copy()
            for n in scope.local_var_names()
            if ("blk" in n or "head" in n)
            and not n.startswith(passes_mod.LAYER_STACK_PREFIX)}


def _data(in_dim=8, n=16, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(n, in_dim).astype("f4"),
            rs.randn(n, 1).astype("f4"))


class TestAcceptance:
    def test_depth48_compile_drops_5x_bitwise(self):
        """The acceptance oracle: a 48-deep transformer-ffn-block stack
        scanned against unrolled — the optimized executable's HLO op
        count shrinks superlinearly (the compile cost, as a COUNT: a
        ratio of two CPU compile times is not a property of the tree
        under six loaded workers), and 4 train steps stay bitwise —
        losses, params, Momentum slots, dropout RNG."""
        X, Y = _data(32)

        def once(scan):
            pt.set_flags({"FLAGS_layer_scan": scan})
            _reset_scan_stats()
            m, s, l = _build_mlp(n_layers=48, width=32, in_dim=32,
                                 dropout=0.1, ffn=128)
            scope = pt.framework.Scope()
            exe = pt.Executor(pt.CPUPlace())
            exe.run(s, scope=scope)
            losses, _, _ = _train(m, s, l, X, Y, scope=scope, exe=exe,
                                  run_startup=False)
            hlo = int(stat_get("executable_hlo_ops") or 0)
            segs = int(stat_get("pass_layer_scan_segments") or 0)
            state = _state(scope)
            exe.close()
            return losses, hlo, segs, state

        u_losses, u_hlo, _, u_state = once(False)
        s_losses, s_hlo, segs, s_state = once(True)

        # forward, backward, and optimizer regions all scan
        assert segs == 3, segs
        assert stat_get("pass_layer_scan_layers") >= 3 * 46
        # executable size ~constant in depth instead of linear: the
        # unrolled HLO is ~8x the scanned one at depth 48
        assert s_hlo * 6 < u_hlo, (s_hlo, u_hlo)
        # bitwise step parity
        np.testing.assert_array_equal(u_losses, s_losses)
        assert u_state.keys() == s_state.keys()
        for n in u_state:
            np.testing.assert_array_equal(u_state[n], s_state[n],
                                          err_msg=n)


class TestParity:
    def test_bitwise_parity_dropout_momentum(self):
        X, Y = _data()
        pt.set_flags({"FLAGS_layer_scan": False})
        base_losses, base_scope, _ = _train(*_build_mlp(), X, Y)

        pt.set_flags({"FLAGS_layer_scan": True})
        _reset_scan_stats()
        scan_losses, scan_scope, _ = _train(*_build_mlp(), X, Y)
        assert stat_get("pass_layer_scan_segments") >= 1
        np.testing.assert_array_equal(base_losses, scan_losses)
        b, s = _state(base_scope), _state(scan_scope)
        assert b.keys() == s.keys()
        assert any("velocity" in n for n in b), "slots missing from oracle"
        for n in b:
            np.testing.assert_array_equal(b[n], s[n], err_msg=n)

    def test_dp_mesh_parity(self, mesh8):
        from paddle_tpu.distributed import fleet

        X, Y = _data()

        def strat():
            st = fleet.DistributedStrategy()
            st.fuse_all_reduce_ops = False
            return st

        pt.set_flags({"FLAGS_layer_scan": False})
        with unique_name.guard():
            m, s, l = _build_mlp(fleet_strategy=strat())
        base_losses, base_scope, _ = _train(m, s, l, X, Y, mesh=mesh8)

        pt.set_flags({"FLAGS_layer_scan": True})
        _reset_scan_stats()
        with unique_name.guard():
            m, s, l = _build_mlp(fleet_strategy=strat())
        scan_losses, scan_scope, _ = _train(m, s, l, X, Y, mesh=mesh8)
        assert stat_get("pass_layer_scan_segments") >= 1
        np.testing.assert_array_equal(base_losses, scan_losses)
        b, s_ = _state(base_scope), _state(scan_scope)
        for n in b:
            np.testing.assert_array_equal(b[n], s_[n], err_msg=n)

    def test_fuse_scan_composition_parity(self, mesh8):
        """Fuse x scan regression: the scanned program's layer_index
        materializations read the stacked grad carrier right after its
        pulled-out allreduce, so FuseAllReducePass must close the
        bucket at that read barrier — without it the coalesced
        reduction lands after the read and the optimizer consumes
        pre-reduce grads (caught as a ~1e-2 loss drift by this test)."""
        from paddle_tpu.distributed import fleet

        X, Y = _data()

        def run(fuse, scan):
            pt.set_flags({"FLAGS_layer_scan": scan})
            st = fleet.DistributedStrategy()
            st.fuse_all_reduce_ops = fuse
            with unique_name.guard():
                m, s, l = _build_mlp(fleet_strategy=st)
            losses, scope, _ = _train(m, s, l, X, Y, mesh=mesh8)
            return losses, _state(scope)

        base_losses, base_state = run(fuse=False, scan=False)
        _reset_scan_stats()
        losses, state = run(fuse=True, scan=True)
        assert stat_get("pass_layer_scan_segments") >= 1
        np.testing.assert_array_equal(base_losses, losses)
        for n in base_state:
            np.testing.assert_array_equal(base_state[n], state[n],
                                          err_msg=n)

    def test_tp_scan_composition(self, mesh_dp_mp):
        """TP x scan on the 2x4 mesh: bitwise parity vs the unrolled tp
        run, and the stacked carrier's sharding applies the per-layer
        spec with the stack axis replicated."""
        from paddle_tpu.distributed import fleet

        rules = [(r"blk\d+_ffn1\.w_\d+$", "None,mp"),
                 (r"blk\d+_ffn1\.b_\d+$", "mp"),
                 (r"blk\d+_ffn2\.w_\d+$", "mp,None")]
        X, Y = _data(32)

        def build():
            st = fleet.DistributedStrategy()
            st.tensor_parallel = True
            st.tensor_parallel_configs = {"partition_rules": rules}
            with unique_name.guard():
                return _build_mlp(n_layers=6, width=32, in_dim=32,
                                  dropout=0.0, ffn=64, fleet_strategy=st)

        pt.set_flags({"FLAGS_layer_scan": False})
        base_losses, base_scope, _ = _train(*build(), X, Y, mesh=mesh_dp_mp)

        pt.set_flags({"FLAGS_layer_scan": True})
        _reset_scan_stats()
        scan_losses, scan_scope, _ = _train(*build(), X, Y, mesh=mesh_dp_mp)
        assert stat_get("pass_layer_scan_segments") >= 1
        np.testing.assert_array_equal(base_losses, scan_losses)
        for n in _state(base_scope):
            np.testing.assert_array_equal(
                np.asarray(base_scope.get_var(n)),
                np.asarray(scan_scope.get_var(n)), err_msg=n)
        # the carrier is mp-sharded on the per-layer dim, replicated on
        # the leading stack axis
        carriers = [n for n in scan_scope.local_var_names()
                    if n.startswith(passes_mod.LAYER_STACK_PREFIX)
                    and "ffn1.w" in n]
        assert carriers
        v = scan_scope.get_var(carriers[0])
        spec = tuple(v.sharding.spec)
        assert v.ndim == 3 and spec[0] is None and "mp" in spec, (
            carriers[0], v.shape, spec)

    def test_remat_policy_parity_and_unroll_knob(self):
        """jax.checkpoint wrapping and lax.scan unroll>1 change neither
        the primal losses nor the trained state."""
        X, Y = _data()
        pt.set_flags({"FLAGS_layer_scan": True})
        base_losses, base_scope, _ = _train(*_build_mlp(), X, Y)

        for flags in ({"FLAGS_layer_scan_policy": "dots_saveable"},
                      {"FLAGS_layer_scan_policy": "nothing_saveable"},
                      {"FLAGS_layer_scan_unroll": 2}):
            pt.set_flags({"FLAGS_layer_scan_policy": "",
                          "FLAGS_layer_scan_unroll": 1, **flags})
            _reset_scan_stats()
            losses, scope, _ = _train(*_build_mlp(), X, Y)
            assert stat_get("pass_layer_scan_segments") >= 1, flags
            np.testing.assert_array_equal(base_losses, losses,
                                          err_msg=str(flags))
            b, s = _state(base_scope), _state(scope)
            for n in b:
                np.testing.assert_array_equal(b[n], s[n], err_msg=n)


class TestElasticity:
    def test_ckpt_roundtrip_into_unrolled_run(self, tmp_path):
        """Checkpoints of a scanned run hold PER-LAYER entries (no
        carrier arrays), restore into an unrolled run, and the resumed
        steps are bitwise the scanned continuation."""
        from paddle_tpu import ckpt as ckpt_mod
        from paddle_tpu.ckpt.state import snapshot_scope

        X, Y = _data()
        pt.set_flags({"FLAGS_layer_scan": True})
        m, s, l = _build_mlp()
        _, scope, exe = _train(m, s, l, X, Y, steps=2)

        snap = snapshot_scope(scope)
        assert not any(k.startswith(passes_mod.LAYER_STACK_PREFIX)
                       for k in snap), "carrier leaked into checkpoint"
        assert any("velocity" in k for k in snap)

        mgr = ckpt_mod.CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(2, scope=scope)
        mgr.wait()

        pt.set_flags({"FLAGS_layer_scan": False})
        m2, s2, l2 = _build_mlp()
        scope2 = pt.framework.Scope()
        exe2 = pt.Executor(pt.CPUPlace())
        exe2.run(s2, scope=scope2)
        meta = mgr.restore(scope=scope2)
        assert meta and meta.get("step") == 2

        resumed, _, _ = _train(m2, s2, l2, X, Y, steps=2, scope=scope2,
                               exe=exe2, run_startup=False)
        pt.set_flags({"FLAGS_layer_scan": True})
        cont, _, _ = _train(m, s, l, X, Y, steps=2, scope=scope,
                            exe=exe, run_startup=False)
        np.testing.assert_array_equal(cont, resumed)

    def test_flag_flip_mid_run_continues_bitwise(self):
        """A live scope survives the flag flipping between runs: the
        executor reads per-layer state through the StackedParamRef
        views, so scanned steps -> unrolled steps == all-unrolled."""
        X, Y = _data()
        pt.set_flags({"FLAGS_layer_scan": False})
        m, s, l = _build_mlp()
        oracle, _, _ = _train(m, s, l, X, Y, steps=4)

        pt.set_flags({"FLAGS_layer_scan": True})
        m2, s2, l2 = _build_mlp()
        first, scope, exe = _train(m2, s2, l2, X, Y, steps=2)
        pt.set_flags({"FLAGS_layer_scan": False})
        rest, _, _ = _train(m2, s2, l2, X, Y, steps=2, scope=scope,
                            exe=exe, run_startup=False)
        np.testing.assert_array_equal(oracle, first + rest)


class TestDetection:
    def test_shallow_program_untouched(self):
        pt.set_flags({"FLAGS_layer_scan": True})
        _reset_scan_stats()
        m, s, l = _build_mlp(n_layers=2)
        X, Y = _data()
        losses, _, _ = _train(m, s, l, X, Y, steps=1)
        assert np.isfinite(losses).all()
        assert not stat_get("pass_layer_scan_segments")
        assert stat_get("pass_layer_scan_skipped") >= 1
        assert stat_get("pass_layer_scan_skipped_no_repeats") >= 1

    def test_non_isomorphic_layers_skipped(self):
        """Alternating widths break the structural fingerprint: nothing
        rewritten, numerics untouched."""
        pt.set_flags({"FLAGS_layer_scan": True})
        _reset_scan_stats()
        main, startup = Program(), Program()
        main.random_seed = 7
        with unique_name.guard(), program_guard(main, startup):
            x = layers.data("x", [8])
            y = layers.data("y", [1])
            h = x
            for i in range(8):
                h = layers.fc(h, 16 if i % 2 else 24, act="relu",
                              bias_attr=False)
            pred = layers.fc(h, 1, bias_attr=False)
            loss = layers.mean(layers.square_error_cost(pred, y))
            MomentumOptimizer(0.05, 0.9).minimize(loss)
        X, Y = _data()
        _train(main, startup, loss, X, Y, steps=1)
        assert not stat_get("pass_layer_scan_segments")

    def test_flag_off_is_default_and_untouched(self):
        _reset_scan_stats()
        m, s, l = _build_mlp()
        out = passes_mod.apply_passes(m, fetch_names=("loss",),
                                      feed_names=("x", "y"))
        assert not any(op.type == "layer_scan"
                       for op in out.global_block.ops)
        assert not stat_get("pass_layer_scan_segments")

    def test_rewrite_emits_one_scan_per_region(self):
        pt.set_flags({"FLAGS_layer_scan": True})
        m, s, l = _build_mlp(dropout=0.0)
        out = passes_mod.apply_passes(
            m, fetch_names=(l.name,), feed_names=("x", "y"))
        scans = [op for op in out.global_block.ops
                 if op.type == "layer_scan"]
        assert len(scans) >= 2  # forward + backward at least
        # each scan op points at a template block holding ONE layer
        for op in scans:
            tblock = out.blocks[int(op.attr("layer_block"))]
            assert 0 < len(tblock.ops) < 12
        # the user program is never mutated
        assert not any(op.type == "layer_scan"
                       for op in m.global_block.ops)


class TestCaching:
    def test_pass_cache_rekeys_on_flag_and_policy_flip(self):
        """FLAGS_layer_scan / FLAGS_layer_scan_policy key the executor
        pass cache: a flip re-runs the pipeline instead of serving the
        stale rewrite (same contract as the compile cache)."""
        X, Y = _data()
        pt.set_flags({"FLAGS_layer_scan": True})
        m, s, l = _build_mlp()
        scope = pt.framework.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(s, scope=scope)

        stat_reset("executor_pass_cache_hit")
        exe.run(m, feed={"x": X, "y": Y}, fetch_list=[l], scope=scope)
        assert not stat_get("executor_pass_cache_hit")
        exe.run(m, feed={"x": X, "y": Y}, fetch_list=[l], scope=scope)
        assert stat_get("executor_pass_cache_hit") == 1

        # policy flip -> new pass-cache key (no hit), scan still fires
        pt.set_flags({"FLAGS_layer_scan_policy": "dots_saveable"})
        _reset_scan_stats()
        exe.run(m, feed={"x": X, "y": Y}, fetch_list=[l], scope=scope)
        assert stat_get("executor_pass_cache_hit") == 1
        assert stat_get("pass_layer_scan_segments") >= 1

        # flag flip -> new key AND the fresh rewrite does not scan
        pt.set_flags({"FLAGS_layer_scan": False,
                      "FLAGS_layer_scan_policy": ""})
        _reset_scan_stats()
        exe.run(m, feed={"x": X, "y": Y}, fetch_list=[l], scope=scope)
        assert stat_get("executor_pass_cache_hit") == 1
        assert not stat_get("pass_layer_scan_segments")
        exe.close()


class TestStrategyPlumbing:
    def test_recompute_configs_scan_layers_enables_per_program(self):
        """recompute_configs={'scan_layers': N, 'policy': ...} turns the
        pass on for THIS program with FLAGS_layer_scan off, via attrs
        stamped on the optimizer ops (clone/fingerprint-safe)."""
        from paddle_tpu.distributed import fleet

        X, Y = _data()
        pt.set_flags({"FLAGS_layer_scan": False})
        st0 = fleet.DistributedStrategy()
        st0.fuse_all_reduce_ops = False
        with unique_name.guard():
            base_losses, _, _ = _train(*_build_mlp(fleet_strategy=st0),
                                       X, Y)

        _reset_scan_stats()
        st = fleet.DistributedStrategy()
        st.fuse_all_reduce_ops = False
        st.recompute = True
        st.recompute_configs = {"scan_layers": 4,
                                "policy": "dots_saveable"}
        assert st.recompute_configs["scan_layers"] == 4
        with unique_name.guard():
            m, s, l = _build_mlp(fleet_strategy=st)
        stamped = [op for op in m.global_block.ops
                   if op.has_attr(passes_mod.LAYER_SCAN_ATTR)]
        assert stamped and all(
            op.attr(passes_mod.LAYER_SCAN_POLICY_ATTR) == "dots_saveable"
            for op in stamped)
        losses, _, _ = _train(m, s, l, X, Y)
        assert stat_get("pass_layer_scan_segments") >= 1
        np.testing.assert_array_equal(base_losses, losses)

    def test_policy_only_recompute_configs_applies(self):
        """recompute_configs={'policy': ...} ALONE (no scan_layers) is
        a legal stamp: it picks the remat policy for a
        FLAGS_layer_scan-enabled run and must not be skipped just
        because no scan_layers attr rides the op."""
        from paddle_tpu.distributed import fleet

        X, Y = _data()
        pt.set_flags({"FLAGS_layer_scan": True})
        st = fleet.DistributedStrategy()
        st.fuse_all_reduce_ops = False
        st.recompute = True
        st.recompute_configs = {"policy": "nothing_saveable"}
        with unique_name.guard():
            m, s, l = _build_mlp(fleet_strategy=st)
        enabled, _, policy = passes_mod.LayerScanPass._config(m)
        assert enabled and policy == "nothing_saveable"
        _reset_scan_stats()
        losses, _, _ = _train(m, s, l, X, Y)
        assert stat_get("pass_layer_scan_segments") >= 1
        # the wrapped body computes the same numbers
        pt.set_flags({"FLAGS_layer_scan": False})
        st0 = fleet.DistributedStrategy()
        st0.fuse_all_reduce_ops = False
        with unique_name.guard():
            base_losses, _, _ = _train(*_build_mlp(fleet_strategy=st0),
                                       X, Y)
        np.testing.assert_array_equal(base_losses, losses)

    def test_layer_scan_fires_with_fuse_passes_off(self):
        """FLAGS_fuse_passes=0 turns off the OPTIMIZATION pipeline, not
        scan-over-layers — the scan flag owns its own gate, so a user
        debugging fusion keeps the compile-time win they asked for."""
        X, Y = _data()
        pt.set_flags({"FLAGS_fuse_passes": False})
        try:
            with unique_name.guard():
                base_losses, _, _ = _train(*_build_mlp(), X, Y)
            pt.set_flags({"FLAGS_layer_scan": True})
            _reset_scan_stats()
            with unique_name.guard():
                losses, _, _ = _train(*_build_mlp(), X, Y)
            assert stat_get("pass_layer_scan_segments") >= 1
            np.testing.assert_array_equal(base_losses, losses)
        finally:
            pt.set_flags({"FLAGS_fuse_passes": True})

    def test_invalid_policy_rejected(self):
        from paddle_tpu.distributed import fleet

        st = fleet.DistributedStrategy()
        st.recompute = True
        st.recompute_configs = {"scan_layers": 4, "policy": "bogus"}
        with unique_name.guard():
            with pytest.raises(ValueError, match="policy"):
                _build_mlp(fleet_strategy=st)


class TestFuseBucketAccounting:
    def test_stacked_grad_sized_num_layers_x(self):
        """The satellite bugfix: a LAYER_STACK_ATTR-stamped allreduce
        moves num_layers x the var's declared per-layer bytes — bucket
        sizing must use the TRUE stacked payload.  Three 8-layer stacks
        of 64KB-per-layer grads = 512KB each under a 1.3MB cap: the
        first two fit one bucket, the third overflows into its own —
        per-layer sizing (3 x 64KB) would silently fuse all three."""
        from paddle_tpu.framework.passes import (FUSE_SIZE_ATTR,
                                                 FUSED_ALLREDUCE_ATTR,
                                                 LAYER_STACK_ATTR,
                                                 FuseAllReducePass,
                                                 PassContext)

        def build(stack):
            main = Program()
            block = main.global_block
            for name in ("g0", "g1", "g2"):
                block.create_var(name=name, shape=[128, 128],
                                 dtype="float32")
                block.append_op("fill_constant", {}, {"Out": [name]},
                                {"shape": [128, 128], "dtype": "float32",
                                 "value": 1.0})
                attrs = {"ring_id": 0, FUSED_ALLREDUCE_ATTR: True,
                         FUSE_SIZE_ATTR: 1.3}
                if stack:
                    attrs[LAYER_STACK_ATTR] = stack
                block.append_op("c_allreduce_sum", {"X": [name]},
                                {"Out": [name]}, attrs)
            return main

        def n_allreduce(prog):
            return sum(1 for op in prog.global_block.ops
                       if op.type == "c_allreduce_sum")

        def coalesce_groups(prog):
            return [op.inputs["Input"] for op in prog.global_block.ops
                    if op.type == "coalesce_tensor"]

        # unstacked: 3 x 64KB fuse into ONE bucket under the cap
        stat_reset("pass_fused_allreduce_buckets")
        p = build(0)
        FuseAllReducePass().apply(p, PassContext())
        assert n_allreduce(p) == 1
        assert stat_get("pass_fused_allreduce_buckets") == 1
        # stacked x8: 512KB each -> [g0,g1] fuse, g2 overflows the cap
        # and stays a singleton
        stat_reset("pass_fused_allreduce_buckets")
        p = build(8)
        FuseAllReducePass().apply(p, PassContext())
        assert n_allreduce(p) == 2
        assert stat_get("pass_fused_allreduce_buckets") == 1
        assert coalesce_groups(p) == [["g0", "g1"]]


class TestRematPolicy:
    def test_policy_name_resolution(self):
        import jax

        from paddle_tpu.ops import layer_scan

        assert layer_scan.checkpoint_policy("") is None
        for name in layer_scan.REMAT_POLICIES:
            assert callable(layer_scan.checkpoint_policy(name))
        assert layer_scan.checkpoint_policy("save_anything") \
            is jax.checkpoint_policies.everything_saveable
        with pytest.raises(ValueError, match="unknown remat policy"):
            layer_scan.checkpoint_policy("no_such_policy")

    def test_wrap_checkpoint_keeps_values_and_skips_when_unnamed(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops import layer_scan

        def body(c, x):
            return c + jnp.sin(x), c

        assert layer_scan.wrap_checkpoint(body, "") is body
        wrapped = layer_scan.wrap_checkpoint(body, "dots_saveable")
        assert wrapped is not body
        xs = jnp.arange(4, dtype="float32")
        g = lambda f: jax.grad(  # noqa: E731
            lambda c: jax.lax.scan(f, c, xs, unroll=2)[0])(jnp.float32(0.5))
        assert float(g(wrapped)) == float(g(body))


class TestStackedCkptHostValue:
    """ckpt/state.py _host_value over StackedParamRef views: the
    fully-addressable fast path slices the layer; a carrier this
    process cannot assemble fails LOUDLY instead of silently dropping
    the parameter from the checkpoint."""

    def test_addressable_carrier_slices(self):
        from paddle_tpu.ckpt.state import _host_value
        from paddle_tpu.framework.scope import StackedParamRef

        scope = pt.framework.Scope()
        carrier = np.arange(12, dtype="f4").reshape(4, 3)
        name = passes_mod.LAYER_STACK_PREFIX + "w"
        scope.set_var(name, carrier)
        ref = StackedParamRef(scope, name, 2, (3,), "float32")
        np.testing.assert_array_equal(_host_value(ref), carrier[2])

    def test_non_addressable_carrier_fails_loudly(self):
        from paddle_tpu.ckpt.manager import CheckpointError
        from paddle_tpu.ckpt.state import _host_value
        from paddle_tpu.framework.scope import StackedParamRef

        class _Shard:
            index = (slice(0, 2), slice(0, 3))
            data = np.zeros((2, 3), "f4")

        class _FakeGlobal:
            # duck-typed multi-process jax global array: local shards
            # cover only part of the (4, 3) stack
            sharding = object()
            dtype = np.dtype("float32")
            shape = (4, 3)
            is_fully_addressable = False
            addressable_shards = [_Shard()]

        scope = pt.framework.Scope()
        name = passes_mod.LAYER_STACK_PREFIX + "w"
        scope.set_var(name, _FakeGlobal())
        ref = StackedParamRef(scope, name, 1, (3,), "float32")
        with pytest.raises(CheckpointError, match="layer stack"):
            _host_value(ref)

    def test_non_addressable_gather_once_per_carrier(self):
        """snapshot_scope gathers a non-addressable carrier ONCE and
        slices every member from it — not once per layer."""
        from paddle_tpu.ckpt.state import snapshot_scope
        from paddle_tpu.framework.scope import StackedParamRef

        gathers = {"n": 0}
        full = np.arange(12, dtype="f4").reshape(4, 3)

        class _Shard:
            index = (slice(0, 4), slice(0, 3))
            data = full

        class _FakeGlobal:
            sharding = object()
            dtype = np.dtype("float32")
            shape = (4, 3)
            is_fully_addressable = False

            @property
            def addressable_shards(self):
                gathers["n"] += 1
                return [_Shard()]

        scope = pt.framework.Scope()
        name = passes_mod.LAYER_STACK_PREFIX + "w"
        scope.set_var(name, _FakeGlobal())
        for i in range(4):
            scope.set_var(f"m{i}", StackedParamRef(scope, name, i, (3,),
                                                   "float32"))
        snap = snapshot_scope(scope)
        assert gathers["n"] == 1, gathers
        assert name not in snap  # carrier itself never checkpointed
        for i in range(4):
            np.testing.assert_array_equal(snap[f"m{i}"], full[i])


class TestEnsureStacked:
    def test_incremental_refresh_on_host_packed_carrier(self):
        """A carrier the program only READS stays the host numpy array
        the full pack built; a later partial concrete write (e.g. a
        partial restore) must take the incremental branch without
        assuming the carrier is a jax array."""
        from paddle_tpu.framework.passes import LayerScanPlan
        from paddle_tpu.framework.scope import StackedParamRef

        scope = pt.framework.Scope()
        name = passes_mod.LAYER_STACK_PREFIX + "w"
        members = tuple(f"m{i}" for i in range(4))
        plan = LayerScanPlan([{"carrier": name, "members": members,
                               "shape": (3,), "dtype": "float32"}])
        for i, m in enumerate(members):
            scope.set_var(m, np.full((3,), float(i), "f4"))
        plan.ensure_stacked(scope)  # full host-side pack
        assert isinstance(scope.get_var("m1"), StackedParamRef)
        # one member restored concrete over the still-host carrier
        scope.set_var("m2", np.full((3,), 9.0, "f4"))
        plan.ensure_stacked(scope)  # incremental branch
        np.testing.assert_array_equal(np.asarray(scope.get_var("m2")),
                                      np.full((3,), 9.0, "f4"))
        np.testing.assert_array_equal(np.asarray(scope.get_var("m3")),
                                      np.full((3,), 3.0, "f4"))
