"""Tensor-parallel auto-sharding (ShardingPropagationPass + GSPMD
executor path + TensorParallelMetaOptimizer).

Oracles, per the reference's dist-test discipline (test_dist_base.py):
the tensor-parallel run's per-step losses must MATCH a small replicated
oracle within 1e-4 rel on the 8-virtual-device CPU mesh, and the
sharding must be REAL — params and their optimizer slots physically
hold 1/mp of their bytes per chip, grad allreduces move shard-sized
payloads over the dp axis only, and FuseAllReducePass never mixes
sharding specs inside one bucket.
"""
import re

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

MLP_RULES = [
    (r"blk_ffn1\.w_\d+$", "None,mp"),
    (r"blk_ffn1\.b_\d+$", "mp"),
    (r"blk_ffn2\.w_\d+$", "mp,None"),
]

# "one simulated chip's budget": the replicated MLP's weights exceed
# it, the per-chip shard stays under it — the assertion that makes
# "model too large for one chip" concrete on the CPU mesh
CHIP_BUDGET_BYTES = 600_000


def _build_mlp(use_tp, rules=MLP_RULES, hidden=256, extra_strategy=None,
               dropout=0.0, recompute_ckpt=False):
    from paddle_tpu.distributed import fleet

    main, startup = Program(), Program()
    main.random_seed = 1
    with unique_name.guard(), program_guard(main, startup):
        x = layers.data("x", [8])
        y = layers.data("y", [1])
        h = layers.fc(x, hidden, act="relu", name="blk_ffn1",
                      param_attr=ParamAttr(
                          initializer=NormalInitializer(0.0, 0.05)))
        if dropout:
            h = layers.dropout(h, dropout, name="blk_drop")
        h2 = layers.fc(h, hidden, act="relu", name="mid",
                       param_attr=ParamAttr(
                           initializer=ConstantInitializer(0.02)),
                       bias_attr=False)
        pred = layers.fc(h2, 1, name="blk_ffn2", param_attr=ParamAttr(
            initializer=ConstantInitializer(0.1)), bias_attr=False)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = MomentumOptimizer(0.05, 0.9)
        if use_tp:
            strat = fleet.DistributedStrategy()
            strat.tensor_parallel = True
            if rules is not None:
                strat.tensor_parallel_configs = {"partition_rules": rules}
            for k, v in (extra_strategy or {}).items():
                setattr(strat, k, v)
            if recompute_ckpt:
                strat.recompute = True
                strat.recompute_configs = {"checkpoints": [h2.name]}
            if extra_strategy and extra_strategy.get("amp"):
                strat.amp_configs = {"use_bf16": True}
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(opt)
            fleet.minimize(loss)
        else:
            opt.minimize(loss)
    return main, startup, loss


def _data(n=16):
    rs = np.random.RandomState(0)
    X = rs.randn(n, 8).astype("float32")
    Y = (X.sum(axis=1, keepdims=True) * 0.3).astype("float32")
    return X, Y


def _train(main, startup, loss, X, Y, mesh, steps=5):
    scope = pt.framework.Scope()
    exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
    exe.run(startup, scope=scope)
    losses = [float(np.asarray(exe.run(
        main, feed={"x": X, "y": Y}, fetch_list=[loss],
        scope=scope)[0]).item()) for _ in range(steps)]
    return losses, scope, exe


class TestShardingPropagationPass:
    def test_rule_match_specs_and_slot_inheritance(self, mesh_dp_mp):
        main, _, loss = _build_mlp(True)
        out = passes_mod.apply_passes(
            main, fetch_names=(loss.name,), feed_names=("x", "y"),
            mesh=mesh_dp_mp)
        plan = out._tp_plan
        assert plan is not None and plan.mp_degree == 4
        assert plan.spec_tuple("blk_ffn1.w_0") == (None, "mp")
        assert plan.spec_tuple("blk_ffn1.b_0") == ("mp",)
        assert plan.spec_tuple("blk_ffn2.w_0") == ("mp", None)
        # optimizer slots inherit their param's spec automatically
        assert plan.spec_tuple("blk_ffn1.w_0_velocity_0") == (None, "mp")
        assert plan.spec_tuple("blk_ffn1.b_0_velocity_0") == ("mp",)
        assert plan.spec_tuple("blk_ffn2.w_0_velocity_0") == ("mp", None)
        # unmatched params stay replicated
        assert plan.spec_tuple("mid.w_0") == ()

    def test_non_divisible_param_falls_back_replicated(self, mesh_dp_mp):
        # hidden=254 is not divisible by mp=4: the rule matches but the
        # pass must fall back to replicated, never shard unevenly
        main, _, loss = _build_mlp(True, hidden=252 + 2)
        out = passes_mod.apply_passes(
            main, fetch_names=(loss.name,), feed_names=("x", "y"),
            mesh=mesh_dp_mp)
        plan = out._tp_plan
        assert plan.spec_tuple("blk_ffn1.w_0") == ()
        assert plan.n_fallback >= 1

    def test_constraint_anchors_stamped_on_matmuls(self, mesh_dp_mp):
        main, _, loss = _build_mlp(True)
        out = passes_mod.apply_passes(
            main, fetch_names=(loss.name,), feed_names=("x", "y"),
            mesh=mesh_dp_mp)
        anchored = [op for op in out.global_block.ops
                    if op.attr(passes_mod.TP_CONSTRAINT_ATTR)]
        assert anchored, "no sharding anchors stamped"
        # the column-parallel fc's output must be anchored mp-sharded
        col = [ent for op in anchored
               for ent in op.attr(passes_mod.TP_CONSTRAINT_ATTR)
               if "mp" in ent.split("\t")[1]]
        assert col, "no mp-sharded activation anchor found"

    def test_grad_collectives_stamped_with_spec(self, mesh_dp_mp):
        main, _, loss = _build_mlp(True)
        out = passes_mod.apply_passes(
            main, fetch_names=(loss.name,), feed_names=("x", "y"),
            mesh=mesh_dp_mp)
        plan = out._tp_plan
        # dp=2 -> the GraphExecution transpile inserted per-grad
        # allreduces; tp-sharded grads carry the shard-bytes accounting
        g = "blk_ffn1.w_0@GRAD"
        assert g in plan.grad_reduce
        rec = plan.grad_reduce[g]
        assert rec["axes"] == ("dp",)
        assert rec["bytes"] == 8 * 256 * 4 // 4  # full bytes / mp

    def test_no_tp_marks_means_no_plan(self, mesh_dp_mp):
        main, _, loss = _build_mlp(False)
        out = passes_mod.apply_passes(
            main, fetch_names=(loss.name,), feed_names=("x", "y"),
            mesh=mesh_dp_mp)
        assert getattr(out, "_tp_plan", None) is None


class TestTensorParallelTraining:
    def test_loss_parity_and_state_sharded(self, mesh_dp_mp):
        """Acceptance: an MLP whose replicated weights exceed one
        simulated chip's budget trains on the dp×mp mesh with loss
        parity (<=1e-4 rel) vs the replicated oracle, and optimizer
        slots verifiably carry their param's sharding spec."""
        from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

        rules = MLP_RULES + [(r"mid\.w_\d+$", "mp,None")]
        X, Y = _data(n=32)
        reset_mesh()  # oracle runs without any mesh
        base, _, _ = _train(*_build_mlp(False, hidden=512), X, Y, None)

        set_mesh(mesh_dp_mp)
        tp, scope, _ = _train(
            *_build_mlp(True, rules=rules, hidden=512), X, Y, mesh_dp_mp)
        assert np.isfinite(tp).all(), tp
        np.testing.assert_allclose(tp, base, rtol=1e-4, atol=1e-6)

        w = scope.get_var("blk_ffn1.w_0")
        v = scope.get_var("blk_ffn1.w_0_velocity_0")
        assert tuple(w.sharding.spec) == (None, "mp"), w.sharding
        # slots carry their param's spec on the LIVE arrays, not just
        # the plan
        assert tuple(v.sharding.spec) == (None, "mp"), v.sharding
        assert tuple(scope.get_var("mid.w_0").sharding.spec) == \
            ("mp", None)

        # "exceeds one chip's budget": the replicated model's param +
        # slot bytes blow the budget; the per-chip sharded footprint
        # fits under it — the model is only trainable BECAUSE of tp
        names = ["blk_ffn1.w_0", "blk_ffn1.b_0", "mid.w_0",
                 "blk_ffn2.w_0"]
        names += [n + "_velocity_0" for n in names]
        full = sum(int(np.prod(scope.get_var(n).shape)) * 4
                   for n in names)
        per_chip = sum(
            int(np.prod(scope.get_var(n).addressable_shards[0].data.shape))
            * 4 for n in names)
        assert full > CHIP_BUDGET_BYTES, full
        assert per_chip <= CHIP_BUDGET_BYTES, per_chip

    def test_parity_with_dropout(self, mesh_dp_mp):
        """Dropout masks must be IDENTICAL between the replicated and
        tp runs (partitionable threefry: bits are sharding-invariant)."""
        from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

        X, Y = _data(n=32)
        reset_mesh()
        base, _, _ = _train(*_build_mlp(False, dropout=0.3), X, Y, None)
        set_mesh(mesh_dp_mp)
        tp, _, _ = _train(*_build_mlp(True, dropout=0.3), X, Y, mesh_dp_mp)
        np.testing.assert_allclose(tp, base, rtol=1e-4, atol=1e-6)

    def test_mp_only_mesh(self, mesh_mp_only):
        """Pure tensor parallelism (dp=1): no grad allreduces at all,
        parity still holds."""
        from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

        X, Y = _data()
        reset_mesh()
        base, _, _ = _train(*_build_mlp(False), X, Y, None)
        set_mesh(mesh_mp_only)
        main, startup, loss = _build_mlp(True)
        assert not any(op.type == "c_allreduce_sum"
                       for op in main.global_block.ops)
        tp, scope, _ = _train(main, startup, loss, X, Y, mesh_mp_only)
        np.testing.assert_allclose(tp, base, rtol=1e-4, atol=1e-6)
        w = scope.get_var("blk_ffn1.w_0")
        assert w.addressable_shards[0].data.shape == (8, 256 // 8)

    def test_pure_mp_1d_mesh(self):
        """A 1D ('mp',)-only mesh (no 'dp' axis anywhere): specs and
        anchors must degrade 'dp' tokens to replicated instead of
        naming a mesh axis jax has never heard of (review regression)."""
        import jax

        from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

        X, Y = _data()
        reset_mesh()
        base, _, _ = _train(*_build_mlp(False), X, Y, None)
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("mp",))
        set_mesh(mesh)
        try:
            tp, scope, _ = _train(*_build_mlp(True), X, Y, mesh)
            np.testing.assert_allclose(tp, base, rtol=1e-4, atol=1e-6)
            w = scope.get_var("blk_ffn1.w_0")
            assert tuple(w.sharding.spec) == (None, "mp")
        finally:
            reset_mesh()

    def test_run_steps_scan_path(self, mesh_dp_mp):
        """Multi-step on-device scan (run_steps) under the GSPMD path."""
        from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

        X, Y = _data(n=16)
        reset_mesh()
        m0, s0, l0 = _build_mlp(False)
        sc0 = pt.framework.Scope()
        e0 = pt.Executor(pt.CPUPlace())
        e0.run(s0, scope=sc0)
        out0 = e0.run_steps(m0, feed={"x": X, "y": Y}, fetch_list=[l0],
                            scope=sc0, steps=4)
        base = np.asarray(out0[0]).ravel()

        set_mesh(mesh_dp_mp)
        m1, s1, l1 = _build_mlp(True)
        sc1 = pt.framework.Scope()
        e1 = pt.Executor(pt.CPUPlace(), mesh=mesh_dp_mp)
        e1.run(s1, scope=sc1)
        out1 = e1.run_steps(m1, feed={"x": X, "y": Y}, fetch_list=[l1],
                            scope=sc1, steps=4)
        np.testing.assert_allclose(np.asarray(out1[0]).ravel(), base,
                                   rtol=1e-4, atol=1e-6)

    def test_tp_program_without_mp_mesh_raises(self, mesh_dp_mp):
        """Two guard layers: minimize refuses a mesh without an 'mp'
        axis outright, and a tp-stamped program handed to an executor
        whose mesh lost the axis refuses at dispatch (the dp loss-grad
        scale was removed, so the shard_map path would be numerically
        wrong)."""
        import jax

        from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

        # built under a valid dp×mp mesh...
        main, startup, loss = _build_mlp(True)
        X, Y = _data()
        # ...then dispatched on a dp-only mesh: executor-level guard
        reset_mesh()
        dp_mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))
        set_mesh(dp_mesh)
        scope = pt.framework.Scope()
        exe = pt.Executor(pt.CPUPlace(), mesh=dp_mesh)
        exe.run(startup, scope=scope)
        with pytest.raises(ValueError, match="'mp' axis"):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                    scope=scope)

        # minimize-level guard: a dp-only global mesh is refused early
        reset_mesh()
        from paddle_tpu.distributed.parallel_env import init_parallel_env

        init_parallel_env()  # 1D dp mesh
        with pytest.raises(ValueError, match="'mp'"):
            _build_mlp(True)
        reset_mesh()

    def test_ckpt_roundtrip_same_topology_bitwise(self, mesh_dp_mp,
                                                  tmp_path):
        """tp-sharded state saves through the ckpt manager and restores
        bitwise on the same topology (single-process: fully-addressable
        arrays snapshot as full host values — elastic by construction)."""
        from paddle_tpu.ckpt import CheckpointManager

        X, Y = _data()
        _, scope, exe = _train(*_build_mlp(True), X, Y, mesh_dp_mp,
                               steps=3)
        m = CheckpointManager(str(tmp_path), async_save=False)
        m.save(3, scope=scope)
        m.close()

        w_before = np.asarray(scope.get_var("blk_ffn1.w_0"))
        m2 = CheckpointManager(str(tmp_path), async_save=False)
        scope2 = pt.framework.Scope()
        meta = m2.restore(scope=scope2)
        m2.close()
        assert meta["step"] == 3
        np.testing.assert_array_equal(
            np.asarray(scope2.get_var("blk_ffn1.w_0")), w_before)


class TestCollectiveTelemetry:
    def test_grad_allreduce_dp_only_shard_bytes(self, mesh_dp_mp):
        """Acceptance: per-param grad allreduces for tp-sharded params
        run over the dp mesh axis only, asserted via the collective
        span/byte telemetry (tracer spans carry axes='dp' + SHARD
        bytes) and the StepTimer's static allreduce accounting."""
        from paddle_tpu import observe
        from paddle_tpu.distributed.parallel_env import set_mesh

        set_mesh(mesh_dp_mp)
        X, Y = _data()
        main, startup, loss = _build_mlp(True)
        scope = pt.framework.Scope()
        exe = pt.Executor(pt.CPUPlace(), mesh=mesh_dp_mp)
        exe.run(startup, scope=scope)
        observe.clear()
        observe.enable()
        try:
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                    scope=scope)
            exe.drain()
            spans = [s for s in observe.snapshot()
                     if s.name == "collective/c_allreduce_sum"]
        finally:
            observe.disable()
            observe.clear()
        assert spans, "no grad-allreduce spans traced"
        by_var = {(s.args or {}).get("var"): (s.args or {}) for s in spans}
        a = by_var.get("blk_ffn1.w_0@GRAD")
        assert a is not None
        assert a.get("axes") == "dp"
        assert a["bytes"] == 8 * 256 * 4 // 4  # mp-shard payload
        # replicated param's grad: full bytes, still dp-only by
        # construction of the 2D mesh collective lowering
        b = by_var.get("mid.w_0@GRAD")
        assert b is not None and b["bytes"] == 256 * 256 * 4

        # compiled-entry static accounting agrees (sum of per-grad
        # dp payloads, shard-sized for mp-sharded grads)
        entry = [e for e in exe._cache.values() if e.allreduce_bytes][-1]
        expected = (8 * 256 * 4 // 4          # blk_ffn1.w col-sharded
                    + 256 * 4 // 4            # blk_ffn1.b
                    + 256 * 256 * 4           # mid.w replicated
                    + 256 * 1 * 4 // 4)       # blk_ffn2.w row-sharded
        assert entry.allreduce_bytes == expected

    def test_fuse_bucket_never_mixes_specs(self):
        """Acceptance: FuseAllReducePass buckets never mix sharding
        specs — same dtype/ring grads with different __tp_spec__ stamps
        land in separate fused buffers."""
        from paddle_tpu.framework.program import Operator

        main = Program()
        block = main.global_block
        mark = {passes_mod.FUSED_ALLREDUCE_ATTR: True,
                passes_mod.FUSE_SIZE_ATTR: 32.0}
        specs = ["None,mp", "None,mp", "", "", "mp,None"]
        for i, spec in enumerate(specs):
            g = f"g{i}"
            block.create_var(name=g, shape=[4, 4], dtype="float32")
            attrs = dict(mark)
            if spec:
                attrs[passes_mod.TP_SPEC_ATTR] = spec
            block.append_op("c_allreduce_sum", {"X": [g]}, {"Out": [g]},
                            attrs)
        work = main.clone()
        passes_mod.FuseAllReducePass().apply(work, passes_mod.PassContext())
        fused = [op for op in work.global_block.ops
                 if op.type == "coalesce_tensor"]
        # g0+g1 fuse (same spec), g2+g3 fuse (unsharded), g4 stays alone
        assert len(fused) == 2
        members = sorted(tuple(op.inputs["Input"]) for op in fused)
        assert members == [("g0", "g1"), ("g2", "g3")]
        # the fused collective keeps its members' spec stamp
        fused_ar = [op for op in work.global_block.ops
                    if op.type == "c_allreduce_sum"
                    and op.inputs["X"][0].startswith("@FUSED_GRAD@")]
        stamped = {op.attr(passes_mod.TP_SPEC_ATTR) for op in fused_ar}
        assert "None,mp" in stamped

    def test_mfu_per_chip_flops_divided_by_mp(self, mesh_dp_mp):
        """Satellite: per-chip FLOPs under tp are program_flops /
        mp_degree, so MFU is not overstated by mp× on sharded runs."""
        from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

        X, Y = _data()
        reset_mesh()
        m0, s0, l0 = _build_mlp(False)
        sc0 = pt.framework.Scope()
        e0 = pt.Executor(pt.CPUPlace())
        e0.run(s0, scope=sc0)
        e0.run(m0, feed={"x": X, "y": Y}, fetch_list=[l0], scope=sc0)
        e0.drain()
        plain = [e for e in e0._cache.values() if e.flops_per_step > 0]
        assert plain

        set_mesh(mesh_dp_mp)
        m1, s1, l1 = _build_mlp(True)
        sc1 = pt.framework.Scope()
        e1 = pt.Executor(pt.CPUPlace(), mesh=mesh_dp_mp)
        e1.run(s1, scope=sc1)
        e1.run(m1, feed={"x": X, "y": Y}, fetch_list=[l1], scope=sc1)
        e1.drain()
        tp = [e for e in e1._cache.values() if e.flops_per_step > 0]
        assert tp
        assert tp[-1].flops_per_step == pytest.approx(
            plain[-1].flops_per_step / 4, rel=1e-6)


class TestMetaOptimizerComposition:
    def test_full_chain_compiles_and_tracks_tp_only(self, mesh_dp_mp):
        """Satellite acceptance: tensor_parallel × fuse_all_reduce ×
        AMP(bf16) × recompute × ZeRO-1 all enabled on one program
        compiles and holds loss parity vs tp-only on the 8-device mesh
        (loose tolerance: bf16 AMP is in the chain)."""
        from paddle_tpu.distributed.parallel_env import set_mesh

        X, Y = _data(n=32)
        set_mesh(mesh_dp_mp)
        tp_only, _, _ = _train(*_build_mlp(True), X, Y, mesh_dp_mp,
                               steps=4)

        set_mesh(mesh_dp_mp)
        main, startup, loss = _build_mlp(
            True,
            extra_strategy={"amp": True, "fuse_all_reduce_ops": True,
                            "sharding": True},
            recompute_ckpt=True)
        # the chain really applied: ZeRO rewired optimizer ops and the
        # tp stamps are on them
        assert any(op.attr("__sharded_accumulators__") is not None
                   for op in main.global_block.ops)
        assert any(op.attr(passes_mod.TP_RULES_ATTR)
                   for op in main.global_block.ops)
        assert any(op.type == "cast" for op in main.global_block.ops)
        full, scope, _ = _train(main, startup, loss, X, Y, mesh_dp_mp,
                                steps=4)
        assert np.isfinite(full).all(), full
        np.testing.assert_allclose(full, tp_only, rtol=3e-2, atol=1e-3)
        # tp sharding survived the whole chain on the live state
        w = scope.get_var("blk_ffn1.w_0")
        assert tuple(w.sharding.spec) == (None, "mp")

    def test_tp_pipeline_composes_localsgd_still_rejected(self,
                                                          mesh_dp_mp):
        """tensor_parallel × pipeline now COMPOSES (the dp×mp×pp mesh;
        full numerics covered in tests/test_parallel_3d.py) — but a
        dp×mp mesh without a 'pp' axis is rejected loudly, and the
        localsgd combo keeps the pinned rejection."""
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.parallel_env import set_mesh

        set_mesh(mesh_dp_mp)  # has 'mp' but no 'pp'
        main, startup = Program(), Program()
        main.random_seed = 1
        with unique_name.guard(), program_guard(main, startup):
            x = layers.data("x", [8])
            y = layers.data("y", [1])
            pred = layers.fc(x, 1, bias_attr=False)
            loss = layers.mean(layers.square_error_cost(pred, y))
            strat = fleet.DistributedStrategy()
            strat.tensor_parallel = True
            strat.pipeline = True
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(MomentumOptimizer(0.05, 0.9))
            with pytest.raises(ValueError, match="'pp'"):
                fleet.minimize(loss)

    def test_tp_rejects_localsgd_combo(self, mesh_dp_mp):
        from paddle_tpu.distributed import fleet

        main, startup = Program(), Program()
        main.random_seed = 1
        with unique_name.guard(), program_guard(main, startup):
            x = layers.data("x", [8])
            y = layers.data("y", [1])
            pred = layers.fc(x, 1, bias_attr=False)
            loss = layers.mean(layers.square_error_cost(pred, y))
            strat = fleet.DistributedStrategy()
            strat.tensor_parallel = True
            strat.localsgd = True
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(MomentumOptimizer(0.05, 0.9))
            with pytest.raises(NotImplementedError,
                               match="does not compose with "
                                     "strategy.localsgd"):
                fleet.minimize(loss)

    def test_degree_mismatch_raises(self, mesh_dp_mp):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.parallel_env import set_mesh

        set_mesh(mesh_dp_mp)  # mp = 4
        main, startup = Program(), Program()
        main.random_seed = 1
        with unique_name.guard(), program_guard(main, startup):
            x = layers.data("x", [8])
            y = layers.data("y", [1])
            pred = layers.fc(x, 8, name="blk_ffn2", bias_attr=False)
            loss = layers.mean(layers.square_error_cost(
                layers.fc(pred, 1, bias_attr=False), y))
            strat = fleet.DistributedStrategy()
            strat.tensor_parallel = True
            strat.tensor_parallel_configs = {"tensor_parallel_degree": 8}
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(MomentumOptimizer(0.05, 0.9))
            fleet.minimize(loss)
        scope = pt.framework.Scope()
        exe = pt.Executor(pt.CPUPlace(), mesh=mesh_dp_mp)
        exe.run(startup, scope=scope)
        X, Y = _data()
        with pytest.raises(ValueError, match="tensor_parallel_degree"):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                    scope=scope)


def _small_bert(use_fleet_tp=False):
    """(main, startup, loss, feed) for a small BERT-style pretraining
    step; with ``use_fleet_tp`` the program is built through
    fleet.distributed_optimizer with strategy.tensor_parallel (the
    default Megatron rules match the enc_*_{q,k,v,out}/ffn1/ffn2 +
    word_embedding naming)."""
    from paddle_tpu.text import bert_base_pretrain_program

    B, S, V, P = 16, 32, 512, 4
    with unique_name.guard():  # repeat builds keep .w_0 param names
        main_p, startup, _, loss, opt = bert_base_pretrain_program(
            batch_size=B, seq_len=S, vocab_size=V, hidden=64,
            n_layers=2, n_heads=4, ffn_size=128, max_preds_per_seq=P)
    main_p.random_seed = 1
    with unique_name.guard(), program_guard(main_p, startup):
        if use_fleet_tp:
            from paddle_tpu.distributed import fleet

            strat = fleet.DistributedStrategy()
            strat.tensor_parallel = True
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(opt)
            fleet.minimize(loss)
        else:
            opt.minimize(loss)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, V, (B, S)).astype("int64")
    flat_pos = np.concatenate(
        [b * S + rng.choice(S, P, replace=False) for b in range(B)]
    ).astype("int64")
    labels = ids.reshape(-1)[flat_pos].reshape(-1, 1).astype("int64")
    feed = {
        "input_ids": ids,
        "token_type_ids": np.zeros((B, S), "int64"),
        "pos_ids": np.tile(np.arange(S, dtype="int64"), (B, 1)),
        "input_mask": np.zeros((B, 1, 1, S), "float32"),
        "masked_flat_pos": flat_pos,
        "masked_labels": labels,
        "masked_weights": np.ones((B * P, 1), "float32"),
        "nsp_labels": rng.randint(0, 2, (B, 1)).astype("int64"),
    }
    return main_p, startup, loss, feed


class TestBertStyleTP:
    def test_bert_default_rules_parity_and_sharding(self, mesh_dp_mp):
        """BERT-style model under the DEFAULT Megatron rules: loss
        parity vs the replicated oracle, QKV/FFN weights and their Adam
        moments mp-sharded, vocab-parallel embedding."""
        from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

        reset_mesh()
        m0, s0, l0, feed = _small_bert()
        sc0 = pt.framework.Scope()
        e0 = pt.Executor(pt.CPUPlace())
        e0.run(s0, scope=sc0)
        base = [float(np.asarray(e0.run(
            m0, feed=feed, fetch_list=[l0], scope=sc0)[0]).ravel()[0])
            for _ in range(3)]

        set_mesh(mesh_dp_mp)
        m1, s1, l1, feed1 = _small_bert(use_fleet_tp=True)
        sc1 = pt.framework.Scope()
        e1 = pt.Executor(pt.CPUPlace(), mesh=mesh_dp_mp)
        e1.run(s1, scope=sc1)
        tp = [float(np.asarray(e1.run(
            m1, feed=feed1, fetch_list=[l1], scope=sc1)[0]).ravel()[0])
            for _ in range(3)]
        assert np.isfinite(tp).all(), tp
        np.testing.assert_allclose(tp, base, rtol=1e-4, atol=1e-6)

        for name, spec in (("enc_0_attn_q.w_0", (None, "mp")),
                           ("enc_0_ffn1.w_0", (None, "mp")),
                           ("enc_0_ffn2.w_0", ("mp", None)),
                           ("word_embedding", ("mp", None)),
                           ("enc_0_attn_q.w_0_moment1_0", (None, "mp"))):
            v = sc1.get_var(name)
            assert tuple(v.sharding.spec) == spec, (name, v.sharding)
