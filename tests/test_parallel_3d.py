"""3D parallelism (dp×mp×pp) with collective–compute overlap.

Composition matrix for the composed mesh (distributed/pipeline.py v4):
tensor parallelism INSIDE pipeline stages (manual Megatron f/g at the
ShardingPropagationPass anchors), scan-over-layers INSIDE each stage
(bitwise vs the unrolled trace), stretched allreduce buckets at the
scan boundary (FuseAllReducePass + FLAGS_overlap_grad_allreduce), the
latency-hiding chunked collective matmul, and elastic checkpoint
save/restore across a pp-degree change.

Oracle discipline: the mp composition is compared against the SAME
GPipe schedule with mp replicated (a pp-only / dp×pp mesh) so micro-
batching and the per-(stage, microbatch) dropout keys are identical —
the only difference left is the mp matmul split, bounded by 1e-4
(float reassociation of the row-parallel psum).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import passes as passes_mod
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.program import (Program, device_guard,
                                          program_guard)
from paddle_tpu.initializer import ConstantInitializer
from paddle_tpu.monitor import stat_get, stat_reset
from paddle_tpu.optimizer import MomentumOptimizer, PipelineOptimizer
from paddle_tpu.param_attr import ParamAttr

H = 16


def _data(n=8, h=H, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, h).astype("f4")
    Y = (X.sum(1, keepdims=True) * 0.2).astype("f4")
    return X, Y


def _attr(v):
    return ParamAttr(initializer=ConstantInitializer(v))


def _build_megatron_pp(use_tp, n_micro=2, dropout=False, n_stages=2):
    """Two Megatron ffn pairs split over ``n_stages`` pipeline stages;
    param names match DEFAULT_MEGATRON_RULES (ffn1 column-parallel,
    ffn2 row-parallel).  Dropout (optional) sits AFTER the row-parallel
    reduce — the replicated point, per the Megatron block shape."""
    from paddle_tpu.distributed import fleet

    main, startup = Program(), Program()
    main.random_seed = 3
    with unique_name.guard(), program_guard(main, startup):
        x = layers.data("x", [H])
        y = layers.data("y", [1])
        with device_guard("stage:0"):
            h = layers.fc(x, 2 * H, act="relu", name="s0_ffn1",
                          param_attr=_attr(0.05), bias_attr=_attr(0.01))
            h = layers.fc(h, H, name="s0_ffn2", param_attr=_attr(0.04),
                          bias_attr=_attr(0.0))
            if dropout:
                h = layers.dropout(h, 0.25)
        with device_guard(f"stage:{n_stages - 1}"):
            h2 = layers.fc(h, 2 * H, act="relu", name="s1_ffn1",
                           param_attr=_attr(0.03), bias_attr=_attr(0.0))
            h2 = layers.fc(h2, H, name="s1_ffn2", param_attr=_attr(0.05),
                           bias_attr=False)
            pred = layers.fc(h2, 1, name="head", param_attr=_attr(0.1),
                             bias_attr=False)
            loss = layers.mean(layers.square_error_cost(pred, y))
        opt = MomentumOptimizer(0.05, 0.9)
        if use_tp:
            strat = fleet.DistributedStrategy()
            strat.tensor_parallel = True
            strat.pipeline = True
            strat.pipeline_configs = {"micro_batch": n_micro}
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(opt)
            fleet.minimize(loss)
        else:
            PipelineOptimizer(opt, num_microbatches=n_micro).minimize(loss)
    return main, startup, loss


def _train(main, startup, loss, X, Y, mesh, steps=4, scope=None):
    sc = scope if scope is not None else pt.framework.Scope()
    exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
    exe.run(startup, scope=sc)
    out = [float(np.asarray(exe.run(main, feed={"x": X, "y": Y},
                                    fetch_list=[loss], scope=sc)[0]).item())
           for _ in range(steps)]
    exe.drain()
    return out, sc, exe


@pytest.fixture
def mesh_pp2():
    import jax

    return jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pp",))


@pytest.fixture
def _set_mesh():
    from paddle_tpu.distributed.parallel_env import reset_mesh, set_mesh

    try:
        yield set_mesh
    finally:
        reset_mesh()


# ---------------------------------------------------------------------------
# tier-1-lean units (no jit compile)
# ---------------------------------------------------------------------------


class TestAnchorsAndBuckets:
    def test_anchor_partial_flag_roundtrip(self):
        enc = "out\tNone,mp"
        assert passes_mod.decode_anchor(enc) == ("out", (None, "mp"),
                                                 False)
        assert passes_mod.decode_anchor("out\tdp,None\tP") == (
            "out", ("dp", None), True)
        assert passes_mod.decode_anchor("out\t") == ("out", (), False)

    def _allreduce_program(self, stacked_first=2, tail=2):
        """``stacked_first`` adjacent stacked-carrier allreduces (the
        pulled-out post-scan collectives), then ``tail`` unstacked
        allreduces each behind a compute op (the unrolled edge-layer
        backward)."""
        from paddle_tpu.framework.passes import (FUSED_ALLREDUCE_ATTR,
                                                 LAYER_STACK_ATTR)

        main = Program()
        block = main.global_block
        names = []

        def grad(name, stack):
            block.create_var(name=name, shape=[64, 64], dtype="float32")
            block.append_op("fill_constant", {}, {"Out": [name]},
                            {"shape": [64, 64], "dtype": "float32",
                             "value": 1.0})
            attrs = {"ring_id": 0, FUSED_ALLREDUCE_ATTR: True}
            if stack:
                attrs[LAYER_STACK_ATTR] = stack
            return name, attrs

        # backward scan -> adjacent stacked carriers
        pending = []
        for i in range(stacked_first):
            n, attrs = grad(f"stk{i}", 8)
            pending.append((n, attrs))
        for n, attrs in pending:
            block.append_op("c_allreduce_sum", {"X": [n]}, {"Out": [n]},
                            attrs)
            names.append(n)
        # unrolled tail: compute between each grad's allreduce
        for i in range(tail):
            n, attrs = grad(f"tail{i}", 0)
            block.append_op("c_allreduce_sum", {"X": [n]}, {"Out": [n]},
                            attrs)
            names.append(n)
        return main, names

    def test_stretched_bucket_closes_at_scan_boundary(self):
        """Overlap ON: the stacked carriers' bucket refuses the
        unstacked tail grads separated by backward compute — the bulk
        allreduce keeps its post-scan anchor (dispatches under the
        remaining backward) instead of being dragged to the tail."""
        from paddle_tpu.framework.passes import (FuseAllReducePass,
                                                 PassContext)

        pt.set_flags({"FLAGS_overlap_grad_allreduce": True})
        stat_reset("pass_overlap_stretched_buckets")
        main, _ = self._allreduce_program()
        FuseAllReducePass().apply(main, PassContext())
        ops = main.global_block.ops
        groups = [op.inputs["Input"] for op in ops
                  if op.type == "coalesce_tensor"]
        assert ["stk0", "stk1"] in groups, groups
        assert all("stk0" not in g or "tail0" not in g for g in groups)
        assert stat_get("pass_overlap_stretched_buckets") >= 1
        # the carrier bucket's fused collective sits BEFORE the tail
        # grads' producing compute ops
        idx_of = {op.type + str(i): i for i, op in enumerate(ops)}
        carrier_ar = next(i for i, op in enumerate(ops)
                          if op.type == "c_allreduce_sum"
                          and "FUSED" in op.inputs["X"][0])
        first_tail_fill = next(
            i for i, op in enumerate(ops)
            if op.type == "fill_constant"
            and op.outputs["Out"][0].startswith("tail"))
        assert carrier_ar < first_tail_fill, (carrier_ar, first_tail_fill)

    def test_sequential_schedule_with_flag_off(self):
        """Overlap OFF (the bench A/B baseline): one greedy bucket
        drags the carriers to the tail — the pre-overlap schedule."""
        from paddle_tpu.framework.passes import (FuseAllReducePass,
                                                 PassContext)

        pt.set_flags({"FLAGS_overlap_grad_allreduce": False})
        try:
            main, _ = self._allreduce_program()
            FuseAllReducePass().apply(main, PassContext())
            groups = [op.inputs["Input"] for op in main.global_block.ops
                      if op.type == "coalesce_tensor"]
            assert any("stk0" in g and "tail1" in g for g in groups), groups
        finally:
            pt.set_flags({"FLAGS_overlap_grad_allreduce": True})

    def test_packed_param_ref_mp_views(self):
        """PackedParamRef over an mp-packed (S, MP, W) buffer
        materializes the TRUE global value: sharded vars reassemble
        along their sharded dim, replicated vars read one rank's row."""
        from paddle_tpu.framework.scope import PackedParamRef, Scope

        sc = Scope()
        w = np.arange(24, dtype=np.float32).reshape(4, 6)
        b = np.arange(4, dtype=np.float32)
        buf = np.zeros((1, 2, 20), np.float32)
        for r in range(2):
            buf[0, r, :12] = w[:, 3 * r:3 * (r + 1)].ravel()
            buf[0, r, 12:16] = b
        sc.set_var("@PK@", buf)
        ref_w = PackedParamRef(sc, "@PK@", 0, 0, (4, 6), np.float32,
                               mp_degree=2, mp_dim=1)
        ref_b = PackedParamRef(sc, "@PK@", 0, 12, (4,), np.float32,
                               mp_degree=2, mp_dim=None)
        np.testing.assert_array_equal(np.asarray(ref_w), w)
        np.testing.assert_array_equal(np.asarray(ref_b), b)
        assert ref_w.local_shape == (4, 3)

    def test_pp_degree_flag_shapes_default_mesh(self):
        from paddle_tpu.distributed.parallel_env import (init_parallel_env,
                                                         reset_mesh)

        pt.set_flags({"FLAGS_pp_degree": 2})
        try:
            mesh = init_parallel_env()
            assert tuple(mesh.axis_names) == ("dp", "pp")
            assert int(mesh.shape["pp"]) == 2
            # an EXPLICIT axis_names wins over the flag
            mesh = init_parallel_env(axis_names=("batch",))
            assert tuple(mesh.axis_names) == ("batch",)
            pt.set_flags({"FLAGS_pp_degree": 3})  # 8 % 3 != 0
            with pytest.raises(ValueError, match="pp_degree"):
                init_parallel_env()
        finally:
            pt.set_flags({"FLAGS_pp_degree": 0})
            reset_mesh()

    def test_mp_flow_validation_rejects_sharded_softmax(self, _set_mesh):
        """An op outside the understood family consuming an mp-sharded
        activation is refused at plan time, naming the op."""
        import jax

        from paddle_tpu.distributed import fleet

        devs = np.array(jax.devices())
        mesh = jax.sharding.Mesh(devs[:4].reshape(2, 2), ("mp", "pp"))
        _set_mesh(mesh)
        main, startup = Program(), Program()
        main.random_seed = 1
        with unique_name.guard(), program_guard(main, startup):
            x = layers.data("x", [H])
            y = layers.data("y", [1])
            with device_guard("stage:0"):
                h = layers.fc(x, 2 * H, name="s0_ffn1",
                              param_attr=_attr(0.05), bias_attr=False)
                # softmax over the COLUMN-PARALLEL (mp-sharded) output:
                # a local softmax would normalize over the shard only
                h = layers.softmax(h)
                h = layers.fc(h, H, name="s0_ffn2",
                              param_attr=_attr(0.04), bias_attr=False)
            with device_guard("stage:1"):
                pred = layers.fc(h, 1, name="head", param_attr=_attr(0.1),
                                 bias_attr=False)
                loss = layers.mean(layers.square_error_cost(pred, y))
            strat = fleet.DistributedStrategy()
            strat.tensor_parallel = True
            strat.pipeline = True
            strat.pipeline_configs = {"micro_batch": 2}
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(MomentumOptimizer(0.05, 0.9))
            fleet.minimize(loss)
        X, Y = _data()
        sc = pt.framework.Scope()
        exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
        exe.run(startup, scope=sc)
        with pytest.raises(NotImplementedError, match="softmax"):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                    scope=sc)


# ---------------------------------------------------------------------------
# composition matrix (compile-heavy: slow tier, but for the mp×pp parity)
# ---------------------------------------------------------------------------


class TestComposedMesh:
    def test_mp_pp_parity_vs_replicated_oracle(self, mesh_pp2, _set_mesh):
        """mp×pp loss parity ≤1e-4 vs the same GPipe schedule with mp
        replicated, plus the memory point: the packed buffer grows an
        mp dim and each (pp, mp) rank holds shard-sized rows, while
        the scope views still materialize full values."""
        import jax

        from paddle_tpu.distributed.pipeline import PACKED_STATE_VAR

        X, Y = _data()
        base, _, _ = _train(*_build_megatron_pp(False), X, Y, mesh_pp2)

        devs = np.array(jax.devices())
        mesh = jax.sharding.Mesh(devs[:4].reshape(2, 2), ("mp", "pp"))
        _set_mesh(mesh)
        stat_reset("pp_bubble_fraction_ppm")
        got, sc, _ = _train(*_build_megatron_pp(True), X, Y, mesh)
        np.testing.assert_allclose(got, base, rtol=1e-4, atol=1e-6)
        # GPipe schedule-cost gauge: S=2, K=2 -> (S-1)/(K+S-1) = 1/3
        assert stat_get("pp_bubble_fraction_ppm") == pytest.approx(
            333333, abs=2)
        assert stat_get("pp_stages") == 2

        buf = sc.get_var(PACKED_STATE_VAR)
        assert buf.shape[0] == 2 and buf.shape[1] == 2  # (S, MP, W)
        # a column-parallel weight's view reassembles the global shape
        w = np.asarray(sc.get_var("s0_ffn1.w_0"))
        assert w.shape == (H, 2 * H)
        # per-(pp, mp) rank: one (1, 1, W) row of the packed buffer
        per_dev = {sh.device: sh.data.shape
                   for sh in buf.addressable_shards}
        assert len(per_dev) == 4
        assert all(s == (1, 1, buf.shape[-1]) for s in per_dev.values())

    @pytest.mark.slow
    def test_dp_mp_pp_parity_with_dropout(self, _set_mesh):
        """Full 3-axis composition (2,2,2) vs the dp×pp oracle WITH
        dropout: identical micro-batching, identical per-(stage,
        microbatch, dp-shard) dropout keys (partitionable threefry),
        so the mp split is the only delta — ≤1e-4."""
        import jax

        X, Y = _data()
        devs = np.array(jax.devices())
        mesh_dpp = jax.sharding.Mesh(devs[:4].reshape(2, 2),
                                     ("dp", "pp"))
        base, _, _ = _train(*_build_megatron_pp(False, dropout=True),
                            X, Y, mesh_dpp)
        mesh_3d = jax.sharding.Mesh(devs[:8].reshape(2, 2, 2),
                                    ("dp", "mp", "pp"))
        _set_mesh(mesh_3d)
        got, _, _ = _train(*_build_megatron_pp(True, dropout=True),
                           X, Y, mesh_3d)
        np.testing.assert_allclose(got, base, rtol=1e-4, atol=1e-6)

    @pytest.mark.slow
    def test_chunked_collective_matmul_pipeline(self, _set_mesh):
        """FLAGS_collective_matmul_chunks on the manual pipeline×mp
        path: per-chunk g-psum, numerics equal to the unchunked run."""
        import jax

        X, Y = _data()
        devs = np.array(jax.devices())
        mesh = jax.sharding.Mesh(devs[:4].reshape(2, 2), ("mp", "pp"))
        _set_mesh(mesh)
        a, _, _ = _train(*_build_megatron_pp(True), X, Y, mesh)
        stat_reset("collective_matmul_chunked")
        pt.set_flags({"FLAGS_collective_matmul_chunks": 2})
        try:
            b, _, _ = _train(*_build_megatron_pp(True), X, Y, mesh)
        finally:
            pt.set_flags({"FLAGS_collective_matmul_chunks": 0})
        assert stat_get("collective_matmul_chunked") > 0
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-7)

    @pytest.mark.slow
    def test_chunked_collective_matmul_gspmd_mp_only(self, _set_mesh):
        """GSPMD path: chunking engages on an mp-only mesh (exact vs
        unchunked); a mesh with a live dp axis falls back LOUDLY — the
        partitioner mis-partitions that pattern (probed), so the dp
        compositions route through the pipeline's manual path."""
        import jax

        from paddle_tpu.distributed import fleet

        def build():
            main, startup = Program(), Program()
            main.random_seed = 3
            with unique_name.guard(), program_guard(main, startup):
                x = layers.data("x", [H])
                y = layers.data("y", [1])
                h = layers.fc(x, 2 * H, act="relu", name="blk_ffn1",
                              param_attr=_attr(0.05), bias_attr=False)
                h = layers.fc(h, H, name="blk_ffn2",
                              param_attr=_attr(0.04), bias_attr=False)
                pred = layers.fc(h, 1, name="head", param_attr=_attr(0.1),
                                 bias_attr=False)
                loss = layers.mean(layers.square_error_cost(pred, y))
                strat = fleet.DistributedStrategy()
                strat.tensor_parallel = True
                fleet.init(is_collective=True, strategy=strat)
                fleet.distributed_optimizer(MomentumOptimizer(0.05, 0.9))
                fleet.minimize(loss)
            return main, startup, loss

        X, Y = _data()
        devs = np.array(jax.devices())
        mesh = jax.sharding.Mesh(devs[:4], ("mp",))
        _set_mesh(mesh)
        a, _, _ = _train(*build(), X, Y, mesh, steps=3)
        stat_reset("collective_matmul_chunked")
        stat_reset("collective_matmul_fallback")
        pt.set_flags({"FLAGS_collective_matmul_chunks": 2})
        try:
            b, _, _ = _train(*build(), X, Y, mesh, steps=3)
            assert stat_get("collective_matmul_chunked") > 0
            np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-7)

            # dp×mp: loud fallback, numerics unchanged
            mesh2 = jax.sharding.Mesh(devs[:8].reshape(2, 4),
                                      ("dp", "mp"))
            _set_mesh(mesh2)
            stat_reset("collective_matmul_chunked")
            c, _, _ = _train(*build(), X, Y, mesh2, steps=3)
            assert stat_get("collective_matmul_chunked") == 0
            assert stat_get("collective_matmul_fallback") > 0
            np.testing.assert_allclose(c, a, rtol=1e-4, atol=1e-6)
        finally:
            pt.set_flags({"FLAGS_collective_matmul_chunks": 0})


@pytest.mark.slow
class TestScanInsideStage:
    def _build_deep(self, n_layers=4, dropout=True, head_stage=2):
        """Two stages of ``n_layers`` isomorphic fc(+dropout) layers;
        the head/loss live in ``head_stage``.  With head_stage=2 every
        scanned stage contains ONLY its layer run — the shape the
        bitwise pin uses: an unscanned op trailing a scan in the SAME
        stage sits at a different XLA fusion boundary and can move by
        one ulp (probed; the 2-stage variant is pinned to 1e-6)."""
        main, startup = Program(), Program()
        main.random_seed = 5
        with unique_name.guard(), program_guard(main, startup):
            x = layers.data("x", [H])
            y = layers.data("y", [1])
            h = x
            for s in range(2):
                with device_guard(f"stage:{s}"):
                    for i in range(n_layers):
                        h = layers.fc(h, H, act="relu",
                                      name=f"st{s}_l{i}",
                                      param_attr=_attr(0.05 + 0.01 * i),
                                      bias_attr=False)
                        if dropout:
                            h = layers.dropout(h, 0.1)
            with device_guard(f"stage:{head_stage}"):
                pred = layers.fc(h, 1, name="head", param_attr=_attr(0.1),
                                 bias_attr=False)
                loss = layers.mean(layers.square_error_cost(pred, y))
            PipelineOptimizer(MomentumOptimizer(0.05, 0.9),
                              num_microbatches=2).minimize(loss)
        return main, startup, loss

    def _run(self, scan, X, Y, mesh, head_stage):
        pt.set_flags({"FLAGS_layer_scan": scan,
                      "FLAGS_layer_scan_min_layers": 4})
        try:
            losses, sc, _ = _train(
                *self._build_deep(head_stage=head_stage), X, Y, mesh)
        finally:
            pt.set_flags({"FLAGS_layer_scan": False})
        state = {n: np.asarray(sc.get_var(n))
                 for n in sorted(sc.local_var_names())
                 if n.startswith("st") and ".w_" in n}
        return losses, state

    def test_scan_inside_stage_bitwise(self):
        """FLAGS_layer_scan on a staged program: isomorphic per-layer
        runs inside each stage trace as ONE lax.scan — losses AND final
        trained state bitwise vs the unscanned pipeline (dropout RNG
        chain threaded through the scan carry)."""
        import jax

        X, Y = _data()
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:3]), ("pp",))
        base, st_base = self._run(False, X, Y, mesh, head_stage=2)
        stat_reset("pipeline_scan_segments")
        got, st_got = self._run(True, X, Y, mesh, head_stage=2)
        assert stat_get("pipeline_scan_segments") >= 2  # fwd + opt runs
        assert got == base, (got, base)
        for n in st_base:
            np.testing.assert_array_equal(st_base[n], st_got[n])

    def test_scan_with_trailing_stage_ops_close(self, mesh_pp2):
        """Head sharing the last scanned stage: the trailing op sits at
        a different fusion boundary, so the pin is 1e-6, not bitwise."""
        X, Y = _data()
        base, _ = self._run(False, X, Y, mesh_pp2, head_stage=1)
        got, _ = self._run(True, X, Y, mesh_pp2, head_stage=1)
        np.testing.assert_allclose(got, base, rtol=1e-6, atol=1e-7)


@pytest.mark.slow
class TestElasticCkptAcrossPPDegree:
    def test_save_restore_across_pp_degree_change(self, tmp_path,
                                                  mesh_pp2):
        """Train 2 steps at pp=2, checkpoint through the manager (the
        PackedParamRef views materialize true per-var values), restore
        into a 4-stage retagging of the same layers on a pp=4 mesh,
        and continue — the restored continuation matches the
        single-device continuation from the same checkpoint ≤1e-4
        (params AND momentum slots round-trip exactly; only schedule
        reassociation differs)."""
        import jax

        from paddle_tpu.ckpt import CheckpointManager

        def build(n_stages):
            main, startup = Program(), Program()
            main.random_seed = 1
            with unique_name.guard(), program_guard(main, startup):
                x = layers.data("x", [H])
                y = layers.data("y", [1])
                h = x
                for i in range(4):
                    stage = i if n_stages == 4 else i // 2
                    with device_guard(f"stage:{stage}"):
                        h = layers.fc(h, H, act="relu", name=f"l{i}",
                                      param_attr=_attr(0.05 + 0.01 * i),
                                      bias_attr=False)
                with device_guard(f"stage:{n_stages - 1}"):
                    pred = layers.fc(h, 1, name="head",
                                     param_attr=_attr(0.1),
                                     bias_attr=False)
                    loss = layers.mean(layers.square_error_cost(pred, y))
                PipelineOptimizer(MomentumOptimizer(0.05, 0.9),
                                  num_microbatches=2).minimize(loss)
            return main, startup, loss

        X, Y = _data()
        # phase 1: pp=2
        main2, startup2, loss2 = build(2)
        _, sc, exe = _train(main2, startup2, loss2, X, Y, mesh_pp2,
                            steps=2)
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state_names = [n for n in sorted(sc.local_var_names())
                       if (".w_" in n or "velocity" in n.lower()
                           or "_moment" in n)]
        mgr.save(2, scope=sc, var_names=state_names)

        def continue_from(main, startup, loss, mesh, steps=2):
            sc2 = pt.framework.Scope()
            exe2 = pt.Executor(pt.CPUPlace(), mesh=mesh)
            exe2.run(startup, scope=sc2)
            res = mgr.restore(scope=sc2, var_names=state_names)
            assert res and res["step"] == 2
            out = [float(np.asarray(
                exe2.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                         scope=sc2)[0]).item()) for _ in range(steps)]
            exe2.drain()
            return out

        # restored continuation on the NEW topology (pp=4)
        devs = np.array(jax.devices())
        mesh4 = jax.sharding.Mesh(devs[:4], ("pp",))
        main4, startup4, loss4 = build(4)
        got = continue_from(main4, startup4, loss4, mesh4)
        # oracle: single-device continuation from the same checkpoint
        main1, startup1, loss1 = build(2)
        base = continue_from(main1, startup1, loss1, None)
        np.testing.assert_allclose(got, base, rtol=1e-4, atol=1e-6)


class TestStretchedBucketE2E:
    def test_stretched_bucket_numerics_bitwise_vs_unfused(self):
        """A layer-scanned dp program whose stacked grad carriers AND
        unrolled head grads ride FuseAllReducePass: stretched buckets
        (overlap ON) keep losses bitwise-equal to the unfused run
        (FLAGS_fuse_passes off — layer scan still applies via its own
        gate), and the carrier bucket's collective sits before the
        unrolled tail in the post-pass stream."""
        import jax

        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.parallel_env import (reset_mesh,
                                                         set_mesh)

        def build():
            main, startup = Program(), Program()
            main.random_seed = 2
            with unique_name.guard(), program_guard(main, startup):
                x = layers.data("x", [H])
                y = layers.data("y", [1])
                h = x
                for i in range(4):
                    h = layers.fc(h, H, act="relu", name=f"l{i}",
                                  param_attr=_attr(0.05), bias_attr=False)
                pred = layers.fc(h, 1, name="head", param_attr=_attr(0.1),
                                 bias_attr=False)
                loss = layers.mean(layers.square_error_cost(pred, y))
                fleet.init(is_collective=True)
                fleet.distributed_optimizer(MomentumOptimizer(0.05, 0.9))
                fleet.minimize(loss)
            return main, startup, loss

        X, Y = _data()
        devs = np.array(jax.devices())
        mesh = jax.sharding.Mesh(devs[:2], ("dp",))
        set_mesh(mesh)
        pt.set_flags({"FLAGS_layer_scan": True,
                      "FLAGS_layer_scan_min_layers": 3})
        try:
            fused, _, _ = _train(*build(), X, Y, mesh, steps=4)
            pt.set_flags({"FLAGS_fuse_passes": False})
            try:
                unfused, _, _ = _train(*build(), X, Y, mesh, steps=4)
            finally:
                pt.set_flags({"FLAGS_fuse_passes": True})
            assert fused == unfused, (fused, unfused)
        finally:
            pt.set_flags({"FLAGS_layer_scan": False})
            reset_mesh()
