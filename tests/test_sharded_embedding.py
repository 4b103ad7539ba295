"""Sharded embedding engine (paddle_tpu.distributed.embedding).

The recommender acceptance of ISSUE/ROADMAP: tables row-sharded over
the mesh's 'mp' axis, lookups routed with an all-to-all, gradients a
dense scatter-add on the owning shard — replacing the reference's
parameter-server sparse stack.  Fast sections exercise the engine
core, the lowering dispatch, the pass stamps and the checkpoint
round-trip; the slow composition matrix trains the wide&deep flagship
on dp×mp / mp×pp meshes against replicated oracles and retags mp
across an elastic resume.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.distributed import embedding as dist_emb
from paddle_tpu.framework import passes as passes_mod
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.program import (Program, device_guard,
                                          program_guard)
from paddle_tpu.monitor import stat_get, stat_reset
from paddle_tpu.ops import embedding_ops
from paddle_tpu.rec import wide_deep_program

# mesh fixtures (mesh8 / mesh_dp_mp / mesh_mp_only): tests/conftest.py


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------

# wide&deep sized so tier-1 compiles stay cheap; the slow matrix
# overrides vocab/dims to the "table exceeds one chip" regime
WD = dict(batch_size=8, vocab_size=64, emb_dim=4, n_fields=4,
          n_dense=3, hidden=(8,), padding_idx=0)


def _np_oracle(w, ids, padding_idx=-1):
    """Dense numpy reference with the engine contract: OOV and padding
    ids yield zero rows."""
    w = np.asarray(w)
    ids = np.asarray(ids)
    keep = (ids >= 0) & (ids < w.shape[0])
    if padding_idx >= 0:
        keep = keep & (ids != padding_idx)
    out = w[np.where(keep, ids, 0)]
    return out * keep[..., None].astype(w.dtype)


def _np_grad_oracle(wshape, ids, ct, padding_idx=-1):
    """Scatter-add gradient oracle matching the custom_vjp backward."""
    g = np.zeros(wshape, ct.dtype)
    flat, ctf = np.asarray(ids).reshape(-1), ct.reshape(-1, wshape[-1])
    for i, t in zip(flat, ctf):
        if 0 <= i < wshape[0] and i != padding_idx:
            g[i] += t
    return g


def _build_wd(sparse, fleet_tp=False, lr=0.1, seed=7, **over):
    cfg = dict(WD, sparse=sparse, lr=lr)
    cfg.update(over)
    # own name scope: every build gets IDENTICAL param names, so
    # checkpoints restore across independently-built programs
    with unique_name.guard():
        main, startup, feeds, loss, opt = wide_deep_program(**cfg)
    main.random_seed = seed
    with unique_name.guard(), program_guard(main, startup):
        if fleet_tp:
            from paddle_tpu.distributed import fleet

            strat = fleet.DistributedStrategy()
            strat.tensor_parallel = True
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(opt)
            fleet.minimize(loss)
        else:
            opt.minimize(loss)
    return main, startup, loss


def _wd_feed(seed=0, **over):
    cfg = dict(WD)
    cfg.update(over)
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, cfg["vocab_size"],
                     (cfg["batch_size"], cfg["n_fields"])).astype("int64")
    ids[0, 0] = cfg["padding_idx"]  # exercise the padding row
    return {
        "sparse_ids": ids,
        "dense_x": rs.randn(cfg["batch_size"],
                            cfg["n_dense"]).astype("float32"),
        "labels": rs.randint(0, 2,
                             (cfg["batch_size"], 1)).astype("int64"),
    }


def _train(main, startup, loss, feed, mesh, steps=3, scope=None):
    scope = scope or pt.framework.Scope()
    exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
    exe.run(startup, scope=scope)
    out = [float(np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                    scope=scope)[0]).ravel()[0])
           for _ in range(steps)]
    exe.drain()
    return out, scope


# ---------------------------------------------------------------------------
# engine core: dense reference + all-to-all shard_map path
# ---------------------------------------------------------------------------


class TestEngineCore:
    def test_dense_ref_forward_contract(self, rng):
        w = rng.randn(16, 4).astype("float32")
        ids = np.array([[3, 15, 2], [-1, 99, 0]], dtype="int64")
        out = np.asarray(embedding_ops.embedding_lookup_ref(w, ids, 2))
        np.testing.assert_array_equal(out, _np_oracle(w, ids, 2))
        # padding + OOV rows are exactly zero, valid rows exact bytes
        assert not out[0, 2].any() and not out[1, 0].any() \
            and not out[1, 1].any()
        np.testing.assert_array_equal(out[0, 0], w[3])

    def test_dense_padding_and_oov_grad_zero(self, rng):
        """Satellite (b): padding_idx gradient exactly zero on the
        dense engine path; OOV ids contribute no gradient."""
        w = rng.randn(16, 4).astype("float32")
        ids = np.array([1, 2, 2, 5, -3, 99, 1], dtype="int64")

        def loss(w):
            return embedding_ops.embedding_lookup_ref(w, ids, 2).sum()

        g = np.asarray(jax.grad(loss)(w))
        ct = np.ones((ids.size, 4), "float32")
        np.testing.assert_array_equal(g, _np_grad_oracle(w.shape, ids,
                                                         ct, 2))
        assert not g[2].any()           # padding row pinned zero
        assert g[1, 0] == 2.0           # id 1 looked up twice
        assert g[0, 0] == 0.0           # id 0 never looked up

    def test_alltoall_bytes_accounting(self):
        # degree=4, 10 ids pad to cap=3 per rank: 4*3 slots of
        # (8-byte id out + 16*4-byte row back)
        assert embedding_ops.alltoall_bytes_per_lookup(10, 4, 16) == \
            4 * 3 * (8 + 64)

    def test_sharded_lookup_roundtrip_and_grad(self, rng):
        """The all-to-all engine under shard_map: forward parity with
        the dense oracle (incl. OOV and a non-divisible id count) and
        the custom_vjp backward yields the exact scatter-add grad with
        the padding row zero — satellite (b), sharded path."""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        degree, vocab, dim, pad = 4, 32, 4, 1
        mesh = Mesh(np.array(jax.devices()[:degree]), ("mp",))
        w = rng.randn(vocab, dim).astype("float32")
        # n=7 ids (not divisible by degree) incl. padding + both OOV kinds
        ids = np.array([5, 1, 31, -2, 40, 5, 17], dtype="int64")
        coef = rng.randn(ids.size, dim).astype("float32")

        f = shard_map(
            lambda lw, i: dist_emb.sharded_lookup(
                lw, i, axis_name="mp", degree=degree, padding_idx=pad),
            mesh=mesh, in_specs=(P("mp", None), P()), out_specs=P(),
            check_rep=False)

        @jax.jit
        def fwd_and_grad(w):  # one compile covers both directions
            out, vjp = jax.vjp(lambda w: f(w, ids), w)
            return out, vjp(coef)[0]

        out, g = map(np.asarray, fwd_and_grad(w))
        np.testing.assert_allclose(out, _np_oracle(w, ids, pad),
                                   rtol=0, atol=0)
        np.testing.assert_allclose(
            g, _np_grad_oracle(w.shape, ids, coef, pad),
            rtol=1e-6, atol=1e-6)
        assert not g[pad].any()

    @pytest.mark.slow
    def test_sharded_matches_dense_ref_vjp(self, rng):
        """The two engine custom_vjps (per-shard all-to-all vs global
        dense ref) are the same mathematical operator."""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        degree, vocab, dim = 4, 16, 3
        mesh = Mesh(np.array(jax.devices()[:degree]), ("mp",))
        w = rng.randn(vocab, dim).astype("float32")
        ids = np.array([[0, 7, 7], [15, 3, 0]], dtype="int64")
        f = shard_map(
            lambda lw, i: dist_emb.sharded_lookup(
                lw, i, axis_name="mp", degree=degree, padding_idx=0),
            mesh=mesh, in_specs=(P("mp", None), P()), out_specs=P(),
            check_rep=False)
        np.testing.assert_allclose(
            np.asarray(f(w, ids)),
            np.asarray(embedding_ops.embedding_lookup_ref(w, ids, 0)),
            rtol=0, atol=0)
        g_sh = jax.grad(lambda w: jnp.sin(f(w, ids)).sum())(w)
        g_ref = jax.grad(lambda w: jnp.sin(
            embedding_ops.embedding_lookup_ref(w, ids, 0)).sum())(w)
        np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# lowering dispatch + the sparse-fallback bugfix
# ---------------------------------------------------------------------------


class TestLoweringDispatch:
    def test_sparse_fallback_warns_and_counts(self):
        """Satellite (a): is_sparse with no sharding plan degrades to a
        dense replicated table LOUDLY — warn once + counter — instead
        of silently ignoring the flag."""
        embedding_ops._warned_sparse_fallback = False
        stat_reset("emb_sparse_fallback_dense")
        main, startup, loss = _build_wd(sparse=True)
        with pytest.warns(UserWarning,
                          match="no active sharding plan"):
            losses, _ = _train(main, startup, loss, _wd_feed(), None,
                               steps=2)
        assert np.isfinite(losses).all()
        assert stat_get("emb_sparse_fallback_dense") >= 2  # both tables
        # warn-once: a second program does not warn again
        import warnings as _w

        main2, startup2, loss2 = _build_wd(sparse=True, seed=8)
        with _w.catch_warnings():
            _w.simplefilter("error", UserWarning)
            _train(main2, startup2, loss2, _wd_feed(), None, steps=1)

    def test_plain_dense_path_untouched(self):
        """sparse=False stays on the historical jnp.take path: no
        warning, no counter."""
        embedding_ops._warned_sparse_fallback = False
        stat_reset("emb_sparse_fallback_dense")
        main, startup, loss = _build_wd(sparse=False)
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("error", UserWarning)
            losses, _ = _train(main, startup, loss, _wd_feed(), None,
                               steps=2)
        assert np.isfinite(losses).all()
        assert stat_get("emb_sparse_fallback_dense") == 0

    def test_is_sparse_attr_reaches_op(self):
        """Satellite (a): the flag survives layers.embedding /
        nn.functional.embedding / nn.Embedding into the op attrs."""
        main, startup = Program(), Program()
        with program_guard(main, startup):
            ids = layers.data("i", [4, 2], dtype="int64",
                              append_batch_size=False)
            layers.embedding(ids, (8, 3), is_sparse=True)
            layers.embedding(ids, (8, 3))
        ops = [op for op in main.global_block.ops
               if op.type.startswith("lookup_table")]
        assert [bool(op.attr("is_sparse", False)) for op in ops] == \
            [True, False]
        emb = pt.nn.Embedding(8, 3, sparse=True)
        assert emb.sparse is True
        assert pt.nn.Embedding(8, 3, is_sparse=True).sparse is True  # 1.x
        assert pt.nn.Embedding(8, 3).sparse is False


# ---------------------------------------------------------------------------
# sharding pass: seeding, stamps, shard_info
# ---------------------------------------------------------------------------


class TestShardingPass:
    def _planned(self, mesh):
        main, _, loss = _build_wd(sparse=True, fleet_tp=True)
        out = passes_mod.apply_passes(
            main, fetch_names=(loss.name,),
            feed_names=("sparse_ids", "dense_x", "labels"), mesh=mesh)
        return out

    def test_pass_seeds_row_sharding_and_stamps(self, mesh_dp_mp):
        """is_sparse tables get P('mp', None) with NO partition rule,
        and every lookup op (forward AND grad) carries the engine
        stamp."""
        out = self._planned(mesh_dp_mp)
        plan = out._tp_plan
        assert plan is not None and plan.mp_degree == 4
        assert plan.spec_tuple("wd_table") == ("mp", None)
        assert plan.spec_tuple("wd_wide_table") == ("mp", None)
        fwd = [op for op in out.global_block.ops
               if op.type in ("lookup_table", "lookup_table_v2")]
        bwd = [op for op in out.global_block.ops
               if op.type in ("lookup_table_grad",
                              "lookup_table_v2_grad")]
        assert fwd and bwd
        for op in fwd + bwd:
            assert int(op.attr(passes_mod.EMB_SHARD_ATTR, 0)) == 4, \
                (op.type, dict(op.attrs))
        # forward ops also pin their output layout (mp -> replicated)
        for op in fwd:
            anchors = op.attr(passes_mod.TP_CONSTRAINT_ATTR, ())
            assert any(a.split("\t")[0] == op.output("Out")[0]
                       for a in anchors), anchors

    def test_table_grad_reduced_in_shard_bytes(self, mesh_dp_mp):
        """The dp grad-allreduce accounting sees the SHARD, not the
        full table — the whole point of not replicating it."""
        plan = self._planned(mesh_dp_mp)._tp_plan
        rec = plan.grad_reduce.get("wd_table@GRAD")
        assert rec is not None and rec["axes"] == ("dp",)
        full = WD["vocab_size"] * WD["emb_dim"] * 4
        assert rec["bytes"] == full // 4

    def test_shard_info(self, mesh_dp_mp):
        out = self._planned(mesh_dp_mp)
        info = dist_emb.shard_info(out, "wd_table", mesh=mesh_dp_mp)
        assert info["row_sharded"] is True
        assert info["spec"] == ("mp", None)
        assert info["shard_divisor"] == 4
        assert info["rows_per_shard"] == WD["vocab_size"] // 4
        assert info["bytes_per_chip"] * 4 == info["global_bytes"] \
            == WD["vocab_size"] * WD["emb_dim"] * 4

    def test_partition_rules_helper(self):
        rules = dist_emb.partition_rules("tbl", "other.w_0")
        assert rules == [(r"^tbl$", "mp,None"),
                         (r"^other\.w_0$", "mp,None")]

    def test_fleet_facade(self):
        from paddle_tpu.distributed import fleet

        assert fleet.distributed_embedding is \
            dist_emb.distributed_embedding


# ---------------------------------------------------------------------------
# eager helper telemetry
# ---------------------------------------------------------------------------


class TestEagerLookup:
    def test_lookup_telemetry(self, rng):
        stat_reset("emb_oov_ids")
        w = rng.randn(8, 3).astype("float32")
        ids = np.array([1, 7, -1, 9], dtype="int64")
        out = np.asarray(dist_emb.lookup(w, ids))
        np.testing.assert_array_equal(out, _np_oracle(w, ids, -1))
        assert stat_get("emb_oov_ids") == 2
        from paddle_tpu.monitor import export_stats

        stats = dict(export_stats())
        assert any(k.startswith("emb_lookup_seconds") for k in stats), \
            sorted(k for k in stats if k.startswith("emb_"))


# ---------------------------------------------------------------------------
# checkpoint: row-sharded table round-trip
# ---------------------------------------------------------------------------


class TestCheckpoint:
    def test_localshard_assembles_row_shards(self, rng):
        """LocalShard covers the table layout: axis-0 row blocks at
        explicit origins reassemble bitwise."""
        from paddle_tpu.ckpt.state import LocalShard, _assemble_blocks

        w = rng.randn(32, 4).astype("float32")
        shards = [LocalShard(w[o:o + 8], w.shape, origin=(o, 0))
                  for o in range(0, 32, 8)]
        arr, origin = _assemble_blocks(
            {s.origin: s.array for s in shards}, 2)
        assert origin == (0, 0)
        np.testing.assert_array_equal(arr, w)

    def test_row_sharded_table_ckpt_roundtrip(self, tmp_path,
                                              mesh_dp_mp):
        """save_sharded/load_sharded round-trips a live mp-row-sharded
        table and the run resumes the uninterrupted trajectory."""
        from paddle_tpu.distributed.checkpoint import (load_sharded,
                                                       save_sharded)

        feed = _wd_feed()

        def fresh():
            main, startup, loss = _build_wd(sparse=True, fleet_tp=True)
            scope = pt.framework.Scope()
            exe = pt.Executor(pt.CPUPlace(), mesh=mesh_dp_mp)
            exe.run(startup, scope=scope)
            return main, loss, exe, scope

        def step(main, loss, exe, scope):
            return float(np.asarray(exe.run(
                main, feed=feed, fetch_list=[loss],
                scope=scope)[0]).ravel()[0])

        main, loss, exe, scope = fresh()
        full = [step(main, loss, exe, scope) for _ in range(4)]
        exe.drain()

        main, loss, exe, scope = fresh()
        for _ in range(2):
            step(main, loss, exe, scope)
        exe.drain()
        # the live table is genuinely row-sharded before the save
        tbl = scope.get_var("wd_table")
        assert tuple(tbl.sharding.spec) == ("mp", None), tbl.sharding
        saved = save_sharded(scope, str(tmp_path))
        assert "wd_table" in saved

        main2, loss2, exe2, scope2 = fresh()
        step(main2, loss2, exe2, scope2)  # materialize layouts
        load_sharded(scope2, str(tmp_path))
        resumed = [step(main2, loss2, exe2, scope2) for _ in range(2)]
        exe2.drain()
        np.testing.assert_allclose(resumed, full[2:4], rtol=1e-5,
                                   atol=1e-7)


# ---------------------------------------------------------------------------
# composition matrix: dp×mp parity+budget; slow-marked: mp×pp, elastic mp retag
# ---------------------------------------------------------------------------

# the "one simulated chip" of the acceptance: both replicated tables
# (~278 KB) blow it, one mp=4 shard (~70 KB) fits
EMB_CHIP_BUDGET_BYTES = 150_000
BIG = dict(vocab_size=4096, emb_dim=16, n_fields=8, batch_size=16,
           n_dense=4, hidden=(32,), padding_idx=0)


class TestComposition:
    def test_dp_mp_parity_and_chip_budget(self, mesh_dp_mp,
                                          restore_flags_budget):
        """Acceptance: a wide&deep model whose tables exceed one
        simulated chip's HBM trains on dp×mp with loss parity <=1e-4
        vs the replicated oracle, the table physically row-sharded,
        and the PR 8 pre-dispatch budget gate passing on the sharded
        footprint (and rejecting the replicated one)."""
        from paddle_tpu.distributed.parallel_env import (reset_mesh,
                                                         set_mesh)
        from paddle_tpu.observe import xla_stats
        from paddle_tpu.observe.xla_stats import MemoryBudgetError

        feed = _wd_feed(seed=3, **BIG)
        reset_mesh()
        base, _ = _train(*_build_wd(sparse=False, **BIG), feed, None,
                         steps=5)

        set_mesh(mesh_dp_mp)
        got, scope = _train(*_build_wd(sparse=True, fleet_tp=True,
                                       **BIG), feed, mesh_dp_mp,
                            steps=5)
        assert np.isfinite(got).all(), got
        np.testing.assert_allclose(got, base, rtol=1e-4, atol=1e-6)

        tbl = scope.get_var("wd_table")
        assert tuple(tbl.sharding.spec) == ("mp", None), tbl.sharding
        assert tbl.addressable_shards[0].data.shape == \
            (BIG["vocab_size"] // 4, BIG["emb_dim"])
        full = sum(int(np.prod(scope.get_var(n).shape)) * 4
                   for n in ("wd_table", "wd_wide_table"))
        per_chip = sum(
            int(np.prod(
                scope.get_var(n).addressable_shards[0].data.shape)) * 4
            for n in ("wd_table", "wd_wide_table"))
        assert full > EMB_CHIP_BUDGET_BYTES >= per_chip, \
            (full, per_chip)

        # PR 8 budget gate on the simulated chip: shard fits, full
        # table is rejected BEFORE dispatch
        pt.set_flags({"FLAGS_hbm_budget_fraction": 1.0,
                      "FLAGS_hbm_bytes_per_device":
                          EMB_CHIP_BUDGET_BYTES})
        assert xla_stats.check_hbm_budget(per_chip)["verdict"] == "pass"
        with pytest.raises(MemoryBudgetError):
            xla_stats.check_hbm_budget(full)

        # the engine accounted its collective traffic
        from paddle_tpu.monitor import export_stats

        stats = dict(export_stats())
        assert stats.get("emb_rows_per_shard") == \
            BIG["vocab_size"] // 4

    @pytest.mark.slow
    def test_pipeline_mp_composed_parity(self):
        """mp×pp: the embedding rides the EXPLICIT all-to-all engine
        inside the per-stage shard_map; parity vs the pp-only
        PipelineOptimizer oracle."""
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.parallel_env import (reset_mesh,
                                                         set_mesh)
        from paddle_tpu.initializer import NormalInitializer
        from paddle_tpu.monitor import stat_get as _sg, \
            stat_reset as _sr
        from paddle_tpu.optimizer import (MomentumOptimizer,
                                          PipelineOptimizer)
        from paddle_tpu.param_attr import ParamAttr

        V, D, B, F = 32, 8, 8, 4

        def build(use_tp, n_micro=2):
            main, startup = Program(), Program()
            main.random_seed = 3
            with program_guard(main, startup):
                ids = layers.data("ids", [B, F], dtype="int64",
                                  append_batch_size=False)
                y = layers.data("y", [B, 1], dtype="float32",
                                append_batch_size=False)
                with device_guard("stage:0"):
                    emb = layers.embedding(
                        ids, (V, D), is_sparse=True, padding_idx=0,
                        param_attr=ParamAttr(
                            name="tbl",
                            initializer=NormalInitializer(0.0, 0.1)))
                    h = layers.reshape(emb, [0, F * D])
                    h = layers.fc(h, 16, act="relu", name="s0_fc",
                                  param_attr=ParamAttr(
                                      initializer=NormalInitializer(
                                          0.0, 0.05)))
                with device_guard("stage:1"):
                    pred = layers.fc(h, 1, name="head",
                                     param_attr=ParamAttr(
                                         initializer=NormalInitializer(
                                             0.0, 0.05)),
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
                    PipelineOptimizer(
                        opt, num_microbatches=n_micro).minimize(loss)
            return main, startup, loss

        rs = np.random.RandomState(0)
        ids = rs.randint(0, V, (B, F)).astype("int64")
        ids[1, 2] = 0
        feed = {"ids": ids, "y": rs.randn(B, 1).astype("float32")}
        devs = np.array(jax.devices())

        reset_mesh()
        mesh_pp = jax.sharding.Mesh(devs[:2], ("pp",))
        with unique_name.guard():
            base, _ = _train(*build(False), feed, mesh_pp, steps=4)

        _sr("emb_alltoall_bytes")
        mesh = jax.sharding.Mesh(devs[:4].reshape(2, 2), ("mp", "pp"))
        set_mesh(mesh)
        try:
            with unique_name.guard():
                got, _ = _train(*build(True), feed, mesh, steps=4)
        finally:
            reset_mesh()
        np.testing.assert_allclose(got, base, rtol=1e-4, atol=1e-6)
        assert _sg("emb_alltoall_bytes") > 0  # explicit engine engaged

    @pytest.mark.slow
    def test_elastic_resume_mp4_to_mp2(self):
        """Elastic retag mp 4 -> 2: the checkpointed table restores
        BITWISE onto the new topology (placed as vocab/2 row shards)
        and training continues with loss parity vs the replicated
        oracle."""
        from paddle_tpu.ckpt import restore_scope, snapshot_scope
        from paddle_tpu.distributed.parallel_env import (
            init_parallel_env, reset_mesh)

        feed = _wd_feed(seed=5, **BIG)
        reset_mesh()
        base, _ = _train(*_build_wd(sparse=False, **BIG), feed, None,
                         steps=4)

        reset_mesh()
        mesh4 = init_parallel_env(mesh_shape=[2, 4],
                                  axis_names=("dp", "mp"))
        with unique_name.guard():
            _, scope = _train(*_build_wd(sparse=True, fleet_tp=True,
                                         **BIG), feed, mesh4, steps=2)
        snap = snapshot_scope(scope)
        saved_tbl = np.asarray(snap["wd_table"])
        reset_mesh()

        # new topology, lr=0: one no-op step just places the restored
        # state -> the table must be bitwise the saved bytes, now
        # sharded vocab/2 per chip
        mesh2 = init_parallel_env(mesh_shape=[4, 2],
                                  axis_names=("dp", "mp"))
        with unique_name.guard():
            main, startup, loss = _build_wd(sparse=True, fleet_tp=True,
                                            lr=0.0, **BIG)
        scope2 = pt.framework.Scope()
        exe = pt.Executor(pt.CPUPlace(), mesh=mesh2)
        exe.run(startup, scope=scope2)
        # keep THIS program's lr=0.0 (the snapshot carries the real lr)
        restore_scope(scope2, snap,
                      var_names=[n for n in snap
                                 if not n.startswith("learning_rate")])
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope2)
        exe.drain()
        tbl = scope2.get_var("wd_table")
        assert tuple(tbl.sharding.spec) == ("mp", None), tbl.sharding
        assert tbl.addressable_shards[0].data.shape == \
            (BIG["vocab_size"] // 2, BIG["emb_dim"])
        np.testing.assert_array_equal(np.asarray(tbl), saved_tbl)
        reset_mesh()

        # and a real-lr continuation tracks the oracle tail
        mesh2b = init_parallel_env(mesh_shape=[4, 2],
                                   axis_names=("dp", "mp"))
        with unique_name.guard():
            main, startup, loss = _build_wd(sparse=True, fleet_tp=True,
                                            **BIG)
        scope3 = pt.framework.Scope()
        exe = pt.Executor(pt.CPUPlace(), mesh=mesh2b)
        exe.run(startup, scope=scope3)
        restore_scope(scope3, snap)
        resumed = [float(np.asarray(exe.run(
            main, feed=feed, fetch_list=[loss],
            scope=scope3)[0]).ravel()[0]) for _ in range(2)]
        exe.drain()
        reset_mesh()
        np.testing.assert_allclose(resumed, base[2:4], rtol=1e-4,
                                   atol=1e-6)


@pytest.fixture
def restore_flags_budget():
    yield
    pt.set_flags({"FLAGS_hbm_budget_fraction": 0.0,
                  "FLAGS_hbm_bytes_per_device": 0})
