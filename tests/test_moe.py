"""Mixture-of-experts over the 'ep' mesh axis (ShardingPropagationPass
ep seeding + ExpertParallelMetaOptimizer + ops/moe_ops.py).

Tier-1-lean units: router determinism and the GShard slot-priority
rule (the router is RNG-free, so determinism holds under any threefry
partitioning config), capacity-factor drop accounting, plan-time
rejection of ep-sharded consumers outside the routed-FFN family, the
aux-loss gradient path, and the FLAGS_ep_degree mesh-carve validation.

Composition matrix, per the dist-test oracle discipline: ep×dp
per-step loss parity <= 1e-4 vs the replicated single-device oracle
(dense execution of the same routed FFN — matched activated FLOPs by
construction), the chunked all-to-all schedule bitwise the sequential
one with the ledger's hidden all-to-alls, and (slow-marked) ep×mp×pp
compile + collective-ledger keys and elastic checkpoint resume across
an ep 2->4 retag (bitwise on the surviving state).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import passes as passes_mod
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.program import (Program, device_guard,
                                          program_guard)
from paddle_tpu.optimizer import MomentumOptimizer

E, K, DM, FFN = 4, 2, 16, 32


def _softmax_np(logits):
    z = logits - logits.max(axis=-1, keepdims=True)
    ez = np.exp(z)
    return ez / ez.sum(axis=-1, keepdims=True)


def _router_inputs(s=12, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(s, DM).astype(np.float32)
    gw = rs.randn(DM, E).astype(np.float32)
    return x, gw


# ---------------------------------------------------------------------------
# tier-1-lean units (no executor compile)
# ---------------------------------------------------------------------------


class TestRouter:
    def test_topk_selection_deterministic_and_correct(self):
        from paddle_tpu.ops.moe_ops import moe_router_ref

        x, gw = _router_inputs()
        kw = dict(num_experts=E, top_k=K, capacity_factor=2.0)
        c1, a1, l1 = moe_router_ref(x, gw, **kw)
        c2, a2, l2 = moe_router_ref(x, gw, **kw)
        # bitwise-deterministic: same inputs, same combine/aux/load
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        assert float(a1) == float(a2)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))

        # each token's nonzero combine experts are exactly its top-k
        # by router logit (softmax is monotone, so logits decide)
        logits = x @ gw
        combine = np.asarray(c1)           # [S, E, C]
        for s in range(x.shape[0]):
            got = set(np.nonzero(combine[s].sum(axis=-1) > 0)[0])
            want = set(np.argsort(-logits[s])[:K])
            assert got == want, (s, got, want)
        # kept gate weights renormalize over the top-k per token
        np.testing.assert_allclose(
            combine.sum(axis=(1, 2)), np.ones(x.shape[0]), atol=1e-5)

    def test_capacity_values(self):
        from paddle_tpu.ops.moe_ops import moe_capacity

        assert moe_capacity(64, 4, 2, 1.25) == 40
        assert moe_capacity(8, 4, 1, 1.0) == 2
        # floor: never zero slots, even at tiny token counts
        assert moe_capacity(1, 64, 1, 0.5) == 1

    def test_capacity_drops_follow_gshard_priority(self):
        """All tokens routed to expert 0 with cap=2: the two lowest
        token indices claim the slots (choice-then-token order), every
        later token is dropped with ZERO combine weight, and the
        balance gauges price the drop fraction in ppm."""
        from paddle_tpu.ops.moe_ops import (moe_balance_gauges,
                                            moe_router_ref)

        s = 8
        x = np.abs(np.random.RandomState(1).randn(s, DM)).astype("f4")
        gw = np.zeros((DM, E), np.float32)
        gw[:, 0] = 1.0                       # every token -> expert 0
        combine, _aux, load = moe_router_ref(
            x, gw, num_experts=E, top_k=1, capacity_factor=1.0)
        combine = np.asarray(combine)        # [S, E, cap=2]
        np.testing.assert_array_equal(np.asarray(load), [2, 0, 0, 0])
        assert (combine[:2].sum(axis=(1, 2)) > 0).all()
        np.testing.assert_array_equal(
            combine[2:], np.zeros_like(combine[2:]))

        g = moe_balance_gauges(load, num_tokens=s, top_k=1,
                               publish=False)
        assert g["moe_dropped_fraction_ppm"] == 750000   # 6/8 dropped
        # one hot expert out of four: mean/max load = 0.25
        assert g["moe_expert_balance_ppm"] == 250000

    def test_aux_loss_gradient_reaches_gate(self):
        """The Switch aux loss must train the ROUTER: its gradient wrt
        the gate weight is finite and nonzero (f is stop-gradient, P is
        not — d(aux)/d(gate) flows through the mean router prob)."""
        import jax

        from paddle_tpu.ops.moe_ops import moe_router_ref

        x, gw = _router_inputs(seed=3)

        def aux_of(g):
            return moe_router_ref(x, g, num_experts=E, top_k=K,
                                  capacity_factor=1.25)[1]

        grad = np.asarray(jax.grad(aux_of)(gw))
        assert np.isfinite(grad).all()
        assert np.abs(grad).max() > 0.0


def _build_moe(use_ep, cf=1.25, seed=1, aux_coeff=0.01):
    from paddle_tpu.distributed import fleet

    main, startup = Program(), Program()
    main.random_seed = seed
    with unique_name.guard(), program_guard(main, startup):
        x = layers.data("x", [DM])
        y = layers.data("y", [1])
        h, aux, load = layers.moe_ffn(
            x, num_experts=E, ffn_dim=FFN, top_k=K,
            capacity_factor=cf, name="moe0")
        pred = layers.fc(h, 1, name="head")
        loss = layers.elementwise_add(
            layers.mean(layers.square_error_cost(pred, y)),
            layers.scale(aux, aux_coeff))
        opt = MomentumOptimizer(0.05, 0.9)
        if use_ep:
            strat = fleet.DistributedStrategy()
            strat.expert_parallel = True
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(opt)
            fleet.minimize(loss)
        else:
            opt.minimize(loss)
    return main, startup, loss


def _data(n=32, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, DM).astype("float32")
    Y = (X.sum(axis=1, keepdims=True) * 0.3).astype("float32")
    return X, Y


def _train(main, startup, loss, X, Y, mesh, steps=4, scope=None):
    sc = scope if scope is not None else pt.framework.Scope()
    exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
    exe.run(startup, scope=sc)
    out = [float(np.asarray(exe.run(
        main, feed={"x": X, "y": Y}, fetch_list=[loss],
        scope=sc)[0]).item()) for _ in range(steps)]
    exe.drain()
    return out, sc, exe


@pytest.fixture
def mesh_dp_ep():
    from paddle_tpu.distributed.parallel_env import (init_parallel_env,
                                                     reset_mesh)

    reset_mesh()
    mesh = init_parallel_env(mesh_shape=[4, 2], axis_names=("dp", "ep"))
    yield mesh
    reset_mesh()


class TestPlanTime:
    def test_plan_stamps_ep_specs(self, mesh_dp_ep):
        main, _, loss = _build_moe(True)
        out = passes_mod.apply_passes(
            main, fetch_names=(loss.name,), feed_names=("x", "y"),
            mesh=mesh_dp_ep)
        plan = out._tp_plan
        assert plan is not None and plan.ep_degree == 2
        # stacked expert carriers shard on the leading (expert) axis;
        # the router gate stays replicated
        assert plan.spec_tuple("moe0.w_1") == ("ep", None, None)
        assert plan.spec_tuple("moe0.w_2") == ("ep", None, None)
        assert plan.spec_tuple("moe0.b_0") == ("ep", None)
        assert plan.spec_tuple("moe0.w_0") == ()
        # optimizer slots inherit the expert sharding
        assert plan.spec_tuple("moe0.w_1_velocity_0") == \
            ("ep", None, None)
        assert passes_mod.has_ep_marks(out)
        moe_ops = [op for op in out.global_block.ops
                   if op.type == "moe_ffn"]
        assert moe_ops and all(
            op.attr(passes_mod.MOE_EP_ATTR) == 2 for op in moe_ops)

    def test_plan_rejects_ep_consumer_outside_ffn_family(
            self, mesh_dp_ep):
        """An op outside the routed-FFN family reading an ep-sharded
        var would silently compute on a 1/ep slice; the strict flow
        walk refuses it at plan time, naming op and var."""
        main, _, loss = _build_moe(True)
        with program_guard(main):
            bad = layers.mean(main.global_block.var("moe0.w_1"))
        with pytest.raises(ValueError,
                           match=r"expert-parallel-sharded var"):
            passes_mod.apply_passes(
                main, fetch_names=(loss.name, bad.name),
                feed_names=("x", "y"), mesh=mesh_dp_ep)

    def test_ep_degree_flag_carve_validation(self):
        """init_parallel_env() must reject bad FLAGS_ep_degree
        factorizations LOUDLY with the axis named — not deep in GSPMD
        with an opaque sharding error."""
        from paddle_tpu.distributed.parallel_env import (
            init_parallel_env, reset_mesh)

        reset_mesh()
        try:
            pt.set_flags({"FLAGS_ep_degree": 3})
            with pytest.raises(ValueError,
                               match=r"FLAGS_ep_degree=3 does not "
                                     r"divide"):
                init_parallel_env()
            # ep x pp over-subscription: 4 x 4 = 16 > 8 devices
            pt.set_flags({"FLAGS_ep_degree": 4, "FLAGS_pp_degree": 4})
            with pytest.raises(ValueError, match=r"exceeds"):
                init_parallel_env()
            # a valid degree carves (dp, ep) out of the 8 devices
            pt.set_flags({"FLAGS_ep_degree": 4, "FLAGS_pp_degree": 0})
            mesh = init_parallel_env()
            assert tuple(mesh.axis_names) == ("dp", "ep")
            assert int(mesh.shape["ep"]) == 4
            assert int(mesh.shape["dp"]) == 2
        finally:
            pt.set_flags({"FLAGS_ep_degree": 0, "FLAGS_pp_degree": 0})
            reset_mesh()


# ---------------------------------------------------------------------------
# slow composition matrix
# ---------------------------------------------------------------------------


class TestComposition:
    def test_ep_dp_parity_vs_replicated_oracle(self, mesh_dp_ep):
        """Per-step losses of the dp×ep run match the replicated
        single-device oracle within 1e-4 rel, and the expert stack is
        PHYSICALLY sharded (each chip holds E/ep experts)."""
        from paddle_tpu.distributed.parallel_env import (reset_mesh,
                                                         set_mesh)

        X, Y = _data()
        reset_mesh()
        base, _, _ = _train(*_build_moe(False), X, Y, None)
        set_mesh(mesh_dp_ep)
        ep_losses, scope, _ = _train(*_build_moe(True), X, Y,
                                     mesh_dp_ep)
        rel = max(abs(a - b) / max(abs(a), 1e-8)
                  for a, b in zip(base, ep_losses))
        assert rel <= 1e-4, (rel, base, ep_losses)
        w1 = scope.get_var("moe0.w_1")
        shard_shapes = {tuple(s.data.shape)
                        for s in w1.addressable_shards}
        assert shard_shapes == {(E // 2, DM, FFN)}

    def test_chunked_alltoall_is_bitwise_the_sequential_schedule(
            self, mesh_dp_ep):
        """FLAGS_moe_alltoall_chunks slices the capacity axis (20 rows
        here, 4 chunks) and combines once: the same program's losses are
        the sequential schedule's bit for bit, the chunked lowering is
        the one that ran, and the ledger hides all-to-alls under it that
        the sequential schedule exposes."""
        from paddle_tpu.distributed.parallel_env import set_mesh
        from paddle_tpu.monitor import stat_get
        from paddle_tpu.observe.phases import collective_inventory

        X, Y = _data()
        set_mesh(mesh_dp_ep)
        losses = {}
        try:
            for chunks in (0, 4):
                pt.set_flags({"FLAGS_moe_alltoall_chunks": chunks})
                chunked0 = stat_get("moe_alltoall_chunked")
                main, startup, loss = _build_moe(True)
                losses[chunks], _, _ = _train(main, startup, loss, X, Y,
                                              mesh_dp_ep)
                assert (stat_get("moe_alltoall_chunked") > chunked0) \
                    == bool(chunks)
        finally:
            pt.set_flags({"FLAGS_moe_alltoall_chunks": 0})
        assert losses[4] == losses[0]

        plan = passes_mod.apply_passes(
            main, fetch_names=(loss.name,), feed_names=("x", "y"),
            mesh=mesh_dp_ep)
        blk = plan.global_block

        def exposed_share(chunks):
            a2a = [e for e in collective_inventory(
                blk, list(blk.ops), mesh=mesh_dp_ep,
                tp_plan=plan._tp_plan, moe_chunks=chunks)
                if e["op"] == "ep_alltoall"]
            return (sum(e["bytes"] for e in a2a if not e["overlap"])
                    / sum(e["bytes"] for e in a2a))

        assert exposed_share(4) < exposed_share(0) == 1.0

    @pytest.mark.slow
    def test_ep_mp_pp_compile_and_ledger_keys(self):
        """The full ep×mp×pp composition compiles and trains (moe
        stage 0, Megatron ffn pair stage 1), and the collective ledger
        prices the dispatch/combine all-to-alls — chunked inventories
        mark overlap=True legs the sequential schedule lacks."""
        import jax

        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.parallel_env import (reset_mesh,
                                                         set_mesh)
        from paddle_tpu.observe.phases import collective_inventory

        reset_mesh()
        devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
        mesh = jax.sharding.Mesh(devs, ("ep", "mp", "pp"))
        set_mesh(mesh)
        try:
            main, startup = Program(), Program()
            main.random_seed = 2
            with unique_name.guard(), program_guard(main, startup):
                x = layers.data("x", [DM])
                y = layers.data("y", [1])
                with device_guard("stage:0"):
                    h, aux, _load = layers.moe_ffn(
                        x, num_experts=E, ffn_dim=FFN, top_k=K,
                        capacity_factor=1.25, name="moe0")
                with device_guard("stage:1"):
                    h2 = layers.fc(h, 2 * DM, act="relu",
                                   name="s1_ffn1")
                    h2 = layers.fc(h2, DM, name="s1_ffn2")
                    pred = layers.fc(h2, 1, name="head")
                    loss = layers.elementwise_add(
                        layers.mean(layers.square_error_cost(pred, y)),
                        layers.scale(aux, 0.01))
                strat = fleet.DistributedStrategy()
                strat.expert_parallel = True
                strat.tensor_parallel = True
                strat.pipeline = True
                strat.pipeline_configs = {"micro_batch": 2}
                fleet.init(is_collective=True, strategy=strat)
                fleet.distributed_optimizer(MomentumOptimizer(0.05, 0.9))
                fleet.minimize(loss)

            from paddle_tpu.monitor import stat_get

            before = stat_get("moe_ep_manual_replicated")
            X, Y = _data(n=8)
            losses, _, _ = _train(main, startup, loss, X, Y, mesh,
                                  steps=2)
            assert all(np.isfinite(v) for v in losses)
            # inside the GPipe shard_map the experts run replicated
            # (GSPMD constraints are illegal under manual axes) and
            # the fallback is COUNTED, not silent
            assert stat_get("moe_ep_manual_replicated") > before

            out = passes_mod.apply_passes(
                main, fetch_names=(loss.name,), feed_names=("x", "y"),
                mesh=mesh)
            assert out._tp_plan.ep_degree == 2

            def a2a(chunks):
                blk = out.global_block
                return [e for e in collective_inventory(
                    blk, list(blk.ops), mesh=mesh,
                    tp_plan=out._tp_plan, moe_chunks=chunks)
                    if e["op"] == "ep_alltoall"]

            seq, chunked = a2a(0), a2a(2)
            assert seq and chunked
            for entry in chunked:
                assert set(entry) >= {"id", "op", "dtype", "bytes",
                                      "overlap"}
            assert not any(e["overlap"] for e in seq)
            assert any(e["overlap"] for e in chunked)
        finally:
            reset_mesh()

    @pytest.mark.slow
    def test_elastic_ckpt_resumes_across_ep_retag(self, tmp_path):
        """ep=2 state saves through the ckpt manager and restores into
        an ep=4 mesh bitwise (single-process: fully-addressable arrays
        snapshot as full host values — elastic by construction); the
        resumed run retags P('ep', ...) at the new degree and keeps
        training."""
        from paddle_tpu.ckpt import CheckpointManager
        from paddle_tpu.distributed.parallel_env import (
            init_parallel_env, reset_mesh, set_mesh)

        X, Y = _data()
        reset_mesh()
        mesh2 = init_parallel_env(mesh_shape=[4, 2],
                                  axis_names=("dp", "ep"))
        try:
            _, scope, _ = _train(*_build_moe(True), X, Y, mesh2,
                                 steps=3)
            m = CheckpointManager(str(tmp_path), async_save=False)
            m.save(3, scope=scope)
            m.close()
            w_before = np.asarray(scope.get_var("moe0.w_1"))
            g_before = np.asarray(scope.get_var("moe0.w_0"))
        finally:
            reset_mesh()

        mesh4 = init_parallel_env(mesh_shape=[2, 4],
                                  axis_names=("dp", "ep"))
        try:
            main, startup, loss = _build_moe(True)
            scope2 = pt.framework.Scope()
            exe = pt.Executor(pt.CPUPlace(), mesh=mesh4)
            exe.run(startup, scope=scope2)
            m2 = CheckpointManager(str(tmp_path), async_save=False)
            meta = m2.restore(scope=scope2)
            m2.close()
            assert meta["step"] == 3
            np.testing.assert_array_equal(
                np.asarray(scope2.get_var("moe0.w_1")), w_before)
            np.testing.assert_array_equal(
                np.asarray(scope2.get_var("moe0.w_0")), g_before)

            out = exe.run(main, feed={"x": X, "y": Y},
                          fetch_list=[loss], scope=scope2)
            exe.drain()
            assert np.isfinite(np.asarray(out[0])).all()
            # the retagged plan physically reshards: 1 expert per chip
            w1 = scope2.get_var("moe0.w_1")
            shard_shapes = {tuple(s.data.shape)
                            for s in w1.addressable_shards}
            assert shard_shapes == {(E // 4, DM, FFN)}
        finally:
            reset_mesh()


# ---------------------------------------------------------------------------
# a held share of the experts at a decode step's rows: the hit form
# (ops/pallas_moe_hit.py), against the dense lines of moe_share_ffn
# ---------------------------------------------------------------------------

HIT_PATTERNS = ("none", "one", "every_other", "all", "last_alone",
                "dead_rows", "zero_weight")


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of ONE lane tile, so a test's widths of two and three
    tiles walk several gate/up blocks and several down blocks an expert
    (as served a block is megabytes: a test's whole width)."""
    from paddle_tpu.ops import pallas_moe_hit as hit

    monkeypatch.setattr(hit, "_GATE_UP_BLOCK", 1)
    monkeypatch.setattr(hit, "_DOWN_BLOCK", 1)
    hit.hit_share_ffn.clear_cache()
    yield hit
    hit.hit_share_ffn.clear_cache()


def _hit_case(pattern, d, f, n_held=6, rows=9):
    """Rows, their weights for the held experts under ``pattern`` and
    the three matrices, float32; ``hit`` is the experts the kernel has
    to read."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops

    k = jax.random.split(jax.random.PRNGKey(48), 6)
    h = jax.random.normal(k[0], (rows, d), jnp.float32)
    w = [jax.random.normal(kk, shape) / np.sqrt(shape[0])
         for kk, shape in zip(k[1:4], ((d, n_held * f), (d, n_held * f),
                                       (n_held * f, d)))]
    weight = jax.random.uniform(k[4], (rows, n_held), minval=0.05,
                                maxval=0.4)
    mask = np.zeros((rows, n_held), bool)
    if pattern == "one":
        mask[[1, 4], 2] = True
    elif pattern == "every_other":
        mask[:, ::2] = np.random.RandomState(1).rand(rows, 3) < 0.5
        mask[0, ::2] = True
    elif pattern == "all":
        mask[:] = True
    elif pattern == "last_alone":
        mask[3, n_held - 1] = True
    elif pattern == "zero_weight":
        # expert 1 is hit by row 0 alone: every other row meets it with
        # a weight of exactly 0
        mask[0, 1] = mask[2, 4] = mask[5, 4] = True
    local = jnp.where(mask, weight, 0.0)
    if pattern == "dead_rows":
        # the router's own weights; the dead rows chose experts no live
        # row chose, and ``live`` takes them out before the form sees them
        router = jax.random.normal(k[5], (d, 16)) / np.sqrt(d)
        route = lambda live: moe_ops.moe_share_route(  # noqa: E731
            h, router, jnp.zeros((16,)), top_k=2,
            held_ids=range(n_held), live=live)[2]
        chosen = np.asarray(route(None)) > 0
        live = np.ones(rows, bool)
        for r in range(rows):       # kill rows until an expert goes dark
            live[r] = False
            if (chosen[live].any(0) != chosen.any(0)).any():
                break
        assert chosen[~live].any() and live.sum() >= 3
        local = route(jnp.asarray(live))
        mask = np.asarray(local) > 0
        assert not mask[~live].any()
        assert mask.any(0).sum() < chosen.any(0).sum()
    return h, local, w, mask.any(0)


@pytest.mark.parametrize("d, f", [(256, 256), (128, 384)])
@pytest.mark.parametrize("pattern", HIT_PATTERNS)
def test_the_hit_form_is_the_dense_form(small_blocks, pattern, d, f):
    """``hit_share_ffn`` (interpreted; two or three blocks a phase an
    expert) against the three matmuls over every row and every held
    expert, to float32's order of summation; it reads exactly the
    experts some row has a non-zero weight for (``n_hit``), none where
    no row chose any (the result is exactly zero), the LAST one alone
    through the clamp, and not those only dead rows chose."""
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops

    h, local, w, hit = _hit_case(pattern, d, f)
    assert small_blocks.hit_blocks(d, f, 4) == (128, 128)
    dense = moe_ops.moe_share_ffn(h, local, *w)
    out, n_hit = small_blocks.hit_share_ffn(h, local, *w, interpret=True)
    assert out.shape == dense.shape and out.dtype == jnp.float32
    assert int(n_hit) == hit.sum() == {
        "none": 0, "one": 1, "every_other": 3, "all": 6, "last_alone": 1,
        "zero_weight": 2}.get(pattern, int(n_hit))
    if pattern == "none":
        assert not np.asarray(out).any()
    scale = float(jnp.abs(dense).max()) or 1.0
    assert float(jnp.abs(out - dense).max()) <= 1e-5 * scale


def test_the_hit_form_counts_its_calls_and_the_experts_it_skipped(
        small_blocks):
    """Through ``moe_share_ffn`` with the model's routing handed in: the
    rule takes the call, ``tally`` gets ``HIT_TALLIES`` and nothing
    else, and skipped + hit = ``n_held`` a call; without ``top_k`` /
    ``num_experts`` the same call keeps the dense lines and counts
    nothing."""
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops

    for pattern in ("none", "every_other", "dead_rows", "all"):
        h, local, w, hit = _hit_case(pattern, 128, 128)
        rows, n_held = local.shape
        assert moe_ops.hit_rule(rows, n_held, 128, 128, 2, 64)
        counted = {}
        out = moe_ops.moe_share_ffn(
            h, local, *w, tally=counted.__setitem__, interpret=True,
            top_k=2, num_experts=64)
        assert tuple(counted) == moe_ops.HIT_TALLIES
        assert counted["moe_hit_form_calls"] == 1
        assert int(counted["moe_experts_skipped"]) + hit.sum() == n_held
        assert int(moe_ops.moe_share_counts(local)[1]) == hit.sum()
        counted.clear()
        dense = moe_ops.moe_share_ffn(h, local, *w,
                                      tally=counted.__setitem__)
        assert not counted
        assert float(jnp.abs(out - dense).max()) <= 1e-5 * max(
            float(jnp.abs(dense).max()), 1.0)
    # no chip and not asked to interpret: the kernel refuses, loudly
    with pytest.raises(ValueError, match="interpret"):
        moe_ops.moe_share_ffn(h, local, *w, top_k=2, num_experts=64)


# (rows of the joint step, n_held, F, D, top_k, num_experts, the
# whole-prompt prefill's buckets) of the four routed cells
ROUTED_CELLS = {
    "kimi_k2_5": (64, 12, 2048, 7168, 8, 384, (8192,)),
    "solar_open2_250b": (128, 40, 1280, 4096, 8, 320, (256, 512, 1024)),
    "mimo_v2_5": (128, 16, 2048, 4096, 8, 256, (512, 1024, 2048)),
    "command_a_plus": (48, 8, 4096, 4096, 8, 128, (4096,)),
}


@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_the_rule_at_the_cells_shapes(cell):
    """Who takes which form, by the call's static shape and the model's
    published routing alone: Kimi-K2.5's step (12 held of 384, 64 rows:
    uniform choices would hit 74 %) takes the hit form; Solar's, MiMo's
    and Command A+'s (96 %, 98 %, 95.5 %) keep the dense form, where
    the kernel's alone-timings lose to it (PERF.md, PR 48); every
    prefill bucket is past the ridge and never asks; no call passes
    both rules; a width no 128 divides keeps the dense form."""
    from paddle_tpu.ops import moe_ops

    rows, n_held, f, d, top_k, n_exp, buckets = ROUTED_CELLS[cell]
    share = moe_ops.expected_hit_share(rows, top_k, n_exp)
    assert share == pytest.approx({
        "kimi_k2_5": 0.740, "solar_open2_250b": 0.961,
        "mimo_v2_5": 0.983, "command_a_plus": 0.955}[cell], abs=1e-3)
    takes = moe_ops.hit_rule(rows, n_held, f, d, top_k, n_exp)
    assert takes == (cell == "kimi_k2_5") == (
        share < moe_ops.HIT_BELOW_SHARE)
    assert not moe_ops.grouped_rule(rows, n_held, f, d)
    for bucket in buckets:
        assert not moe_ops.hit_rule(bucket, n_held, f, d, top_k, n_exp)
        assert moe_ops.grouped_rule(bucket, n_held, f, d) \
            == (bucket >= 512)
    # one row of any of them would; not at a width of broken lanes
    assert moe_ops.hit_rule(1, n_held, f, d, top_k, n_exp)
    assert not moe_ops.hit_rule(1, n_held, f - 64, d, top_k, n_exp)
    assert not moe_ops.hit_rule(1, n_held, f, d - 64, top_k, n_exp)


# -- the grouped form's pair layout (ops/pallas_moe_grouped.py, PR 55) ------

def _layout_until_pr55(local, c, *, tm, m_rows):
    """The layout as ``grouped_share_ffn`` had it until PR 55, kept here
    as the reference: ONE running count over the ``n_held x rows``
    entries of ``local``'s transpose, and two scatters of as many
    updates each, every entry without a pair sent past the buffer's
    end."""
    import jax.numpy as jnp

    rows, n_held = local.shape
    cap, n_tiles = m_rows - n_held * tm, m_rows // tm
    flat = local.T.reshape(-1)
    hit = flat != 0.0
    count = jnp.cumsum(hit, dtype=jnp.int32)
    ends = count[rows - 1::rows]
    starts, pairs = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]]), \
        ends[-1]
    entry = jnp.arange(n_held * rows, dtype=jnp.int32)
    expert, row = entry // rows, entry % rows
    tile_at = jnp.arange(n_tiles, dtype=jnp.int32) * tm
    lo = c * cap
    hi = jnp.minimum(lo + cap, pairs)
    first = jnp.clip(starts, lo, hi)
    size = jnp.clip(ends, lo, hi) - first
    padded = -(-size // tm) * tm
    p_end = jnp.cumsum(padded)
    p_start = p_end - padded
    tile_expert = jnp.minimum(jnp.searchsorted(
        p_end, tile_at, side="right"), n_held - 1).astype(jnp.int32)
    tile_live = jnp.clip(
        size[tile_expert] - (tile_at - p_start[tile_expert]), 0, tm)
    p = count - 1
    slot = jnp.where(hit & (p >= lo) & (p < hi),
                     (p_start - first)[expert] + p, m_rows + entry)
    slot_row = jnp.zeros(m_rows, jnp.int32).at[slot].set(
        row, mode="drop", unique_indices=True)
    slot_weight = jnp.zeros(m_rows, jnp.float32).at[slot].set(
        flat.astype(jnp.float32), mode="drop", unique_indices=True)
    n_active = (p_end[-1] // tm).reshape(1)
    return slot_row, slot_weight, tile_expert, tile_live, n_active


# rows, held experts, the router's top_k of num_experts
LAYOUT_SHAPES = [(512, 16, 4, 16), (512, 8, 8, 128), (256, 12, 8, 384),
                 (384, 20, 3, 20)]
LAYOUT_CASES = ["uniform", "skewed", "dead_rows", "fewer_choices",
                "top_k_none", "two_passes", "three_passes", "one_expert",
                "no_pair"]


def _layout_case(case, rows, n_held, top_k, n_exp):
    """``(local [rows, n_held], the top_k / num_experts the call is
    handed, the passes it must take or None)``."""
    rs = np.random.RandomState(len(case) * 1000 + rows + n_held)
    routing = (top_k, n_exp)
    if case in ("two_passes", "three_passes"):
        # every row on 3 (5) held experts where the buffer holds 2 a row
        each = 3 if case == "two_passes" else 5
        if n_held == n_exp:             # all held: the share's buffer
            routing = (None, None)
        local = np.zeros((rows, n_held), np.float32)
        for r in range(rows):
            local[r, rs.choice(n_held, each, replace=False)] = \
                rs.uniform(0.05, 0.4, each)
        return local, routing, 2 if case == "two_passes" else 3
    if case == "one_expert":
        local = np.zeros((rows, n_held), np.float32)
        local[:, 2] = 0.4
        return local, routing, 1
    if case == "no_pair":
        return np.zeros((rows, n_held), np.float32), routing, 0
    scores = rs.randn(rows, n_exp).astype(np.float32)
    if case == "skewed":                # most rows on experts 0, 1 and 5
        scores[:, [0, 1, 5]] += (4.0, 3.0, 2.0)
    ids = np.argsort(-scores, axis=1)[:, :top_k]
    w = _softmax_np(np.take_along_axis(scores, ids, axis=1))
    full = np.zeros((rows, n_exp), np.float32)
    np.put_along_axis(full, ids, w, axis=1)
    local = full[:, :n_held]
    if case == "dead_rows":
        local[int(0.6 * rows):] = 0.0
    elif case == "fewer_choices":       # half the choices are not held
        local[rs.rand(rows, n_held) < 0.5] = 0.0
    elif case == "top_k_none":
        routing = (None, None)
    return local, routing, None


@pytest.mark.parametrize("shape", LAYOUT_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_the_pair_layout_is_the_one_it_was(case, shape):
    """The five arrays the grouped kernels are fed (``slot_row``,
    ``slot_weight``, ``tile_expert``, ``tile_live``, ``n_active``), from
    the candidates alone (a row's ``min(top_k, n_held)`` entries at
    most, one scatter), against the layout over every entry: equal,
    element for element, in every pass; and the gauge says how many
    updates the layout's scatter walks."""
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops import pallas_moe_grouped as grouped

    rows, n_held, top_k, n_exp = shape
    local, (top_k, n_exp), passes = _layout_case(case, *shape)
    tm = grouped.default_tiles(rows, n_held, top_k, n_exp)
    m_rows = grouped.sorted_rows(rows, n_held, top_k, n_exp)
    cap = m_rows - n_held * tm
    pairs = int((local != 0).sum())
    if passes is not None:
        assert -(-pairs // cap) == passes
    cand = grouped.layout_candidates(jnp.asarray(local), top_k)
    assert stat_get("moe_grouped_layout_updates") == rows * (
        n_held if top_k is None else min(top_k, n_held))
    assert int(cand.ends[-1]) == pairs
    for c in range(max(-(-pairs // cap), 1)):
        want = _layout_until_pr55(jnp.asarray(local), c, tm=tm,
                                  m_rows=m_rows)
        got = grouped.layout_pass(cand, c, tm=tm, m_rows=m_rows)
        for name, x, y in zip(("slot_row", "slot_weight", "tile_expert",
                               "tile_live", "n_active"), got, want):
            assert x.dtype == y.dtype and x.shape == y.shape, name
            np.testing.assert_array_equal(
                np.asarray(x).view(np.int32), np.asarray(y).view(np.int32),
                err_msg=f"{name}, pass {c}")


@pytest.mark.parametrize("case, shape", [
    ("dead_rows", (512, 16, 4, 16)), ("two_passes", (256, 12, 8, 384)),
    ("top_k_none", (512, 8, 8, 128))])
def test_the_grouped_form_is_bitwise_what_the_old_layout_gave(case, shape):
    """``grouped_share_ffn`` (the kernels interpreted) against the same
    two kernels fed the layout as it was until PR 55, pass by pass: the
    same float32 bits in every row."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_moe_grouped as grouped

    rows, n_held, _, _ = shape
    local, (top_k, n_exp), _ = _layout_case(case, *shape)
    d, f = 32, 16
    k = jax.random.split(jax.random.PRNGKey(55), 4)
    h = jax.random.normal(k[0], (rows, d), jnp.float32)
    w_gate, w_up, w_down = [
        jax.random.normal(kk, s) / np.sqrt(s[0]) for kk, s in zip(
            k[1:], ((d, n_held * f), (d, n_held * f), (n_held * f, d)))]
    out, pairs, passes = grouped.grouped_share_ffn(
        h, jnp.asarray(local), w_gate, w_up, w_down, interpret=True,
        top_k=top_k, num_experts=n_exp)
    tm = grouped.default_tiles(rows, n_held, top_k, n_exp)
    m_rows = grouped.sorted_rows(rows, n_held, top_k, n_exp)
    want = jnp.zeros((rows, d), jnp.float32)
    for c in range(int(passes)):
        slot_row, slot_weight, tile_expert, tile_live, n_active = \
            _layout_until_pr55(jnp.asarray(local), c, tm=tm, m_rows=m_rows)
        act = grouped._gate_up_call(
            h[slot_row], slot_weight[:, None], tile_expert, n_active,
            w_gate, w_up, n_held=n_held, tm=tm, interpret=True)
        want = grouped._down_call(
            act, want, tile_expert, n_active, slot_row, tile_live, w_down,
            tm=tm, interpret=True)
    assert int(pairs) == int((local != 0).sum()) and int(passes) >= 1
    np.testing.assert_array_equal(
        np.asarray(out).view(np.int32), np.asarray(want).view(np.int32))
    assert np.asarray(out).any()
