"""Fused flash-attention training kernels (ISSUE 17).

Three layers under test, oracle-first:

- ops/flash_attention.py — the Pallas online-softmax forward + tiled
  recompute backward behind ONE ``jax.custom_vjp``.  Oracle is the
  pure-jnp masked softmax (``flash_attention_ref``), which stays the
  CPU/tier-1 default; the kernels are pinned to it in interpret mode
  (fwd <= 1e-6, grads ~1e-5 f32).
- framework/passes.py FlashAttentionPass — the graph rewrite of the
  unfused matmul -> [mask add] -> softmax -> matmul chain (plus its
  generic grad chain) into flash_attention/flash_attention_grad.
  Oracle is the unfused program itself: with FLAGS_flash_attention
  'never' (or 'auto' on CPU) nothing moves; under 'always' the
  rewritten program's losses match the unfused run bitwise on the CPU
  reference lowering.
- composition — the rewrite rides tensor parallelism (heads-dim mp
  specs flow through the fused op; losses match the single-chip
  oracle) and LayerScanPass (slow matrix).
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import flags as flags_mod
from paddle_tpu.framework import passes as passes_mod
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.program import Program, program_guard
from paddle_tpu.initializer import NormalInitializer
from paddle_tpu.monitor import stat_get, stat_reset
from paddle_tpu.optimizer import MomentumOptimizer
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.ops import flash_attention as fa


@pytest.fixture(autouse=True)
def _flag_reset():
    yield
    pt.set_flags({"FLAGS_flash_attention": "auto",
                  "FLAGS_layer_scan": False,
                  "FLAGS_hbm_bytes_per_device": 0,
                  "FLAGS_hbm_budget_fraction": 0.0})


def _qkv(rs, B=1, H=2, S=256, D=64):
    return (jnp.asarray(rs.randn(B, H, S, D).astype("f4")),
            jnp.asarray(rs.randn(B, H, S, D).astype("f4")),
            jnp.asarray(rs.randn(B, H, S, D).astype("f4")))


def _mask(rs, kind, B=1, H=2, S=256):
    if kind == "none":
        return None
    if kind == "key":
        keep = rs.rand(B, 1, 1, S) > 0.2
        return jnp.asarray(np.where(keep, 0.0, -1e9).astype("f4"))
    return jnp.asarray(rs.randn(B, H, S, S).astype("f4"))


# -- kernel vs jnp reference (interpret mode) -----------------------------


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mask_kind", ["none", "key", "full"])
def test_forward_parity_vs_ref(causal, mask_kind):
    rs = np.random.RandomState(0)
    q, k, v = _qkv(rs)
    mask = _mask(rs, mask_kind)
    ref = fa.flash_attention_ref(q, k, v, mask, sm_scale=0.125,
                                 causal=causal)
    got = fa.flash_attention(q, k, v, mask, sm_scale=0.125,
                             causal=causal, use_pallas=True,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grad_parity_vs_ref(causal):
    """q/k/v cotangents through the tiled recompute backward match
    jax.vjp over the jnp reference (the custom_vjp's whole contract)."""
    rs = np.random.RandomState(1)
    q, k, v = _qkv(rs)
    mask = _mask(rs, "key")
    ct = jnp.asarray(rs.randn(*q.shape).astype("f4"))

    _, vjp_ref = jax.vjp(
        lambda q, k, v: fa.flash_attention_ref(
            q, k, v, mask, sm_scale=0.125, causal=causal), q, k, v)
    _, vjp_got = jax.vjp(
        lambda q, k, v: fa.flash_attention(
            q, k, v, mask, sm_scale=0.125, causal=causal,
            use_pallas=True, interpret=True), q, k, v)
    for name, r, g in zip("qkv", vjp_ref(ct), vjp_got(ct)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=1e-5, rtol=1e-3,
            err_msg=f"d{name} diverged from the reference vjp")


def test_mask_is_a_constant():
    """The fused op treats the additive mask as a constant: its
    cotangent is exactly zero (the pass refuses learnable masks for
    the same reason)."""
    rs = np.random.RandomState(2)
    q, k, v = _qkv(rs)
    mask = _mask(rs, "key")
    _, vjp = jax.vjp(
        lambda m: fa.flash_attention(q, k, v, m, sm_scale=0.125,
                                     use_pallas=True, interpret=True),
        mask)
    (dm,) = vjp(jnp.ones_like(q))
    assert float(jnp.abs(dm).max()) == 0.0


def test_unaligned_shapes_are_loud():
    rs = np.random.RandomState(3)
    q, k, v = _qkv(rs, S=96)  # not a multiple of the 128 block
    with pytest.raises(ValueError, match="multiples"):
        fa.flash_attention(q, k, v, use_pallas=True)
    with pytest.raises(ValueError, match="rank"):
        fa.flash_attention(q[0], k[0], v[0])


def test_cpu_default_is_the_reference():
    """use_pallas=None off-TPU must resolve to the jnp reference —
    tier-1 numerics never move when the kernels land."""
    rs = np.random.RandomState(4)
    q, k, v = _qkv(rs, S=128)
    mask = _mask(rs, "key", S=128)
    got = fa.flash_attention(q, k, v, mask, sm_scale=0.125)
    ref = fa.flash_attention_ref(q, k, v, mask, sm_scale=0.125)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# -- the FlashAttentionPass graph rewrite ---------------------------------

S, HEADS, D = 16, 2, 8
HID = HEADS * D


def _attn_train_program(with_mask=True, dropout=0.0, learnable_mask=False,
                        seed=11, S=S, D=D):
    """A train program around the exact unfused chain static_models
    emits: qkv projections -> matmul(alpha) -> [mask add] -> softmax ->
    matmul -> out projection -> mse, SGD-with-momentum backward."""
    HID = HEADS * D
    main, startup = Program(), Program()
    main.random_seed = seed
    with program_guard(main, startup):
        x = layers.data("x", [S, HID])
        y = layers.data("y", [S, HID])

        def proj(name, src=None):
            t = layers.fc(src if src is not None else x, HID,
                          num_flatten_dims=2, name=name,
                          param_attr=ParamAttr(
                              initializer=NormalInitializer(0.0, 0.05)))
            t = layers.reshape(t, [0, S, HEADS, D])
            return layers.transpose(t, [0, 2, 1, 3])

        q, k, v = proj("attn_q"), proj("attn_k"), proj("attn_v")
        scores = layers.matmul(q, k, transpose_y=True,
                               alpha=1.0 / math.sqrt(D))
        mask = None
        if learnable_mask:
            m = layers.fc(x, S, num_flatten_dims=2, name="attn_mask")
            mask = layers.reshape(m, [0, 1, S, S])
        elif with_mask:
            mask = layers.data("mask", [1, 1, S])
        if mask is not None:
            scores = layers.elementwise_add(scores, mask)
        probs = layers.softmax(scores)
        if dropout:
            probs = layers.dropout(probs, dropout)
        ctxv = layers.matmul(probs, v)
        ctxv = layers.transpose(ctxv, [0, 2, 1, 3])
        ctxv = layers.reshape(ctxv, [0, S, HID])
        out = layers.fc(ctxv, HID, num_flatten_dims=2, name="attn_out",
                        param_attr=ParamAttr(
                            initializer=NormalInitializer(0.0, 0.05)))
        loss = layers.mean(layers.square_error_cost(out, y))
        MomentumOptimizer(0.05, 0.9).minimize(loss)
    return main, startup, loss, probs.name


def _feed(with_mask=True, n=4, S=S, HID=HID):
    rs = np.random.RandomState(0)
    fd = {"x": rs.randn(n, S, HID).astype("f4"),
          "y": rs.randn(n, S, HID).astype("f4")}
    if with_mask:
        fd["mask"] = np.where(rs.rand(n, 1, 1, S) > 0.2,
                              0.0, -1e9).astype("f4")
    return fd


def _train(main, startup, loss, fd, steps=3, mesh=None):
    scope = pt.framework.Scope()
    exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
    exe.run(startup, scope=scope)
    return [float(np.asarray(exe.run(main, feed=fd, fetch_list=[loss],
                                     scope=scope)[0]).item())
            for _ in range(steps)]


def _op_types(program):
    return [op.type for op in program.global_block.ops]


def _reset_pass_stats():
    stat_reset("pass_flash_attention_fused")
    stat_reset("pass_flash_attention_grad_fused")


@pytest.mark.parametrize("with_mask", [True, False])
def test_pass_rewrites_chain_and_grads(with_mask):
    main, _, loss, _ = _attn_train_program(with_mask=with_mask)
    pt.set_flags({"FLAGS_flash_attention": "always"})
    _reset_pass_stats()
    p = passes_mod.FlashAttentionPass()
    ctx = passes_mod.PassContext(fetch_names=(loss.name,))
    assert p.should_apply(main, ctx)
    assert p.apply(main, ctx)
    types = _op_types(main)
    assert types.count("flash_attention") == 1
    assert types.count("flash_attention_grad") == 1
    for gone in ("softmax", "softmax_grad", "matmul_grad"):
        assert gone not in types, f"{gone} survived the rewrite"
    # the qkv/out projection matmuls (via fc -> mul) must survive
    fop = next(op for op in main.global_block.ops
               if op.type == "flash_attention")
    assert ("Mask" in fop.inputs) == with_mask
    assert abs(float(fop.attr("scale")) - 1.0 / math.sqrt(D)) < 1e-12
    assert stat_get("pass_flash_attention_fused") == 1
    assert stat_get("pass_flash_attention_grad_fused") == 1


def test_flag_gating_and_lowering_rekey():
    """'never' and CPU-'auto' never rewrite (tier-1 numerics are
    untouched by default); the flag is affects_lowering so every flip
    re-keys the executor's pass + compile caches."""
    main, _, loss, _ = _attn_train_program()
    p = passes_mod.FlashAttentionPass()
    ctx = passes_mod.PassContext(fetch_names=(loss.name,))
    pt.set_flags({"FLAGS_flash_attention": "never"})
    key_never = flags_mod.lowering_key()
    assert not p.should_apply(main, ctx)
    pt.set_flags({"FLAGS_flash_attention": "auto"})
    assert jax.default_backend() != "tpu" and not p.should_apply(main, ctx)
    pt.set_flags({"FLAGS_flash_attention": "always"})
    assert p.should_apply(main, ctx)
    assert flags_mod.lowering_key() != key_never


def test_executor_always_matches_never_bitwise():
    """End-to-end oracle: the same attention net trained 4 steps under
    'never' (unfused chain) and 'always' (rewritten to the fused op,
    reference lowering on CPU) produces bitwise-identical losses —
    the rewrite changes memory shape, not math."""
    fd = _feed()
    pt.set_flags({"FLAGS_flash_attention": "never"})
    with unique_name.guard():
        ref = _train(*_attn_train_program()[:3], fd, steps=4)
    _reset_pass_stats()
    pt.set_flags({"FLAGS_flash_attention": "always"})
    with unique_name.guard():
        got = _train(*_attn_train_program()[:3], fd, steps=4)
    assert stat_get("pass_flash_attention_fused") >= 1
    assert stat_get("pass_flash_attention_grad_fused") >= 1
    np.testing.assert_array_equal(ref, got)


def test_rewrite_keeps_the_contractions_flops():
    """hapi/model_stat prices the fused op as the two contractions it
    replaced, forward and backward, so an MFU read before and after the
    rewrite has the same numerator but for the softmax it folds in (one
    operation a score, where the contractions are 4 * D)."""
    from paddle_tpu.hapi.model_stat import program_flops

    main, _, loss, _ = _attn_train_program(with_mask=False)
    total0, before = program_flops(main, detail=True)
    pt.set_flags({"FLAGS_flash_attention": "always"})
    assert passes_mod.FlashAttentionPass().apply(
        main, passes_mod.PassContext(fetch_names=(loss.name,)))
    total1, after = program_flops(main, detail=True)
    assert after["flash_attention"] == before["matmul"] > 0
    assert after["flash_attention_grad"] == before["matmul_grad"] > 0
    assert total0 - total1 == before["softmax"]


def test_budget_gate_refuses_the_unfused_chain_and_passes_the_fused(
        monkeypatch):
    """The memory claim as a refusal: at 1,024 keys, with the device's
    capacity pinned to 0.6 of what the unfused step needs, the unfused
    compile raises MemoryBudgetError before any dispatch and the
    rewritten one (the kernels, in interpret mode) compiles and runs."""
    from paddle_tpu.observe.xla_stats import MemoryBudgetError
    from paddle_tpu.ops import fused

    seq, d = 1024, 64
    fd = _feed(with_mask=False, n=1, S=seq, HID=HEADS * d)

    def step(mode, capacity=0):
        pt.set_flags({"FLAGS_flash_attention": mode,
                      "FLAGS_hbm_bytes_per_device": capacity,
                      "FLAGS_hbm_budget_fraction": 1.0 if capacity else 0.0})
        with unique_name.guard():
            main, startup, loss, _ = _attn_train_program(
                with_mask=False, S=seq, D=d)
        out = _train(main, startup, loss, fd, steps=1)
        return out, stat_get("hbm_required_bytes")

    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    ref, unfused_bytes = step("never")
    if not unfused_bytes:
        pytest.skip("this jax gives no memory_analysis")
    capacity = int(0.6 * unfused_bytes)
    with pytest.raises(MemoryBudgetError):
        step("never", capacity)
    engaged0 = stat_get("flash_attention_engaged")
    got, fused_bytes = step("always", capacity)
    assert stat_get("flash_attention_engaged") > engaged0
    assert fused_bytes <= capacity
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-4)


def test_pass_refuses_dropout_on_probs():
    """Dropout on the attention probs consumes the softmax output, so
    the chain must be left alone (the flash trade-off is no probs
    dropout — silently dropping it would change the model)."""
    main, _, loss, _ = _attn_train_program(dropout=0.3)
    pt.set_flags({"FLAGS_flash_attention": "always"})
    assert not passes_mod.FlashAttentionPass().apply(
        main, passes_mod.PassContext(fetch_names=(loss.name,)))
    assert "softmax" in _op_types(main)


def test_pass_refuses_fetched_intermediate():
    main, _, loss, probs_name = _attn_train_program()
    pt.set_flags({"FLAGS_flash_attention": "always"})
    assert not passes_mod.FlashAttentionPass().apply(
        main, passes_mod.PassContext(
            fetch_names=(loss.name, probs_name)))
    assert "softmax" in _op_types(main)


def test_pass_refuses_learnable_mask():
    """A mask that wants gradients can't ride the fused op (it treats
    the mask as a constant): the grad chain's Y@GRAD on the add is the
    refusal signal."""
    main, _, loss, _ = _attn_train_program(learnable_mask=True)
    pt.set_flags({"FLAGS_flash_attention": "always"})
    assert not passes_mod.FlashAttentionPass().apply(
        main, passes_mod.PassContext(fetch_names=(loss.name,)))
    assert "softmax" in _op_types(main)


# -- composition: tensor parallelism & layer scan (slow matrix) -----------

TP_RULES = [(r"attn_[qkv]\.w_\d+$", "None,mp"),
            (r"attn_[qkv]\.b_\d+$", "mp"),
            (r"attn_out\.w_\d+$", "mp,None")]


def _tp_program(seed=5):
    from paddle_tpu.distributed import fleet

    main, startup = Program(), Program()
    main.random_seed = seed
    with unique_name.guard(), program_guard(main, startup):
        x = layers.data("x", [S, HID])
        y = layers.data("y", [S, HID])
        mask = layers.data("mask", [1, 1, S])

        def proj(name):
            t = layers.fc(x, HID, num_flatten_dims=2, name=name,
                          param_attr=ParamAttr(
                              initializer=NormalInitializer(0.0, 0.05)))
            t = layers.reshape(t, [0, S, HEADS, D])
            return layers.transpose(t, [0, 2, 1, 3])

        q, k, v = proj("attn_q"), proj("attn_k"), proj("attn_v")
        scores = layers.matmul(q, k, transpose_y=True,
                               alpha=1.0 / math.sqrt(D))
        scores = layers.elementwise_add(scores, mask)
        probs = layers.softmax(scores)
        ctxv = layers.matmul(probs, v)
        ctxv = layers.transpose(ctxv, [0, 2, 1, 3])
        ctxv = layers.reshape(ctxv, [0, S, HID])
        out = layers.fc(ctxv, HID, num_flatten_dims=2, name="attn_out",
                        param_attr=ParamAttr(
                            initializer=NormalInitializer(0.0, 0.05)))
        loss = layers.mean(layers.square_error_cost(out, y))
        opt = MomentumOptimizer(0.05, 0.9)
        st = fleet.DistributedStrategy()
        st.tensor_parallel = True
        st.tensor_parallel_configs = {"partition_rules": TP_RULES}
        fleet.init(is_collective=True, strategy=st)
        fleet.distributed_optimizer(opt)
        fleet.minimize(loss)
    return main, startup, loss


@pytest.mark.slow
def test_tp_composition_heads_sharded(mesh_dp_mp):
    """Megatron column-parallel qkv shards the fused op's heads dim:
    under the 2x4 dp×mp mesh with FLAGS_flash_attention=always the
    rewrite fires, the mp-flow walk accepts the fused op, and losses
    match the tp run of the UNFUSED chain bitwise (same mesh, same
    math) — which itself sits on the single-chip oracle."""
    fd = _feed()
    pt.set_flags({"FLAGS_flash_attention": "never"})
    plain = _train(*_tp_program(), fd, steps=4, mesh=mesh_dp_mp)
    _reset_pass_stats()
    pt.set_flags({"FLAGS_flash_attention": "always"})
    fused = _train(*_tp_program(), fd, steps=4, mesh=mesh_dp_mp)
    assert stat_get("pass_flash_attention_fused") >= 1
    np.testing.assert_array_equal(plain, fused)


@pytest.mark.slow
def test_layer_scan_composition():
    """FlashAttentionPass runs before LayerScanPass, so the scanned
    layer body already holds the fused op: a 3-deep attention stack
    scanned+fused must match the unscanned unfused oracle bitwise."""
    depth = 3

    def build():
        main, startup = Program(), Program()
        main.random_seed = 13
        with unique_name.guard(), program_guard(main, startup):
            x = layers.data("x", [S, HID])
            y = layers.data("y", [S, HID])
            mask = layers.data("mask", [1, 1, S])
            h = x
            for i in range(depth):
                def proj(name, src):
                    t = layers.fc(src, HID, num_flatten_dims=2,
                                  name=name, param_attr=ParamAttr(
                                      initializer=NormalInitializer(
                                          0.0, 0.05)))
                    t = layers.reshape(t, [0, S, HEADS, D])
                    return layers.transpose(t, [0, 2, 1, 3])

                q = proj(f"blk{i}_q", h)
                k = proj(f"blk{i}_k", h)
                v = proj(f"blk{i}_v", h)
                scores = layers.matmul(q, k, transpose_y=True,
                                       alpha=1.0 / math.sqrt(D))
                scores = layers.elementwise_add(scores, mask)
                probs = layers.softmax(scores)
                ctxv = layers.matmul(probs, v)
                ctxv = layers.transpose(ctxv, [0, 2, 1, 3])
                ctxv = layers.reshape(ctxv, [0, S, HID])
                h = layers.fc(ctxv, HID, num_flatten_dims=2,
                              name=f"blk{i}_out", param_attr=ParamAttr(
                                  initializer=NormalInitializer(
                                      0.0, 0.05)))
            loss = layers.mean(layers.square_error_cost(h, y))
            MomentumOptimizer(0.05, 0.9).minimize(loss)
        return main, startup, loss

    fd = _feed()
    pt.set_flags({"FLAGS_flash_attention": "never",
                  "FLAGS_layer_scan": False})
    ref = _train(*build(), fd, steps=4)

    _reset_pass_stats()
    stat_reset("pass_layer_scan_segments")
    pt.set_flags({"FLAGS_flash_attention": "always",
                  "FLAGS_layer_scan": True,
                  "FLAGS_layer_scan_min_layers": 2})
    try:
        got = _train(*build(), fd, steps=4)
    finally:
        pt.set_flags({"FLAGS_layer_scan": False,
                      "FLAGS_layer_scan_min_layers": 4})
    assert stat_get("pass_flash_attention_fused") >= depth
    np.testing.assert_array_equal(ref, got)
