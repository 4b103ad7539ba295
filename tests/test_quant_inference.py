"""Quantized inference (PR 13): int8/fp8 weight-only serving
(slim.PostTrainingWeightQuantPass + ops/quant_ops.dequant_matmul) and
the quantized paged KV cache (serving/kv_cache.py int8 pages +
per-page scale planes).

The load-bearing invariants:

- WEIGHT quant is a graph pass: flag-gated, cache-re-keyed, carriers +
  per-channel scales in scope, the f32 weight dropped from the
  executable's arguments; composes with LayerScanPass (stacked int8
  carriers), the AMP cast path, and the TP sharding plan.
- KV quant stores WRITE-ONCE bytes (per-position per-head scales), so
  every composition path — prefix hit, CoW, chunked prefill,
  speculative decode — is BITWISE-identical to the plain quantized
  run, and the quality tax vs the full-precision oracle is bounded and
  measured (quant_quality_delta), never assumed.
- Scales are clamped PER SLICE: an all-zero channel/head dequantizes
  to exact zeros instead of dividing by ~0 (the _abs_max bugfix).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.monitor import stat_get
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine, \
    TransformerLM

VOCAB = 61


@pytest.fixture(scope="module")
def model_and_weights():
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    weights = model.init_weights(jax.random.PRNGKey(7))
    return model, weights


def make_engine(model_and_weights, draft=None, **cfg_kw):
    model, weights = model_and_weights
    kw = dict(slots=2, max_seq_len=64, page_size=8, max_new_tokens=8,
              kv_quant=True)
    kw.update(cfg_kw)
    dm, dw = draft if draft is not None else (None, None)
    return DecodeEngine(model, weights, DecodeConfig(**kw),
                        draft_model=dm, draft_weights=dw)


# -- scale clamping: the per-slice bugfix ---------------------------------


def test_scale_clamp_is_per_slice_not_global():
    """An all-zero output channel (weight) or head (KV) must get a
    CLAMPED scale of its own — dequantizing to exact zeros — while its
    non-zero neighbors keep real scales.  A global-max clamp would
    leave the zero slice's scale at ~0 and the new per-page path would
    divide by it."""
    import jax.numpy as jnp

    from paddle_tpu.ops.quant_ops import (SCALE_EPS, dequantize_weight,
                                          quantize_weight)
    from paddle_tpu.serving.kv_cache import dequantize_kv, quantize_kv

    rs = np.random.RandomState(0)
    w = rs.randn(16, 8).astype("f4")
    w[:, 3] = 0.0
    q, s = quantize_weight(w, 1, "int8")
    assert np.isfinite(np.asarray(s)).all()
    assert np.asarray(s)[3] == np.float32(SCALE_EPS)
    assert np.asarray(s)[2] > 1e-4  # neighbor keeps its real scale
    wd = np.asarray(dequantize_weight(q, s, 1))
    assert np.all(wd[:, 3] == 0.0) and np.isfinite(wd).all()

    kv = rs.randn(4, 2, 8).astype("f4")
    kv[1, 0] = 0.0  # one all-zero (position, head) slice
    qk, sk = quantize_kv(jnp.asarray(kv))
    sk = np.asarray(sk)
    assert np.isfinite(sk).all() and (sk > 0).all()
    assert sk[1, 0] == np.float32(SCALE_EPS)
    back = np.asarray(dequantize_kv(qk, jnp.asarray(sk), jnp.float32))
    assert np.all(back[1, 0] == 0.0) and np.isfinite(back).all()


# -- dequant_matmul op ----------------------------------------------------


def test_dequant_matmul_reference_accuracy_and_pallas_interpret():
    import jax.numpy as jnp

    from paddle_tpu.ops.quant_ops import dequant_matmul, quantize_weight

    rs = np.random.RandomState(1)
    x = rs.randn(16, 64).astype("f4")
    w = rs.randn(64, 32).astype("f4")
    q, s = quantize_weight(w, 1, "int8")
    ref = x @ w
    out = np.asarray(dequant_matmul(jnp.asarray(x), q, s,
                                    use_pallas="never"))
    assert np.abs(out - ref).max() / np.abs(ref).max() < 0.02
    pal = np.asarray(dequant_matmul(jnp.asarray(x), q, s,
                                    use_pallas="always", interpret=True))
    np.testing.assert_allclose(pal, out, rtol=1e-5, atol=1e-5)


def test_fp8_mode_quantizes():
    from paddle_tpu.ops.quant_ops import (dequantize_weight,
                                          quantize_weight,
                                          resolve_quant_mode)

    rs = np.random.RandomState(2)
    w = rs.randn(32, 16).astype("f4")
    mode = resolve_quant_mode("fp8_e4m3")
    q, s = quantize_weight(w, 1, "fp8_e4m3")
    assert mode == "fp8_e4m3"
    assert "float8_e4m3" in str(q.dtype)
    err = np.abs(np.asarray(dequantize_weight(q, s, 1)) - w).max()
    assert err < 0.2  # fp8 e4m3: ~2 mantissa bits
    with pytest.raises(ValueError, match="unknown weight-quant mode"):
        resolve_quant_mode("int4")


# -- PostTrainingWeightQuantPass ------------------------------------------


def _fc_program(depth=2, width=16, seed=3):
    from paddle_tpu import layers
    from paddle_tpu.framework.program import Program, program_guard

    main, startup = Program(), Program()
    main.random_seed = seed
    with program_guard(main, startup):
        x = layers.data("x", [width])
        h = x
        for _ in range(depth):
            h = layers.fc(h, width, act="relu")
    return main, startup, h


def test_weight_quant_pass_flag_gated_end_to_end():
    """FLAGS_weight_quant rewrites matmul-family ops to dequant_matmul
    with int8 carriers + per-channel scales in scope; output stays
    close; flipping the flag back re-keys the cache and reproduces the
    float path BITWISE."""
    main, startup, h = _fc_program()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.random.RandomState(0).randn(4, 16).astype("f4")}
    base = np.asarray(exe.run(main, feed=feed, fetch_list=[h],
                              scope=scope)[0])
    n0 = stat_get("pass_weight_quant_ops")
    pt.set_flags({"FLAGS_weight_quant": "int8"})
    try:
        q = np.asarray(exe.run(main, feed=feed, fetch_list=[h],
                               scope=scope)[0])
    finally:
        pt.set_flags({"FLAGS_weight_quant": ""})
    assert stat_get("pass_weight_quant_ops") - n0 == 2
    assert scope.has_var("fc_0.w_0@WQ")
    assert scope.has_var("fc_0.w_0@WQ_SCALE")
    assert np.asarray(scope.get_var("fc_0.w_0@WQ")).dtype == np.int8
    assert np.abs(q - base).max() < 0.05 * max(np.abs(base).max(), 1.0)
    back = np.asarray(exe.run(main, feed=feed, fetch_list=[h],
                              scope=scope)[0])
    assert np.array_equal(back, base)


def test_weight_quant_mark_per_program_without_flag():
    from paddle_tpu.slim import mark_weight_quant

    main, startup, h = _fc_program(depth=1, seed=4)
    mark_weight_quant(main, "int8")
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 16), "f4")}
    out = np.asarray(exe.run(main, feed=feed, fetch_list=[h],
                             scope=scope)[0])
    assert scope.has_var("fc_0.w_0@WQ")
    assert np.isfinite(out).all()
    with pytest.raises(ValueError, match="unknown weight-quant mode"):
        mark_weight_quant(main, "int3")


def test_weight_quant_resolves_through_amp_cast():
    """A weight consumed through an AMP-style cast is quantized at the
    source: the dequant lands at X's dtype and the orphaned cast is
    removed by DCE — the executable takes neither the f32 weight nor
    the cast output."""
    from paddle_tpu.framework import dtypes
    from paddle_tpu.framework.program import (Operator, Program,
                                              program_guard)
    from paddle_tpu import layers

    main, startup = Program(), Program()
    main.random_seed = 5
    with program_guard(main, startup):
        x = layers.data("x", [8])
        h = layers.fc(x, 8, bias_attr=False)
    block = main.global_block
    (op,) = [o for o in block.ops if o.type == "mul"]
    wname = op.input("Y")[0]
    cast_out = block.create_var(name=wname + ".cast", dtype="float32",
                                stop_gradient=False)
    block.ops.insert(
        block.ops.index(op),
        Operator(block, "cast", {"X": [wname]},
                 {"Out": [cast_out.name]},
                 {"out_dtype": dtypes.to_enum("float32")}))
    op._rename_input(wname, cast_out.name)
    main._bump()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.random.RandomState(1).randn(4, 8).astype("f4")}
    base = np.asarray(exe.run(main, feed=feed, fetch_list=[h],
                              scope=scope)[0])
    pt.set_flags({"FLAGS_weight_quant": "int8"})
    try:
        q = np.asarray(exe.run(main, feed=feed, fetch_list=[h],
                               scope=scope)[0])
    finally:
        pt.set_flags({"FLAGS_weight_quant": ""})
    assert scope.has_var(wname + "@WQ")
    assert np.abs(q - base).max() < 0.05 * max(np.abs(base).max(), 1.0)


def test_weight_quant_composes_with_layer_scan():
    """Isomorphic quantized layers still scan: the int8 carriers and
    their scales ride ONE stacked array each, and the scanned program
    is bitwise-equal to the unscanned quantized run."""
    main, startup, h = _fc_program(depth=6, width=32, seed=6)
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.random.RandomState(2).randn(4, 32).astype("f4")}
    pt.set_flags({"FLAGS_weight_quant": "int8"})
    try:
        q_only = np.asarray(exe.run(main, feed=feed, fetch_list=[h],
                                    scope=scope)[0])
        pt.set_flags({"FLAGS_layer_scan": 1,
                      "FLAGS_layer_scan_min_layers": 4})
        try:
            q_scan = np.asarray(exe.run(main, feed=feed,
                                        fetch_list=[h], scope=scope)[0])
        finally:
            pt.set_flags({"FLAGS_layer_scan": 0})
    finally:
        pt.set_flags({"FLAGS_weight_quant": ""})
    assert stat_get("pass_layer_scan_segments") >= 1
    carrier = scope.get_var("@LAYER_STACK@fc_0.w_0@WQ")
    assert np.asarray(carrier).dtype == np.int8
    assert np.asarray(carrier).shape[0] == 6
    scale = scope.get_var("@LAYER_STACK@fc_0.w_0@WQ_SCALE")
    assert np.asarray(scale).shape == (6, 32)
    assert np.array_equal(q_scan, q_only)


def test_weight_quant_scale_inherits_tp_spec():
    """With a TPShardingPlan on the program, the carrier inherits the
    weight's spec and the scale inherits the sharded axis' entry."""
    from paddle_tpu.framework.passes import PassContext, TPShardingPlan
    from paddle_tpu.slim import PostTrainingWeightQuantPass

    main, startup, h = _fc_program(depth=1, seed=7)
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    main._tp_plan = TPShardingPlan(
        {"fc_0.w_0": (None, "mp")}, mp_degree=2)
    changed = PostTrainingWeightQuantPass(mode="int8").apply(
        main, PassContext(scope=scope))
    assert changed
    assert main._tp_plan.specs["fc_0.w_0@WQ"] == (None, "mp")
    assert main._tp_plan.specs["fc_0.w_0@WQ_SCALE"] == ("mp",)


# -- quantized KV cache ---------------------------------------------------


def test_kv_quant_cache_bytes_and_capacity_at_fixed_budget():
    """int8 pages + scale planes cost ~half the bf16 bytes, so a fixed
    pool byte budget holds ~2x the pages — and the page-count admission
    reservation turns that directly into slot capacity."""
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.serving.kv_cache import CacheConfig, PagedKVCache

    kw = dict(num_layers=2, num_heads=2, head_dim=32, num_slots=16,
              max_seq_len=64, page_size=8)
    bf16 = CacheConfig(num_pages=13, dtype="bfloat16", **kw)
    qcfg_probe = CacheConfig(num_pages=2, quantized=True, **kw)
    ratio = bf16.page_bytes() / qcfg_probe.page_bytes()
    assert 1.7 <= ratio <= 2.0  # head_dim 32: (2*32)/(32+4) = 1.78
    budget = bf16.cache_bytes()
    q_pages = budget // qcfg_probe.per_page_pool_bytes()
    qcfg = CacheConfig(num_pages=int(q_pages), quantized=True, **kw)
    assert qcfg.cache_bytes() <= budget

    def capacity(cfg):
        cache = PagedKVCache(cfg, Scope(), prefix_cache=False)
        n = 0
        while cache.claim(n, 16) is not None:  # 2 pages per claim
            n += 1
            if n >= cfg.num_slots:
                break
        return n

    cap_bf16 = capacity(bf16)
    cap_q = capacity(qcfg)
    assert cap_q >= 1.7 * cap_bf16, (cap_q, cap_bf16)


def test_kv_quant_decode_bitwise_vs_quantized_self_oracle(
        model_and_weights):
    """Decode-with-quantized-cache logits equal the quantized full
    recompute BITWISE at every step (the PR 10 oracle contract carried
    into the quantized representation), while the delta vs the FULL-
    PRECISION oracle stays small and measured."""
    from paddle_tpu.ops.quant_ops import quant_quality_delta

    eng = make_engine(model_and_weights).start()
    prompt = [1, 2, 3, 4, 5]
    try:
        r = eng.submit(prompt, max_new_tokens=6, record_logits=True)
        out = r.result(timeout=120)
        full, quant = [], []
        for t in range(len(out)):
            seq = prompt + out[:t]
            qo = eng.recompute_logits(seq, quantized=True)
            assert np.array_equal(qo, r.logits_trace[t]), (
                f"quantized cache diverged from its own quantized "
                f"recompute at step {t}")
            full.append(eng.recompute_logits(seq))
            quant.append(r.logits_trace[t])
    finally:
        eng.stop()
    eng._cache.debug_check()
    delta = quant_quality_delta(np.stack(quant), np.stack(full))
    assert delta["max_abs_logit_delta"] < 0.1
    assert delta["top1_agreement"] >= 0.8  # tiny random model
    assert stat_get("quant_quality_top1_agreement_ppm") >= 800000


@pytest.mark.parametrize("path", [
    "prefix_hit", "chunked",
    # the spec leg is the compile-heaviest (two drafted engines); the
    # tier-1 chaos test already cycles spec rounds with kv_quant on,
    # so the bitwise pin rides the slow tier
    pytest.param("spec", marks=pytest.mark.slow)])
def test_kv_quant_composition_matrix_bitwise(model_and_weights, path):
    """The composition matrix: prefix-hit (+CoW), chunked prefill, and
    speculative decode each produce BITWISE the plain quantized run's
    tokens — per-position write-once scales make stored bytes
    order-independent, so no path can drift."""
    model, weights = model_and_weights
    prompt = [3, 1, 4, 1, 5]
    if path == "prefix_hit":
        eng = make_engine(model_and_weights).start()
        try:
            cow0 = stat_get("decode_cow_copies")
            out1 = eng.generate(prompt, max_new_tokens=6)
            out2 = eng.generate(prompt, max_new_tokens=6)
            st = eng.stats()
            assert out2 == out1
            assert stat_get("decode_prefill_skipped") > 0
            assert stat_get("decode_cow_copies") > cow0
        finally:
            eng.stop()
        eng._cache.debug_check()
        # stats + /metrics surface (piggybacked on this engine rather
        # than compiling another)
        assert st["kv_quant"] is True
        assert st["page_bytes"] == eng._cache.config.page_bytes()
        from paddle_tpu.observe.histogram import prometheus_text

        text = prometheus_text()
        for series in ("decode_kv_quant_enabled",
                       "decode_kv_page_bytes"):
            assert series in text, series
        return
    if path == "chunked":
        long_prompt = list(range(1, 28))

        def run(chunk):
            eng = make_engine(model_and_weights, prefix_cache=False,
                              prefill_chunk_pages=chunk).start()
            try:
                return eng.generate(long_prompt, max_new_tokens=5)
            finally:
                eng.stop()

        assert run(1) == run(0)
        return
    import jax

    draft = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=256)
    dw = draft.init_weights(jax.random.PRNGKey(99))

    def run(spec_k):
        eng = make_engine(model_and_weights, prefix_cache=False,
                          spec_k=spec_k, draft=(draft, dw)).start()
        try:
            return eng.generate(prompt, max_new_tokens=10)
        finally:
            eng.stop()

    assert run(4) == run(0)


def test_kv_quant_debug_check_audits_scale_pools():
    """The extended audit, at cache level (no engine/compiles): writes
    stamp live scales, release resets freed planes; a non-finite scale
    or a freed page whose plane kept live values is a loud
    AssertionError."""
    import jax.numpy as jnp

    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.serving.kv_cache import (CacheConfig, K_PAGES_VAR,
                                             K_SCALES_VAR, PagedKVCache,
                                             write_token_layer)

    scope = Scope()
    cache = PagedKVCache(
        CacheConfig(1, 2, 8, num_slots=2, max_seq_len=16, page_size=4,
                    num_pages=6, quantized=True),
        scope, prefix_cache=False)
    assert cache.claim(0, 8) is not None
    # write one position the way a step would (quantize + scale stamp)
    pid, off = cache.write_coords(0)
    val = jnp.ones((1, 2, 8), jnp.float32)
    pages, scales = write_token_layer(
        scope.get_var(K_PAGES_VAR), scope.get_var(K_SCALES_VAR), 0,
        val, jnp.asarray([pid]), jnp.asarray([off]))
    scope.set_var(K_PAGES_VAR, pages)
    scope.set_var(K_SCALES_VAR, scales)
    cache.lengths[0] = 1
    cache.debug_check()  # live page with a live scale: balanced
    cache.release(0)     # frees the page -> its plane resets
    cache.debug_check()
    arr = scope.get_var(K_SCALES_VAR)
    # corrupt a FREE page's scale plane with a live-looking value
    free_pid = cache.allocator._free[0]
    scope.set_var(K_SCALES_VAR, arr.at[0, free_pid, 0, 0].set(0.5))
    with pytest.raises(AssertionError, match="skipped the reset"):
        cache.debug_check()
    scope.set_var(K_SCALES_VAR,
                  arr.at[0, free_pid, 0, 0].set(jnp.nan))
    with pytest.raises(AssertionError, match="non-finite"):
        cache.debug_check()


def test_kv_quant_pallas_interpret_matches_reference():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_decode_attention import (
        paged_chunk_attention, paged_decode_attention)
    from paddle_tpu.serving.kv_cache import quantize_kv

    rs = np.random.RandomState(0)
    s, h, d, pool, page, pps = 3, 2, 16, 9, 8, 4
    # quantized per head, stored as the stacked pools hold it: int8
    # rows [L, P, page, H*D] beside scale planes [L, P, page, H]
    kq, ks = quantize_kv(jnp.asarray(rs.randn(1, pool, page, h, d)
                                     .astype("f4")))
    vq, vs = quantize_kv(jnp.asarray(rs.randn(1, pool, page, h, d)
                                     .astype("f4")))
    kq = kq.reshape(1, pool, page, h * d)
    vq = vq.reshape(1, pool, page, h * d)
    table = jnp.asarray(rs.randint(1, pool, (s, pps)).astype("i4"))
    q = jnp.asarray(rs.randn(s, h, d).astype("f4"))
    lengths = jnp.asarray(np.array([5, 17, 32], "i4"))
    ref = paged_decode_attention(q, kq, vq, table, lengths,
                                 k_scales=ks, v_scales=vs,
                                 use_pallas="never")
    pal = paged_decode_attention(q, kq, vq, table, lengths,
                                 k_scales=ks, v_scales=vs,
                                 use_pallas="always", interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=1e-5, atol=1e-5)
    qr = jnp.asarray(rs.randn(s, 5, h, d).astype("f4"))
    rl = jnp.asarray(np.array([7, 0, 27], "i4")[:, None]
                     + np.arange(1, 6, dtype="i4")[None, :])
    ref = paged_chunk_attention(qr, kq, vq, table, rl, k_scales=ks,
                                v_scales=vs, use_pallas="never")
    pal = paged_chunk_attention(qr, kq, vq, table, rl, k_scales=ks,
                                v_scales=vs, use_pallas="always",
                                interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=1e-5, atol=1e-5)


