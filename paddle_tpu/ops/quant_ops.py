"""Fake-quantization ops (reference operators/fake_quantize_op.cc:739
family: fake_quantize_abs_max / fake_channel_wise_quantize_abs_max /
fake_quantize_moving_average_abs_max / fake_quantize_range_abs_max and
their *_dequantize_* variants, plus fake_dequantize_max_abs and the
moving_average_abs_max_scale observer).

TPU-native design: quant-dequant SIMULATION stays in float — on TPU the
MXU wants bf16, int8 buys no training-time win, so the value of these
ops is scale calibration + bit-exact export parity, not int arithmetic.
The straight-through estimator falls out of the emission
``x + stop_gradient(qdq(x) - x)``: the generic vjp path
(ops/grad_generic.py) then yields pass-through gradients with zero
bespoke backward kernels (the reference maintains FakeQuantDequantGrad
kernels for the same semantics).

**Real int8/fp8 lowering** (the inference half): ``dequant_matmul`` is
the op the PostTrainingWeightQuantPass (slim/quantization.py) rewrites
matmul-family ops into — the weight rides as a compact int8 (or
float8-e4m3) carrier plus per-output-channel scales, and the op
dequantizes at the MXU's doorstep: the pure-jnp reference path is the
CPU/tier-1 default, the Pallas kernel dequantizes weight tiles in VMEM
so the f32/bf16 weight is never materialized in HBM (same dispatch
pattern as ops/pallas_decode_attention.py; interpret-mode equivalence
is pinned in tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.lowering import register_lower
from .common import as_scalar

# the ONE scale clamp, shared by every scale computation.  It must be
# applied to the PER-SLICE maxima (elementwise), never only to a global
# max: an all-zero channel/page otherwise yields a ~0 scale and the
# dequant divides by it (bugfix pinned in tests/test_quant_inference.py)
SCALE_EPS = 1e-8


def _clamp_scale(scale):
    """Clamp scale(s) away from zero — elementwise, so every slice of a
    per-channel/per-page scale tensor is individually protected."""
    return jnp.maximum(scale, SCALE_EPS)


def _qmax(op):
    return 2.0 ** (int(op.attr("bit_length", 8)) - 1) - 1


def _abs_max(x):
    return _clamp_scale(jnp.max(jnp.abs(x)))


def _channel_abs_max(x, axis):
    red = tuple(i for i in range(x.ndim) if i != axis)
    return _clamp_scale(jnp.max(jnp.abs(x), axis=red))


def _quant(x, scale, qmax):
    """Quantize to the integer grid, kept in float (reference outputs
    float tensors holding integer values)."""
    return jnp.clip(jnp.round(x / scale * qmax), -qmax, qmax)


def _qdq_ste(x, scale, qmax):
    """Quant-dequant with straight-through gradient."""
    qdq = _quant(x, scale, qmax) * scale / qmax
    return x + jax.lax.stop_gradient(qdq - x)


@register_lower("fake_quantize_abs_max")
def lower_fake_quantize_abs_max(ctx, op):
    x = ctx.in1(op, "X")
    qmax = _qmax(op)
    scale = _abs_max(x)
    ctx.set_out(op, "Out", _quant(x, scale, qmax))
    ctx.set_out(op, "OutScale", jnp.reshape(scale, (1,)))


@register_lower("fake_quantize_dequantize_abs_max")
def lower_fake_quantize_dequantize_abs_max(ctx, op):
    x = ctx.in1(op, "X")
    qmax = _qmax(op)
    scale = _abs_max(x)
    ctx.set_out(op, "Out", _qdq_ste(x, scale, qmax))
    ctx.set_out(op, "OutScale", jnp.reshape(scale, (1,)))


@register_lower("fake_channel_wise_quantize_abs_max")
def lower_fake_channel_wise_quantize_abs_max(ctx, op):
    x = ctx.in1(op, "X")
    axis = int(op.attr("quant_axis", 0))
    qmax = _qmax(op)
    scale = _channel_abs_max(x, axis)
    bshape = [1] * x.ndim
    bshape[axis] = -1
    ctx.set_out(op, "Out", _quant(x, scale.reshape(bshape), qmax))
    ctx.set_out(op, "OutScale", scale)


@register_lower("fake_channel_wise_quantize_dequantize_abs_max")
def lower_fake_channel_wise_qdq_abs_max(ctx, op):
    x = ctx.in1(op, "X")
    axis = int(op.attr("quant_axis", 0))
    qmax = _qmax(op)
    scale = _channel_abs_max(x, axis)
    bshape = [1] * x.ndim
    bshape[axis] = -1
    ctx.set_out(op, "Out", _qdq_ste(x, scale.reshape(bshape), qmax))
    ctx.set_out(op, "OutScale", scale)


def _moving_average_scale(ctx, op, x):
    """Shared accumulator update (fake_quantize_op.cc FindMovingAverage):
    state = rate*state + 1;  accum = rate*accum + abs_max(x);
    scale = accum / state.  In is_test mode the stored scale is used
    unchanged and no state is written."""
    rate = float(op.attr("moving_rate", 0.9))
    in_scale = as_scalar(ctx.in1(op, "InScale"))
    if op.attr("is_test", False):
        return jnp.maximum(in_scale, 1e-8), None, None
    state = as_scalar(ctx.in1(op, "InState"))
    accum = as_scalar(ctx.in1(op, "InAccum"))
    state = rate * state + 1.0
    accum = rate * accum + _abs_max(x)
    scale = accum / state
    return jnp.maximum(scale, 1e-8), state, accum


def _emit_moving_average_state(ctx, op, scale, state, accum):
    ctx.set_out(op, "OutScale", jnp.reshape(scale, (1,)))
    if state is not None:
        ctx.set_out(op, "OutState", jnp.reshape(state, (1,)))
        ctx.set_out(op, "OutAccum", jnp.reshape(accum, (1,)))


@register_lower("fake_quantize_moving_average_abs_max")
def lower_fake_quantize_moving_average_abs_max(ctx, op):
    x = ctx.in1(op, "X")
    qmax = _qmax(op)
    scale, state, accum = _moving_average_scale(ctx, op, x)
    ctx.set_out(op, "Out", _quant(x, scale, qmax))
    _emit_moving_average_state(ctx, op, scale, state, accum)


@register_lower("fake_quantize_dequantize_moving_average_abs_max")
def lower_fake_qdq_moving_average_abs_max(ctx, op):
    x = ctx.in1(op, "X")
    qmax = _qmax(op)
    scale, state, accum = _moving_average_scale(ctx, op, x)
    ctx.set_out(op, "Out", _qdq_ste(x, scale, qmax))
    _emit_moving_average_state(ctx, op, scale, state, accum)


@register_lower("fake_quantize_range_abs_max")
def lower_fake_quantize_range_abs_max(ctx, op):
    """Windowed running-max scale (fake_quantize_op.cc FindRangeAbsMax):
    a [window_size] ring buffer of per-step abs-maxes; the scale is the
    max over the window.  State rides explicit InScales/Iter slots
    (functional in-out pairs, same var wired to both) instead of the
    reference's in-place mutation."""
    x = ctx.in1(op, "X")
    qmax = _qmax(op)
    if op.attr("is_test", False):
        scale = jnp.maximum(as_scalar(ctx.in1(op, "InScale")), 1e-8)
        ctx.set_out(op, "Out", _quant(x, scale, qmax))
        return
    window = int(op.attr("window_size", 10000))
    cur = _abs_max(x)
    scales = ctx.in1(op, "InScales")
    it = jnp.asarray(as_scalar(ctx.in1(op, "Iter")), jnp.int32)
    if scales is None:  # windowless degenerate form: running max
        prev = as_scalar(ctx.in1(op, "InScale"))
        scale = jnp.maximum(jnp.maximum(prev, cur), 1e-8)
    else:
        scales = scales.at[it % window].set(cur)
        scale = jnp.maximum(jnp.max(scales), 1e-8)
        ctx.set_out(op, "OutScales", scales)
    ctx.set_out(op, "Out", _quant(x, scale, qmax))
    ctx.set_out(op, "OutScale", jnp.reshape(scale, (1,)))
    ctx.set_out(op, "OutIter", jnp.reshape(it + 1, (1,)))


@register_lower("moving_average_abs_max_scale")
def lower_moving_average_abs_max_scale(ctx, op):
    """Observer only: Out = X unchanged, scale state updated (used by
    the reference's OutScaleForTrainingPass)."""
    x = ctx.in1(op, "X")
    scale, state, accum = _moving_average_scale(ctx, op, x)
    if ctx.out_name(op, "Out"):
        ctx.set_out(op, "Out", x)
    _emit_moving_average_state(ctx, op, scale, state, accum)


@register_lower("fake_dequantize_max_abs")
def lower_fake_dequantize_max_abs(ctx, op):
    x = ctx.in1(op, "X")
    scale = as_scalar(ctx.in1(op, "Scale"))
    max_range = float(op.attr("max_range", 127.0))
    ctx.set_out(op, "Out", x * scale / max_range)


@register_lower("fake_channel_wise_dequantize_max_abs")
def lower_fake_channel_wise_dequantize_max_abs(ctx, op):
    x = ctx.in1(op, "X")
    scales = ctx.in_list(op, "Scales")
    axis = int(op.attr("quant_axis", 0))
    bits = op.attr("quant_bits", [8])
    bshape = [1] * x.ndim
    bshape[axis] = -1
    out = x * scales[0].reshape(bshape) / (2.0 ** (int(bits[0]) - 1) - 1)
    if len(scales) > 1:  # second-level (whole-tensor) scale, mul path
        out = out * as_scalar(scales[1]) / (2.0 ** (int(bits[1]) - 1) - 1)
    ctx.set_out(op, "Out", out)


# ---------------------------------------------------------------------------
# real int8/fp8 weight-only lowering (PostTrainingWeightQuantPass)
# ---------------------------------------------------------------------------

INT8_QMAX = 127.0
FP8_E4M3_MAX = 448.0  # largest finite float8_e4m3 magnitude

WEIGHT_QUANT_MODES = ("int8", "fp8_e4m3")


def resolve_quant_mode(mode: str) -> str:
    """Validate a weight-quant mode string."""
    if mode not in WEIGHT_QUANT_MODES:
        raise ValueError(
            f"unknown weight-quant mode {mode!r}; expected one of "
            f"{WEIGHT_QUANT_MODES}")
    return mode


def quantize_weight(w, axis: int, mode: str = "int8"):
    """Post-training weight quantization: ``w`` -> ``(carrier, scale)``
    with per-output-channel step sizes along ``axis`` (the scale is
    clamped PER CHANNEL, so an all-zero channel dequantizes to exact
    zeros instead of dividing by ~0).  ``carrier * scale`` reconstructs
    the weight; int8 carriers hold the rounded grid, fp8 carriers the
    scaled value itself."""
    w = jnp.asarray(w)
    mode = resolve_quant_mode(mode)
    red = tuple(i for i in range(w.ndim) if i != axis)
    qmax = INT8_QMAX if mode == "int8" else FP8_E4M3_MAX
    scale = _clamp_scale(jnp.max(jnp.abs(w), axis=red) / qmax)
    bshape = [1] * w.ndim
    bshape[axis] = -1
    scaled = w / scale.reshape(bshape)
    if mode == "int8":
        q = jnp.clip(jnp.round(scaled), -INT8_QMAX, INT8_QMAX) \
            .astype(jnp.int8)
    else:
        q = jnp.clip(scaled, -FP8_E4M3_MAX, FP8_E4M3_MAX) \
            .astype(jnp.float8_e4m3fn)
    return q, scale.astype(jnp.float32)


def dequantize_weight(q, scale, axis: int, dtype=jnp.float32):
    """Inverse of :func:`quantize_weight` (the reference path — the
    Pallas kernel below does the same per tile in VMEM)."""
    bshape = [1] * q.ndim
    bshape[axis] = -1
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32).reshape(bshape)).astype(dtype)


def quantize_weight_stacked(w, axis: int, mode: str = "int8"):
    """Per-expert variant of :func:`quantize_weight` for stacked
    ``[E, ...]`` MoE weights: the scale keeps BOTH the leading stack
    axis and the output-channel ``axis`` (shape ``[E, out]``), so each
    expert calibrates its own step sizes — a shared scale would let one
    hot expert's outliers crush every other expert's resolution.  The
    ``[E, out]`` layout also shards alongside the carrier: carrier
    ``P('ep', ...)`` pairs with scale ``P('ep', None)``."""
    w = jnp.asarray(w)
    if w.ndim < 2 or axis == 0:
        raise ValueError(
            f"stacked quantization needs a [E, ...] weight with an "
            f"output-channel axis != 0, got shape {w.shape} axis {axis}")
    mode = resolve_quant_mode(mode)
    red = tuple(i for i in range(w.ndim) if i not in (0, axis))
    qmax = INT8_QMAX if mode == "int8" else FP8_E4M3_MAX
    scale = _clamp_scale(jnp.max(jnp.abs(w), axis=red) / qmax)
    bshape = [1] * w.ndim
    bshape[0] = w.shape[0]
    bshape[axis] = w.shape[axis]
    scaled = w / scale.reshape(bshape)
    if mode == "int8":
        q = jnp.clip(jnp.round(scaled), -INT8_QMAX, INT8_QMAX) \
            .astype(jnp.int8)
    else:
        q = jnp.clip(scaled, -FP8_E4M3_MAX, FP8_E4M3_MAX) \
            .astype(jnp.float8_e4m3fn)
    return q, scale.astype(jnp.float32)


def dequantize_weight_stacked(q, scale, axis: int, dtype=jnp.float32):
    """Inverse of :func:`quantize_weight_stacked`."""
    bshape = [1] * q.ndim
    bshape[0] = q.shape[0]
    bshape[axis] = q.shape[axis]
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32).reshape(bshape)).astype(dtype)


def _dequant_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, n_k):
    """One (bm, bn) output tile: accumulate x_tile @ dequant(w_tile)
    over the K grid axis.  The carrier tile is dequantized in VMEM —
    the full-precision weight never exists in HBM."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32) * s_ref[0].astype(jnp.float32)
    acc_scr[...] += jax.lax.dot(x, w,
                                preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _tile(n, whole_up_to, candidates):
    """A legal Mosaic block extent for a dim of size ``n``: the whole
    dim when small, else the largest candidate that divides it."""
    if n <= whole_up_to:
        return n
    return next((c for c in candidates if n % c == 0), None)


def _dequant_tiles(m, k, n):
    """(bm, bk, bn) or None where no aligned tiling covers the shape
    (e.g. a vocab head whose width is not a multiple of 128)."""
    tiles = (_tile(m, 256, (256, 128, 64, 32, 16, 8)),
             _tile(k, 512, (512, 256, 128)),
             _tile(n, 256, (256, 128)))
    return None if None in tiles else tiles


def _dequant_matmul_call(x, qw, scale, out_dtype, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    _, n = qw.shape
    bm, bk, bn = _dequant_tiles(m, k, n)
    grid = (m // bm, n // bn, k // bk)
    kern = functools.partial(_dequant_matmul_kernel, n_k=grid[2])
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            # scale rides as a (1, bn) row so the block stays 2D (lane-
            # aligned) on real Mosaic
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, qw, scale.reshape(1, n))


def dequant_matmul(x, qw, scale, *, use_pallas="auto", interpret=False,
                   out_dtype=None):
    """``x [M, K] @ dequant(qw [K, N], scale [N])`` with the dequant
    fused into the matmul.  ``use_pallas`` dispatch matches
    ``ops/pallas_decode_attention.py``: 'auto' engages the kernel on
    the TPU backend only (tier-1 stays Mosaic-free), 'always' forces it
    (combine with ``interpret=True`` off-TPU), 'never' forces the
    pure-jnp reference.  Shapes the tiling cannot cover fall back to
    the reference (``quant_pallas_fallback_shape``)."""
    out_dtype = out_dtype or x.dtype
    if use_pallas == "auto":
        use_pallas = "always" if jax.default_backend() == "tpu" \
            else "never"
    if use_pallas == "always":
        if _dequant_tiles(*x.shape, qw.shape[1]) is not None:
            return _dequant_matmul_call(x, qw, scale, out_dtype,
                                        interpret)
        from ..monitor import stat_add

        stat_add("quant_pallas_fallback_shape")
    w = qw.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    return jnp.dot(x.astype(jnp.float32), w).astype(out_dtype)


def _prod(t):
    p = 1
    for v in t:
        p *= int(v)
    return p


@register_lower("dequant_matmul")
def lower_dequant_matmul(ctx, op):
    """The weight-quantized matmul family: ``Y`` is the int8/fp8
    carrier, ``Scale`` the per-output-channel step sizes.  The op
    preserves the ORIGINAL op's semantics (``orig_type`` attr: mul's
    flattening dims, matmul's transpose flags); the weight is
    dequantized at ``X``'s dtype so AMP-bypassed casts keep their
    numerics.  The fused Pallas path engages for the plain 2D
    column-scaled case; everything else dequantizes then matmuls (XLA
    fuses the product into the dot on TPU anyway)."""
    x = ctx.in1(op, "X")
    qw = ctx.in1(op, "Y")
    scale = ctx.in1(op, "Scale")
    axis = int(op.attr("weight_axis", 1))
    orig = op.attr("orig_type", "matmul_v2")
    use_pallas = op.attr("use_pallas", "auto")
    fused_ok = (qw.ndim == 2 and axis == 1)
    if orig == "mul":
        xn = int(op.attr("x_num_col_dims", 1))
        yn = int(op.attr("y_num_col_dims", 1))
        xs, ys = x.shape, qw.shape
        x2 = x.reshape((-1, int(_prod(xs[xn:]))))
        out_shape = tuple(xs[:xn]) + tuple(ys[yn:])
        if fused_ok and yn == 1:
            out = dequant_matmul(x2, qw, scale, use_pallas=use_pallas,
                                 out_dtype=x.dtype)
        else:
            w = dequantize_weight(qw, scale, axis, x.dtype)
            out = x2 @ w.reshape((int(_prod(ys[:yn])), -1))
        ctx.set_out(op, "Out", out.reshape(out_shape))
        return
    trans_x = bool(op.attr("transpose_X", op.attr("trans_x", False)))
    trans_y = bool(op.attr("transpose_Y", op.attr("trans_y", False)))
    alpha = float(op.attr("alpha", 1.0))
    if fused_ok and not trans_x and not trans_y and x.ndim == 2:
        out = dequant_matmul(x, qw, scale, use_pallas=use_pallas,
                             out_dtype=x.dtype)
    else:
        w = dequantize_weight(qw, scale, axis, x.dtype)
        if trans_x and x.ndim > 1:
            x = jnp.swapaxes(x, -1, -2)
        if trans_y and w.ndim > 1:
            w = jnp.swapaxes(w, -1, -2)
        out = jnp.matmul(x, w)
    if alpha != 1.0:
        out = out * alpha
    ctx.set_out(op, "Out", out)


def quant_quality_delta(logits_q, logits_ref):
    """The quantization tax, measured: max-abs-logit delta and greedy
    top-1 agreement of quantized logits vs their full-precision oracle
    over a fixed eval batch.  Returns the report dict AND mirrors it
    onto /metrics (``quant_quality_max_abs_logit_delta_micro``,
    ``quant_quality_top1_agreement_ppm``) so the tax is monitored,
    never assumed."""
    import numpy as np

    from ..monitor import stat_set

    q = np.asarray(logits_q, dtype=np.float32)
    ref = np.asarray(logits_ref, dtype=np.float32)
    if q.shape != ref.shape:
        raise ValueError(
            f"logit shapes differ: {q.shape} vs {ref.shape}")
    q2 = q.reshape(-1, q.shape[-1])
    r2 = ref.reshape(-1, ref.shape[-1])
    max_abs = float(np.max(np.abs(q2 - r2))) if q2.size else 0.0
    agree = float(np.mean(np.argmax(q2, axis=-1)
                          == np.argmax(r2, axis=-1))) if len(q2) else 1.0
    stat_set("quant_quality_max_abs_logit_delta_micro",
             int(max_abs * 1e6))
    stat_set("quant_quality_top1_agreement_ppm", int(agree * 1e6))
    return {"max_abs_logit_delta": max_abs, "top1_agreement": agree}
