"""Fused flash-attention training op: Pallas fwd/bwd as one custom_vjp.

Role parity: reference operators/fused/multihead_matmul_op.cu plus the
training-side attention chain dist_transformer.py emits (matmul ->
mask-add -> softmax -> matmul).  The serving stack already runs Pallas
paged attention (ops/pallas_decode_attention.py); this module gives the
TRAINING graph the same treatment, as one graph-rewritable op that the
pass machinery anchors (framework/passes.py FlashAttentionPass).

Memory shape, which is the whole point (PR 8 telemetry shows training
attention materializing the [B,H,Sq,Sk] fp32 score tensor in both fwd
and bwd — O(N^2) HBM at the flagship seq lens):

- forward: classic tiled online-softmax — one (BQ,BK) score tile in
  VMEM at a time, running per-row max ``m`` and denominator ``l`` in
  scratch; what survives to HBM is the output plus one (Sq,)-sized
  logsumexp vector per (batch, head) — O(N).
- backward: RECOMPUTES the attention tile-by-tile from (q, k, v, lse)
  instead of saving probabilities.  Two kernels, each accumulating its
  result block in VMEM across the innermost grid axis:
    * dq kernel, grid (B*H, n_q, n_k): k-blocks stream past a resident
      dq accumulator;
    * dk/dv kernel, grid (B*H, n_k, n_q): q-blocks stream past
      resident dk/dv accumulators.
  ``delta = rowsum(do * o)`` is precomputed in plain jnp (one O(N*D)
  pass), matching the standard flash-attention backward split.

The pure-jnp masked-softmax reference (``flash_attention_ref``) is the
CPU/tier-1 default — numerically the same composition the unfused op
chain lowers to, so the FlashAttentionPass rewrite preserves loss to
fp32 roundoff on CPU; the Pallas path is pinned against it in
interpret mode (tests/test_flash_attention.py), per the established
kernel pattern (PR 10/11/13).  The additive mask is a CONSTANT
(padding/causal -1e9 masks): its cotangent is zero, and the graph pass
refuses to fuse chains whose mask wants gradients.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.lowering import register_lower

_NEG_INF = -1e30
_LANES = 128


# ---------------------------------------------------------------------------
# reference (CPU/tier-1 default; the rewrite's numerical oracle)
# ---------------------------------------------------------------------------


def flash_attention_ref(q, k, v, mask=None, *, sm_scale, causal=False):
    """Plain masked-softmax attention over (B, H, S, D): exactly the
    composition the unfused matmul/add/softmax/matmul chain lowers to,
    so a pass rewrite to this path is loss-parity-safe on CPU."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    if mask is not None:
        s = s + mask.astype(s.dtype)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(cm[None, None], s, jnp.asarray(_NEG_INF, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# ---------------------------------------------------------------------------
# Pallas forward: online softmax, saves (out, lse)
# ---------------------------------------------------------------------------


def _bias_spec(bias, h, block_q, block_k, *, q_axis, k_axis):
    """(mode, BlockSpec) for the additive mask in its natural 4-D shape
    — broadcast dims map to block 0 so HBM traffic stays at the mask's
    true size.  ``q_axis``/``k_axis`` say which grid position carries
    the q-block / k-block index (fwd+dq iterate (bh, qb, kb); the dk/dv
    kernel iterates (bh, kb, qb))."""
    import jax.experimental.pallas as pl

    if bias is None:
        return "none", pl.BlockSpec((1, 1, 1, 1), lambda *_: (0, 0, 0, 0))
    bb, bh_, bq, _bk = bias.shape

    def idx(*g):
        b = 0 if bb == 1 else g[0] // h
        hh = 0 if bh_ == 1 else g[0] % h
        return (b, hh, 0 if bq == 1 else g[q_axis], g[k_axis])

    if bq == 1:  # key mask: one row broadcast over all queries
        return "key", pl.BlockSpec((1, 1, 1, block_k), idx)
    return "full", pl.BlockSpec((1, 1, block_q, block_k), idx)


def _causal_run(qb, kb, block_q, block_k):
    return (kb * block_k) <= (qb * block_q + block_q - 1)


def _tile_scores(q, k, bias_ref, bias_mode, qb, kb, sm_scale, causal,
                 block_q, block_k):
    """One (BQ, BK) score tile: qk^T * scale + mask (+ causal)."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    if bias_mode == "key":
        s = s + bias_ref[0, 0, 0].astype(jnp.float32)[None, :]
    elif bias_mode == "full":
        s = s + bias_ref[0, 0].astype(jnp.float32)
    if causal:
        rows = qb * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kb * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_scr,
                l_scr, acc_scr, *, sm_scale, causal, block_q, block_k,
                n_k, bias_mode):
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = _causal_run(qb, kb, block_q, block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = _tile_scores(q, k, bias_ref, bias_mode, qb, kb, sm_scale,
                         causal, block_q, block_k)
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kb == n_k - 1)
    def _flush():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)
        # per-row softmax statistic the backward recompute needs:
        # lse = m + log(l); fully-masked rows pin to -inf
        lse_ref[0] = jnp.where(l == 0.0, _NEG_INF,
                               m_scr[:, :1] + jnp.log(safe))


def _fwd_call(q, k, v, bias, sm_scale, causal, block_q, block_k,
              interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    n_q, n_k = sq // block_q, sk // block_k
    bias_mode, bias_spec = _bias_spec(bias, h, block_q, block_k,
                                      q_axis=1, k_axis=2)
    bias_arr = bias if bias is not None else jnp.zeros((1, 1, 1, 1),
                                                       q.dtype)
    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k, bias_mode=bias_mode)
    out, lse = pl.pallas_call(
        kern,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qb, kb: (bh, qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qb, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qb, kb: (bh, kb, 0)),
            bias_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qb, kb: (bh, qb, 0)),
            # row stats keep a trailing unit dim: a (1, block_q) block of
            # a (BH, Sq) array is not a legal Mosaic tile (last two block
            # dims must divide by (8, 128) or equal the array's)
            pl.BlockSpec((1, block_q, 1), lambda bh, qb, kb: (bh, qb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),       # output acc
        ],
        interpret=interpret,
    )(q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
      v.reshape(b * h, sk, d), bias_arr)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# ---------------------------------------------------------------------------
# Pallas backward: per-tile recompute from (q, k, v, lse, delta)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, sm_scale, causal,
                   block_q, block_k, n_k, bias_mode):
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = _causal_run(qb, kb, block_q, block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = _tile_scores(q, k, bias_ref, bias_mode, qb, kb, sm_scale,
                         causal, block_q, block_k)
        p = jnp.exp(s - lse_ref[0])                      # (BQ, BK)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * sm_scale
        dq_scr[...] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_k - 1)
    def _flush():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale, causal, block_q, block_k, n_q, bias_mode):
    import jax.experimental.pallas as pl

    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = _causal_run(qb, kb, block_q, block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = _tile_scores(q, k, bias_ref, bias_mode, qb, kb, sm_scale,
                         causal, block_q, block_k)
        p = jnp.exp(s - lse_ref[0])                      # (BQ, BK)
        # dv += p^T do  — contract the q dim without materializing p^T
        dv_scr[...] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * sm_scale
        dk_scr[...] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == n_q - 1)
    def _flush():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, bias, out, lse, do, sm_scale, causal, block_q,
              block_k, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    n_q, n_k = sq // block_q, sk // block_k
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, d)
    dof = do.reshape(bh, sq, d)
    lsef = lse.reshape(bh, sq, 1)
    # delta_i = do_i . o_i — one O(N*D) pass in plain jnp, shared by
    # both kernels (the canonical flash backward precompute)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, sq, 1)

    bias_arr = bias if bias is not None else jnp.zeros((1, 1, 1, 1),
                                                       q.dtype)
    mode_q, bias_spec_q = _bias_spec(bias, h, block_q, block_k,
                                     q_axis=1, k_axis=2)
    kern_dq = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k, bias_mode=mode_q)
    dq = pl.pallas_call(
        kern_dq,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, qb, kb: (g, qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, qb, kb: (g, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, qb, kb: (g, kb, 0)),
            bias_spec_q,
            pl.BlockSpec((1, block_q, d), lambda g, qb, kb: (g, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda g, qb, kb: (g, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda g, qb, kb: (g, qb, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda g, qb, kb: (g, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, bias_arr, dof, lsef, delta)

    mode_k, bias_spec_k = _bias_spec(bias, h, block_q, block_k,
                                     q_axis=2, k_axis=1)
    kern_dkv = functools.partial(
        _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, n_q=n_q, bias_mode=mode_k)
    dk, dv = pl.pallas_call(
        kern_dkv,
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, kb, qb: (g, qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, kb, qb: (g, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, kb, qb: (g, kb, 0)),
            bias_spec_k,
            pl.BlockSpec((1, block_q, d), lambda g, kb, qb: (g, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda g, kb, qb: (g, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda g, kb, qb: (g, qb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda g, kb, qb: (g, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, kb, qb: (g, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, bias_arr, dof, lsef, delta)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, mask, sm_scale, causal, block_q, block_k, interpret):
    return _fwd_call(q, k, v, mask, sm_scale, causal, block_q, block_k,
                     interpret)[0]


def _flash_fwd_rule(q, k, v, mask, sm_scale, causal, block_q, block_k,
                    interpret):
    out, lse = _fwd_call(q, k, v, mask, sm_scale, causal, block_q,
                         block_k, interpret)
    return out, (q, k, v, mask, out, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, interpret, res,
                    do):
    q, k, v, mask, out, lse = res
    dq, dk, dv = _bwd_call(q, k, v, mask, out, lse, do, sm_scale,
                           causal, block_q, block_k, interpret)
    # the mask is a constant (padding/causal -1e9): zero cotangent by
    # contract — the graph pass refuses chains whose mask wants grads
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# public entry + op lowering
# ---------------------------------------------------------------------------


def _shape_ok(sq, sk, d):
    return sq % 128 == 0 and sk % 128 == 0 and d in (64, 128, 256)


def _check_mask(mask, b, h, sq, sk):
    if mask is None:
        return
    # Mosaic CLAMPS out-of-range block indices — a mis-sized mask would
    # silently reuse the last tile instead of erroring
    ok = (mask.ndim == 4
          and mask.shape[0] in (1, b) and mask.shape[1] in (1, h)
          and mask.shape[2] in (1, sq) and mask.shape[3] == sk)
    if not ok:
        raise ValueError(
            f"mask shape {tuple(mask.shape)} does not broadcast to "
            f"(B={b}, H={h}, Sq={sq}, Sk={sk}); the key dim must be "
            f"exactly Sk")


def flash_attention(q, k, v, mask=None, *, sm_scale=None, causal=False,
                    block_q=128, block_k=128, use_pallas=None,
                    interpret=False):
    """Fused attention over (B, H, S, D) q/k/v with an optional additive
    mask (None, key form [B,1,1,Sk], or full [B,H,Sq,Sk]).

    ``use_pallas``: True forces the Pallas kernels (``interpret=True``
    runs them on CPU for tests), False forces the jnp reference, None
    picks Pallas on TPU and the reference on the CPU; a TPU shape the
    tiling cannot cover takes the reference and counts
    ``flash_attention_refused_shape``.
    Differentiable in q/k/v via the custom VJP (tiled recompute
    backward); the mask is treated as a constant."""
    if q.ndim != 4:
        raise ValueError(f"flash_attention wants (B, H, S, D) inputs; "
                         f"got rank {q.ndim}")
    b, h, sq, d = q.shape
    sk = k.shape[2]
    _check_mask(mask, b, h, sq, sk)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
        if use_pallas and not _shape_ok(sq, sk, d):
            from ..monitor import stat_add

            stat_add("flash_attention_refused_shape")  # never in silence
            use_pallas = False
    if not use_pallas:
        return flash_attention_ref(q, k, v, mask, sm_scale=sm_scale,
                                   causal=causal)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash_attention needs seq multiples of the block "
            f"({block_q}/{block_k}); got Sq={sq}, Sk={sk}")
    return _flash(q, k, v, mask, float(sm_scale), bool(causal),
                  int(block_q), int(block_k), bool(interpret))


def _pallas_engaged(b, h, sq, sk, d):
    """FLAGS_flash_attention engagement for the rewritten op — the same
    contract as ops/fused.py: 'never' forces the reference, 'always'
    engages at any aligned shape, 'auto' only when the score tensor
    would threaten HBM on a TPU backend.  The ``fused._FORCE_INTERPRET``
    test hook engages the kernels in interpret mode off-TPU."""
    from . import fused

    return fused._flash_engaged(b, h, sq, sk, d)


@register_lower("flash_attention")
def _flash_attention_lower(ctx, op):
    from ..monitor import stat_add
    from . import fused

    q = ctx.in1(op, "Q")
    k = ctx.in1(op, "K")
    v = ctx.in1(op, "V")
    mask = ctx.in1(op, "Mask")
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sm_scale = float(op.attr("scale", 0.0)) or 1.0 / math.sqrt(d)
    causal = bool(op.attr("causal", False))
    if _pallas_engaged(b, h, sq, sk, d):
        stat_add("flash_attention_engaged")
        out = flash_attention(
            q, k, v, mask, sm_scale=sm_scale, causal=causal,
            use_pallas=True,
            interpret=bool(fused._FORCE_INTERPRET
                           or jax.default_backend() != "tpu"))
    else:
        out = flash_attention(q, k, v, mask, sm_scale=sm_scale,
                              causal=causal, use_pallas=False)
    ctx.set_out(op, "Out", out)
