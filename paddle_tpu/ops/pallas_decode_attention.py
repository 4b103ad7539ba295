"""Pallas TPU paged decode-attention kernel (one query token per slot).

Role parity: the decode-phase half of the fused attention story
(`ops/pallas_attention.py` covers training/prefill flash attention).
Autoregressive serving holds each slot's K/V history in fixed-size
pages (`serving/kv_cache.py`); at decode each slot contributes exactly
ONE query token that must attend over its own live history:

    q          : [S, H, D]            one token per slot
    k/v_pages  : [P, page, H, D]      the shared page pool (one layer)
    page_table : [S, pps]  int32      slot -> ordered page ids
    lengths    : [S]       int32      live positions per slot

The Pallas kernel iterates grid (slot, page) with the page table and
lengths as SCALAR-PREFETCH operands: the page id is known before the
body runs, so each (slot, page) step DMAs exactly one page of K and V
from the pool — HBM traffic is O(sum(live pages)), never
O(S * max_seq).  Pages at or past the slot's length are skipped
entirely (`pl.when`), and the partial page at the tail is masked by
position.  Online softmax (running max / denominator in VMEM scratch)
accumulates across pages exactly like the prefill flash kernel.

``decode_attention_reference`` is the pure-jnp oracle — gather the
page table (O(S * max_seq) materialization) and do masked attention.
It is also the CPU-backend default so tier-1 stays green without
Mosaic; ``interpret=True`` runs the real kernel on CPU for tests.

``paged_chunk_attention`` is the kernel itself: R query rows per slot
with per-row causal lengths over one shared page table — the attention
shape of chunked/suffix prefill and speculative verification
(serving/decode.py), where shared and partially-filled pages need no
special casing beyond the mask.  One-token decode is that kernel at
R=1: Mosaic has no matmul for a bare (H, D) query with batch H and
contraction D, so the query keeps its row dimension even when it is 1
(tests/test_tpu_compile.py compiles both shapes for the v5e).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
_LANES = 128  # TPU vector lane width; row stats broadcast across lanes
# the Pallas call's own name: a profiler trace names the kernel's device
# event "%paged_attention.<n> = ... custom-call(...)" (still a
# tpu_custom_call), so it can be told apart from any other kernel
KERNEL_NAME = "paged_attention"


def decode_attention_reference(q, k, v, lengths, *, sm_scale=None):
    """Masked single-token attention over full-width K/V.

    q: [S, H, D]; k/v: [S, T, H, D] (slot-major, any width T >= max
    length); lengths: [S] — position t of slot s participates iff
    t < lengths[s].  This exact formulation (mask -> -1e30, softmax
    over the full width) is shared by the decode fallback AND the
    prefill path in serving/decode.py, which is what makes
    decode-with-cache logits bitwise-comparable to a full recompute.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("shd,sthd->sht", qf, kf) * sm_scale      # [S, H, T]
    t = jnp.arange(k.shape[1], dtype=jnp.int32)
    mask = t[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("sht,sthd->shd", p, vf)
    return out.astype(q.dtype)


def _gather_dequant(pages, scales, page_table):
    """Reference-path page gather: [S, pps*page, H, D] at full width,
    dequantized inline when a scale pool rides along."""
    s, pps = page_table.shape
    page = pages.shape[1]
    g = pages[page_table]                    # [S, pps, page, H, D]
    if scales is not None:
        g = g.astype(jnp.float32) \
            * scales[page_table].astype(jnp.float32)[..., None]
    return g.reshape(s, pps * page, *pages.shape[2:])


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           sm_scale=None, use_pallas="auto",
                           interpret=False, k_scales=None,
                           v_scales=None):
    """Decode attention straight off the page pool.

    q [S,H,D]; k/v_pages [P,page,H,D] (ONE layer's pool); page_table
    [S,pps] i32; lengths [S] i32.  ``use_pallas``: 'auto' engages the
    Pallas kernel on the TPU backend only (CPU gets the gather+mask
    reference, keeping tier-1 Mosaic-free), 'always' forces it
    (combine with interpret=True off-TPU), 'never' forces the
    reference.  ``k_scales``/``v_scales`` [P,page,H] arm the quantized
    path (FLAGS_decode_kv_quant): pages are int8 and BOTH paths
    dequantize them inline — the Pallas kernel per tile in VMEM, the
    reference during the gather — before the one shared masked-softmax
    formulation.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas == "auto":
        use_pallas = "always" if jax.default_backend() == "tpu" \
            else "never"
    if use_pallas == "always":
        # one query row per slot IS the chunk kernel at R=1: Mosaic has
        # no matmul for a bare (H, D) left operand with batch H and
        # contraction D (no free row dimension), so the row axis stays
        return _chunk_call(q[:, None], k_pages, v_pages, page_table,
                           lengths[:, None], float(sm_scale), interpret,
                           k_scales=k_scales, v_scales=v_scales)[:, 0]
    # reference: gather the page table to full width, then mask
    k = _gather_dequant(k_pages, k_scales, page_table)
    v = _gather_dequant(v_pages, v_scales, page_table)
    return decode_attention_reference(q, k, v, lengths,
                                      sm_scale=sm_scale)


# -- multi-row variant: chunked prefill + speculative verify --------------


def _chunk_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  sm_scale, page, n_pages, n_rows, quantized=False):
    """The decode kernel generalized to R query rows per slot (a
    prefill chunk or a speculative t0+draft window).  Row r of slot s
    attends positions ``t < len_ref[s*R + r]`` — per-row causal masks
    over one shared page table, so shared and partially-filled pages
    need no special casing beyond the mask."""
    import jax.experimental.pallas as pl

    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None

    s_idx = pl.program_id(0)
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the widest row bounds whether this page matters at all — taken
    # over ALL rows, so the contract holds for arbitrary (not just
    # ascending) per-row lengths
    row_len = jnp.stack(
        [len_ref[s_idx * n_rows + r] for r in range(n_rows)])
    max_len = jnp.max(row_len)

    @pl.when(p_idx * page < max_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (R, H, D)
        k = k_ref[0].astype(jnp.float32)              # (page, H, D)
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:  # dequant-fused: int8 tile * VMEM scale
            k = k * ks_ref[0].astype(jnp.float32)[..., None]
            v = v * vs_ref[0].astype(jnp.float32)[..., None]
        # scores per head per row over this page: (H, R, page)
        s = lax.dot_general(
            q, k, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32) * sm_scale
        pos = p_idx * page + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < row_len[None, :, None], s, _NEG_INF)

        m_prev = m_scr[:, :, :1]                       # (H, R, 1)
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # (H, R, page)
        l_new = alpha * l_scr[:, :, :1] \
            + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)        # (H, R, D)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(p_idx == n_pages - 1)
    def _flush():
        l = l_scr[:, :, :1]
        out = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)  # (H, R, D)
        o_ref[0] = out.transpose(1, 0, 2).astype(o_ref.dtype)


def _chunk_call(q, k_pages, v_pages, page_table, row_lengths, sm_scale,
                interpret, k_scales=None, v_scales=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_slots, n_rows, h, d = q.shape
    pps = page_table.shape[1]
    page = k_pages.shape[1]
    flat_table = page_table.reshape(-1).astype(jnp.int32)
    flat_lengths = row_lengths.reshape(-1).astype(jnp.int32)
    quantized = k_scales is not None

    in_specs = [
        pl.BlockSpec((1, n_rows, h, d),
                     lambda s, p, pt, ln: (s, 0, 0, 0)),
        pl.BlockSpec((1, page, h, d),
                     lambda s, p, pt, ln: (pt[s * pps + p], 0, 0, 0)),
        pl.BlockSpec((1, page, h, d),
                     lambda s, p, pt, ln: (pt[s * pps + p], 0, 0, 0)),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, page, h),
                         lambda s, p, pt, ln: (pt[s * pps + p], 0, 0)),
            pl.BlockSpec((1, page, h),
                         lambda s, p, pt, ln: (pt[s * pps + p], 0, 0)),
        ]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (flat page table, flat row lengths)
        grid=(n_slots, pps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_rows, h, d),
                               lambda s, p, pt, ln: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, n_rows, _LANES), jnp.float32),  # running max
            pltpu.VMEM((h, n_rows, _LANES), jnp.float32),  # denominator
            pltpu.VMEM((h, n_rows, d), jnp.float32),       # accumulator
        ],
    )
    kern = functools.partial(_chunk_kernel, sm_scale=sm_scale,
                             page=page, n_pages=pps, n_rows=n_rows,
                             quantized=quantized)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, n_rows, h, d), q.dtype),
        interpret=interpret,
        name=KERNEL_NAME,
    )(flat_table, flat_lengths, *operands)


def paged_chunk_attention(q, k_pages, v_pages, page_table, row_lengths,
                          *, sm_scale=None, use_pallas="auto",
                          interpret=False, k_scales=None,
                          v_scales=None):
    """Multi-row attention off the page pool — R query rows per slot.

    q [S,R,H,D]; k/v_pages [P,page,H,D] (ONE layer's pool); page_table
    [S,pps] i32; row_lengths [S,R] i32 — row r of slot s attends
    positions ``t < row_lengths[s, r]``.  Serves both tentpole callers
    in serving/decode.py: chunked prefill (R = chunk rows, one slot at
    a time) and speculative-decode verification (R = 1 + draft window,
    every slot jointly).  The reference path broadcasts each slot's
    gathered K/V across its rows and reuses
    ``decode_attention_reference`` VERBATIM — the single masked-softmax
    formulation at one width that keeps every cache path bitwise-equal
    to the full-recompute oracle.  ``use_pallas`` dispatch and the
    quantized ``k_scales``/``v_scales`` contract match
    ``paged_decode_attention``.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas == "auto":
        use_pallas = "always" if jax.default_backend() == "tpu" \
            else "never"
    if use_pallas == "always":
        return _chunk_call(q, k_pages, v_pages, page_table, row_lengths,
                           float(sm_scale), interpret,
                           k_scales=k_scales, v_scales=v_scales)
    s, r = q.shape[:2]
    k = _gather_dequant(k_pages, k_scales, page_table)
    v = _gather_dequant(v_pages, v_scales, page_table)
    kr = jnp.broadcast_to(k[:, None], (s, r) + k.shape[1:]) \
        .reshape(s * r, *k.shape[1:])
    vr = jnp.broadcast_to(v[:, None], (s, r) + v.shape[1:]) \
        .reshape(s * r, *v.shape[1:])
    out = decode_attention_reference(
        q.reshape((s * r,) + q.shape[2:]), kr, vr,
        row_lengths.reshape(-1), sm_scale=sm_scale)
    return out.reshape(q.shape)
