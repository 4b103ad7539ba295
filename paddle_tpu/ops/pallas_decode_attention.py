"""Pallas TPU paged decode-attention kernel (one query token per slot).

Role parity: the decode-phase half of the fused attention story
(`ops/pallas_attention.py` covers training/prefill flash attention).
Autoregressive serving holds each slot's K/V history in fixed-size
pages (`serving/kv_cache.py`); at decode each slot contributes exactly
ONE query token that must attend over its own live history:

    q          : [S, H, D]            one token per slot
    k/v_pages  : [L, P, page, H*D]    the STACKED page pools, all layers
    layer      : int (static)         which layer of the pools to read
    page_table : [S, pps]  int32      slot -> ordered page ids
    lengths    : [S]       int32      live positions per slot
    k/v_scales : [L, P, page, H]      int8 pools only (per head, per row)

**Operand shape and the tile rule behind it.**  A pool row is one
position's heads folded into lanes, ``H*D`` wide, exactly as
`serving/kv_cache.py` stores it.  The chip lays an array out in
(8, 128) tiles of its two trailing dimensions; when ``(page, H*D)``
fills those tiles exactly the pool has ONE device layout from
allocation on, and no program that takes it re-lays it out (a
trailing head_dim of 64 cost GPT-2 four whole-pool copies a step and
2x lane padding).  The kernel takes the stacked pool itself and puts
``layer`` into the BlockSpec's index map: handing it ``pool[layer]``
made XLA materialise a layer-sized slice per layer per step.

The Pallas kernel iterates grid (slot, page) with the page table and
lengths as SCALAR-PREFETCH operands: the page id is known before the
body runs, so each (slot, page) step DMAs exactly one page of K and V
from the pool — HBM traffic is O(sum(live pages)), never
O(S * max_seq).  Pages at or past the slot's length are skipped
entirely (`pl.when`), and the partial page at the tail is masked by
position.  Online softmax (running max / denominator in VMEM scratch)
accumulates across pages exactly like the prefill flash kernel.

Heads are read out of lanes without a reshape: the query rows of
``hb`` heads are stacked block-diagonally (row ``h*R + r`` holds query
row ``r`` in head ``h``'s lanes and zeros elsewhere), so ONE matmul
against the page's ``[page, hb*D]`` lanes yields every head's scores,
one softmax update covers them all, and ``p @ v`` at full lane width
leaves head ``h``'s context in its own lanes of row block ``h``
(`_stack_heads` picks ``hb`` from ``(H, D, R)``).

``decode_attention_reference`` is the pure-jnp oracle — gather the
page table (O(S * max_seq) materialization) and do masked attention.
It is also the CPU-backend default so tier-1 stays green without
Mosaic; ``interpret=True`` runs the real kernel on CPU for tests.

``paged_chunk_attention`` is the kernel itself: R query rows per slot
with per-row causal lengths over one shared page table — the attention
shape of chunked/suffix prefill and speculative verification
(serving/decode.py), where shared and partially-filled pages need no
special casing beyond the mask.  One-token decode is that kernel at
R=1 (tests/test_tpu_compile.py compiles both shapes for the v5e).  A
row of length 0 attends nothing and its output is unspecified (finite).
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


def _gather_dequant(pages, scales, layer, page_table, num_heads):
    """Reference-path page gather out of the stacked pool: [S, pps*page,
    H, D] at full width (the lane-folded row reshaped back to heads,
    free in row-major), dequantized inline when a scale pool rides
    along."""
    s, pps = page_table.shape
    page, hd = pages.shape[2:]
    g = pages[layer, page_table].reshape(
        s, pps * page, num_heads, hd // num_heads)
    if scales is not None:
        sc = scales[layer, page_table].reshape(s, pps * page, num_heads)
        g = g.astype(jnp.float32) * sc.astype(jnp.float32)[..., None]
    return g


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           layer=0, sm_scale=None, use_pallas="auto",
                           interpret=False, k_scales=None,
                           v_scales=None):
    """Decode attention straight off the page pool.

    q [S,H,D]; k/v_pages [L,P,page,H*D] (the stacked pools; ``layer``
    is the static layer to read); page_table [S,pps] i32; lengths [S]
    i32.  ``use_pallas``: 'auto' engages the Pallas kernel on the TPU
    backend only (CPU gets the gather+mask reference, keeping tier-1
    Mosaic-free), 'always' forces it (combine with interpret=True
    off-TPU), 'never' forces the reference.  ``k_scales``/``v_scales``
    [L,P,page,H] arm the quantized path (FLAGS_decode_kv_quant): pages
    are int8 and BOTH paths dequantize them inline — the Pallas kernel
    per tile in VMEM, the reference during the gather — before the one
    shared masked-softmax formulation.
    """
    # one query row per slot IS the chunk kernel at R=1: Mosaic has no
    # matmul for a query with no free row dimension, so the row axis
    # stays even when it is 1
    return paged_chunk_attention(
        q[:, None], k_pages, v_pages, page_table, lengths[:, None],
        layer=layer, sm_scale=sm_scale, use_pallas=use_pallas,
        interpret=interpret, k_scales=k_scales, v_scales=v_scales)[:, 0]


# -- the kernel: R query rows per slot (decode is R=1) --------------------

_MAX_STACK_ROWS = 32  # query rows one matmul carries (hb heads x R rows)


def _stack_heads(num_heads, head_dim, n_rows):
    """How many heads' query rows one matmul stacks block-diagonally.

    A stack spans ``hb * head_dim`` lanes of the pool row, so it must
    cut the row at 128-lane boundaries (or be the whole row: toy
    widths, interpret mode).  More heads a stack means fewer, fuller
    matmuls and softmax updates but ``hb`` times the accumulator, so
    the largest legal ``hb`` with ``hb * n_rows <= _MAX_STACK_ROWS`` is
    taken, and the smallest legal one when none fits."""
    legal = [hb for hb in range(1, num_heads + 1)
             if num_heads % hb == 0
             and ((hb * head_dim) % _LANES == 0 or hb == num_heads)]
    fit = [hb for hb in legal if hb * n_rows <= _MAX_STACK_ROWS]
    return max(fit) if fit else min(legal)


def _chunk_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  sm_scale, page, n_pages, n_rows, head_dim,
                  quantized=False):
    """The decode kernel generalized to R query rows per slot (a
    prefill chunk or a speculative t0+draft window).  Row r of slot s
    attends positions ``t < len_ref[s*R + r]`` — per-row causal masks
    over one shared page table, so shared and partially-filled pages
    need no special casing beyond the mask.

    Refs: q/o (1, R, H*D); k/v (page, H*D) — one page of ONE layer of
    the stacked pool; scales (page, H).  Scratch, per stack of ``hb``
    heads: the block-diagonal query (hb*R, hb*D), running max and
    denominator (hb*R, 128), accumulator (hb*R, hb*D)."""
    import jax.experimental.pallas as pl

    if quantized:
        ks_ref, vs_ref, o_ref, qbd_scr, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, qbd_scr, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None

    s_idx = pl.program_id(0)
    p_idx = pl.program_id(1)
    n_stacks, rows, width = acc_scr.shape
    hb = rows // n_rows                 # heads a stack
    # head (within its stack) that owns each lane of a stack
    lane_head = lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim

    @pl.when(p_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q = q_ref[0].astype(jnp.float32) * sm_scale        # (R, H*D)
        for j in range(n_stacks):
            qj = q[:, j * width:(j + 1) * width]
            for h in range(hb):
                qbd_scr[j, h * n_rows:(h + 1) * n_rows, :] = jnp.where(
                    lane_head == h, qj, 0.0)

    # the widest row bounds whether this page matters at all — taken
    # over ALL rows, so the contract holds for arbitrary (not just
    # ascending) per-row lengths
    lens = [len_ref[s_idx * n_rows + r] for r in range(n_rows)]
    max_len = functools.reduce(jnp.maximum, lens)

    @pl.when(p_idx * page < max_len)
    def _compute():
        # stacked row h*R + r carries query row r: its causal length
        query_row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % n_rows
        row_len = jnp.zeros((rows, 1), jnp.int32) + lens[0]
        for r in range(1, n_rows):
            row_len = jnp.where(query_row == r, lens[r], row_len)
        pos = p_idx * page + lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        live = pos < row_len                               # (rows, page)
        for j in range(n_stacks):
            lanes = slice(j * width, (j + 1) * width)
            k = k_ref[:, lanes].astype(jnp.float32)        # (page, width)
            v = v_ref[:, lanes].astype(jnp.float32)
            if ks_ref is not None:  # dequant-fused: int8 tile * VMEM scale
                k = k * _scale_lanes(ks_ref, j, hb, lane_head)
                v = v * _scale_lanes(vs_ref, j, hb, lane_head)
            # every stacked head's scores in one matmul: (rows, page)
            s = lax.dot_general(
                qbd_scr[j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(live, s, _NEG_INF)
            m_prev = m_scr[j, :, :1]                       # (rows, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                         # (rows, page)
            l_new = alpha * l_scr[j, :, :1] \
                + jnp.sum(p, axis=1, keepdims=True)
            # p @ v at full width: head h's context is in ITS lanes of
            # row block h (the other lanes are discarded at the flush)
            acc_scr[j] = acc_scr[j] * alpha + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # (rows, width)
            m_scr[j] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[j] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(p_idx == n_pages - 1)
    def _flush():
        for j in range(n_stacks):
            out = jnp.zeros((n_rows, width), jnp.float32)
            for h in range(hb):
                blk = slice(h * n_rows, (h + 1) * n_rows)
                l = l_scr[j, blk, :1]
                out = jnp.where(
                    lane_head == h,
                    acc_scr[j, blk, :] / jnp.where(l == 0.0, 1.0, l), out)
            o_ref[0, :, j * width:(j + 1) * width] = out.astype(o_ref.dtype)


def _scale_lanes(scale_ref, stack, hb, lane_head):
    """(page, hb*D) dequant factors for one stack: each head's
    per-position scale column spread over that head's lanes."""
    sc = scale_ref[:, stack * hb:(stack + 1) * hb].astype(jnp.float32)
    out = jnp.zeros((sc.shape[0], lane_head.shape[1]), jnp.float32)
    for h in range(hb):
        out = jnp.where(lane_head == h, sc[:, h:h + 1], out)
    return out


def _chunk_call(q, k_pages, v_pages, layer, page_table, row_lengths,
                sm_scale, interpret, k_scales=None, v_scales=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_slots, n_rows, h, d = q.shape
    pps = page_table.shape[1]
    page, hd = k_pages.shape[2:]
    if hd != h * d:
        raise ValueError(
            f"pool rows are {hd} lanes wide but q has {h} heads of {d}")
    hb = _stack_heads(h, d, n_rows)
    n_stacks, rows, width = h // hb, hb * n_rows, hb * d
    layer = int(layer)
    flat_table = page_table.reshape(-1).astype(jnp.int32)
    flat_lengths = row_lengths.reshape(-1).astype(jnp.int32)
    quantized = k_scales is not None

    # the stacked pool is the operand; layer and page id are block
    # indices, so one page of one layer is all that ever moves
    def page_spec(lanes):
        return pl.BlockSpec(
            (None, None, page, lanes),
            lambda s, p, pt, ln: (layer, pt[s * pps + p], 0, 0))

    row_spec = pl.BlockSpec((1, n_rows, hd),
                            lambda s, p, pt, ln: (s, 0, 0))
    in_specs = [row_spec, page_spec(hd), page_spec(hd)]
    operands = [q.reshape(n_slots, n_rows, hd), k_pages, v_pages]
    if quantized:
        in_specs += [page_spec(h), page_spec(h)]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (flat page table, flat row lengths)
        grid=(n_slots, pps),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((n_stacks, rows, width), jnp.float32),   # query
            pltpu.VMEM((n_stacks, rows, _LANES), jnp.float32),  # max
            pltpu.VMEM((n_stacks, rows, _LANES), jnp.float32),  # denom
            pltpu.VMEM((n_stacks, rows, width), jnp.float32),   # acc
        ],
    )
    kern = functools.partial(_chunk_kernel, sm_scale=sm_scale,
                             page=page, n_pages=pps, n_rows=n_rows,
                             head_dim=d, quantized=quantized)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, n_rows, hd), q.dtype),
        interpret=interpret,
        name=KERNEL_NAME,
    )(flat_table, flat_lengths, *operands)
    return out.reshape(q.shape)


def paged_chunk_attention(q, k_pages, v_pages, page_table, row_lengths,
                          *, layer=0, sm_scale=None, use_pallas="auto",
                          interpret=False, k_scales=None,
                          v_scales=None):
    """Multi-row attention off the page pool — R query rows per slot.

    q [S,R,H,D]; k/v_pages [L,P,page,H*D] (the stacked pools; ``layer``
    is the static layer to read); page_table [S,pps] i32; row_lengths
    [S,R] i32 — row r of slot s attends positions
    ``t < row_lengths[s, r]``.  Serves both tentpole callers
    in serving/decode.py: chunked prefill (R = chunk rows, one slot at
    a time) and speculative-decode verification (R = 1 + draft window,
    every slot jointly).  The reference path broadcasts each slot's
    gathered K/V across its rows and reuses
    ``decode_attention_reference`` VERBATIM — the single masked-softmax
    formulation at one width that keeps every cache path bitwise-equal
    to the full-recompute oracle.  ``use_pallas`` dispatch and the
    quantized ``k_scales``/``v_scales`` [L,P,page,H] contract match
    ``paged_decode_attention``.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas == "auto":
        use_pallas = "always" if jax.default_backend() == "tpu" \
            else "never"
    if use_pallas == "always":
        return _chunk_call(q, k_pages, v_pages, layer, page_table,
                           row_lengths, float(sm_scale), interpret,
                           k_scales=k_scales, v_scales=v_scales)
    s, r, h = q.shape[:3]
    k = _gather_dequant(k_pages, k_scales, layer, page_table, h)
    v = _gather_dequant(v_pages, v_scales, layer, page_table, h)
    kr = jnp.broadcast_to(k[:, None], (s, r) + k.shape[1:]) \
        .reshape(s * r, *k.shape[1:])
    vr = jnp.broadcast_to(v[:, None], (s, r) + v.shape[1:]) \
        .reshape(s * r, *v.shape[1:])
    out = decode_attention_reference(
        q.reshape((s * r,) + q.shape[2:]), kr, vr,
        row_lengths.reshape(-1), sm_scale=sm_scale)
    return out.reshape(q.shape)
