"""Pallas TPU paged decode-attention kernel (one query token per slot).

Role parity: the decode-phase half of the fused attention story
(`ops/pallas_attention.py` covers training/prefill flash attention).
Autoregressive serving holds each slot's K/V history in fixed-size
pages (`serving/kv_cache.py`); at decode each slot contributes exactly
ONE query token that must attend over its own live history:

    q          : [S, H, D]            one token per slot
    k/v_pages  : [L, P, page, H*D]    the STACKED page pools, all layers
    layer      : int                  which layer of the pools to read
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
2x lane padding).  The kernel takes the stacked pool itself and
indexes ``layer`` in its own copies: handing it ``pool[layer]`` made
XLA materialise a layer-sized slice per layer per step.  ``layer`` is
an operand, not a constant of the kernel, so a model's layers share
ONE traced and lowered kernel (a program's set-up pays for the body
once, not once a layer).

**The walk: live pages, a block of them at a time.**  One grid step is
one SLOT; the page table, the row lengths, each slot's widest row and
``layer`` are SCALAR-PREFETCH operands, and the pools stay in HBM.
Inside a slot the kernel loops over its LIVE blocks only — a block is
``ppb`` consecutive page-table entries — so a step's cost follows the
positions attended, never ``S * max_seq``: a dead block costs nothing,
not even a skipped grid step.  ``ppb`` is read from the shapes and the
pools' dtype (`pages_per_block`; no flag, no model's name): the largest
count with ``ppb * page <= 128`` positions, one full lane tile of
scores, whose K and V blocks fit the fast-memory budget twice over
(eight 16-token pages at GPT-2's 1,024 f32 lanes and at 2,048 bf16
lanes, 2 MB; a page of 128 positions is a block by itself) -- and up to
``_STACKED_BLOCK`` = 512 positions, whole lane tiles, where bfloat16
pools meet query rows STACKED on a K/V head (grouped-query decode: 16
rows a head on MiMo-V2.5 and Command A+, 8 on Solar; a chunk's or a
verify window's rows), as far as both buffers of both pools, the scores
and the table allow.  At one row a head a block's arithmetic hides under
its copies (Olmo-Hybrid: 92 % of the roofline at 128); with rows stacked
it is a chain of latencies paid once a block, and was most of the call
(the table by ``_STACKED_BLOCK``: MiMo's global call 2.04 -> 1.24 ms
beside copies of 0.96).  What the shapes decide: Olmo-Hybrid's rows of
15,360 B a position fit 128 positions and no more, MiMo's window ring of
9 pages holds one tile, Ouro and GPT-2 have one row a head, and the
float32 and int8 bodies (float32 copies of a stack's K and V) stay at
one tile.  A larger block costs a slot's last block its dead positions
(computed and masked) and a block that is not whole its page-by-page
copies, of up to ``ppb`` pages: both inside the measured calls.  The
scores of a block are ONE ``(hb*R, ppb*page)`` tile a stack: one masked
online-softmax update (running max / denominator in VMEM scratch, as in
the prefill flash kernel) and one rescale of the accumulator a block,
not a page.

Only live pages move, by the kernel's own async copies into one of two
block buffers a pool: while a block is computed the next one — this
slot's, or the NEXT slot's first — is in flight, so no slot starts on
an exposed copy.  Start and wait walk the same range of live entries,
so every copy is waited for exactly once; a dead table entry is never
read.  **The hazard that follows**: a dead page of a live block was
never copied, its buffer holds whatever was there, and although its
scores are masked (``p == 0``), ``0 * NaN`` is NaN.  So V is zeroed by
position before ``p @ v`` (tests poison dead pages and fresh buffers
with NaN); the bfloat16 feed below zeroes the dead pages where they lie
in the V buffer instead, whole packed tiles, and only in a block that
has any (a slot's last, a window's first), and in the last live page
the positions past the slot's length.  The int8 pools' f32 scale
planes ``[L, P, page, H]`` are the exception to "the pools stay put":
the chip's compiler cuts no page out of a plane H lanes wide in HBM, so
a slot's scales are gathered by its page table outside the kernel (1/D
of the pages' bytes) and arrive as one block a slot.

Heads are read out of lanes without a reshape: the query rows of
``hb`` heads are stacked block-diagonally (row ``h*R + r`` holds query
row ``r`` in head ``h``'s lanes and zeros elsewhere), so ONE matmul
against the page's ``[page, hb*D]`` lanes yields every head's scores,
one softmax update covers them all, and ``p @ v`` at full lane width
leaves head ``h``'s context in its own lanes of row block ``h``
(`_stack_heads` picks ``hb`` from ``(H, D, R)``).

**The feed, chosen by the pools' dtype** (``feed_bits``; no flag, and
nothing but ``k_pages.dtype`` is asked).  Float32 pools, and int8 pools
once dequantized, meet float32 operands on both sides of the block's two
matmuls, one stack after the other.  The chip's compiler runs such a
matmul as ONE bfloat16 pass: it rounds the query and the probabilities
to bfloat16 (measured: a one-term bfloat16 feed gives the float32
feed's bits).  A bfloat16 pool's K and V go to the matmuls as they lie
in the block buffers, with no float32 copy of a block (unpacking 256
lanes x 128 positions twice a stack, a select over all of V and the
repack were the body's largest vector work), and the float32 side is
kept whole by ROWS: ``x = hi + mid + lo`` with ``hi = bf16(x)``, ``mid =
bf16(x - hi)``, ``lo = bf16(x - hi - mid)`` carries all 24 bits of a
float32, a product of two bfloat16 values is exact in float32, and K and
V ARE bfloat16, so the three terms stacked as three groups of rows of
one left operand against ONE load of a K or V tile, their float32
products added smallest first, give the float32 x float32 product to
float32 rounding: more than the float32 feed keeps on the chip.  The
block-diagonal query is built and split once a slot into a bfloat16
scratch (``sm_scale`` stays on the float32 query); a group starts on a
packed tile of 16 rows.  Two things about the ORDER of that body, both
measured on the v5e at Command A+'s rows (48 rings of 257 pages, ms a
layer's call; the copies alone take 1.06): (1) a stack's update is a
chain of latencies (matmul, a lane reduction, exp, a lane reduction,
matmul), and run one stack after the other the chains do not overlap
(2.69 as it was, 2.45 with the bfloat16 feed alone): the body runs a
phase at a time over ALL stacks, every load before the first store
(1.88), and stacks carry ``_MAX_SPLIT_ROWS`` = 16 query rows, twice as
many shorter chains (1.80); (2) sixteen copies a block are started and
waited for by the scalar core, in loops that stand between the blocks'
arithmetic: a block whose every page is live starts its copies one
after the other and waits ONCE a pool for the bytes of all of them
(1.49).  Which pages move is unchanged.

``decode_attention_reference`` is the pure-jnp oracle — gather the
page table (O(S * max_seq) materialization) and do masked attention.
It is also the CPU-backend default so tier-1 stays green without
Mosaic; ``interpret=True`` runs the real kernel on CPU for tests.

**K and V of different widths, a window, a sink.**  The K pool's rows
hold ``H*Dk`` lanes and the V pool's ``H*Dv``: the query and the scores
follow K's width, the accumulator and the output V's (MiMo-V2.5: 192
and 128).  With ``window=W`` a row of length ``n`` attends positions
``n - W <= t < n`` only, and the slot's page table is read as a RING:
logical page ``j`` (positions ``j*page ...``) lives at entry ``j % pps``
(`serving/kv_cache.py` keeps ``ceil(W / page) + 1`` pages a slot for such
a layer and overwrites the oldest; a table that holds every page is the
ring that never wraps).  The walk then starts at the block that holds
position ``n - W`` and moves the pages from there on: one or two blocks
however long the slot.  ``sinks`` is a learned logit a query head that
joins the softmax's denominator and nothing else: the online softmax
starts from ``m = sink, l = 1, acc = 0`` instead of ``-inf, 0, 0``, so
it costs no pass.  The window call has its own name
(``WINDOW_KERNEL_NAME``), so a trace tells the two kinds of layer apart.

**One pool, read once, used twice** (``v_pages=None``, ``value_lanes``).
A latent cache (`serving/kv_cache.py`) keeps ONE row a position, shared
by every query head: the scores read the whole row and the values are
its first ``value_lanes`` lanes.  The query heads then ride as rows of
that one head (64 of them make a stack of 64 rows, whatever
``_MAX_SPLIT_ROWS`` says: one head cannot be cut), a block is copied
from HBM once and meets both matmuls from the one buffer, and the query
is padded with zeros to the pool's row (whole lane tiles, where the
model's row is not).  At 2 x 64 x (576 + 512) FLOP a 1,152-byte row the
body is near the chip's ridge, so the float32 side rides as
``_LATENT_TERMS`` bfloat16 terms and not three (2.35 ms a layer's call
against 1.44 at the cell's rows), and a block is ``_LATENT_BLOCK``
positions and not one lane tile of scores.  The call has its own name
(``LATENT_KERNEL_NAME``).

**One pool, K and V side by side** (``v_pages=None``, ``value_lanes``
AND ``value_offset``).  ``v_pages=None`` has two meanings, and
``value_offset`` tells them apart: None is the latent pool above; a lane
is a JOINT pool (`serving/kv_cache.py` ``CacheConfig.joint``), whose row
is ONE K/V head's keys (its first D lanes) and, from lane
``value_offset`` on, its ``value_lanes`` values.  The kernel then starts
ONE copy a page where two pools cost two, the same bytes in half the
descriptors and half the waits, which is what paces a call whose pages
are 4 KB a pool (the table by ``_STACKED_BLOCK``: Jamba2-3B's call 1.83
-> 1.33 ms).  Nothing else is the latent body's: the K-and-V body runs
on the one buffer (the scores read its first D lanes, the values the
lanes after them), with its three bfloat16 terms, its block
(``pages_per_block`` as for two pools of those widths), its guard on the
values' lanes alone and its name (``KERNEL_NAME``), the arithmetic and
its order unchanged: the output is the two-pool call's on the same K and
V, bit for bit.  The plain path slices K and V out of the gathered row.

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
WINDOW_KERNEL_NAME = "paged_attention_window"    # the call with a window
LATENT_KERNEL_NAME = "paged_attention_latent"    # one pool, values in keys


def decode_attention_reference(q, k, v, lengths, *, sm_scale=None,
                               window=None, sinks=None, offset=None):
    """Masked single-token attention over K/V of any width T >= max length.

    q: [S, H, D]; k/v: [S, T, H, D] (slot-major; V's D may differ, the
    output has it); lengths: [S] — position
    t of slot s participates iff t < lengths[s] (and, with ``window``,
    ``t >= lengths[s] - window``; column t is position ``t + offset[s]``
    where ``offset`` is given).  ``sinks`` [S, H]: a logit that joins each
    row's softmax and takes its share of the weights with it.  This exact
    formulation
    (mask -> -1e30, float32 softmax) is shared by the decode fallback, at
    the cache's width, AND the whole-prompt prefill in serving/decode.py,
    at the prompt's bucket: a masked position weighs exactly zero, so T is
    free, and decode-with-cache logits stay bitwise-comparable to a full
    recompute."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("shd,sthd->sht", qf, kf) * sm_scale      # [S, H, T]
    t = jnp.arange(k.shape[1], dtype=jnp.int32)[None, None, :]
    if offset is not None:
        t = t + offset[:, None, None]
    mask = t < lengths[:, None, None]
    if window is not None:
        mask = mask & (t >= lengths[:, None, None] - window)
    s = jnp.where(mask, s, _NEG_INF)
    if sinks is not None:
        s = jnp.concatenate(
            [s, sinks.astype(jnp.float32)[..., None]], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    if sinks is not None:
        p = p[..., :-1]
    out = jnp.einsum("sht,sthd->shd", p, vf)
    return out.astype(q.dtype)


# float32 scores one block of query rows may hold, all heads: what bounds
# the whole-prompt prefill's attention temporaries (128 heads x 4,096 keys
# make that 256 rows; a prompt whose whole score tensor fits is one block)
_SCORE_BLOCK_BYTES = 512 << 20


def prefill_key_span(t, num_heads, window=None):
    """(query rows a block, keys a block's softmax spans) of
    ``grouped_causal_attention`` at a prompt bucket of ``t`` rows and
    ``num_heads`` query heads: the fewest equal blocks whose float32
    scores ``[H, rows, t]`` fit ``_SCORE_BLOCK_BYTES``; a window layer's
    block spans the keys its rows' windows reach (``window - 1 + rows``,
    up to whole lanes), never more than ``t``."""
    need = -(-num_heads * t * t * 4 // _SCORE_BLOCK_BYTES)
    n = next(n for n in range(max(need, 1), t + 1) if t % n == 0)
    rows = t // n
    if window is None:
        return rows, t
    return rows, min(t, -(-(window - 1 + rows) // _LANES) * _LANES)


def _kernel_asked(use_pallas):
    """``use_pallas`` resolved: 'always', or 'auto' on the TPU backend."""
    return use_pallas == "always" or (
        use_pallas == "auto" and jax.default_backend() == "tpu")


def prefill_walk(t, num_heads, kv_heads, d, dv, window=None,
                 use_pallas="auto"):
    """Which form ``grouped_causal_attention`` runs at these shapes, and
    its blocks: ``("flash", bq, bk)`` where ``use_pallas`` asks for the
    kernel and ``flash_rule`` takes the shape, else ``("blocks", rows,
    span)`` (``prefill_key_span``).  ``use_pallas`` as in
    ``paged_chunk_attention``: 'auto' is the kernel on the TPU backend
    only."""
    from .pallas_prompt_attention import flash_rule

    tiles = flash_rule(t, num_heads, kv_heads, d, dv, window) \
        if _kernel_asked(use_pallas) else None
    if tiles is not None:
        return ("flash",) + tiles
    return ("blocks",) + prefill_key_span(t, num_heads, window)


def prefill_keys_walked(t, n, walk, window=None):
    """Keys a head that form covers for a prompt of ``n`` tokens in a
    bucket of ``t``: the kernel's visited blocks, or every row's span."""
    from .pallas_prompt_attention import keys_visited

    form, rows, keys = walk
    if form == "flash":
        return keys_visited(t, n, rows, keys, window)
    return t * keys


def grouped_causal_attention(q, k, v, *, sm_scale=None, window=None,
                             sinks=None, length=None, use_pallas="auto",
                             interpret=False, select=None):
    """Causal attention of one sequence whose query heads outnumber its
    K/V heads: q [T, H, D], k [T, Hkv, D], v [T, Hkv, Dv], query head i
    reading K/V head ``i // (H // Hkv)``; row t attends positions
    ``<= t`` (with ``window``: the last ``window`` of them, itself
    counted), and ``sinks`` [H] joins each head's softmax as a logit
    with no value; ``select`` [T, T] (nonzero where row t attends key
    s, beside the causal rule: a learned indexer's selection, one a row
    for all heads; every row has such a key).  The whole-prompt prefill
    of a grouped-query model
    (serving/decode.py), in one of two forms (``prefill_walk``).  Where
    ``use_pallas`` asks for it and the shape is one the kernel takes,
    the flash kernel of ``pallas_prompt_attention``: scores in fast
    memory, only the blocks under the diagonal and inside the window,
    and of the row blocks only those that start before ``length`` (the
    prompt's real rows; the others come back zero).  Else plain jnp,
    float32 softmax, in blocks of query rows one after another
    (``prefill_key_span``), so the scores that live at once are a
    block's ``[Hkv, G, rows, span]`` and not ``T x T``; a masked key
    weighs exactly zero, so a block's softmax over the
    span is the row's over the prompt."""
    t, h, d = q.shape
    kv_heads = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    form, rows, span = prefill_walk(t, h, kv_heads, d, v.shape[-1], window,
                                    use_pallas)
    if form == "flash":
        from .pallas_prompt_attention import prompt_flash_attention

        return prompt_flash_attention(
            q, k, v, t if length is None else length, sinks, select,
            sm_scale=float(sm_scale), window=window, tiles=(rows, span),
            interpret=interpret)
    qg = q.astype(jnp.float32).reshape(t // rows, rows, kv_heads,
                                       h // kv_heads, d)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    if sinks is not None:
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(kv_heads, -1, 1, 1),
            (kv_heads, h // kv_heads, rows, 1))

    def block(first, qb):
        """Query rows ``first ...`` against the ``span`` keys that end
        with the block's last row (from 0 while the prompt is shorter)."""
        lo = jnp.clip(first + rows - span, 0, t - span)
        kb = lax.dynamic_slice_in_dim(kf, lo, span)
        vb = lax.dynamic_slice_in_dim(vf, lo, span)
        s = jnp.einsum("thgd,uhd->hgtu", qb, kb) * sm_scale
        row = first + jnp.arange(rows, dtype=jnp.int32)[:, None]
        col = lo + jnp.arange(span, dtype=jnp.int32)[None, :]
        mask = col <= row
        if window is not None:
            mask = mask & (col > row - window)
        if select is not None:
            mask = mask & (lax.dynamic_slice(
                select, (first, lo), (rows, span)) != 0)
        s = jnp.where(mask, s, _NEG_INF)
        if sinks is not None:
            s = jnp.concatenate([s, sink], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        if sinks is not None:
            p = p[..., :-1]
        return jnp.einsum("hgtu,uhd->thgd", p, vb)

    if rows == t:
        out = block(0, qg[0])
    else:
        out = lax.map(lambda a: block(*a), (
            jnp.arange(0, t, rows, dtype=jnp.int32), qg))
    return out.reshape(t, h, v.shape[-1]).astype(q.dtype)


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
                           v_scales=None, window=None, sinks=None,
                           value_lanes=None, value_offset=None):
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
    shared masked-softmax formulation.  ``window`` / ``sinks`` [H],
    ``value_lanes`` / ``value_offset`` (``v_pages=None``): see
    ``paged_chunk_attention``.
    """
    # one query row per slot IS the chunk kernel at R=1: Mosaic has no
    # matmul for a query with no free row dimension, so the row axis
    # stays even when it is 1
    return paged_chunk_attention(
        q[:, None], k_pages, v_pages, page_table, lengths[:, None],
        layer=layer, sm_scale=sm_scale, use_pallas=use_pallas,
        interpret=interpret, k_scales=k_scales, v_scales=v_scales,
        window=window, sinks=sinks, value_lanes=value_lanes,
        value_offset=value_offset)[:, 0]


# -- the kernel: R query rows per slot (decode is R=1) --------------------

_MAX_STACK_ROWS = 32  # query rows one matmul carries (hb heads x R rows)
# ... where each rides as three bfloat16 rows (``feed_bits`` 16): 48
# stacked rows, and twice the stacks to run beside one another
_MAX_SPLIT_ROWS = 16
# both buffers of both pools' blocks, in fast memory beside the body's
# working tiles (float32 copies of a stack's K and V lanes where the
# feed is float32; a fraction of the 16 MB the chip scopes to a kernel)
_BLOCK_VMEM_BYTES = 4 << 20
# positions a block of the latent body holds.  Its two matmuls are most
# of its time (near the ridge), and a block of one lane tile of scores
# gives the MXUs ONE chain of products to run and pays the block's fixed
# work (64 copies started, a wait, the accumulator's rescale) every 128
# positions.  Measured on the v5e at the cell's rows (64 slots of 7.1-10.2k
# positions, ms a layer's call; the rows' bytes alone take 0.88): blocks
# of 128 / 256 / 512 / 1,024 / 2,048 positions read 3.39 / 2.24 / 1.67 /
# 1.44 / 1.39; both buffers of 1,024 fit ``_BLOCK_VMEM_BYTES``
_LATENT_BLOCK = 1024
# ... and of the K-and-V bfloat16 body where query rows are stacked on a
# K/V head (``pages_per_block``).  At ONE row a head the block's
# arithmetic hides under its copies; at 16 rows a head a block of one lane
# tile pays its chain of latencies (matmul, lane reduction, exp, lane
# reduction, matmul) and its fixed work every 128 positions, and that,
# not the copies, was the call's time.  Measured on the v5e, the kernel
# alone in a loop at each serving cell's slots, table, page of 16 and
# live lengths (`tools/sweep_decode_block.py`, my chip run, PR 51), ms a
# layer's call at blocks of 128 / 256 / 512 / 768 / 1,024 positions; in
# brackets a kernel that starts and waits for the same copies in the
# same blocks and computes nothing, then the rows' bytes at 819 GB/s:
#   MiMo global   4 x (192 + 128), 16 rows a head, 128 slots of 0.8-3.5k:
#       2.04  1.52  1.24  1.21  1.21   [1.09 0.97 0.96 0.95 0.95; 0.83]
#   Command A+ window   8 x (128 + 128), 16 rows, 48 rings of 257 pages:
#       1.52  1.17  1.14  1.12  1.13   [1.08 1.08 1.08 1.07 1.07; 0.95]
#   Command A+ global   the same rows, 48 slots of 3.1-6.1k:
#       1.72  1.34  1.32  1.31  1.31   [1.29 1.28 1.28 1.28 1.28; 1.15]
#   Solar   8 x (128 + 128), 8 rows a head, 128 slots of 0.2-1.5k:
#       0.98  0.77  0.70  0.71  0.73   [0.68 0.67 0.66 0.66 0.66; 0.57]
#   Ouro   16 x (128 + 128), ONE row a head, 16 slots of 64-320:
#       0.077 0.077                    [0.067 0.064; 0.026]
#   Olmo-Hybrid   30 x (128 + 128), one row a head, 32 slots of 3.1-5.6k:
#       2.91  2.90                     [2.90 2.89; 2.63]
#   MiMo window   8 x (192 + 128), 8 rows, rings of 9 pages: 0.35 [0.18]
#       at 128, the only block its table holds.
#   Jamba2-3B   ONE head of 128 + 128, 20 rows on it, 256 slots of
#   0.3-3.6k (my chip runs, PR 63), at 128 / 256 / 512 / 1,024:
#       3.25  2.31  1.83  1.69         [1.33 1.20 1.16 1.19; 0.31]
#     a page is 4 KB a pool and the 64 copies of a block of 512 issue in
#     3.7x their bytes' time: the call is paced by their COUNT.  The same
#     K and V in ONE pool of 256-lane rows (a joint pool, the module
#     header: 32 copies of 8 KB a block), the call and [its copies]:
#       -     1.82  1.33  1.21         [1.11 0.75 0.65 0.68; 0.31]
#     what is left over the copies (0.68 at 512) is the one stack's
#     chain, which nothing overlaps at one K/V head.
# 512 takes all but 2 % of what any block gives and both its buffers are
# 2.6 MB at MiMo's rows and ``_BLOCK_VMEM_BYTES`` at Command A+'s; past it
# the dead positions of a slot's last block and the page-by-page copies
# of a block that is not whole start to show (Solar).  A block that is
# not whole was also tried with its live pages started from the unrolled
# form under a ``pl.when`` a page: slower at every shape and block (MiMo
# global at 512: 1.31 against 1.24), so it keeps the loop.
_STACKED_BLOCK = 512
# float32 scores of one block the body may hold at once, all stacks and
# all three terms (it runs a phase at a time over every stack): what
# keeps a chunk's or a verify window's many rows at the block they had
# (Command A+'s 128 decode rows at 512 positions are 768 KB)
_SCORE_VMEM_BYTES = 1 << 20


def _stack_heads(num_heads, head_dim, n_rows, v_dim=None,
                 max_rows=_MAX_STACK_ROWS):
    """How many heads' query rows one matmul stacks block-diagonally.

    A stack spans ``hb * head_dim`` lanes of the pool row (``hb *
    v_dim`` of a V row of another width), so it must
    cut the row at 128-lane boundaries (or be the whole row: toy
    widths, interpret mode).  More heads a stack means fewer, fuller
    matmuls and softmax updates but ``hb`` times the accumulator, so
    the largest legal ``hb`` with ``hb * n_rows <= max_rows`` is
    taken, and the smallest legal one when none fits."""
    legal = [hb for hb in range(1, num_heads + 1)
             if num_heads % hb == 0
             and (((hb * head_dim) % _LANES == 0
                   and (hb * (v_dim or head_dim)) % _LANES == 0)
                  or hb == num_heads)]
    fit = [hb for hb in legal if hb * n_rows <= max_rows]
    return max(fit) if fit else min(legal)


def pages_per_block(page, pps, row_lanes, dtype, v_lanes=None, heads=1,
                    n_rows=1):
    """How many consecutive page-table entries one block covers, from
    the call's shapes and the pools' ``dtype`` alone.

    The positions a block WANTS: one 128-lane tile of scores
    (``ppb * page <= 128``), or ``_STACKED_BLOCK`` where bfloat16 pools
    (``feed_bits`` 16) meet ``n_rows > 1`` query rows stacked on each of
    the ``heads`` K/V heads (``_chunk_call``'s ``q.shape``: decode's
    group of query heads a K/V head, a chunk's or a verify window's rows
    times it), as far as the float32 scores of all the stacks' three
    terms fit ``_SCORE_VMEM_BYTES``.  Then never more than both buffers
    of both pools fit in ``_BLOCK_VMEM_BYTES``, never more than the
    table holds, whole lane tiles of scores where the K-and-V body
    takes more than one, never less than 1 (a page of 128 positions or
    more is a block by itself).  ``pps`` need not be a multiple: the
    last block's missing entries are dead like any other.  ``v_lanes``:
    the V rows' width where it is not K's ``row_lanes``; 0 for a latent
    pool (no V pool: a block of ``_LATENT_BLOCK`` positions, the module
    header)."""
    latent = v_lanes == 0       # one pool: the values are K's own lanes
    tile = max(_LANES // page, 1)   # pages a lane tile of scores
    want = _LANES
    if latent:
        want = _LATENT_BLOCK
    elif feed_bits(dtype) == 16 and n_rows > 1:
        by_scores = _SCORE_VMEM_BYTES // (
            _SPLIT_TERMS * heads * n_rows * 4 * _LANES) * _LANES
        want = max(min(_STACKED_BLOCK, by_scores), _LANES)
    by_vmem = _BLOCK_VMEM_BYTES // (2 * page * (
        row_lanes + (0 if latent else v_lanes or row_lanes))
        * jnp.dtype(dtype).itemsize)
    ppb = min(want // page, by_vmem, pps)
    if ppb > tile and not latent:
        ppb -= ppb % tile
    return max(1, ppb)


def feed_bits(pool_dtype):
    """Width of the K and V operands the kernel's two matmuls take: 16
    where the pools are bfloat16 (the blocks go to the MXU as they lie
    in the pool, the float32 side rides as extra rows), 32 for every
    other pool (float32, and int8 dequantized in fast memory).  The
    pools' dtype alone decides."""
    return 16 if jnp.dtype(pool_dtype) == jnp.bfloat16 else 32


_SPLIT_TERMS = 3    # bfloat16 terms that carry a float32's 24 bits
# ... and what the latent body's query and probabilities ride as: one
# rounding to bfloat16, as every other activation meets a bfloat16 weight
_LATENT_TERMS = 1
_BF16_ROWS = 16     # sublanes of one packed bfloat16 tile


def _bf16_terms(x, n=_SPLIT_TERMS):
    """Float32 ``x`` as ``n`` bfloat16 arrays, largest first, whose sum
    is ``x`` (to bfloat16's rounding of the last): each term rounds what
    the ones before it left."""
    terms = []
    for _ in range(n):
        terms.append(x.astype(jnp.bfloat16))
        x = x - terms[-1].astype(jnp.float32)
    return terms


def _split_rows(rows):
    """Rows one term's group takes in a stacked bfloat16 left operand:
    whole packed tiles, so every group starts on one."""
    return -(-rows // _BF16_ROWS) * _BF16_ROWS


def _join_terms(x, rows, n=_SPLIT_TERMS):
    """The float32 product of a left operand stacked by ``_bf16_terms``:
    its ``n`` row groups added, smallest first."""
    step = _split_rows(rows)
    out = None
    for t in reversed(range(n)):
        group = x[t * step:t * step + rows]
        out = group if out is None else group + out
    return out


def _chunk_kernel(layer_ref, pt_ref, len_ref, slot_len_ref, *rest, sm_scale,
                  page, pps, ppb, n_slots, n_rows, head_dim, v_dim=None,
                  quantized=False, window=None, sinks=False, one_pool=False,
                  v_off=0, terms=_SPLIT_TERMS):
    """One grid step is one SLOT: R query rows (a prefill chunk, a
    speculative t0+draft window, or decode's one) over the slot's live
    blocks of ``ppb`` page-table entries.  Row r of slot s attends
    positions ``t < len_ref[s*R + r]`` — per-row causal masks over one
    shared page table, so shared and partially-filled pages need no
    special casing beyond the mask.  With ``window`` the positions
    below ``len - window`` are masked too, ``slot_lo_ref[s]`` (one more
    scalar-prefetch operand) is the first position any row of the slot
    attends, the walk and the copies start at its block and page, and
    table entry ``j % pps`` holds logical page ``j`` (a ring).

    Refs: q (1, R, H*Dk) and o (1, R, H*Dv) blocks; k/v the whole stacked
    pools, left in HBM; int8 pools' scales (1, positions, H), the slot's
    own, gathered by the caller; ``sinks``: the logit of each stacked row
    (n_stacks, hb*R, 128).  Scratch, per stack of ``hb`` heads: the
    block-diagonal query (hb*R, hb*Dk), running max and denominator
    (hb*R, 128), accumulator (hb*R, hb*Dv); then the two block buffers
    (2, ppb, page, H*D) a pool, one DMA semaphore a buffer, and in SMEM
    which buffer holds the block that is computed next.  Bfloat16 pools
    (``feed_bits`` 16): the query scratch holds the block-diagonal
    query's ``_bf16_terms`` as groups of rows, (n_stacks, 3 * hb*R up to
    whole packed tiles, hb*Dk) in bfloat16, and two more scratch follow:
    the float32 rows the query is stacked in before it is split, and
    each stack's probabilities as groups of rows, (n_stacks, 3 * hb*R
    ..., ppb*page) in bfloat16.  ``one_pool``: there is no V pool and no V
    buffer, the values are ``v_dim`` lanes of the K buffer's rows from
    lane ``v_off`` on (0: a latent row's leading lanes, which the scores
    read too; past the keys: a joint row's second half); ``terms``: how
    many bfloat16 terms the float32 side rides as."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if window is not None:
        slot_lo_ref, *rest = rest
    if one_pool:
        q_ref, k_hbm, *rest = rest
    else:
        q_ref, k_hbm, v_hbm, *rest = rest
    if quantized:
        ks_ref, vs_ref, *rest = rest
    if sinks:
        sink_ref, *rest = rest
    o_ref, qbd_scr, m_scr, l_scr, acc_scr, k_buf, *rest = rest
    if one_pool:
        # one copy of a block serves the scores and the values
        v_buf, pools = k_buf, ((k_hbm, k_buf),)
    else:
        v_buf, *rest = rest
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    sem, cur, *split = rest
    if split:                           # the bfloat16 feed's scratch
        stage_scr, p_scr = split

    s_idx = pl.program_id(0)
    layer = layer_ref[0]
    n_stacks, rows, v_width = acc_scr.shape
    width = qbd_scr.shape[2]
    group = _split_rows(rows)           # rows from a term to the next
    hb = rows // n_rows                 # heads a stack
    block = ppb * page                  # positions a block
    # head (within its stack) that owns each lane of a stack
    lane_head = lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim
    v_lane_head = lane_head if v_width == width else lax.broadcasted_iota(
        jnp.int32, (1, v_width), 1) // v_dim
    # the values' lanes of a V buffer's row: all of it, but of a joint
    # row the half after the keys
    v_row = slice(v_off, v_off + (n_stacks * v_width if v_off
                                  else v_buf.shape[3]))

    def first_block(s):
        """The block a slot's walk starts at."""
        return 0 if window is None else slot_lo_ref[s] // block

    def live_entries(s, b):
        """(first, lo, hi): block ``b`` of slot ``s`` covers the table
        entries from ``first``; those in ``[lo, hi)`` are live."""
        first = b * ppb
        n_live = pl.cdiv(slot_len_ref[s], page)
        lo = first if window is None \
            else jnp.maximum(first, slot_lo_ref[s] // page)
        return first, lo, jnp.minimum(first + ppb, n_live)

    def block_dma(s, b, buf, start):
        """Start, or wait for, the copies of block ``b`` of slot ``s``
        into buffer ``buf``.  Only LIVE pages move, and both sides
        walk the same range, so every started copy is waited for
        exactly once."""
        first, lo, hi = live_entries(s, b)

        def _page(entry, carry):
            # a wait only needs the copy's shape: no table read
            if window is None:
                pid = pt_ref[s * pps + entry] if start else 0
            else:
                pid = pt_ref[s * pps + entry % pps] if start else 0
            for hbm, vmem in pools:
                copy = pltpu.make_async_copy(
                    hbm.at[layer, pid], vmem.at[buf, entry - first],
                    sem.at[buf])
                copy.start() if start else copy.wait()
            return carry

        if not split:
            lax.fori_loop(lo, hi, _page, 0)
            return
        # a block whose every page is live (all but a slot's last and a
        # window's first) needs no loop: its copies start one after the
        # other, and ONE wait a pool takes the bytes of all of them
        whole = hi - lo == ppb

        @pl.when(whole)
        def _whole():
            if start:
                for entry in range(ppb):
                    _page(first + entry, 0)
            else:
                for hbm, vmem in pools:
                    pltpu.make_async_copy(
                        hbm.at[layer, pl.ds(0, ppb)], vmem.at[buf],
                        sem.at[buf]).wait()

        @pl.when(jnp.logical_not(whole))
        def _page_by_page():
            lax.fori_loop(lo, hi, _page, 0)

    @pl.when(s_idx == 0)
    def _first():
        cur[0] = 0
        block_dma(0, first_block(0), 0, start=True)

    if sinks:
        # the sink is one more logit of every row: the running max
        # starts at it and the denominator at its own exp(0)
        m_scr[...] = sink_ref[...]
        l_scr[...] = jnp.ones_like(l_scr)
    else:
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0].astype(jnp.float32) * sm_scale            # (R, H*D)
    for j in range(n_stacks):
        qj = q[:, j * width:(j + 1) * width]
        if split:
            # stacked in float32 rows, then split once a slot: each term
            # is a group of rows of the one left operand (a group's
            # padding rows are never written, and never read back)
            for h in range(hb):
                stage_scr[h * n_rows:(h + 1) * n_rows, :] = jnp.where(
                    lane_head == h, qj, 0.0)
            for t, term in enumerate(_bf16_terms(stage_scr[...], terms)):
                qbd_scr[j, t * group:t * group + rows, :] = term
            continue
        for h in range(hb):
            qbd_scr[j, h * n_rows:(h + 1) * n_rows, :] = jnp.where(
                lane_head == h, qj, 0.0)

    lens = [len_ref[s_idx * n_rows + r] for r in range(n_rows)]
    max_len = slot_len_ref[s_idx]
    # every slot walks at least one block, so the hand-over of the
    # prefetch below never skips a slot; a dead slot's one block moves
    # no page and masks every position
    n_blocks = jnp.maximum(pl.cdiv(max_len, block), 1)
    # stacked row h*R + r carries query row r: its causal length
    query_row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % n_rows
    row_len = jnp.zeros((rows, 1), jnp.int32) + lens[0]
    for r in range(1, n_rows):
        row_len = jnp.where(query_row == r, lens[r], row_len)

    def zero_dead_v(b, buf, last):
        """The bfloat16 feed's form of the guard against ``0 * NaN``:
        the V buffer's dead pages zeroed where they lie, whole packed
        tiles, and only in a block that has any (a slot's last, a
        window's first); in the last live page the positions past the
        slot's length, which hold what the pool held there."""
        first, lo, hi = live_entries(s_idx, b)

        def _zero(entry, carry):
            v_buf[buf, entry - first, :, v_row] = jnp.zeros(
                (page, v_row.stop - v_row.start), v_buf.dtype)
            return carry

        @pl.when(hi - lo < ppb)
        def _dead_pages():
            lax.fori_loop(first, lo, _zero, 0)
            lax.fori_loop(hi, first + ppb, _zero, 0)

        tail = max_len % page

        @pl.when(last & (tail > 0))
        def _tail():
            at = max_len // page - first
            row = lax.broadcasted_iota(jnp.int32, (page, 1), 0)
            v_buf[buf, at, :, v_row] = jnp.where(
                row < tail, v_buf[buf, at, :, v_row].astype(jnp.float32),
                0.0).astype(v_buf.dtype)

    def update_from_bf16(buf, live):
        """One block's online-softmax update of every stack, K and V
        read as they lie in the buffers.  A phase at a time over ALL the
        stacks, every load before the first store: a stack's chain
        (matmul, two lane reductions, matmul) is latency, and the
        stacks' chains overlap only while no store of one stands
        before the loads of the next."""
        nn = (((1,), (0,)), ((), ()))
        nt = (((1,), (1,)), ((), ()))
        stacks = range(n_stacks)
        k = [k_buf[buf, :, :, j * width:(j + 1) * width]
             .reshape(block, width) for j in stacks]
        v = [v_buf[buf, :, :, v_off + j * v_width:v_off + (j + 1) * v_width]
             .reshape(block, v_width) for j in stacks]
        m_prev = [m_scr[j, :, :1] for j in stacks]
        l_prev = [l_scr[j, :, :1] for j in stacks]
        # the split query's three groups of rows meet ONE load of a tile
        s = [jnp.where(live, _join_terms(lax.dot_general(
            qbd_scr[j], k[j], nt, preferred_element_type=jnp.float32),
            rows, terms), _NEG_INF) for j in stacks]
        m_new = [jnp.maximum(m_prev[j], jnp.max(s[j], axis=1, keepdims=True))
                 for j in stacks]
        alpha = [jnp.exp(m_prev[j] - m_new[j]) for j in stacks]
        p = [jnp.exp(s[j] - m_new[j]) for j in stacks]
        l_new = [alpha[j] * l_prev[j] + jnp.sum(p[j], axis=1, keepdims=True)
                 for j in stacks]
        for j in stacks:
            for t, term in enumerate(_bf16_terms(p[j], terms)):
                p_scr[j, t * group:t * group + rows, :] = term
        pv = [_join_terms(lax.dot_general(
            p_scr[j], v[j], nn, preferred_element_type=jnp.float32), rows,
            terms) for j in stacks]
        for j in stacks:
            acc_scr[j] = acc_scr[j] * alpha[j] + pv[j]
            m_scr[j] = jnp.broadcast_to(m_new[j], m_scr.shape[1:])
            l_scr[j] = jnp.broadcast_to(l_new[j], l_scr.shape[1:])

    def _block(b, carry):
        buf = cur[0]
        # the next block (this slot's, or the NEXT slot's first) is in
        # flight while this one is computed
        last = b + 1 == n_blocks
        nxt_s = jnp.where(last, s_idx + 1, s_idx)
        if window is None:
            nxt_b = jnp.where(last, 0, b + 1)
        else:
            nxt_b = jnp.where(last, first_block(
                jnp.minimum(s_idx + 1, n_slots - 1)), b + 1)
        pl.when(nxt_s < n_slots)(
            lambda: block_dma(nxt_s, nxt_b, 1 - buf, start=True))
        block_dma(s_idx, b, buf, start=False)
        cur[0] = 1 - buf

        pos = b * block + lax.broadcasted_iota(jnp.int32, (rows, block), 1)
        live = pos < row_len                               # (rows, block)
        if split:
            if window is not None:
                live = live & (pos >= row_len - window)
            zero_dead_v(b, buf, last)
            update_from_bf16(buf, live)
            return carry
        # a dead page of a live block was never copied: whatever the
        # buffer held there (NaN included) must not reach ``p @ v``
        # through ``0 * v``, so V is zeroed by position (K's scores
        # are replaced by the mask)
        col = b * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        v_live = col < max_len
        if window is not None:
            live = live & (pos >= row_len - window)
            # nor were the pages before the window's first
            v_live = v_live & (col >= slot_lo_ref[s_idx] // page * page)
        for j in range(n_stacks):
            lanes = slice(j * width, (j + 1) * width)
            v_lanes = slice(v_off + j * v_width, v_off + (j + 1) * v_width)
            k = k_buf[buf, :, :, lanes].astype(jnp.float32) \
                .reshape(block, width)
            v = v_buf[buf, :, :, v_lanes].astype(jnp.float32) \
                .reshape(block, v_width)
            if quantized:  # dequant-fused: int8 tile * VMEM scale
                at = pl.ds(pl.multiple_of(b * block, block), block)
                k = k * _scale_lanes(ks_ref, at, j, hb, lane_head)
                v = v * _scale_lanes(vs_ref, at, j, hb, v_lane_head)
            v = jnp.where(v_live, v, 0.0)
            # every stacked head's scores in one matmul: (rows, block)
            s = lax.dot_general(
                qbd_scr[j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(live, s, _NEG_INF)
            m_prev = m_scr[j, :, :1]                       # (rows, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                         # (rows, block)
            l_new = alpha * l_scr[j, :, :1] \
                + jnp.sum(p, axis=1, keepdims=True)
            # p @ v at full width: head h's context is in ITS lanes of
            # row block h (the other lanes are discarded at the flush)
            acc_scr[j] = acc_scr[j] * alpha + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # (rows, width)
            m_scr[j] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[j] = jnp.broadcast_to(l_new, l_scr.shape[1:])
        return carry

    lax.fori_loop(first_block(s_idx), n_blocks, _block, 0)

    for j in range(n_stacks):
        out = jnp.zeros((n_rows, v_width), jnp.float32)
        for h in range(hb):
            blk = slice(h * n_rows, (h + 1) * n_rows)
            l = l_scr[j, blk, :1]
            out = jnp.where(
                v_lane_head == h,
                acc_scr[j, blk, :] / jnp.where(l == 0.0, 1.0, l), out)
        o_ref[0, :, j * v_width:(j + 1) * v_width] = out.astype(o_ref.dtype)


def _scale_lanes(scale_ref, at, stack, hb, lane_head):
    """(block, hb*D) dequant factors for one stack: each head's
    per-position scale column spread over that head's lanes."""
    sc = scale_ref[0, at, stack * hb:(stack + 1) * hb].astype(jnp.float32)
    out = jnp.zeros((sc.shape[0], lane_head.shape[1]), jnp.float32)
    for h in range(hb):
        out = jnp.where(lane_head == h, sc[:, h:h + 1], out)
    return out


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "window", "value_lanes", "value_offset"))
def _chunk_call(q, k_pages, v_pages, layer, page_table, row_lengths,
                k_scales=None, v_scales=None, sinks=None, *, sm_scale,
                interpret, window=None, value_lanes=None,
                value_offset=None):
    """The ``pallas_call``.  ``layer`` is an OPERAND (int32 scalar), so
    a model's layers share one traced and lowered kernel: a program
    pays for the body once, not once a layer.  ``sinks`` [R, H]: the
    logit of each query row and head.  ``v_pages=None``: the values are
    ``value_lanes`` lanes of K's rows (one head; the module header), the
    row's first where ``value_offset`` is None (a latent pool) and those
    from ``value_offset`` on where it is given (a joint pool: the
    K-and-V body on one buffer)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_slots, n_rows, h, d = q.shape
    pps = page_table.shape[1]
    page, hd = k_pages.shape[2:]
    one_pool = v_pages is None
    joint = one_pool and value_offset is not None
    latent = one_pool and not joint
    if joint:
        if k_scales is not None or h != 1 or not value_lanes \
                or not d <= value_offset <= hd - value_lanes:
            raise ValueError(
                f"a joint pool's rows ({hd} lanes) are one unquantized "
                f"head's keys, then its values: q has {h} heads of {d}, "
                f"the values {value_lanes} lanes from {value_offset}")
        v_hd = value_lanes
    elif latent:
        if h != 1 or d > hd or not value_lanes or value_lanes > hd:
            raise ValueError(
                f"a latent pool's rows ({hd} lanes) are one head's: q has "
                f"{h} heads of {d}, the values {value_lanes} lanes")
        # the query meets the pool's row: zeros where the model's row
        # stops short of whole lane tiles
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, hd - d),))
        d, v_hd = hd, value_lanes
    else:
        v_hd = v_pages.shape[3]
    if (hd != h * d and not joint) or v_hd % h:
        raise ValueError(
            f"pool rows are {hd} (K) and {v_hd} (V) lanes wide but q has "
            f"{h} heads of {d}")
    dv = v_hd // h
    # bfloat16 blocks go to the matmuls as they lie in the pool, and the
    # float32 side rides as three groups of bfloat16 rows
    split = feed_bits(k_pages.dtype) == 16 and (
        one_pool or feed_bits(v_pages.dtype) == 16)
    terms = _LATENT_TERMS if latent else _SPLIT_TERMS
    hb = _stack_heads(h, d, n_rows, dv,
                      _MAX_SPLIT_ROWS if split else _MAX_STACK_ROWS)
    n_stacks, rows, width, v_width = h // hb, hb * n_rows, hb * d, hb * dv
    # a joint row's halves are two pools' widths to the rule
    ppb = pages_per_block(page, pps, h * d, k_pages.dtype,
                          0 if latent else v_hd, h, n_rows)
    quantized = k_scales is not None
    row_lengths = row_lengths.astype(jnp.int32)
    prefetch = [layer.reshape(1), page_table.reshape(-1).astype(jnp.int32),
                row_lengths.reshape(-1), row_lengths.max(axis=1)]
    if window is not None:
        # the first position any row of the slot attends
        prefetch.append(jnp.maximum(row_lengths.min(axis=1) - window, 0))

    def slot_block(*shape):
        return pl.BlockSpec((1, *shape), lambda s, *_: (s, 0, 0))

    # the stacked pools stay in HBM; the kernel copies the live pages
    # of one layer itself, so nothing else of a pool ever moves
    pool_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [slot_block(n_rows, h * d), pool_spec] \
        + [pool_spec] * (not one_pool)
    operands = [q.reshape(n_slots, n_rows, h * d), k_pages] \
        + [v_pages] * (not one_pool)
    if quantized:
        # the chip's compiler cuts no page out of a plane H lanes wide
        # in HBM, so a slot's scales are gathered by its table out here
        # (1/D of the pages' bytes) and ride in as one block a slot,
        # padded to whole blocks of positions
        table = jnp.pad(page_table, ((0, 0), (0, -pps % ppb)))
        positions = table.shape[1] * page
        in_specs += [slot_block(positions, h)] * 2
        operands += [sc[layer, table].reshape(n_slots, positions, h)
                     for sc in (k_scales, v_scales)]
    if sinks is not None:
        # stacked row hh*R + r of stack j is query row r of head j*hb+hh
        in_specs.append(pl.BlockSpec((n_stacks, rows, _LANES),
                                     lambda s, *_: (0, 0, 0)))
        operands.append(jnp.broadcast_to(
            sinks.astype(jnp.float32).T.reshape(n_stacks, rows, 1),
            (n_stacks, rows, _LANES)))
    stacked = terms * _split_rows(rows)  # rows of a split operand
    query_scratch = pltpu.VMEM((n_stacks, stacked, width), jnp.bfloat16) \
        if split else pltpu.VMEM((n_stacks, rows, width), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # (layer, flat page table, flat row lengths, widest row a slot
        # [, the window's first position a slot])
        num_scalar_prefetch=len(prefetch),
        grid=(n_slots,),
        in_specs=in_specs,
        out_specs=slot_block(n_rows, v_hd),
        scratch_shapes=[
            query_scratch,
            pltpu.VMEM((n_stacks, rows, _LANES), jnp.float32),  # max
            pltpu.VMEM((n_stacks, rows, _LANES), jnp.float32),  # denom
            pltpu.VMEM((n_stacks, rows, v_width), jnp.float32),  # acc
            pltpu.VMEM((2, ppb, page, hd), k_pages.dtype),      # K blocks
        ] + ([] if one_pool else [
            pltpu.VMEM((2, ppb, page, v_hd), v_pages.dtype),    # V blocks
        ]) + [
            pltpu.SemaphoreType.DMA((2,)),     # one a buffer, K and V
            pltpu.SMEM((1,), jnp.int32),       # the buffer computed next
        ] + ([
            pltpu.VMEM((rows, width), jnp.float32),  # query, unsplit
            pltpu.VMEM((n_stacks, stacked, ppb * page),
                       jnp.bfloat16),                # probabilities
        ] if split else []),
    )
    kern = functools.partial(_chunk_kernel, sm_scale=sm_scale, page=page,
                             pps=pps, ppb=ppb, n_slots=n_slots,
                             n_rows=n_rows, head_dim=d, v_dim=dv,
                             quantized=quantized, window=window,
                             sinks=sinks is not None, one_pool=one_pool,
                             v_off=value_offset or 0, terms=terms)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, n_rows, v_hd), q.dtype),
        # a slot hands its successor's first block over in flight
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=LATENT_KERNEL_NAME if latent else KERNEL_NAME
        if window is None else WINDOW_KERNEL_NAME,
    )(*prefetch, *operands)
    return out.reshape(q.shape[:-1] + (dv,))


def paged_chunk_attention(q, k_pages, v_pages, page_table, row_lengths,
                          *, layer=0, sm_scale=None, use_pallas="auto",
                          interpret=False, k_scales=None,
                          v_scales=None, window=None, sinks=None,
                          value_lanes=None, value_offset=None):
    """Multi-row attention off the page pool — R query rows per slot.

    q [S,R,H,D]; k_pages [L,P,page,Hkv*D] and v_pages [L,P,page,Hkv*Dv]
    (the stacked pools; ``layer``
    is the static layer to read; the output is [S,R,H,Dv]); page_table
    [S,pps] i32; row_lengths
    [S,R] i32 — row r of slot s attends positions
    ``t < row_lengths[s, r]``, with ``window`` only the last ``window``
    of them, the table then read as a ring (the module header: logical
    page j at entry ``j % pps``; every attended position must lie in the
    slot's last ``pps`` pages).  ``sinks`` [H] (or [R, H]): a logit a
    query head that joins the softmax's denominator.  Grouped-query
    heads: a pool row of
    ``Hkv*D`` lanes with ``Hkv < H`` makes query head i read K/V head
    ``i // (H // Hkv)``; the group's heads ride as extra rows of their
    K/V head, so a slot's pages are still read once (one-token decode of
    64 query over 8 K/V heads is the kernel at R=8, H=8).  Serves both
    tentpole callers
    in serving/decode.py: chunked prefill (R = chunk rows, one slot at
    a time) and speculative-decode verification (R = 1 + draft window,
    every slot jointly).  The reference path broadcasts each slot's
    gathered K/V across its rows and reuses
    ``decode_attention_reference`` VERBATIM — the single masked-softmax
    formulation at one width that keeps every cache path bitwise-equal
    to the full-recompute oracle.  ``use_pallas`` dispatch and the
    quantized ``k_scales``/``v_scales`` [L,P,page,H] contract match
    ``paged_decode_attention``.  ``v_pages=None`` with ``value_lanes``:
    a latent pool (the module header), ONE row a position of at least
    D lanes that every query head reads, whose first ``value_lanes``
    lanes are the values (the output is [S,R,H,value_lanes]).  With
    ``value_offset`` too: a JOINT pool, ONE K/V head's keys in a row's
    first D lanes and its values in the ``value_lanes`` from
    ``value_offset`` on, what `serving/kv_cache.py` keeps where a cache
    has one K/V head of whole lane tiles; every answer is the two-pool
    call's on the same K and V, bit for bit.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s, r, h, d = q.shape
    one_pool = v_pages is None      # a latent pool, or a joint one
    kv_heads = 1 if one_pool else k_pages.shape[-1] // d
    if sinks is not None and sinks.ndim == 1:
        sinks = jnp.broadcast_to(sinks, (r, h))
    if kv_heads != h:
        # grouped-query heads: the G query heads that share a K/V head
        # ride as G more ROWS of that head's slot, so the kernel (and
        # the reference) read a slot's pages once for all of them
        if h % kv_heads or (k_pages.shape[-1] % d and not one_pool):
            raise ValueError(
                f"q has {h} heads of {d} but the pool rows hold "
                f"{k_pages.shape[-1]} lanes: not a whole group a K/V head")
        g = h // kv_heads
        rows = q.reshape(s, r, kv_heads, g, d).transpose(0, 1, 3, 2, 4) \
            .reshape(s, r * g, kv_heads, d)
        if sinks is not None:
            sinks = sinks.reshape(r, kv_heads, g).transpose(0, 2, 1) \
                .reshape(r * g, kv_heads)
        out = paged_chunk_attention(
            rows, k_pages, v_pages, page_table,
            jnp.repeat(row_lengths, g, axis=1), layer=layer,
            sm_scale=sm_scale, use_pallas=use_pallas, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales, window=window,
            sinks=sinks, value_lanes=value_lanes,
            value_offset=value_offset)
        return out.reshape(s, r, g, kv_heads, -1).transpose(0, 1, 3, 2, 4) \
            .reshape(s, r, h, -1)
    if _kernel_asked(use_pallas):
        return _chunk_call(q, k_pages, v_pages, jnp.int32(layer),
                           page_table, row_lengths, k_scales, v_scales,
                           sinks, sm_scale=float(sm_scale),
                           interpret=interpret, window=window,
                           value_lanes=value_lanes,
                           value_offset=value_offset)
    offset = None
    if window is not None:
        # the ring read in logical order: its pps entries hold the
        # slot's LAST pps pages, the oldest of them at position offset
        pps, page = page_table.shape[1], k_pages.shape[2]
        first = jnp.maximum(
            -(-row_lengths.max(axis=1) // page) - pps, 0)       # [S]
        page_table = jnp.take_along_axis(
            page_table, (first[:, None] + jnp.arange(pps)) % pps, axis=1)
        offset = jnp.repeat(first * page, r)
    k = _gather_dequant(k_pages, k_scales, layer, page_table, h)
    if one_pool:
        first = value_offset or 0
        k, v = k[..., :d], k[..., first:first + value_lanes]
    else:
        v = _gather_dequant(v_pages, v_scales, layer, page_table, h)
    kr = jnp.broadcast_to(k[:, None], (s, r) + k.shape[1:]) \
        .reshape(s * r, *k.shape[1:])
    vr = jnp.broadcast_to(v[:, None], (s, r) + v.shape[1:]) \
        .reshape(s * r, *v.shape[1:])
    out = decode_attention_reference(
        q.reshape((s * r,) + q.shape[2:]), kr, vr,
        row_lengths.reshape(-1), sm_scale=sm_scale, window=window,
        sinks=None if sinks is None else jnp.tile(sinks, (s, 1)),
        offset=offset)
    return out.reshape(s, r, h, -1)
