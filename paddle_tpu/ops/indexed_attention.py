"""Attention over the positions a learned INDEXER selects (DeepSeek's
sparse attention; ``serving/mixers.py`` ``IndexedMixer`` is the one
caller): every cached position keeps a small index key, a query scores
all live keys

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])

and attends, with a softmax over them ALONE, the ``topk`` positions of
largest ``I[t, .]`` (every live position while there are no more than
``topk``).  Three operations, each plain ``jax.numpy`` here (no kernel
computes any of them yet; ``PERF.md`` has what each costs on the chip):

* the scores (``index_scores``): keys at the pool's dtype against the
  query rounded to it, float32 accumulation; ReLU, the heads' weights and
  their sum in float32 on the vector unit (a float32 matmul would round
  its operands to bfloat16 on the chip);
* the selection, EXACT, ties to the LOWER position, in two forms that
  choose the same set: ``select_top`` hands a step the positions
  themselves (one stable sort, so equal scores come lower index
  first); ``select_mask`` hands a block of a prompt's rows a mask a
  pair, from the k-th largest score found by bisection over the float's
  bits and, among the scores equal to it, a cut by position found the
  same way (no sort of thousands of rows by thousands of keys);
* the attention: over rows gathered by position (``attend_rows``, the
  step's); a prompt's runs where every prompt's does
  (``pallas_decode_attention.grouped_causal_attention(select=)``: the
  flash kernel or the plain blocks, under the mask).

A position that is not live (past a slot's length, a recycled page's
stale row) is masked BEFORE the selection: it is never chosen while a
live one is left, and where fewer than ``topk`` are live the ones chosen
beyond them come back marked not ``ok`` and weigh exactly zero.
"""
from __future__ import annotations

import math

_NEG_INF = -1e30


def index_scores(q, w, keys, relu=None):
    """``q [..., H, D]`` and ``w [..., H]`` (float32) against ``keys
    [..., N, D]`` (the pool's dtype; the leading shapes agree or
    ``keys`` has none) -> ``I [..., N]`` float32.  ``relu``: what stands
    between a head's products and their weighted sum (``jax.nn.relu``)."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("...hd,...nd->...hn", q.astype(keys.dtype), keys,
                   preferred_element_type=jnp.float32)
    s = (relu or jax.nn.relu)(s)
    return jnp.sum(s * w.astype(jnp.float32)[..., None], axis=-2)


def select_top(scores, lengths, k, carry=()):
    """The step's form: ``scores [S, N]`` of which slot s's first
    ``lengths[s]`` are live -> (``positions [S, k']`` int32, ``ok [S,
    k']``, each of ``carry`` (``[S, N]`` arrays) at those positions)
    with ``k' = min(k, N)``: the live positions of largest score, equal
    scores the lower position first; entries beyond the live count are
    not ``ok``.  One stable sort of the negated scores with the positions
    and what is carried behind them (what ``lax.top_k`` lowers to on the
    chip, which hands back the positions alone: a gather of thousands of
    scalars by them costs as much as the sort)."""
    import jax
    import jax.numpy as jnp

    n = scores.shape[-1]
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), scores.shape)
    live = pos < lengths[..., None]
    _, pos, *carried = jax.lax.sort(
        (jnp.where(live, -scores, jnp.inf), pos) + tuple(carry),
        dimension=-1, is_stable=True, num_keys=1)
    k = min(k, n)
    pos = pos[..., :k]
    return (pos, pos < lengths[..., None]) + tuple(
        c[..., :k] for c in carried)


def _ordered_bits(scores):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    import jax
    import jax.numpy as jnp

    b = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7fffffff), b)
    return jax.lax.bitcast_convert_type(b, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def _largest_passing(bits, passes):
    """The largest uint32 ``x [R]`` of ``bits`` bits with ``passes(x)``
    (bool ``[R]``, true at 0 and monotone: once false it stays false as
    x grows), a bit a pass from the top."""
    import jax
    import jax.numpy as jnp

    def one(i, x):
        cand = x | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(passes(cand), cand, x)

    rows = jax.eval_shape(passes, jnp.zeros((), jnp.uint32)).shape
    return jax.lax.fori_loop(0, bits, one, jnp.zeros(rows, jnp.uint32))


def select_mask(scores, live, k):
    """The prompt's form: ``scores [R, N]`` with ``live [R, N]`` (bool)
    -> bool ``[R, N]``, true at the row's ``k`` live positions of largest
    score, equal scores the lower position first; every live position of
    a row that has no more than ``k``.  Exactly ``select_top``'s set."""
    import jax.numpy as jnp

    n = scores.shape[-1]
    u = jnp.where(live, _ordered_bits(scores), jnp.uint32(0))
    # the k-th largest: the largest x that k of the row's keys reach
    kth = _largest_passing(32, lambda x: jnp.sum(
        u >= x[..., None], axis=-1, dtype=jnp.int32) >= k)
    above = live & (u > kth[..., None])
    equal = live & (u == kth[..., None])
    # of the scores equal to it, the lowest positions up to k in all:
    # the largest p that fewer than ``need`` equal positions lie under
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    pos = jnp.arange(n, dtype=jnp.uint32)
    cut = _largest_passing(max(n - 1, 1).bit_length(), lambda p: jnp.sum(
        equal & (pos < p[..., None]), axis=-1, dtype=jnp.int32) < need)
    return above | (equal & (pos <= cut[..., None]))


def pack_bits(mask):
    """bool ``[R, N]`` (N a multiple of 8) -> uint8 ``[R, N / 8]``: bit
    b of byte j is column ``b * N / 8 + j`` (eight slices along the
    lanes, no byte built from neighbouring columns)."""
    import jax.numpy as jnp

    n8 = mask.shape[-1] // 8
    out = jnp.zeros(mask.shape[:-1] + (n8,), jnp.uint8)
    for b in range(8):
        out = out | (mask[..., b * n8:(b + 1) * n8].astype(jnp.uint8)
                     << jnp.uint8(b))
    return out


def unpack_bits(packed):
    """``pack_bits``'s inverse, in numpy (the readers' side)."""
    import numpy as np

    packed = np.asarray(packed)
    return np.concatenate([(packed >> b) & 1 for b in range(8)],
                          axis=-1).astype(bool)


def attend_rows(q, k_rows, v_rows, ok, kv_heads, sm_scale=None):
    """The step's attention: ``q [S, H, D]`` over each slot's gathered
    rows ``k_rows [S, K, Hkv * D]`` / ``v_rows [S, K, Hkv * Dv]`` (heads
    folded along the lanes, as a pool's row lies), of which ``ok [S, K]``
    are a live position's -> ``[S, H, Dv]`` float32.  Query head i reads
    K/V head ``i // (H / Hkv)``.  Each gathered array meets ONE product:
    a query head is laid over all the row's lanes with zeros outside its
    K/V head's (exact zeros in the sums), and of the context's lanes a
    head keeps its own K/V head's.  Rows sliced a head would be formed
    again for every slice: the compiler re-runs the gather, a third of a
    millisecond each, rather than keep 32 MB alive (PERF.md, PR 65)."""
    import jax
    import jax.numpy as jnp

    s_, h, d = q.shape
    g = h // kv_heads
    dv = v_rows.shape[-1] // kv_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    dt = k_rows.dtype
    own = jnp.eye(kv_heads, dtype=jnp.float32)
    wide = jnp.einsum("sjgd,jk->sjgkd", q.reshape(s_, kv_heads, g, d), own)
    s = jnp.einsum("shl,snl->shn",
                   wide.reshape(s_, h, kv_heads * d).astype(dt), k_rows,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(ok[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shn,snl->shl", p.astype(dt), v_rows,
                     preferred_element_type=jnp.float32)
    return jnp.einsum("sjgkd,jk->sjgd",
                      out.reshape(s_, kv_heads, g, kv_heads, dv),
                      own).reshape(s_, h, dv)
