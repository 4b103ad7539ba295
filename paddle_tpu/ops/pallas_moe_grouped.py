"""The held experts of MANY rows as a grouped matmul over the (row, held
expert) pairs that have a weight, sorted by expert.

``moe_ops.moe_share_ffn`` at a prefill's rows: the dense form multiplies
every row by every held expert and most products by zero; here a row
meets only the experts it chose.  The pairs are laid out expert by
expert, each expert's run padded to whole tiles of ``default_tiles`` rows,
so a tile belongs to ONE expert; two Pallas kernels walk the tiles.
The first runs a tile's rows through its expert's gate and up columns
and multiplies the pair's weight in before the cast.  The second runs
the down projection a block of columns at a time with ALL rows of the
result resident, and adds each live pair's row to its row there: a
row's partial results are summed in float32 in the order of its
experts, and there is no plane of pairs x D and no scatter.  Tiles past
the last live pair are skipped: their index maps stand still, so nothing
is copied for them either.
The weights are read where they lie: expert j is the COLUMN block
``j*F:(j+1)*F`` of ``w_gate`` / ``w_up`` ``[D, n_held*F]`` and the row
block of ``w_down`` ``[n_held*F, D]``; no operand is transposed or
copied.

Dropless under any imbalance with a bounded buffer: the sorted buffer
holds ``pairs_a_row`` pairs a row, a function of the call (what a row
CAN choose where the chip holds every expert of the layer: ``top_k``,
exactly; ``PAIRS_A_ROW`` for a share of them), and a call whose rows
chose more runs the same two kernels again over the next pairs (a
``while_loop`` over passes, each of which reads the experts' weights
again; one pass under any routing near uniform, and always one where
every expert is held).

The LAYOUT (``layout_candidates``, ``layout_pass``: from ``local`` to
the five arrays the kernels are fed) costs what the pairs a call CAN
make cost, not rows x held experts.  The chip walks a scatter's updates
one after another, the ones sent past the buffer's end too: 4.6 ns an
update of one word, 3.2-3.5 ns one of two words, at every shape
(``tools/sweep_moe_layout.py`` on the v5e, PERF.md PR 55; until then two
scatters of ``rows * n_held`` updates each were 0.61 of the layout's
0.64 ms at 2,048 rows over 32 experts, of which 5,900 landed).  So a
row's candidates are taken first, the ``min(top_k, n_held)`` entries at
most that can hold a weight (a masked sum over the held experts: no
gather, no ``lax.top_k``: those add 0.25 ms there), and ONE
scatter places row and weight together: ``rows * min(top_k, n_held)``
updates of two words (gauge ``moe_grouped_layout_updates``, set when a
call is traced).  The running count stays ONE ``cumsum`` over all
entries: 1 us there, a scan along the rows an expert at a time 12.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_share_ffn", "default_tiles", "sorted_rows",
           "pairs_a_row", "layout_candidates", "layout_pass", "pass_tiles",
           "GATE_UP_KERNEL_NAME", "DOWN_KERNEL_NAME"]

GATE_UP_KERNEL_NAME = "moe_grouped_gate_up"
DOWN_KERNEL_NAME = "moe_grouped_down"
_LANES = 128
_VMEM_LIMIT = 96 * 1024 * 1024
# pairs a row the sorted buffer holds for a SHARE of a layer's experts:
# such cells' rows choose 0.25-1 of the held experts each, and at a
# skewed router's worst seen four times that.  A chip that holds every
# expert takes the exact number instead (``pairs_a_row``)
PAIRS_A_ROW = 2


def _round_up(x, m):
    return -(-x // m) * m


def _col_tile(width):
    """The widest of 512 / 256 / 128 columns that divides ``width`` (a
    width none divides goes whole: small sizes, interpret mode)."""
    return next((t for t in (512, 256, _LANES) if width % t == 0), width)


def _all_held(n_held, top_k, num_experts):
    return top_k is not None and num_experts is not None \
        and n_held == num_experts


def pairs_a_row(top_k, num_experts, n_held):
    """Pairs a row the sorted buffer holds for a call over ``n_held``
    held experts of a layer that routes ``top_k`` of ``num_experts``
    (None: not said).  Where the chip holds EVERY expert a live row
    makes exactly ``top_k`` pairs: no row can make more, none makes
    fewer, so the buffer holds them all and one pass does.  A share's
    rows make ``top_k * n_held / num_experts`` on average and up to
    ``top_k`` each: ``PAIRS_A_ROW``, with further passes for the
    rest."""
    if _all_held(n_held, top_k, num_experts):
        return min(int(top_k), int(n_held))
    return PAIRS_A_ROW


def default_tiles(rows, n_held, top_k=None, num_experts=None):
    """Rows a tile for a call of ``rows`` rows over ``n_held`` experts:
    128 (the MXU's side), or 256 where the pairs an expert gets would
    fill more than one such tile (a tile is computed whole however few
    of its rows hold a pair: on the chip 512 lost to 256 at 4,096 rows
    over 8 experts, and 256 to 128 at 2,048 over 16).  For a share of a
    layer's experts that is judged by even ``rows / n_held`` pairs an
    expert; where every expert is held, by what an even routing gives
    one: ``rows * top_k / n_held``."""
    if _all_held(n_held, top_k, num_experts):
        return 256 if rows * top_k > 128 * n_held else 128
    return 256 if rows > 128 * n_held else 128


def sorted_rows(rows, n_held, top_k=None, num_experts=None):
    """Rows of the sorted buffer of such a call: ``pairs_a_row`` pairs a
    row in whole tiles, and a tile more an expert, since every expert's
    run may end a tile early.  A pass computes as many of its tiles as
    hold a pair, whole."""
    tm = default_tiles(rows, n_held, top_k, num_experts)
    return _round_up(pairs_a_row(top_k, num_experts, n_held) * rows, tm) \
        + n_held * tm


def _down_cols(rows, f, d):
    """Columns of the result resident at a time in the down kernel: all
    its rows by as many columns as keep that block of float32 and the
    expert's block of ``w_down`` within 8 MiB each (a tile's activations
    are read again for every block of columns, so wide blocks; each
    block is double-buffered)."""
    def fits(tn):
        return max(2 * rows, f) * tn * 2 <= 8 << 20

    if d % _LANES == 0:
        # the widest whole-lane divisor of ``d`` that fits (7,168 is 56
        # lane tiles: halving it leaves the lanes at 224)
        tiles = d // _LANES
        return _LANES * next(
            k for k in range(tiles, 0, -1)
            if tiles % k == 0 and (k == 1 or fits(_LANES * k)))
    tn = d
    while tn > _LANES and tn % 2 == 0 and not fits(tn):
        tn //= 2
    return tn


def _gate_up_kernel(tile_expert, n_active, x_ref, ws_ref, wg_ref, wu_ref,
                    act_ref):
    """One tile's rows through one block of its expert's gate and up
    columns; the pair's weight goes in before the cast."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) < n_active[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        act_ref[...] = (jax.nn.silu(gate) * up * ws_ref[...]).astype(
            act_ref.dtype)


def _down_kernel(tile_expert, n_active, slot_row, tile_live, act_ref,
                 wd_ref, acc_ref, out_ref, y_ref, *, tm):
    """One tile's down projection, each of its live rows then added to
    its row of the result: the block of ``out`` (all rows, one block of
    columns) stays where it is while the tiles go by."""
    import jax.experimental.pallas as pl

    m = pl.program_id(1)

    @pl.when(m == 0)
    def _():
        out_ref[...] = acc_ref[...]

    @pl.when(m < n_active[0])
    def _():
        y_ref[...] = jnp.dot(act_ref[...], wd_ref[...],
                             preferred_element_type=jnp.float32)

        def add_row(i, carry):
            at = pl.ds(slot_row[m * tm + i], 1)
            out_ref[at, :] = out_ref[at, :] + y_ref[pl.ds(i, 1), :]
            return carry

        lax.fori_loop(0, tile_live[m], add_row, 0)


def _clamped(n_active, m):
    """Tile ``m``, or the last live tile where ``m`` is behind it: a
    dead tile's blocks are the ones already there, and the pipeline
    copies nothing for it."""
    return jnp.minimum(m, jnp.maximum(n_active[0] - 1, 0))


def _gate_up_call(x_sorted, w_sorted, tile_expert, n_active, w_gate, w_up,
                  *, n_held, tm, interpret):
    """``act [M, F]``: a tile's rows through ITS expert's gate and up
    columns, times the pairs' weights, in the weights' dtype."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m_rows, d = x_sorted.shape
    f = w_gate.shape[1] // n_held
    tn = _col_tile(f)
    cols = f // tn

    def rows_of(m, n, te, na):
        return _clamped(na, m), 0

    def columns(m, n, te, na):          # a dead tile: the last block
        return jnp.where(m < na[0], n, cols - 1)

    def expert_cols(m, n, te, na):      # expert j is a COLUMN block
        return 0, te[_clamped(na, m)] * cols + columns(m, n, te, na)

    return pl.pallas_call(
        _gate_up_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m_rows // tm, cols),
            in_specs=[pl.BlockSpec((tm, d), rows_of),
                      pl.BlockSpec((tm, 1), rows_of),
                      pl.BlockSpec((d, tn), expert_cols),
                      pl.BlockSpec((d, tn), expert_cols)],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda m, n, te, na: (
                    _clamped(na, m), columns(m, n, te, na)))),
        out_shape=jax.ShapeDtypeStruct((m_rows, f), w_gate.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=GATE_UP_KERNEL_NAME,
    )(tile_expert, n_active, x_sorted, w_sorted, w_gate, w_up)


def _down_call(act, acc, tile_expert, n_active, slot_row, tile_live,
               w_down, *, tm, interpret):
    """``acc [rows, D]`` plus every live pair's down projection, added
    to the pair's row: a block of columns at a time, all rows of it
    resident while the tiles go by (no ``[M, D]`` plane, no scatter)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m_rows, f = act.shape
    rows, d = acc.shape
    tn = _down_cols(rows, f, d)

    def block(n, m, *_):
        return 0, n

    return pl.pallas_call(
        functools.partial(_down_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(d // tn, m_rows // tm),
            in_specs=[
                pl.BlockSpec((tm, f), lambda n, m, te, na, *_: (
                    _clamped(na, m), 0)),
                # expert j is a ROW block of w_down
                pl.BlockSpec((f, tn), lambda n, m, te, na, *_: (
                    te[_clamped(na, m)], n)),
                pl.BlockSpec((rows, tn), block)],
            out_specs=pl.BlockSpec((rows, tn), block),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=DOWN_KERNEL_NAME,
    )(tile_expert, n_active, slot_row, tile_live, act, w_down, acc)


class Candidates(NamedTuple):
    """``layout_candidates``' result, what ``layout_pass`` reads."""
    order: jax.Array        # [n_held, rows] int32
    nth: Optional[jax.Array]    # [n_held, rows] int32
    bits: jax.Array         # [k, rows] int32
    ends: jax.Array         # [n_held] int32


def layout_candidates(local, top_k=None):
    """What every pass's layout reads of ``local [rows, n_held]``, formed
    once a call -> ``Candidates``.  The pairs expert by
    expert, a row after the rows before it, are the non-zero entries of
    ``local``'s transpose in the order they lie: ``order [n_held, rows]``
    is an entry's running count there (pair p reads p + 1; 0: no pair)
    and ``ends [n_held]`` the count at each expert's last row.  A row
    has a weight for ``k = min(top_k, n_held)`` experts at most (every
    entry where ``top_k`` is None): its CANDIDATES, the only entries a
    pass's scatter is handed.  ``nth [n_held, rows]`` says which of its
    row's candidates an entry is (1 .. k; 0: none, and None where every
    entry is one), ``bits [k, rows]`` are the candidates' float32
    weights as the words the scatter moves."""
    from ..monitor import stat_set

    rows, n_held = local.shape
    k = n_held if top_k is None else min(int(top_k), n_held)
    # at trace time: the updates one call of the layout walks
    stat_set("moe_grouped_layout_updates", k * rows)
    weight = local.T.astype(jnp.float32)
    hit = weight != 0.0
    count = jnp.cumsum(hit.reshape(-1), dtype=jnp.int32).reshape(
        n_held, rows)
    order, ends = jnp.where(hit, count, 0), count[:, -1]
    bits = lax.bitcast_convert_type(weight, jnp.int32)
    if k == n_held:
        return Candidates(order, None, bits, ends)
    nth = jnp.where(hit, jnp.cumsum(hit, axis=0, dtype=jnp.int32), 0)
    return Candidates(order, nth, _candidates(bits, nth, k), ends)


def _candidates(plane, nth, k):
    """``plane [n_held, rows] -> [k, rows]``: each row's entries that are
    its 1st .. k-th candidate, 0 where it has fewer (a sum with ONE term
    that is not zero)."""
    if nth is None:
        return plane
    which = lax.broadcasted_iota(jnp.int32, (k, 1, 1), 0) + 1
    return jnp.sum(jnp.where(nth[None] == which, plane[None], 0), axis=1)


def pass_tiles(ends, c, *, tm, m_rows):
    """Pass ``c``'s share of every expert's run, from the experts'
    ``ends`` -> ``(lo, hi, shift, tile_expert, tile_live, n_active)``:
    the pass takes pairs ``lo <= p < hi``, pair ``p`` of expert ``e``
    goes to slot ``shift[e] + p`` (each run from a tile's first slot),
    and a tile belongs to ``tile_expert`` with ``tile_live`` live rows;
    ``n_active [1]`` tiles hold a pair."""
    n_held = ends.shape[0]
    cap = m_rows - n_held * tm
    starts, pairs = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]]), \
        ends[-1]
    tile_at = jnp.arange(m_rows // tm, dtype=jnp.int32) * tm
    lo = c * cap
    hi = jnp.minimum(lo + cap, pairs)
    first = jnp.clip(starts, lo, hi)            # this pass's share of
    size = jnp.clip(ends, lo, hi) - first       # every expert's run
    padded = _round_up(size, tm)
    p_end = jnp.cumsum(padded)
    p_start = p_end - padded
    tile_expert = jnp.minimum(jnp.searchsorted(
        p_end, tile_at, side="right"), n_held - 1).astype(jnp.int32)
    tile_live = jnp.clip(
        size[tile_expert] - (tile_at - p_start[tile_expert]), 0, tm)
    return (lo, hi, p_start - first, tile_expert, tile_live,
            (p_end[-1] // tm).reshape(1))


def layout_pass(cand, c, *, tm, m_rows):
    """Pass ``c``'s sorted buffer of ``m_rows`` slots in tiles of ``tm``
    -> ``(slot_row, slot_weight [m_rows], tile_expert, tile_live
    [m_rows // tm], n_active [1])``: pairs ``c * cap ...`` of
    ``layout_candidates``' order, each expert's run from a tile's first
    slot in ascending row order; a slot no pair takes repeats row 0 with
    the weight zero."""
    order, nth, bits, ends = cand
    k, rows = bits.shape
    lo, hi, shift, tile_expert, tile_live, n_active = pass_tiles(
        ends, c, tm=tm, m_rows=m_rows)
    # every candidate that is a pair of this pass to its slot, every
    # other past the buffer's end, each to a place of its own (the
    # scatter is told its indices are unique): ONE scatter of k x rows
    # updates of two words, the row beside the weight's bits
    place = _candidates(
        jnp.where((order > lo) & (order <= hi), shift[:, None] + order, 0),
        nth, k).reshape(-1)
    slot = jnp.where(place > 0, place - 1,
                     m_rows + jnp.arange(k * rows, dtype=jnp.int32))
    words = jnp.stack(
        [lax.broadcasted_iota(jnp.int32, (k, rows), 1), bits], axis=-1)
    taken = jnp.zeros((m_rows, 2), jnp.int32).at[slot].set(
        words.reshape(-1, 2), mode="drop", unique_indices=True)
    return (taken[:, 0], lax.bitcast_convert_type(taken[:, 1], jnp.float32),
            tile_expert, tile_live, n_active)


@functools.partial(jax.jit, static_argnames=("interpret", "top_k",
                                             "num_experts"))
def grouped_share_ffn(h, local, w_gate, w_up, w_down, *, interpret=False,
                      top_k=None, num_experts=None):
    """``moe_ops.moe_share_ffn``'s result for rows ``h [..., D]`` and
    weights ``local [..., n_held]``, computed over the pairs with a
    non-zero weight only -> ``(out [..., D] float32, pairs, passes)``:
    ``pairs`` (int32) is how many pairs there were, ``passes`` how many
    times the sorted buffer (``sorted_rows``) was filled and walked;
    ``top_k`` / ``num_experts`` (static, the model's own routing) size
    the buffer and the tiles where every expert is held, and ``top_k``
    the layout's work: a row of ``local`` has at most ``min(top_k,
    n_held)`` non-zero entries, as a top-k router's rows have.
    Jitted: a model's layers share one traced and lowered call (six
    layers' calls of a prefill program trace and lower in 0.1 s, not
    0.5: host time of every set-up, timed on the CPU)."""
    n_held = local.shape[-1]
    d = h.shape[-1]
    x = h.reshape(-1, d).astype(w_gate.dtype)
    rows = x.shape[0]
    tm = default_tiles(rows, n_held, top_k, num_experts)
    m_rows = sorted_rows(rows, n_held, top_k, num_experts)
    cap = m_rows - n_held * tm

    cand = layout_candidates(local.reshape(rows, n_held), top_k)
    pairs = cand.ends[-1]
    passes = -(-pairs // cap)

    def one_pass(carry):
        c, out = carry
        slot_row, slot_weight, tile_expert, tile_live, n_active = \
            layout_pass(cand, c, tm=tm, m_rows=m_rows)
        act = _gate_up_call(
            x[slot_row], slot_weight[:, None], tile_expert, n_active,
            w_gate, w_up, n_held=n_held, tm=tm, interpret=interpret)
        return c + 1, _down_call(act, out, tile_expert, n_active, slot_row,
                                 tile_live, w_down, tm=tm,
                                 interpret=interpret)

    _, out = lax.while_loop(
        lambda carry: carry[0] < passes, one_pass,
        (jnp.int32(0), jnp.zeros((rows, d), jnp.float32)))
    return out.reshape(*h.shape[:-1], d), pairs, passes
