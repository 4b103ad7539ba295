"""The held experts of FEW rows as one kernel over the experts some row
chose.

``moe_ops.moe_share_ffn`` at a decode step's rows on a chip that holds
few experts of many: the dense form streams every held expert's weights
and multiplies most of them by zero; here the list of HIT experts (some
row's weight for it is not zero) is formed on the device, handed to the
kernel as scalar prefetch, and the grid walks that list.  Every row
still meets every hit expert, its weight (zero where it did not choose
it) going in before the cast, as in the dense form: the same products,
float32 sums, only the terms that are exactly zero left out.  No pair
is sorted, no row gathered.

One call a layer: grid (position in the list, the expert's blocks).  An
expert's blocks are first ``D / tk`` blocks of ``tk`` rows of its gate
and up columns at their WHOLE width (runs of ``2 F`` bytes), the two
sums held in VMEM, then ``F / tf`` blocks of ``tf`` whole rows of
``w_down``, added into the result, which stays resident from the first
grid step to the last.  While one phase runs the other's index map
stands still, and past the last hit expert all of them do
(``pallas_moe_grouped._clamped``'s pattern): nothing is copied for an
expert no row chose, and the body is skipped.
The weights are read where they lie: expert j is the COLUMN block
``j*F:(j+1)*F`` of ``w_gate`` / ``w_up`` ``[D, n_held*F]`` and the row
block of ``w_down`` ``[n_held*F, D]``; no operand is transposed, sliced
or copied in front of the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["hit_share_ffn", "hit_blocks", "HIT_KERNEL_NAME"]

HIT_KERNEL_NAME = "moe_hit_experts"
_LANES = 128
_SUBLANES = 16      # a bf16 tile's
# bytes of one block of a weight (each is double-buffered; gate and up
# go together).  On the chip, alone, at 64 rows x 12 experts of 2,048 on
# 7,168 (PERF.md, PR 48): blocks of 2-8 MiB and 4-16 MiB read within 1 %
# of each other, so the smallest: what the call asks of VMEM it takes
# from the weights XLA keeps prefetched there for the step's other layers
_GATE_UP_BLOCK = 2 << 20
_DOWN_BLOCK = 4 << 20


def _largest_block(width, most):
    """The largest whole-lane divisor of ``width`` that is <= ``most``
    (``width`` a multiple of 128; at least one lane tile)."""
    tiles = width // _LANES
    return _LANES * max(k for k in range(1, tiles + 1)
                        if tiles % k == 0 and (k == 1 or _LANES * k <= most))


def hit_blocks(d, f, itemsize=2):
    """``(tk, tf)``: rows of ``D`` a gate/up block and rows of ``F`` a
    down block, from the widths alone."""
    return (_largest_block(d, _GATE_UP_BLOCK // (f * itemsize)),
            _largest_block(f, _DOWN_BLOCK // (d * itemsize)))


def _kernel(experts, n_hit, x_ref, loc_ref, wg_ref, wu_ref, wd_ref,
            out_ref, gate_ref, up_ref, act_ref, *, kg, kd, tf):
    import jax.experimental.pallas as pl

    p, s = pl.program_id(0), pl.program_id(1)

    @pl.when((p == 0) & (s == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    live = p < n_hit[0]

    @pl.when(live & (s < kg))
    def _():
        x = x_ref[s]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)

        @pl.when(s == 0)
        def _():
            gate_ref[...] = gate
            up_ref[...] = up

        @pl.when(s > 0)
        def _():
            gate_ref[...] += gate
            up_ref[...] += up

        @pl.when(s == kg - 1)
        def _():
            # the row's weight for this expert goes in before the cast
            act = (jax.nn.silu(gate_ref[...]) * up_ref[...]
                   * loc_ref[...]).astype(act_ref.dtype)
            for j in range(kd):
                act_ref[j] = act[:, j * tf:(j + 1) * tf]

    @pl.when(live & (s >= kg))
    def _():
        out_ref[...] += jnp.dot(act_ref[s - kg], wd_ref[...],
                                preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hit_share_ffn(h, local, w_gate, w_up, w_down, *, interpret=False):
    """``moe_ops.moe_share_ffn``'s result for rows ``h [..., D]`` and
    weights ``local [..., n_held]``, reading the weights of the held
    experts with a non-zero weight only -> ``(out [..., D] float32,
    n_hit)``: ``n_hit`` (int32) is how many experts that were.  Widths
    are whole lane tiles (``moe_ops.hit_rule``).  Jitted: a model's
    layers share one traced and lowered call."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_held = local.shape[-1]
    d = h.shape[-1]
    f = w_down.shape[0] // n_held
    item = jnp.dtype(w_gate.dtype).itemsize
    tk, tf = hit_blocks(d, f, item)
    kg, kd = d // tk, f // tf
    x = h.reshape(-1, d).astype(w_gate.dtype)
    loc = local.reshape(-1, n_held).astype(jnp.float32)
    real = x.shape[0]
    rows = -(-real // _SUBLANES) * _SUBLANES    # whole sublane tiles
    if rows != real:
        x = jnp.pad(x, ((0, rows - real), (0, 0)))
        loc = jnp.pad(loc, ((0, rows - real), (0, 0)))
    # a block of D a leading index: the kernel picks it by grid step
    x = x.reshape(rows, kg, tk).transpose(1, 0, 2)

    # the hit experts first, in ascending id, then the others
    hit = jnp.any(loc != 0.0, axis=0)
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    at = jnp.where(hit, jnp.cumsum(hit) - 1,
                   n_hit + jnp.cumsum(~hit) - 1).astype(jnp.int32)
    experts = jnp.zeros(n_held, jnp.int32).at[at].set(
        jnp.arange(n_held, dtype=jnp.int32), unique_indices=True)

    last = kg + kd - 1
    # the double-buffered blocks, the resident rows and result, the two
    # sums, the activations and as much again for the body's values
    vmem = 2 * (2 * tk * f + tf * d) * item + 2 * rows * d * (4 + item) \
        + 2 * (2 * rows * f * 4 + rows * f * item) + (4 << 20)

    def expert(p, ex, nh):      # past the list: the last hit expert
        return ex[jnp.minimum(p, jnp.maximum(nh[0] - 1, 0))]

    def step(p, s, nh):         # ... at its last block
        return jnp.where(p < nh[0], s, last)

    def gate_up(p, s, ex, nh):  # expert j is a COLUMN block
        return jnp.minimum(step(p, s, nh), kg - 1), expert(p, ex, nh)

    def down(p, s, ex, nh):     # ... and a ROW block of w_down
        return (expert(p, ex, nh) * kd
                + jnp.clip(step(p, s, nh) - kg, 0, kd - 1), 0)

    out = pl.pallas_call(
        functools.partial(_kernel, kg=kg, kd=kd, tf=tf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_held, kg + kd),
            in_specs=[
                pl.BlockSpec((kg, rows, tk), lambda p, s, ex, nh: (0, 0, 0)),
                pl.BlockSpec((None, rows, 1), lambda p, s, ex, nh: (
                    expert(p, ex, nh), 0, 0)),
                pl.BlockSpec((tk, f), gate_up),
                pl.BlockSpec((tk, f), gate_up),
                pl.BlockSpec((tf, d), down)],
            out_specs=pl.BlockSpec((rows, d), lambda p, s, ex, nh: (0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, f), jnp.float32),
                            pltpu.VMEM((rows, f), jnp.float32),
                            pltpu.VMEM((kd, rows, tf), w_down.dtype)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret, name=HIT_KERNEL_NAME,
    )(experts, n_hit.reshape(1), x, loc.T[:, :, None], w_gate, w_up, w_down)
    return out[:real].reshape(*h.shape[:-1], d), n_hit
