"""The gated delta rule with a decay a channel, a head's state held in
fast memory for as many tokens as the call has.

``serving/mixers.py``'s recurrent layers keep a ``[d_k, d_v]``
float32 matrix a head a request.  A token takes it one step on::

    S <- decay (.) S            a factor a d_k channel
    ks = S^T k,  qs = S^T q
    delta = beta (v - ks)
    S <- S + k delta^T
    o  = qs + (q.k) delta       = S_t^T q without another pass

As XLA fusions that is three passes over the state where one read and
one write are the floor, and a prompt's tokens each send the state
through HBM again.  Here ONE kernel body loads a block of heads' states
into VMEM, carries them through the call's ``T`` tokens there, and
stores them once, in place (the state is aliased to the result).  Two
grids over it: the decode step is ``T = 1`` over ``R`` slots (grid
``(slot, head block)``: the slab streams through at the rate of the
copies, the arithmetic under them), and ``R = 1`` over ``T`` consecutive
tokens of one row (grid ``(1, head block)``; the loop stops at the row's
``n_real``, so padding never touches the state).  A row with ``n_real ==
0`` (a dead slot of the step) is written back as read.

WHO CALLS WHICH.  The step of every model that takes its recurrent
layers from ``mixers.KDAMixer`` (``_kda_token``: Solar-Open2's
and Kimi-Linear's) runs the ``T = 1`` grid.  The ``R = 1`` grid over a
chunk's tokens served those models' whole-prompt prefill until PR 58 and
serves NO model since: a prompt's tokens each paid the transposes below,
one after another, and a prompt now runs the rule's chunk (WY) form on
the matrix unit instead (``ops/pallas_kda_chunk.py``, which shares
``kda_rule`` and the VMEM limit with this file and nothing else).  The
grid is kept as the token rule over several tokens, which is what
``tests/test_pallas_kda_update.py`` (several tokens a call against the
XLA form), ``tests/test_tpu_compile.py`` (64 tokens of 64 heads for the
described chip) and ``tools/sweep_kda_chunk.py`` (the form that was,
beside the form that is) call it as: they keep it honest.

All float32 on the vector unit, the token rule's own products and sums
(only the order of a sum over ``d_k`` may differ from XLA's); nothing
goes to the MXU.  The vectors indexed by ``d_k`` (q, k, decay) multiply
the state's ROWS: each is laid over the sublanes and transposed in the
kernel, 48 vector registers through the transpose unit a head a token,
which is what a token costs (the 120 multiplies and adds hide under
them; PERF.md section 5).

``kda_rule`` says from the call's static shape alone whether the kernel
takes it; the caller keeps its XLA form for everything else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["kda_update", "kda_rule", "head_block", "KERNEL_NAME"]

KERNEL_NAME = "kda_state_update"
_LANES, _SUBLANES = 128, 8
_VMEM_LIMIT = 96 * 1024 * 1024
# float32 bytes of a call's blocks (both buffers of each) the head block
# is cut to stay under
_BLOCK_BUDGET = 72 * 1024 * 1024
# heads a grid step: blocks of 1 MiB of state at widths of 128, enough
# grid steps for the copies to overlap, sixteen independent chains for
# the scheduler to interleave (32 read 4 % faster a token and cost a
# second of set-up a program: the body is unrolled over them)
_HEADS_A_STEP = 16


def kda_rule(heads, d_k, d_v, state_dtype) -> bool:
    """Whether the kernel takes a state of ``[rows, heads, d_k, d_v]``:
    float32, both widths whole lane tiles, heads in whole sublane tiles.
    A function of the static shape alone, the same on every backend."""
    return (jnp.dtype(state_dtype) == jnp.float32 and d_k % _LANES == 0
            and d_v % _LANES == 0 and heads % _SUBLANES == 0)


def head_block(tokens, heads, d_k, d_v):
    """Heads a grid step: ``_HEADS_A_STEP``, or the most whole sublane
    tiles of heads under it that divide ``heads`` and whose blocks of
    ``tokens`` tokens fit ``_BLOCK_BUDGET``."""
    g = min(_HEADS_A_STEP, heads)
    a_head = 4 * 2 * (tokens * (3 * d_k + 4 * d_v) + 2 * d_k * d_v)
    while g > _SUBLANES and (heads % g or g * a_head > _BLOCK_BUDGET):
        g -= _SUBLANES
    return g


def _kda_kernel(n_ref, cols_ref, rows_ref, s_ref, o_ref, s_out_ref, *,
                tokens):
    """One row's block of heads through the row's real tokens.
    ``cols_ref [1, T, 3, G, d_k]``: q, k, decay; ``rows_ref [1, T, 3, G,
    d_v]``: v, beta and q.k over the lanes; ``s_ref`` / ``s_out_ref
    [1, G, d_k, d_v]``; ``o_ref [1, T, G, d_v]``."""
    import jax.experimental.pallas as pl

    n = n_ref[pl.program_id(0)]
    _, heads, d_k, d_v = s_ref.shape

    def token(t, src):
        """Token ``t`` of every head of the block, the state before it
        read from ``src`` and the state after it left in ``s_out_ref``."""

        def column(c, h):
            # vector c of head h down the sublanes, the same in every
            # lane: its row over every sublane, transposed
            row = cols_ref[0, t, c, pl.ds(h, 1), :]
            return jnp.broadcast_to(row, (d_v, d_k)).T

        for h in range(heads):
            at = pl.ds(h, 1)
            q, k = column(0, h), column(1, h)
            s = column(2, h) * src[0, h]
            ks = jnp.sum(k * s, axis=0, keepdims=True)
            qs = jnp.sum(q * s, axis=0, keepdims=True)
            delta = rows_ref[0, t, 1, at, :] * (
                rows_ref[0, t, 0, at, :] - ks)
            s_out_ref[0, h] = s + k * delta
            o_ref[0, t, at, :] = qs + rows_ref[0, t, 2, at, :] * delta

    if tokens == 1:
        # the step: a live row's block goes through its token straight
        # from the buffer it was copied into, a dead row's is written
        # back as read
        @pl.when(n == 0)
        def _():
            s_out_ref[...] = s_ref[...]
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        @pl.when(n > 0)
        def _():
            token(0, s_ref)
    else:
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        def one(t, carry):
            token(t, s_out_ref)
            return carry

        lax.fori_loop(0, n, one, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_update(q, k, decay, v, beta, state, n_real, *, interpret=False):
    """``R`` rows of ``T`` consecutive tokens each through the rule:
    ``q``, ``k``, ``decay [R, T, H, d_k]``, ``v [R, T, H, d_v]``, ``beta
    [R, T, H]``, ``state [R, H, d_k, d_v]`` BEFORE the first token,
    ``n_real [R]`` (int32) how many of a row's tokens are real ->
    (``o [R, T, H, d_v]``, zero past ``n_real``; the state after token
    ``n_real - 1``, the row's own where ``n_real`` is 0).  All float32;
    the state is updated in place (hand it over as it lies: a caller's
    reshape in front would be a copy of it).  Jitted, so a model's layers
    share one traced and lowered call."""
    h, d_k, d_v = q.shape[2], q.shape[3], v.shape[-1]
    if not kda_rule(h, d_k, d_v, state.dtype):
        raise ValueError(
            f"kda_update does not take a {state.dtype} state of {h} heads "
            f"of {d_k} x {d_v} (kda_rule)")
    f32 = jnp.float32
    cols = jnp.stack([q, k, decay], axis=2).astype(f32)
    rows = jnp.stack([
        v, jnp.broadcast_to(beta[..., None], v.shape),
        jnp.broadcast_to(jnp.sum(q * k, -1, keepdims=True), v.shape)],
        axis=2).astype(f32)
    return _kernel_call(n_real.astype(jnp.int32), cols, rows, state,
                        interpret)


def _kernel_call(n_real, cols, rows, state, interpret):
    """The kernel over ``cols [R, T, 3, H, d_k]`` (q, k, decay), ``rows
    [R, T, 3, H, d_v]`` (v, beta and q.k over the lanes) and the state,
    aliased to its result."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, t, _, h, d_k = cols.shape
    d_v = rows.shape[-1]
    g = head_block(t, h, d_k, d_v)

    def vectors(width):
        return pl.BlockSpec((1, t, 3, g, width),
                            lambda i, j, n: (i, 0, 0, j, 0))

    slab = pl.BlockSpec((1, g, d_k, d_v), lambda i, j, n: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_kda_kernel, tokens=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(r, h // g),
            in_specs=[vectors(d_k), vectors(d_v), slab],
            out_specs=[pl.BlockSpec((1, t, g, d_v),
                                    lambda i, j, n: (i, 0, j, 0)), slab]),
        out_shape=[jax.ShapeDtypeStruct((r, t, h, d_v), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the scalar operand counts: the state is operand 3
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAME,
    )(n_real, cols, rows, state)
