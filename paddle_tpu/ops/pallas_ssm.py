"""The diagonal selective scan of a state-space (Mamba-1) layer: a state
of ``[d_state, channels]`` float32 a request in which EVERY entry has a
decay of its own, no heads and no matrix product over the state.

``serving/mixers.py``'s ``SSMMixer`` keeps that state a slot.  A token
takes it one step on (``n`` a state row, ``c`` a channel)::

    h[n, c] <- exp(dt[c] a[n, c]) h[n, c] + (dt[c] u[c]) b[n]
    y[c]     = sum_n h[n, c] c_[n]

All float32 on the vector unit (one exponential, four multiplies and two
adds an entry); nothing goes to the MXU.  THE LAYOUT IS THE CHIP'S: the
channels lie on the 128 lanes and the ``d_state`` rows on the sublanes
(``[d_state, channels]``: the paper's ``[channels, d_state]`` would put
16 on the lane axis and pad every slab eightfold).  The vectors indexed
by ``n`` (``b`` and ``c_``) come as rows of ``d_state`` lanes and are
laid down the sublanes in the kernel (``_column``: a masked lane
reduction, two registers a vector); those indexed by ``c`` broadcast
over the sublanes as they are.

Two kernels over the one rule, and one beside the step's:

* ``ssm_update``, the decode step: ONE token of each of ``R`` slots,
  grid ``(slot block, channel block)``.  Every live slot's state is read
  once and written once where it lies (aliased to the result); a dead
  slot's block is written back as read, bit for bit.  Bound by the
  state's HBM bytes.
* ``ssm_scan``, a prompt: ``T`` consecutive tokens of ONE request, grid
  ``(channel block, token tile)``.  A channel block's state stays in
  fast memory through all the call's tokens (it is the revisited output
  block of the inner grid axis) and goes through them eight at a time;
  tokens past ``n_real`` never touch it, and token tiles past it are
  skipped.  Bound by the vector and transcendental units.

* ``ssm_conv_update``, the step's depth-wise causal convolution: the
  ``K - 1`` inputs a slot keeps (one lane-dense row, oldest first) and
  the token's own through the ``K`` taps, the bias and SiLU, and the
  kept row moved one input on WHERE IT LIES (aliased; XLA, asked to
  shift a row by a third of itself in place, copies the slab first).  A
  dead slot's row is written back as read.

``ssm_rule`` says from the state's static shape alone whether the
kernels take it; the caller keeps its XLA form (``ssm_token_xla``, also
the tests' oracle) for everything else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssm_update", "ssm_scan", "ssm_conv_update", "ssm_rule",
           "ssm_token_xla", "ssm_conv_xla", "STEP_KERNEL", "SCAN_KERNEL",
           "CONV_KERNEL", "SCAN_TILE"]

STEP_KERNEL = "ssm_state_update"
CONV_KERNEL = "ssm_conv_update"
SCAN_KERNEL = "ssm_prompt_scan"
_LANES, _SUBLANES = 128, 8
_VMEM_LIMIT = 64 * 1024 * 1024
# the step's blocks: slots x channels of state a grid step (1.3 MiB at
# d_state 16: in and out, two buffers each, 5 MiB of fast memory; 64
# grid steps a layer at 256 slots of 5,120 channels, so the first copy
# in and the last one out, which nothing overlaps, are a thirtieth)
STEP_SLOTS, STEP_CHANNELS = 8, 2560
# the scan's: channels a grid step (a token's vectors are laid down the
# sublanes once a channel block, so wide blocks share that work; 20
# registers of state at d_state 16), tokens a tile
SCAN_CHANNELS, SCAN_TILE = 1280, 64


def ssm_rule(d_state, channels, state_dtype) -> bool:
    """Whether the kernels take a state of ``[rows, d_state, channels]``:
    float32, whole sublane tiles of state rows, whole lane tiles of
    channels.  A function of the static shape alone, the same on every
    backend."""
    return (jnp.dtype(state_dtype) == jnp.float32
            and d_state % _SUBLANES == 0 and channels % _LANES == 0)


def ssm_token_xla(dt, u, b, c, a, state):
    """The rule, one token a row, as XLA operations: ``dt``, ``u [R,
    C]``, ``b``, ``c [R, N]``, ``a [N, C]`` (negative), ``state [R, N,
    C]`` before the token -> (``y [R, C]``, the state after it).  The
    form of every shape the kernels do not take, and what they are
    tested against."""
    h = jnp.exp(dt[:, None, :] * a) * state \
        + (dt * u)[:, None, :] * b[:, :, None]
    return jnp.sum(h * c[:, :, None], axis=1), h


def _column(row, n):
    """``row [1, n]`` (a vector over the lanes) -> ``[n, 1]`` (the same
    down the sublanes): the diagonal of its broadcast, summed along the
    lanes."""
    eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _token(dt, x, b_col, c_col, a, h):
    """One token of one row over a channel block: ``dt``, ``x = dt u [1,
    Cb]``, ``b_col``, ``c_col [N, 1]``, ``a``, ``h [N, Cb]`` -> (``y [1,
    Cb]``, ``h`` after it)."""
    h = jnp.exp(dt * a) * h + x * b_col
    return jnp.sum(h * c_col, axis=0, keepdims=True), h


# -- the decode step ----------------------------------------------------------
def _update_kernel(live_ref, dt_ref, u_ref, b_ref, c_ref, a_ref, s_ref,
                   y_ref, s_out_ref):
    """A block of slots' one token over a block of channels.  ``dt_ref``,
    ``u_ref``, ``y_ref [Rb, Cb]``; ``b_ref``, ``c_ref [Rb, N]``; ``a_ref
    [N, Cb]``; ``s_ref`` / ``s_out_ref [Rb, N, Cb]``."""
    import jax.experimental.pallas as pl

    rows, n, _ = s_ref.shape
    first = pl.program_id(0) * rows
    for r in range(rows):
        at = pl.ds(r, 1)
        live = live_ref[first + r]

        @pl.when(live == 0)
        def _():
            s_out_ref[r] = s_ref[r]
            y_ref[at, :] = jnp.zeros((1, y_ref.shape[1]), y_ref.dtype)

        @pl.when(live != 0)
        def _():
            dt = dt_ref[at, :]
            y, h = _token(dt, dt * u_ref[at, :], _column(b_ref[at, :], n),
                          _column(c_ref[at, :], n), a_ref[...], s_ref[r])
            s_out_ref[r] = h
            y_ref[at, :] = y


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ssm_update(dt, u, b, c, a, state, live, *, block=None, interpret=False):
    """ONE token of each of ``R`` rows through the rule: ``dt``, ``u [R,
    C]``, ``b``, ``c [R, N]``, ``a [N, C]``, ``state [R, N, C]`` BEFORE
    the token, ``live [R]`` (bool or int) which rows are a request's ->
    (``y [R, C]``, zero for a dead row; the state after the token, a
    dead row's own bit for bit).  All float32; the state is updated in
    place (hand it over as it lies).  ``block``: (slots, channels) a grid
    step, for the tests.  Jitted, so a model's layers share one traced
    and lowered call."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, n, ch = state.shape
    if not ssm_rule(n, ch, state.dtype):
        raise ValueError(f"ssm_update does not take a {state.dtype} state "
                         f"of {n} x {ch} (ssm_rule)")
    rb, cb = block or (STEP_SLOTS, STEP_CHANNELS)
    rb, cb = min(rb, r), min(cb, ch)
    if rb != r and rb % _SUBLANES:
        raise ValueError(f"{rb} slots a block are no whole sublane tiles")
    grid = (pl.cdiv(r, rb), pl.cdiv(ch, cb))
    # one flag a row of every block, the last block's padding included
    flags = jnp.zeros((grid[0] * rb,), jnp.int32).at[:r].set(
        live.astype(jnp.int32))
    f32 = jnp.float32
    rows = pl.BlockSpec((rb, cb), lambda i, j, f: (i, j))
    vecs = pl.BlockSpec((rb, n), lambda i, j, f: (i, 0))
    slab = pl.BlockSpec((rb, n, cb), lambda i, j, f: (i, 0, j))
    return pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[rows, rows, vecs, vecs,
                      pl.BlockSpec((n, cb), lambda i, j, f: (0, j)), slab],
            out_specs=[rows, slab]),
        out_shape=[jax.ShapeDtypeStruct((r, ch), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # the scalar operand counts: the state is operand 6
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=STEP_KERNEL,
    )(flags, dt.astype(f32), u.astype(f32), b.astype(f32), c.astype(f32),
      a.astype(f32), state)


# -- the step's convolution ---------------------------------------------------
def ssm_conv_xla(tail, u, taps, bias):
    """The convolution, one token a row, as XLA operations: ``tail [R,
    (K-1) C]`` the rows' last ``K - 1`` inputs, oldest first, side by
    side; ``u [R, C]`` the token's; ``taps [K, C]``, ``bias [C]`` ->
    (``SiLU(conv + bias) [R, C]``, the tail one input on).  The form of
    every width the kernel does not take, and what it is tested
    against."""
    ch = u.shape[-1]
    window = jnp.concatenate([tail, u], axis=1)
    conv = sum(window[:, j * ch:(j + 1) * ch] * taps[j]
               for j in range(taps.shape[0]))
    return jax.nn.silu(conv + bias), window[:, ch:]


def _conv_kernel(live_ref, tail_ref, u_ref, taps_ref, bias_ref, y_ref,
                 tail_out_ref):
    """A block of slots.  ``live_ref [Rb, 1]``; ``tail_ref`` /
    ``tail_out_ref [Rb, (K-1) C]``; ``u_ref``, ``y_ref [Rb, C]``;
    ``taps_ref [K, C]``; ``bias_ref [1, C]``."""
    ch = u_ref.shape[1]
    k = taps_ref.shape[0]
    old = tail_ref[...]
    window = [old[:, j * ch:(j + 1) * ch] for j in range(k - 1)] \
        + [u_ref[...]]
    conv = bias_ref[...] + sum(window[j] * taps_ref[j:j + 1, :]
                               for j in range(k))
    y_ref[...] = conv * (1.0 / (1.0 + jnp.exp(-conv)))
    tail_out_ref[...] = jnp.where(
        live_ref[...] != 0, jnp.concatenate(window[1:], axis=1), old)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ssm_conv_update(tail, u, taps, bias, live, *, block=None,
                    interpret=False):
    """ONE token of each of ``R`` rows through the convolution: the
    arguments and results of ``ssm_conv_xla``, and ``live [R]`` which
    rows are a request's: a dead row's tail comes back bit for bit (its
    ``y`` is computed and means nothing).  All float32, ``C`` whole lane
    tiles; the tail is updated in place.  ``block``: slots a grid step,
    for the tests."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, ch = u.shape
    k = taps.shape[0]
    if ch % _LANES or tail.shape != (r, (k - 1) * ch) \
            or tail.dtype != jnp.float32:
        raise ValueError(f"ssm_conv_update does not take a {tail.dtype} "
                         f"tail of {tail.shape} for {k} taps of {ch}")
    rb = min(block or STEP_SLOTS, r)
    if rb != r and rb % _SUBLANES:
        raise ValueError(f"{rb} slots a block are no whole sublane tiles")
    f32 = jnp.float32
    rows = lambda width: pl.BlockSpec((rb, width),  # noqa: E731
                                      lambda i: (i, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))  # noqa: E731
    bias = bias.reshape(1, ch).astype(f32)
    taps = taps.astype(f32)
    return pl.pallas_call(
        _conv_kernel, grid=(pl.cdiv(r, rb),),
        in_specs=[rows(1), rows((k - 1) * ch), rows(ch), whole(taps),
                  whole(bias)],
        out_specs=[rows(ch), rows((k - 1) * ch)],
        out_shape=[jax.ShapeDtypeStruct((r, ch), f32),
                   jax.ShapeDtypeStruct(tail.shape, f32)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=CONV_KERNEL,
    )(live.astype(jnp.int32).reshape(r, 1), tail, u.astype(f32), taps, bias)


# -- a prompt -----------------------------------------------------------------
def _scan_kernel(n_ref, dt_ref, x_ref, b_ref, c_ref, a_ref, s_ref, y_ref,
                 s_out_ref):
    """A tile of one request's tokens over a block of channels, the
    block's state in ``s_out_ref`` from the first tile to the last.
    ``dt_ref``, ``x_ref``, ``y_ref [Tt, Cb]``; ``b_ref``, ``c_ref [Tt,
    N]``; ``a_ref [N, Cb]``; ``s_ref`` / ``s_out_ref [1, N, Cb]``."""
    import jax.experimental.pallas as pl

    tile, _ = dt_ref.shape
    n = a_ref.shape[0]
    t = pl.program_id(1)
    real = n_ref[0] - t * tile          # of this tile's tokens

    @pl.when(t == 0)
    def _():
        s_out_ref[...] = s_ref[...]

    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(real > 0)
    def _():
        a = a_ref[...]

        def eight(g, h):
            """Tokens ``8g .. 8g + 7``: a whole sublane tile of each
            vector, its rows taken where they lie.  A token past
            ``n_real`` comes with ``dt = 0`` and ``x = 0``: it decays
            nothing and writes nothing."""
            at = pl.ds(pl.multiple_of(g * _SUBLANES, _SUBLANES), _SUBLANES)
            dt, x, b, c = dt_ref[at, :], x_ref[at, :], b_ref[at, :], \
                c_ref[at, :]
            ys = []
            for i in range(_SUBLANES):
                y, h = _token(dt[i:i + 1], x[i:i + 1],
                              _column(b[i:i + 1], n),
                              _column(c[i:i + 1], n), a, h)
                ys.append(y)
            y_ref[at, :] = jnp.concatenate(ys, axis=0)
            return h

        groups = (jnp.minimum(real, tile) + _SUBLANES - 1) // _SUBLANES
        s_out_ref[0] = lax.fori_loop(0, groups, eight, s_out_ref[0])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ssm_scan(dt, u, b, c, a, state, n_real, *, block=None, interpret=False):
    """``T`` consecutive tokens of ONE request through the rule: ``dt``,
    ``u [T, C]``, ``b``, ``c [T, N]``, ``a [N, C]``, ``state [1, N, C]``
    BEFORE the first token, ``n_real`` (int32 scalar) how many of the
    tokens are the request's -> (``y [T, C]``, zero past ``n_real``; the
    state after token ``n_real - 1``).  All float32; ``T`` a whole number
    of sublane tiles.  ``block``: (channels, tokens) a grid step, for the
    tests."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, ch = dt.shape
    n = state.shape[1]
    if not ssm_rule(n, ch, state.dtype):
        raise ValueError(f"ssm_scan does not take a {state.dtype} state of "
                         f"{n} x {ch} (ssm_rule)")
    cb, tile = block or (SCAN_CHANNELS, SCAN_TILE)
    cb, tile = min(cb, ch), min(tile, t)
    if t % tile or tile % _SUBLANES:
        raise ValueError(f"{t} tokens are no whole tiles of {tile} rows "
                         f"of whole sublane tiles")
    f32 = jnp.float32
    real = (jnp.arange(t, dtype=jnp.int32) < n_real)[:, None]
    # a padding token's step is 0: exp(0 a) = 1 and dt u = 0 hand the
    # state on as it was
    dt = jnp.where(real, dt.astype(f32), 0.0)
    rows = pl.BlockSpec((tile, cb), lambda j, i, k: (i, j))
    vecs = pl.BlockSpec((tile, n), lambda j, i, k: (i, 0))
    slab = pl.BlockSpec((1, n, cb), lambda j, i, k: (0, 0, j))
    y, s = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(ch, cb), t // tile),
            in_specs=[rows, rows, vecs, vecs,
                      pl.BlockSpec((n, cb), lambda j, i, k: (0, j)), slab],
            out_specs=[rows, slab]),
        out_shape=[jax.ShapeDtypeStruct((t, ch), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=SCAN_KERNEL,
    )(jnp.reshape(n_real, (1,)).astype(jnp.int32), dt, dt * u.astype(f32),
      b.astype(f32), c.astype(f32), a.astype(f32), state)
    return jnp.where(real, y, 0.0), s
