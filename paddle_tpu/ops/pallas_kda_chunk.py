"""The gated delta rule with a decay a channel over a prompt's tokens: the
rule's chunk (WY) form, its products on the matrix unit, a head's state
held in fast memory for as many chunks as the call has.

``ops/pallas_kda_update.py`` is the TOKEN rule (what the decode step
runs, one token a slot); this is the same rule over ``CHUNK``
consecutive tokens of one request at a time (Kimi Delta Attention's
chunkwise form, arXiv 2510.26692, with Gated Linear Attention's
secondary chunking for the decay ratios, arXiv 2312.06635), what
``serving/mixers.py`` ``KDAMixer._kda_chunk`` hands the engine's
whole-prompt prefill.  With ``g_t <= 0`` a channel's log decay and
``G_t`` its sum up to and with token ``t`` of the chunk, ``u_t`` (what
token ``t`` writes along ``k_t``) solves the unit lower triangular::

    (I + A) U = beta (V - (e^G (.) K) S_0)
    A_tj = beta_t sum_c k_tc k_jc e^{G_tc - G_jc}        j < t
    O   = (e^G (.) Q) S_0 + P U
    P_tj = sum_c q_tc k_jc e^{G_tc - G_jc}               j <= t
    S_C = e^{G_C} (.) S_0 + (e^{G_C - G} (.) K)^T U

The decay does not factor out of ``k_t . k_j`` as a scalar, and
``e^{-G_j}`` may never be formed (a served sum of 16 log decays can pass
float32's range).  So a chunk is cut into sub-chunks of ``SUB`` tokens.
A pair ``(t, j)`` in DIFFERENT sub-chunks is a matrix product of
``q_t e^{G_t - G_b}`` / ``k_t e^{G_t - G_b}`` with ``k_j e^{G_b - G_j}``,
``b`` the first token of ``t``'s sub-chunk (both exponents at most 0;
the keys are decayed again once a later sub-chunk).  A pair in the SAME
sub-chunk takes the difference ``G_t - G_j`` a channel, cut at 0 BEFORE
the ``exp`` (which is the mask ``j <= t``: past it the factor is
multiplied by zero), summed over the channels; there the system is
solved as it is met, token ``j`` handing the tokens after it column
``j`` of ``A`` and ``P`` and one rank-one update of the sub-chunk's
``[SUB, d_v]`` tile (forward substitution: no inverse and no power of
``A`` is formed, so nothing computed is larger than what the token rule
computes).  **No exponential of a positive argument and no quotient of
decays anywhere**, so the form takes the LOG decay where the step takes
the factor.  ``[Q; K] e^G`` against ``S_0``, the blocks of ``A`` and
``P`` between sub-chunks, what they take of the ``U`` before them and
``(...)^T U`` are matrix products, every one of float32 operands at
``HIGHEST`` (the state, the decay and beta are float32 as served; the
matrix unit's default for them is one bfloat16 pass, a different
result).

One kernel body loads a block of heads' states into VMEM, carries them
through the call's chunks there, and stores them once, in place (the
state is aliased to the result).  Rows past ``n_real`` are masked in the
kernel (``beta = 0``, ``g = 0``, ``k = q = 0``: they write nothing,
decay nothing and read zero) and chunks of nothing else are skipped, so
padding never touches the state.  The vectors come in as they lie,
``[T, H * d]``: a head's channels are whole lane tiles.

``pallas_kda_update.kda_rule`` says from the state's static shape alone
whether the kernels take it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kda_update import _SUBLANES, _VMEM_LIMIT, kda_rule

__all__ = ["kda_chunk", "CHUNK", "SUB", "HEADS_A_STEP", "KERNEL_NAME"]

KERNEL_NAME = "kda_chunk_update"
# tokens the form takes at once, and the sub-chunks whose decay ratios
# are taken a channel (PERF.md section 6, PR 58, has what 8, 16 and 32
# read on the chip)
CHUNK, SUB = 64, 16
# heads a grid step: independent chains whose stages the body interleaves
# (``kda_rule``'s heads are a multiple of it; 4 read 9 % faster and cost
# a second more of set-up a program)
HEADS_A_STEP = 2


def _exact(a, b, contract=((1,), (0,))):
    """``a . b`` over ``contract`` as float32 products on the matrix unit
    (its default for float32 operands is one bfloat16 pass)."""
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _chunk_kernel(n_ref, q_ref, k_ref, g_ref, v_ref, b_ref, s_ref, o_ref,
                  s_out_ref, *, sub):
    """One row's block of heads through the row's real tokens, ``CHUNK``
    at a time in the WY form (the module's docstring).  ``q_ref``,
    ``k_ref``, ``g_ref [T, G * d_k]`` (a head's channels are 128-lane
    columns; ``g`` the LOG decay), ``v_ref`` / ``o_ref [T, G * d_v]``,
    ``b_ref [1, 1, T, G]`` beta, ``s_ref`` / ``s_out_ref [1, G, d_k,
    d_v]``."""
    import jax.experimental.pallas as pl

    n = n_ref[pl.program_id(0)]
    _, heads, d_k, d_v = s_ref.shape
    c = CHUNK
    s_out_ref[...] = s_ref[...]
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    token = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    # ones at (t, j) for j <= t of t's own sub-chunk: G = tri . g sums
    # the log decays from the sub-chunk's first token on
    t_of = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j_of = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tri = ((j_of <= t_of) & (j_of // sub == t_of // sub)).astype(jnp.float32)
    # ones down the rows at and after (after) token j of a sub-chunk's
    # rows from ``lo`` on, for every j of the tile of eight at ``lo``
    from_j, after_j = {}, {}
    for lo in range(0, sub, _SUBLANES):
        row = lax.broadcasted_iota(jnp.int32, (sub - lo, 1), 0)
        from_j[lo] = [(row >= j).astype(jnp.float32)
                      for j in range(_SUBLANES)]
        after_j[lo] = [(row > j).astype(jnp.float32)
                       for j in range(_SUBLANES)]

    def head(i, h):
        """Chunk ``i`` of head ``h``, its state read from ``s_out_ref``
        -> (its outputs a tile of eight rows, the state after it), as a
        generator that yields between its stages.  The block's heads
        are independent chains of products and rank-one updates, each
        waiting on the one before it: ``chunk`` takes a stage of every
        head in turn, so that the scheduler finds one head's work next
        to the other's waits, and stores nothing until all are done."""
        at = pl.ds(pl.multiple_of(i * c, c), c)
        ck, cv = slice(h * d_k, (h + 1) * d_k), slice(h * d_v, (h + 1) * d_v)
        real = token < n - i * c
        # a row past n_real writes nothing (k, beta), decays nothing (g)
        # and reads nothing (q)
        q, k, g = (jnp.where(real, ref[at, ck], 0.0)
                   for ref in (q_ref, k_ref, g_ref))
        beta = jnp.where(real, b_ref[0, 0, at, h:h + 1], 0.0)       # [C, 1]
        s0 = s_out_ref[0, h]
        # summed log decays: within a sub-chunk, then the sub-chunks'
        # own sums before it (all of them <= 0, so is every difference
        # taken below)
        local = _exact(tri, g)
        starts = [jnp.zeros((1, d_k), jnp.float32)]
        for b in range(c // sub):
            starts.append(starts[-1] + local[(b + 1) * sub - 1:(b + 1) * sub])
        whole = jnp.concatenate(
            [local[b * sub:(b + 1) * sub] + starts[b]
             for b in range(c // sub)])                             # G
        # what the chunk's tokens read of the state it starts from
        eg = jnp.exp(whole)
        from_s0 = _exact(jnp.concatenate([q * eg, k * eg]), s0)
        yield
        wrote, outs = [], []    # U and O, a tile of eight rows at a time
        for b in range(c // sub):
            rows = slice(b * sub, (b + 1) * sub)
            to_start = jnp.exp(local[rows])
            qb, kb, gb, bb = q[rows], k[rows], local[rows], beta[rows]
            o = from_s0[rows]
            at_b = pl.ds(pl.multiple_of(i * c, c) + b * sub, sub)
            r = bb * (v_ref[at_b, cv]
                      - from_s0[c + b * sub:c + (b + 1) * sub])
            if b:
                # the sub-chunks before this one: their keys decayed to
                # this one's first token, q and k from there on
                before = slice(0, b * sub)
                kh = k[before] * jnp.exp(jnp.minimum(
                    starts[b] - whole[before], 0.0))
                pa = _exact(jnp.concatenate([qb * to_start, kb * to_start]),
                            kh, ((1,), (1,)))                       # P over A
                took = _exact(jnp.concatenate([pa[:sub], bb * pa[sub:]]),
                              jnp.concatenate(wrote))
                o, r = o + took[:sub], r - took[sub:]
            yield
            # the sub-chunk's own tokens one after another: token j's
            # decay ratios a channel, what it hands the tokens after it
            # (A's and P's column j) summed over the channels, and a
            # rank-one update each of the rows from j's tile of eight on
            # (the tiles before it are done)
            for lo in range(0, sub, _SUBLANES):
                q_a, k_a, g_a, b_a = qb[lo:], kb[lo:], gb[lo:], bb[lo:]
                for j in range(_SUBLANES):
                    u_j = r[j:j + 1]
                    w = jnp.exp(jnp.minimum(g_a - g_a[j:j + 1], 0.0)) \
                        * k_a[j:j + 1]
                    p = jnp.sum(q_a * w, axis=-1, keepdims=True)
                    a = jnp.sum(k_a * w, axis=-1, keepdims=True)
                    o = o + from_j[lo][j] * p * u_j
                    r = r - after_j[lo][j] * b_a * a * u_j
                    yield
                wrote.append(r[:_SUBLANES])
                outs.append(o[:_SUBLANES])
                o, r = o[_SUBLANES:], r[_SUBLANES:]
        # the state after the chunk: decayed over all of it, and what
        # each token wrote decayed from its place to the end
        end = starts[-1]
        to_end = k * jnp.exp(jnp.minimum(end - whole, 0.0))
        decay = jnp.broadcast_to(jnp.exp(end), (d_v, d_k)).T
        return outs, decay * s0 + _exact(
            to_end, jnp.concatenate(wrote), ((0,), (0,)))

    def chunk(i, carry):
        running, done = [head(i, h) for h in range(heads)], {}
        while len(done) < heads:        # every head has the same stages
            for h, stages in enumerate(running):
                try:
                    next(stages)
                except StopIteration as last:
                    done[h] = last.value
        for h, (outs, s) in done.items():
            o_ref[pl.ds(pl.multiple_of(i * c, c), c),
                  h * d_v:(h + 1) * d_v] = jnp.concatenate(outs)
            s_out_ref[0, h] = s
        return carry

    lax.fori_loop(0, (n + c - 1) // c, chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk(q, k, log_decay, v, beta, state, n_real, *, interpret=False):
    """``q``, ``k``, ``log_decay [R, T, H, d_k]``, ``v [R, T, H, d_v]``,
    ``beta [R, T, H]``: ``T`` consecutive tokens a row (q and k as the
    rule takes them, the decay a channel as its LOGARITHM, never above
    0, taken where the caller formed it: a factor that underflowed has
    no logarithm to take back); ``state [R, H, d_k, d_v]`` the rows'
    matrices before the first; ``n_real [R]`` (int32) how many of a
    row's tokens are real -> (``o [R, T, H, d_v]``, zero past
    ``n_real``; the state after token ``n_real - 1``, the row's own
    where ``n_real`` is 0).  All float32; the state is updated in place
    (hand it over as it lies).  Jitted, so a model's layers share one
    traced and lowered call."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, t, h, d_k = q.shape
    d_v = v.shape[-1]
    if not kda_rule(h, d_k, d_v, state.dtype):
        raise ValueError(
            f"kda_chunk does not take a {state.dtype} state of {h} heads "
            f"of {d_k} x {d_v} (kda_rule)")
    g, t_run = HEADS_A_STEP, -(-t // CHUNK) * CHUNK

    def flat(x):
        """``[R, T, ...]`` as it lies, ``[R * t_run, H * d]``, the
        tokens padded to whole chunks."""
        return jnp.pad(x.astype(jnp.float32).reshape(r, t, -1), (
            (0, 0), (0, t_run - t), (0, 0))).reshape(r * t_run, -1)

    def vectors(width):
        return pl.BlockSpec((t_run, g * width), lambda i, j, n: (i, j))

    slab = pl.BlockSpec((1, g, d_k, d_v), lambda i, j, n: (i, j, 0, 0))
    # beta a block of heads: [R, H / g, T, g]
    beta = jnp.moveaxis(flat(beta).reshape(r, t_run, h // g, g), 2, 1)
    o, s = pl.pallas_call(
        functools.partial(_chunk_kernel, sub=SUB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(r, h // g),
            in_specs=[vectors(d_k)] * 3 + [vectors(d_v), pl.BlockSpec(
                (1, 1, t_run, g), lambda i, j, n: (i, j, 0, 0)), slab],
            out_specs=[vectors(d_v), slab]),
        out_shape=[jax.ShapeDtypeStruct((r * t_run, h * d_v), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the scalar operand counts: the state is operand 6
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAME,
    )(n_real.astype(jnp.int32), flat(q), flat(k), flat(log_decay), flat(v),
      beta, state)
    return o.reshape(r, t_run, h, d_v)[:, :t], s
