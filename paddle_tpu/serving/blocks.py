"""The stateless pieces the served models' files (``*_lm.py``) are
written with, each once: the matmul feed, the RMS norm, the rotary
pairings, the gated feed-forward, the routed experts' held share, the
head over a prompt's read row, the seeded weight maker, and the
``jax.named_scope`` names a trace reads.  Functions of their arguments
alone: nothing here reads a model's attributes but the helpers that say
so (``model`` a routed model's declarations), nothing keeps state, and
nothing under ``serving/`` is imported (``mixers.py`` and the model
files import this; ``tests/test_tooling.py`` holds the arrows).

A model's ``forward`` stays written out in its own file: what is here
is what several of them spell the same way, so that a change to one
model's block is a change to that model's file alone.
"""
from __future__ import annotations

import math

from ..ops import moe_ops

ROPE_SCOPE = "rope"
DENSE_SCOPE = "dense_ffn"
OUT_PROJ_SCOPE = "latent_out_proj"
# the shared experts' scope has two names in the traces the benchmark's
# readers hold: Solar's and Command A+'s, and the DeepSeek-V3 block's
MOE_SHARED_SCOPE = "moe_shared"
SHARED_FFN_SCOPE = "shared_ffn"


def _mm(a, w):
    """``a @ w`` at the weight's dtype in, float32 out."""
    import jax.numpy as jnp

    return jnp.matmul(a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _mm_t(a, w):
    """``a @ w.T`` at the weight's dtype in, float32 out (``w`` is
    ``[out, in]``)."""
    import jax.numpy as jnp

    return jnp.einsum("...k,nk->...n", a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


# -- the rotary pairings ------------------------------------------------------
def half_split_angles(positions, theta, width):
    """(cos, sin) ``[..., 1, width / 2]`` of the rotary angles at
    ``positions [...]`` and base ``theta``, for ``half_split_rotate``
    over ``width`` lanes."""
    import jax.numpy as jnp

    half = width // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * freq
    return jnp.cos(angle), jnp.sin(angle)


def half_split_rotate(x, cos, sin):
    """The rotary term on the leading lanes of every head of ``x [...,
    heads, D]``, as many as ``half_split_angles`` was asked for: lane j
    pairs with lane ``j + width / 2``; the other lanes pass."""
    import jax.numpy as jnp

    half = cos.shape[-1]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., 2 * half:]],
        axis=-1)


def adjacent_angles(positions, theta, width):
    """(cos, sin) ``[..., 1, width]`` of the rotary angles at
    ``positions [...]``, each pair's angle on both of its lanes, the
    sine UNSIGNED: ``adjacent_rotate_negated_partner``'s two factors."""
    import jax.numpy as jnp

    pair = jnp.arange(width, dtype=jnp.int32) // 2
    freq = theta ** (-2.0 * pair.astype(jnp.float32) / width)
    angle = positions.astype(jnp.float32)[..., None, None] * freq
    return jnp.cos(angle), jnp.sin(angle)


def adjacent_rotate_negated_partner(x, cos, sin):
    """The rotary term on ADJACENT lanes of every head of ``x [...,
    heads, D]``: lanes ``(2j, 2j + 1)`` turn together, the sign in the
    PARTNER (the even lane's is negated).  Each lane's partner comes by
    a roll along the lanes, so no head is cut into pairs (a trailing
    dimension of 2 pads 64-fold on the chip)."""
    import jax.numpy as jnp

    even = jnp.arange(x.shape[-1], dtype=jnp.int32) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return x * cos + partner * sin


def adjacent_angles_signed_sine(positions, freqs, mscale):
    """(cos, sin) ``[..., 1, 2 len(freqs)]`` at ``positions [...]`` for
    the pairs' frequencies ``freqs`` (host floats), a pair's angle on
    both of its lanes, both factors times ``mscale`` and the sign in the
    SINE (negated on the even lane): ``adjacent_rotate_signed_sine``'s
    two factors."""
    import jax.numpy as jnp

    angle = jnp.repeat(
        positions.astype(jnp.float32)[..., None, None]
        * jnp.asarray(freqs, jnp.float32), 2, axis=-1)
    sign = jnp.where(jnp.arange(2 * len(freqs)) % 2 == 0, -1.0, 1.0)
    return jnp.cos(angle) * mscale, jnp.sin(angle) * mscale * sign


def adjacent_rotate_signed_sine(x, cos, sin):
    """The rotary term on ``x [..., heads, D]`` whose lanes ``(2j, 2j +
    1)`` are a pair, where they lie: ``y[2j] = x[2j] cos - x[2j+1]
    sin``, ``y[2j+1] = x[2j+1] cos + x[2j] sin``, each lane times its
    cosine plus its partner times its SIGNED sine (the same numbers as
    ``adjacent_rotate_negated_partner``'s, the sign in the other
    operand: the two lower to different text and each keeps the text of
    the model it came from)."""
    import jax.numpy as jnp

    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return x * cos + partner * sin


def yarn_frequencies(rope_dim, theta, factor, orig_len, beta_fast,
                     beta_slow):
    """The ``rope_dim / 2`` rotary frequencies under YaRN (host floats):
    pair j turns at ``theta^(-2j/d)`` where it completes more than
    ``beta_fast`` turns over the original context, at a ``factor``-th of
    that where fewer than ``beta_slow``, and at a linear blend between
    (the DeepSeek-V3 reading of ``rope_scaling``)."""
    half = rope_dim // 2

    def turns_dim(turns):
        return rope_dim * math.log(orig_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), rope_dim - 1)
    out = []
    for j in range(half):
        f = theta ** (-2.0 * j / rope_dim)
        r = min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f * (1.0 - r) + f / factor * r)
    return out


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


# -- the routed experts' held share -------------------------------------------
def held_ids(held_experts, num_experts):
    """``held_experts`` as a tuple of distinct ids below
    ``num_experts``, or a ``ValueError``."""
    held = tuple(int(e) for e in held_experts)
    if not held or min(held) < 0 or max(held) >= num_experts \
            or len(set(held)) != len(held):
        raise ValueError(
            f"held_experts must be distinct ids below {num_experts}")
    return held


def step_tallies(model, rows):
    """Of a routed model's declared ``tallies``, those a joint step of
    ``rows`` rows reads back: the hit form's only where that step takes
    the form (``moe_ops.hit_rule``: the step's static shape and the
    model's own routing), so a step that keeps the dense form is the
    program it was.  The routed models' ``step_tallies`` method."""
    if moe_ops.hit_rule(rows, len(model.held_experts), model.expert_dim,
                        model.d_model, model.top_k, model.num_experts):
        return model.tallies
    return tuple(n for n in model.tallies if n not in moe_ops.HIT_TALLIES)


def route_share(h, lw, attend, top_k, held_experts, **how):
    """Rows ``h`` routed over all of the layer's experts: the weights
    of the held ones a row (``moe_ops.moe_share_route``'s ``local``),
    with the counts tallied and the chosen ids recorded through
    ``attend``.  ``how``: a model's own ``scoring`` of a logit
    (``moe_ops.moe_share_route``'s), handed on only where a model names
    one: the benchmark's controls stand in for that function with the
    signature it had."""
    ids, _, local = moe_ops.moe_share_route(
        h, lw["moe_router"], lw["moe_router_bias"], top_k=top_k,
        held_ids=held_experts, live=attend.live, **how)
    assigned, hit = moe_ops.moe_share_counts(local)
    attend.tally("moe_local_assignments", assigned)
    attend.tally("moe_experts_hit", hit)
    attend.record("moe_topk", ids)
    return local


def share_ffn(model, h, lw, local, attend):
    """The held experts' part of the routed result for rows ``h`` under
    ``route_share``'s ``local``, in the form the call's shape and the
    model's published routing choose (``moe_ops.moe_share_ffn``)."""
    return moe_ops.moe_share_ffn(
        h, local, lw["moe_w_gate"], lw["moe_w_up"], lw["moe_w_down"],
        tally=attend.tally, interpret=attend.interpret,
        top_k=model.top_k, num_experts=model.num_experts)


# -- the gated feed-forward of the DeepSeek-V3 block --------------------------
def _swiglu(h, lw, name):
    import jax

    return _mm(jax.nn.silu(_mm(h, lw[name + "_w_gate"]))
               * _mm(h, lw[name + "_w_up"]), lw[name + "_w_down"])


def ffn_weights(model, l, dense):
    """Layer ``l``'s feed-forward weights: a dense SwiGLU in the leading
    ``dense_layers``, else the held experts, the router with its
    correction bias and the shared expert."""
    import jax.numpy as jnp

    dm, e, f = model.d_model, model.num_experts, model.expert_dim
    nf, sf = len(model.held_experts) * f, model.shared_dim
    if l < model.dense_layers:
        return dict(ffn_w_gate=dense((dm, model.dense_dim)),
                    ffn_w_up=dense((dm, model.dense_dim)),
                    ffn_w_down=dense((model.dense_dim, dm)))
    return dict(
        moe_router=dense((dm, e), dtype=jnp.float32),
        moe_router_bias=dense((e,), 0.1, jnp.float32),
        moe_w_gate=dense((dm, nf)), moe_w_up=dense((dm, nf)),
        moe_w_down=dense((nf, dm), 1.0 / math.sqrt(f)),
        shared_w_gate=dense((dm, sf)),
        shared_w_up=dense((dm, sf)),
        shared_w_down=dense((sf, dm)))


def feed_forward(model, l, lw, x, attend):
    """``x`` plus layer ``l``'s feed-forward of it: dense in the leading
    layers, else the held experts' scaled part beside the shared
    expert."""
    import jax

    h = rms_norm(x, lw["norm2"], model.rms_eps)
    if l < model.dense_layers:
        with jax.named_scope(DENSE_SCOPE):
            return x + _swiglu(h, lw, "ffn")
    local = route_share(h, lw, attend, model.top_k, model.held_experts)
    routed = share_ffn(model, h, lw, local, attend)
    with jax.named_scope(SHARED_FFN_SCOPE):
        return x + model.routed_scale * routed + _swiglu(h, lw, "shared")


def read_rows(x, attend):
    """The rows of ``x`` whose logits the program reads: of a prompt
    whose ``attend`` names its ``read_row``, that row alone ``[1, D]``
    (the final norm and the head over a whole bucket are up to a
    seventh of a prefill's matmuls and gigabytes of float32 nobody
    reads); else ``x``."""
    import jax

    if getattr(attend, "prompt", False) \
            and getattr(attend, "read_row", None) is not None:
        x = jax.lax.dynamic_slice_in_dim(x, attend.read_row, 1, axis=0)
    return x


def head_logits(model, w, x, attend):
    """The logits of ``x``'s rows, or of a prompt's read row alone."""
    return _mm(rms_norm(read_rows(x, attend), w["norm_f"], model.rms_eps),
               w["lm_head"])


def dense_from(keys, dt):
    """``dense(shape, scale=None, dtype=dt)``: a seeded normal matrix at
    a variance-preserving scale (``shape[0] ** -0.5`` where none is
    given), a key of ``keys`` a call."""
    import jax
    import jax.numpy as jnp

    def dense(shape, scale=None, dtype=dt):
        scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    return dense
