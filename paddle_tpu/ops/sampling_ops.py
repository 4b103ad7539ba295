"""Sampling-based ops: NCE, sample_logits, correlation cost volume —
plus the jit-safe token samplers (greedy / top-k / top-p) the decode
engine (serving/decode.py) runs INSIDE its compiled step.

Reference parity: operators/nce_op.{cc,h} (noise-contrastive estimation
with uniform/log-uniform samplers), operators/sample_logits_op.cc, and
operators/correlation_op.cu (FlowNet cost volume).

Token-sampler contract: every draw takes an EXPLICIT PRNG key (the
engine derives one per request from its seed via fold_in, so a
request's token stream is independent of which slot or replica served
it, and — with ``jax_threefry_partitionable`` enabled process-wide at
Executor construction since PR 7 — independent of how XLA shards the
batch).  ``tests/test_decode_engine.py`` pins two replicas given the same
seed emitting identical tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.lowering import register_lower
from .common import op_seed_key


def _sampler_prob(idx, sampler, n_classes, custom_probs=None):
    """P(class) under the sampler — ONE home for the Zipfian formula
    (reference sampler.cc LogUniformSampler::Probability; CustomSampler
    reads the user distribution)."""
    if sampler == 2:
        return custom_probs[jnp.asarray(idx).astype(jnp.int32)]
    if sampler == 0:
        return jnp.full(jnp.shape(idx), 1.0 / n_classes)
    idxf = jnp.asarray(idx).astype(jnp.float32)
    return (jnp.log((idxf + 2.0) / (idxf + 1.0))) / np.log(n_classes + 1.0)


def _draw_samples(ctx, op, n_samples, n_classes):
    """-> (samples, sample_probs, custom_probs-or-None).  The custom
    distribution is fetched + normalized HERE, once, for every caller
    (nce, sample_logits) — the sampling draw and the probability
    corrections must read the same normalized values."""
    sampler = int(op.attr("sampler", 0))
    k = op_seed_key(ctx, op)
    custom_probs = None
    if sampler == 0:  # uniform
        s = jax.random.randint(k, (n_samples,), 0, n_classes)
    elif sampler == 1:  # log-uniform (Zipfian), reference math
        u = jax.random.uniform(k, (n_samples,))
        s = (jnp.exp(u * np.log(n_classes + 1.0)) - 1.0).astype(jnp.int32)
        s = jnp.clip(s, 0, n_classes - 1)
    elif sampler == 2:
        # custom distribution (reference CustomSampler builds an alias
        # table from CustomDistProbs/Alias/AliasProbs; categorical over
        # the same probs is the TPU-native equivalent — identical
        # distribution, no table plumbing)
        custom_probs = ctx.in1(op, "CustomDistProbs")
        if custom_probs is None:
            raise ValueError(
                f"{op.type} sampler=2 (custom_dist) needs the "
                f"CustomDistProbs input (per-class sampling "
                f"probabilities)")
        custom_probs = custom_probs.reshape(-1).astype(jnp.float32)
        # normalize: categorical would silently normalize raw counts,
        # desynchronizing the draw from the reported probabilities
        custom_probs = custom_probs / jnp.sum(custom_probs)
        s = jax.random.categorical(
            k, jnp.log(jnp.maximum(custom_probs, 1e-30)), shape=(n_samples,))
        s = s.astype(jnp.int32)
    else:
        raise NotImplementedError(f"{op.type} sampler {sampler} is unknown")
    return (s, _sampler_prob(s, sampler, n_classes,
                             custom_probs=custom_probs), custom_probs)


@register_lower("nce")
def _nce(ctx, op):
    """Noise-contrastive estimation (reference nce_op.h): binary logistic
    loss over the true class + num_neg_samples drawn noise classes."""
    x = ctx.in1(op, "Input")  # [B, D]
    label = ctx.in1(op, "Label")  # [B, T] true classes
    w = ctx.in1(op, "Weight")  # [num_classes, D]
    b = ctx.in1(op, "Bias")  # [num_classes] or None
    n_classes = int(op.attr("num_total_classes"))
    n_neg = int(op.attr("num_neg_samples", 10))

    bsz = x.shape[0]
    t = label.shape[1] if label.ndim > 1 else 1
    lbl = label.reshape(bsz, t)
    samples, sample_prob, custom_probs = _draw_samples(
        ctx, op, n_neg, n_classes)

    true_logit = jnp.einsum("bd,btd->bt", x, w[lbl])
    if b is not None:
        true_logit = true_logit + b[lbl]
    noise_logit = x @ w[samples].T  # [B, n_neg]
    if b is not None:
        noise_logit = noise_logit + b[samples]

    sampler = int(op.attr("sampler", 0))
    p_true = _sampler_prob(lbl, sampler, n_classes,
                           custom_probs=custom_probs)
    # NCE: sigmoid cross-entropy against logit - log(k * P_noise);
    # softplus keeps large logits finite (log1p(exp(x)) overflows)
    k = float(n_neg)
    true_adj = true_logit - jnp.log(k * p_true)
    noise_adj = noise_logit - jnp.log(k * sample_prob)[None, :]
    pos_loss = jax.nn.softplus(-true_adj).sum(axis=1)
    neg_loss = jax.nn.softplus(noise_adj).sum(axis=1)
    ctx.set_out(op, "Cost", (pos_loss + neg_loss).reshape(bsz, 1))
    ctx.set_out(op, "SampleLogits",
                jnp.concatenate([true_logit, noise_logit], axis=1))
    ctx.set_out(op, "SampleLabels", jnp.concatenate(
        [lbl, jnp.broadcast_to(samples[None], (bsz, n_neg))],
        axis=1).astype(jnp.int32))


@register_lower("sample_logits")
def _sample_logits(ctx, op):
    """Sampled-softmax helper (reference sample_logits_op): gather the
    true-label logits plus sampled-class logits, with the log-prob
    correction, for a cheap softmax over num_samples classes."""
    logits = ctx.in1(op, "Logits")  # [B, C]
    label = ctx.in1(op, "Labels")  # [B, T]
    n_samples = int(op.attr("num_samples", 10))
    c = logits.shape[1]
    bsz = logits.shape[0]
    t = label.shape[1]
    samples, prob, custom_probs = _draw_samples(ctx, op, n_samples, c)
    all_idx = jnp.concatenate(
        [label.astype(jnp.int32),
         jnp.broadcast_to(samples[None].astype(jnp.int32),
                          (bsz, n_samples))], axis=1)
    picked = jnp.take_along_axis(logits, all_idx, axis=1)
    if bool(op.attr("remove_accidental_hits", True)):
        acc = (all_idx[:, t:, None]
               == label[:, None, :].astype(jnp.int32)).any(-1)
        picked = picked.at[:, t:].add(-1e20 * acc.astype(picked.dtype))
    # subtract log Q as in sampled softmax (true labels use the SAME
    # sampler distribution as the drawn negatives)
    sampler = int(op.attr("sampler", 0))
    logq = jnp.concatenate(
        [jnp.log(_sampler_prob(label, sampler, c,
                               custom_probs=custom_probs)),
         jnp.broadcast_to(jnp.log(prob)[None], (bsz, n_samples))], axis=1)
    ctx.set_out(op, "SampledLogits", picked - logq)
    ctx.set_out(op, "SampledLabels",
                jnp.broadcast_to(jnp.arange(t)[None], (bsz, t))
                .astype(jnp.int32))
    ctx.set_out(op, "Samples", all_idx.astype(jnp.int32))
    ctx.set_out(op, "Probabilities", jnp.exp(logq))
    ctx.set_out(op, "LogitsDim", jnp.asarray(logits.shape, jnp.int32))
    ctx.set_out(op, "LabelsDim", jnp.asarray(label.shape, jnp.int32))


@register_lower("correlation")
def _correlation(ctx, op):
    """FlowNet correlation cost volume (reference correlation_op.cu):
    for each displacement in the max_displacement neighborhood, the
    channel-mean of x1(p) * x2(p + d) over kernel patches."""
    x1 = ctx.in1(op, "Input1")  # [N, C, H, W]
    x2 = ctx.in1(op, "Input2")
    pad = int(op.attr("pad_size", 0))
    ks = int(op.attr("kernel_size", 1))
    max_disp = int(op.attr("max_displacement", 1))
    stride1 = int(op.attr("stride1", 1))
    stride2 = int(op.attr("stride2", 1))
    if ks % 2 == 0:
        raise NotImplementedError("correlation kernel_size must be odd")
    kr = (ks - 1) // 2
    n, c, h, w = x1.shape
    # over-pad by the kernel radius so centered windows at every
    # sampled position (and every displacement) stay in bounds
    pw = pad + kr
    x1p = jnp.pad(x1, ((0, 0), (0, 0), (pw, pw), (pw, pw)))
    x2p = jnp.pad(x2, ((0, 0), (0, 0), (pw, pw), (pw, pw)))
    # reference grid: radius = max_disp // stride2, displacements are
    # multiples of stride2 (correlation_op InferShape)
    radius = max_disp // stride2
    disps = [i * stride2 for i in range(-radius, radius + 1)]
    outs = []
    hp, wp = h + 2 * pad, w + 2 * pad
    # reference geometry (correlation_op.cc CorrelationOutputSize):
    # border_radius = max_displacement + kernel_radius bounds both the
    # output size and the sample centers
    border = max_disp + kr
    oh = -(-(hp - 2 * border) // stride1)  # ceil div
    ow = -(-(wp - 2 * border) // stride1)
    # in top-left-corner coordinates of the k-window box filter, the
    # sampled centers land back at border + stride1*i (pad frame)
    base_y = border + stride1 * jnp.arange(oh)
    base_x = border + stride1 * jnp.arange(ow)
    for dy in disps:
        for dx in disps:
            # roll-shift: wraparound rows/cols sit outside every
            # accessed window (centers stop border short of the edge
            # and |d| <= max_disp <= border), so they are never read
            x2s = jnp.roll(x2p, (-dy, -dx), axis=(2, 3))
            prod = jnp.mean(x1p * x2s, axis=1)  # channel mean [N,Hp,Wp]
            if ks > 1:
                # restrict to the accessed band, then stride the window
                # reduce — corners land exactly on the sample centers
                # (no wasted rows/cols when stride1 > 1)
                lim_y = border + stride1 * (oh - 1) + 2 * kr + 1
                lim_x = border + stride1 * (ow - 1) + 2 * kr + 1
                band = prod[:, border:lim_y, border:lim_x]
                outs.append(jax.lax.reduce_window(
                    band, 0.0, jax.lax.add, (1, ks, ks),
                    (1, stride1, stride1), "VALID") / float(ks * ks))
            else:
                outs.append(prod[:, base_y[:, None], base_x[None, :]])
    ctx.set_out(op, "Output", jnp.stack(outs, axis=1))


# -- decode-time token samplers (serving/decode.py) -----------------------


def greedy_sample(logits):
    """argmax over the vocab axis -> int32 token ids."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def filter_top_k_top_p(logits, top_k, top_p):
    """Mask logits outside the per-row top-k / nucleus-p sets to -inf.

    Fully jit-safe with DYNAMIC per-row knobs: ``top_k`` [..] int32
    (<= 0 disables) and ``top_p`` [..] float (>= 1.0 disables) are
    data, not static arguments, so one compiled step serves any mix of
    per-slot sampling configs.  Ties at the threshold logit are kept
    (the standard sorted-threshold caveat).

    The sort runs only when some row asks for a filter (a ``lax.cond``
    on the knobs): a row with both disabled keeps every logit either
    way, so its result does not depend on its neighbours.
    """
    def filtered():
        v = logits.shape[-1]
        desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        # top-k: keep logits >= the k-th largest (k clipped into [1, V])
        k_idx = jnp.clip(top_k - 1, 0, v - 1)
        thresh_k = jnp.take_along_axis(desc, k_idx[..., None], axis=-1)
        keep_k = (top_k <= 0)[..., None] | (logits >= thresh_k)
        # top-p: over the sorted distribution keep the minimal prefix
        # whose mass reaches p (the first token is always kept: cum -
        # prob < p)
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < top_p[..., None]
        thresh_p = jnp.min(jnp.where(keep_sorted, desc, jnp.inf), axis=-1,
                           keepdims=True)
        keep_p = (top_p >= 1.0)[..., None] | (logits >= thresh_p)
        return jnp.where(keep_k & keep_p, logits, -jnp.inf)

    return jax.lax.cond(jnp.any((top_k > 0) | (top_p < 1.0)), filtered,
                        lambda: logits)


def sample_tokens(keys, logits, temperature, top_k, top_p):
    """One token per row: greedy when temperature <= 0, else a
    categorical draw over the temperature-scaled, top-k/top-p-filtered
    distribution.  ``keys`` [S, 2] uint32 (one PRNGKey per row — the
    explicit key thread), logits [S, V]; temperature/top_k/top_p [S].

    The batch pays for what its rows ask: with no temperature above 0
    the result is the argmax alone, and the draw sorts only when a
    drawing row filters (``filter_top_k_top_p``).  Each row's token is
    the same in either branch, so a request's tokens do not depend on
    its batch neighbours.  The predicates read every row: a row that is
    no request (the engine's dead slot) must carry temperature 0, as
    ``DecodeEngine._step_args`` fills it (with top_k 0, top_p 1).
    """
    def draw():
        greedy = temperature <= 0.0
        t = jnp.where(greedy, 1.0, temperature)
        # a greedy row's filter is never read: its knobs do not count
        # towards the sort
        filt = filter_top_k_top_p(
            logits / t[..., None], jnp.where(greedy, 0, top_k),
            jnp.where(greedy, 1.0, top_p))
        drawn = jax.vmap(jax.random.categorical)(keys, filt)
        return jnp.where(greedy, greedy_sample(logits),
                         drawn.astype(jnp.int32))

    return jax.lax.cond(jnp.any(temperature > 0.0), draw,
                        lambda: greedy_sample(logits))
