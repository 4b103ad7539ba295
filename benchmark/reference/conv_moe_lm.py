"""Plain forward of a decoder whose layers are gated short convolutions
and grouped-query softmax attention, with a dense SwiGLU in the leading
layers and a mixture of experts in the others, the head tied to the
input embedding: the yardstick for ``correct`` of the cells that serve
``paddle_tpu.serving.conv_moe_lm``.

The architecture is LFM2-8B-A1B's (``LiquidAI/LFM2-8B-A1B``
``config.json``, ``model_type: lfm2_moe``), written out from the weights
dictionary in ``jax.numpy`` float32 at ``highest`` matmul precision over
the WHOLE sequence: no cache, no pages, no state, no kernel, no
batching, and none of the model's own methods.

The equations.  ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``.  A
layer is ``x <- x + mixer(RMSNorm(x; g_1))``, ``x <- x + ffn(RMSNorm(x;
g_2))``; after the last layer ``logits = RMSNorm(x; g_f) E^T``, ``E`` the
input embedding (tied; ``lm_head`` where the weights carry one).  No
biases anywhere.

* Gated short convolution (``kinds[l] == "recurrent"``).  ``[B | C | u]
  = h W_in`` (``W_in [D, 3D]``, the three gates side by side in that
  order); ``z_t = B_t * u_t`` lane by lane; ``c_t = sum_j taps[j] *
  z_{t - (K-1) + j}`` for ``j = 0..K-1`` (depth-wise, causal: ``z`` at a
  negative position is zero; the LAST tap meets the token itself; no
  bias and NO activation); ``y_t = (C_t * c_t) W_out``.
* Attention (``"attention"``).  ``q = h W_q`` (H heads of d), ``k = h
  W_k``, ``v = h W_v`` (``num_kv_heads`` heads of d); ``q`` and ``k``
  through an RMSNorm over each head's d lanes (``q_norm``, ``k_norm``);
  rotary on ALL d lanes, lane ``j < d/2`` paired with lane ``j + d/2``
  and turned by ``p * theta^(-2j / d)`` at the token's absolute position
  ``p``; scores ``q_i . k_j / sqrt(d)`` over ``j <= i``, query head i
  reading K/V head ``i // (H / num_kv_heads)``; ``y = concat_heads(softmax
  V) W_o``.
* Feed-forward.  The first ``dense_layers`` layers: ``(SiLU(h W_g) * h
  W_u) W_d``.  The others: ``s = sigmoid(h W_r)`` over ALL experts, top-k
  by ``s + bias`` (the bias in the CHOICE only), ``w_i = s_i / sum_topk
  s`` (``norm_topk_prob``, scaling 1; the published ``+ 1e-6`` in that
  sum, 5e-7 of a weight, is left out here as in the program),
  ``sum_{i in topk} w_i E_i(h)``, ``E(h) = (SiLU(h W_gate) * h W_up)
  W_down``.  No shared expert.

The share.  ``dims["held"]`` lists the expert ids the weights hold
(every one in the served configuration), expert ``held[j]`` in columns
``j*F:(j+1)*F`` of ``moe_w_gate``/``moe_w_up`` and rows ``j*F:(j+1)*F``
of ``moe_w_down``.  The sum above runs over the chosen experts that are
held; what an absent expert would add is left out, here as in the
program.

``routing`` (optional, ``[T, L, k]`` expert ids the SERVED model chose,
L the layers that have experts, in order): those layers then follow the
ids instead of their own top-k, after measuring how far each chosen id
lies below the reference's own k-th largest ranked score (returned as
``gap``: 0 where they agree; ids of another width than k are not
followed and read a gap of 1); weights and everything else are computed
here.  Weights may be bfloat16: each is upcast where it is used, the
experts one at a time and the K/V heads one group at a time, so that the
published widths fit beside a served copy of the model.  ``rows``
(optional ``(first, count)``): the head over those rows only.
"""
import math


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rounded(a, dims, what):
    """``a`` rounded to the values the dtype ``dims[what]`` names can
    hold; as it is where ``dims`` names none.  ``reduce_precision`` and
    not a pair of casts: the compiler is free to drop a cast down and
    up again (``xla_allow_excess_precision``, on by default; it does on
    the TPU), and then nothing is rounded."""
    import jax
    import jax.numpy as jnp

    if dims.get(what) is None:
        return a
    info = jnp.finfo(jnp.dtype(dims[what]))
    return jax.lax.reduce_precision(a, info.nexp, info.nmant)


def _operand(a, dims):
    return _rounded(_f32(a), dims, "operands")


def _dot(a, b, dims):
    """``a @ b`` of float32 ``a`` and a weight ``b``."""
    return _rounded(_operand(a, dims) @ _operand(b, dims), dims, "results")


def _rms(x, g, dims):
    import jax.numpy as jnp

    return _rounded(
        x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                     + dims["eps"]) * _f32(g), dims, "results")


def _rotary(x, theta):
    """x [T, heads, d] at positions 0..T-1, every lane turned."""
    import jax.numpy as jnp

    d = x.shape[-1]
    half = d // 2
    p = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None]
    angle = p / theta ** (2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle)],
                           axis=-1)


def conv_layer(lw, x, dims):
    """The gated short convolution's residual update of the whole
    sequence x [T, D]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t, d = x.shape
        k = dims["conv_kernel"]
        h = _rms(x, lw["norm1"], dims)
        gates = _dot(h, lw["conv_w_in"], dims)
        b, c, u = gates[:, :d], gates[:, d:2 * d], gates[:, 2 * d:]
        z = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), b * u])
        taps = _f32(lw["conv_taps"])
        conv = jnp.zeros((t, d), jnp.float32)
        for j in range(k):
            conv = conv + taps[j] * z[j:j + t]
        return x + _dot(c * conv, lw["conv_w_out"], dims)


def attention_layer(lw, x, dims):
    """The attention sub-block's residual update of the whole sequence
    x [T, D]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, hkv, d = dims["num_heads"], dims["num_kv_heads"], \
            dims["head_dim"]
        h = _rms(x, lw["norm1"], dims)
        q = _dot(h, lw["wq"], dims).reshape(t, nh, d)
        k = _dot(h, lw["wk"], dims).reshape(t, hkv, d)
        v = _operand(_dot(h, lw["wv"], dims).reshape(t, hkv, d), dims)
        q = _operand(_rotary(_rms(q, lw["q_norm"], dims),
                             dims["rope_theta"]), dims)
        k = _operand(_rotary(_rms(k, lw["k_norm"], dims),
                             dims["rope_theta"]), dims)
        seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

        def group(args):
            """The query heads of one K/V head."""
            qg, kg, vg = args               # [T,G,d] [T,d] [T,d]
            s = _rounded(jnp.einsum("igd,jd->gij", qg, kg), dims,
                         "results") / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return _rounded(jnp.einsum("gij,jd->igd", _operand(p, dims),
                                       vg), dims, "results")

        ctx = jax.lax.map(group, (
            jnp.moveaxis(q.reshape(t, hkv, nh // hkv, d), 1, 0),
            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        ctx = jnp.moveaxis(ctx, 0, 1)                       # [T,hkv,G,d]
        return x + _dot(ctx.reshape(t, nh * d), lw["wo"], dims)


def dense_layer(lw, x, dims):
    """A leading layer's dense feed-forward residual update."""
    import jax

    with jax.default_matmul_precision("highest"):
        h = _rms(x, lw["norm2"], dims)
        return x + _dot(jax.nn.silu(_dot(h, lw["ffn_w_gate"], dims))
                        * _dot(h, lw["ffn_w_up"], dims),
                        lw["ffn_w_down"], dims)


def moe_layer(lw, x, dims, ids=None, held=None):
    """The expert layer's residual update of x [T, D] -> (x, gap [T]).
    ``ids`` [T, k]: follow these experts (``gap`` says how far below the
    reference's own k-th ranked score the worst of them lies); ``held``
    overrides ``dims["held"]`` as the ids whose weights ``lw`` holds."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        held = dims["held"] if held is None else held
        f, top_k = dims["expert_dim"], dims["top_k"]
        h = _rms(x, lw["norm2"], dims)
        scores = jax.nn.sigmoid(                            # [T, E]
            h @ _rounded(_f32(lw["moe_router"]), dims, "results"))
        ranked = scores + _f32(lw["moe_router_bias"])
        kth = jax.lax.top_k(ranked, top_k)[0][:, -1]
        # ids of another width than top_k are a router that chose
        # another number of experts: not followed, and a gap of 1
        other_k = ids is not None and ids.shape[-1] != top_k
        if ids is None or other_k:
            ids = jax.lax.top_k(ranked, top_k)[1]
        gap = jnp.max(kth[:, None]
                      - jnp.take_along_axis(ranked, ids, axis=1), axis=1)
        if other_k:
            gap = jnp.ones_like(gap)
        w = jnp.take_along_axis(scores, ids, axis=1)
        w = w / jnp.sum(w, axis=1, keepdims=True)

        def expert(j, y):
            mine = jnp.sum(jnp.where(
                ids == jnp.asarray(held, jnp.int32)[j], w, 0.0), axis=1)
            cols = lambda m: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                m, j * f, f, axis=1)
            act = jax.nn.silu(_dot(h, cols(lw["moe_w_gate"]), dims)) \
                * _dot(h, cols(lw["moe_w_up"]), dims)
            down = jax.lax.dynamic_slice_in_dim(
                lw["moe_w_down"], j * f, f, axis=0)
            return y + mine[:, None] * _dot(act, down, dims)

        y = jax.lax.fori_loop(0, len(held), expert, jnp.zeros_like(x))
        return x + y, gap


def head(w, x, dims, rows=None):
    """Logits of x [T, D] (of ``rows = (first, count)`` of it): the tied
    head is the embedding transposed."""
    import jax

    with jax.default_matmul_precision("highest"):
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        x = _rms(x, w["norm_f"], dims)
        if "lm_head" in w:
            return _dot(x, w["lm_head"], dims)
        return _dot(x, w["tok_emb"].T, dims)


def forward_logits(w, tokens, dims, routing=None, rows=None):
    """``tokens`` [T] int32 -> (logits [T or count, vocab], gap [T, L]),
    L the layers that have experts.  Every position is real: nothing
    here looks ahead, so rows past a sequence's end only cost time."""
    import jax.numpy as jnp

    x = _f32(w["tok_emb"][tokens])
    gaps = []
    for l, (kind, lw) in enumerate(zip(dims["kinds"], w["layers"])):
        x = attention_layer(lw, x, dims) if kind == "attention" \
            else conv_layer(lw, x, dims)
        if l < dims["dense_layers"]:
            x = dense_layer(lw, x, dims)
            continue
        x, gap = moe_layer(lw, x, dims, None if routing is None
                           else routing[:, len(gaps)])
        gaps.append(gap)
    head_w = {k: w[k] for k in ("norm_f", "tok_emb", "lm_head") if k in w}
    return head(head_w, x, dims, rows), (
        jnp.stack(gaps, axis=1) if gaps
        else jnp.zeros((x.shape[0], 0), jnp.float32))
