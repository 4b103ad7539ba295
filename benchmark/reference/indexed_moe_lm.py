"""Plain forward of a decoder whose attention reads only the positions a
learned indexer selects, with a mixture of experts in every layer and
an untied head: the yardstick for ``correct`` of the cells that serve
``paddle_tpu.serving.indexed_moe_lm``.

The architecture is the language model of Keye-VL-2.0-30B-A3B
(``Kwai-Keye/Keye-VL-2.0-30B-A3B`` ``config.json``, ``model_type:
KeyeVL2``: a Qwen3-MoE-shaped block with the lightning indexer of
DeepSeek's sparse attention, ``sa_config``), written out from the
weights dictionary in ``jax.numpy`` float32 at ``highest`` matmul
precision over the WHOLE sequence: no cache, no pages, no kernel, no
batching, and none of the model's own methods.

The equations.  ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``.  A
layer is ``x <- x + attention(RMSNorm(x; g_1))``, ``x <- x +
experts(RMSNorm(x; g_2))``; after the last layer ``logits = RMSNorm(x;
g_f) W_head``.  No biases but the index key's.

* Attention.  ``q = h W_q`` (H heads of d), ``k = h W_k``, ``v = h W_v``
  (``num_kv_heads`` heads of d); ``q`` and ``k`` through an RMSNorm over
  each head's d lanes (``q_norm``, ``k_norm``); rotary on ALL d lanes,
  lane ``j < d/2`` paired with lane ``j + d/2`` and turned by ``p *
  theta^(-2j / d)`` at the token's absolute position ``p``.
* Indexer (a layer its own).  ``qI = h W_Iq`` (``index_heads`` heads of
  ``index_dim``), ``kI = LayerNorm(h W_Ik; gain, bias)`` (ONE head: mean
  removed, variance + eps), ``w = h W_Iw`` (a scalar a head); ``qI`` and
  ``kI`` carry the same rotary term on their ``index_dim`` lanes.
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``;
  ``S_t`` = the ``topk`` positions of largest ``I[t, .]``, EQUAL SCORES
  THE LOWER POSITION FIRST (a full stable sort), every position ``<= t``
  while there are no more than ``topk``.
* Attention over the selection.  ``ctx_i = sum_{s in S_t} softmax_{s in
  S_t}(q_i . k_{i // G, s} / sqrt(d)) v_{i // G, s}``, one selection a
  token a layer for all heads, ``G = H / num_kv_heads``; ``y =
  concat_heads(ctx) W_o``.
* Experts.  ``p = softmax(h W_r)`` over ALL experts, the top-k by ``p``,
  ``w_e = p_e / sum_topk p``, ``sum_e w_e E_e(h)``, ``E(h) = (SiLU(h
  W_gate) * h W_up) W_down``.  No shared expert.

Departures from the published description: DeepSeek's constant factors
on ``I`` (``index_heads^-1/2 * index_dim^-1/2``, positive: they change no
order) are left out; the three multimodal rotary position streams
(``mrope_section``) carry one position (text), which is plain rotary;
the vision tower is absent.

The share.  ``dims["held"]`` lists the expert ids the weights hold,
expert ``held[j]`` in columns ``j*F:(j+1)*F`` of ``moe_w_gate`` /
``moe_w_up`` and rows ``j*F:(j+1)*F`` of ``moe_w_down``.  The sum above
runs over the chosen experts that are held; what an absent expert would
add is left out, here as in the program.

``routing`` (optional, ``[T, L, k]`` expert ids the SERVED model chose):
the layers follow them after measuring each against the reference's own
scores (``route_gap``: how far below its own k-th largest probability
the worst followed one lies).  ``selections`` (optional, ``[L, T, T]``
bool, the positions the SERVED model attended a row a layer): the layers
follow them after measuring each row against the reference's own ``I``
(``select_gap``: how far below its own ``topk``-th largest score the
worst followed position lies, in standard deviations of the row's scores
over its live positions; ``moved``: followed positions that are not in
its own ``S_t``, or positions of its own it misses, whichever is more).
Weights may be bfloat16: each is upcast where it is used, the experts
one at a time, and the attention runs ``dims["block"]`` query rows at a
time, so that the published widths fit beside a served copy of the
model.  ``rows`` (optional ``(first, count)``): the head over those rows
only.
"""
import math


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(g)


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


def index_scores(lw, h, dims):
    """(qI [T, Hi, Di], kI [T, Di], w [T, Hi]) of the normed rows h."""
    import jax.numpy as jnp

    t = h.shape[0]
    qi = (h @ _f32(lw["index_wq"])).reshape(
        t, dims["index_heads"], dims["index_dim"])
    ki = h @ _f32(lw["index_wk"])
    mu = jnp.mean(ki, axis=-1, keepdims=True)
    ki = (ki - mu) / jnp.sqrt(
        jnp.mean(jnp.square(ki - mu), axis=-1, keepdims=True)
        + dims["eps"]) * _f32(lw["index_k_gain"]) + _f32(lw["index_k_bias"])
    return _rotary(qi, dims["rope_theta"]), \
        _rotary(ki[:, None], dims["rope_theta"])[:, 0], \
        h @ _f32(lw["index_ww"])


def attention_layer(lw, x, dims, selected=None):
    """The attention sub-block's residual update of the whole sequence
    x [T, D] -> (x, select_gap [T], moved [T]); ``selected`` [T, T] bool
    is followed in place of the layer's own selection."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, hkv, d = dims["num_heads"], dims["num_kv_heads"], \
            dims["head_dim"]
        topk = dims["topk"]
        blk = min(dims.get("block", 512), t)
        if t % blk:
            raise ValueError(f"{t} rows are not whole blocks of {blk}")
        h = _rms(x, lw["norm1"], dims["eps"])
        q = (h @ _f32(lw["wq"])).reshape(t, nh, d)
        k = (h @ _f32(lw["wk"])).reshape(t, hkv, d)
        v = (h @ _f32(lw["wv"])).reshape(t, hkv, d)
        q = _rotary(_rms(q, lw["q_norm"], dims["eps"]), dims["rope_theta"])
        k = _rotary(_rms(k, lw["k_norm"], dims["eps"]), dims["rope_theta"])
        qi, ki, w = index_scores(lw, h, dims)
        col = jnp.arange(t, dtype=jnp.int32)[None, :]
        follow = selected is not None

        def block(args):
            first, chosen = args
            sl = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, first, blk, axis=0)
            row = first + jnp.arange(blk, dtype=jnp.int32)[:, None]
            seen = col <= row                               # [blk, T]
            i_ts = jnp.sum(jax.nn.relu(jnp.einsum(
                "thd,sd->ths", sl(qi), ki)) * sl(w)[..., None], axis=1)
            masked = jnp.where(seen, i_ts, -jnp.inf)
            # a full stable sort: equal scores the lower position first
            order = jnp.argsort(-masked, axis=-1, stable=True)
            rank = jnp.argsort(order, axis=-1)
            own = seen & (rank < topk)
            if follow:
                chosen = chosen & seen
                kth = jnp.take_along_axis(
                    masked, order[:, min(topk, t) - 1:min(topk, t)],
                    axis=-1)[:, 0]
                kth = jnp.where(row[:, 0] + 1 > topk, kth, -jnp.inf)
                worst = jnp.min(jnp.where(chosen, i_ts, jnp.inf), axis=-1)
                n = (row[:, 0] + 1).astype(jnp.float32)
                mean = jnp.sum(jnp.where(seen, i_ts, 0.0), axis=-1) / n
                sd = jnp.sqrt(jnp.sum(jnp.where(
                    seen, jnp.square(i_ts - mean[:, None]), 0.0),
                    axis=-1) / n)
                gap = jnp.maximum(kth - worst, 0.0) / jnp.maximum(sd, 1e-30)
                moved = jnp.maximum(
                    jnp.sum(chosen & ~own, axis=-1),
                    jnp.sum(own & ~chosen, axis=-1)).astype(jnp.int32)
            else:
                chosen = own
                gap = jnp.zeros((blk,), jnp.float32)
                moved = jnp.zeros((blk,), jnp.int32)
            qb = sl(q).reshape(blk, hkv, nh // hkv, d)
            s = jnp.einsum("thgd,uhd->hgtu", qb, k) / math.sqrt(d)
            p = jax.nn.softmax(
                jnp.where(chosen[None, None], s, -jnp.inf), axis=-1)
            ctx = jnp.einsum("hgtu,uhd->thgd", p, v)
            return ctx.reshape(blk, nh * d), gap, moved

        firsts = jnp.arange(0, t, blk, dtype=jnp.int32)
        chosen = selected.reshape(t // blk, blk, t) if follow \
            else jnp.zeros((t // blk, 1, 1), bool)
        ctx, gap, moved = jax.lax.map(block, (firsts, chosen))
        return x + ctx.reshape(t, nh * d) @ _f32(lw["wo"]), \
            gap.reshape(t), moved.reshape(t)


def moe_layer(lw, x, dims, ids=None, held=None):
    """The expert layer's residual update of x [T, D] -> (x, gap [T]).
    ``ids`` [T, k]: follow these experts (``gap`` says how far below the
    reference's own k-th largest probability the worst of them lies);
    ``held`` overrides ``dims["held"]`` as the ids whose weights ``lw``
    holds."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        held = dims["held"] if held is None else held
        f, top_k = dims["expert_dim"], dims["top_k"]
        h = _rms(x, lw["norm2"], dims["eps"])
        p = jax.nn.softmax(h @ _f32(lw["moe_router"]), axis=-1)  # [T, E]
        kth = jax.lax.top_k(p, top_k)[0][:, -1]
        # ids of another width than top_k are a router that chose
        # another number of experts: not followed, and a gap of 1
        other_k = ids is not None and ids.shape[-1] != top_k
        if ids is None or other_k:
            ids = jax.lax.top_k(p, top_k)[1]
        gap = jnp.max(kth[:, None]
                      - jnp.take_along_axis(p, ids, axis=1), axis=1)
        if other_k:
            gap = jnp.ones_like(gap)
        w = jnp.take_along_axis(p, ids, axis=1)
        w = w / jnp.sum(w, axis=1, keepdims=True)

        def expert(j, y):
            mine = jnp.sum(jnp.where(
                ids == jnp.asarray(held, jnp.int32)[j], w, 0.0), axis=1)
            cols = lambda m: _f32(jax.lax.dynamic_slice_in_dim(  # noqa: E731
                m, j * f, f, axis=1))
            act = jax.nn.silu(h @ cols(lw["moe_w_gate"])) \
                * (h @ cols(lw["moe_w_up"]))
            down = _f32(jax.lax.dynamic_slice_in_dim(
                lw["moe_w_down"], j * f, f, axis=0))
            return y + mine[:, None] * (act @ down)

        y = jax.lax.fori_loop(0, len(held), expert, jnp.zeros_like(x))
        return x + y, gap


def head(w, x, dims, rows=None):
    """Logits of x [T, D] (of ``rows = (first, count)`` of it)."""
    import jax

    with jax.default_matmul_precision("highest"):
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return _rms(x, w["norm_f"], dims["eps"]) @ _f32(w["lm_head"])


def forward_logits(w, tokens, dims, routing=None, selections=None,
                   rows=None):
    """``tokens`` [T] int32 -> (logits [T or count, vocab], route_gap
    [T, L], select_gap [T, L], moved [T, L]).  Every position is real:
    nothing here looks ahead, so rows past a sequence's end only cost
    time."""
    import jax.numpy as jnp

    x = _f32(w["tok_emb"][tokens])
    gaps, sgaps, moves = [], [], []
    for l, lw in enumerate(w["layers"]):
        x, sgap, moved = attention_layer(
            lw, x, dims, None if selections is None else selections[l])
        x, gap = moe_layer(lw, x, dims,
                           None if routing is None else routing[:, l])
        gaps.append(gap)
        sgaps.append(sgap)
        moves.append(moved)
    head_w = {k: w[k] for k in ("norm_f", "lm_head")}
    return head(head_w, x, dims, rows), jnp.stack(gaps, axis=1), \
        jnp.stack(sgaps, axis=1), jnp.stack(moves, axis=1)
