"""Plain forward of a decoder whose attention layers alternate between a
sliding window (with a learned sink) and every position, with K heads
wider than V heads, a partial rotary term, a leading dense feed-forward
and a mixture of experts in the other layers: the yardstick for
``correct`` of the cells that serve ``paddle_tpu.serving.window_moe_lm``.

The architecture is MiMo-V2.5's language model (``XiaomiMiMo/MiMo-V2.5``
``config.json``, ``model_type: mimo_v2``), written out from the weights
dictionary in ``jax.numpy`` float32 at ``highest`` matmul precision over
the WHOLE sequence: no cache, no pages, no ring, no kernel, no batching,
and none of the model's own methods.

The equations.  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.  A layer
is ``x <- x + attn(RMSNorm_1(x))``, ``x <- x + ffn(RMSNorm_2(x))``; after
the last layer ``logits = RMSNorm_f(x) W_head`` (untied).  No biases.

* Attention, layer ``l`` of kind ``k = kinds[l]`` (``"attention"``:
  every position; ``"window"``: the last ``window``).  ``q = h W_q`` (H
  heads of D), ``k = h W_k`` (``kv_heads[k]`` heads of D), ``v = h W_v``
  (``kv_heads[k]`` heads of Dv < D); query head i reads K/V head
  ``i // (H / kv_heads[k])``.  Rotary on the FIRST ``rotary_dim`` lanes
  of every q and k head, the others untouched: lane ``j <
  rotary_dim/2`` pairs with lane ``j + rotary_dim/2`` and the pair turns
  by ``p * theta_k^(-2j / rotary_dim)`` at the token's absolute position
  ``p`` (the half-split convention), ``theta_k`` = ``rope_theta[k]``.
  Scores ``s_ij = q_i . k_j / sqrt(D)``; an attention layer keeps ``j <=
  i``, a window layer ``i - window < j <= i`` (the token itself
  counted).  A window layer has a learned logit a head, ``sink_h``, that
  joins the softmax and takes its share with it: ``p_ij = exp(s_ij - m)
  / (sum_j' exp(s_ij' - m) + exp(sink_h - m))``.  ``ctx_i = value_scale
  * sum_j p_ij v_j`` (both kinds); ``y = concat_heads(ctx) W_o``.
* Feed-forward.  The first ``dense_layers`` layers: ``(SiLU(h W_g) * h
  W_u) W_d``.  The others: ``s = sigmoid(h W_r)`` over ALL experts,
  top-k by ``s + bias`` (the correction bias exists and is zero; no
  group limit), ``w_i = s_i / sum_topk s`` (``norm_topk_prob``, scaling
  1), ``sum_{i in topk} w_i E_i(h)``, ``E(h) = (SiLU(h W_gate) * h W_up)
  W_down``.  There is no shared expert.

The share.  ``dims["held"]`` lists the expert ids this chip holds (one
chip's share of an expert-parallel group); the weights hold those
experts only, expert ``held[j]`` in columns ``j*F:(j+1)*F`` of
``moe_w_gate``/``moe_w_up`` and rows ``j*F:(j+1)*F`` of ``moe_w_down``.
The sum above then runs over the chosen experts that are held: what the
absent experts would add is left out, here as in the program, and that
partial result goes on to the next layer.

Assumptions the published config is silent on (the configuration file
lists them): the half-split rotary pairing, one float32 sink scalar a
query head a window layer, the value scale in both kinds of layer, a
zero correction bias, a window that counts the token itself.

``routing`` (optional, ``[T, L, k]`` expert ids the SERVED model chose,
L the layers that have experts, in order): those layers then follow the
ids instead of their own top-k, after measuring how far each chosen id
lies below the reference's own k-th largest score (returned as ``gap``:
0 where they agree, a near-tie flip is a few 1e-3 of a score); weights
and everything else are computed here.  Weights may be bfloat16: each is
upcast where it is used, the held experts one at a time and the K/V
heads one group at a time, so that the published widths fit beside a
served copy of the model.
"""
import math


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(g)


def _rotary(x, theta, rotary_dim):
    """x [T, heads, D] at positions 0..T-1."""
    import jax.numpy as jnp

    half = rotary_dim // 2
    p = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None]
    angle = p / theta ** (2.0 * jnp.arange(half, dtype=jnp.float32)
                          / rotary_dim)
    lo, hi, rest = x[..., :half], x[..., half:rotary_dim], \
        x[..., rotary_dim:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle),
                            rest], axis=-1)


def attention_layer(lw, x, dims, kind):
    """The attention sub-block's residual update of the whole sequence
    x [T, Dm]; ``kind`` is the layer's."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, hkv = dims["num_heads"], dims["kv_heads"][kind]
        d, dv = dims["head_dim"], dims["v_head_dim"]
        theta = dims["rope_theta"][kind]
        h = _rms(x, lw["norm1"], dims["eps"])
        q = _rotary((h @ _f32(lw["wq"])).reshape(t, nh, d), theta,
                    dims["rotary_dim"])
        k = _rotary((h @ _f32(lw["wk"])).reshape(t, hkv, d), theta,
                    dims["rotary_dim"])
        v = (h @ _f32(lw["wv"])).reshape(t, hkv, dv)
        i = jnp.arange(t)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if kind == "window":
            seen = seen & (i - j < dims["window"])
            sink = _f32(lw["sink"]).reshape(hkv, nh // hkv)
        else:
            sink = jnp.full((hkv, nh // hkv), -jnp.inf)

        def group(args):
            """The query heads of one K/V head."""
            qg, kg, vg, sg = args           # [T,G,D] [T,D] [T,Dv] [G]
            s = jnp.einsum("igd,jd->gij", qg, kg) / math.sqrt(d)
            s = jnp.where(seen[None], s, -jnp.inf)
            m = jnp.maximum(jnp.max(s, axis=-1), sg[:, None])
            e = jnp.exp(s - m[..., None])
            p = e / (jnp.sum(e, axis=-1) + jnp.exp(sg[:, None] - m))[
                ..., None]
            return jnp.einsum("gij,jd->igd", p, vg)

        ctx = jax.lax.map(group, (
            jnp.moveaxis(q.reshape(t, hkv, nh // hkv, d), 1, 0),
            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), sink))
        ctx = dims["value_scale"] * jnp.moveaxis(ctx, 0, 1)  # [T,hkv,G,Dv]
        return x + ctx.reshape(t, nh * dv) @ _f32(lw["wo"])


def dense_layer(lw, x, dims):
    """A leading layer's dense feed-forward residual update."""
    import jax

    with jax.default_matmul_precision("highest"):
        h = _rms(x, lw["norm2"], dims["eps"])
        return x + (jax.nn.silu(h @ _f32(lw["ffn_w_gate"]))
                    * (h @ _f32(lw["ffn_w_up"]))) @ _f32(lw["ffn_w_down"])


def moe_layer(lw, x, dims, ids=None, held=None):
    """The expert layer's residual update of x [T, Dm] -> (x, gap [T]).
    ``ids`` [T, k]: follow these experts (``gap`` says how far below the
    reference's own k-th score the worst of them lies); ``held``
    overrides ``dims["held"]`` as the ids whose weights ``lw`` holds."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        held = dims["held"] if held is None else held
        f, top_k = dims["expert_dim"], dims["top_k"]
        h = _rms(x, lw["norm2"], dims["eps"])
        scores = jax.nn.sigmoid(h @ _f32(lw["moe_router"]))  # [T, E]
        ranked = scores + _f32(lw["moe_router_bias"])
        kth = jax.lax.top_k(ranked, top_k)[0][:, -1]
        if ids is None:
            ids = jax.lax.top_k(ranked, top_k)[1]
        gap = jnp.max(kth[:, None]
                      - jnp.take_along_axis(ranked, ids, axis=1), axis=1)
        w = jnp.take_along_axis(scores, ids, axis=1)
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


def head(w, x, dims):
    import jax

    with jax.default_matmul_precision("highest"):
        return _rms(x, w["norm_f"], dims["eps"]) @ _f32(w["lm_head"])


def forward_logits(w, tokens, dims, routing=None):
    """``tokens`` [T] int32 -> (logits [T, vocab], gap [T, L]), L the
    layers that have experts.  Every position is real: nothing here is
    causal but the attention, so rows past a sequence's end only cost
    time."""
    import jax.numpy as jnp

    x = _f32(w["tok_emb"][tokens])
    gaps = []
    for l, (kind, lw) in enumerate(zip(dims["kinds"], w["layers"])):
        x = attention_layer(lw, x, dims, kind)
        if l < dims["dense_layers"]:
            x = dense_layer(lw, x, dims)
            continue
        x, gap = moe_layer(lw, x, dims, None if routing is None
                           else routing[:, len(gaps)])
        gaps.append(gap)
    return head(w, x, dims), jnp.stack(gaps, axis=1)
