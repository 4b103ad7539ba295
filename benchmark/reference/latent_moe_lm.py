"""Plain forward of a decoder with LATENT attention (keys and values of
every head are projections of one compressed row a position, beside one
rotary key that all heads share), a YaRN-scaled rotary term, a leading
dense feed-forward and, in the other layers, a mixture of experts under
a scaled, bias-corrected sigmoid router beside one shared expert: the
yardstick for ``correct`` of the cells that serve
``paddle_tpu.serving.latent_moe_lm``.

The architecture is Kimi-K2.5's language model (``moonshotai/Kimi-K2.5``
``config.json``, ``model_type: kimi_k2``: the DeepSeek-V3 block), written
out from the weights dictionary in ``jax.numpy`` float32 at ``highest``
matmul precision over the WHOLE sequence: no cache, no pages, no kernel,
no batching, and none of the model's own methods.

The equations.  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.  A layer
is ``x <- x + attn(RMSNorm_1(x))``, ``x <- x + ffn(RMSNorm_2(x))``; after
the last layer ``logits = RMSNorm_f(x) W_head`` (untied).  No biases.

* Attention, for the row ``h`` at position ``p``.  ``c_q = RMSNorm_q(h
  W_qa)``; ``q = c_q W_qb``, H heads of ``[q_nope (nope) | q_rope
  (rope)]``.  ``[c_kv | k_r] = h W_kva``; ``c = RMSNorm_kv(c_kv)`` (the
  norm is over the ``rank`` lanes of ``c_kv`` alone; ``k_r`` is not
  normed).  ``q_rope`` of every head and the one ``k_r`` turn by the
  rotary term at ``p``: lanes ``(2j, 2j+1)`` are a pair, the lanes are
  de-interleaved (evens, then odds) and the half-split rotation applied,
  ``[a cos - b sin | b cos + a sin]``, at the YaRN frequencies ``f'_j =
  f_j (1 - r_j) + (f_j / factor) r_j`` with ``f_j = theta^(-2j/d)``,
  ``r_j = clip((j - low) / (high - low), 0, 1)``, ``low = floor(d ln(L0
  / (beta_fast 2 pi)) / (2 ln theta))``, ``high = ceil(d ln(L0 /
  (beta_slow 2 pi)) / (2 ln theta))``; cos and sin are multiplied by
  ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``,
  ``yarn_mscale(s, m) = 0.1 m ln s + 1``.  With ``W_UK [H, nope, rank]``
  and ``W_UV [H, rank, v]`` (the two halves of ``kv_b_proj``): ``k_head =
  [c W_UK[h]^T | rot(k_r)]``, ``v_head = c W_UV[h]``; ``s_ij = q_i . k_j
  x scale`` over ``j <= i``, ``scale = (nope + rope)^-1/2 x
  yarn_mscale(factor, mscale_all_dim)^2``; softmax; ``ctx = sum_j a_ij
  v_j``; ``y = concat_heads(ctx) W_o``.  ``absorbed=True`` computes the
  same numbers the other way round (``q_lat = q_nope W_UK[h]``, scores
  against ``[c | rot(k_r)]`` as it is, ``ctx = (sum_j a_ij c_j)
  W_UV[h]``): what a cache of ``[c | rot(k_r)]`` rows is read by.
* Feed-forward.  The first ``dense_layers`` layers: ``(SiLU(h W_g) * h
  W_u) W_d``.  The others: ``s = sigmoid(h W_r)`` over ALL experts, the
  top-k by ``s + b`` (``b`` the correction bias, a weight; no group
  limit), ``w_i = routed_scale x s_i / sum_topk s`` (``b`` is in the
  choice only), ``sum_{i in topk} w_i E_i(h)`` plus the one shared
  expert ``E_s(h)``, unweighted; ``E(h) = (SiLU(h W_gate) * h W_up)
  W_down``.

The share.  ``dims["held"]`` lists the expert ids this chip holds (one
chip's share of an expert-parallel group); the weights hold those
experts only, expert ``held[j]`` in columns ``j*F:(j+1)*F`` of
``moe_w_gate``/``moe_w_up`` and rows ``j*F:(j+1)*F`` of ``moe_w_down``.
The routed sum then runs over the chosen experts that are held: what the
absent experts would add is left out, here as in the program, and that
partial result goes on to the next layer.  The shared expert is whole.

Assumptions the published config is silent on (the configuration file
lists them): the DeepSeek-V3 reading of ``rope_scaling``, the
interleaved pairing, the latent's norm over its ``rank`` lanes only, the
bias in the choice only, the shared expert unweighted.

``routing`` (optional, ``[T, L, k]`` expert ids the SERVED model chose,
L the layers that have experts, in order): those layers then follow the
ids instead of their own top-k, after measuring how far each chosen id
lies below the reference's own k-th largest ``s + b`` (returned as
``gap``: 0 where they agree); weights and everything else are computed
here.  Weights may be bfloat16: each is upcast where it is used, the held
experts and the heads one at a time, so that the published widths fit
beside a served copy of the model.
"""
import math


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(g)


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_freqs(dims):
    """[rope / 2] float32: the blended frequencies."""
    import jax.numpy as jnp

    d, theta, y = dims["rope_dim"], dims["rope_theta"], dims["yarn"]

    def at(turns):
        return d * math.log(y["orig_len"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(at(y["beta_fast"])), 0)
    high = min(math.ceil(at(y["beta_slow"])), d - 1)
    j = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * j / d)
    r = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - r) + f / y["factor"] * r


def _rotary(x, dims):
    """x [T, heads, rope] at positions 0..T-1: pairs (2j, 2j+1)."""
    import jax.numpy as jnp

    y = dims["yarn"]
    p = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None]
    angle = p * yarn_freqs(dims)
    m = _mscale(y["factor"], y["mscale"]) \
        / _mscale(y["factor"], y["mscale_all_dim"])
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def softmax_scale(dims):
    y = dims["yarn"]
    return (dims["nope_dim"] + dims["rope_dim"]) ** -0.5 \
        * _mscale(y["factor"], y["mscale_all_dim"]) ** 2


def latent_rows(lw, h, dims):
    """(c [T, rank], rot(k_r) [T, rope]) of the normed rows ``h``: what
    a cache keeps of each position."""
    rank = dims["kv_rank"]
    kv = h @ _f32(lw["wkv_a"])
    return _rms(kv[:, :rank], lw["kv_norm"], dims["eps"]), \
        _rotary(kv[:, None, rank:], dims)[:, 0]


def attention_layer(lw, x, dims, absorbed=False):
    """The attention sub-block's residual update of the whole sequence
    x [T, Dm], a head at a time."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, nope = dims["num_heads"], dims["nope_dim"]
        h = _rms(x, lw["norm1"], dims["eps"])
        q = (_rms(h @ _f32(lw["wq_a"]), lw["q_norm"], dims["eps"])
             @ _f32(lw["wq_b"])).reshape(t, nh, nope + dims["rope_dim"])
        q_rope = _rotary(q[..., nope:], dims)
        c, k_rot = latent_rows(lw, h, dims)
        seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        scale = softmax_scale(dims)

        def head(args):
            qn, qr, w_uk, w_uv = args   # [T,nope] [T,rope] [nope,R] [R,v]
            w_uk, w_uv = _f32(w_uk), _f32(w_uv)
            if absorbed:
                s = (qn @ w_uk) @ c.T
            else:
                s = qn @ (c @ w_uk.T).T
            s = jnp.where(seen, (s + qr @ k_rot.T) * scale, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            return (a @ c) @ w_uv if absorbed else a @ (c @ w_uv)

        ctx = jax.lax.map(head, (
            jnp.moveaxis(q[..., :nope], 1, 0), jnp.moveaxis(q_rope, 1, 0),
            lw["w_uk"], lw["w_uv"]))                        # [H, T, v]
        return x + jnp.moveaxis(ctx, 0, 1).reshape(t, -1) @ _f32(lw["wo"])


def _swiglu(h, lw, name):
    import jax

    return (jax.nn.silu(h @ _f32(lw[name + "_w_gate"]))
            * (h @ _f32(lw[name + "_w_up"]))) @ _f32(lw[name + "_w_down"])


def dense_layer(lw, x, dims):
    """A leading layer's dense feed-forward residual update."""
    import jax

    with jax.default_matmul_precision("highest"):
        return x + _swiglu(_rms(x, lw["norm2"], dims["eps"]), lw, "ffn")


def routed_part(lw, h, dims, ids=None, held=None):
    """(the held experts' part of the routed sum for the normed rows
    ``h`` [T, Dm], scaled; gap [T]).  ``ids`` [T, k]: follow these
    experts; ``held`` overrides ``dims["held"]`` as the ids whose weights
    ``lw`` holds."""
    import jax
    import jax.numpy as jnp

    held = dims["held"] if held is None else held
    f, top_k = dims["expert_dim"], dims["top_k"]
    scores = jax.nn.sigmoid(h @ _f32(lw["moe_router"]))     # [T, E]
    ranked = scores + _f32(lw["moe_router_bias"])
    kth = jax.lax.top_k(ranked, top_k)[0][:, -1]
    if ids is None:
        ids = jax.lax.top_k(ranked, top_k)[1]
    gap = jnp.max(kth[:, None] - jnp.take_along_axis(ranked, ids, axis=1),
                  axis=1)
    w = jnp.take_along_axis(scores, ids, axis=1)
    w = dims["routed_scale"] * w / jnp.sum(w, axis=1, keepdims=True)

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

    return jax.lax.fori_loop(0, len(held), expert, jnp.zeros_like(h)), gap


def moe_layer(lw, x, dims, ids=None, held=None):
    """The expert layer's residual update of x [T, Dm] -> (x, gap [T]):
    the held experts' routed part and the shared expert."""
    import jax

    with jax.default_matmul_precision("highest"):
        h = _rms(x, lw["norm2"], dims["eps"])
        y, gap = routed_part(lw, h, dims, ids, held)
        return x + y + _swiglu(h, lw, "shared"), gap


def head(w, x, dims):
    import jax

    with jax.default_matmul_precision("highest"):
        return _rms(x, w["norm_f"], dims["eps"]) @ _f32(w["lm_head"])


def forward_logits(w, tokens, dims, routing=None, absorbed=False):
    """``tokens`` [T] int32 -> (logits [T, vocab], gap [T, L]), L the
    layers that have experts.  Every position is real: nothing here is
    causal but the attention, so rows past a sequence's end only cost
    time."""
    import jax.numpy as jnp

    x = _f32(w["tok_emb"][tokens])
    gaps = []
    for l, lw in enumerate(w["layers"]):
        x = attention_layer(lw, x, dims, absorbed)
        if l < dims["dense_layers"]:
            x = dense_layer(lw, x, dims)
            continue
        x, gap = moe_layer(lw, x, dims, None if routing is None
                           else routing[:, len(gaps)])
        gaps.append(gap)
    return head(w, x, dims), jnp.stack(gaps, axis=1)
