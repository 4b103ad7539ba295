"""Plain forward of a decoder whose layers are gated-delta-rule linear
attention with a decay a channel (Kimi Delta Attention, KDA) and, one in
a period, position-free multi-head LATENT attention; a leading dense
feed-forward and, in the other layers, a mixture of experts under a
scaled, bias-corrected sigmoid router beside one shared expert: the
yardstick for ``correct`` of the cells that serve
``paddle_tpu.serving.linear_latent_lm``.

The architecture is Kimi-Linear-48B-A3B-Instruct's
(``moonshotai/Kimi-Linear-48B-A3B-Instruct`` ``config.json``,
``model_type: kimi_linear``), written out from the weights dictionary in
``jax.numpy`` float32 at ``highest`` matmul precision over the WHOLE
sequence: no cache, no pages, no kernel, no batching, the token
recurrence as the equations say, latent attention in its expanded
definition only, and none of the model's own methods.

The equations.  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.  A block
is ``x <- x + mixer(RMSNorm_1(x))``, ``x <- x + ffn(RMSNorm_2(x))``;
after the last layer ``logits = RMSNorm_f(x) W_head`` (untied).  No
biases, and no positional term anywhere (``mla_use_nope``).

* KDA layer (``kinds[l] == "recurrent"``), per head, ``d_k = d_v = d``,
  state ``S`` in ``R^{d x d}``: ``q~, k~, v~ = SiLU(conv(h W_{q,k,v}))``
  (depth-wise causal convolution over time, kernel ``K``: ``conv_t =
  sum_j c_j u_{t-K+1+j}``, zeros before the sequence); ``q = q~ / |q~| *
  d^-1/2``, ``k = k~ / |k~|``, ``v = v~``; decay ``a = exp(-exp(A_log) *
  softplus(h W_fa W_fb + dt_bias))`` in ``(0,1)^d``; ``b = sigmoid(h
  W_b)`` in ``(0, 1)``; ``S' = Diag(a) S_{t-1}``; ``S_t = S' + b k (v -
  S'^T k)^T``; ``o = S_t^T q``; ``y = (RMSNorm_head(o) * sigmoid(h W_ga
  W_gb)) W_o``.
* Latent layer (``"attention"``), for the row ``h``: ``q = h W_q``, H
  heads of ``nope + rope`` lanes (no bottleneck); ``[c_kv | k_r] = h
  W_kva``; ``c = RMSNorm_kv(c_kv)`` (over the ``rank`` lanes of ``c_kv``
  alone; ``k_r`` is not normed and NOT ROTATED, nor are the queries'
  last ``rope`` lanes).  With ``W_UK [H, nope, rank]`` and ``W_UV [H,
  rank, v]`` (the two halves of ``kv_b_proj``): ``k_head = [c W_UK[h]^T
  | k_r]``, ``v_head = c W_UV[h]``; ``s_ij = q_i . k_j (nope +
  rope)^-1/2`` over ``j <= i``; softmax; ``ctx = sum_j a_ij v_j``; ``y =
  concat_heads(ctx) W_o``.
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

Departures from the published description, and what the config is
silent on (the configuration file lists each under ``assumed``):
* the correction bias in the router (no ``topk_method`` key): a weight,
  in the choice only;
* ``b`` in (0, 1): no ``kda_allow_neg_eigval`` key;
* rank ``gate_rank`` (the head width) for the decay's and the output
  gate's low-rank projections, neither with a bias but ``dt_bias``;
* SiLU on the three convolutions, ``1e-6`` under the l2 norms' root;
* ``k_r`` unnormed and unrotated, the softmax scale ``(nope +
  rope)^-1/2``;
* a shared expert of the routed experts' width, unweighted;
* ``kv_b_proj`` held as its two halves (a one-time split).

``routing`` (optional, ``[T, L, k]`` expert ids the SERVED model chose,
L the layers that have experts, in order): those layers then follow the
ids instead of their own top-k, after measuring how far each chosen id
lies below the reference's own k-th largest ``s + b`` (returned as
``gap``: 0 where they agree); weights and everything else are computed
here.  Weights may be bfloat16: each is upcast where it is used, the held
experts and the heads one at a time, and the latent layer attends
``rows`` query rows at a time where given, so that the published widths
fit beside a served copy of the model.
"""
import math


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(g)


def kda_layer(lw, x, dims):
    """The KDA mixer's residual update of x [T, Dm]: one token after
    another from the zero state."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, dk, kk = dims["lin_heads"], dims["lin_head_dim"], \
            dims["conv_kernel"]
        c = nh * dk
        h = _rms(x, lw["norm1"], dims["eps"])
        u = h @ _f32(lw["kda_wqkv"])                        # [T, 3C]
        u_pad = jnp.concatenate([jnp.zeros((kk - 1, 3 * c)), u])
        conv = sum(_f32(lw["kda_conv"])[j] * u_pad[j:j + t]
                   for j in range(kk))
        qkv = jax.nn.silu(conv).reshape(t, 3, nh, dk)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            / math.sqrt(dk)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        gate = (h @ _f32(lw["kda_wa_down"])) @ _f32(lw["kda_wa_up"]) \
            + _f32(lw["kda_dt_bias"])
        a = jnp.exp(-jnp.exp(_f32(lw["kda_a_log"]))[None, :, None]
                    * jax.nn.softplus(gate).reshape(t, nh, dk))
        b = jax.nn.sigmoid(h @ _f32(lw["kda_wbeta"]))       # [T, nh]

        def token(s, row):
            q_t, k_t, v_t, a_t, b_t = row
            s = a_t[:, :, None] * s                         # Diag(a) S
            ks = jnp.einsum("hk,hkv->hv", k_t, s)           # S'^T k
            s = s + b_t[:, None, None] * k_t[:, :, None] \
                * (v_t - ks)[:, None, :]
            return s, jnp.einsum("hk,hkv->hv", q_t, s)      # S^T q

        _, o = jax.lax.scan(token, jnp.zeros((nh, dk, dk)), (q, k, v, a, b))
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + dims["eps"]) \
            * _f32(lw["kda_onorm"])
        og = jax.nn.sigmoid(
            (h @ _f32(lw["kda_wo_down"])) @ _f32(lw["kda_wo_up"]))
        return x + (o.reshape(t, c) * og) @ _f32(lw["kda_wout"])


def latent_layer(lw, x, dims, rows=None):
    """The latent mixer's residual update of the whole sequence x [T,
    Dm] in the EXPANDED definition, a head at a time; ``rows`` (a
    divisor of T): that many query rows at a time."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, nope, rank = dims["num_heads"], dims["nope_dim"], dims["kv_rank"]
        h = _rms(x, lw["norm1"], dims["eps"])
        q = (h @ _f32(lw["wq"])).reshape(t, nh, nope + dims["rope_dim"])
        kv = h @ _f32(lw["wkv_a"])
        c, k_r = _rms(kv[:, :rank], lw["kv_norm"], dims["eps"]), kv[:, rank:]
        scale = (nope + dims["rope_dim"]) ** -0.5
        rows = t if rows is None else rows

        def head(args):
            qh, w_uk, w_uv = args       # [T, nope+rope] [nope,R] [R,v]
            k = jnp.concatenate([c @ _f32(w_uk).T, k_r], axis=1)
            v = c @ _f32(w_uv)

            def block(i):
                qb = jax.lax.dynamic_slice_in_dim(qh, i * rows, rows)
                seen = jnp.arange(t)[None, :] \
                    <= (i * rows + jnp.arange(rows))[:, None]
                s = jnp.where(seen, (qb @ k.T) * scale, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ v

            return jax.lax.map(block, jnp.arange(t // rows)).reshape(t, -1)

        ctx = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), lw["w_uk"],
                                 lw["w_uv"]))               # [H, T, v]
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


def moe_layer(lw, x, dims, ids=None, held=None, shared=True):
    """The expert layer's residual update of x [T, Dm] -> (x, gap [T]):
    the held experts' routed part and (``shared``) the shared expert."""
    import jax

    with jax.default_matmul_precision("highest"):
        h = _rms(x, lw["norm2"], dims["eps"])
        y, gap = routed_part(lw, h, dims, ids, held)
        if shared:
            y = y + _swiglu(h, lw, "shared")
        return x + y, gap


def head(w, x, dims):
    import jax

    with jax.default_matmul_precision("highest"):
        return _rms(x, w["norm_f"], dims["eps"]) @ _f32(w["lm_head"])


def forward_logits(w, tokens, dims, routing=None, rows=None):
    """``tokens`` [T] int32 -> (logits [T, vocab], gap [T, L]), L the
    layers that have experts.  Every position is real: nothing here is
    causal but the mixers, so rows past a sequence's end only cost
    time."""
    import jax.numpy as jnp

    x = _f32(w["tok_emb"][tokens])
    gaps = []
    for l, (kind, lw) in enumerate(zip(dims["kinds"], w["layers"])):
        x = kda_layer(lw, x, dims) if kind == "recurrent" \
            else latent_layer(lw, x, dims, rows)
        if l < dims["dense_layers"]:
            x = dense_layer(lw, x, dims)
            continue
        x, gap = moe_layer(lw, x, dims, None if routing is None
                           else routing[:, len(gaps)])
        gaps.append(gap)
    return head(w, x, dims), jnp.stack(gaps, axis=1)
