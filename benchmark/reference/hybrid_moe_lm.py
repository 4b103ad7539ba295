"""Plain forward of a hybrid linear-attention / softmax-attention decoder
with a mixture-of-experts feed-forward in every layer: the yardstick for
``correct`` of the cells that serve ``paddle_tpu.serving.hybrid_moe_lm``.

The architecture is Solar-Open2-250B's (``upstage/Solar-Open2-250B``
``config.json``), written out from the weights dictionary in
``jax.numpy`` float32 at ``highest`` matmul precision over the WHOLE
sequence: no cache, no pages, no kernel, no batching, the token
recurrence as the equations say, and none of the model's own methods.

The equations.  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.  A block
is ``x <- x + mixer(RMSNorm_1(x))``, ``x <- x + moe(RMSNorm_2(x))``;
after the last layer ``logits = RMSNorm_f(x) W_head``.  There is no
positional term anywhere (``use_rope: false``).

* Softmax layer (``kinds[l] == "attention"``): ``q = h W_q`` (H heads of
  D), ``k = h W_k``, ``v = h W_v`` (Hkv heads of D), causal
  ``softmax(q k^T / sqrt(D)) v`` with query head i reading K/V head
  ``i // (H / Hkv)``; ``y = (o * sigmoid(h W_g)) W_o`` (``use_gqa_gate``,
  element-wise).
* Linear layer (``"recurrent"``: gated delta rule with a decay a channel,
  KDA), per head, ``d_k = d_v``, state ``S`` in ``R^{d_k x d_v}``:
  ``q~, k~, v~ = SiLU(conv(h W_{q,k,v}))`` (depthwise causal convolution
  over time, kernel ``K``: ``conv_t = sum_j c_j u_{t-K+1+j}``, zeros
  before the sequence); ``q = q~ / |q~| * d_k^-1/2``, ``k = k~ / |k~|``,
  ``v = v~``; decay ``a = exp(-exp(A_log) * softplus(h W_a1 W_a2 +
  dt_bias))`` in ``(0,1)^{d_k}``; ``b = 2 sigmoid(h W_b)`` (the 2 is
  ``kda_allow_neg_eigval``); ``S' = Diag(a) S_{t-1}``; ``S_t = S' +
  b k (v - S'^T k)^T``; ``o = S_t^T q``; ``y = (RMSNorm_head(o) *
  sigmoid(h W_o1 W_o2)) W_out``.
* Experts: ``s = sigmoid(h W_r)`` over ALL experts, top-k by ``s +
  bias`` (the correction bias exists and is zero), ``w_i = s_i /
  sum_topk s`` (``norm_topk_prob``, scaling 1), ``moe(h) = sum_{i in
  topk} w_i E_i(h) + E_shared(h)``, ``E(h) = (SiLU(h W_gate) * h W_up)
  W_down``.

The share.  ``dims["held"]`` lists the expert ids this chip holds (one
chip's share of an expert-parallel group); the weights hold those
experts only, expert ``held[j]`` in columns ``j*F:(j+1)*F`` of
``moe_w_gate``/``moe_w_up`` and rows ``j*F:(j+1)*F`` of ``moe_w_down``.
The sum above then runs over the chosen experts that are held: what the
absent experts would add is left out, here as in the program, and that
partial result goes on to the next layer.

Assumptions the published config is silent on (the configuration file
lists them): sigmoid router scores with a zero correction bias, rank
``gate_rank`` for the two low-rank gate projections, an element-wise
softmax-layer gate, a shared expert of the routed experts' width.

``routing`` (optional, ``[T, L, k]`` expert ids the SERVED model chose):
the layers then follow those ids instead of their own top-k, after
measuring how far each chosen id lies below the reference's own k-th
largest score (returned as ``gap``: 0 where they agree, a near-tie flip
is a few 1e-3 of a score); weights and everything else are computed
here.  Weights may be bfloat16: each is upcast where it is used, the
held experts one at a time, so that the published widths fit beside a
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


def softmax_layer(lw, x, dims):
    """The softmax mixer's residual update of the whole sequence x [T, Dm]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        hq, hkv, d = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
        h = _rms(x, lw["norm1"], dims["eps"])
        q = (h @ _f32(lw["wq"])).reshape(t, hq, d)
        k = (h @ _f32(lw["wk"])).reshape(t, hkv, d)
        v = (h @ _f32(lw["wv"])).reshape(t, hkv, d)
        k = jnp.repeat(k, hq // hkv, axis=1)      # head i reads i // group
        v = jnp.repeat(v, hq // hkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((t, t), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, hq * d)
        return x + (o * jax.nn.sigmoid(h @ _f32(lw["wg"]))) @ _f32(lw["wo"])


def kda_layer(lw, x, dims):
    """The gated-delta-rule mixer's residual update of x [T, Dm]: one
    token after another from the zero state."""
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
        b = 2.0 * jax.nn.sigmoid(h @ _f32(lw["kda_wbeta"]))  # [T, nh]

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


def moe_layer(lw, x, dims, ids=None, shared=True, held=None):
    """The expert layer's residual update of x [T, Dm] -> (x, gap [T]).
    ``ids`` [T, k]: follow these experts (``gap`` says how far below the
    reference's own k-th score the worst of them lies); ``held``
    overrides ``dims["held"]`` as the ids whose weights ``lw`` holds;
    ``shared=False`` leaves the shared expert out (the share test counts
    it once)."""
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
        if shared:
            y = y + (jax.nn.silu(h @ _f32(lw["shared_w_gate"]))
                     * (h @ _f32(lw["shared_w_up"]))) \
                @ _f32(lw["shared_w_down"])
        return x + y, gap


def head(w, x, dims):
    import jax

    with jax.default_matmul_precision("highest"):
        return _rms(x, w["norm_f"], dims["eps"]) @ _f32(w["lm_head"])


def forward_logits(w, tokens, dims, routing=None):
    """``tokens`` [T] int32 -> (logits [T, vocab], gap [T, L]).  Every
    position is real: nothing here is causal but the mixers, so rows
    past a sequence's end only cost time."""
    import jax.numpy as jnp

    x = _f32(w["tok_emb"][tokens])
    gaps = []
    for l, (kind, lw) in enumerate(zip(dims["kinds"], w["layers"])):
        x = (softmax_layer if kind == "attention" else kda_layer)(lw, x, dims)
        x, gap = moe_layer(lw, x, dims,
                           None if routing is None else routing[:, l])
        gaps.append(gap)
    return head(w, x, dims), jnp.stack(gaps, axis=1)
