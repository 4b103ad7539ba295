"""Plain forward of a dense hybrid decoder: Gated DeltaNet layers (linear
attention by the gated delta rule, ONE scalar decay a head, keys
narrower than values) beside position-free softmax-attention layers with
QK-norm, a dense SwiGLU feed-forward in every layer, every sub-block's
OUTPUT normalised before it joins the residual stream, an untied head:
the yardstick for ``correct`` of the cells that serve
``paddle_tpu.serving.gated_delta_lm``.

The architecture is Olmo-Hybrid-7B's (``allenai/Olmo-Hybrid-7B``
``config.json``, ``model_type: olmo_hybrid``), written out from the
weights dictionary in ``jax.numpy`` float32 at ``highest`` matmul
precision over the WHOLE sequence: no cache, no pages, no slabs, no
kernel, no batching, no chunks (the recurrence runs token by token as the
equations say), and none of the model's own methods.

The equations.  ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``; no
matrix has a bias.  Layer ``l``, input ``x [T, Dm]``, ``kinds[l]`` from
``layer_types`` (``linear_attention`` -> ``"recurrent"``,
``full_attention`` -> ``"attention"``)::

    x      = x + RMSNorm(Mixer(x); g_mix)                    # the norm is on the OUTPUT
    x      = x + RMSNorm(W_down (SiLU(x W_gate) * x W_up); g_ffn)
    logits = RMSNorm(x; g_f) W_head                          # W_head untied

* ``"attention"``: ``q = RMSNorm(x W_q; g_q)``, ``k = RMSNorm(x W_k;
  g_k)``, each over the WHOLE projection (all heads' lanes) before the
  split into H heads of D; ``v = x W_v``; as many K/V heads as query
  heads; NO positional term; causal ``softmax(q k^T / sqrt(D)) v``; then
  ``W_o``.
* ``"recurrent"`` (Gated DeltaNet), H_l heads, keys of ``d_k``, values of
  ``d_v``, state ``S`` in ``R^{d_k x d_v}`` a head, zero before the
  sequence: ``u = x [W_q | W_k | W_v]`` (widths ``H_l d_k | H_l d_k |
  H_l d_v``); ``c_t = SiLU(sum_j w_j u_{t-K+1+j})`` (depthwise causal
  convolution over time, kernel ``K``, no bias, zeros before the
  sequence); split into heads: ``q^ = q / |q| / sqrt(d_k)``, ``k^ = k /
  |k|``, ``v``; ``beta = 2 sigmoid(x W_b)`` a head (the 2 is
  ``linear_allow_neg_eigval``); ``alpha = exp(-exp(A_log) softplus(x W_a
  + dt_bias))``, ONE scalar a head; ``S' = alpha S_{t-1}``; ``S_t = S' +
  k^ (beta (v - S'^T k^))^T``; ``o = S_t^T q^``; ``y = (RMSNorm(o; g_o)
  * SiLU(x W_g)) W_out`` (the norm over a head's ``d_v`` lanes).

Assumptions the published config is silent on are listed in the
configuration file (``assumed``): the placement of the norms, the
QK-norm's span, the absence of a positional term, the output gate's
activation and the decay's parametrisation.

Weights may be bfloat16: each is upcast where it is used.  The softmax
layers' query rows run ``dims["row_block"]`` (512) at a time, each block
against the keys its rows can see, and ``head`` takes the rows it is
asked for, so that 4,400 positions at the published widths (a float32
head of 1.5 GB) fit beside a served copy of the model.
"""
import math


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(g)


def softmax_mixer(lw, x, dims):
    """The softmax mixer of the whole sequence x [T, Dm] -> [T, Dm]
    (before the output norm), query rows a block at a time."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, d, eps = dims["num_heads"], dims["head_dim"], dims["eps"]
        q = _rms(x @ _f32(lw["wq"]), lw["q_norm"], eps).reshape(t, nh, d)
        k = _rms(x @ _f32(lw["wk"]), lw["k_norm"], eps).reshape(t, nh, d)
        v = (x @ _f32(lw["wv"])).reshape(t, nh, d)
        rb = min(int(dims.get("row_block", 512)), t)
        outs = []
        for lo in range(0, t, rb):
            hi = min(lo + rb, t)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / math.sqrt(d)
            causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi]))
        o = jnp.concatenate(outs).reshape(t, nh * d)
        return o @ _f32(lw["wo"])


def gdn_mixer(lw, x, dims):
    """The Gated DeltaNet mixer of x [T, Dm] -> [T, Dm] (before the
    output norm): one token after another from the zero state."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, dk, dv = dims["lin_heads"], dims["lin_key_dim"], \
            dims["lin_value_dim"]
        kk = dims["conv_kernel"]
        u = x @ _f32(lw["gdn_wqkv"])                    # [T, 2 H dk + H dv]
        u_pad = jnp.concatenate([jnp.zeros((kk - 1, u.shape[1])), u])
        conv = jax.nn.silu(sum(_f32(lw["gdn_conv"])[j] * u_pad[j:j + t]
                               for j in range(kk)))
        q = conv[:, :nh * dk].reshape(t, nh, dk)
        k = conv[:, nh * dk:2 * nh * dk].reshape(t, nh, dk)
        v = conv[:, 2 * nh * dk:].reshape(t, nh, dv)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            / math.sqrt(dk)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        a = jnp.exp(-jnp.exp(_f32(lw["gdn_a_log"])) * jax.nn.softplus(
            x @ _f32(lw["gdn_wa"]) + _f32(lw["gdn_dt_bias"])))   # [T, H]
        b = 2.0 * jax.nn.sigmoid(x @ _f32(lw["gdn_wb"]))          # [T, H]

        def token(s, row):
            q_t, k_t, v_t, a_t, b_t = row
            s = a_t[:, None, None] * s                      # alpha S
            ks = jnp.einsum("hk,hkv->hv", k_t, s)           # S'^T k
            s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - ks))[:, None, :]
            return s, jnp.einsum("hk,hkv->hv", q_t, s)      # S^T q

        _, o = jax.lax.scan(token, jnp.zeros((nh, dk, dv)), (q, k, v, a, b))
        o = _rms(o, lw["gdn_onorm"], dims["eps"]).reshape(t, nh * dv)
        return (o * jax.nn.silu(x @ _f32(lw["gdn_wg"]))) @ _f32(lw["gdn_wout"])


def layer(lw, x, dims, kind):
    """One block's two residual updates of x [T, Dm]."""
    import jax

    with jax.default_matmul_precision("highest"):
        mixer = softmax_mixer if kind == "attention" else gdn_mixer
        x = x + _rms(mixer(lw, x, dims), lw["norm_mix"], dims["eps"])
        y = (jax.nn.silu(x @ _f32(lw["ffn_w_gate"]))
             * (x @ _f32(lw["ffn_w_up"]))) @ _f32(lw["ffn_w_down"])
        return x + _rms(y, lw["norm_ffn"], dims["eps"])


def head(w, x, dims):
    import jax

    with jax.default_matmul_precision("highest"):
        return _rms(x, w["norm_f"], dims["eps"]) @ _f32(w["lm_head"])


def forward_logits(w, tokens, dims):
    """``tokens`` [T] int32 -> logits [T, vocab].  Every position is
    real: nothing here is causal but the mixers, so rows past a
    sequence's end only cost time."""
    x = _f32(w["tok_emb"][tokens])
    for kind, lw in zip(dims["kinds"], w["layers"]):
        x = layer(lw, x, dims, kind)
    return head(w, x, dims)
