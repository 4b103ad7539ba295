"""Plain forward of a dense hybrid decoder: selective state-space
(Mamba-1) layers beside a few position-free softmax-attention layers
whose query heads share FEWER K/V heads, a dense SwiGLU feed-forward in
every layer, every sub-block's INPUT normalised, the head tied to the
embedding: the yardstick for ``correct`` of the cells that serve
``paddle_tpu.serving.mamba_lm``.

The architecture is AI21-Jamba2-3B's (``ai21labs/AI21-Jamba2-3B``
``config.json``, ``model_type: jamba``), written out from the weights
dictionary in ``jax.numpy`` float32 at ``highest`` matmul precision over
the WHOLE sequence: no cache, no pages, no slabs, no kernel, no
batching, no chunks (the recurrence runs token by token as the equations
say), and none of the model's own methods.

The equations.  ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``; no
matrix has a bias.  Layer ``l``, input ``x [T, Dm]``, ``kinds[l]``
(``"attention"`` where ``l % attn_layer_period == attn_layer_offset``,
counted from 0, else ``"recurrent"``)::

    x      = x + Mixer(RMSNorm(x; g_1))
    x      = x + W_down (SiLU(h W_gate) * h W_up),   h = RMSNorm(x; g_2)
    logits = RMSNorm(x; g_f) E^T                      # E the embedding

* ``"attention"``: ``q = h W_q`` (H heads of D), ``k = h W_k``, ``v = h
  W_v`` (H_kv heads of D, query head i reading K/V head ``i // (H /
  H_kv)``); NO positional term; causal ``softmax(q k^T / sqrt(D)) v``;
  then ``W_o``.
* ``"recurrent"`` (Mamba-1), ``d`` channels, ``n`` state rows a channel,
  state ``h`` in ``R^{n x d}``, zero before the sequence: ``[u | z] = x
  W_in``; ``u_t = SiLU(sum_j w_j u_{t-K+1+j} + b_conv)`` (depthwise
  causal convolution over time, ``K`` taps, zeros before the sequence);
  ``[dl | B | C] = u W_x`` (widths ``r | n | n``), each through an
  RMSNorm of its own; ``dt = softplus(dl W_dt + b_dt)`` in ``R^d``; ``A
  = -exp(A_log)`` in ``R^{n x d}``; ``h_t = exp(dt (x) A) (.) h_{t-1} +
  (dt (.) u) (x) B``; ``y = h_t^T C + D (.) u``; out ``= (y (.)
  SiLU(z)) W_out``.

Departures from the published description, one a line:

* ``A_log`` (and the state) lie ``[n, d]``, the transpose of the
  published ``[d, n]``: the same numbers, the served layout.

Assumptions the published config is silent on are listed in the
configuration file (``assumed``): the layer order, the three inner
norms, ``b_dt`` inside the softplus, the head width and scale, no
positional term.

Weights may be bfloat16: each is upcast where it is used.  The softmax
layers' query rows run ``dims["row_block"]`` (512) at a time, and
``head`` takes the rows it is asked for, so that the compared rows'
float32 logits fit beside a served copy of the model.
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
    """The softmax mixer of the whole normed sequence x [T, Dm] -> [T,
    Dm], query rows a block at a time."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, nkv, d = dims["num_heads"], dims["num_kv_heads"], \
            dims["head_dim"]
        q = (x @ _f32(lw["wq"])).reshape(t, nkv, nh // nkv, d)
        k = (x @ _f32(lw["wk"])).reshape(t, nkv, d)
        v = (x @ _f32(lw["wv"])).reshape(t, nkv, d)
        rb = min(int(dims.get("row_block", 512)), t)
        outs = []
        for lo in range(0, t, rb):
            hi = min(lo + rb, t)
            s = jnp.einsum("qgjd,kgd->gjqk", q[lo:hi], k[:hi]) / math.sqrt(d)
            causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            outs.append(jnp.einsum("gjqk,kgd->qgjd", p, v[:hi]))
        return jnp.concatenate(outs).reshape(t, nh * d) @ _f32(lw["wo"])


def ssm_mixer(lw, x, dims):
    """The Mamba-1 mixer of the normed sequence x [T, Dm] -> [T, Dm]:
    one token after another from the zero state."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        d, n, r, kk = dims["d_inner"], dims["d_state"], dims["dt_rank"], \
            dims["d_conv"]
        eps = dims["eps"]
        uz = x @ _f32(lw["ssm_w_in"])
        u, z = uz[:, :d], uz[:, d:]
        u_pad = jnp.concatenate([jnp.zeros((kk - 1, d)), u])
        u = jax.nn.silu(sum(_f32(lw["ssm_conv"])[j] * u_pad[j:j + t]
                            for j in range(kk)) + _f32(lw["ssm_conv_b"]))
        dbc = u @ _f32(lw["ssm_w_x"])
        dl = _rms(dbc[:, :r], lw["ssm_dt_norm"], eps)
        b = _rms(dbc[:, r:r + n], lw["ssm_b_norm"], eps)
        c = _rms(dbc[:, r + n:], lw["ssm_c_norm"], eps)
        dt = jax.nn.softplus(dl @ _f32(lw["ssm_w_dt"])
                             + _f32(lw["ssm_dt_b"]))            # [T, d]
        a = -jnp.exp(_f32(lw["ssm_a_log"]))                      # [n, d]

        def token(h, row):
            dt_t, u_t, b_t, c_t = row
            h = jnp.exp(dt_t[None, :] * a) * h \
                + (dt_t * u_t)[None, :] * b_t[:, None]
            return h, jnp.sum(h * c_t[:, None], axis=0)

        _, y = jax.lax.scan(token, jnp.zeros((n, d)), (dt, u, b, c))
        y = y + _f32(lw["ssm_d"]) * u
        return (y * jax.nn.silu(z)) @ _f32(lw["ssm_w_out"])


def layer(lw, x, dims, kind):
    """One block's two residual updates of x [T, Dm]."""
    import jax

    with jax.default_matmul_precision("highest"):
        mixer = softmax_mixer if kind == "attention" else ssm_mixer
        x = x + mixer(lw, _rms(x, lw["norm1"], dims["eps"]), dims)
        h = _rms(x, lw["norm2"], dims["eps"])
        return x + (jax.nn.silu(h @ _f32(lw["ffn_w_gate"]))
                    * (h @ _f32(lw["ffn_w_up"]))) @ _f32(lw["ffn_w_down"])


def head(w, x, dims):
    """Logits of the rows ``x``: the final norm, then the embedding
    ``[V, Dm]`` as the head."""
    import jax

    with jax.default_matmul_precision("highest"):
        return _rms(x, w["norm_f"], dims["eps"]) @ _f32(w["tok_emb"]).T


def forward_logits(w, tokens, dims, rows=None):
    """``tokens`` [T] int32 -> logits [T, vocab], or of ``rows`` (first,
    count) alone.  Every position is real: nothing here is causal but
    the mixers, so rows past a sequence's end only cost time."""
    x = _f32(w["tok_emb"][tokens])
    for kind, lw in zip(dims["kinds"], w["layers"]):
        x = layer(lw, x, dims, kind)
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    return head(w, x, dims)
