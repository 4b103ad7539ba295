"""Plain forward of a LOOPED dense decoder: one stack of layers that every
token passes through ``loops`` times on the same weights, each pass with
keys and values of its own; the yardstick for ``correct`` of the cells
that serve ``paddle_tpu.serving.looped_lm``.

The architecture is Ouro-2.6B's (``ByteDance/Ouro-2.6B`` ``config.json``,
``model_type: ouro``, ``total_ut_steps`` passes; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741), written out
from the weights dictionary in ``jax.numpy`` float32 at ``highest``
matmul precision over the WHOLE sequence: no cache, no pages, no kernel,
no batching, no loop construct (two Python ``for``s, as the equations
say), and none of the model's own methods.

The equations.  ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``; no
matrix has a bias.  ``x [T, Dm]`` the residual stream, ``t = 0 ..
loops - 1`` the pass, ``l`` the layer; the weights have no ``t``::

    x            <- E[tokens]
    for t:
      for l:
        h        = RMSNorm(x; g1_l)
        q, k, v  = h Wq_l^T, h Wk_l^T, h Wv_l    # H heads of D each
        q, k     = rope(q, pos), rope(k, pos)    # all D lanes, halves rotated
        a        = softmax(q k^T / sqrt(D), causal) v
        x        = x + RMSNorm(a Wo_l; g2_l)
        h        = RMSNorm(x; g3_l)
        x        = x + RMSNorm((SiLU(h Wg_l) * h Wu_l) Wd_l; g4_l)
      x          = RMSNorm(x; g_f)               # after EVERY pass
      lambda_t   = sigmoid(x w_e + b_e)          # the exit gate
    logits       = x W_head                      # of the last pass

``rope`` with base ``theta``: lane ``j < D/2`` of a head pairs with lane
``j + D/2`` under the angle ``pos * theta^(-2j/D)``.  Pass ``t`` of a
token sees the keys pass ``t`` of the earlier tokens made: the whole
sequence runs pass by pass, so nothing else can happen here.  The exit
distribution ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` (the rest on
the last pass) decides nothing at the published threshold of 1
(``exit_mass`` gives ``sum_t p_t (t + 1)`` a token, the counter's
yardstick).

Assumptions the published config is silent on are listed in the
configuration file (``assumed``): the four norms' placement, the absence
of biases, the norm between passes, the gate's form.

``w["layers"]`` is a list of one dictionary a layer; ``Wq`` and ``Wk``
are stored ``[out, in]`` (as a checkpoint's linear layers are), every
other matrix ``[in, out]``.  Weights may be bfloat16: each is upcast
where it is used.  ``head`` takes
the rows it is asked for.
"""
import math


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _dot(a, b):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(a, _f32(b), precision=jax.lax.Precision.HIGHEST)


def rope(x, theta):
    """``x [T, H, D]`` at positions ``0 .. T - 1``."""
    import jax.numpy as jnp

    t, _, d = x.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)        # [T, D/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + turned * sin


def layer(lw, x, dims):
    """One application of one layer to the whole sequence ``x [T, Dm]``."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    heads, d, eps = dims["num_heads"], dims["head_dim"], dims["eps"]
    h = _rms(x, lw["norm_attn_in"], eps)
    q = rope(_dot(h, lw["wq"].T).reshape(t, heads, d), dims["rope_theta"])
    k = rope(_dot(h, lw["wk"].T).reshape(t, heads, d), dims["rope_theta"])
    v = _dot(h, lw["wv"]).reshape(t, heads, d)
    scores = jnp.einsum("thd,shd->hts", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hts,shd->thd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(t, -1)
    x = x + _rms(_dot(a, lw["wo"]), lw["norm_attn_out"], eps)
    h = _rms(x, lw["norm_ffn_in"], eps)
    y = _dot(jax.nn.silu(_dot(h, lw["ffn_w_gate"]))
             * _dot(h, lw["ffn_w_up"]), lw["ffn_w_down"])
    return x + _rms(y, lw["norm_ffn_out"], eps)


def between(w, x, dims):
    """What follows every pass: the final norm, and the exit gate of the
    normed rows -> (x, lambda [T])."""
    import jax

    x = _rms(x, w["norm_f"], dims["eps"])
    return x, jax.nn.sigmoid(_dot(x, w["exit_w"]) + _f32(w["exit_b"]))


def head(w, x):
    return _dot(x, w["lm_head"])


def stream(w, tokens, dims):
    """``tokens`` [T] -> (the stream after the last pass [T, Dm], the
    gates [loops, T])."""
    import jax.numpy as jnp

    x = _f32(w["tok_emb"][tokens])
    gates = []
    for _ in range(dims["loops"]):
        for lw in w["layers"]:
            x = layer(lw, x, dims)
        x, lam = between(w, x, dims)
        gates.append(lam)
    return x, jnp.stack(gates)


def exit_mass(gates):
    """``sum_t p_t (t + 1)`` a token, of ``gates`` [loops, T]."""
    import jax.numpy as jnp

    stay = jnp.ones_like(gates[0])
    mass = jnp.zeros_like(stay)
    for t, lam in enumerate(gates):
        leave = stay if t == len(gates) - 1 else lam * stay
        mass, stay = mass + leave * (t + 1), stay * (1.0 - lam)
    return mass


def forward_logits(w, tokens, dims, rows=None):
    """``tokens`` [T] int32 -> logits [T, vocab], or of the ``rows``
    (first, count) asked for.  Every position is real: nothing here is
    causal but the attention, so rows past a sequence's end only cost
    time."""
    x, _ = stream(w, tokens, dims)
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    return head(w, x)
