"""A served DENSE model whose layers are of two kinds: Gated DeltaNet
linear attention (the gated delta rule with ONE scalar decay a head, keys
narrower than values, a short causal convolution, an output gate at full
rank) that keeps a fixed-size recurrent state a request instead of keys,
and position-free softmax attention with as many K/V heads as query
heads and an RMSNorm over the whole q and k projections (QK-norm).  Every
layer's feed-forward is one dense SwiGLU; every sub-block's OUTPUT is
normalised before it joins the residual stream (nothing normalises its
input); the head is a matrix of its own.  The architecture is
Olmo-Hybrid-7B's (``olmo_hybrid``); the equations are in the reference's
docstring (``benchmark/reference/gated_delta_lm.py``, a copy in
``tests/``), which this file is tested against and shares no code with.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring, like ``hybrid_moe_lm.py``: ``forward(weights, tokens,
positions, cache, attend)``.  What it declares: ``layer_kinds``
(``"attention"`` or ``"recurrent"`` a layer), ``num_kv_heads`` (the query
heads' count), ``recurrent_state`` (one slot's state of one recurrent
layer), ``tallies`` (none).  What it hands ``attend.recur`` beside the
one-token update: the same rule over a CHUNK of ``CHUNK`` consecutive
tokens of one request (``_gdn_chunk``), which the whole-prompt prefill
runs once a chunk instead of the token update once a token.

Precision as served: weights (and K/V pages) in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, norms,
softmax, gates, decays and THE RECURRENT STATE in float32 (the chunk
form's own products, all float32 on both sides, at ``highest``).
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import numpy as np

from .hybrid_moe_lm import _mm, rms_norm

GDN_SCOPE = "gdn_update"        # the one-token update's operations
GDN_CHUNK_SCOPE = "gdn_chunk"   # the chunk form's
FFN_SCOPE = "dense_ffn"
CHUNK = 64                      # tokens the chunk form takes at once


class GatedDeltaLM:
    """Sized by constructor arguments; ``layer_kinds`` is the pattern
    (Olmo-Hybrid: three ``"recurrent"`` then one ``"attention"`` a
    period)."""

    def __init__(self, vocab_size: int, d_model: int,
                 layer_kinds: Sequence[str], num_heads: int, head_dim: int,
                 lin_heads: int, lin_key_dim: int, lin_value_dim: int,
                 conv_kernel: int, ffn_dim: int, rms_eps: float = 1e-6,
                 dtype="bfloat16", max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.layer_kinds = tuple(layer_kinds)
        bad = set(self.layer_kinds) - {"attention", "recurrent"}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad) or 'nothing'}")
        self.num_layers = len(self.layer_kinds)
        self.num_heads = self.num_kv_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.lin_heads = int(lin_heads)
        self.lin_key_dim, self.lin_value_dim = (int(lin_key_dim),
                                                int(lin_value_dim))
        self.conv_kernel, self.ffn_dim = int(conv_kernel), int(ffn_dim)
        self.rms_eps = float(rms_eps)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)     # no positional table
        self.tallies = ()
        # q | k | v side by side, as the convolution sees them
        self.lin_width = self.lin_heads * (2 * self.lin_key_dim
                                           + self.lin_value_dim)
        # one slot's state of ONE recurrent layer: the delta rule's
        # d_k x d_v matrix a head, and the K-1 positions the convolution
        # looks back on, oldest first, side by side in one row
        self.recurrent_state = {
            "s": ((self.lin_heads, self.lin_key_dim, self.lin_value_dim),
                  np.float32),
            "tail": (((self.conv_kernel - 1) * self.lin_width,),
                     np.float32)}

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales; the decay's
        ``A_log``/``dt_bias`` as the gated linear-attention families set
        them (rates 1..16, steps 1e-3..1e-1), one of each a head."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v, f = self.d_model, self.vocab_size, self.ffn_dim
        hd = self.num_heads * self.head_dim
        nh, cv = self.lin_heads, self.lin_heads * self.lin_value_dim
        keys = iter(jax.random.split(key, 2 + 12 * self.num_layers))

        def dense(shape, scale=None, dtype=dt):
            scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * scale).astype(dtype)

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((v, dm), 1.0), "lm_head": dense((dm, v)),
             "norm_f": ones(dm), "layers": []}
        for kind in self.layer_kinds:
            lw = {"norm_mix": ones(dm), "norm_ffn": ones(dm),
                  "ffn_w_gate": dense((dm, f)), "ffn_w_up": dense((dm, f)),
                  "ffn_w_down": dense((f, dm))}
            if kind == "attention":
                lw.update(wq=dense((dm, hd)), wk=dense((dm, hd)),
                          wv=dense((dm, hd)), wo=dense((hd, dm)),
                          q_norm=ones(hd), k_norm=ones(hd))
            else:
                rate = jax.random.uniform(next(keys), (nh,), jnp.float32,
                                          1.0, 16.0)
                step = jnp.exp(jax.random.uniform(
                    next(keys), (nh,), jnp.float32,
                    math.log(1e-3), math.log(1e-1)))
                lw.update(
                    gdn_wqkv=dense((dm, self.lin_width)),
                    gdn_conv=dense((self.conv_kernel, self.lin_width),
                                   1.0 / math.sqrt(self.conv_kernel),
                                   jnp.float32),
                    gdn_a_log=jnp.log(rate),
                    # softplus^-1(step)
                    gdn_dt_bias=step + jnp.log(-jnp.expm1(-step)),
                    gdn_wa=dense((dm, nh)), gdn_wb=dense((dm, nh)),
                    gdn_wg=dense((dm, cv)),
                    gdn_onorm=ones(self.lin_value_dim),
                    gdn_wout=dense((cv, dm)))
            w["layers"].append(lw)
        return w

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) -> ``(logits [..., V], cache)``; ``positions`` are not
        read (no positional term).  See the module header for what
        ``attend`` carries."""
        import jax
        import jax.numpy as jnp

        x = weights["tok_emb"][tokens].astype(jnp.float32)
        for l, kind in enumerate(self.layer_kinds):
            lw = weights["layers"][l]
            mixer = self._attention if kind == "attention" else self._gdn
            y, cache = mixer(l, lw, x, cache, attend)
            x = x + self._rms(y, lw["norm_mix"])
            with jax.named_scope(FFN_SCOPE):
                y = _mm(jax.nn.silu(_mm(x, lw["ffn_w_gate"]))
                        * _mm(x, lw["ffn_w_up"]), lw["ffn_w_down"])
            x = x + self._rms(y, lw["norm_ffn"])
        return _mm(self._rms(x, weights["norm_f"]), weights["lm_head"]), \
            cache

    def _rms(self, x, g):
        return rms_norm(x, g, self.rms_eps)

    def _attention(self, l, lw, x, cache, attend):
        """Layer ``l``'s softmax attention of the rows ``x`` -> (its
        output through ``wo``, cache).  q and k are normalised over all
        their heads' lanes at once, before the split."""
        import jax.numpy as jnp

        heads = (*x.shape[:-1], self.num_heads, self.head_dim)
        q = self._qk_norm(_mm(x, lw["wq"]), lw["q_norm"]).reshape(heads)
        k = self._qk_norm(_mm(x, lw["wk"]), lw["k_norm"]).reshape(heads)
        v = _mm(x, lw["wv"]).reshape(heads)
        ctx, cache = attend(l, q, k, v, cache)
        return _mm(ctx.reshape(*x.shape[:-1], -1).astype(jnp.float32),
                   lw["wo"]), cache

    def _qk_norm(self, x, g):
        return self._rms(x, g)

    def _gdn(self, l, lw, x, cache, attend):
        """Layer ``l``'s Gated DeltaNet mixer of the rows ``x`` -> (its
        output through ``gdn_wout``, cache)."""
        import jax

        rows = {"u": _mm(x, lw["gdn_wqkv"]), "a": _mm(x, lw["gdn_wa"]),
                "b": _mm(x, lw["gdn_wb"])}
        o, cache = attend.recur(
            l, functools.partial(self._gdn_token, lw), rows, cache,
            chunk_fn=functools.partial(self._gdn_chunk, lw), chunk=CHUNK)
        o = rms_norm(o, lw["gdn_onorm"], self.rms_eps)
        return _mm(o.reshape(*x.shape[:-1], -1)
                   * jax.nn.silu(_mm(x, lw["gdn_wg"])), lw["gdn_wout"]), cache

    # -- the gated delta rule -------------------------------------------------
    def _heads(self, conv):
        """The convolved, activated rows ``[R, lin_width]`` split into
        (q^ [R, H, dk], k^ [R, H, dk], v [R, H, dv]): q and k at unit
        length a head, q scaled by ``dk^-1/2``."""
        import jax
        import jax.numpy as jnp

        nh, dk, dv = self.lin_heads, self.lin_key_dim, self.lin_value_dim
        q = conv[:, :nh * dk].reshape(-1, nh, dk)
        k = conv[:, nh * dk:2 * nh * dk].reshape(-1, nh, dk)
        v = conv[:, 2 * nh * dk:].reshape(-1, nh, dv)
        q = q * jax.lax.rsqrt(
            jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(dk)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        return q, k, v

    def _log_decay(self, lw, a):
        """``log alpha`` [R, H] (never above 0) of the gate rows ``a``."""
        import jax
        import jax.numpy as jnp

        return -jnp.exp(lw["gdn_a_log"]) * jax.nn.softplus(
            a + lw["gdn_dt_bias"])

    def _beta(self, b):
        """The write strength [R, H] in (0, 2): above 1 the rule's
        ``I - beta k k^T`` has a negative eigenvalue."""
        import jax

        return 2.0 * jax.nn.sigmoid(b)

    def _gdn_token(self, lw, rows, state):
        """One token a row through a recurrent layer: ``rows`` the
        token's projections (``u [R, lin_width]`` before the
        convolution, ``a`` and ``b [R, H]``), ``state`` the rows' state
        BEFORE it (``s [R, H, dk, dv]``, ``tail [R, (K-1)*lin_width]``)
        -> (``o [R, H, dv]``, the state after it).  All float32.  The
        state is read once and written once: ``S'^T k`` and ``S'^T q``
        come out of one pass, and ``o = S'^T q + (k.q) beta (v - S'^T
        k)`` is ``S_t^T q`` without another."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope(GDN_SCOPE):
            wide = rows["u"].shape[-1]
            window = jnp.concatenate([state["tail"], rows["u"]], axis=1)
            conv = sum(window[:, j * wide:(j + 1) * wide]
                       * lw["gdn_conv"][j] for j in range(self.conv_kernel))
            q, k, v = self._heads(jax.nn.silu(conv))
            decay = jnp.exp(self._log_decay(lw, rows["a"]))     # [R, H]
            s = decay[..., None, None] * state["s"]             # alpha S
            ks = jnp.sum(k[..., None] * s, axis=-2)             # S'^T k
            qs = jnp.sum(q[..., None] * s, axis=-2)             # S'^T q
            delta = self._beta(rows["b"])[..., None] * (v - ks)
            s = s + k[..., None] * delta[..., None, :]
            o = qs + jnp.sum(q * k, -1, keepdims=True) * delta
        return o, {"s": s, "tail": window[:, wide:]}

    def _gdn_chunk(self, lw, rows, n_real, state):
        """``CHUNK`` consecutive tokens of ONE request through a
        recurrent layer at once: ``rows`` their projections (``u [C,
        lin_width]``, ``a`` and ``b [C, H]``), of which the first
        ``n_real`` are the request's (the rest is padding and never
        touches the state), ``state`` the request's state before the
        chunk (``s [1, H, dk, dv]``, ``tail [1, (K-1)*lin_width]``) ->
        (``o [C, H, dv]``, the state after token ``n_real - 1``).

        The rule's WY form.  With ``G_t`` the summed log-decays up to
        and with token t, ``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)``
        (what token t writes along ``k_t``) solves the unit lower
        triangular system ``(I + A) U = beta (V - e^G K S_0)``, ``A_tj =
        beta_t e^{G_t - G_j} k_t.k_j`` for ``j < t``; then ``O = e^G Q
        S_0 + P U`` with ``P_tj = e^{G_t - G_j} q_t.k_j`` for ``j <= t``
        and ``S_C = e^{G_C} S_0 + (e^{G_C - G} K)^T U``.  Every decay
        ratio is the ``exp`` of a difference of summed logs that is
        never above 0: no quotient of products, nothing to overflow.  A
        padding row has ``beta = 0`` and ``log alpha = 0``: it writes
        nothing and decays nothing."""
        import jax
        import jax.numpy as jnp

        hi = _exact
        with jax.named_scope(GDN_CHUNK_SCOPE):
            c, wide = rows["u"].shape
            real = jnp.arange(c, dtype=jnp.int32) < n_real
            window = jnp.concatenate(
                [state["tail"].reshape(self.conv_kernel - 1, wide),
                 rows["u"]])
            conv = sum(window[j:j + c] * lw["gdn_conv"][j]
                       for j in range(self.conv_kernel))
            # head-major from here: [H, C, ...]
            q, k, v = (jnp.swapaxes(x, 0, 1)
                       for x in self._heads(jax.nn.silu(conv)))
            beta = jnp.where(real[:, None], self._beta(rows["b"]), 0.0).T
            g = jnp.cumsum(jnp.where(
                real[:, None], self._log_decay(lw, rows["a"]), 0.0),
                axis=0).T                                       # [H, C]
            ratio = g[:, :, None] - g[:, None, :]               # G_t - G_j
            at, on = jnp.tril(jnp.ones((c, c), bool)), \
                jnp.tril(jnp.ones((c, c), bool), -1)
            ratio = jnp.exp(jnp.where(at, ratio, 0.0))
            a = jnp.where(on, ratio * beta[..., None]
                          * hi(k, jnp.swapaxes(k, 1, 2)), 0.0)
            p = jnp.where(at, ratio * hi(q, jnp.swapaxes(k, 1, 2)), 0.0)
            eg = jnp.exp(g)[..., None]                          # e^G
            s0 = state["s"][0]                                  # [H, dk, dv]
            # T [beta V | beta e^G K]: what each token would write from
            # a zero state, and what of S_0 it has to take back
            t = hi(_unit_lower_inverse(a), jnp.concatenate(
                [beta[..., None] * v, beta[..., None] * eg * k], axis=-1))
            read = hi(jnp.concatenate([eg * q, t[..., v.shape[-1]:]],
                                      axis=1), s0)              # [H, 2C, dv]
            u = t[..., :v.shape[-1]] - read[:, c:]
            o = read[:, :c] + hi(p, u)
            left = jnp.exp(g[:, -1:] - g)[..., None] * k        # e^{G_C - G} K
            s = jnp.exp(g[:, -1])[:, None, None] * s0 \
                + hi(jnp.swapaxes(left, 1, 2), u)
            tail = jax.lax.dynamic_slice_in_dim(
                window, n_real, self.conv_kernel - 1)
        return jnp.swapaxes(o, 0, 1), {"s": s[None],
                                       "tail": tail.reshape(1, -1)}


def _exact(a, b):
    """``a @ b`` of float32 operands as float32 products (the chip's
    default for them is one bfloat16 pass)."""
    import jax
    import jax.numpy as jnp

    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [..., C, C]`` strictly lower triangular, C
    a power of two: the inverse of a block ``[[A, 0], [B, D]]`` is
    ``[[A^-1, 0], [-D^-1 B A^-1, D^-1]]``, from 1 x 1 blocks (whose
    inverse is 1) up, log2(C) rounds of two matmuls over all the
    diagonal blocks at once.  No power of ``a`` is ever formed, so what
    is computed is no larger than the inverse's own entries."""
    import jax
    import jax.numpy as jnp

    c = a.shape[-1]
    if c & (c - 1):
        raise ValueError(f"a chunk of {c} rows is not a power of two")
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (c, 1, 1), a.dtype)
    b = 1
    while b < c:
        nb = c // (2 * b)
        # the diagonal blocks of 2b, and of each its lower-left quarter
        diag = jnp.moveaxis(jnp.diagonal(
            a.reshape(lead + (nb, 2 * b, nb, 2 * b)), axis1=-4, axis2=-2),
            -1, -3)
        pair = inv.reshape(lead + (nb, 2, b, b))
        first, second = pair[..., 0, :, :], pair[..., 1, :, :]
        low = -_exact(_exact(second, diag[..., b:, :b]), first)
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([low, second], axis=-1)], axis=-2)
        b *= 2
    return inv[..., 0, :, :]
