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
docstring (the matmul feed and the norm are ``blocks.py``'s):
``forward(weights, tokens, positions, cache, attend)``.  What it
declares: ``layer_kinds``
(``"attention"`` or ``"recurrent"`` a layer), ``num_kv_heads`` (the query
heads' count), ``recurrent_state`` (one slot's state of one recurrent
layer), ``tallies`` (none), ``prefill_chunks_per_call(rows)``.  What it
hands ``attend.recur`` beside the one-token update: the same rule over a
GROUP of chunks of ``CHUNK`` consecutive tokens of one request
(``_gdn_chunk``), which the whole-prompt prefill runs once a group
instead of the token update once a token.  One call covers
``prefill_chunks_per_call(bucket)`` chunks (the bucket's, capped by
``GROUP_BYTES`` of temporaries: four at the served widths, a function
of the call's shapes alone) and tells ``recur`` so
(``chunks_per_call``): what of the rule's WY form reads no state (the
convolution, the unit-length q and k, the decays, ``Q K^T`` and ``K
K^T``, the triangular inverse, ``T [beta V | beta e^G K]``) is formed
for the whole group at once inside the engine's one loop a layer, the
state passes through the group's chunks in sequence (``_state_pass``),
and the group's outputs follow from what the pass kept.

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

from .blocks import DENSE_SCOPE, _mm, dense_from, head_logits, rms_norm

GDN_SCOPE = "gdn_update"        # the one-token update's operations
GDN_CHUNK_SCOPE = "gdn_chunk"   # the chunk form's
CHUNK = 64                      # tokens of one chunk of the rule's WY form
# what the chunks one call takes together may hold in temporaries: half
# of the 128 MiB of fast memory the chip's compiler keeps a loop body's
# operands in (PERF.md section 6, PR 53, has the sweep this is read from)
GROUP_BYTES = 64 << 20


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

        dense = dense_from(keys, dt)
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
            x = x + rms_norm(y, lw["norm_mix"], self.rms_eps)
            with jax.named_scope(DENSE_SCOPE):
                y = _mm(jax.nn.silu(_mm(x, lw["ffn_w_gate"]))
                        * _mm(x, lw["ffn_w_up"]), lw["ffn_w_down"])
            x = x + rms_norm(y, lw["norm_ffn"], self.rms_eps)
        # every row's logits, or a prompt's ``attend.read_row`` alone
        return head_logits(self, weights, x, attend), cache

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
        return rms_norm(x, g, self.rms_eps)

    def _gdn(self, l, lw, x, cache, attend):
        """Layer ``l``'s Gated DeltaNet mixer of the rows ``x`` -> (its
        output through ``gdn_wout``, cache)."""
        import jax

        rows = {"u": _mm(x, lw["gdn_wqkv"]), "a": _mm(x, lw["gdn_wa"]),
                "b": _mm(x, lw["gdn_wb"])}
        group = self.prefill_chunks_per_call(x.shape[0])
        o, cache = attend.recur(
            l, functools.partial(self._gdn_token, lw), rows, cache,
            chunk_fn=functools.partial(self._gdn_chunk, lw),
            chunk=group * CHUNK, chunks_per_call=group)
        o = rms_norm(o, lw["gdn_onorm"], self.rms_eps)
        return _mm(o.reshape(*x.shape[:-1], -1)
                   * jax.nn.silu(_mm(x, lw["gdn_wg"])), lw["gdn_wout"]), cache

    def prefill_chunks_per_call(self, rows):
        """Chunks ONE call of ``_gdn_chunk`` takes of a prompt bucket of
        ``rows`` rows: all of them, up to what ``GROUP_BYTES`` of the
        group's float32 temporaries allow (a chunk's: four ``C x C``
        matrices a head, the rows q, k, v, ``T [beta V | beta e^G K]``,
        ``e^G q``, the decayed keys, what the tokens wrote and the
        output, and the state it started from)."""
        nh, dk, dv = self.lin_heads, self.lin_key_dim, self.lin_value_dim
        a_chunk = 4 * nh * (4 * CHUNK * CHUNK + CHUNK * (5 * dk + 4 * dv)
                            + dk * dv)
        return max(1, min(-(-int(rows) // CHUNK), GROUP_BYTES // a_chunk))

    # -- the gated delta rule -------------------------------------------------
    def _heads(self, conv):
        """The convolved, activated rows ``[R, lin_width]`` split into
        (q^ [R, H, dk], k^ [R, H, dk], v [R, H, dv]): q and k at unit
        length a head, q scaled by ``dk^-1/2``."""
        nh, dk, dv = self.lin_heads, self.lin_key_dim, self.lin_value_dim
        q = conv[:, :nh * dk].reshape(-1, nh, dk)
        k = conv[:, nh * dk:2 * nh * dk].reshape(-1, nh, dk)
        v = conv[:, 2 * nh * dk:].reshape(-1, nh, dv)
        return _unit(q) / math.sqrt(dk), _unit(k), v

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
        """A GROUP of whole ``CHUNK``-token chunks, consecutive tokens
        of ONE request, through a recurrent layer at once: ``rows``
        their projections (``u [G*C, lin_width]``, ``a`` and ``b [G*C,
        H]``), of which the first ``n_real`` are the request's (the rest
        is padding and never touches the state), ``state`` the request's
        state before the group (``s [1, H, dk, dv]``, ``tail [1,
        (K-1)*lin_width]``) -> (``o [G*C, H, dv]``, the state after
        token ``n_real - 1``).

        The rule's WY form, a chunk.  With ``G_t`` the summed log-decays
        up to and with token t OF ITS CHUNK, ``u_t = beta_t (v_t -
        alpha_t S_{t-1}^T k_t)`` (what token t writes along ``k_t``)
        solves the unit lower triangular system ``(I + A) U = beta (V -
        e^G K S_0)``, ``A_tj = beta_t e^{G_t - G_j} k_t.k_j`` for ``j <
        t``; then ``O = e^G Q S_0 + P U`` with ``P_tj = e^{G_t - G_j}
        q_t.k_j`` for ``j <= t`` and ``S_C = e^{G_C} S_0 + (e^{G_C - G}
        K)^T U``.  Every decay ratio is the ``exp`` of a difference of
        summed logs that is never above 0: no quotient of products,
        nothing to overflow.  A padding row has ``beta = 0`` and ``log
        alpha = 0``: it writes nothing and decays nothing, and a chunk
        of padding rows hands the state on as it got it.

        Of all that only ``U``'s right side, ``O``'s first term and
        ``S_C`` read the chunk's ``S_0``.  Everything else is a function
        of the chunk's own rows and is formed for all G chunks at once,
        on ``[G, H, C, ...]`` operands; the state then passes through
        the chunks one after another (``_state_pass``: two products and
        two element-wise operations a chunk), and the outputs of the
        whole group follow from what the pass kept."""
        import jax
        import jax.numpy as jnp

        hi = _exact
        with jax.named_scope(GDN_CHUNK_SCOPE):
            r, wide = rows["u"].shape
            c, nh = CHUNK, self.lin_heads
            dk, dv = self.lin_key_dim, self.lin_value_dim
            if r % c:
                raise ValueError(f"{r} rows are no whole number of chunks "
                                 f"of {c}")
            n = r // c
            real = (jnp.arange(r, dtype=jnp.int32) < n_real)[:, None]
            window = jnp.concatenate(
                [state["tail"].reshape(self.conv_kernel - 1, wide),
                 rows["u"]])

            def heads(lo, d):
                """Columns ``lo`` on of the window, ``d`` a head, head-
                major BEFORE anything is computed on them (the products
                want the heads in front: turned here the raw rows move
                once, turned after the convolution every row moves
                again), convolved and activated -> ``[H, G, C, d]``."""
                win, taps = (jnp.swapaxes(
                    x[:, lo:lo + nh * d].reshape(-1, nh, d), 0, 1)
                    for x in (window, lw["gdn_conv"]))
                conv = sum(win[:, j:j + r] * taps[:, j, None]
                           for j in range(self.conv_kernel))
                return jax.nn.silu(conv).reshape(nh, n, c, d)

            q, k, v = (jnp.swapaxes(x, 0, 1) for x in (
                _unit(heads(0, dk)) / math.sqrt(dk),
                _unit(heads(nh * dk, dk)), heads(2 * nh * dk, dv)))
            beta = jnp.swapaxes(jnp.where(
                real, self._beta(rows["b"]), 0.0).reshape(n, c, nh), 1, 2)
            g = jnp.swapaxes(jnp.cumsum(jnp.where(
                real, self._log_decay(lw, rows["a"]), 0.0).reshape(
                    n, c, nh), axis=1), 1, 2)                   # [G, H, C]
            ratio = g[..., :, None] - g[..., None, :]           # G_t - G_j
            at, on = jnp.tril(jnp.ones((c, c), bool)), \
                jnp.tril(jnp.ones((c, c), bool), -1)
            ratio = jnp.exp(jnp.where(at, ratio, 0.0))
            # Q K^T over K K^T, one product
            qk = hi(jnp.concatenate([q, k], axis=-2), jnp.swapaxes(k, -1, -2))
            a = jnp.where(on, ratio * beta[..., None] * qk[..., c:, :], 0.0)
            p = jnp.where(at, ratio * qk[..., :c, :], 0.0)
            eg = jnp.exp(g)[..., None]                          # e^G
            # T [beta V | beta e^G K]: what each token would write from
            # a zero state, and what of S_0 it has to take back
            t = hi(_unit_lower_inverse(a), jnp.concatenate(
                [beta[..., None] * v, beta[..., None] * eg * k], axis=-1))
            left = jnp.exp(g[..., -1:] - g)[..., None] * k      # e^{G_C - G} K
            s0, u, s = _state_pass(
                t[..., :dv], t[..., dv:], jnp.swapaxes(left, -1, -2),
                jnp.exp(g[..., -1])[..., None, None], state["s"][0])
            o = hi(eg * q, s0) + hi(p, u)                       # [G, H, C, dv]
            tail = jax.lax.dynamic_slice_in_dim(
                window, n_real, self.conv_kernel - 1)
        return jnp.swapaxes(o, 1, 2).reshape(r, nh, dv), {
            "s": s[None], "tail": tail.reshape(1, -1)}


def _state_pass(t_v, t_k, left_t, decay, s):
    """The state ``s [H, dk, dv]`` through G chunks in sequence, the one
    part of the chunk form that waits on the chunk before: ``t_v [G, H,
    C, dv]`` what each token would write from a zero state, ``t_k [G, H,
    C, dk]`` what it takes back of the state it starts from, ``left_t
    [G, H, dk, C]`` the keys decayed to the chunk's end, ``decay [G, H,
    1, 1]`` the chunk's whole decay -> (the state each chunk started
    from ``[G, H, dk, dv]``, what its tokens wrote ``[G, H, C, dv]``,
    the state after the last chunk)."""
    import jax.numpy as jnp

    starts, wrote = [], []
    for i in range(t_v.shape[0]):
        starts.append(s)
        wrote.append(t_v[i] - _exact(t_k[i], s))
        s = decay[i] * s + _exact(left_t[i], wrote[-1])
    return jnp.stack(starts), jnp.stack(wrote), s


def _unit(x):
    """The rows ``x [..., d]`` at unit length."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _exact(a, b):
    """``a @ b`` of float32 operands as float32 products (the chip's
    default for them is one bfloat16 pass)."""
    import jax
    import jax.numpy as jnp

    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


_SOLVED = 16    # rows of a diagonal block inverted by substitution


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [..., C, C]`` strictly lower triangular, C
    a power of two, in float32 throughout.  The diagonal blocks of
    ``_SOLVED`` rows by forward substitution, row by row on the vector
    unit (``x_i = e_i - sum_{j<i} a_ij x_j``: fifteen steps over all
    blocks at once; products of matrices that small leave the matrix
    unit idle).  From there by halves: the inverse of a block ``[[A, 0],
    [B, D]]`` is ``[[A^-1, 0], [-D^-1 B A^-1, D^-1]]``, a round over all
    the diagonal blocks of ``2b`` at once as two products of whole ``C x
    C`` matrices: with ``X`` the inverses of the blocks of ``b`` (zero
    elsewhere) and ``L`` the lower-left quarters of the blocks of ``2b``
    (zero elsewhere), ``X - (X L) X`` holds the inverses of the blocks
    of ``2b``, and the terms the zeros add are exactly zero; the last
    round's one block is sliced out instead.  No power of ``a`` is ever
    formed, so what is computed is no larger than the inverse's own
    entries."""
    import jax.numpy as jnp

    c = a.shape[-1]
    if c & (c - 1):
        raise ValueError(f"a chunk of {c} rows is not a power of two")
    lead, b = a.shape[:-2], min(c, _SOLVED)
    own = jnp.eye(c // b, dtype=bool)[:, None, :, None]     # block I is J
    blocks = jnp.sum(jnp.where(own, a.reshape(
        lead + (c // b, b, c // b, b)), 0.0), axis=-2)      # [.., C/b, b, b]
    unit = jnp.broadcast_to(jnp.eye(b, dtype=a.dtype), blocks.shape)
    solved = unit[..., :1, :]                               # rows so far
    for i in range(1, b):
        solved = jnp.concatenate([solved, unit[..., i:i + 1, :] - jnp.sum(
            blocks[..., i, :i, None] * solved, axis=-2, keepdims=True)],
            axis=-2)
    inv = jnp.where(own, solved[..., None, :], 0.0).reshape(a.shape)
    row = jnp.arange(c, dtype=jnp.int32)[:, None]
    col = jnp.arange(c, dtype=jnp.int32)[None, :]
    while 2 * b < c:
        low = jnp.where((row // (2 * b) == col // (2 * b))
                        & (row % (2 * b) >= b) & (col % (2 * b) < b), a, 0.0)
        inv = inv - _exact(_exact(inv, low), inv)
        b *= 2
    if b < c:
        first, second = inv[..., :b, :b], inv[..., b:, b:]
        low = -_exact(_exact(second, a[..., b:, :b]), first)
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([low, second], axis=-1)], axis=-2)
    return inv
