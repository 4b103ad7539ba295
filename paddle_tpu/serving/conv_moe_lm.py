"""A served model whose layers are of two kinds: a GATED SHORT
CONVOLUTION (three gates out of one projection, a depth-wise causal
convolution of ``conv_kernel`` taps over the product of two of them, the
third on its output) that keeps ``conv_kernel - 1`` rows of its own
inputs a request and no keys, and softmax attention with grouped-query
heads, a norm on every q and k head and a rotary term on all lanes; a
dense SwiGLU in the leading layers and, in the others, a mixture of
experts of which this chip may hold EVERY one (``ops/moe_ops.py``
``moe_share_*``: a row then makes exactly ``top_k`` local pairs) with no
shared expert beside it; the head is the transposed input embedding.
The architecture is LFM2-8B-A1B's; the equations are in the reference's
docstring (``benchmark/reference/conv_moe_lm.py``), which this file is
tested against and shares no code with.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring (the matmul feed, the norm, the half-split rotary pairing and
the routed share are ``blocks.py``'s): ``forward(weights, tokens,
positions, cache, attend)``.  What it declares:
``layer_kinds`` (``"recurrent"``: the convolution; ``"attention"``),
``num_kv_heads``, ``recurrent_state`` (one slot's state of one
convolution layer: ``tail``, the last ``conv_kernel - 1`` inputs of the
taps, oldest first), ``tallies`` / ``step_tallies`` /
``prefill_tallies`` and ``prefill_chunks_per_call``.  The
convolution's one-token update has a ``live`` parameter and hands a
dead row's tail back as it was.  Its prompt form is ONE call for the
whole bucket (``chunk`` = the bucket: the convolution reads no state but
its own inputs, so nothing is scanned), which leaves the tail of the
prompt's last REAL token.  In a whole-prompt prefill the head runs over
``attend.read_row`` alone.

Precision as served: weights (and K/V pages) in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, norms, the
rotary term, router scores, softmax, the gates, the taps and THE TAIL in
float32.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import numpy as np

from ..ops import moe_ops
from .blocks import (DENSE_SCOPE, ROPE_SCOPE, _mm, dense_from,
                     half_split_angles, half_split_rotate, held_ids,
                     read_rows, rms_norm, route_share, share_ffn,
                     step_tallies)

CONV_SCOPE = "short_conv"
CONV_PROMPT_SCOPE = "short_conv_prompt"
# what a whole-prompt prefill counts of its convolution layers: the real
# rows the prompt form took, a layer
CONV_ROWS_TALLY = "decode_prefill_conv_rows"


class ConvMoELM:
    """Sized by constructor arguments; ``layer_kinds`` is the pattern
    (LFM2: ``"recurrent"`` for a convolution layer, ``"attention"`` for
    a ``full_attention`` one), the first ``dense_layers`` layers have a
    dense feed-forward of ``ffn_dim``, the others the routed experts.
    ``held_experts`` are the routed-expert ids this chip holds of
    ``num_experts`` (all of them in the served configuration); the
    router keeps its full width.  ``tie_head``: the head is the input
    embedding read as ``[V, D]``, no second matrix."""

    def __init__(self, vocab_size: int, d_model: int,
                 layer_kinds: Sequence[str], num_heads: int,
                 num_kv_heads: int, head_dim: int, conv_kernel: int,
                 ffn_dim: int, dense_layers: int, num_experts: int,
                 top_k: int, held_experts: Sequence[int], expert_dim: int,
                 rope_theta: float = 1e6, rms_eps: float = 1e-5,
                 tie_head: bool = True, dtype="bfloat16",
                 max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.layer_kinds = tuple(layer_kinds)
        bad = set(self.layer_kinds) - {"attention", "recurrent"}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad) or 'nothing'}")
        self.num_layers = len(self.layer_kinds)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        self.head_dim = int(head_dim)
        if self.head_dim % 2:
            raise ValueError("head_dim must be even: every lane turns")
        self.conv_kernel = int(conv_kernel)
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel must be 2 or more: a layer that "
                             "looks back on nothing keeps no state")
        self.ffn_dim, self.dense_layers = int(ffn_dim), int(dense_layers)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held_experts = held_ids(held_experts, self.num_experts)
        self.expert_dim = int(expert_dim)
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self.tie_head = bool(tie_head)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)     # no positional table
        # the counters forward adds to through attend.tally: a joint
        # step's (``HIT_TALLIES`` read back only by a step that takes
        # the hit form, ``step_tallies``), and those only a whole-prompt
        # prefill reads back
        self.tallies = ("moe_local_assignments", "moe_experts_hit") \
            + moe_ops.HIT_TALLIES
        self.prefill_tallies = moe_ops.GROUPED_TALLIES + (CONV_ROWS_TALLY,)
        # one slot's state of ONE convolution layer: the inputs of the
        # taps at the last ``conv_kernel - 1`` positions, oldest first
        self.recurrent_state = {
            "tail": ((self.conv_kernel - 1, self.d_model), np.float32)}

    step_tallies = step_tallies

    def prefill_chunks_per_call(self, rows):
        """One call of the prompt form covers the whole bucket."""
        return 1

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales.  The input
        embedding at ``d_model^-1/2`` (it is the head too: logits of
        unit size); the taps at ``conv_kernel^-1/2``; the experts'
        choice bias uniform in +-0.05, a fifth of the spread of the
        scores of random weights (the published buffer starts at zero
        and training moves it: a zero here would let a bias that leaks
        into the weights pass unseen)."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v = self.d_model, self.vocab_size
        hq = self.num_heads * self.head_dim
        hkv = self.num_kv_heads * self.head_dim
        e, f = self.num_experts, self.expert_dim
        nf = len(self.held_experts) * f
        keys = iter(jax.random.split(key, 4 + 12 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((v, dm), 1.0 / math.sqrt(dm)),
             "norm_f": ones(dm), "layers": []}
        if not self.tie_head:
            w["lm_head"] = dense((dm, v))
        for l, kind in enumerate(self.layer_kinds):
            lw = {"norm1": ones(dm), "norm2": ones(dm)}
            if kind == "attention":
                lw.update(wq=dense((dm, hq)), wk=dense((dm, hkv)),
                          wv=dense((dm, hkv)), wo=dense((hq, dm)),
                          q_norm=ones(self.head_dim),
                          k_norm=ones(self.head_dim))
            else:
                lw.update(
                    conv_w_in=dense((dm, 3 * dm)),
                    conv_taps=dense((self.conv_kernel, dm),
                                    1.0 / math.sqrt(self.conv_kernel),
                                    jnp.float32),
                    conv_w_out=dense((dm, dm)))
            if l < self.dense_layers:
                lw.update(ffn_w_gate=dense((dm, self.ffn_dim)),
                          ffn_w_up=dense((dm, self.ffn_dim)),
                          ffn_w_down=dense((self.ffn_dim, dm)))
            else:
                lw.update(
                    moe_router=dense((dm, e), dtype=jnp.float32),
                    moe_router_bias=jax.random.uniform(
                        next(keys), (e,), jnp.float32, -0.05, 0.05),
                    moe_w_gate=dense((dm, nf)), moe_w_up=dense((dm, nf)),
                    moe_w_down=dense((nf, dm), 1.0 / math.sqrt(f)))
            w["layers"].append(lw)
        return w

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) at their absolute ``positions`` -> ``(logits [..., V],
        cache)``; a whole-prompt prefill's are ``[1, V]``, the row
        ``attend.read_row``.  See the module header for what ``attend``
        carries."""
        import jax
        import jax.numpy as jnp

        w = weights
        x = w["tok_emb"][tokens].astype(jnp.float32)
        lead = x.shape[:-1]
        for l, kind in enumerate(self.layer_kinds):
            lw = w["layers"][l]
            h = rms_norm(x, lw["norm1"], self.rms_eps)
            if kind == "attention":
                y, cache = self._attention(l, lw, h, positions, cache,
                                           attend)
            else:
                y, cache = self._short_conv(l, lw, h, cache, attend)
            x = x + y
            h = rms_norm(x, lw["norm2"], self.rms_eps)
            if l < self.dense_layers:
                with jax.named_scope(DENSE_SCOPE):
                    x = x + _mm(jax.nn.silu(_mm(h, lw["ffn_w_gate"]))
                                * _mm(h, lw["ffn_w_up"]), lw["ffn_w_down"])
            else:
                local = route_share(h, lw, attend, self.top_k,
                                    self.held_experts)
                x = x + share_ffn(self, h, lw, local, attend)
        return self._head(w, rms_norm(read_rows(x, attend), w["norm_f"],
                                      self.rms_eps)), cache

    def _head(self, w, x):
        """``x [..., D]`` over the vocabulary: the tied head reads the
        embedding where it lies, ``[V, D]``, contracted over D."""
        import jax
        import jax.numpy as jnp

        if not self.tie_head:
            return _mm(x, w["lm_head"])
        emb = w["tok_emb"]
        return jax.lax.dot_general(
            x.astype(emb.dtype), emb,
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _attention(self, l, lw, h, positions, cache, attend):
        """A full-attention layer's output ``[..., D]`` of rows ``h``:
        q and k normed a head, turned, attended through the engine."""
        import jax
        import jax.numpy as jnp

        lead = h.shape[:-1]
        q = _mm(h, lw["wq"]).reshape(*lead, self.num_heads, self.head_dim)
        k = _mm(h, lw["wk"]).reshape(*lead, self.num_kv_heads,
                                     self.head_dim)
        v = _mm(h, lw["wv"]).reshape(*lead, self.num_kv_heads,
                                     self.head_dim)
        q = rms_norm(q, lw["q_norm"], self.rms_eps)
        k = rms_norm(k, lw["k_norm"], self.rms_eps)
        with jax.named_scope(ROPE_SCOPE):
            turn = half_split_angles(positions, self.rope_theta,
                                     self.head_dim)
            q, k = half_split_rotate(q, *turn), half_split_rotate(k, *turn)
        ctx, cache = attend(l, q, k, v, cache)
        return _mm(ctx.reshape(*lead, -1).astype(jnp.float32),
                   lw["wo"]), cache

    def _short_conv(self, l, lw, h, cache, attend):
        """A convolution layer's output ``[..., D]`` of rows ``h``: the
        three gates' projection, the rule over the engine's state, the
        output projection; under ``short_conv`` in a step and
        ``short_conv_prompt`` in a whole-prompt prefill."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope(CONV_PROMPT_SCOPE if attend.prompt
                             else CONV_SCOPE):
            rows = {"bcu": _mm(h, lw["conv_w_in"])}
            if attend.prompt:
                attend.tally(CONV_ROWS_TALLY,
                             jnp.sum(attend.live, dtype=jnp.int32))
            gated, cache = attend.recur(
                l, functools.partial(self._conv_token, lw), rows, cache,
                chunk_fn=functools.partial(self._conv_chunk, lw),
                chunk=h.shape[0])
            return _mm(gated, lw["conv_w_out"]), cache

    def _gates(self, bcu):
        """``[..., 3D]`` -> (``z = B * u``, ``C``), each ``[..., D]``."""
        d = self.d_model
        return bcu[..., :d] * bcu[..., 2 * d:], bcu[..., d:2 * d]

    def _conv_token(self, lw, rows, state, live=None):
        """One token a row through a convolution layer: ``rows`` the
        token's projection (``bcu [R, 3D]``: the gates ``B | C | u``),
        ``state`` the rows' state BEFORE it (``tail [R, K-1, D]``, the
        taps' inputs at the K-1 positions before, oldest first) -> (``C
        * c [R, D]``, the state after it).  All float32, no activation.

        It takes ``live`` (bool ``[R]``; None: every row) and OWNS the
        dead rows: a row that is not live comes back with the tail it
        had, bit for bit."""
        import jax.numpy as jnp

        z, gate = self._gates(rows["bcu"])
        window = jnp.concatenate([state["tail"], z[:, None]], axis=1)
        conv = sum(window[:, j] * lw["conv_taps"][j]
                   for j in range(self.conv_kernel))
        tail = window[:, 1:]
        if live is not None:
            tail = jnp.where(live[:, None, None], tail, state["tail"])
        return gate * conv, {"tail": tail}

    def _conv_chunk(self, lw, rows, n_real, state):
        """The rows of ONE request's prompt through a convolution layer
        in one call: ``rows`` their projections (``bcu [T, 3D]``), of
        which the first ``n_real`` are the request's, ``state`` the
        request's before them (leading dimension 1) -> (``C * c [T,
        D]``, the state after token ``n_real - 1``: padding rows are
        never in it).  The taps in the token form's order, over all the
        rows at once."""
        import jax
        import jax.numpy as jnp

        z, gate = self._gates(rows["bcu"])
        t, k = z.shape[0], self.conv_kernel
        window = jnp.concatenate([state["tail"][0], z])     # [K-1+T, D]
        conv = sum(window[j:j + t] * lw["conv_taps"][j] for j in range(k))
        tail = jax.lax.dynamic_slice_in_dim(window, n_real, k - 1)
        return gate * conv, {"tail": tail[None]}
