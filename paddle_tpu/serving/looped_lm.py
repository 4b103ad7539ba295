"""A served dense decoder whose whole stack of layers every token passes
through ``loops`` times ON THE SAME WEIGHTS: pass ``t`` of layer ``l``
keeps keys and values of its own (cache layer ``t * num_layers + l``), so
``num_layers`` weight layers own ``loops * num_layers`` cache layers, and
pass ``t`` of a token attends what pass ``t`` of the earlier tokens wrote,
never another pass's.  A layer is pre-norm AND post-norm (an RMSNorm on
each sub-block's input and another on its output), softmax attention with
as many K/V heads as query heads under a rotary term on all lanes (halves
rotated), and a dense SwiGLU; the final norm is applied after EVERY pass
(the next pass starts from the normed stream) and feeds an exit gate, one
sigmoid a token a pass; the head is a matrix of its own.  The
architecture is Ouro-2.6B's (``model_type: ouro``, "Scaling Latent
Reasoning via Looped Language Models"); the equations are in the
reference's docstring (``benchmark/reference/looped_lm.py``), which this
file is tested against and shares no code with.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring: ``forward(weights, tokens, positions, cache, attend)``.  What
it declares: ``cache_layers`` (``loops * num_layers``: the pools' depth),
``tallies`` and ``prefill_tallies``, and NO ``layer_kinds``: its cache is
pages alone, so the prefix index, copy-on-write, chunked prefill and
speculation are what they are for ``TransformerLM``.

**The passes are one rolled loop** (``_passes``: ``lax.fori_loop`` over
``t`` with the stream, the pools and the gate's running products as the
carry), and the layers of a pass a second one inside it (``_stack``:
``lax.scan`` over the weights, which are STACKED: every matrix of
``weights["layers"]`` has a leading ``[num_layers]``).  The program's
size grows with neither ``loops`` nor ``num_layers``: a step's text holds
ONE layer body and one paged-attention call, which takes its cache layer
as a traced ``int32``, and the pools are updated in place through both
loops.  (Unrolled over 48 layers the step and each prefill bucket take
the chip's compiler a minute and a half apiece; rolled, seconds.)

**The exit gate** is computed and acted on by nothing: with the published
``early_exit_threshold`` of 1 the exit distribution ``p_t = lambda_t
prod_{j<t} (1 - lambda_j)`` (the rest on the last pass) reaches 1 only at
the last pass, so every token runs every pass.  What it leaves is a
counter: ``decode_loop_exit_mass``, the sum over a step's live rows of
``sum_t p_t (t + 1)`` (the pass a row would leave at, in expectation), in
thousandths of a pass; ``decode_loop_passes`` adds ``loops`` a step and a
whole-prompt prefill.

Precision as served: weights (and K/V pages) in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, norms,
rotary term, softmax and gate in float32.
"""
from __future__ import annotations

import math

from .blocks import (DENSE_SCOPE, _mm, _mm_t, half_split_angles,
                     half_split_rotate, rms_norm)

PASS_SCOPE = "loop_pass"    # one pass's whole body
NORM_SCOPE = "loop_norm"    # the norm between passes and the gate
PASSES_TALLY, EXIT_TALLY = "decode_loop_passes", "decode_loop_exit_mass"


class LoopedLM:
    """Sized by constructor arguments; Ouro-2.6B is 48 layers, 4 loops."""

    def __init__(self, vocab_size: int, d_model: int, num_layers: int,
                 loops: int, num_heads: int, head_dim: int, ffn_dim: int,
                 rope_theta: float = 1e6, rms_eps: float = 1e-6,
                 dtype="bfloat16", max_seq_len: int = 65536):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.num_layers, self.loops = int(num_layers), int(loops)
        if self.num_layers < 1 or self.loops < 1:
            raise ValueError(f"{num_layers} layers x {loops} loops: a "
                             f"stack has a layer and runs once at least")
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.ffn_dim = int(ffn_dim)
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)
        # K and V of every pass of every layer
        self.cache_layers = self.loops * self.num_layers
        self.tallies = (PASSES_TALLY, EXIT_TALLY)
        self.prefill_tallies = (PASSES_TALLY,)

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales, the layers'
        stacked (``layers[name]`` is ``[num_layers, ...]``); every
        norm's gain 1, the gate's bias 0."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v, f = self.d_model, self.vocab_size, self.ffn_dim
        hd = self.num_heads * self.head_dim
        n = self.num_layers
        keys = iter(jax.random.split(key, 3 + 7))

        def dense(shape, scale=None, dtype=dt):
            # a matrix's rows are its second-to-last axis (the last of a
            # vector): the stacked ones lead with the layer
            fan_in = shape[-2] if len(shape) > 1 else shape[-1]
            scale = 1.0 / math.sqrt(fan_in) if scale is None else scale
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * scale).astype(dtype)

        ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
        return {
            "tok_emb": dense((v, dm), 1.0), "lm_head": dense((dm, v)),
            "norm_f": ones(dm), "exit_w": dense((dm,), dtype=jnp.float32),
            "exit_b": jnp.zeros((), jnp.float32),
            "layers": {
                "norm_attn_in": ones(n, dm), "norm_attn_out": ones(n, dm),
                "norm_ffn_in": ones(n, dm), "norm_ffn_out": ones(n, dm),
                # q and k as a checkpoint stores them, [out, in]: the
                # chip's compiler wants the matrices whose product goes
                # under the rotary term that way round, and turns a
                # stack of [in, out] ones over before every step
                "wq": dense((n, hd, dm), 1.0 / math.sqrt(dm)),
                "wk": dense((n, hd, dm), 1.0 / math.sqrt(dm)),
                "wv": dense((n, dm, hd)), "wo": dense((n, hd, dm)),
                "ffn_w_gate": dense((n, dm, f)),
                "ffn_w_up": dense((n, dm, f)),
                "ffn_w_down": dense((n, f, dm))}}

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (any leading shape) at ``positions`` ->
        ``(logits [..., V], cache)``.  ``attend(c, q, k, v, cache)`` is
        called once a cache layer ``c = t * num_layers + l``, ``t`` the
        loop's counter."""
        import jax
        import jax.numpy as jnp

        x = weights["tok_emb"][tokens].astype(jnp.float32)
        # the rotary term covers every lane of a head
        cos, sin = half_split_angles(positions, self.rope_theta,
                                     self.head_dim)
        last = self.loops - 1

        def one_pass(t, carry):
            x, cache, stay, mass = carry
            with jax.named_scope(PASS_SCOPE):
                x, cache = self._stack(
                    lambda l, lw, inner: self._layer(
                        self._cache_layer(t, l), lw, *inner, cos, sin,
                        attend), (x, cache), weights["layers"])
            with jax.named_scope(NORM_SCOPE):
                x = self._between(weights, x, t)
                lam = self._gate(weights, x)
                # the share that leaves here: all that is left, at the
                # last pass
                leave = jnp.where(t == last, stay, lam * stay)
                return (x, cache, stay * (1.0 - lam),
                        mass + leave * (t + 1))

        stay = jnp.ones(x.shape[:-1], jnp.float32)
        x, cache, _, mass = self._passes(
            one_pass, (x, cache, stay, jnp.zeros_like(stay)))
        attend.tally(PASSES_TALLY, self.loops)
        attend.tally(EXIT_TALLY, jnp.round(1e3 * jnp.sum(
            jnp.where(attend.live, mass, 0.0))).astype(jnp.int32))
        return _mm(x, weights["lm_head"]), cache

    def _passes(self, one_pass, carry):
        """``one_pass(t, carry)`` for ``t = 0 .. loops - 1``: ONE rolled
        loop, its counter a traced ``int32``."""
        import jax

        return jax.lax.fori_loop(0, self.loops, one_pass, carry)

    def _stack(self, one_layer, carry, layers):
        """``one_layer(l, layers[:, l], carry)`` for ``l = 0 ..
        num_layers - 1``: ONE rolled loop over the stacked weights."""
        import jax
        import jax.numpy as jnp

        return jax.lax.scan(
            lambda carry, xs: (one_layer(*xs, carry), None), carry,
            (jnp.arange(self.num_layers, dtype=jnp.int32), layers))[0]

    def _cache_layer(self, t, l):
        """Where pass ``t`` of layer ``l`` keeps its K and V."""
        return t * self.num_layers + l

    def _between(self, weights, x, t):
        """The stream after pass ``t``: the final norm, after EVERY
        pass."""
        return rms_norm(x, weights["norm_f"], self.rms_eps)

    def _out_norm(self, y, g):
        """The norm on a sub-block's OUTPUT, before the residual add."""
        return rms_norm(y, g, self.rms_eps)

    def _gate(self, weights, x):
        """``lambda`` of the normed rows ``x``: the exit gate, one
        number a row."""
        import jax

        return jax.nn.sigmoid(x @ weights["exit_w"] + weights["exit_b"])

    def _layer(self, c, lw, x, cache, cos, sin, attend):
        """One application of the layer ``lw`` to the rows ``x``, its K
        and V at cache layer ``c`` -> (x, cache)."""
        import jax
        import jax.numpy as jnp

        h = rms_norm(x, lw["norm_attn_in"], self.rms_eps)
        heads = (*x.shape[:-1], self.num_heads, self.head_dim)
        q = half_split_rotate(_mm_t(h, lw["wq"]).reshape(heads), cos, sin)
        k = half_split_rotate(_mm_t(h, lw["wk"]).reshape(heads), cos, sin)
        v = _mm(h, lw["wv"]).reshape(heads)
        ctx, cache = attend(c, q, k, v, cache)
        y = _mm(ctx.reshape(*x.shape[:-1], -1).astype(jnp.float32),
                lw["wo"])
        x = x + self._out_norm(y, lw["norm_attn_out"])
        with jax.named_scope(DENSE_SCOPE):
            h = rms_norm(x, lw["norm_ffn_in"], self.rms_eps)
            y = _mm(jax.nn.silu(_mm(h, lw["ffn_w_gate"]))
                    * _mm(h, lw["ffn_w_up"]), lw["ffn_w_down"])
        return x + self._out_norm(y, lw["norm_ffn_out"]), cache
