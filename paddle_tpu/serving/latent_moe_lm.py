"""A served model with LATENT attention: what a layer caches of a
position is one row, ``[c | rot(k_r)]`` (a compressed latent of
``kv_rank`` lanes that every head's keys and values are projections of,
and one rotary key of ``rope_dim`` lanes that every head shares), in
place of per-head K and V.  The queries come through a low-rank
bottleneck too (``q_rank``); a YaRN-scaled rotary term on DeepSeek's
interleaved pairing; a dense SwiGLU feed-forward in the leading layers
and, in the others, a mixture of experts of which this chip HOLDS A
SHARE (``ops/moe_ops.py`` ``moe_share_*``: sigmoid scores, the top-k
taken by score + a correction bias, the weights the plain scores
normalised and scaled by ``routed_scale``) beside one shared expert that
every row takes unweighted.  The architecture is Kimi-K2.5's (the
DeepSeek-V3 block); the equations are in the reference's docstring
(``benchmark/reference/latent_moe_lm.py``), which this file is tested
against and shares no code with.

**Two forms of one attention.**  With ``W_UK [H, nope, rank]`` and
``W_UV [H, rank, v]`` the two halves of the published ``kv_b_proj`` (a
one-time split: they ARE the weights, there is no second copy), head
h's keys are ``[c W_UK[h]^T | rot(k_r)]`` and its values ``c W_UV[h]``.

* *Expanded* (a whole prompt, ``attend.prompt``): build the ``H`` heads
  of K (``nope + rope`` lanes) and V from the prompt's own latents and
  attend as any model with a head a K/V head does; the rows to cache go
  to ``attend`` as ``keep=``.  ``2 (nope + rope + v)`` FLOP a key a
  head.  The head runs over ``attend.read_row`` alone.
* *Absorbed* (the step): carry the query into the latent's space,
  ``q_lat = q_nope W_UK[h]``, attend the cached rows as they lie
  (scores over all ``rank + rope`` lanes, values the first ``rank``) and
  carry the context out, ``ctx = ctx_lat W_UV[h]``: the same numbers,
  ``2 (2 rank + rope)`` FLOP a key a head, and no K or V is ever built
  for a cached position.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring; the two forms are ``mixers.LatentMixer``'s, the feed-forward,
the head and the rotary pairing ``blocks.py``'s.  What it declares:
``layer_kinds`` (all ``"attention"``: every position in pages),
``num_kv_heads`` 1,
``head_dim`` (the cached row, ``kv_rank + rope_dim``), ``v_head_dim``
(``kv_rank``) and ``values_in_keys`` (the values are the row's leading
lanes: the cache keeps no V pool), ``prompt_heads`` (the expanded form's
K/V head count, K and V lanes), ``tallies``.

Precision as served: weights and cached rows in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, the three
norms, the rotary term, router scores, and softmax in float32.
"""
from __future__ import annotations

from typing import Sequence

from ..ops import moe_ops
from .blocks import (OUT_PROJ_SCOPE, ROPE_SCOPE, _mm,
                     adjacent_angles_signed_sine, adjacent_rotate_signed_sine,
                     dense_from, feed_forward, ffn_weights, head_logits,
                     held_ids, rms_norm, step_tallies, yarn_frequencies,
                     yarn_mscale)
from .mixers import LatentMixer


class LatentMoELM(LatentMixer):
    """Sized by constructor arguments.  The first ``dense_layers`` of
    ``num_layers`` layers have a dense feed-forward of ``dense_dim``, the
    others the routed experts (``held_experts`` of ``num_experts``, the
    router at its full width) and one shared expert of ``shared_dim``.
    Heads: ``num_heads`` of ``nope_dim + rope_dim`` query lanes and
    ``v_dim`` value lanes over a latent of ``kv_rank``; ``q_rank`` is the
    query's bottleneck.  ``rope_*`` are the published ``rope_scaling``
    keys."""

    def __init__(self, vocab_size: int, d_model: int, num_layers: int,
                 dense_layers: int, num_heads: int, q_rank: int,
                 kv_rank: int, nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float, rope_factor: float, rope_orig_len: int,
                 rope_beta_fast: float, rope_beta_slow: float,
                 rope_mscale: float, rope_mscale_all_dim: float,
                 dense_dim: int, num_experts: int, top_k: int,
                 held_experts: Sequence[int], expert_dim: int,
                 shared_dim: int, routed_scale: float,
                 rms_eps: float = 1e-5, dtype="bfloat16",
                 max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.num_layers = int(num_layers)
        self.dense_layers = int(dense_layers)
        self.layer_kinds = ("attention",) * self.num_layers
        self.num_heads = int(num_heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim = int(v_dim)
        if self.rope_dim % 2:
            raise ValueError("rope_dim must be even")
        self.latent_declares()
        self.rope_theta = float(rope_theta)
        self.rope_freqs = tuple(yarn_frequencies(
            self.rope_dim, self.rope_theta, float(rope_factor),
            int(rope_orig_len), float(rope_beta_fast),
            float(rope_beta_slow)))
        # cos and sin carry mscale / mscale_all_dim (1 where they are
        # equal); the softmax's scale carries mscale_all_dim's square
        self.rope_mscale = yarn_mscale(rope_factor, rope_mscale) \
            / yarn_mscale(rope_factor, rope_mscale_all_dim)
        self.softmax_scale = (self.nope_dim + self.rope_dim) ** -0.5 \
            * yarn_mscale(rope_factor, rope_mscale_all_dim) ** 2
        self.dense_dim = int(dense_dim)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held_experts = held_ids(held_experts, self.num_experts)
        self.expert_dim, self.shared_dim = int(expert_dim), int(shared_dim)
        self.routed_scale = float(routed_scale)
        self.rms_eps = float(rms_eps)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)     # no positional table
        # the counters forward adds to through attend.tally: a joint
        # step's, and those only a whole-prompt prefill reads back
        # ``HIT_TALLIES``: read back by a step that takes the hit form
        # (``step_tallies``), counted and dropped anywhere else
        self.tallies = ("moe_local_assignments", "moe_experts_hit") \
            + moe_ops.HIT_TALLIES
        self.prefill_tallies = moe_ops.GROUPED_TALLIES

    step_tallies = step_tallies

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales; the router's
        correction bias from N(0, 0.1^2): small beside a score in (0, 1)
        and not zero, so that choosing by score + bias and weighing by
        the score are told apart."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v = self.d_model, self.vocab_size
        keys = iter(jax.random.split(key, 4 + 16 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((v, dm), 1.0), "lm_head": dense((dm, v)),
             "norm_f": ones(dm), "layers": []}
        for l in range(self.num_layers):
            lw = {"norm1": ones(dm), "norm2": ones(dm),
                  **self.latent_weights(dense, ones),
                  **ffn_weights(self, l, dense)}
            w["layers"].append(lw)
        return w

    def _query_weights(self, dense, ones):
        return {"q_norm": ones(self.q_rank),
                "wq_a": dense((self.d_model, self.q_rank)),
                "wq_b": dense((self.q_rank, self.num_heads
                               * (self.nope_dim + self.rope_dim)))}

    def _queries(self, lw, h):
        """Through the bottleneck: q_a, its norm, q_b."""
        return _mm(rms_norm(_mm(h, lw["wq_a"]), lw["q_norm"],
                            self.rms_eps), lw["wq_b"])

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) at their absolute ``positions`` -> ``(logits [..., V],
        cache)``.  See the module header for what ``attend`` carries."""
        import jax
        import jax.numpy as jnp

        w = weights
        x = w["tok_emb"][tokens].astype(jnp.float32)
        with jax.named_scope(ROPE_SCOPE):
            turn = self._rotary(positions)
        for l in range(self.num_layers):
            lw = w["layers"][l]
            ctx, cache = self._attention(
                lw, l, rms_norm(x, lw["norm1"], self.rms_eps), turn, cache,
                attend)
            with jax.named_scope(OUT_PROJ_SCOPE):
                x = x + _mm(ctx.reshape(*ctx.shape[:-2], -1), lw["wo"])
            x = feed_forward(self, l, lw, x, attend)
        return head_logits(self, w, x, attend), cache

    def _rotary(self, positions):
        """(cos, sin) ``[..., 1, rope_dim]`` at ``positions [...]``:
        ``_rotate``'s two factors, the sine signed."""
        return adjacent_angles_signed_sine(positions, self.rope_freqs,
                                           self.rope_mscale)

    # the reference de-interleaves first (evens, then odds) and so names
    # the same 64 numbers in another order; queries and the cached key
    # keep the lanes where they lie, and a score is a sum over lanes
    _rotate = staticmethod(adjacent_rotate_signed_sine)
