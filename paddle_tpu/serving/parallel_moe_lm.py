"""A served model whose block is PARALLEL: one LayerNorm (mean
subtracted, no bias) feeds the attention AND the feed-forward, and both
join the residual stream in one add.  Its attention layers are of two
kinds: layers that attend a sliding window, with a rotary term on
adjacent lane pairs over the whole head, and layers that attend every
position with NO positional term; both with grouped-query heads.  The
feed-forward is a mixture of experts of which this chip HOLDS A SHARE
(``ops/moe_ops.py`` ``moe_share_*``) beside several shared experts whose
outputs are AVERAGED; the logits are the final norm times the TRANSPOSED
input embedding (there is no head matrix).  The architecture is Command
A+'s (``cohere2_moe``); the equations are in the reference's docstring
(``benchmark/reference/parallel_moe_lm.py``, a copy in ``tests/``),
which this file is tested against and shares no code with.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring (the matmul feed, the rotary pairing and the routed share are
``blocks.py``'s): ``forward(weights, tokens, positions, cache,
attend)``.  What it declares: ``layer_kinds``
(``"attention"`` or ``"window"`` a layer), ``num_kv_heads`` /
``window_kv_heads`` (the same count here), ``head_dim`` / ``v_head_dim``
(the same width), ``window``, ``tallies``.  The engine decides where
each kind's K/V live (all positions in pages; a ring of the last
``window``) and what is attended.

Precision as served: weights (and K/V pages) in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, the norms,
the rotary term, router scores and softmax in float32.
"""
from __future__ import annotations

import math
from typing import Sequence

from ..ops import moe_ops
from .blocks import (MOE_SHARED_SCOPE, ROPE_SCOPE, _mm, adjacent_angles,
                     adjacent_rotate_negated_partner, dense_from, held_ids,
                     read_rows, route_share, share_ffn, step_tallies)


class ParallelMoELM:
    """Sized by constructor arguments; ``layer_kinds`` is the pattern
    (Command A+: three ``"window"`` to one ``"attention"``).
    ``held_experts`` are the routed-expert ids this chip holds of
    ``num_experts``; the router keeps its full width and has no
    correction bias.  ``shared_experts`` experts of ``shared_dim`` each
    run on every row and their mean joins the routed sum."""

    def __init__(self, vocab_size: int, d_model: int,
                 layer_kinds: Sequence[str], num_heads: int,
                 num_kv_heads: int, head_dim: int, rope_theta: float,
                 window: int, num_experts: int, top_k: int,
                 held_experts: Sequence[int], expert_dim: int,
                 shared_experts: int, shared_dim: int,
                 norm_eps: float = 1e-5, logit_scale: float = 1.0,
                 dtype="bfloat16", max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.layer_kinds = tuple(layer_kinds)
        bad = set(self.layer_kinds) - {"attention", "window"}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad) or 'nothing'}")
        self.num_layers = len(self.layer_kinds)
        self.num_heads = int(num_heads)
        self.num_kv_heads = self.window_kv_heads = int(num_kv_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        self.head_dim = self.v_head_dim = int(head_dim)
        if self.head_dim % 2:
            raise ValueError("head_dim must be even: lanes turn in pairs")
        self.rope_theta = float(rope_theta)
        # the kinds of layer whose q and k turn with the position: a
        # layer that attends everything has no positional term at all
        self.rotary_kinds = ("window",)
        self.window = int(window)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held_experts = held_ids(held_experts, self.num_experts)
        self.expert_dim = int(expert_dim)
        self.shared_experts = int(shared_experts)
        self.shared_dim = int(shared_dim)
        self.norm_eps = float(norm_eps)
        self.logit_scale = float(logit_scale)
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
        """Seeded weights at variance-preserving scales.  The shared
        experts lie side by side: expert s in columns ``s*F:(s+1)*F`` of
        ``shared_w_gate`` / ``shared_w_up`` and rows ``s*F:(s+1)*F`` of
        ``shared_w_down``, as the held routed experts do in theirs."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, e, f = self.d_model, self.num_experts, self.expert_dim
        hq = self.num_heads * self.head_dim
        hkv = self.num_kv_heads * self.head_dim
        nf = len(self.held_experts) * f
        sf = self.shared_experts * self.shared_dim
        keys = iter(jax.random.split(key, 2 + 12 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((self.vocab_size, dm), 1.0),
             "norm_f": ones(dm), "layers": []}
        for _ in self.layer_kinds:
            w["layers"].append(dict(
                norm=ones(dm), wq=dense((dm, hq)), wk=dense((dm, hkv)),
                wv=dense((dm, hkv)), wo=dense((hq, dm)),
                moe_router=dense((dm, e), dtype=jnp.float32),
                moe_w_gate=dense((dm, nf)), moe_w_up=dense((dm, nf)),
                moe_w_down=dense((nf, dm), 1.0 / math.sqrt(f)),
                shared_w_gate=dense((dm, sf)), shared_w_up=dense((dm, sf)),
                shared_w_down=dense((sf, dm),
                                    1.0 / math.sqrt(self.shared_dim))))
        return w

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) at their absolute ``positions`` -> ``(logits [..., V],
        cache)``.  See the module header for what ``attend`` carries."""
        import jax.numpy as jnp

        x = weights["tok_emb"][tokens].astype(jnp.float32)
        for l, lw in enumerate(weights["layers"]):
            h = self._norm(x, lw["norm"])
            a, cache = self._attention(l, lw, h, positions, cache, attend)
            # ONE add: attention and the feed-forward read the same h
            x = x + a + self._feed_forward(lw, h, attend)
        return self._head(weights, read_rows(x, attend)), cache

    def _attention(self, l, lw, h, positions, cache, attend):
        """Layer ``l``'s attention of the normed rows ``h`` -> (its
        output through ``wo``, cache)."""
        import jax
        import jax.numpy as jnp

        lead = h.shape[:-1]
        q = _mm(h, lw["wq"]).reshape(*lead, self.num_heads, self.head_dim)
        k = _mm(h, lw["wk"]).reshape(*lead, self.num_kv_heads,
                                     self.head_dim)
        v = _mm(h, lw["wv"]).reshape(*lead, self.num_kv_heads,
                                     self.head_dim)
        if self.layer_kinds[l] in self.rotary_kinds:
            with jax.named_scope(ROPE_SCOPE):
                turn = self._rotary(positions)
                q, k = self._rotate(q, *turn), self._rotate(k, *turn)
        ctx, cache = attend(l, q, k, v, cache)
        return _mm(ctx.reshape(*lead, -1).astype(jnp.float32),
                   lw["wo"]), cache

    def _feed_forward(self, lw, h, attend):
        """The held experts' part of the routed sum plus the MEAN of
        the shared experts, for the normed rows ``h``."""
        import jax
        import jax.numpy as jnp

        local = route_share(
            h, dict(lw, moe_router_bias=jnp.zeros((self.num_experts,),
                                                  jnp.float32)),
            attend, self.top_k, self.held_experts)
        with jax.named_scope(MOE_SHARED_SCOPE):
            # the concatenated down-projection SUMS the shared experts
            shared = _mm(jax.nn.silu(_mm(h, lw["shared_w_gate"]))
                         * _mm(h, lw["shared_w_up"]),
                         lw["shared_w_down"]) / self.shared_experts
        return share_ffn(self, h, lw, local, attend) + shared

    def _head(self, weights, x):
        """The final norm times the TRANSPOSED input embedding."""
        import jax.numpy as jnp

        emb = weights["tok_emb"]
        return self.logit_scale * jnp.einsum(
            "...d,vd->...v", self._norm(x, weights["norm_f"]).astype(
                emb.dtype), emb, preferred_element_type=jnp.float32)

    def _norm(self, x, g):
        """LayerNorm without a bias: the mean goes, then the scale."""
        import jax
        import jax.numpy as jnp

        x = x - jnp.mean(x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.norm_eps) * g

    def _rotary(self, positions):
        """(cos, sin) ``[..., 1, head_dim]`` of the rotary angles at
        ``positions [...]``, each pair's angle on both of its lanes."""
        return adjacent_angles(positions, self.rope_theta, self.head_dim)

    # on ADJACENT lanes of every head: lanes ``(2j, 2j + 1)`` turn together
    _rotate = staticmethod(adjacent_rotate_negated_partner)
