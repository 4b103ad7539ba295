"""A served model whose layers are of two kinds: softmax attention with
grouped-query heads and an output gate, and gated-delta-rule linear
attention (a decay a channel, a short causal convolution) that keeps a
fixed-size recurrent state a request instead of keys; every layer's
feed-forward is a mixture of experts of which this chip HOLDS A SHARE
(``ops/moe_ops.py`` ``moe_share_*``) beside a shared expert.  The
architecture is Solar-Open2-250B's; the equations are in the reference's
docstring (``benchmark/reference/hybrid_moe_lm.py``, a copy in
``tests/``), which this file is tested against and shares no code with.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring, like ``transformer_lm.py``: ``forward(weights, tokens,
positions, cache, attend)``.  What it declares beyond the reference
model's attributes: ``layer_kinds`` (``"attention"`` or ``"recurrent"`` a
layer), ``num_kv_heads``, ``recurrent_state`` (one slot's state of one
recurrent layer, ``{name: (shape, dtype)}``), ``tallies`` (the counters
``forward`` adds to, by name).  What it asks of ``attend``
beyond the call: ``attend.recur`` runs a recurrent layer's one-token
update over the rows' state, ``attend.live`` masks dead and padding rows
out of the routing, ``attend.tally`` and ``attend.record`` take the
routing counts and the chosen expert ids.

The recurrent layers are ``mixers.KDAMixer``'s (the rule's token form in
the step, its chunk form over a whole prompt where the kernels take the
state's shape; that file's header has the forms); the matmul feed, the
norm and the routed share are ``blocks.py``'s.

Precision as served: weights (and K/V pages) in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, norms, router
scores, softmax, the gates and THE RECURRENT STATE in float32.
"""
from __future__ import annotations

import math
from typing import Sequence

from ..ops import moe_ops
from .blocks import (MOE_SHARED_SCOPE, _mm, dense_from, head_logits,
                     held_ids, rms_norm, route_share, share_ffn, step_tallies)
from .mixers import KDAMixer


class HybridMoELM(KDAMixer):
    """Sized by constructor arguments; ``layer_kinds`` is the pattern
    (Solar-Open2: one ``"attention"`` then three ``"recurrent"`` a
    period).  ``held_experts`` are the routed-expert ids this chip holds
    of ``num_experts``; the router keeps its full width."""

    def __init__(self, vocab_size: int, d_model: int,
                 layer_kinds: Sequence[str], num_heads: int,
                 num_kv_heads: int, head_dim: int, lin_heads: int,
                 lin_head_dim: int, conv_kernel: int, gate_rank: int,
                 num_experts: int, top_k: int,
                 held_experts: Sequence[int], expert_dim: int,
                 shared_dim: int, rms_eps: float = 1e-5,
                 dtype="bfloat16", max_seq_len: int = 1 << 20):
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
        self.lin_heads, self.lin_head_dim = int(lin_heads), int(lin_head_dim)
        self.conv_kernel, self.gate_rank = int(conv_kernel), int(gate_rank)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held_experts = held_ids(held_experts, self.num_experts)
        self.expert_dim, self.shared_dim = int(expert_dim), int(shared_dim)
        self.rms_eps = float(rms_eps)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)     # no positional table
        # the counters forward adds to through attend.tally: a joint
        # step's, and those only a whole-prompt prefill reads back
        # ``kda_kernel_rows``: live rows x recurrent layers whose state a
        # step's kernel calls updated (0 where the XLA form serves)
        # ``HIT_TALLIES``: read back by a step that takes the hit form
        # (``step_tallies``), counted and dropped anywhere else
        self.tallies = ("moe_local_assignments", "moe_experts_hit",
                        "kda_kernel_rows") + moe_ops.HIT_TALLIES
        self.prefill_tallies = moe_ops.GROUPED_TALLIES
        self.recurrent_state = self.kda_state()

    step_tallies = step_tallies

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales
        (``kda_weights`` has the decay's)."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v = self.d_model, self.vocab_size
        hq = self.num_heads * self.head_dim
        hkv = self.num_kv_heads * self.head_dim
        e, f = self.num_experts, self.expert_dim
        nf = len(self.held_experts) * f
        keys = iter(jax.random.split(key, 4 + 24 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((v, dm), 1.0), "lm_head": dense((dm, v)),
             "norm_f": ones(dm), "layers": []}
        for kind in self.layer_kinds:
            lw = {"norm1": ones(dm), "norm2": ones(dm)}
            if kind == "attention":
                lw.update(wq=dense((dm, hq)), wk=dense((dm, hkv)),
                          wv=dense((dm, hkv)), wg=dense((dm, hq)),
                          wo=dense((hq, dm)))
            else:
                lw.update(self.kda_weights(dense, keys, ones))
            lw.update(
                moe_router=dense((dm, e), dtype=jnp.float32),
                moe_router_bias=jnp.zeros((e,), jnp.float32),
                moe_w_gate=dense((dm, nf)), moe_w_up=dense((dm, nf)),
                moe_w_down=dense((nf, dm), 1.0 / math.sqrt(f)),
                shared_w_gate=dense((dm, self.shared_dim)),
                shared_w_up=dense((dm, self.shared_dim)),
                shared_w_down=dense((self.shared_dim, dm)))
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

        w = weights
        x = w["tok_emb"][tokens].astype(jnp.float32)
        lead = x.shape[:-1]
        for l, kind in enumerate(self.layer_kinds):
            lw = w["layers"][l]
            h = rms_norm(x, lw["norm1"], self.rms_eps)
            if kind == "attention":
                q = _mm(h, lw["wq"]).reshape(*lead, self.num_heads,
                                             self.head_dim)
                k = _mm(h, lw["wk"]).reshape(*lead, self.num_kv_heads,
                                             self.head_dim)
                v = _mm(h, lw["wv"]).reshape(*lead, self.num_kv_heads,
                                             self.head_dim)
                ctx, cache = attend(l, q, k, v, cache)
                y = _mm(ctx.reshape(*lead, -1).astype(jnp.float32)
                        * jax.nn.sigmoid(_mm(h, lw["wg"])), lw["wo"])
            else:
                y, cache = self.kda_mixer(l, lw, h, cache, attend)
            x = x + y
            h = rms_norm(x, lw["norm2"], self.rms_eps)
            local = route_share(h, lw, attend, self.top_k,
                                self.held_experts)
            with jax.named_scope(MOE_SHARED_SCOPE):
                shared = _mm(jax.nn.silu(_mm(h, lw["shared_w_gate"]))
                             * _mm(h, lw["shared_w_up"]),
                             lw["shared_w_down"])
            x = x + share_ffn(self, h, lw, local, attend) + shared
        # every row's logits, or a prompt's ``attend.read_row`` alone
        return head_logits(self, w, x, attend), cache
