"""A served model whose attention reads only the cached positions a
learned INDEXER selects: grouped-query heads with an RMSNorm on every q
and k head and half-split rotary on all lanes; beside K and V every layer
caches ONE small index key a position, a query scores all live keys
(``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``) and the softmax runs
over the ``index_topk`` positions of largest score alone (every live
position while there are no more than that); then a mixture of experts
of which this chip HOLDS A SHARE (``ops/moe_ops.py`` ``moe_share_*``: a
softmax router over all experts, the top-k renormalised) with no shared
expert beside it; an untied head.  The architecture is the language
model of Keye-VL-2.0-30B-A3B (a Qwen3-MoE-shaped block with DeepSeek's
lightning indexer); the equations are in the reference's docstring
(``benchmark/reference/indexed_moe_lm.py``), which this file is tested
against and shares no code with.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring; the attention and the indexer's two forms are
``mixers.IndexedMixer``'s, the routed share, the head and the rotary
pairing ``blocks.py``'s.  What it declares: ``layer_kinds`` (all
``"attention"``: every position in pages), ``num_kv_heads``,
``index_dim`` (the engine keeps a third pool of that many lanes a
position behind the K/V pools' page ids), ``index_heads``,
``index_topk``, ``index_block`` (query rows a block of the prompt's
form), ``tallies`` / ``step_tallies`` / ``prefill_tallies``.  In a
whole-prompt prefill the head runs over ``attend.read_row`` alone.

Precision as served: weights, K/V pages and index keys in ``dtype``
(bfloat16), every matmul accumulating in float32; the residual stream,
the norms (RMS and the index key's LayerNorm), the rotary term, router
scores, the index scores and their selection, and softmax in float32.
"""
from __future__ import annotations

import math
from typing import Sequence

from ..ops import moe_ops
from .blocks import (dense_from, head_logits, held_ids, rms_norm,
                     route_share, share_ffn, step_tallies)
from .mixers import IndexedMixer


class IndexedMoELM(IndexedMixer):
    """Sized by constructor arguments.  Every one of ``num_layers``
    layers is indexed attention and the routed experts (``held_experts``
    of ``num_experts``, the router at its full width, ``top_k`` a
    token)."""

    def __init__(self, vocab_size: int, d_model: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 index_heads: int, index_dim: int, index_topk: int,
                 num_experts: int, top_k: int,
                 held_experts: Sequence[int], expert_dim: int,
                 index_block: int = 512, rope_theta: float = 1e7,
                 rms_eps: float = 1e-6, dtype="bfloat16",
                 max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.num_layers = int(num_layers)
        self.layer_kinds = ("attention",) * self.num_layers
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        self.head_dim = int(head_dim)
        self.index_heads, self.index_dim = int(index_heads), int(index_dim)
        if self.head_dim % 2 or self.index_dim % 2:
            raise ValueError("head_dim and index_dim must be even: every "
                             "lane turns")
        self.index_topk, self.index_block = int(index_topk), int(index_block)
        if self.index_topk < 1 or self.index_block < 1:
            raise ValueError("index_topk and index_block must be positive")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held_experts = held_ids(held_experts, self.num_experts)
        self.expert_dim = int(expert_dim)
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)     # no positional table
        # the counters forward adds to through attend.tally: a joint
        # step's (``HIT_TALLIES`` read back only by a step that takes
        # the hit form, ``step_tallies``), and those only a whole-prompt
        # prefill reads back
        self.tallies = ("moe_local_assignments", "moe_experts_hit") \
            + moe_ops.HIT_TALLIES
        self.prefill_tallies = moe_ops.GROUPED_TALLIES

    step_tallies = step_tallies

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales; the router has
        no bias (the zeros ``route_share`` reads are the published
        absence of one)."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v = self.d_model, self.vocab_size
        e, f = self.num_experts, self.expert_dim
        nf = len(self.held_experts) * f
        keys = iter(jax.random.split(key, 4 + 16 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((v, dm), 1.0), "lm_head": dense((dm, v)),
             "norm_f": ones(dm), "layers": []}
        for _ in range(self.num_layers):
            w["layers"].append({
                "norm1": ones(dm), "norm2": ones(dm),
                **self.indexed_weights(dense, ones, keys),
                "moe_router": dense((dm, e), dtype=jnp.float32),
                "moe_router_bias": jnp.zeros((e,), jnp.float32),
                "moe_w_gate": dense((dm, nf)), "moe_w_up": dense((dm, nf)),
                "moe_w_down": dense((nf, dm), 1.0 / math.sqrt(f))})
        return w

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) at their absolute ``positions`` -> ``(logits [..., V],
        cache)``; a whole-prompt prefill's are ``[1, V]``, the row
        ``attend.read_row``."""
        import jax.numpy as jnp

        w = weights
        x = w["tok_emb"][tokens].astype(jnp.float32)
        for l in range(self.num_layers):
            lw = w["layers"][l]
            y, cache = self._attention(
                lw, l, rms_norm(x, lw["norm1"], self.rms_eps), positions,
                cache, attend)
            x = x + y
            h = rms_norm(x, lw["norm2"], self.rms_eps)
            local = route_share(h, lw, attend, self.top_k,
                                self.held_experts, scoring="softmax")
            x = x + share_ffn(self, h, lw, local, attend)
        return head_logits(self, w, x, attend), cache
