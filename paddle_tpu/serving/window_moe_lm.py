"""A served model whose attention layers are of two kinds: layers that
attend every position and layers that attend a sliding window with a
learned sink logit a head, each kind with its own count of K/V heads; K
heads wider than V heads; a rotary term on the leading lanes of every
head, at one base a kind; a dense SwiGLU feed-forward in the leading
layers and, in the others, a mixture of experts of which this chip HOLDS
A SHARE (``ops/moe_ops.py`` ``moe_share_*``) with no shared expert
beside it.  The architecture is MiMo-V2.5's; the equations are in the
reference's docstring (``benchmark/reference/window_moe_lm.py``, a copy
in ``tests/``), which this file is tested against and shares no code
with.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring (the matmul feed, the norm, the rotary pairing and the routed
share are ``blocks.py``'s): ``forward(weights, tokens, positions, cache,
attend)``.
What it declares: ``layer_kinds`` (``"attention"`` or ``"window"`` a
layer), ``num_kv_heads`` / ``window_kv_heads``, ``head_dim`` (K) and
``v_head_dim``, ``window``, ``tallies``.  A window layer hands its
``sinks`` to ``attend``; the engine decides where each kind's K/V live
(all positions in pages; a ring of the last ``window``) and what is
attended.

Precision as served: weights (and K/V pages) in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, norms, the
rotary term, router scores, softmax and the sink in float32.
"""
from __future__ import annotations

import math
from typing import Sequence

from ..ops import moe_ops
from .blocks import (DENSE_SCOPE, ROPE_SCOPE, _mm, dense_from,
                     half_split_angles, half_split_rotate, head_logits,
                     held_ids, rms_norm, route_share, share_ffn, step_tallies)


class WindowMoELM:
    """Sized by constructor arguments; ``layer_kinds`` is the pattern
    (MiMo-V2.5: one ``"attention"`` to five ``"window"``), the first
    ``dense_layers`` layers have a dense feed-forward of ``dense_dim``,
    the others the routed experts.  ``held_experts`` are the
    routed-expert ids this chip holds of ``num_experts``; the router
    keeps its full width.  ``rotary_dim`` leading lanes of every q and
    k head turn with the position (pairs ``(j, j + rotary_dim / 2)``),
    at ``rope_theta`` in an attention layer and ``window_rope_theta`` in
    a window layer."""

    def __init__(self, vocab_size: int, d_model: int,
                 layer_kinds: Sequence[str], dense_layers: int,
                 num_heads: int, num_kv_heads: int, window_kv_heads: int,
                 head_dim: int, v_head_dim: int, rotary_dim: int,
                 rope_theta: float, window_rope_theta: float, window: int,
                 value_scale: float, dense_dim: int, num_experts: int,
                 top_k: int, held_experts: Sequence[int], expert_dim: int,
                 rms_eps: float = 1e-5, dtype="bfloat16",
                 max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.layer_kinds = tuple(layer_kinds)
        bad = set(self.layer_kinds) - {"attention", "window"}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad) or 'nothing'}")
        self.num_layers = len(self.layer_kinds)
        self.dense_layers = int(dense_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.window_kv_heads = int(window_kv_heads)
        if self.num_heads % self.num_kv_heads \
                or self.num_heads % self.window_kv_heads:
            raise ValueError("num_heads must be a multiple of both kinds' "
                             "K/V head counts")
        self.head_dim, self.v_head_dim = int(head_dim), int(v_head_dim)
        self.rotary_dim = int(rotary_dim)
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError("rotary_dim must be even and within head_dim")
        self.rope_theta = float(rope_theta)
        self.window_rope_theta = float(window_rope_theta)
        self.window = int(window)
        self.value_scale = float(value_scale)
        self.dense_dim = int(dense_dim)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held_experts = held_ids(held_experts, self.num_experts)
        self.expert_dim = int(expert_dim)
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

    def kv_heads(self, kind: str) -> int:
        return self.window_kv_heads if kind == "window" \
            else self.num_kv_heads

    step_tallies = step_tallies

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales; a window
        layer's sink logits from N(0, 1), so that they carry weight."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v = self.d_model, self.vocab_size
        e, f = self.num_experts, self.expert_dim
        nf = len(self.held_experts) * f
        keys = iter(jax.random.split(key, 4 + 12 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((v, dm), 1.0), "lm_head": dense((dm, v)),
             "norm_f": ones(dm), "layers": []}
        for l, kind in enumerate(self.layer_kinds):
            hkv = self.kv_heads(kind)
            lw = {"norm1": ones(dm), "norm2": ones(dm),
                  "wq": dense((dm, self.num_heads * self.head_dim)),
                  "wk": dense((dm, hkv * self.head_dim)),
                  "wv": dense((dm, hkv * self.v_head_dim)),
                  "wo": dense((self.num_heads * self.v_head_dim, dm))}
            if kind == "window":
                lw["sink"] = dense((self.num_heads,), 1.0, jnp.float32)
            if l < self.dense_layers:
                lw.update(ffn_w_gate=dense((dm, self.dense_dim)),
                          ffn_w_up=dense((dm, self.dense_dim)),
                          ffn_w_down=dense((self.dense_dim, dm)))
            else:
                lw.update(
                    moe_router=dense((dm, e), dtype=jnp.float32),
                    moe_router_bias=jnp.zeros((e,), jnp.float32),
                    moe_w_gate=dense((dm, nf)), moe_w_up=dense((dm, nf)),
                    moe_w_down=dense((nf, dm), 1.0 / math.sqrt(f)))
            w["layers"].append(lw)
        return w

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) at their absolute ``positions`` -> ``(logits [..., V],
        cache)``.  See the module header for what ``attend`` carries."""
        import jax
        import jax.numpy as jnp

        w = weights
        x = w["tok_emb"][tokens].astype(jnp.float32)
        lead = x.shape[:-1]
        for l, kind in enumerate(self.layer_kinds):
            lw = w["layers"][l]
            h = rms_norm(x, lw["norm1"], self.rms_eps)
            hkv = self.kv_heads(kind)
            q = _mm(h, lw["wq"]).reshape(*lead, self.num_heads,
                                         self.head_dim)
            k = _mm(h, lw["wk"]).reshape(*lead, hkv, self.head_dim)
            v = _mm(h, lw["wv"]).reshape(*lead, hkv, self.v_head_dim)
            with jax.named_scope(ROPE_SCOPE):
                turn = half_split_angles(
                    positions, self.window_rope_theta if kind == "window"
                    else self.rope_theta, self.rotary_dim)
                q, k = (half_split_rotate(q, *turn),
                        half_split_rotate(k, *turn))
            if kind == "window":
                ctx, cache = attend(l, q, k, v, cache, sinks=lw["sink"])
            else:
                ctx, cache = attend(l, q, k, v, cache)
            x = x + _mm(ctx.reshape(*lead, -1).astype(jnp.float32)
                        * self.value_scale, lw["wo"])
            h = rms_norm(x, lw["norm2"], self.rms_eps)
            if l < self.dense_layers:
                with jax.named_scope(DENSE_SCOPE):
                    x = x + _mm(jax.nn.silu(_mm(h, lw["ffn_w_gate"]))
                                * _mm(h, lw["ffn_w_up"]), lw["ffn_w_down"])
            else:
                local = route_share(h, lw, attend, self.top_k,
                                    self.held_experts)
                x = x + share_ffn(self, h, lw, local, attend)
        # every row's logits, or a prompt's ``attend.read_row`` alone
        return head_logits(self, w, x, attend), cache
