"""A served model whose layers are of two kinds that keep DIFFERENT
things of a request in ONE cache: gated-delta-rule linear attention with
a decay a channel (``"recurrent"``: a fixed-size float32 state a slot in
slabs, no keys) and position-free LATENT attention (``"attention"``: one
row ``[c | k_r]`` a position in pages, no V pool), several
recurrent layers to one latent layer; a dense SwiGLU
feed-forward in the leading layers (whose MIXER is a recurrent one) and,
in the others, a mixture of experts of which this chip HOLDS A SHARE
under a scaled, bias-corrected sigmoid router beside one shared expert.
The architecture is Kimi-Linear-48B-A3B's; the equations are in the
reference's docstring (``benchmark/reference/linear_latent_lm.py``, a
copy in ``tests/``), which this file is tested against and shares no
code with.

Nothing of either mixer is written here.  The recurrent layers are
``mixers.KDAMixer``'s (the rule's token form in the step,
``ops/pallas_kda_update.py``, and its chunk (WY) form on the matrix unit
over a whole prompt, ``ops/pallas_kda_chunk.py``, a group of 64-token
chunks a call) with the step's range ``beta_scale`` 1:
the published config carries no ``kda_allow_neg_eigval``.  The latent
layers are ``mixers.LatentMixer``'s two forms of one attention
(absorbed in the step, expanded in a prompt) with the queries straight
from the hidden row (no bottleneck) and ``turn`` None (``_rotary``):
``mla_use_nope``, no positional term anywhere, so the 64 lanes the
sibling turns are lanes like the others and no rotary work is traced.
The feed-forward and the head are ``blocks.py``'s.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring.  What it declares: ``layer_kinds``, ``recurrent_state``,
``num_kv_heads`` 1, ``head_dim`` (the cached row), ``v_head_dim``,
``values_in_keys``, ``prompt_heads``, ``dense_layers``, ``tallies``,
``prefill_tallies``, ``step_tallies``, ``prefill_chunks_per_call``.

Precision as served: weights and cached rows in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, norms, router
scores, softmax, the gates and THE RECURRENT STATE in float32.
"""
from __future__ import annotations

from typing import Sequence

from ..ops import moe_ops
from .blocks import (OUT_PROJ_SCOPE, _mm, dense_from, feed_forward,
                     ffn_weights, head_logits, held_ids, rms_norm,
                     step_tallies)
from .mixers import KDAMixer, LatentMixer


class LinearLatentLM(KDAMixer, LatentMixer):
    """Sized by constructor arguments.  ``layer_kinds`` is the pattern
    (Kimi-Linear: three ``"recurrent"`` then one ``"attention"`` a
    period; the published lists name the layers one by one); the
    first ``dense_layers`` layers have a dense feed-forward of
    ``dense_dim``, the others ``held_experts`` of ``num_experts`` (the
    router at its full width) and one shared expert of ``shared_dim``.
    ``lin_*``, ``conv_kernel``, ``gate_rank``: the recurrent layers';
    ``num_heads`` of ``nope_dim + rope_dim`` query lanes and ``v_dim``
    value lanes over a latent of ``kv_rank``: the latent layers'."""

    beta_scale = 1.0

    def __init__(self, vocab_size: int, d_model: int,
                 layer_kinds: Sequence[str], dense_layers: int,
                 lin_heads: int,
                 lin_head_dim: int, conv_kernel: int, gate_rank: int,
                 num_heads: int, kv_rank: int, nope_dim: int,
                 rope_dim: int, v_dim: int, dense_dim: int,
                 num_experts: int, top_k: int,
                 held_experts: Sequence[int], expert_dim: int,
                 shared_dim: int, routed_scale: float,
                 rms_eps: float = 1e-5, dtype="bfloat16",
                 max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.layer_kinds = tuple(layer_kinds)
        bad = set(self.layer_kinds) - {"attention", "recurrent"}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad) or 'nothing'}")
        self.num_layers = len(self.layer_kinds)
        self.dense_layers = int(dense_layers)
        self.lin_heads, self.lin_head_dim = int(lin_heads), int(lin_head_dim)
        self.conv_kernel, self.gate_rank = int(conv_kernel), int(gate_rank)
        self.num_heads, self.kv_rank = int(num_heads), int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim = int(v_dim)
        self.latent_declares()
        self.softmax_scale = (self.nope_dim + self.rope_dim) ** -0.5
        self.dense_dim = int(dense_dim)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held_experts = held_ids(held_experts, self.num_experts)
        self.expert_dim, self.shared_dim = int(expert_dim), int(shared_dim)
        self.routed_scale = float(routed_scale)
        self.rms_eps = float(rms_eps)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)     # no positional table
        # the counters forward adds to through attend.tally, as the two
        # siblings declare them: ``kda_kernel_rows`` (live rows x
        # recurrent layers a step's kernel calls updated), the routing's,
        # the hit form's; a whole-prompt prefill reads back the grouped
        # experts' (and the engine's own scan counters)
        self.tallies = ("moe_local_assignments", "moe_experts_hit",
                        "kda_kernel_rows") + moe_ops.HIT_TALLIES
        self.prefill_tallies = moe_ops.GROUPED_TALLIES
        self.recurrent_state = self.kda_state()

    step_tallies = step_tallies

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales: the decay's as
        ``KDAMixer.kda_weights`` sets them, the router's correction bias
        from N(0, 0.1^2) (``blocks.ffn_weights``)."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v = self.d_model, self.vocab_size
        keys = iter(jax.random.split(key, 4 + 24 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((v, dm), 1.0), "lm_head": dense((dm, v)),
             "norm_f": ones(dm), "layers": []}
        for l, kind in enumerate(self.layer_kinds):
            mixer = self.latent_weights(dense, ones) \
                if kind == "attention" \
                else self.kda_weights(dense, keys, ones)
            w["layers"].append({"norm1": ones(dm), "norm2": ones(dm),
                                **mixer, **ffn_weights(self, l, dense)})
        return w

    def _query_weights(self, dense, ones):
        return {"wq": dense((self.d_model, self.num_heads
                             * (self.nope_dim + self.rope_dim)))}

    def _queries(self, lw, h):
        """Straight from the hidden row: ``q_lora_rank`` null."""
        return _mm(h, lw["wq"])

    def _rotary(self, positions):
        """No positional term: nothing for ``_attention`` to turn by."""
        return None

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) -> ``(logits [..., V], cache)``; ``positions`` are not
        read (no positional term).  See the module header for what each
        mixer asks of ``attend``."""
        import jax
        import jax.numpy as jnp

        w = weights
        x = w["tok_emb"][tokens].astype(jnp.float32)
        turn = self._rotary(positions)
        for l, kind in enumerate(self.layer_kinds):
            lw = w["layers"][l]
            h = rms_norm(x, lw["norm1"], self.rms_eps)
            if kind == "recurrent":
                y, cache = self.kda_mixer(l, lw, h, cache, attend)
            else:
                ctx, cache = self._attention(lw, l, h, turn, cache, attend)
                with jax.named_scope(OUT_PROJ_SCOPE):
                    y = _mm(ctx.reshape(*ctx.shape[:-2], -1), lw["wo"])
            x = feed_forward(self, l, lw, x + y, attend)
        return head_logits(self, w, x, attend), cache
