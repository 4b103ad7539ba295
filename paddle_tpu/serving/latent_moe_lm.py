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
docstring, like ``window_moe_lm.py`` (whose dense layer and
``held_experts`` plumbing it follows, and whose matmul, norm and routing
helpers it uses).  What it declares: ``layer_kinds`` (all
``"attention"``: every position in pages), ``num_kv_heads`` 1,
``head_dim`` (the cached row, ``kv_rank + rope_dim``), ``v_head_dim``
(``kv_rank``) and ``values_in_keys`` (the values are the row's leading
lanes: the cache keeps no V pool), ``prompt_heads`` (the expanded form's
K/V head count, K and V lanes), ``tallies``.

Precision as served: weights and cached rows in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, the three
norms, the rotary term, router scores, and softmax in float32.
"""
from __future__ import annotations

import math
from typing import Sequence

from ..ops import moe_ops
from .hybrid_moe_lm import (_mm, dense_from, held_ids, rms_norm,
                            route_share, share_ffn, step_tallies)
from .window_moe_lm import DENSE_SCOPE, ROPE_SCOPE

Q_PROJ_SCOPE = "latent_q_proj"          # q_a, its norm, q_b
KV_PROJ_SCOPE = "latent_kv_proj"        # kv_a and the latent's norm
ABSORB_Q_SCOPE = "latent_absorb_q"      # q_nope W_UK: into the row's space
ABSORB_V_SCOPE = "latent_absorb_v"      # ctx_lat W_UV: out of it
EXPAND_SCOPE = "latent_expand"          # a prompt's K and V from its rows
OUT_PROJ_SCOPE = "latent_out_proj"
SHARED_SCOPE = "shared_ffn"


def yarn_frequencies(rope_dim, theta, factor, orig_len, beta_fast,
                     beta_slow):
    """The ``rope_dim / 2`` rotary frequencies under YaRN (host floats):
    pair j turns at ``theta^(-2j/d)`` where it completes more than
    ``beta_fast`` turns over the original context, at a ``factor``-th of
    that where fewer than ``beta_slow``, and at a linear blend between
    (the DeepSeek-V3 reading of ``rope_scaling``)."""
    half = rope_dim // 2

    def turns_dim(turns):
        return rope_dim * math.log(orig_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), rope_dim - 1)
    out = []
    for j in range(half):
        f = theta ** (-2.0 * j / rope_dim)
        r = min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f * (1.0 - r) + f / factor * r)
    return out


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


class LatentMixer:
    """Latent attention of a model with ``num_heads`` heads of
    ``nope_dim + rope_dim`` query lanes and ``v_dim`` value lanes over a
    latent of ``kv_rank``, ``softmax_scale`` and ``rms_eps``: its
    weights, what the engine reads of it, and the two forms.  A model
    says how its queries are made (``_queries``, ``_query_weights``) and
    hands ``turn``, the rotary term's two factors, or None where its
    last ``rope_dim`` lanes carry no position (they are then lanes like
    the others: nothing turns them, not by the identity either).
    ``LatentMoELM``'s, and ``linear_latent_lm.py``'s."""

    def latent_declares(self):
        """What the engine reads: ONE cached row a position, all heads'."""
        self.num_kv_heads = 1
        self.head_dim = self.kv_rank + self.rope_dim
        self.v_head_dim = self.kv_rank
        self.values_in_keys = True
        self.prompt_heads = (self.num_heads, self.nope_dim + self.rope_dim,
                             self.v_dim)

    def latent_weights(self, dense, ones):
        """A latent layer's mixer weights, the queries' first."""
        h = self.num_heads
        up = 1.0 / math.sqrt(self.kv_rank)
        return {**self._query_weights(dense, ones),
                "kv_norm": ones(self.kv_rank),
                "wkv_a": dense((self.d_model, self.kv_rank + self.rope_dim)),
                # kv_b_proj, split once: head h's keys are c W_UK[h]^T,
                # its values c W_UV[h]
                "w_uk": dense((h, self.nope_dim, self.kv_rank), up),
                "w_uv": dense((h, self.kv_rank, self.v_dim), up),
                "wo": dense((h * self.v_dim, self.d_model))}

    def _attention(self, lw, l, h, turn, cache, attend):
        """One layer's context ``[..., H, v_dim]`` of rows ``h``, in the
        form the program asks for; ``turn``: the class docstring."""
        import jax
        import jax.numpy as jnp

        lead, nh = h.shape[:-1], self.num_heads
        with jax.named_scope(Q_PROJ_SCOPE):
            q = self._queries(lw, h).reshape(
                *lead, nh, self.nope_dim + self.rope_dim)
        with jax.named_scope(KV_PROJ_SCOPE):
            c, k_r = self._latent(lw, _mm(h, lw["wkv_a"]))
        if turn is None:
            q_rot, k_rot = q[..., self.nope_dim:], k_r[..., None, :]
        else:
            with jax.named_scope(ROPE_SCOPE):
                q_rot = self._rotate(q[..., self.nope_dim:], *turn)
                k_rot = self._rotate(k_r[..., None, :], *turn)
        q_nope = q[..., :self.nope_dim]
        row = jnp.concatenate([c[..., None, :], k_rot], axis=-1)
        dt = lw["w_uk"].dtype
        if attend.prompt:
            with jax.named_scope(EXPAND_SCOPE):
                cb = c.astype(dt)
                k_nope = jnp.einsum("...c,hdc->...hd", cb, lw["w_uk"],
                                    preferred_element_type=jnp.float32)
                v = jnp.einsum("...c,hcd->...hd", cb, lw["w_uv"],
                               preferred_element_type=jnp.float32)
                k = jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_rot, (*lead, nh, self.rope_dim))], axis=-1)
            return attend(l, self._scaled(
                jnp.concatenate([q_nope, q_rot], axis=-1)), k, v, cache,
                keep=row)
        with jax.named_scope(ABSORB_Q_SCOPE):
            q_lat = jnp.einsum("...hd,hdc->...hc", q_nope.astype(dt),
                               lw["w_uk"],
                               preferred_element_type=jnp.float32)
        ctx_lat, cache = attend(l, self._scaled(
            jnp.concatenate([q_lat, q_rot], axis=-1)), row, None, cache)
        with jax.named_scope(ABSORB_V_SCOPE):
            return jnp.einsum("...hc,hcd->...hd", ctx_lat.astype(dt),
                              lw["w_uv"],
                              preferred_element_type=jnp.float32), cache

    def _latent(self, lw, kv):
        """(c, k_r) of ``kv = h W_kva``: the norm is the latent's alone,
        the shared key is not normed."""
        return rms_norm(kv[..., :self.kv_rank], lw["kv_norm"],
                        self.rms_eps), kv[..., self.kv_rank:]

    def _scaled(self, q):
        """The engine's attention divides scores by the square root of
        the query's width; what the softmax's scale holds beyond that
        (the heads' own width, YaRN's ``mscale^2``) rides the query."""
        return q * (self.softmax_scale * math.sqrt(q.shape[-1]))


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
        """(cos, sin) ``[..., 1, rope_dim]`` at ``positions [...]``, a
        pair's angle on both of its lanes, the sine negated on the even
        one: ``_rotate``'s two factors."""
        import jax.numpy as jnp

        angle = jnp.repeat(
            positions.astype(jnp.float32)[..., None, None]
            * jnp.asarray(self.rope_freqs, jnp.float32), 2, axis=-1)
        sign = jnp.where(jnp.arange(self.rope_dim) % 2 == 0, -1.0, 1.0)
        return (jnp.cos(angle) * self.rope_mscale,
                jnp.sin(angle) * self.rope_mscale * sign)

    @staticmethod
    def _rotate(x, cos, sin):
        """The rotary term on ``x [..., heads, rope_dim]`` whose lanes
        ``(2j, 2j + 1)`` are a pair, where they lie: ``y[2j] = x[2j] cos
        - x[2j+1] sin``, ``y[2j+1] = x[2j+1] cos + x[2j] sin``, each lane
        times its cosine plus its partner times its signed sine.  The
        reference de-interleaves first (evens, then odds) and so names
        the same 64 numbers in another order; queries and the cached key
        keep THIS one, and a score is a sum over lanes."""
        import jax.numpy as jnp

        even = jnp.arange(x.shape[-1]) % 2 == 0
        partner = jnp.where(even, jnp.roll(x, -1, axis=-1),
                            jnp.roll(x, 1, axis=-1))
        return x * cos + partner * sin


def ffn_weights(model, l, dense):
    """Layer ``l``'s feed-forward weights: a dense SwiGLU in the leading
    ``dense_layers``, else the held experts, the router with its
    correction bias and the shared expert."""
    import jax.numpy as jnp

    dm, e, f = model.d_model, model.num_experts, model.expert_dim
    nf, sf = len(model.held_experts) * f, model.shared_dim
    if l < model.dense_layers:
        return dict(ffn_w_gate=dense((dm, model.dense_dim)),
                    ffn_w_up=dense((dm, model.dense_dim)),
                    ffn_w_down=dense((model.dense_dim, dm)))
    return dict(
        moe_router=dense((dm, e), dtype=jnp.float32),
        moe_router_bias=dense((e,), 0.1, jnp.float32),
        moe_w_gate=dense((dm, nf)), moe_w_up=dense((dm, nf)),
        moe_w_down=dense((nf, dm), 1.0 / math.sqrt(f)),
        shared_w_gate=dense((dm, sf)),
        shared_w_up=dense((dm, sf)),
        shared_w_down=dense((sf, dm)))


def feed_forward(model, l, lw, x, attend):
    """``x`` plus layer ``l``'s feed-forward of it: dense in the leading
    layers, else the held experts' scaled part beside the shared
    expert."""
    import jax

    h = rms_norm(x, lw["norm2"], model.rms_eps)
    if l < model.dense_layers:
        with jax.named_scope(DENSE_SCOPE):
            return x + _swiglu(h, lw, "ffn")
    local = route_share(h, lw, attend, model.top_k, model.held_experts)
    routed = share_ffn(model, h, lw, local, attend)
    with jax.named_scope(SHARED_SCOPE):
        return x + model.routed_scale * routed + _swiglu(h, lw, "shared")


def head_logits(model, w, x, attend):
    """The logits of ``x``'s rows, or of a prompt's read row alone."""
    import jax

    if attend.prompt and attend.read_row is not None:
        # the one row of a prompt whose logits are read: the head
        # over every row would be a seventh of a prefill's matmuls
        # and 0.67 GB of float32 nobody reads
        x = jax.lax.dynamic_slice_in_dim(x, attend.read_row, 1, axis=0)
    return _mm(rms_norm(x, w["norm_f"], model.rms_eps), w["lm_head"])


def _swiglu(h, lw, name):
    import jax

    return _mm(jax.nn.silu(_mm(h, lw[name + "_w_gate"]))
               * _mm(h, lw[name + "_w_up"]), lw[name + "_w_down"])
