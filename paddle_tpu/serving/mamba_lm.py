"""A served DENSE model whose layers are of two kinds: selective
state-space (Mamba-1) layers, which keep a fixed-size float32 state a
request (``"recurrent"``: a ``[d_state, d_inner]`` state whose every
entry decays by itself, and the convolution's last inputs, in slabs; no
keys, no heads) and, a few among them, position-free softmax attention
with several query heads on FEWER K/V heads (``"attention"``: every
position in pages; Jamba2-3B has twenty query heads on ONE).  Every
layer's feed-forward is one dense SwiGLU, every sub-block normalises its
INPUT, and the head is the embedding.  The architecture is
AI21-Jamba2-3B's (``jamba``); the equations are in the reference's
docstring (``benchmark/reference/mamba_lm.py``), which this file is
tested against and shares no code with.

Nothing of the state-space mixer is written here: the recurrent layers
are ``mixers.SSMMixer``'s (the rule's token form in the step and its
scan over a whole prompt, both ``ops/pallas_ssm.py``).  The feed-forward
is spelled as the other dense models spell it, the head is
``blocks.head_logits`` over the embedding read where it lies.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring.  What it declares: ``layer_kinds``, ``recurrent_state``,
``num_kv_heads``, ``head_dim``, ``tallies`` (``ssm_kernel_rows``: live
rows x recurrent layers a step's kernel calls updated),
``prefill_chunks_per_call``.

Precision as served: weights (and K/V pages) in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, the norms
(the block's two and the mixer's three), the step ``dt``, ``exp(dt a)``,
``A_log``, ``D``, the convolution and THE STATE in float32.
"""
from __future__ import annotations

from typing import Sequence

from .blocks import DENSE_SCOPE, _mm, dense_from, head_logits, rms_norm
from .mixers import SSMMixer


class MambaLM(SSMMixer):
    """Sized by constructor arguments.  ``layer_kinds`` is the pattern
    (Jamba2-3B: layers 7 and 21 of 28 ``"attention"``, the others
    ``"recurrent"``).  ``d_inner``, ``d_state``, ``d_conv``,
    ``dt_rank``: the recurrent layers'; ``num_heads`` query heads on
    ``num_kv_heads`` K/V heads of ``head_dim``: the attention layers'."""

    def __init__(self, vocab_size: int, d_model: int,
                 layer_kinds: Sequence[str], d_inner: int, d_state: int,
                 d_conv: int, dt_rank: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, ffn_dim: int,
                 rms_eps: float = 1e-6, dtype="bfloat16",
                 max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.layer_kinds = tuple(layer_kinds)
        bad = set(self.layer_kinds) - {"attention", "recurrent"}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad) or 'nothing'}")
        self.num_layers = len(self.layer_kinds)
        self.d_inner, self.d_state = int(d_inner), int(d_state)
        self.d_conv, self.dt_rank = int(d_conv), int(dt_rank)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads are no whole "
                             f"groups over {self.num_kv_heads} K/V heads")
        self.head_dim, self.ffn_dim = int(head_dim), int(ffn_dim)
        self.rms_eps = float(rms_eps)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)     # no positional table
        self.tallies = ("ssm_kernel_rows",)
        self.recurrent_state = self.ssm_state()

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales; the diagonal's
        as ``SSMMixer.ssm_weights`` sets them.  No ``lm_head``: the head
        is ``tok_emb``."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, f = self.d_model, self.ffn_dim
        hq, hkv = (h * self.head_dim for h in (self.num_heads,
                                               self.num_kv_heads))
        keys = iter(jax.random.split(key, 2 + 12 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((self.vocab_size, dm), dm ** -0.5),
             "norm_f": ones(dm), "layers": []}
        for kind in self.layer_kinds:
            mixer = dict(wq=dense((dm, hq)), wk=dense((dm, hkv)),
                         wv=dense((dm, hkv)), wo=dense((hq, dm))) \
                if kind == "attention" \
                else self.ssm_weights(dense, keys, ones)
            w["layers"].append({
                "norm1": ones(dm), "norm2": ones(dm), **mixer,
                "ffn_w_gate": dense((dm, f)), "ffn_w_up": dense((dm, f)),
                "ffn_w_down": dense((f, dm))})
        return w

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) -> ``(logits [..., V], cache)``; a whole-prompt
        prefill's are ``[1, V]``, the row ``attend.read_row``.
        ``positions`` are not read (no positional term)."""
        import jax

        w = weights
        x = w["tok_emb"][tokens].astype("float32")
        for l, kind in enumerate(self.layer_kinds):
            lw = w["layers"][l]
            h = rms_norm(x, lw["norm1"], self.rms_eps)
            mixer = self._attention if kind == "attention" \
                else self.ssm_mixer
            y, cache = mixer(l, lw, h, cache, attend)
            x = x + y
            h = rms_norm(x, lw["norm2"], self.rms_eps)
            with jax.named_scope(DENSE_SCOPE):
                x = x + _mm(jax.nn.silu(_mm(h, lw["ffn_w_gate"]))
                            * _mm(h, lw["ffn_w_up"]), lw["ffn_w_down"])
        # the tied head: the embedding ``[V, D]`` contracted over D (the
        # transpose folds into the product: nothing is moved)
        head = {"norm_f": w["norm_f"], "lm_head": w["tok_emb"].T}
        return head_logits(self, head, x, attend), cache

    def _attention(self, l, lw, h, cache, attend):
        """Layer ``l``'s softmax attention of the normed rows ``h`` ->
        (its output through ``wo``, cache): no bias, no norm, nothing
        turned; the engine scales the scores by ``head_dim^-1/2``."""
        lead = h.shape[:-1]
        q = _mm(h, lw["wq"]).reshape(*lead, self.num_heads, self.head_dim)
        k, v = (_mm(h, lw[n]).reshape(*lead, self.num_kv_heads,
                                      self.head_dim) for n in ("wk", "wv"))
        ctx, cache = attend(l, q, k, v, cache)
        return _mm(ctx.reshape(*lead, -1).astype("float32"),
                   lw["wo"]), cache
