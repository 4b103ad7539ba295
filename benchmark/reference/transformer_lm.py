"""Plain decoder-only transformer forward: the yardstick for ``correct``
in the serving cells.

GPT-2's block as published (Radford et al. 2019; ``openai-community``
``config.json``): learned token and position embeddings, pre-LN blocks
(causal multi-head self-attention, ``gelu_new`` feed-forward of width
4*d), a final layer norm and a projection to the vocabulary.  Written out
from the weights dictionary in ``jax.numpy`` float32 at ``highest``
matmul precision over the WHOLE sequence: no cache, no pages, no kernel,
no batching, and none of the model's own methods.  It started from
``chip_smoke.py``'s ``reference_forward`` (which calls the model's
``_qkv``/``_mlp``; this one does not).

The program's ``TransformerLM`` departs from the published GPT-2 (see
``configs/gpt2_medium.json`` ``departures``); this reference follows the
program there, so that both compute the same function: no biases in the
attention and feed-forward projections, an untied ``lm_head``.
"""
import math


def _ln(x, g, b, eps=1e-5):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def forward_logits(w, tokens, num_heads):
    """``tokens`` [T] int32 (padded: causal attention keeps padding out
    of every earlier position) -> logits [T, vocab]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        x = w["tok_emb"][tokens] + w["pos_emb"][jnp.arange(t)]
        dm = x.shape[-1]
        d = dm // num_heads
        causal = jnp.tril(jnp.ones((t, t), bool))
        for lw in w["layers"]:
            h = _ln(x, lw["ln1_g"], lw["ln1_b"])
            q = (h @ lw["wq"]).reshape(t, num_heads, d)
            k = (h @ lw["wk"]).reshape(t, num_heads, d)
            v = (h @ lw["wv"]).reshape(t, num_heads, d)
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
            ctx = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, dm)
            x = x + ctx @ lw["wo"]
            h = _ln(x, lw["ln2_g"], lw["ln2_b"])
            x = x + jax.nn.gelu(h @ lw["w1"], approximate=True) @ lw["w2"]
        return _ln(x, w["lnf_g"], w["lnf_b"]) @ w["lm_head"]
