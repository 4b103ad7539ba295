"""Plain BERT pretraining forward: the yardstick for ``correct`` in the
training cells.

Written from the published description (Devlin et al. 2018, and the
``google-research/bert`` pretraining script): token + position + segment
embeddings, layer norm, N post-LN encoder layers (multi-head
self-attention, GELU feed-forward), the masked-LM head (transform, layer
norm, projection to the vocabulary) on the gathered masked positions and
the next-sentence head on the pooled [CLS] vector; the loss is the mean
masked-LM cross entropy plus the mean next-sentence cross entropy.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no AMP, no dropout.  Weights arrive as a dictionary keyed by
the program's parameter names; nothing of ``paddle_tpu`` is imported.

Departures from the published model, which the program under test makes
and this reference follows so that the two compute the same function:
  * the masked-LM projection is its own matrix (``mlm_out.w_0``), not
    tied to the word embeddings;
  * layer-norm epsilon is 1e-5 (published: 1e-12);
  * GELU is the exact erf form (HF ``hidden_act: "gelu"``);
  * dropout is Paddle's default ``downgrade_in_infer``: training
    multiplies by the mask and does not rescale, evaluation multiplies by
    ``1 - dropout_prob`` (the published code rescales in training
    instead; the two agree in expectation).  The reference is compared
    with the program's evaluation clone, so it applies that factor at
    the program's three dropout sites: after the embedding layer norm,
    on the attention output and on the feed-forward output.
"""
import math


def _ln(x, w, b, eps=1e-5):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _dense(w, x, name):
    return x @ w[name + ".w_0"] + w[name + ".b_0"]


def _xent(logits, labels):
    """Mean-free cross entropy per row: logsumexp - picked logit."""
    import jax
    import jax.numpy as jnp

    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - picked


def pretrain_forward(w, feed, n_layers, n_heads, dropout_prob=0.0,
                     skip_layer=None):
    """(MLM + NSP loss, encoder output [B, S, hidden]) of one batch.
    ``feed`` holds the program's feeds (``masked_flat_pos`` indexes the
    flattened [batch*seq] positions).
    ``skip_layer`` leaves one encoder layer out: used once, by hand, to
    see how far a broken step moves the loss and the encoder output
    (the tolerances' reasons are in the cells' workload files)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        ids = feed["input_ids"].astype(jnp.int32)
        b, s = ids.shape
        x = (w["word_embedding"][ids]
             + w["pos_embedding"][feed["pos_ids"].astype(jnp.int32)]
             + w["sent_embedding"][feed["token_type_ids"].astype(jnp.int32)])
        keep = 1.0 - dropout_prob
        x = _ln(x, w["emb_ln.w_0"], w["emb_ln.b_0"]) * keep
        hidden = x.shape[-1]
        d = hidden // n_heads
        mask = feed["input_mask"].astype(jnp.float32)       # [B,1,1,S]
        for i in range(n_layers):
            if i == skip_layer:
                continue
            p = f"enc_{i}"

            def heads(t):
                return t.reshape(b, s, n_heads, d).transpose(0, 2, 1, 3)

            q = heads(_dense(w, x, p + "_attn_q"))
            k = heads(_dense(w, x, p + "_attn_k"))
            v = heads(_dense(w, x, p + "_attn_v"))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
            probs = jax.nn.softmax(scores + mask, axis=-1)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, hidden)
            x = _ln(x + _dense(w, ctx, p + "_attn_out") * keep,
                    w[p + "_ln1.w_0"], w[p + "_ln1.b_0"])
            ffn = jax.nn.gelu(_dense(w, x, p + "_ffn1"), approximate=False)
            x = _ln(x + _dense(w, ffn, p + "_ffn2") * keep,
                    w[p + "_ln2.w_0"], w[p + "_ln2.b_0"])

        picked = x.reshape(b * s, hidden)[
            feed["masked_flat_pos"].astype(jnp.int32)]
        h = jax.nn.gelu(_dense(w, picked, "mlm_trans"), approximate=False)
        h = _ln(h, w["mlm_ln.w_0"], w["mlm_ln.b_0"])
        tok = _xent(_dense(w, h, "mlm_out"),
                    feed["masked_labels"].astype(jnp.int32).reshape(-1))
        weights = feed["masked_weights"].astype(jnp.float32).reshape(-1)
        mlm = jnp.sum(tok * weights) / jnp.maximum(jnp.sum(weights), 1.0)

        pooled = jnp.tanh(_dense(w, x[:, 0], "pooler"))
        nsp = jnp.mean(_xent(
            _dense(w, pooled, "nsp_out"),
            feed["nsp_labels"].astype(jnp.int32).reshape(-1)))
        return mlm + nsp, x
