"""A decoder of channel-decay delta-rule layers and position-free latent
attention layers over ONE cache, a leading dense layer, a held share of
its experts under a scaled, bias-corrected sigmoid router and one shared
expert behind the program's ``DecodeServer``:
``paddle_tpu.serving.linear_latent_lm.LinearLatentLM`` at the
configuration's ``model`` sizes, weights made on the device in one
jitted call from the seed (``held_experts`` in the file is ``[first,
end)``).

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes
the served model's ``routing`` (chosen expert ids a position and expert
layer) and returns the reference's logits with how far below its own
k-th score each followed choice lay
(``benchmark/reference/linear_latent_lm.py``).
"""
import functools
import json

from benchmark.models.hybrid_moe_lm import _sizes, decode_config  # noqa: F401

# query rows a block of the reference's latent layers: 34 blocks of a
# 4,352-row check sequence, 2.2 MB of scores a head at a time
_ROWS = 128


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.linear_latent_lm import LinearLatentLM

    return LinearLatentLM(**_sizes(config))


def build(config, seed):
    """(model, weights): made on the device in one jitted call."""
    import jax

    model = make_model(config)
    weights = jax.jit(model.init_weights)(jax.random.PRNGKey(int(seed)))
    return model, weights


def dims(config):
    """What the reference needs of the sizes, as plain values."""
    m = _sizes(config)
    return {"kinds": m["layer_kinds"], "dense_layers": m["dense_layers"],
            "lin_heads": m["lin_heads"], "lin_head_dim": m["lin_head_dim"],
            "conv_kernel": m["conv_kernel"], "num_heads": m["num_heads"],
            "nope_dim": m["nope_dim"], "rope_dim": m["rope_dim"],
            "kv_rank": m["kv_rank"], "top_k": m["top_k"],
            "held": m["held_experts"], "expert_dim": m["expert_dim"],
            "routed_scale": m["routed_scale"], "eps": m["rms_eps"]}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes, rows):
    """The reference's blocks at ``sizes`` (``dims`` as JSON), each
    jitted alone: one layer's upcast weights at a time beside the served
    copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import linear_latent_lm as ref

    d = json.loads(sizes)
    return {
        "recurrent": jax.jit(lambda lw, x: ref.kda_layer(lw, x, d)),
        "attention": jax.jit(lambda lw, x: ref.latent_layer(lw, x, d, rows)),
        "dense": jax.jit(lambda lw, x: ref.dense_layer(lw, x, d)),
        "moe": jax.jit(lambda lw, x, ids: ref.moe_layer(lw, x, d, ids)),
        "head": jax.jit(lambda w, x: ref.head(w, x, d)),
    }


def reference_logits(config, weights, tokens, routing=None):
    """Plain float32 (logits [T, vocab], gap [T, expert layers]) of the
    padded sequence, layer by layer, the latent layers' query rows
    ``_ROWS`` at a time where that divides the sequence; ``routing`` [T,
    expert layers, k] makes the expert layers follow the served model's
    choices."""
    import jax.numpy as jnp

    d = dims(config)
    fns = _layer_fns(json.dumps(d, sort_keys=True),
                     None if tokens.shape[0] % _ROWS else _ROWS)
    x = weights["tok_emb"][tokens].astype(jnp.float32)
    gaps = []
    for l, (kind, lw) in enumerate(zip(d["kinds"], weights["layers"])):
        x = fns[kind](lw, x)
        if l < d["dense_layers"]:
            x = fns["dense"](lw, x)
            continue
        x, gap = fns["moe"](lw, x, None if routing is None
                            else jnp.asarray(routing)[:, len(gaps)])
        gaps.append(gap)
    head = {"norm_f": weights["norm_f"], "lm_head": weights["lm_head"]}
    return fns["head"](head, x), jnp.stack(gaps, axis=1)


def kv_bytes_per_token(config):
    """Bytes one cached position holds over the LATENT layers AS
    PUBLISHED: a latent of ``kv_rank`` lanes and the shared key,
    whatever the pool's layout pads them to; the recurrent layers keep
    nothing a position (``flops_linear_latent.latent_row_bytes``)."""
    from benchmark import flops_linear_latent

    m = config["model"]
    return m["layer_kinds"].count("attention") \
        * flops_linear_latent.latent_row_bytes(
            m["kv_rank"], m["rope_dim"],
            config["serving"].get("cache_dtype", "float32"))
