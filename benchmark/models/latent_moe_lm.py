"""A decoder with latent attention, a leading dense layer, a held share
of its experts under a scaled, bias-corrected sigmoid router and one
shared expert behind the program's ``DecodeServer``:
``paddle_tpu.serving.latent_moe_lm.LatentMoELM`` at the configuration's
``model`` sizes, weights made on the device in one jitted call from the
seed (``held_experts`` in the file is ``[first, end)``).

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes
the served model's ``routing`` (chosen expert ids a position and expert
layer) and returns the reference's logits with how far below its own
k-th score each followed choice lay
(``benchmark/reference/latent_moe_lm.py``).
"""
import functools
import json

from benchmark.models.hybrid_moe_lm import decode_config  # noqa: F401

# what the configuration's ``model`` holds for the accepted readers only
# (the model derives it: every layer keeps every position in pages)
_READERS_KEYS = ("layer_kinds",)


def _sizes(config):
    m = {k: v for k, v in config["model"].items() if k not in _READERS_KEYS}
    m["held_experts"] = list(range(*m["held_experts"]))
    return m


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.latent_moe_lm import LatentMoELM

    return LatentMoELM(**_sizes(config))


def build(config, seed):
    """(model, weights): made on the device in one jitted call."""
    import jax

    model = make_model(config)
    weights = jax.jit(model.init_weights)(jax.random.PRNGKey(int(seed)))
    return model, weights


def dims(config):
    """What the reference needs of the sizes, as plain values."""
    m = _sizes(config)
    return {"num_heads": m["num_heads"], "nope_dim": m["nope_dim"],
            "rope_dim": m["rope_dim"], "kv_rank": m["kv_rank"],
            "rope_theta": m["rope_theta"],
            "yarn": {"factor": m["rope_factor"],
                     "orig_len": m["rope_orig_len"],
                     "beta_fast": m["rope_beta_fast"],
                     "beta_slow": m["rope_beta_slow"],
                     "mscale": m["rope_mscale"],
                     "mscale_all_dim": m["rope_mscale_all_dim"]},
            "dense_layers": m["dense_layers"], "top_k": m["top_k"],
            "held": m["held_experts"], "expert_dim": m["expert_dim"],
            "routed_scale": m["routed_scale"], "eps": m["rms_eps"]}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as JSON), each
    jitted alone: one layer's upcast weights at a time beside the served
    copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import latent_moe_lm as ref

    d = json.loads(sizes)
    return {
        "attention": jax.jit(lambda lw, x: ref.attention_layer(lw, x, d)),
        "dense": jax.jit(lambda lw, x: ref.dense_layer(lw, x, d)),
        "moe": jax.jit(lambda lw, x, ids: ref.moe_layer(lw, x, d, ids)),
        "head": jax.jit(lambda w, x: ref.head(w, x, d)),
    }


def reference_logits(config, weights, tokens, routing=None):
    """Plain float32 (logits [T, vocab], gap [T, expert layers]) of the
    padded sequence, layer by layer; ``routing`` [T, expert layers, k]
    makes the expert layers follow the served model's choices."""
    import jax.numpy as jnp

    d = dims(config)
    fns = _layer_fns(json.dumps(d, sort_keys=True))
    x = weights["tok_emb"][tokens].astype(jnp.float32)
    gaps = []
    for l, lw in enumerate(weights["layers"]):
        x = fns["attention"](lw, x)
        if l < d["dense_layers"]:
            x = fns["dense"](lw, x)
            continue
        x, gap = fns["moe"](lw, x, None if routing is None
                            else jnp.asarray(routing)[:, len(gaps)])
        gaps.append(gap)
    head = {"norm_f": weights["norm_f"], "lm_head": weights["lm_head"]}
    return fns["head"](head, x), jnp.stack(gaps, axis=1)


def kv_bytes_per_token(config):
    """Bytes one cached position holds over the layers AS PUBLISHED: a
    latent of ``kv_rank`` lanes and the rotary key, whatever the pool's
    layout pads them to (``flops_latent_moe.latent_row_bytes``)."""
    from benchmark import flops_latent_moe

    m = config["model"]
    return m["num_layers"] * flops_latent_moe.latent_row_bytes(
        m["kv_rank"], m["rope_dim"],
        config["serving"].get("cache_dtype", "float32"))
