"""A hybrid linear/softmax-attention decoder with a held share of its
experts behind the program's ``DecodeServer``:
``paddle_tpu.serving.hybrid_moe_lm.HybridMoELM`` at the configuration's
``model`` sizes, weights made on the device in one jitted call from the
seed (``held_experts`` in the file is ``[first, end)``).

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes
the served model's ``routing`` (chosen expert ids a position and layer)
and returns the reference's logits with how far below its own k-th
score each followed choice lay (``benchmark/reference/hybrid_moe_lm.py``).
"""
import functools


def _sizes(config):
    m = dict(config["model"])
    m["held_experts"] = list(range(*m["held_experts"]))
    return m


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.hybrid_moe_lm import HybridMoELM

    return HybridMoELM(**_sizes(config))


def build(config, seed):
    """(model, weights): made on the device in one jitted call."""
    import jax

    model = make_model(config)
    weights = jax.jit(model.init_weights)(jax.random.PRNGKey(int(seed)))
    return model, weights


def decode_config(config, **overrides):
    """The engine's knobs as the configuration serves them; everything
    it does not name stays at ``DecodeConfig``'s default."""
    from paddle_tpu.serving import DecodeConfig

    return DecodeConfig(**dict(config["serving"], **overrides))


def dims(config):
    """What the reference needs of the sizes, as plain values."""
    m = _sizes(config)
    return {"num_heads": m["num_heads"], "num_kv_heads": m["num_kv_heads"],
            "head_dim": m["head_dim"], "lin_heads": m["lin_heads"],
            "lin_head_dim": m["lin_head_dim"],
            "conv_kernel": m["conv_kernel"], "top_k": m["top_k"],
            "held": m["held_experts"], "expert_dim": m["expert_dim"],
            "eps": m["rms_eps"], "kinds": m["layer_kinds"]}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as sorted items),
    each jitted alone: one layer's upcast weights at a time beside the
    served copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import hybrid_moe_lm as ref

    d = {k: list(v) if isinstance(v, tuple) else v for k, v in sizes}
    return {
        "attention": jax.jit(lambda lw, x: ref.softmax_layer(lw, x, d)),
        "recurrent": jax.jit(lambda lw, x: ref.kda_layer(lw, x, d)),
        "moe": jax.jit(lambda lw, x, ids: ref.moe_layer(lw, x, d, ids)),
        "head": jax.jit(lambda w, x: ref.head(w, x, d)),
    }


def reference_logits(config, weights, tokens, routing=None):
    """Plain float32 (logits [T, vocab], gap [T, L]) of the padded
    sequence, layer by layer; ``routing`` [T, L, k] makes the expert
    layers follow the served model's choices."""
    import jax.numpy as jnp

    d = dims(config)
    fns = _layer_fns(tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in d.items())))
    x = weights["tok_emb"][tokens].astype(jnp.float32)
    gaps = []
    for l, (kind, lw) in enumerate(zip(d["kinds"], weights["layers"])):
        x = fns[kind](lw, x)
        x, gap = fns["moe"](
            lw, x, None if routing is None else jnp.asarray(routing)[:, l])
        gaps.append(gap)
    head = {"norm_f": weights["norm_f"], "lm_head": weights["lm_head"]}
    return fns["head"](head, x), jnp.stack(gaps, axis=1)


def kv_bytes_per_token(config):
    from benchmark import flops_hybrid_moe

    m = config["model"]
    return flops_hybrid_moe.kv_bytes_per_token(
        m["layer_kinds"].count("attention"), m["num_kv_heads"],
        m["head_dim"], config["serving"].get("cache_dtype", "float32"))
