"""A decoder of gated short convolutions and grouped-query attention
with every expert of its routed layers on the chip, behind the program's
``DecodeServer``: ``paddle_tpu.serving.conv_moe_lm.ConvMoELM`` at the
configuration's ``model`` sizes, weights made on the device in one jitted
call from the seed (``held_experts`` in the file is ``[first, end)``).

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes
the served model's ``routing`` (chosen expert ids a position and EXPERT
layer) and returns the reference's logits with how far below its own
k-th ranked score each followed choice lay
(``benchmark/reference/conv_moe_lm.py``); ``rows`` takes the head over
those positions only; ``dims_`` overrides what the reference reads.
"""
import functools
import json


def _sizes(config):
    m = dict(config["model"])
    m["held_experts"] = list(range(*m["held_experts"]))
    return m


def make_model(config):
    """The program's model object at the configuration's sizes.  A
    checkout whose program has no such model (the parent of the PR that
    added it) fails here, at once, by name."""
    try:
        from paddle_tpu.serving.conv_moe_lm import ConvMoELM
    except ImportError as e:
        from benchmark.run import BenchmarkError

        raise BenchmarkError(
            "this checkout's paddle_tpu has no serving.conv_moe_lm: the "
            "configuration cannot be built") from e

    return ConvMoELM(**_sizes(config))


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
            "head_dim": m["head_dim"], "conv_kernel": m["conv_kernel"],
            "rope_theta": m["rope_theta"],
            "dense_layers": m["dense_layers"], "top_k": m["top_k"],
            "held": m["held_experts"], "expert_dim": m["expert_dim"],
            "eps": m["rms_eps"], "kinds": m["layer_kinds"]}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as JSON), each
    jitted alone: one layer's upcast weights at a time beside the served
    copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import conv_moe_lm as ref

    d = json.loads(sizes)
    return {
        "attention": jax.jit(lambda lw, x: ref.attention_layer(lw, x, d)),
        "recurrent": jax.jit(lambda lw, x: ref.conv_layer(lw, x, d)),
        "dense": jax.jit(lambda lw, x: ref.dense_layer(lw, x, d)),
        "moe": jax.jit(lambda lw, x, ids: ref.moe_layer(lw, x, d, ids)),
        "head": jax.jit(lambda w, x: ref.head(w, x, d)),
    }


def reference_logits(config, weights, tokens, routing=None, rows=None,
                     dims_=None):
    """Plain float32 (logits [T or count, vocab], gap [T, expert
    layers]) of the padded sequence, layer by layer; ``routing`` [T,
    expert layers, k] makes the expert layers follow the served model's
    choices."""
    import jax.numpy as jnp

    d = dict(dims(config), **(dims_ or {}))
    fns = _layer_fns(json.dumps(d, sort_keys=True))
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
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    head = {k: weights[k] for k in ("norm_f", "tok_emb", "lm_head")
            if k in weights}
    return fns["head"](head, x), jnp.stack(gaps, axis=1)


def kv_bytes_per_token(config):
    from benchmark import flops_conv_moe

    m = config["model"]
    return flops_conv_moe.kv_bytes_per_token(
        m["layer_kinds"].count("attention"), m["num_kv_heads"],
        m["head_dim"], config["serving"].get("cache_dtype", "float32"))
