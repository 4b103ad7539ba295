"""A looped dense decoder (one stack of layers every token passes
through ``loops`` times on the same weights, each pass with K and V of
its own) behind the program's ``DecodeServer``:
``paddle_tpu.serving.looped_lm.LoopedLM`` at the configuration's
``model`` sizes, weights made on the device in one jitted call from the
seed.

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  The program's weights are
STACKED (one array a name with a leading ``[num_layers]``) and the
reference takes a list of layers: ``reference_logits`` hands it layer
``l``'s slice of every array, one layer's upcast weights at a time
beside the served copy of the model, and the head over the rows asked
for alone (``benchmark/reference/looped_lm.py``).
"""
import functools


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.looped_lm import LoopedLM

    return LoopedLM(**config["model"])


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
    m = config["model"]
    return {"num_heads": m["num_heads"], "head_dim": m["head_dim"],
            "eps": m["rms_eps"], "rope_theta": m["rope_theta"],
            "loops": m["loops"]}


def unstacked(weights):
    """The reference's view of the program's weights: a list of one
    dictionary a layer."""
    layers = weights["layers"]
    n = next(iter(layers.values())).shape[0]
    return dict(weights, layers=[{k: v[l] for k, v in layers.items()}
                                 for l in range(n)])


@functools.lru_cache(maxsize=None)
def _fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as sorted items),
    each jitted alone; the layer takes the stacked weights and which
    layer to cut out of them, so one trace serves all 192 applications."""
    import jax

    from benchmark.reference import looped_lm as ref

    d = dict(sizes)
    return {
        "layer": jax.jit(lambda layers, l, x: ref.layer(
            {k: v[l] for k, v in layers.items()}, x, d)),
        "between": jax.jit(lambda w, x: ref.between(w, x, d)[0]),
        "head": jax.jit(ref.head)}


def reference_logits(config, weights, tokens, rows=None, dims_=None):
    """Plain float32 logits of the padded sequence ``tokens`` [T], pass
    by pass and layer by layer; ``rows`` (first, count) takes the head
    over those positions only.  ``dims_`` overrides the sizes the
    reference reads (a control's)."""
    import jax.numpy as jnp

    d = dict(dims(config), **(dims_ or {}))
    fns = _fns(tuple(sorted(d.items())))
    n = config["model"]["num_layers"]
    small = {k: v for k, v in weights.items() if k != "layers"}
    x = weights["tok_emb"][tokens].astype(jnp.float32)
    for _ in range(d["loops"]):
        for l in range(n):
            x = fns["layer"](weights["layers"], l, x)
        x = fns["between"](small, x)
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    return fns["head"](small, x)


def kv_bytes_per_token(config):
    from benchmark import flops_hybrid_moe

    m = config["model"]
    return flops_hybrid_moe.kv_bytes_per_token(
        m["loops"] * m["num_layers"], m["num_heads"], m["head_dim"],
        config["serving"].get("cache_dtype", "float32"))
