"""A dense hybrid decoder (selective state-space layers beside a few
position-free multi-query softmax layers, the head tied to the
embedding) behind the program's ``DecodeServer``:
``paddle_tpu.serving.mamba_lm.MambaLM`` at the configuration's ``model``
sizes, weights made on the device in one jitted call from the seed.

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes
the rows whose logits are wanted: the head over 65,536 rows is applied
to those alone (``benchmark/reference/mamba_lm.py``).
"""
import functools


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.mamba_lm import MambaLM

    return MambaLM(**config["model"])


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
    return {"num_heads": m["num_heads"], "num_kv_heads": m["num_kv_heads"],
            "head_dim": m["head_dim"], "d_inner": m["d_inner"],
            "d_state": m["d_state"], "d_conv": m["d_conv"],
            "dt_rank": m["dt_rank"], "eps": m["rms_eps"],
            "kinds": m["layer_kinds"], "row_block": 512}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as sorted items),
    each jitted alone: one layer's upcast weights at a time beside the
    served copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import mamba_lm as ref

    d = {k: list(v) if isinstance(v, tuple) else v for k, v in sizes}
    fns = {kind: jax.jit(functools.partial(
        lambda lw, x, kind: ref.layer(lw, x, d, kind), kind=kind))
        for kind in ("attention", "recurrent")}
    fns["head"] = jax.jit(lambda w, x: ref.head(w, x, d))
    return fns


def reference_logits(config, weights, tokens, rows=None):
    """Plain float32 logits of the padded sequence ``tokens`` [T], layer
    by layer; ``rows`` (first, count) takes the head over those
    positions only."""
    import jax.numpy as jnp

    d = dims(config)
    fns = _layer_fns(tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in d.items())))
    x = weights["tok_emb"][tokens].astype(jnp.float32)
    for kind, lw in zip(d["kinds"], weights["layers"]):
        x = fns[kind](lw, x)
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    head = {"norm_f": weights["norm_f"], "tok_emb": weights["tok_emb"]}
    return fns["head"](head, x)


def kv_bytes_per_token(config):
    """K and V of one position over the model's attention layers."""
    import jax.numpy as jnp

    m = config["model"]
    return m["layer_kinds"].count("attention") * 2 * m["num_kv_heads"] \
        * m["head_dim"] * jnp.dtype(
            config["serving"].get("cache_dtype", "float32")).itemsize
