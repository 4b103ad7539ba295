"""A dense hybrid decoder (Gated DeltaNet layers beside position-free
softmax layers with QK-norm) behind the program's ``DecodeServer``:
``paddle_tpu.serving.gated_delta_lm.GatedDeltaLM`` at the configuration's
``model`` sizes, weights made on the device in one jitted call from the
seed.

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes the
rows whose logits are wanted: the head over 100,352 rows is applied to
those alone (``benchmark/reference/gated_delta_lm.py``).
"""
import functools


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.gated_delta_lm import GatedDeltaLM

    return GatedDeltaLM(**config["model"])


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
            "lin_heads": m["lin_heads"], "lin_key_dim": m["lin_key_dim"],
            "lin_value_dim": m["lin_value_dim"],
            "conv_kernel": m["conv_kernel"], "eps": m["rms_eps"],
            "kinds": m["layer_kinds"], "row_block": 512}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as sorted items),
    each jitted alone: one layer's upcast weights at a time beside the
    served copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import gated_delta_lm as ref

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
    head = {"norm_f": weights["norm_f"], "lm_head": weights["lm_head"]}
    return fns["head"](head, x)


def kv_bytes_per_token(config):
    from benchmark import flops_hybrid_moe

    m = config["model"]
    return flops_hybrid_moe.kv_bytes_per_token(
        m["layer_kinds"].count("attention"), m["num_heads"],
        m["head_dim"], config["serving"].get("cache_dtype", "float32"))
