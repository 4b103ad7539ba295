"""A decoder of window and global attention layers with a held share of
its experts behind the program's ``DecodeServer``:
``paddle_tpu.serving.window_moe_lm.WindowMoELM`` at the configuration's
``model`` sizes, weights made on the device in one jitted call from the
seed (``held_experts`` in the file is ``[first, end)``).

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes
the served model's ``routing`` (chosen expert ids a position and expert
layer) and returns the reference's logits with how far below its own
k-th score each followed choice lay
(``benchmark/reference/window_moe_lm.py``).
"""
import functools
import json

from benchmark.models.hybrid_moe_lm import _sizes, decode_config  # noqa: F401


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.window_moe_lm import WindowMoELM

    return WindowMoELM(**_sizes(config))


def build(config, seed):
    """(model, weights): made on the device in one jitted call."""
    import jax

    model = make_model(config)
    weights = jax.jit(model.init_weights)(jax.random.PRNGKey(int(seed)))
    return model, weights


def dims(config):
    """What the reference needs of the sizes, as plain values."""
    m = _sizes(config)
    return {"num_heads": m["num_heads"],
            "kv_heads": {"attention": m["num_kv_heads"],
                         "window": m["window_kv_heads"]},
            "head_dim": m["head_dim"], "v_head_dim": m["v_head_dim"],
            "rotary_dim": m["rotary_dim"],
            "rope_theta": {"attention": m["rope_theta"],
                           "window": m["window_rope_theta"]},
            "window": m["window"], "value_scale": m["value_scale"],
            "dense_layers": m["dense_layers"], "top_k": m["top_k"],
            "held": m["held_experts"], "expert_dim": m["expert_dim"],
            "eps": m["rms_eps"], "kinds": m["layer_kinds"]}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as JSON), each
    jitted alone: one layer's upcast weights at a time beside the served
    copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import window_moe_lm as ref

    d = json.loads(sizes)
    return {
        "attention": jax.jit(
            lambda lw, x: ref.attention_layer(lw, x, d, "attention")),
        "window": jax.jit(
            lambda lw, x: ref.attention_layer(lw, x, d, "window")),
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
    """Bytes of K and V one cached position holds in the layers that
    keep EVERY position (the global layers); a window layer's are
    ``flops_window_moe.window_bytes_per_token``."""
    from benchmark import flops_window_moe

    m = config["model"]
    return flops_window_moe.kv_bytes_per_token(
        m["layer_kinds"].count("attention"), m["num_kv_heads"],
        m["head_dim"], m["v_head_dim"],
        config["serving"].get("cache_dtype", "float32"))
