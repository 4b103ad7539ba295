"""A decoder with a parallel block, window and position-free global
layers, a held share of its experts, averaged shared experts and a tied
head behind the program's ``DecodeServer``:
``paddle_tpu.serving.parallel_moe_lm.ParallelMoELM`` at the
configuration's ``model`` sizes, weights made on the device in one
jitted call from the seed (``held_experts`` in the file is ``[first,
end)``).

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes
the served model's ``routing`` (chosen expert ids a position and layer)
and returns the reference's logits with how far below its own k-th score
each followed choice lay (``benchmark/reference/parallel_moe_lm.py``).
"""
import functools
import json

from benchmark.models.hybrid_moe_lm import decode_config  # noqa: F401

# what the configuration's ``model`` holds for the kind and the accepted
# readers only: the model derives them (one K/V geometry for both kinds
# of layer, no leading dense layer)
_READERS_KEYS = ("window_kv_heads", "v_head_dim", "dense_layers")


def _sizes(config):
    m = {k: v for k, v in config["model"].items() if k not in _READERS_KEYS}
    m["held_experts"] = list(range(*m["held_experts"]))
    return m


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.parallel_moe_lm import ParallelMoELM

    return ParallelMoELM(**_sizes(config))


def build(config, seed):
    """(model, weights): made on the device in one jitted call."""
    import jax

    model = make_model(config)
    weights = jax.jit(model.init_weights)(jax.random.PRNGKey(int(seed)))
    return model, weights


def dims(config):
    """What the reference needs of the sizes, as plain values."""
    m = _sizes(config)
    return {"num_heads": m["num_heads"], "num_kv_heads": m["num_kv_heads"],
            "head_dim": m["head_dim"], "rope_theta": m["rope_theta"],
            "window": m["window"], "top_k": m["top_k"],
            "held": m["held_experts"], "expert_dim": m["expert_dim"],
            "shared_experts": m["shared_experts"],
            "shared_dim": m["shared_dim"], "eps": m["norm_eps"],
            "logit_scale": m["logit_scale"], "kinds": m["layer_kinds"]}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as JSON), each
    jitted alone: one layer's upcast weights at a time beside the served
    copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import parallel_moe_lm as ref

    d = json.loads(sizes)
    fns = {kind: jax.jit(functools.partial(
        lambda kind, lw, x, ids: ref.block(lw, x, d, kind, ids), kind))
        for kind in ("attention", "window")}
    fns["head"] = jax.jit(lambda w, x: ref.head(w, x, d))
    return fns


def reference_logits(config, weights, tokens, routing=None):
    """Plain float32 (logits [T, vocab], gap [T, layers]) of the padded
    sequence, layer by layer; ``routing`` [T, layers, k] makes the
    layers follow the served model's choices."""
    import jax.numpy as jnp

    d = dims(config)
    fns = _layer_fns(json.dumps(d, sort_keys=True))
    x = weights["tok_emb"][tokens].astype(jnp.float32)
    gaps = []
    for l, (kind, lw) in enumerate(zip(d["kinds"], weights["layers"])):
        x, gap = fns[kind](lw, x, None if routing is None
                           else jnp.asarray(routing)[:, l])
        gaps.append(gap)
    head = {"norm_f": weights["norm_f"], "tok_emb": weights["tok_emb"]}
    return fns["head"](head, x), jnp.stack(gaps, axis=1)


def kv_bytes_per_token(config):
    """Bytes of K and V one cached position holds in the layers that
    keep EVERY position (the global layers); a window layer's are
    ``flops_window_moe.window_attention_bytes``'s."""
    from benchmark import flops_window_moe

    m = config["model"]
    return flops_window_moe.kv_bytes_per_token(
        m["layer_kinds"].count("attention"), m["num_kv_heads"],
        m["head_dim"], m["v_head_dim"],
        config["serving"].get("cache_dtype", "float32"))
