"""A decoder whose attention reads the positions a learned indexer
selects (a third pool of index keys beside K and V), with a held share
of its experts under a softmax router and no shared expert, behind the
program's ``DecodeServer``:
``paddle_tpu.serving.indexed_moe_lm.IndexedMoELM`` at the
configuration's ``model`` sizes, weights made on the device in one
jitted call from the seed (``held_experts`` in the file is ``[first,
end)``).

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).  ``reference_logits`` takes
the served model's ``routing`` (chosen expert ids a position and layer)
and ``selections`` (a layer's attended positions a row, ``[T, T]`` bool
each) and returns the reference's logits of ``rows`` with how each
followed choice measures against its own scores
(``benchmark/reference/indexed_moe_lm.py``).
"""
import functools
import json

from benchmark.models.hybrid_moe_lm import decode_config  # noqa: F401

# what the configuration's ``model`` holds for the accepted readers only
# (the model derives them: every layer keeps every position in pages and
# has experts)
_READERS_KEYS = ("layer_kinds", "dense_layers")
# query rows a block of the reference's attention: the check's padded
# sequence (8,320 = 65 x 128) is whole blocks of it
_REFERENCE_BLOCK = 128


def _sizes(config):
    m = {k: v for k, v in config["model"].items() if k not in _READERS_KEYS}
    m["held_experts"] = list(range(*m["held_experts"]))
    return m


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.indexed_moe_lm import IndexedMoELM

    return IndexedMoELM(**_sizes(config))


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
            "head_dim": m["head_dim"], "index_heads": m["index_heads"],
            "index_dim": m["index_dim"], "topk": m["index_topk"],
            "rope_theta": m["rope_theta"], "top_k": m["top_k"],
            "held": m["held_experts"], "expert_dim": m["expert_dim"],
            "eps": m["rms_eps"], "block": _REFERENCE_BLOCK}


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes):
    """The reference's blocks at ``sizes`` (``dims`` as JSON), each
    jitted alone: one layer's upcast weights at a time beside the served
    copy of the model, one trace for all requests."""
    import jax

    from benchmark.reference import indexed_moe_lm as ref

    d = json.loads(sizes)
    return {
        "attention": jax.jit(
            lambda lw, x, chosen: ref.attention_layer(lw, x, d, chosen)),
        "moe": jax.jit(lambda lw, x, ids: ref.moe_layer(lw, x, d, ids)),
        "head": jax.jit(lambda w, x, first, count: ref.head(
            w, x, d, (first, count)), static_argnums=3),
    }


def reference_logits(config, weights, tokens, routing=None, selections=None,
                     rows=None):
    """Plain float32 (logits [count, vocab], route gap [T, L], select gap
    [T, L], moved [T, L]) of the padded sequence, layer by layer;
    ``routing`` [T, L, k] and ``selections`` (a sequence of L ``[T, T]``
    bool arrays) make the layers follow the served model's choices;
    ``rows = (first, count)``: the head over those rows (all by
    default)."""
    import jax.numpy as jnp

    fns = _layer_fns(json.dumps(dims(config), sort_keys=True))
    x = weights["tok_emb"][tokens].astype(jnp.float32)
    gaps, sgaps, moves = [], [], []
    for l, lw in enumerate(weights["layers"]):
        x, sgap, moved = fns["attention"](
            lw, x, None if selections is None else jnp.asarray(selections[l]))
        x, gap = fns["moe"](lw, x, None if routing is None
                            else jnp.asarray(routing)[:, l])
        gaps.append(gap)
        sgaps.append(sgap)
        moves.append(moved)
    first, count = rows if rows is not None else (0, x.shape[0])
    head = {"norm_f": weights["norm_f"], "lm_head": weights["lm_head"]}
    return fns["head"](head, x, jnp.int32(first), int(count)), \
        jnp.stack(gaps, axis=1), jnp.stack(sgaps, axis=1), \
        jnp.stack(moves, axis=1)


def kv_bytes_per_token(config):
    """Bytes one cached position holds over the layers AS PUBLISHED: K
    and V of every K/V head and the one index key, whatever the pools'
    layout pads them to (``flops_indexed_moe.position_bytes``)."""
    from benchmark import flops_indexed_moe

    m = config["model"]
    return m["num_layers"] * flops_indexed_moe.position_bytes(
        m["num_kv_heads"], m["head_dim"], m["index_dim"],
        config["serving"].get("cache_dtype", "float32"))
