"""A decoder-only transformer behind the program's ``DecodeServer``:
``paddle_tpu.serving.decode.TransformerLM`` with float32 weights made on
the device in one jitted call from the seed.

A model module gives a serving kind: ``build``, ``decode_config``,
``reference_logits`` and ``kv_bytes_per_token`` (and ``make_model`` to
whoever needs the model without weights).
"""
import functools


def make_model(config):
    """The program's model object at the configuration's sizes."""
    from paddle_tpu.serving.decode import TransformerLM

    m = config["model"]
    return TransformerLM(
        vocab_size=m["vocab_size"], d_model=m["n_embd"],
        num_layers=m["n_layer"], num_heads=m["n_head"],
        ffn_dim=m["ffn_dim"], max_seq_len=m["n_positions"])


def build(config, seed):
    """(model, weights): float32, made on the device in one jitted call."""
    import jax

    model = make_model(config)
    weights = jax.jit(model.init_weights)(jax.random.PRNGKey(int(seed)))
    return model, weights


def decode_config(config, **overrides):
    """The engine's knobs as the configuration serves them; everything
    it does not name stays at ``DecodeConfig``'s default."""
    from paddle_tpu.serving import DecodeConfig

    return DecodeConfig(**dict(config["serving"], **overrides))


@functools.lru_cache(maxsize=None)
def _reference_fn(heads):
    """One jitted reference per head count: one trace for all requests."""
    import jax

    from benchmark.reference import transformer_lm as ref

    return jax.jit(lambda w, t: ref.forward_logits(w, t, heads))


def reference_logits(config, weights, tokens):
    """Plain float32 logits [T, vocab] of the padded sequence."""
    return _reference_fn(config["model"]["n_head"])(weights, tokens)


def kv_bytes_per_token(config):
    from benchmark import flops

    return flops.kv_bytes_per_token(
        config["model"]["n_layer"], config["model"]["n_embd"],
        config["serving"].get("cache_dtype", "float32"))
