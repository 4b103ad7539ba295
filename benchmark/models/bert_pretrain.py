"""BERT pretraining step, built through the program's normal entry
points: ``paddle_tpu.text.bert_base_pretrain_program`` + bf16 static AMP
+ AdamW, exactly as ``chip_smoke.py``'s ``bert_program`` builds it (this
started as a copy of it and of ``bert_feed``; the originals stay where
they are, see PERF.md Open questions).

A model module gives a training kind four things: ``build``, ``feed``,
``probes``, ``reference`` and ``flops_per_sample``.
"""
import numpy as np

_SIZES = ("seq_len", "vocab_size", "hidden", "n_layers", "n_heads",
          "ffn_size", "max_preds_per_seq", "dropout_prob")


def sizes(config):
    """The builder's keyword arguments, from the configuration file."""
    m = config["model"]
    return {k: m[k] for k in _SIZES}


def build(config, batch_size, seed, fleet_dp=False):
    """(main, startup, loss) for ``batch_size`` sequences a program.
    Weights come from the startup program, whose ``random_seed`` is the
    run's; ``fleet_dp`` routes minimize through the fleet collective
    optimizer (all-reduced gradients over the 'dp' mesh)."""
    from paddle_tpu.amp.static_amp import decorate
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.program import program_guard
    from paddle_tpu.text import bert_base_pretrain_program

    # a fresh name generator, so that parameters are named alike in every
    # build of one process (the reference reads them by name)
    with unique_name.guard():
        main_p, startup, _, loss, opt = bert_base_pretrain_program(
            batch_size=batch_size, **sizes(config))
        main_p.random_seed = startup.random_seed = int(seed)
        with program_guard(main_p, startup):
            opt = decorate(opt, use_bf16=True)
            if fleet_dp:
                from paddle_tpu.distributed import fleet

                fleet.init(is_collective=True)
                fleet.distributed_optimizer(opt)
                fleet.minimize(loss)
            else:
                opt.minimize(loss)
    return main_p, startup, loss


def feed(config, batch_size, seed, shards=1):
    """One synthetic batch from ``seed``.  ``masked_flat_pos`` indexes
    the flattened [batch*seq] activations of the program that consumes
    it, so under data parallelism it is local to each shard's slice."""
    m = config["model"]
    S, P, V = m["seq_len"], m["max_preds_per_seq"], m["vocab_size"]
    B = batch_size
    rng = np.random.RandomState(int(seed))
    ids = rng.randint(0, V, (B, S)).astype("int64")
    pos = np.stack([rng.choice(S, P, replace=False) for _ in range(B)])
    labels = np.take_along_axis(ids, pos, axis=1).reshape(-1, 1)
    local_b = np.arange(B) % (B // shards)
    return {
        "input_ids": ids,
        "token_type_ids": np.zeros((B, S), "int64"),
        "pos_ids": np.tile(np.arange(S, dtype="int64"), (B, 1)),
        "input_mask": np.zeros((B, 1, 1, S), "float32"),
        "masked_flat_pos": (local_b[:, None] * S + pos).reshape(-1)
        .astype("int64"),
        "masked_labels": labels.astype("int64"),
        "masked_weights": np.ones((B * P, 1), "float32"),
        "nsp_labels": rng.randint(0, 2, (B, 1)).astype("int64"),
    }


def probes(config, program):
    """Variables of ``program`` that the check fetches beside the loss,
    by the name the reference gives them: the encoder's output (the
    last layer's second layer norm, found by its scale parameter since
    temporaries are numbered by a process-wide counter), which a skipped
    or broken layer moves by tens of percent where the loss at random
    weights barely moves."""
    scale = f"enc_{config['model']['n_layers'] - 1}_ln2.w_0"
    for op in program.global_block.ops:
        if op.type == "layer_norm" and scale in op.input_arg_names():
            return {"encoder_out": op.outputs["Y"][0]}
    raise KeyError(f"no layer_norm scaled by {scale} in the program")


def reference(config, weights, batch, **kw):
    """The plain float32 forward of ``batch`` (a one-shard feed) under
    ``weights`` (the scope's parameters by name): {"loss": ...,
    "encoder_out": ...}."""
    import jax

    from benchmark.reference import bert

    m = config["model"]
    fn = jax.jit(lambda w, f: bert.pretrain_forward(
        w, f, m["n_layers"], m["n_heads"], m["dropout_prob"], **kw))
    loss, hidden = fn(weights, batch)
    return {"loss": loss, "encoder_out": hidden}


def flops_per_sample(config):
    from benchmark import flops

    return flops.bert_pretrain_flops_per_sample(config["model"])
