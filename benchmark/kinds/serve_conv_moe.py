"""The ``serve_routed`` kind (loaded from its file and run as it is: the
``serve`` kind's set-up, load, window and metrics, the model's counters,
the callers' tails, the check that follows the served routing) for a
model whose recurrent layers are gated short convolutions.

Two things differ.

**The state's size.**  ``serve_routed`` holds Solar's recurrent state (a
matrix a head and a tail of q | k | v) to float32 by its size; here the
state is the convolution's tail alone: the program's
``decode_state_bytes`` gauge must equal ``slots x convolution layers x
(conv_kernel - 1) x d_model x 4 B``.

**One limit more: the served logits' error over that of the reference
at the STATED precision.**  Twelve layers of bfloat16 operands on random
weights leave the logits 2 % (RMS) from the float32 reference's, and
that reading moves by 3 % (one sd) from seed to seed with the prompts'
lengths and the weights, so its limit leaves the largest sound reading
16 %.  A program that also rounds every product's result, every norm
and the router's operands to bfloat16 (the precision below the stated
one) reads 1.4 times the served model AT THE SAME SEED, and its
smallest reading passes that limit by 7 % only: room on one side.  So
the check forms the reference a second time on the same tokens, weights
and routing with both operands of every matrix product rounded to the
model's ``dtype`` and nothing else (the reference's ``operands``: the
equations at the precision the configuration states) and divides: the
served model's worst RMS error over that pass's worst RMS error, both
against the float32 reference.  Rounding is chaotic (a sum that differs
in its last bit rounds the other way one time in a few thousand, and
within a layer or two the two sets of rounding errors are independent),
so the second pass does not track the served logits; it has the same
NUMBER of roundings through the same weights, and the ratio is near 1
whatever the seed does to both.  ``rms_over_stated_max`` lies between
that and what the lower precision reads (the workload file has both).

The window's sources also carry the gauge a reader needs (a gauge has no
delta over a window) and the positions of a pool layer, as
``serve_looped``'s do.
"""
import types

import numpy as np

GAUGES = ("decode_kv_pool_bytes",)


def state_bytes_read_and_owed(config):
    """(the program's ``decode_state_bytes`` gauge, what float32 tails
    of the configuration's sizes take)."""
    from paddle_tpu.monitor import stat_get

    from benchmark import flops_conv_moe

    m = config["model"]
    return stat_get("decode_state_bytes"), flops_conv_moe.conv_tail_bytes(
        config["serving"]["slots"], m["layer_kinds"].count("recurrent"),
        m["conv_kernel"], m["d_model"])


def over_the_stated_precision(check_logits):
    """``serve_routed``'s ``check_logits`` with the limit of the module's
    header on top: every reference pass it asks for is followed by one at
    the stated precision, of which the squared errors and the squared
    logits a position are kept; the ratio is formed over the positions
    the check compared.  A model whose ``dtype`` rounds nothing
    (float32: the tests' tiny sizes) has a second pass equal to the
    first and no ratio; ``rms_over_stated_max`` absent or None: the
    ratio is a reading."""

    def check(bench, srv, weights, seed):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def squares(stated, want):
            """A position's squared errors and squared logits, with no
            plane of differences beside the two planes of logits."""
            return (jnp.sum(jnp.square(stated - want), axis=-1),
                    jnp.sum(jnp.square(want), axis=-1))

        reference = bench.model.reference_logits
        operands = {"operands": bench.config["model"]["dtype"]}
        passes = []

        def both(config, weights, seq, routing):
            want, gap = reference(config, weights, seq, routing=routing)
            stated, _ = reference(config, weights, seq, routing=routing,
                                  dims_=operands)
            passes.append(tuple(map(np.asarray, squares(stated, want))))
            return want, gap

        ok, checks = check_logits(
            types.SimpleNamespace(
                spec=bench.spec, config=bench.config,
                model=types.SimpleNamespace(reference_logits=both)),
            srv, weights, seed)
        new = checks["positions"]
        stated = max(
            float(np.sqrt(err[n - 1:n - 1 + new].sum()
                          / sq[n - 1:n - 1 + new].sum()))
            for (err, sq), n in zip(passes, checks["prompt_lens"]))
        ratio = checks["worst_logit_rms_rel_err"] / stated if stated \
            else None
        most = bench.spec["check"].get("rms_over_stated_max")
        return bool(ok and (most is None or ratio is None
                            or ratio <= float(most))), dict(
            checks, stated_logit_rms_rel_err=stated,
            logit_rms_over_stated=ratio, rms_over_stated_max=most)

    return check


def routed_kind(cell):
    """``kinds/serve_routed.py`` loaded from its file, with this kind's
    size check and limit in place."""
    from benchmark import run as bench_run

    routed = bench_run.load_piece(cell["root"], cell["bench_dir"], "kinds",
                                  "serve_routed")
    routed.state_bytes_read_and_owed = state_bytes_read_and_owed
    routed.check_logits = over_the_stated_precision(routed.check_logits)
    return routed


def pool_positions(bench):
    """Positions of one layer of the page pools."""
    dcfg = bench.model.decode_config(bench.config)
    pages = dcfg.num_pages or \
        dcfg.slots * (dcfg.max_seq_len // dcfg.page_size) + 1
    return int(pages) * dcfg.page_size


def run(bench):
    from paddle_tpu.monitor import stat_get

    result = routed_kind(bench.cell).run(bench)
    result["sources"]["serve"].update(
        kv_pool_positions=pool_positions(bench),
        gauges={n: stat_get(n) for n in GAUGES})
    return result
