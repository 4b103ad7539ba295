"""The ``serve_routed`` kind (loaded from its file and run as it is: the
``serve`` kind's set-up, load, window and metrics, the model's counters,
the callers' tails) for a DENSE model with recurrent layers: there is no
routing to follow, so the check is the plain one with an RMS.

**The check.**  Seeded requests of the cell's own prompt lengths through
the real server: the whole-prompt prefill (the recurrent layers in
chunks, the full-attention layers in blocks), then ``new_tokens - 1``
joint steps through pages and slabs, every step's logits recorded,
against the plain float32 whole-sequence reference given the server's
own tokens (its recurrence token by token; the head over the compared
rows only).  ``correct`` needs the RMS of a request's logit errors over
the RMS of its logits within ``logit_rms_rtol`` (an RMS over millions of
logits barely moves from seed to seed where a maximum does; the
workload file has the served readings and the controls' beside the
limit) and the recurrent state's bytes float32's at the configuration's
sizes (``decode_state_bytes``: a state kept in half the bytes fails by
its size whatever its logits read).  The worst |dlogit| / max |logit| of
a position is reported and limits nothing.
"""
import numpy as np


def check_logits(bench, srv, weights, seed):
    import jax.numpy as jnp

    chk = bench.spec["check"]
    vocab = bench.config["model"]["vocab_size"]
    n_new, pad = int(chk["new_tokens"]), int(chk["pad"])
    rng = np.random.RandomState(seed)
    lo, hi = chk["prompt_len"]
    prompts = [rng.randint(0, vocab, rng.randint(lo, hi + 1)).tolist()
               for _ in range(int(chk["requests"]))]
    reqs = [srv.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = worst_rms = 0.0
    finite = True
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=1100)
        got = np.stack([np.asarray(x) for x in r.logits_trace])
        n = len(p) + n_new - 1
        seq = np.zeros((pad,), np.int32)
        seq[:n] = p + toks[:n_new - 1]
        want = np.asarray(bench.model.reference_logits(
            bench.config, weights, jnp.asarray(seq),
            rows=(len(p) - 1, n_new)))
        finite = finite and len(toks) == n_new and got.shape == want.shape \
            and bool(np.isfinite(got).all())
        worst_rms = max(worst_rms, float(
            np.sqrt(np.mean(np.square(got - want))
                    / np.mean(np.square(want)))))
        worst = max(worst, float((np.abs(got - want).max(axis=1)
                                  / np.abs(want).max(axis=1)).max()))
    rms_rtol = float(chk["logit_rms_rtol"])
    state_bytes, want_bytes = state_bytes_read_and_owed(bench.config)
    return (finite and worst_rms <= rms_rtol
            and state_bytes == want_bytes), {
        "worst_logit_rel_err": worst,
        "worst_logit_rms_rel_err": worst_rms, "logit_rms_rtol": rms_rtol,
        "state_bytes": state_bytes, "state_bytes_float32": want_bytes,
        "prompt_lens": [len(p) for p in prompts], "positions": n_new}


def state_bytes_read_and_owed(config):
    """(the program's ``decode_state_bytes`` gauge, what float32 state
    of the configuration's sizes takes: a matrix of ``d_k x d_v`` a head
    and the convolution's ``K - 1`` rows of q | k | v, a slot a
    recurrent layer)."""
    from paddle_tpu.monitor import stat_get

    m, slots = config["model"], config["serving"]["slots"]
    wide = m["lin_heads"] * (2 * m["lin_key_dim"] + m["lin_value_dim"])
    one_layer = m["lin_heads"] * m["lin_key_dim"] * m["lin_value_dim"] \
        + (m["conv_kernel"] - 1) * wide
    return stat_get("decode_state_bytes"), \
        4 * slots * m["layer_kinds"].count("recurrent") * one_layer


def run(bench):
    from benchmark import run as bench_run

    routed = bench_run.load_piece(bench.cell["root"],
                                  bench.cell["bench_dir"], "kinds",
                                  "serve_routed")
    routed.check_logits = check_logits
    return routed.run(bench)
