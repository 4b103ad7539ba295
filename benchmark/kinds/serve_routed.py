"""The ``serve`` kind for a model that routes tokens to experts: the
same set-up, load, window and metrics (``kinds/serve.py``, loaded from
its file and run as it is), with two things of its own.

**The check follows the served routing.**  Top-k over hundreds of
experts on random weights has near-ties: the k-th and (k+1)-th scores of
a token lie closer than the rounding of its bf16 activations in about
one token-layer in ten, and a flipped choice of a held expert moves a
logit by a per cent without anything being wrong.  So each check
request records, beside its logits, the expert ids it routed to at every
position and layer (``req.records["moe_topk"]``); the reference follows
those ids, but only after measuring each against its OWN float32 scores
(``gap``: how far the worst followed choice lies below the reference's
k-th largest score; 0 where they agree), and computes the weights, the
experts and everything else itself.  ``correct`` needs four things.
The RMS of a request's logit errors over the RMS of its logits within
``logit_rms_rtol``: an RMS over 100,000 logits barely moves from seed to
seed where a maximum does, so its limit can lie between the served
reading and that of the same model with its recurrent state in
bfloat16, a factor of 1.35 apart; one wrong logit of a position, a
wrong page, mask, expert or state update is far above it.  (The worst
|dlogit| / max |logit| of a position is reported and limits nothing: it
moves 12 % from seed to seed and the lower precision reads 1.2 times
the largest served reading, so no limit on it can sit between.)  The
worst gap within ``route_eps``: a router that picks experts the
reference's scores do not bear out.  The share of token-layers where
the served choice is not the reference's OWN top-k (any gap above
zero: the reference's routing, followed by nobody) within
``reroute_share``: near-ties flip under the bf16 activations' rounding
in a few token-layers of a hundred, and a router computed in bfloat16
flips half as many again.  The recurrent state's bytes float32's: a
state kept in half the bytes.

**The window's counters** also hold the model's own (``more_counters``
of the workload file's ``serve``), for the readers of its layers, and
the callers' latency tails ride along as per-layer readings
(``sources["serve"]["caller_ms"]``): at capacity they swing with the
window's mix, so they bound nothing, and the next change to the prefill
will trade them against ``serve_tok_s``.
"""
import numpy as np


def check_logits(bench, srv, weights, seed):
    """Seeded requests through the real server, every step's logits and
    routing recorded, against the plain float32 whole-sequence forward
    given the server's own tokens and routing."""
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
    worst = worst_rms = worst_gap = 0.0
    finite, flips, routed_layers = True, 0, 0
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=1100)
        got = np.stack([np.asarray(x) for x in r.logits_trace])
        n = len(p) + n_new - 1
        seq = np.zeros((pad,), np.int32)
        seq[:n] = p + toks[:n_new - 1]
        # a prefill's entry holds every prompt position, a step's one
        routed = r.records["moe_topk"]
        ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
        routing = np.zeros((pad,) + ids.shape[1:], np.int32)
        routing[:n] = ids[:n]
        want, gap = bench.model.reference_logits(
            bench.config, weights, jnp.asarray(seq), routing=routing)
        want = np.asarray(want)[len(p) - 1:len(p) - 1 + n_new]
        gap = np.asarray(gap)[:n]
        finite = finite and len(toks) == n_new and got.shape == want.shape \
            and bool(np.isfinite(got).all())
        worst_gap = max(worst_gap, float(gap.max()))
        flips += int((gap > 0).sum())
        routed_layers += gap.size
        worst_rms = max(worst_rms, float(
            np.sqrt(np.mean(np.square(got - want))
                    / np.mean(np.square(want)))))
        for j in range(n_new):
            worst = max(worst, float(np.abs(got[j] - want[j]).max()
                                     / np.abs(want[j]).max()))
    eps, rms_rtol = float(chk["route_eps"]), float(chk["logit_rms_rtol"])
    share, share_max = flips / max(routed_layers, 1), \
        float(chk["reroute_share"])
    state_bytes, want_bytes = state_bytes_read_and_owed(bench.config)
    return (finite and worst_rms <= rms_rtol and worst_gap <= eps
            and share <= share_max and state_bytes == want_bytes), {
        "worst_logit_rel_err": worst,
        "worst_logit_rms_rel_err": worst_rms, "logit_rms_rtol": rms_rtol,
        "worst_route_gap": worst_gap, "route_eps": eps,
        "token_layers_rerouted": flips, "token_layers": routed_layers,
        "rerouted_share": share, "reroute_share": share_max,
        "state_bytes": state_bytes, "state_bytes_float32": want_bytes,
        "prompt_lens": [len(p) for p in prompts], "positions": n_new}


def state_bytes_read_and_owed(config):
    """(the program's ``decode_state_bytes`` gauge, what float32 state
    of the configuration's sizes takes).  A recurrent state kept in
    bfloat16 moves a logit no further than the bf16 weights' own
    rounding does (the workload file has both readings), so the logits
    cannot hold the program to float32 state; its size can."""
    from paddle_tpu.monitor import stat_get

    m, slots = config["model"], config["serving"]["slots"]
    c = m["lin_heads"] * m["lin_head_dim"]
    one_layer = c * m["lin_head_dim"] + (m["conv_kernel"] - 1) * 3 * c
    return stat_get("decode_state_bytes"), \
        4 * slots * m["layer_kinds"].count("recurrent") * one_layer


def run(bench):
    from benchmark import run as bench_run

    serve = bench_run.load_piece(bench.cell["root"], bench.cell["bench_dir"],
                                 "kinds", "serve")
    serve.check_logits = check_logits
    serve._COUNTERS = serve._COUNTERS + tuple(
        bench.spec["serve"].get("more_counters", ()))
    window, in_window = {}, serve.in_window

    def keep(records, t_open, t_close):
        window.update(in_window(records, t_open, t_close))
        return window

    serve.in_window = keep
    result = serve.run(bench)

    def pct(values, q):
        return float(np.percentile(values, q)) * 1e3 if values else None

    result["sources"]["serve"]["caller_ms"] = {
        "ttft_p90": pct(window.get("ttft_s"), 90),
        "itl_p99": pct(window.get("itl_s"), 99)}
    return result
