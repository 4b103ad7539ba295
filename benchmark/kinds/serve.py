"""A serving cell: the model behind the program's ``DecodeServer``, load
from the cell's traffic generator, latencies on the client's own clock
through ``on_token``.

Workload file: ``traffic`` (see the generator), ``serve``: ``replicas``,
``fill_s`` (how long the callers run before the window opens, so that it
sees full, out-of-phase slots); ``check``: ``requests``, ``prompt_len``,
``new_tokens``, ``pad``, ``logit_rtol`` with its reason.

Set-up: weights on the device in one jitted call from the seed, the
server, the correctness check (which also compiles the decode step), one
request per prefill bucket the traffic can hit, then the callers' fill.
Window: ``--seconds`` of the closed loop.  End-to-end metrics:
``serve_tok_s`` (tokens delivered inside the window over its length) and
whatever ``<ttft|itl>_p<NN>_ms`` the manifest names for the cell (the
NN-th percentile, over requests whose first token arrived inside the
window, of submit -> first ``on_token``; over all gaps between
consecutive ``on_token`` calls of one request that end inside it).
Requests in flight when the window closes are not waited for
(``stop(drain=False)``): their tokens inside the window count, they
count neither as done nor as failed.
"""
import re
import time

import numpy as np

_TAIL = re.compile(r"^(ttft|itl)_p(\d+)_ms$")

_COUNTERS = ("decode_tokens_total", "decode_steps", "decode_prefills",
             "decode_step_errors", "decode_prefill_errors",
             "decode_prefill_compiles", "decode_callback_errors")
_HISTOGRAMS = ("decode_step_seconds", "decode_prefill_seconds",
               "ttft_seconds", "tpot_seconds")


def snapshot():
    """The program's counters and histogram (count, sum) pairs, now."""
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.observe.histogram import histogram

    snap = {n: stat_get(n) for n in _COUNTERS}
    for n in _HISTOGRAMS:
        h = histogram(n)
        snap[n] = (h.count, h.sum)
    return snap


def delta(before, after):
    out = {}
    for k, v in after.items():
        if isinstance(v, tuple):
            out[k] = {"count": v[0] - before[k][0],
                      "sum": v[1] - before[k][1]}
        else:
            out[k] = v - before[k]
    return out


def check_logits(bench, srv, weights, seed):
    """Seeded requests through the real server with every step's logits
    recorded, against the plain float32 whole-sequence forward given the
    server's own tokens: worst |dlogit| / max |logit| per position."""
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
    worst, finite = 0.0, True
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=1100)
        got = np.stack([np.asarray(x) for x in r.logits_trace])
        seq = np.zeros((pad,), np.int32)
        seq[:len(p) + n_new - 1] = p + toks[:n_new - 1]
        want = np.asarray(bench.model.reference_logits(
            bench.config, weights, jnp.asarray(seq)))[
                len(p) - 1:len(p) - 1 + n_new]
        finite = finite and len(toks) == n_new and got.shape == want.shape \
            and bool(np.isfinite(got).all())
        for j in range(n_new):
            worst = max(worst, float(np.abs(got[j] - want[j]).max()
                                     / np.abs(want[j]).max()))
    rtol = float(chk["logit_rtol"])
    return finite and worst <= rtol, {
        "worst_logit_rel_err": worst, "logit_rtol": rtol,
        "prompt_lens": [len(p) for p in prompts], "positions": n_new}


def warm_buckets(bench, srv, dcfg, seed):
    """One short request per prefill bucket the traffic's prompt lengths
    fall into: the cell's own shapes and no others."""
    from paddle_tpu.serving.buckets import prefill_bucket_grid

    grid = prefill_bucket_grid(dcfg.max_seq_len, dcfg.page_size)
    lens = {p for p, _ in bench.traffic.size_pool(bench.spec["traffic"])}
    buckets = sorted({next(b for b in grid if b >= n) for n in lens})
    vocab = bench.config["model"]["vocab_size"]
    rng = np.random.RandomState(seed)
    reqs = [srv.submit(rng.randint(0, vocab, b).tolist(), max_new_tokens=2)
            for b in buckets]
    for r in reqs:
        r.result(timeout=1100)
    return buckets


def in_window(records, t_open, t_close):
    """The window's numbers from the client's records."""
    tokens, ttft, itl, contexts = 0, [], [], []
    done = failed = bad = 0
    for rec in records:
        if rec.error is not None:
            # a failed request counts where it ended (the client stamps
            # that), a refused one where it was submitted
            t_end = rec.ts[-1] if rec.ts else rec.t_submit
            failed += t_open <= t_end <= t_close
            continue
        for j, t in enumerate(rec.ts):
            if not t_open <= t <= t_close:
                continue
            tokens += 1
            if j == 0:
                ttft.append(t - rec.t_submit)
            else:
                itl.append(t - rec.ts[j - 1])
                # the decode step that made token j+1 attended the
                # prompt and the j tokens before it
                contexts.append(rec.prompt_len + j)
        if len(rec.ts) >= rec.max_new and t_open <= rec.ts[-1] <= t_close:
            done += 1
            if len(rec.tokens) != rec.max_new:
                bad += 1
    return {"tokens": tokens, "ttft_s": ttft, "itl_s": itl,
            "decode_contexts": contexts, "done": done, "failed": failed,
            "wrong_length": bad}


def run(bench):
    from paddle_tpu.serving import DecodeServer

    spec, config = bench.spec, bench.config
    sv = spec["serve"]
    seed_w, seed_check, seed_warm, seed_load = bench.seeds(4)
    vocab = config["model"]["vocab_size"]

    t0 = time.perf_counter()
    model, weights = bench.model.build(config, seed_w)
    dcfg = bench.model.decode_config(config)
    srv = DecodeServer(model, weights, dcfg,
                       replicas=int(sv.get("replicas", 1)))
    build_s = time.perf_counter() - t0
    srv.start()
    client = None
    try:
        t0 = time.perf_counter()
        logits_ok, checks = check_logits(
            bench, srv, srv.replicas[0].weights, seed_check)
        check_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        buckets = warm_buckets(bench, srv, dcfg, seed_warm)
        warm_s = time.perf_counter() - t0
        bench.emit(phase="setup", build_s=build_s, check_s=check_s,
                   warm_s=warm_s, prefill_buckets=buckets, checks=checks)

        client = bench.traffic.Client(
            srv.submit, bench.traffic.requests(spec["traffic"], vocab,
                                               seed_load),
            spec["traffic"], span=bench.span).start()
        time.sleep(float(sv["fill_s"]))
        seconds = bench.window_seconds()
        with bench.window():
            before = snapshot()
            t_open = time.perf_counter()
            time.sleep(seconds)
            t_close = time.perf_counter()
            after = snapshot()      # before the profiler stops: that takes
            # seconds, and the engine goes on stepping meanwhile
    finally:
        if client is not None:
            client.stop()
        srv.stop(drain=False)

    win = in_window(client.records, t_open, t_close)
    counters = delta(before, after)
    toks = [t for rec in client.records for t in rec.tokens]
    in_vocab = all(0 <= t < vocab for t in toks)
    span_s = t_close - t_open

    def pct(values, q):
        return float(np.percentile(values, q)) * 1e3 if values else None

    def ladder(values):
        """Not metrics: the distribution around the ones that are."""
        out = {f"p{q}": pct(values, q) for q in (50, 75, 90, 95, 99, 100)}
        out["mean"] = float(np.mean(values)) * 1e3 if values else None
        return out

    bench.emit(phase="window", span_s=span_s, requests_done=win["done"],
               requests_failed=win["failed"], tokens=win["tokens"],
               ttft_n=len(win["ttft_s"]), itl_n=len(win["itl_s"]),
               ttft_ms=ladder(win["ttft_s"]), itl_ms=ladder(win["itl_s"]),
               submit_errors=client.submit_errors[:4], counters=counters)
    checks.update(tokens_in_vocab=in_vocab, wrong_length=win["wrong_length"],
                  step_errors=counters["decode_step_errors"],
                  prefill_errors=counters["decode_prefill_errors"],
                  prefill_compiles_in_window=counters[
                      "decode_prefill_compiles"])
    # the cell's latency metrics are named for what they are:
    # <ttft|itl>_p<NN>_ms is the NN-th percentile of that list
    end_to_end = {"serve_tok_s": win["tokens"] / span_s}
    for m in bench.cell["end_to_end"]:
        named = _TAIL.match(m["name"])
        if named:
            end_to_end[m["name"]] = pct(win[named.group(1) + "_s"],
                                        int(named.group(2)))
    correct = (logits_ok and in_vocab and win["wrong_length"] == 0
               and counters["decode_prefill_compiles"] == 0
               and win["tokens"] > 0)
    return {
        "correct": correct,
        "attempted": win["done"] + win["failed"],
        "failed": win["failed"],
        "end_to_end": end_to_end,
        "checks": checks,
        "info": {"build_s": build_s, "check_s": check_s, "warm_s": warm_s},
        "sources": {
            "serve": {
                "counters": counters, "slots": dcfg.slots,
                "page_size": dcfg.page_size, "span_s": span_s,
                "decode_contexts": win["decode_contexts"],
                "kv_bytes_per_token": bench.model.kv_bytes_per_token(config),
            },
        },
    }
