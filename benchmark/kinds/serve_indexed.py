"""The ``serve_routed`` kind (loaded from its file and run as it is: the
``serve`` kind's set-up, load, window and metrics, the model's counters,
the callers' tails) for a model whose attention reads the positions a
learned indexer selects, the index keys in a THIRD pool beside K and V.

The check differs (``check_logits`` here in ``serve_routed``'s place).

**It follows the served selections as it follows the served routing.**
The 2,048th and 2,049th largest of up to 8,286 index scores lie closer
than the rounding of the bf16 keys in a share of the rows, and a swapped
position moves a logit without anything being wrong.  So each check
request records, beside its logits and expert ids, the positions it
attended a row a layer (``req.records["index_selected"]``: a bit a pair
for the prompt's rows, the positions for a decoded token's); the
reference follows them, but only after measuring each row against its
OWN float32 scores: ``select_gap``, how far below its own
2,048th-largest score the worst followed position lies, in standard
deviations of the row's scores over its live positions, and ``moved``,
the followed positions that are not in its own selection.  ``correct``
needs, beside what ``serve_routed`` holds (the logits' RMS error within
``logit_rms_rtol``, the worst routing gap within ``route_eps``, the
re-routed share within ``reroute_share``): the worst ``select_gap``
within ``select_eps`` (an indexer that picks positions the reference's
scores do not bear out) and the share of selected positions that moved
within ``reselect_share`` (near-ties flip under the rounding in a few
positions of a hundred; keys cached in 8 bits flip more).

**The sizes held are the pools'.**  The program's ``decode_index_bytes``
gauge must equal what ``num_pages`` pages of ``page_size`` rows a layer
take at the configuration's widths and cache dtype, a row being the
index key (``index_dim`` lanes) up to whole tiles of 128 lanes and no
more (256 B stored where 128 B are published), and
``decode_kv_pool_bytes`` what K and V of every K/V head take at the
cache dtype: expanded, 8-bit or further-padded pools fail it
whatever their logits read.

**What a window attempts.**  ``serve`` counts the requests that END
inside the window.  This cell's requests outlast set-up and window
together (8,192-16,384 new tokens at the step's time), so none ends
there: the window then reports, as attempted, the requests it delivered
tokens to (the live slots of its steps, none failed), not zero.

The window's sources also carry the sizes' gauges and the pools'
positions, for the readers that divide them (``decode_kv_pool_bytes``
there is the bytes of BOTH: what a cached position takes over the model
is K, V and the index key).
"""
import numpy as np

INDEX_RECORD = "index_selected"
_LANES = 128


def pool_bytes_read_and_owed(bench):
    """((index gauge, owed), (K/V gauge, owed), positions in a pool
    layer)."""
    from paddle_tpu.monitor import stat_get

    m = bench.config["model"]
    dcfg = bench.model.decode_config(bench.config)
    pages = dcfg.num_pages or \
        dcfg.slots * (dcfg.max_seq_len // dcfg.page_size) + 1
    rows = int(pages) * dcfg.page_size
    item = np.dtype(dcfg.cache_dtype).itemsize
    index_owed = m["num_layers"] * rows * item \
        * -(-m["index_dim"] // _LANES) * _LANES
    kv_owed = m["num_layers"] * rows * 2 * m["num_kv_heads"] \
        * m["head_dim"] * item
    return (stat_get("decode_index_bytes"), index_owed), \
        (stat_get("decode_kv_pool_bytes"), kv_owed), rows


def selections_of(records, n_prompt, n, pad, layers):
    """A list of ``layers`` bool arrays ``[pad, pad]``: what the served
    model attended a row a layer, from a request's recorded selections
    (the prompt's entry: a bit a pair, all zeros for a row that attends
    every live position; a step's: the positions, -1 beyond the live
    ones).  Padding rows attend position 0."""
    from paddle_tpu.ops.indexed_attention import unpack_bits

    prompt = unpack_bits(records[0])            # [n_prompt, L, bucket]
    causal = np.tri(n_prompt, min(pad, prompt.shape[-1]), dtype=bool)
    out = []
    for l in range(layers):
        sel = np.zeros((pad, pad), bool)
        rows = prompt[:, l, :causal.shape[1]]
        whole = ~rows.any(axis=1, keepdims=True)
        sel[:n_prompt, :causal.shape[1]] = np.where(whole, causal,
                                                    rows & causal)
        for j, step in enumerate(records[1:n - n_prompt + 1]):
            pos = np.asarray(step[l])
            sel[n_prompt + j, pos[pos >= 0]] = True
        sel[n:, 0] = True
        out.append(sel)
    return out


def check_logits(bench, srv, weights, seed):
    """Seeded requests through the real server, every step's logits,
    routing and selections recorded, against the plain float32
    whole-sequence forward given the server's own tokens, routing and
    selections."""
    import jax.numpy as jnp

    chk, m = bench.spec["check"], bench.config["model"]
    vocab, layers, topk = m["vocab_size"], m["num_layers"], m["index_topk"]
    n_new, pad = int(chk["new_tokens"]), int(chk["pad"])
    rng = np.random.RandomState(seed)
    lo, hi = chk["prompt_len"]
    prompts = [rng.randint(0, vocab, rng.randint(lo, hi + 1)).tolist()
               for _ in range(int(chk["requests"]))]
    reqs = [srv.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = worst_rms = worst_gap = worst_sgap = 0.0
    finite, flips, routed_layers, moved, selected = True, 0, 0, 0, 0
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=1100)
        got = np.stack([np.asarray(x) for x in r.logits_trace])
        n = len(p) + n_new - 1
        seq = np.zeros((pad,), np.int32)
        seq[:n] = p + toks[:n_new - 1]
        routed = r.records["moe_topk"]
        ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
        routing = np.zeros((pad,) + ids.shape[1:], np.int32)
        routing[:n] = ids[:n]
        want, gap, sgap, moves = bench.model.reference_logits(
            bench.config, weights, jnp.asarray(seq), routing=routing,
            selections=selections_of(r.records[INDEX_RECORD], len(p), n,
                                     pad, layers),
            rows=(len(p) - 1, n_new))
        want, gap = np.asarray(want), np.asarray(gap)[:n]
        sgap, moves = np.asarray(sgap)[:n], np.asarray(moves)[:n]
        finite = finite and len(toks) == n_new and got.shape == want.shape \
            and bool(np.isfinite(got).all()) \
            and bool(np.isfinite(sgap).all())
        worst_gap = max(worst_gap, float(gap.max()))
        flips += int((gap > 0).sum())
        routed_layers += gap.size
        worst_sgap = max(worst_sgap, float(sgap.max()))
        moved += int(moves.sum())
        selected += layers * int(np.minimum(np.arange(n) + 1, topk).sum())
        worst_rms = max(worst_rms, float(
            np.sqrt(np.mean(np.square(got - want))
                    / np.mean(np.square(want)))))
        for j in range(n_new):
            worst = max(worst, float(np.abs(got[j] - want[j]).max()
                                     / np.abs(want[j]).max()))
    eps, rms_rtol = float(chk["route_eps"]), float(chk["logit_rms_rtol"])
    share, share_max = flips / max(routed_layers, 1), \
        float(chk["reroute_share"])
    seps, moved_max = float(chk["select_eps"]), float(chk["reselect_share"])
    moved_share = moved / max(selected, 1)
    (index_bytes, index_owed), (kv_bytes, kv_owed), _ = \
        pool_bytes_read_and_owed(bench)
    return (finite and worst_rms <= rms_rtol and worst_gap <= eps
            and share <= share_max and worst_sgap <= seps
            and moved_share <= moved_max and index_bytes == index_owed
            and kv_bytes == kv_owed), {
        "worst_logit_rel_err": worst,
        "worst_logit_rms_rel_err": worst_rms, "logit_rms_rtol": rms_rtol,
        "worst_route_gap": worst_gap, "route_eps": eps,
        "token_layers_rerouted": flips, "token_layers": routed_layers,
        "rerouted_share": share, "reroute_share": share_max,
        "worst_select_gap": worst_sgap, "select_eps": seps,
        "positions_reselected": moved, "positions_selected": selected,
        "reselected_share": moved_share, "reselect_share": moved_max,
        "index_bytes": index_bytes, "index_bytes_owed": index_owed,
        "kv_pool_bytes": kv_bytes, "kv_pool_bytes_owed": kv_owed,
        "prompt_lens": [len(p) for p in prompts], "positions": n_new}


def run(bench):
    from benchmark import run as bench_run
    from paddle_tpu.monitor import stat_get

    routed = bench_run.load_piece(bench.cell["root"],
                                  bench.cell["bench_dir"], "kinds",
                                  "serve_routed")
    routed.check_logits = check_logits
    result = routed.run(bench)
    c = result["sources"]["serve"]["counters"]
    if not result["attempted"] and c.get("decode_steps"):
        result["attempted"] = round(
            (c["decode_tokens_total"] - c["decode_prefills"])
            / c["decode_steps"])
    rows = pool_bytes_read_and_owed(bench)[2]
    held = {n: stat_get(n) for n in ("decode_kv_pool_bytes",
                                     "decode_index_bytes")}
    result["sources"]["serve"].update(
        kv_pool_positions=rows, index_pool_rows=rows
        * bench.config["model"]["num_layers"],
        gauges={"decode_index_bytes": held["decode_index_bytes"],
                "decode_kv_pool_bytes": sum(held.values())})
    return result
