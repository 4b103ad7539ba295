"""The ``serve_routed`` kind (loaded from its file and run as it is: the
``serve`` kind's set-up, load, window and metrics, the model's counters,
the callers' tails) with ``serve_recurrent``'s plain check (no routing
to follow: the RMS of the logit errors and a size) for a model
whose stack runs several times on the same weights, each pass with K
and V of its own.

One function differs.  ``serve_recurrent`` holds a recurrent state to
float32 by its size; here the size that is held is the page pools': the
program's ``decode_kv_pool_bytes`` gauge must equal what ``num_pages``
pages of ``page_size`` positions take at ``loops x num_layers`` cache
layers of the configuration's heads in its cache dtype, K and V.  A
cache that shares one pass's K and V between the passes (a quarter of
the layers), pages in 8 bits, or a pool of other depth than the passes
need fails it whatever its logits read.  The check's result carries the
pair under ``kv_pool_bytes`` / ``kv_pool_bytes_owed``, and the window's
sources the gauges a reader needs (a gauge has no delta over a window).
"""


def pool_bytes_read_and_owed(bench):
    """(the program's ``decode_kv_pool_bytes`` gauge, the two pools'
    bytes at the configuration's sizes, positions in a pool layer)."""
    import numpy as np

    from paddle_tpu.monitor import stat_get

    m = bench.config["model"]
    dcfg = bench.model.decode_config(bench.config)
    # the engine's default pool where the configuration names none: a
    # full table a slot and the trash page
    pages = dcfg.num_pages or \
        dcfg.slots * (dcfg.max_seq_len // dcfg.page_size) + 1
    positions = int(pages) * dcfg.page_size
    owed = m["loops"] * m["num_layers"] * positions * 2 \
        * m["num_heads"] * m["head_dim"] \
        * np.dtype(dcfg.cache_dtype).itemsize
    return stat_get("decode_kv_pool_bytes"), owed, positions


def check_logits(bench, srv, weights, seed):
    """``serve_recurrent``'s plain check with the pools' size in place
    of a recurrent state's."""
    from benchmark import run as bench_run

    recurrent = bench_run.load_piece(bench.cell["root"],
                                     bench.cell["bench_dir"], "kinds",
                                     "serve_recurrent")
    recurrent.state_bytes_read_and_owed = \
        lambda config: pool_bytes_read_and_owed(bench)[:2]
    ok, checks = recurrent.check_logits(bench, srv, weights, seed)
    checks["kv_pool_bytes"] = checks.pop("state_bytes")
    checks["kv_pool_bytes_owed"] = checks.pop("state_bytes_float32")
    return ok, checks


def run(bench):
    from paddle_tpu.monitor import stat_get

    from benchmark import run as bench_run

    routed = bench_run.load_piece(bench.cell["root"],
                                  bench.cell["bench_dir"], "kinds",
                                  "serve_routed")
    routed.check_logits = check_logits
    result = routed.run(bench)
    result["sources"]["serve"].update(
        kv_pool_positions=pool_bytes_read_and_owed(bench)[2],
        gauges={n: stat_get(n) for n in ("decode_kv_pool_bytes",
                                         "decode_cache_layers")})
    return result
