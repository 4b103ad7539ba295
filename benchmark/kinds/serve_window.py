"""The ``serve_routed`` kind (loaded from its file and run as it is: the
check that follows the served routing, the model's counters, the
callers' tails) for a model whose per-slot state is a RING of pages a
window layer instead of a recurrent matrix.

One function differs.  ``serve_routed`` holds a recurrent state to
float32 by its size; here the size that is held is the window layers':
the program's ``decode_window_bytes`` gauge must equal what ``slots``
rings of ``ceil(window / page) + 1`` pages a window layer take at the
configuration's widths and cache dtype, a number with no term in
``max_seq_len``.  A cache that kept every position of a window layer
(11.7 GB at this cell's sizes) fails it before it fails to fit.  The
check's result carries the pair under ``window_bytes`` /
``window_bytes_owed`` too.
"""


def window_bytes_read_and_owed(bench):
    """(the program's ``decode_window_bytes`` gauge, the rings' bytes at
    the configuration's sizes)."""
    import numpy as np

    from paddle_tpu.monitor import stat_get

    m = bench.config["model"]
    dcfg = bench.model.decode_config(bench.config)
    ring = -(-m["window"] // dcfg.page_size) + 1
    lanes = m["window_kv_heads"] * (m["head_dim"] + m["v_head_dim"])
    owed = m["layer_kinds"].count("window") * (dcfg.slots * ring + 1) \
        * dcfg.page_size * lanes * np.dtype(dcfg.cache_dtype).itemsize
    return stat_get("decode_window_bytes"), owed


def run(bench):
    from benchmark import run as bench_run

    routed = bench_run.load_piece(bench.cell["root"],
                                  bench.cell["bench_dir"], "kinds",
                                  "serve_routed")
    routed.state_bytes_read_and_owed = \
        lambda config: window_bytes_read_and_owed(bench)
    result = routed.run(bench)
    checks = result["checks"]
    checks["window_bytes"] = checks["state_bytes"]
    checks["window_bytes_owed"] = checks["state_bytes_float32"]
    return result
