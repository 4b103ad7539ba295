"""The ``serve_routed`` kind (loaded from its file and run as it is: the
check that follows the served routing, the model's counters, the
callers' tails) for a model whose cache keeps ONE latent row a position
a layer in place of per-head K and V.

One function differs.  ``serve_routed`` holds a recurrent state to
float32 by its size; here the size that is held is the latent pool's:
the program's ``decode_latent_bytes`` gauge must equal what ``num_pages``
pages of ``page_size`` rows a layer take at the configuration's widths
and cache dtype, a row being the latent and its rotary key
(``kv_rank + rope_dim`` lanes) up to whole tiles of 128 lanes and no
more.  A cache of expanded K and V (64 heads of 192 + 128 lanes, 35.6
times the bytes) fails it, and so does one in 8 bits, or one that pads a
row further.  The check's result carries the pair under ``latent_bytes``
/ ``latent_bytes_owed``, and the row's bytes as stored beside the
published ones (``latent_row_bytes`` / ``latent_row_bytes_published``).
"""
_LANES = 128


def latent_bytes_read_and_owed(bench):
    """(the program's ``decode_latent_bytes`` gauge, the latent pool's
    bytes at the configuration's sizes, positions x layers in it)."""
    import numpy as np

    from paddle_tpu.monitor import stat_get

    m = bench.config["model"]
    dcfg = bench.model.decode_config(bench.config)
    # the engine's default pool where the configuration names none: a
    # full table a slot and the trash page
    pages = dcfg.num_pages or \
        dcfg.slots * (dcfg.max_seq_len // dcfg.page_size) + 1
    rows = m["num_layers"] * int(pages) * dcfg.page_size
    lanes = -(-(m["kv_rank"] + m["rope_dim"]) // _LANES) * _LANES
    return stat_get("decode_latent_bytes"), \
        rows * lanes * np.dtype(dcfg.cache_dtype).itemsize, rows


def run(bench):
    import numpy as np

    from benchmark import run as bench_run

    routed = bench_run.load_piece(bench.cell["root"],
                                  bench.cell["bench_dir"], "kinds",
                                  "serve_routed")
    routed.state_bytes_read_and_owed = \
        lambda config: latent_bytes_read_and_owed(bench)[:2]
    result = routed.run(bench)
    checks, m = result["checks"], bench.config["model"]
    checks["latent_bytes"] = checks["state_bytes"]
    checks["latent_bytes_owed"] = checks["state_bytes_float32"]
    rows = latent_bytes_read_and_owed(bench)[2]
    checks["latent_row_bytes"] = checks["latent_bytes"] / rows
    checks["latent_row_bytes_published"] = (m["kv_rank"] + m["rope_dim"]) \
        * np.dtype(bench.config["serving"]["cache_dtype"]).itemsize
    result["sources"]["serve"]["latent_pool_rows"] = rows
    return result
