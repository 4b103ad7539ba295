"""The ``serve_routed`` kind (loaded from its file and run as it is: the
check that follows the served routing, the model's counters, the
callers' tails) for a model that keeps BOTH a recurrent state a slot (its
linear-attention layers) and one latent row a position (its latent
layers) in one cache.

One function differs.  ``serve_routed`` holds the recurrent state to
float32 by its size, and that stays (its own
``state_bytes_read_and_owed``, unedited: ``decode_state_bytes`` against
float32 state of the configuration's sizes).  Here the latent pool's
size is held BESIDE it, as ``kinds/serve_latent.py`` holds it for a
model that has no other: the program's ``decode_latent_bytes`` gauge
must equal what ``num_pages`` pages of ``page_size`` rows a LATENT layer
take at the configuration's widths and cache dtype, a row being the
latent and the shared key (``kv_rank + rope_dim`` lanes) up to whole
tiles of 128 lanes and no more.  A cache of expanded K and V fails it,
and so does one in 8 bits, one that pads a row further, or one with a
pool layer for every layer of the model.  The check's result carries
both pairs (``state_bytes`` / ``state_bytes_float32``, ``latent_bytes``
/ ``latent_bytes_owed``) and the row's bytes as stored beside the
published ones.
"""
import os

_LANES = 128
GAUGES = ("decode_kv_pool_bytes", "decode_state_bytes", "decode_latent_bytes")


def _routed():
    """``kinds/serve_routed.py`` beside this file, loaded afresh."""
    from benchmark import run as bench_run

    kinds = os.path.dirname(os.path.abspath(__file__))
    bench_dir = os.path.dirname(kinds)
    return bench_run.load_piece(os.path.dirname(bench_dir),
                                os.path.basename(bench_dir), "kinds",
                                "serve_routed")


def latent_bytes_read_and_owed(bench):
    """(the program's ``decode_latent_bytes`` gauge, the latent pool's
    bytes at the configuration's sizes, positions x latent layers)."""
    import numpy as np

    from paddle_tpu.monitor import stat_get

    m = bench.config["model"]
    dcfg = bench.model.decode_config(bench.config)
    # the engine's default pool where the configuration names none: a
    # full table a slot and the trash page
    pages = dcfg.num_pages or \
        dcfg.slots * (dcfg.max_seq_len // dcfg.page_size) + 1
    rows = m["layer_kinds"].count("attention") * int(pages) * dcfg.page_size
    lanes = -(-(m["kv_rank"] + m["rope_dim"]) // _LANES) * _LANES
    return stat_get("decode_latent_bytes"), \
        rows * lanes * np.dtype(dcfg.cache_dtype).itemsize, rows


def check(bench, srv, weights, seed, check_logits=None):
    """``serve_routed``'s check (``check_logits``, its own where none is
    handed: RMS of logits, worst gap, rerouted share, finiteness, the
    state's bytes) and the latent pool's bytes."""
    import numpy as np

    ok, checks = (check_logits or _routed().check_logits)(
        bench, srv, weights, seed)
    m = bench.config["model"]
    read, owed, rows = latent_bytes_read_and_owed(bench)
    checks.update(
        latent_bytes=read, latent_bytes_owed=owed,
        latent_row_bytes=read / rows,
        latent_row_bytes_published=(m["kv_rank"] + m["rope_dim"])
        * np.dtype(bench.config["serving"]["cache_dtype"]).itemsize,
        latent_pool_rows=rows)
    return ok and read == owed, checks


def run(bench):
    from paddle_tpu.monitor import stat_get

    routed = _routed()
    routed_check = routed.check_logits
    routed.check_logits = lambda *a: check(*a, check_logits=routed_check)
    result = routed.run(bench)
    rows = result["checks"]["latent_pool_rows"]
    result["sources"]["serve"].update(
        latent_pool_rows=rows,
        # positions of ONE layer of the pool, and the sizes' gauges: what
        # ``kv_bytes_per_token.serve`` and ``state_bytes_per_slot.serve``
        # divide
        kv_pool_positions=rows
        // bench.config["model"]["layer_kinds"].count("attention"),
        gauges={n: stat_get(n) for n in GAUGES})
    return result
