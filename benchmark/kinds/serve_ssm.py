"""The ``serve_recurrent`` kind (loaded from its file and run as it is:
the ``serve`` kind's set-up, load, window and metrics, the model's
counters, the callers' tails, the RMS of a request's logit errors, the
worst gap as a reading, finiteness) for a dense model whose recurrent
layers are selective STATE-SPACE layers.

One function differs.  ``serve_recurrent`` reckons a delta rule's state
(a matrix a head and three convolutions' rows) from keys this model does
not have; here float32 state owes, a slot a recurrent layer, the ``d_state x
d_inner`` state and the ``d_conv - 1`` rows of ``d_inner`` the
convolution looks back on, read from the configuration's ``model`` keys.
The program's ``decode_state_bytes`` gauge (the slabs' logical bytes,
both arrays) must equal it: a state kept in half the bytes fails by its
size whatever its logits read.  The window's sources also carry the
sizes' gauges and the pool's positions, for the readers that divide
them.
"""
import os

GAUGES = ("decode_kv_pool_bytes", "decode_state_bytes")


def _recurrent():
    """``kinds/serve_recurrent.py`` beside this file, loaded afresh."""
    from benchmark import run as bench_run

    kinds = os.path.dirname(os.path.abspath(__file__))
    bench_dir = os.path.dirname(kinds)
    return bench_run.load_piece(os.path.dirname(bench_dir),
                                os.path.basename(bench_dir), "kinds",
                                "serve_recurrent")


def state_bytes_read_and_owed(config):
    """(the program's ``decode_state_bytes`` gauge, what float32 state
    of the configuration's sizes takes)."""
    from paddle_tpu.monitor import stat_get

    m, slots = config["model"], config["serving"]["slots"]
    one_layer = m["d_state"] * m["d_inner"] \
        + (m["d_conv"] - 1) * m["d_inner"]
    return stat_get("decode_state_bytes"), \
        4 * slots * m["layer_kinds"].count("recurrent") * one_layer


def run(bench):
    from paddle_tpu.monitor import stat_get

    recurrent = _recurrent()
    recurrent.state_bytes_read_and_owed = state_bytes_read_and_owed
    result = recurrent.run(bench)
    dcfg = bench.model.decode_config(bench.config)
    pages = dcfg.num_pages or \
        dcfg.slots * (dcfg.max_seq_len // dcfg.page_size) + 1
    result["sources"]["serve"].update(
        kv_pool_positions=int(pages) * dcfg.page_size,
        gauges={n: stat_get(n) for n in GAUGES})
    return result
