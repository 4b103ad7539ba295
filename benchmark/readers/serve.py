"""Per-layer readers of the serving cells.  A reader takes the run's
``sources`` and its metric file's ``params`` and returns a number, or
None where there is nothing to read.  Counters and histograms are the
program's own (``serving/decode.py``), read as differences over the
window."""
from benchmark import flops


def _counters(sources):
    return sources.get("serve", {}).get("counters")


def slot_occupancy(sources, params):
    """Engine loop: tokens per decode step over the slots, in %.  The
    first token of a request comes from its prefill, not from a step,
    so prefills are taken off the token count."""
    c = _counters(sources)
    if not c or not c["decode_steps"]:
        return None
    stepped = c["decode_tokens_total"] - c["decode_prefills"]
    return 100.0 * stepped / c["decode_steps"] / sources["serve"]["slots"]


def _mean_ms(sources, name):
    c = _counters(sources)
    if not c or not c[name]["count"]:
        return None
    return 1e3 * c[name]["sum"] / c[name]["count"]


def decode_step_ms(sources, params):
    """Engine loop: mean of ``decode_step_seconds`` over the window
    (dispatch + sync, host clock).  The mean, not the median: the
    histogram's buckets are powers of two, so a median read from it is
    an interpolation inside a bucket twice as wide as its lower edge;
    sum and count are exact."""
    return _mean_ms(sources, "decode_step_seconds")


def device_ms_per_run(sources, params):
    """Model step, serving: device time of one run of a jitted program
    (``params["module"]``, as the trace's "XLA Modules" line names it:
    ``jit_step`` is the joint decode step, ``jit_prefill`` the
    whole-prompt prefills of every bucket), mean over its runs inside
    the traced window."""
    trace = sources.get("trace")
    row = (trace or {}).get("modules", {}).get(params.get("module"))
    if not row or not row["count"]:
        return None
    return 1e3 * row["total_s"] / row["count"]


def decode_attn_roofline(sources, params):
    """Kernels, serving: the least time the chip could take to read the
    K and V bytes the live slots' pages hold (memory-bound: bytes over
    HBM bandwidth), over the paged-attention kernel's device time in
    the trace; in %.  None while the kernel cannot be told apart by
    name in the trace."""
    trace, serve = sources.get("trace"), sources.get("serve")
    if not trace or not serve:
        return None
    kernel_s = trace.get("kernel_s", {}).get(params.get("kernel"))
    if not kernel_s:
        return None
    need = flops.decode_attention_bytes(
        serve["decode_contexts"], serve["page_size"],
        serve["kv_bytes_per_token"])
    least_s = need / (sources["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / kernel_s
