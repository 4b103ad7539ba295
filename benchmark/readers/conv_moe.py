"""Per-layer readers of the cells that serve the convolution/attention
model with every expert of its routed layers on the chip.  Device times
are found as ``readers/hybrid_moe.py`` finds them (the events whose
instruction matches the metric file's ``pattern`` and that start inside
a run of ``params["module"]``): the convolution's one-token update by
the projections and the tails it reads (``conv_w_in`` / ``conv_w_out``
/ ``conv_taps`` operands, the slabs ``%state_2_`` and up: ``%state_0_``
and ``%state_1_`` are the K and V pools), the dense feed-forward's
matmuls by the width only they have, the grouped experts' two
kernels by their own names, the prompt's blocked attention by the score
planes it forms (``f32[heads, rows, keys]`` at the bucket's size, which
nothing else in a prefill has).  Every reader returns None where there
is nothing to read: a run without a trace, a program without such
operations, counters or gauges (the parent of the PR that added them), a
configuration without these keys, or a window without a run.
"""
from benchmark import flops_conv_moe
from benchmark.readers import hybrid_moe

ops_ms_per_run = hybrid_moe.ops_ms_per_run


def _sizes(sources):
    m = sources["config"]["model"]
    return m if "conv_kernel" in m and "tie_head" in m else None


def _counters(sources):
    return (sources.get("serve") or {}).get("counters") or {}


def dense_ffn_ms(sources, params):
    """Kernels: device ms a run of ``params["module"]`` in the COMPUTING
    events that hold an array of the dense feed-forward's width (the
    configuration's ``ffn_dim``) as a result or an operand: the leading
    layers' matmuls and what the compiler fused into them, over a weight
    in HBM or over a prefetched copy of it that names no weight."""
    m = _sizes(sources)
    if m is None or not m.get("dense_layers"):
        return None
    return ops_ms_per_run(sources, dict(params, pattern=(
        r"^(?=.*[\[,]%d[\],]).* (fusion|convolution|dot)\("
        % m["ffn_dim"])))


def moe_prefill_roofline(sources, params):
    """Kernels: the operations of the REAL (row, expert) pairs of the
    window's prompts (the program's ``moe_grouped_pairs``, scaled from
    the prefills that ended in the window to those that ran in the
    trace) at the bf16 peak, over the two grouped kernels' time in the
    window's prefill runs, in %: the grouped form's share of the peak.
    A tile's padding rows are computed and not counted, so it cannot
    pass 100 %."""
    c, m = _counters(sources), _sizes(sources)
    if m is None or not c.get("moe_grouped_pairs") \
            or not c.get("decode_prefills"):
        return None
    s, runs, _ = hybrid_moe.ops_in_runs(
        hybrid_moe.view(sources), params["pattern"], params["module"])
    if s <= 0 or not runs:
        return None
    pairs = c["moe_grouped_pairs"] / c["decode_prefills"] * runs
    least = flops_conv_moe.grouped_pair_flops(
        pairs, m["d_model"], m["expert_dim"]) \
        / (sources["peaks"]["bf16_tflops"] * 1e12)
    return 100.0 * least / s


def moe_pairs_per_row(sources, params):
    """Model step: (row, held expert) pairs a live row of a decode step
    makes an expert layer (``moe_local_assignments`` over the stepped
    tokens over the layers that have experts): ``top_k`` exactly where
    every expert is held."""
    c, m = _counters(sources), _sizes(sources)
    stepped = c.get("decode_tokens_total", 0) - c.get("decode_prefills", 0)
    if m is None or stepped <= 0 or "moe_local_assignments" not in c:
        return None
    return c["moe_local_assignments"] / stepped \
        / (len(m["layer_kinds"]) - m["dense_layers"])


def prefill_device_share(sources, params):
    """Model step: of the device time of the window's joint steps and
    whole-prompt prefills, the prefills' share, in %."""
    mods = (sources.get("trace") or {}).get("modules") or {}
    pre, step = mods.get(params["prefill"]), mods.get(params["step"])
    if _sizes(sources) is None or not pre or not step \
            or not pre["total_s"] + step["total_s"]:
        return None
    return 100.0 * pre["total_s"] / (pre["total_s"] + step["total_s"])
