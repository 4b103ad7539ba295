"""Per-layer readers of the cells that serve the dense hybrid model
(Gated DeltaNet layers beside full-attention layers, a dense SwiGLU).
Device times are found as ``readers/hybrid_moe.py`` finds them (the
events whose instruction matches the metric file's ``pattern`` and that
start inside a run of ``params["module"]``): the one-token update by the
slab operands it reads, the feed-forward by its weight operands, the
chunk form as the ``while`` loops of a prefill run that carry a slot's
state (the engine runs one loop over the prompt's chunks a recurrent
layer, the whole rule inside it; the loop over the blocks of a
full-attention layer carries no ``[1, heads, d_k, d_v]``).  Every
reader returns None where there is nothing to read: a run without a
trace, a program without such operations or counters (the parent of the
PR that added them), a configuration without these keys, or a window
without a run.
"""
import re

from benchmark import flops_gated_delta
from benchmark.readers import hybrid_moe


def _sizes(sources):
    m = sources["config"]["model"]
    if "lin_key_dim" not in m:
        return None
    return m


def gdn_state_roofline(sources, params):
    """Kernels: a step's live slots x recurrent layers x (state read +
    written) over the HBM bandwidth, over the state update's time."""
    c = (sources.get("serve") or {}).get("counters") or {}
    m = _sizes(sources)
    if not c.get("decode_steps") or m is None:
        return None
    live = (c["decode_tokens_total"] - c["decode_prefills"]) \
        / c["decode_steps"]
    return hybrid_moe._share(
        sources, params, flops_gated_delta.gdn_state_bytes(
            live, m["layer_kinds"].count("recurrent"), m["lin_heads"],
            m["lin_key_dim"], m["lin_value_dim"]))


_FFN_OPERAND = re.compile(r"layers___(\d+)___ffn_w_(gate|up|down)__")
_COMPUTES = re.compile(r" (fusion|convolution|dot)\(")


def dense_ffn_roofline(sources, params):
    """Kernels: the feed-forward weights the MATCHED events read from
    HBM (a layer's matrix counts once a step if some matched event
    names it as an operand) over the HBM bandwidth, over those events'
    time, a step.  Not all 24 matrices: the compiler prefetches some
    into fast memory under another operation (seen: three gate matrices
    under the paged kernel and the step's start), the matmul that reads
    that copy names no weight and takes a tenth of the time, and bytes
    counted for it would read above 100 %."""
    m = _sizes(sources)
    v = hybrid_moe.view(sources)
    if m is None or not v:
        return None
    runs = sorted(v["runs"].get(params["module"], ()))
    if not runs:
        return None
    pat, read = re.compile(params["pattern"]), set()
    a0, b0 = runs[len(runs) // 2]           # every step is one program
    for a, _, name in v["ops"]:
        # a prefetch's own event (copy-start ...) names the weight too:
        # it moves bytes under another operation and is no read here
        if a0 <= a < b0 and pat.search(name) and _COMPUTES.search(name):
            read.update(_FFN_OPERAND.findall(name))
    return hybrid_moe._share(
        sources, params, flops_gated_delta.dense_ffn_bytes(
            len(read) / 3.0, m["d_model"], m["ffn_dim"],
            m.get("dtype", "bfloat16")))


def _loops(sources, params):
    """(seconds, runs) of the matched loops inside runs of the module."""
    total, runs, _ = hybrid_moe.ops_in_runs(
        hybrid_moe.view(sources), params["pattern"], params["module"])
    return total, runs


def gdn_prefill_ms(sources, params):
    """Kernels: device time of the chunk form's loops a prefill run,
    all recurrent layers, ms."""
    s, runs = _loops(sources, params)
    return 1e3 * s / runs if s > 0 and runs else None


def gdn_prefill_roofline(sources, params):
    """Kernels: the least time the window's chunks could take (the
    program's ``decode_prefill_scan_steps``: chunk x layer pairs; the
    larger of their matrix products at the bf16 peak and their rows and
    state at the HBM bandwidth) over the loops' time in the window's
    prefill runs, in %.  The counter counts the prefills that ENDED in
    the window and the trace those that ran in it: the two differ by a
    prefill at each edge, a part in twenty of a 4 s window."""
    c = (sources.get("serve") or {}).get("counters") or {}
    m = _sizes(sources)
    s, runs = _loops(sources, params)
    chunks = c.get("decode_prefill_scan_steps")
    if m is None or not chunks or not c.get("decode_prefills") or s <= 0:
        return None
    chunks = chunks / c["decode_prefills"] * runs      # of the traced runs
    size = (params["chunk"], m["lin_heads"], m["lin_key_dim"],
            m["lin_value_dim"])
    peaks = sources["peaks"]
    least = max(
        flops_gated_delta.gdn_chunk_flops(chunks, *size)
        / (peaks["bf16_tflops"] * 1e12),
        flops_gated_delta.gdn_chunk_bytes(chunks, *size)
        / (peaks["hbm_gbps"] * 1e9))
    return 100.0 * least / s


def prefill_tokens_per_scan_step(sources, params):
    """Model step: real prompt tokens a scan iteration of the recurrent
    layers' whole-prompt prefills (the program's two counters): 1 for a
    token scan, the chunk's length less the last chunk's padding for the
    chunk form."""
    c = (sources.get("serve") or {}).get("counters") or {}
    if not c.get("decode_prefill_scan_steps"):
        return None
    return c.get("decode_prefill_scan_tokens", 0) \
        / c["decode_prefill_scan_steps"]


ops_ms_per_run = hybrid_moe.ops_ms_per_run
