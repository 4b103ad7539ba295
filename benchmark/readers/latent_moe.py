"""Per-layer readers of the cells that serve the latent-attention model
with a held share of its experts.  Device times are found as
``readers/hybrid_moe.py`` finds them (the events whose instruction
matches the metric file's ``pattern`` and that start inside a run of
``params["module"]``): the latent kernel by ITS name
``%paged_attention_latent.<n>`` (none of ``paged_attn_*``, ``full_attn_*``
or ``window_attn_*`` reads it under theirs), the projections by the
attention weight they read.  Every reader returns None where there is
nothing to read: a run without a trace, a program without such
operations or counters (the parent of the PR that added them), a
configuration without these keys, or a window without a step.

The yardsticks count the work AS PUBLISHED (``benchmark/
flops_latent_moe.py``): a cached row is ``kv_rank + rope_dim`` lanes
whatever the pool pads it to, so a layout that moves more reads below
100 %.
"""
import re

from benchmark import flops_latent_moe
from benchmark.readers import gated_delta, hybrid_moe

ops_ms_per_run = hybrid_moe.ops_ms_per_run


def _sizes(sources):
    m = sources["config"]["model"]
    return m if "kv_rank" in m else None


def latent_attn_roofline(sources, params):
    """Kernels: the larger of the absorbed attention's byte time (the
    rows the live slots attend, the program's
    ``decode_latent_positions_live``, at the published 1,152 B a row a
    layer, and the queries and contexts) and its FLOP time at the bf16
    peak, over the latent kernel's time by its own name, a step."""
    m, c = _sizes(sources), (sources.get("serve") or {}).get("counters") or {}
    ms = ops_ms_per_run(sources, params)
    if m is None or not ms or not c.get("decode_steps") \
            or "decode_latent_positions_live" not in c:
        return None
    steps = c["decode_steps"]
    positions = c["decode_latent_positions_live"] / steps
    live = (c["decode_tokens_total"] - c["decode_prefills"]) / steps
    dtype = sources["config"]["serving"].get("cache_dtype", "float32")
    need_bytes = flops_latent_moe.latent_attention_bytes(
        positions, live, m["num_layers"], m["num_heads"], m["kv_rank"],
        m["rope_dim"], dtype)
    need_flops = flops_latent_moe.latent_attention_flops(
        positions, m["num_layers"], m["num_heads"], m["kv_rank"],
        m["rope_dim"])
    least_ms = 1e3 * max(
        need_bytes / (sources["peaks"]["hbm_gbps"] * 1e9),
        need_flops / (sources["peaks"]["bf16_tflops"] * 1e12))
    return 100.0 * least_ms / ms


_PROJ_OPERAND = re.compile(
    r"layers___(\d+)___(wq_a|wq_b|wkv_a|w_uk|w_uv|wo)__")


def latent_proj_roofline(sources, params):
    """Kernels: the attention weights the MATCHED events read from HBM
    (a matrix counts once a step if some matched event names it as an
    operand) over the HBM bandwidth, over those events' time, a step.
    Not all six matrices of every layer: the compiler prefetches most of
    them into fast memory under the latent kernel and the experts'
    matmuls (``copy-start`` / ``slice-start`` into the v5e's 128 MiB),
    the matmul that reads such a copy names no weight and takes a
    fraction of the read's time, and bytes counted for it would read
    above 100 % (``readers/gated_delta.py`` ``dense_ffn_roofline``: the
    same rule)."""
    m, v = _sizes(sources), hybrid_moe.view(sources)
    if m is None or not v:
        return None
    runs = sorted(v["runs"].get(params["module"], ()))
    if not runs:
        return None
    sizes = flops_latent_moe.latent_projection_params(
        m["d_model"], m["num_heads"], m["q_rank"], m["kv_rank"],
        m["nope_dim"], m["rope_dim"], m["v_dim"])
    pat, read, seconds = re.compile(params["pattern"]), set(), 0.0
    a0, b0 = runs[len(runs) // 2]           # every step is one program
    for a, b, name in v["ops"]:
        # a prefetch's own event (copy-start ...) names the weight too:
        # it moves bytes under another operation and is no read here
        if a0 <= a < b0 and pat.search(name) \
                and gated_delta._COMPUTES.search(name):
            read.update(_PROJ_OPERAND.findall(name))
            seconds += b - a
    if not read or not seconds:
        return None
    need = sum(sizes[w] for _, w in read) * flops_latent_moe._DTYPE_BYTES[
        str(m.get("dtype", "bfloat16"))]
    return 100.0 * need / (sources["peaks"]["hbm_gbps"] * 1e9) / seconds


def latent_row_bytes(sources, params):
    """Model step: bytes the latent pool takes a position a layer (the
    program's ``decode_latent_bytes`` gauge over the pool's rows): 1,152
    published, what the pool's layout really takes."""
    serve = sources.get("serve") or {}
    rows = serve.get("latent_pool_rows")
    if not rows or _sizes(sources) is None:
        return None
    from paddle_tpu.monitor import stat_get

    held = stat_get("decode_latent_bytes")
    return held / rows if held else None
