"""Per-layer readers of the cells that serve the window/global-attention
model with a held share of its experts.  Device times are found as
``readers/hybrid_moe.py`` finds them (the events whose instruction
matches the metric file's ``pattern`` and that start inside a run of
``params["module"]``): the global layers' paged kernel by its name
``%paged_attention.<n>``, the window layers' by ITS name
``%paged_attention_window.<n>``.  Every reader returns None where there
is nothing to read: a run without a trace, a program without such
operations or counters (the parent of the PR that added them), or a
window without a step.

Rooflines are per decode step, memory-bound: the K and V bytes of the
whole pages a step's tokens attend in that kind of layer
(``benchmark/flops_window_moe.py``; the contexts from the client's
records) over the HBM bandwidth, over the matched time a run.
"""
from benchmark import flops_window_moe
from benchmark.readers import hybrid_moe

ops_ms_per_run = hybrid_moe.ops_ms_per_run
# Kernels: the global layers' K and V bytes of the whole pages a step's
# tokens attend, EVERY position of the context (the cell's
# ``kv_bytes_per_token`` counts the global layers only, K and V at their
# own widths), over the HBM bandwidth, over the paged kernel's time by
# its own name: the grouped kernel's reader as it is
full_attn_roofline = hybrid_moe.paged_attn_roofline


def window_attn_roofline(sources, params):
    """Kernels: the window layers' K and V bytes of the pages the window
    reaches (at most ``ceil(window / page) + 1`` a slot, however long
    its context) over the HBM bandwidth, over the window kernel's time
    by its own name."""
    serve = sources.get("serve") or {}
    steps = (serve.get("counters") or {}).get("decode_steps")
    contexts = serve.get("decode_contexts")
    m = sources["config"]["model"]
    if not steps or not contexts or "window" not in m:
        return None
    per_token = flops_window_moe.kv_bytes_per_token(
        m["layer_kinds"].count("window"), m["window_kv_heads"],
        m["head_dim"], m["v_head_dim"],
        sources["config"]["serving"].get("cache_dtype", "float32"))
    return hybrid_moe._share(
        sources, params, flops_window_moe.window_attention_bytes(
            contexts, sources["serve"]["page_size"], m["window"],
            per_token) / steps)


def window_positions_live_share(sources, params):
    """Kernels: of the positions the window kernel's walked blocks hold
    (the program's ``decode_window_blocks_walked`` x a block's
    positions), the share inside some row's window
    (``decode_window_positions_live``), in %: what a block of
    ``params["block_positions"]`` positions wastes on the window."""
    c = (sources.get("serve") or {}).get("counters") or {}
    walked = c.get("decode_window_blocks_walked")
    if not walked or "decode_window_positions_live" not in c:
        return None
    return 100.0 * c["decode_window_positions_live"] \
        / (walked * params["block_positions"])


def routed_experts_hit_share(sources, params):
    """Model step: of the held experts x the layers THAT HAVE EXPERTS,
    the share some live row of a decode step chose, in % (the weights
    the step has to read)."""
    hit = hybrid_moe._per_step(sources, "moe_experts_hit")
    m = sources["config"]["model"]
    if hit is None or "dense_layers" not in m:
        return None
    lo, hi = m["held_experts"]
    return 100.0 * hit / ((hi - lo)
                          * (len(m["layer_kinds"]) - m["dense_layers"]))
