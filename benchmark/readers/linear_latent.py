"""Per-layer readers of the cells that serve the linear/latent hybrid
model (channel-decay delta-rule layers whose state lies in slabs BESIDE
one pool of latent rows) with a held share of its experts.  Device times
are found as ``readers/hybrid_moe.py`` finds them (the events whose
instruction matches the metric file's ``pattern`` and that start inside
a run of ``params["module"]``).  The step's persistent state is (the
latent pool, then each recurrent layer's ``s`` and ``tail``): the slabs
are operands ``%state_1_`` and up (a model whose cache has K AND V pools
counts them from ``%state_2_``: ``kda_ms_per_step.serve``'s pattern
would lose this model's first slab, so the files here bring their own).
Every reader returns None where there is nothing to read: a run without
a trace, a program without such operations, counters or gauges (the
parent of the PR that added them), a configuration without these keys,
or a window without a run.

The yardsticks count the work AS PUBLISHED
(``benchmark/flops_linear_latent.py``).
"""
from benchmark import flops_linear_latent as fl
from benchmark.readers import gated_delta, hybrid_moe

ops_ms_per_run = hybrid_moe.ops_ms_per_run
_loops = gated_delta._loops         # (seconds, runs) of the matched loops


def _sizes(sources):
    m = sources["config"]["model"]
    return m if "kv_rank" in m and "lin_heads" in m else None


def _counters(sources):
    return (sources.get("serve") or {}).get("counters") or {}


def _live_per_step(c):
    return (c["decode_tokens_total"] - c["decode_prefills"]) \
        / c["decode_steps"]


def kda_step_roofline(sources, params):
    """Kernels: a step's live slots x recurrent layers x (state read +
    written) over the HBM bandwidth, over the state update's time."""
    m, c = _sizes(sources), _counters(sources)
    ms = ops_ms_per_run(sources, params)
    if m is None or not ms or not c.get("decode_steps"):
        return None
    need = fl.kda_state_bytes(
        _live_per_step(c), m["layer_kinds"].count("recurrent"),
        m["lin_heads"], m["lin_head_dim"])
    return 100.0 * 1e3 * need / (sources["peaks"]["hbm_gbps"] * 1e9) / ms


def kda_prefill_ms(sources, params):
    """Kernels: device time of the recurrent layers' loops over a
    prompt's chunks a prefill run (the kernel's token loop and the
    chunk's vectors, all recurrent layers), ms."""
    s, runs = _loops(sources, params)
    return 1e3 * s / runs if _sizes(sources) and s > 0 and runs else None


def kda_prefill_roofline(sources, params):
    """Kernels: the float32 multiplies and adds of the token recurrence
    over the window's REAL prompt tokens (the program's
    ``decode_prefill_scan_tokens``: token x recurrent layer pairs, scaled
    from the prefills that ended in the window to those that ran in the
    trace) at the vector unit's issue rate, over the loops' time, in %."""
    m, c = _sizes(sources), _counters(sources)
    s, runs = _loops(sources, params)
    if m is None or not c.get("decode_prefill_scan_tokens") \
            or not c.get("decode_prefills") or s <= 0:
        return None
    pairs = c["decode_prefill_scan_tokens"] / c["decode_prefills"] * runs
    least = fl.kda_token_ops(pairs, m["lin_heads"], m["lin_head_dim"]) \
        / fl.VECTOR_F32_OPS_PER_S
    return 100.0 * least / s


def latent_step_roofline(sources, params):
    """Kernels: the larger of the absorbed attention's byte time (the
    rows the live slots attend, the program's
    ``decode_latent_positions_live`` a layer, at the published bytes a
    row, over the LATENT layers, and the queries and contexts) and its
    FLOP time at the bf16 peak, over the latent kernel's time by its own
    name, a step."""
    m, c = _sizes(sources), _counters(sources)
    ms = ops_ms_per_run(sources, params)
    if m is None or not ms or not c.get("decode_steps") \
            or "decode_latent_positions_live" not in c:
        return None
    positions = c["decode_latent_positions_live"] / c["decode_steps"]
    layers = m["layer_kinds"].count("attention")
    dtype = sources["config"]["serving"].get("cache_dtype", "float32")
    least_ms = 1e3 * max(
        fl.latent_attention_bytes(
            positions, _live_per_step(c), layers, m["num_heads"],
            m["kv_rank"], m["rope_dim"], dtype)
        / (sources["peaks"]["hbm_gbps"] * 1e9),
        fl.latent_attention_flops(
            positions, layers, m["num_heads"], m["kv_rank"], m["rope_dim"])
        / (sources["peaks"]["bf16_tflops"] * 1e12))
    return 100.0 * least_ms / ms


def state_bytes_per_slot(sources, params):
    """Model step: bytes the recurrent layers' slabs take a slot (the
    program's ``decode_state_bytes`` gauge over the slots)."""
    serve = sources.get("serve") or {}
    held = (serve.get("gauges") or {}).get("decode_state_bytes")
    if not held or not serve.get("slots") or _sizes(sources) is None:
        return None
    return held / serve["slots"]
