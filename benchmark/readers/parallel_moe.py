"""Per-layer readers of the cells that serve the parallel-block model
(window and position-free global layers, a held share of the experts,
averaged shared experts).  Device times are found as
``readers/hybrid_moe.py`` finds them (the events whose instruction
matches the metric file's ``pattern`` and that start inside a run of
``params["module"]``): the shared experts' matmuls by the
``shared_w_*`` weight operand they read.  Every reader returns None
where there is nothing to read: a run without a trace, a program without
such operations or counters (the parent of the PR that added them), a
configuration without these keys, or a window without a step.
"""
from benchmark import flops_parallel_moe
from benchmark.readers import hybrid_moe


def shared_ffn_roofline(sources, params):
    """Kernels: the shared experts' weights, all layers, that every
    decode step reads whatever its batch
    (``flops_parallel_moe.shared_expert_bytes``) over the HBM bandwidth,
    over the time of the events that read them, a step."""
    m = sources["config"]["model"]
    if "shared_experts" not in m:
        return None
    return hybrid_moe._share(
        sources, params, flops_parallel_moe.shared_expert_bytes(
            len(m["layer_kinds"]), m["d_model"], m["shared_experts"],
            m["shared_dim"], m.get("dtype", "bfloat16")))


def _counter_share(sources, part, whole):
    c = (sources.get("serve") or {}).get("counters") or {}
    if not c.get(whole) or part not in c:
        return None
    return 100.0 * c[part] / c[whole]


def window_capped_share(sources, params):
    """Engine loop: of a window's live decode rows (a row a slot a
    step), the share whose length exceeds the window, in %: the rows
    for which a window layer reads less than a global one."""
    return _counter_share(sources, "decode_window_rows_capped",
                          "decode_window_rows")


def prefill_keys_live_share(sources, params):
    """Model step: of the keys the whole-prompt prefills' softmaxes span
    (a head, over the layers that have keys), the share a prompt row can
    see, in %: the rest is the causal triangle's other half, the
    bucket's padding and what a block reaches beyond its rows' windows."""
    return _counter_share(sources, "decode_prefill_keys_live",
                          "decode_prefill_keys_attended")
