"""Per-layer readers of the cells that serve the hybrid linear/softmax-
attention model with a held share of its experts.

A ``--trace 1`` run's device events carry their whole HLO instruction as
their name (operands included, and a jitted program's operands are named
after its arguments: ``%weights__layers___1___moe_w_gate__.1``,
``%state_2_.1``), never a ``jax.named_scope``.  So a layer's device time
is the time of the events whose instruction matches the metric file's
``pattern`` - a Pallas call by its own ``name=``, a fusion by the weight
or state operand it reads - and, unlike ``trace_reduce``'s ``kernel_s``,
only of the events that START inside a run of ``params["module"]``
(``jit_step``: a prefill reads the same weights and is another program).
Runs are taken from the chip's "XLA Modules" line, wholly inside
``bench/window``.  Every reader returns None where there is nothing to
read: a run without a trace, a program without such operations (the
parent of the PR that added them), or a window without a run.

Rooflines are per decode step, both memory-bound: the bytes a step
cannot avoid (``benchmark/flops_hybrid_moe.py``; live slots and experts
hit from the program's counters over the window, a step's mean) over
the HBM bandwidth, over the matched time a run.
"""
import bisect
import os
import re

from benchmark import flops, flops_hybrid_moe
from benchmark import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CACHE = {}


def parse(path):
    """{"runs": {module: [(start, end)]}, "ops": [(start, end, name)]}
    of the first chip, inside the window; None without a device plane."""
    pd = tr.load(path)
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tr.WINDOW_SPAN:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
    chips = sorted((int(tr.DEVICE_PLANE.match(p.name).group(1)), p)
                   for p in pd.planes if tr.DEVICE_PLANE.match(p.name))
    if not chips:
        return None
    ops, runs = [], {}
    for line in chips[0][1].lines:
        if line.name == tr.OPS_LINE:
            ops = tr._events(line)
        elif line.name == tr.MODULES_LINE:
            for a, b, name in tr._events(line):
                if window is None or (a >= window[0] and b <= window[1]):
                    runs.setdefault(name.split("(")[0], []).append((a, b))
    return {"runs": runs, "ops": ops}


def view(sources):
    """The parsed trace of this run; None for a run without one."""
    if not sources.get("trace"):
        return None
    try:
        path = tr.find_xplane(os.path.join(
            ROOT, ".bench_runs", sources["spec"]["name"], "trace"))
    except (FileNotFoundError, KeyError):
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = parse(path)
    return _CACHE[key]


def ops_in_runs(v, pattern, module):
    """(seconds, runs, {instruction id: [seconds, count]}) of the device
    events matching ``pattern`` that start inside a run of ``module``."""
    runs = sorted((v or {}).get("runs", {}).get(module, ()))
    if not runs:
        return 0.0, 0, {}
    starts = [a for a, _ in runs]
    pat, total, by_id = re.compile(pattern), 0.0, {}
    for a, b, name in v["ops"]:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < runs[i][1] and pat.search(name):
            total += b - a
            row = by_id.setdefault(name.split(" = ")[0], [0.0, 0])
            row[0] += b - a
            row[1] += 1
    return total, len(runs), by_id


def ops_ms_per_run(sources, params):
    """Device time of the matched events a run of the module, ms."""
    s, runs, _ = ops_in_runs(view(sources), params["pattern"],
                             params["module"])
    return 1e3 * s / runs if s and runs else None


def _per_step(sources, counter):
    c = sources.get("serve", {}).get("counters") or {}
    if not c.get("decode_steps") or counter not in c:
        return None
    return c[counter] / c["decode_steps"]


def _share(sources, params, need_bytes):
    ms = ops_ms_per_run(sources, params)
    if not ms or need_bytes is None:
        return None
    least_ms = 1e3 * need_bytes / (sources["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_ms / ms


def kda_state_roofline(sources, params):
    """Kernels: a step's live slots x recurrent layers x (state read +
    written) over the HBM bandwidth, over the state update's time."""
    c = sources.get("serve", {}).get("counters") or {}
    if not c.get("decode_steps"):
        return None
    m = sources["config"]["model"]
    live = (c["decode_tokens_total"] - c["decode_prefills"]) \
        / c["decode_steps"]
    return _share(sources, params, flops_hybrid_moe.kda_state_bytes(
        live, m["layer_kinds"].count("recurrent"), m["lin_heads"],
        m["lin_head_dim"]))


def moe_experts_roofline(sources, params):
    """Kernels: the weights of the held experts a step's live rows chose
    (the program's ``moe_experts_hit``, summed over layers) over the HBM
    bandwidth, over the routed experts' matmuls' time."""
    hit = _per_step(sources, "moe_experts_hit")
    m = sources["config"]["model"]
    return _share(sources, params, None if hit is None
                  else flops_hybrid_moe.moe_expert_bytes(
                      hit, m["d_model"], m["expert_dim"]))


def paged_attn_roofline(sources, params):
    """Kernels: the K and V bytes of the whole pages a step's tokens
    attend (the client's records) over the HBM bandwidth, over the paged
    kernel's time by its own name."""
    serve = sources.get("serve") or {}
    steps = (serve.get("counters") or {}).get("decode_steps")
    if not steps or not serve.get("decode_contexts"):
        return None
    return _share(sources, params, flops.decode_attention_bytes(
        serve["decode_contexts"], serve["page_size"],
        serve["kv_bytes_per_token"]) / steps)


def experts_hit_share(sources, params):
    """Model step: of the held experts x layers, the share some live row
    of a decode step chose, in % (the weights the step has to read)."""
    hit = _per_step(sources, "moe_experts_hit")
    if hit is None:
        return None
    m = sources["config"]["model"]
    lo, hi = m["held_experts"]
    return 100.0 * hit / ((hi - lo) * len(m["layer_kinds"]))


def caller_tail_ms(sources, params):
    """Engine loop, seen by a caller: a latency percentile of the window
    on the client's clock (``params["which"]``: ``ttft_p90`` | ``itl_p99``,
    as the kind computed it from the client's records), ms."""
    return ((sources.get("serve") or {}).get("caller_ms") or {}).get(
        params["which"])
