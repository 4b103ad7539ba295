"""Per-layer readers of the cells that serve the state-space hybrid
model (selective state-space layers whose state lies in slabs beside the
pages of a few multi-query attention layers).  Device times are found as
``readers/hybrid_moe.py`` finds them (the events whose instruction
matches the metric file's ``pattern`` and that start inside a run of
``params["module"]``): the two kernels by their own names, the
convolution by the slab it reads, the projections by their weight
operands.  Every reader returns None where there is nothing to read: a
run without a trace, a program without such operations or counters (the
parent of the PR that added them), a configuration without these keys,
or a window without a run.

The yardsticks count the work AS PUBLISHED (``benchmark/flops_mamba.py``).
"""
import re

from benchmark import flops_mamba as fm
from benchmark.readers import hybrid_moe
from benchmark.readers.linear_latent import _counters, _live_per_step


def _sizes(sources):
    m = sources["config"]["model"]
    return m if "d_state" in m and "d_inner" in m else None


def ops_ms_per_run(sources, params):
    """Device time of the matched events a run of the module, ms."""
    if _sizes(sources) is None:
        return None
    return hybrid_moe.ops_ms_per_run(sources, params)


def ssm_step_roofline(sources, params):
    """Kernels: a step's live slots x recurrent layers x (state read +
    written: the matrix and the convolution's rows) over the HBM
    bandwidth, over the time of the operations that pass over the slabs
    (the state kernel and the convolution with its tail)."""
    m, c = _sizes(sources), _counters(sources)
    ms = ops_ms_per_run(sources, params)
    if m is None or not ms or not c.get("decode_steps"):
        return None
    need = fm.ssm_state_bytes(
        _live_per_step(c), m["layer_kinds"].count("recurrent"),
        m["d_state"], m["d_inner"], m["d_conv"])
    return 100.0 * 1e3 * need / (sources["peaks"]["hbm_gbps"] * 1e9) / ms


def ssm_prefill_roofline(sources, params):
    """Kernels: the least time the token recurrence could take over the
    window's REAL prompt tokens (the program's
    ``decode_prefill_scan_tokens``: token x recurrent layer pairs, scaled
    from the prefills that ended in the window to those that ran in the
    trace; ``flops_mamba.ssm_scan_least_s``: the vector unit's time, the
    larger of the two) over the scan kernel's time, in %."""
    m, c = _sizes(sources), _counters(sources)
    if m is None or not c.get("decode_prefill_scan_tokens") \
            or not c.get("decode_prefills"):
        return None
    s, runs, _ = hybrid_moe.ops_in_runs(
        hybrid_moe.view(sources), params["pattern"], params["module"])
    if s <= 0 or not runs:
        return None
    pairs = c["decode_prefill_scan_tokens"] / c["decode_prefills"] * runs
    return 100.0 * fm.ssm_scan_least_s(pairs, m["d_state"],
                                       m["d_inner"]) / s


_PROJ_OPERAND = re.compile(r"layers___(\d+)___ssm_w_(in|x|dt|out)__")
_COMPUTES = re.compile(r" (fusion|convolution|dot)\(")


def ssm_proj_roofline(sources, params):
    """Kernels: the projections' weights the MATCHED events read from
    HBM (a layer's matrix counts once a step if some matched event names
    it as an operand; a matmul over a copy the compiler prefetched into
    fast memory names no weight and is neither timed nor counted:
    ``readers/gated_delta.py`` ``dense_ffn_roofline``'s rule) over the
    HBM bandwidth, over those events' time, a step."""
    m, v = _sizes(sources), hybrid_moe.view(sources)
    if m is None or not v:
        return None
    runs = sorted(v["runs"].get(params["module"], ()))
    if not runs:
        return None
    pat, read = re.compile(params["pattern"]), set()
    a0, b0 = runs[len(runs) // 2]           # every step is one program
    for a, _, name in v["ops"]:
        if a0 <= a < b0 and pat.search(name) and _COMPUTES.search(name):
            read.update(_PROJ_OPERAND.findall(name))
    need = fm.ssm_proj_bytes(
        [which for _, which in read], m["d_model"], m["d_inner"],
        m["d_state"], m["dt_rank"], m.get("dtype", "bfloat16"))
    ms = hybrid_moe.ops_ms_per_run(sources, params)
    if not ms or not need:
        return None
    return 100.0 * 1e3 * need / (sources["peaks"]["hbm_gbps"] * 1e9) / ms
