"""Per-layer readers of the cells that serve the looped model (one stack
of layers run ``loops`` times on the same weights, the passes and the
layers of a pass two rolled loops of the step).  Device times are found
as ``readers/hybrid_moe.py`` finds them (the events whose instruction
matches the metric file's ``pattern`` and that start inside a run of
``params["module"]``).  Inside a loop's body an instruction's operands
are elements of the loop's tuple and carry no weight's name, but every
operand is printed with its type: an event READS a layer's matrix if the
STACKED array (``bf16[48,2048,5632]``, its shape from the configuration)
is among its operands - the matmul that cuts its layer out as it reads,
or the copy of a layer's slice into fast memory, whose matmul then names
no stack and reads no HBM - and the head by its own shape.  A pass is
the inner loop that IS it.  Every reader
returns None where there is nothing to read: a run without a trace, a
program without such operations or counters (the parent of the PR that
added them), a configuration without these keys, or a window without a
run.
"""
import re

from benchmark import flops_looped
from benchmark.readers import hybrid_moe

ops_ms_per_run = hybrid_moe.ops_ms_per_run
# the paged kernel's bytes over the live pages of all cache layers (the
# cell's ``kv_bytes_per_token`` counts every pass's K and V)
attn_roofline = hybrid_moe.paged_attn_roofline


def _sizes(sources):
    m = sources["config"]["model"]
    return m if "loops" in m else None


def _step_events(sources, params):
    """The events [(start, end, name)] of the middle run of the module,
    or None."""
    v = hybrid_moe.view(sources)
    runs = sorted((v or {}).get("runs", {}).get(params["module"], ()))
    if not runs:
        return None
    a0, b0 = runs[len(runs) // 2]           # every step is one program
    return [(a, b, n) for a, b, n in v["ops"] if a0 <= a < b0]


def loop_pass_ms(sources, params):
    """Model step: device ms of one pass of a step: the INNER loop over
    the layers (the matched ``while`` events that hold no other matched
    one), a pass."""
    m, events = _sizes(sources), _step_events(sources, params)
    if m is None or events is None:
        return None
    pat = re.compile(params["pattern"])
    loops = [(a, b) for a, b, n in events if pat.search(n)]
    inner = [(a, b) for a, b in loops
             if not any(a <= c and d <= b and (c, d) != (a, b)
                        for c, d in loops)]
    if not inner:
        return None
    return 1e3 * sum(b - a for a, b in inner) / len(inner)


def _leaf_seconds(events, pattern=None, skip=None):
    """Seconds of the events that hold no other event (a loop's own
    event spans its body's), matching ``pattern`` and not ``skip``."""
    rows, stack = [], []
    for a, b, n in events:                  # sorted by start, outer first
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            stack[-1][3] = True             # it holds this one
        stack.append([a, b, n, False])
        rows.append(stack[-1])
    return sum(b - a for a, b, n, holds in rows if not holds
               and (pattern is None or pattern.search(n))
               and not (skip is not None and skip.search(n)))


_DTYPE_TAGS = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def reads_a_matrix(m):
    """The pattern of an instruction one of whose OPERANDS is a stack of
    the layers' matrices, or the head: what reads weights from HBM."""
    n, d, f = m["num_layers"], m["d_model"], m["ffn_dim"]
    hd = m["num_heads"] * m["head_dim"]
    shapes = {(n, d, hd), (n, hd, d), (n, d, f), (n, f, d),
              (d, m["vocab_size"])}
    tag = _DTYPE_TAGS[str(m.get("dtype", "bfloat16"))]
    return re.compile(r"\(.*\b%s\[(?:%s)\]" % (tag, "|".join(
        ",".join(map(str, s)) for s in sorted(shapes))))


def loop_weights_roofline(sources, params):
    """Kernels: the bytes of the matrices a step's dense matmuls read
    (every layer's seven once a pass, the head once:
    ``flops_looped.step_weight_bytes``) over the HBM bandwidth, over the
    time of the events that read them (``reads_a_matrix``), a step."""
    m, events = _sizes(sources), _step_events(sources, params)
    if m is None or events is None:
        return None
    seconds = _leaf_seconds(events, reads_a_matrix(m))
    if not seconds:
        return None
    need = flops_looped.step_weight_bytes(
        m["num_layers"], m["loops"], m["d_model"], m["num_heads"],
        m["head_dim"], m["ffn_dim"], m["vocab_size"],
        m.get("dtype", "bfloat16"))
    return 100.0 * need / (sources["peaks"]["hbm_gbps"] * 1e9) / seconds


def loop_small_ops_ms_per_step(sources, params):
    """Kernels: a step's device time in neither the matmuls that read
    weights nor the paged kernel: the norms, the rotary term, the
    residual adds, the page writes, the sampler - the fixed-cost tail."""
    m, events = _sizes(sources), _step_events(sources, params)
    if m is None or events is None:
        return None
    skip = re.compile("|".join((reads_a_matrix(m).pattern,
                                params["pattern"])))
    seconds = _leaf_seconds(events, skip=skip)
    return 1e3 * seconds if seconds else None


def _counters(sources):
    return (sources.get("serve") or {}).get("counters") or {}


def loop_passes_per_token(sources, params):
    """Model step: passes of the stack a program ran (the program's
    ``decode_loop_passes``, which ``forward`` adds ``loops`` to) over
    the joint steps and whole-prompt prefills of the window."""
    c = _counters(sources)
    runs = c.get("decode_steps", 0) + c.get("decode_prefills", 0)
    if not runs or "decode_loop_passes" not in c:
        return None
    return c["decode_loop_passes"] / runs


def kv_bytes_per_token(sources, params):
    """Model step: bytes of K and V a cached position takes over all
    cache layers (the program's ``decode_kv_pool_bytes`` gauge over the
    positions of a pool layer)."""
    serve = sources.get("serve") or {}
    held = (serve.get("gauges") or {}).get("decode_kv_pool_bytes")
    if not held or not serve.get("kv_pool_positions"):
        return None
    return held / serve["kv_pool_positions"]
