"""Per-layer readers of the cells that serve the model whose attention
reads the positions a learned indexer selects (a third pool of index
keys beside K and V).  Device times are found as ``readers/hybrid_moe.py``
finds them (the events whose instruction matches the metric file's
``pattern`` and that start inside a run of ``params["module"]``).  No
kernel of the indexed attention has a name of its own yet: its
operations are XLA's gathers, fusions and sorts, told by the arrays they
form or read, whose shapes follow from the configuration.  So a pattern
is a format string over the cell's sizes (``slots``, ``positions``: a
table's width in positions, ``pages``: in pages, ``page``, ``topk``,
``rows``: slots x topk, ``lanes``: the stored index row's, ``kv_lanes``:
a K or V row's, ``heads``), and ``params["except"]``
(optional, the same form) takes out of the matched events those that
also match it.  Every reader returns None where there is nothing to
read: a run without a trace, a program without such operations, counters
or gauges (the parent of the PR that added them), a configuration
without these keys, or a window without a run.

The yardsticks count the work AS PUBLISHED
(``benchmark/flops_indexed_moe.py``).
"""
import bisect
import re

from benchmark import flops_indexed_moe as fi
from benchmark.readers import hybrid_moe


def _sizes(sources):
    m = sources["config"]["model"]
    return m if "index_dim" in m and "index_topk" in m else None


def _counters(sources):
    return (sources.get("serve") or {}).get("counters") or {}


def shapes(sources):
    """The sizes a pattern may name, from the configuration."""
    m, s = sources["config"]["model"], sources["config"]["serving"]
    page = int(s.get("page_size", 16))
    return dict(slots=s["slots"], positions=s["max_seq_len"],
                pages=s["max_seq_len"] // page, page=page,
                topk=m["index_topk"], rows=s["slots"] * m["index_topk"],
                lanes=-(-m["index_dim"] // 128) * 128,
                kv_lanes=m["num_kv_heads"] * m["head_dim"],
                heads=m["num_heads"])


def matched_s_and_runs(sources, params):
    """(seconds, runs) of the device events inside runs of the module
    that match ``params["pattern"]`` and not ``params["except"]``."""
    v = hybrid_moe.view(sources)
    if _sizes(sources) is None or not v or not params.get("pattern"):
        return 0.0, 0
    runs = sorted(v["runs"].get(params["module"], ()))
    if not runs:
        return 0.0, 0
    sz = shapes(sources)
    pat = re.compile(params["pattern"] % sz)
    but = re.compile(params["except"] % sz) if params.get("except") \
        else None
    starts, total = [a for a, _ in runs], 0.0
    for a, b, name in v["ops"]:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < runs[i][1] and pat.search(name) \
                and not (but and but.search(name)):
            total += b - a
    return total, len(runs)


def ops_ms_per_run(sources, params):
    """Device time of the matched events a run of the module, ms."""
    s, runs = matched_s_and_runs(sources, params)
    return 1e3 * s / runs if s and runs else None


def indexer_roofline(sources, params):
    """Kernels: a step's live positions x layers x the PUBLISHED index
    row's bytes, read once, over the HBM bandwidth, over the SUM of the
    score operations' time (``pattern``) and the selection's (the
    events that match ``select_pattern`` and not ``pattern``), a step,
    in %."""
    m, c = _sizes(sources), _counters(sources)
    score, runs = matched_s_and_runs(sources, params)
    select, _ = matched_s_and_runs(sources, {
        "module": params.get("module"),
        "pattern": params.get("select_pattern"),
        "except": params.get("pattern")})
    if m is None or not score or not c.get("decode_steps") \
            or not c.get("decode_index_positions_scored"):
        return None
    need = fi.indexer_bytes(
        c["decode_index_positions_scored"] / c["decode_steps"],
        m["num_layers"], m["index_dim"],
        sources["config"]["serving"].get("cache_dtype", "float32"))
    return 100.0 * need / (sources["peaks"]["hbm_gbps"] * 1e9) \
        / ((score + select) / runs)


def sparse_attn_roofline(sources, params):
    """Kernels: the sum over a step's live slot-layers of min(context,
    topk) rows of K and V at their published width, read once, over the
    HBM bandwidth, over the time of the operations that gather and attend
    them, a step, in %.  The same work whatever implements it."""
    m, c = _sizes(sources), _counters(sources)
    s, runs = matched_s_and_runs(sources, params)
    if m is None or not s or not c.get("decode_steps") \
            or not c.get("decode_index_positions_selected"):
        return None
    need = fi.sparse_attention_bytes(
        c["decode_index_positions_selected"] / c["decode_steps"],
        m["num_layers"], m["num_kv_heads"], m["head_dim"],
        sources["config"]["serving"].get("cache_dtype", "float32"))
    return 100.0 * need / (sources["peaks"]["hbm_gbps"] * 1e9) / (s / runs)


def selected_share_of_context(sources, params):
    """Model step: positions selected over positions scored, the
    window's steps, in % (the program's two counters)."""
    c = _counters(sources)
    if _sizes(sources) is None \
            or not c.get("decode_index_positions_scored"):
        return None
    return 100.0 * c.get("decode_index_positions_selected", 0) \
        / c["decode_index_positions_scored"]


def index_row_bytes(sources, params):
    """Model step: bytes the index pool takes a position a layer (the
    program's ``decode_index_bytes`` gauge over the pool's rows): 128
    published, what the pool's layout really takes."""
    serve = sources.get("serve") or {}
    held = (serve.get("gauges") or {}).get("decode_index_bytes")
    if not held or not serve.get("index_pool_rows") \
            or _sizes(sources) is None:
        return None
    return held / serve["index_pool_rows"]
