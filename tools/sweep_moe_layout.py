"""Time the grouped experts' pair layout ALONE at the routed cells' prompt shapes.

The layout is what `ops/pallas_moe_grouped.py` `grouped_share_ffn` does
before its two kernels: from ``local [rows, n_held]`` to the five arrays
the kernels are fed (``slot_row``, ``slot_weight``, ``tile_expert``,
``tile_live``, ``n_active``).  This tool runs it on the chip, without the
kernels, on a seeded router's ``local`` at each cell's (rows, n_held,
top_k, num_experts), in the form the module serves (``served``:
`layout_candidates` + `layout_pass`), in the form it had until PR 55
(``parent``: a copy kept here so that the table can be read again: one
1-D running count over ``n_held x rows`` entries and two scatters of as
many updates), and in the forms the module did NOT take (``FORMS``): ms a
call from a traced loop's device time, the scatter's updates and ns an
update, and the device's operations by their time.

    chiprun -- python -m tools.sweep_moe_layout [--shapes lfm2,kimi]
        [--forms parent,served] [--ops 6]

Prints one JSON line a (shape, form) and writes them all to
``chiprun_out/sweep_moe_layout.json``.  Every form's five arrays are
compared with the parent's, element for element (``equal``).  A TPU or
nothing: a time from the CPU is not a time (``--tiny`` rehearses the walk
on the CPU at small sizes).
"""
from __future__ import annotations

import argparse
import collections
import functools
import glob
import json
import math
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import pallas_moe_grouped as grouped

# a cell's whole-prompt prefill: rows, held experts, the model's top_k of
# num_experts, the share of the bucket's rows that are a prompt's
SHAPES = {
    "lfm2": (2048, 32, 4, 32, 0.72),
    "mimo_2048": (2048, 16, 8, 256, 0.8),
    "mimo_1024": (1024, 16, 8, 256, 0.8),
    "mimo_512": (512, 16, 8, 256, 0.8),
    "command_a": (4096, 8, 8, 128, 0.97),
    "kimi": (8192, 12, 8, 384, 0.98),
    "solar_1024": (1024, 40, 8, 320, 0.8),
    "solar_512": (512, 40, 8, 320, 0.8),
}
TINY = {"all_held": (512, 16, 4, 16, 0.6), "share": (512, 8, 8, 128, 0.9),
        "odd": (256, 12, 8, 384, 0.9)}


def seeded_local(rows, n_held, top_k, num_experts, live, seed):
    """A seeded router's ``local [rows, n_held]``: every live row's
    ``top_k`` of ``num_experts`` by normal scores, softmax weights over
    the chosen, the chip holding experts 0 .. n_held - 1; rows past the
    prompt's length choose nothing."""
    scores = jax.random.normal(jax.random.PRNGKey(seed),
                               (rows, num_experts), jnp.float32)
    top, ids = lax.top_k(scores, top_k)
    w = jax.nn.softmax(top, axis=-1)
    w = jnp.where(jnp.arange(rows)[:, None] < int(live * rows), w, 0.0)
    return jnp.zeros((rows, num_experts), jnp.float32).at[
        jnp.arange(rows)[:, None], ids].set(w)[:, :n_held]


def parent_layout(local, c, *, tm, m_rows, top_k=None):
    """The layout as `grouped_share_ffn` had it until PR 55, the same
    operations moved: one running count over ``n_held x rows`` entries,
    and two scatters of as many updates each."""
    rows, n_held = local.shape
    cap, n_tiles = m_rows - n_held * tm, m_rows // tm
    flat = local.T.reshape(-1)
    hit = flat != 0.0
    count = jnp.cumsum(hit, dtype=jnp.int32)
    ends = count[rows - 1::rows]
    starts, pairs = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]]), \
        ends[-1]
    entry = jnp.arange(n_held * rows, dtype=jnp.int32)
    expert, row = entry // rows, entry % rows
    tile_at = jnp.arange(n_tiles, dtype=jnp.int32) * tm
    lo = c * cap
    hi = jnp.minimum(lo + cap, pairs)
    first = jnp.clip(starts, lo, hi)
    size = jnp.clip(ends, lo, hi) - first
    padded = -(-size // tm) * tm
    p_end = jnp.cumsum(padded)
    p_start = p_end - padded
    tile_expert = jnp.minimum(jnp.searchsorted(
        p_end, tile_at, side="right"), n_held - 1).astype(jnp.int32)
    tile_live = jnp.clip(
        size[tile_expert] - (tile_at - p_start[tile_expert]), 0, tm)
    p = count - 1
    slot = jnp.where(hit & (p >= lo) & (p < hi),
                     (p_start - first)[expert] + p, m_rows + entry)
    slot_row = jnp.zeros(m_rows, jnp.int32).at[slot].set(
        row, mode="drop", unique_indices=True)
    slot_weight = jnp.zeros(m_rows, jnp.float32).at[slot].set(
        flat.astype(jnp.float32), mode="drop", unique_indices=True)
    n_active = (p_end[-1] // tm).reshape(1)
    return slot_row, slot_weight, tile_expert, tile_live, n_active


def served_layout(local, c, *, tm, m_rows, top_k=None):
    return grouped.layout_pass(grouped.layout_candidates(local, top_k), c,
                               tm=tm, m_rows=m_rows)


# -- the forms the module did not take: the served layout with ONE of its
# -- three parts replaced (the count, the candidates, the scatter) --------

def _count_1d(hit):
    """The served count: one running count over all the entries."""
    return jnp.cumsum(hit.reshape(-1), dtype=jnp.int32).reshape(hit.shape)


def _count_lanes(hit):
    """A scan along the rows an expert, the experts' offsets on top."""
    seen = jnp.cumsum(hit, axis=1, dtype=jnp.int32)
    return seen + (jnp.cumsum(seen[:, -1]) - seen[:, -1])[:, None]


def _count_matmul(hit):
    """Blocks of up to 128 rows against a triangle of ones on the matrix
    unit (0/1 in bfloat16, sums in float32: exact), the blocks' totals
    counted on."""
    b = math.gcd(hit.size, 128)
    blocks = hit.reshape(-1, b).astype(jnp.bfloat16)
    within = jnp.matmul(blocks, jnp.triu(jnp.ones((b, b), jnp.bfloat16)),
                        preferred_element_type=jnp.float32
                        ).astype(jnp.int32)
    before = jnp.cumsum(within[:, -1]) - within[:, -1]
    return (within + before[:, None]).reshape(hit.shape)


COUNTS = {"1d": _count_1d, "lanes": _count_lanes, "matmul": _count_matmul}


def _pick_sum(planes, hit, k):
    nth = jnp.where(hit, jnp.cumsum(hit, axis=0, dtype=jnp.int32), 0)
    which = lax.broadcasted_iota(jnp.int32, (k, 1, 1), 0) + 1
    return [jnp.sum(jnp.where(nth[None] == which, p[None], 0), axis=1)
            for p in planes]


def _pick_top_k(planes, hit, k):
    _, ids = lax.top_k(hit.T.astype(jnp.float32), k)        # [rows, k]
    return [jnp.take_along_axis(p.T, ids, axis=1).T
            * jnp.take_along_axis(hit.T, ids, axis=1).T.astype(p.dtype)
            for p in planes]


PICKS = {"sum": _pick_sum, "top_k": _pick_top_k}


def _place_two(slot, row, bits, m_rows):
    """Two scatters, as the parent's, of the candidates alone."""
    zeros = jnp.zeros(m_rows, jnp.int32)
    return (zeros.at[slot].set(row, mode="drop", unique_indices=True),
            zeros.at[slot].set(bits, mode="drop", unique_indices=True))


def _place_rows2(slot, row, bits, m_rows):
    """One scatter of two-word updates into ``[m_rows, 2]``."""
    taken = jnp.zeros((m_rows, 2), jnp.int32).at[slot].set(
        jnp.stack([row, bits], axis=-1), mode="drop", unique_indices=True)
    return taken[:, 0], taken[:, 1]


def _place_index(slot, row, bits, m_rows):
    """The candidate's own index scattered once, row and weight read
    back through it."""
    n = slot.shape[0]
    src = jnp.zeros(m_rows, jnp.int32).at[slot].set(
        jnp.arange(1, n + 1, dtype=jnp.int32), mode="drop",
        unique_indices=True)
    at = jnp.maximum(src - 1, 0)
    return (jnp.where(src > 0, row[at], 0), jnp.where(src > 0, bits[at], 0))


PLACES = {"two": _place_two, "rows2": _place_rows2, "index": _place_index}


def form_layout(local, c, *, tm, m_rows, top_k=None, count="1d",
                pick="sum", place="rows2"):
    """The served layout's steps with each part a named form."""
    rows, n_held = local.shape
    k = n_held if top_k is None else min(int(top_k), n_held)
    weight = local.T.astype(jnp.float32)
    hit = weight != 0.0
    count = COUNTS[count](hit)
    order, ends = jnp.where(hit, count, 0), count[:, -1]
    lo, hi, shift, tile_expert, tile_live, n_active = grouped.pass_tiles(
        ends, c, tm=tm, m_rows=m_rows)
    place_at = jnp.where((order > lo) & (order <= hi),
                         shift[:, None] + order, 0)
    bits = lax.bitcast_convert_type(weight, jnp.int32)
    if k < n_held:
        place_at, bits = PICKS[pick]([place_at, bits], hit, k)
    row = lax.broadcasted_iota(jnp.int32, (k, rows), 1).reshape(-1)
    place_at = place_at.reshape(-1)
    slot = jnp.where(place_at > 0, place_at - 1,
                     m_rows + jnp.arange(k * rows, dtype=jnp.int32))
    slot_row, slot_bits = PLACES[place](slot, row, bits.reshape(-1), m_rows)
    return (slot_row, lax.bitcast_convert_type(slot_bits, jnp.float32),
            tile_expert, tile_live, n_active)


FORMS = {
    "parent": parent_layout,
    "served": served_layout,
    "count_lanes": functools.partial(form_layout, count="lanes"),
    "count_matmul": functools.partial(form_layout, count="matmul"),
    "pick_top_k": functools.partial(form_layout, pick="top_k"),
    "place_two": functools.partial(form_layout, place="two"),
    "place_index": functools.partial(form_layout, place="index"),
}


def is_scatter(name, m_rows):
    """Whether a device event is a layout's scatter: the fusion around
    one (``kind=kCustom``, as the benchmark prints it) whose result is
    the sorted buffer."""
    return "kind=kCustom" in name and re.search(
        rf" = \w+\[{m_rows}[,\]]", name) is not None


def device_ops(fn, args, runs):
    """(us a run of device time, [(instruction, calls a run, us a run)])
    of ``runs`` traced calls: the device's "XLA Ops" line, an event's
    own time less the events nested in it, longest first."""
    out_dir = tempfile.mkdtemp(prefix="moe_layout_trace_")
    jax.profiler.start_trace(out_dir)
    for _ in range(runs):
        jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    own, calls = collections.Counter(), collections.Counter()
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            stack = []                  # [end, name, own ns]
            for ev in sorted(line.events, key=lambda e: (
                    e.start_ns, -e.duration_ns)):
                while stack and stack[-1][0] <= ev.start_ns:
                    _, name, ns = stack.pop()
                    own[name] += ns
                if stack:
                    stack[-1][2] -= ev.duration_ns
                stack.append([ev.start_ns + ev.duration_ns, ev.name,
                              ev.duration_ns])
                calls[ev.name] += 1
            for _, name, ns in stack:
                own[name] += ns
    return sum(own.values()) / 1e3 / runs, [
        (n, round(calls[n] / runs, 1), round(ns / 1e3 / runs, 2))
        for n, ns in own.most_common()]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=None)
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--runs", type=int, default=20)
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--ops", type=int, default=8,
                    help="device operations listed a (shape, form)")
    ap.add_argument("--out", default="chiprun_out/sweep_moe_layout.json")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    if not a.tiny and jax.default_backend() != "tpu":
        raise SystemExit("a TPU or nothing: a time from the CPU is no time")
    shapes = TINY if a.tiny else SHAPES
    lines = []
    for name in (a.shapes.split(",") if a.shapes else shapes):
        rows, n_held, top_k, num_experts, live = shapes[name]
        tm = grouped.default_tiles(rows, n_held, top_k, num_experts)
        m_rows = grouped.sorted_rows(rows, n_held, top_k, num_experts)
        local = seeded_local(rows, n_held, top_k, num_experts, live, a.seed)
        want = None
        for form in a.forms.split(","):
            line = dict(shape=name, form=form, rows=rows, n_held=n_held,
                        top_k=top_k, num_experts=num_experts, tile=tm,
                        buffer=m_rows, pairs=int((local != 0).sum()),
                        device=jax.devices()[0].device_kind)
            try:
                args = (local, jnp.int32(0))
                fn = jax.jit(functools.partial(
                    FORMS[form], tm=tm, m_rows=m_rows, top_k=top_k)
                    ).lower(*args).compile()
                got = jax.block_until_ready(fn(*args))
                line["updates"] = {
                    "parent": rows * n_held,
                    "served": stat_get("moe_grouped_layout_updates"),
                }.get(form, rows * min(top_k, n_held))
                want = want or got
                line["equal"] = all(
                    np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(got, want))
                if not a.tiny:
                    us, ops = device_ops(fn, args, a.runs)
                    walks = [o for o in ops if is_scatter(o[0], m_rows)]
                    line.update(
                        us_a_call=round(us, 2), scatters=len(walks),
                        scatter_us=round(sum(o[2] for o in walks), 2),
                        scatter_ns_an_update=round(
                            1e3 * sum(o[2] for o in walks)
                            / (line["updates"] * max(len(walks), 1)), 3),
                        ops=[(n[:150], c, us) for n, c, us in ops[:a.ops]])
            except Exception as e:      # a form the chip's compiler refuses
                line["error"] = f"{type(e).__name__}: {e}"[:400]
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
