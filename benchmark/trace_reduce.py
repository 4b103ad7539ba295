"""From a profiler trace (``.xplane.pb``) to numbers.

The one reduction every PR's traced runs go through:

* device busy time: the union of the intervals in which an operation ran
  on a chip (the device plane's "XLA Ops" line), per chip and averaged;
* per-operation self time: an event's duration less the events nested in
  it (a ``while`` that holds a scan's body counts only its own part);
* the time of a named kernel: the summed durations of the events whose
  name matches a pattern;
* idle gaps on the first chip, each named by what the host was doing at
  the gap's middle: the innermost of the benchmark's own spans
  (``jax.profiler.TraceAnnotation`` names starting ``bench/``) or, where
  there is none, the longest host event that covers it.

Reads the file with ``jax.profiler.ProfileData`` alone: no chip, no
TensorFlow.  ``python benchmark/trace_reduce.py <file> [--describe]``
prints what a file holds, for the one look by hand that comes before
any code is written against a new kind of trace.
"""
import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
MIN_GAP_S = 20e-6          # shorter pauses between two ops are not gaps
# host events that only wrap others and say nothing about the work
_HOST_NOISE = ("ThreadpoolListener", "PythonRefManager", "$")


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


_OP = re.compile(r"^%?(?P<id>[\w.\-]+) = (?P<out>.*?) (?P<code>[\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"kind=(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_group(name):
    """A device event's name is its whole HLO instruction; the group is
    what is left when the instruction's number and operands go: opcode,
    fusion kind or custom-call target, and the output shapes without
    layouts.  The twelve layers' copies of one fusion share a group."""
    m = _OP.match(name)
    if not m:
        return name[:120]
    out = _LAYOUT.sub("", m.group("out"))
    label = m.group("code")
    extra = _KIND.search(name) or _TARGET.search(name)
    if extra:
        label += ":" + extra.group(1)
    return f"{label} -> {out}"[:160]


def _events(line):
    """[(start_s, end_s, name)] of one line, sorted by start."""
    out = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
           for e in line.events]
    out.sort(key=lambda t: (t[0], -t[1]))
    return out


def union(intervals):
    """Merged, sorted, disjoint intervals of [(start, end), ...]."""
    merged = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals):
    return sum(b - a for a, b in intervals)


def self_times(events):
    """{name: [self seconds, total seconds, count]} of one line's events:
    self time is the duration less the directly nested events."""
    out = {}
    stack = []                      # [end, name, duration, children]

    def close(item):
        end, name, dur, kids = item
        row = out.setdefault(name, [0.0, 0.0, 0])
        row[0] += max(dur - kids, 0.0)
        row[1] += dur
        row[2] += 1

    for a, b, name in events:
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(b, stack[-1][0]) - a
        stack.append([b, name, b - a, 0.0])
    while stack:
        close(stack.pop())
    return out


def _host_events(pd):
    """(bench spans, other host events), each [(start, end, name)]."""
    spans, others = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) or not plane.name.startswith(
                "/host:"):
            continue
        for line in plane.lines:
            for a, b, name in _events(line):
                if name.startswith(SPAN_PREFIX):
                    spans.append((a, b, name))
                elif b > a and not name.startswith(_HOST_NOISE):
                    others.append((a, b, name))
    spans.sort()
    others.sort()
    return spans, others


def _covering(events, starts, t, innermost):
    """The event of sorted ``events`` that covers time ``t``: the
    shortest (``innermost``) or the longest."""
    best = None
    i = bisect.bisect_right(starts, t)
    for a, b, name in events[max(0, i - 4096):i]:
        if a <= t < b:
            if best is None or ((b - a < best[0]) == innermost):
                best = (b - a, name)
    return best[1] if best else None


def reduce(path, kernels=None):
    """The reduction.  ``kernels``: {label: regex} of device-op names
    whose events' durations are summed under ``label``.

    Returns a dict: ``chips``, ``window_s`` (the ``bench/window`` span,
    else first to last device event), ``busy_s`` (mean over chips, inside
    the window) and ``busy_s_per_chip``, ``ops`` {name: {self_s, total_s,
    count}} summed over chips, ``modules`` {program: {total_s, count}},
    ``kernel_s`` {label: seconds, mean over chips}, ``device_ops`` (self
    time by ``op_group``) and ``idle_gaps`` (top ten, [name, seconds])."""
    pd = load(path)
    spans, others = _host_events(pd)
    per_chip, module_events = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                per_chip[int(m.group(1))] = _events(line)
            elif line.name == MODULES_LINE:
                module_events.append(_events(line))
    if not per_chip:
        raise ValueError(
            f"{path}: no device plane with an {OPS_LINE!r} line "
            f"(planes: {[p.name for p in pd.planes]})")
    window = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if window:
        lo, hi = window[0][0], window[-1][1]
    else:
        lo = min(ev[0][0] for ev in per_chip.values() if ev)
        hi = max(max(b for _, b, _ in ev) for ev in per_chip.values() if ev)

    busy, ops, kernel_s = [], {}, {k: 0.0 for k in (kernels or {})}
    groups = {}
    pats = {k: re.compile(v) for k, v in (kernels or {}).items()}
    for chip in sorted(per_chip):
        ev = [(max(a, lo), min(b, hi), n) for a, b, n in per_chip[chip]
              if min(b, hi) > max(a, lo)]
        busy.append(total(union([(a, b) for a, b, _ in ev])))
        for name, (s, t, c) in self_times(ev).items():
            row = ops.setdefault(name, {"self_s": 0.0, "total_s": 0.0,
                                        "count": 0})
            row["self_s"] += s
            row["total_s"] += t
            row["count"] += c
            group = op_group(name)
            groups[group] = groups.get(group, 0.0) + s
            for label, pat in pats.items():
                if pat.search(name):
                    kernel_s[label] += t
    n = len(per_chip)
    kernel_s = {k: v / n for k, v in kernel_s.items()}
    # whole programs ("jit_step(<fingerprint>)" -> "jit_step"): runs that
    # lie wholly inside the window, summed over chips
    modules = {}
    for ev in module_events:
        for a, b, name in ev:
            if a >= lo and b <= hi:
                row = modules.setdefault(name.split("(")[0],
                                         {"total_s": 0.0, "count": 0})
                row["total_s"] += b - a
                row["count"] += 1

    first = per_chip[min(per_chip)]
    merged = union(clip([(a, b) for a, b, _ in first], lo, hi))
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= MIN_GAP_S]
    inner = [s for s in spans if s[2] != WINDOW_SPAN]
    inner_starts = [s[0] for s in inner]
    other_starts = [s[0] for s in others]
    by_name = {}
    for a, b in gaps:
        mid = (a + b) / 2
        name = _covering(inner, inner_starts, mid, innermost=True)
        host = _covering(others, other_starts, mid, innermost=False)
        label = name or "no_bench_span"
        if host:
            label += " | " + host
        by_name[label] = by_name.get(label, 0.0) + (b - a)

    def top(pairs):
        return [[k, v] for k, v in sorted(
            pairs, key=lambda kv: -kv[1])[:10]]

    return {
        "chips": n,
        "window_s": hi - lo,
        "busy_s": sum(busy) / n,
        "busy_s_per_chip": busy,
        "ops": ops,
        "kernel_s": kernel_s,
        "modules": modules,
        "device_ops": top((k, v / n) for k, v in groups.items()),
        "idle_gaps": top(by_name.items()),
    }


def describe(path, top=25):
    """What the file holds: planes, lines, event counts, the names that
    took most time on each line.  For reading by hand."""
    pd = load(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            ev = _events(line)
            if not ev:
                continue
            agg = {}
            for a, b, name in ev:
                row = agg.setdefault(name, [0.0, 0])
                row[0] += b - a
                row[1] += 1
            names = sorted(agg.items(), key=lambda kv: -kv[1][0])[:top]
            lines.append({
                "line": line.name, "events": len(ev),
                "first_s": ev[0][0], "last_s": max(b for _, b, _ in ev),
                "union_s": total(union([(a, b) for a, b, _ in ev])),
                "top": [[n, round(s, 6), c] for n, (s, c) in names]})
        out.append({"plane": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    if "--describe" in sys.argv[2:]:
        print(json.dumps(describe(target), indent=1))
    else:
        red = reduce(target)
        red.pop("ops")
        print(json.dumps(red, indent=1))
