"""The engine loop's period on one clock: the device's idle time split
over the host phase it lies under.

A ``--trace 1`` window holds the device's events on the device's clock
and the program's spans on the host's, and the two differ by an offset
of a millisecond or two that changes from trace to trace (read by hand
on ``tests/data/tiny_engine_trace.xplane.pb.gz`` first: a ``jit_step``
run "starts" 0.6 ms BEFORE the ``serving/step_dispatch`` that hands it
over).  A gap of 3 ms cannot be handed to a phase of 0.3 ms across
that.  So, per trace:

1. **The offset** d = device clock - host clock is bounded by
   causality.  A run cannot start before its hand-over begins, and
   cannot end after its read-back has returned: over every joint step
   k, max_k(run end - sync end) <= d <= min_k(run start - dispatch
   begin).  The runtime's own host events tighten both sides (they are
   host-stamped too, so they are causality bounds like the spans, not
   an anchor): ``DoEnqueueProgram`` carries the ``run_id`` of the
   device's "XLA Modules" event it enqueues (every program's run, the
   prefills' too: a run cannot start before its enqueue begins), and the
   completion callback ``tpu::System::Execute=>Done`` that begins last
   inside a step's hand-over..read-back interval is that step's (a run
   cannot end after its callback begins).  d is taken midway; the width
   is reported (``clock_align_slack_ms.serve``): each part below is
   uncertain by about half of it a step.  Bounds that cross are a wrong
   pairing: None, never a number.
2. **The split.**  Chip 0's idle time inside the window is cut in two:
   what lies inside a program's run (between two ops of one
   ``jit_step``: the device's own) and what lies between runs.  The
   second, shifted by d, is shared out over the engine thread's leaf
   spans BY OVERLAP (a gap under sync -> deliver -> reap -> args ->
   dispatch gives each its own part), grouped as ``PARTS``; what no
   named span covers joins the first as ``unattributed``.  The five
   parts sum to the window's idle time.

Pure functions over lists of intervals (``offset_bounds``,
``pair_steps``, ``split_idle``), a loader around them (``load``,
``measure``), and the readers the metric files name.  Every reader
returns None where there is nothing to read: a run without a trace, a
program without the spans, a pairing that fails.
"""
import bisect
import os

from benchmark import trace_reduce as tr
from benchmark.readers import engine

PARTS = {
    "deliver": ("step_deliver", "prefill_deliver"),
    "prepare": ("reap", "step_cow", "step_args", "lock_wait", "admit",
                "prefill_args"),
    "dispatch": ("step_dispatch", "prefill_dispatch"),
    "sync": ("step_sync", "prefill_sync"),
}
REST = "unattributed"
_PART_OF = {engine.PREFIX + name: part
            for part, names in PARTS.items() for name in names}
DISPATCH, SYNC = "serving/step_dispatch", "serving/step_sync"
ENQUEUE = "DoEnqueueProgram"            # stat run_id: the run it enqueues
DONE = "tpu::System::Execute=>Done"     # the runtime's completion callback
_CACHE = {}


# -- pure functions ---------------------------------------------------------

def offset_bounds(not_before, not_after):
    """(lo, hi) of d = device clock - host clock.  ``not_before``:
    [(host t, device t)], a device instant that cannot lie before that
    host instant (d <= device - host); ``not_after``: one that cannot lie
    after it (d >= device - host).  None where either list is empty or
    the bounds cross (a wrong pairing)."""
    if not not_before or not not_after:
        return None
    hi = min(dev - host for host, dev in not_before)
    lo = max(dev - host for host, dev in not_after)
    return (lo, hi) if lo <= hi else None


def pair_steps(steps, runs, enqueued):
    """[(step, run)]: each joint step of ``steps`` ([(hand-over begin,
    read-back end)], host clock, in order) with its run of ``runs``
    ([(start, end, run_id)], device clock): the last one whose enqueue
    (``enqueued``: {run_id: host time it began}) lies inside the step's
    own interval, both on the host's clock.  A step without one (the
    window's cut ends) is left out: nothing goes by rank."""
    by_time = sorted((enqueued[r[2]], r) for r in runs if r[2] in enqueued)
    times = [t for t, _ in by_time]
    out = []
    for step in steps:
        i = bisect.bisect_right(times, step[1])
        if i and times[i - 1] >= step[0]:
            out.append((step, by_time[i - 1][1]))
    return out


def split_idle(idle, spans, offset):
    """{part: seconds} of the device-clock intervals ``idle`` under the
    host-clock ``spans`` ([(start, end, part)], disjoint, sorted), the
    idle shifted onto the host's clock by ``offset``; what no span
    covers under ``REST``.  The parts sum to the idle."""
    out = dict.fromkeys(list(PARTS) + [REST], 0.0)
    starts = [s[0] for s in spans]
    for a, b in idle:
        a, b = a - offset, b - offset
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(spans) and spans[i][0] < b:
            lo, hi, part = spans[i]
            over = min(hi, b) - max(lo, a)
            if over > 0:
                out[part] += over
                covered += over
            i += 1
        out[REST] += (b - a) - covered
    return out


def complement(intervals, lo, hi):
    """What ``union(intervals)`` leaves of [lo, hi]."""
    merged = tr.union(tr.clip(intervals, lo, hi))
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def measure(window, spans, ops, runs, enqueued, done=(),
            module="jit_step"):
    """The whole reduction over lists.  ``window``: (lo, hi), host
    clock; ``spans``: the engine thread's [(start, end, name, attrs)];
    ``ops``: chip 0's [(start, end)]; ``runs``: its programs' [(start,
    end, name, run_id)]; ``enqueued``: {run_id: host time}; ``done``:
    sorted host times at which a completion callback began.  Returns
    {"offset_s", "slack_s", "steps", "idle_s", "parts": {part: s}} or
    None where the clocks cannot be aligned."""
    dispatch = {s[3].get("step"): s for s in spans if s[2] == DISPATCH}
    steps = sorted((dispatch[s[3].get("step")][0], s[1]) for s in spans
                   if s[2] == SYNC and s[3].get("step") in dispatch)
    step_runs = [(a, b, rid) for a, b, name, rid in runs if name == module]
    pairs = pair_steps(steps, step_runs, enqueued)
    if not pairs:
        return None
    not_before = [(step[0], run[0]) for step, run in pairs]
    not_after = [(step[1], run[1]) for step, run in pairs]
    not_before += [(enqueued[rid], a) for a, _, _, rid in runs
                   if rid in enqueued]
    for step, run in pairs:
        i = bisect.bisect_right(done, step[1])
        if i and done[i - 1] >= step[0]:
            not_after.append((done[i - 1], run[1]))
    bounds = offset_bounds(not_before, not_after)
    if bounds is None:
        return None
    offset = (bounds[0] + bounds[1]) / 2
    lo, hi = window[0] + offset, window[1] + offset     # device clock
    busy = tr.union(tr.clip(ops, lo, hi))
    covered = tr.union(busy + tr.clip([r[:2] for r in runs], lo, hi))
    leaves = sorted((a, b, _PART_OF[name]) for a, b, name, _ in spans
                    if name in _PART_OF)
    parts = split_idle(complement(covered, lo, hi), leaves, offset)
    # between two ops of one program's run: the device's own
    parts[REST] += tr.total(covered) - tr.total(busy)
    return {"offset_s": offset, "slack_s": bounds[1] - bounds[0],
            "steps": sum(lo <= r[0] < hi for r in step_runs),
            "idle_s": (hi - lo) - tr.total(busy), "parts": parts}


# -- the loader -------------------------------------------------------------

def load(path):
    """(ops, runs, enqueued, done) of one trace, as ``measure`` takes
    them: the first chip's two lines and the runtime's host events."""
    ops, runs, enqueued, done, chip = [], [], {}, [], None
    for plane in tr.load(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m and (chip is None or int(m.group(1)) < chip):
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = [(a, b) for a, b, _ in tr._events(line)]
                elif line.name == tr.MODULES_LINE:
                    runs = sorted(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         e.name.split("(")[0], dict(e.stats).get("run_id"))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ENQUEUE:
                        run_id = dict(e.stats).get("run_id")
                        if run_id is not None:
                            enqueued[run_id] = e.start_ns * 1e-9
                    elif e.name == DONE:
                        done.append(e.start_ns * 1e-9)
    return ops, runs, enqueued, sorted(done)


def view(sources):
    """``measure`` of this run's trace (first engine thread, first
    chip); None for a run without a trace or without the spans."""
    v = engine.view(sources)
    if not v:
        return None
    path = tr.find_xplane(os.path.join(
        engine.ROOT, ".bench_runs", sources["spec"]["name"], "trace"))
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        ops, runs, enqueued, done = load(path)
        _CACHE[key] = measure(v["window"], v["threads"][0], ops, runs,
                              enqueued, done)
    return _CACHE[key]


# -- the readers ------------------------------------------------------------

def idle_ms_per_step(sources, params):
    """Device idle time under the host phases of ``params["part"]`` (a
    key of ``PARTS``, or ``unattributed``), over the window's joint
    steps, ms."""
    m = view(sources)
    if not m or not m["steps"]:
        return None
    return 1e3 * m["parts"][params["part"]] / m["steps"]


def align_slack_ms(sources, params):
    """Width of the causality bounds on the clocks' offset, ms."""
    m = view(sources)
    return 1e3 * m["slack_s"] if m else None
