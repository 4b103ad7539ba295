"""Per-layer readers of the decode engine's own spans.

The program's spans (``paddle_tpu/observe/tracer.py``) are
``jax.profiler.TraceAnnotation``s, so a ``--trace 1`` run's
``.xplane.pb`` holds them on the host plane, on the device trace's
clock, their attributes as the events' stats (seen with
``trace_reduce.py --describe`` on a chip trace: stats, never a tail of
the name).  One iteration of the
engine thread (``serving/decode.py`` ``_loop``) is a row of leaf spans
``serving/<phase>`` that follow one another; the engine thread is the
line that holds ``serving/step_dispatch``.

A reader opens the run's own trace (``<checkout>/.bench_runs/<cell>/
trace``, as ``run.py`` lays it out), parses it once a process, keeps
what lies inside ``bench/window``, and returns a number - or None where
there is nothing to read: a run without a trace, or a program without
these spans (the parent of the PR that added them).  A span's duration
is read, never a device gap: the device's clock runs about 1.5 ms ahead
of the host's in these traces, so a 4 ms gap may be handed to the
neighbouring phase, while a span's own start and end share one clock.

Every function takes ``params`` from its metric file, so a metric over
another span, attribute or histogram is a file, not code.
"""
import os

from benchmark import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PREFIX = "serving/"
ENGINE_MARK = "serving/step_dispatch"
# phases in which the host waits for the device or for work, or only
# hands a program over: not the host's own work on a step
_NOT_HOST_WORK = ("_dispatch", "_sync", "/idle_wait")
_CACHE = {}


def parse(path):
    """{"window": (lo, hi), "threads": [[(start, end, name, attrs)]]} of
    one trace: per engine thread, its ``serving/*`` spans that touch the
    window, sorted by start.  None without a window or an engine thread."""
    pd = tr.load(path)
    window, threads = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            rows = []
            for e in line.events:
                if e.name == tr.WINDOW_SPAN:
                    window = (e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                elif e.name.startswith(PREFIX):
                    a = e.start_ns * 1e-9
                    rows.append((a, a + e.duration_ns * 1e-9, e.name,
                                 dict(e.stats)))
            if any(r[2] == ENGINE_MARK for r in rows):
                threads.append(sorted(rows, key=lambda r: (r[0], -r[1])))
    if window is None or not threads:
        return None
    lo, hi = window
    threads = [[r for r in rows if r[1] > lo and r[0] < hi]
               for rows in threads]
    return {"window": window, "threads": threads}


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


def _inside(v, want):
    """Per engine thread, the spans ``want(name)`` accepts that lie
    wholly in the window."""
    lo, hi = v["window"]
    return [[r for r in rows if r[0] >= lo and r[1] <= hi and want(r[2])]
            for rows in v["threads"]]


def _flat(per_thread):
    return [r for rows in per_thread for r in rows]


def span_mean_ms(sources, params):
    """Mean duration of the spans called ``params["span"]``, ms."""
    v = view(sources)
    rows = _flat(_inside(v, lambda n: n == params["span"])) if v else []
    if not rows:
        return None
    return 1e3 * sum(b - a for a, b, _, _ in rows) / len(rows)


def host_ms_per_step(sources, params):
    """The engine thread's own work a decode step, ms: the time under
    every ``serving/*`` span except the dispatches, the syncs and the
    idle wait (nested spans counted once), over the window's
    ``decode_steps``.  Two replicas' threads both work: summed."""
    v = view(sources)
    steps = (sources.get("serve", {}).get("counters") or {}).get(
        "decode_steps")
    if not v or not steps:
        return None
    busy = sum(tr.total(tr.union([(a, b) for a, b, _, _ in rows]))
               for rows in _inside(
                   v, lambda n: not n.endswith(_NOT_HOST_WORK)))
    return 1e3 * busy / steps if busy else None


def attr_per_span(sources, params):
    """Sum of the attribute ``params["attr"]`` over the spans whose name
    ends in ``params["suffix"]``, over the count of ``params["per"]``
    spans."""
    v = view(sources)
    if not v:
        return None
    per = _flat(_inside(v, lambda n: n == params["per"]))
    vals = [r[3][params["attr"]] for r in _flat(_inside(
        v, lambda n: n.endswith(params["suffix"])))
        if params["attr"] in r[3]]
    if not per or not vals:
        return None
    return sum(float(x) for x in vals) / len(per)


def unspanned_share(sources, params):
    """Share of the window in which the engine thread is inside no
    ``serving/*`` span, in %; the mean over engine threads.  The window
    is taken from the thread's first recorded span to its last: a span
    in flight when the profiler session starts or stops leaves no event
    (a ``step_sync`` of 64 ms cut by the window's end would read as 1.6 %
    of a 4 s window under no span), so the two ends say nothing."""
    v = view(sources)
    if not v:
        return None
    shares = []
    for rows in v["threads"]:
        spans = tr.clip([(a, b) for a, b, _, _ in rows], *v["window"])
        if spans:
            lo, hi = spans[0][0], max(b for _, b in spans)
            shares.append(1.0 - tr.total(tr.union(spans)) / (hi - lo))
    return 100.0 * sum(shares) / len(shares) if shares else None


def histogram_mean_ms(sources, params):
    """Difference of sum over difference of count, over the window, of
    the program's histogram ``params["histogram"]``, ms."""
    row = (sources.get("serve", {}).get("counters") or {}).get(
        params["histogram"])
    if not isinstance(row, dict) or not row.get("count"):
        return None
    return 1e3 * row["sum"] / row["count"]


def kernel_ms_per_run(sources, params):
    """Device time of the kernel ``params["kernel"]`` (the label of the
    metric file's own ``kernels`` pattern) inside the window over the
    runs of the program ``params["module"]`` there, ms.  None while no
    device event matches the pattern."""
    trace = sources.get("trace") or {}
    kernel_s = trace.get("kernel_s", {}).get(params["kernel"])
    runs = trace.get("modules", {}).get(params["module"], {}).get("count")
    if not kernel_s or not runs:
        return None
    return 1e3 * kernel_s / runs
