"""Per-layer readers of set-up: the program's birth log.

``paddle_tpu.observe.xla_stats.program_births()`` holds one record a
program the process compiled or loaded from the compile cache (its
trace, its lowering, its backend compile or cache load, hit or miss, on
the clock of the program's span buffer).  The window compiles nothing
(``correct`` requires it) and nothing after it runs a jax program, so
when a reader is called the log is set-up's: every function here reads
the whole of it, as ``latent_moe:latent_row_bytes`` reads the program's
gauge, and returns None on a program without the log (the parent of the
PR that added it) or with an empty one.

A metric here moves ``setup_s``; none is a share of it: what the births
do not cover (process start → chip, the weights' run, the check's
reference, the callers' fill) is ``setup_s`` less ``setup_births_s``.
"""


def _log(sources):
    """The births to read: the program's log, or ``sources["births"]``
    where a test hands a list over."""
    if "births" in sources:
        return sources["births"] or None
    try:
        from paddle_tpu.observe import xla_stats

        return xla_stats.program_births() or None
    except (ImportError, AttributeError):
        return None


def births_s(sources, params):
    """Seconds in which some program was being born: the union, over
    all threads, of the births' ``[t_begin, t_end]``."""
    log = _log(sources)
    if log is None:
        return None
    total, hi = 0.0, float("-inf")
    for a, b in sorted((r["t_begin"], r["t_end"]) for r in log):
        if b > hi:
            total += b - max(a, hi)
            hi = b
    return total


def _sum(sources, value, cache=None):
    """Σ ``value(record)`` over the births (of one ``cache`` outcome)."""
    log = _log(sources)
    if log is None:
        return None
    return sum(value(r) for r in log if cache is None or r["cache"] == cache)


def trace_lower_s(sources, params):
    """Σ ``trace_s`` + ``lower_s``: host work that no cache removes."""
    return _sum(sources, lambda r: r["trace_s"] + r["lower_s"])


def backend_compile_s(sources, params):
    """Σ ``backend_s`` of the misses: 0 on a warm side."""
    return _sum(sources, lambda r: r["backend_s"], "miss")


def cache_load_s(sources, params):
    """Σ ``cache_load_s`` of the hits: the read and the
    deserialization of cached executables."""
    return _sum(sources, lambda r: r["cache_load_s"], "hit")


def cache_misses(sources, params):
    """Births with ``cache == "miss"``: a cold side has some, a warm
    side none."""
    return _sum(sources, lambda r: 1, "miss")
