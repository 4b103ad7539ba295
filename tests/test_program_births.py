"""The birth log (PR 52): one record a program the process compiles.

``observe/xla_stats.py`` listens on ``jax.monitoring`` and assembles, per
thread, a program's trace, lowering and backend compile (or the cache
load that took its place), hit or miss, and the span it ran under.  It
covers every program, the serving engine's lazy ``jax.jit``s like the
Executor's AOT entries; ``on_compile``'s record holds the split of its
``compile_seconds``; a ``serving/slow_step`` event says what its process
had compiled; ``benchmark/readers/setup.py`` turns the log into the five
``setup_*`` metrics.
"""
import contextlib
import importlib
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, observe
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.program import Program, program_guard
from paddle_tpu.monitor import stat_get
from paddle_tpu.observe import flight, xla_stats
from paddle_tpu.observe.histogram import histogram
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine, \
    TransformerLM

from benchmark.readers import setup as setup_reader


def born_since(seq0, program=None):
    return [b for b in xla_stats.program_births() if b["seq"] > seq0
            and (program is None or b["program"] == program)]


def last_seq():
    return max((b["seq"] for b in xla_stats.program_births()), default=0)


def make_step(name, scale):
    """A fresh function object each call: a jit of it traces anew, and
    two of one name and scale lower to the same module."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.sin(x) * scale

    def step(x):
        return inner(x) + inner(x + 1.0).sum()

    step.__name__ = step.__qualname__ = name
    return jax.jit(step)


@contextlib.contextmanager
def compile_cache(path):
    """jax's persistent compile cache at ``path`` (None: no cache), every
    program cached whatever its size; the process's own settings after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = [getattr(jax.config, k) for k in keys]
    jax.config.update(keys[0], path)
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in zip(keys, old):
            jax.config.update(k, v)
        cc.reset_cache()


# -- one program, one record ------------------------------------------------

def test_a_jitted_function_is_one_birth_with_its_three_phases_in_order():
    import jax.numpy as jnp

    seq0 = last_seq()
    n0 = stat_get("xla_program_births")
    traced0 = histogram("xla_trace_seconds").count
    fn = make_step("born_once", 2.0)
    x = jnp.ones((4, 4))
    seq1 = last_seq()               # jnp.ones may be a program of its own
    fn(x)
    fn(x)                           # the second call compiles nothing
    (rec,) = born_since(seq1, "jit_born_once")
    assert [b["program"] for b in born_since(seq1)] == ["jit_born_once"]
    assert rec["thread"] == threading.current_thread().name
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["backend_s"] > 0
    # trace, lowering and backend follow one another inside the record
    assert rec["t_begin"] < rec["t_end"]
    assert rec["trace_s"] + rec["lower_s"] + rec["backend_s"] \
        <= rec["t_end"] - rec["t_begin"] + 1e-6
    assert rec["under"] is None and rec["under_attrs"] == {}
    assert stat_get("xla_program_births") - n0 == last_seq() - seq0
    assert histogram("xla_trace_seconds").count - traced0 \
        == last_seq() - seq0


def test_the_traces_of_inner_jitted_functions_are_not_added_twice():
    """jax reports ``inner``'s traces before ``nested``'s own, which
    spans them: the record's ``trace_s`` is the outer trace alone."""
    import jax
    import jax.numpy as jnp

    seen = []

    def on_duration(event, duration, **kw):
        if event == xla_stats._TRACE_EVENT:
            seen.append((kw.get("fun_name"), duration))

    x = jnp.ones((4, 4))
    seq0 = last_seq()
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        make_step("nested", 3.0)(x)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    (rec,) = born_since(seq0, "jit_nested")
    inner = [d for name, d in seen if name == "inner"]
    (outer,) = [d for name, d in seen if name == "nested"]
    assert len(inner) == 2 and rec["trace_s"] == outer
    assert sum(d for _, d in seen) > outer


# -- hit, miss, off ----------------------------------------------------------

def test_a_miss_then_a_hit_on_a_cache_directory_and_off_without_one(
        tmp_path):
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    x.block_until_ready()
    hits0, misses0 = stat_get("xla_cache_hits"), stat_get("xla_cache_misses")
    loads0 = histogram("xla_cache_load_seconds").count
    flight0 = max([e["seq"] for e in flight.tail()], default=0)
    with compile_cache(str(tmp_path)):
        seq0 = last_seq()
        make_step("cached_prog", 5.0)(x)
        make_step("cached_prog", 5.0)(x)    # another trace, the same module
    with compile_cache(None):
        make_step("cached_prog", 6.0)(x)
    cold, warm, off = born_since(seq0, "jit_cached_prog")
    assert [cold["cache"], warm["cache"], off["cache"]] \
        == ["miss", "hit", "off"]
    assert cold["cache_load_s"] is None and off["cache_load_s"] is None
    assert 0 < warm["cache_load_s"] <= warm["backend_s"]
    assert warm["compile_saved_s"] is not None
    assert warm["trace_s"] > 0 and warm["lower_s"] > 0   # no cache saves these
    assert stat_get("xla_cache_hits") - hits0 == 1
    assert stat_get("xla_cache_misses") - misses0 == 1
    assert histogram("xla_cache_load_seconds").count - loads0 == 1
    # every miss, and nothing else, is a flight-recorder event
    events = [e for e in flight.tail() if e["seq"] > flight0
              and e["event"] == "xla/program_born"]
    assert [(e["program"], e["birth"]) for e in events] \
        == [("jit_cached_prog", cold["seq"])]
    assert events[0]["backend_s"] == round(cold["backend_s"], 6)
    summary = xla_stats.births_summary()
    assert summary["cache_hits"] >= 1 and summary["cache_misses"] >= 1
    assert summary["births"] == sum(
        v["births"] for v in summary["by_cache"].values())
    assert summary["since_last_birth_s"] >= 0


# -- threads -----------------------------------------------------------------

def test_two_threads_compiling_at_once_give_two_records_that_do_not_mix():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    x.block_until_ready()
    seq0 = last_seq()
    gate = threading.Barrier(2, timeout=60)
    failures = []

    def compile_one(name, step):
        try:
            fn = make_step(name, float(step))
            gate.wait()
            with observe.span("t52/compile", step=step):
                fn(x)
        except Exception as e:  # noqa: BLE001 - reported by the assert
            failures.append(e)

    threads = [threading.Thread(target=compile_one, name=f"t52-{i}",
                                args=(f"prog_of_thread_{i}", 10 + i))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not failures and not any(t.is_alive() for t in threads)
    for i in range(2):
        (rec,) = born_since(seq0, f"jit_prog_of_thread_{i}")
        assert rec["thread"] == f"t52-{i}"
        assert rec["under"] == "t52/compile"
        assert rec["under_attrs"] == {"step": 10 + i}
        assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert len({b["seq"] for b in born_since(seq0)}) \
        == len(born_since(seq0))


# -- the span that caused it ---------------------------------------------------

def test_under_names_the_open_span_with_the_ring_buffer_off():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    x.block_until_ready()
    assert not observe.enabled()
    n_ring = len(observe.snapshot())
    seq0 = last_seq()
    with observe.span("t52/dispatch", iter=4, step=9, live=2):
        # a wrapper that says nothing of the step: not what is named
        with observe.span("t52/persistent", state=3):
            make_step("under_a_step", 7.0)(x)
    with observe.span("t52/outer"):
        with observe.span("t52/aot"):
            make_step("under_no_attrs", 7.5)(x)
    (rec,) = born_since(seq0, "jit_under_a_step")
    assert rec["under"] == "t52/dispatch"
    assert rec["under_attrs"] == {"iter": 4, "step": 9}
    (rec,) = born_since(seq0, "jit_under_no_attrs")
    assert rec["under"] == "t52/aot" and rec["under_attrs"] == {}
    assert len(observe.snapshot()) == n_ring      # the buffer took nothing
    assert observe.tracer.open_spans() == []


def test_births_are_closed_spans_of_the_ring_buffer_while_it_is_on():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    x.block_until_ready()
    pt.set_flags({"enable_tracer": True})
    observe.clear()
    try:
        seq0 = last_seq()
        with observe.span("t52/dispatch", bucket=64):
            make_step("in_the_ring", 8.0)(x)
        ring = observe.snapshot()
    finally:
        pt.set_flags({"enable_tracer": False})
        observe.clear()
    (rec,) = born_since(seq0, "jit_in_the_ring")
    mine = [r for r in ring if r.name.startswith("xla/")
            and r.args["birth"] == rec["seq"]]
    assert [r.name for r in mine] \
        == ["xla/trace", "xla/lower", "xla/backend_compile"]
    (outer,) = [r for r in ring if r.name == "t52/dispatch"]
    for r in mine:
        assert r.parent == "t52/dispatch" and r.depth == 1
        assert r.args["program"] == "jit_in_the_ring" and r.args["bucket"] == 64
        assert outer.t_begin <= r.t_begin <= r.t_end <= outer.t_end
    assert [round(r.duration, 9) for r in mine] == [
        round(rec[k], 9) for k in ("trace_s", "lower_s", "backend_s")]
    assert mine[0].t_begin == rec["t_begin"] and mine[2].t_end == rec["t_end"]
    assert mine[0].t_end <= mine[1].t_begin <= mine[1].t_end \
        <= mine[2].t_begin
    # and chrome_trace() shows set-up as it shows a step
    doc = observe.chrome_trace(ring)
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert "xla/backend_compile" in names and "t52/dispatch" in names


# -- the executor's record says what it timed ----------------------------------

def test_the_aot_path_is_one_birth_and_on_compiles_record_holds_it():
    main, startup = Program(), Program()
    main.random_seed = 3
    with unique_name.guard(), program_guard(main, startup):
        x = layers.data("x", [8])
        y = layers.data("y", [1])
        pred = layers.fc(layers.fc(x, 16, act="relu"), 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    xla_stats.clear_compile_records()
    seq0 = last_seq()
    X = np.random.RandomState(0).randn(16, 8).astype("f4")
    exe.run(main, feed={"x": X, "y": X.sum(1, keepdims=True)},
            fetch_list=[loss], scope=scope)
    rec = xla_stats.last_compile()
    birth = rec["birth"]
    (born,) = [b for b in born_since(seq0) if b["seq"] == birth["seq"]]
    assert born["under"] == "executor/aot_compile"
    assert sum(b["under"] == "executor/aot_compile"
               for b in born_since(seq0)) == 1
    assert birth["cache"] in ("hit", "miss", "off")
    for key in ("program", "trace_s", "lower_s", "backend_s", "cache"):
        assert birth[key] == born[key]
    # the split lies inside what compile_seconds timed
    assert 0 < birth["trace_s"] + birth["lower_s"] + birth["backend_s"] \
        <= rec["compile_seconds"]
    (reported,) = xla_stats.memory_report()["compiles"]
    assert reported["birth"] == birth


def test_on_compile_takes_no_birth_that_is_not_its_own():
    """A record made long after the thread's newest birth (a compile the
    log did not see) carries none, and a birth is attached once."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    fn = make_step("aot_by_hand", 9.0)
    compiled = fn.lower(x).compile()
    born = xla_stats._birth_local.last
    assert born["program"] == "jit_aot_by_hand"
    born["t_begin"] -= 3600.0                   # an hour ago
    try:
        stale = xla_stats.on_compile(compiled, fingerprint="stale",
                                     seconds=0.5)
    finally:
        born["t_begin"] += 3600.0
    assert "birth" not in stale
    compiled = jax.jit(lambda a: a * 2.0).lower(x).compile()
    first = xla_stats.on_compile(compiled, fingerprint="own", seconds=5.0)
    again = xla_stats.on_compile(compiled, fingerprint="own", seconds=5.0)
    assert first["birth"]["program"] == "jit_<lambda>" and "birth" not in again


# -- bounded, and registered once ----------------------------------------------

def test_the_ring_is_bounded_and_the_listeners_are_registered_once():
    import jax.numpy as jnp
    from jax._src import monitoring as jm

    def mine(listeners):
        return [f for f in listeners
                if getattr(f, "__module__", "") == xla_stats.__name__]

    importlib.reload(observe)
    importlib.import_module("paddle_tpu.observe")
    xla_stats.listen_for_births()
    assert [f.__name__ for f in mine(jm.get_event_listeners())] \
        == ["_on_event"]
    assert [f.__name__ for f in mine(jm.get_event_duration_listeners())] \
        == ["_on_duration"]
    assert xla_stats._BIRTHS.maxlen == xla_stats.BIRTHS_CAPACITY


def test_traces_that_no_lowering_claims_do_not_pile_up(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(xla_stats, "_PENDING_TRACES", 8)
    for n in range(40):
        jax.eval_shape(make_step(f"never_lowered_{n}", 1.5), jnp.ones((3,)))
        assert len(xla_stats._birth_local.traces) <= 8


def test_a_lowering_that_traces_much_does_not_lose_the_programs_trace():
    """A 24-layer step's lowering traces hundreds of small jitted
    functions after the step's own trace has ended (seen on the chip:
    every large program read ``trace_s`` 0): the program's trace is
    found by its name, however many came after it."""
    import jax

    seq0 = last_seq()
    say = jax.monitoring.record_event_duration_secs
    say(xla_stats._TRACE_EVENT, 0.25, fun_name="inner_of_big")
    say(xla_stats._TRACE_EVENT, 2.0, fun_name="big_step")
    for n in range(3000):
        say(xla_stats._TRACE_EVENT, 0.001, fun_name=f"_made_in_lowering_{n % 700}")
    say(xla_stats._LOWER_EVENT, 1.0, fun_name="jit(big_step)")
    say(xla_stats._BACKEND_EVENT, 0.5, fun_name="jit(big_step)")
    (rec,) = born_since(seq0, "jit_big_step")
    assert rec["trace_s"] == 2.0 and rec["lower_s"] == 1.0
    assert rec["t_end"] - rec["t_begin"] >= 2.0
    assert not xla_stats._birth_local.traces


def test_a_fault_of_the_log_is_no_fault_of_the_compile(monkeypatch):
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    x.block_until_ready()

    def broken(*a, **kw):
        raise RuntimeError("the log is broken")

    errors0 = stat_get("xla_birth_log_errors")
    monkeypatch.setattr(xla_stats, "_close_birth", broken)
    out = make_step("compiles_all_the_same", 2.5)(x)
    assert out.shape == (4, 4)
    assert stat_get("xla_birth_log_errors") - errors0 == 1


# -- the serving engine ----------------------------------------------------------

VOCAB = 61


@pytest.fixture(scope="module")
def engine_births():
    """One toy engine's first request: the births on its thread, and a
    slow-step event recorded by hand afterwards."""
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    weights = model.init_weights(jax.random.PRNGKey(11))
    eng = DecodeEngine(model, weights, DecodeConfig(
        slots=2, max_seq_len=64, page_size=8, max_new_tokens=8,
        prefix_cache=False))
    seq0 = last_seq()
    eng.start()
    try:
        toks = eng.submit(list(range(1, 12)),
                          max_new_tokens=4).result(timeout=120)
    finally:
        eng.stop()
    assert len(toks) == 4
    flight0 = max([e["seq"] for e in flight.tail()], default=0)
    eng._record_slow_step({"iter": 5, "step": 2, "live": 1}, 0,
                          (1.0, 1.2, 1.3, 2.0))
    (event,) = [e for e in flight.tail() if e["seq"] > flight0
                and e["event"] == "serving/slow_step"]
    return born_since(seq0), event


def test_an_engines_first_step_and_prefill_are_born_under_their_dispatch(
        engine_births):
    births, _ = engine_births
    (step,) = [b for b in births if b["program"] == "jit_step"]
    assert step["under"] == "serving/step_dispatch"
    assert step["under_attrs"]["step"] == 0 and "iter" in step["under_attrs"]
    (prefill,) = [b for b in births if b["program"] == "jit_prefill"]
    assert prefill["under"] == "serving/prefill_dispatch"
    assert prefill["under_attrs"]["bucket"] == 16
    assert step["thread"] == prefill["thread"] \
        != threading.current_thread().name
    # every birth of the engine's thread lies under one of its spans
    assert all(b["under"] and b["under"].startswith("serving/")
               for b in births if b["thread"] == step["thread"])


def test_a_slow_step_event_says_what_its_process_had_compiled(
        engine_births):
    births, event = engine_births
    assert event["births"] >= births[-1]["seq"] > 0
    assert event["cache_misses"] == xla_stats.births_summary()["cache_misses"]
    assert 0 <= event["since_last_birth_s"] < 600
    assert event["iter"] == 5 and event["step"] == 2


# -- the benchmark's readers -------------------------------------------------------

def _birth(t_begin, t_end, cache, trace_s=0.0, lower_s=0.0, backend_s=0.0,
           cache_load_s=None, thread="main"):
    return {"t_begin": t_begin, "t_end": t_end, "cache": cache,
            "trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s,
            "cache_load_s": cache_load_s, "thread": thread}


# two threads whose births overlap (3.0–6.0 and 4.0–9.0), one birth wholly
# inside another's (5.0–5.5), a gap (9.0–20.0), a miss among hits, one off
HAND_MADE = [
    _birth(1.0, 2.0, "hit", 0.25, 0.25, 0.5, cache_load_s=0.375),
    _birth(3.0, 6.0, "hit", 1.0, 0.5, 1.5, cache_load_s=1.25,
           thread="engine"),
    _birth(4.0, 9.0, "miss", 0.5, 0.5, 4.0),
    _birth(5.0, 5.5, "hit", 0.125, 0.125, 0.25, cache_load_s=0.125,
           thread="engine"),
    _birth(20.0, 20.5, "off", 0.125, 0.125, 0.25),
]


@pytest.mark.parametrize("metric,reader,want", [
    ("setup_births_s", "births_s", 1.0 + 6.0 + 0.5),
    ("setup_trace_lower_s", "trace_lower_s", 0.5 + 1.5 + 1.0 + 0.25 + 0.25),
    ("setup_backend_compile_s", "backend_compile_s", 4.0),
    ("setup_cache_load_s", "cache_load_s", 0.375 + 1.25 + 0.125),
    ("setup_cache_misses", "cache_misses", 1),
])
def test_a_setup_metric_on_a_hand_made_log(metric, reader, want):
    from benchmark import run as bench_run
    from benchmark.tests.rehearsal import ROOT

    fn = getattr(setup_reader, reader)
    assert fn({"births": HAND_MADE}, {}) == want
    assert fn({"births": list(reversed(HAND_MADE))}, {}) == want
    assert fn({"births": []}, {}) is None        # an empty log reads None
    warm = [b for b in HAND_MADE if b["cache"] == "hit"]
    if reader in ("backend_compile_s", "cache_misses"):
        assert fn({"births": warm}, {}) == 0     # a warm side reads 0
    # every cell finds the metric under this name, by its own file
    for cell in bench_run.load_json(
            ROOT + "/BENCHMARK.json")["workloads"]:
        found = {e["name"]: (e, r) for e, _, r in bench_run.resolve_cell(
            ROOT, cell["name"])["per_layer"]}
        entry, resolved = found[metric]
        assert resolved.__name__ == reader and entry["moves"] == "setup_s"


def test_the_setup_readers_read_the_programs_own_log():
    import jax.numpy as jnp

    make_step("read_by_the_benchmark", 4.5)(jnp.ones((4, 4)))
    log = xla_stats.program_births()
    assert setup_reader.births_s({}, {}) > 0
    assert setup_reader.trace_lower_s({}, {}) == pytest.approx(
        sum(b["trace_s"] + b["lower_s"] for b in log))
    assert setup_reader.cache_misses({}, {}) == sum(
        b["cache"] == "miss" for b in log)
