"""The program's spans in a ``jax.profiler`` trace (PR 26).

``observe/tracer.py`` is the one span API: every ``span()`` /
``begin()``-``end()`` / ``profiler.RecordEvent`` is also a
``jax.profiler.TraceAnnotation``, so a trace taken on the CPU and read
back with ``ProfileData`` holds each of them exactly once, attributes as
the event's stats.  The decode engine's loop is a row of leaf phases
under ``serving/``; its ``*_args`` spans carry the uploads the counters
count; ``decode_prefill_seconds`` runs through the prefill's sync.
"""
import contextlib
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observe, profiler
from paddle_tpu.monitor import stat_get
from paddle_tpu.observe.histogram import histogram
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine, \
    TransformerLM

VOCAB = 61


@contextlib.contextmanager
def traced(log_dir):
    """A profiler session as the benchmark's ``--trace 1`` opens it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_lines(log_dir, prefix):
    """Per host thread that has any, its events named ``prefix*``:
    [[(start_ns, end_ns, name, stats)]], each sorted by start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            rows = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name,
                 dict(e.stats))
                for e in line.events if e.name.startswith(prefix))
            if rows:
                out.append(rows)
    return out


@pytest.fixture(scope="module")
def model_and_weights():
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    return model, model.init_weights(jax.random.PRNGKey(7))


def make_engine(model_and_weights, **cfg_kw):
    model, weights = model_and_weights
    kw = dict(slots=2, max_seq_len=64, page_size=8, max_new_tokens=8,
              prefix_cache=False)
    kw.update(cfg_kw)
    return DecodeEngine(model, weights, DecodeConfig(**kw))


# -- one span API, one clock ----------------------------------------------

@pytest.fixture(scope="module")
def api_trace(tmp_path_factory):
    """One trace holding a span opened each way, ring buffer on."""
    log_dir = tmp_path_factory.mktemp("api_trace")
    event = profiler.RecordEvent("t26/record_event")

    @profiler.RecordEvent("t26/decorated")
    def decorated():
        return 1

    pt.set_flags({"enable_tracer": True})
    observe.clear()
    try:
        with traced(log_dir):
            with observe.span("t26/span", rows=3, kind="x"):
                observe.set_span_args(bytes=40)
            observe.begin("t26/begin_end", n=2)
            observe.end()
            with event:
                pass
            event.begin()
            event.end()
            decorated()
        ring = [r.name for r in observe.snapshot()]
    finally:
        pt.set_flags({"enable_tracer": False})
        observe.clear()
    return [e for rows in host_lines(log_dir, "t26/") for e in rows], ring


@pytest.mark.parametrize("name,times", [
    ("t26/span", 1), ("t26/begin_end", 1), ("t26/record_event", 2),
    ("t26/decorated", 1)])
def test_a_span_is_in_the_profiler_trace_exactly_once(api_trace, name,
                                                      times):
    events, ring = api_trace
    assert [e[2] for e in events].count(name) == times
    assert ring.count(name) == times


def test_span_attributes_reach_the_trace_as_stats(api_trace):
    events, _ = api_trace
    (span,) = [e for e in events if e[2] == "t26/span"]
    # the ones given at the start and the one set after the body ran
    assert span[3] == {"rows": 3, "kind": "x", "bytes": 40}
    (pair,) = [e for e in events if e[2] == "t26/begin_end"]
    assert pair[3] == {"n": 2}


def test_spans_reach_the_trace_with_the_ring_buffer_off(tmp_path):
    assert not observe.enabled()
    observe.clear()
    with traced(tmp_path):
        with observe.span("t26/flag_off"):
            pass
    names = [e[2] for rows in host_lines(tmp_path, "t26/") for e in rows]
    assert names == ["t26/flag_off"]
    assert observe.snapshot() == []     # the flag still gates the buffer


def test_unbalanced_end_and_flag_flip_stay_balanced():
    observe.end()                        # nothing open: dropped
    observe.begin("t26/flip")
    pt.set_flags({"enable_tracer": True})
    try:
        observe.end()                    # begun with the buffer off
        assert [r.name for r in observe.snapshot()
                if r.name == "t26/flip"] == []
        with observe.span("t26/after"):
            pass
        assert [r.name for r in observe.snapshot()][-1] == "t26/after"
        assert observe.snapshot()[-1].depth == 0
    finally:
        pt.set_flags({"enable_tracer": False})
        observe.clear()


# -- the engine loop as sequential leaf phases ----------------------------

PREFILL = ["serving/prefill_args", "serving/prefill_dispatch",
           "serving/prefill_sync", "serving/prefill_deliver"]
STEP = ["serving/step_cow", "serving/step_args", "serving/step_dispatch",
        "serving/step_sync", "serving/step_deliver"]


def test_engine_iteration_is_a_row_of_leaf_phases(model_and_weights,
                                                  tmp_path):
    eng = make_engine(model_and_weights).start()
    try:
        eng.submit(list(range(1, 12)), max_new_tokens=2).result(timeout=120)
        uploads0 = stat_get("decode_h2d_uploads")
        bytes0 = stat_get("decode_h2d_bytes")
        with traced(tmp_path):
            req = eng.submit(list(range(2, 12)), max_new_tokens=3)
            req.result(timeout=120)
            time.sleep(0.05)             # the last deliver span closes
        uploads = stat_get("decode_h2d_uploads") - uploads0
        nbytes = stat_get("decode_h2d_bytes") - bytes0
    finally:
        eng.stop()
    (engine,) = [rows for rows in host_lines(tmp_path, "serving/")
                 if any(e[2] == "serving/step_dispatch" for e in rows)]
    names = [e[2] for e in engine]
    # (reap ->) lock_wait -> admit -> the request's prefill, then whole
    # iterations with nothing to admit: the step's arguments are built
    # and uploaded BEFORE the admission, the step is handed over after
    # it: 1 + 2 tokens.  The idle wait the submit woke the engine from
    # began before the trace and left no event, and the microsecond of
    # reap right behind it is not always kept.
    if names[0] == "serving/reap":
        del names[0], engine[0]
    head = ["serving/lock_wait", "serving/admit"]
    iteration = ["serving/reap"] + STEP[:2] + head + STEP[2:]
    first = head + PREFILL
    assert names[:len(first)] == first
    assert names[len(first):][:len(iteration)] == iteration
    assert names.count("serving/step_dispatch") == 2
    # leaf phases: each ends before the next begins, none encloses another
    for a, b in zip(engine, engine[1:]):
        assert a[1] <= b[0], (a, b)
    by_name = {}
    for e in engine:
        by_name.setdefault(e[2], []).append(e[3])
    assert by_name["serving/admit"][0] == {"admitted": 1, "queued": 0}
    # one TTFT can be walked by the request's id, one step by its number
    rid = req.trace.trace_id
    assert all(by_name[n][0]["req"] == rid for n in PREFILL)
    step = by_name["serving/step_cow"][0]["step"]
    assert all(by_name[n][0]["step"] == step for n in STEP)
    assert by_name["serving/step_cow"][1]["step"] == step + 1
    # the *_args spans carry what the counters counted, no more, no less
    args = [e[3] for e in engine if e[2].endswith("_args")]
    assert sum(a["uploads"] for a in args) == uploads > 0
    assert sum(a["upload_bytes"] for a in args) == nbytes > 0
    # ONE packed array a joint step and a whole-prompt prefill, whatever
    # the number of fields in it
    assert [a["uploads"] for a in by_name["serving/step_args"]] == [1, 1]
    assert [a["uploads"] for a in by_name["serving/prefill_args"]] == [1]
    assert uploads == 3


def test_speculative_round_has_the_same_phases(model_and_weights,
                                               tmp_path):
    model, weights = model_and_weights
    eng = DecodeEngine(model, weights, DecodeConfig(
        slots=2, max_seq_len=64, page_size=8, max_new_tokens=8,
        prefix_cache=False, spec_k=2),
        draft_model=model, draft_weights=weights).start()
    try:
        eng.submit(list(range(1, 12)), max_new_tokens=4).result(timeout=120)
        with traced(tmp_path):
            eng.submit(list(range(2, 12)),
                       max_new_tokens=4).result(timeout=120)
            time.sleep(0.05)
    finally:
        eng.stop()
    (engine,) = [rows for rows in host_lines(tmp_path, "serving/")
                 if any(e[2] == "serving/step_dispatch" for e in rows)]
    names = [e[2] for e in engine]
    # a round: the draft burst and the verify, each args/dispatch/sync
    burst = STEP[1:4]
    i = names.index("serving/step_cow")
    assert names[i:i + 8] == ["serving/step_cow"] + burst + burst + \
        ["serving/step_deliver"]
    assert engine[i][3]["k"] == 2
    assert not any(n.startswith("serving/decode_") for n in names)
    for a, b in zip(engine, engine[1:]):
        assert a[1] <= b[0], (a, b)


# -- timers that time what their names say --------------------------------

SLOW_SYNC_S = 0.08


class _SlowToken:
    """A sampled token whose read-back takes ``SLOW_SYNC_S``."""

    def __init__(self, array):
        self._array = array

    def __array__(self, dtype=None, copy=None):
        time.sleep(SLOW_SYNC_S)
        return np.asarray(self._array)


@pytest.mark.parametrize("path,cfg", [
    ("full", {}),
    ("rows", {"prefill_chunk_pages": 1}),
    ("ragged", {"prefill_chunk_pages": 1, "ragged_prefill_rows": 16}),
])
def test_prefill_seconds_run_through_the_sync(model_and_weights,
                                              monkeypatch, path, cfg):
    eng = make_engine(model_and_weights, **cfg)
    run = eng._exe.run_persistent

    def slow(fn, state_vars, args, scope):
        out = run(fn, state_vars, args=args, scope=scope)
        if fn is eng._step_fn or not out:
            return out
        return (_SlowToken(out[0]),) + tuple(out[1:])

    monkeypatch.setattr(eng._exe, "run_persistent", slow)
    prefill, ttft = (histogram("decode_prefill_seconds"),
                     histogram("ttft_seconds"))
    before = (prefill.count, prefill.sum, ttft.count, ttft.sum)
    eng.start()
    try:
        # one chunk (page_size 8): the dispatch that samples the token
        toks = eng.submit(list(range(1, 8)),
                          max_new_tokens=2).result(timeout=120)
    finally:
        eng.stop()
    assert len(toks) == 2
    assert prefill.count - before[0] == 1
    assert prefill.sum - before[1] >= SLOW_SYNC_S
    # the first token leaves after the sync on every path
    assert ttft.count - before[2] == 1
    assert ttft.sum - before[3] >= SLOW_SYNC_S


def test_a_chunk_that_samples_nothing_has_no_sync(model_and_weights,
                                                  tmp_path):
    eng = make_engine(model_and_weights, prefill_chunk_pages=1).start()
    try:
        eng.submit(list(range(1, 20)), max_new_tokens=1).result(timeout=120)
        with traced(tmp_path):
            eng.submit(list(range(2, 21)),
                       max_new_tokens=1).result(timeout=120)
            time.sleep(0.05)
    finally:
        eng.stop()
    names = [e[2] for rows in host_lines(tmp_path, "serving/prefill")
             for e in rows]
    # 19 tokens in chunks of 8: three dispatches, the last one read back
    assert names.count("serving/prefill_dispatch") == 3
    assert names.count("serving/prefill_sync") == 1


# -- kernels and steps with stable names ----------------------------------

def test_lowered_step_names_its_scopes_and_kernel(model_and_weights):
    from paddle_tpu.ops.pallas_decode_attention import KERNEL_NAME

    eng = make_engine(model_and_weights)
    text = eng.lower_step().as_text(debug_info=True)
    assert "module @jit_step" in text       # the program keeps its name
    assert f"jit(step)/decode_step/{KERNEL_NAME}/" in text
    # lowering the step for a look counts no upload
    before = stat_get("decode_h2d_uploads")
    eng.lower_step()
    assert stat_get("decode_h2d_uploads") == before
