"""The program's spans in a ``jax.profiler`` trace (PR 26).

``observe/tracer.py`` is the one span API: every ``span()`` /
``begin()``-``end()`` / ``profiler.RecordEvent`` is also a
``jax.profiler.TraceAnnotation``, so a trace taken on the CPU and read
back with ``ProfileData`` holds each of them exactly once, attributes as
the event's stats.  The decode engine's loop is a row of leaf phases
under ``serving/``; its ``*_args`` spans carry the uploads the counters
count; ``decode_prefill_seconds`` runs through the prefill's sync.

PR 37: every leaf of one engine iteration carries the same ``iter``;
the ``*_deliver`` spans say what they carried while a sink takes them
(``observe.tracer.recording``); ``decode_turnaround_seconds`` is the
loop between two joint steps; a step slower than ``SLOW_STEP_S`` leaves
a flight-recorder event, the program's first run apart.

PR 38: a joint step is handed over while the one before it is in flight
and read an iteration later: a step's five leaves span two iterations
and share one ``step``; ``step_dispatch`` says how many joint steps were
in flight (``in_flight``); the turnaround is observed only where the
loop runs one step at a time (a round with a speculative slot).
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


# -- whether a span opened now reaches a sink -------------------------------

@pytest.mark.parametrize("sink", ["none", "ring", "trace"])
def test_recording_says_whether_a_sink_takes_a_span(sink, tmp_path):
    from paddle_tpu.observe import tracer

    assert not tracer.recording()
    if sink == "ring":
        pt.set_flags({"enable_tracer": True})
        try:
            assert tracer.recording()
        finally:
            pt.set_flags({"enable_tracer": False})
            observe.clear()
    elif sink == "trace":
        with traced(tmp_path):
            assert tracer.recording()
    assert not tracer.recording()


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
        ahead0 = stat_get("decode_steps_ahead")
        with traced(tmp_path):
            req = eng.submit(list(range(2, 12)), max_new_tokens=3)
            req.result(timeout=120)
            time.sleep(0.05)             # the last deliver span closes
        uploads = stat_get("decode_h2d_uploads") - uploads0
        nbytes = stat_get("decode_h2d_bytes") - bytes0
        ahead = stat_get("decode_steps_ahead") - ahead0
    finally:
        eng.stop()
    (engine,) = [rows for rows in host_lines(tmp_path, "serving/")
                 if any(e[2] == "serving/step_dispatch" for e in rows)]
    names = [e[2] for e in engine]
    # (reap ->) lock_wait -> admit -> the request's prefill, then whole
    # iterations with nothing to admit: the step's arguments are built
    # and uploaded BEFORE the admission, the step is handed over after
    # it, and only THEN the step of the iteration before is read and
    # delivered: 1 + 2 tokens, the second step handed over while the
    # first is in flight, no third built (the budget ends at the step
    # in flight, which is therefore read at the head of the next
    # iteration: the slot it frees is where an admission would come
    # from).  The idle wait the submit woke the engine from began
    # before the trace and left no event, and the microsecond of reap
    # right behind it is not always kept.
    if names[0] == "serving/reap":
        del names[0], engine[0]
    head = ["serving/lock_wait", "serving/admit"]
    hand_over = ["serving/reap"] + STEP[:2] + head + STEP[2:3]
    read = STEP[3:]
    first = head + PREFILL
    assert names[:len(first)] == first
    rest = names[len(first):]
    want = hand_over + hand_over + read + ["serving/reap"] + read + head
    assert rest[:len(want)] == want
    assert names.count("serving/step_dispatch") == 2 and ahead == 1
    # leaf phases: each ends before the next begins, none encloses another
    for a, b in zip(engine, engine[1:]):
        assert a[1] <= b[0], (a, b)
    by_name = {}
    for e in engine:
        by_name.setdefault(e[2], []).append(e[3])
    admit = by_name["serving/admit"][0]
    assert (admit["admitted"], admit["queued"]) == (1, 0)
    # one TTFT can be walked by the request's id, one step by its
    # number, fixed at its hand-over: its read-back and delivery, an
    # iteration later, carry it too
    rid = req.trace.trace_id
    assert all(by_name[n][0]["req"] == rid for n in PREFILL)
    step = by_name["serving/step_cow"][0]["step"]
    for k in (0, 1):
        assert all(by_name[n][k]["step"] == step + k for n in STEP)
        assert by_name["serving/step_deliver"][k]["iter"] == \
            by_name["serving/step_dispatch"][k]["iter"] + 1
    assert [a["in_flight"] for a in by_name["serving/step_dispatch"]] \
        == [0, 1]
    # the *_args spans carry what the counters counted, no more, no less
    args = [e[3] for e in engine if e[2].endswith("_args")]
    assert sum(a["uploads"] for a in args) == uploads > 0
    assert sum(a["upload_bytes"] for a in args) == nbytes > 0
    # ONE packed array a joint step and a whole-prompt prefill, whatever
    # the number of fields in it: the carried tokens are no upload
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
            # the prefill's token, then two rounds (the draft is the
            # target: each yields 3) and a joint step for the eighth
            eng.submit(list(range(2, 12)),
                       max_new_tokens=8).result(timeout=120)
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
    # the round's eight leaves are of one iteration, the next round's
    # of the next
    assert len({e[3]["iter"] for e in engine[i:i + 8]}) == 1
    j = names.index("serving/step_cow", i + 1)
    assert engine[j][3]["iter"] == engine[i][3]["iter"] + 1
    deliver = engine[i + 7][3]
    assert 1 <= deliver["tokens"] <= 3 and deliver["live"] == 1
    for a, b in zip(engine, engine[1:]):
        assert a[1] <= b[0], (a, b)


# -- an iteration has a number, a delivery says what it carried ------------

SLOW_CALLBACK_S = 0.02


def engine_line(log_dir):
    """The engine thread's ``serving/*`` events, sorted by start."""
    (rows,) = [rows for rows in host_lines(log_dir, "serving/")
               if any(e[2] == "serving/step_dispatch" for e in rows)]
    return rows


@pytest.fixture(scope="module")
def loop_trace(model_and_weights, tmp_path_factory):
    """Two requests of four tokens, one after the other with the engine
    idle between them, the first with a slow ``on_token``: the engine
    thread's events and what the two histograms saw."""
    log_dir = tmp_path_factory.mktemp("loop_trace")
    idle_s = 0.3

    def slow(_token):
        time.sleep(SLOW_CALLBACK_S)

    eng = make_engine(model_and_weights).start()
    try:
        eng.submit(list(range(1, 12)), max_new_tokens=2).result(timeout=120)
        time.sleep(0.1)                  # the engine is in its idle wait
        turn, step = (histogram("decode_turnaround_seconds"),
                      histogram("decode_step_seconds"))
        before = (turn.count, turn.sum, step.count,
                  stat_get("decode_steps_ahead"))
        with traced(log_dir):
            eng.submit(list(range(2, 12)), max_new_tokens=4,
                       on_token=slow).result(timeout=120)
            time.sleep(idle_s)
            eng.submit(list(range(3, 12)),
                       max_new_tokens=4).result(timeout=120)
            time.sleep(0.05)             # the last deliver span closes
        seen = (turn.count - before[0], turn.sum - before[1],
                step.count - before[2],
                stat_get("decode_steps_ahead") - before[3])
    finally:
        eng.stop()
    return {"engine": engine_line(log_dir), "idle_s": idle_s,
            "turnarounds": seen[0], "turnaround_s": seen[1],
            "steps": seen[2], "ahead": seen[3]}


def by_iteration(engine):
    """{iter: [event]} in the order of the thread's line."""
    out = {}
    for e in engine:
        out.setdefault(e[3]["iter"], []).append(e)
    return out


def test_every_leaf_of_an_iteration_carries_its_ordinal(loop_trace):
    engine = loop_trace["engine"]
    assert all("iter" in e[3] for e in engine)
    iters = by_iteration(engine)
    # consecutive iterations, consecutive ordinals, each a stretch of
    # the line of its own
    assert sorted(iters) == list(range(min(iters), max(iters) + 1))
    assert [e[3]["iter"] for e in engine] == sorted(
        e[3]["iter"] for e in engine)
    stepped = [rows for rows in iters.values()
               if any(e[2] == "serving/step_dispatch" for e in rows)]
    assert len(stepped) == loop_trace["steps"] == 6
    hand_over = ["serving/reap"] + STEP[:2] + \
        ["serving/lock_wait", "serving/admit"] + STEP[2:3]
    for rows in stepped:
        (dispatch,) = [e[3] for e in rows
                       if e[2] == "serving/step_dispatch"]
        # behind a step in flight the iteration also reads that step;
        # a request's first step finds nothing to read
        assert [e[2] for e in rows] == \
            hand_over + (STEP[3:] if dispatch["in_flight"] else [])
        # the step spans' own number stays beside the iteration's: the
        # leaves that hand over carry this step's, the two that read
        # the number of the step handed over an iteration ago
        for e in rows:
            if "step" in e[3]:
                assert e[3]["step"] == dispatch["step"] - (
                    e[2] in STEP[3:])
    # the last step of a request is read in an iteration that hands
    # nothing over: every step is read exactly once
    by_step = {}
    for e in engine:
        if "step" in e[3]:
            by_step.setdefault(e[3]["step"], []).append(e[2])
    assert len(by_step) == 6 and all(v == STEP for v in by_step.values())
    (first, second) = [rows for rows in iters.values()
                       if any(e[2] == "serving/prefill_dispatch"
                              for e in rows)]
    assert [e[2] for e in first if "prefill" in e[2]] == PREFILL


def test_steps_run_ahead_except_behind_an_idle_wait(loop_trace):
    """Two requests of four tokens with the engine idle between them:
    three joint steps each, the first handed over with nothing in
    flight, the other two behind the step before them."""
    in_flight = [e[3]["in_flight"] for e in loop_trace["engine"]
                 if e[2] == "serving/step_dispatch"]
    assert in_flight == [0, 1, 1, 0, 1, 1]
    assert loop_trace["ahead"] == sum(in_flight) == loop_trace["steps"] - 2


def test_step_deliver_says_what_it_carried(loop_trace):
    delivers = [e for e in loop_trace["engine"]
                if e[2] == "serving/step_deliver"]
    assert len(delivers) == 6
    for a, b, _, attrs in delivers:
        assert attrs["tokens"] == attrs["live"] == 1
        assert attrs["emit_ms"] + attrs["finish_ms"] <= (b - a) * 1e-6
    # a request of 4 tokens: the prefill's and three steps', the last
    # of which ends the slot
    assert [e[3]["finished"] for e in delivers] == [0, 0, 1, 0, 0, 1]
    assert all(e[3]["finish_ms"] > 0 for e in delivers[2::3])
    assert all(e[3]["finish_ms"] == 0 for e in delivers
               if not e[3]["finished"])
    prefills = [e for e in loop_trace["engine"]
                if e[2] == "serving/prefill_deliver"]
    assert [(e[3]["tokens"], e[3]["finished"]) for e in prefills] == \
        [(1, 0), (1, 0)]


def test_a_slow_on_token_shows_in_emit_ms_alone(loop_trace):
    carried = [e[3] for e in loop_trace["engine"]
               if e[2].endswith("_deliver")]
    slow, fast = carried[:4], carried[4:]
    assert all(c["emit_ms"] >= SLOW_CALLBACK_S * 1e3 for c in slow)
    assert all(c["emit_ms"] < SLOW_CALLBACK_S * 1e3 / 2 for c in fast)
    assert all(c["finish_ms"] < SLOW_CALLBACK_S * 1e3 / 2 for c in carried)


def test_a_delivery_reads_no_clock_while_no_sink_takes_its_span(
        model_and_weights, monkeypatch):
    """No profiler session, the ring buffer off: ``_deliver`` makes no
    ``perf_counter_ns`` call a token, and counts nothing."""
    from paddle_tpu.serving import decode

    eng = make_engine(model_and_weights)
    carried = []
    deliver = eng._deliver

    def spy(slot, token):
        carried.append(eng._carried)
        return deliver(slot, token)

    monkeypatch.setattr(eng, "_deliver", spy)
    reads = []
    clock = time.perf_counter_ns
    monkeypatch.setattr(decode.time, "perf_counter_ns",
                        lambda: reads.append(1) or clock())
    assert not observe.enabled()
    eng.start()
    try:
        toks = eng.submit(list(range(1, 8)),
                          max_new_tokens=4).result(timeout=120)
        assert len(toks) == 4 and carried == [None] * 4 and not reads
        pt.set_flags({"enable_tracer": True})
        eng.submit(list(range(1, 8)), max_new_tokens=4).result(timeout=120)
        time.sleep(0.05)                 # the last deliver span closes
    finally:
        pt.set_flags({"enable_tracer": False})
        eng.stop()
    # the ring buffer on: two reads a token, two more for the slot's end
    assert len(reads) == 2 * 4 + 2
    assert all(c is not None for c in carried[4:])
    delivered = [r.args for r in observe.snapshot()
                 if r.name.endswith("_deliver")]
    observe.clear()
    assert [a["tokens"] for a in delivered] == [1] * 4
    assert eng._carried is None


def test_turnaround_is_seen_at_no_hand_over_behind_a_step_in_flight(
        loop_trace):
    # three joint steps a request; the first follows the engine's idle
    # wait (and an iteration with the prefill alone), the others are
    # handed over behind the step before them: the device waits for
    # nothing the host does, and nothing is observed, not even beside
    # a callback that sleeps
    assert loop_trace["steps"] == 6 and loop_trace["ahead"] == 4
    assert loop_trace["turnarounds"] == 0
    assert loop_trace["turnaround_s"] == 0
    assert any(e[2] == "serving/idle_wait" for e in loop_trace["engine"])


@pytest.fixture(scope="module")
def serial_trace(model_and_weights, tmp_path_factory):
    """A round that holds a speculative slot runs one step at a time:
    a request that speculates beside one that opted out, so that every
    iteration is a speculative round and then a joint step, read in its
    own iteration."""
    log_dir = tmp_path_factory.mktemp("serial_trace")
    model, weights = model_and_weights
    eng = DecodeEngine(model, weights, DecodeConfig(
        slots=2, max_seq_len=64, page_size=8, max_new_tokens=8,
        prefix_cache=False, spec_k=2),
        draft_model=model, draft_weights=weights).start()
    try:
        eng.submit(list(range(1, 12)), max_new_tokens=4).result(timeout=120)
        eng.submit(list(range(1, 12)), max_new_tokens=3,
                   speculative=False).result(timeout=120)
        time.sleep(0.1)                  # the engine is in its idle wait
        turn = histogram("decode_turnaround_seconds")
        before = (turn.count, turn.sum, stat_get("decode_steps_ahead"))
        with traced(log_dir):
            plain = eng.submit(list(range(3, 12)), max_new_tokens=6,
                               speculative=False)
            spec = eng.submit(list(range(2, 12)), max_new_tokens=16)
            plain.result(timeout=120), spec.result(timeout=120)
            time.sleep(0.05)
        seen = (turn.count - before[0], turn.sum - before[1],
                stat_get("decode_steps_ahead") - before[2])
    finally:
        eng.stop()
    return {"engine": engine_line(log_dir), "turnarounds": seen[0],
            "turnaround_s": seen[1], "ahead": seen[2]}


def joint_steps(engine, name):
    """The ``name`` leaves of joint steps (a speculative round's carry
    ``k``)."""
    return [e for e in engine if e[2] == name and "k" not in e[3]]


def test_turnaround_is_seen_where_the_loop_runs_one_step_at_a_time(
        serial_trace):
    engine = serial_trace["engine"]
    dispatch = joint_steps(engine, "serving/step_dispatch")
    # the request that opted out: five joint steps beside the other's
    # speculative rounds, none ahead, each read in its own iteration
    assert len(dispatch) >= 5 and serial_trace["ahead"] == 0
    assert all(e[3]["in_flight"] == 0 for e in engine
               if e[2] == "serving/step_dispatch")
    for d, sync in zip(dispatch, joint_steps(engine, "serving/step_sync")):
        assert d[3]["iter"] == sync[3]["iter"]
    # every joint step but the first of the busy stretch saw one
    assert serial_trace["turnarounds"] == len(dispatch) - 1


def test_turnaround_runs_from_the_sync_to_the_next_hand_over(serial_trace):
    """Where the loop runs serially the histogram is the other half of
    ``decode_step_seconds``' period: what it saw is the trace's time
    from each joint ``step_sync``'s end to the next joint
    ``step_dispatch``'s begin, the two spans' own opening and closing
    aside."""
    engine = serial_trace["engine"]
    dispatch = joint_steps(engine, "serving/step_dispatch")
    sync = joint_steps(engine, "serving/step_sync")
    gaps = [dispatch[k][0] - sync[k - 1][1]
            for k in range(1, len(dispatch))]
    assert all(g > 0 for g in gaps)
    assert abs(sum(gaps) * 1e-9 - serial_trace["turnaround_s"]) < 5e-3


# -- a slow step leaves a record -------------------------------------------

class _SlowRead:
    """``numpy`` as the engine's module sees it, but for ``asarray`` of
    one chosen array, which takes ``SLOW_SYNC_S``: a slow read-back of a
    step whose tokens stay a device array (the next step carries them)."""

    def __init__(self):
        self.slow = None

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if a is self.slow:
            time.sleep(SLOW_SYNC_S)
        return np.asarray(a, *args, **kw)


@pytest.mark.parametrize("slow_run", [0, 1])
def test_a_slow_step_leaves_a_flight_record(model_and_weights,
                                            monkeypatch, slow_run):
    """The step program's run number ``slow_run`` is slow: its first
    run (a compile, or a load from the compile cache) leaves nothing,
    any later one a record and a count."""
    from paddle_tpu.observe import flight
    from paddle_tpu.serving import decode

    monkeypatch.setattr(decode, "SLOW_STEP_S", SLOW_SYNC_S * 0.9)
    reads = _SlowRead()
    monkeypatch.setattr(decode, "np", reads)
    eng = make_engine(model_and_weights)
    run = eng._exe.run_persistent
    runs = []

    def slow_once(fn, state_vars, args, scope):
        out = run(fn, state_vars, args=args, scope=scope)
        if fn is eng._step_fn:
            runs.append(True)
            if len(runs) == slow_run + 1:
                reads.slow = out[0]
        return out

    monkeypatch.setattr(eng._exe, "run_persistent", slow_once)
    seq0 = max([e["seq"] for e in flight.tail()], default=0)
    slow0 = stat_get("decode_steps_slow")
    eng.start()
    try:
        toks = eng.submit(list(range(1, 8)),
                          max_new_tokens=4).result(timeout=120)
    finally:
        eng.stop()
    assert len(toks) == 4 and len(runs) == 3
    records = [e for e in flight.tail() if e["seq"] > seq0
               and e["event"] == "serving/slow_step"]
    assert stat_get("decode_steps_slow") - slow0 == len(records) \
        == slow_run
    if not slow_run:
        return
    (rec,) = records
    assert rec["live"] == 1 and rec["prefills_ahead"] == 0
    assert rec["iter"] >= 3 and rec["step"] == 1
    stamps = [rec[k] for k in ("t_handover_begin", "t_handover_end",
                               "t_readback_begin", "t_readback_end")]
    assert stamps == sorted(stamps)
    # the phase that was late is the read-back
    assert stamps[3] - stamps[2] >= SLOW_SYNC_S
    assert rec["seconds"] >= SLOW_SYNC_S


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
