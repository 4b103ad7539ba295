"""readers/period.py: the pure functions on made-up intervals, then the
whole reader on a small trace recorded on the chip (one TPU v5 lite, PR
37; ``record_engine_trace.py`` as it is, on the program WITH ``iter``
and the ``*_deliver`` attributes: ``tiny_period_trace``; the PR 26
recording beside it stands for a program without them).

Times in the made-up cases are milliseconds written as numbers: the
functions know no unit.  The device's clock is ``OFFSET`` behind the
host's there, as the chip's was in both recordings."""
import pytest

from benchmark import run as bench_run
from benchmark.readers import engine, period
from benchmark.tests.rehearsal import ROOT
from benchmark.tests.test_engine_reader import CELL, _lay_out

NEW = ("idle_under_deliver_ms_per_step.serve",
       "idle_under_prepare_ms_per_step.serve",
       "idle_under_dispatch_ms_per_step.serve",
       "idle_under_sync_ms_per_step.serve",
       "idle_unattributed_ms_per_step.serve",
       "clock_align_slack_ms.serve",
       "deliver_emit_ms_per_step.serve")
IDLE = NEW[:5]
OFFSET = -1.5


# -- a made-up loop -----------------------------------------------------------

def loop(periods, launch=0.3, readback=0.4, run=3.0, prefill_at=(),
         prefill=5.0):
    """A serial loop on two clocks.  Step k is handed over at the start
    of its period (``periods[k]`` long), enqueued ``launch`` later, runs
    ``run`` at once, or behind a prefill of ``prefill`` that was handed
    over ahead of it where k is in ``prefill_at``, and its tokens are on
    the host ``readback`` after its end.  Returns (spans, ops, runs,
    enqueued, done): host times as they are, device times shifted by
    ``OFFSET``."""
    spans, ops, runs, enqueued, done = [], [], [], {}, []
    t, run_id = 100.0, 7
    for k, period_ in enumerate(periods):
        attrs = {"step": k}
        start = t + launch
        if k in prefill_at:
            spans.append((t - 0.5, t - 0.1, "serving/prefill_dispatch", {}))
            enqueued[run_id] = t - 0.3
            runs.append((t - 0.2 + OFFSET, t - 0.2 + prefill + OFFSET,
                         "jit_prefill", run_id))
            ops.append(runs[-1][:2])
            done.append(t - 0.2 + prefill + 0.2)
            start = max(start, t - 0.2 + prefill)
            run_id += 1
        end = start + run
        spans += [
            (t, t + 0.25, "serving/step_dispatch", attrs),
            (t + 0.25, end + readback, "serving/step_sync", attrs),
            (end + readback, end + readback + 0.6, "serving/step_deliver",
             attrs),
            (end + readback + 0.7, end + readback + 1.0,
             "serving/step_args", {"step": k + 1})]
        enqueued[run_id] = t + launch
        # two ops a run with a pause between them: the device's own
        runs.append((start + OFFSET, end + OFFSET, "jit_step", run_id))
        ops += [(start + OFFSET, start + 1.0 + OFFSET),
                (start + 1.2 + OFFSET, end + OFFSET)]
        done.append(end + readback - 0.1)
        run_id += 1
        t += max(period_, end + readback + 1.1 - t)
    return sorted(spans), sorted(ops), runs, enqueued, sorted(done)


PERIODS = [5.0, 6.5, 5.0, 5.0, 7.0, 5.0]


def test_a_known_offset_lies_inside_its_bounds():
    spans, ops, runs, enqueued, done = loop(PERIODS)
    window = (99.0, spans[-1][1] + 1)
    # a run starts AT its enqueue here and its tokens are on the host
    # 0.4 after its end: enqueues and spans bound the offset to that
    loose = period.measure(window, spans, ops, runs, enqueued)
    assert loose["slack_s"] == pytest.approx(0.4)
    assert loose["offset_s"] - loose["slack_s"] / 2 <= OFFSET \
        <= loose["offset_s"] + loose["slack_s"] / 2
    # the runtime's completion callback, 0.3 after the run's end,
    # tightens the other side
    tight = period.measure(window, spans, ops, runs, enqueued, done)
    assert tight["slack_s"] == pytest.approx(0.3)
    assert tight["offset_s"] == pytest.approx(OFFSET - 0.15)
    assert tight["steps"] == loose["steps"] == len(PERIODS)
    # the spans alone (an enqueue known for no run): hand-over -> run
    # 0.3, run -> tokens 0.4, and nothing to pair by
    assert period.offset_bounds(
        [(s[0], r[0]) for s, r in zip(spans[0::4], runs)],
        [(s[1], r[1]) for s, r in zip(spans[1::4], runs)]) == \
        pytest.approx((OFFSET - 0.4, OFFSET + 0.3))
    assert period.measure(window, spans, ops, runs, {}) is None


def test_a_prefill_riding_ahead_of_a_step_does_not_loosen_the_bounds():
    plain = loop(PERIODS)
    riding = loop(PERIODS, prefill_at=(1, 4))
    for args in (plain, riding):
        spans, ops, runs, enqueued, done = args
        window = (99.0, spans[-1][1] + 1)
        m = period.measure(window, spans, ops, runs, enqueued, done)
        assert m["slack_s"] == pytest.approx(0.3)
        # a step behind a prefill starts late and is paired all the
        # same: the last run enqueued inside its own interval
        assert m["steps"] == len(PERIODS)


def test_a_crossed_pairing_gives_none():
    spans, ops, runs, enqueued, done = loop(PERIODS)
    window = (99.0, spans[-1][1] + 1)
    # every run under its neighbour's enqueue: each step beside the run
    # before it.  Periods of 5 to 7 cannot all be one offset
    crossed = {rid: enqueued[rid + 1] for rid in enqueued
               if rid + 1 in enqueued}
    assert len(period.pair_steps(
        [(s[0], s[1] + 4) for s in spans[0::4]],
        [(a, b, rid) for a, b, _, rid in runs], crossed)) == 5
    assert period.measure(window, spans, ops, runs, crossed, done) is None
    assert period.offset_bounds([(0.0, 1.0)], [(0.0, 2.0)]) is None
    assert period.offset_bounds([], [(0.0, 2.0)]) is None
    # the window's cut ends took the first step's spans and the last
    # run: nothing goes by rank, the same trace is aligned on the steps
    # it still has whole
    shifted = [s for s in spans if s[3].get("step") != 0]
    m = period.measure(window, shifted, ops, runs[:-1], enqueued, done)
    assert m["slack_s"] == pytest.approx(0.3)
    # no step whose run is in the trace: nothing to align
    assert period.measure(window, spans, ops, [], enqueued, done) is None


def test_the_parts_of_a_split_sum_to_the_idle():
    spans = [(0.0, 2.0, "sync"), (2.0, 3.0, "deliver"),
             (3.5, 4.0, "prepare"), (4.0, 6.0, "dispatch")]
    # one gap from inside the sync to inside the dispatch, on a clock
    # 10 behind: overlap, not the middle (which is the deliver's)
    got = period.split_idle([(-9.0, -5.0)], spans, -10.0)
    assert got == {"sync": 1.0, "deliver": 1.0, "prepare": 0.5,
                   "dispatch": 1.0, "unattributed": 0.5}
    assert sum(got.values()) == pytest.approx(4.0)
    # under no span at all
    assert period.split_idle([(20.0, 21.0)], spans, 0.0)["unattributed"] \
        == 1.0
    assert period.complement([(1, 2), (1.5, 3), (5, 9)], 0, 6) == \
        [(0, 1), (3, 5)]


def test_idle_inside_a_run_goes_to_the_remainder():
    spans, ops, runs, enqueued, done = loop(PERIODS, prefill_at=(2,))
    window = (99.0, spans[-1][1] + 1)
    m = period.measure(window, spans, ops, runs, enqueued, done)
    parts = m["parts"]
    assert sum(parts.values()) == pytest.approx(m["idle_s"])
    busy = sum(b - a for a, b in ops)
    assert m["idle_s"] == pytest.approx(window[1] - window[0] - busy)
    # 0.2 between the two ops of each of the six steps' runs, under a
    # step_sync on the host: the device's own all the same.  Beside it
    # what no named span covers: the window's two ends and the 0.1
    # between deliver and args
    inside = 0.2 * len(PERIODS)
    between = m["idle_s"] - inside
    named = sum(parts[p] for p in period.PARTS)
    assert parts["unattributed"] == pytest.approx(
        inside + between - named)
    assert parts["unattributed"] >= inside
    # every step's deliver (0.6) and args (0.3) lie wholly under idle;
    # on the true clock 0.3 of each dispatch and 0.4 of each sync would:
    # the midway offset, 0.15 early, moves that much between the two
    n = len(PERIODS)
    assert parts["deliver"] == pytest.approx(0.6 * n)
    assert parts["prepare"] == pytest.approx(0.3 * n)


# -- the whole reader on recorded traces --------------------------------------

@pytest.fixture
def readers():
    """{metric: (reader, params)} as the manifest and the metric files
    give them, the function taken from the imported module so that a
    test can point ``engine.ROOT`` at a directory of its own."""
    cell = bench_run.resolve_cell(ROOT, CELL)
    out = {}
    for entry, mfile, reader in cell["per_layer"]:
        if entry["name"] in NEW:
            module, _, fn = mfile["reader"].partition(":")
            module = {"period": period, "engine": engine}[module]
            assert reader.__name__ == fn
            assert entry["layer"] == "engine loop"
            assert entry["moves"] == "serve_tok_s"
            assert entry["better"] == "lower" and entry["unit"] == "ms"
            out[entry["name"]] = (getattr(module, fn),
                                  mfile.get("params", {}))
    return out


def _read(tmp_path, monkeypatch, readers, trace_file):
    _lay_out(str(tmp_path), trace_file)
    monkeypatch.setattr(engine, "ROOT", str(tmp_path))
    monkeypatch.setattr(engine, "_CACHE", {})
    monkeypatch.setattr(period, "_CACHE", {})
    sources = {"trace": {"modules": {}}, "spec": {"name": CELL}}
    got = {name: reader(sources, params)
           for name, (reader, params) in readers.items()}
    return got, period.view(sources)


def test_the_manifest_lists_the_new_metrics_for_the_serving_cells(readers):
    assert set(readers) == set(NEW)
    for cell in ("solar_open2_250b.chat_closed_c128",
                 "mimo_v2_5.reason_closed_c128"):
        names = {e["name"] for e, _, _ in
                 bench_run.resolve_cell(ROOT, cell)["per_layer"]}
        assert set(NEW) <= names
    train = bench_run.resolve_cell(ROOT, "bert_base.pretrain_b256_s128")
    assert not {e["name"] for e, _, _ in train["per_layer"]} & set(NEW)


def test_a_program_without_the_attributes_reads_the_split_alone(
        tmp_path, monkeypatch, readers):
    """The PR 26 recording: ``*_deliver`` spans with no ``emit_ms``, as
    the parent of PR 37 writes them.  The split and the slack need the
    spans' names alone; the attribute's reader returns None."""
    got, m = _read(tmp_path, monkeypatch, readers,
                   "tiny_engine_trace.xplane.pb.gz")
    assert got["deliver_emit_ms_per_step.serve"] is None
    # read by hand: run 106 starts 1.1448 ms "before" its enqueue begins
    # and ends 1.5475 "before" its callback; over all eight runs the
    # enqueues bound the offset to <= -1.1152, the two steps' callbacks
    # to >= -1.5475 (from the spans alone: 1.28 ms of slack)
    assert got["clock_align_slack_ms.serve"] == \
        pytest.approx(0.3717, abs=1e-3)
    assert m["offset_s"] == pytest.approx(-1.3617e-3, abs=1e-6)
    assert m["steps"] == 2
    assert sum(got[n] for n in IDLE) == \
        pytest.approx(1e3 * m["idle_s"] / 2)
    # the toy's eleven uploads a step: 3.1-3.8 ms of arguments
    assert got["idle_under_prepare_ms_per_step.serve"] == \
        pytest.approx(5.228, abs=1e-3)
    # a run without a trace reads nothing
    bare = {"spec": {"name": CELL}}
    for name, (reader, params) in readers.items():
        assert reader(bare, params) is None


def test_the_new_metrics_on_the_recorded_trace(tmp_path, monkeypatch,
                                               readers):
    """The PR 37 recording (``trace_reduce.py --describe`` and the
    spans' list read by hand first): iteration 10 admits and prefills
    both requests, 11 and 12 step, 13 finds nothing; runs 96-99 (two
    ``jit_prefill``, two ``jit_step``) each with its ``DoEnqueueProgram``
    by ``run_id``."""
    got, m = _read(tmp_path, monkeypatch, readers,
                   "tiny_period_trace.xplane.pb.gz")
    assert all(got[n] is not None for n in NEW)
    # run 97 starts 1.0964 ms "before" its enqueue begins (98: 1.0603)
    # and run 98 ends 1.3795 "before" its callback (99: 1.5259)
    assert m["offset_s"] == pytest.approx(-1.23796e-3, abs=1e-6)
    assert got["clock_align_slack_ms.serve"] == \
        pytest.approx(0.2831, abs=1e-3)
    assert m["steps"] == 2
    # the five parts are the window's idle time, 10.55 of its 10.65 ms
    assert sum(got[n] for n in IDLE) == \
        pytest.approx(1e3 * m["idle_s"] / 2)
    assert 1e3 * m["idle_s"] == pytest.approx(10.554, abs=1e-3)
    assert got["idle_under_deliver_ms_per_step.serve"] == \
        pytest.approx(0.2447, abs=1e-3)     # the four delivers, whole
    assert got["idle_under_prepare_ms_per_step.serve"] == \
        pytest.approx(1.0041, abs=1e-3)
    assert got["idle_under_dispatch_ms_per_step.serve"] == \
        pytest.approx(1.0457, abs=1e-3)     # the four hand-overs, whole
    assert got["idle_under_sync_ms_per_step.serve"] == \
        pytest.approx(1.1426, abs=1e-3)
    # the toy thread idles at both ends of the window: under no span
    assert got["idle_unattributed_ms_per_step.serve"] == \
        pytest.approx(1.8401, abs=1e-3)
    # emit_ms 0.012931 + 0.017111 + 0.010830 + 0.011920 over two
    # dispatches
    assert got["deliver_emit_ms_per_step.serve"] == \
        pytest.approx(0.026396, rel=1e-4)
    # parsed once a process
    sources = {"trace": {"modules": {}}, "spec": {"name": CELL}}
    assert period.view(sources) is m
