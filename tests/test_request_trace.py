"""Per-request tracing + SLO burn-rate/goodput plane (observe/
request_trace.py, observe/slo.py, and their threading through the
serving stack).

The load-bearing properties:

- recording is always on, retention is head-sampled, and an SLO
  violator / abnormal ending is retained even at
  ``FLAGS_request_trace_sample=0`` (tail retention) with its FULL
  timeline — admission wait, prefill chunks, spec rounds, outcome;
- tracing must be a pure observer: decode outputs are bitwise-equal
  with sampling on vs off at the spec x prefix x chunked composition,
  and the recording path costs <= 5% tokens/sec;
- the debug plane (``/debug/requests``, ``/debug/request/<id>``)
  stays well-formed under concurrent scrape while the engine
  admits/reaps (the test_xla_stats 4-scraper x 25-GET pattern);
- every terminal outcome lands in the flat per-outcome counters so
  error-rate SLOs have a denominator.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from paddle_tpu.framework import flags as flags_mod
from paddle_tpu.monitor import stat_get
from paddle_tpu.observe import request_trace as rt
from paddle_tpu.observe import slo as slo_mod
from paddle_tpu.serving.batcher import InferenceRequest
from paddle_tpu.serving.buckets import (DeadlineExceededError,
                                        QueueFullError,
                                        RequestTooLargeError)
from paddle_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                       TransformerLM)
from paddle_tpu.serving.server import DecodeServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 37


@pytest.fixture(scope="module")
def model_and_weights():
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    return model, model.init_weights(jax.random.PRNGKey(5))


@pytest.fixture(autouse=True)
def _clean_plane():
    """Every test starts from an empty trace store, default sampling,
    and flag-default SLO objectives."""
    rt.get_trace_store().clear()
    flags_mod.set_flags({"request_trace_sample": 1.0})
    slo_mod.configure(None)
    yield
    rt.get_trace_store().clear()
    flags_mod.set_flags({"request_trace_sample": 1.0})
    slo_mod.configure(None)


def make_engine(model_and_weights, **cfg_kw):
    model, weights = model_and_weights
    kw = dict(slots=2, max_seq_len=64, page_size=8, max_new_tokens=8)
    kw.update(cfg_kw)
    return DecodeEngine(model, weights, DecodeConfig(**kw))


# ---------------------------------------------------------------------------
# store + SLO engine units
# ---------------------------------------------------------------------------


def test_head_sampling_is_deterministic_exact_rate():
    store = rt.TraceStore(capacity=64)
    flags_mod.set_flags({"request_trace_sample": 0.25})
    kept = 0
    for _ in range(32):
        tr = store.start("decode", replica="r0")
        store.finish(tr, outcome="completed")
        kept += tr.sampled
    assert kept == 8  # exactly 25%, not a coin flip
    assert len(store.retained()) == 8


def test_tail_retention_keeps_violators_and_abnormal_at_sample_zero():
    store = rt.TraceStore(capacity=64)
    flags_mod.set_flags({"request_trace_sample": 0.0})
    ok = store.start("decode")
    store.finish(ok, outcome="completed")
    bad = store.start("decode")
    store.finish(bad, outcome="deadline", reason="mid-decode")
    viol = store.start("decode")
    store.finish(viol, outcome="completed", violations=["ttft_p99"])
    ids = [t.trace_id for t in store.retained()]
    assert bad.trace_id in ids and viol.trace_id in ids
    assert ok.trace_id not in ids
    assert [t.trace_id for t in store.violators()] == ids
    # lookup works for retained and is None for the sampled-out one
    assert store.get(bad.trace_id) is bad
    assert store.get(ok.trace_id) is None


def test_trace_event_cap_counts_drops():
    store = rt.TraceStore(capacity=4)
    tr = store.start("decode")
    for i in range(rt.MAX_EVENTS_PER_TRACE + 7):
        tr.event("token", n=i)
    assert len(tr.events) == rt.MAX_EVENTS_PER_TRACE
    assert tr.dropped_events == 7
    store.finish(tr, outcome="error", reason="overflow test")
    d = tr.to_dict()
    assert d["dropped_events"] == 7
    # finish appended its terminal event inside the cap'd list? finish
    # always lands (appended after the flag flip)
    assert tr.events[-1][1] == "finish"


def test_slo_engine_burn_rates_and_goodput():
    eng = slo_mod.SLOEngine(
        objectives=[slo_mod.Objective("ttft_p99", "ttft", 0.010, 0.01),
                    slo_mod.Objective("error_rate", "error", None, 0.5)],
        windows=(60.0, 300.0))
    # 3 good, 1 slow-ttft, 1 error
    for _ in range(3):
        assert eng.observe({"outcome": "completed", "ttft_s": 0.001}) == []
    assert eng.observe({"outcome": "completed", "ttft_s": 0.5}) \
        == ["ttft_p99"]
    assert eng.observe({"outcome": "deadline", "ttft_s": None}) \
        == ["ttft_p99", "error_rate"]
    snap = eng.snapshot()
    # ttft: 2 bad of 5 -> frac 0.4 over budget 0.01 -> burn 40x
    assert snap["burn_rates"]["ttft_p99"]["60s"] == pytest.approx(40.0)
    # error: 1 bad of 5 -> 0.2 / 0.5 -> 0.4x, budget remaining 60%
    assert snap["burn_rates"]["error_rate"]["60s"] == pytest.approx(0.4)
    assert snap["budget_remaining"]["error_rate"] == pytest.approx(0.6)
    assert snap["budget_remaining"]["ttft_p99"] == 0.0  # exhausted
    assert snap["goodput_rps"] > 0.0  # 3 good completions just landed
    assert snap["violations_total"] == 3


def test_slo_latency_objective_counts_missing_signal_as_violated():
    o = slo_mod.Objective("ttft_p99", "ttft", 0.5, 0.01)
    assert o.is_violated({"outcome": "deadline", "ttft_s": None})
    assert not o.is_violated({"outcome": "completed", "ttft_s": 0.1})


# ---------------------------------------------------------------------------
# the acceptance scenario: induced violation, retained at sample=0
# ---------------------------------------------------------------------------


def test_induced_violation_end_to_end(model_and_weights, tmp_path,
                                      capsys):
    """The acceptance scenario in one run: long-prompt adversary + an
    unmeetable ttft objective, head sampling fully OFF — the violator
    must still come back with its whole timeline, burn gauges must be
    nonzero, and the trace must render on every surface (chrome
    export, /metrics gauges, postmortem requests.json, tools/reqtrace,
    tools/postmortem, python -m reqtrace)."""
    flags_mod.set_flags({"request_trace_sample": 0.0})
    slo_mod.configure([
        slo_mod.Objective("ttft_p99", "ttft", 1e-4, 0.01),
        slo_mod.Objective("error_rate", "error", None, 0.01)])
    eng = make_engine(model_and_weights, slots=2,
                      prefill_chunk_pages=1)
    with eng:
        # adversary: a 5-page prompt prefilled one page per step
        # boundary; the victim rides behind it
        adv = eng.submit(list(range(1, 41)), max_new_tokens=4)
        vic = eng.submit([1, 2, 3], max_new_tokens=4)
        adv.result(timeout=120)
        vic.result(timeout=120)

    store = rt.get_trace_store()
    tid = adv.trace.trace_id
    tr = store.get(tid)
    assert tr is not None, "violator dropped despite sample=0"
    assert "ttft_p99" in tr.violations
    names = [e[1] for e in tr.events]
    assert "enqueue" in names and "admit" in names
    assert names.count("prefill_chunk") >= 5  # 5 pages, 1 per chunk
    assert "token" in names and "finish" in names
    assert tr.outcome == "completed" and tr.reason == "budget"
    assert tr.summary["ttft_s"] > 1e-4
    # the victim (also a violator under the 0.1ms objective) shows
    # the admission wait behind the adversary
    tv = store.get(vic.trace.trace_id)
    assert tv is not None and "ttft_p99" in tv.violations
    # burn-rate + goodput gauges are live on the registry
    assert stat_get("slo_burn_rate_ttft_p99_ppm") > 0
    assert stat_get("slo_budget_remaining_ttft_p99_ppm") == 0
    assert stat_get("decode_goodput_rps_ppm") == 0  # nobody met SLO
    assert stat_get("decode_slo_violations") > 0

    # chrome export through observe/timeline.py
    doc = rt.chrome_trace(tid)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert any(e["name"] == "request/admit" for e in spans)
    assert doc["otherData"]["trace_id"] == tid

    # postmortem bundle requests.json
    from paddle_tpu.observe import health

    b = health.dump_postmortem("slo_violation", directory=str(tmp_path))
    rq = json.load(open(os.path.join(b, "requests.json")))
    assert any(t["trace_id"] == tid for t in rq["violators"])
    assert rq["slo"]["burn_rates"]["ttft_p99"]["60s"] > 0

    # tools/reqtrace renders the section and the single timeline
    from tools import reqtrace

    assert reqtrace.main([os.path.join(b, "requests.json")]) == 0
    out = capsys.readouterr().out
    assert "SLO verdict" in out and tid in out
    assert reqtrace.main([os.path.join(b, "requests.json"),
                          "--id", tid]) == 0
    out = capsys.readouterr().out
    assert "prefill_chunk" in out and "outcome:  completed" in out

    # tools/postmortem renders the violator table + SLO verdict
    from tools import postmortem as pm

    assert pm.main([b]) == 0
    out = capsys.readouterr().out
    assert "violators" in out and tid in out and "ttft_p99" in out

    # the pure-stdlib CLI works from a clean interpreter
    r = subprocess.run(
        [sys.executable, "-m", "tools.reqtrace",
         os.path.join(b, "requests.json"), "--id", tid],
        capture_output=True, text=True, cwd=ROOT, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "timeline" in r.stdout and "admit" in r.stdout


# ---------------------------------------------------------------------------
# pure-observer contract: bitwise parity + bounded overhead
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def self_draft(model_and_weights):
    # self-draft (full acceptance) keeps the spec path deterministic
    # and fast; the low-acceptance path is pinned elsewhere
    return model_and_weights


def test_trace_on_off_bitwise_parity_spec_prefix_chunked(
        model_and_weights, self_draft):
    """spec x prefix x chunked composition decoded twice — sampling
    fully on vs fully off — must produce bitwise-identical tokens AND
    logits (tracing is a pure observer)."""
    model, weights = model_and_weights
    dm, dw = self_draft
    prompts = [list(range(1, 20)), list(range(1, 23)),
               list(range(1, 20)), [5, 6, 7]]

    def run(sample):
        flags_mod.set_flags({"request_trace_sample": sample})
        eng = DecodeEngine(
            model, weights,
            DecodeConfig(slots=2, max_seq_len=64, page_size=8,
                         prefix_cache=True, prefill_chunk_pages=1,
                         spec_k=2),
            draft_model=dm, draft_weights=dw)
        outs, logits = [], []
        with eng:
            for i, p in enumerate(prompts):
                r = eng.submit(p, max_new_tokens=6, seed=i,
                               record_logits=True)
                outs.append(r.result(timeout=120))
                logits.append([a.copy() for a in r.logits_trace])
            st = eng.stats()
        return outs, logits, st

    on_outs, on_logits, on_stats = run(1.0)
    off_outs, off_logits, _ = run(0.0)
    assert on_outs == off_outs
    for a_seq, b_seq in zip(on_logits, off_logits):
        assert len(a_seq) == len(b_seq)
        for a, b in zip(a_seq, b_seq):
            assert np.array_equal(a, b)
    # the composition actually engaged every path while traced
    store = rt.get_trace_store()
    all_events = [e[1] for t in store.retained() for e in t.events]
    assert "prefill_chunk" in all_events
    assert "spec_round" in all_events
    assert "cache/register" in all_events
    # the run exercised prefix sharing + full-acceptance speculation
    # (per-ENGINE exact rates; the registry gauges below are global
    # cumulative and other tests in the process feed them too)
    assert on_stats["cache_hit_rate"] > 0
    assert on_stats["spec_accept_rate"] == 1.0  # self-draft
    # float-precision _ppm companions of the (deprecated) integer
    # percent gauges are live and mutually consistent
    hit_pct = stat_get("decode_cache_hit_rate")
    hit_ppm = stat_get("decode_cache_hit_rate_ppm")
    assert hit_ppm > 0
    assert abs(hit_ppm / 1e4 - hit_pct) < 1.0  # same quantity, finer
    acc_pct = stat_get("spec_accept_rate")
    acc_ppm = stat_get("spec_accept_rate_ppm")
    assert acc_ppm > 0
    assert abs(acc_ppm / 1e4 - acc_pct) < 1.0
    # all 8 requests completed within the default (error-rate-only)
    # objectives -> goodput is live and nonzero on the registry
    assert stat_get("decode_goodput_rps_ppm") > 0


def test_recording_is_one_event_a_token_and_sampling_out_keeps_none(
        model_and_weights):
    """What recording costs, as counts no loaded CPU moves: a request's
    timeline holds ONE ``token`` event a generated token beside a fixed
    handful of lifecycle events (enqueue, the cache's claim, admit,
    prefill, finish: the same whether the request makes 12 tokens or
    48), nothing is dropped, and the timeline is the same with
    ``FLAGS_request_trace_sample`` 0, where a normal completion leaves
    nothing in the ring."""
    eng = make_engine(model_and_weights, slots=1, max_seq_len=128,
                      prefix_cache=False)

    def one_run(sample, n_new):
        flags_mod.set_flags({"request_trace_sample": sample})
        kept, out0 = stat_get("request_traces_retained"), \
            stat_get("request_traces_sampled_out")
        r = eng.submit([1, 2, 3], max_new_tokens=n_new)
        toks = r.result(timeout=120)
        # the verdict lands behind the caller's wake-up
        for _ in range(4000):
            if stat_get("request_traces_retained") + stat_get(
                    "request_traces_sampled_out") > kept + out0:
                break
            time.sleep(0.005)
        names = [name for _t, name, _a in r.trace.events]
        assert names.count("token") == len(toks) == n_new
        assert r.trace.dropped_events == 0
        return (toks, [n for n in names if n != "token"],
                stat_get("request_traces_retained") - kept,
                stat_get("request_traces_sampled_out") - out0)

    with eng:
        toks, life, kept, out = one_run(1.0, 48)
        assert (kept, out) == (1, 0) and 0 < len(life) <= 8
        assert one_run(1.0, 12)[1] == life
        assert one_run(0.0, 48) == (toks, life, 0, 1)


# ---------------------------------------------------------------------------
# outcome counters (error-rate SLO denominator)
# ---------------------------------------------------------------------------


class TestOutcomeCounters:
    def test_deadline_and_reject_counters(self, model_and_weights):
        eng = make_engine(model_and_weights, slots=1, max_queue=1)
        base_dl = stat_get("decode_requests_total_deadline")
        base_rej = stat_get("decode_requests_total_rejected")
        lat_count = stat_get("decode_request_latency_seconds_count") or 0
        with eng:
            with pytest.raises(RequestTooLargeError):
                eng.submit(list(range(200)), max_new_tokens=200)
            r = eng.submit([1, 2], max_new_tokens=4, deadline_ms=0.0001)
            with pytest.raises(DeadlineExceededError):
                r.result(timeout=60)
        assert stat_get("decode_requests_total_rejected") == base_rej + 1
        assert stat_get("decode_requests_total_deadline") == base_dl + 1
        from paddle_tpu.observe.histogram import histogram

        assert histogram("decode_request_latency_seconds").count \
            > lat_count
        # both abnormal endings are tail-retained with outcomes
        outs = {t.outcome for t in rt.get_trace_store().retained()}
        assert {"rejected", "deadline"} <= outs

    def test_abandon_outcome(self, model_and_weights):
        base = stat_get("decode_requests_total_abandoned")
        eng = make_engine(model_and_weights, slots=1, max_seq_len=128)
        with eng:
            r = eng.submit([1, 2], max_new_tokens=64,
                           on_token=lambda t: time.sleep(0.01))
            for _ in range(200):
                if r.generated:
                    break
                time.sleep(0.005)
            assert r.abandon("test gives up")
            # the engine must free the slot at a step boundary and
            # accept new work
            out = eng.generate([3, 4], max_new_tokens=2)
            assert len(out) == 2
        assert stat_get("decode_requests_total_abandoned") == base + 1
        tr = rt.get_trace_store().get(r.trace.trace_id)
        assert tr is not None and tr.outcome == "abandoned"

    def test_batcher_deadline_records_latency_and_counter(self):
        from paddle_tpu.observe.histogram import histogram

        base = stat_get("serving_requests_total_deadline")
        count = histogram("serving_latency_seconds").count
        req = InferenceRequest([], 1, (1,),
                               deadline=time.monotonic() - 0.01)
        with pytest.raises(DeadlineExceededError):
            req.result()
        assert stat_get("serving_requests_total_deadline") == base + 1
        assert histogram("serving_latency_seconds").count == count + 1

    def test_queue_full_rejection_counter(self, model_and_weights):
        base = stat_get("decode_requests_total_rejected")
        eng = make_engine(model_and_weights, slots=1, max_queue=1)
        # engine NOT started: the queue fills and stays full
        eng.submit([1], max_new_tokens=2)
        with pytest.raises(QueueFullError):
            eng.submit([2], max_new_tokens=2)
        eng.stop(drain=False)
        assert stat_get("decode_requests_total_rejected") == base + 1


# ---------------------------------------------------------------------------
# /debug plane under concurrent scrape (test_xla_stats pattern)
# ---------------------------------------------------------------------------


class TestConcurrentDebugScrape:
    def test_debug_requests_while_engine_admits_and_reaps(
            self, model_and_weights):
        """4 scrapers x 25 GETs over real HTTP against /debug/requests,
        /debug/request/<id>, /debug/slo, and /metrics while the server
        admits, decodes, deadline-reaps, and finishes a request stream:
        every response must stay well-formed JSON (or a well-formed
        exposition) and never 500."""
        model, weights = model_and_weights
        slo_mod.configure([
            slo_mod.Objective("ttft_p99", "ttft", 1e-4, 0.01)])
        srv = DecodeServer(
            model, weights,
            DecodeConfig(slots=2, max_seq_len=64, page_size=8,
                         max_queue=64),
            replicas=2, http_port=0)
        errors = []
        reqs = []
        stop = threading.Event()

        def feeder():
            i = 0
            while not stop.is_set() and i < 40:
                i += 1
                try:
                    reqs.append(srv.submit(
                        [1 + i % 7, 2, 3], max_new_tokens=3 + i % 5,
                        deadline_ms=0.05 if i % 9 == 0 else None,
                        seed=i))
                except QueueFullError:
                    pass
                time.sleep(0.002)

        def scraper():
            port = srv.http_port
            tid = None
            for _ in range(25):
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/debug/requests",
                            timeout=10) as r:
                        assert r.status == 200
                        doc = json.loads(r.read().decode())
                    assert "requests" in doc and isinstance(
                        doc["requests"], list)
                    for row in doc["requests"]:
                        assert "phase" in row and "replica" in row
                        tid = row.get("trace_id") or tid
                    if tid is not None:
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{port}"
                                f"/debug/request/{tid}",
                                timeout=10) as r:
                            assert r.status == 200
                            json.loads(r.read().decode())
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/debug/slo",
                            timeout=10) as r:
                        assert r.status == 200
                        assert "burn_rates" in json.loads(
                            r.read().decode())
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/metrics",
                            timeout=10) as r:
                        body = r.read().decode()
                    for ln in body.splitlines():
                        if ln and not ln.startswith("#"):
                            float(ln.rsplit(" ", 1)[1])
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        with srv:
            srv.generate([1, 2], max_new_tokens=2)  # warm compiles
            ft = threading.Thread(target=feeder, daemon=True)
            scrapers = [threading.Thread(target=scraper)
                        for _ in range(4)]
            ft.start()
            for s in scrapers:
                s.start()
            for s in scrapers:
                s.join()
            stop.set()
            ft.join(timeout=30)
            for r in reqs:
                try:
                    r.result(timeout=120)
                except DeadlineExceededError:
                    pass
            st = srv.stats()
        assert not errors, errors[:3]
        # the metrics surface carried the SLO plane
        assert stat_get("slo_burn_rate_ttft_p99_ppm") >= 0
        assert stat_get("decode_requests_total_completed") > 0
        # DecodeServer aggregation carries the goodput/violation plane
        assert "goodput_rps" in st and "slo_violations" in st
        # replica-tagged traces from the engines land in ONE store
        replicas = {t.replica for t in rt.get_trace_store().retained()
                    if t.kind == "decode"}
        assert replicas and all(r.startswith("replica-")
                                for r in replicas)

    def test_debug_request_unknown_id_is_a_json_answer(
            self, model_and_weights):
        model, weights = model_and_weights
        srv = DecodeServer(model, weights,
                           DecodeConfig(slots=1, max_seq_len=32,
                                        page_size=8),
                           replicas=1, http_port=0)
        with srv:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.http_port}"
                    f"/debug/request/nope-000001", timeout=10) as r:
                doc = json.loads(r.read().decode())
        assert "error" in doc and "nope-000001" in doc["error"]


