"""Disaggregated prefill/decode serving (paddle_tpu.serving.disagg):
deterministic routing, KV-page migration bitwise parity, the
migrated-page cache audit, chaos-driven prefill-replica death with
zero drops, and the SLO autoscaler's hysteresis/cooldown policy.

The load-bearing oracle: a request served disaggregated (prefill on
one engine, pages migrated, decode on another) must produce BITWISE
the same tokens and logits as the same request served locally with the
same seed — plain and kv_quant pools both.
"""
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.fleet.elastic import chaos
from paddle_tpu.framework.scope import Scope
from paddle_tpu.monitor import stat_get
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine, \
    TransformerLM
from paddle_tpu.serving.disagg import Autoscaler, DisaggConfig, \
    DisaggServer
from paddle_tpu.serving.kv_cache import CacheConfig, PagedKVCache
from paddle_tpu.serving.server import least_loaded_order

VOCAB = 61


@pytest.fixture(scope="module")
def model_and_weights():
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    weights = model.init_weights(jax.random.PRNGKey(7))
    return model, weights


def _decode_cfg(**kw):
    cfg = dict(slots=2, max_seq_len=32, page_size=8, max_new_tokens=6)
    cfg.update(kw)
    return DecodeConfig(**cfg)


# -- satellite: deterministic least-loaded tie-break ----------------------


class _FakeEngine:
    def __init__(self, free_slots, queue_depth):
        self.free_slots = free_slots
        self.queue_depth = queue_depth


def test_least_loaded_tie_break_is_lowest_index():
    # four identical replicas: the order must be the INDEX order, not
    # an iteration-order accident
    engines = [_FakeEngine(2, 0) for _ in range(4)]
    assert least_loaded_order(engines) == engines
    # ties broken only after (free_slots desc, queue_depth asc)
    a, b, c, d = (_FakeEngine(1, 2), _FakeEngine(2, 1),
                  _FakeEngine(2, 1), _FakeEngine(2, 0))
    assert least_loaded_order([a, b, c, d]) == [d, b, c, a]


# -- migration bitwise oracle ---------------------------------------------


def _run_disagg_vs_local(model_and_weights, kv_quant):
    model, weights = model_and_weights
    prompts = [[5, 4, 3, 2, 1, 6, 7, 8],   # exactly one page
               list(range(1, 14)),          # two pages, partial tail
               [7]]                         # single-token prompt
    seeds = [11, 22, 33]
    srv = DisaggServer(
        model, weights, config=_decode_cfg(kv_quant=kv_quant),
        disagg=DisaggConfig(prefill_replicas=1, decode_replicas=1))
    with srv:
        dreqs = [srv.submit(p, max_new_tokens=5, temperature=1.0,
                            seed=s, record_logits=True)
                 for p, s in zip(prompts, seeds)]
        douts = [r.result(timeout=120) for r in dreqs]
    # engines stopped (threads joined): the audit can read the books
    # without racing the engine loop
    for rep in srv.replicas:
        rep.engine._cache.debug_check()
    local = DecodeEngine(model, weights,
                         _decode_cfg(kv_quant=kv_quant)).start()
    try:
        lreqs = [local.submit(p, max_new_tokens=5, temperature=1.0,
                              seed=s, record_logits=True)
                 for p, s in zip(prompts, seeds)]
        louts = [r.result(timeout=120) for r in lreqs]
    finally:
        local.stop()
    for p, dout, lout, dreq, lreq in zip(prompts, douts, louts, dreqs,
                                         lreqs):
        assert dout == lout, (
            f"migrated decode diverged from local for prompt {p}: "
            f"{dout} vs {lout}")
        dtrace = dreq.decode_request.logits_trace
        assert len(dtrace) == len(lreq.logits_trace) == 5
        for i, (dl, ll) in enumerate(zip(dtrace, lreq.logits_trace)):
            assert np.array_equal(np.asarray(dl), np.asarray(ll)), (
                f"logits diverged at step {i} for prompt {p}")


def test_migrated_decode_bitwise_equals_local(model_and_weights):
    before = stat_get("migrate_pages_total")
    _run_disagg_vs_local(model_and_weights, kv_quant=False)
    assert stat_get("migrate_pages_total") > before
    assert stat_get("decode_migrated_admissions") > 0
    assert stat_get("decode_kv_exports") > 0


def test_migrated_decode_bitwise_equals_local_kv_quant(
        model_and_weights):
    before = stat_get("migrate_bytes_total")
    _run_disagg_vs_local(model_and_weights, kv_quant=True)
    assert stat_get("migrate_bytes_total") > before


# -- migrated-page audit (cache level) ------------------------------------


def test_debug_check_migrated_page_audit():
    cfg = CacheConfig(2, 2, 8, num_slots=2, max_seq_len=32,
                      page_size=8, quantized=True)
    src = PagedKVCache(cfg, Scope())
    dst = PagedKVCache(cfg, Scope())
    prompt = list(range(1, 14))  # 13 tokens -> 2 pages
    assert src.claim(0, len(prompt) + 4, prompt=prompt) is not None
    export_pages = src.slot_pages(0)[:cfg.pages_for(len(prompt))]
    arrays = src.export_pages(export_pages)
    assert set(arrays) == set(src.state_var_names())
    assert dst.claim(0, len(prompt) + 4, prompt=None) is not None
    from paddle_tpu.serving.kv_cache import KVPageExport

    exp = KVPageExport(n_tokens=len(prompt), n_pages=2,
                       src_pages=export_pages, arrays=arrays,
                       quantized=True, page_size=8)
    dst.install_pages(0, exp)
    assert len(dst._migrated_in) == 2
    dst.debug_check()  # refcount 1, unregistered, live scales: OK
    # tamper: register a migrated page in the prefix index while it is
    # still slot-owned — the audit must catch the leaked sharing
    pid = dst.slot_pages(0)[0]
    dst.prefix.register([pid], prompt[:8], on_new=dst._incref)
    with pytest.raises(AssertionError, match="migrated-in page"):
        dst.debug_check()
    dst.prefix.evict(1, can_evict=lambda p: True,
                     on_evict=dst._decref)
    dst.debug_check()
    # release ends the invariant: pages become ordinary, audit stays
    # green and the tracking empties
    dst.release(0)
    assert not dst._migrated_in
    dst.debug_check()
    src.release(0)
    src.debug_check()


# -- chaos: prefill replica killed mid-stream -----------------------------


def test_chaos_prefill_kill_zero_drops(model_and_weights):
    model, weights = model_and_weights
    srv = DisaggServer(
        model, weights, config=_decode_cfg(),
        disagg=DisaggConfig(prefill_replicas=2, decode_replicas=1))
    deaths0 = stat_get("disagg_replica_deaths")
    redisp0 = stat_get("disagg_redispatches_total")
    chaos.clear()
    # the router's deterministic tie-break picks replica 0 first, so
    # arming replica=0 kills the FIRST request's prefill mid-stream
    chaos.inject("kill_prefill_replica", count=1, replica=0)
    try:
        with srv:
            reqs = [srv.submit([3 + i, 5, 7, 9, 2], max_new_tokens=4,
                               seed=100 + i) for i in range(4)]
            outs = [r.result(timeout=120) for r in reqs]
            # zero drops: every request produced its full budget
            assert all(len(o) == 4 for o in outs)
            assert stat_get("disagg_replica_deaths") == deaths0 + 1
            assert stat_get("disagg_redispatches_total") > redisp0
            assert [r.dead for r in srv.replicas] == [True, False,
                                                      False]
        # server stopped: the migrated-page audit holds on the
        # surviving fleet's books
        for rep in srv.replicas:
            if not rep.dead:
                rep.engine._cache.debug_check()
    finally:
        chaos.clear()


# -- autoscaler: re-role, hysteresis, cooldown, preflight -----------------


class _Signals:
    def __init__(self):
        self.burn = 0.0
        self.queue = 0.0
        self.now = 1000.0
        self.preflight_ok = True

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += s


def _roles(srv):
    return [r.role for r in srv.replicas]


def test_autoscaler_rerole_cooldown_and_preflight(model_and_weights):
    model, weights = model_and_weights
    srv = DisaggServer(
        model, weights, config=_decode_cfg(),
        disagg=DisaggConfig(prefill_replicas=1, decode_replicas=3,
                            autoscale_cooldown_s=30.0,
                            autoscale_burn_high=1.0,
                            autoscale_burn_low=0.25,
                            autoscale_queue_high=4))
    sig = _Signals()
    auto = Autoscaler(srv, burn_fn=lambda: sig.burn,
                      queue_fn=lambda: sig.queue,
                      preflight=lambda: sig.preflight_ok,
                      clock=sig.clock, sleep=sig.sleep)
    assert _roles(srv) == ["prefill", "decode", "decode", "decode"]
    # healthy signals: no action
    assert auto.tick() is None
    # induced ttft burn: one decode replica re-roles to prefill
    # (lowest index wins the tie — deterministic)
    sig.burn = 2.0
    reroles0 = stat_get("autoscale_reroles_total")
    skips0 = stat_get("autoscale_cooldown_skips_total")
    assert auto.tick() == "decode->prefill"
    assert _roles(srv) == ["prefill", "prefill", "decode", "decode"]
    assert stat_get("autoscale_reroles_total") == reroles0 + 1
    # still burning, but inside the cooldown window: counted + DROPPED
    # — the no-flap pin
    assert auto.tick() is None
    assert _roles(srv) == ["prefill", "prefill", "decode", "decode"]
    assert stat_get("autoscale_cooldown_skips_total") == skips0 + 1
    # cooldown elapsed, burn healthy, decode queue piling up: the
    # replica comes back (hysteresis: burn must sit UNDER burn_low)
    sig.now += 31.0
    sig.burn = 0.1
    sig.queue = 5.0
    assert auto.tick() == "prefill->decode"
    # the pick is least-loaded/lowest-index among PREFILL replicas, so
    # replica 0 (the original prefill) converts — deterministic
    assert _roles(srv) == ["decode", "prefill", "decode", "decode"]
    # queue pressure with burn INSIDE the hysteresis band: no action
    sig.now += 31.0
    sig.burn = 0.5
    assert auto.tick() is None
    # preflight failure aborts the re-role: roles unchanged, replica
    # undrained, failure counted
    sig.burn = 2.0
    sig.preflight_ok = False
    pf0 = stat_get("autoscale_preflight_failures")
    assert auto.tick() is None
    assert _roles(srv) == ["decode", "prefill", "decode", "decode"]
    assert stat_get("autoscale_preflight_failures") == pf0 + 1
    assert all(not r.draining for r in srv.replicas)


def test_autoscaler_reroles_on_the_burn_of_real_traffic(
        model_and_weights):
    """Nothing of the trigger is a stand-in but the bar: an objective no
    request can meet makes every served request a violation, the
    autoscaler's OWN burn signal (observe/slo.py's snapshot) fires, one
    decode replica re-roles, and the cooldown drops the retrigger."""
    from paddle_tpu.observe import slo

    model, weights = model_and_weights
    slo.configure([slo.Objective("ttft_p99", "ttft", 1e-6, 0.01)])
    try:
        srv = DisaggServer(
            model, weights, config=_decode_cfg(),
            disagg=DisaggConfig(prefill_replicas=1, decode_replicas=3,
                                autoscale_cooldown_s=3600.0))
        with srv:
            for r in [srv.submit([9, 8, 7], max_new_tokens=4, seed=70 + i)
                      for i in range(4)]:
                r.result(timeout=120)
            auto = Autoscaler(srv, queue_fn=lambda: 0.0,
                              preflight=lambda: True)
            skips0 = stat_get("autoscale_cooldown_skips_total")
            assert auto.tick() == "decode->prefill"
            assert _roles(srv) == ["prefill", "prefill", "decode",
                                   "decode"]
            assert auto.tick() is None
            assert stat_get("autoscale_cooldown_skips_total") == skips0 + 1
    finally:
        slo.configure(None)


def test_autoscaler_thread_lifecycle(model_and_weights):
    model, weights = model_and_weights
    srv = DisaggServer(
        model, weights, config=_decode_cfg(),
        disagg=DisaggConfig(prefill_replicas=1, decode_replicas=1,
                            autoscale_interval_s=0.01))
    ticks = []
    auto = Autoscaler(srv, burn_fn=lambda: ticks.append(1) or 0.0,
                      queue_fn=lambda: 0.0,
                      preflight=lambda: True)
    auto.start()
    try:
        deadline = time.monotonic() + 5.0
        while len(ticks) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(ticks) >= 3, "autoscaler loop never ticked"
        assert stat_get("disagg_prefill_replicas") == 1
        assert stat_get("disagg_decode_replicas") == 1
    finally:
        auto.stop()
    assert auto._thread is None
