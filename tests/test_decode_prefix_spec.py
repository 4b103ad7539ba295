"""Prefix-cache page sharing, chunked prefill, and speculative decoding
(serving/kv_cache.py PrefixIndex + serving/decode.py tentpole paths).

The load-bearing property carried over from PR 10: decode-with-cache
logits are BITWISE equal to the full-recompute oracle on EVERY path —
full prefix hit (prefill skipped entirely), partial-tail borrow with
copy-on-write at the first divergent token, suffix prefill after a
page-aligned divergence, chunked prefill, and speculative verify.  Any
sharing bug (stale page, wrong CoW timing, draft desync) shows up as a
bit difference or a refcount imbalance (``PagedKVCache.debug_check``).
"""
import time

import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.monitor import stat_get
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine, \
    TransformerLM
from paddle_tpu.serving.kv_cache import PageAllocator, PrefixIndex

VOCAB = 61


@pytest.fixture(scope="module")
def model_and_weights():
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    weights = model.init_weights(jax.random.PRNGKey(7))
    return model, weights


@pytest.fixture(scope="module")
def draft_and_weights():
    import jax

    # a real small draft: same vocab, smaller body, DIFFERENT weights
    # (low acceptance — exercises the rejection paths)
    draft = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=256)
    return draft, draft.init_weights(jax.random.PRNGKey(99))


def make_engine(model_and_weights, draft=None, **cfg_kw):
    model, weights = model_and_weights
    kw = dict(slots=2, max_seq_len=64, page_size=8, max_new_tokens=8)
    kw.update(cfg_kw)
    dm, dw = draft if draft is not None else (None, None)
    return DecodeEngine(model, weights, DecodeConfig(**kw),
                        draft_model=dm, draft_weights=dw)


def assert_oracle_bitwise(eng, prompt, req, out):
    for t in range(len(out)):
        oracle = eng.recompute_logits(list(prompt) + list(out[:t]))
        assert np.array_equal(oracle, req.logits_trace[t]), (
            f"cached logits diverged from the full recompute at step "
            f"{t} (max diff "
            f"{np.abs(oracle - req.logits_trace[t]).max()})")


# -- prefix index plumbing ------------------------------------------------


def test_prefix_index_lookup_register_evict():
    idx = PrefixIndex(page_size=4)
    # register two pages of [1..8] then a partial tail [9, 9]
    n = idx.register([5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8, 9, 9],
                     on_new=lambda pid: None)
    assert n == 3 and len(idx) == 3
    full, partial = idx.lookup([1, 2, 3, 4, 5, 6, 7, 8, 9, 9])
    assert full == [5, 6] and partial == 7
    # a SHORTER tail that prefixes the registered partial also hits
    full, partial = idx.lookup([1, 2, 3, 4, 5, 6, 7, 8, 9])
    assert full == [5, 6] and partial == 7
    # divergence inside page 2 -> only page 1 matches, no partial
    full, partial = idx.lookup([1, 2, 3, 4, 5, 6, 99, 8, 1])
    assert full == [5] and partial is None
    # duplicate registration adopts the existing chain, registers none
    assert idx.register([11, 12], [1, 2, 3, 4, 5, 6, 7, 8],
                        on_new=lambda pid: None) == 0
    # eviction is bottom-up: the mid-chain page is never a victim
    # while its child lives
    evicted = []
    idx.evict(1, can_evict=lambda pid: True, on_evict=evicted.append)
    assert evicted == [7]  # the leaf (LRU-ranked among childless)
    idx.evict(10, can_evict=lambda pid: True, on_evict=evicted.append)
    assert evicted == [7, 6, 5] and len(idx) == 0


def test_page_allocator_double_free_raises():
    a = PageAllocator(6)
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(RuntimeError, match="double free"):
        a.free([pages[0]])


def test_page_allocator_zero_alloc_takes_nothing():
    """Review pin: a fully-shared claim needs ZERO fresh pages; the
    n==0 slice (`_free[-0:]` == whole list) must not drain the pool."""
    a = PageAllocator(6)
    assert a.alloc(0) == []
    assert a.num_free == 5


def test_claim_eviction_never_recycles_matched_pages():
    """Review-hardening pin: under pool pressure the eviction-backed
    allocation must never free a page the SAME claim just matched and
    hand it back as a fresh page (one physical page in two table
    roles).  Matched pages are pinned before allocation; a partial
    borrow that then cannot fit is dropped (becoming evictable again)
    rather than deadlocking the queue head behind its own match."""
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.serving.kv_cache import CacheConfig, PagedKVCache

    cfg = CacheConfig(1, 1, 4, num_slots=2, max_seq_len=16,
                      page_size=4, num_pages=5)  # 4 usable pages
    cache = PagedKVCache(cfg, Scope(), prefix_cache=True)
    assert cache.claim(0, 8, prompt=[1, 2, 3, 4, 5, 6]) is not None
    cache.release(0, register_tokens=[1, 2, 3, 4, 5, 6])
    assert cache.shared_pages == 2 and cache.allocator.num_free == 2
    # total 4 pages, full hit 1, partial hit 1 -> 3 fresh vs 2 free:
    # the matched partial must not be evicted into the fresh set
    info = cache.claim(1, 16, prompt=[1, 2, 3, 4, 5, 6])
    assert info is not None  # liveness: the borrow is dropped, not stuck
    assert info.full_hits == 1 and not info.partial
    held = cache.slot_pages(1) + cache._cow_spare[1]
    assert len(held) == len(set(held)), \
        f"one physical page holds two table roles: {held}"
    cache.debug_check()
    cache.release(1)
    cache.debug_check()


# -- full prefix hit: prefill skipped, CoW at the first new token ---------


def test_full_hit_skips_prefill_cow_bitwise(model_and_weights):
    eng = make_engine(model_and_weights).start()
    prompt = [1, 2, 3, 4, 5]  # 5 tokens: partial tail page -> CoW
    try:
        out1 = eng.generate(prompt, max_new_tokens=6)
        skip0 = stat_get("decode_prefill_skipped")
        cow0 = stat_get("decode_cow_copies")
        r2 = eng.submit(prompt, max_new_tokens=6, record_logits=True)
        out2 = r2.result(timeout=120)
    finally:
        eng.stop()
    assert out2 == out1  # greedy: the shared-prefix replay is identical
    assert stat_get("decode_prefill_skipped") == skip0 + 1
    # the borrowed partial tail page was copy-on-written exactly once,
    # at the first token the new request wrote into it
    assert stat_get("decode_cow_copies") == cow0 + 1
    assert_oracle_bitwise(eng, prompt, r2, out2)
    assert eng.stats()["cache_hit_rate"] > 0
    eng._cache.debug_check()


def test_page_aligned_divergence_suffix_prefill_bitwise(
        model_and_weights):
    """Prompts sharing whole pages then diverging: the shared pages
    are borrowed, ONLY the unmatched suffix is prefilled, and logits
    stay bitwise-equal to the no-sharing oracle."""
    eng = make_engine(model_and_weights).start()
    base = list(range(1, 17))  # 2 full pages (page_size=8)
    try:
        eng.generate(base + [20, 21], max_new_tokens=4)
        hit0 = stat_get("decode_prefix_pages_hit")
        r = eng.submit(base + [40, 41, 42], max_new_tokens=5,
                       record_logits=True)
        out = r.result(timeout=120)
    finally:
        eng.stop()
    assert stat_get("decode_prefix_pages_hit") - hit0 == 2
    assert len(out) == 5
    assert_oracle_bitwise(eng, base + [40, 41, 42], r, out)
    eng._cache.debug_check()


def test_mid_page_divergence_is_a_miss_and_stays_bitwise(
        model_and_weights):
    eng = make_engine(model_and_weights).start()
    try:
        eng.generate([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], max_new_tokens=4)
        # diverges at position 9 (inside page 2): page 1 hits, the
        # divergent page is computed fresh
        r = eng.submit([1, 2, 3, 4, 5, 6, 7, 8, 9, 77],
                       max_new_tokens=4, record_logits=True)
        out = r.result(timeout=120)
    finally:
        eng.stop()
    assert_oracle_bitwise(eng, [1, 2, 3, 4, 5, 6, 7, 8, 9, 77], r, out)
    eng._cache.debug_check()


# -- admission capacity: >= 2x at fixed pool size -------------------------


@pytest.mark.slow  # wall-clock paced (sleep-held slots)
def test_shared_admission_capacity_at_least_doubles(model_and_weights):
    """The acceptance bar: at a FIXED pool size, prefix sharing must
    admit >= 2x the concurrent requests of the unshared engine.  Each
    request needs 3 pages unshared; the pool holds 7, so unshared
    concurrency is 2.  With the 2-page prefix shared, each extra
    request only allocates 1 fresh page."""
    prefix = list(range(1, 17))  # 2 full pages
    model, weights = model_and_weights

    def max_live(prefix_cache):
        eng = make_engine(model_and_weights, slots=6, max_seq_len=64,
                          page_size=8, num_pages=8,
                          prefix_cache=prefix_cache).start()
        try:
            if prefix_cache:  # register the prefix
                eng.generate(prefix + [50], max_new_tokens=5)
            reqs = [eng.submit(prefix + [51 + i], max_new_tokens=6,
                               on_token=lambda t: time.sleep(0.05))
                    for i in range(6)]
            peak = 0
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline \
                    and not all(r.done() for r in reqs):
                peak = max(peak, eng.live_slots)
                time.sleep(0.005)
            for r in reqs:
                r.result(timeout=120)
        finally:
            eng.stop()
        return peak

    unshared = max_live(False)
    shared = max_live(True)
    assert unshared <= 2  # 7 pages // 3 per request
    assert shared >= 2 * unshared, (
        f"sharing admitted {shared} concurrent vs {unshared} unshared")


def test_prefix_eviction_under_pool_pressure(model_and_weights):
    """Cache-retained pages are reclaimed (LRU, childless-first) when
    admission needs them — retention never blocks new work."""
    eng = make_engine(model_and_weights, slots=2, max_seq_len=64,
                      page_size=8, num_pages=9).start()
    try:
        # three disjoint finished requests pin 2 registered pages each
        for base in (0, 20, 40):
            eng.generate([base + i for i in range(1, 9)],
                         max_new_tokens=8)
        ev0 = stat_get("decode_prefix_evictions")
        assert eng._cache.shared_pages == 6  # 8 usable, 2 free
        out = eng.generate(list(range(50, 50 + 16)), max_new_tokens=8)
    finally:
        eng.stop()
    assert len(out) == 8
    assert stat_get("decode_prefix_evictions") > ev0
    eng._cache.debug_check()


# -- chunked prefill ------------------------------------------------------


def test_chunked_prefill_bitwise(model_and_weights):
    eng = make_engine(model_and_weights, slots=2, max_seq_len=64,
                      page_size=8, prefill_chunk_pages=1,
                      prefix_cache=False).start()
    prompt = list(range(1, 28))  # 27 tokens -> 4 one-page chunks
    try:
        c0 = stat_get("prefill_chunks")
        r = eng.submit(prompt, max_new_tokens=5, record_logits=True)
        out = r.result(timeout=120)
    finally:
        eng.stop()
    assert stat_get("prefill_chunks") - c0 == 4
    assert_oracle_bitwise(eng, prompt, r, out)


def test_chunked_prefill_protects_ttft_under_long_prompt_adversary(
        model_and_weights):
    """A long prompt fills its pages across several step boundaries;
    short requests keep streaming between chunks, so the adversary
    cannot stall their time-to-first-token behind its whole prefill.
    Deterministic scheduling property: the short request's first token
    must arrive BEFORE the long request's (the long prefill needs ~6
    boundaries, the short one 1)."""
    eng = make_engine(model_and_weights, slots=3, max_seq_len=128,
                      page_size=8, prefill_chunk_pages=1,
                      max_new_tokens=64, prefix_cache=False).start()
    try:
        eng.generate([9, 9], max_new_tokens=2)  # pay the step compiles
        adversary = eng.submit(list(range(1, 49)), max_new_tokens=4)
        short = eng.submit([3, 1], max_new_tokens=4)
        out_s = short.result(timeout=120)
        out_a = adversary.result(timeout=120)
    finally:
        eng.stop()
    assert len(out_s) == 4 and len(out_a) == 4
    assert short.t_first_token < adversary.t_first_token, (
        "the short request's first token waited for the adversary's "
        "whole prefill — chunking did not yield the step loop")


# -- speculative decoding -------------------------------------------------


@pytest.mark.parametrize(
    "k", [1, pytest.param(4, marks=pytest.mark.slow)])
# tier-1 keeps k=1 here and k=4 in the self-draft test below: both k
# values and both acceptance regimes stay covered within the budget
def test_spec_greedy_bitwise_low_acceptance_draft(
        model_and_weights, draft_and_weights, k):
    """With a REAL (weak) draft, rejections dominate — output must
    still be bitwise-identical to non-speculative greedy decode, and
    every emitted token's logits must match the full-recompute
    oracle."""
    prompt = [1, 2, 3, 4, 5]
    eng = make_engine(model_and_weights).start()
    try:
        ref = eng.generate(prompt, max_new_tokens=10)
    finally:
        eng.stop()
    eng = make_engine(model_and_weights, draft=draft_and_weights,
                      spec_k=k).start()
    try:
        r = eng.submit(prompt, max_new_tokens=10, record_logits=True)
        out = r.result(timeout=120)
    finally:
        eng.stop()
    assert out == ref
    assert_oracle_bitwise(eng, prompt, r, out)
    eng._cache.debug_check()


@pytest.mark.parametrize(
    "k", [pytest.param(1, marks=pytest.mark.slow), 4])
def test_spec_self_draft_full_acceptance_fewer_rounds(
        model_and_weights, k):
    """Draft == target: every proposal is accepted, so N tokens take
    ~N/(k+1) verify rounds instead of N steps — the speedup mechanism,
    pinned via dispatch counts (wall-clock-free)."""
    model, weights = model_and_weights
    prompt = [1, 2, 3]
    n_new = 12
    eng = make_engine(model_and_weights).start()
    try:
        ref = eng.generate(prompt, max_new_tokens=n_new)
    finally:
        eng.stop()
    eng = make_engine(model_and_weights, draft=(model, weights),
                      spec_k=k).start()
    try:
        r0 = stat_get("decode_spec_rounds")
        p0 = stat_get("decode_spec_proposed")
        a0 = stat_get("decode_spec_accepted")
        r = eng.submit(prompt, max_new_tokens=n_new, record_logits=True)
        out = r.result(timeout=120)
    finally:
        eng.stop()
    assert out == ref
    assert_oracle_bitwise(eng, prompt, r, out)
    rounds = stat_get("decode_spec_rounds") - r0
    proposed = stat_get("decode_spec_proposed") - p0
    accepted = stat_get("decode_spec_accepted") - a0
    assert accepted == proposed > 0  # self-draft: full acceptance
    # prefill emits 1, each round emits k+1, a possible final single
    # step emits the remainder
    import math
    assert rounds <= math.ceil((n_new - 1) / (k + 1))


def test_spec_composes_with_prefix_sharing(model_and_weights):
    """A full prefix hit on a spec engine: prefill skipped AND the
    draft reads the shared pages (its pools share page ids), with
    output still bitwise-equal to the oracle."""
    model, weights = model_and_weights
    prompt = [7, 6, 5, 4, 3, 2, 1]
    eng = make_engine(model_and_weights, draft=(model, weights),
                      spec_k=2).start()
    try:
        out1 = eng.generate(prompt, max_new_tokens=8)
        skip0 = stat_get("decode_prefill_skipped")
        r = eng.submit(prompt, max_new_tokens=8, record_logits=True)
        out2 = r.result(timeout=120)
    finally:
        eng.stop()
    assert out2 == out1
    assert stat_get("decode_prefill_skipped") == skip0 + 1
    assert_oracle_bitwise(eng, prompt, r, out2)
    eng._cache.debug_check()


def test_spec_vocab_mismatch_and_submit_rejections(model_and_weights,
                                                   draft_and_weights):
    model, weights = model_and_weights
    bad_draft = TransformerLM(vocab_size=VOCAB + 1, d_model=16,
                              num_layers=1, num_heads=2,
                              max_seq_len=256)
    import jax

    with pytest.raises(ValueError, match="vocab mismatch"):
        make_engine(model_and_weights,
                    draft=(bad_draft,
                           bad_draft.init_weights(jax.random.PRNGKey(0))))
    # a request that DEMANDS speculation fails loudly at submit when
    # the engine cannot honor it
    eng = make_engine(model_and_weights)  # no draft
    with pytest.raises(ValueError, match="no draft"):
        eng.submit([1, 2], speculative=True)
    eng2 = make_engine(model_and_weights, draft=draft_and_weights,
                       spec_k=0)
    with pytest.raises(ValueError, match="spec_k"):
        eng2.submit([1, 2], speculative=True)
    eng3 = make_engine(model_and_weights, draft=draft_and_weights,
                       spec_k=2)
    with pytest.raises(ValueError, match="greedy-only"):
        eng3.submit([1, 2], speculative=True, temperature=0.7)


# -- pallas multi-row kernel ----------------------------------------------


def test_paged_chunk_attention_pallas_interpret_matches_reference():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_decode_attention import \
        paged_chunk_attention

    rs = np.random.RandomState(0)
    s, r, h, d, pool, page, pps = 3, 5, 2, 16, 9, 8, 4
    q = jnp.asarray(rs.randn(s, r, h, d).astype("f4"))
    # the stacked pools [L, P, page, H*D] (one layer here)
    kp = jnp.asarray(rs.randn(1, pool, page, h * d).astype("f4"))
    vp = jnp.asarray(rs.randn(1, pool, page, h * d).astype("f4"))
    table = jnp.asarray(rs.randint(1, pool, (s, pps)).astype("i4"))
    # starts at a mid-page offset, zero, and near the table's end
    starts = np.array([7, 0, 27], "i4")
    row_lengths = jnp.asarray(
        starts[:, None] + np.arange(1, r + 1, dtype="i4")[None, :])
    ref = paged_chunk_attention(q, kp, vp, table, row_lengths,
                                use_pallas="never")
    pal = paged_chunk_attention(q, kp, vp, table, row_lengths,
                                use_pallas="always", interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=1e-5, atol=1e-5)
    # review pin: the kernel's page-skip bound must hold for ARBITRARY
    # per-row lengths, not just the ascending ones the engine passes
    # (the widest row used to be assumed last)
    weird = jnp.asarray(np.array([[20, 5, 1, 17, 9],
                                  [3, 30, 2, 2, 2],
                                  [1, 1, 1, 1, 32]], "i4"))
    ref = paged_chunk_attention(q, kp, vp, table, weird,
                                use_pallas="never")
    pal = paged_chunk_attention(q, kp, vp, table, weird,
                                use_pallas="always", interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=1e-5, atol=1e-5)


# -- free-list audit: chaos across admit / CoW / reap ---------------------


@pytest.mark.parametrize("kv_quant", [False, True],
                         ids=["fp", "kv_quant"])
def test_chaos_admit_cow_reap_never_leaks_or_double_frees(
        model_and_weights, kv_quant):
    """The bugfix-sweep pin: randomized waves of shared-prefix
    requests — full hits, partial borrows, CoW, deadline reaps,
    abandons, chunked prefills, speculative rounds — must leave the
    refcount/free-list/index books EXACTLY balanced
    (``debug_check``).  With ``kv_quant`` the audit extends to the
    scale pools (target + draft): finite scales everywhere, freed
    pages' scale planes reset."""
    model, weights = model_and_weights
    rs = np.random.RandomState(11)
    prefixes = [list(range(1, 9)), list(range(30, 42)), [5, 5, 5]]
    eng = make_engine(model_and_weights, slots=3, max_seq_len=64,
                      page_size=8, num_pages=17, max_queue=64,
                      prefill_chunk_pages=1, kv_quant=kv_quant,
                      draft=(model, weights), spec_k=2).start()
    try:
        waves = []
        for _ in range(6):
            reqs = []
            for _ in range(6):
                prompt = list(prefixes[rs.randint(len(prefixes))])
                prompt += [int(t) for t in
                           rs.randint(1, VOCAB, rs.randint(0, 5))]
                kw = dict(max_new_tokens=int(rs.randint(2, 8)))
                roll = rs.rand()
                if roll < 0.2:
                    kw["deadline_ms"] = 1  # reaped while queued/early
                elif roll < 0.4:
                    kw["temperature"] = 1.0  # non-spec slot in the mix
                reqs.append(eng.submit(prompt, **kw))
            waves.append(reqs)
            time.sleep(0.02)
        for reqs in waves:
            for r in reqs:
                try:
                    r.result(timeout=120)
                except serving.DeadlineExceededError:
                    pass
        # quiesce, then audit the books
        deadline = time.monotonic() + 30
        while eng.live_slots and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.live_slots == 0
        eng._cache.debug_check()
        usable = eng._cache.config.num_pages - 1
        assert (eng._cache.allocator.num_free
                + eng._cache.shared_pages) == usable
        # the chaos actually exercised the tentpole paths
        st = eng.stats()
        assert st["prefix_hit_pages"] > 0
        assert st["prefill_chunks"] > 0
        assert st["spec_proposed"] > 0
    finally:
        eng.stop()


# -- observability --------------------------------------------------------


def test_tentpole_metrics_on_prometheus(model_and_weights):
    model, weights = model_and_weights
    eng = make_engine(model_and_weights, draft=(model, weights),
                      spec_k=2, prefill_chunk_pages=1).start()
    try:
        prompt = list(range(1, 12))
        eng.generate(prompt, max_new_tokens=4)
        eng.generate(prompt, max_new_tokens=4)  # hit + CoW
    finally:
        eng.stop()
    from paddle_tpu.observe.histogram import prometheus_text

    text = prometheus_text()
    for series in ("decode_cache_hit_rate", "decode_shared_pages",
                   "decode_cow_copies", "spec_accept_rate",
                   "prefill_chunks", "decode_prefix_pages_hit",
                   "decode_prefill_skipped"):
        assert series in text, series


@pytest.mark.slow  # two spec replicas = the compile-heaviest setup;
# the aggregation fields are plain sums over the per-replica stats
# that test_tentpole_metrics_on_prometheus already exercises
def test_decode_server_aggregates_tentpole_stats(model_and_weights):
    model, weights = model_and_weights
    cfg = DecodeConfig(slots=2, max_seq_len=64, page_size=8,
                       max_new_tokens=6, spec_k=2)
    srv = serving.DecodeServer(model, weights, cfg, replicas=2,
                               draft_model=model,
                               draft_weights=weights).start()
    try:
        prompt = [2, 4, 6, 8]
        for eng in srv.replicas:  # register + hit on BOTH replicas
            eng.generate(prompt, max_new_tokens=4)
            eng.generate(prompt, max_new_tokens=4)
        st = srv.stats()
    finally:
        srv.stop()
    assert st["cache_hit_rate"] > 0
    assert st["shared_pages"] > 0
    assert st["cow_copies"] >= 2
    assert {p["name"] for p in st["replicas"]} == \
        {"replica-0", "replica-1"}
    assert all("cache_hit_rate" in p for p in st["replicas"])


# -- the padded paths' waste ------------------------------------------------


def test_pad_waste_gauge_accounts_padded_path(model_and_weights):
    """The pad-waste gauge moves on the padded paths (full prefill
    and chunked rows): what a bucket costs in dead rows is counted."""
    from paddle_tpu.serving.buckets import record_pad_waste

    w0 = stat_get("prefill_padded_tokens_total")
    eng = make_engine(model_and_weights, slots=2,
                      prefix_cache=False).start()
    try:
        eng.generate([1, 2, 3, 4, 5], max_new_tokens=2)  # 5 -> bucket 8
    finally:
        eng.stop()
    assert stat_get("prefill_padded_tokens_total") - w0 >= 3
    # the gauge re-derives ppm from the cumulative counters
    record_pad_waste(1, 2)
    g = stat_get("prefill_pad_waste")
    assert 0 < g < 1_000_000
    assert eng.stats()["prefill_pad_waste"] == pytest.approx(g / 1e6)
