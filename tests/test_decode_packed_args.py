"""The decode engine's packed arguments (PR 32).

The joint step and the whole-prompt prefill take everything after the
weights as ONE int32 host array: the memory of a numpy record array
viewed as int32, sliced back inside the jitted body.  What is pinned
here: every field survives the trip to the last bit (floats and key
words travel by bit pattern), a request's key words are those of
``jax.random.PRNGKey(seed)`` without a device dispatch, a dead slot's
row reads zeros, a dispatch uploads one array, and requests that share
a step with other sampling settings yield what they yield alone.
"""
import jax
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.serving import decode
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine, \
    TransformerLM

VOCAB = 61
ROWS = {"step": decode._step_row(8), "prefill": decode._prefill_row(16, 8)}
FIELDS = [(kind, name) for kind, row in ROWS.items() for name in row.names]


@pytest.fixture(scope="module")
def model_and_weights():
    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    return model, model.init_weights(jax.random.PRNGKey(7))


def make_engine(model_and_weights, **cfg_kw):
    model, weights = model_and_weights
    kw = dict(slots=3, max_seq_len=64, page_size=8, max_new_tokens=8)
    kw.update(cfg_kw)
    return DecodeEngine(model, weights, DecodeConfig(**kw))


def records(row, n=5):
    """``n`` records of ``row`` whose every word is a random bit
    pattern, with the values that matter at the edges set by hand."""
    rng = np.random.default_rng(0)
    words = rng.integers(-2**31, 2**31, (n, row.itemsize // 4),
                         dtype=np.int64).astype(np.int32)
    recs = words.view(row).reshape(n)
    recs["temperature"][:3] = [0.0, 1.0, np.float32(0.7)]
    recs["top_p"][:3] = [1.0, np.float32(0.9), np.nextafter(
        np.float32(1.0), np.float32(0.0))]
    recs["key"][0] = [2**31, 2**32 - 1]
    recs["key"][1] = [0, 2**31 + 5]
    return recs


@pytest.mark.parametrize("kind,name", FIELDS,
                         ids=[f"{k}.{n}" for k, n in FIELDS])
def test_field_survives_pack_and_unpack_to_the_last_bit(kind, name):
    row = ROWS[kind]
    recs = records(row)
    words = decode._words(recs)
    assert words.dtype == np.int32
    assert words.shape == (len(recs), row.itemsize // 4)
    assert np.shares_memory(words, recs)    # a view: nothing is cast
    got = np.asarray(jax.jit(
        lambda w: decode._unpack(w, row)[name])(words))
    want = recs[name]
    assert got.dtype == want.dtype and got.shape == want.shape
    # NaN payloads included: compare the bytes
    assert got.tobytes() == want.tobytes()


def test_one_record_unpacks_to_scalars():
    """The prefill's argument is ONE record, ``[words]``: its scalar
    fields come back 0-d, its rows 1-d."""
    row = ROWS["prefill"]
    rec = records(row)[:1]
    got = jax.jit(lambda w: decode._unpack(w, row))(decode._words(rec)[0])
    assert got["length"].shape == () and got["top_p"].shape == ()
    assert got["tokens"].shape == (16,) and got["key"].shape == (2,)
    assert np.asarray(got["top_p"]).tobytes() == rec["top_p"].tobytes()


@pytest.mark.parametrize("seed", [0, 1, 7, 12345, 2**31 - 1, 2**31,
                                  2**31 + 5, 2**32 - 1, 2**32 + 7, -1])
def test_host_key_words_are_the_prng_keys(seed):
    words = decode._key_words(seed)
    assert words.dtype == np.uint32 and words.shape == (2,)
    np.testing.assert_array_equal(
        words, np.asarray(jax.random.key_data(jax.random.PRNGKey(seed))))


def test_dead_slots_read_zeros_beside_their_table_row(model_and_weights):
    """Slot reuse: a released slot's row is today's zeros (not live,
    position 0, ``top_p`` 1) and the page row the cache holds."""
    eng = make_engine(model_and_weights).start()
    try:
        eng.submit(list(range(1, 20)), max_new_tokens=3, temperature=0.8,
                   top_k=4, top_p=0.5, seed=2**31 + 3).result(timeout=120)
    finally:
        eng.stop()
    row = eng._step_row
    recs = eng._step_args(()).view(row).reshape(-1)
    assert len(recs) == 3
    for name in row.names:
        want = {"top_p": 1.0, "pages": eng._cache.page_table}.get(name, 0)
        np.testing.assert_array_equal(recs[name], np.broadcast_to(
            want, recs[name].shape), err_msg=name)


def test_live_row_holds_the_slots_fields(model_and_weights):
    eng = make_engine(model_and_weights)
    req = decode.DecodeRequest([5, 6, 7], 4, None, 0.7, 3, 0.9,
                               2**31 + 9, None)
    st = decode._SlotState(req, decode._key_words(req.seed))
    st.last_token, st.n_generated, st.write_trash_once = 17, 2, True
    eng._slots[1] = st
    eng._cache.lengths[1] = 11
    eng._cache.page_table[1, :2] = [4, 9]
    recs = eng._step_args([1]).view(eng._step_row).reshape(-1)
    live = recs[1]
    assert (live["token"], live["position"], live["counter"]) == (17, 11, 2)
    assert live["flags"] == decode._LIVE | decode._TRASH
    assert live["key"].tolist() == [0, 2**31 + 9]
    assert live["temperature"] == np.float32(0.7)
    assert live["top_k"] == 3 and live["top_p"] == np.float32(0.9)
    assert live["pages"][:2].tolist() == [4, 9]
    st.write_trash_once = False
    assert eng._step_args([1]).view(eng._step_row)["flags"][1, 0] \
        == decode._LIVE
    assert not recs["flags"][[0, 2]].any()


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "draft"])
def test_a_dispatch_uploads_one_array(model_and_weights, spec):
    """One joint step = one upload; a whole-prompt prefill one too, the
    draft's mirror of it taking the SAME device array."""
    model, weights = model_and_weights
    kw = dict(draft_model=model, draft_weights=weights) if spec else {}
    eng = DecodeEngine(model, weights, DecodeConfig(
        slots=2, max_seq_len=64, page_size=8, prefix_cache=False,
        spec_k=2 if spec else 0), **kw).start()
    try:
        before = {n: stat_get(n) for n in (
            "decode_h2d_uploads", "decode_h2d_bytes", "decode_steps",
            "decode_prefills")}
        # speculative=False: the slot takes the normal step
        toks = eng.submit(list(range(1, 12)), max_new_tokens=4,
                          speculative=False).result(timeout=120)
    finally:
        eng.stop()
    assert len(toks) == 4
    got = {n: stat_get(n) - v for n, v in before.items()}
    assert got["decode_prefills"] == 1 and got["decode_steps"] == 3
    assert got["decode_h2d_uploads"] == 1 + 3
    pps = eng._cache.config.pages_per_slot
    assert got["decode_h2d_bytes"] == (
        decode._prefill_row(16, pps).itemsize
        + 3 * 2 * eng._step_row.itemsize)


SAMPLING = [
    dict(),                                           # greedy
    dict(temperature=0.9, seed=2**31 + 1),
    dict(temperature=1.3, top_k=5, seed=3),
    dict(temperature=0.7, top_p=0.8, seed=104),
    dict(temperature=1.0, top_k=7, top_p=0.6, seed=2**32 - 1),
]


def _mixed_requests():
    rng = np.random.default_rng(11)
    out = []
    for j in range(10):
        prompt = rng.integers(1, VOCAB, rng.integers(3, 30)).tolist()
        out.append((prompt, int(rng.integers(2, 9)),
                    SAMPLING[j % len(SAMPLING)]))
    return out


@pytest.fixture(scope="module")
def mixed_run(model_and_weights):
    """Ten requests with MIXED sampling settings through three slots
    (admissions, finishes and slot reuse in between), then each of them
    alone through a fresh engine."""
    reqs = _mixed_requests()
    eng = make_engine(model_and_weights, prefix_cache=False).start()
    try:
        handles = [eng.submit(p, max_new_tokens=n, **kw)
                   for p, n, kw in reqs]
        joint = [h.result(timeout=300) for h in handles]
    finally:
        eng.stop()
    alone = []
    eng = make_engine(model_and_weights, prefix_cache=False).start()
    try:
        for p, n, kw in reqs:
            alone.append(eng.submit(p, max_new_tokens=n,
                                    **kw).result(timeout=300))
    finally:
        eng.stop()
    return reqs, joint, alone


@pytest.mark.parametrize("j", range(10))
def test_mixed_sampling_yields_what_each_request_yields_alone(mixed_run,
                                                              j):
    reqs, joint, alone = mixed_run
    assert len(joint[j]) == reqs[j][1]
    assert joint[j] == alone[j], reqs[j][2]


def test_mixed_run_did_sample(mixed_run, model_and_weights):
    """The settings reach the sampler: run greedily, the sampled
    requests of the mix yield other tokens."""
    reqs, joint, _ = mixed_run
    eng = make_engine(model_and_weights, prefix_cache=False).start()
    try:
        greedy = [eng.submit(p, max_new_tokens=n).result(timeout=300)
                  for p, n, _kw in reqs]
    finally:
        eng.stop()
    differs = [g != t for g, t, (_p, _n, kw) in zip(greedy, joint, reqs)
               if kw]
    assert differs and sum(differs) >= len(differs) // 2
    assert all(g == t for g, t, (_p, _n, kw) in zip(greedy, joint, reqs)
               if not kw)


def test_lowering_for_a_look_uploads_nothing(model_and_weights):
    eng = make_engine(model_and_weights)
    before = stat_get("decode_h2d_uploads")
    assert "module @jit_step" in eng.lower_step().as_text()
    assert "module @jit_prefill" in eng.lower_prefill(16).as_text()
    assert stat_get("decode_h2d_uploads") == before
    # two operands after the state and the weights: the packed words,
    # and the tokens of the step before as they lie on the device
    packed, carried = eng.lower_step().args_info[0][2:]
    assert packed.shape == (3, eng._step_row.itemsize // 4)
    assert packed.dtype == np.int32
    assert (carried.shape, carried.dtype) == ((3,), np.int32)


# -- the order of an iteration ----------------------------------------------

def test_admission_follows_the_steps_upload_and_its_prefill_goes_first(
        model_and_weights, monkeypatch):
    """A request that is queued while another decodes (here from that
    one's first ``on_token``, on the engine's thread: no race) is
    admitted AFTER the joint step's arguments were built and BEFORE the
    step is handed to the device; its prefill is handed over ahead of
    that step and its first token delivered; it joins the next step,
    which is handed over BEFORE the tokens of the step in flight are
    read: the next token of a slot that was live in it never visits
    the host.  Both requests end by their budget at the step in flight,
    so no third step is built for them."""
    eng = make_engine(model_and_weights, slots=2, prefix_cache=False)
    log = []
    run, admit, deliver, step_args = (
        eng._exe.run_persistent, eng._admit_locked, eng._deliver,
        eng._step_args)

    def run_persistent(fn, *args, **kw):
        log.append("step" if fn is eng._step_fn else "prefill")
        return run(fn, *args, **kw)

    def admit_locked():
        admitted = admit()
        log.extend("admit" for _ in admitted)
        return admitted

    def deliver_token(slot, token):
        log.append(f"token{slot}")
        deliver(slot, token)

    def build_args(live_idx):
        log.append(f"args{list(live_idx)}")
        return step_args(live_idx)

    monkeypatch.setattr(eng._exe, "run_persistent", run_persistent)
    monkeypatch.setattr(eng, "_admit_locked", admit_locked)
    monkeypatch.setattr(eng, "_deliver", deliver_token)
    monkeypatch.setattr(eng, "_step_args", build_args)
    second = []

    def on_token(tok):
        if not second:
            second.append(eng.submit([3, 4, 5], max_new_tokens=2))

    eng.start()
    try:
        first = eng.submit([1, 2], max_new_tokens=3, on_token=on_token)
        assert len(first.result(timeout=120)) == 3
        assert len(second[0].result(timeout=120)) == 2
    finally:
        eng.stop()
    assert log == ["admit", "prefill", "token0",
                   "args[0]", "admit", "prefill", "step", "token1",
                   "args[0, 1]", "step", "token0",
                   "token0", "token1"]
