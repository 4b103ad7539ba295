"""The engine loop a step ahead (PR 38): a joint step is handed to the
device while the one before it is in flight, its input token taken from
that step's output on the device, and read an iteration later.

The reference is the SAME engine made to run one step at a time
(``serial``: every round says its step may not stay in flight, which is
the loop as it was): every request's tokens and recorded logits are
bitwise the serial loop's, for the three served model kinds, greedy and
sampled; an end token, a deadline reap, an abort and a failed read-back
with a step in flight hand no token to another request and leave no
write in a released slot's pages, ring or state rows; pages registered
in the prefix index while a step was in flight read as registered; a
round with a speculative slot and a chunked prefill give the tokens they
gave; ``decode_steps_ahead`` / ``decode_rows_discarded`` count what
happened; the step program compiles once.
"""
import time

import jax
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.buckets import (DeadlineExceededError,
                                        ServerClosedError)
from paddle_tpu.serving.decode import TransformerLM
from paddle_tpu.serving.hybrid_moe_lm import HybridMoELM
from paddle_tpu.serving.window_moe_lm import WindowMoELM

VOCAB = 61
KINDS = ["transformer", "hybrid", "window"]
COUNTERS = ("decode_steps", "decode_steps_ahead", "decode_rows_discarded",
            "decode_step_errors")


def build(kind):
    """(model, weights, DecodeConfig keywords) of one served kind, tiny,
    float32."""
    if kind == "transformer":
        model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                              num_heads=2, max_seq_len=128)
    elif kind == "hybrid":
        model = HybridMoELM(
            vocab_size=VOCAB, d_model=32,
            layer_kinds=("attention", "recurrent", "recurrent"),
            num_heads=4, num_kv_heads=2, head_dim=8, lin_heads=2,
            lin_head_dim=8, conv_kernel=4, gate_rank=4, num_experts=8,
            top_k=2, held_experts=(0, 1, 2, 3), expert_dim=16,
            shared_dim=16, dtype="float32")
    else:
        model = WindowMoELM(
            vocab_size=VOCAB, d_model=32,
            layer_kinds=("attention", "window", "window"), dense_layers=1,
            num_heads=8, num_kv_heads=2, window_kv_heads=4, head_dim=12,
            v_head_dim=8, rotary_dim=4, rope_theta=1e7,
            window_rope_theta=1e4, window=20, value_scale=0.707,
            dense_dim=48, num_experts=8, top_k=2,
            held_experts=(0, 1, 2, 3), expert_dim=16, dtype="float32")
    weights = model.init_weights(jax.random.PRNGKey(11))
    return model, weights, dict(slots=3, max_seq_len=128, page_size=8,
                                prefix_cache=False)


@pytest.fixture(scope="module", params=KINDS)
def served(request):
    return (request.param,) + build(request.param)


@pytest.fixture(scope="module")
def lm():
    return build("transformer")


def engine(model, weights, cfg, **over):
    draft = over.pop("draft", False)
    extra = dict(draft_model=model, draft_weights=weights) if draft else {}
    return DecodeEngine(model, weights, DecodeConfig(**dict(cfg, **over)),
                        **extra)


def serial(eng):
    """``eng`` with the loop as it was: no step stays in flight."""
    prepare = eng._prepare_decode_round
    eng._prepare_decode_round = lambda: (prepare()[0], False)
    return eng


def counters():
    return {n: stat_get(n) for n in COUNTERS}


def since(before):
    return {n: stat_get(n) - v for n, v in before.items()}


def script(seed, n=7, new=(3, 14)):
    """``n`` requests (more than slots: slots refill mid-run): greedy,
    drawn, drawn through ``top_k`` and through ``top_p``, each with its
    own seed and budget."""
    rng = np.random.RandomState(seed)
    knobs = [{}, {"temperature": 0.9}, {"temperature": 0.8, "top_k": 7},
             {"temperature": 1.1, "top_p": 0.8},
             {"temperature": 0.7, "top_k": 12, "top_p": 0.9}]
    return [(rng.randint(1, VOCAB, rng.randint(3, 30)).tolist(),
             dict(knobs[j % len(knobs)], seed=100 + j,
                  max_new_tokens=int(rng.randint(*new))))
            for j in range(n)]


def run(eng, requests, **kw):
    """Everything queued before the loop starts (the admission order is
    the script's), then every reply: [(tokens, logits)]."""
    reqs = [eng.submit(p, record_logits=True, **dict(k, **kw))
            for p, k in requests]
    with eng:
        return [(r.result(timeout=300), np.stack(r.logits_trace))
                for r in reqs], reqs


def same(got, want):
    assert [t for t, _ in got] == [t for t, _ in want]
    for (_, a), (_, b) in zip(got, want):
        assert a.shape == b.shape and (a == b).all()


# -- (a) the tokens are the serial loop's ----------------------------------

def test_tokens_and_logits_are_the_serial_loops(served):
    kind, model, weights, cfg = served
    requests = script(1)
    before = counters()
    want, _ = run(serial(engine(model, weights, cfg)), requests)
    base = since(before)
    assert base["decode_steps"] > 0 and base["decode_steps_ahead"] == 0
    before = counters()
    got, _ = run(engine(model, weights, cfg), requests)
    ahead = since(before)
    same(got, want)
    assert all(len(t) == k["max_new_tokens"]
               for (t, _), (_, k) in zip(got, requests))
    # steps were handed over behind the one before them (but behind a
    # step at which a budget ends); a budget's end costs no row
    assert ahead["decode_steps_ahead"] >= ahead["decode_steps"] * 0.3
    assert ahead["decode_rows_discarded"] == 0
    assert ahead["decode_step_errors"] == 0


def test_a_request_alone_gets_what_it_gets_in_the_batch(lm):
    """The sampler's ``counter`` and the position are computed ahead:
    a drawn request's tokens do not depend on who decodes beside it."""
    model, weights, cfg = lm
    requests = script(2, n=5)
    got, _ = run(engine(model, weights, cfg), requests)
    # one slot: the requests run one after the other, each alone
    alone, _ = run(engine(model, weights, cfg, slots=1), requests)
    same(got, alone)


# -- (b) an end token nobody saw coming -------------------------------------

def pick_eos(outs, j_min=1):
    """A token of the first reply, first met at a step (not the prefill's
    token) before its end, that the other replies never hold."""
    first, others = outs[0], set(t for o in outs[1:] for t in o)
    for j in range(j_min, len(first) - 1):
        if first[j] not in first[:j] and first[j] not in others:
            return first[j], j
    raise AssertionError(f"no usable end token in {outs}")


def cut(tokens, eos):
    return tokens[:tokens.index(eos) + 1] if eos in tokens else tokens


def device_state(eng):
    """Every state array of the stopped engine without its trash page
    (page 0 of the pools and of the window pools: dead rows aim there)."""
    slabs = set(eng._cache.recurrent_var_names())
    return {n: np.asarray(eng._scope.get_var(n))[
        (slice(None),) if n in slabs else (slice(None), slice(1, None))]
        for n in eng._state_vars}


def test_an_end_token_ends_the_request_at_its_step(served):
    """A request ends by ``eos_id`` at step N while step N+1 is in
    flight: it gets no token of N+1, that step wrote nothing of it (its
    row ran dead on the device), and the request admitted into its slot
    gets its own tokens."""
    kind, model, weights, cfg = served
    cfg = dict(cfg, slots=2)
    rng = np.random.RandomState(5)
    requests = [(rng.randint(1, VOCAB, n).tolist(),
                 {"temperature": 1.0, "seed": 40 + j, "max_new_tokens": m})
                for j, (n, m) in enumerate([(9, 14), (17, 22), (5, 9)])]
    full, _ = run(engine(model, weights, cfg), requests)
    eos, j = pick_eos([t for t, _ in full])
    want = [(cut(t, eos), logits[:len(cut(t, eos))]) for t, logits in full]
    # the slot that ends stays empty: what the device holds at the end
    # is what the steps wrote, the step in flight included
    outs, states = {}, {}
    for mode in ("serial", "ahead"):
        eng = engine(model, weights, cfg, eos_id=eos)
        before = counters()
        got, reqs = run(serial(eng) if mode == "serial" else eng,
                        requests[:2])
        outs[mode], states[mode] = got, device_state(eng)
        same(got, want[:2])
        assert len(got[0][0]) == j + 1 and reqs[0].finish_reason == "eos"
        assert reqs[1].finish_reason == "budget"
        # the step in flight when the end token was read held a row of
        # that slot: dropped at its delivery, in no other loop
        assert since(before)["decode_rows_discarded"] == (mode == "ahead")
        eng._cache.debug_check()
    # pages, rings and state rows: nothing the serial loop did not write
    for name, held in states["serial"].items():
        assert (states["ahead"][name] == held).all(), name
    # and with a request waiting for the slot: each gets its own tokens
    eng = engine(model, weights, cfg, eos_id=eos)
    got, reqs = run(eng, requests)
    same(got, want)
    eng._cache.debug_check()


# -- (c) released with a step in flight -------------------------------------

def test_a_deadline_reap_drops_the_row_in_flight(served):
    """A slot reaped by its deadline while a step that holds its row is
    in flight: the row is nobody's, the neighbour's tokens are what they
    were, and the request admitted into the slot gets its own."""
    kind, model, weights, cfg = served
    cfg = dict(cfg, slots=2)
    rng = np.random.RandomState(7)
    stalled, neighbour, heir = [
        (rng.randint(1, VOCAB, n).tolist(),
         {"temperature": 0.9, "seed": 60 + j, "max_new_tokens": m})
        for j, (n, m) in enumerate([(6, 100), (11, 60), (8, 6)])]
    want, _ = run(engine(model, weights, cfg), [neighbour, heir])

    with engine(model, weights, cfg) as eng:
        for p, _ in (stalled, neighbour):   # compiled before a deadline runs
            eng.submit(p, max_new_tokens=2).result(timeout=300)
        before = counters()
        # every step waits 20 ms in the callback: the neighbour's 60
        # tokens outlast the deadline however slow the machine is
        slow = eng.submit(stalled[0], deadline_ms=600, on_token=lambda _t:
                          time.sleep(0.02), **stalled[1])
        beside = eng.submit(neighbour[0], record_logits=True,
                            **neighbour[1])
        after = eng.submit(heir[0], record_logits=True, **heir[1])
        with pytest.raises(DeadlineExceededError):
            slow.result(timeout=300)
        got = [(r.result(timeout=300), np.stack(r.logits_trace))
               for r in (beside, after)]
    same(got, want)
    seen = since(before)
    assert 1 < len(slow.generated) < 100
    assert seen["decode_rows_discarded"] == 1
    assert seen["decode_step_errors"] == 0
    eng._cache.debug_check()


def test_an_abort_with_a_step_in_flight_fails_every_request_once(lm):
    model, weights, cfg = lm
    eng = engine(model, weights, cfg).start()
    before = counters()
    reqs = [eng.submit([1 + j, 2, 3], max_new_tokens=100,
                       on_token=lambda _t: time.sleep(0.005))
            for j in range(2)]
    while not all(r.generated for r in reqs):
        time.sleep(0.005)
    eng.stop(drain=False)
    for r in reqs:
        with pytest.raises(ServerClosedError):
            r.result(timeout=10)
        assert 0 < len(r.generated) < 100
    # the loop left nothing in flight, and what was there was dropped
    assert eng._flying is None and eng._thread is None
    assert since(before)["decode_rows_discarded"] in (0, 2)
    assert all(s is None for s in eng._slots)
    eng._cache.debug_check()


class _BrokenRead:
    """``numpy`` as the engine's module sees it, but for ``asarray`` of
    one chosen array, which raises: a step whose read-back fails while
    its tokens stay a device array the next step has already carried."""

    def __init__(self):
        self.broken = None
        self.raised = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if a is self.broken:
            self.raised += 1
            raise RuntimeError("read-back failed")
        return np.asarray(a, *args, **kw)


def test_a_failed_read_back_fails_its_batch_once(lm, monkeypatch):
    """Step 3's read-back raises while step 4 is in flight: the two
    requests in it fail with that error, step 4's rows are nobody's, the
    loop lives, and the request behind them gets its own tokens."""
    from paddle_tpu.serving import decode

    model, weights, cfg = lm
    cfg = dict(cfg, slots=2)
    reads = _BrokenRead()
    monkeypatch.setattr(decode, "np", reads)
    later = ([5, 6, 7, 8], {"max_new_tokens": 6, "temperature": 0.9,
                            "seed": 3})
    (want,), _ = run(engine(model, weights, cfg), [later])
    eng = engine(model, weights, cfg)
    step, steps = eng._exe.run_persistent, []

    def run_persistent(fn, state_vars, args, scope):
        out = step(fn, state_vars, args=args, scope=scope)
        if fn is eng._step_fn:
            steps.append(out[0])
            if len(steps) == 3:
                reads.broken = out[0]
        return out

    monkeypatch.setattr(eng._exe, "run_persistent", run_persistent)
    before = counters()
    doomed = [eng.submit([1 + j, 2, 3], max_new_tokens=20) for j in (0, 1)]
    heir = eng.submit(later[0], record_logits=True, **later[1])
    with eng:
        for r in doomed:
            with pytest.raises(RuntimeError, match="read-back failed"):
                r.result(timeout=300)
            assert len(r.generated) == 3    # the prefill's, steps 1 and 2
        got = (heir.result(timeout=300), np.stack(heir.logits_trace))
    same([got], [want])
    seen = since(before)
    assert reads.raised == 1 and seen["decode_step_errors"] == 1
    assert seen["decode_rows_discarded"] == 2   # step 4's two rows
    assert eng._thread is None and eng._flying is None
    eng._cache.debug_check()


# -- (d) pages registered while a step was in flight ------------------------

def test_a_prefix_hit_reads_what_was_registered_under_a_step_in_flight(lm):
    """A slot reaped while the step in flight still writes its next
    position: the pages it registers hold every registered position as
    written (the write in flight lands one past them), so a prompt that
    borrows them, the partial tail page included, decodes bitwise as it
    does from the pages of a request that wrote the same positions and
    ended by its budget in the serial loop, with nothing in flight."""
    model, weights, cfg = lm
    cfg = dict(cfg, slots=2, prefix_cache=True)
    rng = np.random.RandomState(9)
    prompt = rng.randint(1, VOCAB, 13).tolist()
    knobs = dict(temperature=0.9, seed=1)

    def borrow(eng, written):
        # every cut of what the first request wrote: whole pages only,
        # a partial tail page, and the very last registered position
        # (generated[-1] itself was never fed to the model)
        cuts = [16, len(written) - 4, len(written) - 1]
        hits0 = stat_get("decode_prefix_pages_hit")
        replies = []
        for n in cuts:
            r = eng.submit(written[:n], max_new_tokens=5,
                           record_logits=True)
            replies.append((r.result(timeout=300),
                            np.stack(r.logits_trace)))
        assert stat_get("decode_prefix_pages_hit") - hits0 >= \
            sum(n // 8 for n in cuts)
        return replies

    with engine(model, weights, cfg) as eng:
        # compiled before a deadline runs (other tokens: no shared page)
        eng.submit([VOCAB - 1] * 13, max_new_tokens=2).result(timeout=300)
        before = counters()
        first = eng.submit(prompt, max_new_tokens=90, deadline_ms=500,
                           on_token=lambda _t: time.sleep(0.01), **knobs)
        with pytest.raises(DeadlineExceededError):
            first.result(timeout=300)
        while eng.live_slots or eng._flying is not None:
            time.sleep(0.005)               # the loop reaps, then drains
        assert since(before)["decode_rows_discarded"] == 1
        written = prompt + first.generated
        assert 3 < len(first.generated) < 90
        got = borrow(eng, written)
    eng._cache.debug_check()
    with serial(engine(model, weights, cfg)) as eng:
        again = eng.submit(prompt, max_new_tokens=len(first.generated),
                           **knobs).result(timeout=300)
        assert again == first.generated
        same(got, borrow(eng, written))


# -- (e) the rounds that do not run ahead, and the prefills that queue -----

@pytest.mark.parametrize("path,over", [
    ("speculative", {"spec_k": 2, "draft": True, "prefix_cache": True}),
    ("chunked", {"prefill_chunk_pages": 1}),
    ("suffix", {"prefix_cache": True}),
])
def test_the_other_paths_give_the_tokens_they_gave(lm, path, over):
    model, weights, cfg = lm
    requests = script(3, n=6)
    if path == "speculative":
        # greedy requests speculate; one opts out and rides the joint
        # step beside their rounds; the drawn ones never speculate
        requests = [(p, dict({kk: v for kk, v in k.items()
                              if j % 2 or kk in ("seed", "max_new_tokens")},
                             **({"speculative": False} if j == 4 else {})))
                    for j, (p, k) in enumerate(requests)]
    if path == "suffix":
        # prompts that share whole pages with an earlier one
        head = requests[0][0][:3] * 6
        requests = [(head[:16] + p, k) for p, k in requests]
    before = counters()
    want, _ = run(serial(engine(model, weights, cfg, **over)), requests)
    assert since(before)["decode_steps_ahead"] == 0
    before = counters()
    eng = engine(model, weights, cfg, **over)
    got, _ = run(eng, requests)
    same(got, want)
    seen = since(before)
    assert seen["decode_rows_discarded"] == 0
    # a chunked or suffix prefill queues behind the step in flight
    assert seen["decode_steps_ahead"] > 0
    assert eng._step_fn._cache_size() == 1
    eng._cache.debug_check()


def test_a_round_with_a_speculative_slot_leaves_nothing_in_flight(lm):
    model, weights, cfg = lm
    eng = engine(model, weights, cfg, spec_k=2, draft=True)
    flying = []
    spec = eng._run_spec

    def run_spec(idx):
        flying.append(eng._flying)
        return spec(idx)

    eng._run_spec = run_spec
    before = counters()
    plain = eng.submit([1, 2, 3], max_new_tokens=30, speculative=False)
    with eng:
        while len(plain.generated) < 4:     # joint steps run ahead
            time.sleep(0.002)
        greedy = eng.submit([4, 5, 6, 7], max_new_tokens=12)
        greedy.result(timeout=300), plain.result(timeout=300)
    assert flying and all(f is None for f in flying)
    seen = since(before)
    # ahead before the speculative slot joined and after it left, one
    # step at a time beside it
    assert 0 < seen["decode_steps_ahead"] < seen["decode_steps"] - 3


# -- (f) the counters on a scripted run, (g) one program --------------------

def test_steps_ahead_are_the_steps_less_those_behind_an_idle_engine(lm):
    model, weights, cfg = lm
    with engine(model, weights, cfg) as eng:
        eng.submit([1, 2, 3], max_new_tokens=2).result(timeout=300)
        for new in (2, 5, 9):
            time.sleep(0.1)                 # the engine waits for work
            before = counters()
            toks = eng.submit([4, 5, 6], max_new_tokens=new).result(
                timeout=300)
            time.sleep(0.05)
            seen = since(before)
            # the prefill's token, then one joint step a token: the
            # first finds nothing in flight, every other one the step
            # before it; none is built past the budget
            assert len(toks) == new and seen["decode_steps"] == new - 1
            assert seen["decode_steps_ahead"] == new - 2
            assert seen["decode_rows_discarded"] == 0
        # ahead or not, a carried token or the host's, an end token set
        # or not: one program
        assert eng._step_fn._cache_size() == 1


def test_nothing_is_handed_over_behind_a_step_that_frees_a_slot(lm):
    """Two requests of 3 and 6 tokens side by side: the prefills' two
    tokens, then steps A B | C D E.  B is the first's last (its budget
    ends there, known at B's hand-over): it is read before C is handed
    over, so C finds nothing in flight, as A did behind the idle engine;
    B, D and E run ahead.  The one hand-over of a busy engine with
    nothing in flight is the one turnaround observed."""
    from paddle_tpu.observe.histogram import histogram

    model, weights, cfg = lm
    eng = engine(model, weights, cfg, slots=2)
    in_flight, dispatch = [], eng._dispatch_step

    def spy(*args, **kw):
        in_flight.append(eng._flying is not None)
        return dispatch(*args, **kw)

    eng._dispatch_step = spy
    turn = histogram("decode_turnaround_seconds")
    before, turns = counters(), turn.count
    got, _ = run(eng, [([1, 2, 3], {"max_new_tokens": 3}),
                       ([4, 5, 6, 7], {"max_new_tokens": 6})])
    assert [len(t) for t, _ in got] == [3, 6]
    assert in_flight == [False, True, False, True, True]
    seen = since(before)
    assert (seen["decode_steps"], seen["decode_steps_ahead"]) == (5, 3)
    assert seen["decode_rows_discarded"] == 0
    assert turn.count - turns == 1


@pytest.mark.parametrize("pinned", [False, True], ids=["default", "pinned"])
def test_the_step_compiles_once(served, pinned):
    from paddle_tpu.framework.place import TPUPlace

    kind, model, weights, cfg = served
    eng = DecodeEngine(model, weights, DecodeConfig(**cfg),
                       place=TPUPlace(0) if pinned else None)
    got, _ = run(eng, script(4, n=4))
    assert eng._step_fn._cache_size() == 1
    assert all(len(t) for t, _ in got)


def test_the_step_compiles_once_over_an_expert_parallel_mesh():
    """Weights spread over a mesh: the step's tokens come out replicated
    over it, and the operand of its first run is placed the same."""
    from jax.sharding import Mesh

    from paddle_tpu.serving.decode import shard_moe_weights

    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=64, moe_experts=4,
                          moe_mesh=mesh)
    weights = shard_moe_weights(
        model.init_weights(jax.random.PRNGKey(0)), mesh)
    eng = DecodeEngine(model, weights, DecodeConfig(max_seq_len=64, slots=2))
    before = counters()
    got, _ = run(eng, [(list(range(1, 20)), {"max_new_tokens": 8})])
    assert len(got[0][0]) == 8 and since(before)["decode_steps_ahead"] == 6
    assert eng._step_fn._cache_size() == 1
