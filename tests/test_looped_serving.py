"""The looped model (``serving/looped_lm.py``: one stack of layers every
token passes through ``loops`` times on the same weights, each pass with
K and V of its own) behind the real ``DecodeEngine``, against the plain
reference (``benchmark/reference/looped_lm.py``, the one the cell's check
uses): seeded, tiny, on the CPU."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.monitor import stat_get
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.decode import TransformerLM, cache_layers
from paddle_tpu.serving.looped_lm import LoopedLM

from benchmark.models.looped_lm import unstacked
from benchmark.reference import looped_lm as ref

VOCAB, PAGE, LAYERS, LOOPS = 97, 8, 3, 4


def make_model(**kw):
    """Ouro's block in small: heads of 16 on a stream of 32."""
    sizes = dict(vocab_size=VOCAB, d_model=32, num_layers=LAYERS,
                 loops=LOOPS, num_heads=2, head_dim=16, ffn_dim=48,
                 rope_theta=1e4, dtype="float32")
    sizes.update(kw)
    return LoopedLM(**sizes)


def dims(m):
    return dict(num_heads=m.num_heads, head_dim=m.head_dim, eps=m.rms_eps,
                rope_theta=m.rope_theta, loops=m.loops)


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=64, page_size=PAGE), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def served(eng, prompts, n_new):
    """[(tokens, logits [n_new, V])] a prompt, through the engine."""
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    return [(r.result(timeout=300), np.stack(r.logits_trace)) for r in reqs]


def worst_against_reference(model, weights, prompts, got):
    worst = 0.0
    for p, (toks, logits) in zip(prompts, got):
        want = ref.forward_logits(
            unstacked(weights), jnp.asarray(p + toks[:-1], jnp.int32),
            dims(model), rows=(len(p) - 1, len(toks)))
        worst = max(worst, float(np.abs(logits - np.asarray(want)).max()))
    return worst


def prompts_of(*lens, seed=2):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).tolist() for n in lens]


@pytest.mark.parametrize("dtype,band", [("float32", 1e-5),
                                        ("bfloat16", 0.25)])
def test_prefill_then_steps_through_the_pages_match_the_reference(dtype,
                                                                  band):
    """A prompt whose reply crosses two pages beside a short one: the
    whole-prompt prefill, then joint steps through 12 cache layers of
    pages.  float32 to 1e-5; as served (bfloat16 weights and pages,
    float32 sums) inside the band that bfloat16's 2^-8 steps leave on
    logits of size 3 after 12 layer applications, far under what a wrong
    page, pass or mask gives (the controls read above 0.5)."""
    model = make_model(dtype=dtype)
    weights = model.init_weights(jax.random.PRNGKey(1))
    prompts = prompts_of(5, 21)
    names = ("decode_loop_passes", "decode_steps", "decode_prefills")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights, cache_dtype=dtype) as eng:
        cc = eng._cache.config
        assert cc.pool_shape() == (LOOPS * LAYERS, 3 * 8 + 1, PAGE, 32)
        assert eng._cache.prefix is not None     # pages alone: the index
        got = served(eng, prompts, 14)
        assert stat_get("decode_cache_layers") == LOOPS * LAYERS
        assert stat_get("decode_kv_pool_bytes") == cc.cache_bytes() \
            == 2 * 12 * 25 * PAGE * 32 * cc.dtype.itemsize
    assert worst_against_reference(model, weights, prompts, got) < band
    d = {n: stat_get(n) - v for n, v in before.items()}
    # ``loops`` a step and a prefill
    assert d["decode_loop_passes"] == LOOPS * (
        d["decode_steps"] + d["decode_prefills"])


def test_one_loop_is_a_plain_stack():
    """``loops = 1``: a plain pass over the layers (and the final norm),
    one cache layer a weight layer."""
    model = make_model(loops=1)
    weights = model.init_weights(jax.random.PRNGKey(3))
    assert model.cache_layers == cache_layers(model) == LAYERS
    prompts = prompts_of(9)
    with engine(model, weights) as eng:
        assert eng._cache.config.num_layers == LAYERS
        got = served(eng, prompts, 6)
    assert worst_against_reference(model, weights, prompts, got) < 1e-5
    # and it is not the four-pass model's answer
    four = make_model()
    with engine(four, weights) as eng:
        other = served(eng, prompts, 1)
    assert np.abs(other[0][1][0] - got[0][1][0]).max() > 1e-2


def _unrolled(model):
    """The same model with both loops written out in Python: the pass
    and the layer are Python ints, and so is the cache layer."""
    model._passes = lambda one_pass, carry: _fold(
        one_pass, carry, range(model.loops))
    model._stack = lambda one_layer, carry, layers: _fold(
        lambda l, c: one_layer(
            l, {k: v[l] for k, v in layers.items()}, c),
        carry, range(model.num_layers))
    return model


def _fold(fn, carry, over):
    for i in over:
        carry = fn(i, carry)
    return carry


def test_the_rolled_loops_are_the_unrolled_ones_to_the_bit():
    weights = make_model().init_weights(jax.random.PRNGKey(4))
    prompts = prompts_of(7, 19, seed=5)
    runs = []
    for model in (make_model(), _unrolled(make_model())):
        with engine(model, weights) as eng:
            runs.append(served(eng, prompts, 10))
            text = eng.lower_step().as_text()
        # the loops that carry the pools: passes and layers, or none
        pool = "tensor<12x25x8x32xf32>"
        runs.append(len([ln for ln in text.splitlines()
                         if "stablehlo.while" in ln and pool in ln]))
    (rolled, n_rolled, unrolled, n_unrolled) = runs
    assert (n_rolled, n_unrolled) == (2, 0)
    for (ta, la), (tb, lb) in zip(rolled, unrolled):
        assert ta == tb and np.array_equal(la, lb)


def test_a_call_reads_its_own_cache_layer_and_no_other():
    """Every call of the step's attention is handed pools in which all
    layers but its own are NaN (the carry keeps the true ones): pass
    ``t`` of layer ``l`` reads cache layer ``t * layers + l`` alone, so
    not a bit moves; reading any other pass's pages would read NaN."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(6))
    prompts = prompts_of(11, 4, seed=7)

    def poison(eng):
        inner = eng._paged_attend

        def attend_own_layer(attention, *coords):
            def only(q, k_pages, v_pages, table, lengths, *, layer, **kw):
                own = (jnp.arange(k_pages.shape[0]) == layer)[
                    :, None, None, None]
                return attention(
                    q, jnp.where(own, k_pages, jnp.nan),
                    jnp.where(own, v_pages, jnp.nan), table, lengths,
                    layer=layer, **kw)
            return inner(only, *coords)

        eng._paged_attend = attend_own_layer

    runs = []
    for change in (None, poison):
        with engine(model, weights) as eng:
            if change:
                change(eng)
            runs.append(served(eng, prompts, 9))
    for (ta, la), (tb, lb) in zip(*runs):
        assert ta == tb and np.isfinite(lb).all() \
            and np.array_equal(la, lb)


def test_a_shared_prefix_is_found_and_all_its_cache_layers_reused():
    """The prefix index is live (no ``layer_kinds``): a second request
    with the same prompt skips its prefill, borrows the pages of all 12
    cache layers, copies the partial tail page on its first divergent
    token, and reads what the reference and the recompute oracle read."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(8))
    prompt = prompts_of(13, seed=9)[0]          # a page and a partial one
    with engine(model, weights, slots=2) as eng:
        first = eng.generate(prompt, max_new_tokens=6)
        before = {n: stat_get(n) for n in (
            "decode_prefill_skipped", "decode_cow_copies",
            "decode_prefix_pages_hit")}
        req = eng.submit(prompt, max_new_tokens=6, record_logits=True)
        again = req.result(timeout=300)
        got = [(again, np.stack(req.logits_trace))]
        for t in range(len(again)):
            oracle = eng.recompute_logits(prompt + again[:t])
            assert np.array_equal(oracle, req.logits_trace[t]), t
    assert again == first
    assert stat_get("decode_prefill_skipped") == \
        before["decode_prefill_skipped"] + 1
    assert stat_get("decode_cow_copies") == before["decode_cow_copies"] + 1
    assert stat_get("decode_prefix_pages_hit") \
        - before["decode_prefix_pages_hit"] == 2
    assert worst_against_reference(model, weights, [prompt], got) < 1e-5
    eng._cache.debug_check()


def test_a_request_cut_short_resumes_where_it_stopped():
    """A request reaped at its deadline mid-decode frees its slot; sent
    again with what it had produced, it goes on to the tokens the
    uninterrupted request gives."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(10))
    prompt = prompts_of(10, seed=11)[0]
    with engine(model, weights, slots=1) as eng:
        whole = eng.generate(prompt, max_new_tokens=40)
        # the sleep paces the engine's thread: 150 ms a token against a
        # deadline of 2 s (40 tokens cannot fit; a first one does, on a
        # machine as loaded as the tier-1 run's)
        slow = eng.submit(prompt, max_new_tokens=40, deadline_ms=2000,
                          on_token=lambda t: time.sleep(0.15))
        with pytest.raises(serving.DeadlineExceededError):
            slow.result(timeout=60)
        had = list(slow.generated)
        assert 0 < len(had) < 40 and had == whole[:len(had)]
        rest = eng.generate(prompt + had, max_new_tokens=40 - len(had))
        assert eng.free_slots == 1
    assert had + rest == whole
    eng._cache.debug_check()


def test_without_cache_layers_the_pools_are_todays():
    """A model that declares no ``cache_layers`` gets one cache layer a
    weight layer: the pools' shapes are what they were."""
    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    assert not hasattr(model, "cache_layers") and cache_layers(model) == 2
    weights = model.init_weights(jax.random.PRNGKey(12))
    eng = engine(model, weights)
    assert eng._cache.config.pool_shape() == (2, 3 * 8 + 1, PAGE, 32)
    assert eng._tallies == () and eng._prefill_tallies == ()


def test_the_lowered_step_holds_one_loop_over_the_passes_and_one_kernel():
    """At heads of whole lanes, lowered for the chip: the step's text
    has the loop over the passes and the loop over the layers inside it
    (the only ``while``s that carry the pool) and ONE paged-attention
    kernel call for all 12 cache layers, its layer a traced scalar."""
    model = make_model(d_model=128, num_heads=1, head_dim=128, ffn_dim=128)
    weights = model.init_weights(jax.random.PRNGKey(13))
    eng = engine(model, weights, use_pallas="always")
    args = (tuple(eng._scope.get_var(n) for n in eng._state_vars),
            eng.weights, eng._step_args(()), eng._no_tokens)
    text = eng._step_fn.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert 1 <= text.count("@tpu_custom_call") <= model.num_layers
    # ONE K/V head of 128 lanes: K and V of a position share a row of the
    # one pool the loops carry (``CacheConfig.joint``)
    assert eng._cache.config.joint and len(eng._state_vars) == 1
    pool = "tensor<12x25x8x256xf32>"
    whiles = [ln for ln in text.splitlines()
              if "stablehlo.while" in ln and pool in ln]
    assert len(whiles) == 2, whiles


# sha256 of the lowered text of ``make_model()``'s joint step and 8-row
# whole-prompt prefill (the smallest bucket) behind ``engine()``, as
# PR 58's tree lowers them (taken on that commit, before the blocks this
# model shares with others moved out of the others' files: PR 59)
PROGRAMS_AS_LOWERED = {
    "step": "fd488ac01ad625e3a27745e473d86e05d5cc671a41a31b5782d579409bc131fc",
    "prefill":
        "d4866e965376e1d5c86721f42d8b9a881f00c1a0129d8df874531890bf4eca9d"}


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_the_programs_are_still_the_ones_lowered_before_the_block_library(
        program):
    """The two matmul feeds, the norm and the half-split rotary pairing
    are ``serving/blocks.py``'s, functions of what they read; this model
    borrows no other model's methods any more (PR 59).  Where they are
    written moves no line of what they lower to: the joint step and the
    whole-prompt prefill are the text they were.  A change MEANT to move
    these programs replaces the digests; one that was not has found out
    here."""
    import hashlib

    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(1))
    eng = engine(model, weights)
    text = (eng.lower_step() if program == "step"
            else eng.lower_prefill(8)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PROGRAMS_AS_LOWERED[program]
