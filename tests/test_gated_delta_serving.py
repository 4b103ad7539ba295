"""The dense hybrid model (``serving/gated_delta_lm.py``: Gated DeltaNet
layers with a scalar decay a head and keys narrower than values, beside
position-free softmax layers with QK-norm and one K/V head a query head)
behind the real ``DecodeEngine``, against the plain reference
(``benchmark/reference/gated_delta_lm.py``, the one the cell's check
uses): float32, seeded, tiny, with widths that keep ``dk != dv`` and
neither a multiple of the other's tile."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.serving import DecodeConfig, DecodeEngine, GatedDeltaLM
from paddle_tpu.serving import gated_delta_lm as gdl

from benchmark.reference import gated_delta_lm as ref

PERIOD = ("recurrent", "recurrent", "recurrent", "attention")
VOCAB = 97
PAD = 192     # the longest prompt and its reply fit


def make_model(kinds=PERIOD * 2, **kw):
    sizes = dict(vocab_size=VOCAB, d_model=32, layer_kinds=kinds,
                 num_heads=3, head_dim=8, lin_heads=3, lin_key_dim=6,
                 lin_value_dim=12, conv_kernel=4, ffn_dim=40,
                 dtype="float32")
    sizes.update(kw)
    return GatedDeltaLM(**sizes)


def dims(m):
    return dict(num_heads=m.num_heads, head_dim=m.head_dim,
                lin_heads=m.lin_heads, lin_key_dim=m.lin_key_dim,
                lin_value_dim=m.lin_value_dim, conv_kernel=m.conv_kernel,
                eps=m.rms_eps, kinds=list(m.layer_kinds), row_block=16)


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=256, page_size=8), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def served_vs_reference(eng, model, weights, prompts, n_new=5):
    """Worst |dlogit| over the prompts' prefill and decode positions,
    the reference given the server's own tokens."""
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = 0.0
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=300)
        got = np.stack(r.logits_trace)
        # the reference is causal: rows past the sequence only cost time,
        # and ONE padded length is one trace of its scan
        seq = p + toks[:-1]
        want = ref.forward_logits(weights, jnp.asarray(
            seq + [0] * (PAD - len(seq)), jnp.int32), dims(model))
        assert got.shape == (n_new, VOCAB)
        worst = max(worst, float(np.abs(
            got - np.asarray(want)[len(p) - 1:len(seq)]).max()))
    return worst


@pytest.mark.parametrize("kinds", [("attention",), ("recurrent",), PERIOD * 2],
                         ids=["softmax", "gdn", "two_periods"])
def test_prefill_then_decode_matches_the_reference(kinds):
    """Prompts shorter than a chunk, of a whole number of chunks, and of
    chunks and a rest: prefill in chunks of 64, then steps through pages
    and slabs.  Eight layers of float32 rounding read up to 1e-4 (the
    token form alone reads 6e-5); a departure reads above 1e-2."""
    model = make_model(kinds)
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 128, 71)]
    with engine(model, weights) as eng:
        assert served_vs_reference(eng, model, weights, prompts) < (
            2e-4 if len(kinds) > 1 else 5e-5)


def test_paged_kernel_serves_one_row_a_kv_head_in_interpret_mode():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(3))
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (100, 70)]
    with engine(model, weights, use_pallas="always", interpret=True) as eng:
        assert served_vs_reference(eng, model, weights, prompts, 4) < 5e-5


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 2e-5),
                                         (jnp.bfloat16, 2e-2)],
                         ids=["f32_pool", "bf16_pool"])
def test_ungrouped_kernel_against_the_reference_in_interpret_mode(dtype,
                                                                  atol):
    """As many K/V heads as query heads (a group of ONE row a K/V head),
    at a float32 and at a bfloat16 pool: the case the grouped kernel's
    row stacking was never tuned for."""
    s, h, d, page, pps, layers = 3, 6, 8, 8, 4, 2
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(s, h, d), jnp.float32)
    k_pages, v_pages = (jnp.asarray(rng.randn(layers, 16, page, h * d),
                                    dtype) for _ in range(2))
    table = jnp.asarray(rng.permutation(np.arange(1, 13)).reshape(s, pps),
                        jnp.int32)
    lengths = jnp.asarray([5, 32, 17], jnp.int32)
    got = pda.paged_decode_attention(
        q, k_pages, v_pages, table, lengths, layer=1, use_pallas="always",
        interpret=True)
    full = [p[1][table].reshape(s, pps * page, h, d).astype(jnp.float32)
            for p in (k_pages, v_pages)]
    want = pda.decode_attention_reference(q, *full, lengths)
    np.testing.assert_allclose(got, want, atol=atol)


def test_a_slots_second_request_sees_none_of_the_firsts_state():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    with engine(model, weights, slots=1) as eng:
        for n in (80, 65, 127):     # one slot: each reuses the last's rows
            p = [rng.randint(0, VOCAB, n).tolist()]
            assert served_vs_reference(eng, model, weights, p) < 5e-5


def _state_after_prefill(model, weights, prompt, page_size):
    with engine(model, weights, slots=2, page_size=page_size) as eng:
        eng.submit([1, 2, 3], max_new_tokens=1).result(timeout=300)
        eng.submit(prompt, max_new_tokens=1).result(timeout=300)
        names = eng._cache.recurrent_var_names()
        return {n: np.asarray(eng._scope.get_var(n)) for n in names}


@pytest.mark.parametrize("wide", [64, 256],
                         ids=["a_chunks_rest", "whole_chunks_of_a_group"])
def test_padding_rows_leave_the_state_alone(wide):
    """The same 70-token prompt (a chunk and six tokens of the next)
    prefilled in a bucket of 72 and in one of 128 or of 256: what the
    slot's rows hold is the state after token 70, however many padding
    rows the second chunk carried, and whether or not the call's group
    goes on for two more chunks that are padding alone (a bucket of 256:
    ONE call of four chunks)."""
    model = make_model(("recurrent", "attention"))
    assert model.prefill_chunks_per_call(72) == 2 \
        and model.prefill_chunks_per_call(wide) == wide // 64
    weights = model.init_weights(jax.random.PRNGKey(7))
    prompt = np.random.RandomState(8).randint(0, VOCAB, 70).tolist()
    a = _state_after_prefill(model, weights, prompt, 8)
    b = _state_after_prefill(model, weights, prompt, wide)
    assert set(a) == set(b) and len(a) == 2
    for name in a:
        assert np.abs(a[name][0]).max() > 0      # slot 0 was written
        np.testing.assert_allclose(a[name][0], b[name][0], atol=1e-6)
        assert not a[name][1].any()              # slot 1 never was


# -- the chunk form against the token form ---------------------------------

def _rows(model, lw, n, seed, scale=1.0, same_key=False):
    """Seeded projections of ``n`` tokens as ``_gdn`` would hand them."""
    rng = np.random.RandomState(seed)
    x = rng.randn(1 if same_key else n, model.d_model).astype(np.float32)
    x = jnp.asarray(np.broadcast_to(x, (n, model.d_model)) * scale)
    return {"u": x @ lw["gdn_wqkv"], "a": x @ lw["gdn_wa"],
            "b": x @ lw["gdn_wb"]}


def _state0(model, seed):
    """A slot's state: zero (``seed`` None) or seeded and non-zero."""
    shapes = {n: (1,) + tuple(s) for n, (s, _) in
              model.recurrent_state.items()}
    if seed is None:
        return {n: jnp.zeros(s, jnp.float32) for n, s in shapes.items()}
    rng = np.random.RandomState(seed)
    return {n: jnp.asarray(rng.randn(*s), jnp.float32)
            for n, s in shapes.items()}


@functools.lru_cache(maxsize=None)
def _jitted(model):
    """(token scan, one chunk) of ``model`` taking the layer's weights
    as an argument: traced once a shape for the whole file."""
    def token(lw, state, row):
        o, state = model._gdn_token(
            lw, {k: v[None] for k, v in row.items()}, state)
        return state, o[0]

    scan = jax.jit(lambda lw, st, r: jax.lax.scan(
        functools.partial(token, lw), st, r))
    return scan, jax.jit(model._gdn_chunk)


def _by_tokens(model, lw, rows, n, state):
    state, outs = _jitted(model)[0](
        lw, state, {k: v[:n] for k, v in rows.items()})
    return outs, state


def _by_chunks(model, lw, rows, n, state, pad_with=None, group=1):
    """``n`` real tokens in calls of ``group`` chunks of ``CHUNK``; the
    rows past them are ``pad_with`` (seeded noise: a padding row must
    weigh nothing whatever it holds)."""
    c = group * gdl.CHUNK
    total = -(-n // c) * c
    rng = np.random.RandomState(0 if pad_with is None else pad_with)
    padded = {k: jnp.concatenate([v[:n], jnp.asarray(
        rng.randn(total - n, v.shape[1]) * (pad_with is not None),
        jnp.float32)]) for k, v in rows.items()}
    outs = []
    for i in range(0, total, c):
        o, state = _jitted(model)[1](
            lw, {k: v[i:i + c] for k, v in padded.items()},
            jnp.int32(min(n - i, c)), state)
        outs.append(o)
    return jnp.concatenate(outs)[:n], state


@functools.lru_cache(maxsize=None)
def _one_layer(seed):
    model = make_model(("recurrent",))
    return model, model.init_weights(jax.random.PRNGKey(seed))["layers"][0]


# a call of ONE chunk, then of a group of four: a prompt shorter than a
# chunk, exactly one group, a group and a part of the next, a last group
# whose last live chunk is partial and whose other two are padding alone
LENGTHS = [(n, 1) for n in (64, 128, 1, 63, 65, 150)] + [
    (40, 4), (256, 4), (300, 4), (326, 4), (150, 2)]


@pytest.mark.parametrize("n, group", LENGTHS, ids=[
    f"{n}_tokens" + (f"_by_{g}" if g > 1 else "") for n, g in LENGTHS])
@pytest.mark.parametrize("initial", [None, 21], ids=["zero", "nonzero"])
def test_the_chunk_form_is_the_token_form(n, group, initial):
    """From a zero and from a non-zero state (matrix AND convolution
    tail), at lengths that are and are not multiples of 64, one chunk a
    call and a group of chunks a call, with noise in the padding rows:
    outputs and the final state agree to 2e-5 (float32 on both sides;
    the state's entries are O(1))."""
    model, lw = _one_layer(9)
    rows = _rows(model, lw, n, seed=10)
    want_o, want_s = _by_tokens(model, lw, rows, n, _state0(model, initial))
    got_o, got_s = _by_chunks(model, lw, rows, n, _state0(model, initial),
                              pad_with=11, group=group)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    for name in want_s:
        np.testing.assert_allclose(got_s[name], want_s[name], atol=2e-5)


@pytest.mark.parametrize("group", [1, 2], ids=["a_chunk", "a_group_of_2"])
def test_a_negative_eigenvalue_survives_the_chunk_form(group):
    """Every token the SAME key, written at ``beta`` close to 2 and
    hardly decayed: ``I - beta k k^T`` has the eigenvalue ``1 - beta``
    near -1 along k, so what the state holds along k flips its sign
    token after token instead of fading.  The solve's matrix then has
    ``A_tj`` near 2 for every ``j < t`` (its powers grow like ``2^n
    C(64, n)``, far beyond float32), and the chunk form still gives the
    token form's outputs and state."""
    model, lw = _one_layer(9)
    n = 128
    rows = _rows(model, lw, n, seed=13, same_key=True)
    rows["b"] = jnp.full_like(rows["b"], 4.0)            # beta = 1.964
    rows["a"] = jnp.full_like(rows["a"], -6.0)           # alpha near 1
    beta = float(model._beta(rows["b"])[0, 0])
    assert 1.9 < beta < 2.0
    want_o, want_s = _by_tokens(model, lw, rows, n, _state0(model, 14))
    got_o, got_s = _by_chunks(model, lw, rows, n, _state0(model, 14),
                              group=group)
    assert np.isfinite(np.asarray(got_o)).all()
    # what lies along k neither fades nor is forgotten: it keeps the
    # rounding of all 128 tokens (6.5e-5 read; the entries are O(1))
    np.testing.assert_allclose(got_o, want_o, atol=2e-4)
    np.testing.assert_allclose(got_s["s"], want_s["s"], atol=2e-4)


def test_the_unit_lower_inverse_inverts():
    rng = np.random.RandomState(15)
    a = jnp.asarray(np.tril(rng.randn(2, 3, 64, 64), -1), jnp.float32)
    inv = gdl._unit_lower_inverse(a)
    eye = np.eye(64, dtype=np.float32)
    want = np.linalg.inv(np.asarray(a, np.float64) + eye)
    np.testing.assert_allclose(inv, want, rtol=1e-3, atol=1e-3 * np.abs(
        want).max())
    with pytest.raises(ValueError, match="power of two"):
        gdl._unit_lower_inverse(a[..., :48, :48])


# -- the engine's side ------------------------------------------------------

@pytest.mark.parametrize("cfg, names", [
    (dict(prefill_chunk_pages=1), "chunked prefill"),
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_quant=True), "kv_quant"),
], ids=["chunked", "speculative", "kv_quant"])
def test_what_cannot_carry_recurrent_state_refuses_by_name(cfg, names):
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(14))
    with pytest.raises(ValueError, match="recurrent layers.*" + names):
        engine(model, weights, **cfg)


def test_a_draft_model_and_the_disaggregated_hand_over_refuse():
    from paddle_tpu.serving.decode import TransformerLM
    from paddle_tpu.serving.disagg import DisaggServer

    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(15))
    draft = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=64)
    with pytest.raises(ValueError, match="recurrent.*speculative decoding"):
        DecodeEngine(model, weights, DecodeConfig(
            slots=2, max_seq_len=64, page_size=8), draft_model=draft,
            draft_weights=draft.init_weights(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="disaggregated"):
        DisaggServer(model, weights, config=DecodeConfig(
            slots=2, max_seq_len=64, page_size=8))
    eng = engine(model, weights)
    with pytest.raises(ValueError, match="extract_kv"):
        eng.submit([1, 2, 3], max_new_tokens=2, extract_kv=True)


def test_a_slots_pages_and_slab_rows_are_admitted_and_released_together():
    """Two slots, pages for one long request and a short one: while a
    request lives its slot owns pages AND its rows of the slabs hold its
    state; when it ends the pages go back, and the slot's next request
    starts from zero rows (the answer says so: it is the reference's)."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(18))
    rng = np.random.RandomState(19)
    with engine(model, weights, slots=2, max_seq_len=128) as eng:
        cache = eng._cache
        free0 = cache.allocator.num_free
        assert eng._cache.prefix is None
        long_p = rng.randint(0, VOCAB, 90).tolist()
        r = eng.submit(long_p, max_new_tokens=20, record_logits=True)
        r.result(timeout=300)
        # released: every page is free again, nothing leaked
        deadline = 0
        while cache.allocator.num_free != free0 and deadline < 200:
            deadline += 1
            import time
            time.sleep(0.01)
        assert cache.allocator.num_free == free0
        names = cache.recurrent_var_names()
        assert len(names) == 2 * 3
        # the rows still hold the finished request's state (nobody reads
        # them): the next request in that slot must not see it
        assert any(np.abs(np.asarray(eng._scope.get_var(n))).max() > 0
                   for n in names)
        p = [rng.randint(0, VOCAB, 70).tolist(),
             rng.randint(0, VOCAB, 66).tolist()]
        assert served_vs_reference(eng, model, weights, p) < 5e-5
        assert cache.state_bytes() == stat_get("decode_state_bytes") == 2 * (
            3 * (3 * 6 * 12 + 3 * 3 * (6 + 6 + 12)) * 4)
    eng._cache.debug_check()


def test_the_prompt_is_scanned_in_chunks_and_the_counts_ride_the_sync():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(16))
    names = ("decode_prefill_scan_steps", "decode_prefill_scan_tokens",
             "decode_prefix_bypassed", "decode_prefills",
             "decode_h2d_uploads", "decode_steps")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights) as eng:
        assert eng._tallies == model.tallies == ()
        for n in (100, 64, 127):
            eng.submit(list(range(1, n + 1)) if n < VOCAB else
                       [i % VOCAB for i in range(n)],
                       max_new_tokens=3).result(timeout=300)
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_prefills"] == d["decode_prefix_bypassed"] == 3
    # three recurrent layers; 2 + 1 + 2 chunks a layer
    assert d["decode_prefill_scan_steps"] == 3 * (2 + 1 + 2)
    assert d["decode_prefill_scan_tokens"] == 3 * (100 + 64 + 127)
    # one upload a step and one a prefill, as for any model
    assert d["decode_h2d_uploads"] == d["decode_steps"] + 3


def test_a_model_that_hands_no_chunk_form_is_scanned_token_by_token():
    """The same model with ``chunk_fn`` taken out of what it hands
    ``attend.recur``: the prefill's loop runs once a token, the answers
    are the same."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(17))
    prompt = np.random.RandomState(20).randint(0, VOCAB, 70).tolist()
    with engine(model, weights) as eng:
        want = eng.submit(prompt, max_new_tokens=4,
                          record_logits=True)
        want.result(timeout=300)

    class TokenOnly(GatedDeltaLM):
        def _gdn(self, l, lw, x, cache, attend):
            recur = attend.recur
            attend.recur = lambda l, fn, rows, cache, **kw: recur(
                l, fn, rows, cache)
            try:
                return super()._gdn(l, lw, x, cache, attend)
            finally:
                attend.recur = recur

    slow = make_model(PERIOD)
    slow.__class__ = TokenOnly
    before = {n: stat_get(n) for n in ("decode_prefill_scan_steps",
                                       "decode_prefill_scan_tokens")}
    with engine(slow, weights) as eng:
        got = eng.submit(prompt, max_new_tokens=4, record_logits=True)
        got.result(timeout=300)
    assert stat_get("decode_prefill_scan_steps") - before[
        "decode_prefill_scan_steps"] == 3 * 70
    assert stat_get("decode_prefill_scan_tokens") - before[
        "decode_prefill_scan_tokens"] == 3 * 70
    np.testing.assert_allclose(np.stack(got.logits_trace),
                               np.stack(want.logits_trace), atol=5e-5)


@pytest.mark.parametrize("departure", ["beta_without_the_2", "no_qk_norm",
                                       "no_decay", "tail_dropped"])
def test_the_check_would_see_a_departure(departure):
    """What the cell's controls change, at the small size: each moves the
    logits far beyond the agreement with the reference."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(22))
    if departure == "beta_without_the_2":
        model._beta = lambda b: jax.nn.sigmoid(b)
    elif departure == "no_qk_norm":
        model._qk_norm = lambda x, g: x
    elif departure == "no_decay":
        model._log_decay = lambda lw, a: jnp.zeros_like(a)
    else:
        chunk = model._gdn_chunk

        def dropped(lw, rows, n_real, state):
            o, new = chunk(lw, rows, n_real, state)
            return o, dict(new, tail=jnp.zeros_like(new["tail"]))

        model._gdn_chunk = dropped
    prompts = [np.random.RandomState(23).randint(0, VOCAB, 70).tolist()]
    with engine(model, weights) as eng:
        assert served_vs_reference(eng, model, weights, prompts) > 1e-2


def test_the_group_is_read_from_the_calls_shapes():
    """``prefill_chunks_per_call``: the bucket's chunks, capped by what
    ``GROUP_BYTES`` of a group's temporaries allow.  Olmo-Hybrid's
    widths (30 heads, 96 on 192: 13.1 MB a chunk) take four chunks a
    call at every bucket from 256 rows up; the toy widths the whole
    bucket; a model without a chunk form reads 0 on the gauge."""
    from paddle_tpu.serving.decode import TransformerLM

    toy = make_model(PERIOD)
    assert [toy.prefill_chunks_per_call(r) for r in (8, 64, 72, 128, 256)] \
        == [1, 1, 2, 2, 4]
    wide = make_model(PERIOD, lin_heads=30, lin_key_dim=96,
                      lin_value_dim=192)
    assert [wide.prefill_chunks_per_call(r)
            for r in (64, 128, 256, 4096, 5632)] == [1, 2, 4, 4, 4]
    assert gdl.GROUP_BYTES // (4 * 30 * (4 * 64 * 64 + 64 * (
        5 * 96 + 4 * 192) + 96 * 192)) == 4
    plain = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=64)
    DecodeEngine(plain, plain.init_weights(jax.random.PRNGKey(0)),
                 DecodeConfig(slots=2, max_seq_len=64, page_size=8))
    assert stat_get("decode_prefill_chunks_per_call") == 0


def _loops_of_main(text):
    """[(header, body lines)] of the ``stablehlo.while`` operations that
    the program's ``main`` holds itself (a header prints its carry's
    types; its regions end at the next ``}`` of its own indentation)."""
    lines, loops, i = text.splitlines(), [], 0
    while i < len(lines):
        if lines[i].startswith("    %") and "stablehlo.while(" in lines[i]:
            end = lines.index("    }", i)
            loops.append((lines[i], lines[i + 1:end]))
            i = end
        i += 1
    return loops


def test_a_recurrent_layer_of_the_prompt_is_one_loop_that_holds_the_group():
    """What the benchmark's readers find a recurrent layer's chunk form
    by (``benchmark/layer_metrics/gdn_prefill_*.json``: the ``while``
    whose carry holds ``f32[1, heads, d_k, d_v]``, which spans its
    body): the 256-row prefill of a model with two recurrent layers
    holds exactly one such loop a layer, the state's pass through a
    call's four chunks is no loop of its own, and the products of the
    whole group that read no state (``Q K^T`` over ``K K^T`` of ``[4,
    H, 128, 64]``, the rounds of the triangular inverse, ``T [beta V |
    beta e^G K]``) lie inside it and nowhere else."""
    model = make_model(("recurrent", "attention", "recurrent"))
    group = model.prefill_chunks_per_call(256)
    assert group == 4
    with engine(model, model.init_weights(jax.random.PRNGKey(1))) as eng:
        assert stat_get("decode_prefill_chunks_per_call") == group
        text = eng.lower_prefill(256).as_text()
    main = text[:text.index("func.func private")]
    state = "tensor<1x3x6x12xf32>"
    loops = [(head, body) for head, body in _loops_of_main(main)
             if state in head]
    assert len(loops) == 2
    inside = 0
    for head, body in loops:
        assert not [ln for ln in body if "stablehlo.while" in ln]
        products = [ln for ln in body if "stablehlo.dot_general" in ln]
        assert all("precision = [HIGHEST, HIGHEST]" in ln
                   for ln in products)
        shapes = [ln.rsplit("-> ", 1)[1] for ln in products]
        assert shapes.count(f"tensor<{group}x3x128x64xf32>") == 1
        assert shapes.count(f"tensor<{group}x3x64x18xf32>") == 1
        # the inverse from blocks of 16: one round of whole matrices and
        # the last round's one block, two products each; the state's
        # pass, two a chunk, on operands of ONE chunk; the outputs' two
        assert shapes.count(f"tensor<{group}x3x64x64xf32>") == 2
        assert shapes.count(f"tensor<{group}x3x32x32xf32>") == 2
        assert shapes.count("tensor<3x64x12xf32>") \
            == shapes.count("tensor<3x6x12xf32>") == group
        assert len(products) == 1 + 4 + 1 + 2 * group + 2
        inside += len(products)
    # every product at ``highest`` in the program is the chunk form's
    assert main.count("precision = [HIGHEST, HIGHEST]") == inside
    outside = set(main.splitlines()) - {
        ln for _, body in loops for ln in body}
    assert not [ln for ln in outside if "x64x64xf32>" in ln]


# sha256 of the lowered text of ``make_model()``'s joint step and
# 128-row whole-prompt prefill behind ``engine()``: the step as PR 46's
# tree lowers it, the prefill since its head forms the one row the
# engine reads (PR 62; until then as PR 53 left it, its chunk form a
# group of chunks a call and the head over all 128 rows)
PROGRAMS_AS_LOWERED = {
    "step": "93ad8ee9930b9c80b7f7980246e89a35d1b3df4b9a69cbc6688e0aa3435f8573",
    "prefill":
        "25ed6649e15f0b32d6dbe973519ca6649c114cebb8d8fa9994728c140de1a604"}


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_the_programs_are_still_the_ones_lowered_before_the_kernel(program):
    """This model's one-token update takes no ``live``: the engine keeps
    masking its dead rows (``where`` over every slab) and hands its
    update nothing new, so the joint step lowers to the text it had
    before ``HybridMoELM``'s update moved into a kernel (PR 47), and the
    whole-prompt prefill to the text PR 53 gave it (its chunk form a
    group of chunks a call) but for the head, which PR 62 cut to the row
    the engine reads.  A change MEANT to move these programs
    replaces the digests; one that was not has found out here."""
    import hashlib

    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(1))
    eng = engine(model, weights)
    text = (eng.lower_step() if program == "step"
            else eng.lower_prefill(128)).as_text()
    slab = "tensor<3x3x6x12xf32>"
    if program == "step":
        # six recurrent layers, each slab masked by the engine
        assert len([ln for ln in text.splitlines() if "call @_where" in ln
                    and slab in ln.split("->")[-1]]) == 6
    assert "tpu_custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PROGRAMS_AS_LOWERED[program]


@pytest.mark.parametrize("length, bucket", [(11, 16), (37, 64)])
def test_a_prompts_head_forms_the_one_row_the_engine_reads(
        length, bucket, monkeypatch):
    """The whole-prompt prefill names the row it reads and the model
    hands back ``[1, V]`` (``blocks.head_logits``, PR 62): the head over
    a 4,096-row bucket of the cell was 22.8 of a prefill's 145 ms.  Rows
    inside a short and a longer bucket: tokens and recorded logits are
    those of the form that made every row's; the joint step makes every
    slot's as ever."""
    import sys

    from prompt_head_forms import the_read_row_is_the_every_row_forms

    the_read_row_is_the_every_row_forms(
        sys.modules[__name__], length, bucket, monkeypatch)
