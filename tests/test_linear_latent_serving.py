"""The model of channel-decay delta-rule layers and position-free latent
attention layers over ONE cache (``serving/linear_latent_lm.py``: state
slabs beside latent pages) behind the real ``DecodeEngine``, against the
plain reference (``benchmark/reference/linear_latent_lm.py``, the one
the cell's check uses): float32, seeded, tiny."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.ops import pallas_prompt_attention as ppa
from paddle_tpu.serving import (CacheConfig, DecodeConfig, DecodeEngine,
                                PagedKVCache)
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving import HybridMoELM, mixers
from paddle_tpu.serving.kv_cache import RecurrentSpec
from paddle_tpu.serving.linear_latent_lm import LinearLatentLM

import test_hybrid_moe_serving as solar   # the chunk form's helpers
from benchmark.reference import linear_latent_lm as ref
from benchmark.tests.linear_latent_controls import CONTROLS, REWEIGH

VOCAB, PAGE = 97, 8
PERIOD = ("recurrent", "recurrent", "recurrent", "attention")


def make_model(kinds=PERIOD + ("recurrent", "attention"),
               held=(0, 1, 2, 3, 4), **kw):
    """Kimi-Linear's first layers in small: a leading dense layer under
    a RECURRENT mixer, a whole period and a short one; a cached row of
    16 + 8 lanes under 4 heads, a state of 2 heads of 8 x 8."""
    sizes = dict(vocab_size=VOCAB, d_model=32, layer_kinds=kinds,
                 dense_layers=1, lin_heads=2, lin_head_dim=8, conv_kernel=4,
                 gate_rank=8, num_heads=4, kv_rank=16, nope_dim=8,
                 rope_dim=8, v_dim=8, dense_dim=48, num_experts=16, top_k=4,
                 held_experts=held, expert_dim=16, shared_dim=16,
                 routed_scale=2.446, dtype="float32")
    sizes.update(kw)
    return LinearLatentLM(**sizes)


def dims(m, held=None):
    return dict(kinds=list(m.layer_kinds), dense_layers=m.dense_layers,
                lin_heads=m.lin_heads, lin_head_dim=m.lin_head_dim,
                conv_kernel=m.conv_kernel, num_heads=m.num_heads,
                nope_dim=m.nope_dim, rope_dim=m.rope_dim, kv_rank=m.kv_rank,
                top_k=m.top_k, held=list(held or m.held_experts),
                expert_dim=m.expert_dim, routed_scale=m.routed_scale,
                eps=m.rms_eps)


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=256, page_size=PAGE), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def expert_layers(model):
    return model.num_layers - model.dense_layers


def checked(model, weights, prompt, req, n_new):
    """Worst |dlogit| of one finished request over its prefill and
    decode positions, the reference following the recorded routing
    (which it finds to be its own)."""
    toks = req.result(timeout=600)
    got = np.stack(req.logits_trace)
    assert got.shape == (n_new, VOCAB)
    routed = req.records["moe_topk"]
    ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
    assert ids.shape == (len(prompt) + n_new - 1, expert_layers(model),
                         model.top_k)
    want, gap = ref.forward_logits(
        weights, jnp.asarray(prompt + toks[:-1], jnp.int32), dims(model),
        routing=jnp.asarray(ids))
    assert float(gap.max()) == 0.0
    return float(np.abs(got - np.asarray(want)[len(prompt) - 1:]).max())


def served_vs_reference(eng, model, weights, prompts, n_new):
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    return max(checked(model, weights, p, r, n_new)
               for p, r in zip(prompts, reqs))


@pytest.fixture
def blocks_of_128(monkeypatch):
    """The latent body's block cut to 128 positions (1,024 as served: a
    test's whole table), so that a walk has blocks to cross."""
    monkeypatch.setattr(pda, "_LATENT_BLOCK", 128)
    pda._chunk_call.clear_cache()
    yield
    pda._chunk_call.clear_cache()


@pytest.fixture
def short_chunks(monkeypatch):
    """Prefill chunks of 16 tokens: a test's prompts span several."""
    monkeypatch.setattr(mixers, "PREFILL_CHUNK", 16)


# linear heads of whole lane tiles in whole sublane tiles (``kda_rule``):
# the step's state update is the token rule's kernel
# (``ops/pallas_kda_update.py``), the prefill's the chunk form's
# (``ops/pallas_kda_chunk.py``, a group of chunks a call); interpreted,
# as is the latent body of the paged kernel
WIDTHS = {"jnp": ({}, {}),
          "kernels": (dict(lin_heads=8, lin_head_dim=128),
                      dict(use_pallas="always", interpret=True))}


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_prefill_then_decode_over_slabs_and_latent_pages(
        widths, blocks_of_128, short_chunks):
    """Two slots of different lengths admitted at different steps: the
    long prompt's reply crosses a page (8) and a block of the latent
    kernel (16 pages: position 128), the short one is admitted while the
    long one decodes.  The expanded prefill, the absorbed step and the
    rule's chunk and token forms against the reference's definitions;
    one read-back a step carries both mechanisms' counters, and both
    sizes are the cache's."""
    sizes, cfg = WIDTHS[widths]
    model = make_model(**sizes)
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    long, short = (rng.randint(0, VOCAB, n).tolist() for n in (121, 21))
    names = ("decode_prefix_bypassed", "decode_prefix_pages_hit",
             "decode_latent_positions_live", "decode_latent_blocks_walked",
             "decode_prefill_scan_steps", "decode_prefill_scan_tokens",
             "kda_kernel_rows", "decode_tokens_total", "decode_prefills",
             "decode_steps", "decode_h2d_uploads")
    before = {n: stat_get(n) for n in names}
    rec = model.layer_kinds.count("recurrent")
    with engine(model, weights, slots=2, **cfg) as eng:
        cache = eng._cache
        assert cache.prefix is None and cache.prefix_bypassed
        # ONE pool (no V), then a slab of s and one of tail a layer
        assert cache.state_var_names()[0] == "__decode_k_pages__"
        assert len(cache.state_var_names()) == 1 + 2 * rec
        assert cache.config.num_layers == 2     # the latent layers alone
        assert stat_get("decode_latent_bytes") == cache.latent_bytes() \
            == 2 * (2 * 32 + 1) * PAGE * 128 * 4
        c = model.lin_heads * model.lin_head_dim
        assert stat_get("decode_state_bytes") == cache.state_bytes() \
            == 2 * rec * (c * model.lin_head_dim + 3 * 3 * c) * 4
        first = eng.submit(long, max_new_tokens=12, record_logits=True)
        while stat_get("decode_steps") - before["decode_steps"] < 3:
            pass                                # the long one is decoding
        second = eng.submit(short, max_new_tokens=12, record_logits=True)
        tol = 2e-4 if sizes else 5e-5
        assert checked(model, weights, long, first, 12) < tol
        assert checked(model, weights, short, second, 12) < tol
        # the same prompt again: a prefix cache would skip its prefill
        assert len(eng.submit(short, max_new_tokens=3).result(
            timeout=600)) == 3
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_prefix_bypassed"] == 3
    assert d["decode_prefix_pages_hit"] == 0
    # a layer's: the 11 steps of each first request attend 122..132 and
    # 22..32 positions (the longer crosses into its second block at 129),
    # the third's 2 steps 22 and 23
    assert d["decode_latent_positions_live"] == sum(range(122, 133)) \
        + sum(range(22, 33)) + 22 + 23
    assert d["decode_latent_blocks_walked"] == (7 + 2 * 4) + 11 + 2
    assert d["decode_prefill_scan_tokens"] == rec * (121 + 21 + 21)
    chunk = 16 if sizes else 1
    assert d["decode_prefill_scan_steps"] == rec * (
        -(-121 // chunk) + 2 * -(-21 // chunk))
    # a live row of a step is a token that no prefill delivered; the
    # XLA form of the rule (the toy widths) counts none
    assert d["kda_kernel_rows"] == (rec * (
        d["decode_tokens_total"] - d["decode_prefills"]) if sizes else 0)
    # one upload a step and one a prefill: the counts came back with
    # the tokens
    assert d["decode_h2d_uploads"] == d["decode_steps"] + 3


def _slabs_after(model, weights, prompts, news, **cfg):
    """The recurrent slabs once every request has ended, and the steps
    the engine ran; the requests are admitted in order, one a slot."""
    before = stat_get("decode_steps")
    with engine(model, weights, slots=3, **cfg) as eng:
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        for r in reqs:
            r.result(timeout=600)
        names = eng._cache.recurrent_var_names()
        return {n: np.asarray(eng._scope.get_var(n)) for n in names}, \
            stat_get("decode_steps") - before


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_a_dead_slots_state_and_tail_come_back_as_they_were(
        widths, short_chunks):
    """Slot 1's request ends after two tokens and its slot lies dead
    while slot 0 decodes a dozen steps on: its rows of every slab are
    what the same request leaves when it is served alone, and a slot
    nobody was admitted to stays zero."""
    sizes, cfg = WIDTHS[widths]
    model = make_model(**sizes)
    weights = model.init_weights(jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    long, short = (rng.randint(0, VOCAB, n).tolist() for n in (19, 9))
    both, steps = _slabs_after(model, weights, [long, short], [14, 2], **cfg)
    alone, _ = _slabs_after(model, weights, [short], [2], **cfg)
    assert steps >= 13
    assert len(both) == 2 * model.layer_kinds.count("recurrent")
    for n in both:
        assert np.abs(both[n][1]).max() > 0          # slot 1 was written
        np.testing.assert_allclose(both[n][1], alone[n][0], atol=1e-6)
        assert np.abs(both[n][0] - both[n][1]).max() > 0
        assert not both[n][2].any()                  # slot 2 never was


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold 16 experts each of one 64-expert layer: their
    routed parts, scaled, and the shared expert counted ONCE are what
    the reference gives for the whole layer."""
    whole = make_model(kinds=("recurrent",), held=tuple(range(64)),
                       num_experts=64, dense_layers=0)
    lw = whole.init_weights(jax.random.PRNGKey(12))["layers"][0]
    assert float(jnp.abs(lw["moe_router_bias"]).min()) > 0
    x = jax.random.normal(jax.random.PRNGKey(13), (24, 32))
    want, _ = ref.moe_layer(lw, x, dims(whole))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * lw["norm2"]
    f, total = 16, jnp.zeros_like(x)
    for chip in range(4):
        held = list(range(16 * chip, 16 * chip + 16))
        cols = slice(16 * chip * f, 16 * (chip + 1) * f)
        mine = {**lw, "moe_w_gate": lw["moe_w_gate"][:, cols],
                "moe_w_up": lw["moe_w_up"][:, cols],
                "moe_w_down": lw["moe_w_down"][cols]}
        _, _, local = moe_ops.moe_share_route(
            h, lw["moe_router"], lw["moe_router_bias"], top_k=4,
            held_ids=held)
        part = whole.routed_scale * moe_ops.moe_share_ffn(
            h, local, mine["moe_w_gate"], mine["moe_w_up"],
            mine["moe_w_down"])
        # the reference given the same share (and no shared expert)
        ref_part, _ = ref.moe_layer(mine, x, dims(whole), held=held,
                                    shared=False)
        np.testing.assert_allclose(x + part, ref_part, atol=1e-4)
        total = total + part
    shared = (jax.nn.silu(h @ lw["shared_w_gate"])
              * (h @ lw["shared_w_up"])) @ lw["shared_w_down"]
    np.testing.assert_allclose(x + total + shared, want, atol=2e-4)


def test_a_latent_cache_with_a_recurrent_spec_holds_both_and_splits():
    """``PagedKVCache(CacheConfig(latent=True), recurrent=spec)``: one
    pool and the slabs in one state tuple, both byte counts side by
    side, every admission fresh; the engine's ``_Mixed`` splits that
    tuple into (the one pool and no V, no window pools, a dict a
    recurrent layer) and joins it back in the same order."""
    from paddle_tpu.framework.scope import Scope

    model = make_model()
    spec = RecurrentSpec(model.layer_kinds.count("recurrent"),
                         model.recurrent_state)
    cache = PagedKVCache(
        CacheConfig(2, 1, model.head_dim, 3, 64, PAGE,
                    v_head_dim=model.v_head_dim, latent=True),
        Scope(), prefix_cache=True, recurrent=spec)
    names = cache.state_var_names()
    assert names[0] == "__decode_k_pages__" \
        and names[1:] == cache.recurrent_var_names() and len(names) == 9
    assert cache.prefix is None and cache.prefix_bypassed
    assert cache.latent_bytes() == 2 * (3 * 8 + 1) * PAGE * 128 * 4 > 0
    assert cache.state_bytes() == 3 * 4 * (2 * 8 * 8 + 3 * 3 * 16) * 4 > 0
    assert cache.window_bytes() == 0
    with pytest.raises(ValueError, match="exports no pages"):
        cache.export_pages([1])
    mixed = decode_mod._Mixed(model, spec, None, 3)
    state = tuple(range(len(names)))            # stand-ins, in order
    pools, window, rec = mixed.split(state)
    assert pools == (0, None, None, None) and window == ()
    assert rec == tuple({"s": 1 + 2 * i, "tail": 2 + 2 * i}
                        for i in range(4))
    assert mixed.join((pools, window, rec)) == state
    assert mixed.layer["attention"] == {3: 0, 5: 1}
    assert mixed.layer["recurrent"] == {0: 0, 1: 1, 2: 2, 4: 3}


@pytest.mark.parametrize("cfg, names", [
    (dict(prefill_chunk_pages=1), "chunked prefill"),
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_quant=True), "kv_quant"),
], ids=["chunked", "speculative", "kv_quant"])
def test_each_refusal_names_its_mechanism_for_both_of_the_models_kinds(
        cfg, names):
    """What cannot carry a state a slot cannot carry a latent page
    either: ONE refusal names the recurrent layers and the latent page,
    each with the mechanism asked for."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(14))
    with pytest.raises(ValueError, match=(
            f"recurrent layers.*{names}.*; and .*latent page.*{names}")):
        engine(model, weights, **cfg)


def test_a_draft_model_and_the_disaggregated_hand_over_refuse():
    from paddle_tpu.serving.decode import TransformerLM, per_slot_kinds
    from paddle_tpu.serving.disagg import DisaggServer

    model = make_model()
    assert [k for k, _ in per_slot_kinds(model)] == ["recurrent"]
    weights = model.init_weights(jax.random.PRNGKey(15))
    draft = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=256)
    cfg = DecodeConfig(slots=2, max_seq_len=256, page_size=PAGE)
    with pytest.raises(ValueError, match=(
            "recurrent layers.*speculative decoding.*; and "
            ".*latent page.*speculative")):
        DecodeEngine(model, weights, cfg, draft_model=draft,
                     draft_weights=draft.init_weights(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="disaggregated.*recurrent layers"):
        DisaggServer(model, weights, config=cfg)
    eng = engine(model, weights)
    with pytest.raises(ValueError, match="extract_kv.*recurrent layers"):
        eng.submit([1, 2, 3], max_new_tokens=2, extract_kv=True)
    with pytest.raises(ValueError, match="exports no pages"):
        eng._cache.export_pages([1])


def test_the_tallies_are_both_siblings_and_no_rotary_work_is_traced():
    """The declared counters are the recurrent sibling's beside the
    routing's; a step that keeps the dense form reads back none of the
    hit form's.  ``mla_use_nope``: the lowered step and prefill hold no
    sine or cosine (a rotation by the identity would)."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(17))
    eng = engine(model, weights)
    assert eng._tallies == model.step_tallies(3) == (
        "moe_local_assignments", "moe_experts_hit", "kda_kernel_rows")
    assert model.tallies == eng._tallies + moe_ops.HIT_TALLIES
    assert eng._prefill_tallies == decode_mod._SCAN_TALLIES \
        + moe_ops.GROUPED_TALLIES
    assert model.beta_scale == 1.0 and HybridMoELM.beta_scale == 2.0
    for text in (eng.lower_step().as_text(),
                 eng.lower_prefill(64).as_text()):
        assert "sine" not in text and "cosine" not in text
        assert "exponential" in text            # the gates are there


def test_as_served_the_kernels_rules_take_the_published_shapes():
    """What ``pages_per_block``, ``flash_rule``, ``kda_rule`` and the
    experts' rules choose at the configuration's widths, from shapes
    alone: the latent body walks blocks of 1,024 positions with 32 heads
    as rows of the one head (64 at the sibling's), the prompt's expanded
    form is the flash kernel's at one head a group of 192 K lanes, the
    state goes through the kernel, a step of 128 rows over 32 of 256
    experts keeps the dense form and a 4,096-row prompt the grouped."""
    from paddle_tpu.ops import pallas_kda_update as kda

    assert pda.pages_per_block(16, 384, 640, "bfloat16", 0, 1, 32) == 64
    assert ppa.flash_rule(4096, 32, 32, 192, 128) == (1024, 1024)
    assert kda.kda_rule(32, 128, 128, "float32")
    assert kda.head_block(1, 32, 128, 128) == 16
    assert not moe_ops.hit_rule(128, 32, 1024, 2304, 8, 256)
    assert not moe_ops.grouped_rule(128, 32, 1024, 2304, 8, 256)
    assert moe_ops.grouped_rule(4096, 32, 1024, 2304, 8, 256)


class _Prompt:
    """``attend`` of one whole prompt in plain jnp, as the engine's
    contract has it (``prompt``: the model runs its expanded form, its
    recurrent layers one token after another from the zero state): a
    control is read off the model's own forward with no engine."""

    prompt, interpret, read_row = True, False, None

    def __init__(self, n):
        self.live = jnp.ones((n,), bool)
        self.ids = []

    def __call__(self, layer, q, k, v, cache, keep=None):
        s = jnp.einsum("ihd,jhd->hij", q, k) / np.sqrt(q.shape[-1])
        i = jnp.arange(q.shape[0])
        s = jnp.where(i[None, :] <= i[:, None], s, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, -1), v), cache

    def recur(self, layer, token_fn, rows, cache, **_):
        n = self.live.shape[0]
        state = {name: jnp.zeros((1,) + shape, dtype)
                 for name, (shape, dtype) in self.state.items()}
        outs = []
        for t in range(n):
            o, state = token_fn({k: v[t:t + 1] for k, v in rows.items()},
                                state)
            state = {k: v.astype(self.state[k][1]) for k, v in state.items()}
            outs.append(o)
        return jnp.concatenate(outs), cache

    def tally(self, name, n):
        pass

    def record(self, name, rows):
        self.ids.append(rows)


@functools.lru_cache(maxsize=None)
def _control_case():
    """(weights, tokens, the reference's logits) every control is read
    against: made once."""
    model = make_model(d_model=64)
    weights = model.init_weights(jax.random.PRNGKey(21))
    seq = jnp.asarray(np.random.RandomState(22).randint(0, VOCAB, 40))
    return weights, seq, ref.forward_logits(weights, seq, dims(model))[0]


@pytest.mark.parametrize("name", [n for n in CONTROLS
                                  if not n.startswith("latent_")])
def test_each_control_is_told_from_the_model_at_a_small_size(name):
    """The cell's controls (``benchmark/tests/linear_latent_controls.py``)
    on the model's own forward: the model as it is reads the reference
    to float32 rounding, each control does not (a router in bfloat16 by
    its choices over many rows, a bfloat16 state by its size too, every
    other one by its logits)."""
    change, patch = CONTROLS[name]
    model = make_model(d_model=64)
    weights, seq, want = _control_case()
    if name == "bf16_router":
        # near-ties are few: 40 rows have none, 4,096 a handful
        lw = weights["layers"][1]
        rows = jax.random.normal(jax.random.PRNGKey(25), (4096, 64))
        route = lambda: moe_ops.moe_share_route(  # noqa: E731
            rows, lw["moe_router"], lw["moe_router_bias"], top_k=4,
            held_ids=model.held_experts)[0]
        own, undo = route(), patch()
        try:
            flipped = jnp.any(jnp.sort(route()) != jnp.sort(own), axis=-1)
        finally:
            undo()
        assert 0 < int(flipped.sum()) < 4096 // 20
        return
    if change:
        change(model)
    served = REWEIGH[name](model, weights, 7) if name in REWEIGH \
        else weights
    att = _Prompt(40)
    att.state = model.recurrent_state
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(served, seq, jnp.arange(40), None, att)
    err = float(jnp.abs(got - want).max())
    if name == "served":
        _, gap = ref.forward_logits(weights, seq, dims(model),
                                    routing=jnp.stack(att.ids, axis=1))
        assert err < 5e-5 and float(gap.max()) == 0.0
    elif name == "state_in_bf16":
        # half the matrices' bytes: the size check's
        assert RecurrentSpec(1, model.recurrent_state).slot_bytes() \
            < RecurrentSpec(1, make_model().recurrent_state).slot_bytes()
        assert err > 1e-2, err
    else:
        assert err > 1e-2, err


@pytest.mark.parametrize("name", ["latent_in_8_bits",
                                  "latent_on_int8_grid"])
def test_a_latent_rounded_to_eight_bits_is_told_by_the_step(name):
    """Only what reads the pages sees their rounding: the prefill's
    logit is the reference's, the steps' are not."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(23))
    prompt = np.random.RandomState(24).randint(0, VOCAB, 30).tolist()
    undo = CONTROLS[name][1]()
    try:
        with engine(model, weights) as eng:
            r = eng.submit(prompt, max_new_tokens=6, record_logits=True)
            toks = r.result(timeout=600)
    finally:
        undo()
    want, _ = ref.forward_logits(
        weights, jnp.asarray(prompt + toks[:-1], jnp.int32), dims(model))
    err = np.abs(np.stack(r.logits_trace) - np.asarray(want)[29:]).max(1)
    assert err[0] < 5e-5 and err[1:].min() > 1e-3, err


def test_a_pool_that_holds_eight_bit_rows_is_half_the_bytes():
    """``latent_pool_fp8``: the engine's plain forms serve a pool of
    8-bit floats; the gauge reads half of what the configuration's
    dtype owes (the size limit's control), and every logit that read a
    page is off by the rows' rounding."""
    from benchmark.tests.linear_latent_controls import SERVING

    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(26))
    prompt = np.random.RandomState(27).randint(0, VOCAB, 30).tolist()
    with engine(model, weights, cache_dtype="bfloat16") as eng:
        owed = eng._cache.latent_bytes()
    with engine(model, weights, **SERVING["latent_pool_fp8"]) as eng:
        assert stat_get("decode_latent_bytes") \
            == eng._cache.latent_bytes() == owed // 2
        r = eng.submit(prompt, max_new_tokens=6, record_logits=True)
        toks = r.result(timeout=600)
    want, _ = ref.forward_logits(
        weights, jnp.asarray(prompt + toks[:-1], jnp.int32), dims(model))
    err = np.abs(np.stack(r.logits_trace) - np.asarray(want)[29:]).max(1)
    assert err.min() > 1e-3, err


def test_the_reference_in_blocks_of_rows_is_the_reference():
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(3))
    seq = jnp.asarray(np.random.RandomState(4).randint(0, VOCAB, 48))
    whole, _ = ref.forward_logits(weights, seq, dims(model))
    blocks, _ = ref.forward_logits(weights, seq, dims(model), rows=16)
    assert float(jnp.abs(whole).max()) > 1.0
    np.testing.assert_allclose(blocks, whole, atol=2e-5)


# sha256 of the joint step's lowered text (``make_model()`` at each of
# ``WIDTHS``, 3 slots; at the kernels' widths the state kernel and the
# latent body interpreted), taken on the commit before the rule's chunk
# form (PR 58): the prompt's form changed, the step's did not
STEPS_AS_LOWERED = {
    "jnp": "62caf67c6af15ca0f251f38b47af230a14f664658c51742828b4a4db6f8bd0d6",
    "kernels":
        "b0c4be916904525a210a51f31e542318ee1cb53341247a836044407f53acdb8f",
}


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_the_step_is_the_program_it_was(widths):
    """``_kda_token`` and the token rule's kernel are not the chunk
    form's to touch: the joint step lowers to the parent's text at the
    toy widths (the XLA lines) and at lane-wide heads (the kernel's
    ``T = 1`` grid over the slots), while the same engine's prefill
    holds the chunk form and says so on the gauge."""
    import hashlib

    sizes, cfg = WIDTHS[widths]
    model = make_model(**sizes)
    eng = engine(model, model.init_weights(jax.random.PRNGKey(1)), **cfg)
    text = eng.lower_step().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == STEPS_AS_LOWERED[widths]
    group = model.prefill_chunks_per_call(eng.config.max_seq_len)
    assert stat_get("decode_prefill_chunks_per_call") == group \
        == (4 if sizes else 0)


@functools.lru_cache(maxsize=None)
def _kernel_model():
    return make_model(**WIDTHS["kernels"][0])


@pytest.mark.parametrize("length", list(solar.LENGTHS))
def test_the_chunk_form_is_the_token_form_at_beta_scale_one(
        length, monkeypatch):
    """``KDAMixer._kda_chunk`` as this model takes it (beta in (0, 1):
    no ``kda_allow_neg_eigval``) against the token recurrence from a
    non-zero state, two chunks a call: outputs, matrices and tail to
    1e-5 (``tests/test_hybrid_moe_serving.py`` has the helpers and
    Solar's range)."""
    model = _kernel_model()
    assert model.beta_scale == 1.0
    solar.assert_the_chunk_form_is_the_token_form(
        model, solar.recurrent_case(model, 3 * 128, 41),
        solar.LENGTHS[length], solar.GROUP, monkeypatch)


@pytest.mark.parametrize("case", ["decays_side_by_side", "beta0", "beta1",
                                  "all_padding"])
def test_the_chunk_forms_ends_hold_at_beta_scale_one(case, monkeypatch):
    """Log decays of 0, -1e-3 and -40 a token side by side in one chunk
    (nothing ``inf`` or ``nan``), beta at 0 and at 1, and a call of
    nothing but padding (the state and the tail come back bit for bit)."""
    model = _kernel_model()
    lw, rows, state = solar.recurrent_case(
        model, 128, 42,
        log_decays=(0.0, -1e-3, -40.0) if case.startswith("decays") else None,
        beta={"beta0": -1e9, "beta1": 1e9}.get(case))
    if case.startswith("beta"):
        assert float(model.beta_scale * jax.nn.sigmoid(
            rows["beta"]).max()) == float(case[-1])
    length = 0 if case == "all_padding" else 100
    new = solar.assert_the_chunk_form_is_the_token_form(
        model, (lw, rows, state), length, solar.GROUP, monkeypatch,
        rows_run=128)
    if not length:
        for name in state:
            assert np.array_equal(np.asarray(new[name]),
                                  np.asarray(state[name]))
