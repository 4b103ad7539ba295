"""The latent-attention model with a held share of its experts
(``serving/latent_moe_lm.py``) behind the real ``DecodeEngine``, against
the plain reference (``benchmark/reference/latent_moe_lm.py``, the one the
cell's check uses): float32, seeded, tiny."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.ops import pallas_prompt_attention as ppa
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.latent_moe_lm import LatentMoELM

from benchmark.reference import latent_moe_lm as ref
from benchmark.tests.latent_moe_controls import CONTROLS

VOCAB, PAGE = 97, 8
YARN = dict(rope_theta=50000.0, rope_factor=64.0, rope_orig_len=64,
            rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
            rope_mscale_all_dim=1.0)


def make_model(held=(0, 1, 2, 3, 4), **kw):
    """Kimi-K2.5's first layers in small: a leading dense layer, two
    expert layers; a cached row of 16 + 8 lanes under 4 heads."""
    sizes = dict(vocab_size=VOCAB, d_model=32, num_layers=3, dense_layers=1,
                 num_heads=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=8,
                 v_dim=8, dense_dim=48, num_experts=16, top_k=4,
                 held_experts=held, expert_dim=16, shared_dim=16,
                 routed_scale=2.827, dtype="float32", **YARN)
    sizes.update(kw)
    return LatentMoELM(**sizes)


def dims(m, held=None):
    return dict(num_heads=m.num_heads, nope_dim=m.nope_dim,
                rope_dim=m.rope_dim, kv_rank=m.kv_rank,
                rope_theta=YARN["rope_theta"],
                yarn=dict(factor=64.0, orig_len=64, beta_fast=32.0,
                          beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
                dense_layers=m.dense_layers, top_k=m.top_k,
                held=list(held or m.held_experts), expert_dim=m.expert_dim,
                routed_scale=m.routed_scale, eps=m.rms_eps)


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=256, page_size=PAGE), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def served_vs_reference(eng, model, weights, prompts, n_new):
    """Worst |dlogit| over the prompts' prefill and decode positions,
    the reference given the server's own tokens (its own routing)."""
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = 0.0
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=300)
        got = np.stack(r.logits_trace)
        assert got.shape == (n_new, VOCAB)
        seq = jnp.asarray(p + toks[:-1], jnp.int32)
        # the recorded routing (prefill rows, then one row a step:
        # [positions, expert layers, k]) is the reference's own: it
        # follows the served ids and finds no gap, so ONE forward gives
        # its own logits too
        routed = r.records["moe_topk"]
        ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
        assert ids.shape == (len(p) + n_new - 1, 2, model.top_k)
        want, gap = ref.forward_logits(weights, seq, dims(model),
                                       routing=jnp.asarray(ids))
        assert float(gap.max()) == 0.0
        worst = max(worst, float(np.abs(
            got - np.asarray(want)[len(p) - 1:]).max()))
    return worst


@pytest.fixture
def blocks_of_128(monkeypatch):
    """The latent body's block cut to 128 positions (1,024 as served: a
    test's whole table), so that a walk has blocks to cross."""
    monkeypatch.setattr(pda, "_LATENT_BLOCK", 128)
    pda._chunk_call.clear_cache()
    yield
    pda._chunk_call.clear_cache()


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "interpret"])
def test_prefill_then_decode_through_latent_pages_matches_the_reference(
        kernel, blocks_of_128):
    """A prompt whose reply crosses a page (8) and a block of the kernel
    (16 pages: position 128) beside a short one; the expanded prefill
    and the absorbed step against the reference's definition.  Every
    request is admitted fresh, and the one pool is the rows' size."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 121)]
    names = ("decode_prefix_bypassed", "decode_prefix_pages_hit",
             "decode_latent_positions_live", "decode_latent_blocks_walked",
             "decode_steps")
    before = {n: stat_get(n) for n in names}
    cfg = dict(use_pallas="always", interpret=True) if kernel else {}
    with engine(model, weights, **cfg) as eng:
        cache = eng._cache
        assert cache.prefix is None and cache.prefix_bypassed
        # 16 + 8 lanes of a row take one whole lane tile; no V pool
        assert cache.state_var_names() == ("__decode_k_pages__",)
        assert cache.config.pool_shape() == (3, 3 * 32 + 1, PAGE, 128)
        assert stat_get("decode_latent_bytes") == cache.latent_bytes() \
            == 3 * 97 * PAGE * 128 * 4
        assert served_vs_reference(eng, model, weights, prompts, 12) < 5e-5
        # the same prompt again: a prefix cache would skip its prefill
        again = eng.submit(prompts[0], max_new_tokens=3).result(timeout=300)
        assert len(again) == 3
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_prefix_bypassed"] == 3
    assert d["decode_prefix_pages_hit"] == 0
    # a layer's: the 11 steps of each first request attend 6..16 and
    # 122..132 positions (the longer crosses into its second block at
    # 129), the third's 2 steps 6 and 7
    assert d["decode_latent_positions_live"] == sum(range(6, 17)) \
        + sum(range(122, 133)) + 6 + 7
    assert d["decode_latent_blocks_walked"] == 11 + (7 + 2 * 4) + 2


def test_absorbed_and_expanded_are_the_same_numbers():
    """The reference's two forms on the same weights, to float32
    rounding: scores against the cached rows as they lie are the scores
    against the keys built from them."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(3))
    seq = jnp.asarray(np.random.RandomState(4).randint(0, VOCAB, 40))
    expanded, _ = ref.forward_logits(weights, seq, dims(model))
    absorbed, _ = ref.forward_logits(weights, seq, dims(model),
                                     absorbed=True)
    assert float(jnp.abs(expanded).max()) > 1.0
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5)


def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """Thirty-two chips hold one expert each of one 32-expert layer:
    their routed parts, scaled, and the shared expert counted ONCE are
    what the reference gives for the whole layer."""
    whole = make_model(held=tuple(range(32)), num_experts=32,
                       dense_layers=0, num_layers=1)
    lw = whole.init_weights(jax.random.PRNGKey(12))["layers"][0]
    assert float(jnp.abs(lw["moe_router_bias"]).min()) > 0
    x = jax.random.normal(jax.random.PRNGKey(13), (24, 32))
    want, _ = ref.moe_layer(lw, x, dims(whole))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * lw["norm2"]
    f = 16

    @jax.jit
    def share(chip):
        """(the program's routed part of the chip that holds expert
        ``chip``, the reference's given the same share)."""
        cut = lambda m, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            m, chip * f, f, axis)
        mine = {**lw, "moe_w_gate": cut(lw["moe_w_gate"], 1),
                "moe_w_up": cut(lw["moe_w_up"], 1),
                "moe_w_down": cut(lw["moe_w_down"], 0)}
        _, _, local = moe_ops.moe_share_route(
            h, lw["moe_router"], lw["moe_router_bias"], top_k=4,
            held_ids=chip[None])
        part = whole.routed_scale * moe_ops.moe_share_ffn(
            h, local, mine["moe_w_gate"], mine["moe_w_up"],
            mine["moe_w_down"])
        with jax.default_matmul_precision("highest"):
            return part, ref.routed_part(mine, h, dims(whole),
                                         held=chip[None])[0]

    total = jnp.zeros_like(x)
    for chip in range(32):
        part, ref_part = share(jnp.int32(chip))
        np.testing.assert_allclose(part, ref_part, atol=1e-4)
        total = total + part
    shared = (jax.nn.silu(h @ lw["shared_w_gate"])
              * (h @ lw["shared_w_up"])) @ lw["shared_w_down"]
    np.testing.assert_allclose(x + total + shared, want, atol=2e-4)


@pytest.mark.parametrize("pool", ["float32", "bfloat16"])
def test_the_latent_body_in_interpret_mode_at_64_rows_a_head(
        pool, blocks_of_128):
    """The published row (576 lanes in a pool of 640, values the first
    512) under 64 query heads stacked as rows of the one head, lengths
    inside a page, across a block and past two; pages nobody owns hold
    NaN.  One pool, one call, its own name."""
    assert pda.pages_per_block(16, 20, 640, pool, 0) == 8
    rng = np.random.RandomState(0)
    n_pages, page, s, pps = 70, 16, 3, 20
    rows = rng.randn(2, n_pages, page, 640).astype(np.float32)
    rows[..., 576:] = 0
    lengths = np.array([5, 130, 300], np.int32)
    table, nxt = np.full((s, pps), 65, np.int32), 1
    for i, n in enumerate(-(-lengths // page)):
        table[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    clean = jnp.asarray(rows.copy(), pool)
    rows[:, 60:] = np.nan
    q = jnp.asarray(rng.randn(s, 64, 576).astype(np.float32))
    args = (jnp.asarray(table), jnp.asarray(lengths))
    want = pda.paged_decode_attention(
        q, clean, None, *args, layer=1, use_pallas="never", value_lanes=512)
    got = pda.paged_decode_attention(
        q, jnp.asarray(rows, pool), None, *args, layer=1,
        use_pallas="always", interpret=True, value_lanes=512)
    assert got.shape == (s, 64, 512) and bool(jnp.isfinite(got).all())
    # bfloat16 rows meet a query and probabilities rounded to bfloat16
    # once (``_LATENT_TERMS``), float32 rows float32 operands
    np.testing.assert_allclose(got, want, atol=2e-2 if pool == "bfloat16"
                               else 2e-5)
    jaxpr = jax.make_jaxpr(lambda q, p: pda.paged_decode_attention(
        q, p, None, *args, layer=1, use_pallas="always", interpret=True,
        value_lanes=512))(q, clean)
    text = str(jaxpr)
    assert text.count("pallas_call") == 1
    assert pda.LATENT_KERNEL_NAME in text
    with pytest.raises(ValueError, match="latent pool's rows"):
        pda.paged_decode_attention(
            jnp.zeros((s, 64, 700)), clean, None, *args,
            use_pallas="always", interpret=True, value_lanes=512)


def test_as_served_a_block_of_the_latent_body_is_1024_positions():
    """64 pages of 16 rows of 640 bfloat16 lanes, both buffers of which
    fit beside the body's tiles; the pools of K and V keep their blocks
    of one lane tile of scores."""
    assert pda.pages_per_block(16, 640, 640, "bfloat16", 0) == 64
    assert 2 * 64 * 16 * 640 * 2 <= pda._BLOCK_VMEM_BYTES
    assert pda.pages_per_block(8, 32, 128, "float32", 0) == 32  # short table
    assert pda.pages_per_block(16, 640, 2048, "bfloat16", 2048) == 8
    assert pda.pages_per_block(16, 64, 1024, "float32") == 8
    # the rows riding on the one head do not move the latent block
    assert pda.pages_per_block(16, 640, 640, "bfloat16", 0, 1, 64) == 64


def test_flash_rule_takes_ungrouped_heads_of_192_and_nothing_else_moves():
    """64 ungrouped heads of K 192 / V 128 at 8,192 rows are taken (the
    query head-major), and the four models that use the kernel get the
    answers they got."""
    assert ppa.flash_rule(8192, 64, 64, 192, 128) == (1024, 1024)
    was = {
        # MiMo-V2.5: global (4 K/V heads) and window (8, 128) layers
        (2048, 64, 4, 192, 128, None): (128, 1024),
        (2048, 64, 8, 192, 128, 128): (128, 128),
        # Command A+: 96 heads on 8, global and a window of 4,096
        (4096, 96, 8, 128, 128, None): None,
        (4096, 96, 8, 128, 128, 4096): None,
        # Olmo-Hybrid: 30 ungrouped heads of 128
        (4096, 30, 30, 128, 128, None): (256, 1024),
        # Solar-Open2: 64 heads on 8 of 128
        (1024, 64, 8, 128, 128, None): (256, 1024),
        # GPT-2's heads of 64 never were
        (1024, 16, 16, 64, 64, None): None,
    }
    for shape, tiles in was.items():
        assert ppa.flash_rule(*shape) == tiles, shape
    # one head a group is what the new shape needs: groups of heads
    # whose lanes end inside a tile stay refused
    assert ppa.flash_rule(4096, 6, 2, 96, 128) is None
    rng = np.random.RandomState(5)
    t, h = 256, 4
    q, k = (jnp.asarray(rng.randn(t, h, 192), jnp.float32) for _ in "qk")
    v = jnp.asarray(rng.randn(t, h, 128), jnp.float32)
    want = pda.grouped_causal_attention(q, k, v, length=200,
                                        use_pallas="never")
    got = pda.grouped_causal_attention(q, k, v, length=200,
                                       use_pallas="always", interpret=True)
    np.testing.assert_allclose(got[:200], want[:200], atol=2e-5)


@pytest.mark.parametrize("cfg, names", [
    (dict(prefill_chunk_pages=1), "latent page.*chunked prefill"),
    (dict(spec_k=2), "latent page.*speculative decoding"),
    (dict(kv_quant=True), "latent page.*kv_quant"),
], ids=["chunked", "speculative", "kv_quant"])
def test_what_is_not_built_for_a_latent_page_refuses_by_name(cfg, names):
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(18))
    with pytest.raises(ValueError, match=names):
        engine(model, weights, **cfg)


def test_a_draft_model_and_the_disaggregated_hand_over_refuse():
    from paddle_tpu.serving.decode import TransformerLM, per_slot_kinds
    from paddle_tpu.serving.disagg import DisaggServer

    model = make_model()
    assert per_slot_kinds(model) == []      # nothing refuses by kind
    weights = model.init_weights(jax.random.PRNGKey(19))
    draft = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=256)
    cfg = DecodeConfig(slots=2, max_seq_len=256, page_size=PAGE)
    with pytest.raises(ValueError, match="latent page.*speculative"):
        DecodeEngine(model, weights, cfg, draft_model=draft,
                     draft_weights=draft.init_weights(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="disaggregated.*latent page"):
        DisaggServer(model, weights, config=cfg)
    eng = engine(model, weights)
    with pytest.raises(ValueError, match="extract_kv.*latent page"):
        eng.submit([1, 2, 3], max_new_tokens=2, extract_kv=True)
    with pytest.raises(ValueError, match="latent pages exports none"):
        eng._cache.export_pages([1])


class _Prompt:
    """``attend`` of one whole prompt in plain jnp, as the engine's
    contract has it (``prompt``: the model runs its expanded form): a
    control is read off the model's own forward with no engine."""

    prompt, interpret, read_row = True, False, None

    def __init__(self, n):
        self.live = jnp.ones((n,), bool)

    def __call__(self, layer, q, k, v, cache, keep=None):
        s = jnp.einsum("ihd,jhd->hij", q, k) / np.sqrt(q.shape[-1])
        i = jnp.arange(q.shape[0])
        s = jnp.where(i[None, :] <= i[:, None], s, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, -1), v), cache

    def tally(self, name, n):
        pass

    def record(self, name, rows):
        pass


@functools.lru_cache(maxsize=None)
def _control_case():
    """(weights, tokens, the reference's logits) every control is read
    against: made once."""
    model = make_model(d_model=64)
    weights = model.init_weights(jax.random.PRNGKey(21))
    seq = jnp.asarray(np.random.RandomState(22).randint(0, VOCAB, 48))
    return weights, seq, ref.forward_logits(weights, seq, dims(model))[0]


@pytest.mark.parametrize("name", [n for n in CONTROLS
                                  if n != "latent_in_8_bits"])
def test_each_control_is_told_from_the_model_at_a_small_size(name):
    """The cell's controls (``benchmark/tests/latent_moe_controls.py``)
    on the model's own forward: the model as it is reads the reference
    to float32 rounding, each control does not (a router in bfloat16 by
    its choices over many rows, every other one by its logits)."""
    change, patch = CONTROLS[name]
    model = make_model(d_model=64)
    weights, seq, want = _control_case()
    if name == "bf16_router":
        # near-ties are few: 48 rows have none, 4,096 a handful
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
    undo = patch() if patch else None
    try:
        att = _Prompt(48)
        ids = []
        att.record = lambda name, rows: ids.append(rows)
        with jax.default_matmul_precision("highest"):
            got, _ = model.forward(weights, seq, jnp.arange(48), None, att)
    finally:
        if undo:
            undo()
    err = float(jnp.abs(got - want).max())
    _, gap = ref.forward_logits(weights, seq, dims(model),
                                routing=jnp.stack(ids, axis=1))
    if name == "served":
        assert err < 5e-5 and float(gap.max()) == 0.0
    else:
        assert err > 1e-2, err


def test_a_latent_rounded_to_eight_bits_is_told_by_the_step():
    """Only what reads the pages sees their rounding: the prefill's
    logit is the reference's, the steps' are not."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(23))
    prompt = np.random.RandomState(24).randint(0, VOCAB, 30).tolist()
    undo = CONTROLS["latent_in_8_bits"][1]()
    try:
        with engine(model, weights) as eng:
            r = eng.submit(prompt, max_new_tokens=6, record_logits=True)
            toks = r.result(timeout=300)
    finally:
        undo()
    want, _ = ref.forward_logits(
        weights, jnp.asarray(prompt + toks[:-1], jnp.int32), dims(model))
    err = np.abs(np.stack(r.logits_trace) - np.asarray(want)[29:]).max(1)
    assert err[0] < 5e-5 and err[1:].min() > 1e-3, err


def test_a_step_that_holds_few_experts_of_many_reads_the_hit_ones_alone():
    """Widths of whole lane tiles and 2 of 64 experts a row, 6 of them
    held: three slots' uniform choices would hit 9 % of the held
    experts, so the joint step takes the hit form (interpreted) and the
    whole-prompt prefill, a bucket of 16 or 64 rows, takes it or the
    dense lines by the same rule.  The served logits are the whole-
    sequence reference's, and the step's own counters say one call an
    expert layer a step, and that skipped + hit is what is held, every
    step."""
    held = (3, 9, 20, 33, 47, 60)
    model = make_model(held=held, d_model=128, expert_dim=128,
                       num_experts=64, top_k=2)
    assert model.step_tallies(3) == model.tallies
    assert model.tallies[-2:] == moe_ops.HIT_TALLIES
    assert moe_ops.hit_rule(3, len(held), 128, 128, 2, 64)
    assert moe_ops.hit_rule(16, len(held), 128, 128, 2, 64)
    assert not moe_ops.hit_rule(128, len(held), 128, 128, 2, 64)
    weights = model.init_weights(jax.random.PRNGKey(48))
    rng = np.random.RandomState(49)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (7, 100)]
    names = moe_ops.HIT_TALLIES + ("moe_experts_hit", "decode_steps")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights, interpret=True) as eng:
        assert eng._tallies == model.tallies
        assert served_vs_reference(eng, model, weights, prompts, 9) < 5e-5
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_steps"] >= 8
    assert d["moe_hit_form_calls"] == 2 * d["decode_steps"] > 0
    assert d["moe_experts_skipped"] + d["moe_experts_hit"] \
        == 2 * len(held) * d["decode_steps"]
    assert d["moe_experts_skipped"] > d["moe_experts_hit"]


# sha256 of the lowered text of ``make_model()``'s joint step and 128-row
# whole-prompt prefill behind ``engine()``, as PR 48's tree lowers them
# (taken before PR 50 touched the engine)
PROGRAMS_AS_LOWERED = {
    "step": "654cd27118f1be4179f683c57c3962d6f31169c4fe4910dfb5b44af673288efc",
    "prefill":
        "6b284d0da4049818c9ee9e6e29efa1d41b29253b81d02c5403b14f3d72800a88"}


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_the_programs_are_still_the_ones_lowered_before_cache_layers(
        program):
    """This model's cache layers are its weight layers and its ``attend``
    gets a Python ``int`` a layer: the joint step and the whole-prompt
    prefill lower to the text they had before a model could own more
    cache layers than weight layers (PR 50): no line that this cell's
    programs lower has changed.  A change MEANT to move these programs
    replaces the digests; one that was not has found out here."""
    import hashlib

    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(1))
    eng = engine(model, weights)
    text = (eng.lower_step() if program == "step"
            else eng.lower_prefill(128)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PROGRAMS_AS_LOWERED[program]
