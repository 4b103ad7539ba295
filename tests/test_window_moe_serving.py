"""The window/global-attention model with a held share of its experts
(``serving/window_moe_lm.py``) behind the real ``DecodeEngine``, against
the plain reference (``benchmark/reference/window_moe_lm.py``, the one the
cell's check uses): float32, seeded, tiny."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.serving import DecodeConfig, DecodeEngine, kv_cache
from paddle_tpu.serving.window_moe_lm import WindowMoELM

from benchmark.reference import window_moe_lm as ref

# MiMo-V2.5's first layers in small: a leading dense layer that attends
# everything, window layers, a second global layer
PERIOD = ("attention", "window", "window", "attention", "window")
VOCAB, WINDOW, PAGE = 97, 20, 8
RING = 4                    # ceil(20 / 8) + 1 pages = 32 positions


def make_model(kinds=PERIOD, held=(0, 1, 2, 3, 4), dense_layers=1, **kw):
    sizes = dict(vocab_size=VOCAB, d_model=32, layer_kinds=kinds,
                 dense_layers=dense_layers, num_heads=8, num_kv_heads=2,
                 window_kv_heads=4, head_dim=12, v_head_dim=8, rotary_dim=4,
                 rope_theta=1e7, window_rope_theta=1e4, window=WINDOW,
                 value_scale=0.707, dense_dim=48, num_experts=16, top_k=4,
                 held_experts=held, expert_dim=16, dtype="float32")
    sizes.update(kw)
    return WindowMoELM(**sizes)


def dims(m, held=None):
    return dict(num_heads=m.num_heads,
                kv_heads={"attention": m.num_kv_heads,
                          "window": m.window_kv_heads},
                head_dim=m.head_dim, v_head_dim=m.v_head_dim,
                rotary_dim=m.rotary_dim,
                rope_theta={"attention": m.rope_theta,
                            "window": m.window_rope_theta},
                window=m.window, value_scale=m.value_scale,
                dense_layers=m.dense_layers, top_k=m.top_k,
                held=list(held or m.held_experts), expert_dim=m.expert_dim,
                eps=m.rms_eps, kinds=list(m.layer_kinds))


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=128, page_size=PAGE), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def served_vs_reference(eng, model, weights, prompts, n_new=5):
    """Worst |dlogit| over the prompts' prefill and decode positions,
    the reference given the server's own tokens (its own routing)."""
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = 0.0
    n_moe = model.num_layers - model.dense_layers
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=300)
        got = np.stack(r.logits_trace)
        seq = jnp.asarray(p + toks[:-1], jnp.int32)
        want, _ = ref.forward_logits(weights, seq, dims(model))
        assert got.shape == (n_new, VOCAB)
        worst = max(worst, float(np.abs(
            got - np.asarray(want)[len(p) - 1:]).max()))
        if n_moe:
            # the recorded routing is the reference's own: prefill rows
            # then one row a step, [positions, expert layers, k]
            routed = r.records["moe_topk"]
            ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
            assert ids.shape == (len(p) + n_new - 1, n_moe, model.top_k)
            _, gap = ref.forward_logits(weights, seq, dims(model),
                                        routing=jnp.asarray(ids))
            assert float(gap.max()) == 0.0
    return worst


@pytest.mark.parametrize("kinds", [("attention",), ("window",), PERIOD],
                         ids=["global", "window", "period"])
def test_prefill_then_decode_matches_the_reference(kinds):
    """Prompts shorter and longer than the window and than the ring."""
    model = make_model(kinds, dense_layers=0 if len(kinds) == 1 else 1)
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 45, 19, 33)]
    with engine(model, weights) as eng:
        assert served_vs_reference(eng, model, weights, prompts) < 5e-5


def test_a_reply_that_wraps_the_ring_more_than_once():
    """70 new tokens through rings of 32 positions: every ring page is
    overwritten at least twice and the logits stay the reference's."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(3))
    rng = np.random.RandomState(4)
    # 14 = -2 mod 8: the second new token opens a page
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (14, 41)]
    before = stat_get("decode_window_pages_recycled")
    with engine(model, weights) as eng:
        assert served_vs_reference(eng, model, weights, prompts, 70) < 1e-4
    # three window layers; the prompt of 14 recycles pages 4..10 of its
    # ring (positions 32..83), the one of 41 pages 6..13 (48..110)
    assert stat_get("decode_window_pages_recycled") - before == 3 * (7 + 8)


def test_paged_kernel_serves_both_kinds_in_interpret_mode():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (13, 37)]
    with engine(model, weights, use_pallas="always", interpret=True) as eng:
        assert served_vs_reference(eng, model, weights, prompts, 12) < 5e-5


@pytest.mark.parametrize("cache_dtype, want", [
    ("float32", (128, 32)), ("bfloat16", (512, 32))])
def test_the_engine_says_which_block_each_kernel_walks(cache_dtype, want):
    """``decode_attn_block_positions`` / ``decode_window_block_positions``:
    four query rows on each of the global layers' two K/V heads walk
    blocks of 512 positions where the pools are bfloat16 and of one lane
    tile where they are float32; the ring of 4 pages is one block either
    way.  The engine's block counters read the function the op reads,
    with the arguments the op's call has."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(5))
    eng = engine(model, weights, max_seq_len=1024, cache_dtype=cache_dtype)
    assert (eng._attn_block, eng._window_block) == want
    assert (stat_get("decode_attn_block_positions"),
            stat_get("decode_window_block_positions")) == want
    cc = eng._cache.config
    assert want == (
        PAGE * pda.pages_per_block(PAGE, cc.pages_per_slot, 2 * 12,
                                   cc.store_dtype, 2 * 8, 2, 4),
        PAGE * pda.pages_per_block(PAGE, RING, 4 * 12, cc.store_dtype,
                                   4 * 8, 4, 2))


def test_blocks_of_512_behind_the_engine_in_interpret_mode():
    """Bfloat16 pools behind the engine, the kernel in the interpreter:
    a prompt of 150 tokens and 10 steps walk one partial block of 512
    positions in the global layers (the table holds two) and the ring in
    the window layers; the logits are those of the engine that gathers
    the same pools."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(5))
    prompt = np.random.RandomState(6).randint(0, VOCAB, 150).tolist()
    traces = []
    for cfg in (dict(use_pallas="always", interpret=True),
                dict(use_pallas="never")):
        with engine(model, weights, max_seq_len=1024,
                    cache_dtype="bfloat16", **cfg) as eng:
            assert eng._attn_block == 512
            r = eng.submit(prompt, max_new_tokens=10, record_logits=True)
            traces.append((r.result(timeout=300), np.stack(r.logits_trace)))
    (toks, got), (want_toks, want) = traces
    assert toks == want_toks
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_a_slots_second_request_sees_none_of_the_firsts_ring():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(7))
    rng = np.random.RandomState(8)
    with engine(model, weights, slots=1) as eng:
        for n in (50, 6, 27):       # one slot: each reuses the last's ring
            p = [rng.randint(0, VOCAB, n).tolist()]
            assert served_vs_reference(eng, model, weights, p, 9) < 5e-5


def test_decode_through_the_ring_equals_a_recompute():
    """Every step's logits against the whole sequence through the
    prefill from scratch: what the ring kept is what a recompute sees."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(9))
    prompt = np.random.RandomState(10).randint(0, VOCAB, 30).tolist()
    with engine(model, weights) as eng:
        r = eng.submit(prompt, max_new_tokens=40, record_logits=True)
        toks = r.result(timeout=300)
        for j in (0, 1, 17, 39):
            again = eng.recompute_logits(prompt + toks[:j])
            np.testing.assert_allclose(r.logits_trace[j], again, atol=2e-5)


def _paged_case(h, hkv, dk, dv, page, ring, lengths, seed,
                dtype="float32"):
    """Pools filled as the cache fills them (logical page j at ring
    entry j % ring), never-written pages poisoned with NaN; returns the
    kernel's arguments (the pools in ``dtype``) and the K/V in
    logical order, float32 arrays of the values the pools hold."""
    rng = np.random.RandomState(seed)
    s, t = len(lengths), max(lengths)
    kfull = rng.randn(s, t, hkv, dk).astype(dtype).astype(np.float32)
    vfull = rng.randn(s, t, hkv, dv).astype(dtype).astype(np.float32)
    table = (1 + np.arange(s * ring, dtype=np.int32)).reshape(s, ring)
    kp = np.full((2, 1 + s * ring, page, hkv * dk), np.nan, np.float32)
    vp = np.full((2, 1 + s * ring, page, hkv * dv), np.nan, np.float32)
    for i, n in enumerate(lengths):
        for p in range(n):
            pid = table[i, (p // page) % ring]
            for pool, full in ((kp, kfull), (vp, vfull)):
                if np.isnan(pool[1, pid]).all():
                    pool[1, pid] = 7.0      # a written page holds numbers
                pool[1, pid, p % page] = full[i, p].reshape(-1)
    q = rng.randn(s, h, dk).astype(np.float32)
    return q, kp.astype(dtype), vp.astype(dtype), table, kfull, vfull


def _plain(q, kfull, vfull, lengths, window, sinks):
    s, h, dk = q.shape
    g = h // kfull.shape[2]
    out = np.zeros((s, h, vfull.shape[-1]), np.float32)
    for i, n in enumerate(lengths):
        lo = max(n - window, 0) if window else 0
        for j in range(h):
            sc = kfull[i, lo:n, j // g] @ q[i, j] / np.sqrt(dk)
            if sinks is not None:
                sc = np.concatenate([sc, sinks[j:j + 1]])
            p = np.exp(sc - sc.max())
            p = (p / p.sum())[:n - lo]
            out[i, j] = p @ vfull[i, lo:n, j // g]
    return out


@pytest.mark.parametrize("pool", ["float32", "bfloat16"])
@pytest.mark.parametrize("h, hkv, window, sink, dk, dv", [
    (64, 4, None, False, 24, 16), (64, 8, 128, True, 24, 16),
    (16, 4, 20, False, 24, 16), (8, 8, 20, True, 24, 16),
    (32, 2, None, False, 192, 128), (16, 2, 128, True, 192, 128)],
    ids=["groups16", "groups8_window_sink", "window",
         "ungrouped_window_sink", "mimo_lanes_global",
         "mimo_lanes_window_sink"])
def test_kernel_with_wider_keys_a_window_and_sinks_in_interpret_mode(
        h, hkv, window, sink, dk, dv, pool):
    """K heads of 24 lanes over V heads of 16 (and MiMo's 192 over 128:
    stacks cut at lane tiles), groups of 16 and 8 query heads a K/V
    head, the window read off a ring that has wrapped, never-written
    pages NaN; the kernel and the gather reference against plain numpy.
    Bfloat16 pools take the other feed (blocks as they lie, the
    float32 side as groups of rows) and hold the same tolerance."""
    page = 16
    ring = -(-window // page) + 1 if window else 12
    lengths = [5, 190, 64, 131]
    q, kp, vp, table, kfull, vfull = _paged_case(
        h, hkv, dk, dv, page, ring, lengths, seed=h + hkv, dtype=pool)
    sinks = np.random.RandomState(1).randn(h).astype(np.float32) \
        if sink else None
    want = _plain(q, kfull, vfull, lengths, window, sinks)
    kw = dict(layer=1, window=window,
              sinks=None if sinks is None else jnp.asarray(sinks))
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(lengths, jnp.int32))
    got = pda.paged_decode_attention(*args, use_pallas="always",
                                     interpret=True, **kw)
    assert got.shape == (len(lengths), h, dv)
    np.testing.assert_allclose(got, want, atol=3e-5)
    # the gather reads whole pages: no poison for it
    clean = (args[0], jnp.nan_to_num(args[1], nan=3.0),
             jnp.nan_to_num(args[2], nan=3.0)) + args[3:]
    via_ref = pda.paged_decode_attention(*clean, use_pallas="never", **kw)
    np.testing.assert_allclose(via_ref, want, atol=3e-5)


@pytest.mark.parametrize("hkv, window, sink, ring, lengths, ppb", [
    (2, None, False, 20, [5, 300, 256, 0, 319], 16),
    (2, None, False, 70, [600, 512, 1040, 0, 17], 32),
    (2, 300, True, None, [700, 40, 0, 1290, 256], 16),
    (2, 900, True, None, [1500, 0, 2300, 900, 530], 32),
    (4, 900, False, None, [1033, 2047], 32)],
    ids=["blocks_of_256", "blocks_of_512", "window_ring_wraps_in_256",
         "window_ring_wraps_in_512", "two_stacks_window_512"])
def test_the_bf16_body_at_blocks_of_several_lane_tiles_in_interpret_mode(
        hkv, window, sink, ring, lengths, ppb):
    """Sixteen query rows stacked on a K/V head of 192 lanes over values
    of 128 (MiMo's global rows), bfloat16 pools: the rule gives blocks
    of 256 positions where the table holds 20 pages and of 512 where it
    holds more.  A slot whose last block is partial (and one that ends
    on a block's edge), a dead slot, a window whose first block is
    partial and whose ring (20 and 58 entries, no multiple of the block)
    wraps INSIDE a block, a sink; never-written pages and the
    interpreter's fresh buffers read NaN, so a dead page of a larger
    block that reached ``p @ v`` would show.  Against
    ``decode_attention_reference`` on the values the pools hold."""
    from jax.experimental.pallas import tpu as pltpu

    h, dk, dv, page = 16 * hkv, 192, 128, 16
    if ring is None:
        ring = -(-window // page) + 1
    assert pda.pages_per_block(page, ring, hkv * dk, jnp.bfloat16,
                               hkv * dv, hkv, 16) == ppb
    assert ring % ppb                   # the table is no whole blocks
    q, kp, vp, table, kfull, vfull = _paged_case(
        h, hkv, dk, dv, page, ring, lengths, seed=ring, dtype="bfloat16")
    sinks = jnp.asarray(np.random.RandomState(2).randn(h), jnp.float32) \
        if sink else None
    lens = jnp.asarray(lengths, jnp.int32)
    got = pda.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), lens, layer=1, use_pallas="always",
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"),
        window=window, sinks=sinks)
    assert got.shape == (len(lengths), h, dv)
    assert bool(jnp.isfinite(got).all())
    k, v = (jnp.repeat(jnp.asarray(x), 16, axis=2) for x in (kfull, vfull))
    want = pda.decode_attention_reference(
        jnp.asarray(q), k, v, lens, window=window,
        sinks=None if sinks is None else jnp.broadcast_to(
            sinks, (len(lengths), h)))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)


def test_the_window_call_has_its_own_name_and_walks_two_blocks_at_most():
    """A trace tells the window layers' kernel from the global layers'
    by name; its table is the ring, however long the slot."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(11))
    eng = engine(model, weights, use_pallas="always", interpret=True)
    text = eng.lower_step().as_text(debug_info=True)
    assert "window_attention" in text and "rope" in text \
        and "dense_ffn" in text
    names = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.add((eqn.params["name"],
                           eqn.invars[1].aval.shape[0]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    state = tuple(eng._scope.get_var(n) for n in eng._state_vars)
    walk(jax.make_jaxpr(eng._step_fn)(
        state, eng.weights, eng._step_args(()), eng._no_tokens).jaxpr)
    # (name, flat page-table words): 3 slots x 16 pages, 3 slots x ring
    assert names == {("paged_attention", 3 * 16),
                     ("paged_attention_window", 3 * RING)}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips hold one expert each of one 16-expert layer: their
    routed parts (there is no shared expert) are what the reference
    gives for the whole layer."""
    whole = make_model(("attention",), held=tuple(range(16)),
                       dense_layers=0)
    lw = whole.init_weights(jax.random.PRNGKey(12))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(13), (24, 32))
    want, _ = ref.moe_layer(lw, x, dims(whole))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * lw["norm2"]
    total, f = jnp.zeros_like(x), 16
    for chip in range(16):
        cols = slice(chip * f, (chip + 1) * f)
        _, _, local = moe_ops.moe_share_route(
            h, lw["moe_router"], lw["moe_router_bias"], top_k=4,
            held_ids=(chip,))
        part = moe_ops.moe_share_ffn(
            h, local, lw["moe_w_gate"][:, cols], lw["moe_w_up"][:, cols],
            lw["moe_w_down"][cols])
        # the reference given the same share
        share = {**lw, "moe_w_gate": lw["moe_w_gate"][:, cols],
                 "moe_w_up": lw["moe_w_up"][:, cols],
                 "moe_w_down": lw["moe_w_down"][cols]}
        ref_part, _ = ref.moe_layer(share, x, dims(whole), held=[chip])
        np.testing.assert_allclose(part, ref_part - x, atol=1e-4)
        total = total + part
    np.testing.assert_allclose(x + total, want, atol=1e-4)


@pytest.mark.parametrize("max_seq_len", [128, 512])
def test_a_window_layer_never_holds_more_than_its_ring(max_seq_len):
    """Churn: requests of every length through three slots; the rings'
    pools have the size the slots give them whatever ``max_seq_len``
    is, the gauge never passes slots x layers x ring, and the books
    balance after every request."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(14))
    rng = np.random.RandomState(15)
    with engine(model, weights, max_seq_len=max_seq_len) as eng:
        cache = eng._cache
        shapes = [tuple(eng._scope.get_var(n).shape)
                  for n in cache.window_var_names()]
        # 3 window layers, 3 slots x 4 pages + trash, 4 K/V heads
        assert shapes == [(3, 13, PAGE, 4 * 12), (3, 13, PAGE, 4 * 8)]
        assert cache.window_bytes() == 3 * 13 * PAGE * 4 * (12 + 8) * 4
        reqs = [eng.submit(rng.randint(0, VOCAB, int(n)).tolist(),
                           max_new_tokens=int(m))
                for n, m in zip(rng.randint(1, 60, 10),
                                rng.randint(1, 50, 10))]
        worst = 0
        for r in reqs:
            r.result(timeout=300)
            worst = max(worst, stat_get("decode_window_pages_held"))
        assert 0 < worst <= 3 * 3 * RING
    cache.debug_check()
    assert cache.window_pages_held() == 0
    assert cache.allocator.num_free == cache.config.num_pages - 1


def test_the_prefill_leaves_only_the_windows_tail_in_the_ring():
    """A 45-token prompt in a ring of 4 pages: logical pages 2..5 are
    kept (positions 16..44 and the padding behind them), page j at ring
    entry j % 4; slot 1's ring and the trash page's neighbours stay
    clean."""
    model = make_model(("window",), dense_layers=0)
    weights = model.init_weights(jax.random.PRNGKey(16))
    prompt = np.random.RandomState(17).randint(0, VOCAB, 45).tolist()
    with engine(model, weights, slots=2) as eng:
        eng.submit(prompt, max_new_tokens=1).result(timeout=300)
        k = np.asarray(eng._scope.get_var(kv_cache.WINDOW_K_VAR))
    assert k.shape == (1, 2 * RING + 1, PAGE, 4 * 12)
    assert np.abs(k[0, 1:1 + RING]).min(axis=(1, 2)).max() > 0
    assert not k[0, 1 + RING:].any()        # slot 1 never was written
    # entry 1 holds logical page 5 (positions 40..47), entry 2 page 2
    d = dims(model)
    h = ref._rms(ref._f32(weights["tok_emb"][jnp.asarray(prompt)]),
                 weights["layers"][0]["norm1"], d["eps"])
    keys = ref._rotary((h @ weights["layers"][0]["wk"]).reshape(45, 4, 12),
                       1e4, 4).reshape(45, 48)
    np.testing.assert_allclose(k[0, 1 + 5 % RING, :5], keys[40:45],
                               atol=1e-5)
    np.testing.assert_allclose(k[0, 1 + 2 % RING], keys[16:24], atol=1e-5)


@pytest.mark.parametrize("cfg, names", [
    (dict(prefill_chunk_pages=1), "window layers.*chunked prefill"),
    (dict(spec_k=2), "window layers.*speculative decoding"),
    (dict(kv_quant=True), "window layers.*kv_quant"),
], ids=["chunked", "speculative", "kv_quant"])
def test_what_cannot_hold_over_a_ring_refuses_by_kind_and_name(cfg, names):
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(18))
    with pytest.raises(ValueError, match=names):
        engine(model, weights, **cfg)


def test_a_draft_model_and_the_disaggregated_hand_over_refuse():
    from paddle_tpu.serving.decode import TransformerLM
    from paddle_tpu.serving.disagg import DisaggServer

    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(19))
    draft = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=128)
    cfg = DecodeConfig(slots=2, max_seq_len=128, page_size=PAGE)
    with pytest.raises(ValueError, match="window.*speculative decoding"):
        DecodeEngine(model, weights, cfg, draft_model=draft,
                     draft_weights=draft.init_weights(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="disaggregated.*window layers"):
        DisaggServer(model, weights, config=cfg)
    eng = engine(model, weights)
    with pytest.raises(ValueError, match="extract_kv.*window layers"):
        eng.submit([1, 2, 3], max_new_tokens=2, extract_kv=True)
    with pytest.raises(ValueError, match="window layers exports no pages"):
        eng._cache.export_pages([1])
    with pytest.raises(ValueError, match="layer_kinds holds"):
        model.layer_kinds = ("attention", "conv")
        engine(model, weights)


def test_every_request_is_admitted_fresh_and_the_ring_costs_no_upload():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(20))
    prompt = list(range(1, 25))
    names = ("decode_prefix_bypassed", "decode_prefix_pages_hit",
             "moe_experts_hit", "decode_steps", "decode_h2d_uploads",
             "decode_h2d_bytes", "decode_prefills",
             "decode_window_blocks_walked", "decode_window_positions_live")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights) as eng:
        assert eng._cache.prefix is None and eng._cache.prefix_bypassed
        first = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        # the same prompt again: a prefix cache would skip its prefill
        again = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        assert first == again
        step_words = eng._step_args(()).nbytes
        prefill_words = eng._prefill_args(32, prompt).nbytes  # its bucket
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_prefix_bypassed"] == 2 and d["decode_prefills"] == 2
    assert d["decode_prefix_pages_hit"] == 0
    # one upload a step and one a prefill, of the size a model without
    # window layers uploads: the page-table row a slot and nothing for
    # the ring
    assert d["decode_h2d_uploads"] == d["decode_steps"] + 2
    assert step_words == 3 * 4 * (9 + 128 // PAGE)
    assert d["decode_h2d_bytes"] == d["decode_steps"] * step_words \
        + 2 * prefill_words
    # each of the 10 steps attends 25..29 positions, 20 of them in the
    # window, in one block of the ring
    assert d["decode_steps"] == 10
    assert d["decode_window_positions_live"] == 10 * WINDOW
    assert d["decode_window_blocks_walked"] == 10
    assert 0 < d["moe_experts_hit"] <= 10 * 4 * 5
    assert stat_get("decode_window_bytes") == eng._cache.window_bytes()


@pytest.mark.parametrize("length, bucket", [(11, 16), (37, 64)])
def test_a_prompts_head_forms_the_one_row_the_engine_reads(
        length, bucket, monkeypatch):
    """The whole-prompt prefill names the row it reads and the model
    hands back ``[1, V]`` (``blocks.head_logits``, PR 62).  Rows inside
    a short and a longer bucket: tokens and recorded logits are those
    of the form that made every row's; the joint step makes every slot's
    as ever."""
    import sys

    from prompt_head_forms import the_read_row_is_the_every_row_forms

    the_read_row_is_the_every_row_forms(
        sys.modules[__name__], length, bucket, monkeypatch)
