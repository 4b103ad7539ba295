"""The model whose attention reads the positions a learned indexer
selects, with a held share of its experts (``serving/indexed_moe_lm.py``)
behind the real ``DecodeEngine``, against the plain reference
(``benchmark/reference/indexed_moe_lm.py``, the one the cell's check
uses): float32, seeded, tiny."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import indexed_attention as ixa
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.indexed_moe_lm import IndexedMoELM
from paddle_tpu.serving.mixers import INDEX_RECORD

from benchmark.reference import indexed_moe_lm as ref

VOCAB, PAGE, TOPK = 97, 8, 12


def make_model(held=(0, 1, 2, 3, 4), **kw):
    """Keye-VL-2.0's language block in small: two layers, 4 heads on 2
    K/V heads of 8, an indexer of 3 heads of 6 that keeps 12 positions a
    query, prompt blocks of 4 rows, 16 experts top-4."""
    sizes = dict(vocab_size=VOCAB, d_model=32, num_layers=2, num_heads=4,
                 num_kv_heads=2, head_dim=8, index_heads=3, index_dim=6,
                 index_topk=TOPK, index_block=4, num_experts=16, top_k=4,
                 held_experts=held, expert_dim=16, rope_theta=1e4,
                 dtype="float32")
    sizes.update(kw)
    return IndexedMoELM(**sizes)


def dims(m, held=None, block=8):
    return dict(num_heads=m.num_heads, num_kv_heads=m.num_kv_heads,
                head_dim=m.head_dim, index_heads=m.index_heads,
                index_dim=m.index_dim, topk=m.index_topk,
                rope_theta=m.rope_theta, top_k=m.top_k,
                held=list(held or m.held_experts), expert_dim=m.expert_dim,
                eps=m.rms_eps, block=block)


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=128, page_size=PAGE), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def selections_of(req, n_prompt, n):
    """[layers, n, n] bool: what the served model attended a row a
    layer, from a request's records (the prompt's entry a bit a pair,
    all zeros for a row under ``index_topk``, which attends every live
    position; a step's entry the positions)."""
    rec = req.records[INDEX_RECORD]
    layers = rec[0].shape[1]
    out = np.zeros((layers, n, n), bool)
    bits = ixa.unpack_bits(rec[0])                 # [n_prompt, L, bucket]
    for t in range(n_prompt):
        for l in range(layers):
            out[l, t, :t + 1] = bits[t, l, :t + 1] if bits[t, l].any() \
                else True
    for j, step in enumerate(rec[1:]):
        t = n_prompt + j
        if t >= n:
            break
        for l in range(layers):
            out[l, t, step[l][step[l] >= 0]] = True
    return out


def padded(seq, block=8):
    out = np.zeros((-(-len(seq) // block) * block,), np.int32)
    out[:len(seq)] = seq
    return out


def served_vs_reference(eng, model, weights, prompts, n_new):
    """Worst |dlogit| over the prompts' prefill and decode positions;
    the served selections must BE the reference's own, position for
    position."""
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = 0.0
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=300)
        got = np.stack(r.logits_trace)
        assert got.shape == (n_new, VOCAB)
        n = len(p) + n_new - 1
        seq = padded(p + toks[:-1])
        routed = r.records["moe_topk"]
        ids = np.zeros((len(seq), model.num_layers, model.top_k), np.int32)
        ids[:n] = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
        sel = np.zeros((model.num_layers, len(seq), len(seq)), bool)
        sel[:, :n, :n] = selections_of(r, len(p), n)
        sel[:, n:, 0] = True                        # padding rows: one key
        want, gap, sgap, moved = ref.forward_logits(
            weights, jnp.asarray(seq), dims(model),
            routing=jnp.asarray(ids), selections=jnp.asarray(sel))
        assert float(gap[:n].max()) == 0.0
        assert float(sgap[:n].max()) == 0.0 and int(moved[:n].max()) == 0
        worst = max(worst, float(np.abs(
            got - np.asarray(want)[len(p) - 1:n]).max()))
    return worst


def test_prefill_then_decode_through_the_index_pool_matches_the_reference():
    """Prompts under and over ``index_topk``, replies that cross it, a
    page (8) and a block of the prompt's selection (4 rows); the prompt's
    form and the step's against the reference's definition, the selection
    position for position.  Every request is admitted fresh, and the
    third pool is the keys' size at whole lane tiles."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 37)]
    with engine(model, weights) as eng:
        cache = eng._cache
        assert cache.prefix is None and cache.prefix_bypassed
        assert cache.state_var_names() == (
            "__decode_k_pages__", "__decode_v_pages__",
            "__decode_index_pages__")
        assert stat_get("decode_index_bytes") == cache.index_bytes() \
            == 2 * (3 * 16 + 1) * PAGE * 128 * 4
        assert served_vs_reference(eng, model, weights, prompts, 14) < 5e-5


def test_slots_admitted_at_different_steps_select_their_own_positions():
    """Three requests of different lengths, the third admitted while the
    others decode: each row's selection is the reference's (every live
    position and no other while there are no more than ``index_topk``),
    and the counters say what a step scored and selected."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(3))
    rng = np.random.RandomState(4)
    names = ("decode_index_positions_scored",
             "decode_index_positions_selected")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights) as eng:
        first = [eng.submit(rng.randint(0, VOCAB, n).tolist(),
                            max_new_tokens=20, record_logits=True)
                 for n in (3, 30)]
        list(first[0].tokens(timeout=300))[:1]      # the others decode
        late = rng.randint(0, VOCAB, 9).tolist()
        assert served_vs_reference(eng, model, weights, [late], 9) < 5e-5
        for r in first:
            r.result(timeout=300)
        short = first[0]
    # a context under index_topk: every live position, and no other
    for j, step in enumerate(short.records[INDEX_RECORD][1:8]):
        t = 3 + j
        for l in range(model.num_layers):
            assert sorted(step[l][step[l] >= 0]) == list(range(t + 1))
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert 0 < d[names[1]] < d[names[0]]


def test_a_recycled_pages_stale_index_rows_are_never_selected():
    """One slot, a pool of few pages: a long request writes index keys
    into every page it held; the short request that takes its pages over
    selects positions under its own length only (the stale rows lie past
    it, masked before the selection), and reads the reference's logits."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    with engine(model, weights, slots=1, max_seq_len=64) as eng:
        cache = eng._cache
        eng.submit(rng.randint(0, VOCAB, 40).tolist(),
                   max_new_tokens=20).result(timeout=300)
        assert cache.allocator.num_free == cache.config.num_pages - 1
        stale = np.asarray(cache.scope.get_var("__decode_index_pages__"))
        assert np.abs(stale[:, 1:]).sum() > 0       # nothing is cleared
        p = rng.randint(0, VOCAB, 14).tolist()
        r = eng.submit(p, max_new_tokens=10, record_logits=True)
        toks = r.result(timeout=300)
        for j, step in enumerate(r.records[INDEX_RECORD][1:]):
            chosen = step[step >= 0]
            assert chosen.size and chosen.max() <= len(p) + j
        seq = padded(p + toks[:-1])
        want = ref.forward_logits(weights, jnp.asarray(seq), dims(model))[0]
        assert float(np.abs(np.stack(r.logits_trace) - np.asarray(want)[
            len(p) - 1:len(p) + 9]).max()) < 5e-5
    cache.debug_check()


def test_the_index_pool_is_claimed_and_released_with_the_pages():
    """The third pool has the K/V pools' page ids: one table, one free
    list; its bytes are the stored rows' (whole lane tiles, said by the
    shape), and a cache without an indexer has no such pool."""
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.serving.kv_cache import (CacheConfig, IndexSpec,
                                             PagedKVCache)

    cfg = CacheConfig(2, 2, 8, 3, 64, PAGE, dtype="bfloat16")
    cache = PagedKVCache(cfg, Scope(), index=IndexSpec(2, 6))
    pool = cache.scope.get_var("__decode_index_pages__")
    assert pool.shape == (2, cfg.num_pages, PAGE, 128)
    assert cache.index_bytes() == 2 * cfg.num_pages * PAGE * 128 * 2
    assert cache.prefix is None and cache.prefix_bypassed
    free = cache.allocator.num_free
    cache.claim(0, 20)
    assert cache.allocator.num_free == free - 3
    cache.release(0)
    assert cache.allocator.num_free == free
    plain = PagedKVCache(cfg, Scope())
    assert plain.index_bytes() == 0 and plain.index_var_names() == ()
    assert plain.prefix is not None
    with pytest.raises(ValueError, match="index pool.*kv_quant"):
        PagedKVCache(CacheConfig(2, 2, 8, 3, 64, PAGE, quantized=True),
                     Scope(), index=IndexSpec(2, 6))


@pytest.mark.parametrize("cfg, names", [
    (dict(prefill_chunk_pages=1), "index pool.*chunked prefill"),
    (dict(spec_k=2), "index pool.*speculative decoding"),
    (dict(kv_quant=True), "index pool.*kv_quant"),
], ids=["chunked", "speculative", "kv_quant"])
def test_what_is_not_built_for_an_index_pool_refuses_by_name(cfg, names):
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
                          num_heads=2, max_seq_len=128)
    cfg = DecodeConfig(slots=2, max_seq_len=128, page_size=PAGE)
    with pytest.raises(ValueError, match="index pool.*speculative"):
        DecodeEngine(model, weights, cfg, draft_model=draft,
                     draft_weights=draft.init_weights(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="disaggregated.*index pool"):
        DisaggServer(model, weights, config=cfg)
    eng = engine(model, weights)
    with pytest.raises(ValueError, match="extract_kv.*index pool"):
        eng.submit([1, 2, 3], max_new_tokens=2, extract_kv=True)
    with pytest.raises(ValueError, match="index pool exports no pages"):
        eng._cache.export_pages([1])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of one 16-expert layer: their
    routed parts are what the reference gives for the whole layer (a
    softmax router over all 16, the top-4 renormalised, nothing beside
    the experts)."""
    from paddle_tpu.ops import moe_ops

    whole = make_model(held=tuple(range(16)), num_layers=1)
    lw = whole.init_weights(jax.random.PRNGKey(12))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(13), (24, 32))
    want, _ = ref.moe_layer(lw, x, dims(whole))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + whole.rms_eps) * lw["norm2"]
    f = 16

    @jax.jit
    def share(chip):
        cut = lambda m, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            m, chip * 2 * f, 2 * f, axis)
        held = chip * 2 + jnp.arange(2)
        _, _, local = moe_ops.moe_share_route(
            h, lw["moe_router"], lw["moe_router_bias"], top_k=4,
            held_ids=held, scoring="softmax")
        part = moe_ops.moe_share_ffn(
            h, local, cut(lw["moe_w_gate"], 1), cut(lw["moe_w_up"], 1),
            cut(lw["moe_w_down"], 0))
        mine = {**lw, "moe_w_gate": cut(lw["moe_w_gate"], 1),
                "moe_w_up": cut(lw["moe_w_up"], 1),
                "moe_w_down": cut(lw["moe_w_down"], 0)}
        return part, ref.moe_layer(mine, x, dims(whole), held=held)[0] - x

    total = jnp.zeros_like(x)
    for chip in range(8):
        part, ref_part = share(jnp.int32(chip))
        np.testing.assert_allclose(part, ref_part, atol=1e-4)
        total = total + part
    np.testing.assert_allclose(x + total, want, atol=2e-4)


class _Forms:
    """``attend`` as the indexer's two forms see it, with no engine: the
    selection a form reaches is kept instead of attended."""

    def __init__(self):
        self.records, self.select = {}, None

    def record(self, name, rows):
        self.records[name] = rows

    def causal(self, q, k, v, length, select=None):
        self.select = select
        return jnp.zeros(q.shape, jnp.float32)


def test_the_steps_and_the_prompts_forms_choose_the_same_positions():
    """One sequence's rows through the prompt form (blocks of 4 rows, a
    mask a pair) and, a row at a time, through the step form (the
    positions): the same set a row, with index keys repeated on purpose
    so that scores tie and the lower position has to win in both."""
    model = make_model()
    t, hi, di = 40, model.index_heads, model.index_dim
    rng = np.random.RandomState(7)
    qi = jnp.asarray(rng.randn(t, hi, di), jnp.float32)
    w = jnp.asarray(rng.randn(t, hi), jnp.float32)
    keys = rng.randn(t, di).astype(np.float32)
    keys[[3, 9, 20, 31]] = keys[2]              # five positions tie
    keys[[15, 16]] = keys[14]
    keys = jnp.asarray(keys)
    zeros = jnp.zeros((t, model.num_heads, model.head_dim))
    kv = jnp.zeros((t, model.num_kv_heads, model.head_dim))
    prompt = _Forms()
    model._index_prompt(prompt, qi, w, zeros, kv, kv, keys, jnp.int32(t))
    chosen = np.asarray(prompt.select) != 0
    bits = ixa.unpack_bits(prompt.records[INDEX_RECORD])
    assert chosen[:TOPK].all() and not bits[:TOPK].any()
    tied = 0
    for row in range(TOPK, t):
        step = _Forms()
        where = 100 + jnp.arange(t, dtype=jnp.int32)[None]
        pos, ok, at = model._index_step(
            step, qi[row:row + 1], w[row:row + 1], keys[None],
            jnp.asarray([row + 1], jnp.int32), where)
        np.testing.assert_array_equal(at, pos + 100)
        got = sorted(np.asarray(pos)[0][np.asarray(ok)[0]].tolist())
        assert got == np.flatnonzero(chosen[row, :row + 1]).tolist()
        assert got == np.flatnonzero(bits[row, :row + 1]).tolist()
        assert len(got) == TOPK
        tied += (2 in got) != (3 in got) and row >= 9
    assert tied                 # some row's cut fell among the equal keys


def test_the_prompts_flash_kernel_runs_under_the_selection_interpreted():
    """Heads of whole lane tiles, a prompt in the 256 bucket: the
    whole-prompt prefill takes the flash kernel with the int8 mask a
    pair; the logits are the reference's."""
    model = make_model(head_dim=128, index_topk=32, index_block=32)
    weights = model.init_weights(jax.random.PRNGKey(8))
    p = np.random.RandomState(9).randint(0, VOCAB, 150).tolist()
    with engine(model, weights, max_seq_len=256, use_pallas="always",
                interpret=True) as eng:
        assert eng._prefill_walks(256)[0][2][0] == "flash"
        assert served_vs_reference(eng, model, weights, [p], 3) < 1e-4
