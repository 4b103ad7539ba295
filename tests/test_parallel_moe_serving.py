"""The parallel-block model with window and position-free global layers,
a held share of its experts, averaged shared experts and a tied head
(``serving/parallel_moe_lm.py``) behind the real ``DecodeEngine``,
against the plain reference (``benchmark/reference/parallel_moe_lm.py``,
the one the cell's check uses): float32, seeded, tiny."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.blocks import rms_norm
from paddle_tpu.serving.parallel_moe_lm import ParallelMoELM

from benchmark.reference import parallel_moe_lm as ref

# Command A+'s period in small: three window layers to a global one
PERIOD = ("window", "window", "window", "attention")
VOCAB, WINDOW, PAGE = 97, 20, 8
RING = 4                    # ceil(20 / 8) + 1 pages = 32 positions


def make_model(kinds=PERIOD, held=(0, 1, 2, 3, 4), cls=ParallelMoELM, **kw):
    sizes = dict(vocab_size=VOCAB, d_model=32, layer_kinds=kinds,
                 num_heads=8, num_kv_heads=2, head_dim=12, rope_theta=5e4,
                 window=WINDOW, num_experts=16, top_k=4, held_experts=held,
                 expert_dim=16, shared_experts=4, shared_dim=16,
                 logit_scale=0.5, dtype="float32")
    sizes.update(kw)
    return cls(**sizes)


def dims(m, held=None):
    return dict(num_heads=m.num_heads, num_kv_heads=m.num_kv_heads,
                head_dim=m.head_dim, rope_theta=m.rope_theta,
                window=m.window, top_k=m.top_k,
                held=list(held or m.held_experts), expert_dim=m.expert_dim,
                shared_experts=m.shared_experts, shared_dim=m.shared_dim,
                eps=m.norm_eps, logit_scale=m.logit_scale,
                kinds=list(m.layer_kinds), row_block=16)


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=128, page_size=PAGE), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def served_vs_reference(eng, model, weights, prompts, n_new=5, d=None):
    """Worst |dlogit| over the prompts' prefill and decode positions,
    the reference (sized by ``d``) given the server's own tokens."""
    d = d or dims(model)
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = 0.0
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=300)
        got = np.stack(r.logits_trace)
        seq = jnp.asarray(p + toks[:-1], jnp.int32)
        want, _ = ref.forward_logits(weights, seq, d)
        assert got.shape == (n_new, VOCAB)
        worst = max(worst, float(np.abs(
            got - np.asarray(want)[len(p) - 1:]).max()))
        # the recorded routing is the reference's own: prefill rows
        # then one row a step, [positions, layers, k]
        routed = r.records["moe_topk"]
        ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
        assert ids.shape == (len(p) + n_new - 1, model.num_layers,
                             model.top_k)
        _, gap = ref.forward_logits(weights, seq, d,
                                    routing=jnp.asarray(ids))
        assert float(gap.max()) == 0.0
    return worst


@pytest.mark.parametrize("kinds", [("attention",), ("window",), PERIOD],
                         ids=["global", "window", "period"])
def test_prefill_then_decode_matches_the_reference(kinds):
    """Prompts shorter and longer than the window and than the ring."""
    model = make_model(kinds)
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 45, 19, 33)]
    with engine(model, weights) as eng:
        assert served_vs_reference(eng, model, weights, prompts) < 5e-5


def test_a_reply_that_wraps_the_ring_more_than_once_and_the_cap_counters():
    """70 new tokens through rings of 32 positions: every ring page is
    overwritten at least twice and the logits stay the reference's; the
    two counters say how many live rows the window capped."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(3))
    rng = np.random.RandomState(4)
    names = ("decode_window_rows", "decode_window_rows_capped",
             "decode_window_pages_recycled", "decode_steps")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights) as eng:
        for n in (14, 41):          # one after the other: 69 steps each
            p = [rng.randint(0, VOCAB, n).tolist()]
            assert served_vs_reference(eng, model, weights, p, 70) < 1e-4
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_steps"] == d["decode_window_rows"] == 2 * 69
    # a step at position p attends p + 1 rows: capped once p + 1 > 20.
    # The prompt of 14 decodes positions 14..82 (63 past 19), the one
    # of 41 is past the window from its first step
    assert d["decode_window_rows_capped"] == 63 + 69
    assert d["decode_window_pages_recycled"] == 3 * (7 + 8)


def test_paged_kernel_serves_both_kinds_in_interpret_mode():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (13, 37)]
    with engine(model, weights, use_pallas="always", interpret=True) as eng:
        assert served_vs_reference(eng, model, weights, prompts, 12) < 5e-5


def test_decode_through_the_ring_equals_a_recompute():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(9))
    prompt = np.random.RandomState(10).randint(0, VOCAB, 30).tolist()
    with engine(model, weights) as eng:
        r = eng.submit(prompt, max_new_tokens=40, record_logits=True)
        toks = r.result(timeout=300)
        for j in (0, 17, 39):
            again = eng.recompute_logits(prompt + toks[:j])
            np.testing.assert_allclose(r.logits_trace[j], again, atol=2e-5)


# -- the kernel on bfloat16 pools, at the served models' rows ---------------

def _bf16_pools(hkv, d, page, table_pages, lengths, window, seed):
    """Bfloat16 pools filled as the cache fills them (logical page j of a
    slot at table entry ``j % table_pages``: a ring that wraps once the
    slot outgrows it), every position never written NaN (dead pages,
    and the last live page past the slot's length); beside them the K/V
    in logical order, float32 arrays of the values the pools hold."""
    rng = np.random.RandomState(seed)
    s, t = len(lengths), max(lengths)
    full = [rng.randn(s, t, hkv, d).astype(jnp.bfloat16)
            .astype(np.float32) for _ in range(2)]
    table = (1 + np.arange(s * table_pages, dtype=np.int32)) \
        .reshape(s, table_pages)
    pools = [np.full((2, 1 + s * table_pages, page, hkv * d), np.nan,
                     np.float32) for _ in range(2)]
    for i, n in enumerate(lengths):
        for pos in range(n):
            pid = table[i, (pos // page) % table_pages]
            for pool, x in zip(pools, full):
                pool[1, pid, pos % page] = x[i, pos].reshape(-1)
    return [jnp.asarray(x, jnp.bfloat16) for x in pools], table, full


@pytest.mark.parametrize("hq, hkv, rows, window", [
    (32, 2, 1, None), (32, 2, 1, 100), (16, 2, 1, None), (8, 2, 3, None)],
    ids=["16_rows_a_kv_head", "16_rows_over_a_wrapped_ring",
         "8_rows_a_kv_head", "3_ragged_query_rows"])
def test_kernel_on_bfloat16_pools_against_the_float32_reference(
        hq, hkv, rows, window):
    """Command A+'s 16 query rows a K/V head of 128 lanes (a global
    table, and a window read off a ring that has wrapped three times),
    Solar's 8 rows, and three query rows of ragged causal lengths a
    slot: the blocks go to the matmuls as bfloat16, the float32 query
    and probabilities as three groups of bfloat16 rows, and the result
    is the float32 reference's at the tolerance float32 pools are held
    to.  Never-written positions are NaN: dead pages of a slot's last
    block and of a window's first, and the last live page's positions
    past the slot's length, must not reach the output."""
    d, page = 128, 16
    lengths = [9, 401, 128, 250]
    table_pages = -(-window // page) + 1 if window else 26
    (kp, vp), table, (kfull, vfull) = _bf16_pools(
        hkv, d, page, table_pages, lengths, window, seed=hq + rows)
    s, g = len(lengths), hq // hkv
    q = jnp.asarray(np.random.RandomState(3).randn(s, rows, hq, d),
                    jnp.float32)
    # row r of a slot is causal: it attends rows - 1 - r positions fewer
    row_lengths = np.maximum(
        np.asarray(lengths)[:, None] - np.arange(rows - 1, -1, -1), 1)
    got = pda.paged_chunk_attention(
        q, kp, vp, jnp.asarray(table), jnp.asarray(row_lengths, jnp.int32),
        layer=1, use_pallas="always", interpret=True, window=window)
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    k, v = (jnp.repeat(jnp.asarray(x), g, axis=2) for x in (kfull, vfull))
    for r in range(rows):
        want = pda.decode_attention_reference(
            q[:, r], k, v, jnp.asarray(row_lengths[:, r], jnp.int32),
            window=window)
        np.testing.assert_allclose(got[:, r], want, rtol=2e-5, atol=2e-5)


# -- what must fail: each departure served, the reference as it is ----------

class _Sequential(ParallelMoELM):
    """Attention first, the feed-forward on ITS result."""

    def forward(self, weights, tokens, positions, cache, attend):
        x = weights["tok_emb"][tokens].astype(jnp.float32)
        for l, lw in enumerate(weights["layers"]):
            a, cache = self._attention(l, lw, self._norm(x, lw["norm"]),
                                       positions, cache, attend)
            x = x + a
            x = x + self._feed_forward(lw, self._norm(x, lw["norm"]), attend)
        return self._head(weights, x), cache


class _HalfSplit(ParallelMoELM):
    """Lane j pairs with lane j + D/2, at pair j's frequency."""

    def _rotary(self, positions):
        half = self.head_dim // 2
        freq = self.rope_theta ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        angle = positions.astype(jnp.float32)[..., None, None] * freq
        return jnp.cos(angle), jnp.sin(angle)

    def _rotate(self, x, cos, sin):
        half = self.head_dim // 2
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class _MeanKept(ParallelMoELM):
    def _norm(self, x, g):
        return rms_norm(x, g, self.norm_eps)


class _Untied(ParallelMoELM):
    """A head matrix of its own (seeded) instead of the embedding."""

    def _head(self, weights, x):
        other = jax.random.normal(jax.random.PRNGKey(99),
                                  weights["tok_emb"].shape)
        return super()._head(dict(weights, tok_emb=other), x)


def _rotate_global(m):
    m.rotary_kinds = ("window", "attention")


def _sum_shared(m):
    m.shared_experts = 1        # the divisor: the four outputs are summed


@pytest.mark.parametrize("cls, change", [
    (ParallelMoELM, _rotate_global), (_HalfSplit, None), (_Sequential, None),
    (ParallelMoELM, _sum_shared), (_MeanKept, None), (_Untied, None)],
    ids=["rotary_in_a_global_layer", "half_split_pairing",
         "sequential_block", "shared_experts_summed", "mean_not_subtracted",
         "untied_head"])
def test_a_departure_from_the_equations_fails_the_comparison(cls, change):
    model = make_model(PERIOD, cls=cls)
    weights = model.init_weights(jax.random.PRNGKey(21))
    d = dims(model)
    if change is not None:
        change(model)
    # nonzero mean rows, so that subtracting it shows
    weights["tok_emb"] = weights["tok_emb"] + 0.5
    prompts = [np.random.RandomState(22).randint(0, VOCAB, 33).tolist()]
    with engine(model, weights) as eng:
        reqs = [eng.submit(p, max_new_tokens=4, record_logits=True)
                for p in prompts]
        toks = reqs[0].result(timeout=300)
    want, _ = ref.forward_logits(
        weights, jnp.asarray(prompts[0] + toks[:-1], jnp.int32), d)
    got = np.stack(reqs[0].logits_trace)
    err = np.abs(got - np.asarray(want)[len(prompts[0]) - 1:]).max()
    assert err > 1e-2, err


# -- the whole-prompt prefill's attention, in blocks of query rows ----------

def _unblocked(q, k, v, window, sinks):
    """The formula over the whole ``T x T`` score tensor."""
    t, h, d = q.shape
    hkv = k.shape[1]
    s = jnp.einsum("thgd,uhd->hgtu", q.reshape(t, hkv, h // hkv, d), k) \
        / math.sqrt(d)
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask = mask & (pos[None, :] > pos[:, None] - window)
    s = jnp.where(mask, s, -jnp.inf)
    if sinks is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sinks.reshape(hkv, -1, 1, 1), s.shape[:-1] + (1,))], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    if sinks is not None:
        p = p[..., :-1]
    return jnp.einsum("hgtu,uhd->thgd", p, v).reshape(t, h, v.shape[-1])


@pytest.mark.parametrize("window, sink", [
    (None, False), (None, True), (40, False), (40, True), (700, False)],
    ids=["global", "global_sink", "window", "window_sink",
         "window_longer_than_the_prompt"])
def test_blocked_prefill_attention_is_the_unblocked_formula(
        monkeypatch, window, sink):
    """512 rows in 8 blocks of 64: a window of 40 makes a block span 128
    keys of the 512, a global layer all of them."""
    t, h, hkv, d, dv = 512, 4, 2, 8, 4
    monkeypatch.setattr(pda, "_SCORE_BLOCK_BYTES", h * 64 * t * 4)
    assert pda.prefill_key_span(t, h, window) == (
        64, 128 if window == 40 else t)
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(t, n, w), jnp.float32)
               for n, w in ((h, d), (hkv, d), (hkv, dv)))
    sinks = jnp.asarray(rng.randn(h), jnp.float32) if sink else None
    got = pda.grouped_causal_attention(q, k, v, window=window, sinks=sinks)
    np.testing.assert_allclose(got, _unblocked(q, k, v, window, sinks),
                               atol=2e-6)


def test_a_prompt_that_fits_is_one_block_and_the_counter_counts_the_span():
    """At the default budget a tiny prompt is one block (the form it
    always had); a prefill's counters follow what its layers span."""
    assert pda.prefill_key_span(2048, 64, 128) == (1024, 1152)
    assert pda.prefill_key_span(1024, 64, None) == (1024, 1024)
    assert pda.prefill_key_span(4096, 128, 4096) == (256, 4096)
    assert pda.prefill_key_span(48, 8, 20) == (48, 48)
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(23))
    names = ("decode_prefill_keys_attended", "decode_prefill_keys_live")
    before = [stat_get(n) for n in names]
    with engine(model, weights) as eng:
        eng.submit(list(range(1, 34)), max_new_tokens=1).result(timeout=300)
    attended, live = (stat_get(n) - b for n, b in zip(names, before))
    # bucket 64, 33 rows: one global layer and three window-20 layers
    # (a block of 64 rows spans the bucket)
    assert attended == 4 * 64 * 64
    assert live == 33 * 34 // 2 + 3 * (20 * 21 // 2 + 13 * 20)


# -- the share ---------------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips hold one routed expert each of one 16-expert layer
    and ALL of them the attention and the four shared experts: the
    routed parts summed, attention and the shared mean counted ONCE, are
    what the reference gives for the whole layer."""
    whole = make_model(("window",), held=tuple(range(16)))
    lw = whole.init_weights(jax.random.PRNGKey(12))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(13), (24, 32)) + 0.3
    d = dims(whole)
    want, _ = ref.block(lw, x, d, "window")
    h = whole._norm(x, lw["norm"])
    total, f = jnp.zeros_like(x), 16
    for chip in range(16):
        cols = slice(chip * f, (chip + 1) * f)
        _, _, local = moe_ops.moe_share_route(
            h, lw["moe_router"], jnp.zeros((16,)), top_k=4,
            held_ids=(chip,))
        part = moe_ops.moe_share_ffn(
            h, local, lw["moe_w_gate"][:, cols], lw["moe_w_up"][:, cols],
            lw["moe_w_down"][cols])
        # the reference given the same share
        share = {**lw, "moe_w_gate": lw["moe_w_gate"][:, cols],
                 "moe_w_up": lw["moe_w_up"][:, cols],
                 "moe_w_down": lw["moe_w_down"][cols]}
        with jax.default_matmul_precision("highest"):
            ref_part, _ = ref.routed(share, h, d, held=[chip])
        np.testing.assert_allclose(part, ref_part, atol=1e-4)
        total = total + part
    with jax.default_matmul_precision("highest"):
        once = ref.attention(lw, h, d, "window") + ref.shared(lw, h, d)
    np.testing.assert_allclose(x + total + once, want, atol=1e-4)


@pytest.mark.parametrize("max_seq_len", [128, 512])
def test_the_rings_bytes_have_no_term_in_max_seq_len(max_seq_len):
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(14))
    rng = np.random.RandomState(15)
    with engine(model, weights, max_seq_len=max_seq_len) as eng:
        cache = eng._cache
        shapes = [tuple(eng._scope.get_var(n).shape)
                  for n in cache.window_var_names()]
        # 3 window layers, 3 slots x 4 pages + trash, 2 K/V heads of 12
        assert shapes == [(3, 13, PAGE, 24)] * 2
        assert cache.window_bytes() == 2 * 3 * 13 * PAGE * 24 * 4
        assert stat_get("decode_window_bytes") == cache.window_bytes()
        reqs = [eng.submit(rng.randint(0, VOCAB, int(n)).tolist(),
                           max_new_tokens=int(m))
                for n, m in zip(rng.randint(1, 60, 6),
                                rng.randint(1, 50, 6))]
        for r in reqs:
            r.result(timeout=300)
    cache.debug_check()
    assert cache.window_pages_held() == 0


def test_the_head_is_the_embedding_and_the_scopes_name_the_layers():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(16))
    assert "lm_head" not in weights and "moe_router_bias" not in \
        weights["layers"][0]
    eng = engine(model, weights)
    text = eng.lower_step().as_text(debug_info=True)
    assert "window_attention" in text and "rope" in text \
        and "moe_shared" in text and "moe_experts" in text


def test_what_cannot_hold_over_a_ring_refuses():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(18))
    with pytest.raises(ValueError, match="window layers.*chunked"):
        engine(model, weights, prefill_chunk_pages=1)
    with pytest.raises(ValueError, match="window layers.*speculative"):
        engine(model, weights, spec_k=2)


@pytest.mark.parametrize("length, bucket", [(11, 16), (37, 64)])
def test_a_prompts_head_forms_the_one_row_the_engine_reads(
        length, bucket, monkeypatch):
    """The whole-prompt prefill names the row it reads and the model
    cuts the residual to it in front of ``_head`` (``blocks.read_rows``,
    PR 62): ``[1, V]`` through the LayerNorm, the transposed embedding
    and ``logit_scale``.  Rows inside a short and a longer bucket:
    tokens and recorded logits are those of the form that made every
    row's; the joint step makes every slot's as ever."""
    import sys

    from prompt_head_forms import the_read_row_is_the_every_row_forms

    the_read_row_is_the_every_row_forms(
        sys.modules[__name__], length, bucket, monkeypatch)


@pytest.mark.parametrize("says, rows", [
    ({}, 8), (dict(prompt=False, read_row=5), 8),
    (dict(prompt=True, read_row=None), 8), (dict(prompt=True, read_row=5), 1),
], ids=["a_plain_callable", "rows_that_are_no_prompt",
        "a_prompt_that_names_no_row", "a_prompt_that_names_its_row"])
def test_only_a_prompt_that_names_its_row_is_cut_to_it(says, rows):
    """``blocks.read_rows`` is the one place a served model's head asks
    which rows are read: a test's plain callable says nothing, the joint
    step and the multi-row program of chunks and verification
    (``prompt`` False) read every row, and so does a prompt whose
    engine names none."""
    import types

    from paddle_tpu.serving.blocks import head_logits, read_rows

    def attend(*a):
        raise AssertionError("the head attends nothing")

    vars(attend).update(says)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16), jnp.float32)
    got = read_rows(x, attend)
    if rows == 8:
        assert got is x
    else:
        np.testing.assert_array_equal(got, x[5:6])
    w = dict(norm_f=jnp.full((16,), 1.5),
             lm_head=jax.random.normal(jax.random.PRNGKey(1), (16, 24)))
    model = types.SimpleNamespace(rms_eps=1e-6)
    every = head_logits(model, w, x, lambda *a: None)
    assert every.shape == (8, 24)
    np.testing.assert_allclose(
        head_logits(model, w, x, attend),
        every if rows == 8 else every[5:6], rtol=1e-6, atol=1e-6)
