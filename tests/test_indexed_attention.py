"""``ops/indexed_attention.py`` (the index scores, the exact selection in
its two forms, the attention over gathered rows) and the prompt
attention's ``select=`` against plain numpy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import indexed_attention as ixa
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.ops import pallas_prompt_attention as ppa


def np_scores(q, w, keys):
    s = np.einsum("...hd,...nd->...hn", q, keys)
    return (np.maximum(s, 0.0) * w[..., None]).sum(-2)


def np_select(scores, n_live, k):
    """The k live positions of largest score, equal scores the lower
    position first (a stable sort of the negated scores)."""
    order = np.argsort(-scores[:n_live], kind="stable")
    return sorted(order[:k].tolist())


@pytest.mark.parametrize("n, h, d", [(37, 3, 6), (128, 16, 64)])
def test_index_scores_are_the_weighted_relu_sum(n, h, d):
    rng = np.random.RandomState(0)
    q = rng.randn(5, h, d).astype(np.float32)
    w = rng.randn(5, h).astype(np.float32)
    keys = rng.randn(5, n, d).astype(np.float32)
    got = ixa.index_scores(jnp.asarray(q), jnp.asarray(w), jnp.asarray(keys))
    np.testing.assert_allclose(got, np_scores(q, w, keys), rtol=1e-4,
                               atol=1e-4)
    # one prompt's keys for every row (no leading shape on the keys)
    got = ixa.index_scores(jnp.asarray(q), jnp.asarray(w),
                           jnp.asarray(keys[0]))
    np.testing.assert_allclose(got, np_scores(q, w, keys[0]), rtol=1e-4,
                               atol=1e-4)


def tied_scores(rows, n, seed):
    """Scores drawn from a handful of values: ties everywhere, the k-th
    largest among them."""
    return np.random.RandomState(seed).randint(
        -3, 4, (rows, n)).astype(np.float32)


@pytest.mark.parametrize("n, k", [(37, 8), (64, 16), (20, 32)])
def test_both_forms_select_the_same_set_with_ties_to_the_lower_position(
        n, k):
    scores = tied_scores(6, n, n + k)
    lengths = np.minimum(
        [n, n - 1, k, max(k - 3, 1), 1, n // 2], n).astype(np.int32)
    pos, ok, twice = ixa.select_top(
        jnp.asarray(scores), jnp.asarray(lengths), k,
        carry=(2 * jnp.arange(n, dtype=jnp.int32)[None].repeat(6, 0),))
    np.testing.assert_array_equal(twice, 2 * np.asarray(pos))
    live = np.arange(n)[None, :] < lengths[:, None]
    mask = np.asarray(ixa.select_mask(jnp.asarray(scores),
                                      jnp.asarray(live), k))
    pos, ok = np.asarray(pos), np.asarray(ok)
    assert pos.shape == (6, min(k, n))
    for r in range(6):
        want = np_select(scores[r], lengths[r], k)
        assert sorted(pos[r][ok[r]].tolist()) == want
        assert np.flatnonzero(mask[r]).tolist() == want
        assert len(want) == min(k, lengths[r])


def test_a_dead_position_is_never_chosen_while_a_live_one_is_left():
    """Stale rows with the largest scores of all, past the length."""
    scores = np.full((2, 40), -5.0, np.float32)
    scores[:, 30:] = 100.0
    lengths = np.asarray([30, 12], np.int32)
    pos, ok = ixa.select_top(jnp.asarray(scores), jnp.asarray(lengths), 16)
    pos, ok = np.asarray(pos), np.asarray(ok)
    assert pos[0].max() < 30 and ok[0].all()
    assert ok[1].sum() == 12 and pos[1][ok[1]].max() < 12
    live = np.arange(40)[None, :] < lengths[:, None]
    mask = np.asarray(ixa.select_mask(jnp.asarray(scores),
                                      jnp.asarray(live), 16))
    assert not mask[:, 30:].any() and mask[1, :12].all()
    assert mask[0].sum() == 16 and mask[0, :16].all()   # ties: the lowest


def test_negative_zero_infinite_and_equal_scores_keep_their_order():
    scores = np.asarray([[0.0, -0.0, 1e-38, -1e-38, np.inf, -np.inf, 2.0,
                          2.0, -7.5, 3e38]], np.float32)
    live = np.ones_like(scores, bool)
    for k in range(1, 11):
        mask = np.asarray(ixa.select_mask(jnp.asarray(scores),
                                          jnp.asarray(live), k))
        order = np.argsort(-scores[0], kind="stable")
        # -0.0 sorts under 0.0 by its bits; numpy holds them equal and
        # keeps the lower position first: the same set either way
        assert np.flatnonzero(mask[0]).tolist() == sorted(order[:k].tolist())


def test_bits_pack_and_unpack():
    mask = np.random.RandomState(1).rand(5, 48) < 0.4
    packed = ixa.pack_bits(jnp.asarray(mask))
    assert packed.shape == (5, 6) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(ixa.unpack_bits(packed), mask)


@pytest.mark.parametrize("kv_heads, d, dv", [(2, 8, 8), (1, 16, 8)])
def test_attention_over_gathered_rows(kv_heads, d, dv):
    rng = np.random.RandomState(2)
    s_, h, k = 3, 4, 11
    q = rng.randn(s_, h, d).astype(np.float32)
    kr = rng.randn(s_, k, kv_heads * d).astype(np.float32)
    vr = rng.randn(s_, k, kv_heads * dv).astype(np.float32)
    ok = rng.rand(s_, k) < 0.7
    ok[:, 0] = True
    got = ixa.attend_rows(jnp.asarray(q), jnp.asarray(kr), jnp.asarray(vr),
                          jnp.asarray(ok), kv_heads)
    g = h // kv_heads
    for s in range(s_):
        for i in range(h):
            j = i // g
            sc = kr[s, :, j * d:(j + 1) * d] @ q[s, i] / np.sqrt(d)
            sc = np.where(ok[s], sc, -np.inf)
            p = np.exp(sc - sc.max())
            p /= p.sum()
            np.testing.assert_allclose(
                got[s, i], p @ vr[s, :, j * dv:(j + 1) * dv], rtol=1e-4,
                atol=1e-5)


def np_selected_attention(q, k, v, select):
    t, h, d = q.shape
    g = h // k.shape[1]
    out = np.zeros((t, h, v.shape[-1]), np.float32)
    for i in range(h):
        s = q[:, i] @ k[:, i // g].T / np.sqrt(d)
        seen = (np.arange(t)[None] <= np.arange(t)[:, None]) & (select != 0)
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, i] = (p / p.sum(-1, keepdims=True)) @ v[:, i // g]
    return out


def selection(t, keep, seed):
    """[t, t] int8: ``keep`` causal keys a row, some rows' all in late
    key blocks (the early blocks then hold nothing the row chose)."""
    rng = np.random.RandomState(seed)
    sel = np.zeros((t, t), np.int8)
    for r in range(t):
        lo = 0 if r % 3 else max(r - keep, 0)
        sel[r, rng.choice(np.arange(lo, r + 1), min(keep, r + 1 - lo),
                          replace=False)] = 1
    return sel


def test_the_plain_blocks_attend_under_a_selection(monkeypatch):
    monkeypatch.setattr(pda, "_SCORE_BLOCK_BYTES", 4 * 4 * 16 * 48)
    rng = np.random.RandomState(3)
    t, h, hkv, d = 48, 4, 2, 8
    q, k, v = (rng.randn(t, n, d).astype(np.float32) for n in (h, hkv, hkv))
    sel = selection(t, 7, 4)
    assert pda.prefill_walk(t, h, hkv, d, d)[:2] == ("blocks", 16)
    got = pda.grouped_causal_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        select=jnp.asarray(sel))
    np.testing.assert_allclose(got, np_selected_attention(q, k, v, sel),
                               rtol=1e-4, atol=1e-5)


def test_the_flash_kernel_attends_under_a_selection(monkeypatch):
    """Interpreted, blocks of 128 rows by 128 keys: rows whose chosen
    keys all lie in a later key block, and a prompt shorter than the
    bucket."""
    monkeypatch.setattr(ppa, "_MAX_ROWS", 128)
    monkeypatch.setattr(ppa, "_KEY_BLOCK", 128)
    rng = np.random.RandomState(5)
    t, h, hkv, d = 384, 4, 2, 128
    q, k, v = (rng.randn(t, n, d).astype(np.float32) for n in (h, hkv, hkv))
    sel = selection(t, 40, 6)
    assert ppa.flash_rule(t, h, hkv, d, d) == (128, 128)
    got = pda.grouped_causal_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), length=300,
        select=jnp.asarray(sel), use_pallas="always", interpret=True)
    want = np_selected_attention(q, k, v, sel)
    np.testing.assert_allclose(got[:300], want[:300], rtol=2e-4, atol=2e-5)
    # without a selection the kernel is the one it was
    plain = pda.grouped_causal_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), length=300,
        use_pallas="always", interpret=True)
    np.testing.assert_allclose(
        plain[:300], np_selected_attention(
            q, k, v, np.ones((t, t), np.int8))[:300], rtol=2e-4, atol=2e-5)
