"""The whole-prompt prefill's grouped attention as a flash kernel
(``ops/pallas_prompt_attention.py``) against the plain form of
``grouped_causal_attention``: the kernel interpreted on the CPU at small
sizes, the engagement rule at the cells' shapes, and the engine's
counter of the keys the form that runs walks."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.ops import pallas_prompt_attention as ppa

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def _operands(t, h, hkv, d, dv, sinks, dtype=jnp.float32, seed=3):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(t, h, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(t, hkv, w), jnp.float32).astype(dtype)
            for w in (d, dv))
    return q, k, v, jnp.asarray(rng.randn(h), jnp.float32) if sinks else None


def _both(q, k, v, sinks, window, length=None):
    """(the kernel interpreted, the plain form)."""
    got = pda.grouped_causal_attention(
        q, k, v, window=window, sinks=sinks, length=length,
        use_pallas="always", interpret=True)
    want = pda.grouped_causal_attention(
        q, k, v, window=window, sinks=sinks, use_pallas="never")
    return np.asarray(got), np.asarray(want)


def _rms_rel(got, want):
    return np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean())


@pytest.fixture
def small_tiles(monkeypatch):
    """Blocks of 128 rows at most and 128 keys: 512 rows are 4 x 4 of
    them, so the walk has a diagonal, blocks under it and blocks it
    never visits."""
    monkeypatch.setattr(ppa, "_MAX_ROWS", 128)
    monkeypatch.setattr(ppa, "_KEY_BLOCK", 128)


# t, h, hkv, d, dv, window, sinks
CASES = {
    "causal_g1": (512, 2, 2, 128, 128, None, False),
    "causal_g8": (512, 8, 1, 128, 128, None, False),
    "causal_g16": (512, 16, 1, 128, 128, None, False),
    "causal_sinks": (512, 8, 2, 128, 128, None, True),
    "window_20": (512, 4, 2, 128, 128, 20, False),
    "window_20_sinks": (512, 4, 2, 128, 128, 20, True),
    "window_200_crosses_blocks": (512, 4, 2, 128, 128, 200, True),
    "window_is_the_bucket": (512, 4, 2, 128, 128, 512, False),
    "keys_wider_than_values": (512, 8, 2, 192, 128, None, True),
    "window_keys_wider_than_values": (512, 8, 1, 192, 128, 128, True),
    # V heads of 64 lanes in whole groups: two heads' values a lane tile
    "heads_of_64": (512, 8, 2, 64, 64, None, False),
    "heads_of_64_window_200_sinks": (512, 8, 2, 64, 64, 200, True),
    "keys_of_128_values_of_64": (512, 4, 2, 128, 64, None, True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_plain_form(small_tiles, case):
    t, h, hkv, d, dv, window, sinks = CASES[case]
    form, bq, bk = pda.prefill_walk(t, h, hkv, d, dv, window, "always")
    assert (form, bk) == ("flash", 128) and bq == min(128, 2048 * hkv // h)
    q, k, v, s = _operands(t, h, hkv, d, dv, sinks)
    got, want = _both(q, k, v, s, window)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if window == 20:
        # the window's edge is where the plain form has it: 19 and 21
        # are other answers
        for other in (19, 21):
            off = np.asarray(pda.grouped_causal_attention(
                q, k, v, window=other, sinks=s, use_pallas="never"))
            assert np.abs(got - off).max() > 1e-2


@pytest.mark.parametrize("lanes", [128, 64])
@pytest.mark.parametrize("length", [1, 130, 257, 512])
def test_row_blocks_past_the_length_are_not_computed(small_tiles, length,
                                                     lanes):
    """A prompt short of its bucket by more than a row block: the real
    rows are the plain form's, a block of padding rows alone comes back
    zero; at heads of whole lane tiles and of 64 lanes."""
    t, h, hkv = 512, 16, 1
    assert ppa.flash_rule(t, h, hkv, lanes, lanes) == (128, 128)
    q, k, v, _ = _operands(t, h, hkv, lanes, lanes, False)
    got, want = _both(q, k, v, None, None, length=jnp.int32(length))
    live = -(-length // 128) * 128
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5)
    assert not got[live:].any()
    assert ppa.keys_visited(t, length, 128, 128) == sum(
        128 * (i + 1) * 128 for i in range(live // 128))


def test_bfloat16_keys_meet_one_bfloat16_term():
    """K and V in the pages' bfloat16 go to the matmuls as they lie and
    the query and the probabilities as one bfloat16 term: what the chip
    gives the plain form's float32 einsums."""
    q, k, v, s = _operands(256, 8, 2, 128, 128, True, jnp.bfloat16)
    got, want = _both(q, k, v, s, None)
    err = _rms_rel(got, want)
    assert 1e-4 < err < 6e-3, err


def test_bfloat16_keys_of_64_lanes_meet_one_bfloat16_term():
    """The same feed at heads of 64 lanes: the error against the plain
    form's float32 is what 128 lanes read (1.91e-3 here, 1.87e-3 there:
    one bfloat16 term for the query and one for the probabilities).
    ``sm_scale`` at 64 lanes is 0.125, a power of two, so rounding the
    scaled query is rounding the query: a query on bfloat16's grid
    meets the plain form's operands bit for bit and leaves the
    probabilities' term alone (at 128 lanes it leaves both)."""
    q, k, v, s = _operands(256, 8, 2, 64, 64, True, jnp.bfloat16)
    got, want = _both(q, k, v, s, None)
    err = _rms_rel(got, want)
    assert 1.4e-3 < err < 2.6e-3, err       # 1.91e-3 at this seed
    # with the query on bfloat16's grid already, the scaled query's
    # rounding is exact and only the probabilities' term is left
    q = q.astype(jnp.bfloat16).astype(jnp.float32)
    got, want = _both(q, k, v, s, None)
    exact_q = _rms_rel(got, want)
    assert 8e-4 < exact_q < 0.8 * err, (exact_q, err)   # 1.26e-3


@pytest.mark.parametrize("shape", [
    (512, 8, 2, 8, 4), (512, 8, 3, 128, 128), (72, 8, 2, 128, 128),
    (512, 8, 2, 128, 96), (512, 2, 2, 64, 64), (512, 6, 2, 128, 64)],
    ids=["narrow_heads", "no_whole_groups", "no_whole_blocks",
         "narrow_values", "ungrouped_heads_of_64",
         "odd_groups_of_64_lane_values"])
def test_a_shape_the_rule_refuses_keeps_the_plain_form(shape):
    t, h, hkv, d, dv = shape
    assert ppa.flash_rule(t, h, hkv, d, dv) is None
    assert pda.prefill_walk(t, h, hkv, d, dv, None, "always") == (
        "blocks",) + pda.prefill_key_span(t, h)
    if h % hkv:
        return
    q, k, v, s = _operands(t, h, hkv, d, dv, True)
    got, want = _both(q, k, v, s, 40)
    assert np.array_equal(got, want)


def test_the_rule_at_the_cells_shapes():
    """(bucket, query heads, K/V heads, K lanes, V lanes, window) of the
    four cells' whole-prompt prefills: every one engages; 'auto' off the
    chip keeps the plain form."""
    assert ppa.flash_rule(4096, 128, 8, 128, 128) == (128, 1024)
    assert ppa.flash_rule(4096, 128, 8, 128, 128, 4096) == (128, 1024)
    assert ppa.flash_rule(4096, 30, 30, 128, 128) == (256, 1024)
    for t in (512, 1024, 2048):
        assert ppa.flash_rule(t, 64, 4, 192, 128) == (128, min(t, 1024))
        assert ppa.flash_rule(t, 64, 8, 192, 128, 128) == (128, 128)
    for t in (128, 256, 512, 1024):
        assert ppa.flash_rule(t, 64, 8, 128, 128) == (
            min(t, 256), min(t, 1024))
    # LFM2's 32 heads on 8 of K 64 / V 64: four heads' lanes are two
    # whole tiles on both sides
    assert ppa.flash_rule(2048, 32, 8, 64, 64) == (256, 1024)
    assert pda.prefill_walk(4096, 128, 8, 128, 128, 4096, "auto") == (
        "blocks", 256, 4096)
    assert pda.prefill_walk(4096, 128, 8, 128, 128, 4096, "always") == (
        "flash", 128, 1024)
    # a window layer's row block walks its window's blocks, not the bucket
    assert ppa._steps(2048, 128, 128, 128) == 3
    assert ppa._steps(4096, 128, 1024, None) == 4
    # a 3,600-token prompt in the 4,096 bucket: 29 row blocks of 128,
    # each up to its diagonal's block of 1,024
    assert ppa.keys_visited(4096, 3600, 128, 1024) == 128 * 1024 * (
        8 + 8 * 2 + 8 * 3 + 5 * 4)


BUCKETS = (128, 256, 512, 1024, 2048, 4096, 8192)
# (query heads, K/V heads, K lanes, V lanes, window) of a cell's layers
# and ``flash_rule``'s answer a bucket of ``BUCKETS`` as it was before
# the rule took V heads of 64 lanes (read off the parent commit, PR 59)
WAS = {
    "mimo_v2_5": {
        (64, 4, 192, 128, None): [(128, 128), (128, 256), (128, 512)]
        + [(128, 1024)] * 4,
        (64, 8, 192, 128, 128): [(128, 128)] * 7},
    "command_a_plus": {
        (128, 8, 128, 128, None): [(128, 128), (128, 256), (128, 512)]
        + [(128, 1024)] * 4,
        (128, 8, 128, 128, 4096): [(128, 128), (128, 256), (128, 512)]
        + [(128, 1024)] * 4},
    "olmo_hybrid_7b": {
        (30, 30, 128, 128, None): [(128, 128), (256, 256), (256, 512)]
        + [(256, 1024)] * 4},
    "kimi_k2_5": {
        (64, 64, 192, 128, None): [(128, 128), (256, 256), (512, 512)]
        + [(1024, 1024)] * 4},
    "kimi_linear_48b": {
        (32, 32, 192, 128, None): [(128, 128), (256, 256), (512, 512)]
        + [(1024, 1024)] * 4},
    "solar_open2_250b": {
        (64, 8, 128, 128, None): [(128, 128), (256, 256), (256, 512)]
        + [(256, 1024)] * 4},
}


@pytest.mark.parametrize("cell", list(WAS))
def test_the_other_cells_keep_the_tiles_they_had(cell):
    """The six cells whose prompts ran the kernel before it took V heads
    of 64 lanes: the rule's answer at every bucket is the parent's, so
    their prefills trace the lines they traced."""
    for (h, hkv, d, dv, window), tiles in WAS[cell].items():
        assert dv % 128 == 0
        assert [ppa.flash_rule(t, h, hkv, d, dv, window)
                for t in BUCKETS] == tiles, (cell, h, hkv, d, dv, window)


def test_the_engine_counts_the_walk_of_the_form_that_runs(monkeypatch):
    """One window-20 layer and one global layer at heads of 128 lanes, a
    prompt of 150 in a bucket of 256: with the kernel asked for, the
    counters hold its visited blocks and the layer-calls by form, and
    the logits are the reference's."""
    from benchmark.reference import parallel_moe_lm as ref
    from test_parallel_moe_serving import dims, engine, make_model

    monkeypatch.setattr(ppa, "_MAX_ROWS", 64)
    monkeypatch.setattr(ppa, "_KEY_BLOCK", 128)
    model = make_model(("window", "attention"), num_heads=4,
                       num_kv_heads=2, head_dim=128)
    weights = model.init_weights(jax.random.PRNGKey(5))
    prompt = [(5 * i + 2) % 97 for i in range(150)]
    names = ("decode_prefill_keys_attended", "decode_prefill_keys_live",
             "decode_prefill_attn_flash", "decode_prefill_attn_blocks")
    seen = {}
    for use in ("always", "never"):
        before = [stat_get(n) for n in names]
        with engine(model, weights, max_seq_len=256, use_pallas=use,
                    interpret=True) as eng:
            req = eng.submit(prompt, max_new_tokens=1, record_logits=True)
            req.result(timeout=300)
        seen[use] = [stat_get(n) - b for n, b in zip(names, before)]
        want, _ = ref.forward_logits(
            weights, jnp.asarray(prompt, jnp.int32), dims(model))
        assert np.abs(req.logits_trace[0] - np.asarray(want)[-1]).max() \
            < 1e-4
    live = 150 * 151 // 2 + (20 * 21 // 2 + 130 * 20)
    # rows 0..191 are the live row blocks of 64; the global layer's walk
    # ends at each block's diagonal, the window layer's is one or two
    # blocks of 128 keys
    walked = 64 * 128 * (1 + 1 + 2) + 64 * 128 * (1 + 1 + 2)
    assert ppa.keys_visited(256, 150, 64, 128, 20) == 64 * 128 * 4
    assert seen["always"] == [walked, live, 2, 0]
    assert seen["never"] == [2 * 256 * 256, live, 0, 2]
