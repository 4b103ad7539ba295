"""Decode engine (paddle_tpu.serving.decode): paged KV cache,
continuous batching, streaming, deadlines, sampling determinism, and
multi-replica scale-out.  (tests/test_decode.py was already taken by
the beam-search text decoder.)

The load-bearing test is the prefix-cache ORACLE: decode-with-cache
logits must be BITWISE equal to a full recompute of the whole prefix
at every generated step — prefill and decode share one masked-softmax
formulation (each at its own width: a masked position weighs exactly
zero), so any cache bug (wrong page, wrong offset, stale entry) shows
up as a bit difference.
"""
import json
import re
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.monitor import stat_get
from paddle_tpu.observe.histogram import histogram
from paddle_tpu.serving.buckets import prefill_bucket_grid
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine, \
    TransformerLM
from paddle_tpu.serving.kv_cache import CacheConfig, PageAllocator

VOCAB = 61  # prime-ish: catches transposed vocab/d_model bugs


@pytest.fixture(scope="module")
def model_and_weights():
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=256)
    weights = model.init_weights(jax.random.PRNGKey(7))
    return model, weights


def make_engine(model_and_weights, **cfg_kw):
    model, weights = model_and_weights
    kw = dict(slots=2, max_seq_len=64, page_size=8, max_new_tokens=8)
    kw.update(cfg_kw)
    return DecodeEngine(model, weights, DecodeConfig(**kw))


# -- kv cache plumbing ----------------------------------------------------


def test_page_allocator_alloc_free_exhaust():
    a = PageAllocator(8)  # pages 1..7 allocatable
    assert a.num_free == 7
    p1 = a.alloc(3)
    assert len(p1) == 3 and 0 not in p1
    assert a.alloc(5) is None  # atomic: nothing taken on failure
    assert a.num_free == 4
    p2 = a.alloc(4)
    assert a.num_free == 0 and set(p1) | set(p2) == set(range(1, 8))
    a.free(p1)
    assert a.num_free == 3
    a.free([0])  # the trash page is never pooled
    assert a.num_free == 3


def test_cache_config_validation():
    with pytest.raises(ValueError):
        CacheConfig(2, 2, 16, 4, max_seq_len=65, page_size=8)
    c = CacheConfig(2, 2, 16, num_slots=4, max_seq_len=64, page_size=8)
    assert c.pages_per_slot == 8
    assert c.num_pages == 4 * 8 + 1  # default pool + trash page
    assert c.pages_for(1) == 1 and c.pages_for(9) == 2
    assert c.cache_bytes() == 2 * 2 * 33 * 8 * 2 * 16 * 4


@pytest.mark.parametrize("heads,head_dim,page,dense", [
    (2, 16, 8, 0),      # a toy row: 32 lanes of a tile's 128
    (2, 64, 8, 1),      # two heads of 64 fill one lane tile
    (12, 64, 16, 1),    # GPT-2 small
    (2, 64, 4, 0),      # the row fills the lanes, the page not the rows
])
def test_engine_build_says_whether_the_pools_are_lane_dense(
        heads, head_dim, page, dense):
    """The counter that says whether the one-layout representation is
    also an unpadded one for this model's shape, set at engine build."""
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=heads * head_dim,
                          num_layers=1, num_heads=heads, max_seq_len=32)
    weights = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    eng = DecodeEngine(model, weights, DecodeConfig(
        slots=2, max_seq_len=32, page_size=page))
    assert stat_get("decode_kv_lane_dense") == dense
    assert stat_get("decode_kv_pool_row_lanes") == heads * head_dim
    k_pool, _ = eng._cache.arrays()
    assert k_pool.shape == (1, 2 * (32 // page) + 1, page,
                            heads * head_dim)


@pytest.mark.parametrize("cfg, bits", [
    (dict(), 32), (dict(cache_dtype="bfloat16"), 16),
    (dict(kv_quant=True), 32)], ids=["float32", "bfloat16", "int8"])
def test_engine_start_says_how_the_attention_kernel_is_fed(
        model_and_weights, cfg, bits):
    """The gauge that says the bfloat16 feed engaged, read from the
    pools' dtype alone; and the served logits through the kernel (in
    interpret mode) are those of the gather reference over the same
    cache."""
    prompt = list(range(3, 24))
    traces = []
    for use_pallas in ("always", "never"):
        with make_engine(model_and_weights, use_pallas=use_pallas,
                         interpret=True, **cfg) as eng:
            assert stat_get("decode_attn_feed_bits") == bits
            r = eng.submit(prompt, max_new_tokens=6, record_logits=True)
            r.result(timeout=300)
            traces.append(np.stack(r.logits_trace))
    np.testing.assert_allclose(traces[0], traces[1], atol=5e-5)


def test_prefill_bucket_grid():
    assert prefill_bucket_grid(64, 8) == (8, 16, 32, 64)
    assert prefill_bucket_grid(48, 16) == (16, 32, 48)


# -- pallas kernel --------------------------------------------------------


def test_paged_attention_pallas_interpret_matches_reference():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_decode_attention import \
        paged_decode_attention

    rs = np.random.RandomState(0)
    s, h, d, pool, page, pps = 4, 2, 16, 9, 8, 4
    q = jnp.asarray(rs.randn(s, h, d).astype("f4"))
    # the stacked pools [L, P, page, H*D]; layer 1 of 2 is read
    kp = jnp.asarray(rs.randn(2, pool, page, h * d).astype("f4"))
    vp = jnp.asarray(rs.randn(2, pool, page, h * d).astype("f4"))
    table = jnp.asarray(rs.randint(1, pool, (s, pps)).astype("i4"))
    # edge lengths: page-boundary, partial page, full table, one token
    lengths = jnp.asarray(np.array([8, 17, 32, 1], "i4"))
    ref = paged_decode_attention(q, kp, vp, table, lengths, layer=1,
                                 use_pallas="never")
    pal = paged_decode_attention(q, kp, vp, table, lengths, layer=1,
                                 use_pallas="always", interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pool", ["f32", "int8", "bf16"])
@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("h,d", [(16, 64), (12, 64), (4, 16), (8, 128)])
def test_paged_kernel_reads_heads_out_of_lanes(h, d, rows, pool):
    """The kernel in interpret mode against the plain masked-softmax
    reference on the heads-major K/V, at GPT-2-medium's and -small's
    head shapes, a toy row narrower than a lane tile and head_dim 128,
    one query row (decode) and four (a chunk), over ragged lengths:
    nothing, one token, a page boundary - 1 / on it / + 1, the full
    table.  The pools are stacked and lane-folded as the cache stores
    them, and a middle layer is read.  A bfloat16 pool's blocks go to
    the matmuls as bfloat16 and the float32 query and probabilities as
    three groups of bfloat16 rows: the same tolerance holds, against the
    reference in float32 on the pool's own values."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_decode_attention import (
        decode_attention_reference, paged_chunk_attention)
    from paddle_tpu.serving.kv_cache import dequantize_kv, quantize_kv

    rs = np.random.RandomState(h * d + rows)
    layers, layer, pool, page, pps = 3, 1, 14, 16, 4
    base = np.array([0, 1, page - 1, page, page + 1, pps * page], "i4")
    s = len(base)
    # row r of a slot attends r more positions, up to the full table
    row_lengths = np.minimum(base[:, None] + np.arange(rows, dtype="i4"),
                             pps * page)
    table = rs.randint(1, pool, (s, pps)).astype("i4")
    q = jnp.asarray(rs.randn(s, rows, h, d).astype("f4"))
    kv = [jnp.asarray(rs.randn(layers, pool, page, h, d).astype("f4"))
          for _ in range(2)]
    scales = [None, None]
    quantized = pool == "int8"
    if quantized:
        (kv[0], scales[0]), (kv[1], scales[1]) = map(quantize_kv, kv)
    elif pool == "bf16":
        kv = [x.astype(jnp.bfloat16) for x in kv]
    pal = paged_chunk_attention(
        q, *(x.reshape(layers, pool, page, h * d) for x in kv),
        jnp.asarray(table), jnp.asarray(row_lengths), layer=layer,
        use_pallas="always", interpret=True, k_scales=scales[0],
        v_scales=scales[1])
    if quantized:
        kv = [dequantize_kv(x, sc, jnp.float32)
              for x, sc in zip(kv, scales)]
    # the reference, one query row at a time over its slot's pages
    full = [np.asarray(x, np.float32)[layer][table]
            .reshape(s, pps * page, h, d) for x in kv]
    for r in range(rows):
        ref = decode_attention_reference(
            q[:, r], jnp.asarray(full[0]), jnp.asarray(full[1]),
            jnp.asarray(row_lengths[:, r]))
        live = row_lengths[:, r] > 0     # a row of nothing is unspecified
        np.testing.assert_allclose(np.asarray(pal)[live, r],
                                   np.asarray(ref)[live],
                                   rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(pal)).all()


def block_edge_case(rows, pool, pps=64, h=4, d=32, seed=0):
    """The paged kernel's inputs at its block's edges, and what it must
    return.  One batch: lengths 1, a page, a page + 1, a block - 1, a
    block, a block + 1, 448 and the full table (as far as ``pps``
    reaches), and a slot whose every row attends nothing.  Row r of a
    slot is causal: the last row attends the slot's length, the ones
    before it one position fewer each.  Every DEAD table entry names
    page 0, which is poisoned: NaN through K and V (through the scale
    planes of int8 pools), the way an uninitialised or trash page may
    read.  Returns (args, kwargs, want, live): the op's operands, the
    reference on the unpoisoned pools and which rows attend
    anything.  ``chip_smoke``-style callers run it on the chip too."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_decode_attention import paged_chunk_attention
    from paddle_tpu.serving.kv_cache import quantize_kv

    rs = np.random.RandomState(seed + rows)
    layers, layer, page = 2, 1, 16
    width = pps * page
    base = np.array([n for n in (1, 16, 17, 127, 128, 129, 448, width)
                     if n <= width] + [0], "i4")
    s = len(base)
    row_lengths = np.maximum(
        base[:, None] - np.arange(rows - 1, -1, -1, dtype="i4"), 0)
    n_pages = -(-base // page)
    n_pool = 1 + int(n_pages.sum())
    table = np.zeros((s, pps), "i4")                  # dead: page 0
    ids = rs.permutation(np.arange(1, n_pool))
    at = 0
    for i, n in enumerate(n_pages):
        table[i, :n] = ids[at:at + n]
        at += n
    q = jnp.asarray(rs.randn(s, rows, h, d).astype("f4"))
    kv = [rs.randn(layers, n_pool, page, h, d).astype("f4")
          for _ in range(2)]
    clean, poisoned = [], []
    for x in kv:
        scale = None
        x = jnp.asarray(x)
        if pool == "int8":
            x, scale = quantize_kv(x)
        else:
            x = x.astype(pool)
        x = x.reshape(layers, n_pool, page, h * d)
        clean.append((x, scale))
        if scale is None:
            poisoned.append((x.at[:, 0].set(jnp.nan), None))
        else:
            poisoned.append((x, scale.at[:, 0].set(jnp.nan)))

    def operands(pools):
        (k, ks), (v, vs) = pools
        return ((q, k, v, jnp.asarray(table), jnp.asarray(row_lengths)),
                dict(layer=layer, k_scales=ks, v_scales=vs))

    args, kwargs = operands(clean)
    want = paged_chunk_attention(*args, use_pallas="never", **kwargs)
    args, kwargs = operands(poisoned)
    return args, kwargs, np.asarray(want), row_lengths > 0


@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("rows", [1, 5, 16])
def test_paged_kernel_at_its_blocks_edges(rows, pool):
    """Eight pages a block: lengths on both sides of a page's and a
    block's edge, the longest request and the full table in one batch,
    a slot of nothing, and dead table entries that name a NaN page —
    the outputs are finite and the reference's."""
    from paddle_tpu.ops.pallas_decode_attention import paged_chunk_attention

    args, kwargs, want, live = block_edge_case(rows, pool)
    got = np.asarray(paged_chunk_attention(
        *args, use_pallas="always", interpret=True, **kwargs))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pool", ["float32", "bfloat16"])
def test_paged_kernel_ragged_last_block_and_nan_scratch(pool):
    """A table of 12 entries is a block of 8 and a block of 4: the
    entries the last block lacks are dead like any other.  Run under
    the TPU interpreter, whose fresh buffers read NaN, so a page that
    was never copied shows if it reaches the output (a bfloat16 pool's
    feed zeroes such pages where they lie in the V buffer)."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.pallas_decode_attention import (
        pages_per_block, paged_chunk_attention)

    args, kwargs, want, live = block_edge_case(5, pool, pps=12)
    # five rows a head on bfloat16 pools would take more than a lane
    # tile of scores: the table of 12 holds one whole tile and no second
    assert pages_per_block(16, 12, 128, args[1].dtype, None, 4, 5) == 8
    got = np.asarray(paged_chunk_attention(
        *args, use_pallas="always",
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"),
        **kwargs))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "page,pps,lanes,dtype,v_lanes,heads,n_rows,want", [
        # GPT-2-medium f32: a full lane tile of scores
        (16, 64, 1024, "float32", None, 16, 1, 8),
        (16, 64, 2048, "bfloat16", None, 16, 1, 8),  # 16 x 128 bf16, one row
        (128, 8, 768, "float32", None, 1, 1, 1),     # a page IS a block
        (16, 12, 1024, "float32", None, 1, 1, 8),    # ppb need not divide pps
        (16, 4, 1024, "float32", None, 1, 1, 4),     # never past the table
        # VMEM-bound: 2 buffers x (K + V) <= 4 MiB; never less than a page
        (16, 64, 8192, "float32", None, 1, 1, 2),
        (16, 64, 32768, "float32", None, 1, 1, 1),
        # MiMo-V2.5's global layers: 16 rows on each of 4 K/V heads of
        # 192 over 128, a table of 256 pages: 512 positions, 2.6 MB of
        # buffers
        (16, 256, 768, "bfloat16", 512, 4, 16, 32),
        # ... its window layers: 8 rows a head, but the ring of 9 pages
        # holds ONE whole lane tile
        (16, 9, 1536, "bfloat16", 1024, 8, 8, 8),
        # Command A+'s rings of 257 pages, 16 rows on each of 8 heads:
        # 512, both buffers of both pools exactly the budget
        (16, 257, 1024, "bfloat16", 1024, 8, 16, 32),
        (16, 384, 1024, "bfloat16", 1024, 8, 16, 32),   # ... its global layer
        # Solar: 8 rows a head at the same rows
        (16, 128, 1024, "bfloat16", 1024, 8, 8, 32),
        # Ouro: ONE row a head (and contexts of 320 at most)
        (16, 20, 2048, "bfloat16", 2048, 16, 1, 8),
        # Olmo-Hybrid: one row a head; and 15,360 B a position fit 136
        # positions twice over, whatever the rows
        (16, 352, 3840, "bfloat16", 3840, 30, 1, 8),
        (16, 352, 3840, "bfloat16", 3840, 30, 16, 8),
        # float32 and int8 pools hold float32 copies of a stack's K and V
        (16, 64, 1024, "float32", None, 16, 16, 8),
        (16, 64, 1024, "int8", None, 16, 16, 8),
        # Kimi-K2.5: the latent body's own block, 64 rows or one
        (16, 640, 640, "bfloat16", 0, 1, 64, 64),
        # a verify window of 5 rows on Command A+'s 128 heads: the
        # scores of 640 rows at 512 positions are past their budget
        (16, 257, 1024, "bfloat16", 1024, 8, 80, 8),
        # a chunk of 16 rows on 16 heads of 128: 4 MB of buffers are 256
        (16, 64, 2048, "bfloat16", 2048, 16, 16, 16),
        # whole lane tiles: a table of 30 pages holds 3 of them, not 30
        (16, 30, 256, "bfloat16", 256, 2, 4, 24),
    ], ids=["gpt2_medium", "bf16_2048", "page128", "ragged", "short_table",
            "vmem_bound", "vmem_floor", "mimo_global", "mimo_window_ring",
            "command_window_ring", "command_global", "solar", "ouro_one_row",
            "olmo_one_row", "olmo_vmem", "f32_stacked_rows",
            "int8_stacked_rows", "kimi_latent", "verify_window_scores",
            "chunk_vmem", "whole_tiles"])
def test_pages_per_block_is_read_from_the_shapes(page, pps, lanes, dtype,
                                                 v_lanes, heads, n_rows,
                                                 want):
    """Which block a call gets, the serving cells' among them, and why:
    more than one lane tile of scores only where bfloat16 pools meet
    query rows stacked on a K/V head, as far as the buffers, the scores
    and the table allow."""
    from paddle_tpu.ops.pallas_decode_attention import pages_per_block

    assert pages_per_block(page, pps, lanes, dtype, v_lanes, heads,
                           n_rows) == want


def test_engine_counts_the_attention_blocks_a_step_meets(model_and_weights):
    """``decode_attn_blocks_live`` / ``_walked``: once a joint step, a
    layer's blocks that hold an attended position (a dead slot's one
    included) against every block of every slot's table — here blocks
    of 8 pages of 16, two a slot, and a request that grows across the
    first block's edge while the other slot stays dead."""
    eng = make_engine(model_and_weights, max_seq_len=256, page_size=16,
                      max_new_tokens=16).start()
    names = ("decode_attn_blocks_live", "decode_attn_blocks_walked",
             "decode_steps")
    before = [stat_get(n) for n in names]
    try:
        assert len(eng.generate(list(range(1, 41)) * 3,
                                max_new_tokens=16)) == 16
    finally:
        eng.stop()
    live, walked, steps = (stat_get(n) - b for n, b in zip(names, before))
    assert steps == 15                 # the first token is the prefill's
    assert walked == steps * 2 * 2
    # the step at position p attends p + 1: 120 .. 134 cross 128
    assert live == sum(p // 128 + 1 for p in range(120, 135)) + steps


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_folded_pool_holds_the_bytes_of_the_heads_major_pool(quantized):
    """After the same token and prompt writes, the lane-folded pool
    reshaped back to [..., H, D] holds byte for byte what the old
    [L, P, page, H, D] pool held (and the scale planes are equal)."""
    import jax.numpy as jnp

    from paddle_tpu.serving import kv_cache as kvc

    rs = np.random.RandomState(3)
    c = CacheConfig(2, 4, 16, num_slots=2, max_seq_len=32, page_size=8,
                    num_pages=7, quantized=quantized)
    assert c.pool_shape() == (2, 7, 8, 64) and not c.lane_dense
    old_shape = (2, 7, 8, 4, 16)
    new = jnp.zeros(c.pool_shape(), c.store_dtype)
    old = jnp.zeros(old_shape, c.store_dtype)
    sc_new = sc_old = None
    if quantized:
        sc_new = sc_old = jnp.full(c.pool_shape(row_lanes=4),
                                   kvc.SCALE_EPS, jnp.float32)

    def stored(val):
        return kvc.quantize_kv(val) if quantized else (val, None)

    # a prompt of two pages into pages 5 and 2 of layer 1 ...
    prompt = jnp.asarray(rs.randn(16, 4, 16).astype("f4"))
    ids = jnp.asarray([5, 2], jnp.int32)
    new, sc_new = kvc.write_prompt_layer(new, sc_new, 1, prompt, ids)
    pv, ps = stored(prompt.reshape(2, 8, 4, 16))
    old = old.at[1, ids].set(pv.astype(old.dtype))
    # ... then three tokens, one of them aimed at the trash page
    tok = jnp.asarray(rs.randn(3, 4, 16).astype("f4"))
    pid = jnp.asarray([2, 0, 6], jnp.int32)
    off = jnp.asarray([7, 0, 3], jnp.int32)
    new, sc_new = kvc.write_token_layer(new, sc_new, 0, tok, pid, off)
    tv, ts = stored(tok)
    old = old.at[0, pid, off].set(tv.astype(old.dtype))
    if quantized:
        sc_old = sc_old.at[1, ids].set(ps).at[0, pid, off].set(ts)
        np.testing.assert_array_equal(np.asarray(sc_new),
                                      np.asarray(sc_old))
    assert np.asarray(new).reshape(old_shape).tobytes() \
        == np.asarray(old).tobytes()
    assert np.asarray(new).any()


# -- THE oracle: cached decode == full recompute, bitwise -----------------


def test_decode_bitwise_equals_full_recompute_every_step(
        model_and_weights):
    eng = make_engine(model_and_weights, slots=3).start()
    try:
        prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11] * 17]
        reqs = [eng.submit(p, max_new_tokens=6, record_logits=True,
                           seed=i) for i, p in enumerate(prompts)]
        outs = [r.result(timeout=120) for r in reqs]
    finally:
        eng.stop()
    for p, r, out in zip(prompts, reqs, outs):
        assert len(out) == 6 and len(r.logits_trace) == 6
        for t in range(len(out)):
            oracle = eng.recompute_logits(p + out[:t])
            assert np.array_equal(oracle, r.logits_trace[t]), (
                f"decode-with-cache logits diverged from the full "
                f"recompute at step {t} (max diff "
                f"{np.abs(oracle - r.logits_trace[t]).max()})")


# -- the whole-prompt prefill: decode's formulation at the bucket's width --


def _cache_width_prefill_logits(model, weights, prompt, t_pad, t_max,
                                quantized):
    """The whole-prompt prefill's last-row logits with every row's
    softmax spanning all ``t_max`` cache positions, K/V zero-padded from
    the bucket to that width: the formulation the engine's prefill had
    before it took its bucket's own width, kept here as the oracle of
    the narrower one."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_decode_attention import \
        decode_attention_reference
    from paddle_tpu.serving import kv_cache as kvc

    def attend(l, q, k, v, cache):
        if quantized:  # what the pages store, read back
            k = kvc.dequantize_kv(*kvc.quantize_kv(k), jnp.float32)
            v = kvc.dequantize_kv(*kvc.quantize_kv(v), jnp.float32)
        shape = (t_max,) + k.shape[1:]
        kf = jnp.zeros(shape, k.dtype).at[:t_pad].set(k)
        vf = jnp.zeros(shape, v.dtype).at[:t_pad].set(v)
        return decode_attention_reference(
            q, jnp.broadcast_to(kf[None], (t_pad,) + shape),
            jnp.broadcast_to(vf[None], (t_pad,) + shape),
            jnp.arange(t_pad, dtype=jnp.int32) + 1), cache

    tokens = np.zeros((t_pad,), np.int32)
    tokens[:len(prompt)] = prompt
    logits, _ = jax.jit(lambda w, t: model.forward(
        w, t, jnp.arange(t_pad, dtype=jnp.int32), None, attend))(
            weights, tokens)
    return np.asarray(logits[len(prompt) - 1])


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("t_pad,length", [(8, 5), (32, 19), (48, 43)],
                         ids=["short", "middle", "cache_width"])
def test_whole_prompt_prefill_attends_its_own_bucket(
        model_and_weights, t_pad, length, quantized):
    """A whole-prompt prefill spans its bucket, not the cache: (a) its
    recorded logits and first token are bitwise those of the same
    formulation at the cache's width (the masked tail weighs exactly
    zero); (b) its lowered program holds no tensor with a cache-width
    dimension beside a bucket-width one; (c) the counters say how much
    of the attention is work."""
    t_max = 48      # equals no other size of this model or engine
    model, weights = model_and_weights
    eng = make_engine(model_and_weights, max_seq_len=t_max,
                      kv_quant=quantized, max_new_tokens=2).start()
    prompt = [(7 * i + 3) % VOCAB for i in range(length)]
    names = ("decode_prefill_keys_attended", "decode_prefill_keys_live",
             "decode_prefills")
    before = [stat_get(n) for n in names]
    try:
        req = eng.submit(prompt, max_new_tokens=2, record_logits=True)
        out = req.result(timeout=120)
    finally:
        eng.stop()
    attended, live, prefills = (
        stat_get(n) - b for n, b in zip(names, before))
    # a head's, summed over the layers
    assert (prefills, attended, live) == (
        1, model.num_layers * t_pad * t_pad,
        model.num_layers * length * (length + 1) // 2)

    want = _cache_width_prefill_logits(model, weights, prompt, t_pad,
                                       t_max, quantized)
    assert np.array_equal(req.logits_trace[0], want), (
        np.abs(req.logits_trace[0] - want).max())
    assert out[0] == int(np.argmax(want))
    assert np.array_equal(
        eng.recompute_logits(prompt, quantized=quantized), want)

    shapes = {tuple(int(d) for d in dims.split("x")) for dims in re.findall(
        r"tensor<([\dx]+)x\w+>", eng.lower_prefill(t_pad).as_text())}
    assert (t_pad, model.num_heads, model.head_dim) in shapes   # q, k, v
    if t_pad < t_max:
        wide = sorted(sh for sh in shapes if t_max in sh and t_pad in sh)
        assert not wide, wide


# -- the seam: the engine serves whatever implements its contract ---------


class _ParallelRmsLM:
    """A served model that is NOT TransformerLM: an RMS-style norm with
    no gain, ONE norm a layer feeding attention and MLP in parallel, a
    fused QKV projection, relu.  It implements only what
    ``DecodeEngine``'s docstring lists."""

    def __init__(self, vocab_size, d_model, num_layers, num_heads,
                 max_seq_len):
        self.vocab_size, self.d_model = vocab_size, d_model
        self.num_layers, self.num_heads = num_layers, num_heads
        self.head_dim, self.max_seq_len = d_model // num_heads, max_seq_len

    def init_weights(self, key):
        import jax

        d, v = self.d_model, self.vocab_size
        keys = iter(jax.random.split(key, 3 + 4 * self.num_layers))

        def w(*shape):
            return jax.random.normal(next(keys), shape) * shape[0] ** -0.5

        return {"emb": w(v, d), "pos": w(self.max_seq_len, d),
                "head": w(d, v),
                "blocks": [{"qkv": w(d, 3 * d), "o": w(d, d),
                            "up": w(d, 2 * d), "down": w(2 * d, d)}
                           for _ in range(self.num_layers)]}

    @staticmethod
    def _rms(x):
        import jax
        import jax.numpy as jnp

        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)

    def forward(self, weights, tokens, positions, cache, attend):
        import jax
        import jax.numpy as jnp

        x = weights["emb"][tokens] + weights["pos"][positions]
        for l, b in enumerate(weights["blocks"]):
            h = self._rms(x)
            q, k, v = jnp.split((h @ b["qkv"]).reshape(
                *h.shape[:-1], self.num_heads, 3 * self.head_dim), 3, -1)
            ctx, cache = attend(l, q, k, v, cache)
            x = x + ctx.reshape(x.shape) @ b["o"] \
                + jax.nn.relu(h @ b["up"]) @ b["down"]
        return self._rms(x) @ weights["head"], cache


@pytest.mark.parametrize("path", ["whole_prompt", "chunked", "suffix_hit"])
def test_engine_serves_a_model_that_only_meets_the_contract(path):
    """Another block than TransformerLM's behind the same engine, through
    each of its three ``attend``s (one token a slot in every decode
    step; the whole prompt; R rows a slot for chunks and for the suffix
    after a prefix-cache hit): greedy tokens and logits bitwise equal to
    the engine's own full-recompute oracle."""
    import jax

    model = _ParallelRmsLM(VOCAB, 32, 2, 2, max_seq_len=64)
    cfg = dict(slots=2, max_seq_len=64, page_size=8, max_new_tokens=8)
    if path == "chunked":
        cfg.update(prefill_chunk_pages=1, prefix_cache=False)
    eng = DecodeEngine(model, model.init_weights(jax.random.PRNGKey(3)),
                       DecodeConfig(**cfg)).start()
    base = list(range(1, 17))  # two whole pages
    prompt = {"whole_prompt": base + [20, 21, 22],
              "chunked": list(range(1, 28)),       # four one-page chunks
              "suffix_hit": base + [40, 41, 42]}[path]
    try:
        chunks0 = stat_get("prefill_chunks")
        hits0 = 0
        if path == "suffix_hit":
            eng.generate(base + [20, 21], max_new_tokens=2)
            chunks0 = stat_get("prefill_chunks")
            hits0 = stat_get("decode_prefix_pages_hit")
        r = eng.submit(prompt, max_new_tokens=5, record_logits=True)
        out = r.result(timeout=120)
    finally:
        eng.stop()
    # the path the case is named for is the one the prompt took
    assert stat_get("prefill_chunks") - chunks0 == {
        "whole_prompt": 0, "chunked": 4, "suffix_hit": 1}[path]
    if path == "suffix_hit":
        assert stat_get("decode_prefix_pages_hit") - hits0 == 2
    assert len(out) == 5 and len(r.logits_trace) == 5
    for t in range(len(out)):
        oracle = eng.recompute_logits(prompt + out[:t])
        assert np.array_equal(oracle, r.logits_trace[t]), (path, t)
        assert out[t] == int(np.argmax(oracle))


def test_forward_with_a_dense_causal_attend_matches_the_oracle(
        model_and_weights):
    """``TransformerLM.forward`` under a plain causal softmax ``attend``
    with no cache at all (chip_smoke.py's reference, which it compares
    with on the chip) agrees with the paged engine's oracle."""
    import jax.numpy as jnp

    from chip_smoke import reference_forward

    model, weights = model_and_weights
    eng = make_engine(model_and_weights)
    seq = [5, 9, 2, 40, 17, 3, 3, 58, 11, 7, 1]
    buf = np.zeros((16,), np.int32)
    buf[:len(seq)] = seq
    dense = reference_forward(model, weights, jnp.asarray(buf), len(seq))
    np.testing.assert_allclose(np.asarray(dense), eng.recompute_logits(seq),
                               rtol=1e-4, atol=1e-5)


def test_batch_composition_invariance(model_and_weights):
    """A request's (greedy) tokens must not depend on what else is in
    the slot batch — the continuous-batching correctness property."""
    eng = make_engine(model_and_weights, slots=1).start()
    try:
        solo = eng.generate([5, 4, 3], max_new_tokens=5)
    finally:
        eng.stop()
    eng = make_engine(model_and_weights, slots=3).start()
    try:
        # same request staggered among unrelated neighbors
        others = [eng.submit([7, 7, 7, 7], max_new_tokens=8, seed=50),
                  eng.submit([1] * 9, max_new_tokens=8, seed=51)]
        joined = eng.generate([5, 4, 3], max_new_tokens=5)
        for o in others:
            o.result(timeout=120)
    finally:
        eng.stop()
    assert joined == solo


# -- continuous batching join/leave ---------------------------------------


def test_join_and_leave_at_step_boundaries(model_and_weights):
    """Short requests submitted while a long one is mid-flight must
    complete BEFORE it (slots join a running batch; finished slots
    free immediately — no group barrier)."""
    eng = make_engine(model_and_weights, slots=2, max_seq_len=128,
                      max_new_tokens=64).start()
    done_order = []
    try:
        long_req = eng.submit([3, 1], max_new_tokens=60)
        # wait until the long request is actually decoding
        for _ in long_req.tokens(timeout=60):
            break
        short1 = eng.submit([2, 2], max_new_tokens=3)
        short1.result(timeout=60)
        done_order.append("short1")
        if long_req.done():
            pytest.skip("machine too fast: long request finished first")
        # leave: short1's slot freed mid-flight; a second short joins
        short2 = eng.submit([4, 4], max_new_tokens=3)
        short2.result(timeout=60)
        done_order.append("short2")
        long_req.result(timeout=120)
        done_order.append("long")
    finally:
        eng.stop()
    assert done_order == ["short1", "short2", "long"]


def test_admission_blocks_on_pages_not_slots(model_and_weights):
    """A shared page pool smaller than slots*max_seq exercises real
    paging pressure: the second request waits for pages, then runs.
    prefix_cache=False pins the PURE paging semantics (with the prefix
    cache on, released pages are deliberately RETAINED by the index —
    tests/test_decode_prefix_spec.py covers that accounting)."""
    # pool: trash + 6 pages of 8 = 48 positions; each request needs
    # ceil((2+30)/8) = 4 pages, so two can't fit at once
    eng = make_engine(model_and_weights, slots=2, max_seq_len=64,
                      page_size=8, num_pages=7,
                      prefix_cache=False).start()
    blocked0 = stat_get("decode_admission_blocked_pages")
    try:
        r1 = eng.submit([1, 2], max_new_tokens=30)
        r2 = eng.submit([3, 4], max_new_tokens=30)
        out1 = r1.result(timeout=120)
        out2 = r2.result(timeout=120)
    finally:
        eng.stop()
    assert len(out1) == 30 and len(out2) == 30
    assert stat_get("decode_admission_blocked_pages") > blocked0
    assert eng._cache.allocator.num_free == 6  # everything returned


# -- streaming ------------------------------------------------------------


def test_streaming_generator_and_callback_order(model_and_weights):
    eng = make_engine(model_and_weights).start()
    try:
        cb_tokens = []
        req = eng.submit([1, 2, 3], max_new_tokens=6,
                         on_token=cb_tokens.append)
        streamed = list(req.tokens(timeout=60))
        final = req.result(timeout=10)
    finally:
        eng.stop()
    assert streamed == final == cb_tokens
    assert len(final) == 6


def test_streaming_starts_before_completion(model_and_weights):
    """First token arrives while the request is still generating —
    streaming is per-step, not a batch reply at the end."""
    eng = make_engine(model_and_weights, max_seq_len=128,
                      max_new_tokens=64).start()
    try:
        req = eng.submit([1, 2], max_new_tokens=40)
        it = req.tokens(timeout=60)
        first = next(it)
        assert isinstance(first, int)
        assert not req.done()  # 39 tokens still to come
        rest = list(it)
    finally:
        eng.stop()
    assert [first] + rest == req.result(timeout=10)


# -- deadlines ------------------------------------------------------------


def test_deadline_reaped_mid_decode_frees_slot(model_and_weights):
    """The satellite contract: a lapsed deadline is honored at the next
    step boundary — the slot frees immediately instead of staying
    pinned for the full max_new_tokens."""
    eng = make_engine(model_and_weights, slots=1, max_seq_len=256,
                      max_new_tokens=200).start()
    reaped0 = stat_get("decode_deadline_exceeded")
    try:
        eng.generate([9, 9], max_new_tokens=2)  # pay the compiles first
        # the on_token sleep paces the engine thread deterministically:
        # ~25 ms/token against a 120 ms deadline -> reaped after a few
        slow = eng.submit([1, 2], max_new_tokens=200, deadline_ms=120,
                          on_token=lambda t: time.sleep(0.025))
        with pytest.raises(serving.DeadlineExceededError):
            slow.result(timeout=60)
        # partial output survives the reap
        assert 0 < len(slow.generated) < 200
        # the slot must be free NOW: a follow-up request completes
        out = eng.generate([5, 5], max_new_tokens=3)
        assert len(out) == 3
        assert eng.free_slots == 1
    finally:
        eng.stop()
    assert stat_get("decode_deadline_exceeded") > reaped0


def test_deadline_reaped_while_queued(model_and_weights):
    eng = make_engine(model_and_weights, slots=1).start()
    try:
        blocker = eng.submit([1], max_new_tokens=8,
                             on_token=lambda t: time.sleep(0.05))
        doomed = eng.submit([2], max_new_tokens=4, deadline_ms=60)
        with pytest.raises(serving.DeadlineExceededError):
            doomed.result(timeout=30)
        assert doomed.generated == []
        blocker.result(timeout=60)
    finally:
        eng.stop()


def test_streaming_deadline_raises_after_partial_yield(
        model_and_weights):
    eng = make_engine(model_and_weights, slots=1, max_seq_len=256,
                      max_new_tokens=200).start()
    try:
        eng.generate([9, 9], max_new_tokens=2)  # pay the compiles first
        req = eng.submit([1, 2], max_new_tokens=200, deadline_ms=120,
                         on_token=lambda t: time.sleep(0.025))
        got = []
        with pytest.raises(serving.DeadlineExceededError):
            for tok in req.tokens(timeout=60):
                got.append(tok)
        assert got == req.generated and len(got) > 0
    finally:
        eng.stop()


# -- admission control ----------------------------------------------------


def test_submit_validation_and_backpressure(model_and_weights):
    eng = make_engine(model_and_weights, slots=1, max_queue=2)
    # not started: queue accepts, nothing drains
    with pytest.raises(serving.RequestTooLargeError):
        eng.submit(list(range(60)), max_new_tokens=10)  # 70 > 64
    with pytest.raises(ValueError):
        eng.submit([])
    eng.submit([1], max_new_tokens=2)
    eng.submit([2], max_new_tokens=2)
    with pytest.raises(serving.QueueFullError):
        eng.submit([3], max_new_tokens=2)
    eng.start()
    try:
        pass
    finally:
        eng.stop(drain=True)  # drains the two queued requests
    with pytest.raises(serving.ServerClosedError):
        eng.submit([4])


def test_unsatisfiable_page_reservation_rejected_at_submit(
        model_and_weights):
    """A reservation the pool can NEVER cover must be rejected at
    submit: queued, it would head-of-line-block the engine forever (no
    finish can free enough pages) and hang stop(drain=True)."""
    # usable pool: 4 pages of 8 = 32 positions; slot capacity is 64
    eng = make_engine(model_and_weights, slots=2, max_seq_len=64,
                      page_size=8, num_pages=5)
    with pytest.raises(serving.RequestTooLargeError, match="pages"):
        eng.submit([1, 2], max_new_tokens=40)  # needs 6 > 4 pages
    # the boundary case still fits and completes
    eng.start()
    try:
        out = eng.generate([1, 2], max_new_tokens=30)
        assert len(out) == 30
    finally:
        eng.stop()


def test_recompute_oracle_safe_while_engine_serving(model_and_weights):
    """The oracle runs on throwaway page pools, so calling it from a
    client thread must not race the engine thread's donating step."""
    eng = make_engine(model_and_weights, slots=1, max_seq_len=256,
                      max_new_tokens=200).start()
    try:
        eng.generate([9, 9], max_new_tokens=2)  # pay the compiles
        req = eng.submit([1, 2], max_new_tokens=60,
                         on_token=lambda t: time.sleep(0.005))
        for _ in range(10):  # concurrent with live decode steps
            eng.recompute_logits([3, 1, 4])
        out = req.result(timeout=120)
    finally:
        eng.stop()
    assert len(out) == 60  # no step died on a deleted/donated buffer


def test_stop_without_drain_cancels(model_and_weights):
    eng = make_engine(model_and_weights, slots=1)
    r1 = eng.submit([1], max_new_tokens=4)
    eng.stop(drain=False)
    with pytest.raises(serving.ServerClosedError):
        r1.result(timeout=5)


def test_stop_without_drain_fails_the_dispatch_in_flight(
        model_and_weights):
    """An aborted engine completes nothing: a request whose last
    dispatch is already running when stop(drain=False) lands fails like
    the slots the loop still finds live, whatever the timing (the disagg
    router's kill-and-redispatch rests on this)."""
    import threading

    eng = make_engine(model_and_weights, slots=1).start()
    real = eng._exe.run_persistent
    entered, release = threading.Event(), threading.Event()

    def held(*a, **kw):
        entered.set()
        assert release.wait(60)
        return real(*a, **kw)

    eng._exe.run_persistent = held
    # one new token: the prefill dispatch is also the request's last
    req = eng.submit([1, 2, 3], max_new_tokens=1)
    assert entered.wait(60)
    stopper = threading.Thread(target=eng.stop, kwargs={"drain": False})
    stopper.start()
    while not eng._abort:
        time.sleep(0.001)
    release.set()
    with pytest.raises(serving.ServerClosedError):
        req.result(timeout=60)
    stopper.join(60)
    assert not stopper.is_alive()


# -- sampling determinism (satellite) -------------------------------------


def test_sampling_filters_unit():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sampling_ops import (filter_top_k_top_p,
                                             sample_tokens)

    rs = np.random.RandomState(3)
    logits = jnp.asarray(rs.randn(5, 17).astype("f4"))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(5))
    ids = np.argsort(np.asarray(logits), axis=-1)

    filt = np.asarray(filter_top_k_top_p(
        logits, jnp.full((5,), 3, jnp.int32), jnp.ones((5,))))
    assert ((filt > -np.inf).sum(-1) == 3).all()
    assert (np.take_along_axis(filt, ids[:, -3:], -1) > -np.inf).all()

    # top_k=1 and near-zero top_p both collapse to greedy
    g = np.asarray(logits).argmax(-1)
    t1 = sample_tokens(keys, logits, jnp.ones((5,)),
                       jnp.full((5,), 1, jnp.int32), jnp.ones((5,)))
    t2 = sample_tokens(keys, logits, jnp.ones((5,)),
                       jnp.zeros((5,), jnp.int32), jnp.full((5,), 1e-6))
    t3 = sample_tokens(keys, logits, jnp.zeros((5,)),
                       jnp.zeros((5,), jnp.int32), jnp.ones((5,)))
    assert (np.asarray(t1) == g).all()
    assert (np.asarray(t2) == g).all()
    assert (np.asarray(t3) == g).all()
    # explicit key thread: same key -> same draw, jit-stable
    jit = jax.jit(sample_tokens)
    a = jit(keys, logits, jnp.ones((5,)), jnp.full((5,), 8, jnp.int32),
            jnp.full((5,), 0.9))
    b = jit(keys, logits, jnp.ones((5,)), jnp.full((5,), 8, jnp.int32),
            jnp.full((5,), 0.9))
    assert (np.asarray(a) == np.asarray(b)).all()


def _sample_tokens_unconditional(keys, logits, temperature, top_k, top_p):
    """The sampler as it was before it chose its work from the knobs:
    every batch sorts, filters and draws, then greedy rows take the
    argmax.  ``sample_tokens`` is held to this bit for bit."""
    import jax
    import jax.numpy as jnp

    v = logits.shape[-1]
    greedy = temperature <= 0.0
    t = jnp.where(greedy, 1.0, temperature)
    x = logits / t[..., None]
    desc = jnp.flip(jnp.sort(x, axis=-1), axis=-1)
    k_idx = jnp.clip(top_k - 1, 0, v - 1)
    thresh_k = jnp.take_along_axis(desc, k_idx[..., None], axis=-1)
    keep_k = (top_k <= 0)[..., None] | (x >= thresh_k)
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p[..., None]
    thresh_p = jnp.min(jnp.where(keep_sorted, desc, jnp.inf), axis=-1,
                       keepdims=True)
    keep_p = (top_p >= 1.0)[..., None] | (x >= thresh_p)
    filt = jnp.where(keep_k & keep_p, x, -jnp.inf)
    drawn = jax.vmap(jax.random.categorical)(keys, filt)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                     drawn.astype(jnp.int32))


_ROWS = 6
# (temperature, top_k, top_p) a row; a scalar stands for every row
_SAMPLING_MIXES = {
    "all_greedy": (0.0, 0, 1.0),
    "all_temperature_only": ([0.7, 1.0, 1.3, 0.2, 2.0, 1.0], 0, 1.0),
    "all_top_k": (1.0, [1, 2, 3, 5, 8, 40], 1.0),
    "all_top_p": ([0.8, 1.0, 1.0, 1.2, 1.0, 0.5], 0,
                  [0.1, 0.5, 0.9, 0.95, 0.99, 0.3]),
    "one_filtered_among_greedy": ([0, 0, 0, 0.9, 0, 0],
                                  [0, 0, 0, 7, 0, 0],
                                  [1, 1, 1, 0.95, 1, 1]),
    "one_temperature_only_among_greedy": ([0, 1.1, 0, 0, 0, 0], 0, 1.0),
    # a greedy caller that leaves its knobs set: they are never read
    "greedy_with_knobs_among_temperature_only": (
        [1.0, 0, 0.6, 1.0, 0, 1.5], [0, 5, 0, 0, 0, 0],
        [1, 1, 1, 1, 0.5, 1]),
    # what _step_args hands over for a step of dead slots
    "dead_slot_record": (0.0, 0, 1.0),
}


def _sampling_mix(name):
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(11)
    logits = rs.randn(_ROWS, 97).astype("f4") * 3.0
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(_ROWS) + 40)
    if name == "dead_slot_record":
        # a dead slot's key and counter are zeros too
        keys = jnp.zeros_like(keys)
    temp, top_k, top_p = _SAMPLING_MIXES[name]
    return (keys, jnp.asarray(logits),
            jnp.asarray(np.broadcast_to(temp, (_ROWS,)), jnp.float32),
            jnp.asarray(np.broadcast_to(top_k, (_ROWS,)), jnp.int32),
            jnp.asarray(np.broadcast_to(top_p, (_ROWS,)), jnp.float32))


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("mix", sorted(_SAMPLING_MIXES))
def test_sample_tokens_bitwise_equals_unconditional_formula(mix, jitted):
    import jax

    from paddle_tpu.ops.sampling_ops import sample_tokens

    args = _sampling_mix(mix)
    new, old = sample_tokens, _sample_tokens_unconditional
    if jitted:
        new, old = jax.jit(new), jax.jit(old)
    got, want = np.asarray(new(*args)), np.asarray(old(*args))
    assert got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got, want)


_VOCAB_WIDE_WORK = ("sort", "cumsum", "random_bits", "threefry2x32")


def _primitives(jaxpr, *, into_conditionals):
    """Names of the primitives of ``jaxpr`` and of every jaxpr nested in
    it; a ``cond``'s branches only where ``into_conditionals``."""
    import jax

    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not into_conditionals:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _primitives(sub, into_conditionals=into_conditionals)
    return names


def test_sample_tokens_sorts_and_draws_only_inside_a_conditional():
    import jax

    from paddle_tpu.ops.sampling_ops import sample_tokens

    jaxpr = jax.make_jaxpr(sample_tokens)(
        *_sampling_mix("all_greedy")).jaxpr
    top = _primitives(jaxpr, into_conditionals=False)
    assert "cond" in top
    assert not set(top) & set(_VOCAB_WIDE_WORK), top
    every = _primitives(jaxpr, into_conditionals=True)
    # the work is still there, behind the predicates: a sort inside the
    # filter's conditional inside the draw's
    assert {"sort", "cumsum"} <= set(every), every
    assert every.count("cond") == 2
    (outer,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    greedy_branch, draw_branch = outer.params["branches"]
    assert "sort" not in _primitives(greedy_branch.jaxpr,
                                     into_conditionals=True)
    assert "sort" not in _primitives(draw_branch.jaxpr,
                                     into_conditionals=False)


def _unconditional_lines(text):
    """The lines of a lowered module that run whatever any conditional
    takes: ``@main`` and the functions it calls, without the regions of
    ``stablehlo.case`` / ``stablehlo.if`` (and what only they call)."""
    funcs, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@([\w.]+)\(",
                     line)
        if m:
            cur = funcs.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    seen, todo, out = set(), ["main"], []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        closer = None  # indentation of the conditional we are inside
        for line in funcs[name]:
            indent = len(line) - len(line.lstrip())
            if closer is not None:
                if indent == closer and line.lstrip().startswith("})"):
                    closer = None
                continue
            if re.search(r"stablehlo\.(case|if)\b", line):
                closer = indent
                continue
            out.append(line)
            todo += re.findall(r"call @([\w.]+)\(", line)
    return out


def test_engine_step_sorts_the_vocabulary_only_inside_a_conditional(
        model_and_weights):
    eng = make_engine(model_and_weights, slots=3)
    text = eng.lower_step().as_text()
    wide = f"x{VOCAB}xui32"  # random bits a vocabulary wide
    assert "stablehlo.sort" in text and wide in text
    always = "\n".join(_unconditional_lines(text))
    assert "stablehlo.dot_general" in always  # the model is in there
    for work in ("stablehlo.sort", "cumsum", "reduce_window", wide):
        assert work not in always, work
    # the whole-prompt prefill's one-row tail likewise
    text = eng.lower_prefill(16).as_text()
    assert "stablehlo.sort" in text
    always = "\n".join(_unconditional_lines(text))
    assert "stablehlo.dot_general" in always
    assert "stablehlo.sort" not in always


def _sampler_counts():
    return np.array([stat_get("decode_steps"),
                     stat_get("decode_steps_drawn"),
                     stat_get("decode_steps_filtered")])


def test_sampling_request_same_tokens_beside_greedy_neighbours(
        model_and_weights):
    """The branch the step's sampler takes depends on the whole batch;
    a request's tokens do not.  The counters say how many joint steps
    drew and how many sorted."""
    kw = dict(max_new_tokens=8, temperature=1.0, top_k=7, top_p=0.95,
              seed=123)
    # no prefix cache: a prompt seen before would take its first token
    # from a joint step, and the counts below are of steps
    eng = make_engine(model_and_weights, slots=3, max_new_tokens=16,
                      prefix_cache=False).start()
    try:
        c0 = _sampler_counts()
        greedy_alone = eng.generate([9] * 5, max_new_tokens=8)
        c1 = _sampler_counts()
        # an all-greedy run takes the argmax alone: 7 joint steps (the
        # first token is the prefill's), none drawn
        assert (c1 - c0).tolist() == [7, 0, 0]
        alone = eng.generate([4, 5, 6], **kw)
        c2 = _sampler_counts()
        assert (c2 - c1).tolist() == [7, 7, 7]
        # beside greedy neighbours that are there before it and outlive
        # it: only the steps it is live in draw and sort
        others = [eng.submit([9] * 5, max_new_tokens=16),
                  eng.submit([3, 1, 4, 1, 5], max_new_tokens=16)]
        beside = eng.generate([4, 5, 6], **kw)
        outs = [o.result(timeout=120) for o in others]
        c3 = _sampler_counts()
        steps, drawn, filtered = (c3 - c2).tolist()
        assert drawn == filtered == 7 and steps >= 15
        # temperature alone draws without the sort
        eng.generate([4, 5, 6], max_new_tokens=8, temperature=0.8, seed=5)
        assert (_sampler_counts() - c3).tolist() == [7, 7, 0]
    finally:
        eng.stop()
    assert beside == alone and len(alone) == 8
    # and the greedy neighbour's tokens are what it emits alone
    assert outs[0][:8] == greedy_alone


def test_two_replicas_same_seed_emit_identical_tokens(
        model_and_weights):
    """The PR 7 sharding-invariant-RNG guarantee carried to serving:
    stochastic sampling is keyed by request seed + token index only,
    so replica choice, slot index, and batch neighbors cannot change
    a request's tokens."""
    kw = dict(max_new_tokens=8, temperature=1.0, top_k=7, top_p=0.95,
              seed=123)
    eng_a = make_engine(model_and_weights, slots=2).start()
    try:
        out_a = eng_a.generate([4, 5, 6], **kw)
    finally:
        eng_a.stop()
    eng_b = make_engine(model_and_weights, slots=3).start()
    try:
        # occupy slot 0 first so the same request lands on a DIFFERENT
        # slot with different neighbors on replica B
        other = eng_b.submit([9] * 5, max_new_tokens=8, seed=999)
        out_b = eng_b.generate([4, 5, 6], **kw)
        other.result(timeout=120)
    finally:
        eng_b.stop()
    assert out_a == out_b
    assert len(out_a) == 8


# -- executor persistent entry --------------------------------------------


def test_executor_run_persistent_state_stays_on_device():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.scope import Scope, is_device_array

    scope = Scope()
    scope.set_var("acc", jnp.zeros((4,), jnp.float32))
    exe = pt.Executor(pt.CPUPlace())

    @jax.jit
    def step(state, delta):
        (acc,) = state
        acc = acc + delta
        return (jnp.sum(acc),), (acc,)

    d0 = stat_get("executor_steps_dispatched")
    exe.run_persistent(step, ("acc",), args=(jnp.ones((4,)),),
                       scope=scope)
    (total,) = exe.run_persistent(step, ("acc",),
                                  args=(jnp.ones((4,)),), scope=scope)
    assert float(total) == 8.0
    acc = scope.get_var("acc")
    assert is_device_array(acc)  # never round-tripped to host
    np.testing.assert_array_equal(np.asarray(acc), np.full((4,), 2.0))
    assert stat_get("executor_steps_dispatched") == d0 + 2
    with pytest.raises(KeyError):
        exe.run_persistent(step, ("missing",), scope=scope)


# -- throughput: cache, not recompute -------------------------------------


def test_per_token_cost_flat_as_sequence_grows(model_and_weights):
    """8x more generated tokens must cost ~8x the wall time (cached
    decode: O(1) per token).  A prefix-recompute engine would be ~8x
    per-token slower at the long length; the 2.5x bound leaves room
    for CPU timing noise while still refuting recompute."""
    eng = make_engine(model_and_weights, slots=1, max_seq_len=256,
                      max_new_tokens=200).start()
    try:
        eng.generate([1, 2], max_new_tokens=140)  # warm every compile

        t0 = time.monotonic()
        eng.generate([1, 2], max_new_tokens=16)
        per_tok_short = (time.monotonic() - t0) / 16

        t0 = time.monotonic()
        eng.generate([1, 2], max_new_tokens=128)
        per_tok_long = (time.monotonic() - t0) / 128
    finally:
        eng.stop()
    assert per_tok_long < 2.5 * per_tok_short, (
        f"per-token cost grew {per_tok_long / per_tok_short:.2f}x over "
        f"an 8x longer generation — cache is not being reused")


# -- open-loop load smoke (capped for tier-1) -----------------------------


def test_poisson_open_loop_smoke(model_and_weights):
    rs = np.random.RandomState(0)
    eng = make_engine(model_and_weights, slots=4).start()
    tok0 = stat_get("decode_tokens_total")
    ttft0 = histogram("ttft_seconds").count
    try:
        reqs = []
        for i in range(12):
            plen = int(rs.randint(1, 12))
            reqs.append(eng.submit(
                list(rs.randint(0, VOCAB, plen)),
                max_new_tokens=int(rs.randint(2, 8)), seed=i))
            time.sleep(float(rs.exponential(0.01)))  # open loop
        outs = [r.result(timeout=120) for r in reqs]
    finally:
        eng.stop()
    produced = sum(len(o) for o in outs)
    assert all(outs)
    assert stat_get("decode_tokens_total") - tok0 == produced
    assert histogram("ttft_seconds").count - ttft0 == len(reqs)
    # the decode series must be on the Prometheus exposition
    from paddle_tpu.observe.histogram import prometheus_text

    text = prometheus_text()
    for series in ("decode_tokens_total", "decode_slot_occupancy",
                   "ttft_seconds", "tpot_seconds"):
        assert series in text, series


# -- multi-replica server -------------------------------------------------


def test_decode_server_least_loaded_dispatch_and_stats(
        model_and_weights):
    model, weights = model_and_weights
    cfg = DecodeConfig(slots=1, max_seq_len=64, page_size=8,
                       max_new_tokens=6)
    srv = serving.DecodeServer(model, weights, cfg, replicas=2,
                               http_port=0).start()
    try:
        # 2 one-slot replicas + slow-paced tokens: concurrent requests
        # must spread across BOTH replicas
        reqs = [srv.submit([i + 1], max_new_tokens=4,
                           on_token=lambda t: time.sleep(0.01))
                for i in range(4)]
        outs = [r.result(timeout=120) for r in reqs]
        assert all(len(o) == 4 for o in outs)
        st = srv.stats()
        assert st["n_replicas"] == 2
        assert len(st["replicas"]) == 2
        per_replica = [p["tokens_total"] for p in st["replicas"]]
        assert all(t > 0 for t in per_replica), per_replica
        assert st["tokens_total"] == sum(per_replica) == 16

        # per-replica stats over real HTTP
        url = f"http://127.0.0.1:{srv.http_port}"
        via_http = json.loads(
            urllib.request.urlopen(f"{url}/stats", timeout=10).read())
        assert via_http["n_replicas"] == 2
        assert {p["name"] for p in via_http["replicas"]} == \
            {"replica-0", "replica-1"}
        health = json.loads(
            urllib.request.urlopen(f"{url}/health", timeout=10).read())
        assert health["status"] == "ok" and health["replicas"] == 2
        metrics = urllib.request.urlopen(
            f"{url}/metrics", timeout=10).read().decode()
        assert "decode_tokens_total" in metrics
    finally:
        srv.stop()


# sha256 of the lowered text of the module fixture's model's joint step
# and 64-row whole-prompt prefill behind ``make_engine``, as PR 48's tree
# lowers them (taken before PR 50 touched the engine)
PROGRAMS_AS_LOWERED = {
    "step": "7d26b3dd3ebe8718647a3594fbb670062cfe6bdb14bc7c25ed85c425a8b49386",
    "prefill":
        "05f1fab5bce98713758f0ca7432cfba732f840e4e5e73c96a38054580f90db20"}


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_the_programs_are_still_the_ones_lowered_before_cache_layers(
        model_and_weights, program):
    """A model that declares neither ``cache_layers`` nor ``tallies``
    hands ``attend`` a Python ``int`` a layer and counts nothing: its
    joint step and whole-prompt prefill lower to the text they had
    before a model could own more cache layers than weight layers (PR
    50).  A change MEANT to move these programs replaces the digests;
    one that was not has found out here."""
    import hashlib

    eng = make_engine(model_and_weights)
    text = (eng.lower_step() if program == "step"
            else eng.lower_prefill(64)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PROGRAMS_AS_LOWERED[program]
