"""A cache of ONE K/V head of whole lane tiles keeps K and V of a
position side by side in one pool row (`serving/kv_cache.py`
``CacheConfig.joint``): the rule read from the shape, the paged kernel's
joint call bit for bit the two-pool call, the books over one array, and
an engine at that width decoding what the full-recompute oracle gives
through every path that reads or writes the pool.
"""
import numpy as np
import pytest

from paddle_tpu.framework.scope import Scope
from paddle_tpu.monitor import stat_get
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine, \
    TransformerLM
from paddle_tpu.serving import kv_cache
from paddle_tpu.serving.kv_cache import CacheConfig, PagedKVCache

VOCAB = 61
PAGE = 16


# -- the rule -------------------------------------------------------------

def _config(heads=1, head_dim=128, **kw):
    return CacheConfig(2, heads, head_dim, num_slots=3, max_seq_len=64,
                       page_size=8, dtype="bfloat16", **kw)


@pytest.mark.parametrize("kw, joint, row, v_row", [
    (dict(), True, 256, 0),                          # Jamba2-3B's head
    (dict(v_head_dim=256), True, 384, 0),            # V wider than K
    (dict(head_dim=8), False, 8, 8),                 # a toy width
    (dict(head_dim=192, v_head_dim=128), False, 192, 128),  # K off a tile
    (dict(heads=2), False, 256, 256),                # two K/V heads
    (dict(quantized=True), False, 128, 128),         # int8 pages
    (dict(latent=True, head_dim=576, v_head_dim=512), False, 640, 0),
], ids=["1x128+128", "1x128+256", "1x8", "1x192+128", "2x128", "int8",
        "latent"])
def test_the_cache_reads_from_its_shape_whether_rows_are_joint(
        kw, joint, row, v_row):
    """One unquantized K/V head whose keys and values are each whole
    lane tiles wide shares a row; every other shape keeps what it had."""
    c = _config(**kw)
    assert c.joint is joint
    assert (c.row_lanes, c.v_row_lanes) == (row, v_row)
    assert c.pool_shape() == (2, 3 * 8 + 1, 8, row)
    names = PagedKVCache(c, Scope()).state_var_names()
    assert names[0] == kv_cache.K_PAGES_VAR
    assert (kv_cache.V_PAGES_VAR in names) == bool(v_row)
    assert c.attended_lanes() == (
        (c.head_dim, c.v_head_dim) if joint else (row, v_row))


def test_a_joint_cache_costs_the_bytes_of_the_two_pools_it_replaces():
    joint = _config()
    # the same rows at two K/V heads of 64 lanes: two pools
    two = _config(heads=2, head_dim=64)
    assert joint.joint and not two.joint
    for c in (joint, two):
        assert c.lane_dense
        assert c.per_page_pool_bytes() == 2 * 8 * 256 * 2
        assert c.cache_bytes() == 25 * 2 * 8 * 256 * 2
    assert joint.page_bytes() == 2 * two.page_bytes() == 8 * 256 * 2
    assert joint.page_bytes(v=True) == 0


# -- the kernel's joint call ----------------------------------------------

def _pools(rng, n_pages, d, dv, dtype):
    import jax.numpy as jnp

    k = jnp.asarray(rng.randn(2, n_pages, PAGE, d), dtype)
    v = jnp.asarray(rng.randn(2, n_pages, PAGE, dv), dtype)
    return k, v, jnp.concatenate([k, v], axis=-1)


def _tables(rng, lengths, pps):
    """A table a slot whose live entries are distinct pages in a
    shuffled order and whose dead entries name page 0."""
    slots = len(lengths)
    table = np.zeros((slots, pps), np.int32)
    perm = 1 + rng.permutation(slots * pps).reshape(slots, pps)
    for s, n in enumerate(lengths):
        live = -(-int(n) // PAGE)
        table[s, :live] = perm[s, :live]
    return table


@pytest.mark.parametrize("dtype, dv", [
    ("bfloat16", 128), ("bfloat16", 256), ("float32", 128)])
def test_the_joint_call_is_the_two_pool_call_bit_for_bit(dtype, dv):
    """Twenty query heads on the one K/V head (Jamba2-3B's stack of 20
    rows; bfloat16 pools walk blocks of 512 positions): whole blocks, a
    partial last block, a length that ends mid-page, a dead slot; the
    plain path slices the same row."""
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_decode_attention as pda

    rng = np.random.RandomState(0)
    d, pps = 128, 72
    lengths = np.array([1024, 700, 0, 517, 1152], np.int32)
    k, v, kv = _pools(rng, len(lengths) * pps + 1, d, dv, dtype)
    table = jnp.asarray(_tables(rng, lengths, pps))
    q = jnp.asarray(rng.randn(len(lengths), 20, d), jnp.float32)
    if dtype == "bfloat16":
        assert pda.pages_per_block(PAGE, pps, d, k.dtype, dv, 1, 20) == 32
    args = (table, jnp.asarray(lengths))
    kernel = dict(layer=1, use_pallas="always", interpret=True)
    one = dict(value_lanes=dv, value_offset=d)
    two_pools = np.asarray(pda.paged_decode_attention(q, k, v, *args,
                                                      **kernel))
    joint = np.asarray(pda.paged_decode_attention(q, kv, None, *args,
                                                  **kernel, **one))
    plain = np.asarray(pda.paged_decode_attention(
        q, kv, None, *args, layer=1, use_pallas="never", **one))
    live = lengths > 0
    assert joint.shape == (len(lengths), 20, dv)
    assert np.isfinite(joint).all()
    assert joint[live].tobytes() == two_pools[live].tobytes()
    np.testing.assert_allclose(joint[live], plain[live], rtol=2e-5,
                               atol=2e-5)
    assert plain[live].tobytes() == np.asarray(pda.paged_decode_attention(
        q, k, v, *args, layer=1, use_pallas="never"))[live].tobytes()


@pytest.mark.parametrize("heads", [1, 4])
def test_the_joint_call_at_several_rows_a_slot(heads):
    """A chunk's or a verify window's rows, each with its own causal
    length, under the TPU interpreter whose fresh buffers read NaN: a
    page that was never copied, or a dead position of the values' half,
    would show."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.pallas_decode_attention import paged_chunk_attention

    rng = np.random.RandomState(1)
    d, rows, pps = 128, 5, 12
    ends = np.array([150, 37, 192], np.int32)        # the last row's length
    lengths = np.maximum(ends[:, None] - np.arange(rows)[::-1], 0)
    k, v, kv = _pools(rng, len(ends) * pps + 1, d, d, "bfloat16")
    table = jnp.asarray(_tables(rng, ends, pps))
    q = jnp.asarray(rng.randn(len(ends), rows, heads, d), jnp.float32)
    args = (table, jnp.asarray(lengths.astype(np.int32)))
    nan = pltpu.InterpretParams(uninitialized_memory="nan")
    two_pools = np.asarray(paged_chunk_attention(
        q, k, v, *args, use_pallas="always", interpret=nan))
    joint = np.asarray(paged_chunk_attention(
        q, kv, None, *args, use_pallas="always", interpret=nan,
        value_lanes=d, value_offset=d))
    plain = np.asarray(paged_chunk_attention(
        q, kv, None, *args, use_pallas="never", value_lanes=d,
        value_offset=d))
    assert np.isfinite(joint).all()
    assert joint.tobytes() == two_pools.tobytes()
    np.testing.assert_allclose(joint, plain, rtol=2e-5, atol=2e-5)


def test_the_joint_call_is_the_k_and_v_body_under_its_name():
    """Three bfloat16 terms, the 512-position block and the name the
    accepted metrics find: none of the latent body's."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_decode_attention as pda

    kv = jax.ShapeDtypeStruct((1, 65, PAGE, 256), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((2, 1, 20, 128), jnp.float32)
    table = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    lens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    text = str(jax.make_jaxpr(lambda q, kv, t, n: pda.paged_chunk_attention(
        q, kv, None, t, n, use_pallas="always", interpret=False,
        value_lanes=128, value_offset=128))(q, kv, table, lens))
    assert f"name={pda.KERNEL_NAME}\n" in text
    assert pda.LATENT_KERNEL_NAME not in text
    # both buffers of a block of 512 positions of the joint row, and no
    # V buffer; the query and the probabilities as three groups of 32 rows
    assert "bf16[2,32,16,256]" in text and "bf16[2,32,16,128]" not in text
    assert "bf16[1,96,128]" in text and "bf16[1,96,512]" in text
    with pytest.raises(ValueError, match="joint pool"):
        pda.paged_chunk_attention(
            jnp.zeros((2, 1, 20, 128)), jnp.zeros(kv.shape, kv.dtype),
            None, jnp.zeros((2, 64), jnp.int32),
            jnp.ones((2, 1), jnp.int32), use_pallas="always",
            interpret=True, value_lanes=128, value_offset=192)


# -- the books over one array ---------------------------------------------

def test_claim_write_copy_on_write_release_on_a_joint_pool():
    """The free list, the tables, the refcounts and the prefix index see
    one array where they saw two and nothing else: a request's pages
    are written jointly, a second request borrows its partial tail page
    and copies it before writing, and every page comes back."""
    import jax.numpy as jnp

    c = CacheConfig(2, 1, 128, num_slots=2, max_seq_len=64, page_size=8,
                    dtype="float32")
    scope = Scope()
    cache = PagedKVCache(c, scope)
    assert cache.state_var_names() == (kv_cache.K_PAGES_VAR,)
    assert cache.arrays()[1] is None and cache.prefix is not None
    rng = np.random.RandomState(2)
    tokens = list(range(1, 13))                    # a page and a half
    assert cache.claim(0, 24, tokens).hit_tokens == 0
    pages = cache.slot_pages(0)
    k = rng.randn(16, 1, 128).astype(np.float32)
    v = rng.randn(16, 1, 128).astype(np.float32)
    pool, _ = kv_cache.write_prompt_layer(
        scope.get_var(kv_cache.K_PAGES_VAR), None, 1,
        jnp.concatenate([k, v], axis=-1), jnp.asarray(pages[:2]))
    scope.set_var(kv_cache.K_PAGES_VAR, pool)
    cache.lengths[0] = len(tokens)
    row = np.asarray(pool)[1, pages[1], 3]
    assert row.shape == (256,) and row.tobytes() == np.concatenate(
        [k[11, 0], v[11, 0]]).tobytes()
    cache.debug_check()
    cache.release(0, register_tokens=tokens)
    # the same prompt again: the whole page shared, the partial borrowed
    plan = cache.claim(1, 24, tokens)
    assert (plan.full_hits, plan.partial) == (1, True)
    assert not cache.writable(1, len(tokens) - 1)
    (src, dst), = cache.plan_cow(1, [len(tokens)])
    assert src == pages[1] and cache.writable(1, len(tokens))
    pool = scope.get_var(kv_cache.K_PAGES_VAR)
    scope.set_var(kv_cache.K_PAGES_VAR, pool.at[:, dst].set(pool[:, src]))
    assert np.asarray(scope.get_var(kv_cache.K_PAGES_VAR))[
        1, dst, 3].tobytes() == row.tobytes()
    cache.debug_check()
    exported = cache.export_pages(cache.slot_pages(1)[:2])
    assert {n: a.shape for n, a in exported.items()} == {
        kv_cache.K_PAGES_VAR: (2, 2, 8, 256)}
    cache.release(1)
    cache.debug_check()


# -- an engine at ONE K/V head of 128 lanes -------------------------------

@pytest.fixture(scope="module")
def one_head():
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=128, num_layers=2,
                          num_heads=1, max_seq_len=256)
    return model, model.init_weights(jax.random.PRNGKey(11))


def _engine(one_head, draft=(None, None), **cfg):
    cfg = dict(dict(slots=2, max_seq_len=64, page_size=8, max_new_tokens=8),
               **cfg)
    return DecodeEngine(*one_head, DecodeConfig(**cfg),
                        draft_model=draft[0], draft_weights=draft[1])


def _assert_oracle_bitwise(eng, prompt, req, out):
    for t in range(len(out)):
        oracle = eng.recompute_logits(list(prompt) + list(out[:t]))
        assert np.array_equal(oracle, req.logits_trace[t]), (
            t, np.abs(oracle - req.logits_trace[t]).max())
        assert out[t] == int(np.argmax(oracle))


@pytest.mark.parametrize("path", [
    "whole_prompt", "chunked", "suffix_hit", "full_hit_cow", "speculative"])
def test_an_engine_of_one_head_of_128_lanes_decodes_the_oracles_tokens(
        one_head, path):
    """Through the whole-prompt prefill, decode steps that cross page
    boundaries, chunks, the suffix after a prefix hit, a borrowed tail
    page's copy-on-write and a verify window beside a draft model's TWO
    pools: one scatter a layer into the joint pool, logits bitwise the
    full-recompute oracle's."""
    import jax

    cfg, draft = {}, (None, None)
    if path == "chunked":
        cfg.update(prefill_chunk_pages=1, prefix_cache=False)
    if path == "speculative":
        dm = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                           num_heads=2, max_seq_len=256)
        draft = (dm, dm.init_weights(jax.random.PRNGKey(99)))
        cfg.update(spec_k=3)
    base = list(range(1, 17))                      # two whole pages
    prompt = {"whole_prompt": base + [20, 21, 22],
              # three one-page chunks; with its reply inside the bucket of
              # 32 rows, past which this width's CPU products round apart
              "chunked": list(range(1, 20)),
              "suffix_hit": base + [40, 41, 42],
              "full_hit_cow": base + [20, 21, 22],
              "speculative": base + [20, 21, 22]}[path]
    eng = _engine(one_head, draft, **cfg).start()
    try:
        assert eng._cache.config.joint
        assert stat_get("decode_kv_joint_rows") == 1
        assert stat_get("decode_kv_pool_row_lanes") == 256
        assert [tuple(eng._scope.get_var(n).shape)
                for n in eng._cache.state_var_names()] == [(2, 17, 8, 256)]
        if path in ("suffix_hit", "full_hit_cow"):
            eng.generate(base + [20, 21, 22], max_new_tokens=2)
        chunks0 = stat_get("prefill_chunks")
        hits0 = stat_get("decode_prefix_pages_hit")
        cow0 = stat_get("decode_cow_copies")
        r = eng.submit(prompt, max_new_tokens=9, record_logits=True,
                       speculative=path == "speculative")
        out = r.result(timeout=300)
    finally:
        eng.stop()
    assert stat_get("prefill_chunks") - chunks0 == {
        "chunked": 3, "suffix_hit": 1}.get(path, 0)
    if path == "suffix_hit":
        assert stat_get("decode_prefix_pages_hit") - hits0 == 2
    if path == "full_hit_cow":
        assert stat_get("decode_cow_copies") - cow0 == 1
    assert len(out) == 9 and len(r.logits_trace) == 9
    _assert_oracle_bitwise(eng, prompt, r, out)
    eng._cache.debug_check()


def test_the_joint_engine_through_the_kernel_reads_the_plain_paths_logits(
        one_head):
    """The kernel (interpreted) over the engine's joint pool against the
    gather over the same cache, prefill and nine decode steps."""
    traces = []
    for use_pallas in ("always", "never"):
        with _engine(one_head, use_pallas=use_pallas, interpret=True,
                     cache_dtype="bfloat16") as eng:
            assert stat_get("decode_kv_joint_rows") == 1
            r = eng.submit(list(range(3, 24)), max_new_tokens=9,
                           record_logits=True)
            r.result(timeout=300)
            traces.append(np.stack(r.logits_trace))
    np.testing.assert_allclose(traces[0], traces[1], atol=5e-4)


def test_an_engine_of_two_heads_keeps_two_pools():
    import jax

    model = TransformerLM(vocab_size=VOCAB, d_model=256, num_layers=1,
                          num_heads=2, max_seq_len=64)
    weights = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    eng = DecodeEngine(model, weights, DecodeConfig(
        slots=2, max_seq_len=32, page_size=8))
    assert stat_get("decode_kv_joint_rows") == 0
    assert stat_get("decode_kv_pool_row_lanes") == 256
    assert len(eng._cache.state_var_names()) == 2


def test_a_joint_page_is_handed_over_between_engines(one_head):
    """Disaggregated serving's export and install see one array: a
    prompt prefilled on one engine decodes on another the tokens the
    one engine alone gives."""
    from paddle_tpu.serving.disagg import DisaggConfig, DisaggServer

    prompt = list(range(5, 30))
    cfg = DecodeConfig(slots=2, max_seq_len=64, page_size=8,
                       max_new_tokens=8)
    with DecodeEngine(*one_head, cfg) as eng:
        want = eng.generate(prompt, max_new_tokens=8)
    pages0 = stat_get("migrate_pages_total")
    with DisaggServer(*one_head, config=cfg, disagg=DisaggConfig(
            prefill_replicas=1, decode_replicas=1)) as srv:
        got = srv.submit(prompt, max_new_tokens=8).result(timeout=300)
    assert got == want
    assert stat_get("migrate_pages_total") - pages0 == 4
