"""The hybrid linear/softmax-attention model with a held share of its
experts (``serving/hybrid_moe_lm.py``) behind the real ``DecodeEngine``,
against the plain reference (``benchmark/reference/hybrid_moe_lm.py``, the
one the cell's check uses): float32, seeded, tiny."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving import mixers
from paddle_tpu.serving.hybrid_moe_lm import HybridMoELM

from benchmark.reference import hybrid_moe_lm as ref

PERIOD = ("attention", "recurrent", "recurrent", "recurrent")
VOCAB = 97


def make_model(kinds=PERIOD, held=(0, 1, 2, 3, 4), **kw):
    sizes = dict(vocab_size=VOCAB, d_model=32, layer_kinds=kinds,
                 num_heads=4, num_kv_heads=2, head_dim=8, lin_heads=2,
                 lin_head_dim=8, conv_kernel=4, gate_rank=4, num_experts=16,
                 top_k=4, held_experts=held, expert_dim=16, shared_dim=16,
                 dtype="float32")
    sizes.update(kw)
    return HybridMoELM(**sizes)


def dims(m, held=None):
    return dict(num_heads=m.num_heads, num_kv_heads=m.num_kv_heads,
                head_dim=m.head_dim, lin_heads=m.lin_heads,
                lin_head_dim=m.lin_head_dim, conv_kernel=m.conv_kernel,
                top_k=m.top_k, held=list(held or m.held_experts),
                expert_dim=m.expert_dim, eps=m.rms_eps,
                kinds=list(m.layer_kinds))


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=64, page_size=8), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def served_vs_reference(eng, model, weights, prompts, n_new=5):
    """Worst |dlogit| over the prompts' prefill and decode positions,
    the reference given the server's own tokens (its own routing)."""
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = 0.0
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=300)
        got = np.stack(r.logits_trace)
        want, _ = ref.forward_logits(
            weights, jnp.asarray(p + toks[:-1], jnp.int32), dims(model))
        assert got.shape == (n_new, VOCAB)
        worst = max(worst, float(np.abs(
            got - np.asarray(want)[len(p) - 1:]).max()))
        # the recorded routing is the reference's own: prefill rows then
        # one row a step, [positions, layers, k]
        routed = r.records["moe_topk"]
        ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
        assert ids.shape == (len(p) + n_new - 1, model.num_layers,
                             model.top_k)
        _, gap = ref.forward_logits(
            weights, jnp.asarray(p + toks[:-1], jnp.int32), dims(model),
            routing=jnp.asarray(ids))
        assert float(gap.max()) == 0.0
    return worst


@pytest.mark.parametrize("kinds", [("attention",), ("recurrent",), PERIOD],
                         ids=["softmax", "kda", "period"])
def test_prefill_then_decode_matches_the_reference(kinds):
    model = make_model(kinds)
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 19, 11, 8)]
    with engine(model, weights) as eng:
        assert served_vs_reference(eng, model, weights, prompts) < 5e-5


def test_paged_kernel_serves_the_grouped_heads_in_interpret_mode():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(3))
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (13, 21)]
    with engine(model, weights, use_pallas="always", interpret=True) as eng:
        assert served_vs_reference(eng, model, weights, prompts, 4) < 5e-5


# linear heads of whole lane tiles in whole sublane tiles (``kda_rule``):
# the step's state update is the token rule's kernel
# (``ops/pallas_kda_update.py``), the prefill's the chunk form's
# (``ops/pallas_kda_chunk.py``, a group of chunks a call); interpreted
WIDTHS = {"toy": ({}, {}),
          "kernel": (dict(lin_heads=8, lin_head_dim=128),
                     dict(interpret=True))}
widths = pytest.mark.parametrize("widths", list(WIDTHS))


@pytest.fixture
def short_chunks(monkeypatch):
    """Prefill chunks of 16 tokens: a test's prompts span several."""
    monkeypatch.setattr(mixers, "PREFILL_CHUNK", 16)


@pytest.mark.parametrize("kinds", [("recurrent",), PERIOD],
                         ids=["kda", "period"])
def test_prefill_then_decode_through_the_kernel_matches_the_reference(
        kinds, short_chunks):
    """The real engine at lane-wide heads, the kernel in the step and in
    the prefill: prompts of less than a chunk, of whole chunks, and one
    that ends in the middle of its third chunk."""
    sizes, cfg = WIDTHS["kernel"]
    model = make_model(kinds, **sizes)
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 32, 41, 16)]
    before = {n: stat_get(n) for n in (
        "decode_prefill_scan_steps", "decode_prefill_scan_tokens",
        "kda_kernel_rows", "decode_tokens_total", "decode_prefills")}
    with engine(model, weights, **cfg) as eng:
        assert served_vs_reference(eng, model, weights, prompts) < 2e-4
    got = {n: stat_get(n) - v for n, v in before.items()}
    layers = kinds.count("recurrent")
    assert got["decode_prefill_scan_tokens"] == layers * (5 + 32 + 41 + 16)
    assert got["decode_prefill_scan_steps"] == layers * (1 + 2 + 3 + 1)
    # a live row of a step is a token that no prefill delivered
    assert got["kda_kernel_rows"] == layers * (
        got["decode_tokens_total"] - got["decode_prefills"])


@widths
def test_a_slots_second_request_sees_none_of_the_firsts_state(
        widths, short_chunks):
    sizes, cfg = WIDTHS[widths]
    model = make_model(PERIOD, **sizes)
    weights = model.init_weights(jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    with engine(model, weights, slots=1, **cfg) as eng:
        for n in (23, 6, 17):       # one slot: each reuses the last's rows
            p = [rng.randint(0, VOCAB, n).tolist()]
            assert served_vs_reference(eng, model, weights, p) < (
                2e-4 if sizes else 5e-5)


def _state_after_prefill(model, weights, prompt, page_size, **cfg):
    with engine(model, weights, slots=2, page_size=page_size, **cfg) as eng:
        eng.submit([1, 2, 3], max_new_tokens=1).result(timeout=300)
        eng.submit(prompt, max_new_tokens=1).result(timeout=300)
        names = eng._cache.recurrent_var_names()
        return {n: np.asarray(eng._scope.get_var(n)) for n in names}


@widths
def test_padding_rows_leave_the_state_alone(widths, short_chunks):
    """The same 9-token prompt prefilled in a bucket of 16 and in one of
    32: what the slot's rows hold is the state after token 9, however
    many padding rows followed it (through the chunk kernel: one call
    whose rows past 9 are masked, in a bucket of 16 and in one of 32)."""
    sizes, cfg = WIDTHS[widths]
    model = make_model(("recurrent", "attention"), **sizes)
    weights = model.init_weights(jax.random.PRNGKey(7))
    prompt = np.random.RandomState(8).randint(0, VOCAB, 9).tolist()
    a = _state_after_prefill(model, weights, prompt, 8, **cfg)
    b = _state_after_prefill(model, weights, prompt, 32, **cfg)
    assert set(a) == set(b) and len(a) == 2
    for name in a:
        assert np.abs(a[name][0]).max() > 0      # slot 0 was written
        np.testing.assert_allclose(a[name][0], b[name][0], atol=1e-6)
        assert not a[name][1].any()              # slot 1 never was


def test_grouped_query_kernel_against_the_reference_in_interpret_mode():
    s, hq, hkv, d, page, pps, layers = 3, 8, 2, 8, 8, 4, 2
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(s, hq, d), jnp.float32)
    k_pages, v_pages = (jnp.asarray(rng.randn(layers, 16, page, hkv * d),
                                    jnp.float32) for _ in range(2))
    table = jnp.asarray(rng.permutation(np.arange(1, 13)).reshape(s, pps),
                        jnp.int32)
    lengths = jnp.asarray([5, 32, 17], jnp.int32)
    got = pda.paged_decode_attention(
        q, k_pages, v_pages, table, lengths, layer=1, use_pallas="always",
        interpret=True)
    # query head i reads K/V head i // 4: gather, repeat, attend
    full = [jnp.repeat(p[1][table].reshape(s, pps * page, hkv, d),
                       hq // hkv, axis=2) for p in (k_pages, v_pages)]
    want = pda.decode_attention_reference(q, *full, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)
    via_ref = pda.paged_decode_attention(
        q, k_pages, v_pages, table, lengths, layer=1, use_pallas="never")
    np.testing.assert_allclose(via_ref, want, atol=2e-5)


def _expert(lw, j, f, h):
    cols = slice(j * f, (j + 1) * f)
    return (jax.nn.silu(h @ lw["moe_w_gate"][:, cols])
            * (h @ lw["moe_w_up"][:, cols])) @ lw["moe_w_down"][cols]


def test_routing_is_dropless_when_every_row_picks_one_held_expert():
    model = make_model(("attention",))
    lw = model.init_weights(jax.random.PRNGKey(10))["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(11), (64, 32))
    # the correction bias lifts expert 3 into every row's top-k: all 64
    # rows land on it, where a capacity of S*K/E slots would keep 16
    bias = jnp.zeros((16,)).at[3].set(50.0)
    ids, _, local = moe_ops.moe_share_route(
        h, lw["moe_router"], bias, top_k=4, held_ids=model.held_experts)
    assert bool((ids == 3).any(axis=1).all())
    assert float(local[:, 3].min()) > 0              # nobody was dropped
    assigned, hit = moe_ops.moe_share_counts(local)
    assert int(assigned) >= 64 and 1 <= int(hit) <= 5
    out = moe_ops.moe_share_ffn(h, local, lw["moe_w_gate"],
                                lw["moe_w_up"], lw["moe_w_down"])
    want = sum(local[:, j:j + 1] * _expert(lw, j, 16, h)
               for j in range(len(model.held_experts)))
    np.testing.assert_allclose(out, want, atol=1e-4)
    # the bias ranks, it does not weigh: a row's weights are its plain
    # scores over the chosen, summing to one over all of them
    scores = jax.nn.sigmoid(h @ lw["moe_router"])
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    np.testing.assert_allclose(
        local[:, 3], scores[:, 3] / chosen.sum(axis=1), rtol=1e-5)
    # dead rows choose as any row and give the chip nothing to compute
    live = jnp.arange(64) % 2 == 0
    _, _, masked = moe_ops.moe_share_route(
        h, lw["moe_router"], bias, top_k=4, held_ids=model.held_experts,
        live=live)
    assert not np.asarray(masked)[1::2].any()
    np.testing.assert_allclose(np.asarray(masked)[::2],
                               np.asarray(local)[::2])


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of one 16-expert layer: their
    routed parts, with the shared expert counted once, are what the
    reference gives for the whole layer."""
    whole = make_model(("attention",), held=tuple(range(16)))
    lw = whole.init_weights(jax.random.PRNGKey(12))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(13), (24, 32))
    want, _ = ref.moe_layer(lw, x, dims(whole))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * lw["norm2"]
    total = (jax.nn.silu(h @ lw["shared_w_gate"]) * (h @ lw["shared_w_up"])) \
        @ lw["shared_w_down"]
    f = 16
    for chip in range(4):
        held = tuple(range(4 * chip, 4 * chip + 4))
        cols = slice(4 * chip * f, (4 * chip + 4) * f)
        _, _, local = moe_ops.moe_share_route(
            h, lw["moe_router"], lw["moe_router_bias"], top_k=4,
            held_ids=held)
        part = moe_ops.moe_share_ffn(
            h, local, lw["moe_w_gate"][:, cols], lw["moe_w_up"][:, cols],
            lw["moe_w_down"][cols])
        # the reference given the same share (and no shared expert)
        share = {**lw, "moe_w_gate": lw["moe_w_gate"][:, cols],
                 "moe_w_up": lw["moe_w_up"][:, cols],
                 "moe_w_down": lw["moe_w_down"][cols]}
        ref_part, _ = ref.moe_layer(share, x, dims(whole), shared=False,
                                    held=list(held))
        np.testing.assert_allclose(part, ref_part - x, atol=1e-4)
        total = total + part
    np.testing.assert_allclose(x + total, want, atol=1e-4)


def _share_case(case, dtype, d=32, f=16):
    """Rows, the held experts' weights and ``local`` for one way the
    rows may have chosen.  200 rows end a tile of 128 early, 128 fill
    theirs; every row on all of 8 held experts is 1,600 pairs for a
    sorted buffer that holds 512."""
    rows = 128 if case == "whole_tiles" else 200
    n_held = 8 if case == "overflow" else 4
    k = jax.random.split(jax.random.PRNGKey(21), 6)
    h = jax.random.normal(k[0], (rows, d), jnp.float32)
    router = jax.random.normal(k[1], (d, 16)) / np.sqrt(d)
    w = [(jax.random.normal(kk, shape) / np.sqrt(shape[0] / (
        n_held if shape[0] > d else 1))).astype(dtype)
        for kk, shape in zip(k[2:5], ((d, n_held * f), (d, n_held * f),
                                      (n_held * f, d)))]
    live = jnp.arange(rows) < rows - 9 if case == "padding_tail" else None
    _, _, local = moe_ops.moe_share_route(
        h, router, jnp.zeros((16,)), top_k=3, held_ids=range(n_held),
        live=live)
    if case == "one_expert":
        local = jnp.zeros_like(local).at[:, 2].set(0.4)
    elif case == "no_expert":
        local = jnp.zeros_like(local)
    elif case == "overflow":
        local = jax.random.uniform(k[5], local.shape, minval=0.05,
                                   maxval=0.2)
    return h, local, w


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "routers_draw", "one_expert", "no_expert", "padding_tail",
    "whole_tiles", "overflow"])
def test_the_grouped_form_is_the_dense_form(case, dtype):
    """``grouped_share_ffn`` (the kernels interpreted, the tiles and the
    sorted buffer it gives itself) against the three matmuls over every
    row and every held expert: float32 to 1e-5 of the largest value,
    bfloat16 weights to the dense form's own rounding (its distance
    from the same sum over the float32 values of the same weights)."""
    from paddle_tpu.ops import pallas_moe_grouped as grouped

    h, local, w = _share_case(case, jnp.dtype(dtype))
    rows, n_held = local.shape
    assert not moe_ops.grouped_rule(rows, n_held, 16, 32)
    dense = moe_ops.moe_share_ffn(h, local, *w)
    out, pairs, passes = grouped.grouped_share_ffn(
        h, local, *w, interpret=True)
    n = int((np.asarray(local) != 0).sum())
    assert int(pairs) == n
    assert grouped.default_tiles(rows, n_held) == 128
    holds = grouped.sorted_rows(rows, n_held) - n_held * 128
    assert holds == (256 if case == "whole_tiles" else 512)
    assert int(passes) == -(-n // holds)
    if case == "overflow":
        assert int(passes) == 4
    if case == "padding_tail":
        assert not np.asarray(out)[-9:].any()
    scale = float(jnp.abs(dense).max()) or 1.0
    if dtype == "float32":
        tol = 1e-5 * scale
    else:
        exact = moe_ops.moe_share_ffn(
            h.astype(jnp.bfloat16).astype(jnp.float32), local,
            *(x.astype(jnp.float32) for x in w))
        tol = float(jnp.abs(dense - exact).max())
    assert float(jnp.abs(out - dense).max()) <= tol


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_the_form_follows_the_calls_shape():
    """The rule reads the call's static shape and nothing else: a
    decode step's rows keep the three matmuls they had, as do too few
    experts and a width the chip's lanes do not divide; a prompt's rows
    over enough experts take the two kernels inside the loop over
    passes, and the counts go where ``tally`` says."""
    f, d, n_held = 128, 128, 8
    w = (jnp.zeros((d, n_held * f)), jnp.zeros((d, n_held * f)),
         jnp.zeros((n_held * f, d)))

    def lowered(rows, held=n_held, f=f, **kw):
        return list(_primitives(jax.make_jaxpr(
            lambda h, local: moe_ops.moe_share_ffn(
                h, local, w[0][:, :held * f], w[1][:, :held * f],
                w[2][:held * f], **kw))(
            jnp.zeros((rows, d)), jnp.zeros((rows, held))).jaxpr))

    # the v5e's ridge where no chip is attached: 197 TFLOP/s / 819 GB/s
    assert 240 < moe_ops.ridge_rows() < 241
    for rows, held, width in ((128, n_held, f), (480, n_held, f),
                              (512, 5, f), (4096, 1, f), (512, n_held, 16)):
        assert not moe_ops.grouped_rule(rows, held, width, d)
        ops = lowered(rows, held, width)
        assert ops.count("dot_general") == 3 and "pallas_call" not in ops
        assert "while" not in ops and "sort" not in ops
    counted = {}
    assert moe_ops.grouped_rule(512, n_held, f, d)
    ops = lowered(512, tally=counted.__setitem__)
    assert ops.count("pallas_call") == 2 and "while" in ops
    assert "sort" not in ops        # 16-19 s a layer to compile for the chip
    assert tuple(counted) == moe_ops.GROUPED_TALLIES
    # no chip and not asked to interpret: the kernels refuse, loudly
    with pytest.raises(ValueError, match="interpret"):
        moe_ops.moe_share_ffn(jnp.zeros((512, d)), jnp.ones((512, n_held)),
                              *w)


def test_a_long_prompts_prefill_groups_its_pairs_and_counts_them():
    """A prompt whose bucket (512 rows over 8 held experts) passes the
    rule: the served logits are the reference's, and the prefill's
    counters, read back with its token behind the scan's, say the
    grouped form computed exactly the pairs the prompt's rows chose
    among the held experts."""
    held = tuple(range(8))
    # widths of whole lanes, as the rule asks; the engine's
    # ``interpret`` is what lets the two kernels run without a chip
    model = make_model(("attention", "recurrent"), held=held, d_model=128,
                       expert_dim=128)
    weights = model.init_weights(jax.random.PRNGKey(22))
    rng = np.random.RandomState(23)
    prompt = rng.randint(0, VOCAB, 300).tolist()
    names = moe_ops.GROUPED_TALLIES + ("decode_prefills",
                                       "decode_prefill_scan_tokens")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights, max_seq_len=512, interpret=True) as eng:
        assert eng._prefill_tallies[2:] == moe_ops.GROUPED_TALLIES
        assert not set(eng._tallies) & set(moe_ops.GROUPED_TALLIES)
        eng.submit(rng.randint(0, VOCAB, 40).tolist(),
                   max_new_tokens=2).result(timeout=300)
        # a bucket of 64 rows keeps the three matmuls: nothing counted
        assert all(stat_get(n) == before[n] for n in moe_ops.GROUPED_TALLIES)
        req = eng.submit(prompt, max_new_tokens=3, record_logits=True)
        toks = req.result(timeout=300)
    want, _ = ref.forward_logits(
        weights, jnp.asarray(prompt + toks[:-1], jnp.int32), dims(model))
    assert float(np.abs(np.stack(req.logits_trace)
                        - np.asarray(want)[len(prompt) - 1:]).max()) < 5e-5
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_prefills"] == 2
    assert d["decode_prefill_scan_tokens"] == 40 + 300
    chosen = np.asarray(req.records["moe_topk"][0])     # [300, layers, k]
    assert chosen.shape == (300, 2, model.top_k)
    assert d["moe_grouped_pairs"] == int(np.isin(chosen, held).sum()) > 300
    assert d["moe_grouped_rows_dense"] == 2 * 512 * len(held)
    assert d["moe_grouped_extra_passes"] == 0


@pytest.mark.parametrize("cfg, names", [
    (dict(prefill_chunk_pages=1), "chunked prefill"),
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_quant=True), "kv_quant"),
], ids=["chunked", "speculative", "kv_quant"])
def test_what_cannot_carry_recurrent_state_refuses_by_name(cfg, names):
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(14))
    with pytest.raises(ValueError, match=names):
        engine(model, weights, **cfg)


def test_a_draft_model_and_the_disaggregated_hand_over_refuse():
    from paddle_tpu.serving.decode import TransformerLM
    from paddle_tpu.serving.disagg import DisaggServer

    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(15))
    draft = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=64)
    with pytest.raises(ValueError, match="speculative decoding"):
        DecodeEngine(model, weights, DecodeConfig(
            slots=2, max_seq_len=64, page_size=8), draft_model=draft,
            draft_weights=draft.init_weights(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="disaggregated"):
        DisaggServer(model, weights, config=DecodeConfig(
            slots=2, max_seq_len=64, page_size=8))
    eng = engine(model, weights)
    with pytest.raises(ValueError, match="extract_kv"):
        eng.submit([1, 2, 3], max_new_tokens=2, extract_kv=True)
    with pytest.raises(ValueError, match="exports no pages"):
        eng._cache.export_pages([1])


def test_every_request_is_admitted_fresh_and_the_counts_ride_the_sync():
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(16))
    prompt = list(range(1, 25))
    before = {n: stat_get(n) for n in (
        "decode_prefix_bypassed", "decode_prefix_pages_hit",
        "moe_local_assignments", "moe_experts_hit", "decode_steps",
        "decode_h2d_uploads", "decode_prefills")}
    with engine(model, weights) as eng:
        assert eng._cache.prefix is None and eng._cache.prefix_bypassed
        first = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        # the same prompt again: a prefix cache would skip its prefill
        again = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        assert first == again
        state_bytes = eng._cache.state_bytes()
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_prefix_bypassed"] == 2 and d["decode_prefills"] == 2
    assert d["decode_prefix_pages_hit"] == 0
    # one upload a step and one a prefill, as for any model: the counts
    # came back with the tokens
    assert d["decode_h2d_uploads"] == d["decode_steps"] + 2
    steps = d["decode_steps"]
    assert 0 < d["moe_experts_hit"] <= steps * 4 * 5
    assert d["moe_experts_hit"] <= d["moe_local_assignments"] \
        <= steps * 4 * 4
    assert stat_get("decode_state_bytes") == state_bytes == 3 * (
        3 * (2 * 8 * 8 + 3 * 3 * 16) * 4)


def test_the_tallies_are_the_models_declared_names_before_any_trace():
    """The names behind a step's tokens are fixed when the engine is
    built, from what the model declares: an engine whose step was never
    traced in this process reads them all the same, and a count the
    model did not declare fails the trace by name."""
    model = make_model(PERIOD)
    weights = model.init_weights(jax.random.PRNGKey(17))
    eng = engine(model, weights)
    # a step whose shape keeps the dense form reads back no counter of
    # the hit form's: they are declared, counted and dropped
    assert eng._tallies == model.step_tallies(3) == (
        "moe_local_assignments", "moe_experts_hit", "kda_kernel_rows")
    assert model.tallies == eng._tallies + moe_ops.HIT_TALLIES
    # a step reads back no counter of the prefill's; a count of one
    # there goes nowhere and fails nothing
    assert eng._prefill_tallies[-3:] == model.prefill_tallies \
        == moe_ops.GROUPED_TALLIES
    mix = decode_mod._Mixers(eng._mixed, None, None)
    mix.tally("moe_grouped_pairs", 7)
    assert "moe_grouped_pairs" not in mix.counts
    model.tallies = ("moe_experts_hit",)
    with pytest.raises(KeyError, match="moe_local_assignments"):
        engine(model, weights).lower_step()


# sha256 of the joint step's lowered text at the three test files' sizes
# (``make_model()``, 3 slots), taken on the commit before the hit form
STEPS_AS_LOWERED = {
    "hybrid": (
        "test_hybrid_moe_serving",
        "23b6b195485bee5f378f52bef5cee09d580ef8b02124b31074235d758f0fb13c"),
    "window": (
        "test_window_moe_serving",
        "f56d9f177d2b575b22bc1ec8ddf398b45767e37d87124243529f20670cfae4eb"),
    "parallel": (
        "test_parallel_moe_serving",
        "c59449e598f7237f0f7df03b192fe62395a97438c12c1ba58e2727908d2c3c56"),
}


@pytest.mark.parametrize("which", sorted(STEPS_AS_LOWERED))
def test_a_step_that_keeps_the_dense_form_is_the_program_it_was(which):
    """``HybridMoELM``, ``WindowMoELM`` and ``ParallelMoELM`` call the
    same ``moe_share_ffn`` as the model whose step takes the hit form
    (PR 48).  Where the rule leaves a step on the dense form (here by
    the toy widths, in the three cells by the share of the held experts
    their rows are expected to hit: ``tests/test_moe.py`` has the
    table) the step reads back no counter of the hit form's and lowers
    to the text it had before.  A change MEANT to move these programs
    replaces the digests; one that was not has found out here."""
    import hashlib
    import importlib

    module, digest = STEPS_AS_LOWERED[which]
    tests = importlib.import_module(module)
    model = tests.make_model()
    eng = tests.engine(model, model.init_weights(jax.random.PRNGKey(1)))
    assert not moe_ops.hit_rule(
        3, len(model.held_experts), model.expert_dim, model.d_model,
        model.top_k, model.num_experts)
    assert not set(eng._tallies) & set(moe_ops.HIT_TALLIES)
    assert set(model.tallies) - set(eng._tallies) \
        == set(moe_ops.HIT_TALLIES)
    text = eng.lower_step().as_text()
    assert "tpu_custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == digest


# sha256 of the 64-row whole-prompt prefill's lowered text (``make_model()``,
# 3 slots) and what the gauge says of the model, both since the head
# forms the one row the engine reads (PR 62).  The toy widths': no chunk
# form (until PR 62 the text was that of the commit before the engine's
# ``recur`` learned how many of a rule's chunks a call covers, PR 53).
# The kernel widths': the chunk form interpreted (PR 58; until then a
# call walked ``PREFILL_CHUNK`` tokens through the token rule's kernel)
PREFILLS_AS_LOWERED = {
    "toy": ("961f1ad842cfeffdc79f2643f9633be6383cd9bad625081248b885bcb1b8a778",
            0),
    "kernel": (
        "9cd6327c9827a110228455eb938a26e66de9ebdf8be92c6386d383b26f0f2e2e", 1),
}


@widths
def test_a_prefills_calls_cover_the_buckets_chunks(widths):
    """``GatedDeltaLM`` hands ``attend.recur`` a group of its rule's
    chunks a call (PR 53).  At toy widths this model hands no chunk
    form at all, says so (gauge ``decode_prefill_chunks_per_call``), and
    its prefill lowers to the text it had (but for the head's one row,
    PR 62).  Where the kernels take the state a call covers the
    bucket's chunks of the rule's WY form
    (``PREFILL_CHUNK`` tokens) up to what ``GROUP_BYTES`` of its
    temporaries allow (PR 58): a function of the bucket and the widths
    alone, the same number on the gauge; lowered for the chip the
    prefill holds ONE call of the chunk kernel a recurrent layer, inside
    the engine's loop, and no call of the step's."""
    import hashlib
    from paddle_tpu.ops import pallas_kda_chunk as chunked
    from paddle_tpu.ops import pallas_kda_update as kda

    sizes, cfg = WIDTHS[widths]
    digest, per_call = PREFILLS_AS_LOWERED[widths]
    model = make_model(**sizes)
    assert model.prefill_chunks_per_call(64) == per_call
    eng = engine(model, model.init_weights(jax.random.PRNGKey(1)), **cfg)
    assert stat_get("decode_prefill_chunks_per_call") \
        == model.prefill_chunks_per_call(eng.config.max_seq_len) == per_call
    text = eng.lower_prefill(64).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest
    if widths == "toy":
        assert model.prefill_chunks_per_call(4096) == 0
        return
    assert mixers.PREFILL_CHUNK == chunked.CHUNK == 64
    # 8 heads of 128: 2 MiB of temporaries a chunk, 16 chunks under the cap
    assert [model.prefill_chunks_per_call(r)
            for r in (8, 64, 72, 128, 256, 2048, 4096)] \
        == [1, 1, 2, 2, 4, 16, 16]
    # the two cells' widths: Kimi-Linear's 32 heads, Solar's 64
    for heads, want in ((32, [4]), (64, [2, 2, 2])):
        wide = make_model(lin_heads=heads, lin_head_dim=128)
        assert [wide.prefill_chunks_per_call(r)
                for r in ((4096,) if heads == 32 else (256, 512, 1024))] \
            == want
    # lowered for the chip, the kernel not interpreted
    eng = engine(model, eng.weights, use_pallas="always")
    args = (tuple(eng._scope.get_var(n) for n in eng._state_vars),
            eng.weights, eng._prefill_args(64, (0,)))
    text = eng._prefill_fn(64).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("@tpu_custom_call") == 1 \
        and chunked.KERNEL_NAME in text and kda.KERNEL_NAME not in text


# -- the chunk form against the token form, with no engine -----------------
def recurrent_case(model, n, seed, log_decays=None, beta=None):
    """(a recurrent layer's weights, ``n`` rows of its projections, one
    request's non-zero state before them).  ``log_decays``: the tokens'
    log decay a channel takes these values in turn over the channels
    (the decay's weights set to rate 1 and bias 0, the gate's rows to
    ``softplus^-1`` of each); ``beta``: the same number in every row of
    that projection."""
    lw = dict(model.init_weights(jax.random.PRNGKey(seed))["layers"][
        model.layer_kinds.index("recurrent")])
    rng = np.random.RandomState(seed)
    c = model.lin_heads * model.lin_head_dim
    rows = {"u": rng.randn(n, 3 * c), "gate": rng.randn(n, c),
            "beta": rng.randn(n, model.lin_heads) * 3}
    if log_decays is not None:
        lw["kda_a_log"] = jnp.zeros_like(lw["kda_a_log"])
        lw["kda_dt_bias"] = jnp.zeros_like(lw["kda_dt_bias"])
        with np.errstate(divide="ignore"):
            gate = np.log(np.expm1(-np.asarray(log_decays, np.float64)))
        rows["gate"] = np.broadcast_to(np.resize(np.maximum(gate, -100.0),
                                                 c), (n, c))
    if beta is not None:
        rows["beta"] = np.full_like(rows["beta"], beta)
    state = {name: jnp.asarray(rng.randn(1, *shape) * 0.5, dtype)
             for name, (shape, dtype) in model.recurrent_state.items()}
    return lw, {k: jnp.asarray(v, jnp.float32) for k, v in rows.items()}, \
        state


@functools.lru_cache(maxsize=None)
def _jitted(model, form):
    """``model``'s chunk function (the kernel interpreted) or its
    one-token update, jitted once a model: the cases share a compile.
    The token form is only ever traced by ``token_form``, which has
    turned the kernels' rule off by then."""
    if form == "chunk":
        return jax.jit(lambda lw, rows, n_real, state: model._kda_chunk(
            lw, rows, n_real, state, interpret=True))
    return jax.jit(lambda lw, rows, state: model._kda_token(lw, rows, state))


@functools.lru_cache(maxsize=None)
def kernel_model():
    return make_model(**WIDTHS["kernel"][0])


def chunk_form(model, lw, rows, state, length, group):
    """The first ``length`` of ``rows`` as the engine's prefill ``recur``
    hands them to ``_kda_chunk``: ``group`` chunks a call, the last call
    told how many of its rows are real -> (``o`` of all rows, the state
    after token ``length - 1``)."""
    per_call = group * mixers.PREFILL_CHUNK
    call = _jitted(model, "chunk")
    outs = []
    for lo in range(0, max(length, 1), per_call):
        o, state = call(lw, {k: v[lo:lo + per_call] for k, v in rows.items()},
                        jnp.int32(min(length - lo, per_call)), state)
        outs.append(o)
    return jnp.concatenate(outs), state


def token_form(model, lw, rows, state, length, monkeypatch):
    """The same rows one after another through the one-token update's
    XLA lines (``_kda_rule_xla``: the decay as a FACTOR, no chunk)."""
    monkeypatch.setattr(mixers.kda, "kda_rule", lambda *a: False)
    step = _jitted(model, "token")
    outs = []
    for t in range(length):
        o, state = step(lw, {k: v[t:t + 1] for k, v in rows.items()}, state)
        outs.append(o)
    return jnp.concatenate(outs) if outs else None, state


def assert_the_chunk_form_is_the_token_form(model, case, length, group,
                                            monkeypatch, rows_run=None):
    """Outputs, matrices and tail to 1e-5, every value finite, rows past
    ``length`` zero."""
    lw, rows, state = case
    rows_run = rows_run or -(-max(length, 1) // (
        group * mixers.PREFILL_CHUNK)) * group * mixers.PREFILL_CHUNK
    rows = {k: v[:rows_run] for k, v in rows.items()}
    o, new = chunk_form(model, lw, rows, state, length, group)
    want_o, want = token_form(model, lw, rows, state, length, monkeypatch)
    assert o.shape == (rows_run, model.lin_heads, model.lin_head_dim)
    assert np.isfinite(np.asarray(o)).all() and not np.asarray(
        o[length:]).any()
    if length:
        np.testing.assert_allclose(o[:length], want_o, atol=1e-5)
    for name in state:
        assert np.isfinite(np.asarray(new[name])).all()
        np.testing.assert_allclose(new[name], want[name], atol=1e-5)
    return new


GROUP = 2       # chunks a call in the tests below: a call is 128 rows
LENGTHS = {"one_token": 1, "one_short_of_a_chunk": 63, "a_chunk": 64,
           "a_chunk_and_one": 65, "one_short_of_a_group": 127,
           "several_groups": 2 * 128 + 37}


@pytest.mark.parametrize("length", list(LENGTHS))
def test_the_chunk_form_is_the_token_form(length, monkeypatch):
    """``_kda_chunk`` (the rule's WY form on the matrix unit, a group of
    chunks a call) against the token recurrence from a non-zero state,
    beta in (0, 2) as Solar's config has it: a prompt of one token, of a
    chunk less one, of a chunk, of a chunk and one, of a group less one,
    and one that needs three calls and ends inside a chunk."""
    model = kernel_model()
    assert model.beta_scale == 2.0
    n = LENGTHS[length]
    assert_the_chunk_form_is_the_token_form(
        model, recurrent_case(model, 3 * 128, 31), n, GROUP, monkeypatch)


@pytest.mark.parametrize("decays", [
    (0.0,), (-1e-3,), (-40.0,), (0.0, -1e-3, -40.0)],
    ids=["none", "1e-3", "40", "side_by_side"])
def test_no_decay_overflows_or_divides(decays, monkeypatch):
    """Log decays of 0, of -1e-3 and of -40 a token, alone and side by
    side in the channels of one chunk (64 tokens of -40 sum to -2,560:
    ``e^{-G}`` is ``inf`` after three): nothing is ``inf`` or ``nan`` and
    the result is the recurrence's."""
    model = kernel_model()
    case = recurrent_case(model, 128, 32, log_decays=decays)
    lw, rows, _ = case
    got = model._kda_vectors(lw, rows["u"], rows["gate"], rows["beta"],
                             log_decay=True)[3]
    np.testing.assert_allclose(np.unique(np.asarray(got)),
                               sorted(set(decays)), rtol=1e-5, atol=1e-30)
    assert_the_chunk_form_is_the_token_form(model, case, 100, GROUP,
                                            monkeypatch)


@pytest.mark.parametrize("beta", [-1e9, 1e9], ids=["beta0", "beta_scale"])
def test_the_write_strengths_ends_hold(beta, monkeypatch):
    """beta 0 (nothing is written: the state only decays) and beta at
    the top of its range (2: ``I - beta k k^T`` reflects)."""
    model = kernel_model()
    case = recurrent_case(model, 128, 33, beta=beta)
    assert float(model.beta_scale * jax.nn.sigmoid(case[1]["beta"]).max()) \
        == (0.0 if beta < 0 else 2.0)
    assert_the_chunk_form_is_the_token_form(model, case, 90, GROUP,
                                            monkeypatch)


@pytest.mark.parametrize("length", [0, 40], ids=["all_padding", "padded"])
def test_padding_leaves_state_and_tail_as_they_were(length, monkeypatch):
    """A call of 128 rows of which 40 are the request's, or none: the
    state and the tail are what token 40 left (what came in, where no
    row is real: bit for bit), whatever the padding rows hold."""
    model = kernel_model()
    lw, rows, state = recurrent_case(model, 128, 34)
    new = assert_the_chunk_form_is_the_token_form(
        model, (lw, rows, state), length, GROUP, monkeypatch, rows_run=128)
    loud = {k: v.at[length:].set(1e4) for k, v in rows.items()}
    _, again = chunk_form(model, lw, loud, state, length, GROUP)
    for name in state:
        assert np.array_equal(np.asarray(again[name]), np.asarray(new[name]))
        if not length:
            assert np.array_equal(np.asarray(new[name]),
                                  np.asarray(state[name]))


def _function(text, name):
    """The lines of ``func.func ... @name(`` in lowered text, its
    signature first."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if re.search(r"func\.func \w+ @%s\(" % re.escape(name), ln))
    end = next(i for i in range(start + 1, len(lines))
               if lines[i].startswith("  }"))
    return lines[start:end]


def test_the_steps_slabs_go_through_the_kernel_and_nothing_else():
    """The joint step of a Solar-shaped model, lowered for the chip: a
    recurrent layer's matrices ``[slots, heads, d_k, d_v]`` are the
    program's own argument handed straight to ONE instruction, the
    state-update kernel, whose result of that shape is the operand's
    buffer; nothing selects over that shape (the dead rows' mask is the
    kernel's ``n_real``, not the engine's ``where``)."""
    from paddle_tpu.ops import pallas_kda_update as kda

    sizes, _ = WIDTHS["kernel"]
    model = make_model(("attention", "recurrent", "recurrent"), **sizes)
    weights = model.init_weights(jax.random.PRNGKey(19))
    eng = engine(model, weights, use_pallas="always")
    args = (tuple(eng._scope.get_var(n) for n in eng._state_vars),
            eng.weights, eng._step_args(()), eng._no_tokens)
    text = eng._step_fn.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    slab = "tensor<3x8x128x128xf32>"
    main = _function(text, "main")
    slabs = re.findall(r"(%arg\d+): " + re.escape(slab), main[0])
    assert len(slabs) == 2                          # one a recurrent layer
    for arg in slabs:
        uses = [ln for ln in main[1:] if re.search(arg + r"\b", ln)]
        assert len(uses) == 1 and "call @kda_update(" in uses[0], uses
    inner = _function(text, "kda_update")
    passed = re.search(r"(%arg\d+): " + re.escape(slab), inner[0])[1]
    uses = [ln for ln in inner[1:] if re.search(passed + r"\b", ln)]
    assert len(uses) == 1 and "@tpu_custom_call" in uses[0]
    call = uses[0]
    assert kda.KERNEL_NAME in call
    operands = re.search(r"@tpu_custom_call\(([^)]*)\)", call)[1].split(", ")
    assert operands.index(passed) == 3
    assert re.search(r"output_tuple_indices = \[1\], operand_index = 3, "
                     r"operand_tuple_indices = \[\]", call)
    assert not [ln for ln in text.splitlines()
                if "stablehlo.select" in ln and slab in ln]
    # the convolution's tail is the model's to mask: one select a layer
    assert len([ln for ln in main if "call @_where" in ln
                and "tensor<3x9216xf32>" in ln.split("->")[-1]]) == 2


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
