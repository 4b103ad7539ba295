"""The convolution/attention model with every expert held
(``serving/conv_moe_lm.py``: gated short convolutions that keep two
positions a slot and no keys, grouped-query attention with a norm on
every q and k head, a leading dense layer, all of a top-k router's
experts on the chip, a tied head) behind the real ``DecodeEngine``,
against the plain reference (``benchmark/reference/conv_moe_lm.py``, the
one the cell's check uses): seeded, tiny, on the CPU."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import pallas_moe_grouped as grouped
from paddle_tpu.ops import pallas_prompt_attention as ppa
from paddle_tpu.serving import ConvMoELM, DecodeConfig, DecodeEngine
from paddle_tpu.serving.decode import per_slot_kinds

from benchmark.reference import conv_moe_lm as ref
from benchmark.tests import conv_moe_controls as controls

# LFM2's first period and the layer after it: conv conv attn conv
KINDS = ("recurrent", "recurrent", "attention", "recurrent")
VOCAB = 97


def make_model(kinds=KINDS, held=range(8), **kw):
    sizes = dict(vocab_size=VOCAB, d_model=32, layer_kinds=kinds,
                 num_heads=4, num_kv_heads=2, head_dim=8, conv_kernel=3,
                 ffn_dim=48, dense_layers=1, num_experts=8, top_k=2,
                 held_experts=held, expert_dim=16, rope_theta=1e4,
                 dtype="float32")
    sizes.update(kw)
    return ConvMoELM(**sizes)


def dims(m, held=None):
    return dict(num_heads=m.num_heads, num_kv_heads=m.num_kv_heads,
                head_dim=m.head_dim, conv_kernel=m.conv_kernel,
                rope_theta=m.rope_theta, dense_layers=m.dense_layers,
                top_k=m.top_k, held=list(held or m.held_experts),
                expert_dim=m.expert_dim, eps=m.rms_eps,
                kinds=list(m.layer_kinds))


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=3, max_seq_len=64, page_size=8), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def served_vs_reference(eng, model, weights, prompts, n_new=6, gap_max=0.0):
    """Worst |dlogit| over the prompts' prefill and decode positions,
    the reference given the server's own tokens and its OWN routing,
    which the served routing must be (gap 0 in float32; under
    bfloat16's rounding a near-tie may flip by ``gap_max``, and the
    reference then follows the served ids)."""
    reqs = [eng.submit(p, max_new_tokens=n_new, record_logits=True)
            for p in prompts]
    worst = 0.0
    for p, r in zip(prompts, reqs):
        toks = r.result(timeout=300)
        got = np.stack(r.logits_trace)
        seq = jnp.asarray(p + toks[:-1], jnp.int32)
        routed = r.records["moe_topk"]
        ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
        assert ids.shape == (len(p) + n_new - 1, model.num_layers
                             - model.dense_layers, model.top_k)
        # the reference follows the served ids only where a flip is
        # allowed (the cell's check does the same), after measuring them
        want, gap = ref.forward_logits(
            weights, seq, dims(model), rows=(len(p) - 1, n_new),
            routing=jnp.asarray(ids) if gap_max else None)
        if not gap_max:
            _, gap = ref.forward_logits(weights, seq, dims(model),
                                        routing=jnp.asarray(ids))
        assert float(gap.max()) <= gap_max
        assert got.shape == (n_new, VOCAB)
        worst = max(worst, float(np.abs(got - np.asarray(want)).max()))
    return worst


def prompts_of(*lens, seed=2):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).tolist() for n in lens]


@pytest.mark.parametrize("dtype, band", [("float32", 1e-5),
                                         ("bfloat16", 0.12)])
def test_prefill_then_steps_through_pages_and_tails_match_the_reference(
        dtype, band):
    """Three prompts at once (one of a single token, one that crosses
    two pages): the whole-prompt prefill with the convolution's prompt
    form, then joint steps through pages and tails.  float32 to 1e-5; as
    served (bfloat16 weights and pages, float32 sums and tails) inside
    the band that bfloat16's 2^-8 steps leave on logits of size 3 after
    four layers, far under what a wrong tap, gate or tail gives (the
    controls below read above 0.5)."""
    model = make_model(dtype=dtype)
    weights = model.init_weights(jax.random.PRNGKey(1))
    names = ("decode_prefill_conv_rows", "decode_prefill_scan_steps",
             "decode_prefill_scan_tokens", "decode_prefills")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights, cache_dtype=dtype) as eng:
        worst = served_vs_reference(
            eng, model, weights, prompts_of(5, 21, 1),
            gap_max=0.0 if dtype == "float32" else 0.02)
        # the tails are all the state there is: 3 layers x 3 slots
        assert stat_get("decode_state_bytes") == 3 * 3 * 2 * 32 * 4 \
            == eng._cache.state_bytes()
    assert 0 < worst < band
    d = {n: stat_get(n) - v for n, v in before.items()}
    # ONE call a convolution layer a prompt, whatever its length
    assert d["decode_prefills"] == 3
    assert d["decode_prefill_scan_steps"] == 3 * 3
    assert d["decode_prefill_conv_rows"] == 3 * 27 \
        == d["decode_prefill_scan_tokens"]


@pytest.mark.parametrize("name", [
    "taps_reversed", "no_b_gate", "no_c_gate", "tail_zeroed_at_every_step",
    "bias_in_the_weights_too", "no_qk_norm"])
def test_a_model_that_is_not_the_references_reads_far_off(name):
    """What the bands above are far under: the served model with one
    piece of the layer wrong (the cell's own controls,
    ``benchmark/tests/conv_moe_controls.py``), against the same
    reference."""
    change_model, change_weights, patch = controls.CONTROLS[name]
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(1))
    if name == "bias_in_the_weights_too":
        # a bias of the scores' own size, served and reference alike
        weights = dict(weights, layers=[
            dict(lw, moe_router_bias=lw["moe_router_bias"] * 20)
            if "moe_router_bias" in lw else lw for lw in weights["layers"]])
    if change_model:
        change_model(model)
    undo = patch() if patch else None
    try:
        worst = _worst_ignoring_routing(
            model, weights,
            change_weights(weights) if change_weights else weights)
    finally:
        if undo:
            undo()
    assert worst > (0.05 if name == "bias_in_the_weights_too" else 0.5)


def _worst_ignoring_routing(model, weights, served):
    prompts = prompts_of(5, 21)
    with engine(model, served) as eng:
        reqs = [eng.submit(p, max_new_tokens=6, record_logits=True)
                for p in prompts]
        worst = 0.0
        for p, r in zip(prompts, reqs):
            toks = r.result(timeout=300)
            want, _ = ref.forward_logits(
                weights, jnp.asarray(p + toks[:-1], jnp.int32), dims(model),
                rows=(len(p) - 1, 6))
            worst = max(worst, float(np.abs(
                np.stack(r.logits_trace) - np.asarray(want)).max()))
    return worst


def _conv_inputs(model, t, seed=3):
    lw = model.init_weights(jax.random.PRNGKey(seed))["layers"][0]
    k = jax.random.split(jax.random.PRNGKey(seed + 1), 2)
    bcu = jax.random.normal(k[0], (t, 3 * model.d_model), jnp.float32)
    tail = jax.random.normal(k[1], (1, model.conv_kernel - 1,
                                    model.d_model), jnp.float32)
    return lw, bcu, tail


@pytest.mark.parametrize("n_real", [1, 2, 3, 15])
@pytest.mark.parametrize("from_zero", [True, False],
                         ids=["zero_tail", "carried_tail"])
def test_the_prompt_form_is_the_token_form_to_the_bit(n_real, from_zero):
    """A bucket of 16 rows of which ``n_real`` are the request's, the
    rest POISONED: the prompt form's outputs for the real rows and the
    tail it leaves are, bit for bit in float32, those of the one-token
    update run token by token; the tail is ``(z_{n-2}, z_{n-1})`` and
    no padding row is in it."""
    model = make_model()
    lw, bcu, tail = _conv_inputs(model, 16)
    if from_zero:
        tail = jnp.zeros_like(tail)
    poisoned = bcu.at[n_real:].set(jnp.nan)
    out, new = jax.jit(functools.partial(model._conv_chunk, lw))(
        {"bcu": poisoned}, jnp.int32(n_real), {"tail": tail})
    token = jax.jit(functools.partial(model._conv_token, lw))
    state, outs = {"tail": tail}, []
    for t in range(n_real):
        o, state = token({"bcu": bcu[t:t + 1]}, state)
        outs.append(o[0])
    assert np.array_equal(np.asarray(out[:n_real]), np.stack(outs))
    assert np.array_equal(np.asarray(new["tail"]),
                          np.asarray(state["tail"]))
    assert new["tail"].shape == (1, 2, 32)
    z = bcu[:, :32] * bcu[:, 64:]
    want = jnp.concatenate([tail[0], z])[n_real:n_real + 2]
    assert np.array_equal(np.asarray(new["tail"][0]), np.asarray(want))


def test_a_dead_rows_tail_comes_back_bit_for_bit():
    model = make_model()
    lw, bcu, _ = _conv_inputs(model, 4)
    tail = jax.random.normal(jax.random.PRNGKey(9), (4, 2, 32))
    live = jnp.asarray([True, False, True, False])
    out, new = jax.jit(functools.partial(model._conv_token, lw))(
        {"bcu": bcu}, {"tail": tail}, live=live)
    new, tail = np.asarray(new["tail"]), np.asarray(tail)
    assert np.array_equal(new[1], tail[1]) and np.array_equal(new[3],
                                                              tail[3])
    for r in (0, 2):
        assert np.array_equal(new[r, 0], tail[r, 1])
        assert np.array_equal(new[r, 1], np.asarray(
            bcu[r, :32] * bcu[r, 64:]))


def _tails_after_prefill(model, weights, prompt, page_size):
    with engine(model, weights, slots=2, page_size=page_size) as eng:
        eng.submit([1, 2, 3], max_new_tokens=1).result(timeout=300)
        eng.submit(prompt, max_new_tokens=1).result(timeout=300)
        names = eng._cache.recurrent_var_names()
        return {n: np.asarray(eng._scope.get_var(n)) for n in names}


def test_padding_rows_and_idle_slots_leave_the_tails_alone():
    """The same 9-token prompt prefilled in a bucket of 16 and in one of
    32: the slot's tail is that of token 9 however many padding rows
    followed; the slot no request ever took stays zero through every
    joint step."""
    model = make_model(("recurrent", "attention"), dense_layers=0)
    weights = model.init_weights(jax.random.PRNGKey(7))
    prompt = prompts_of(9, seed=8)[0]
    a = _tails_after_prefill(model, weights, prompt, 8)
    b = _tails_after_prefill(model, weights, prompt, 32)
    assert set(a) == set(b) and len(a) == 1
    for name in a:
        assert a[name].shape == (2, 2, 32)
        assert np.abs(a[name][0]).max() > 0      # slot 0 was written
        assert np.array_equal(a[name][0], b[name][0])
        assert not a[name][1].any()              # slot 1 never was


def test_a_waiting_request_takes_a_left_slot_with_a_fresh_tail():
    """Three requests on one slot: each waits for the one before it to
    leave, is admitted into the slot it left and reads
    none of its tail or pages (the engine has no preemption: a request
    that cannot be admitted waits, and resumes nothing)."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(5))
    with engine(model, weights, slots=1) as eng:
        worst = served_vs_reference(eng, model, weights,
                                    prompts_of(23, 6, 17, seed=6), n_new=4)
    assert worst < 1e-5


def test_two_requests_in_one_batch_do_not_read_each_others_tails():
    """The same prompt served alone and beside another that starts a
    step later: its logits do not move beyond float32's summation."""
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(11))
    mine, other = prompts_of(13, 7, seed=12)
    with engine(model, weights, slots=2) as eng:
        alone = eng.submit(mine, max_new_tokens=8, record_logits=True)
        alone.result(timeout=300)
        beside = eng.submit(mine, max_new_tokens=8, record_logits=True)
        eng.submit(other, max_new_tokens=8)
        beside.result(timeout=300)
    assert alone.result() == beside.result()
    np.testing.assert_allclose(np.stack(alone.logits_trace),
                               np.stack(beside.logits_trace), atol=2e-6)


def test_the_expert_bias_moves_the_choice_and_not_the_weights():
    """A bias that lifts the lowest-scored expert over every other: it
    is chosen by every row, and its weight is its plain score over the
    chosen scores' sum - the bias is in no weight."""
    h = jax.random.normal(jax.random.PRNGKey(31), (12, 32))
    router = jax.random.normal(jax.random.PRNGKey(32), (32, 8)) / 6
    scores = np.asarray(jax.nn.sigmoid(h @ router))
    lowest = int(scores.sum(0).argmin())
    bias = jnp.zeros((8,)).at[lowest].set(1.0)
    ids0, _, _ = moe_ops.moe_share_route(h, router, jnp.zeros((8,)),
                                         top_k=2, held_ids=range(8))
    ids, w, local = moe_ops.moe_share_route(h, router, bias, top_k=2,
                                            held_ids=range(8))
    assert (np.asarray(ids) == lowest).any(axis=1).all()
    assert not (np.asarray(ids0) == lowest).any(axis=1).all()
    chosen = np.take_along_axis(scores, np.asarray(ids), axis=1)
    np.testing.assert_allclose(w, chosen / chosen.sum(1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(local).sum(1), 1.0, rtol=1e-6)
    # and the served model follows the reference under such a bias
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(33))
    for lw in weights["layers"]:
        if "moe_router_bias" in lw:
            lw["moe_router_bias"] = lw["moe_router_bias"] * 20
    with engine(model, weights) as eng:
        assert served_vs_reference(eng, model, weights,
                                   prompts_of(9), n_new=4) < 1e-5


def test_the_shares_add_up_to_the_all_held_layer_and_the_uncut_reference():
    """The guide's share test: four chips hold 8 experts each of one
    32-expert top-4 layer; their parts add up, a layer, to what the chip
    that holds all 32 computes and to what the reference gives for the
    whole layer."""
    whole = make_model(("recurrent",), held=range(32), num_experts=32,
                       top_k=4, dense_layers=0)
    lw = whole.init_weights(jax.random.PRNGKey(12))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(13), (24, 32))
    want, _ = ref.moe_layer(lw, x, dims(whole))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * lw["norm2"]
    route = functools.partial(moe_ops.moe_share_route, h, lw["moe_router"],
                              lw["moe_router_bias"], top_k=4)
    _, _, local = route(held_ids=range(32))
    all_held = moe_ops.moe_share_ffn(
        h, local, lw["moe_w_gate"], lw["moe_w_up"], lw["moe_w_down"],
        top_k=4, num_experts=32)
    np.testing.assert_allclose(np.asarray(local > 0).sum(1), 4)
    total, f = jnp.zeros_like(x), 16
    for chip in range(4):
        held = tuple(range(8 * chip, 8 * chip + 8))
        cols = slice(8 * chip * f, (8 * chip + 8) * f)
        _, _, local = route(held_ids=held)
        part = moe_ops.moe_share_ffn(
            h, local, lw["moe_w_gate"][:, cols], lw["moe_w_up"][:, cols],
            lw["moe_w_down"][cols], top_k=4, num_experts=32)
        share = {**lw, "moe_w_gate": lw["moe_w_gate"][:, cols],
                 "moe_w_up": lw["moe_w_up"][:, cols],
                 "moe_w_down": lw["moe_w_down"][cols]}
        ref_part, _ = ref.moe_layer(share, x, dims(whole), held=list(held))
        np.testing.assert_allclose(part, ref_part - x, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, all_held, atol=1e-5)
    np.testing.assert_allclose(x + total, want, atol=1e-5)


# (rows, n_held, top_k, num_experts) of the four routed cells' largest
# prompt bucket and joint step, with the tile and the sorted buffer the
# PARENT of the PR that made them functions of the routing gave them
ROUTED_CELLS = {
    "solar_prompt": ((1024, 40, 8, 320), 128, 2 * 1024 + 40 * 128),
    "solar_step": ((128, 40, 8, 320), 128, 256 + 40 * 128),
    "mimo_prompt": ((2048, 16, 8, 256), 128, 2 * 2048 + 16 * 128),
    "mimo_step": ((128, 16, 8, 256), 128, 256 + 16 * 128),
    "command_a_plus_prompt": ((4096, 8, 8, 128), 256, 2 * 4096 + 8 * 256),
    "command_a_plus_step": ((48, 8, 8, 128), 128, 128 + 8 * 128),
    "kimi_prompt": ((8192, 12, 8, 384), 256, 2 * 8192 + 12 * 256),
    "kimi_step": ((64, 12, 8, 384), 128, 128 + 12 * 128),
}


@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_a_share_of_the_experts_keeps_the_buffer_and_the_tiles_it_had(cell):
    (rows, n_held, top_k, n_exp), tile, buffer = ROUTED_CELLS[cell]
    assert grouped.pairs_a_row(top_k, n_exp, n_held) == 2 \
        == grouped.pairs_a_row(None, None, n_held) == grouped.PAIRS_A_ROW
    assert grouped.default_tiles(rows, n_held, top_k, n_exp) == tile \
        == grouped.default_tiles(rows, n_held) \
        == (256 if rows > 128 * n_held else 128)
    assert grouped.sorted_rows(rows, n_held, top_k, n_exp) == buffer \
        == grouped.sorted_rows(rows, n_held)


def test_every_expert_held_sizes_the_buffer_by_what_a_row_can_choose():
    assert grouped.pairs_a_row(4, 32, 32) == 4
    assert grouped.pairs_a_row(8, 4, 4) == 4        # no more than held
    # 2,048 rows x 4 / 32 = 256 pairs an expert fill two tiles of 128
    assert grouped.default_tiles(2048, 32, 4, 32) == 256
    assert grouped.default_tiles(2048, 32) == 128   # a share's rule
    assert grouped.default_tiles(1024, 32, 4, 32) == 128
    assert grouped.sorted_rows(2048, 32, 4, 32) == 8192 + 32 * 256
    # LFM2's shapes: the prompt grouped, the step dense, neither hit
    shape = (32, 1792, 2048, 4, 32)
    assert moe_ops.grouped_rule(2048, *shape)
    assert not moe_ops.grouped_rule(128, *shape)
    assert not moe_ops.hit_rule(128, *shape)
    assert moe_ops.expected_hit_share(128, 4, 32) > 0.9999


def _all_held_call(rows=512, d=128, f=128, n=16, top_k=4, live=300):
    k = jax.random.split(jax.random.PRNGKey(41), 5)
    h = jax.random.normal(k[0], (rows, d), jnp.float32)
    router = jax.random.normal(k[1], (d, n)) / np.sqrt(d)
    w = [jax.random.normal(kk, s) / np.sqrt(s[0])
         for kk, s in zip(k[2:], ((d, n * f), (d, n * f), (n * f, d)))]
    _, _, local = moe_ops.moe_share_route(
        h, router, jnp.zeros((n,)), top_k=top_k, held_ids=range(n),
        live=jnp.arange(rows) < live)
    return h, local, w


def test_four_pairs_a_row_take_one_pass_where_two_a_row_took_two():
    """512 rows of which 300 are live, each on 4 of 16 held experts:
    1,200 pairs.  A buffer of two pairs a row (the share's rule: 1,024)
    walks twice and reads the experts' weights twice; told that every
    expert is held it holds 4 x 512 and walks once.  One answer, the
    dense form's."""
    h, local, w = _all_held_call()
    dense = jnp.matmul(
        ((jax.nn.silu(h @ w[0]) * (h @ w[1])).reshape(512, 16, 128)
         * local[..., None]).reshape(512, -1), w[2])
    out2, pairs2, passes2 = grouped.grouped_share_ffn(
        h, local, *w, interpret=True)
    out4, pairs4, passes4 = grouped.grouped_share_ffn(
        h, local, *w, interpret=True, top_k=4, num_experts=16)
    assert int(pairs2) == int(pairs4) == 4 * 300
    assert (int(passes2), int(passes4)) == (2, 1)
    np.testing.assert_allclose(out4, dense, atol=2e-5)
    np.testing.assert_allclose(out2, dense, atol=2e-5)


def test_a_prompt_over_all_held_experts_groups_its_pairs_in_one_pass():
    """A 300-token prompt through a model that holds all 16 experts of a
    top-4 router, at widths of whole lanes and heads of 64 (``interpret``
    lets the grouped two, the prompt's flash kernel and the paged one
    run without a chip): the served logits are the reference's;
    the prefill counted 4 pairs a real row an expert layer and NO extra
    pass; every joint step's live row made 4 local pairs a layer."""
    model = make_model(("recurrent", "attention"), held=range(16),
                       num_experts=16, top_k=4, d_model=128, expert_dim=128,
                       dense_layers=0, num_heads=2, num_kv_heads=1,
                       head_dim=64)
    weights = model.init_weights(jax.random.PRNGKey(22))
    prompt = prompts_of(300, seed=23)[0]
    names = moe_ops.GROUPED_TALLIES + (
        "moe_local_assignments", "decode_tokens_total", "decode_prefills",
        "decode_prefill_attn_flash", "decode_prefill_attn_blocks")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights, max_seq_len=512, use_pallas="always",
                interpret=True) as eng:
        assert eng._prefill_tallies[2:] == moe_ops.GROUPED_TALLIES + (
            "decode_prefill_conv_rows",)
        req = eng.submit(prompt, max_new_tokens=4, record_logits=True)
        toks = req.result(timeout=600)
    want, _ = ref.forward_logits(
        weights, jnp.asarray(prompt + toks[:-1], jnp.int32), dims(model),
        rows=(299, 4))
    assert float(np.abs(np.stack(req.logits_trace)
                        - np.asarray(want)).max()) < 5e-5
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["moe_grouped_pairs"] == 4 * 300 * 2
    assert d["moe_grouped_rows_dense"] == 2 * 512 * 16
    assert d["moe_grouped_extra_passes"] == 0
    stepped = d["decode_tokens_total"] - d["decode_prefills"]
    assert stepped == 3 and d["moe_local_assignments"] == 4 * stepped * 2
    # V heads of 64 lanes in a whole group of two: the flash kernel
    # serves the prompt's attention, interpreted here
    assert ppa.flash_rule(512, 2, 1, 64, 64, None) == (256, 512)
    assert eng._prefill_walks(512) == [(1, None, ("flash", 256, 512))]
    assert d["decode_prefill_attn_flash"] >= 1
    assert d["decode_prefill_attn_blocks"] == 0


def test_the_flash_kernel_takes_heads_of_64_lanes_in_whole_groups():
    """The cell's shape gets the tiles every grouped model's prompt gets;
    what no 128 divides but 64 stays refused, and so does a group whose
    V lanes end inside a tile."""
    assert ppa.flash_rule(2048, 32, 8, 64, 64, None) == (256, 1024)
    assert ppa.flash_rule(2048, 32, 8, 128, 128, None) == (256, 1024)
    assert ppa.flash_rule(2048, 32, 8, 64, 96, None) is None
    assert ppa.flash_rule(2048, 24, 8, 128, 64, None) is None
    assert ppa.flash_rule(2048, 16, 16, 64, 64, None) is None


@pytest.mark.parametrize("cfg, names", [
    (dict(prefill_chunk_pages=1), "chunked prefill"),
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_quant=True), "kv_quant"),
], ids=["chunked", "speculative", "kv_quant"])
def test_what_cannot_carry_a_tail_refuses_by_the_kinds_name(cfg, names):
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(14))
    assert [k for k, _ in per_slot_kinds(model)] == ["recurrent"]
    with pytest.raises(ValueError, match=names) as err:
        engine(model, weights, **cfg)
    assert "recurrent" in str(err.value)


def test_every_request_is_admitted_fresh_and_the_hand_over_refuses():
    from paddle_tpu.serving.disagg import DisaggServer

    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(16))
    with pytest.raises(ValueError, match="disaggregated"):
        DisaggServer(model, weights, config=DecodeConfig(
            slots=2, max_seq_len=64, page_size=8))
    prompt = list(range(1, 25))
    names = ("decode_prefix_bypassed", "decode_prefills",
             "moe_local_assignments", "decode_tokens_total")
    before = {n: stat_get(n) for n in names}
    with engine(model, weights) as eng:
        assert eng._cache.prefix is None and eng._cache.prefix_bypassed
        with pytest.raises(ValueError, match="extract_kv"):
            eng.submit([1, 2, 3], max_new_tokens=2, extract_kv=True)
        first = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        again = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        assert first == again
    d = {n: stat_get(n) - v for n, v in before.items()}
    assert d["decode_prefix_bypassed"] == 2 == d["decode_prefills"]
    # all 8 experts held, top-2: every stepped row makes 2 local pairs
    # in each of the 3 expert layers
    stepped = d["decode_tokens_total"] - d["decode_prefills"]
    assert d["moe_local_assignments"] == 2 * stepped * 3


def test_the_tied_head_keeps_one_matrix_and_the_untied_one_its_own():
    tied = make_model().init_weights(jax.random.PRNGKey(0))
    assert "lm_head" not in tied
    model = make_model(tie_head=False)
    weights = model.init_weights(jax.random.PRNGKey(0))
    assert weights["lm_head"].shape == (32, VOCAB)
    with engine(model, weights) as eng:
        assert served_vs_reference(eng, model, weights,
                                   prompts_of(7), n_new=3) < 1e-5


def test_the_reference_at_a_stated_precision_rounds_operands_and_no_more():
    """``dims["operands"]``: both operands of every matrix product
    through that dtype (float32: the plain pass to the bit; bfloat16:
    logits off by the rounding's size, the routing followed);
    ``dims["results"]`` besides: the reference computed in that dtype,
    further off."""
    model = make_model(dtype="bfloat16")
    weights = model.init_weights(jax.random.PRNGKey(5))
    seq = jnp.asarray(prompts_of(40, seed=6)[0], jnp.int32)
    want, _ = ref.forward_logits(weights, seq, dims(model))
    ids = jax.lax.top_k(jnp.zeros((40, 3, 8)).at[..., :2].set(1.0), 2)[1]
    followed, gap = ref.forward_logits(weights, seq, dims(model),
                                       routing=ids)
    assert float(gap.max()) > 0         # experts 0 and 1, whatever it chose

    def rms_off(**precision):
        got, _ = ref.forward_logits(
            weights, seq, dict(dims(model), **precision), routing=ids)
        return float(jnp.sqrt(jnp.mean(jnp.square(got - followed))
                              / jnp.mean(jnp.square(followed))))

    assert rms_off(operands="float32") == 0.0
    stated = rms_off(operands="bfloat16")
    below = rms_off(operands="bfloat16", results="bfloat16")
    assert 1e-3 < stated < 3e-2 and stated < below < 6e-2
    assert float(jnp.abs(want - followed).max()) > 0


# sha256 of the lowered text of ``make_model()``'s joint step and 8-row
# whole-prompt prefill (the smallest bucket) behind ``engine()``, as
# PR 58's tree lowers them (taken on that commit, before the blocks this
# model shares with others moved out of the others' files: PR 59)
PROGRAMS_AS_LOWERED = {
    "step": "a2ddc6fa9abb5c9b476ef1b9f7c36fb7f940780a46365d10c4989612caa2735c",
    "prefill":
        "b4a11d14c88d72f2f1352cc7e17226689a6a962b6fb80600ff0749f54ae90a40"}


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_the_programs_are_still_the_ones_lowered_before_the_block_library(
        program):
    """The matmul feed, the norm, the half-split rotary pairing and the
    routed share are ``serving/blocks.py``'s, functions of what they
    read; this model borrows no other model's methods any more (PR 59).
    Where they are written moves no line of what they lower to: the
    joint step and the whole-prompt prefill are the text they were.  A
    change MEANT to move these programs replaces the digests; one that
    was not has found out here."""
    import hashlib

    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(1))
    eng = engine(model, weights)
    text = (eng.lower_step() if program == "step"
            else eng.lower_prefill(8)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PROGRAMS_AS_LOWERED[program]
