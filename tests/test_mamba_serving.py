"""The state-space hybrid (``serving/mamba_lm.py``: selective
state-space layers whose float32 state lies in slabs, beside
position-free multi-query attention layers in pages, the head tied to
the embedding) behind the real ``DecodeEngine``, against the plain
reference (``benchmark/reference/mamba_lm.py``, the one the cell's check
uses): float32, seeded, tiny, at widths the kernels take (128 channels,
interpret mode) and at widths they do not (48: the XLA forms)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import stat_get
from paddle_tpu.ops import pallas_prompt_attention as ppa
from paddle_tpu.ops import pallas_ssm as ps
from paddle_tpu.serving import DecodeConfig, DecodeEngine, MambaLM, mixers
from paddle_tpu.serving.kv_cache import RecurrentSpec

from benchmark.reference import mamba_lm as ref
from benchmark.tests.mamba_controls import CONTROLS

KINDS = ("recurrent", "recurrent", "attention", "recurrent")
VOCAB = 97
PAD = 256     # the longest prompt and its reply fit
WIDTHS = {"kernels": (dict(d_inner=128), dict(interpret=True)),
          "xla": (dict(d_inner=48), {})}


def make_model(kinds=KINDS, **kw):
    sizes = dict(vocab_size=VOCAB, d_model=32, layer_kinds=kinds,
                 d_inner=128, d_state=8, d_conv=4, dt_rank=6, num_heads=4,
                 num_kv_heads=1, head_dim=8, ffn_dim=40, dtype="float32")
    sizes.update(kw)
    return MambaLM(**sizes)


def dims(m):
    return dict(num_heads=m.num_heads, num_kv_heads=m.num_kv_heads,
                head_dim=m.head_dim, d_inner=m.d_inner, d_state=m.d_state,
                dt_rank=m.dt_rank, d_conv=m.d_conv, eps=m.rms_eps,
                kinds=list(m.layer_kinds), row_block=16)


def engine(model, weights, **cfg):
    cfg = dict(dict(slots=2, max_seq_len=256, page_size=8), **cfg)
    return DecodeEngine(model, weights, DecodeConfig(**cfg))


def served_vs_reference(eng, model, weights, prompts, news):
    """Worst |dlogit| over the prompts' prefill and decode positions,
    the reference given the server's own tokens."""
    reqs = [eng.submit(p, max_new_tokens=n, record_logits=True)
            for p, n in zip(prompts, news)]
    worst = 0.0
    for p, n, r in zip(prompts, news, reqs):
        toks = r.result(timeout=600)
        got = np.stack(r.logits_trace)
        seq = p + toks[:-1]
        want = ref.forward_logits(weights, jnp.asarray(
            seq + [0] * (PAD - len(seq)), jnp.int32), dims(model),
            rows=(len(p) - 1, n))
        assert got.shape == (n, VOCAB) == want.shape
        worst = max(worst, float(np.abs(got - np.asarray(want)).max()))
    return worst


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_prefill_then_decode_over_slabs_and_pages(widths):
    """Three requests on two slots: a prompt inside one tile of the scan,
    one that crosses two tile boundaries (150 of 64) and, admitted when
    the first ends (another step than the second), one of a whole number
    of pages; replies that cross page boundaries of 8.  Float32
    rounding through four layers reads under 3e-5; a departure reads
    above 1e-3 (the controls below)."""
    sizes, cfg = WIDTHS[widths]
    model = make_model(**sizes)
    weights = model.init_weights(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 150, 72)]
    steps = stat_get("decode_steps")
    with engine(model, weights, **cfg) as eng:
        assert (model.prefill_chunks_per_call(256) > 0) == (
            widths == "kernels")
        assert served_vs_reference(eng, model, weights, prompts,
                                   [6, 19, 11]) < 3e-5
    assert stat_get("decode_steps") - steps >= 18


def test_the_kernels_count_their_rows_and_the_scan_its_tiles():
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(3))
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (150, 9)]
    before = {n: stat_get(n) for n in (
        "ssm_kernel_rows", "decode_tokens_total", "decode_prefills",
        "decode_prefill_scan_steps", "decode_prefill_scan_tokens")}
    with engine(model, weights, interpret=True) as eng:
        for r in [eng.submit(p, max_new_tokens=4) for p in prompts]:
            r.result(timeout=600)
        assert stat_get("decode_prefill_chunks_per_call") == 4
    d = {n: stat_get(n) - v for n, v in before.items()}
    # three recurrent layers: a live row a layer a step; 150 tokens are
    # three tiles of 64, 9 are one
    assert d["ssm_kernel_rows"] == 3 * (
        d["decode_tokens_total"] - d["decode_prefills"]) == 3 * 6
    assert d["decode_prefill_scan_steps"] == 3 * (3 + 1)
    assert d["decode_prefill_scan_tokens"] == 3 * (150 + 9)
    assert model.tallies == ("ssm_kernel_rows",)


def _slabs_after(model, weights, prompts, news, **cfg):
    """Every slab as it lies when ``prompts`` have been served to the
    end of their ``news`` tokens, and how many steps the engine ran."""
    before = stat_get("decode_steps")
    with engine(model, weights, slots=3, **cfg) as eng:
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        for r in reqs:
            r.result(timeout=600)
        names = eng._cache.recurrent_var_names()
        return {n: np.asarray(eng._scope.get_var(n)) for n in names}, \
            stat_get("decode_steps") - before


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_a_dead_slots_state_and_tail_come_back_bit_for_bit(widths):
    """Slot 1's request ends after two tokens and its slot lies dead
    while slot 0 decodes a dozen steps on: its rows of every slab are,
    bit for bit, what the same request leaves when it is served alone,
    and a slot nobody was admitted to stays zero."""
    sizes, cfg = WIDTHS[widths]
    model = make_model(**sizes)
    weights = model.init_weights(jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    long, short = (rng.randint(0, VOCAB, n).tolist() for n in (19, 9))
    both, steps = _slabs_after(model, weights, [long, short], [14, 2], **cfg)
    alone, _ = _slabs_after(model, weights, [short], [2], **cfg)
    assert steps >= 13
    assert sorted(both) == sorted(alone) and len(both) == 2 * 3
    for n in both:
        assert np.abs(both[n][1]).max() > 0          # slot 1 was written
        assert np.array_equal(both[n][1], alone[n][0])
        assert np.abs(both[n][0] - both[n][1]).max() > 0
        assert not both[n][2].any()                  # slot 2 never was


def test_the_state_is_two_arrays_counted_without_padding():
    """What a slot keeps of a recurrent layer, in the chip's layout, at
    the published widths: 16 x 5,120 and one row of 3 x 5,120, float32:
    389,120 B, 10,117,120 B over the 26 layers, whatever a device's
    tiles would add to another layout."""
    published = make_model(
        ("recurrent",) * 7 + ("attention",), d_model=2560, d_inner=5120,
        d_state=16, dt_rank=160, num_heads=20, head_dim=128, ffn_dim=8192)
    state = published.recurrent_state
    assert {n: (s, np.dtype(d).name) for n, (s, d) in state.items()} == {
        "ssm": ((16, 5120), "float32"), "conv": ((3 * 5120,), "float32")}
    assert RecurrentSpec(1, state).slot_bytes() == 389120
    assert RecurrentSpec(26, state).slot_bytes() == 10117120
    assert 256 * RecurrentSpec(26, state).slot_bytes() == 2589982720
    assert ps.ssm_rule(*state["ssm"][0], state["ssm"][1])
    assert published.prefill_chunks_per_call(512) == 8
    assert published.prefill_chunks_per_call(4096) \
        == mixers.SCAN_CALL_TOKENS // mixers.SCAN_CHUNK == 16
    # twenty heads a group stack no whole row block of the flash kernel:
    # the two attention layers' prompts run in plain blocks
    assert ppa.flash_rule(512, 20, 1, 128, 128) is None
    # the engine's gauge is the same sum over the slots
    model = make_model()
    with engine(model, model.init_weights(jax.random.PRNGKey(0)),
                slots=3, interpret=True):
        assert stat_get("decode_state_bytes") \
            == 3 * 3 * (8 * 128 + 3 * 128) * 4


@pytest.mark.parametrize("cfg, names", [
    (dict(prefill_chunk_pages=1), "chunked prefill"),
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_quant=True), "kv_quant"),
], ids=["chunked", "speculative", "kv_quant"])
def test_what_cannot_carry_recurrent_state_refuses_by_name(cfg, names):
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(14))
    with pytest.raises(ValueError, match="recurrent layers.*" + names):
        engine(model, weights, **cfg)


def test_a_draft_model_and_the_disaggregated_hand_over_refuse():
    from paddle_tpu.serving.decode import TransformerLM
    from paddle_tpu.serving.disagg import DisaggServer

    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(15))
    draft = TransformerLM(vocab_size=VOCAB, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=64)
    with pytest.raises(ValueError, match="recurrent.*speculative decoding"):
        DecodeEngine(model, weights, DecodeConfig(
            slots=2, max_seq_len=64, page_size=8), draft_model=draft,
            draft_weights=draft.init_weights(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="disaggregated"):
        DisaggServer(model, weights, config=DecodeConfig(
            slots=2, max_seq_len=64, page_size=8))
    eng = engine(model, weights)
    with pytest.raises(ValueError, match="extract_kv"):
        eng.submit([1, 2, 3], max_new_tokens=2, extract_kv=True)
    with pytest.raises(ValueError, match="layer_kinds holds"):
        make_model(("recurrent", "window"))
    with pytest.raises(ValueError, match="no whole groups"):
        make_model(num_heads=4, num_kv_heads=3)


def test_the_prefix_cache_is_asked_for_and_bypassed():
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(16))
    before = stat_get("decode_prefix_bypassed")
    with engine(model, weights, interpret=True) as eng:
        assert eng.config.prefix_cache
        p = list(range(1, 20))
        a = eng.submit(p, max_new_tokens=3).result(timeout=300)
        b = eng.submit(p, max_new_tokens=3).result(timeout=300)
    assert a == b and stat_get("decode_prefix_bypassed") - before == 2


@pytest.mark.parametrize("name", list(CONTROLS))
def test_each_control_is_told_from_the_model_at_a_small_size(name):
    """The cell's controls (``benchmark/tests/mamba_controls.py``)
    through the engine at the kernels' widths: the model as it is reads
    the reference to float32 rounding, each control does not (a
    bfloat16 state by its size too)."""
    change, patch, reweigh = CONTROLS[name]
    model = make_model()
    weights = model.init_weights(jax.random.PRNGKey(21))
    if change:
        change(model)
    undo = patch() if patch else None
    jax.clear_caches()
    rng = np.random.RandomState(22)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (70, 30)]
    try:
        # the reference keeps the model's own weights
        with engine(model, reweigh(weights) if reweigh else weights,
                    interpret=True) as eng:
            err = served_vs_reference(eng, make_model(), weights, prompts,
                                      [10, 10])
    finally:
        if undo:
            undo()
            jax.clear_caches()
    if name == "served":
        assert err < 3e-5, err
        return
    assert err > 1e-3, (name, err)
    if name == "state_in_bf16":
        # half the state's bytes: the size check's
        assert RecurrentSpec(3, model.recurrent_state).slot_bytes() \
            == 3 * (8 * 128 * 2 + 3 * 128 * 4)
