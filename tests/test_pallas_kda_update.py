"""``ops/pallas_kda_update.py`` (the gated delta rule's TOKEN form with
a head's state held in VMEM) interpreted on the CPU, at lanes of 128,
against the XLA form it stands in for (``serving/mixers.py``
``_kda_rule_xla`` and the one-token update built on it).  Its grid over
one row's ``T > 1`` tokens served a prompt until PR 58 and serves no
model since (the prompt's form is ``ops/pallas_kda_chunk.py``, tested in
``tests/test_pallas_kda_chunk.py``): the cases here that call it with
several tokens are what keeps it honest."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kda_update as kda
from paddle_tpu.serving import HybridMoELM, mixers

D = 128


def _vectors(rng, r, t, h, decay=(0.2, 0.999), beta=(0.0, 2.0)):
    def normal(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    return dict(
        q=normal(r, t, h, D) * 0.1, k=normal(r, t, h, D) * 0.1,
        v=normal(r, t, h, D),
        decay=jnp.asarray(rng.uniform(*decay, (r, t, h, D)), jnp.float32),
        beta=jnp.asarray(rng.uniform(*beta, (r, t, h)), jnp.float32))


def _kernel(x, state, n):
    return kda.kda_update(x["q"], x["k"], x["decay"], x["v"], x["beta"],
                          state, jnp.asarray(n, jnp.int32), interpret=True)


def _xla_token(x, t, state):
    return mixers._kda_rule_xla(
        x["q"][:, t], x["k"][:, t], x["v"][:, t], x["decay"][:, t],
        x["beta"][:, t], state)


@pytest.mark.parametrize("beta", [(0.0, 0.0), (2.0, 2.0), (0.0, 2.0)],
                         ids=["beta0", "beta2", "beta_any"])
@pytest.mark.parametrize("decay", [(0.2, 0.2), (0.999, 0.999), (0.2, 0.999)],
                         ids=["decay.2", "decay.999", "decay_any"])
def test_one_token_is_the_xla_form_and_dead_rows_stay(decay, beta):
    """T = 1 over several rows, dead rows among them: a live row's state
    and output are the XLA form's, a dead row's state comes back bit for
    bit and its output is zero.  The decay's and beta's ends are the
    configuration's (``kda_allow_neg_eigval``: beta up to 2)."""
    rng = np.random.RandomState(0)
    rows, heads = 5, 8
    x = _vectors(rng, rows, 1, heads, decay, beta)
    s0 = jnp.asarray(rng.randn(rows, heads, D, D), jnp.float32)
    live = np.array([True, False, True, False, True])
    o, s = _kernel(x, s0, live)
    want_o, want_s = _xla_token(x, 0, s0)
    np.testing.assert_allclose(o[live, 0], want_o[live], atol=1e-5)
    np.testing.assert_allclose(s[live], want_s[live], atol=1e-5)
    assert np.array_equal(np.asarray(s)[~live], np.asarray(s0)[~live])
    assert not np.asarray(o)[~live].any()
    # the step did something: the oracle is not the identity
    assert float(jnp.abs(want_s - s0)[live].max()) > 1e-3


@pytest.mark.parametrize("rows, tokens, heads", [(2, 1, 32), (1, 6, 32)],
                         ids=["step_two_blocks", "chunk_two_blocks"])
def test_every_block_of_heads_gets_its_own_vectors(rows, tokens, heads):
    """More heads than a grid step holds: each block reads its own
    slice of the vectors and writes its own heads' state."""
    assert heads // kda.head_block(tokens, heads, D, D) == 2
    rng = np.random.RandomState(1)
    x = _vectors(rng, rows, tokens, heads)
    s = want = jnp.asarray(rng.randn(rows, heads, D, D), jnp.float32)
    o, s = _kernel(x, s, [tokens] * rows)
    for t in range(tokens):
        want_o, want = _xla_token(x, t, want)
        np.testing.assert_allclose(o[:, t], want_o, atol=2e-5)
    np.testing.assert_allclose(s, want, atol=2e-5)


def _model(**kw):
    sizes = dict(vocab_size=64, d_model=32,
                 layer_kinds=("attention", "recurrent"), num_heads=4,
                 num_kv_heads=2, head_dim=8, lin_heads=8, lin_head_dim=D,
                 conv_kernel=4, gate_rank=4, num_experts=8, top_k=2,
                 held_experts=(0, 1, 2), expert_dim=16, shared_dim=16,
                 dtype="float32")
    sizes.update(kw)
    model = HybridMoELM(**sizes)
    return model, model.init_weights(jax.random.PRNGKey(3))["layers"][1]


def _projections(model, rng, n):
    c = model.lin_heads * model.lin_head_dim
    return {"u": jnp.asarray(rng.randn(n, 3 * c), jnp.float32),
            "gate": jnp.asarray(rng.randn(n, c), jnp.float32),
            "beta": jnp.asarray(rng.randn(n, model.lin_heads) * 3,
                                jnp.float32)}


def _state(model, rng, rows):
    return {name: jnp.asarray(rng.randn(rows, *shape) * 0.5, dtype)
            for name, (shape, dtype) in model.recurrent_state.items()}


@pytest.mark.parametrize("n_real", [12, 7, 1],
                         ids=["whole_chunk", "partial_chunk", "one_token"])
def test_a_chunk_is_its_real_tokens_one_by_one(n_real, monkeypatch):
    """The model's chunk function (the rule's chunk form, here over a
    call of 12 rows) against ``n_real``
    calls of the one-token update in its XLA form, from a non-zero
    state: the matrices, the convolution's tail and the real rows'
    output; rows past ``n_real`` touch nothing and read zero."""
    model, lw = _model()
    rng = np.random.RandomState(4)
    chunk = 12
    rows, state = _projections(model, rng, chunk), _state(model, rng, 1)
    o, new = model._kda_chunk(lw, rows, jnp.int32(n_real), state,
                              interpret=True)
    monkeypatch.setattr(mixers.kda, "kda_rule", lambda *a: False)
    want = state
    for t in range(n_real):
        want_o, want = model._kda_token(
            lw, {n: v[t:t + 1] for n, v in rows.items()}, want)
        np.testing.assert_allclose(o[t], want_o[0], atol=1e-5)
    assert o.shape == (chunk, model.lin_heads, D)
    assert not np.asarray(o[n_real:]).any()
    np.testing.assert_allclose(new["s"], want["s"], atol=1e-5)
    np.testing.assert_allclose(new["tail"], want["tail"], atol=1e-6)


def test_the_token_update_owns_its_dead_rows_in_both_forms(monkeypatch):
    """``_kda_token(..., live=)``: kernel and XLA form agree on the live
    rows, and in both a dead row keeps matrices AND tail bit for bit."""
    model, lw = _model()
    rng = np.random.RandomState(5)
    rows, state = _projections(model, rng, 4), _state(model, rng, 4)
    live = jnp.asarray([True, False, True, True])
    o, new = model._kda_token(lw, rows, state, live=live, interpret=True)
    monkeypatch.setattr(mixers.kda, "kda_rule", lambda *a: False)
    want_o, want = model._kda_token(lw, rows, state, live=live)
    np.testing.assert_allclose(o[np.asarray(live)],
                               want_o[np.asarray(live)], atol=1e-5)
    for name in state:
        np.testing.assert_allclose(new[name], want[name], atol=1e-5)
        for got in (new, want):
            assert np.array_equal(np.asarray(got[name][1]),
                                  np.asarray(state[name][1]))
        assert float(jnp.abs(new[name][0] - state[name][0]).max()) > 1e-3


@pytest.mark.parametrize("heads, d_k, d_v, dtype, takes", [
    (8, 128, 128, "float32", True), (64, 128, 256, "float32", True),
    (8, 96, 96, "float32", False), (8, 128, 96, "float32", False),
    (8, 128, 128, "bfloat16", False), (2, 128, 128, "float32", False),
    (2, 8, 8, "float32", False)])
def test_the_rule_follows_the_states_shape(heads, d_k, d_v, dtype, takes):
    """Float32, widths of whole lane tiles, heads in whole sublane
    tiles: the kernel's; anything else keeps the XLA form, and the
    kernel's entry refuses it by name."""
    assert kda.kda_rule(heads, d_k, d_v, dtype) is takes
    if not takes:
        f = functools.partial(jnp.zeros, dtype=jnp.float32)
        with pytest.raises(ValueError, match="kda_rule"):
            kda.kda_update(
                f((1, 1, heads, d_k)), f((1, 1, heads, d_k)),
                f((1, 1, heads, d_k)), f((1, 1, heads, d_v)),
                f((1, 1, heads)), jnp.zeros((1, heads, d_k, d_v), dtype),
                jnp.ones((1,), jnp.int32), interpret=True)


@pytest.mark.parametrize("width, kernel", [(D, True), (96, False)])
def test_the_models_form_follows_the_rule(width, kernel):
    """A model at lanes of 96 traces no kernel anywhere; at 128 its
    one-token update and its chunk function both do."""
    model, lw = _model(lin_head_dim=width)
    rng = np.random.RandomState(6)
    rows, state = _projections(model, rng, 2), _state(model, rng, 2)
    text = str(jax.make_jaxpr(functools.partial(
        model._kda_token, lw, interpret=True))(rows, state))
    assert ("pallas_call" in text) == kernel
