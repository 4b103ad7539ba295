"""``ops/pallas_kda_chunk.py`` (the channel-decay delta rule's chunk form
on the matrix unit, a head's state held in VMEM over a call's chunks)
interpreted on the CPU, at lanes of 128, against the token rule one
token a call (``ops/pallas_kda_update.py`` at ``T = 1``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kda_chunk as chunked
from paddle_tpu.ops import pallas_kda_update as kda

D, C = 128, chunked.CHUNK
# decays a channel a token: every channel near 1, every channel strong,
# every channel so strong that sixteen tokens underflow float32
# (1e-3 ** 16), and the three side by side in every head
DECAYS = {"decay.999": (0.999,), "decay.2": (0.2,), "decay1e-3": (1e-3,),
          "decay_mix": (0.999, 0.2, 1e-3)}


def _served_vectors(rng, t, h, decays, beta=2.0):
    """``t`` tokens of ``h`` heads as ``KDAMixer._kda_vectors`` leaves
    them: q and k at unit length (q scaled by ``d^-1/2``), the LOG decay
    a channel from ``decays`` in turn, beta up to ``beta``."""
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    g = np.log(np.resize(np.asarray(decays, np.float32), D))
    return {n: jnp.asarray(v, jnp.float32) for n, v in dict(
        q=unit(rng.randn(1, t, h, D)) / np.sqrt(D),
        k=unit(rng.randn(1, t, h, D)), v=rng.randn(1, t, h, D),
        g=np.broadcast_to(g, (1, t, h, D)),
        beta=rng.uniform(0.0, beta, (1, t, h))).items()}


@jax.jit
def _token_by_token(x, state, n):
    """The first ``n`` tokens of ``x`` through ``T = 1`` calls of the
    step's kernel, one after another -> (``o``, zero past ``n``; the
    state)."""
    def one(state, at):
        t, row = at
        o, state = kda.kda_update(
            row["q"][:, None], row["k"][:, None],
            jnp.exp(row["g"])[:, None], row["v"][:, None],
            row["beta"][:, None], state,
            (t < n).astype(jnp.int32).reshape(1), interpret=True)
        return state, o[:, 0]

    t = x["q"].shape[1]
    state, o = jax.lax.scan(one, state, (
        jnp.arange(t), {n_: jnp.moveaxis(v, 1, 0) for n_, v in x.items()}))
    return jnp.moveaxis(o, 0, 1), state


def _chunk_form(x, state, n):
    return chunked.kda_chunk(x["q"], x["k"], x["g"], x["v"], x["beta"],
                             state, jnp.asarray([n], jnp.int32),
                             interpret=True)


def _assert_the_token_rule(got, want):
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("n_real", [0, 1, 15, 16, 17, C - 1, C])
@pytest.mark.parametrize("decays", list(DECAYS))
def test_the_chunk_form_is_the_token_rule(decays, n_real):
    """One call of a chunk's ``C`` tokens against ``n_real`` calls of one
    token, from a non-zero state, beta up to 2: outputs and state to
    2e-5, every value finite (no decay is divided by, no exponential
    overflows: 1e-3 a token leaves nothing of a channel after a
    sub-chunk), rows past ``n_real`` read zero and touch nothing."""
    rng = np.random.RandomState(7)
    x = _served_vectors(rng, C, 16, DECAYS[decays])
    s0 = jnp.asarray(rng.randn(1, 16, D, D), jnp.float32)
    o, s = _chunk_form(x, s0, n_real)
    _assert_the_token_rule((o, s), _token_by_token(x, s0, n_real))
    assert not np.asarray(o[:, n_real:]).any()
    if n_real == 0:
        assert np.array_equal(np.asarray(s), np.asarray(s0))
    else:
        assert float(jnp.abs(s - s0).max()) > 1e-3


@pytest.mark.parametrize("heads", [16, 32, 64])
def test_the_state_carries_from_a_call_to_the_next(heads):
    """Two consecutive calls (two chunks and a partial third, then one
    chunk and a half) against the token rule over the same tokens: the
    second call starts from what the first left, at the heads of the
    tests above, of Kimi-Linear and of Solar."""
    rng = np.random.RandomState(heads)
    first, second = 2 * C + 24, C + C // 2
    x = _served_vectors(rng, 3 * C + 2 * C, heads, DECAYS["decay_mix"])
    s0 = jnp.asarray(rng.randn(1, heads, D, D), jnp.float32)
    cut = lambda lo, hi: {n: v[:, lo:hi] for n, v in x.items()}  # noqa: E731
    o1, s1 = _chunk_form(cut(0, 3 * C), s0, first)
    o2, s2 = _chunk_form(cut(3 * C, 5 * C), s1, second)
    want_o1, want_s1 = _token_by_token(cut(0, 3 * C), s0, first)
    want_o2, want_s2 = _token_by_token(cut(3 * C, 5 * C), want_s1, second)
    _assert_the_token_rule((o1, s1, o2, s2),
                           (want_o1, want_s1, want_o2, want_s2))
    assert not np.asarray(o2[:, second:]).any()


@pytest.mark.parametrize("sub", [8, 32])
def test_the_sub_chunk_is_a_knob_of_speed_alone(sub, monkeypatch):
    """Sub-chunks of 8 and of 32 tokens (16 as served) give the same
    chunk: what a pair of tokens takes through a matrix product and what
    a channel at a time moves, the numbers do not."""
    monkeypatch.setattr(chunked, "SUB", sub)
    chunked.kda_chunk.clear_cache()
    try:
        rng = np.random.RandomState(sub)
        x = _served_vectors(rng, C + 8, 8, DECAYS["decay_mix"])
        s0 = jnp.asarray(rng.randn(1, 8, D, D), jnp.float32)
        _assert_the_token_rule(_chunk_form(x, s0, C + 3),
                               _token_by_token(x, s0, C + 3))
    finally:
        chunked.kda_chunk.clear_cache()


def test_a_decay_that_underflowed_still_has_its_logarithm():
    """The chunk form takes the LOG decay: a channel whose factor is 0
    in float32 (``exp(-200)``) forgets everything a token and is finite,
    where the logarithm of the factor would be ``-inf``."""
    rng = np.random.RandomState(9)
    x = _served_vectors(rng, C, 8, (1.0,))
    x["g"] = jnp.full_like(x["g"], -200.0)
    s0 = jnp.asarray(rng.randn(1, 8, D, D), jnp.float32)
    assert not np.asarray(jnp.exp(x["g"])).any()
    _assert_the_token_rule(_chunk_form(x, s0, C), _token_by_token(x, s0, C))


def test_the_entry_refuses_a_state_the_rule_does_not_take():
    f = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
    with pytest.raises(ValueError, match="kda_rule"):
        chunked.kda_chunk(f(1, C, 2, 8), f(1, C, 2, 8), f(1, C, 2, 8),
                          f(1, C, 2, 8), f(1, C, 2), f(1, 2, 8, 8),
                          jnp.ones((1,), jnp.int32), interpret=True)
