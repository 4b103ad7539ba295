"""The three kernels of ``ops/pallas_ssm.py`` in interpret mode against
the token recurrence in plain ``jax.numpy``: the step's state update
(dead rows bit for bit, slots and channels that are no whole blocks),
the prompt's scan (tiles, a real length inside a tile and inside a group
of eight, a state that is not zero) and the step's convolution with its
tail in place."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_ssm as ps


def _vectors(rng, rows, n, ch):
    """dt in 1e-3..1e-1, u, b, c from N(0, 1), a = -(1..n) a channel."""
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (rows, ch)))
    a = -np.tile(np.arange(1.0, n + 1)[:, None], (1, ch)) \
        * rng.uniform(0.5, 2.0, (1, ch))
    return [jnp.asarray(x, jnp.float32) for x in (
        dt, rng.randn(rows, ch), rng.randn(rows, n), rng.randn(rows, n), a)]


def _recurrence(dt, u, b, c, a, h, n_real):
    """One request's tokens one after another: plain numpy, float64."""
    dt, u, b, c, a, h = (np.asarray(x, np.float64)
                         for x in (dt, u, b, c, a, h))
    ys = []
    for t in range(n_real):
        h = np.exp(dt[t][None, :] * a) * h \
            + (dt[t] * u[t])[None, :] * b[t][:, None]
        ys.append((h * c[t][:, None]).sum(0))
    return np.stack(ys), h


def test_the_rule_takes_whole_tiles_of_float32_and_nothing_else():
    assert ps.ssm_rule(16, 5120, jnp.float32)
    assert ps.ssm_rule(8, 128, np.float32)
    assert not ps.ssm_rule(16, 5120, jnp.bfloat16)
    assert not ps.ssm_rule(16, 48, jnp.float32)
    assert not ps.ssm_rule(4, 128, jnp.float32)
    z = jnp.zeros
    with pytest.raises(ValueError, match="ssm_rule"):
        ps.ssm_update(z((2, 48)), z((2, 48)), z((2, 8)), z((2, 8)),
                      z((8, 48)), z((2, 8, 48)), z((2,), bool),
                      interpret=True)
    with pytest.raises(ValueError, match="ssm_rule"):
        ps.ssm_scan(z((8, 128)), z((8, 128)), z((8, 8)), z((8, 8)),
                    z((8, 128)), z((1, 8, 128), jnp.bfloat16), 3,
                    interpret=True)


@pytest.mark.parametrize("rows, n, ch, block", [
    (5, 16, 384, (8, 256)),      # fewer slots than a block, 1.5 blocks
    (16, 8, 256, (8, 128)),      # whole blocks of both
    (11, 16, 384, (8, 256)),     # a last block of three slots
    (4, 16, 128, None),          # the served blocks, cut to the call
], ids=["5x384", "16x256", "11x384", "served_blocks"])
def test_the_step_kernel_is_the_token_recurrence(rows, n, ch, block):
    rng = np.random.RandomState(rows * ch)
    dt, u, b, c, a = _vectors(rng, rows, n, ch)
    state = jnp.asarray(rng.randn(rows, n, ch), jnp.float32)
    live = rng.rand(rows) > 0.3
    live[0], live[-1] = True, False
    y, new = ps.ssm_update(dt, u, b, c, a, state, jnp.asarray(live),
                           block=block, interpret=True)
    y0, new0 = ps.ssm_token_xla(dt, u, b, c, a, state)
    for r in range(rows):
        want_y, want_h = _recurrence(dt[r:r + 1], u[r:r + 1], b[r:r + 1],
                                     c[r:r + 1], a, state[r], 1)
        if live[r]:
            np.testing.assert_allclose(y[r], want_y[0], atol=2e-5)
            np.testing.assert_allclose(new[r], want_h, atol=2e-6)
            np.testing.assert_allclose(y0[r], want_y[0], atol=2e-5)
            np.testing.assert_allclose(new0[r], want_h, atol=2e-6)
        else:
            # a dead row: the state it had, every bit; no output
            assert np.array_equal(np.asarray(new[r]), np.asarray(state[r]))
            assert not np.asarray(y[r]).any()


@pytest.mark.parametrize("t, n, ch, block, n_real, zero", [
    (64, 16, 384, (256, 32), 50, True),    # 1.5 channel blocks, 2 tiles
    (64, 16, 384, (256, 32), 64, False),   # every token real
    (32, 8, 128, (128, 16), 3, False),     # inside the first group of 8
    (128, 16, 256, None, 70, False),       # the served tile of 64
    (64, 8, 128, (128, 16), 0, False),     # nothing real: nothing moves
], ids=["50_of_64", "64_of_64", "3_of_32", "70_of_128", "0_of_64"])
def test_the_scan_kernel_is_the_token_recurrence(t, n, ch, block, n_real,
                                                 zero):
    rng = np.random.RandomState(t + ch + n_real)
    dt, u, b, c, a = _vectors(rng, t, n, ch)
    state = jnp.zeros((1, n, ch), jnp.float32) if zero \
        else jnp.asarray(rng.randn(1, n, ch), jnp.float32)
    y, new = ps.ssm_scan(dt, u, b, c, a, state, jnp.int32(n_real),
                         block=block, interpret=True)
    assert y.shape == (t, ch) and new.shape == (1, n, ch)
    assert not np.asarray(y[n_real:]).any()         # padding rows: zero
    if n_real == 0:
        assert np.array_equal(np.asarray(new), np.asarray(state))
        return
    want_y, want_h = _recurrence(dt, u, b, c, a, state[0], n_real)
    np.testing.assert_allclose(y[:n_real], want_y, atol=5e-5)
    np.testing.assert_allclose(new[0], want_h, atol=5e-6)


def test_the_scan_of_two_calls_is_the_scan_of_one():
    """A prompt longer than a call: the state handed from call to call
    (through HBM) is the state one call keeps in fast memory."""
    rng = np.random.RandomState(7)
    dt, u, b, c, a = _vectors(rng, 128, 8, 128)
    zero = jnp.zeros((1, 8, 128), jnp.float32)
    y, h = ps.ssm_scan(dt, u, b, c, a, zero, jnp.int32(100),
                       interpret=True)
    y1, h1 = ps.ssm_scan(dt[:64], u[:64], b[:64], c[:64], a, zero,
                         jnp.int32(64), interpret=True)
    y2, h2 = ps.ssm_scan(dt[64:], u[64:], b[64:], c[64:], a, h1,
                         jnp.int32(36), interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), y, atol=1e-6)
    np.testing.assert_allclose(h2, h, atol=1e-6)


def test_the_scan_refuses_rows_that_are_no_whole_tiles():
    z = jnp.zeros
    with pytest.raises(ValueError, match="whole tiles"):
        ps.ssm_scan(z((100, 128)), z((100, 128)), z((100, 8)), z((100, 8)),
                    z((8, 128)), z((1, 8, 128)), 3, interpret=True)


@pytest.mark.parametrize("rows, ch, block", [(5, 256, None), (19, 128, 8)],
                         ids=["5x256", "19x128_blocks_of_8"])
def test_the_convolution_kernel_moves_its_tail_in_place(rows, ch, block):
    rng = np.random.RandomState(rows)
    k = 4
    tail = jnp.asarray(rng.randn(rows, (k - 1) * ch), jnp.float32)
    u = jnp.asarray(rng.randn(rows, ch), jnp.float32)
    taps = jnp.asarray(rng.randn(k, ch), jnp.float32)
    bias = jnp.asarray(rng.randn(ch), jnp.float32)
    live = rng.rand(rows) > 0.3
    live[0], live[-1] = True, False
    y, new = ps.ssm_conv_update(tail, u, taps, bias, jnp.asarray(live),
                                block=block, interpret=True)
    window = np.concatenate([np.asarray(tail), np.asarray(u)], axis=1)
    conv = sum(window[:, j * ch:(j + 1) * ch] * np.asarray(taps)[j]
               for j in range(k)) + np.asarray(bias)
    want = conv / (1.0 + np.exp(-conv))
    np.testing.assert_allclose(np.asarray(y)[live], want[live], atol=2e-6)
    assert np.array_equal(np.asarray(new)[live], window[live][:, ch:])
    assert np.array_equal(np.asarray(new)[~live], np.asarray(tail)[~live])
    y0, new0 = ps.ssm_conv_xla(tail, u, taps, bias)
    np.testing.assert_allclose(y0, want, atol=2e-6)
    assert np.array_equal(np.asarray(new0), window[:, ch:])
    with pytest.raises(ValueError, match="does not take"):
        ps.ssm_conv_update(tail[:, :ch], u, taps, bias, jnp.asarray(live),
                           interpret=True)
