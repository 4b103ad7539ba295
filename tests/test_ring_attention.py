"""Ring attention (sequence parallelism) vs full-attention oracle.

Beyond-reference component (the reference has no long-context story,
SURVEY §5); parity oracle is plain softmax attention on the gathered
sequence, forward AND backward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.ring_attention import (
    ring_attention_sharded,
)

B, H, S, D = 2, 3, 32, 8
SP = 4


@pytest.fixture
def mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:SP]), ("sp",))


def _full_attention(q, k, v, causal=False):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _qkv(seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(B, H, S, D), jnp.float32),
            jnp.asarray(rs.randn(B, H, S, D), jnp.float32),
            jnp.asarray(rs.randn(B, H, S, D), jnp.float32))


def test_forward_matches_full_attention(mesh):
    q, k, v = _qkv()
    got = ring_attention_sharded(q, k, v, mesh)
    want = _full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_causal_matches_full_attention(mesh):
    q, k, v = _qkv(1)
    got = ring_attention_sharded(q, k, v, mesh, causal=True)
    want = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_backward_matches_full_attention(mesh):
    """jax.vjp through the ring (ppermute transposes to a reverse ring)
    must equal the dense-attention gradient."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.ring_attention import ring_attention

    q, k, v = _qkv(2)
    spec = P(None, None, "sp", None)

    def ring_loss(q, k, v):
        def f(q, k, v):
            return ring_attention(q, k, v, axis_name="sp")

        out = shard_map(f, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
        return jnp.sum(out * out)

    def full_loss(q, k, v):
        out = _full_attention(q, k, v)
        return jnp.sum(out * out)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ring, g_full, "qkv"):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"grad {name}")


def test_fused_op_uses_ring_under_sp(mesh):
    """The fused_multihead_attention lowering routes to the ring when the
    executor runs inside an 'sp' shard_map."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.framework.lowering import LOWERINGS, LoweringContext

    hidden = H * D
    q2 = np.random.RandomState(3).randn(B, S, hidden).astype("f4")

    class FakeOp:
        type = "fused_multihead_attention"
        inputs = {"Q": ["q"], "K": ["k"], "V": ["v"]}
        outputs = {"Out": ["o"]}

        def attr(self, name, default=None):
            return {"head_number": H, "alpha": 0.0,
                    "sequence_parallel": True}.get(name, default)

        def output_arg_names(self):
            return ["o"]

    def f(qkv):
        env = {"q": qkv, "k": qkv, "v": qkv}

        class B_:
            program = None

            def _find_var_recursive(self, n):
                return None

        ctx = LoweringContext(B_(), env, axis_env=("sp",))
        LOWERINGS["fused_multihead_attention"](ctx, FakeOp())
        return env["o"]

    spec = P(None, "sp", None)
    got = jax.jit(shard_map(f, mesh=mesh, in_specs=(spec,),
                            out_specs=spec, check_vma=False))(
        jnp.asarray(q2))
    # oracle: dense self-attention with q=k=v
    qh = jnp.transpose(jnp.asarray(q2).reshape(B, S, H, D), (0, 2, 1, 3))
    want = jnp.transpose(_full_attention(qh, qh, qh), (0, 2, 1, 3)).reshape(
        B, S, hidden)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_key_mask_bias_matches_full_attention(mesh):
    """A padding key-mask rotates around the ring with its k/v shard
    (round-5: the SP path previously rejected any bias)."""
    q, k, v = _qkv(5)
    rs = np.random.RandomState(6)
    bias = jnp.asarray(
        np.where(rs.rand(B, 1, 1, S) > 0.25, 0.0, -1e9), jnp.float32)
    got = ring_attention_sharded(q, k, v, mesh, bias=bias)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D) + bias
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_key_mask_bias_backward(mesh):
    q, k, v = _qkv(7)
    rs = np.random.RandomState(8)
    bias = jnp.asarray(
        np.where(rs.rand(B, 1, 1, S) > 0.25, 0.0, -1e9), jnp.float32)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh,
                                              bias=bias) ** 2)

    def loss_full(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D) + bias
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        return jnp.sum(o ** 2)

    gr = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gg, "qkv"):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"grad {name}")
