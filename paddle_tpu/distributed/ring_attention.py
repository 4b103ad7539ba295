"""Ring attention: sequence-parallel attention over an 'sp' mesh axis.

The reference has NO long-context story (SURVEY §5: no ring attention,
no sequence parallel — LoD + single-device fused attention only), so
this is a beyond-parity, TPU-first component: Q/K/V are sharded on the
sequence dim over the 'sp' axis; K/V blocks rotate around the ring via
`lax.ppermute` while each rank folds every block into its local queries
with the online-softmax (running max / running sum) rescaling — the
same math as flash attention, distributed.  Peak memory per chip is
O(S_local^2 -> S_local * D) instead of O(S^2), so sequence length
scales linearly with the ring size; the ppermute rides ICI.

Differentiable by construction: ppermute has a transpose rule, so
jax.vjp of this function IS ring attention backward (a reverse ring).
"""
from __future__ import annotations

import math


def ring_attention(q, k, v, axis_name="sp", sm_scale=None, causal=False,
                   bias=None):
    """Per-shard attention inside shard_map.

    Args:
      q, k, v: [B, H, S_local, D] — the local sequence shard.
      axis_name: mesh axis carrying the sequence ring.
      sm_scale: score scale; defaults to 1/sqrt(D).
      causal: causal masking with GLOBAL sequence positions (shard i
        holds positions [i*S_local, (i+1)*S_local)).
      bias: optional additive KEY mask [B, 1, 1, S_local] — each rank
        holds the mask shard for ITS keys; the shard rotates around the
        ring with its k/v block, so a padding mask costs one extra
        O(B*S_local) ppermute per step.  (A full [B,H,Sq,Sk] bias has
        no shardable rotation form and is rejected upstream.)

    Returns [B, H, S_local, D] in q.dtype.  Differentiable by
    construction — ppermute's transpose rule makes jax.vjp of this the
    reverse ring, including the bias cotangent.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, h, s_local, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    p = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]

    qf = q.astype(jnp.float32) * sm_scale
    neg = jnp.float32(-1e30)

    def block(qf, kj, vj, bj, j_rank):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj.astype(jnp.float32))
        if bj is not None:
            s = s + bj.astype(jnp.float32)  # [B,1,1,Sk] broadcasts
        if causal:
            q_pos = r * s_local + jnp.arange(s_local)
            k_pos = j_rank * s_local + jnp.arange(s_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, neg)
        m = jnp.max(s, axis=-1)  # [B, H, Sq]
        e = jnp.exp(s - m[..., None])
        l = jnp.sum(e, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", e, vj.astype(jnp.float32))
        return m, l, o

    # carry: (k_block, v_block, bias_block, owner_rank, m/l/acc)
    m_run = jnp.full((b, h, s_local), neg)
    l_run = jnp.zeros((b, h, s_local), jnp.float32)
    acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    kj, vj, bj, owner = k, v, bias, r
    for _step in range(p):
        m_j, l_j, o_j = block(qf, kj, vj, bj, owner)
        m_new = jnp.maximum(m_run, m_j)
        alpha = jnp.exp(m_run - m_new)  # rescale old accumulator
        beta = jnp.exp(m_j - m_new)  # rescale this block
        l_run = l_run * alpha + l_j * beta
        acc = acc * alpha[..., None] + o_j * beta[..., None]
        m_run = m_new
        if _step < p - 1:
            kj = lax.ppermute(kj, axis_name, perm)
            vj = lax.ppermute(vj, axis_name, perm)
            if bj is not None:
                bj = lax.ppermute(bj, axis_name, perm)
            owner = (owner - 1) % p
    out = acc / jnp.maximum(l_run[..., None], 1e-30)
    return out.astype(q.dtype)


_SHARDED_CACHE = {}


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", sm_scale=None,
                           causal=False, bias=None):
    """Convenience wrapper: global [B, H, S, D] arrays in, shard_map over
    the sequence dim, global array out (for tests / eager use).  A key
    mask ``bias`` [B, 1, 1, S] shards on its key dim.  The jitted
    callable is cached per (mesh, axis, scale, causal, has-bias) so
    repeated calls hit the compile cache instead of retracing."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = (id(mesh), axis_name, sm_scale, causal, bias is not None)
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        spec = P(None, None, axis_name, None)
        in_specs = (spec, spec, spec) + (
            (P(None, None, None, axis_name),) if bias is not None else ())

        def f(q, k, v, bias=None):
            return ring_attention(q, k, v, axis_name=axis_name,
                                  sm_scale=sm_scale, causal=causal,
                                  bias=bias)

        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                               out_specs=spec, check_vma=False))
        _SHARDED_CACHE[key] = fn
    args = (q, k, v) if bias is None else (q, k, v, bias)
    return fn(*args)
