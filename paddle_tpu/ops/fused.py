"""Fused ops: multi-head attention, flash-kernel engagement by flag.

Role parity: reference operators/fused/multihead_matmul_op.cu (the
transformer attention fusion used by inference + the fused bert encoder
functors in operators/math/bert_encoder_functor.cu).

Three lowerings share one op:
- plain XLA composition (default; XLA's own fusion is speed-competitive
  with flash at flagship shapes — see _flash_engaged's measurements);
- the stock jax Pallas flash kernel for big UNBIASED attention (keeps
  the [B,H,S,S] score tensor out of HBM);
- the custom Pallas kernel (ops/pallas_attention.py) for big BIASED
  attention — it streams the additive mask block-by-block, which the
  stock kernel cannot.
Engagement is controlled by FLAGS_flash_attention (auto/always/never)
and tested off-TPU through interpret mode.  All kernels carry a custom
VJP, so the framework's generic vjp-replay gradient path
(ops/grad_generic.py) differentiates through them unchanged.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..framework.lowering import register_lower


def _plain_attention(q, k, v, bias, sm_scale, causal=False):
    """Reference composition: softmax((q k^T) * scale + bias) v, fp32
    softmax internals, inputs' dtype out."""
    dt = q.dtype
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask[None, None], s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


_FORCE_INTERPRET = False  # tests: engage the pallas path on CPU


def _flash_mode() -> str:
    from ..framework.flags import flag

    return str(flag("flash_attention"))


def _shape_ok(sq, sk, d):
    # pallas kernels want lane-aligned sequence blocks; head dims
    # 64/128/256 map cleanly onto the MXU
    return sq % 128 == 0 and sk % 128 == 0 and d in (64, 128, 256)


def _flash_engaged(b, h, sq, sk, d):
    """Flag-controlled engagement (FLAGS_flash_attention).

    'auto': no record holds a time for the pallas kernel (the ledger's
    BERT-base cell, S=128, runs XLA's own attention fusion at 209 ms a
    step; no cell engages a flash kernel), so what flash is engaged for
    is the MEMORY ceiling, not throughput — the plain path
    materializes the [B,H,Sq,Sk] fp32 score tensor in backward.  Auto
    engages only when that tensor would threaten HBM (>2 GB).
    'always' engages at any aligned shape (A/B testing, memory-bound
    configs the heuristic misses); 'never' forces the plain path."""
    mode = _flash_mode()
    if mode == "never":
        return False
    if not (_FORCE_INTERPRET or jax.default_backend() == "tpu"):
        return False
    wanted = mode == "always" or 4 * b * h * sq * sk > (2 << 30)
    if wanted and not _shape_ok(sq, sk, d):
        # the plain path stands in for a kernel the flag asked for:
        # never in silence (a smoke or a bench reads this counter)
        from ..monitor import stat_add

        stat_add("flash_attention_refused_shape")
        return False
    return wanted


@register_lower("fused_multihead_attention")
def _fused_mha(ctx, op):
    q = ctx.in1(op, "Q")
    k = ctx.in1(op, "K")
    v = ctx.in1(op, "V")
    bias = ctx.in1(op, "BiasQK")  # additive mask, [B,1,1,S] or [B,H,S,S]
    n_heads = int(op.attr("head_number", op.attr("num_heads", 1)))
    b, s, hidden = q.shape
    d = hidden // n_heads
    sm_scale = float(op.attr("alpha", 0.0)) or 1.0 / math.sqrt(d)

    def heads(x):
        return jnp.transpose(x.reshape(b, s, n_heads, d), (0, 2, 1, 3))

    qh, kh, vh = heads(q), heads(k), heads(v)
    causal = bool(op.attr("causal", False))

    if bool(op.attr("sequence_parallel", False)):
        # EXPLICIT opt-in: the caller asserts the op runs inside an 'sp'
        # shard_map with q/k/v sequence-sharded (shard i holds global
        # positions [i*S_local, (i+1)*S_local)); presence of an sp axis
        # alone is not enough — replicated inputs would make each rank
        # compute a different wrong answer
        from ..distributed.ring_attention import ring_attention

        if "sp" not in getattr(ctx, "axis_env", ()):
            raise ValueError(
                "fused_multihead_attention(sequence_parallel=True) needs "
                "an 'sp' mesh axis in scope (run under a sequence-sharded "
                "shard_map)")
        if bias is not None and not (bias.shape[1] == 1
                                     and bias.shape[2] == 1):
            raise NotImplementedError(
                "fused attention under sequence parallelism takes only a "
                "key mask [B,1,1,S_local] (it rotates around the ring "
                "with its k/v shard); a full [B,H,S,S] bias has no "
                "shardable rotation form")
        out = ring_attention(qh, kh, vh, axis_name="sp", sm_scale=sm_scale,
                             causal=causal, bias=bias)
    elif _flash_engaged(b, n_heads, s, s, d):
        from ..monitor import stat_add

        stat_add("flash_attention_engaged")
        if bias is not None:
            # biased attention: OUR kernel streams the additive mask
            # block-by-block (pallas_attention.py) — the stock kernel
            # only takes a pre-materialized [B,H,S,S] `ab`, which is the
            # HBM blowup flash exists to avoid
            from .pallas_attention import flash_attention_bias

            out = flash_attention_bias(
                qh, kh, vh, bias, sm_scale=sm_scale, causal=causal,
                interpret=jax.default_backend() != "tpu")
        elif jax.default_backend() == "tpu":
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention,
            )

            out = flash_attention(qh, kh, vh, sm_scale=sm_scale,
                                  causal=causal)
        else:  # _FORCE_INTERPRET engagement off-TPU (tests)
            from .pallas_attention import flash_attention_bias

            out = flash_attention_bias(qh, kh, vh, None,
                                       sm_scale=sm_scale, causal=causal,
                                       interpret=True)
    else:
        out = _plain_attention(qh, kh, vh, bias, sm_scale, causal=causal)

    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, hidden)
    ctx.set_out(op, "Out", out)
