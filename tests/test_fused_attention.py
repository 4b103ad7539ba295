"""fused_multihead_attention: numpy-oracle parity + gradient flow.

Reference parity: operators/fused/multihead_matmul_op.cu (the fused
transformer attention path).
"""
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework.backward import append_backward
from paddle_tpu.framework.program import Program, program_guard

B, S, H, NH = 2, 8, 16, 4


def _oracle(q, k, v, bias, n_heads):
    b, s, hidden = q.shape
    d = hidden // n_heads

    def heads(x):
        return x.reshape(b, s, n_heads, d).transpose(0, 2, 1, 3)

    qh, kh, vh = heads(q), heads(k), heads(v)
    scores = np.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(d)
    if bias is not None:
        scores = scores + bias
    scores = scores - scores.max(-1, keepdims=True)
    p = np.exp(scores)
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bhkd->bhqd", p, vh)
    return out.transpose(0, 2, 1, 3).reshape(b, s, hidden)


def test_fused_attention_matches_numpy_and_grads_flow():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        q = layers.data("q", [B, S, H], append_batch_size=False)
        k = layers.data("k", [B, S, H], append_batch_size=False)
        v = layers.data("v", [B, S, H], append_batch_size=False)
        mask = layers.data("mask", [B, 1, 1, S], append_batch_size=False)
        for t in (q, k, v):
            t.stop_gradient = False
        out = layers.fused_multihead_attention(q, k, v, num_heads=NH,
                                               bias_qk=mask)
        loss = layers.mean(out)
        append_backward(loss)
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    qv = rng.randn(B, S, H).astype("float32")
    kv = rng.randn(B, S, H).astype("float32")
    vv = rng.randn(B, S, H).astype("float32")
    bias = np.zeros((B, 1, 1, S), "float32")
    bias[0, 0, 0, -2:] = -1e4  # mask the last two keys of batch 0
    got, dq = exe.run(
        main, feed={"q": qv, "k": kv, "v": vv, "mask": bias},
        fetch_list=[out, "q@GRAD"], scope=scope)
    want = _oracle(qv, kv, vv, bias, NH)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    dq = np.asarray(dq)
    assert dq.shape == (B, S, H) and np.any(dq != 0.0)


def test_bert_builder_fused_matches_unfused():
    """Same weights (shared startup seeds won't match across builds), so
    compare structurally: the fused program must produce a finite loss
    and strictly fewer ops than the unfused chain."""
    from paddle_tpu.text import bert_base_pretrain_program

    m1, *_ = bert_base_pretrain_program(
        batch_size=2, seq_len=8, vocab_size=32, hidden=16, n_layers=1,
        n_heads=4, ffn_size=32, dropout_prob=0.0, max_preds_per_seq=2,
        use_fused_attention=True)
    m2, *_ = bert_base_pretrain_program(
        batch_size=2, seq_len=8, vocab_size=32, hidden=16, n_layers=1,
        n_heads=4, ffn_size=32, dropout_prob=0.0, max_preds_per_seq=2,
        use_fused_attention=False)
    n1 = len(m1.global_block.ops)
    n2 = len(m2.global_block.ops)
    assert n1 < n2
    assert any(op.type == "fused_multihead_attention"
               for op in m1.global_block.ops)


def test_flag_wanted_kernel_refused_by_shape_is_counted(monkeypatch):
    """FLAGS_flash_attention=always at a shape the tiling cannot cover:
    the plain path stands in, and the stand-in is COUNTED (on the chip a
    silent reference would hide the kernel from every measurement)."""
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops import fused

    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    pt.set_flags({"FLAGS_flash_attention": "always"})
    try:
        before = stat_get("flash_attention_refused_shape")
        assert fused._flash_engaged(2, 12, 128, 128, 64)
        assert stat_get("flash_attention_refused_shape") == before
        assert not fused._flash_engaged(2, 12, 100, 100, 64)
        assert stat_get("flash_attention_refused_shape") == before + 1
        pt.set_flags({"FLAGS_flash_attention": "auto"})
        # auto below its memory threshold never wanted the kernel
        assert not fused._flash_engaged(2, 12, 100, 100, 64)
        assert stat_get("flash_attention_refused_shape") == before + 1
    finally:
        pt.set_flags({"FLAGS_flash_attention": "auto"})
