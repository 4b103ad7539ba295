"""Main-path kernels compiled by the TPU's own compiler, without a TPU.

The chip's compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (``v5e:2x2``).  Interpret mode cannot see what it
refuses: a matmul with no free row dimension, a block that is not a legal
(8, 128) tile, too much VMEM.  These cases pin every Pallas kernel of the
serving and training main paths at real widths, plus the whole
GPT-2-width decode step.

This is the ONLY file that describes the chip, and it does so inside a
module-scoped, non-autouse fixture: nothing chip-related runs at import,
in a ``skipif``, in a ``parametrize`` argument or in ``conftest.py``
(only one process at a time may load the TPU library; a worker that
collected a different set of tests would make xdist run none).
Compiles happen in the test's own process with the persistent
compilation cache off (an entry compiled here cannot be read back
without a chip).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.ops import quant_ops as qo


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs;
    returns the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


SLOTS, PPS = 8, 64                      # one replica's slot batch


def _paged_shapes(h, d, page, rows, quantized):
    """(q, k_pages, v_pages, page_table, lengths[, k_scales, v_scales])
    for the paged kernels; ``rows=0`` is the one-token decode shape."""
    pool = SLOTS * PPS
    q = (SLOTS, rows, h, d) if rows else (SLOTS, h, d)
    ln = (SLOTS, rows) if rows else (SLOTS,)
    kv = ((pool, page, h, d), jnp.int8 if quantized else jnp.float32)
    out = [(q, jnp.float32), kv, kv, ((SLOTS, PPS), jnp.int32),
           (ln, jnp.int32)]
    if quantized:
        out += [((pool, page, h), jnp.float32)] * 2
    return out


def _paged(op, q, k, v, pt, ln, ks=None, vs=None):
    return op(q, k, v, pt, ln, use_pallas="always", k_scales=ks,
              v_scales=vs)


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["plain", "int8kv"])
@pytest.mark.parametrize("h,d,page", [(12, 64, 16), (12, 64, 128),
                                      (8, 128, 16)])
def test_paged_decode_attention_compiles(one_chip, h, d, page, quantized):
    text = _compile(one_chip,
                    functools.partial(_paged, pda.paged_decode_attention),
                    *_paged_shapes(h, d, page, 0, quantized))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["plain", "int8kv"])
@pytest.mark.parametrize("h,d,rows", [(12, 64, 16), (12, 64, 5),
                                      (8, 128, 16)])
def test_paged_chunk_attention_compiles(one_chip, h, d, rows, quantized):
    """rows=16 is one chunked-prefill page, rows=5 a speculative
    t0+4-draft verify window."""
    text = _compile(one_chip,
                    functools.partial(_paged, pda.paged_chunk_attention),
                    *_paged_shapes(h, d, 16, rows, quantized))
    assert "tpu_custom_call" in text


def _qkv(b, h, s, d):
    return [((b, h, s, d), jnp.bfloat16)] * 3


def _grad_of(attn):
    def loss(q, k, v, *mask):
        return attn(q, k, v, *mask).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("mask", ["causal", "key", "full"])
@pytest.mark.parametrize("h,d", [(12, 64), (8, 128)])
def test_flash_attention_compiles(one_chip, h, d, mask, backward):
    b, s = 2, 2048
    shapes = _qkv(b, h, s, d)
    if mask == "key":
        shapes.append(((b, 1, 1, s), jnp.bfloat16))
    elif mask == "full":
        shapes.append(((b, h, s, s), jnp.bfloat16))

    def attn(q, k, v, m=None):
        return fa.flash_attention(q, k, v, m, causal=mask == "causal",
                                  use_pallas=True)

    text = _compile(one_chip, _grad_of(attn) if backward else attn,
                    *shapes)
    # forward kernel, or forward + dq + dk/dv kernels
    assert text.count("tpu_custom_call") == (3 if backward else 1)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("h,d", [(12, 64), (8, 128)])
def test_flash_attention_bias_compiles(one_chip, h, d, backward):
    """The streamed-bias kernel with BERT's key mask; its backward is
    the q-chunked XLA recompute, compiled with it."""
    b, s = 2, 2048
    shapes = _qkv(b, h, s, d) + [((b, 1, 1, s), jnp.bfloat16)]
    text = _compile(
        one_chip,
        _grad_of(pa.flash_attention_bias) if backward
        else pa.flash_attention_bias, *shapes)
    # the gradient never needs the forward's output (the backward
    # recomputes), so only the forward-alone program keeps the kernel
    assert backward or "tpu_custom_call" in text


def test_stock_flash_attention_compiles(one_chip):
    """ops/fused.py hands big unbiased attention to jax's own kernel."""
    from jax.experimental.pallas.ops.tpu.flash_attention import \
        flash_attention

    text = _compile(one_chip,
                    functools.partial(flash_attention, causal=True),
                    *_qkv(2, 12, 2048, 64))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", [(8, 768, 3072), (8, 3072, 768),
                                   (8, 768, 2304), (256, 1024, 4096)])
def test_dequant_matmul_compiles(one_chip, m, k, n):
    text = _compile(
        one_chip, functools.partial(qo.dequant_matmul, use_pallas="always"),
        ((m, k), jnp.float32), ((k, n), jnp.int8), ((n,), jnp.float32))
    assert "tpu_custom_call" in text  # tiled, not the reference


@pytest.mark.parametrize("kv_quant", [False, True], ids=["plain", "int8kv"])
def test_gpt2_width_decode_step_compiles(one_chip, kv_quant):
    """The engine's whole joint decode step at GPT-2-small width (depth
    cut to 2 layers): the kernel must be IN the step the compiler
    accepted.  'auto' reads the live backend (the CPU here), so the test
    steers the engine to the kernel itself."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.decode import TransformerLM

    model = TransformerLM(vocab_size=50257, d_model=768, num_layers=2,
                          num_heads=12, ffn_dim=3072, max_seq_len=1024)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    eng = DecodeEngine(model, weights, DecodeConfig(
        max_seq_len=1024, use_pallas="always", kv_quant=kv_quant))
    lowered = eng.lower_step(sharding=one_chip)
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == model.num_layers
    # each call carries the kernel's own name, which is how a trace
    # tells it from any other tpu_custom_call: the instruction is
    # "%paged_attention[.n] = ... custom-call(...)" under the step's scope
    from paddle_tpu.ops.pallas_decode_attention import KERNEL_NAME

    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == model.num_layers
    assert all(ln.lstrip().lstrip("%").startswith(KERNEL_NAME)
               for ln in calls), calls[0][:200]
    assert f"jit(step)/decode_step/{KERNEL_NAME}/" in lowered.as_text(
        debug_info=True)
