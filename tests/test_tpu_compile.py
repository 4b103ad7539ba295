"""Main-path kernels compiled by the TPU's own compiler, without a TPU.

The chip's compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (``v5e:2x2``).  Interpret mode cannot see what it
refuses: a matmul with no free row dimension, a block that is not a legal
(8, 128) tile, too much VMEM.  These cases pin every Pallas kernel of the
serving and training main paths at real widths, plus the whole
GPT-2-width decode step, and that the K/V page pools keep one device
layout through the step, the prefill and copy-on-write.

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
import json
import os
import re

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


LAYERS = 2                              # the pools are stacked


def _paged_shapes(h, d, page, rows, quantized, dtype=jnp.float32):
    """(q, k_pages, v_pages, page_table, lengths[, k_scales, v_scales])
    for the paged kernels, the pools stacked and lane-folded as the
    cache stores them; ``rows=0`` is the one-token decode shape."""
    pool = SLOTS * PPS
    q = (SLOTS, rows, h, d) if rows else (SLOTS, h, d)
    ln = (SLOTS, rows) if rows else (SLOTS,)
    kv = ((LAYERS, pool, page, h * d), jnp.int8 if quantized else dtype)
    out = [(q, jnp.float32), kv, kv, ((SLOTS, PPS), jnp.int32),
           (ln, jnp.int32)]
    if quantized:
        out += [((LAYERS, pool, page, h), jnp.float32)] * 2
    return out


def _paged(op, q, k, v, pt, ln, ks=None, vs=None):
    return op(q, k, v, pt, ln, layer=LAYERS - 1, use_pallas="always",
              k_scales=ks, v_scales=vs)


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


@pytest.mark.parametrize("rows", [0, 5, 16],
                         ids=["decode", "verify5", "chunk16"])
def test_paged_attention_compiles_at_2048_bf16_lanes(one_chip, rows):
    """16 heads of 128 in bfloat16 (an OLMoE-shaped row, 2,048 lanes):
    eight pages a block are 2 MB of block buffers, as at GPT-2's f32
    rows; where rows are stacked on a head (a verify window's 5, a
    chunk's 16) a block is the 16 pages whose buffers are 4 MB.  The
    bfloat16 feed: a stack's query rows ride as three groups of
    bfloat16 rows, each group on whole packed tiles (5 rows of a
    speculative window take 16)."""
    assert pda.pages_per_block(16, PPS, 16 * 128, jnp.bfloat16, None, 16,
                               max(rows, 1)) == (16 if rows else 8)
    op = pda.paged_chunk_attention if rows else pda.paged_decode_attention
    text = _compile(one_chip, functools.partial(_paged, op),
                    *_paged_shapes(16, 128, 16, rows, False, jnp.bfloat16))
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


def _gpt2_width_engine(num_heads, kv_quant, vocab_size=50257, **cfg):
    """An engine at GPT-2's head shape (``num_heads`` x 64), depth cut
    to 2 layers, weights zero: only shapes matter to a compile.  'auto'
    reads the live backend (the CPU here), so the engine is steered to
    the kernel itself."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.decode import TransformerLM

    model = TransformerLM(vocab_size=vocab_size, d_model=num_heads * 64,
                          num_layers=2, num_heads=num_heads,
                          ffn_dim=4 * num_heads * 64, max_seq_len=1024)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        max_seq_len=1024, use_pallas="always", kv_quant=kv_quant, **cfg))


@pytest.mark.parametrize("kv_quant", [False, True], ids=["plain", "int8kv"])
def test_gpt2_width_decode_step_compiles(one_chip, kv_quant):
    """The engine's whole joint decode step at GPT-2-small width (depth
    cut to 2 layers): the kernel must be IN the step the compiler
    accepted."""
    eng = _gpt2_width_engine(12, kv_quant)
    model = eng.model
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


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _pallas_calls(jaxpr):
    return (e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call")


def test_gpt2_width_step_walks_blocks_of_eight_pages():
    """The benchmark's step (32 slots, 16 x 64 heads, f32 pools of
    16-token pages): every layer's kernel runs one grid step a SLOT and
    holds two buffers of EIGHT pages a pool, so a silent fall-back to a
    page a block fails here.  Traced, not compiled: needs no chip."""
    eng = _gpt2_width_engine(16, False, vocab_size=512, slots=32)
    args = (tuple(eng._scope.get_var(n) for n in eng._state_vars),
            eng.weights, eng._step_args(()), eng._no_tokens)
    calls = list(_pallas_calls(eng._step_fn.trace(*args).jaxpr.jaxpr))
    assert len(calls) == eng.model.num_layers
    for eqn in calls:
        grid = eqn.params["grid_mapping"]
        assert grid.grid == (32,)
        buffers = [a.shape for a in grid.scratch_avals
                   if len(a.shape) == 4]
        assert buffers == [(2, 8, 16, 1024)] * 2, buffers


# -- what the kernel's two matmuls are fed, by the pools' dtype -------------

def _kernel_body(dtype, quantized=False, h=16, d=64, hq=None, window=None):
    """The jaxpr of the paged kernel's body (SLOTS slots of PPS pages)."""
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in
              _paged_shapes(h, d, 16, 0, quantized, dtype)]
    if hq:
        shapes[0] = jax.ShapeDtypeStruct((SLOTS, hq, d), jnp.float32)

    def fn(q, k, v, pt, ln, ks=None, vs=None):
        return pda.paged_decode_attention(
            q, k, v, pt, ln, layer=1, use_pallas="always", k_scales=ks,
            v_scales=vs, window=window)

    call, = _pallas_calls(jax.make_jaxpr(fn)(*shapes).jaxpr)
    return call.params["jaxpr"]


@pytest.mark.parametrize("hq, h, d, window", [
    (128, 8, 128, None), (128, 8, 128, 4096), (64, 8, 128, None),
    (None, 16, 64, None)],
    ids=["command_a_plus", "command_a_plus_window", "solar", "mha_16x64"])
def test_bf16_pools_reach_the_matmuls_as_they_lie(hq, h, d, window):
    """A bfloat16 pool's two matmuls take bfloat16 operands on both
    sides (K and V as the block buffers hold them, the float32 query
    and probabilities as bfloat16 terms), accumulate in float32, and
    nothing makes a float32 copy of a block.  Traced: needs no chip."""
    body = _kernel_body(jnp.bfloat16, h=h, d=d, hq=hq, window=window)
    dots = [e for e in _eqns(body) if e.primitive.name == "dot_general"]
    assert len(dots) >= 2
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert e.outvars[0].aval.dtype == jnp.float32
    block = 128                          # eight pages of 16 positions
    for e in _eqns(body):
        out = e.outvars[0].aval if e.outvars else None
        if e.primitive.name == "convert_element_type" \
                and out.dtype == jnp.float32:
            assert block not in out.shape[:1] and out.shape[:2] != (8, 16), \
                f"a float32 copy of a block: {out}"


@pytest.mark.parametrize("quantized, want", [
    (False, (435, 2, 4, 2, 55)), (True, (637, 2, 4, 2, 87))],
    ids=["f32", "int8"])
def test_float32_and_int8_pools_keep_the_body_they_had(quantized, want):
    """GPT-2's path: the body of a float32 (and an int8) pool's kernel
    is the one it was before the bfloat16 feed, counted from its jaxpr
    at commit e4b55d6 (equations; matmuls; copies started and waited
    for; selects).  Its matmuls take float32 on both sides.  A jax
    upgrade moves the counts: re-read them from that commit's kernel."""
    body = _kernel_body(jnp.float32, quantized)
    count = {}
    for e in _eqns(body):
        count[e.primitive.name] = count.get(e.primitive.name, 0) + 1
        if e.primitive.name == "dot_general":
            assert [v.aval.dtype for v in e.invars] == [jnp.float32] * 2
    assert (sum(count.values()), count["dot_general"], count["dma_start"],
            count["dma_wait"], count["select_n"]) == want


# -- the K/V pools keep ONE layout through every serving program ----------
#
# A CPU run can show what is IN a compiled program, never a time: these
# cases are the standing proof that no program re-lays a pool out, slices
# a layer out of it or copies it, and that a token's K/V lands in place.

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<type>.+?) "
    r"(?P<op>[a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _computation(text, header):
    """The lines of the computation whose first line starts with
    ``header``, up to its closing brace."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.startswith(header))
    end = next(i for i in range(start, len(lines))
               if lines[i].startswith("}"))
    return lines[start + 1:end]


def _assert_pools_stay_put(compiled, pools, relaid=()):
    """``pools``: the shapes of the state tuple, which is the program's
    FIRST parameters and LAST results.  Shapes in ``relaid`` are known
    NOT to keep their layout inside the program (they are still aliased
    and enter and leave alike)."""
    text = compiled.as_text()
    n = len(pools)
    ins = jax.tree_util.tree_leaves(compiled.input_formats)
    outs = jax.tree_util.tree_leaves(compiled.output_formats)
    # every state output is the donated state input, updated in place
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    pairs = {(int(o), int(i)) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", alias[1])}
    want = {(len(outs) - n + i, i) for i in range(n)}
    assert want <= pairs, (sorted(want), sorted(pairs))
    # ... and arrives and leaves in the same device layout
    for i in range(n):
        assert ins[i].layout == outs[len(outs) - n + i].layout, (
            i, ins[i].layout, outs[len(outs) - n + i].layout)
    # nothing pool-sized or layer-of-pool-sized is materialised (the
    # ENTRY computation's results; a fusion's inner instructions live
    # in registers and VMEM) except by the scatter or
    # dynamic-update-slice that writes the pool in place
    strict = [s for s in pools if s not in relaid]
    sized = {tuple(s) for s in strict} | {tuple(s[1:]) for s in strict}
    offenders, writers = [], 0
    for line in _computation(text, "ENTRY "):
        m = _INSTR.match(line)
        if not m or m["op"] in ("parameter", "get-tuple-element", "tuple",
                                "bitcast"):
            continue
        dims = {tuple(int(d) for d in a.split(",") if d)
                for a in _ARRAY.findall(m["type"])}
        if not {d[1:] if d[:1] == (1,) else d for d in dims} & sized:
            continue
        called = re.search(r"calls=(%[\w.\-]+)", line)
        root = next(ln for ln in _computation(text, called[1] + " ")
                    if "ROOT " in ln) if called else line
        if " scatter(" in root or " dynamic-update-slice(" in root:
            writers += 1
        else:
            offenders.append(f"{m['name']} = {m['type'][:80]} {m['op']}")
    assert not offenders, offenders
    assert writers, "no in-place write of the pools"


def _on_chip(tree, one_chip):
    """Shapes of ``tree`` placed on the described chip: a program is
    lowered from them, nothing runs."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                       sharding=one_chip), tree)


def _lower_program(eng, program, one_chip):
    """One of the engine's pool-taking programs lowered at its own
    shapes; returns it with the shapes of its state tuple."""
    state = tuple(eng._scope.get_var(n) for n in eng._state_vars)
    i32 = jnp.int32
    if program == "step":
        lowered = eng.lower_step(sharding=one_chip)
    elif program == "prefill":        # one whole-prompt bucket
        lowered = eng.lower_prefill(128, sharding=one_chip)
    else:                             # copy-on-write of one page
        lowered = eng._build_cow_fn().lower(
            *_on_chip((state, i32(1), i32(2)), one_chip))
    return lowered, [tuple(v.shape) for v in state]


@pytest.mark.parametrize("program", ["step", "prefill", "cow"])
@pytest.mark.parametrize("kv_quant", [False, True], ids=["plain", "int8kv"])
@pytest.mark.parametrize("heads", [16, 12], ids=["16x64", "12x64"])
def test_pools_keep_one_layout_through_program(one_chip, heads, kv_quant,
                                               program):
    """GPT-2-medium's and GPT-2-small's head shapes, f32 and int8 pools:
    the compiled step, whole-prompt prefill and copy-on-write hold no
    pool- or layer-sized copy, slice or transposition."""
    # 32 slots and their default 2,049 pages: a pool of a few MB is
    # prefetched whole into fast memory (seen up to 24 MB), which would
    # prove nothing.  The vocabulary is cut: it only costs compile time
    eng = _gpt2_width_engine(heads, kv_quant, vocab_size=512, slots=32)
    cc = eng._cache.config
    assert cc.lane_dense
    lowered, pools = _lower_program(eng, program, one_chip)
    # the int8 pools' scale planes [L, P, page, H] cannot fill the
    # tiles (H lanes of 128): they enter in a transposed layout and
    # the step and the prefill re-lay them out around their writes.
    # A standing finding, not a contract: drop the exemption with it
    relaid = (cc.pool_shape(row_lanes=cc.num_heads),) \
        if kv_quant and program != "cow" else ()
    compiled = lowered.compile()
    _assert_pools_stay_put(compiled, pools, relaid)
    if program == "prefill":
        _assert_prompt_attends_its_bucket(compiled.as_text(), 128, cc)


def _assert_prompt_attends_its_bucket(text, t_pad, cc):
    """No instruction of the compiled whole-prompt prefill, inside a
    fusion or out, has a result of the prompt's rows by the cache's
    ``max_seq_len`` positions: the scores ``[t_pad, max_seq_len, heads]``
    and the K/V every row read, ``[..., heads, head_dim]`` (at GPT-2
    medium's widths the device trace's ``f32[128,1024,16]`` fusions,
    in any order of the dimensions).  The rows' own are there."""
    wide = {tuple(sorted((t_pad, cc.max_seq_len, cc.num_heads) + tail))
            for tail in ((), (cc.head_dim,))}
    found = {tuple(int(d) for d in a.split(",") if d)
             for line in text.splitlines() if (m := _INSTR.match(line))
             for a in _ARRAY.findall(m["type"])}
    assert (t_pad, cc.num_heads, cc.head_dim) in found
    offenders = sorted(d for d in found if tuple(sorted(d)) in wide)
    assert not offenders, offenders


# -- the hybrid model: grouped heads, slabs beside the pools ---------------

def test_paged_attention_compiles_with_grouped_query_heads(one_chip):
    """64 query heads over 8 K/V heads of 128 in bf16 pools of 1,024
    lanes (Solar-Open2's softmax layer): the group's heads ride as rows,
    so one-token decode is the kernel at R=8, H=8."""
    shapes = _paged_shapes(8, 128, 16, 0, False, jnp.bfloat16)
    shapes[0] = ((SLOTS, 64, 128), jnp.float32)     # 8 query heads a K/V
    text = _compile(one_chip,
                    functools.partial(_paged, pda.paged_decode_attention),
                    *shapes)
    assert text.count("tpu_custom_call") == 1


def _hybrid_engine():
    """Solar-Open2's head shapes (64 x 128 linear heads cut to 16, 8
    query over 2 K/V heads of 128), one layer of each kind, narrow
    everywhere else: only shapes matter to a compile.  128 slots make
    the layer's matrices 134 MB: a slab of 64 MB was still prefetched
    whole into fast memory, which would prove nothing."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.hybrid_moe_lm import HybridMoELM

    model = HybridMoELM(
        vocab_size=512, d_model=512,
        layer_kinds=("attention", "recurrent"),
        num_heads=8, num_kv_heads=2, head_dim=128, lin_heads=16,
        lin_head_dim=128, conv_kernel=4, gate_rank=128, num_experts=32,
        top_k=8, held_experts=range(4), expert_dim=256, shared_dim=256)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=128, max_seq_len=1024, use_pallas="always",
        cache_dtype="bfloat16"))


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_recurrent_state_is_updated_in_place(one_chip, program):
    """The slabs of the recurrent layers beside the pools: every state
    output is its donated input in the same device layout, and no
    program holds a slab-sized copy or transposition (the step updates
    all slots' rows, the prefill one slot's, in place)."""
    eng = _hybrid_engine()
    lowered, state = _lower_program(eng, program, one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    # the step's paged kernel, the prefill's flash kernel, and in each
    # the recurrent layer's rule: the step's is the token rule's kernel,
    # the prompt's the chunk form's (in its loop over the prompt's calls)
    from paddle_tpu.ops import pallas_kda_chunk as chunked
    from paddle_tpu.ops import pallas_kda_update as kda
    assert text.count("tpu_custom_call") == 2
    assert ("prompt_flash_attention" in text) == (program == "prefill")
    assert (f"%{kda.KERNEL_NAME}" in text) == (program == "step")
    assert (f"%{chunked.KERNEL_NAME}" in text) == (program == "prefill")
    n = len(state)
    assert n == 2 + 2
    ins = jax.tree_util.tree_leaves(compiled.input_formats)
    outs = jax.tree_util.tree_leaves(compiled.output_formats)
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    pairs = {(int(o), int(i)) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", alias[1])}
    assert {(len(outs) - n + i, i) for i in range(n)} <= pairs
    for i in range(n):
        assert ins[i].layout == outs[len(outs) - n + i].layout, (
            i, state[i], ins[i].layout, outs[len(outs) - n + i].layout)
    # the matrices (a few MB a slot); a convolution tail small enough
    # is prefetched whole into fast memory, which is no re-layout
    slabs = {tuple(s) for s in state[2::2]}
    for line in _computation(text, "ENTRY "):
        m = _INSTR.match(line)
        if m and m["op"] in ("copy", "transpose", "copy-start"):
            dims = {tuple(int(d) for d in a.split(",") if d)
                    for a in _ARRAY.findall(m["type"])}
            assert not dims & slabs, line[:160]
    if program == "step":
        _assert_the_benchmarks_pattern_finds_the_state_update(text, slabs)
        _assert_one_kernel_passes_over_the_slab(text, slabs)


@pytest.mark.parametrize("rows, tokens", [(128, 1), (1, 64)],
                         ids=["step", "tokens_of_one_row"])
def test_kda_state_update_compiles_at_solar_rows(one_chip, rows, tokens):
    """The token rule's kernel at the cell's own shapes (64 heads of
    128 x 128 float32): the step's 128 slots at one token, and one
    row's 64 tokens one after another (the grid a prompt ran until PR
    58; no model calls it so any more, the kernel's own tests do).  The
    state is the call's operand as it lies and its second result's
    buffer; the blocks fit the kernel's VMEM budget."""
    from paddle_tpu.ops import pallas_kda_update as kda

    h, d = 64, 128
    vec, f32 = (rows, tokens, h, d), jnp.float32
    assert kda.kda_rule(h, d, d, f32)
    assert kda.head_block(tokens, h, d, d) == 16
    text = _compile(
        one_chip, kda.kda_update, (vec, f32), (vec, f32), (vec, f32),
        (vec, f32), (vec[:3], f32), ((rows, h, d, d), f32),
        ((rows,), jnp.int32))
    call = [ln for ln in text.splitlines()
            if f"%{kda.KERNEL_NAME}" in ln.split(" = ")[0]
            and "tpu_custom_call" in ln]
    assert len(call) == 1
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(3, \{\}\)\}",
                     call[0])


@pytest.mark.parametrize("heads, bucket", [
    (64, 256), (64, 512), (64, 1024), (32, 4096)],
    ids=["solar_256", "solar_512", "solar_1024", "kimi_linear_4096"])
def test_kda_chunk_form_compiles_at_the_cells_calls(one_chip, heads, bucket):
    """The rule's chunk form (``ops/pallas_kda_chunk.py``) at ONE call
    of each bucket the two cells prefill: Solar's 64 heads at 256, 512
    and 1,024 rows, Kimi-Linear's 32 at 4,096, the group
    ``KDAMixer.prefill_chunks_per_call`` gives the bucket (its
    temporaries under ``GROUP_BYTES``).  The vectors reach the kernel
    lane-folded as they lie, the state is the call's operand as it lies
    and its second result's buffer, the blocks fit the VMEM budget and
    the compiled call's own temporaries the cap."""
    from paddle_tpu.ops import pallas_kda_chunk as chunked
    from paddle_tpu.serving import mixers
    from tools.sweep_kda_chunk import mixer_of

    d, f32 = 128, jnp.float32
    group = mixer_of(dict(heads=heads, beta=1.0)).prefill_chunks_per_call(
        bucket)
    assert group == {64: 2, 32: 4}[heads] and bucket % (group * 64) == 0
    tokens = group * mixers.PREFILL_CHUNK
    assert 4 * tokens * 8 * heads * d <= mixers.GROUP_BYTES
    vec = (1, tokens, heads, d)
    args = [jax.ShapeDtypeStruct(s_, t, sharding=one_chip) for s_, t in (
        (vec, f32), (vec, f32), (vec, f32), (vec, f32), (vec[:3], f32),
        ((1, heads, d, d), f32), ((1,), jnp.int32))]
    compiled = jax.jit(chunked.kda_chunk).lower(*args).compile()
    text = compiled.as_text()
    call = [ln for ln in text.splitlines()
            if re.search(r"%" + chunked.KERNEL_NAME + r"[.\d]* = ", ln)
            and "tpu_custom_call" in ln]
    assert len(call) == 1 and text.count("tpu_custom_call") == 1
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(6, \{\}\)\}",
                     call[0])
    assert call[0].count(f"f32[{tokens},{heads * d}]") == 5
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= mixers.GROUP_BYTES


def _assert_one_kernel_passes_over_the_slab(text, slabs):
    """The step's matrices ``%state_2_`` are an operand of ONE
    instruction of the entry computation, the state-update kernel, as
    the program's own argument (no copy, bitcast or mask in front), and
    that call's result of the slab's shape is the operand's buffer."""
    from paddle_tpu.ops import pallas_kda_update as kda

    entry = _computation(text, "ENTRY ")
    readers = [ln for ln in entry
               if re.search(r"%state_2_\.\d+[,)]", ln.split(" = ", 1)[-1])]
    assert len(readers) == 1, [ln[:120] for ln in readers]
    call = readers[0]
    assert f"%{kda.KERNEL_NAME}" in call.split(" = ")[0] \
        and "tpu_custom_call" in call
    m = _INSTR.match(call)
    assert {tuple(int(d) for d in a.split(",") if d)
            for a in _ARRAY.findall(m["type"])} & slabs
    # operand 3 (after the live flags and the two stacks of vectors) is
    # result 1
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(3, \{\}\)\}",
                     call), call[-400:]
    assert not [ln for ln in entry if " select(" in ln and any(
        "[%s]" % ",".join(map(str, s)) in ln for s in slabs)]


def _assert_the_benchmarks_pattern_finds_the_state_update(text, slabs):
    """``kda_ms_per_step.serve`` and ``kda_state_roofline`` find the
    state update in a trace by the operands its instructions read: the
    slabs' positions in the step's state tuple.  A state tuple in another
    order, or an update that no longer reads the slabs by those names,
    must fail HERE and not read None on the chip: the pattern has to
    match, in the compiled step, an instruction that writes a new slab
    and none that reads only the pools."""
    import json

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics")
    patterns = set()
    for name in ("kda_ms_per_step.serve", "kda_state_roofline"):
        with open(os.path.join(bench, name + ".json")) as f:
            patterns.add(json.load(f)["params"]["pattern"])
    assert len(patterns) == 1
    pat = re.compile(patterns.pop())
    matched = [line for line in _computation(text, "ENTRY ")
               if pat.search(line.split(" = ", 1)[-1])]
    writes = [line for line in matched if {
        tuple(int(d) for d in a.split(",") if d)
        for a in _ARRAY.findall(_INSTR.match(line)["type"])} & slabs]
    assert writes, "no matched instruction writes a slab"
    assert not [line for line in matched if "paged_attention" in line]
    # the pools are operands 0 and 1: the pattern leaves them out
    assert not pat.search("%x = f32[1] fusion(%state_0_.1, %state_1_.1)")


# -- MiMo-V2.5's rows: K wider than V, a window off a ring, a sink ----------

@pytest.mark.parametrize("kv_heads, window", [(4, None), (8, 128)],
                         ids=["global_768_512", "window_1536_1024"])
def test_paged_attention_compiles_at_mimo_rows(one_chip, kv_heads, window):
    """64 query heads of 192 lanes over 4 (global) or 8 (window) K/V
    heads, K rows of 768 / 1,536 bf16 lanes beside V rows of 512 /
    1,024, pages of 16: the global call over a table of 256 pages a slot
    (4,096 positions), the window call over a ring of 9 with its sinks
    and its own name."""
    pps = PPS * 4 if window is None else 9
    pool = SLOTS * pps + 1
    # the block the rule gives: 512 positions of the global table, the
    # one whole lane tile the ring of 9 pages holds
    assert pda.pages_per_block(
        16, pps, kv_heads * 192, jnp.bfloat16, kv_heads * 128, kv_heads,
        64 // kv_heads) == (32 if window is None else 8)
    shapes = [((SLOTS, 64, 192), jnp.float32),
              ((LAYERS, pool, 16, kv_heads * 192), jnp.bfloat16),
              ((LAYERS, pool, 16, kv_heads * 128), jnp.bfloat16),
              ((SLOTS, pps), jnp.int32), ((SLOTS,), jnp.int32)]
    if window is None:
        fn = functools.partial(_paged, pda.paged_decode_attention)
    else:
        shapes.append(((64,), jnp.float32))

        def fn(q, k, v, pt, ln, sinks):
            return pda.paged_decode_attention(
                q, k, v, pt, ln, layer=1, use_pallas="always",
                window=window, sinks=sinks)

    text = _compile(one_chip, fn, *shapes)
    assert text.count("tpu_custom_call") == 1
    assert ("paged_attention_window" in text) == (window is not None)
    assert re.search(r"bf16\[%d,64,128\]|f32\[%d,64,128\]" % (SLOTS, SLOTS),
                     text), "the output has V's width"


# -- Command A+'s rows: rings of 257 pages, 16 query heads a K/V head ------

@pytest.mark.parametrize("window", [None, 4096],
                         ids=["global_385_pages", "window_ring_257"])
def test_paged_attention_compiles_at_command_a_plus_rows(one_chip, window):
    """128 query heads over 8 K/V heads of 128 lanes (16 rows a K/V
    head), K and V rows of 1,024 bf16 lanes, pages of 16: the global
    call over a table of 385 pages a slot (6,144 positions and a spare),
    the window call over a ring of 257 with no sink, nine blocks of 512
    positions at most (both buffers of both pools: 4 MB)."""
    pps = 385 if window is None else 257
    pool = SLOTS * pps + 1
    assert pda.pages_per_block(16, pps, 1024, jnp.bfloat16, 1024, 8,
                               16) == 32
    shapes = [((SLOTS, 128, 128), jnp.float32),
              ((LAYERS, pool, 16, 1024), jnp.bfloat16),
              ((LAYERS, pool, 16, 1024), jnp.bfloat16),
              ((SLOTS, pps), jnp.int32), ((SLOTS,), jnp.int32)]

    def fn(q, k, v, pt, ln):
        return pda.paged_decode_attention(
            q, k, v, pt, ln, layer=1, use_pallas="always", window=window)

    text = _compile(one_chip, fn, *shapes)
    assert text.count("tpu_custom_call") == 1
    assert ("paged_attention_window" in text) == (window is not None)


def _parallel_engine():
    """Command A+'s widths behind the engine at the cell's serving
    sizes (48 slots, 6,144 positions, bf16 pages), one layer of each
    kind, one held expert and a short vocabulary: only shapes matter to
    a compile, and these are the ones the chip's compiler could refuse
    (1,024-lane rows, a ring of 257, 128 heads over a 4,096-row prompt)."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.parallel_moe_lm import ParallelMoELM

    model = ParallelMoELM(
        vocab_size=1024, d_model=4096, layer_kinds=("window", "attention"),
        num_heads=128, num_kv_heads=8, head_dim=128, rope_theta=5e4,
        window=4096, num_experts=128, top_k=8, held_experts=(0,),
        expert_dim=4096, shared_experts=4, shared_dim=4096)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=48, max_seq_len=6144, num_pages=48 * 385 + 1,
        use_pallas="always", cache_dtype="bfloat16"))


_LAYER_METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "layer_metrics")


def _assert_prefill_holds_the_flash_kernel(text, bucket):
    """The whole-prompt prefill's attention is the flash kernel, by its
    own name, which no pattern of ``benchmark/layer_metrics/`` takes
    for one of the step's kernels; no float32 score plane ``[..,
    rows, bucket]`` is left in the program."""
    import glob

    from paddle_tpu.ops.pallas_prompt_attention import KERNEL_NAME

    calls = [line.strip() for line in text.splitlines()
             if re.match(r"\s*%" + KERNEL_NAME + r"[.\d]* = ", line)]
    assert calls and all("custom-call(" in c for c in calls), calls
    assert not re.search(r"f32\[(\d+,){2,}%d\]" % bucket, text)
    names = [os.path.basename(path)[:-len(".json")] for path in glob.glob(
        os.path.join(_LAYER_METRICS, "*.json"))]
    patterns = {n: _metric_pattern(n) for n in names}
    assert sum(p is not None for p in patterns.values()) >= 17
    for name, pattern in patterns.items():
        assert pattern is None or not [
            c for c in calls if pattern.search(c)], name
    return calls


@pytest.mark.parametrize("program", ["step", "prefill_4096"])
def test_command_a_plus_width_programs_compile(one_chip, program):
    """The joint step (both kernels in it, by name) and the 4,096-row
    whole-prompt prefill, whose attention is the flash kernel in both
    layers: no tensor of the program holds a score plane, not even a
    block of 256 rows of one."""
    eng = _parallel_engine()
    # 16 rows a K/V head on bfloat16 rings: blocks of 512 positions
    assert eng._ring == 257 and eng._window_block == 512 \
        and eng._attn_block == 512
    if program == "step":
        compiled = eng.lower_step(sharding=one_chip).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 2
        assert "paged_attention_window" in text
    else:
        assert pda.prefill_key_span(4096, 128, 4096) == (256, 4096)
        assert eng._prefill_walks(4096) == [
            (1, None, ("flash", 128, 1024)), (1, 4096, ("flash", 128, 1024))]
        compiled = eng.lower_prefill(4096, sharding=one_chip).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 2
        assert len(_assert_prefill_holds_the_flash_kernel(text, 4096)) == 2
    # what the program needs beside its operands stays far inside the chip
    assert compiled.memory_analysis().temp_size_in_bytes < 5 << 30


# -- Olmo-Hybrid's rows: ONE query row a K/V head, keys of 96 on values of 192

def test_paged_attention_compiles_at_olmo_hybrid_rows(one_chip):
    """30 query heads over 30 K/V heads of 128 lanes (a group of ONE row
    a K/V head), K and V rows of 3,840 bf16 lanes, pages of 16, a table
    of 352 pages a slot: the kernel's row stacking at its smallest
    group, blocks of 8 pages."""
    pps = 352
    pool = SLOTS * pps + 1
    assert pda.pages_per_block(16, pps, 3840, jnp.bfloat16, 3840, 30,
                               1) == 8
    shapes = [((SLOTS, 30, 128), jnp.float32),
              ((LAYERS, pool, 16, 3840), jnp.bfloat16),
              ((LAYERS, pool, 16, 3840), jnp.bfloat16),
              ((SLOTS, pps), jnp.int32), ((SLOTS,), jnp.int32)]
    text = _compile(one_chip,
                    functools.partial(_paged, pda.paged_decode_attention),
                    *shapes)
    assert text.count("tpu_custom_call") == 1


def _gated_delta_engine():
    """Olmo-Hybrid-7B's widths behind the engine at the cell's serving
    sizes (32 slots, 5,632 positions, bf16 pages, the whole vocabulary),
    one layer of each kind: only shapes matter to a compile, and these
    are the ones the chip's compiler could refuse (a 96 x 192 state a
    head, 3,840-lane rows, 30 heads over a 4,096-row prompt, logits of
    100,352)."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine, GatedDeltaLM

    model = GatedDeltaLM(
        vocab_size=100352, d_model=3840,
        layer_kinds=("recurrent", "attention"), num_heads=30, head_dim=128,
        lin_heads=30, lin_key_dim=96, lin_value_dim=192, conv_kernel=4,
        ffn_dim=11008)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=32, max_seq_len=5632, num_pages=32 * 353 + 1,
        use_pallas="always", cache_dtype="bfloat16"))


def _metric_pattern(name):
    """The pattern by which the benchmark's metric ``name`` finds its
    device events (None: it reads no events by name)."""
    import json

    with open(os.path.join(_LAYER_METRICS, name + ".json")) as f:
        pattern = json.load(f).get("params", {}).get("pattern")
    return None if pattern is None else re.compile(pattern)


@pytest.mark.parametrize("program", ["step", "prefill_4096"])
def test_olmo_hybrid_width_programs_compile(one_chip, program):
    """The joint step (the paged kernel at one row a K/V head, the
    one-token update on the slabs) and the 4,096-row whole-prompt
    prefill, whose recurrent layer is ONE loop over chunks of 64 and
    whose attention is the flash kernel; the
    benchmark's patterns find in the compiled programs what their
    metrics time."""
    eng = _gated_delta_engine()
    slab = tuple(eng._scope.get_var(
        eng._cache.recurrent_var_names()[0]).shape)
    assert slab == (32, 30, 96, 192)
    if program == "step":
        compiled = eng.lower_step(sharding=one_chip).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        entry = [line.split(" = ", 1)[-1]
                 for line in _computation(text, "ENTRY ")]
        gdn = _metric_pattern("gdn_ms_per_step.serve")
        assert _metric_pattern("gdn_state_roofline").pattern == gdn.pattern
        assert [l for l in entry if gdn.search(l) and "f32[32,30,96,192]"
                in l], "no matched instruction writes a slab"
        assert not [l for l in entry if gdn.search(l)
                    and "paged_attention" in l]
        ffn = _metric_pattern("dense_ffn_ms_per_step.serve")
        assert {m for l in entry for m in ffn.findall(l)} == {"gate", "up",
                                                               "down"}
    else:
        assert pda.prefill_key_span(4096, 30) == (1024, 4096)
        assert eng._prefill_walks(4096) == [
            (1, None, ("flash", 256, 1024))]
        compiled = eng.lower_prefill(4096, sharding=one_chip).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        _assert_prefill_holds_the_flash_kernel(text, 4096)
        loop = _metric_pattern("gdn_prefill_ms.serve")
        assert _metric_pattern("gdn_prefill_roofline").pattern \
            == loop.pattern
        whiles = [line for line in _computation(text, "ENTRY ")
                  if re.search(r"\bwhile\(", line)]
        chunked = [line for line in whiles if loop.search(line.strip())]
        # one loop a recurrent layer carries the slot's state (the
        # attention is a kernel now, no loop over blocks), four chunks
        # of the rule a call, and nothing inside it is a loop that
        # carries such a state of its own: the readers' time counts once
        assert len(chunked) == 1, whiles
        assert eng.model.prefill_chunks_per_call(4096) == 4
        assert len([line for line in text.splitlines()
                    if re.search(r"\bwhile\(", line)
                    and loop.search(line.strip())]) == 1
    # what the program needs beside its operands stays inside the chip:
    # 4.9 GB of weights, 5.6 GB of pages and 0.6 GB of slabs are resident
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


# -- MiMo-V2.5's prefill: keys of 192 on values of 128, sinks, a window ----

def _window_engine():
    """MiMo-V2.5's head widths behind the engine (64 query heads of 192
    lanes on 4 global / 8 window K/V heads, values of 128, a window of
    128 with a sink a head), one layer of each kind, one held expert and
    a short vocabulary: only shapes matter to a compile.  Its 8 slots
    stand for the cell's 128, so it routes 8 of 16 experts a row where
    the cell routes 8 of 256: four choices a held expert a step either
    way, and the step keeps the dense form as the cell's does
    (``moe_ops.hit_rule``)."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.window_moe_lm import WindowMoELM

    model = WindowMoELM(
        vocab_size=1024, d_model=4096, layer_kinds=("window", "attention"),
        dense_layers=0, num_heads=64, num_kv_heads=4, window_kv_heads=8,
        head_dim=192, v_head_dim=128, rotary_dim=64, rope_theta=1e7,
        window_rope_theta=1e4, window=128, value_scale=0.707,
        dense_dim=512, num_experts=16, top_k=8, held_experts=(0,),
        expert_dim=2048)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=8, max_seq_len=4096, use_pallas="always",
        cache_dtype="bfloat16"))


@pytest.mark.parametrize("program", ["step", "prefill_2048"])
def test_mimo_width_programs_compile(one_chip, program):
    """The joint step (the two paged kernels, as ever) and the 2,048-row
    whole-prompt prefill: the flash kernel once a layer, the window
    layer's walk three key blocks of 128 a row block, K rows of 192
    lanes on V rows of 128, the sinks."""
    from paddle_tpu.ops import pallas_prompt_attention as ppa

    eng = _window_engine()
    # the global table in blocks of 512 positions, the ring of 9 pages
    # in the one lane tile it holds
    assert (eng._attn_block, eng._window_block) == (512, 128)
    if program == "step":
        text = eng.lower_step(sharding=one_chip).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        assert "paged_attention_window" in text
        assert ppa.KERNEL_NAME not in text
    else:
        assert eng._prefill_walks(2048) == [
            (1, None, ("flash", 128, 1024)), (1, 128, ("flash", 128, 128))]
        assert ppa._steps(2048, 128, 128, 128) == 3
        text = eng.lower_prefill(2048, sharding=one_chip).compile() \
            .as_text()
        assert text.count("tpu_custom_call") == 2
        calls = _assert_prefill_holds_the_flash_kernel(text, 2048)
        assert len(calls) == 2
        # both calls' outputs have V's width a head, their queries and
        # keys K's: 64 heads folded into a row's lanes
        assert all(c.startswith("%s = f32[2048,8192]" % c.split(" = ")[0])
                   and "bf16[2048,12288]" in c
                   and re.search(r"bf16\[[48],2048,192\]", c)
                   for c in calls), calls


# -- a prefill's held experts as a grouped matmul (the three routed cells) --

def _routed_engine(cell):
    """One routed layer at the cell's expert widths (hidden 4,096, the
    held experts as the cell holds them) behind the engine, few heads
    and a short vocabulary: the grouped form sees the rows, ``n_held``,
    ``F`` and ``D`` it sees in the cell."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.hybrid_moe_lm import HybridMoELM
    from paddle_tpu.serving.parallel_moe_lm import ParallelMoELM
    from paddle_tpu.serving.window_moe_lm import WindowMoELM

    if cell == "command_a_plus":
        model = ParallelMoELM(
            vocab_size=1024, d_model=4096, layer_kinds=("attention",),
            num_heads=16, num_kv_heads=8, head_dim=128, rope_theta=5e4,
            window=4096, num_experts=128, top_k=8, held_experts=range(8),
            expert_dim=4096, shared_experts=1, shared_dim=512)
    elif cell == "mimo_v2_5":
        model = WindowMoELM(
            vocab_size=1024, d_model=4096, layer_kinds=("attention",),
            dense_layers=0, num_heads=16, num_kv_heads=4, window_kv_heads=8,
            head_dim=192, v_head_dim=128, rotary_dim=64, rope_theta=1e7,
            window_rope_theta=1e4, window=128, value_scale=0.707,
            dense_dim=512, num_experts=256, top_k=8, held_experts=range(16),
            expert_dim=2048)
    else:
        model = HybridMoELM(
            vocab_size=1024, d_model=4096, layer_kinds=("attention",),
            num_heads=16, num_kv_heads=8, head_dim=128, lin_heads=16,
            lin_head_dim=128, conv_kernel=4, gate_rank=128, num_experts=320,
            top_k=8, held_experts=range(40), expert_dim=1280, shared_dim=512)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=4, max_seq_len=4096, use_pallas="always",
        cache_dtype="bfloat16"))


@pytest.mark.parametrize("cell, rows", [
    ("command_a_plus", 4096), ("mimo_v2_5", 2048),
    ("solar_open2_250b", 1024)])
def test_routed_prefills_compile_with_the_grouped_experts(
        one_chip, cell, rows):
    """The longest whole-prompt prefill of each routed cell, one layer:
    the two kernels of the grouped form are in the program by name, the
    dense form's ``[rows, n_held * F]`` plane is not, and the held
    experts' matrices are read where they lie: no transposition or copy
    of anything their size."""
    from paddle_tpu.ops import moe_ops, pallas_moe_grouped as grouped

    eng = _routed_engine(cell)
    lw = eng.weights["layers"][0]
    wide, tall = lw["moe_w_gate"].shape, lw["moe_w_down"].shape
    assert wide == tall[::-1] == (4096, tall[0]) and tall[0] >= 32768
    assert moe_ops.grouped_rule(rows, len(eng.model.held_experts),
                                eng.model.expert_dim, 4096)
    compiled = eng.lower_prefill(rows, sharding=one_chip).compile()
    text = compiled.as_text()
    # the two grouped kernels, and the attention's flash kernel
    assert text.count("tpu_custom_call") == 3
    assert grouped.GATE_UP_KERNEL_NAME in text
    assert grouped.DOWN_KERNEL_NAME in text
    assert not re.search(r"f32\[%d,%d\]" % (rows, wide[1]), text)
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and m["op"] in ("copy", "transpose", "copy-start"):
            dims = {tuple(int(d) for d in a.split(",") if d)
                    for a in _ARRAY.findall(m["type"])}
            assert not dims & {wide, tall}, line[:160]
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


# -- Kimi-K2.5's rows: ONE latent row a position, 64 heads as its rows ------

def _latent_engine(layers=2, **cfg):
    """Kimi-K2.5's attention widths behind the engine at the cell's
    serving sizes (64 slots, 10,240 positions, bf16 latent pages), a
    dense layer and an expert layer, 12 held experts of the published
    width and a short vocabulary: only shapes matter to a compile, and
    these are the ones the chip's compiler could refuse (rows of 576
    lanes in a pool of 640, 64 stacked query rows, blocks of 1,024
    positions, 64 ungrouped heads of 192 over an 8,192-row prompt, the
    grouped experts at a width of 7,168)."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.latent_moe_lm import LatentMoELM

    model = LatentMoELM(
        vocab_size=1024, d_model=7168, num_layers=layers, dense_layers=1,
        num_heads=64, q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
        v_dim=128, rope_theta=5e4, rope_factor=64.0, rope_orig_len=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, dense_dim=2048, num_experts=384, top_k=8,
        held_experts=range(12), expert_dim=2048, shared_dim=2048,
        routed_scale=2.827)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(**dict(dict(
        slots=64, max_seq_len=10240, num_pages=64 * 20 + 1,
        use_pallas="always", cache_dtype="bfloat16"), **cfg)))


def _hit_form_calls(eng, text):
    """The hit form's kernel in a compiled step of ``eng``: one call an
    expert layer, ``[slots, D]`` float32 out; the held experts' three
    matrices reach it as the program's own arguments, by the names the
    routed experts' metrics match, and nothing copies, transposes or
    slices anything their size in front of it; no dense plane ``[slots,
    n_held * F]`` is formed."""
    from paddle_tpu.ops import moe_ops, pallas_moe_hit as hit

    m = eng.model
    slots, n_held = eng.config.slots, len(m.held_experts)
    assert moe_ops.hit_rule(slots, n_held, m.expert_dim, m.d_model,
                            m.top_k, m.num_experts)
    assert eng._tallies[-2:] == moe_ops.HIT_TALLIES
    wide = (m.d_model, n_held * m.expert_dim)
    calls = [line.strip() for line in text.splitlines() if re.match(
        r"\s*%" + hit.HIT_KERNEL_NAME + r"[.\d]* = ", line)]
    pattern = _metric_pattern("moe_ffn_ms_per_step.serve")
    assert _metric_pattern("moe_experts_roofline").pattern \
        == pattern.pattern
    for c in calls:
        assert c.startswith("%s = f32[%d,%d]" % (
            c.split(" = ")[0], slots, m.d_model)), c[:200]
        assert sorted(pattern.findall(c)) == ["down", "gate", "up"], c[:600]
    assert not re.search(r"f32\[%d,%d\]" % (slots, wide[1]), text)
    for line in text.splitlines():
        i = _INSTR.match(line)
        if i and i["op"] in ("copy", "transpose", "copy-start", "slice",
                             "slice-start", "dynamic-slice"):
            dims = {tuple(int(d) for d in a.split(",") if d)
                    for a in _ARRAY.findall(i["type"])}
            assert not dims & {wide, wide[::-1]}, line[:160]
    return calls


@pytest.mark.parametrize("program", ["step", "prefill_8192"])
def test_kimi_width_programs_compile(one_chip, program):
    """The joint step (the latent kernel a layer, by its own name: one
    pool in, 64 x 64 rows of 512 lanes out, no V pool anywhere; the
    expert layer's held experts as the ONE kernel of the hit form, 64
    rows over 12 experts of 2,048 on 7,168, fed the program's own three
    weight arguments and found by the routed experts' metrics) and the
    8,192-row whole-prompt prefill (the flash kernel over 64 ungrouped
    heads of K 192 / V 128, the query head-major; the two grouped-expert
    kernels at a width of 7,168; the latents, not the expanded K/V, to
    the pages)."""
    from paddle_tpu.ops import pallas_moe_grouped as grouped
    from paddle_tpu.ops import pallas_prompt_attention as ppa

    eng = _latent_engine()
    cc = eng._cache.config
    assert (cc.latent, cc.row_lanes, cc.v_row_lanes, cc.lane_dense) == (
        True, 640, 0, True)
    assert eng._state_vars == ("__decode_k_pages__",)
    assert eng._attn_block == 1024
    pool = "bf16[2,1281,16,640]"
    if program == "step":
        compiled = eng.lower_step(sharding=one_chip).compile()
        text = compiled.as_text()
        calls = [line.strip() for line in text.splitlines() if re.match(
            r"\s*%" + pda.LATENT_KERNEL_NAME + r"[.\d]* = ", line)]
        hit_calls = _hit_form_calls(eng, text)
        assert len(calls) + len(hit_calls) \
            == text.count("tpu_custom_call") == 3
        assert all(c.startswith("%s = f32[64,64,512]" % c.split(" = ")[0])
                   and "f32[64,64,640]" in c and c.count(pool) == 1
                   for c in calls), calls
        # the names by which the other cells' metrics find THEIR kernels
        # take none of these calls; the latent metrics' pattern all
        for name in ("paged_attn_ms_per_step.serve",
                     "full_attn_ms_per_step.serve",
                     "window_attn_ms_per_step.serve"):
            with open(os.path.join(_LAYER_METRICS, name + ".json")) as f:
                mfile = json.load(f)
            pats = list(mfile.get("kernels", {}).values()) + [
                mfile.get("params", {}).get("pattern")]
            assert not [c for c in calls for p in pats
                        if p and re.search(p, c)], name
        assert all(_metric_pattern("latent_attn_ms_per_step.serve")
                   .search(c) for c in calls)
        assert ppa.KERNEL_NAME not in text
    else:
        assert eng._prefill_walks(8192) == [(2, None, ("flash", 1024, 1024))]
        compiled = eng.lower_prefill(8192, sharding=one_chip).compile()
        text = compiled.as_text()
        # the flash kernel a layer and the expert layer's two kernels
        assert text.count("tpu_custom_call") == 4
        assert grouped.GATE_UP_KERNEL_NAME in text
        assert grouped.DOWN_KERNEL_NAME in text
        calls = _assert_prefill_holds_the_flash_kernel(text, 8192)
        assert len(calls) == 2
        # 64 heads of V's 128 lanes out, the query and K head-major
        assert all(c.startswith("%s = f32[8192,8192]" % c.split(" = ")[0])
                   and c.count("bf16[64,8192,192]") == 2
                   and "bf16[64,8192,128]" in c for c in calls), calls
        assert pda.LATENT_KERNEL_NAME not in text
        # the head runs over the one row that is read, not the bucket
        assert "f32[1,1024]" in text and "f32[8192,1024]" not in text
    # the one pool goes in and comes out in its one layout, and nothing
    # of an expanded cache (64 heads x (192 + 128) lanes a position) is
    # kept: what the program needs beside its operands stays small
    _assert_pools_stay_put(compiled, [(2, 1281, 16, 640)])
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def test_kimi_cell_pool_is_the_latent_rows_at_whole_lane_tiles():
    """The cell's cache by arithmetic (no chip, nothing allocated): 5
    layers x 41,025 pages x 16 rows of 640 bfloat16 lanes, 1,280 B a
    position a layer where 1,152 are published and 40,960 would be the
    expanded heads'."""
    from paddle_tpu.serving.kv_cache import CacheConfig

    cc = CacheConfig(5, 1, 576, 64, 10240, 16, num_pages=41025,
                     dtype="bfloat16", v_head_dim=512, latent=True)
    assert cc.pool_shape() == (5, 41025, 16, 640)
    assert cc.cache_bytes() == 5 * 41025 * 16 * 1280 == 4200960000
    assert cc.page_bytes() == 16 * 1280 and cc.page_bytes(v=True) == 0
    with pytest.raises(ValueError, match="latent page"):
        CacheConfig(5, 64, 192, 64, 10240, 16, latent=True)
    with pytest.raises(ValueError, match="latent page"):
        CacheConfig(5, 1, 576, 64, 10240, 16, v_head_dim=512, latent=True,
                    quantized=True)


# -- Ouro-2.6B: the pools in place through a loop over the passes ---------

def _looped_engine():
    """Ouro-2.6B's widths behind the engine at the cell's serving sizes
    (16 slots of 320 positions, 337 bf16 pages, the whole vocabulary),
    two weight layers under four passes (8 cache layers): the layers run
    as a rolled loop over stacked weights, so the program is the cell's
    but for the loop's trip count and the stacks' depth."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine, LoopedLM

    model = LoopedLM(vocab_size=49152, d_model=2048, num_layers=2, loops=4,
                     num_heads=16, head_dim=128, ffn_dim=5632)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=16, max_seq_len=320, num_pages=337, use_pallas="always",
        cache_dtype="bfloat16"))


@pytest.mark.parametrize("program", ["step", "prefill_128"])
def test_ouro_width_programs_keep_the_pools_in_place_through_the_loops(
        one_chip, program):
    """The joint step (ONE paged-attention call whose layer is the
    loops' counters, inside the loop over the layers inside the loop
    over the passes) and the 128-row whole-prompt prefill: both pools
    are the loops' carry, aliased from argument to result, and what the
    program needs beside its operands is megabytes (a copy of one pool
    of the cell is 4.2 GB and would not fit beside 5.3 GB of weights and
    8.5 GB of pages); no stack of weights is turned over before the
    loop (wq and wk lie [out, in] for that)."""
    eng = _looped_engine()
    pool = (8, 337, 16, 2048)
    assert eng._cache.config.pool_shape() == pool
    if program == "step":
        compiled = eng.lower_step(sharding=one_chip).compile()
    else:
        compiled = eng.lower_prefill(128, sharding=one_chip).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (program == "step")
    carried = "bf16[8,337,16,2048]"
    whiles = [ln for ln in text.splitlines()
              if re.search(r" while\(", ln) and carried in ln]
    assert len(whiles) == 2, whiles                 # passes, layers
    entry = _computation(text, "ENTRY ")
    assert not [ln for ln in entry if re.search(r" copy\(", ln)
                and "weights__layers" in ln]
    # the two pools are the program's first parameters and last results,
    # each result its donated parameter in the same device layout (the
    # in-place writes are inside the loops' bodies, not in ENTRY, which
    # is where ``_assert_pools_stay_put`` looks for them)
    ins = jax.tree_util.tree_leaves(compiled.input_formats)
    outs = jax.tree_util.tree_leaves(compiled.output_formats)
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    pairs = {(int(o), int(i)) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", alias[1])}
    assert {(len(outs) - 2 + i, i) for i in range(2)} <= pairs
    assert all(ins[i].layout == outs[len(outs) - 2 + i].layout
               for i in range(2))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * 8 * 337 * 16 * 2048 * 2
    assert ma.temp_size_in_bytes < 256 << 20


# -- LFM2-8B-A1B's rows: convolution tails, heads of 64, every expert held --

@functools.lru_cache(maxsize=1)
def _conv_moe_engine():
    """LFM2-8B-A1B's widths behind the engine at the cell's serving
    sizes (128 slots of 2,560 positions, 20,609 bf16 pages, the whole
    vocabulary under a tied head), three layers: a convolution layer
    with the dense feed-forward, an attention layer with it, and a
    convolution layer with all 32 experts.  Built once for both
    programs: a gigabyte of zeros and the pools."""
    from paddle_tpu.serving import ConvMoELM, DecodeConfig, DecodeEngine

    model = ConvMoELM(
        vocab_size=65536, d_model=2048,
        layer_kinds=("recurrent", "attention", "recurrent"), num_heads=32,
        num_kv_heads=8, head_dim=64, conv_kernel=3, ffn_dim=7168,
        dense_layers=2, num_experts=32, top_k=4, held_experts=range(32),
        expert_dim=1792)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=128, max_seq_len=2560, num_pages=20609, use_pallas="always",
        cache_dtype="bfloat16"))


@pytest.mark.parametrize("program", ["step", "prefill_2048"])
def test_lfm2_width_programs_compile(one_chip, program):
    """The 128-row joint step (the paged kernel at 8 K/V heads of 64
    lanes, the experts in the dense form: 128 rows hit every expert) and
    the 2,048-row whole-prompt prefill (the grouped experts' two kernels
    at four pairs a row, the attention in the flash kernel at four heads
    of 64 lanes a group, so no plane of float32 scores anywhere; the
    head over one row): each needs under 1.5 GB beside its operands,
    updates pools and tails in place, and copies or transposes nothing
    an expert matrix's size."""
    from paddle_tpu.ops import moe_ops, pallas_moe_grouped as grouped
    from paddle_tpu.ops import pallas_prompt_attention as ppa

    eng = _conv_moe_engine()
    m = eng.model
    assert eng._cache.config.pool_shape() == (1, 20609, 16, 512)
    assert eng._cache.state_bytes() == 2 * 128 * 2 * 2048 * 4
    assert ppa.flash_rule(2048, 32, 8, 64, 64, None) == (256, 1024)
    assert eng._prefill_walks(2048) == [(1, None, ("flash", 256, 1024))]
    shape = (len(m.held_experts), m.expert_dim, m.d_model, m.top_k,
             m.num_experts)
    assert not moe_ops.grouped_rule(128, *shape)
    assert not moe_ops.hit_rule(128, *shape)
    assert moe_ops.grouped_rule(2048, *shape)
    assert grouped.default_tiles(2048, 32, 4, 32) == 256
    assert grouped.sorted_rows(2048, 32, 4, 32) == 4 * 2048 + 32 * 256
    if program == "step":
        compiled = eng.lower_step(sharding=one_chip).compile()
    else:
        compiled = eng.lower_prefill(2048, sharding=one_chip).compile()
    text = compiled.as_text()
    if program == "step":
        assert text.count("tpu_custom_call") == 1       # the paged kernel
        assert "paged_attention" in text
        assert not re.search(r"f32\[2048,65536\]", text)
    else:
        # the grouped two and the prompt's flash kernel
        assert text.count("tpu_custom_call") == 3
        assert grouped.GATE_UP_KERNEL_NAME in text
        assert grouped.DOWN_KERNEL_NAME in text
        assert re.search(r"%" + ppa.KERNEL_NAME + r"[.\d]* = .*custom-call\(",
                         text)
        # no plane of scores, whole (f32[8,4,2048,2048]) or in blocks of
        # rows (the tails are f32[128,2,2048]: d_model is the bucket)
        assert not re.search(r"f32\[(\d+,)+\d+,2048,2048\]", text)
        assert not re.search(r"f32\[8,4,\d+,2048\]", text)
        # the head over the read row alone, no plane of every row
        assert re.search(r"f32\[1,65536\]", text)
        assert not re.search(r"f32\[2048,65536\]", text)
    wide, tall = (2048, 32 * 1792), (32 * 1792, 2048)
    for line in text.splitlines():
        mt = _INSTR.match(line)
        if mt and mt["op"] in ("copy", "transpose", "copy-start"):
            dims = {tuple(int(d) for d in a.split(",") if d)
                    for a in _ARRAY.findall(mt["type"])}
            assert not dims & {wide, tall, (65536, 2048), (2048, 65536)}, \
                line[:160]
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1536 << 20
    pools_and_tails = 2 * 20609 * 16 * 512 * 2 + 2 * 128 * 2 * 2048 * 4
    assert ma.alias_size_in_bytes >= pools_and_tails


# t, query heads, K/V heads, K lanes, V lanes, window, sinks
_FLASH_64 = {
    "lfm2": (2048, 32, 8, 64, 64, None, False),
    "window_and_sinks": (2048, 32, 8, 64, 64, 128, True),
    "keys_of_128": (1024, 16, 8, 128, 64, None, False),
}


@pytest.mark.parametrize("case", list(_FLASH_64))
def test_prompt_flash_kernel_compiles_at_values_of_64_lanes(one_chip, case):
    """The prompt's flash kernel ALONE at every kind of shape
    ``flash_rule`` takes V heads of 64 lanes in (the cell's four heads a
    group, a window layer with sinks, K heads of whole tiles over V
    heads of half a tile): the chip's compiler lowers the half-tile
    slices of the query block and the half-tile stores of the result,
    which the interpreter cannot refuse."""
    from paddle_tpu.ops import pallas_prompt_attention as ppa

    t, h, hkv, d, dv, window, sinks = _FLASH_64[case]
    tiles = ppa.flash_rule(t, h, hkv, d, dv, window)
    assert tiles is not None

    def call(q, k, v, n, *sink):
        return ppa.prompt_flash_attention(
            q, k, v, n, *sink, sm_scale=d ** -0.5, window=window,
            tiles=tiles)

    shapes = [((t, h, d), jnp.float32), ((t, hkv, d), jnp.bfloat16),
              ((t, hkv, dv), jnp.bfloat16), ((), jnp.int32)]
    if sinks:
        shapes.append(((h,), jnp.float32))
    text = _compile(one_chip, call, *shapes)
    assert text.count("tpu_custom_call") == 1 and ppa.KERNEL_NAME in text
    assert f"f32[{t},{h},{dv}]" in text


# -- Kimi-Linear: state slabs BESIDE one pool of latent rows ----------------

def _linear_latent_engine():
    """Kimi-Linear's mixer widths behind the engine at the cell's serving
    sizes (128 slots, 6,144 positions, bf16 latent pages): one recurrent
    layer under the dense feed-forward and one latent layer over 32 held
    experts of the published width, a short vocabulary and a narrow
    dense layer: only shapes matter to a compile, and these are the ones
    the chip's compiler could refuse; 5,121 pages make the pool 105 MB,
    past what is prefetched whole into fast memory (a state tuple of ONE pool and then
    slabs, 32 stacked query rows on rows of 576 lanes in a pool of 640,
    32 ungrouped heads of 192 over a 4,096-row prompt beside the state
    kernel's loop, the grouped experts at a width of 2,304)."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.linear_latent_lm import LinearLatentLM

    model = LinearLatentLM(
        vocab_size=768, d_model=2304,
        layer_kinds=("recurrent", "attention"), dense_layers=1,
        lin_heads=32, lin_head_dim=128, conv_kernel=4, gate_rank=128,
        num_heads=32, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        dense_dim=2048, num_experts=256, top_k=8, held_experts=range(32),
        expert_dim=1024, shared_dim=1024, routed_scale=2.446)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=128, max_seq_len=6144, num_pages=128 * 40 + 1,
        use_pallas="always", cache_dtype="bfloat16"))


@pytest.mark.parametrize("program", ["step", "prefill_4096"])
def test_kimi_linear_width_programs_compile(one_chip, program):
    """The joint step (the state kernel over the 128 slots' slab, which
    is operand 1 of the program: the benchmark's NEW pattern finds it and
    the accepted one, which counts slabs from operand 2, does not; the
    latent kernel by its own name at 32 rows of the one head, one pool
    in and no V pool anywhere; 128 rows over 32 of 256 experts keep the
    dense form) and the 4,096-row whole-prompt prefill (the rule's
    chunk kernel in the loop over calls of 4 chunks of 64, the flash kernel over 32
    ungrouped heads of K 192 / V 128, the two grouped-expert kernels at
    a width of 2,304; the latents, not the expanded K/V, to the pages).
    The pool and both slabs go in and come out where they lie."""
    from paddle_tpu.ops import pallas_kda_chunk as chunked
    from paddle_tpu.ops import pallas_kda_update as kda
    from paddle_tpu.ops import pallas_moe_grouped as grouped
    from paddle_tpu.ops import pallas_prompt_attention as ppa

    eng = _linear_latent_engine()
    cc = eng._cache.config
    assert (cc.latent, cc.num_layers, cc.row_lanes, cc.v_row_lanes) == (
        True, 1, 640, 0)
    assert eng._state_vars == ("__decode_k_pages__",) \
        + eng._cache.recurrent_var_names()
    assert eng._attn_block == 1024
    state = [tuple(eng._scope.get_var(n).shape) for n in eng._state_vars]
    assert state == [(1, 5121, 16, 640), (128, 32, 128, 128),
                     (128, 3 * 3 * 4096)]
    slab = "f32[128,32,128,128]"
    if program == "step":
        compiled = eng.lower_step(sharding=one_chip).compile()
        text = compiled.as_text()
        entry = _computation(text, "ENTRY ")
        latent = [ln.strip() for ln in entry if re.match(
            r"\s*%" + pda.LATENT_KERNEL_NAME + r"[.\d]* = ", ln)]
        update = [ln.strip() for ln in entry if re.match(
            r"\s*%" + kda.KERNEL_NAME + r"[.\d]* = ", ln)]
        assert len(latent) == len(update) == 1 \
            and text.count("tpu_custom_call") == 2
        assert latent[0].startswith(
            "%s = f32[128,32,512]" % latent[0].split(" = ")[0]) \
            and "f32[128,32,640]" in latent[0] \
            and latent[0].count("bf16[1,5121,16,640]") == 1
        assert _metric_pattern("latent_step_ms.serve").search(latent[0])
        # the slab reaches the kernel as the program's own operand 1
        new = _metric_pattern("kda_step_ms.serve")
        assert _metric_pattern("kda_step_roofline").pattern == new.pattern
        body = update[0].split(" = ", 1)[-1]
        assert slab in update[0] and re.search(r"%state_1_\.\d+[,)]", body)
        assert new.search(body)
        assert not _metric_pattern("kda_ms_per_step.serve").search(body)
        assert not new.search(latent[0].split(" = ", 1)[-1])
        assert not new.search("%x = f32[1] fusion(%state_0_.1)")
        assert ppa.KERNEL_NAME not in text
        assert grouped.GATE_UP_KERNEL_NAME not in text
    else:
        assert eng._prefill_walks(4096) == [(1, None, ("flash", 1024, 1024))]
        assert eng.model.prefill_chunks_per_call(4096) == 4
        compiled = eng.lower_prefill(4096, sharding=one_chip).compile()
        text = compiled.as_text()
        # the rule's chunk form in its loop (256 tokens a call of 32
        # heads: the vectors lane-folded, the step's kernel nowhere),
        # the flash kernel, the expert layer's two kernels
        assert text.count("tpu_custom_call") == 4
        chunk = [ln for ln in text.splitlines() if re.match(
            r"\s*%" + chunked.KERNEL_NAME + r"[.\d]* = ", ln)]
        assert len(chunk) == 1 and f"%{kda.KERNEL_NAME}" not in text
        assert chunk[0].count("f32[256,4096]") == 5 \
            and chunk[0].count("f32[1,32,128,128]") >= 2
        assert grouped.GATE_UP_KERNEL_NAME in text
        assert grouped.DOWN_KERNEL_NAME in text
        calls = _assert_prefill_holds_the_flash_kernel(text, 4096)
        assert len(calls) == 1
        assert calls[0].count("bf16[32,4096,192]") == 2 \
            and "bf16[32,4096,128]" in calls[0], calls
        assert pda.LATENT_KERNEL_NAME not in text
        # the loop the prompt's recurrence runs in is what
        # ``kda_prefill_*`` time: its carry holds one slot's state
        loops = _metric_pattern("kda_prefill_ms.serve")
        assert _metric_pattern("kda_prefill_roofline").pattern \
            == loops.pattern
        found = [ln for ln in text.splitlines() if loops.search(ln.strip())]
        assert len(found) == 1 and "f32[1,32,128,128]" in found[0]
        # the head runs over the one row that is read, not the bucket
        assert "f32[1,768]" in text and "f32[4096,768]" not in text
    # no rotary work anywhere: ``mla_use_nope``
    assert " sine(" not in text and " cosine(" not in text
    # the one pool and both slabs are the program's first parameters
    # and last results, aliased, in one device layout; nothing the size
    # of the pool, of its layer or of the matrices' slab is copied or
    # turned (the tails' 18 MB are prefetched whole into fast memory,
    # which is no re-layout)
    n = len(state)
    ins = jax.tree_util.tree_leaves(compiled.input_formats)
    outs = jax.tree_util.tree_leaves(compiled.output_formats)
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    pairs = {(int(o), int(i)) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", alias[1])}
    assert {(len(outs) - n + i, i) for i in range(n)} <= pairs
    for i in range(n):
        assert ins[i].layout == outs[len(outs) - n + i].layout, (i, state[i])
    held = {state[0], state[0][1:], state[1]}
    for line in _computation(text, "ENTRY "):
        m = _INSTR.match(line)
        if m and m["op"] in ("copy", "transpose", "copy-start"):
            dims = {tuple(int(d) for d in a.split(",") if d)
                    for a in _ARRAY.findall(m["type"])}
            assert not dims & held, line[:160]
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


# -- Jamba2-3B: the selective scan's kernels, 256 slots, ONE K/V head -------

def _mamba_engine():
    """Jamba2-3B's published widths behind the engine at the cell's
    serving sizes (256 slots, 4,096 positions, bf16 pages, the whole
    vocabulary tied to the head), one state-space layer on either side
    of one attention layer: only shapes matter to a compile, and these
    are the ones the chip's compiler could refuse (slabs of ``[256, 16,
    5120]`` and ``[256, 15360]``, 20 stacked query rows on ONE K/V head,
    logits of ``[256, 65536]``); 2,049 pages make the pools 16 MB."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine, MambaLM

    model = MambaLM(
        vocab_size=65536, d_model=2560,
        layer_kinds=("recurrent", "attention", "recurrent"), d_inner=5120,
        d_state=16, d_conv=4, dt_rank=160, num_heads=20, num_kv_heads=1,
        head_dim=128, ffn_dim=8192)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(
        slots=256, max_seq_len=4096, num_pages=256 * 8 + 1,
        use_pallas="always", cache_dtype="bfloat16"))


@pytest.mark.parametrize("program", ["step", "prefill_512"])
def test_jamba2_width_programs_compile(one_chip, program):
    """The joint step at 256 slots (the state kernel and the
    convolution's over the slabs IN PLACE: no copy of a slab anywhere,
    both by the names the benchmark's pattern finds; the paged kernel at
    20 rows of the one head, reading K and V out of the ONE joint pool
    under the name the accepted metric finds; the head over the embedding
    where it lies)
    and the 512-row whole-prompt prefill (one call of the scan kernel a
    recurrent layer, eight tiles of 64; the attention in plain blocks).
    The slabs' device layout holds no padding: the memory analysis reads
    the logical bytes."""
    from paddle_tpu.ops import pallas_ssm as ps

    eng = _mamba_engine()
    state = [tuple(eng._scope.get_var(n).shape) for n in eng._state_vars]
    # ONE pool: a position's keys and values side by side in a row
    assert eng._cache.config.joint
    assert state == [(1, 2049, 16, 256)] \
        + [(256, 16, 5120), (256, 15360)] * 2
    slabs = 2 * 256 * (16 * 5120 + 15360) * 4
    pools = 2 * 2049 * 16 * 128 * 2
    if program == "step":
        compiled = eng.lower_step(sharding=one_chip).compile()
        text = compiled.as_text()
        entry = _computation(text, "ENTRY ")
        step = _metric_pattern("ssm_step_ms.serve")
        assert _metric_pattern("ssm_step_roofline").pattern == step.pattern
        calls = [ln.strip() for ln in entry if step.search(ln.strip())]
        assert sorted(c.split(".")[0] for c in calls) == [
            "%" + ps.CONV_KERNEL] * 2 + ["%" + ps.STEP_KERNEL] * 2
        assert all("f32[256,16,5120]" in c or "f32[256,15360]" in c
                   for c in calls)
        paged = [ln for ln in entry if _metric_pattern(
            "full_attn_ms_per_step.serve").search(ln.strip())]
        assert len(paged) == 1 and text.count("tpu_custom_call") == 5
        # no slab is copied: the kernels' results ARE the program's
        assert not re.search(
            r"= f32\[256,(16,5120|15360)\][^ ]* copy\(", text)
        proj = _metric_pattern("ssm_proj_ms_per_step.serve")
        assert {m for ln in entry for m in proj.findall(ln)} >= {"in", "out"}
        ffn = _metric_pattern("dense_ffn_ms_per_step.serve")
        assert {m for ln in entry for m in ffn.findall(ln)} == {
            "gate", "up", "down"}
        # the tied head contracts the embedding where it lies
        assert "bf16[2560,65536]" not in text
    else:
        assert eng._prefill_walks(512) == [(1, None, ("blocks", 512, 512))]
        assert eng.model.prefill_chunks_per_call(512) == 8
        compiled = eng.lower_prefill(512, sharding=one_chip).compile()
        text = compiled.as_text()
        scan = _metric_pattern("ssm_prefill_ms.serve")
        assert _metric_pattern("ssm_prefill_roofline").pattern \
            == scan.pattern
        calls = [ln.strip() for ln in text.splitlines()
                 if scan.search(ln.strip())]
        assert len(calls) == 2 == text.count("tpu_custom_call")
        assert all(c.count("f32[512,5120]") >= 3
                   and "f32[1,16,5120]" in c for c in calls)
        assert f"%{ps.STEP_KERNEL}" not in text
    mem = compiled.memory_analysis()
    # arguments: the weights (1.05 GB of three layers and the embedding),
    # the pools and the slabs at their LOGICAL bytes, a packed row
    weights = sum(x.size * x.dtype.itemsize for x in
                  jax.tree_util.tree_leaves(eng.weights))
    assert weights + pools + slabs <= mem.argument_size_in_bytes \
        < weights + pools + slabs + (4 << 20)
    assert mem.alias_size_in_bytes == pools + slabs
    assert mem.temp_size_in_bytes < 1 << 30


def _indexed_engine(layers=2, **cfg):
    """Keye-VL-2.0-30B-A3B's language block behind the engine at the
    cell's serving sizes (16 slots, tables of 1,536 pages and the spare:
    24,576 positions, bf16 pages and index keys), two layers, 16 held
    experts of the published width and a short vocabulary: only shapes
    matter to a compile, and these are the ones the chip's compiler
    could refuse (a third pool of 64-lane keys at 128 lanes, every
    slot's 24,576 keys scored and the 2,048 best of them taken, rows of
    512 lanes gathered by position, the 8,192-row prompt's flash kernel
    under a selection a pair).  The pools hold half the cell's pages: a
    compile reads the table's width, not the pool's depth, but a pool
    has to stay too large for the compiler to move it through fast
    memory whole (it does that with an index pool of 78 MB; the cell's
    is 805 MB)."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.indexed_moe_lm import IndexedMoELM

    model = IndexedMoELM(
        vocab_size=1536, d_model=2048, num_layers=layers, num_heads=32,
        num_kv_heads=4, head_dim=128, index_heads=16, index_dim=64,
        index_topk=2048, num_experts=128, top_k=8, held_experts=range(16),
        expert_dim=768)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    return DecodeEngine(model, weights, DecodeConfig(**dict(dict(
        slots=16, max_seq_len=24576, num_pages=16 * 768 + 1,
        use_pallas="always", cache_dtype="bfloat16"), **cfg)))


@pytest.mark.parametrize("program", ["step", "prefill_8192"])
def test_keye_vl2_width_programs_compile(one_chip, program):
    """The joint step (16 slots x 1,536 pages: every slot's 24,576 index
    keys gathered by its table and scored, the 2,048 best positions
    taken, their K and V rows gathered from the pools by flat row; no
    kernel but the experts' hit form: the three pools go in and come out
    in place and no layer of a pool is copied) and the 8,192-row
    whole-prompt prefill (the flash kernel a layer under the selection, an int8 plane of 8,192 x 8,192
    its operand; the two grouped-expert kernels; the head over the one
    row that is read)."""
    from paddle_tpu.ops import pallas_moe_grouped as grouped

    eng = _indexed_engine()
    cc = eng._cache.config
    assert (cc.num_heads, cc.row_lanes, cc.v_row_lanes, cc.pages_per_slot,
            cc.lane_dense) == (4, 512, 512, 1536, True)
    assert eng._state_vars == (
        "__decode_k_pages__", "__decode_v_pages__",
        "__decode_index_pages__")
    pools = [(2, 12289, 16, 512), (2, 12289, 16, 512),
             (2, 12289, 16, 128)]
    if program == "step":
        compiled = eng.lower_step(sharding=one_chip).compile()
        text = compiled.as_text()
        # the one kernel of a step is the experts' hit form (16 rows
        # over 16 held of 128, top-8: 64 % of them hit), a layer
        from paddle_tpu.ops import pallas_moe_hit as hit

        calls = [ln for ln in text.splitlines() if re.match(
            r"\s*%" + hit.HIT_KERNEL_NAME + r"[.\d]* = ", ln)]
        assert len(calls) == text.count("tpu_custom_call") == 2
        # each slot's keys as the pool lays them out, its scores, and the
        # rows of the positions it selected
        assert "bf16[16,1536,16,128]" in text or "bf16[16,24576,128]" in text
        assert "f32[16,24576]" in text and "bf16[16,2048,512]" in text
    else:
        assert eng._prefill_walks(8192) == [(2, None, ("flash", 256, 1024))]
        compiled = eng.lower_prefill(8192, sharding=one_chip).compile()
        text = compiled.as_text()
        assert grouped.GATE_UP_KERNEL_NAME in text
        assert grouped.DOWN_KERNEL_NAME in text
        from paddle_tpu.ops.pallas_prompt_attention import KERNEL_NAME

        calls = [line.strip() for line in text.splitlines()
                 if re.match(r"\s*%" + KERNEL_NAME + r"[.\d]* = ", line)]
        assert len(calls) == 2
        assert all("s8[8192,8192]" in c and "bf16[4,8192,128]" in c
                   for c in calls), calls
        # the float32 planes over the bucket are a block's index products
        # (512 rows x 16 heads) and scores; no plane of attention scores
        planes = set(re.findall(r"f32\[(?:\d+,){2,}8192\]", text))
        assert planes <= {"f32[512,16,8192]", "f32[12,512,8192]"}, planes
        # the head runs over the one row that is read, not the bucket
        assert "f32[1,1536]" in text and "f32[8192,1536]" not in text
    _assert_pools_stay_put(compiled, pools)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
