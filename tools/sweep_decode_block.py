"""Sweep the paged decode kernel's block at the serving cells' real shapes.

The kernel of `ops/pallas_decode_attention.py` ALONE, in a loop on the
chip, at each cell's slots, table width, page and live lengths (drawn
from the cell's traffic), with `pages_per_block` replaced by each block
in turn; beside every reading a COPY-ONLY kernel that starts and waits
for the same pages in the same blocks and computes nothing, so a row
says how much of a call is the rows' bytes and how much the block's
arithmetic.  The numbers behind the rule in `pages_per_block` (PR 51).

    chiprun -- python -m tools.sweep_decode_block [--shapes a,b] [--out f]

Prints one JSON line a (shape, block), ``rule`` true on the block the
rule gives that shape, and writes them all to
``chiprun_out/sweep_decode_block.json``.  A shape of ONE K/V head of whole
lane tiles (Jamba2-3B's; `serving/kv_cache.py` then keeps K and V of a
position in one pool row) is also timed from that joint pool: the
copy-only kernel with ONE copy a page (``joint_copies_ms``: the same
bytes in half the descriptors) and the call itself (``joint_kernel_ms``,
its output held bitwise to the two-pool call's).  A TPU or nothing: a time from
the CPU is not a time (``--tiny`` rehearses the walk in the interpreter
at small sizes and compares each block's output with the reference).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.serving.kv_cache import CacheConfig

PAGE = 16
PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "peaks.json")

# name: query heads, K/V heads, K lanes a head, V lanes a head, slots,
# table entries a slot, (window, sinks) or None, live lengths
SHAPES = {
    "mimo_global": (64, 4, 192, 128, 128, 256, None, (800, 3500)),
    "mimo_window": (64, 8, 192, 128, 128, 9, 128, (800, 3500)),
    "command_window": (128, 8, 128, 128, 48, 257, 4096, (3072, 6100)),
    "command_global": (128, 8, 128, 128, 48, 384, None, (3072, 6100)),
    "solar": (64, 8, 128, 128, 128, 128, None, (200, 1500)),
    "ouro": (16, 16, 128, 128, 16, 20, None, (64, 320)),
    "olmo_hybrid": (30, 30, 128, 128, 32, 352, None, (3072, 5600)),
    "jamba2": (20, 1, 128, 128, 256, 256, None, (330, 3584)),
}
BLOCKS = (128, 256, 512, 768, 1024)
# a rehearsal's sizes (--tiny: the interpreter on the CPU, no time)
TINY = {
    "mimo_global": (8, 2, 192, 128, 3, 40, None, (100, 600)),
    "mimo_window": (8, 2, 192, 128, 3, 9, 128, (100, 600)),
    "command_window": (4, 2, 128, 128, 2, 41, 640, (500, 900)),
    "jamba2": (4, 1, 128, 128, 3, 40, None, (100, 600)),
}


def make_case(shape, seed):
    hq, h, d, dv, slots, pps, window, (lo, hi) = shape
    rng = np.random.RandomState(seed)
    lengths = rng.randint(lo, hi + 1, size=slots).astype(np.int32)
    held = pps if window else -(-hi // PAGE)    # pages a slot owns
    n_pages = slots * held + 1
    table = np.zeros((slots, pps), np.int32)    # entry 0: the trash page
    table[:, :held] = 1 + rng.permutation(slots * held).reshape(slots, held)
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    layers = 2
    k_pages = jax.random.normal(kk, (layers, n_pages, PAGE, h * d),
                                jnp.bfloat16)
    v_pages = jax.random.normal(kv, (layers, n_pages, PAGE, h * dv),
                                jnp.bfloat16)
    q = jax.random.normal(kq, (slots, hq, d), jnp.float32)
    sinks = jnp.zeros((hq,), jnp.float32) if window == 128 else None
    case = dict(q=q, k_pages=k_pages, v_pages=v_pages,
                table=jnp.asarray(table), lengths=jnp.asarray(lengths),
                sinks=sinks, layers=layers)
    if joint_shape(shape):
        # the same K and V, a position's side by side in one pool row
        case["kv_pages"] = jnp.concatenate([k_pages, v_pages], axis=-1)
    return case


def joint_shape(shape):
    """Whether `serving/kv_cache.py` keeps this shape's K and V in one
    pool row (its own rule, asked of a cache of these widths)."""
    _, h, d, dv, slots, pps, *_ = shape
    return CacheConfig(1, h, d, slots, pps * PAGE, PAGE, dtype="bfloat16",
                       v_head_dim=dv).joint


def attended_bytes(shape, lengths):
    """Bytes of K and V rows a call must read: each slot's attended
    positions (its last ``window``), at the pools' row widths."""
    _, h, d, dv, _, _, window, _ = shape
    pos = np.minimum(lengths, window) if window else lengths
    return int(pos.sum()) * h * (d + dv) * 2


def _copy_kernel(layer_ref, pt_ref, len_ref, lo_ref, *rest, page, pps, ppb,
                 n_slots, ring, n_pools):
    """The kernel's walk and copies with no arithmetic: same blocks, the
    next in flight while this one is waited for, a whole block's copies
    started unrolled and waited for once a pool.  ``rest``: the pools in
    HBM (K and V, or the one joint pool), the output, a buffer a pool,
    the semaphores and the buffer's turn."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_idx = pl.program_id(0)
    layer = layer_ref[0]
    block = ppb * page
    hbm, (o_ref, *bufs, sem, cur) = rest[:n_pools], rest[n_pools:]
    pools = tuple(zip(hbm, bufs))

    def first_block(s):
        return lo_ref[s] // block

    def dma(s, b, buf, start):
        first = b * ppb
        lo = jnp.maximum(first, lo_ref[s] // page)
        hi = jnp.minimum(first + ppb, pl.cdiv(len_ref[s], page))

        def _page(entry, carry):
            e = entry % pps if ring else entry
            pid = pt_ref[s * pps + e] if start else 0
            for hbm, vmem in pools:
                c = pltpu.make_async_copy(
                    hbm.at[layer, pid], vmem.at[buf, entry - first],
                    sem.at[buf])
                c.start() if start else c.wait()
            return carry

        whole = hi - lo == ppb

        @pl.when(whole)
        def _():
            if start:
                for entry in range(ppb):
                    _page(first + entry, 0)
            else:
                for hbm, vmem in pools:
                    pltpu.make_async_copy(
                        hbm.at[layer, pl.ds(0, ppb)], vmem.at[buf],
                        sem.at[buf]).wait()

        @pl.when(jnp.logical_not(whole))
        def _():
            lax.fori_loop(lo, hi, _page, 0)

    @pl.when(s_idx == 0)
    def _():
        cur[0] = 0
        dma(0, first_block(0), 0, True)

    n_blocks = jnp.maximum(pl.cdiv(len_ref[s_idx], block), 1)

    def _block(b, carry):
        buf = cur[0]
        last = b + 1 == n_blocks
        nxt_s = jnp.where(last, s_idx + 1, s_idx)
        nxt_b = jnp.where(last, first_block(
            jnp.minimum(s_idx + 1, n_slots - 1)), b + 1)
        pl.when(nxt_s < n_slots)(lambda: dma(nxt_s, nxt_b, 1 - buf, True))
        dma(s_idx, b, buf, False)
        cur[0] = 1 - buf
        return carry

    lax.fori_loop(first_block(s_idx), n_blocks, _block, 0)
    o_ref[...] = jnp.zeros_like(o_ref)


def copy_only(pools, layer, table, lengths, window, ppb, interpret=False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_slots, pps = table.shape
    page = pools[0].shape[2]
    lo = jnp.maximum(lengths - window, 0) if window \
        else jnp.zeros_like(lengths)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(n_slots,), in_specs=[hbm] * len(pools),
        out_specs=pl.BlockSpec((1, 8, 128), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, pool.shape[3]), pool.dtype)
            for pool in pools] + [
            pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        functools.partial(_copy_kernel, page=page, pps=pps, ppb=ppb,
                          n_slots=n_slots, ring=window is not None,
                          n_pools=len(pools)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, 8, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_copies_only", interpret=interpret,
    )(layer.reshape(1), table.reshape(-1), lengths, lo, *pools)


def time_loop(fn, case, iters, reps=3, pools=("k_pages", "v_pages")):
    """ms a call: ``iters`` calls inside one program, the layer an
    operand that changes from call to call.  ``pools``: the case's pools
    the call reads (``fn(q, pools, layer, table, lengths)``)."""
    def run(q, table, lengths, *pools):
        def body(i, acc):
            return acc + fn(q, pools, jnp.int32(i) % case["layers"],
                            table, lengths).astype(jnp.float32).sum()
        return lax.fori_loop(0, iters, body, jnp.float32(0))

    args = (case["q"], case["table"], case["lengths"],
            *(case[name] for name in pools))
    run = jax.jit(run)
    t0 = time.perf_counter()
    run(*args).block_until_ready()
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3, compile_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--blocks", default=",".join(map(str, BLOCKS)))
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("--out", default="chiprun_out/sweep_decode_block.json")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    if not a.tiny and jax.default_backend() != "tpu":
        raise SystemExit("a TPU or nothing: a time from the CPU is no time")
    shapes = {**SHAPES, **TINY} if a.tiny else SHAPES
    interpret = jax.default_backend() != "tpu"
    with open(PEAKS) as f:      # the rows' bytes at the v5e's HBM rate
        hbm_gbps = json.load(f)["TPU v5 lite"]["hbm_gbps"]
    rule = pda.pages_per_block
    lines = []
    try:
        for name in a.shapes.split(","):
            case = make_case(shapes[name], a.seed)
            hq, h, d, dv, _, pps, window, _ = shapes[name]
            need = attended_bytes(shapes[name], np.asarray(case["lengths"]))
            floor_ms = need / (hbm_gbps * 1e9) * 1e3
            # the block the rule itself gives this shape
            ruled = PAGE * rule(PAGE, pps, h * d, jnp.bfloat16, h * dv, h,
                                hq // h)
            for block in map(int, a.blocks.split(",")):
                both = 2 * block * h * (d + dv) * 2
                if (block > 128 and block > pps * PAGE) or both > (8 << 20):
                    continue
                ppb = min(block // PAGE, pps)
                pda.pages_per_block = lambda *_, **__: ppb
                pda._chunk_call.clear_cache()

                def kernel(q, pools, layer, table, lengths):
                    # one pool: the joint row, the values after the keys
                    kp, vp = pools if len(pools) == 2 else (pools[0], None)
                    return pda.paged_decode_attention(
                        q, kp, vp, table, lengths, layer=layer,
                        use_pallas="always", interpret=interpret,
                        window=window, sinks=case["sinks"],
                        **({} if vp is not None else dict(
                            value_lanes=dv, value_offset=d)))

                def copies(q, pools, layer, table, lengths):
                    return copy_only(pools, layer, table, lengths, window,
                                     ppb, interpret)

                line = dict(shape=name, block=block, rule=block == ruled,
                            buffers_bytes=both, attended_bytes=need,
                            bytes_floor_ms=round(floor_ms, 4))
                try:
                    two = (case["k_pages"], case["v_pages"])
                    rest = (case["table"], case["lengths"])
                    if a.tiny:
                        want = pda.paged_decode_attention(
                            case["q"], *two, *rest, layer=1,
                            use_pallas="never", window=window,
                            sinks=case["sinks"])
                        line["max_err"] = float(jnp.abs(
                            kernel(case["q"], two, 1, *rest) - want).max())
                    reps = 1 if a.tiny else 3
                    line["kernel_ms"], line["kernel_compile_s"] = time_loop(
                        kernel, case, a.iters, reps)
                    line["copies_ms"], _ = time_loop(copies, case, a.iters,
                                                     reps)
                    if "kv_pages" in case:
                        one = ("kv_pages",)
                        line["joint_copies_ms"], _ = time_loop(
                            copies, case, a.iters, reps, one)
                        line["joint_bitwise"] = bool(jnp.array_equal(
                            kernel(case["q"], (case["kv_pages"],), 1, *rest),
                            kernel(case["q"], two, 1, *rest)))
                        line["joint_kernel_ms"], _ = time_loop(
                            kernel, case, a.iters, reps, one)
                except Exception as e:  # a block the chip's compiler refuses
                    line["error"] = f"{type(e).__name__}: {e}"[:400]
                print(json.dumps(line), flush=True)
                lines.append(line)
            del case
    finally:
        pda.pages_per_block = rule
        pda._chunk_call.clear_cache()
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
