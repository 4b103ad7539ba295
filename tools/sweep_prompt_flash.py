"""Sweep the prompt's flash kernel's tiles ALONE at the cells' prefill shapes.

`ops/pallas_prompt_attention.py` `prompt_flash_attention` on the chip,
with no engine around it, at a cell's (bucket, query heads, K/V heads, K
and V lanes a head, window) and each of its prompts' lengths, under each
pair of (query rows, keys) a block in turn, beside the plain blocks of
`grouped_causal_attention` (``blocks``: the form a shape keeps where
`flash_rule` refuses it, and the baseline row).  Where a head's V lanes
are not whole lane tiles a second form of the kernel's V side runs
beside the served one: ``flash_v128`` pads V to 128 lanes before the
call and slices the result after it (the kernel at whole tiles, zero
lanes riding along), ``flash`` is the call as `flash_rule` takes it (an
accumulator and stores of the head's own lanes).  The numbers behind the
rule's tiles at heads of 64 lanes (PR 60).

    chiprun -- python -m tools.sweep_prompt_flash [--shapes lfm2,solar]
        [--rows 128,256,512] [--keys 256,512,1024] [--forms blocks,flash]
        [--lengths 1100,1300]

A reading is the device's own time a call from a traced loop (the
kernel's event and, beside it, everything else the call runs: the
head-major copies of K and V, the query's scaling, a pad and a slice),
with the keys the walk covers and the share of them that are live.
Prints one JSON line a (shape, length, form, tiles), ``rule`` true on the
tiles the rule gives that shape, and writes them all to
``chiprun_out/sweep_prompt_flash.json``.  Every form's rows under the
length are compared with the plain form's (``rms_err``).  A TPU or
nothing: a time from the CPU is not a time (``--tiny`` rehearses the walk
in the interpreter at small sizes).
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import pallas_decode_attention as pda
from paddle_tpu.ops import pallas_prompt_attention as ppa
from tools.sweep_moe_layout import device_ops

# a cell's whole-prompt prefill: bucket, query heads, K/V heads, K lanes
# and V lanes a head, window, sinks, the prompts' lengths
SHAPES = {
    "lfm2": (2048, 32, 8, 64, 64, None, False, (1024, 1536, 2048)),
    "solar": (1024, 64, 8, 128, 128, None, False, (700, 1024)),
    "mimo_global": (2048, 64, 4, 192, 128, None, False, (1536, 2048)),
    "mimo_window": (2048, 64, 8, 192, 128, 128, True, (1536, 2048)),
    "command_global": (4096, 128, 8, 128, 128, None, False, (3600, 4096)),
    "olmo_hybrid": (4096, 30, 30, 128, 128, None, False, (3600, 4096)),
}
ROWS = (128, 256, 512)
KEYS = (256, 512, 1024)
FORMS = ("blocks", "flash", "flash_v128")
# a rehearsal's sizes (--tiny: the interpreter on the CPU, no time)
TINY = {
    "heads_of_64": (256, 8, 2, 64, 64, None, False, (130, 256)),
    "window_64": (256, 4, 2, 64, 64, 40, True, (200,)),
}


def make_case(shape, seed):
    """q in float32 as a projection leaves it, K and V in the pages'
    bfloat16, seeded."""
    t, h, hkv, d, dv, _, sinks, _ = shape
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(kq, (t, h, d), jnp.float32),
            jax.random.normal(kk, (t, hkv, d), jnp.bfloat16),
            jax.random.normal(kv, (t, hkv, dv), jnp.bfloat16),
            jax.random.normal(ks, (h,), jnp.float32) if sinks else None)


def live_keys(n, window):
    """Keys a head the prompt's ``n`` rows attend: the triangle, or the
    window's band of it."""
    if window is None or window >= n:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def form_call(form, shape, tiles, interpret):
    """``fn(q, k, v, sinks, length) -> [T, H, Dv]`` of one form."""
    window = shape[5]
    scale = float(shape[3]) ** -0.5

    def blocks(q, k, v, sinks, length):
        return pda.grouped_causal_attention(
            q, k, v, window=window, sinks=sinks, use_pallas="never")

    def flash(q, k, v, sinks, length):
        return ppa.prompt_flash_attention(
            q, k, v, length, sinks, sm_scale=scale, window=window,
            tiles=tiles, interpret=interpret)

    def flash_v128(q, k, v, sinks, length):
        dv = v.shape[-1]
        wide = jnp.pad(v, ((0, 0), (0, 0), (0, -dv % 128)))
        return flash(q, k, wide, sinks, length)[..., :dv]

    return {"blocks": blocks, "flash": flash, "flash_v128": flash_v128}[form]


def device_time(fn, args, runs):
    """(us a run of the device's time, us a run inside the kernel's own
    events, [(instruction, us a run)] longest first) of ``runs`` traced
    calls (`sweep_moe_layout.device_ops`: an event's own time less the
    events nested in it)."""
    us, ops = device_ops(fn, args, runs)
    kernel = sum(t for name, _, t in ops
                 if name.startswith("%" + ppa.KERNEL_NAME))
    return us, kernel, [(name[:120], t) for name, _, t in ops]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="lfm2")
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--keys", default=",".join(map(str, KEYS)))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--lengths", default=None,
                    help="prompt lengths in place of the shape's own")
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--seed", type=int, default=60)
    ap.add_argument("--ops", type=int, default=4,
                    help="device operations listed a line")
    ap.add_argument("--out", default="chiprun_out/sweep_prompt_flash.json")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    if not a.tiny and jax.default_backend() != "tpu":
        raise SystemExit("a TPU or nothing: a time from the CPU is no time")
    shapes = TINY if a.tiny else SHAPES
    interpret = jax.default_backend() != "tpu"
    print("shape length form rows keys rule ms_a_call kernel_ms "
          "live_over_walked", flush=True)
    lines = []
    for name in a.shapes.split(","):
        shape = shapes[name]
        t, h, hkv, d, dv, window, _, lengths = shape
        if a.lengths:
            lengths = tuple(map(int, a.lengths.split(",")))
        q, k, v, sinks = make_case(shape, a.seed)
        ruled = ppa.flash_rule(t, h, hkv, d, dv, window)
        want = np.asarray(form_call("blocks", shape, None, interpret)(
            q, k, v, sinks, t))
        pairs = [(bq, bk) for bq in map(int, a.rows.split(","))
                 for bk in map(int, a.keys.split(","))
                 if t % bq == 0 and t % bk == 0
                 and (window is None or bq <= bk)]
        if ruled and ruled not in pairs:
            pairs.append(ruled)
        for form in a.forms.split(","):
            if form == "flash_v128" and dv % 128 == 0:
                continue                # whole tiles already: one form
            for tiles in [None] if form == "blocks" else pairs:
                fn = jax.jit(form_call(form, shape, tiles, interpret))
                for n in lengths:
                    if form == "blocks" and n != lengths[-1]:
                        continue        # it attends the bucket whatever n
                    walk = ("blocks",) + pda.prefill_key_span(t, h, window) \
                        if form == "blocks" else ("flash",) + tiles
                    walked = pda.prefill_keys_walked(t, n, walk, window)
                    line = dict(
                        shape=name, length=n, form=form,
                        rows=walk[1], keys=walk[2],
                        rule=form == "flash" and tiles == ruled,
                        keys_walked=walked,
                        live_over_walked=round(
                            live_keys(n, window) / walked, 4),
                        device=jax.devices()[0].device_kind)
                    try:
                        args = (q, k, v, sinks, jnp.int32(n))
                        got = np.asarray(fn(*args))[:n]
                        line["rms_err"] = float(np.sqrt(
                            ((got - want[:n]) ** 2).mean()
                            / (want[:n] ** 2).mean()))
                        if not a.tiny:
                            us, kernel_us, ops = device_time(
                                fn, args, a.runs)
                            line.update(ms_a_call=round(us / 1e3, 4),
                                        kernel_ms=round(kernel_us / 1e3, 4),
                                        ops=ops[:a.ops])
                    except Exception as e:  # tiles the compiler refuses
                        line["error"] = f"{type(e).__name__}: {e}"[:400]
                    print(json.dumps(line), flush=True)
                    lines.append(line)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
