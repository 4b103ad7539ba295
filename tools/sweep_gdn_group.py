"""Sweep the group of the gated delta rule's chunk form at Olmo-Hybrid's shapes.

ONE recurrent layer's whole-prompt prefill loop ALONE, on the chip, as
`serving/decode.py`'s prefill `recur` runs it (a `fori_loop` whose carry
is the slot's state and whose iteration is one call of
`GatedDeltaLM._gdn_chunk`), at the cell's shapes (30 heads, keys of 96 on
values of 192, 3,600 real tokens in the 4,096 bucket) with G chunks of 64
tokens a call for each G in turn, the state's pass through a call's
chunks unrolled (the model's `_state_pass`) and rolled (a `fori_loop` of
this file's): ms a layer, the compiled program's temporaries, seconds to
trace and lower and to compile.  The numbers behind `GROUP_BYTES` and
the unrolled pass in `serving/gated_delta_lm.py` (PR 53).

    chiprun -- python -m tools.sweep_gdn_group [--groups 1,8] [--ops 8]

Prints one JSON line a (G, pass), ``rule`` true on the G the model's
`prefill_chunks_per_call` gives the bucket, and writes them all to
``chiprun_out/sweep_gdn_group.json``; ``--ops G`` also traces a few runs
of that group (unrolled) and lists the device's operations by their
time.  A TPU or nothing: a time from the CPU is not a time (``--tiny``
rehearses the walk on the CPU at small sizes).
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import math
import os
import re
import tempfile
import time

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.serving import GatedDeltaLM
from paddle_tpu.serving import gated_delta_lm as gdl

# the cell's recurrent layer: heads, d_k, d_v, conv taps, bucket, tokens
CELL = dict(lin_heads=30, lin_key_dim=96, lin_value_dim=192, conv_kernel=4,
            bucket=4096, tokens=3600)
TINY = dict(lin_heads=3, lin_key_dim=6, lin_value_dim=12, conv_kernel=4,
            bucket=512, tokens=420)
GROUPS = (1, 2, 4, 8, 16, 64)
# where the benchmark keeps its pattern for the loops it times
METRIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "layer_metrics", "gdn_prefill_ms.serve.json")


def rolled_pass(t_v, t_k, left_t, decay, s):
    """`gated_delta_lm._state_pass` as a loop the compiler keeps rolled:
    the form the model did NOT take, kept here so that the table can be
    read again.  Its carry holds no `[1, H, dk, dv]` (a group of ONE
    chunk takes the model's own lines)."""
    if t_v.shape[0] == 1:
        return unrolled_pass(t_v, t_k, left_t, decay, s)

    def body(i, carry):
        s, starts, wrote = carry
        u = t_v[i] - gdl._exact(t_k[i], s)
        return (decay[i] * s + gdl._exact(left_t[i], u),
                starts.at[i].set(s), wrote.at[i].set(u))

    s, starts, wrote = lax.fori_loop(
        0, t_v.shape[0], body,
        (s, jnp.zeros(t_v.shape[:1] + s.shape, s.dtype),
         jnp.zeros_like(t_v)))
    return starts, wrote, s


unrolled_pass = gdl._state_pass
PASSES = {"unrolled": unrolled_pass, "rolled": rolled_pass}


def make_case(sizes, seed):
    """(model, the layer's weights the chunk form reads, the bucket's
    projections, the prompt's length)."""
    model = GatedDeltaLM(
        vocab_size=8, d_model=8, layer_kinds=("recurrent",), num_heads=1,
        head_dim=8, ffn_dim=8, **{k: v for k, v in sizes.items()
                                  if k not in ("bucket", "tokens")})
    nh, t = model.lin_heads, sizes["bucket"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    lw = {"gdn_conv": jax.random.normal(
              ks[0], (model.conv_kernel, model.lin_width), jnp.float32)
          / math.sqrt(model.conv_kernel),
          "gdn_a_log": jnp.log(jax.random.uniform(
              ks[1], (nh,), jnp.float32, 1.0, 16.0)),
          "gdn_dt_bias": jax.random.uniform(ks[2], (nh,), jnp.float32,
                                            -6.0, -2.0)}
    rows = {"u": jax.random.normal(ks[3], (t, model.lin_width), jnp.float32),
            "a": jax.random.normal(ks[4], (t, nh), jnp.float32),
            "b": jax.random.normal(ks[5], (t, nh), jnp.float32)}
    return model, lw, rows, jnp.int32(sizes["tokens"])


def layer_loop(model, group):
    """The engine's prefill ``recur`` of one layer, ``group`` chunks a
    call: (lw, rows, length) -> (outputs, the state after the prompt)."""
    chunk = group * gdl.CHUNK

    def run(lw, rows, length):
        t_pad = rows["u"].shape[0]
        t_run = -(-t_pad // chunk) * chunk
        rows = {n: jnp.pad(v, ((0, t_run - t_pad), (0, 0)))
                for n, v in rows.items()}
        state0 = {n: jnp.zeros((1,) + tuple(shape), dtype)
                  for n, (shape, dtype) in model.recurrent_state.items()}

        def scan_step(i, carry):
            st, outs = carry
            o, new = model._gdn_chunk(
                lw, {n: lax.dynamic_slice_in_dim(v, i * chunk, chunk)
                     for n, v in rows.items()},
                jnp.minimum(length - i * chunk, chunk), st)
            return new, lax.dynamic_update_slice_in_dim(
                outs, o, i * chunk, axis=0)

        st, outs = lax.fori_loop(
            0, -(-length // chunk), scan_step,
            (state0, jnp.zeros((t_run, model.lin_heads,
                                model.lin_value_dim), jnp.float32)))
        return outs[:t_pad], st

    return jax.jit(run)


def measure(model, case, group, which, reps):
    """One (G, pass): the line's numbers and the program's results."""
    gdl._state_pass = PASSES[which]
    try:
        t0 = time.perf_counter()
        lowered = layer_loop(model, group).lower(*case)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    finally:
        gdl._state_pass = unrolled_pass
    with open(METRIC) as f:     # the loops `gdn_prefill_ms.serve` would match
        loop = re.compile(json.load(f)["params"]["pattern"])
    whiles = [ln.strip() for ln in compiled.as_text().splitlines()
              if " while(" in ln]
    out = compiled(*case)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0r = time.perf_counter()
        jax.block_until_ready(compiled(*case))
        times.append(time.perf_counter() - t0r)
    times.sort()
    mem = compiled.memory_analysis()
    return dict(
        ms_a_layer=times[len(times) // 2] * 1e3, ms_best=times[0] * 1e3,
        temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        trace_lower_s=t1 - t0, compile_s=t2 - t1,
        whiles=len(whiles),
        whiles_matched=sum(bool(loop.search(w)) for w in whiles)), \
        compiled, out


def device_ops(compiled, case, runs):
    """[(instruction, calls a run, us a run)] of ``runs`` traced runs,
    the device's "XLA Ops" line, longest first."""
    out_dir = tempfile.mkdtemp(prefix="gdn_trace_")
    jax.profiler.start_trace(out_dir)
    for _ in range(runs):
        jax.block_until_ready(compiled(*case))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    total, calls = collections.Counter(), collections.Counter()
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name[:140]
                total[name] += ev.duration_ns / 1e3 / runs
                calls[name] += 1.0 / runs
    return [(n, round(calls[n], 1), round(us, 1))
            for n, us in total.most_common()]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", default=",".join(map(str, GROUPS)))
    ap.add_argument("--passes", default="unrolled,rolled")
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--seed", type=int, default=53)
    ap.add_argument("--ops", type=int, default=0,
                    help="trace this group's runs and list the device's "
                         "operations")
    ap.add_argument("--out", default="chiprun_out/sweep_gdn_group.json")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    if not a.tiny and jax.default_backend() != "tpu":
        raise SystemExit("a TPU or nothing: a time from the CPU is no time")
    sizes = TINY if a.tiny else CELL
    model, *case = make_case(sizes, a.seed)
    ruled = model.prefill_chunks_per_call(sizes["bucket"])
    lines, want, traced = [], None, None
    for group in map(int, a.groups.split(",")):
        if group * gdl.CHUNK > sizes["bucket"]:
            continue
        for which in a.passes.split(","):
            if group == 1 and which != "unrolled":
                continue            # one chunk a call has no pass to roll
            line = dict(group=group, state_pass=which, rule=group == ruled,
                        device=jax.devices()[0].device_kind, **sizes)
            try:
                numbers, compiled, out = measure(model, case, group, which,
                                                 1 if a.tiny else a.reps)
                line.update(numbers)
                # the real rows' outputs and the state after them
                out = (out[0][:sizes["tokens"]], out[1])
                if want is None:
                    want = out      # the first form read: G = 1 by default
                line["max_err"] = max(
                    float(jnp.abs(x - y).max()) for x, y in zip(
                        jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(want)))
                if group == a.ops and which == "unrolled" and not a.tiny:
                    traced = device_ops(compiled, case, 3)
            except Exception as e:  # a group the chip's compiler refuses
                line["error"] = f"{type(e).__name__}: {e}"[:400]
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(dict(lines=lines, ops=traced), f, indent=1)
    if traced:
        print(f"# operations of G = {a.ops}: calls and us a run")
        for name, n, us in traced[:70]:
            print(f"{us:9.1f} {n:6.1f}  {name}")


if __name__ == "__main__":
    main()
