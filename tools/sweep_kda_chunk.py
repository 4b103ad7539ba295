"""Sweep the channel-decay delta rule's prompt forms at the two cells' shapes.

The rule ALONE, on the chip, as `serving/decode.py`'s prefill `recur`
runs it (a `fori_loop` whose carry is the slot's state and whose
iteration is one call of the form over `tokens a call` consecutive
tokens), at Kimi-Linear's shape (32 heads of 128 x 128, 3,600 real
tokens in the 4,096 bucket) and Solar's (64 heads, 200 of 256, 450 of
512 and 900 of 1,024).  Three forms:

  token  `ops/pallas_kda_update.py` at T > 1, the prompt's form until PR
         58: the state in VMEM, one token after another on the vector
         unit (no model calls it so any more; the table can be read
         again)
  chunk  `ops/pallas_kda_chunk.py` as served: the WY form a chunk of 64
         tokens, products on the matrix unit, state and chunk in VMEM;
         `--subs` its sub-chunk, `--heads` its heads a grid step,
         `--tokens` the group (tokens a call)
  xla    the same algebra as XLA operations over a call's chunks at once
         (`GatedDeltaLM._gdn_chunk` with a decay a channel): the form
         NOT taken, kept here for the same reason

  layer  the MODEL's own call (`KDAMixer._kda_chunk`: the convolution,
         the unit q and k, the log decay and beta formed from a call's
         projections, then the chunk kernel) in the same loop: what the
         engine's loop holds and `kda_prefill_ms.serve` times; `--ops N`
         traces the layer form at N tokens a call and lists the device's
         operations by time (what an iteration is made of)

    chiprun -- python -m tools.sweep_kda_chunk [--shapes kimi,solar512]
        [--forms token,chunk,xla,layer] [--tokens 64,256,1024]
        [--heads 2,4] [--subs 8,16,32] [--ops 512]

Every form's outputs and final state are compared with the token
kernel's BEFORE it is timed (`max_err`).  Prints one JSON line a (shape,
form, tokens a call, heads a grid step, sub-chunk) with us a (token,
layer), `served` on the line the model would run (the module's heads
and sub-chunk, `KDAMixer.prefill_chunks_per_call`'s group), and writes
them all to ``chiprun_out/sweep_kda_chunk.json``.  A TPU or nothing: a
time from the CPU is not a time (``--tiny`` rehearses the walk on the
CPU, kernels interpreted, against `T = 1` calls).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops import pallas_kda_chunk as chunked
from paddle_tpu.ops import pallas_kda_update as kda
from paddle_tpu.serving import mixers

D = 128
SHAPES = {      # heads, the bucket's rows, the prompt's real tokens, beta's top
    "kimi": dict(heads=32, bucket=4096, tokens=3600, beta=1.0),
    "solar256": dict(heads=64, bucket=256, tokens=200, beta=2.0),
    "solar512": dict(heads=64, bucket=512, tokens=450, beta=2.0),
    "solar1024": dict(heads=64, bucket=1024, tokens=900, beta=2.0),
}
TINY = {"tiny": dict(heads=8, bucket=128, tokens=100, beta=2.0)}


def token_form(q, k, g, v, beta, state, n_real, interpret=False):
    """The token rule's kernel over a call's tokens, one after another."""
    return kda.kda_update(q, k, jnp.exp(g), v, beta, state, n_real,
                          interpret=interpret)


def mixer_of(sizes):
    """A `KDAMixer` of the shape's heads, nothing else of a model."""
    mixer = mixers.KDAMixer()
    mixer.lin_heads, mixer.lin_head_dim, mixer.conv_kernel = \
        sizes["heads"], D, 4
    mixer.beta_scale = sizes["beta"]
    mixer.recurrent_state = mixer.kda_state()
    return mixer


def served_group(sizes):
    """Tokens a call `KDAMixer.prefill_chunks_per_call` gives the
    shape's bucket."""
    return mixer_of(sizes).prefill_chunks_per_call(sizes["bucket"]) \
        * mixers.PREFILL_CHUNK


def layer_form(sizes, seed):
    """The model's own call as a form: the vectors it is handed are the
    bucket's PROJECTIONS (`u`, `gate`, `beta`, seeded), its state the
    layer's (`s` and the convolution's tail)."""
    mixer, c = mixer_of(sizes), sizes["heads"] * D
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 6)
    lw = {"kda_conv": jax.random.normal(ks[0], (4, 3 * c), jnp.float32) / 2,
          "kda_a_log": jnp.log(jax.random.uniform(
              ks[1], (sizes["heads"],), jnp.float32, 1.0, 16.0)),
          "kda_dt_bias": jax.random.uniform(ks[2], (c,), jnp.float32,
                                            -6.0, -2.0)}
    rows = (jax.random.normal(ks[3], (sizes["bucket"], 3 * c), jnp.float32),
            jax.random.normal(ks[4], (sizes["bucket"], c), jnp.float32),
            jax.random.normal(ks[5], (sizes["bucket"], sizes["heads"]),
                              jnp.float32))

    def form(u, gate, beta, state, n_real, interpret=False):
        o, new = mixer._kda_chunk(
            lw, {"u": u[0], "gate": gate[0], "beta": beta[0]}, n_real[0],
            state, interpret=interpret)
        return o[None], new

    state0 = {n: jnp.zeros((1,) + tuple(shape), dtype)
              for n, (shape, dtype) in mixer.recurrent_state.items()}
    return form, rows, state0


# -- the form not taken: the chunk algebra as XLA operations -----------------
def _hi(spec, a, b):
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def xla_form(q, k, g, v, beta, state, n_real, interpret=False):
    """The chunk form of the module's docstring over ALL the call's
    chunks at once (`[N, H, C, ...]` operands) but the state's pass;
    decay ratios a channel inside sub-blocks of 16 tokens, between them
    both factors relative to the later sub-block's first token."""
    del interpret
    c, sub = chunked.CHUNK, 16
    r, t, h, d_k = q.shape
    assert r == 1 and t % c == 0
    n, nb = t // c, c // sub
    real = (jnp.arange(t) < n_real[0])[:, None, None]

    def lay(x):     # [1, T, H, d] -> [N, H, C, d]
        return jnp.swapaxes(jnp.where(real, x[0], 0.0).reshape(
            n, c, h, -1), 1, 2)

    q, k, g, v = lay(q), lay(k), lay(g), lay(v)
    beta = lay(beta[..., None])
    gs = g.reshape(n, h, nb, sub, d_k)
    local = jnp.cumsum(gs, axis=3)                      # from a sub-block's start
    starts = jnp.cumsum(local[:, :, :, -1], axis=2) - local[:, :, :, -1]
    whole = (local + starts[:, :, :, None]).reshape(n, h, c, d_k)
    end = whole[:, :, -1:]
    # diagonal sub-blocks: e^{G_t - G_j} a channel, t >= j
    ratio = local[:, :, :, :, None] - local[:, :, :, None]      # [.., t, j, d]
    at = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    ratio = jnp.where(at, jnp.exp(jnp.where(at, ratio, 0.0)), 0.0)
    qb, kb = (x.reshape(n, h, nb, sub, d_k) for x in (q, k))
    p_d = jnp.sum(qb[:, :, :, :, None] * ratio * kb[:, :, :, None], -1)
    a_d = jnp.sum(kb[:, :, :, :, None] * ratio * kb[:, :, :, None], -1)
    # between sub-blocks: relative to the later one's first token
    p = jnp.zeros((n, h, c, c), jnp.float32)
    a = jnp.zeros((n, h, c, c), jnp.float32)
    for b in range(nb):
        rows = slice(b * sub, (b + 1) * sub)
        p = p.at[:, :, rows, rows].set(p_d[:, :, b])
        a = a.at[:, :, rows, rows].set(a_d[:, :, b])
        if b:
            to_start = jnp.exp(local[:, :, b])
            kh = k[:, :, :b * sub] * jnp.exp(jnp.minimum(
                starts[:, :, b, None] - whole[:, :, :b * sub], 0.0))
            p = p.at[:, :, rows, :b * sub].set(
                _hi("nhtd,nhjd->nhtj", qb[:, :, b] * to_start, kh))
            a = a.at[:, :, rows, :b * sub].set(
                _hi("nhtd,nhjd->nhtj", kb[:, :, b] * to_start, kh))
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), beta * a, 0.0)
    from paddle_tpu.serving.gated_delta_lm import _unit_lower_inverse
    eg = jnp.exp(whole)
    tt = _hi("nhtj,nhjd->nhtd", _unit_lower_inverse(a), jnp.concatenate(
        [beta * v, beta * eg * k], axis=-1))
    t_v, t_k = tt[..., :v.shape[-1]], tt[..., v.shape[-1]:]
    to_end = k * jnp.exp(jnp.minimum(end - whole, 0.0))
    s, s0s, us = state[0], [], []
    for i in range(n):
        s0s.append(s)
        us.append(t_v[i] - _hi("htd,hdv->htv", t_k[i], s))
        s = jnp.swapaxes(jnp.exp(end[i]), -1, -2) * s + _hi(
            "htd,htv->hdv", to_end[i], us[-1])
    o = _hi("nhtd,nhdv->nhtv", eg * q, jnp.stack(s0s)) + _hi(
        "nhtj,nhjv->nhtv", p, jnp.stack(us))
    return jnp.swapaxes(o, 1, 2).reshape(1, t, h, -1), s[None]


def chunk_form(q, k, g, v, beta, state, n_real, interpret=False):
    return chunked.kda_chunk(q, k, g, v, beta, state, n_real,
                             interpret=interpret)


FORMS = {"token": token_form, "chunk": chunk_form, "xla": xla_form}


def make_case(sizes, seed):
    """The bucket's vectors as `KDAMixer._kda_vectors` leaves them: q and
    k at unit length a head (q scaled by d^-1/2), decays 0.2 ... 0.999 a
    channel a token as their logarithm, beta in (0, top)."""
    h, t = sizes["heads"], sizes["bucket"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))  # noqa: E731
    q = unit(jax.random.normal(ks[0], (t, h, D), jnp.float32)) / D ** 0.5
    k = unit(jax.random.normal(ks[1], (t, h, D), jnp.float32))
    v = jax.random.normal(ks[2], (t, h, D), jnp.float32)
    g = jnp.log(jax.random.uniform(ks[3], (t, h, D), jnp.float32, 0.2, 0.999))
    beta = jax.random.uniform(ks[4], (t, h), jnp.float32, 0.0, sizes["beta"])
    return (q, k, g, v, beta), jnp.int32(sizes["tokens"])


def layer_loop(form, per_call, heads, interpret, state0=None):
    """The engine's prefill ``recur`` of one layer, ``per_call`` tokens a
    call of ``form``: (vectors, length) -> (outputs, the state after)."""
    if state0 is None:
        state0 = jnp.zeros((1, heads, D, D), jnp.float32)

    def run(vectors, length):
        t = vectors[0].shape[0]

        def scan_step(i, carry):
            st, outs = carry
            o, st = form(*(lax.dynamic_slice_in_dim(
                x, i * per_call, per_call)[None] for x in vectors), st,
                jnp.reshape(jnp.minimum(length - i * per_call, per_call),
                            (1,)), interpret=interpret)
            return st, lax.dynamic_update_slice_in_dim(
                outs, o[0], i * per_call, axis=0)

        return lax.fori_loop(
            0, -(-length // per_call), scan_step,
            (state0, jnp.zeros((t, heads, D), jnp.float32)))[::-1]

    return jax.jit(run)


def token_by_token(vectors, length, heads):
    """The oracle of ``--tiny``: ``T = 1`` calls of the step's kernel."""
    q, k, g, v, beta = vectors
    state, outs = jnp.zeros((1, heads, D, D), jnp.float32), []
    for t in range(int(length)):
        o, state = kda.kda_update(
            q[None, t:t + 1], k[None, t:t + 1], jnp.exp(g[None, t:t + 1]),
            v[None, t:t + 1], beta[None, t:t + 1], state,
            jnp.ones((1,), jnp.int32), interpret=True)
        outs.append(o[0, 0])
    return jnp.stack(outs), state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="kimi,solar512,solar1024")
    ap.add_argument("--forms", default="token,chunk,xla")
    ap.add_argument("--tokens", default="64,256,512,1024",
                    help="tokens a call (the token form runs the first)")
    ap.add_argument("--heads", default=str(chunked.HEADS_A_STEP),
                    help="heads a grid step of the chunk form")
    ap.add_argument("--subs", default=str(chunked.SUB),
                    help="tokens a sub-chunk of the chunk form")
    ap.add_argument("--ops", type=int, default=0,
                    help="trace the layer form at this many tokens a call "
                         "and list the device's operations")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=57)
    ap.add_argument("--out", default="chiprun_out/sweep_kda_chunk.json")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    if not a.tiny and jax.default_backend() != "tpu":
        raise SystemExit("a TPU or nothing: a time from the CPU is no time")
    shapes = TINY if a.tiny else {n: SHAPES[n] for n in a.shapes.split(",")}
    served = chunked.HEADS_A_STEP, chunked.SUB
    ints = lambda text: [int(x) for x in text.split(",")]  # noqa: E731
    lines, traced = [], {}
    for name, sizes in shapes.items():
        vectors, length = make_case(sizes, a.seed)
        layer, rows, state0 = layer_form(sizes, a.seed)
        want = None
        if a.tiny:
            want = token_by_token(vectors, length, sizes["heads"])
        for form in a.forms.split(","):
            per_calls = [x for x in ints(a.tokens) if x <= sizes["bucket"]]
            if form == "token":
                per_calls = per_calls[:1]
            knobs = [(g, sub) for g in ints(a.heads) for sub in ints(a.subs)] \
                if form in ("chunk", "layer") else [served]
            for per_call in per_calls:
                for g, sub in knobs:
                    line = dict(
                        shape=name, form=form, tokens_a_call=per_call,
                        heads_a_step=g if form in ("chunk", "layer") else None,
                        sub_chunk=sub if form in ("chunk", "layer") else None,
                        served=form in ("chunk", "layer") and (g, sub) == served
                        and per_call == served_group(sizes),
                        device=jax.devices()[0].device_kind, **sizes)
                    try:
                        chunked.HEADS_A_STEP, chunked.SUB = g, sub
                        jax.clear_caches()
                        t0 = time.perf_counter()
                        case = (rows if form == "layer" else vectors, length)
                        compiled = layer_loop(
                            layer if form == "layer" else FORMS[form],
                            per_call, sizes["heads"], a.tiny,
                            state0 if form == "layer" else None,
                        ).lower(*case).compile()
                        line["lower_compile_s"] = time.perf_counter() - t0
                        out = jax.block_until_ready(compiled(*case))
                        got = jax.tree_util.tree_leaves(
                            (out[0][:sizes["tokens"]], out[1]))
                        line["finite"] = all(
                            bool(jnp.isfinite(x).all()) for x in got)
                        if form != "layer":     # its inputs are its own
                            if want is None:
                                want = got      # the token kernel, run first
                            line["max_err"] = max(
                                float(jnp.abs(x - y).max())
                                for x, y in zip(got, want))
                        times = []
                        for _ in range(0 if a.tiny else a.reps):
                            t0 = time.perf_counter()
                            jax.block_until_ready(compiled(*case))
                            times.append(time.perf_counter() - t0)
                        if form == "layer" and per_call == a.ops \
                                and (g, sub) == served and not a.tiny:
                            from tools.sweep_gdn_group import device_ops
                            traced[name] = device_ops(compiled, case, 3)
                        if times:       # the CPU's are no times
                            times.sort()
                            line["ms_a_layer"] = times[len(times) // 2] * 1e3
                            line["us_a_token_layer"] = \
                                line["ms_a_layer"] * 1e3 / sizes["tokens"]
                    except Exception as e:  # what the chip's compiler refuses
                        line["error"] = f"{type(e).__name__}: {e}"[:400]
                    finally:        # nothing traced at a knob outlives it
                        chunked.HEADS_A_STEP, chunked.SUB = served
                        jax.clear_caches()
                    print(json.dumps(line), flush=True)
                    lines.append(line)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(dict(lines=lines, ops=traced), f, indent=1)
    for name, ops in traced.items():
        print(f"# {name}: the layer form's operations at {a.ops} tokens a "
              "call: us a run, calls a run")
        for op, n, us in ops[:40]:
            print(f"{us:9.1f} {n:6.1f}  {op}")


if __name__ == "__main__":
    main()
