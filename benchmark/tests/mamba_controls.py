"""By hand, on the chip: the readings the limit of
``jamba2_3b.reason_closed_c256``'s check must FAIL, and the served
model's over as many seeds as asked for.

    chiprun -- python3 benchmark/tests/mamba_controls.py --seeds N[,N...]
        [--only served,state_in_bf16,...] [--slots 2]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check (``kinds/serve_ssm.py``:
``serve_recurrent``'s ``check_logits`` with this model's state bytes), on
fewer slots than the cell (an engine, the reference's temporaries and
whatever of the control before it is not yet collected fit beside each
other), and prints one JSON line: the check's verdict and its numbers.
``served`` is the model as it is.  Nothing here is run by the
benchmark's command.

A control is ``(change(model) or None, patch() -> undo or None,
reweigh(weights) -> served weights or None)``.
"""
import argparse
import gc
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "jamba2_3b.reason_closed_c256"


def _state_in_bf16(m):
    """The 16 x d_inner state kept in half the bytes: the engine rounds
    what the step's kernel and the prompt's scan return to the slab's
    dtype; both still compute in float32 from the rounded state."""
    import copy

    import jax.numpy as jnp
    import numpy as np

    # the calls keep the tiles the float32 state's shape gives them
    m.prefill_chunks_per_call = copy.copy(m).prefill_chunks_per_call
    shape, _ = m.recurrent_state["ssm"]
    m.recurrent_state = dict(m.recurrent_state,
                             ssm=(shape, np.dtype("bfloat16")))
    token, chunk = m._ssm_token, m._ssm_chunk

    def up(state):
        return dict(state, ssm=state["ssm"].astype(jnp.float32))

    m._ssm_token = lambda lw, rows, state, live=None, interpret=False: \
        token(lw, rows, up(state), live=live, interpret=interpret)
    m._ssm_chunk = lambda lw, rows, n, state, interpret=False: \
        chunk(lw, rows, n, up(state), interpret=interpret)


def _round_bf16(x):
    """float32 ``x`` rounded to bfloat16's 8 bits of mantissa (to
    nearest, ties to even), in integer operations both XLA and the
    kernels' compiler take."""
    import jax.numpy as jnp
    from jax import lax

    i = lax.bitcast_convert_type(x, jnp.int32)
    i = (i + 0x7FFF + ((i >> 16) & 1)) & jnp.int32(-65536)
    return lax.bitcast_convert_type(i, jnp.float32)


def _exp_in_bf16():
    """``exp(dt (x) A)`` computed in bfloat16: its argument and its
    result rounded to 8 bits, in the step's kernel and the scan's."""
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_ssm as ps

    token = ps._token

    def rounded(dt, x, b_col, c_col, a, h):
        h = _round_bf16(jnp.exp(_round_bf16(dt * a))) * h + x * b_col
        return jnp.sum(h * c_col, axis=0, keepdims=True), h

    ps._token = rounded
    return lambda: setattr(ps, "_token", token)


def _no_inner_norms():
    """The step, B and C straight from ``W_x``: the three RMSNorms left
    out (their gains kept)."""
    from paddle_tpu.serving import mixers

    norm = mixers.rms_norm
    mixers.rms_norm = lambda x, g, eps: x * g
    return lambda: setattr(mixers, "rms_norm", norm)


def _zeroed(name):
    """The served weights with ``name`` of every recurrent layer zero."""
    def reweigh(weights):
        import jax.numpy as jnp

        return dict(weights, layers=[
            dict(lw, **{name: jnp.zeros_like(lw[name])}) if name in lw
            else lw for lw in weights["layers"]])
    return reweigh


def _rope_on_attention(m):
    """A rotary term at theta 10,000 on q and k of the two attention
    layers (the published model has no positional term)."""
    from paddle_tpu.serving.blocks import (_mm, half_split_angles,
                                           half_split_rotate)

    forward = m.forward

    def remembering(weights, tokens, positions, cache, attend):
        m._positions = positions
        return forward(weights, tokens, positions, cache, attend)

    def attention(l, lw, h, cache, attend):
        lead = h.shape[:-1]
        turn = half_split_angles(m._positions, 10000.0, m.head_dim)
        q = half_split_rotate(_mm(h, lw["wq"]).reshape(
            *lead, m.num_heads, m.head_dim), *turn)
        k = half_split_rotate(_mm(h, lw["wk"]).reshape(
            *lead, m.num_kv_heads, m.head_dim), *turn)
        v = _mm(h, lw["wv"]).reshape(*lead, m.num_kv_heads, m.head_dim)
        ctx, cache = attend(l, q, k, v, cache)
        return _mm(ctx.reshape(*lead, -1).astype("float32"),
                   lw["wo"]), cache

    m.forward, m._attention = remembering, attention


CONTROLS = {
    "served": (None, None, None),
    "state_in_bf16": (_state_in_bf16, None, None),
    "exp_in_bf16": (None, _exp_in_bf16, None),
    "no_inner_norms": (None, _no_inner_norms, None),
    "no_dt_bias": (None, None, _zeroed("ssm_dt_b")),
    "no_d_skip": (None, None, _zeroed("ssm_d")),
    "rope_on_attention": (_rope_on_attention, None, None),
    "no_conv_bias": (None, None, _zeroed("ssm_conv_b")),
}


def run_control(cell, config, name, seed, slots):
    """(the check's verdict, its numbers) of control ``name`` served on
    ``slots`` slots of ``config`` through the cell's kind's check."""
    import jax
    import numpy as np

    from paddle_tpu.serving import DecodeServer

    model_mod = cell["model"]
    sv = config["serving"]
    over = dict(slots=slots, num_pages=slots * (
        sv["max_seq_len"] // sv.get("page_size", 16) + 1) + 1)
    # the size check's slots are the engine's here, not the cell's
    sized = dict(config, serving=dict(sv, **over))
    seed_w, seed_check = (int(s) & 0x7FFFFFFF for s in
                          np.random.SeedSequence(seed).generate_state(2))
    change, patch, reweigh = CONTROLS[name]
    model, weights = model_mod.build(config, seed_w)
    if change:
        change(model)
    undo = patch() if patch else None
    # a kernel's jitted call traced before the patch would be found again
    jax.clear_caches()
    bench = types.SimpleNamespace(
        spec=cell["spec"], config=sized, model=types.SimpleNamespace(
            reference_logits=model_mod.reference_logits))
    srv = DecodeServer(model, reweigh(weights) if reweigh else weights,
                       model_mod.decode_config(config, **over), replicas=1)
    srv.start()
    try:
        recurrent = cell["kind"]._recurrent()
        recurrent.state_bytes_read_and_owed = \
            cell["kind"].state_bytes_read_and_owed
        return recurrent.check_logits(bench, srv, weights, seed_check)
    finally:
        srv.stop(drain=False)
        if undo:
            undo()
            jax.clear_caches()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--slots", type=int, default=2)
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as bench_run

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = bench_run.resolve_cell(ROOT, CELL)
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in args.only.split(","):
            ok, checks = run_control(cell, cell["config"], name, seed,
                                     args.slots)
            print(json.dumps({"control": name, "correct": bool(ok),
                              "seed": seed, **checks}), flush=True)
            # an engine and its jitted programs refer to each other:
            # without a collection its bytes stay, and the next control
            # does not fit
            gc.collect()


if __name__ == "__main__":
    main()
