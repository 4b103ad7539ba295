"""By hand, on the chip: the readings the limits of
``command_a_plus.rag_closed_c48``'s check must FAIL.

    chiprun -- python3 benchmark/tests/parallel_moe_controls.py --seed N
        [--only served,window_4095,...] [--slots 2]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check (``kinds/serve_routed.py``
``check_logits`` with ``kinds/serve_window.py``'s size check), on fewer
slots than the cell (an engine, the reference's temporaries and whatever
of the control before it is not yet collected fit beside each other),
and prints one JSON line: the check's verdict and its numbers.
``served`` is the model as it is.  Nothing here is run by the
benchmark's command.
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
CELL = "command_a_plus.rag_closed_c48"


def _rotate_global(m):
    m.rotary_kinds = ("window", "attention")


def _half_split(m):
    """Lane j pairs with lane j + D/2, at pair j's frequency."""
    import jax.numpy as jnp

    half = m.head_dim // 2

    def rotary(positions):
        freq = m.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = positions.astype(jnp.float32)[..., None, None] * freq
        return jnp.cos(angle), jnp.sin(angle)

    def rotate(x, cos, sin):
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    m._rotary, m._rotate = rotary, rotate


def _sum_shared(m):
    """One shared expert four times as wide IS the four summed: the same
    matrices, no division."""
    m.shared_dim *= m.shared_experts
    m.shared_experts = 1


def _bf16_router():
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops

    real = moe_ops.moe_share_route

    def rounded(h, router_w, router_bias, **kw):
        return real(h.astype(jnp.bfloat16).astype(jnp.float32),
                    router_w.astype(jnp.bfloat16).astype(jnp.float32),
                    router_bias, **kw)

    moe_ops.moe_share_route = rounded
    return lambda: setattr(moe_ops, "moe_share_route", real)


# name -> (change the served model, patch)
CONTROLS = {
    "served": (None, None),
    "window_4095": (lambda m: setattr(m, "window", 4095), None),
    "window_4097": (lambda m: setattr(m, "window", 4097), None),
    "rotary_in_the_global_layer": (_rotate_global, None),
    "half_split_pairing": (_half_split, None),
    "shared_experts_summed": (_sum_shared, None),
    "bf16_router": (None, _bf16_router),
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--slots", type=int, default=2)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import run as bench_run
    from paddle_tpu.serving import DecodeServer

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_compile_cache"))
    cell = bench_run.resolve_cell(ROOT, CELL)
    config, model_mod = cell["config"], cell["model"]
    routed = bench_run.load_piece(ROOT, cell["bench_dir"], "kinds",
                                  "serve_routed")
    over = dict(slots=args.slots, num_pages=args.slots * 385 + 1)
    seed_w, seed_check = (int(s) & 0x7FFFFFFF for s in
                          np.random.SeedSequence(args.seed).generate_state(2))
    for name in args.only.split(","):
        change_model, patch = CONTROLS[name]
        model, weights = model_mod.build(config, seed_w)
        if change_model:
            change_model(model)
        undo = patch() if patch else None
        bench = types.SimpleNamespace(
            spec=cell["spec"], config=config, model=types.SimpleNamespace(
                reference_logits=model_mod.reference_logits,
                decode_config=lambda c: model_mod.decode_config(c, **over)))
        routed.state_bytes_read_and_owed = \
            lambda c: cell["kind"].window_bytes_read_and_owed(bench)
        srv = DecodeServer(model, weights,
                           model_mod.decode_config(config, **over),
                           replicas=1)
        srv.start()
        try:
            ok, checks = routed.check_logits(bench, srv, weights, seed_check)
        finally:
            srv.stop(drain=False)
            if undo:
                undo()
        print(json.dumps({"control": name, "correct": bool(ok),
                          "seed": args.seed, **checks}), flush=True)
        # an engine and its jitted programs refer to each other: without
        # a collection its 10 GB stay, and the third control does not fit
        del srv, model, weights, bench
        gc.collect()


if __name__ == "__main__":
    main()
