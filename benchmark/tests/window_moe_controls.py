"""By hand, on the chip: the readings the limits of
``mimo_v2_5.reason_closed_c128``'s check must FAIL.

    chiprun -- python3 benchmark/tests/window_moe_controls.py --seed N
        [--only served,window_127,...] [--slots 4]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check (``kinds/serve_routed.py``
``check_logits`` with ``kinds/serve_window.py``'s size check), on fewer
slots than the cell so that seven engines fit a call, and prints one
JSON line: the check's verdict and its numbers.  ``served`` is the model
as it is.  Nothing here is run by the benchmark's command.
"""
import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "mimo_v2_5.reason_closed_c128"


def _swap_bases(m):
    m.rope_theta, m.window_rope_theta = m.window_rope_theta, m.rope_theta


def _no_sink(weights):
    import jax.numpy as jnp

    return dict(weights, layers=[
        dict(lw, sink=jnp.full_like(lw["sink"], -1e30)) if "sink" in lw
        else lw for lw in weights["layers"]])


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


# name -> (change the served model, change the served weights, patch)
CONTROLS = {
    "served": (None, None, None),
    "window_127": (lambda m: setattr(m, "window", 127), None, None),
    "window_129": (lambda m: setattr(m, "window", 129), None, None),
    "no_sink": (None, _no_sink, None),
    "rotary_bases_swapped": (_swap_bases, None, None),
    "no_value_scale": (lambda m: setattr(m, "value_scale", 1.0), None, None),
    "bf16_router": (None, None, _bf16_router),
    "window_layers_attend_every_position": (
        lambda m: setattr(m, "window", 4096), None, None),
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--slots", type=int, default=4)
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
    over = dict(slots=args.slots, num_pages=args.slots * 225 + 1)
    seed_w, seed_check = (int(s) & 0x7FFFFFFF for s in
                          np.random.SeedSequence(args.seed).generate_state(2))
    for name in args.only.split(","):
        change_model, change_weights, patch = CONTROLS[name]
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
        srv = DecodeServer(
            model, change_weights(weights) if change_weights else weights,
            model_mod.decode_config(config, **over), replicas=1)
        srv.start()
        try:
            ok, checks = routed.check_logits(bench, srv, weights, seed_check)
        finally:
            srv.stop(drain=False)
            if undo:
                undo()
        print(json.dumps({"control": name, "correct": bool(ok),
                          "seed": args.seed, **checks}), flush=True)
        del srv, model, weights


if __name__ == "__main__":
    main()
