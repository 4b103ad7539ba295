"""By hand, on the chip: the readings the limits of
``ouro_2_6b.mathqa_closed_c16``'s check must FAIL.

    chiprun -- python3 benchmark/tests/looped_controls.py --seed N
        [--only served,three_passes,...] [--slots 2]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check
(``kinds/serve_looped.py`` ``check_logits``), on fewer slots than the cell (an
engine, the reference's temporaries and whatever of the control before
it is not yet collected fit beside each other), and prints one JSON
line: the check's verdict and its numbers.  ``served`` is the model as
it is.  Nothing here is run by the benchmark's command.
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
CELL = "ouro_2_6b.mathqa_closed_c16"


def _three_passes(m):
    """One pass short; the pools keep their 192 layers, so only the
    logits can tell."""
    m.loops -= 1


def _no_norm_between_passes(m):
    """The final norm before the head alone: passes 0..2 hand the next
    pass the stream as it is."""
    import jax.numpy as jnp

    between = m._between
    m._between = lambda weights, x, t: jnp.where(
        t == m.loops - 1, between(weights, x, t), x)


def _passes_share_kv(m):
    """The family's reduced cache: every pass keeps its K and V where
    the LAST pass does, so a step's passes 0..2 attend the earlier
    tokens' last-pass keys (beside their own token's).  The pools keep
    their size here (3/4 of them unused); a deployment of it would
    shrink them and fail by the bytes too."""
    m._cache_layer = lambda t, l: (m.loops - 1) * m.num_layers + l


def _no_output_norms(m):
    m._out_norm = lambda y, g: y


def _rope_base_1e4(m):
    m.rope_theta = 1e4


# name -> (change of the served model, DecodeConfig overrides)
CONTROLS = {
    "served": (None, {}),
    "three_passes": (_three_passes, {}),
    "no_norm_between_passes": (_no_norm_between_passes, {}),
    "passes_share_kv": (_passes_share_kv, {}),
    "no_output_norms": (_no_output_norms, {}),
    "rope_base_1e4": (_rope_base_1e4, {}),
    # pages in 8 bits with a scale a head a position: half the bytes
    "int8_pages": (None, {"kv_quant": True}),
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
    over = dict(slots=args.slots, num_pages=args.slots * 21 + 1)
    # the size check's pages are the engine's here, not the cell's
    sized = dict(config, serving=dict(config["serving"], **over))
    seed_w, seed_check = (int(s) & 0x7FFFFFFF for s in
                          np.random.SeedSequence(args.seed).generate_state(2))
    # one copy of the weights serves every control: none changes them
    weights = model_mod.build(config, seed_w)[1]
    for name in args.only.split(","):
        change, knobs = CONTROLS[name]
        model = model_mod.make_model(config)
        if change:
            change(model)
        bench = types.SimpleNamespace(
            cell=cell, spec=cell["spec"], config=sized,
            model=types.SimpleNamespace(
                reference_logits=model_mod.reference_logits,
                decode_config=model_mod.decode_config))
        srv = DecodeServer(model, weights,
                           model_mod.decode_config(config, **over, **knobs),
                           replicas=1)
        srv.start()
        try:
            ok, checks = cell["kind"].check_logits(bench, srv, weights,
                                                   seed_check)
        finally:
            srv.stop(drain=False)
        print(json.dumps({"control": name, "correct": bool(ok),
                          "seed": args.seed, **checks}), flush=True)
        # an engine and its jitted programs refer to each other: without
        # a collection its bytes stay, and the next control does not fit
        del srv, model, bench
        gc.collect()


if __name__ == "__main__":
    main()
