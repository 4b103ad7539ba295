"""By hand, on the chip: the readings the limit of
``olmo_hybrid_7b.docqa_closed_c32``'s check must FAIL.

    chiprun -- python3 benchmark/tests/gated_delta_controls.py --seed N
        [--only served,bf16_state,...] [--slots 2]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check
(``kinds/serve_recurrent.py`` ``check_logits``), on fewer slots than the
cell (an engine, the reference's temporaries and whatever of the control
before it is not yet collected fit beside each other), and prints one
JSON line: the check's verdict and its numbers.  ``served`` is the model
as it is.  Nothing here is run by the benchmark's command.
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
CELL = "olmo_hybrid_7b.docqa_closed_c32"


def _bf16_state(m):
    """The delta rule's matrices kept in half the bytes (the engine
    rounds what the update returns to the slab's dtype)."""
    import numpy as np

    shape, _ = m.recurrent_state["s"]
    m.recurrent_state = dict(m.recurrent_state, s=(shape, np.dtype(
        "bfloat16")))
    token, chunk = m._gdn_token, m._gdn_chunk

    def up(state):
        import jax.numpy as jnp

        return dict(state, s=state["s"].astype(jnp.float32))

    m._gdn_token = lambda lw, rows, state: token(lw, rows, up(state))
    m._gdn_chunk = lambda lw, rows, n, state: chunk(lw, rows, n, up(state))


def _beta_without_the_2(m):
    import jax

    m._beta = lambda b: jax.nn.sigmoid(b)


def _no_qk_norm(m):
    m._qk_norm = lambda x, g: x


def _no_decay(m):
    import jax.numpy as jnp

    m._log_decay = lambda lw, a: jnp.zeros_like(a)


def _tail_dropped(m):
    """The prefill leaves a zero convolution tail in the slot's rows:
    the first three steps after it convolve with nothing behind them."""
    import jax.numpy as jnp

    chunk = m._gdn_chunk

    def dropped(lw, rows, n_real, state):
        o, new = chunk(lw, rows, n_real, state)
        return o, dict(new, tail=jnp.zeros_like(new["tail"]))

    m._gdn_chunk = dropped


def _bf16_chunk_products(m):
    """The chunk form's own products in ONE bfloat16 pass (the chip's
    default for float32 operands) instead of float32 products."""
    import jax.numpy as jnp

    from paddle_tpu.serving import gated_delta_lm

    gated_delta_lm._exact = jnp.matmul


CONTROLS = {
    "served": None,
    "bf16_state": _bf16_state,
    "beta_without_the_2": _beta_without_the_2,
    "no_qk_norm": _no_qk_norm,
    "no_decay": _no_decay,
    "tail_dropped": _tail_dropped,
    "bf16_chunk_products": _bf16_chunk_products,
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
    over = dict(slots=args.slots, num_pages=args.slots * 353 + 1)
    # the size check's slots are the engine's here, not the cell's
    sized = dict(config, serving=dict(config["serving"], **over))
    seed_w, seed_check = (int(s) & 0x7FFFFFFF for s in
                          np.random.SeedSequence(args.seed).generate_state(2))
    for name in args.only.split(","):
        model, weights = model_mod.build(config, seed_w)
        if CONTROLS[name]:
            CONTROLS[name](model)
        bench = types.SimpleNamespace(
            spec=cell["spec"], config=sized, model=types.SimpleNamespace(
                reference_logits=model_mod.reference_logits))
        srv = DecodeServer(model, weights,
                           model_mod.decode_config(config, **over),
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
        del srv, model, weights, bench
        gc.collect()


if __name__ == "__main__":
    main()
