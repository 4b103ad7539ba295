"""By hand, on the chip: the readings the limits of
``keye_vl2_30b.vreason_closed_c16``'s check must FAIL.

    chiprun -- python3 benchmark/tests/indexed_moe_controls.py --seeds N[,M]
        [--only served,index_keys_in_8_bits,...] [--slots 2]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check (``kinds/serve_indexed.py``
``check_logits``), on fewer slots than the cell so that a dozen engines
fit a call, and prints one JSON line: the check's verdict and its
numbers.  ``served`` is the model as it is.  Nothing here is run by the
benchmark's command; ``benchmark/tests/test_indexed_moe_cell.py`` holds
every control at a small size.
"""
import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "keye_vl2_30b.vreason_closed_c16"


def _patched(module, name, make):
    real = getattr(module, name)
    setattr(module, name, make(real))
    return lambda: setattr(module, name, real)


def _index_keys_in_8_bits():
    """Every index key rounded to an int8 grid (one scale a key) where it
    leaves the layer, at the pool's own dtype and size: what both forms
    score is what an 8-bit pool would hold."""
    import jax.numpy as jnp

    from paddle_tpu.serving import mixers

    def make(real):
        def init(self, key, step, prompt):
            k = key.astype(jnp.float32)
            scale = jnp.maximum(
                jnp.max(jnp.abs(k), axis=-1, keepdims=True) / 127.0, 1e-30)
            real(self, jnp.round(k / scale) * scale, step, prompt)
        return init

    return _patched(mixers.IndexCall, "__init__", make)


def _weights_not_renormalised():
    """The chosen experts weighed by their probabilities as they are."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops

    def make(real):
        def route(h, router_w, router_bias, *, top_k, held_ids, live=None,
                  scoring="sigmoid"):
            ids, _, _ = real(h, router_w, router_bias, top_k=top_k,
                             held_ids=held_ids, live=live, scoring=scoring)
            p = jax.nn.softmax(jnp.einsum(
                "...d,de->...e", h.astype(jnp.float32),
                router_w.astype(jnp.float32), precision="highest"))
            w = jnp.take_along_axis(p, ids, axis=-1)
            chosen = ids[..., :, None] == jnp.asarray(held_ids, jnp.int32)
            if live is not None:
                chosen = chosen & live[..., None, None]
            return ids, w, jnp.sum(
                jnp.where(chosen, w[..., None], 0.0), axis=-2)
        return route

    return _patched(moe_ops, "moe_share_route", make)


def _bf16_router():
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops

    def make(real):
        def rounded(h, router_w, router_bias, **kw):
            return real(h.astype(jnp.bfloat16).astype(jnp.float32),
                        router_w.astype(jnp.bfloat16).astype(jnp.float32),
                        router_bias, **kw)
        return rounded

    return _patched(moe_ops, "moe_share_route", make)


def _no_selection(m):
    """Every live position attended: the mechanism left out."""
    m.index_topk = 1 << 20


def _unweighted_heads(m):
    import jax.numpy as jnp

    m._index_weights = lambda lw, h: jnp.ones(
        h.shape[:-1] + (m.index_heads,), jnp.float32)


def _adjacent_pairing(m):
    """Rotary lanes paired ``(2j, 2j + 1)`` at the same angles."""
    import jax.numpy as jnp

    def rotate(x, cos, sin):
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)

    m._rotate = rotate


# name -> (change the served model, patch the program)
CONTROLS = {
    "served": (None, None),
    "index_keys_in_8_bits": (None, _index_keys_in_8_bits),
    "no_selection": (_no_selection, None),
    "topk_1024": (lambda m: setattr(m, "index_topk", 1024), None),
    "no_relu": (lambda m: setattr(m, "_index_relu", lambda s: s), None),
    "unweighted_heads": (_unweighted_heads, None),
    "no_key_layernorm": (
        lambda m: setattr(m, "_index_key", lambda lw, x: x), None),
    "no_index_rotary": (lambda m: setattr(
        m, "_index_rotary", lambda qi, ki, positions: (qi, ki)), None),
    "no_qk_norm": (
        lambda m: setattr(m, "_qk_norm", lambda lw, q, k: (q, k)), None),
    "weights_not_renormalised": (None, _weights_not_renormalised),
    "theta_1e4": (lambda m: setattr(m, "rope_theta", 1e4), None),
    "adjacent_pairing": (_adjacent_pairing, None),
    "bf16_router": (None, _bf16_router),
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
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
    config, model_mod, kind = cell["config"], cell["model"], cell["kind"]
    pages = config["serving"]["max_seq_len"] // 16 + 1
    over = dict(slots=args.slots, num_pages=args.slots * pages + 1)
    for seed in (int(s) for s in args.seeds.split(",")):
        seed_w, seed_check = (
            int(s) & 0x7FFFFFFF for s in
            np.random.SeedSequence(seed).generate_state(2))
        for name in args.only.split(","):
            change_model, patch = CONTROLS[name]
            model, weights = model_mod.build(config, seed_w)
            if change_model:
                change_model(model)
            undo = patch() if patch else None
            bench = types.SimpleNamespace(
                spec=cell["spec"], config=config,
                model=types.SimpleNamespace(
                    reference_logits=model_mod.reference_logits,
                    decode_config=lambda c: model_mod.decode_config(
                        c, **over)))
            srv = DecodeServer(model, weights,
                               model_mod.decode_config(config, **over),
                               replicas=1)
            srv.start()
            try:
                ok, checks = kind.check_logits(bench, srv, weights,
                                               seed_check)
            finally:
                srv.stop(drain=False)
                if undo:
                    undo()
            print(json.dumps({"control": name, "correct": bool(ok),
                              "seed": seed, **checks}), flush=True)
            del srv, model, weights


if __name__ == "__main__":
    main()
