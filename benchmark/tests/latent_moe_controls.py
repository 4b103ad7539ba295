"""By hand, on the chip: the readings the limits of
``kimi_k2_5.agent_closed_c64``'s check must FAIL.

    chiprun -- python3 benchmark/tests/latent_moe_controls.py --seed N
        [--only served,no_mscale,...] [--slots 2]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check (``kinds/serve_routed.py``
``check_logits`` with ``kinds/serve_latent.py``'s size check), on fewer
slots than the cell so that nine engines fit a call, and prints one JSON
line: the check's verdict and its numbers.  ``served`` is the model as
it is.  Nothing here is run by the benchmark's command;
``tests/test_latent_moe_serving.py`` holds every control at a small size.
"""
import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "kimi_k2_5.agent_closed_c64"


def _no_mscale(m):
    """The softmax's scale without YaRN's ``mscale^2``."""
    m.softmax_scale = (m.nope_dim + m.rope_dim) ** -0.5


def _plain_theta(m):
    """The rotary frequencies without the YaRN blend."""
    m.rope_freqs = tuple(m.rope_theta ** (
        -2.0 * j / m.rope_dim) for j in range(m.rope_dim // 2))


def _half_split(m):
    """Rotary lanes paired ``(j, j + d/2)``: no de-interleave."""
    import jax.numpy as jnp

    def rotate(x, cos, sin):
        # the model's factors hold a pair's angle on both of its lanes
        # and the sine negated on the even one
        half = x.shape[-1] // 2
        a, b = x[..., :half], x[..., half:]
        cos, sin = cos[..., 0::2], sin[..., 1::2]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1)

    m._rotate = rotate


def _normed_rope_key(m):
    """``k_r`` normed with ``c_kv``: one RMS over all the row's lanes."""
    import jax.numpy as jnp

    from paddle_tpu.serving.latent_moe_lm import rms_norm

    def latent(lw, kv):
        g = jnp.concatenate([lw["kv_norm"], jnp.ones(
            (kv.shape[-1] - m.kv_rank,), lw["kv_norm"].dtype)])
        both = rms_norm(kv, g, m.rms_eps)
        return both[..., :m.kv_rank], both[..., m.kv_rank:]

    m._latent = latent


def _patched(module, name, make):
    real = getattr(module, name)
    setattr(module, name, make(real))
    return lambda: setattr(module, name, real)


def _bias_in_the_weights():
    """The correction bias added to the chosen scores' weights too."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops

    def make(real):
        def route(h, router_w, router_bias, *, top_k, held_ids, live=None):
            ids, _, _ = real(h, router_w, router_bias, top_k=top_k,
                             held_ids=held_ids, live=live)
            # the weights are s / sum s over the chosen: s + b in its place
            s = jax.nn.sigmoid(jnp.einsum(
                "...d,de->...e", h.astype(jnp.float32),
                router_w.astype(jnp.float32), precision="highest"))
            sb = jnp.take_along_axis(s + router_bias, ids, axis=-1)
            wb = sb / jnp.sum(sb, axis=-1, keepdims=True)
            chosen = ids[..., :, None] == jnp.asarray(held_ids, jnp.int32)
            if live is not None:
                chosen = chosen & live[..., None, None]
            return ids, wb, jnp.sum(
                jnp.where(chosen, wb[..., None], 0.0), axis=-2)
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


def _latent_in_8_bits():
    """Every cached row rounded to an int8 grid (one scale a row), at
    the pool's own dtype and size: only the logits can tell."""
    import jax.numpy as jnp

    from paddle_tpu.serving import kv_cache

    def make(real):
        def rows(val, lanes):
            r = real(val, lanes).astype(jnp.float32)
            scale = jnp.maximum(
                jnp.max(jnp.abs(r), axis=-1, keepdims=True) / 127.0, 1e-30)
            return jnp.round(r / scale) * scale
        return rows

    return _patched(kv_cache, "_pool_rows", make)


# name -> (change the served model, patch the program)
CONTROLS = {
    "served": (None, None),
    "no_mscale": (_no_mscale, None),
    "rope_key_normed": (_normed_rope_key, None),
    "half_split_pairing": (_half_split, None),
    "plain_theta": (_plain_theta, None),
    "bias_in_the_weights": (None, _bias_in_the_weights),
    "no_routed_scaling": (lambda m: setattr(m, "routed_scale", 1.0), None),
    "latent_in_8_bits": (None, _latent_in_8_bits),
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
    over = dict(slots=args.slots, num_pages=args.slots * 641 + 1)
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
            lambda c: cell["kind"].latent_bytes_read_and_owed(bench)[:2]
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
        del srv, model, weights


if __name__ == "__main__":
    main()
