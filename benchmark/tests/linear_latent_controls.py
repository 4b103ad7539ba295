"""By hand, on the chip: the readings the limits of
``kimi_linear_48b.agent_closed_c128``'s check must FAIL.

    chiprun -- python3 benchmark/tests/linear_latent_controls.py
        --seed N[,N...] [--only served,state_in_bf16,...] [--slots 2]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check (``kinds/serve_routed.py``
``check_logits`` with ``kinds/serve_linear_latent.py``'s two size
checks), on fewer slots than the cell so that the engines fit a call,
and prints one JSON line: the check's verdict and its numbers.
``served`` is the model as it is.  Nothing here is run by the
benchmark's command; ``tests/test_linear_latent_serving.py`` holds every
control at a small size.
"""
import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "kimi_linear_48b.agent_closed_c128"


def _state_in_bf16(m):
    """The recurrent state kept in bfloat16 (the rule's XLA form: the
    kernel takes float32 alone): half the bytes, the logits within the
    bf16 weights' own rounding."""
    import numpy as np

    m.recurrent_state = {n: (shape, np.dtype("bfloat16") if n == "s"
                             else dtype)
                         for n, (shape, dtype) in m.recurrent_state.items()}


def _rotary(m):
    """A rotary term at theta 10,000 on the queries' and the shared
    key's last ``rope_dim`` lanes (interleaved pairs, no scaling): what
    the sibling architecture does and this one's ``mla_use_nope``
    forbids."""
    from paddle_tpu.serving.latent_moe_lm import LatentMoELM

    m.rope_freqs = tuple(10000.0 ** (-2.0 * j / m.rope_dim)
                         for j in range(m.rope_dim // 2))
    m.rope_mscale = 1.0
    m._rotary = types.MethodType(LatentMoELM._rotary, m)
    m._rotate = LatentMoELM._rotate


def _normed_key(m):
    """``k_r`` normed with ``c_kv``: one RMS over all the row's lanes."""
    import jax.numpy as jnp

    from paddle_tpu.serving.hybrid_moe_lm import rms_norm

    def latent(lw, kv):
        g = jnp.concatenate([lw["kv_norm"], jnp.ones(
            (kv.shape[-1] - m.kv_rank,), lw["kv_norm"].dtype)])
        both = rms_norm(kv, g, m.rms_eps)
        return both[..., :m.kv_rank], both[..., m.kv_rank:]

    m._latent = latent


def _dense_layer_routed(m):
    """The leading layer given experts (``first_k_dense_replace`` not
    read); ``REWEIGH`` gives that layer the weights to route over."""
    m.dense_layers = 0


def _experts_for_layer_0(model, weights, seed):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.hybrid_moe_lm import dense_from
    from paddle_tpu.serving.latent_moe_lm import ffn_weights

    keys = iter(jax.random.split(jax.random.PRNGKey(seed ^ 0x5EED), 16))
    first = {**weights["layers"][0], **ffn_weights(
        model, 0, dense_from(keys, jnp.dtype(model.dtype)))}
    return {**weights, "layers": [first] + list(weights["layers"][1:])}


def _patched(module, name, make):
    real = getattr(module, name)
    setattr(module, name, make(real))
    return lambda: setattr(module, name, real)


def _bf16_router():
    """The router's rows and weights rounded to bfloat16
    (``lax.reduce_precision``: the chip's compiler drops a pair of
    casts, ``xla_allow_excess_precision``)."""
    from jax import lax

    from paddle_tpu.ops import moe_ops

    def make(real):
        def rounded(h, router_w, router_bias, **kw):
            return real(lax.reduce_precision(h, 8, 7),
                        lax.reduce_precision(router_w, 8, 7),
                        router_bias, **kw)
        return rounded

    return _patched(moe_ops, "moe_share_route", make)


def _pool_rows_rounded(round_rows):
    """``kv_cache._pool_rows`` (what every write of the latent pool
    passes its rows through) followed by ``round_rows``, at the pool's
    own dtype and size: only the logits can tell."""
    import jax.numpy as jnp

    from paddle_tpu.serving import kv_cache

    def make(real):
        return lambda val, lanes: round_rows(
            real(val, lanes).astype(jnp.float32))

    return _patched(kv_cache, "_pool_rows", make)


def _latent_in_8_bits():
    """Every cached row rounded to an 8-bit FLOAT (e4m3: 3 bits of
    mantissa, no scale): the precision below bfloat16 for a row that
    "has no head to scale by" (the engine's own words for refusing
    ``kv_quant`` over a latent page).  ``lax.reduce_precision``, not a
    pair of casts, which the chip's compiler drops."""
    from jax import lax

    return _pool_rows_rounded(lambda r: lax.reduce_precision(r, 4, 3))


def _latent_on_int8_grid():
    """Every cached row rounded to an int8 grid under ONE float32 scale
    a row (the sibling cell's 8-bit control): 127 levels of the row's
    largest lane, which is finer than e4m3 wherever a lane is above a
    sixteenth of the largest."""
    import jax.numpy as jnp

    def grid(r):
        scale = jnp.maximum(
            jnp.max(jnp.abs(r), axis=-1, keepdims=True) / 127.0, 1e-30)
        return jnp.round(r / scale) * scale

    return _pool_rows_rounded(grid)


# name -> (change the served model, patch the program)
CONTROLS = {
    "served": (None, None),
    "state_in_bf16": (_state_in_bf16, None),
    "latent_in_8_bits": (None, _latent_in_8_bits),
    "latent_on_int8_grid": (None, _latent_on_int8_grid),
    "latent_pool_fp8": (None, None),
    "beta_2_sigmoid": (lambda m: setattr(m, "beta_scale", 2.0), None),
    "key_normed": (_normed_key, None),
    "no_routed_scaling": (lambda m: setattr(m, "routed_scale", 1.0), None),
    "rotary_applied": (_rotary, None),
    "dense_layer_routed": (_dense_layer_routed, None),
    "bf16_router": (None, _bf16_router),
}
# name -> (model, weights, seed) -> the served model's weights, where
# the change needs weights the configuration's model has none of
REWEIGH = {"dense_layer_routed": _experts_for_layer_0}
# name -> what the SERVED engine's ``DecodeConfig`` gets beside the
# configuration's (the check keeps the configuration's).  A pool that
# really holds 8-bit rows: the kernels are not built for them (the
# engine refuses ``kv_quant`` over a latent page for that reason), so
# the plain forms serve it; rows rounded to 8 bits INSIDE a 16-bit pool
# are ``latent_in_8_bits`` / ``latent_on_int8_grid``
SERVING = {"latent_pool_fp8": dict(cache_dtype="float8_e4m3fn",
                                   use_pallas="never")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", required=True,
                    help="one seed, or several with commas between")
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--slots", type=int, default=2)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import run as bench_run

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_compile_cache"))
    cell = bench_run.resolve_cell(ROOT, CELL)
    config, model_mod = cell["config"], cell["model"]
    pps = config["serving"]["max_seq_len"] // 16 + 1
    over = dict(slots=args.slots, num_pages=args.slots * pps + 1)
    served_config = dict(config, serving=dict(config["serving"], **over))
    for seed in map(int, args.seed.split(",")):
        seed_w, seed_check = (
            int(s) & 0x7FFFFFFF
            for s in np.random.SeedSequence(seed).generate_state(2))
        for name in args.only.split(","):
            ok, checks = run_control(cell, served_config, name, seed_w,
                                     seed_check)
            print(json.dumps({"control": name, "correct": bool(ok),
                              "seed": seed, **checks}), flush=True)


def run_control(cell, served_config, name, seed_w, seed_check):
    """Serve the cell's model under control ``name`` through the cell's
    kind's check -> (the verdict, the check's numbers).  ``cell`` as
    ``benchmark.run.resolve_cell`` gives it; ``served_config`` the
    configuration the check holds the served engine to."""
    from paddle_tpu.serving import DecodeServer

    config, model_mod = cell["config"], cell["model"]
    change_model, patch = CONTROLS[name]
    model, weights = model_mod.build(config, seed_w)
    served = weights
    if change_model:
        change_model(model)
    if name in REWEIGH:
        served = REWEIGH[name](model, weights, seed_w)
    undo = patch() if patch else None
    bench = types.SimpleNamespace(
        spec=cell["spec"], config=served_config,
        model=types.SimpleNamespace(
            reference_logits=model_mod.reference_logits,
            decode_config=model_mod.decode_config))
    srv = DecodeServer(model, served, model_mod.decode_config(
        served_config, **SERVING.get(name, {})), replicas=1)
    srv.start()
    try:
        return cell["kind"].check(bench, srv, weights, seed_check)
    finally:
        srv.stop(drain=False)
        if undo:
            undo()


if __name__ == "__main__":
    main()
