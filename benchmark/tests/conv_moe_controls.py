"""By hand, on the chip: the readings the limits of
``lfm2_8b_a1b.extract_closed_c128``'s check must FAIL.

    chiprun -- python3 benchmark/tests/conv_moe_controls.py --seed N[,M,...]
        [--only served,taps_reversed,...] [--slots 2]

Each control serves the cell's model at the configuration's widths with
ONE thing wrong (the reference keeps the configuration's model and
weights) through the cell's own kind's check (``kinds/serve_routed.py``
``check_logits`` with ``kinds/serve_conv_moe.py``'s size check and its
limit over the stated precision), on fewer slots than the cell so that a
dozen engines fit a call, and prints one JSON line: the check's verdict
and its numbers.  ``served`` is the model as it is.  ``all_bf16`` is the
program computing in the precision below the stated one and keeping what
it declares (its tails float32: ``bf16_tail`` is the other control);
``reference_in_bf16`` is the contract's own: the sound program serves,
and the logits it recorded are replaced by the REFERENCE's computed in
bfloat16 (``operands`` and ``results``) on the same tokens and routing,
before the check compares them.  Nothing here is run by the benchmark's
command.
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
CELL = "lfm2_8b_a1b.extract_closed_c128"


def _tail_zeroed(m):
    """Every joint step convolves with nothing behind its token."""
    import jax.numpy as jnp

    token = m._conv_token
    m._conv_token = lambda lw, rows, state, live=None: token(
        lw, rows, {"tail": jnp.zeros_like(state["tail"])}, live=live)


def _taps_reversed(weights):
    return dict(weights, layers=[
        dict(lw, conv_taps=lw["conv_taps"][::-1]) if "conv_taps" in lw
        else lw for lw in weights["layers"]])


def _no_b_gate(m):
    d = m.d_model
    m._gates = lambda bcu: (bcu[..., 2 * d:], bcu[..., d:2 * d])


def _no_c_gate(m):
    import jax.numpy as jnp

    d = m.d_model
    m._gates = lambda bcu: (bcu[..., :d] * bcu[..., 2 * d:],
                            jnp.ones_like(bcu[..., :d]))


def _bf16_tail(m):
    import jax.numpy as jnp

    m.recurrent_state = {"tail": (m.recurrent_state["tail"][0],
                                  jnp.bfloat16)}


def _patched_route(weights_of):
    """``moe_share_route`` with the chosen experts' weights replaced by
    ``weights_of(scores, ranked, ids)``."""
    def patch():
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops import moe_ops

        real = moe_ops.moe_share_route

        def changed(h, router_w, router_bias, *, top_k, held_ids,
                    live=None):
            ids, _, _ = real(h, router_w, router_bias, top_k=top_k,
                             held_ids=held_ids, live=live)
            scores = jax.nn.sigmoid(jnp.einsum(
                "...d,de->...e", h.astype(jnp.float32), router_w,
                precision=jax.lax.Precision.HIGHEST))
            w = weights_of(scores, scores + router_bias, ids)
            chosen = ids[..., :, None] == jnp.asarray(held_ids, jnp.int32)
            if live is not None:
                chosen = chosen & live[..., None, None]
            return ids, w, jnp.sum(
                jnp.where(chosen, w[..., None], 0.0), axis=-2)

        moe_ops.moe_share_route = changed
        return lambda: setattr(moe_ops, "moe_share_route", real)
    return patch


def _biased(scores, ranked, ids):
    import jax.numpy as jnp

    w = jnp.take_along_axis(ranked, ids, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True)


def _plain(scores, ranked, ids):
    import jax.numpy as jnp

    return jnp.take_along_axis(scores, ids, axis=-1)


def _no_qk_norm():
    """The norms over a head's lanes (the calls on ``[rows, heads,
    head_dim]``) pass their input through; the stream's stay."""
    from paddle_tpu.serving import conv_moe_lm

    real = conv_moe_lm.rms_norm
    conv_moe_lm.rms_norm = lambda x, g, eps: \
        x if x.ndim == 3 else real(x, g, eps)
    return lambda: setattr(conv_moe_lm, "rms_norm", real)


def _all_bf16():
    """Every projection's output, every norm's and the router's inputs
    rounded to bfloat16 (the experts' own matmuls keep their float32
    sums: they are ``ops/moe_ops.py``'s); the tails stay float32."""
    import jax

    from paddle_tpu.ops import moe_ops
    from paddle_tpu.serving import conv_moe_lm

    def bf16(x):
        # not a pair of casts: the compiler may drop those on the chip
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def rounded(fn):
        return lambda *a: bf16(fn(*a))

    real = (conv_moe_lm._mm, conv_moe_lm.rms_norm, moe_ops.moe_share_route)
    conv_moe_lm._mm = rounded(real[0])
    conv_moe_lm.rms_norm = rounded(real[1])

    def route(h, router_w, router_bias, **kw):
        return real[2](bf16(h), bf16(router_w), router_bias, **kw)

    moe_ops.moe_share_route = route

    def undo():
        conv_moe_lm._mm, conv_moe_lm.rms_norm = real[:2]
        moe_ops.moe_share_route = real[2]
    return undo


# name -> (change the served model, change the served weights, patch)
CONTROLS = {
    "served": (None, None, None),
    "tail_zeroed_at_every_step": (_tail_zeroed, None, None),
    "taps_reversed": (None, _taps_reversed, None),
    "no_b_gate": (_no_b_gate, None, None),
    "no_c_gate": (_no_c_gate, None, None),
    "bias_in_the_weights_too": (None, None, _patched_route(_biased)),
    "weights_not_renormalised": (None, None, _patched_route(_plain)),
    "top_3": (lambda m: setattr(m, "top_k", 3), None, None),
    "no_qk_norm": (None, None, _no_qk_norm),
    "rope_base_1e4": (lambda m: setattr(m, "rope_theta", 1e4), None, None),
    "bf16_tail": (_bf16_tail, None, None),
    "all_bf16": (None, None, _all_bf16),
    "reference_in_bf16": (None, None, None),
}


class _ReferenceAnswers:
    """A server whose requests come back with the tokens and routing
    they had and, for the logits they recorded, ``logits_of(prompt,
    tokens, records)``."""

    def __init__(self, srv, logits_of):
        self._srv, self._logits_of = srv, logits_of

    def submit(self, prompt, **kw):
        req, logits_of = self._srv.submit(prompt, **kw), self._logits_of
        answer = types.SimpleNamespace(records=req.records)

        def result(timeout=None):
            toks = req.result(timeout=timeout)
            answer.logits_trace = logits_of(prompt, toks, req.records)
            return toks

        answer.result = result
        return answer


def _reference_in_bf16(model_mod, config, weights, chk):
    import jax.numpy as jnp
    import numpy as np

    pad, n_new = int(chk["pad"]), int(chk["new_tokens"])

    def logits_of(prompt, toks, records):
        n = len(prompt) + n_new - 1
        seq = np.zeros((pad,), np.int32)
        seq[:n] = list(prompt) + list(toks[:n_new - 1])
        routed = records["moe_topk"]
        ids = np.concatenate([routed[0]] + [x[None] for x in routed[1:]])
        routing = np.zeros((pad,) + ids.shape[1:], np.int32)
        routing[:n] = ids[:n]
        got, _ = model_mod.reference_logits(
            config, weights, jnp.asarray(seq), routing=routing,
            rows=(len(prompt) - 1, n_new),
            dims_={"operands": "bfloat16", "results": "bfloat16"})
        return list(np.asarray(got))

    return logits_of


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", required=True,
                    help="one seed, or several with commas between")
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--slots", type=int, default=2)
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as bench_run

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_compile_cache"))
    cell = bench_run.resolve_cell(ROOT, CELL)
    model_mod = cell["model"]
    routed = cell["kind"].routed_kind(cell)
    over = dict(slots=args.slots, num_pages=args.slots * 161 + 1)
    config = dict(cell["config"],
                  serving=dict(cell["config"]["serving"], **over))
    for seed in map(int, args.seed.split(",")):
        _one_seed(seed, args, cell, model_mod, routed, config)


def _one_seed(seed, args, cell, model_mod, routed, config):
    import numpy as np

    from paddle_tpu.serving import DecodeServer

    seed_w, seed_check = (int(s) & 0x7FFFFFFF for s in
                          np.random.SeedSequence(seed).generate_state(2))
    for name in args.only.split(","):
        change_model, change_weights, patch = CONTROLS[name]
        model, weights = model_mod.build(config, seed_w)
        if change_model:
            change_model(model)
        undo = patch() if patch else None
        bench = types.SimpleNamespace(
            spec=cell["spec"], config=config, model=types.SimpleNamespace(
                reference_logits=model_mod.reference_logits))
        srv = DecodeServer(
            model, change_weights(weights) if change_weights else weights,
            model_mod.decode_config(config), replicas=1)
        srv.start()
        answers = srv if name != "reference_in_bf16" else _ReferenceAnswers(
            srv, _reference_in_bf16(model_mod, config, weights,
                                    cell["spec"]["check"]))
        try:
            ok, checks = routed.check_logits(bench, answers, weights,
                                             seed_check)
        finally:
            srv.stop(drain=False)
            if undo:
                undo()
        print(json.dumps({"control": name, "correct": bool(ok),
                          "seed": seed, **checks}), flush=True)
        del srv, answers, model, weights
        gc.collect()    # an engine's threads and closures hold its pools


if __name__ == "__main__":
    main()
