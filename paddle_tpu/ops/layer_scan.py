"""Lowering of the LayerScanPass region ops (framework/passes.py).

``layer_scan`` — ONE ``jax.lax.scan`` whose body lowers the template
block (the first segment of an isomorphic repeated-layer run) once:
per-layer weights arrive stacked on a leading ``num_layers`` axis as
scan xs, the chained activation/gradient flows through the carry, and
per-layer outputs come back as stacked ys.  The RNG key threads through
the carry so the split chain is BITWISE the one the unrolled program
would draw (iteration k performs exactly the splits unrolled layer k
performed, in the same order).  The body is optionally wrapped in
``jax.checkpoint`` under the pass's remat policy (``wrap_checkpoint``
below maps the framework's policy names onto ``jax.checkpoint_policies``).

``layer_index`` — materializes one per-layer member out of a stacked
carrier for the few consumers the pass left unrolled (an edge layer a
trimmed run excluded, a fetch of a mid-stack activation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.lowering import (LoweringContext, apply_tp_constraints,
                                  get_lowering, register_lower)

# framework-facing policy names -> jax.checkpoint_policies attr names
# ("save_anything" is this framework's spelling of "do not recompute
# anything the body produced" == everything_saveable)
_CHECKPOINT_POLICY_NAMES = {
    "nothing_saveable": "nothing_saveable",
    "dots_saveable": "dots_saveable",
    "checkpoint_dots": "dots_saveable",  # historical jax alias
    "save_anything": "everything_saveable",
    "everything_saveable": "everything_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
}

REMAT_POLICIES = tuple(_CHECKPOINT_POLICY_NAMES)


def checkpoint_policy(name):
    """The ``jax.checkpoint_policies`` callable behind a framework
    policy name; None for the empty name (no remat wrap)."""
    if not name:
        return None
    if name not in _CHECKPOINT_POLICY_NAMES:
        raise ValueError(f"unknown remat policy {name!r}; expected one of "
                         f"{sorted(REMAT_POLICIES)}")
    return getattr(jax.checkpoint_policies, _CHECKPOINT_POLICY_NAMES[name])


def wrap_checkpoint(fn, policy_name: str = ""):
    """``jax.checkpoint(fn, policy=<resolved>)``.  With ``policy_name``
    empty the wrap is skipped entirely — primal values are
    bitwise-identical either way, so the un-wrapped body stays the
    cheapest default."""
    if not policy_name:
        return fn
    return jax.checkpoint(fn, policy=checkpoint_policy(policy_name))


def _ints(op, name):
    return [int(v) for v in (op.attr(name, []) or [])]


def _strs(op, name):
    return [str(v) for v in (op.attr(name, []) or [])]


@register_lower("layer_scan")
def _layer_scan(ctx: LoweringContext, op):
    from ..framework import flags
    from ..framework.passes import TP_CONSTRAINT_ATTR

    program = ctx.program
    tblock = program.blocks[int(op.attr("layer_block"))]
    n_layers = int(op.attr("num_layers"))

    carry_in_tpl = _strs(op, "carry_in_tpl")
    carry_out_tpl = _strs(op, "carry_out_tpl")
    shared_names = op.inputs.get("Shared", [])
    xs_tpl = _strs(op, "xs_tpl")
    xs_src = _strs(op, "xs_src")
    xs_flip = _ints(op, "xs_flip")
    xs_start = _ints(op, "xs_start")
    xs_stop = _ints(op, "xs_stop")
    ys_tpl = _strs(op, "ys_tpl")
    ys_pre = _ints(op, "ys_pre")
    ys_flip = _ints(op, "ys_flip")
    ys_ustart = _ints(op, "ys_update_start")

    # -- assemble the scan xs ---------------------------------------------
    stacked_in = list(op.inputs.get("StackedIn", []))
    gather_in = list(op.inputs.get("GatherIn", []))
    xs_vals = []
    si = gi = 0
    for i in range(len(xs_tpl)):
        if xs_src[i] == "c":
            v = ctx.get(stacked_in[si])
            si += 1
            if xs_start[i] >= 0:
                v = v[xs_start[i]:xs_stop[i]]
            if xs_flip[i]:
                v = jnp.flip(v, axis=0)
        else:  # "g": members exist individually; stack at trace time
            v = jnp.stack([ctx.get(n)
                           for n in gather_in[gi:gi + n_layers]], axis=0)
            gi += n_layers
        xs_vals.append(v)

    shared_vals = {n: ctx.get(n) for n in shared_names}
    init = tuple(ctx.get(n) for n in op.inputs.get("CarryIn", []))
    has_key = ctx.rng_key is not None
    consumed = [False]
    mesh = ctx.mesh

    def body(carry, x):
        if has_key:
            key, cvals = carry[0], carry[1:]
        else:
            key, cvals = None, carry
        env = dict(shared_vals)
        env.update(zip(carry_in_tpl, cvals))
        if xs_tpl:
            env.update(zip(xs_tpl, x))
        bctx = LoweringContext(tblock, env, rng_key=key, mesh=mesh,
                               axis_env=ctx.axis_env,
                               ring_axes=ctx.ring_axes,
                               fold_axes=ctx.fold_axes)
        # pre-ys (a carry's value at iteration START) snapshot before
        # the body may rebind the name
        pre_vals = {t: env[t] for t, p in zip(ys_tpl, ys_pre) if p}
        for top in tblock.ops:
            try:
                get_lowering(top.type)(bctx, top)
                if mesh is not None and top.has_attr(TP_CONSTRAINT_ATTR):
                    apply_tp_constraints(env, top, mesh)
            except Exception as e:
                site = top.callstack[-1] if top.callstack else "<unknown>"
                raise type(e)(
                    f"while lowering op {top.type!r} inside layer_scan "
                    f"(built at {site}): {e}") from e
        consumed[0] = consumed[0] or bctx.rng_consumed
        ys = tuple(pre_vals[t] if p else env[t]
                   for t, p in zip(ys_tpl, ys_pre))
        new_carry = tuple(env[w] for w in carry_out_tpl)
        if has_key:
            new_key = bctx.rng_key if bctx.rng_consumed else key
            return (new_key,) + new_carry, ys
        return new_carry, ys

    body = wrap_checkpoint(body, str(op.attr("remat_policy", "") or ""))
    init_carry = ((ctx.rng_key,) + init) if has_key else init
    final_carry, ys_stacks = lax.scan(
        body, init_carry, tuple(xs_vals) if xs_vals else None,
        length=n_layers,
        unroll=max(int(flags.flag("layer_scan_unroll") or 1), 1))

    if has_key:
        new_key, final_vals = final_carry[0], final_carry[1:]
        if consumed[0]:
            ctx._rng = new_key
            ctx.rng_consumed = True
    else:
        final_vals = final_carry

    for name, v in zip(op.outputs.get("CarryOut", []), final_vals):
        ctx.set(name, v)
    for i, (name, v) in enumerate(zip(op.outputs.get("StackedOut", []),
                                      ys_stacks)):
        if ys_flip[i]:
            v = jnp.flip(v, axis=0)
        if ys_ustart[i] >= 0:
            # in-place slice update of an existing carrier (a trimmed
            # run updating the middle of a wider weight stack)
            cur = ctx.get(name)
            v = cur.at[ys_ustart[i]:ys_ustart[i] + n_layers].set(v)
        ctx.set(name, v)


@register_lower("layer_index")
def _layer_index(ctx: LoweringContext, op):
    x = ctx.in1(op, "X")
    ctx.set_out(op, "Out", x[int(op.attr("index", 0))])
