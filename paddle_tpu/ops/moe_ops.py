"""Mixture-of-experts routed FFN: top-k routing, capacity-factor
dispatch, stacked per-expert einsums, all-to-all combine.

Role parity: the reference's incubate MoE layer (distributed expert
parallelism over its fleet collectives).  TPU-native shape (GShard/
Switch lineage): the router scores every token against E experts,
keeps the top-k gates, and DISPATCHES tokens into a dense
[E, capacity, D] buffer — a static shape, so one compiled executable
serves every routing outcome; tokens past an expert's capacity are
DROPPED (their combine weight is zero, so the residual stream simply
passes them through unchanged).  Expert FFNs run as ONE stacked einsum
per chip over the locally-resident experts ([E, D, H] weights), and
the combine einsum scatters expert outputs back to token order.

Expert parallelism is pure GSPMD: when the plan stamped the op
(``__moe_ep__``) and the mesh has an 'ep' axis, the [E, C, D] dispatch
buffer is sharding-constrained to ``P('ep', None, None)`` — XLA
materializes the dispatch all-to-all in front of the expert compute
and the combine all-to-all behind it.  Latency hiding generalizes the
PR 15 collective-matmul chunking to all-to-all: slice the CAPACITY
axis into FLAGS_moe_alltoall_chunks chunks, so chunk k's all-to-all
overlaps chunk k+1's expert einsums.  Chunk outputs are CONCATENATED
and combined once — every (e, c) slot's compute is independent along
the capacity axis, so chunked and sequential schedules are
bitwise-identical by construction (the A/B the bench asserts).

The pure-jnp reference (``moe_ffn_ref``) is the CPU/tier-1 default and
the only path tier-1 exercises — no Pallas anywhere in this op.  The
router's aux loss is the Switch load-balance loss
``E * sum_e f_e * P_e`` (f_e = fraction of tokens whose TOP-1 choice
is e, P_e = mean router probability of e): differentiable through
P_e, so the generic vjp gives the router gradient for free.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.lowering import register_lower

__all__ = [
    "moe_capacity",
    "moe_router_ref",
    "moe_ffn_ref",
    "moe_balance_gauges",
    "moe_share_route",
    "moe_share_counts",
    "moe_share_ffn",
    "grouped_rule",
    "hit_rule",
    "expected_hit_share",
    "ridge_rows",
    "GROUPED_TALLIES",
    "HIT_TALLIES",
]


def moe_capacity(num_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Static per-expert slot count: ceil(S*K/E * factor), >= 1."""
    return max(1, int(math.ceil(
        num_tokens * top_k * capacity_factor / num_experts)))


# ---------------------------------------------------------------------------
# router (pure jnp; shared by training lowering and serving)
# ---------------------------------------------------------------------------


def moe_router_ref(x2d, gate_w, *, num_experts, top_k, capacity_factor):
    """Route [S, D] tokens: returns (combine [S,E,C] f32, aux_loss
    scalar, expert_load [E] f32 kept-token counts).

    Deterministic: ties in top-k resolve by lax.top_k's stable index
    order, and capacity slots are claimed in (choice, token) order —
    choice 0 of every token outranks choice 1 of any token, and within
    a choice lower token index wins (the GShard priority rule).
    """
    s = x2d.shape[0]
    e = int(num_experts)
    k = int(top_k)
    cap = moe_capacity(s, e, k, capacity_factor)

    logits = jnp.einsum("sd,de->se", x2d.astype(jnp.float32),
                        gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # [S, E]
    gate_vals, gate_idx = lax.top_k(probs, k)                  # [S, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    combine = jnp.zeros((s, e, cap), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)   # slots claimed per expert
    for choice in range(k):
        oh = jax.nn.one_hot(gate_idx[:, choice], e,
                            dtype=jnp.float32)                 # [S, E]
        # slot index of each token within its expert: tokens of this
        # choice queue behind every earlier choice's claims
        pos = jnp.cumsum(oh, axis=0) - oh + counts[None, :]    # [S, E]
        slot = jnp.sum(pos * oh, axis=-1)                      # [S]
        # one_hot zeroes out-of-range slots, so slot >= cap == dropped
        slot_oh = jax.nn.one_hot(slot, cap, dtype=jnp.float32)
        slot_oh = slot_oh * jnp.sum(oh, axis=-1, keepdims=True)
        combine = combine + (gate_vals[:, choice, None, None]
                             * oh[:, :, None] * slot_oh[:, None, :])
        counts = counts + jnp.sum(oh, axis=0)

    expert_load = jnp.sum(combine > 0.0, axis=(0, 2)).astype(jnp.float32)
    # Switch aux loss: top-1 assignment fraction x mean router prob
    f = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                 axis=0)
    p = jnp.mean(probs, axis=0)
    aux_loss = jnp.asarray(e, jnp.float32) * jnp.sum(
        lax.stop_gradient(f) * p)
    return combine, aux_loss, expert_load


# ---------------------------------------------------------------------------
# expert FFN body
# ---------------------------------------------------------------------------


def _expert_ffn(dispatched, w1, b1, w2, b2):
    """[E, C', D] dispatched slots -> [E, C', D] expert outputs; one
    stacked einsum pair over the locally-resident experts."""
    h = jnp.einsum("ecd,edh->ech", dispatched, w1) + b1[:, None, :]
    h = jax.nn.gelu(h)
    return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]


def _ep_constraint(val, mesh, spec):
    from jax.sharding import NamedSharding, PartitionSpec

    return lax.with_sharding_constraint(
        val, NamedSharding(mesh, PartitionSpec(*spec)))


def moe_ffn_ref(x, gate_w, w1, b1, w2, b2, *, num_experts, top_k,
                capacity_factor, mesh=None, ep=False, chunks=0):
    """Full routed FFN over x [..., D] -> (out [..., D], aux_loss,
    expert_load [E]).  ``ep=True`` + a mesh with an 'ep' axis adds the
    GSPMD sharding constraints that materialize the dispatch/combine
    all-to-alls; ``chunks`` > 1 slices the capacity axis (bitwise-equal
    to the sequential schedule, see module docstring)."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2d = x.reshape((-1, d))
    combine, aux_loss, expert_load = moe_router_ref(
        x2d, gate_w, num_experts=num_experts, top_k=top_k,
        capacity_factor=capacity_factor)
    cap = combine.shape[-1]
    dispatch = (combine > 0.0).astype(x2d.dtype)               # [S,E,C]
    combine = combine.astype(x2d.dtype)

    use_ep = bool(ep) and mesh is not None and "ep" in getattr(
        mesh, "axis_names", ())
    k = int(chunks or 0)
    chunked = k > 1 and cap % k == 0

    def body(disp_slice):
        buf = jnp.einsum("sec,sd->ecd", disp_slice, x2d)
        if use_ep:
            buf = _ep_constraint(buf, mesh, ("ep", None, None))
        y = _expert_ffn(buf, w1, b1, w2, b2)
        if use_ep:
            y = _ep_constraint(y, mesh, ("ep", None, None))
        return y

    if chunked:
        cc = cap // k
        y = jnp.concatenate(
            [body(dispatch[:, :, i * cc:(i + 1) * cc])
             for i in range(k)], axis=1)
    else:
        y = body(dispatch)
    out = jnp.einsum("sec,ecd->sd", combine, y)
    if use_ep:
        # token order is the caller's layout again: pin it replicated
        # over 'ep' so the combine all-to-all lands HERE, not later
        out = _ep_constraint(out, mesh, (None, None))
    return out.reshape(lead + (d,)), aux_loss, expert_load, chunked


# ---------------------------------------------------------------------------
# gauges (host-side; bench + serving)
# ---------------------------------------------------------------------------


def moe_balance_gauges(expert_load, num_tokens: int, top_k: int,
                       publish: bool = True):
    """Utilization gauges from one step's kept-token counts: balance =
    mean/max load in ppm (1e6 = perfectly even), dropped fraction of
    routed assignments in ppm.  Published via monitor stat_set."""
    import numpy as np

    load = np.asarray(expert_load, dtype=np.float64)
    routed = float(max(1, num_tokens * top_k))
    kept = float(load.sum())
    balance = float(load.mean() / load.max()) if load.max() > 0 else 0.0
    gauges = {
        "moe_expert_balance_ppm": int(balance * 1e6),
        "moe_dropped_fraction_ppm": int(
            max(0.0, 1.0 - kept / routed) * 1e6),
    }
    if publish:
        from ..monitor import stat_set

        for key, val in gauges.items():
            stat_set(key, val)
    return gauges


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------


def _dequant_stacked(carrier, scale):
    """Per-expert per-output-channel dequant of a stacked [E, *, O]
    carrier with scale [E, O] (ops/quant_ops.quantize_weight_stacked)."""
    return carrier.astype(scale.dtype) * scale[:, None, :]


@register_lower("moe_ffn")
def _moe_ffn_lower(ctx, op):
    from ..framework import flags as _flags
    from ..framework.passes import MOE_EP_ATTR
    from ..monitor import stat_add

    x = ctx.in1(op, "X")
    gate_w = ctx.in1(op, "GateW")
    w1 = ctx.in1(op, "W1")
    b1 = ctx.in1(op, "B1")
    w2 = ctx.in1(op, "W2")
    b2 = ctx.in1(op, "B2")
    s1 = ctx.in1(op, "W1Scale")
    s2 = ctx.in1(op, "W2Scale")
    if s1 is not None:
        w1 = _dequant_stacked(w1, s1)
    if s2 is not None:
        w2 = _dequant_stacked(w2, s2)

    chunks = int(_flags.flag("moe_alltoall_chunks") or 0)
    ep = bool(op.attr(MOE_EP_ATTR, False))
    manual = bool(getattr(ctx, "axis_env", ()) or ())
    if ep and manual:
        # The GPipe pipeline traces inside a shard_map with EVERY mesh
        # axis manual, where GSPMD sharding constraints are illegal —
        # and a manual slab/psum expert split would need the router's
        # gate gradient psum'd over 'ep', which the pipeline's grad
        # accumulation (dp-only) does not do.  Experts therefore stay
        # REPLICATED inside pipeline stages: each rank computes the
        # full routed FFN bitwise-identically, the plan's ep marks
        # still price the intended all-to-alls in the ledger, and this
        # counter records the runtime fallback.
        stat_add("moe_ep_manual_replicated")
        ep = False
    out, aux, load, chunked = moe_ffn_ref(
        x, gate_w, w1, b1, w2, b2,
        num_experts=int(op.attr("num_experts")),
        top_k=int(op.attr("top_k", 1)),
        capacity_factor=float(op.attr("capacity_factor", 1.0)),
        mesh=ctx.mesh, ep=ep, chunks=chunks)
    stat_add("moe_ffn_engaged")
    if chunked:
        stat_add("moe_alltoall_chunked")
    elif chunks > 1:
        stat_add("moe_alltoall_fallback")
    ctx.set_out(op, "Out", out)
    ctx.set_out(op, "AuxLoss", jnp.reshape(aux, (1,)))
    ctx.set_out(op, "ExpertLoad", load)


# ---------------------------------------------------------------------------
# an expert layer that holds a SHARE of the experts (serving)
# ---------------------------------------------------------------------------
# Expert parallelism seen from one chip: the router keeps its full width,
# every token picks its top-k over ALL experts, and this chip computes the
# part of the result its own experts give.  Nothing is dropped and nothing
# is sized by a capacity that drops: the held experts' weights are three
# plain matrices ([D, n_held*F], [D, n_held*F], [n_held*F, D]) and one
# result has three forms, chosen by the call's static shape and the
# model's published ``top_k`` / ``num_experts`` (``grouped_rule``,
# ``hit_rule``; no flag, the same answer on every backend).
# DENSE, few rows that between them choose nearly every held expert (a
# decode step where a chip holds a large share of the experts): every row
# runs through all three matrices and a row's hidden units of an expert
# it did not choose are multiplied by zero: three matmuls that stream
# the weights once and are bound by that read.
# HIT, few rows that are expected to leave held experts un-chosen (a
# decode step where a chip holds few experts of many): the dense form
# would stream weights that are multiplied by nothing but zeros, so one
# kernel walks the list of held experts some live row chose and reads
# those alone (``ops/pallas_moe_hit.py``); every row still meets every
# HIT expert, the weight decides.
# GROUPED, a prefill's rows: the dense form would pay n_held / (local
# assignments a row) times the multiply-adds, so the (row, held expert)
# pairs with a weight are sorted by expert and run as a grouped matmul
# (``ops/pallas_moe_grouped.py``): the weights still read once, where
# they lie, and a row meets only the experts it chose (PERF.md says what
# each form costs).
# The scopes name the ops in lowered text; a trace's device events carry
# the HLO instruction, whose operands name the weights they read.

ROUTE_SCOPE = "moe_route"
EXPERTS_SCOPE = "moe_experts"


def moe_share_route(h, router_w, router_bias, *, top_k, held_ids,
                    live=None, scoring="sigmoid"):
    """Routing of rows ``h [..., D]`` over ALL experts
    (``router_w [D, E]``, float32 at ``highest``: a score's rounding
    decides the top-k), for a chip that holds ``held_ids`` ([n_held]
    expert ids).  A score is the logit's ``scoring``: ``"sigmoid"``
    (an expert's own) or ``"softmax"`` (over all the experts).  The
    top-k is taken by score + ``router_bias`` [E] (the load-balance
    correction: the model's own buffer, zero in four of
    the served configurations and drawn from the seed in one); the
    weights are the plain scores, WITHOUT the bias, normalised over the
    chosen k.  ``live`` (rows' shape, bool)
    takes dead and padding rows out: they choose and weigh as any row,
    and give this chip nothing to compute.

    Returns ``(ids [..., K] int32, weights [..., K] f32, local
    [..., n_held] f32)``: ``local[r, j]`` is row r's weight for held
    expert j, zero where it did not choose it."""
    with jax.named_scope(ROUTE_SCOPE):
        score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}
        scores = score[scoring](jnp.einsum(
            "...d,de->...e", h.astype(jnp.float32),
            router_w.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, ids = lax.top_k(scores + router_bias.astype(jnp.float32),
                           int(top_k))
        weights = jnp.take_along_axis(scores, ids, axis=-1)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        held = jnp.asarray(held_ids, jnp.int32)
        chosen = ids[..., :, None] == held                  # [..., K, n]
        if live is not None:
            chosen = chosen & live[..., None, None]
        local = jnp.sum(jnp.where(chosen, weights[..., None], 0.0),
                        axis=-2)
    return ids.astype(jnp.int32), weights, local


def moe_share_counts(local):
    """(local assignments, held experts hit) of one routed layer, int32
    scalars: how many (row, chosen expert) pairs this chip computes and
    how many of its experts some row chose."""
    hit = (local > 0.0).reshape(-1, local.shape[-1])
    return (jnp.sum(hit, dtype=jnp.int32),
            jnp.sum(jnp.any(hit, axis=0), dtype=jnp.int32))


# what the grouped form counts of the calls it takes, through ``tally``
GROUPED_TALLIES = ("moe_grouped_pairs", "moe_grouped_rows_dense",
                   "moe_grouped_extra_passes")
# measured on the v5e, alone, 40 experts of 1,280: 256 rows read 2.1 ms
# dense and 2.8 grouped, 512 rows 3.7 and 3.2 (PERF.md, PR 43): the
# crossover lies between one ridge and two, nearer two
GROUPED_FROM_RIDGES = 2.0


def ridge_rows():
    """Rows at which the dense form's multiply-adds take as long as the
    read of the weights it cannot avoid: the attached chip's bf16 peak
    over its HBM rate (``observe/device_peaks.py``; the v5e's 197
    TFLOP/s over 819 GB/s = 240.5).  A process whose device has no row
    there (the CPU: tests, a compile for a described chip) takes the
    v5e's, the part every cell runs on."""
    from ..observe.device_peaks import DEVICE_PEAKS, device_peak

    row = device_peak() or DEVICE_PEAKS["TPU v5 lite"]
    return row["bf16_tflops"] * 1e3 / row["hbm_gbps"]


def grouped_rule(rows, n_held, expert_dim, d_model, top_k=None,
                 num_experts=None):
    """Whether a call of ``rows`` rows over ``n_held`` experts of
    ``expert_dim`` hidden units takes the grouped form: a function of
    the call's static shape and, where given, the model's published
    routing (``top_k`` of ``num_experts``: they size the sorted buffer
    where every expert is held, ``pallas_moe_grouped.pairs_a_row``).
    Under ``GROUPED_FROM_RIDGES`` ridges
    the dense form is bound by the weight read, or nearly, and the
    grouped form's own passes (the pairs to their slots, the rows
    gathered, the result's blocks read and written) buy nothing.  The
    sorted buffer's tiles at their worst (its pairs a row, every
    expert's run ending a tile early) are computed whole: they must be
    under half the dense form's rows x experts.  And the kernels' blocks
    are whole lanes: a width no 128 divides keeps the dense form."""
    from .pallas_moe_grouped import sorted_rows

    return (rows >= GROUPED_FROM_RIDGES * ridge_rows()
            and 2 * sorted_rows(rows, n_held, top_k, num_experts)
            <= rows * n_held
            and expert_dim % 128 == 0 and d_model % 128 == 0)


# what the hit form counts of the calls it takes, through ``tally``: the
# calls, and the held experts whose weights such a call did not read
HIT_TALLIES = ("moe_hit_form_calls", "moe_experts_skipped")
# the expected share of the held experts some row chooses, under which
# the hit form takes a call.  Measured on the v5e, alone, at the four
# cells' step shapes (PERF.md, PR 48): the kernel reads a hit expert at
# 81-88 % of the HBM rate where the dense form reads all of them at 90 %,
# and is the faster wherever under 90 % of the held experts are hit (the
# crossovers: 97, 90, 90.5 and 98 %); a trained or seeded router hits
# fewer than uniform choices would, so the expectation errs to the dense
# form's side
HIT_BELOW_SHARE = 0.9


def expected_hit_share(rows, top_k, num_experts):
    """The share of a chip's held experts that ``rows`` rows choose at
    least once, were each row's ``top_k`` of ``num_experts`` uniform:
    what the call's shape says about the weights it has to read."""
    return 1.0 - (1.0 - top_k / num_experts) ** rows


def hit_rule(rows, n_held, expert_dim, d_model, top_k, num_experts):
    """Whether a call of ``rows`` rows over ``n_held`` held experts of a
    model that routes ``top_k`` of ``num_experts`` takes the hit form: a
    function of the call's static shape and the model's published
    routing alone.  Under a ridge the weight read is the cost, and the
    hit form reads ``expected_hit_share`` of what the dense form reads,
    a little slower a byte: it takes the calls whose expected share is
    under ``HIT_BELOW_SHARE``.  The kernel's blocks are whole lanes: a
    width no 128 divides keeps the dense form."""
    return (rows < ridge_rows()
            and expected_hit_share(rows, top_k, num_experts)
            < HIT_BELOW_SHARE
            and expert_dim % 128 == 0 and d_model % 128 == 0)


def moe_share_ffn(h, local, w_gate, w_up, w_down, *, tally=None,
                  interpret=False, top_k=None, num_experts=None):
    """The held experts' part of the routed result for rows ``h
    [..., D]``: ``sum_j local[r, j] * E_j(h_r)`` with ``E(h) =
    (silu(h W_gate) * h W_up) W_down``.  ``w_gate``/``w_up`` are
    ``[D, n_held*F]`` (expert j's columns ``j*F:(j+1)*F``), ``w_down``
    ``[n_held*F, D]``; matmuls take the weights' dtype in and float32
    out.  Dropless under any imbalance.  Many rows (``grouped_rule``):
    the pairs with a weight, sorted by expert, as a grouped matmul.
    ``top_k`` of ``num_experts`` (static; the model's own) size that
    form's buffer where every expert is held.  Few rows of a model
    whose routing leaves held experts un-chosen (``hit_rule``; never
    where the two are not given): every row meets every held expert
    some row chose, and the others' weights are not read.  Else every
    row meets every held expert, the weight decides.  One sum to
    float32's order of summation.  ``tally(name, n)``, where given,
    takes what the grouped form counts (``GROUPED_TALLIES``: the pairs
    it computed, rows x ``n_held`` of the same calls, the passes over
    the sorted buffer beyond a call's first) and the hit form
    (``HIT_TALLIES``: its calls, the held experts it skipped).
    ``interpret`` runs either form's kernels interpreted (tests on
    the CPU, as ``DecodeConfig.interpret`` does the attention's); with
    no chip and without it the kernels fail to lower, loudly."""
    n_held = local.shape[-1]
    rows = math.prod(h.shape[:-1])
    shape = (rows, n_held, w_down.shape[0] // n_held, h.shape[-1])
    with jax.named_scope(EXPERTS_SCOPE):
        if grouped_rule(*shape, top_k, num_experts):
            from .pallas_moe_grouped import grouped_share_ffn

            out, pairs, passes = grouped_share_ffn(
                h, local, w_gate, w_up, w_down, interpret=interpret,
                top_k=top_k, num_experts=num_experts)
            if tally is not None:
                for name, n in zip(GROUPED_TALLIES, (
                        pairs, rows * n_held, jnp.maximum(passes - 1, 0))):
                    tally(name, n)
            return out
        if top_k is not None and hit_rule(*shape, top_k, num_experts):
            from .pallas_moe_hit import hit_share_ffn

            out, n_hit = hit_share_ffn(
                h, local, w_gate, w_up, w_down, interpret=interpret)
            if tally is not None:
                for name, n in zip(HIT_TALLIES, (1, n_held - n_hit)):
                    tally(name, n)
            return out
        x = h.astype(w_gate.dtype)
        gate = jnp.matmul(x, w_gate, preferred_element_type=jnp.float32)
        up = jnp.matmul(x, w_up, preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gate) * up).reshape(
            *h.shape[:-1], n_held, -1) * local[..., None]
        return jnp.matmul(
            act.reshape(*h.shape[:-1], -1).astype(w_down.dtype), w_down,
            preferred_element_type=jnp.float32)
