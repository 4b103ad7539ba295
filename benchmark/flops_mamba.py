"""Operations and bytes the state-space hybrid model's kernels cannot
avoid, computed from shapes AS PUBLISHED: the yardsticks of
``ssm_step_roofline`` (memory-bound at decode: a live slot's state, the
``d_state x d_inner`` matrix and the convolution's ``d_conv - 1`` rows,
read once and written once a recurrent layer, over ``peaks.json``'s HBM
bandwidth), ``ssm_prefill_roofline`` (a prompt's token recurrence:
float32 multiplies and adds and one exponential an entry of the state,
none of which a matrix unit takes) and ``ssm_proj_roofline.serve`` (the
mixer's four projections' weights, read once a step).

WHICH UNIT BINDS THE SCAN.  A token of a layer touches ``d_inner x
d_state`` entries; each costs one exponential on the transcendental unit
and six multiplies or adds on the vector unit (below).  At the rates
stated here a token-layer of 5,120 x 16 entries needs 81,920 x 6 /
6.14e12 = 0.080 us of the vector unit and 81,920 / 1.54e12 = 0.053 us of
the transcendental unit: THE VECTOR UNIT BINDS, by 1.5; the two run in
different slots of a bundle, so the larger is the floor, not their sum.
"""
from benchmark.flops_gated_delta import _DTYPE_BYTES
from benchmark.flops_linear_latent import VECTOR_F32_OPS_PER_S  # noqa: F401

# float32 exponentials a second one TensorCore's transcendental unit can
# take: ONE push a bundle x 1,024 lanes x the 1.5 GHz
# ``flops_linear_latent.VECTOR_F32_OPS_PER_S`` is stated at
TRANSCENDENTAL_F32_PER_S = 1 * 1024 * 1.5e9
# multiplies and adds an entry of the state a token, that the rule cannot
# avoid: dt * a (the exponential's argument), exp(.) * h, x * b, their
# sum, h * c, and the sum over d_state that makes y (one add an entry,
# less one a channel); x = dt * u and D * u are a d_state-th of that and
# are not counted, nor is what lays b and c down the sublanes: that is
# the kernel's cost, not the rule's
SCAN_OPS_PER_ENTRY = 6


def ssm_slot_bytes(d_state, d_inner, d_conv, itemsize=4):
    """Bytes ONE slot's state takes in ONE recurrent layer: the ``d_state
    x d_inner`` state and the ``d_conv - 1`` rows of the convolution's
    inputs, float32."""
    return (d_state * d_inner + (d_conv - 1) * d_inner) * itemsize


def ssm_state_bytes(slot_steps, layers, d_state, d_inner, d_conv):
    """Bytes the one-token update must move for ``slot_steps`` (live
    slot, decode step) pairs: each recurrent layer's state of a live
    slot read once and written once a step.  The token's own vectors
    (five rows of ``d_inner``) are a fiftieth of that and are not
    counted."""
    return slot_steps * layers * 2 * ssm_slot_bytes(d_state, d_inner, d_conv)


def ssm_scan_ops(token_layers, d_state, d_inner):
    """Float32 multiplies and adds of the token recurrence for
    ``token_layers`` (real prompt token, recurrent layer) pairs."""
    return token_layers * d_state * d_inner * SCAN_OPS_PER_ENTRY


def ssm_scan_exps(token_layers, d_state, d_inner):
    """Exponentials of the same: one an entry."""
    return token_layers * d_state * d_inner


def ssm_scan_least_s(token_layers, d_state, d_inner):
    """The least time the scan could take: the larger of its vector
    time and its transcendental time (the vector unit's, at these
    rates)."""
    return max(ssm_scan_ops(token_layers, d_state, d_inner)
               / VECTOR_F32_OPS_PER_S,
               ssm_scan_exps(token_layers, d_state, d_inner)
               / TRANSCENDENTAL_F32_PER_S)


def ssm_proj_params(d_model, d_inner, d_state, dt_rank):
    """{weight: parameters} of ONE state-space mixer's four projections:
    ``W_in`` (u | z), ``W_x`` (step | B | C), ``W_dt``, ``W_out``."""
    return {"in": d_model * 2 * d_inner,
            "x": d_inner * (dt_rank + 2 * d_state),
            "dt": dt_rank * d_inner, "out": d_inner * d_model}


def ssm_proj_bytes(read, d_model, d_inner, d_state, dt_rank,
                   dtype="bfloat16"):
    """Bytes of the projection matrices ``read`` (names of
    ``ssm_proj_params``, one a matrix a layer that a step reads)."""
    sizes = ssm_proj_params(d_model, d_inner, d_state, dt_rank)
    return sum(sizes[which] for which in read) * _DTYPE_BYTES[str(dtype)]
