"""Bytes the hybrid model's new kernels cannot avoid, computed from
shapes: the yardstick of ``kda_state_roofline`` and
``moe_experts_roofline`` (both memory-bound at decode: a roofline share
divides these bytes by ``peaks.json``'s HBM bandwidth and by a device
time from the trace).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def kv_bytes_per_token(attention_layers, kv_heads, head_dim,
                       cache_dtype="float32"):
    """Bytes of K and V one cached position holds, over the layers that
    have keys (grouped-query: ``kv_heads`` of them)."""
    return 2 * attention_layers * kv_heads * head_dim \
        * _DTYPE_BYTES[str(cache_dtype)]


def kda_state_bytes(slot_steps, layers, heads, head_dim, itemsize=4):
    """Bytes the gated-delta-rule update must move for ``slot_steps``
    (live slot, decode step) pairs: each recurrent layer's ``[heads,
    d_k, d_v]`` state of a live slot is read once and written once a
    step.  The convolution tail and the token's own rows are a
    thousandth of that and are not counted."""
    return slot_steps * layers * 2 * heads * head_dim * head_dim * itemsize


def moe_expert_bytes(experts_hit, d_model, expert_dim, itemsize=2):
    """Bytes of routed-expert weights a decode step cannot avoid
    reading: three ``d_model x expert_dim`` matrices an expert that some
    live row chose (``experts_hit``: summed over layers and steps).  An
    expert nobody chose is not counted, so a kernel that reads it anyway
    reads below 100 %."""
    return experts_hit * 3 * d_model * expert_dim * itemsize
