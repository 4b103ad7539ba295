"""Bytes the parallel-block model's shared experts cannot avoid, computed
from shapes: the yardstick of ``shared_ffn_roofline.serve`` (memory-bound
at decode: the share divides these bytes by ``peaks.json``'s HBM
bandwidth and by a device time from the trace).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def shared_expert_bytes(layers, d_model, shared_experts, shared_dim,
                        dtype="bfloat16"):
    """Bytes of shared-expert weights ONE decode step reads: every layer
    runs every shared expert on every row, so all three ``d_model x
    shared_dim`` matrices of each are read once a step whatever the
    batch."""
    return layers * shared_experts * 3 * d_model * shared_dim \
        * _DTYPE_BYTES[str(dtype)]
