"""Bytes and operations the convolution/attention model with every
expert held cannot avoid, computed from shapes: what a cached token
and a slot's tails take, and the yardstick of ``moe_prefill_roofline``
(compute-bound at a prompt: the real pairs' multiply-adds over the bf16
peak over the grouped kernels' time).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def kv_bytes_per_token(attention_layers, kv_heads, head_dim,
                       cache_dtype="float32"):
    """Bytes of K and V one cached position holds, over the layers that
    have keys (a convolution layer has none)."""
    return 2 * attention_layers * kv_heads * head_dim \
        * _DTYPE_BYTES[str(cache_dtype)]


def conv_tail_bytes(slots, layers, conv_kernel, d_model, itemsize=4):
    """Device bytes of the convolution layers' tails: ``conv_kernel -
    1`` rows of ``d_model`` a slot a layer, float32."""
    return slots * layers * (conv_kernel - 1) * d_model * itemsize


def grouped_pair_flops(pairs, d_model, expert_dim):
    """Floating-point operations of ``pairs`` (row, expert) pairs
    through a gated expert: three ``d_model x expert_dim`` products a
    pair, two operations a multiply-add.  Padding rows of a tile and
    pairs nobody chose are not counted, so a kernel that computes them
    reads below its peak."""
    return pairs * 3 * 2 * d_model * expert_dim
