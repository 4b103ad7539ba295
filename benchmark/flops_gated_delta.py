"""Operations and bytes the dense hybrid model's new kernels cannot
avoid, computed from shapes: the yardsticks of ``gdn_state_roofline``,
``dense_ffn_roofline.serve`` (both memory-bound at decode: bytes over
``peaks.json``'s HBM bandwidth) and ``gdn_prefill_roofline`` (the chunk
form: the larger of its FLOP time at the bf16 peak and its byte time).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def gdn_state_bytes(slot_steps, layers, heads, key_dim, value_dim,
                    itemsize=4):
    """Bytes the one-token gated-delta update must move for
    ``slot_steps`` (live slot, decode step) pairs: each recurrent
    layer's ``[heads, d_k, d_v]`` state of a live slot is read once and
    written once a step, at the bytes the numbers take (a layout that
    pads a row of ``d_v`` lanes moves more and reads below 100 %).  The
    convolution tail and the token's own rows are a hundredth of that
    and are not counted."""
    return slot_steps * layers * 2 * heads * key_dim * value_dim * itemsize


def dense_ffn_bytes(layers, d_model, ffn_dim, dtype="bfloat16"):
    """Bytes of feed-forward weights ONE decode step reads: three
    ``d_model x ffn_dim`` matrices a layer once, whatever the batch
    (``layers`` may be a fraction: matrices read over three)."""
    return layers * 3 * d_model * ffn_dim * _DTYPE_BYTES[str(dtype)]


def gdn_chunk_flops(chunks, chunk, heads, key_dim, value_dim):
    """Multiply-adds x 2 of the chunk form's matrix products for
    ``chunks`` (chunk, recurrent layer) pairs of ``chunk`` rows, a head:
    ``K K^T`` and ``Q K^T`` (``C x C x d_k`` each), the unit lower
    triangular inverse by halves (``log2 C`` rounds of two products over
    the diagonal blocks: ``C^3 / 3`` multiply-adds in the limit, counted
    exactly), ``T [beta V | beta e^G K]`` (``C x C x (d_v + d_k)``),
    ``[e^G Q ; W] S_0`` (``2C x d_k x d_v``), ``P U`` (``C x C x d_v``)
    and ``(e^{G_C - G} K)^T U`` (``d_k x C x d_v``).  The element-wise
    work (decays, masks, the convolution) is not counted."""
    c, dk, dv = chunk, key_dim, value_dim
    inverse, b = 0, 1
    while b < c:
        inverse += (c // (2 * b)) * 2 * b ** 3      # two b x b x b a pair
        b *= 2
    macs = 2 * c * c * dk + inverse + c * c * (dv + dk) \
        + 2 * c * dk * dv + c * c * dv + dk * c * dv
    return 2 * macs * heads * chunks


def gdn_chunk_bytes(chunks, chunk, heads, key_dim, value_dim, itemsize=4):
    """Bytes the chunk form must move for ``chunks`` (chunk, recurrent
    layer) pairs: a chunk's q, k, v rows in (before the convolution) and
    its outputs out, and the state read once and written once a chunk
    (it is carried from chunk to chunk through the loop), all float32."""
    rows = chunk * heads * (2 * key_dim + value_dim) + chunk * heads \
        * value_dim
    return chunks * (rows + 2 * heads * key_dim * value_dim) * itemsize
