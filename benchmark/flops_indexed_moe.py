"""Bytes the indexed attention's operations cannot avoid, computed from
shapes AS PUBLISHED: the yardsticks of ``indexer_roofline.serve`` and
``sparse_attn_roofline.serve`` (both memory-bound at decode: bytes over
``peaks.json``'s HBM bandwidth).  The same work whatever implements it:
a form that reads more (a padded index row, every live row of K and V
under a mask) reads a lower share, never one over 100 %.
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def index_row_bytes(index_dim, dtype="bfloat16"):
    """Bytes one position's index key holds in one layer."""
    return index_dim * _DTYPE_BYTES[str(dtype)]


def kv_row_bytes(num_kv_heads, head_dim, dtype="bfloat16"):
    """Bytes one position's K and V hold in one layer, all K/V heads."""
    return 2 * num_kv_heads * head_dim * _DTYPE_BYTES[str(dtype)]


def position_bytes(num_kv_heads, head_dim, index_dim, dtype="bfloat16"):
    """Bytes a cached position holds in one layer: K, V and the key."""
    return kv_row_bytes(num_kv_heads, head_dim, dtype) \
        + index_row_bytes(index_dim, dtype)


def indexer_bytes(positions_scored, layers, index_dim, dtype="bfloat16"):
    """Bytes a step's index scores must move: every live position's key
    (``positions_scored``: live slot x live position pairs a layer) read
    ONCE a layer.  Scores that never reach HBM owe nothing more; a
    selection that re-reads them shows as a lower share."""
    return positions_scored * layers * index_row_bytes(index_dim, dtype)


def sparse_attention_bytes(positions_selected, layers, num_kv_heads,
                           head_dim, dtype="bfloat16"):
    """Bytes a step's attention over the selection must move: K and V of
    every selected position (``positions_selected``: the sum over live
    slots of min(context, topk), a layer) read once a layer."""
    return positions_selected * layers * kv_row_bytes(
        num_kv_heads, head_dim, dtype)
