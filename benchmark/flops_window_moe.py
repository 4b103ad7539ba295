"""Bytes the window/global-attention model's kernels cannot avoid,
computed from shapes: the yardstick of ``full_attn_roofline.serve`` and
``window_attn_roofline.serve`` (both memory-bound at decode: a roofline
share divides these bytes by ``peaks.json``'s HBM bandwidth and by a
device time from the trace).  K and V are counted at their OWN widths.
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def kv_bytes_per_token(layers, kv_heads, head_dim, v_head_dim,
                       cache_dtype="float32"):
    """Bytes of K and V one cached position holds over ``layers`` layers
    of one kind: ``kv_heads`` heads of ``head_dim`` (K) and
    ``v_head_dim`` (V) lanes."""
    return layers * kv_heads * (head_dim + v_head_dim) \
        * _DTYPE_BYTES[str(cache_dtype)]


def window_pages(context_len, page_size, window):
    """Whole pages that hold a position a new token at context length
    ``context_len`` (positions attended were the window not there, the
    new one included) attends through a window of ``window``: the pages
    of positions ``max(n - window, 0) .. n - 1``, at most ``ceil(window
    / page_size) + 1``."""
    n = int(context_len)
    return (n - 1) // page_size - max(n - window, 0) // page_size + 1


def window_attention_bytes(context_lens, page_size, window,
                           bytes_per_token):
    """K and V bytes the window layers' paged attention must read for
    one new token at each context length: whole pages (the unit the
    ring holds and the kernel moves), only those the window reaches."""
    return sum(window_pages(n, page_size, window)
               for n in context_lens) * page_size * bytes_per_token
