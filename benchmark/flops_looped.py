"""Bytes the looped model's step cannot avoid, computed from shapes: the
yardsticks of ``loop_weights_roofline.serve`` and ``loop_attn_roofline.
serve`` (both memory-bound at decode: 16 rows meet every matrix, a flop
a byte; a roofline share divides these bytes by ``peaks.json``'s HBM
bandwidth and by a device time from the trace).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def layer_matrix_params(d_model, num_heads, head_dim, ffn_dim):
    """{name: elements} of ONE layer's seven matrices."""
    hd = num_heads * head_dim
    return {"wq": d_model * hd, "wk": d_model * hd, "wv": d_model * hd,
            "wo": hd * d_model, "ffn_w_gate": d_model * ffn_dim,
            "ffn_w_up": d_model * ffn_dim, "ffn_w_down": ffn_dim * d_model}


def step_weight_bytes(num_layers, loops, d_model, num_heads, head_dim,
                      ffn_dim, vocab_size, dtype="bfloat16"):
    """Bytes of matrices ONE decode step reads, whatever the batch: every
    layer's seven matrices once a PASS (the passes share the weights and
    not the reads: 197 MB of them do not stay on the chip from one pass
    to the next) and the head once.  The embedding is a gather of the
    batch's rows and is not counted."""
    layer = sum(layer_matrix_params(d_model, num_heads, head_dim,
                                    ffn_dim).values())
    return (loops * num_layers * layer + d_model * vocab_size) \
        * _DTYPE_BYTES[str(dtype)]


def kv_bytes_per_token(num_layers, loops, num_heads, head_dim,
                       cache_dtype="bfloat16"):
    """Bytes of K and V one cached position holds: every pass of every
    layer keeps its own."""
    return 2 * loops * num_layers * num_heads * head_dim \
        * _DTYPE_BYTES[str(cache_dtype)]
