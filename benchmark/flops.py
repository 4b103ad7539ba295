"""Operations and bytes the algorithms need, computed from shapes.

The yardstick's arithmetic: a roofline share divides these by a peak
from ``peaks.json`` and by a device time from the trace.  One
multiply-add is 2 FLOPs; a training step is forward + backward = 3x the
forward's matmul FLOPs (recomputation does not count; the optimizer's
elementwise update, layer norms, softmax and GELU are not counted: they
are under 1 % of the matmuls at these widths).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def bert_pretrain_macs_per_sample(m):
    """Forward multiply-adds of one sequence of ``m['seq_len']`` tokens
    through BERT with the MLM head on ``max_preds_per_seq`` positions
    and the NSP head on the pooled [CLS]."""
    s, h, f = m["seq_len"], m["hidden"], m["ffn_size"]
    v, p, n = m["vocab_size"], m["max_preds_per_seq"], m["n_layers"]
    per_token_layer = 4 * h * h + 2 * h * f      # q, k, v, out; ffn1, ffn2
    attn_token_layer = 2 * s * h                 # q.k^T and p.v
    encoder = s * n * (per_token_layer + attn_token_layer)
    mlm = p * (h * h + h * v)                    # transform + decoder
    nsp = h * h + 2 * h                          # pooler + 2-way
    return encoder + mlm + nsp


def bert_pretrain_flops_per_sample(m):
    """Forward + backward FLOPs of one sequence (3 x 2 x MACs)."""
    return 6 * bert_pretrain_macs_per_sample(m)


def kv_bytes_per_token(n_layer, d_model, cache_dtype="float32"):
    """Bytes of K and V one cached position holds over all layers."""
    return 2 * n_layer * d_model * _DTYPE_BYTES[str(cache_dtype)]


def decode_attention_bytes(context_lens, page_size, bytes_per_token):
    """K and V bytes paged decode attention must read, over all layers,
    for one new token at each context length in ``context_lens``
    (positions attended, the new one included): whole pages, since a
    page is the unit the cache holds and the kernel moves."""
    pages = sum(-(-int(n) // page_size) for n in context_lens)
    return pages * page_size * bytes_per_token
