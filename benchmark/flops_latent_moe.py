"""Operations and bytes the latent-attention model's new kernels cannot
avoid, computed from shapes AS PUBLISHED: the yardsticks of
``latent_attn_roofline.serve`` (the larger of its byte time and its FLOP
time at the bf16 peak: the absorbed form sits near the chip's ridge) and
``latent_proj_roofline.serve`` (memory-bound at decode: the projections'
weights over ``peaks.json``'s HBM bandwidth).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def latent_row_bytes(kv_rank, rope_dim, dtype="bfloat16"):
    """Bytes one position's cached row holds in one layer: the latent
    and the shared rotary key.  A pool that pads the row to whole lane
    tiles moves more and reads below 100 %; one that reads fewer has
    changed the model."""
    return (kv_rank + rope_dim) * _DTYPE_BYTES[str(dtype)]


def latent_attention_bytes(positions, slot_steps, layers, num_heads,
                           kv_rank, rope_dim, dtype="bfloat16"):
    """Bytes the absorbed step's attention must move for ``positions``
    (live slot, attended position) pairs a layer and ``slot_steps``
    (live slot, step) pairs: every attended row once a layer, and a
    slot's float32 query (``num_heads`` rows of the cached row's width)
    in and context (of the latent's) out."""
    rows = positions * latent_row_bytes(kv_rank, rope_dim, dtype)
    ends = slot_steps * num_heads * (2 * kv_rank + rope_dim) * 4
    return layers * (rows + ends)


def latent_attention_flops(positions, layers, num_heads, kv_rank,
                           rope_dim):
    """Multiply-adds x 2 of the absorbed form for ``positions`` (live
    slot, attended position) pairs a layer: every head's score over the
    row's ``kv_rank + rope_dim`` lanes and its value sum over the
    latent's ``kv_rank``."""
    return 2 * positions * layers * num_heads * (2 * kv_rank + rope_dim)


def latent_projection_params(d_model, num_heads, q_rank, kv_rank, nope_dim,
                             rope_dim, v_dim):
    """{weight name: parameters} of one layer's attention matrices, as
    the served model names them: ``q_a``, ``q_b``, ``kv_a``, the two
    halves of ``kv_b`` (the absorb products) and ``o``."""
    return {"wq_a": d_model * q_rank,
            "wq_b": q_rank * num_heads * (nope_dim + rope_dim),
            "wkv_a": d_model * (kv_rank + rope_dim),
            "w_uk": kv_rank * num_heads * nope_dim,
            "w_uv": kv_rank * num_heads * v_dim,
            "wo": num_heads * v_dim * d_model}


def latent_projection_bytes(layers, d_model, num_heads, q_rank, kv_rank,
                            nope_dim, rope_dim, v_dim, dtype="bfloat16"):
    """Bytes of attention weights ONE decode step reads whatever its
    batch, all six matrices of every layer."""
    return layers * sum(latent_projection_params(
        d_model, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
        v_dim).values()) * _DTYPE_BYTES[str(dtype)]
