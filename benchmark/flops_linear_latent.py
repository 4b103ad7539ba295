"""Operations and bytes the linear/latent hybrid model's kernels cannot
avoid, computed from shapes AS PUBLISHED: the yardsticks of
``kda_step_roofline`` (memory-bound at decode: a live slot's state read
once and written once a recurrent layer, over ``peaks.json``'s HBM
bandwidth), ``kda_prefill_roofline`` (the prompt's token recurrence:
float32 multiplies and adds that no matrix unit takes, over the vector
unit's issue rate below) and ``latent_step_roofline.serve`` (the
absorbed attention over the LATENT layers alone: the larger of its byte
time and its FLOP time at the bf16 peak).
"""
from benchmark.flops_hybrid_moe import kda_state_bytes  # noqa: F401
from benchmark.flops_latent_moe import (  # noqa: F401
    latent_attention_bytes, latent_attention_flops, latent_row_bytes)

# float32 multiplies and adds a second one TensorCore's vector unit can
# issue with no matrix unit in play: 4 vector ALU slots a bundle x 1,024
# lanes (8 sublanes x 128) x 1.5 GHz (the clock the 197 TFLOP/s of four
# 128 x 128 matrix units give; PERF.md section 5 reads the same 4 a
# bundle off the state kernel's static schedule: 120 register-wide
# multiplies and adds in 30 bundles).  A fused multiply-add is not
# counted as two: this generation's vector unit has none.
VECTOR_F32_OPS_PER_S = 4 * 1024 * 1.5e9


def kda_token_ops(token_layers, heads, head_dim):
    """Float32 multiplies and adds of the delta rule's token recurrence
    for ``token_layers`` (real prompt token, recurrent layer) pairs:
    a head's ``d x d`` state is decayed (``d^2``), read by ``k`` and by
    ``q`` (``2 d^2`` each, multiply and add) and takes the rank-one
    update (``2 d^2``): ``7 d^2`` a head a token.  The vectors' own work
    (norms, the convolution, the decay's exponentials) is a hundredth of
    that and is not counted, nor are the transposes that lay q, k and
    the decay down the state's sublanes: they are the kernel's cost, not
    the rule's."""
    return token_layers * heads * 7 * head_dim * head_dim


def state_slot_bytes(layers, heads, head_dim, conv_kernel, itemsize=4):
    """Bytes ONE slot's recurrent state takes over ``layers`` recurrent
    layers: a ``d x d`` matrix a head and the ``conv_kernel - 1`` rows
    of the three convolutions' inputs, float32."""
    c = heads * head_dim
    return layers * (c * head_dim + (conv_kernel - 1) * 3 * c) * itemsize
