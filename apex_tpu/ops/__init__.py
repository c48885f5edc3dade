"""apex_tpu.ops — fused TPU kernels (Pallas) + XLA compositions.

TPU-native replacement for the reference's CUDA extension zoo
(``csrc/`` + ``apex/contrib/csrc/``; SURVEY.md §2.4, §2.7): layer
norm/RMSNorm, scaled-mask softmax, RoPE, fused attention, memory-saving
cross entropy, fused dense/MLP, group norm.  Every op ships a Pallas
TPU kernel (where fusion beats XLA) plus a jnp golden composition, and
dispatches per platform (`implementation=` / APEX_TPU_OPS_IMPL).
"""

from apex_tpu.ops.layer_norm import (
    fused_layer_norm,
    fused_rms_norm,
    layer_norm_reference,
    rms_norm_reference,
)
from apex_tpu.ops.softmax import (
    fused_scale_mask_softmax,
    scale_mask_softmax_reference,
)
from apex_tpu.ops.rope import fused_rope, rope_reference, rope_cos_sin
from apex_tpu.ops.xentropy import (
    softmax_cross_entropy,
    softmax_cross_entropy_reference,
)
from apex_tpu.ops.mlp import (
    FusedDense,
    FusedDenseGeluDense,
    MLP,
    fused_dense,
)
from apex_tpu.ops.group_norm import group_norm, GroupNorm
from apex_tpu.ops.batch_norm import (
    batch_norm_train,
    batch_norm_inference,
    batch_norm_reference,
)
from apex_tpu.ops.attention import fused_attention, attention_reference
from apex_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)
from apex_tpu.ops.fused_sampling import (
    fused_sample,
    fused_sample_reference,
)
from apex_tpu.ops.ssm import (
    causal_conv_step, ssd_chunk_scan, ssm_decode_update)
from apex_tpu.ops.multihead_attn import SelfMultiheadAttn, EncdecMultiheadAttn

__all__ = [
    "fused_layer_norm", "fused_rms_norm",
    "layer_norm_reference", "rms_norm_reference",
    "fused_scale_mask_softmax", "scale_mask_softmax_reference",
    "fused_rope", "rope_reference", "rope_cos_sin",
    "softmax_cross_entropy", "softmax_cross_entropy_reference",
    "FusedDense", "FusedDenseGeluDense", "MLP", "fused_dense",
    "group_norm", "GroupNorm",
    "batch_norm_train", "batch_norm_inference", "batch_norm_reference",
    "fused_attention", "attention_reference",
    "paged_attention", "paged_attention_reference",
    "fused_sample", "fused_sample_reference",
    "SelfMultiheadAttn", "EncdecMultiheadAttn",
]
