from ray_tpu.ops.activations import geglu, gelu, swiglu
from ray_tpu.ops.attention import FLASH_KEPT, attention, repeat_kv
from ray_tpu.ops.flash_attention import (flash_attention, flash_attention_forward,
                                         flash_prefix_attention, prefix_blocks)
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.ops.losses import fused_head_cross_entropy, softmax_cross_entropy
from ray_tpu.ops.moe import (RoutingInfo, held_slots, moe_apply, moe_sorted, onehot_dispatch,
                             share_counts, sigmoid_topk, softmax_topk, sorted_pays,
                             topk_routing)
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.ragged_paged_attention import (
    ragged_decode_attention, ragged_decode_attention_reference)
from ray_tpu.ops.rope import Yarn, apply_rope, rope_frequencies
from ray_tpu.ops.ssm import (causal_conv, conv_tail, kda_chunk_scan, kda_state_update,
                             live_rows, ssm_chunk_scan, ssm_state_update)

__all__ = [
    "FLASH_KEPT",
    "RoutingInfo",
    "Yarn",
    "apply_rope",
    "attention",
    "causal_conv",
    "conv_tail",
    "flash_attention",
    "flash_attention_forward",
    "flash_prefix_attention",
    "fused_head_cross_entropy",
    "geglu",
    "gelu",
    "grouped_matmul",
    "layer_norm",
    "kda_chunk_scan",
    "kda_state_update",
    "live_rows",
    "moe_apply",
    "held_slots",
    "moe_sorted",
    "onehot_dispatch",
    "prefix_blocks",
    "ragged_decode_attention",
    "ragged_decode_attention_reference",
    "repeat_kv",
    "rms_norm",
    "rope_frequencies",
    "sigmoid_topk",
    "softmax_cross_entropy",
    "softmax_topk",
    "share_counts",
    "sorted_pays",
    "ssm_chunk_scan",
    "ssm_state_update",
    "swiglu",
]
