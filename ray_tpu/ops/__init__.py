from .attention import (attention, blockwise_attention, flash_attention,
                        flash_attention_with_lse, latent_decode_attention,
                        mha_reference, streamed_attention)
from .layers import (apply_rope, apply_rope_halves, apply_rope_interleaved,
                     fused_softmax_cross_entropy, gelu_mlp,
                     layer_norm, rms_norm, rope_table,
                     softmax_cross_entropy, swiglu, yarn_frequencies)
from .kda import kda_chunk, kda_step
from .mamba import selective_scan_chunk, selective_step
from .quantize import (dequantize_blockwise, quantization_error,
                       quantize_blockwise)
from .retention import retention_chunk, retention_step
from .ring_attention import ring_attention, ring_attention_sharded
from .ssd import ssd_chunk, ssd_step
from .ulysses import ulysses_attention, ulysses_attention_sharded

__all__ = [
    "quantize_blockwise", "dequantize_blockwise", "quantization_error",
    "attention", "flash_attention", "flash_attention_with_lse",
    "blockwise_attention", "mha_reference", "streamed_attention",
    "latent_decode_attention",
    "ring_attention", "ring_attention_sharded",
    "ulysses_attention", "ulysses_attention_sharded",
    "retention_chunk", "retention_step", "kda_chunk", "kda_step",
    "selective_scan_chunk", "selective_step", "ssd_chunk", "ssd_step",
    "rms_norm", "layer_norm", "rope_table", "apply_rope", "apply_rope_halves",
    "apply_rope_interleaved", "yarn_frequencies", "swiglu",
    "gelu_mlp", "softmax_cross_entropy", "fused_softmax_cross_entropy",
]
