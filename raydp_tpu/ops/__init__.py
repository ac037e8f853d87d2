"""TPU ops: fused kernels (pallas) with XLA fallbacks."""

from raydp_tpu.ops.embedding import (
    embedding_lookup_vocab_sharded,
    sharded_embedding_lookup,
)
from raydp_tpu.ops.flash_attention import flash_attention, flash_decode
from raydp_tpu.ops.interaction import (
    dot_interaction,
    dot_interaction_pallas,
    interaction_fused,
    interaction_pallas,
    interaction_xla,
)
from raydp_tpu.ops.quantization import (
    dequantize_int8,
    int8_matmul,
    quantize_int8,
)

__all__ = [
    "dequantize_int8",
    "dot_interaction",
    "dot_interaction_pallas",
    "flash_attention",
    "flash_decode",
    "int8_matmul",
    "interaction_fused",
    "interaction_pallas",
    "interaction_xla",
    "quantize_int8",
    "embedding_lookup_vocab_sharded",
    "sharded_embedding_lookup",
]
