"""Quantized inference tier: int8 weight-only matmuls + int8 paged KV.

``quantize_params`` builds the weight-only int8 tree (per-output-channel
symmetric fp32 scales, sharding derived from the partition rule tables);
``w8_matmul``/``w8_matmul_nk`` are the Pallas dequant-fused matmuls the
serving cores plug in as ``dense_fns``/``logits_fn``; ``kv_quantize``/
``kv_dequantize`` are the per-page-per-head KV codecs the paged cores
use when the cache carries ``k_scale``/``v_scale``. See
``docs/source/quantization.rst`` for the scale layout, the accuracy
gates, and the budgets workflow.
"""

from apex_tpu.quant.kernels import (
    kv_dequantize,
    kv_quantize,
    w8_matmul,
    w8_matmul_nk,
)
from apex_tpu.quant.params import (
    dequantize_tensor,
    is_quantized_tree,
    quant_partition_specs,
    quantize_params,
    quantize_tensor,
)

__all__ = [
    "dequantize_tensor",
    "is_quantized_tree",
    "kv_dequantize",
    "kv_quantize",
    "quant_partition_specs",
    "quantize_params",
    "quantize_tensor",
    "w8_matmul",
    "w8_matmul_nk",
]
