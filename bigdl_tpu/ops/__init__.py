"""bigdl_tpu.ops — TPU kernels (Pallas) + lax reference implementations.

This is the rebuild's "native layer".  The reference BigDL ships
hand-written native kernels (MKL/MKL-DNN `.so` loaded via JNI,
SURVEY.md §2.3); on TPU the equivalent of that layer is XLA itself plus
hand-written Pallas kernels for the few hot ops where manual tiling or
fusion beats the compiler (attention, quantized matmul).

Every op here has (a) a pure jax/lax reference implementation that runs
anywhere, and (b) optionally a Pallas TPU kernel selected automatically
on TPU backends.  Numerics of (a) and (b) are locked together by tests
(tests/test_ops.py) — the same role the reference's Torch7 oracle specs
play for its native kernels (SURVEY.md §4.3).
"""

from bigdl_tpu.ops import autotune
from bigdl_tpu.ops.attention import dot_product_attention, flash_attention
from bigdl_tpu.ops.decode_attention import paged_decode_attention
from bigdl_tpu.ops.grouped_matmul import grouped_matmul
from bigdl_tpu.ops.quantized_matmul import int8_matmul, quantize_per_channel

__all__ = [
    "autotune",
    "dot_product_attention",
    "flash_attention",
    "grouped_matmul",
    "int8_matmul",
    "paged_decode_attention",
    "quantize_per_channel",
]
