"""Pallas TPU kernels — the tier where the reference used hand-written
CUDA (src/operator/*.cu, SURVEY §2.5 "TPU mapping"): ops XLA cannot fuse
well on its own get explicit MXU/VMEM-aware kernels here.

Every kernel ships with an ``interpret`` mode so the unit tests run on the
CPU mesh (SURVEY §4 test strategy); on TPU backends the compiled Mosaic
kernel runs.
"""
from .flash_attention import flash_attention
from .paged_decode import paged_decode_attention

__all__ = ["flash_attention", "paged_decode_attention"]
