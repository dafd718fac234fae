"""TPU kernels (Pallas) for the framework's hot ops.

The reference's hot kernels live in CUDA via torch; here they are Pallas
TPU kernels. A kernel is interpreted on the CPU backend (the tests) and
compiled everywhere else (``ops/backend.py`` is the only place that
asks); shapes outside a kernel's stated guard take the ``jnp`` form.
"""

from ray_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_grouped,
)
from ray_tpu.ops.fused import rms_norm_fused, softmax_cross_entropy
from ray_tpu.ops.paged_attention import paged_attention_decode

__all__ = [
    "flash_attention",
    "flash_attention_grouped",
    "paged_attention_decode",
    "rms_norm_fused",
    "softmax_cross_entropy",
]
