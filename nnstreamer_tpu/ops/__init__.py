"""Pallas TPU kernels for the framework's hot ops.

Where the reference hand-vectorizes with Orc SIMD kernels
(/root/reference/gst/nnstreamer/elements/nnstreamer-orc.orc), this
package holds hand-written TPU kernels for the ops worth owning below
XLA: the streaming normalize/typecast prologue and the flash-attention
block kernel behind long-context attention.  Every kernel has a jnp
reference implementation; callers fall back automatically when shapes
don't tile or Pallas is unavailable.
"""

from .kernels import (
    flash_attention,
    flash_attention_available,
    flash_attention_reference,
    scale_bias_cast,
    scale_bias_cast_available,
)

__all__ = [
    "scale_bias_cast", "scale_bias_cast_available",
    "flash_attention", "flash_attention_available",
    "flash_attention_reference",
]
