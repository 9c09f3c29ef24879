"""Pallas TPU kernels for the framework's hot ops.

Where the reference hand-vectorizes with Orc SIMD kernels
(/root/reference/gst/nnstreamer/elements/nnstreamer-orc.orc), this
package holds hand-written TPU kernels for the ops worth owning below
XLA: the streaming normalize/typecast prologue, the flash-attention
block kernel behind long-context attention, whole-sequence attention
for short sequences, the two decode attention kernels (over a latent
cache, whose rows pack two positions where the sizes allow:
``latent_cache_row``; and grouped-query over a ring or a dense K/V
cache: both leave their caches in HBM and walk a stream's live rows,
copying them through one queue of buffers), the routed experts' grouped
product and
the Mamba-2 decode step.  Every kernel has a jnp
reference implementation (the grouped product's is the loop in
``models/moe.py``); the first two
say through their ``*_available`` rule when a caller should use it
instead (and take it themselves), the others refuse a shape they
cannot take, with an ``*_available`` or ``*_refusal`` rule for the
caller to ask first.
"""

from .kernels import (
    flash_attention,
    flash_attention_available,
    flash_attention_reference,
    gqa_decode_attention,
    gqa_decode_attention_refusal,
    gqa_decode_attention_reference,
    grouped_gated_product,
    grouped_gated_product_refusal,
    grouped_tile,
    latent_cache_row,
    latent_decode_attention,
    latent_decode_attention_refusal,
    latent_decode_attention_reference,
    latent_pack,
    latent_place,
    latent_unpack,
    scale_bias_cast,
    scale_bias_cast_available,
    short_attention,
    short_attention_available,
    short_attention_reference,
)

__all__ = [
    "scale_bias_cast", "scale_bias_cast_available",
    "flash_attention", "flash_attention_available",
    "flash_attention_reference",
    "short_attention", "short_attention_available",
    "short_attention_reference",
    "latent_decode_attention", "latent_decode_attention_refusal",
    "latent_decode_attention_reference",
    "latent_cache_row", "latent_pack", "latent_unpack", "latent_place",
    "gqa_decode_attention", "gqa_decode_attention_refusal",
    "gqa_decode_attention_reference",
    "grouped_gated_product", "grouped_gated_product_refusal",
    "grouped_tile",
]
