"""Operations and compulsory bytes of the ViT configuration, counted from
its shapes.

``frame_cost(cfg)`` gives, for ONE frame, the floating-point operations
of the patch projection, of every block's four matrix multiplications and
its two attention products, and of the head (2 per multiply-add;
LayerNorm, softmax, GELU and the residual adds are left out, so the count
is a floor), the bytes of one input frame (uint8) and the weight bytes
read once per window (bf16 matrices, f32 vectors).
"""

from __future__ import annotations


def frame_cost(cfg: dict) -> dict:
    size, patch = int(cfg["image_size"]), int(cfg["patch_size"])
    d, depth = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    mlp, classes = int(cfg["intermediate_size"]), int(cfg["num_classes"])
    s = (size // patch) ** 2
    embed = s * patch * patch * 3 * d
    per_block = (s * d * 3 * d            # qkv
                 + s * s * d              # q k^T over all heads
                 + s * s * d              # p v over all heads
                 + s * d * d              # proj
                 + 2 * s * d * mlp)       # mlp1, mlp2
    head = d * classes
    macs = embed + depth * per_block + head
    matrices = (patch * patch * 3 * d + s * d
                + depth * (3 * d * d + d * d + 2 * d * mlp) + d * classes)
    vectors = (d + depth * (4 * d + 3 * d + d + mlp + d) + 2 * d + classes)
    return {"flops_per_frame": float(2 * macs),
            "in_bytes_per_frame": float(size * size * 3),
            "weight_bytes": float(matrices * 2 + vectors * 4)}
