"""Operations and compulsory bytes of the SSD-MobileNetV2 configuration,
counted from its shapes.

``frame_cost(cfg)`` gives, for ONE frame, the floating-point operations
of every convolution (2 per multiply-add; batch norm, ReLU6, box decode
and NMS are left out, so the count is a floor), the bytes of one input
frame (uint8) and the weight bytes read once per window (bf16 kernels,
f32 batch-norm vectors).  Output bytes are what the sink is served and
are measured by the traffic generator.  SAME padding: a stride-s layer
maps n to ceil(n / s).
"""

from __future__ import annotations

import math


def conv_layers(cfg: dict) -> list:
    """(k, cin, cout, groups, out_side) of every convolution."""
    side = math.ceil(int(cfg["image_size"]) / 2)
    stem = int(cfg["stem_channels"])
    rows = [(3, 3, stem, 1, side)]
    cin = stem
    maps = []
    flat = []
    for t, c, n, s in cfg["backbone_blocks"]:
        flat.extend((t, c, s if r == 0 else 1) for r in range(n))
    for i, (t, c, s) in enumerate(flat):
        mid = cin * t
        if t != 1:
            rows.append((1, cin, mid, 1, side))
        side = math.ceil(side / s)
        rows.append((3, mid, mid, mid, side))
        rows.append((1, mid, c, 1, side))
        cin = c
        if i == int(cfg["tap_block"]):
            maps.append((side, c))
    maps.append((side, cin))
    for c in cfg["extra_channels"]:
        side = math.ceil(side / 2)
        rows.append((3, cin, c, 1, side))
        cin = c
        maps.append((side, c))
    a = int(cfg["anchors_per_cell"])
    for side_m, c in maps:
        rows.append((3, c, a * 4, 1, side_m))
        rows.append((3, c, a * int(cfg["num_classes"]), 1, side_m))
    return rows


def frame_cost(cfg: dict) -> dict:
    flops = 0
    weight_bytes = 0
    for k, cin, cout, groups, side in conv_layers(cfg):
        macs = side * side * k * k * (cin // groups) * cout
        flops += 2 * macs
        weight_bytes += k * k * (cin // groups) * cout * 2 + 4 * cout * 4
    size = int(cfg["image_size"])
    return {"flops_per_frame": float(flops),
            "in_bytes_per_frame": float(size * size * 3),
            "weight_bytes": float(weight_bytes)}
