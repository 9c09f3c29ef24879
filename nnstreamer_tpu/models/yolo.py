"""YOLO detection family: v8 wire layout + on-device decode/NMS head.

Parity target: the reference's YOLO decoder strategies
(/root/reference/ext/nnstreamer/tensor_decoder/box_properties/yolo.cc:384
— v5 ``(1, A, 5+C)`` and v8 ``(1, 4+C, A)`` output layouts, pixel-space
xywh, class-confidence thresholding + NMS on the host).  The reference
treats YOLO models as opaque backend files; here the family is a
jittable JAX program whose *raw* variant emits the exact v8 wire layout
the ``bounding_boxes`` decoder's ``yolov8`` scheme parses, and whose
*end-to-end* variant keeps decode + class-aware NMS ON the accelerator
(one XLA computation, fixed shapes) and emits the postprocess 4-tensor
contract (boxes/classes/scores/num) — so it composes with the device
overlay renderer exactly like the SSD family.

Architecture note: a compact anchor-free v8-STYLE network (stride
8/16/32 pyramid, per-cell xywh + class scores).  It is layout- and
pipeline-compatible with YOLOv8, not weight-compatible — the zoo's
models are initialized, not pretrained (the reference's test models are
likewise tiny stand-ins, tests/test_models/).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .mobilenet import _conv_bn, _conv_init, _rng_of
from .ssd import batched_nms

Params = Dict[str, Any]

_STRIDES = (8, 16, 32)


def _block_init(rng, cin, cout):
    """conv(s2) + depthwise + pointwise refine (CSP-lite)."""
    return {
        "down": _conv_init(rng, 3, 3, cin, cout),
        "dw": _conv_init(rng, 3, 3, cout, cout, groups=cout),
        "pw": _conv_init(rng, 1, 1, cout, cout),
    }


def _refine_init(rng, c):
    return {
        "dw": _conv_init(rng, 3, 3, c, c, groups=c),
        "pw": _conv_init(rng, 1, 1, c, c),
    }


def _block(p, x, dtype):
    x = _conv_bn(p["down"], x, stride=2, dtype=dtype)
    y = _conv_bn(p["dw"], x, stride=1, groups=x.shape[-1], dtype=dtype)
    y = _conv_bn(p["pw"], y, stride=1, dtype=dtype)
    x = x + y
    for r in p.get("refines", []):
        y = _conv_bn(r["dw"], x, stride=1, groups=x.shape[-1],
                     dtype=dtype)
        y = _conv_bn(r["pw"], y, stride=1, dtype=dtype)
        x = x + y
    return x


def yolo_init(key, num_classes: int = 80, width: int = 32,
              depth: int = 1) -> Params:
    """Init the v8-style pyramid network.  ``width`` scales channels;
    ``depth`` adds residual dw+pw refinement blocks per stage (the C2f
    repeat analog) — width=64, depth=2 at 640px is the bench
    configuration, yolov8n-class work per frame (``bench.yolo_flops()``
    computes the figure from the exact program)."""
    rng = _rng_of(key)
    c = [width, width * 2, width * 4, width * 8]
    p: Params = {
        "stem": _conv_init(rng, 3, 3, 3, c[0]),
        "num_classes": num_classes,
    }
    for i in range(3):  # stages to strides 8, 16, 32 (stem is s2, b0 s4)
        p[f"b{i}"] = _block_init(rng, c[i], c[i + 1])
        if depth > 1:
            p[f"b{i}"]["refines"] = [
                _refine_init(rng, c[i + 1]) for _ in range(depth - 1)]
    # extra early downsample so stage outputs land on strides 8/16/32
    p["early"] = _block_init(rng, c[0], c[0])
    for i, _s in enumerate(_STRIDES):
        p[f"head{i}"] = _conv_init(rng, 1, 1, c[i + 1], 4 + num_classes)
    return p


def _pyramid(params: Params, x, dtype):
    x = x.astype(dtype)
    x = _conv_bn(params["stem"], x, stride=2, dtype=dtype)   # s2
    x = _block(params["early"], x, dtype)                    # s4
    feats = []
    for i in range(3):
        x = _block(params[f"b{i}"], x, dtype)                # s8/s16/s32
        feats.append(x)
    return feats


def yolo_raw_apply(params: Params, x, dtype=jnp.bfloat16):
    """(B,H,W,3) float input → the v8 WIRE layout ``(B, 4+C, A)``:
    rows 0..3 are xywh in INPUT PIXELS, rows 4.. are per-class
    confidences in [0,1] — exactly what the ``yolov8`` decoder scheme
    expects (yolo.cc v8 branch; decoder divides by option5's in-dim)."""
    feats = _pyramid(params, x, dtype)
    outs = []
    for i, (f, stride) in enumerate(zip(feats, _STRIDES)):
        h = _conv_bn(params[f"head{i}"], f, stride=1, relu6=False,
                     dtype=dtype).astype(jnp.float32)        # (B,h,w,4+C)
        gh, gw = h.shape[1], h.shape[2]
        gy, gx = jnp.mgrid[0:gh, 0:gw]
        # anchor-free decode: cell center + sigmoid offset, exp size
        cx = (gx + jax.nn.sigmoid(h[..., 0])) * stride
        cy = (gy + jax.nn.sigmoid(h[..., 1])) * stride
        w = jnp.minimum(jnp.exp(h[..., 2]), 8.0) * stride
        hh = jnp.minimum(jnp.exp(h[..., 3]), 8.0) * stride
        cls = jax.nn.sigmoid(h[..., 4:])
        out = jnp.concatenate(
            [jnp.stack([cx, cy, w, hh], axis=-1), cls], axis=-1)
        outs.append(out.reshape(x.shape[0], gh * gw, -1))
    cat = jnp.concatenate(outs, axis=1)                      # (B,A,4+C)
    return jnp.swapaxes(cat, 1, 2)                           # (B,4+C,A)


def yolo_detect_apply(params: Params, x, max_out: int = 100,
                      iou_thresh: float = 0.5,
                      score_thresh: float = 0.25,
                      dtype=jnp.bfloat16):
    """End-to-end on-device: raw head → corner-form normalized boxes →
    class-aware fast NMS (ssd.batched_nms) → the postprocess contract
    (boxes (B,N,4) ymin..xmax normalized, classes, scores, num) consumed
    by ``mobilenet-ssd-postprocess`` decoding and the device overlay."""
    size_h, size_w = float(x.shape[1]), float(x.shape[2])
    raw = jnp.swapaxes(yolo_raw_apply(params, x, dtype=dtype), 1, 2)
    cx, cy = raw[..., 0] / size_w, raw[..., 1] / size_h
    w, h = raw[..., 2] / size_w, raw[..., 3] / size_h
    boxes = jnp.stack([cy - h / 2, cx - w / 2,
                       cy + h / 2, cx + w / 2], axis=-1)     # (B,A,4)
    # batched_nms treats column 0 as background: prepend a zero column
    # so YOLO's class 0 stays a real class (ids come back 1-based)
    scores = raw[..., 4:]
    padded = jnp.concatenate(
        [jnp.zeros_like(scores[..., :1]), scores], axis=-1)
    b, s, c = jax.vmap(
        lambda bb, ss: batched_nms(bb, ss, max_out=max_out,
                                   iou_thresh=iou_thresh,
                                   score_thresh=score_thresh))(
        boxes, padded)
    num = jnp.sum((s > score_thresh).astype(jnp.int32), axis=-1)
    return b, (c - 1).astype(jnp.float32), s, num


def register_yolo(name: str = "yolo_v8n", batch: int = 1,
                  image_size: int = 256, num_classes: int = 80,
                  raw: bool = False, max_out: int = 100,
                  seed: int = 0, width: int = 32, depth: int = 1) -> str:
    """Register with the jax-xla filter.  ``raw=True`` emits the v8 wire
    layout for the host ``yolov8`` decoder scheme; default is the
    end-to-end on-device variant in the postprocess contract."""
    from ..filters.jax_xla import register_model

    params = yolo_init(jax.random.PRNGKey(seed), num_classes=num_classes,
                       width=width, depth=depth)
    if raw:
        fn = lambda p, x: yolo_raw_apply(p, x)  # noqa: E731
    else:
        fn = lambda p, x: yolo_detect_apply(p, x, max_out=max_out)  # noqa: E731
    register_model(name, fn, params=params,
                   in_shapes=[(batch, image_size, image_size, 3)],
                   in_dtypes=np.float32)
    return name
