"""Application glue for the SSD configuration: registers the program's
detector under a model name, the way an application calls
``register_model``, and says what a pulled buffer serves."""

from __future__ import annotations

import numpy as np

from benchmark.appglue import fence, served_nbytes, unregister  # noqa: F401


def register(cfg: dict, params, batch: int, name: str) -> None:
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.models.ssd import ssd_anchors, ssd_detect_apply

    size = int(cfg["image_size"])
    anchors = ssd_anchors(size, tuple(cfg["feature_maps"]),
                          cfg["anchor_min_scale"], cfg["anchor_max_scale"])
    max_out = int(cfg["max_detections"])
    thresh = float(cfg["score_threshold"])
    iou = float(cfg["iou_threshold"])

    # outputs in the postprocess wire order the bounding_boxes
    # mobilenet-ssd-postprocess decoder consumes
    def detect(p, x):
        boxes, scores, classes = ssd_detect_apply(
            p, x, anchors, max_out=max_out, score_thresh=thresh,
            iou_thresh=iou)
        num = jnp.sum((scores > thresh).astype(jnp.int32), axis=-1)
        return boxes, classes, scores, num

    register_model(name, detect, params=params,
                   in_shapes=[(batch, size, size, 3)], in_dtypes=np.float32)


def outputs(buf) -> dict:
    """The detections a pulled buffer serves, as device arrays: from the
    device decoder's meta where the line has one, else the filter's four
    tensors."""
    det = buf.meta.get("detections_device")
    if det is not None:
        return {k: det[k] for k in ("boxes", "classes", "scores", "num")}
    names = ("boxes", "classes", "scores", "num")
    return {k: t.jax() for k, t in zip(names, buf.tensors)}
