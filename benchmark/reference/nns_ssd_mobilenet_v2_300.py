"""Plain float32 reference of the repo's own SSD on a MobileNetV2
backbone, the comparison that decides ``correct`` for its cells, and the
control.

Written from ``benchmark/configs/nns_ssd_mobilenet_v2_300.json`` (its sizes
and its ``head`` list): straightforward ``jax.numpy`` convolutions
at ``precision=HIGHEST``, no kernels, no batching tricks, no fusion.  It
imports nothing of the program and makes its own weights from the seed
(``benchmark/weights``), upcast from the bf16 values that are served.

What is compared is what the timed path hands the application: the
detections of sampled frames (boxes, classes, scores, num).  Every
served detection is matched to the reference anchor whose decoded box
lies nearest, and three numbers are taken over the sample:

``box_rms``    RMS distance (largest coordinate gap, in image sides)
               between served boxes and the matched reference boxes
``score_rms``  RMS gap between served scores and the reference's
               sigmoid score at the matched anchor and served class
``top1_short`` mean shortfall of the served best detection's reference
               score below the reference's best score of the frame
``empty``      frames served without a detection although the reference
               sees one clearly above the threshold (exact: 0)

Selection (top-k, NMS) is discontinuous, so the served *set* is not
compared slot by slot; a wrong, missing or permuted frame moves every
number by two orders of magnitude.

The control is this same forward pass with every convolution's inputs
and kernels rounded to float8_e4m3fn, the nearest precision below the
configuration's bfloat16, decoded and selected by a plain NMS here.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _weights_module():
    path = os.path.join(os.path.dirname(_HERE), "weights",
                        "nns_ssd_mobilenet_v2_300.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_weights_nns_ssd_mobilenet_v2_300", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- anchors and box decode, from the configuration --------------------------


def anchors(cfg: dict) -> np.ndarray:
    """(A,4) centre-form (cy,cx,h,w) priors: the SSD scale progression
    over the feature maps, ``anchors_per_cell`` shapes a cell, cell-major
    as the head's (fs,fs,A,4) output flattens."""
    sizes = cfg["feature_maps"]
    lo, hi = cfg["anchor_min_scale"], cfg["anchor_max_scale"]
    n = len(sizes)
    scales = [lo + (hi - lo) * i / (n - 1) for i in range(n)] + [1.0]
    out = []
    for li, fs in enumerate(sizes):
        s, s_next = scales[li], scales[li + 1]
        dims = [(s * np.sqrt(r), s / np.sqrt(r))
                for r in cfg["aspect_ratios"]]
        dims.append((np.sqrt(s * s_next),) * 2)
        centre = (np.arange(fs) + 0.5) / fs
        cy, cx = np.meshgrid(centre, centre, indexing="ij")
        per_cell = [np.stack([cy, cx, np.full_like(cy, h),
                              np.full_like(cx, w)], axis=-1).reshape(-1, 4)
                    for (w, h) in dims]
        out.append(np.stack(per_cell, axis=1).reshape(-1, 4))
    return np.clip(np.concatenate(out), 0.0, 1.5).astype(np.float32)


def decode(loc, anc, cfg: dict):
    """Centre-form regression -> corner-form (ymin,xmin,ymax,xmax)."""
    import jax.numpy as jnp

    sy, sx, sh, sw = cfg["box_coder_scales"]
    cy = loc[..., 0] / sy * anc[:, 2] + anc[:, 0]
    cx = loc[..., 1] / sx * anc[:, 3] + anc[:, 1]
    h = jnp.exp(loc[..., 2] / sh) * anc[:, 2]
    w = jnp.exp(loc[..., 3] / sw) * anc[:, 3]
    return jnp.stack([cy - h / 2, cx - w / 2, cy + h / 2, cx + w / 2],
                     axis=-1)


# -- the forward pass ---------------------------------------------------------


def _forward_fn(cfg: dict, lower: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = float(cfg["bn_epsilon"])
    num_classes = int(cfg["num_classes"])
    strides = []
    for _t, _c, n, s in cfg["backbone_blocks"]:
        strides.extend([s] + [1] * (n - 1))
    tap = int(cfg["tap_block"])

    def q(a):
        if not lower:
            return a
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def conv_bn(p, x, stride, groups=1, relu6=True):
        y = lax.conv_general_dilated(
            q(x), q(p["w"].astype(jnp.float32)), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=lax.Precision.HIGHEST)
        inv = p["scale"] / jnp.sqrt(p["var"] + eps)
        y = y * inv + (p["bias"] - p["mean"] * inv)
        return jnp.clip(y, 0.0, 6.0) if relu6 else y

    def forward(params, frames_u8):
        x = (frames_u8.astype(jnp.float32) - 127.5) / 127.5
        bb = params["backbone"]
        x = conv_bn(bb["stem"], x, 2)
        maps = []
        for i, stride in enumerate(strides):
            blk = bb["blocks"][i]
            h = x
            if "expand" in blk:
                h = conv_bn(blk["expand"], h, 1)
            h = conv_bn(blk["dw"], h, stride, groups=h.shape[-1])
            h = conv_bn(blk["project"], h, 1, relu6=False)
            if stride == 1 and h.shape[-1] == x.shape[-1]:
                h = h + x
            x = h
            if i == tap:
                maps.append(x)
        maps.append(x)
        for p in params["extras"]:
            x = conv_bn(p, x, 2)
            maps.append(x)
        locs, clss = [], []
        for fmap, head in zip(maps, params["heads"]):
            n = fmap.shape[0]
            locs.append(conv_bn(head["loc"], fmap, 1, relu6=False)
                        .reshape(n, -1, 4))
            clss.append(conv_bn(head["cls"], fmap, 1, relu6=False)
                        .reshape(n, -1, num_classes))
        return jnp.concatenate(locs, 1), jnp.concatenate(clss, 1)

    return jax.jit(forward)


@functools.lru_cache(maxsize=4)
def _forward_cached(cfg_key: str, lower: bool):
    import json

    return _forward_fn(json.loads(cfg_key), lower)


def _cfg_key(cfg: dict) -> str:
    import json

    keys = ("bn_epsilon", "num_classes", "backbone_blocks", "tap_block")
    return json.dumps({k: cfg[k] for k in keys}, sort_keys=True)


def raw_outputs(cfg: dict, seed: int, frames_u8, lower: bool = False,
                block: int = 16):
    """(loc (n,A,4), cls (n,A,C)) as numpy float32, computed in blocks of
    ``block`` frames so that the float32 activations fit beside whatever
    else the device holds."""
    import jax

    params = _weights_module().make(cfg, seed)
    params = {k: v for k, v in params.items() if k != "num_classes"}
    fwd = _forward_cached(_cfg_key(cfg), bool(lower))
    locs, clss = [], []
    frames_u8 = np.asarray(frames_u8)
    for i in range(0, len(frames_u8), block):
        part = frames_u8[i:i + block]
        pad = block - len(part)
        if pad:
            part = np.concatenate([part, np.repeat(part[-1:], pad, 0)])
        loc, cls = fwd(params, jax.device_put(part))
        locs.append(np.asarray(loc)[:block - pad])
        clss.append(np.asarray(cls)[:block - pad])
    return np.concatenate(locs), np.concatenate(clss)


# -- the comparison -----------------------------------------------------------


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def compare_numbers(cfg: dict, loc, cls, served: dict) -> dict:
    """The numbers of the module docstring from reference raw outputs
    ``loc``/``cls`` and ``served`` detections of the same frames:
    ``boxes`` (n,K,4), ``classes`` (n,K), ``scores`` (n,K), ``num`` (n,)."""
    import jax.numpy as jnp

    anc = anchors(cfg)
    ref_boxes = np.asarray(decode(jnp.asarray(loc), jnp.asarray(anc), cfg))
    prob = _sigmoid(cls[:, :, 1:])                      # foreground
    thresh = float(cfg["score_threshold"])
    boxes = np.asarray(served["boxes"], np.float32)
    classes = np.asarray(served["classes"]).astype(np.int64)
    scores = np.asarray(served["scores"], np.float32)
    num = np.asarray(served["num"]).reshape(-1).astype(np.int64)
    n, k = scores.shape
    if not (np.isfinite(boxes).all() and np.isfinite(scores).all()):
        return {"box_rms": float("inf"), "score_rms": float("inf"),
                "top1_short": float("inf"), "empty": float(n)}
    box_gaps, score_gaps, short, empty = [], [], [], 0
    for f in range(n):
        best = float(prob[f].max())
        if num[f] <= 0:
            empty += int(best > thresh + 0.05)
            continue
        valid = slice(0, int(min(num[f], k)))
        d = np.abs(ref_boxes[f][None, :, :] - boxes[f, valid][:, None, :]
                   ).max(-1)                            # (v, A)
        a = d.argmin(-1)
        c = np.clip(classes[f, valid] - 1, 0, prob.shape[-1] - 1)
        box_gaps.extend(d[np.arange(len(a)), a])
        ref_score = prob[f, a, c]
        score_gaps.extend(np.abs(scores[f, valid] - ref_score))
        top = int(np.argmax(scores[f, valid]))
        short.append(max(best - float(ref_score[top]), 0.0))
    if not box_gaps:
        return {"box_rms": float("inf"), "score_rms": float("inf"),
                "top1_short": float("inf"), "empty": float(empty)}
    return {"box_rms": float(np.sqrt(np.mean(np.square(box_gaps)))),
            "score_rms": float(np.sqrt(np.mean(np.square(score_gaps)))),
            "top1_short": float(np.mean(short)),
            "empty": float(empty)}


def check(cfg: dict, seed: int, frames_u8, served: dict) -> list:
    """[{name, value, limit}] for served detections of ``frames_u8``."""
    loc, cls = raw_outputs(cfg, seed, frames_u8)
    numbers = compare_numbers(cfg, loc, cls, served)
    return [{"name": k, "value": v, "limit": float(cfg["limits"][k])}
            for k, v in numbers.items()]


# -- the control ----------------------------------------------------------------


def select(cfg: dict, loc, cls) -> dict:
    """Plain decode + class-aware greedy NMS on raw outputs: what an
    application would be served if these raw outputs were the model's."""
    import jax.numpy as jnp

    anc = anchors(cfg)
    boxes = np.asarray(decode(jnp.asarray(loc), jnp.asarray(anc), cfg))
    prob = _sigmoid(cls[:, :, 1:])
    k = int(cfg["max_detections"])
    thresh, iou_t = float(cfg["score_threshold"]), float(cfg["iou_threshold"])
    n = len(boxes)
    out = {"boxes": np.zeros((n, k, 4), np.float32),
           "classes": np.zeros((n, k), np.int32),
           "scores": np.zeros((n, k), np.float32),
           "num": np.zeros((n,), np.int32)}
    for f in range(n):
        flat = prob[f].ravel()
        order = np.argsort(-flat)[:20 * k]
        kept = []
        for idx in order:
            if flat[idx] <= thresh or len(kept) == k:
                break
            a, c = divmod(int(idx), prob.shape[-1])
            b = boxes[f, a]
            if any(cc == c and _iou(b, bb) > iou_t for bb, cc, _ in kept):
                continue
            kept.append((b, c, flat[idx]))
        for j, (b, c, s) in enumerate(kept):
            out["boxes"][f, j], out["classes"][f, j] = b, c + 1
            out["scores"][f, j] = s
        out["num"][f] = len(kept)
    return out


def _iou(a, b) -> float:
    tl, br = np.maximum(a[:2], b[:2]), np.minimum(a[2:], b[2:])
    wh = np.maximum(br - tl, 0)
    inter = wh[0] * wh[1]
    area = lambda x: max(x[2] - x[0], 0) * max(x[3] - x[1], 0)  # noqa: E731
    return float(inter / max(area(a) + area(b) - inter, 1e-9))


def control(cfg: dict, seed: int, frames_u8) -> list:
    """The control's numbers: the reference in float8_e4m3fn put in the
    program's place, against the float32 reference."""
    loc, cls = raw_outputs(cfg, seed, frames_u8)
    loc8, cls8 = raw_outputs(cfg, seed, frames_u8, lower=True)
    numbers = compare_numbers(cfg, loc, cls, select(cfg, loc8, cls8))
    return [{"name": k, "value": v, "limit": float(cfg["limits"][k])}
            for k, v in numbers.items()]
