"""SSD-MobileNetV2 object detection in pure JAX, with on-device box decode
and NMS.

Capability parity: the reference's object-detection pipelines run
``ssd_mobilenet_v2_coco.tflite`` through tensor_filter and then decode boxes
on the *CPU* in the bounding_boxes decoder sub-plugin
(/root/reference/ext/nnstreamer/tensor_decoder/tensordec-boundingbox.cc and
``box_properties/mobilenetssd.cc`` — box priors, logistic decode, IoU NMS).
Here the whole post-processing chain (decode + NMS) is jittable and fuses
into the same XLA computation as the backbone, so detection output leaves
the TPU already final — no host round-trip in the hot path.

TPU design notes:
- Fixed-size everything: anchors are a static (A,4) table; NMS returns a
  fixed ``max_out`` slate with validity scores (XLA needs static shapes).
- NMS is the O(k²) IoU-matrix formulation (one matmul-shaped reduction on
  the MXU beats a data-dependent loop on TPU for k ≤ a few hundred).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .mobilenet import (
    Params,
    _conv_bn,
    _conv_init,
    mobilenet_v2_backbone,
    mobilenet_v2_init,
)

# SSDLite feature pyramid: tap MobileNetV2 block 13 (stride 16, the last
# stride-16 block: expansion output used by the standard ssd_mobilenet_v2
# head) and the final block (stride 32), then 4 extra stride-2 layers.
_TAP_BLOCK = 12  # 0-based index of last (6,96,3,1) block output, stride 16
_EXTRA_CHANNELS = (512, 256, 256, 128)
_ANCHORS_PER_CELL = 6
# Center-form decode scale factors, identical convention to the reference's
# mobilenetssd box properties (tensordec-boundingbox box_properties).
_SCALE_XY = 10.0
_SCALE_WH = 5.0


def ssd_anchors(image_size: int = 300,
                feature_sizes: Sequence[int] = (19, 10, 5, 3, 2, 1),
                min_scale: float = 0.2, max_scale: float = 0.95) -> np.ndarray:
    """Static anchor table, center-form (cy, cx, h, w), normalized [0,1].

    Parity: the box-prior table the reference loads from
    ``box_priors.txt`` for mobilenetssd decoding; here generated by the
    standard SSD scale progression so it is self-contained.
    """
    n_layers = len(feature_sizes)
    scales = [min_scale + (max_scale - min_scale) * i / (n_layers - 1)
              for i in range(n_layers)] + [1.0]
    ratios = (1.0, 2.0, 0.5, 3.0, 1.0 / 3.0)
    anchors: List[np.ndarray] = []
    for li, fs in enumerate(feature_sizes):
        s, s_next = scales[li], scales[li + 1]
        dims = [(s * np.sqrt(r), s / np.sqrt(r)) for r in ratios]
        dims.append((np.sqrt(s * s_next),) * 2)  # extra scale, ratio 1
        cy, cx = np.meshgrid(
            (np.arange(fs) + 0.5) / fs, (np.arange(fs) + 0.5) / fs,
            indexing="ij")
        for (w, h) in dims:
            a = np.stack([cy, cx, np.full_like(cy, h), np.full_like(cx, w)],
                         axis=-1)
            anchors.append(a.reshape(-1, 4))
    # Interleave per-cell: reorder so anchors of one cell are adjacent,
    # matching head output layout (fs, fs, A, 4) flattening.
    out = []
    off = 0
    for fs in feature_sizes:
        per = [anchors[i] for i in range(off, off + _ANCHORS_PER_CELL)]
        stacked = np.stack(per, axis=1).reshape(-1, 4)  # (fs*fs*A, 4)
        out.append(stacked)
        off += _ANCHORS_PER_CELL
    return np.clip(np.concatenate(out, axis=0), 0.0, 1.5).astype(np.float32)


def decode_boxes(loc, anchors):
    """Center-form regression → corner-form (ymin,xmin,ymax,xmax).

    Parity: mobilenetssd.cc decode with scale factors 10/10/5/5.
    ``loc``: (..., A, 4) raw head output; ``anchors``: (A, 4) cy,cx,h,w.
    """
    cy = loc[..., 0] / _SCALE_XY * anchors[:, 2] + anchors[:, 0]
    cx = loc[..., 1] / _SCALE_XY * anchors[:, 3] + anchors[:, 1]
    h = jnp.exp(loc[..., 2] / _SCALE_WH) * anchors[:, 2]
    w = jnp.exp(loc[..., 3] / _SCALE_WH) * anchors[:, 3]
    return jnp.stack(
        [cy - h / 2, cx - w / 2, cy + h / 2, cx + w / 2], axis=-1)


def _iou_matrix(boxes):
    """(k,4) corner-form → (k,k) IoU. One fused elementwise block on TPU."""
    area = jnp.maximum(boxes[:, 2] - boxes[:, 0], 0) * \
        jnp.maximum(boxes[:, 3] - boxes[:, 1], 0)
    tl = jnp.maximum(boxes[:, None, :2], boxes[None, :, :2])
    br = jnp.minimum(boxes[:, None, 2:], boxes[None, :, 2:])
    wh = jnp.maximum(br - tl, 0)
    inter = wh[..., 0] * wh[..., 1]
    union = area[:, None] + area[None, :] - inter
    return inter / jnp.maximum(union, 1e-9)


def nms_single(boxes, scores, max_out: int = 100, iou_thresh: float = 0.5,
               score_thresh: float = 0.25, pre_topk: int = 256):
    """Fixed-shape NMS for one class: returns (boxes (max_out,4),
    scores (max_out,)); suppressed slots carry score 0.

    XLA-friendly: top-k preselect, IoU matrix, then a ``lax.scan`` greedy
    pass over the k candidates (static trip count, no dynamic shapes).
    """
    k = min(pre_topk, scores.shape[0])
    top_scores, idx = jax.lax.top_k(scores, k)
    top_boxes = boxes[idx]
    iou = _iou_matrix(top_boxes)

    def step(keep_mask, i):
        # candidate i survives iff not suppressed by any kept higher-score box
        suppressed = jnp.any((iou[i] > iou_thresh) & keep_mask
                             & (jnp.arange(k) < i))
        keep_i = jnp.logical_not(suppressed) & (top_scores[i] > score_thresh)
        return keep_mask.at[i].set(keep_i), keep_i

    keep0 = jnp.zeros((k,), dtype=bool)
    # unroll: the greedy pass is k tiny sequential steps; TPU per-step
    # dispatch overhead dominates unless XLA fuses runs of them
    keep, _ = jax.lax.scan(step, keep0, jnp.arange(k), unroll=16)
    kept_scores = jnp.where(keep, top_scores, 0.0)
    out_scores, out_idx = jax.lax.top_k(kept_scores, min(max_out, k))
    out_boxes = top_boxes[out_idx]
    if max_out > k:  # pad
        pad = max_out - k
        out_scores = jnp.pad(out_scores, (0, pad))
        out_boxes = jnp.pad(out_boxes, ((0, pad), (0, 0)))
    return out_boxes, out_scores


def _preselect_top_k(scores, k: int):
    """Candidate preselect: ``approx_max_k`` where programs compile for a
    TPU, exact ``lax.top_k`` elsewhere.  Both return their values in
    non-increasing order, which ``_best_pairs`` relies on.  At the SSD's
    3000 anchors and k=128 the v5e's compiler lowers the approximate form
    to one full sort of the anchor axis (0.18 ms a window of 256; a recall
    of 0.95 at that k needs more partial results than there are inputs):
    the values are the exact top-k's, and equal values come in an order
    of its own, not lower index first (PERF.md section 6, PR 30).  The
    backend check (``ops.kernels.on_tpu``, shared with the Pallas kernels)
    happens at trace time, matching where the computation compiles for
    the common (default-device) case."""
    from ..ops.kernels import on_tpu

    if on_tpu():
        return jax.lax.approx_max_k(scores, k)
    return jax.lax.top_k(scores, k)


_PRE_TOPK = 128  # shared candidates per image, whoever selects them


def _best_pairs(s, M: int):
    """Exact top-``M`` (candidate, class) pairs of ``s`` (k, C-1), flat
    candidate-major, from the first ``min(M, k)`` rows alone.  Rows arrive
    in non-increasing order of their best class (``_preselect_top_k``
    returns them sorted), so a pair in row r >= M has M pairs before it —
    the best of each earlier row — with a value at least its own and a
    lower flat index, and ``lax.top_k`` breaks ties by lower index: it is
    never among the M.  Values and indices are those of a top-k over all
    k rows (tests/test_ssd_candidates.py holds the lemma to that)."""
    return jax.lax.top_k(s[:min(M, s.shape[0])].reshape(-1), M)


def _suppress_candidates(b, s, max_out: int, iou_thresh: float,
                         score_thresh: float, fill: float):
    """Fast matrix NMS over one image's k candidates: ``b`` (k,4) boxes
    and ``s`` (k,C-1) foreground scores, in the preselect's order.  The
    part every caller shares, wherever its candidates came from."""
    k, num_fg = s.shape
    overlap = (_iou_matrix(b) > iou_thresh) \
        & ~jnp.eye(k, dtype=bool)                        # (k,k), no self
    # Suppression ON DEMAND: a full (k,k,C) "who beats whom" tensor would
    # dominate the postprocess, but only pairs that can reach the output
    # need their verdict.  Preselect the top 4·max_out (candidate, class)
    # pairs by raw score and test just those M pairs against all k
    # candidates — an (M,k) compare instead of (k,k,C), ~100× less work.
    # Exactness: fast-NMS suppression is pairwise (no cascade), so each
    # selected pair's verdict is exact; the output can only differ if
    # >3·max_out of the best raw pairs are all suppressed — then slots
    # beyond are filled from the next-best survivors of the M pool.
    # tiny inputs: fewer slates than max_out
    m = min(max_out, k * num_fg)
    M = min(4 * max_out, k * num_fg)
    raw, fidx = _best_pairs(s, M)                        # (M,) pair preselect
    cand = fidx // num_fg                                # (M,) candidate row
    ccls = fidx % num_fg                                 # (M,) class column
    sj = s.T[ccls]                                   # (M,k) class scores ∀j
    # beats: overlapping candidate with strictly higher class score;
    # EXACT ties broken by candidate rank (without a tie-break the
    # symmetric comparison keeps both duplicates of an equal-score pair)
    beats = (sj > raw[:, None]) | \
        ((sj == raw[:, None]) & (jnp.arange(k)[None, :] < cand[:, None]))
    suppressed = jnp.any(overlap[cand] & beats, axis=-1)       # (M,)
    keep = (raw > score_thresh) & ~suppressed
    kept = jnp.where(keep, raw, jnp.asarray(fill, s.dtype))
    top_scores, sel = jax.lax.top_k(kept, m)             # final slate from M
    out_b = b[cand[sel]]
    out_s = top_scores
    out_c = (ccls[sel] + 1).astype(jnp.int32)            # back to class ids
    if m < max_out:                                      # fixed-shape contract
        pad = max_out - m
        out_b = jnp.pad(out_b, ((0, pad), (0, 0)))
        out_s = jnp.pad(out_s, (0, pad), constant_values=fill)
        out_c = jnp.pad(out_c, (0, pad))
    return out_b, out_s, out_c


def batched_nms(boxes, class_scores, max_out: int = 100,
                iou_thresh: float = 0.5, score_thresh: float = 0.25,
                pre_topk: int = _PRE_TOPK, fill: float = 0.0):
    """Class-aware matrix NMS over (A,4) boxes and (A,C) scores (class 0
    = background, skipped as in the reference decoder).  Returns
    (boxes (max_out,4), scores (max_out,), classes (max_out,) int32).

    TPU-native redesign (vs the reference's per-class greedy loops,
    mobilenetssd.cc): ONE shared candidate top-k per image on the best
    class score, ONE IoU matrix for all classes, and fast matrix
    suppression — a candidate survives a class iff no overlapping
    candidate has a higher score for that class (YOLACT-style fast NMS).
    No per-class sorts, no sequential scan: everything is batched matrix
    work XLA fuses into a handful of kernels.  ``nms_single`` keeps the
    exact sequential-greedy semantics for callers that need it.

    Scores need not be probabilities: any monotonic score space works
    (ranking and thresholding are order-only).  When scoring in logit
    space pass ``score_thresh`` in the same space and ``fill=-inf`` so
    suppressed/padded slots stay below every survivor.

    This entry takes an ASSEMBLED score tensor (YOLO, any caller that has
    one); ``ssd_detect_apply`` picks the same candidates from its heads'
    maps without assembling it, and both share ``_suppress_candidates``."""
    with jax.named_scope("topk"):
        fg = class_scores[:, 1:]                     # drop background
        k = min(pre_topk, boxes.shape[0])
        _, idx = _preselect_top_k(fg.max(axis=-1), k)  # shared candidates
    with jax.named_scope("nms"):
        return _suppress_candidates(boxes[idx], fg[idx], max_out,
                                    iou_thresh, score_thresh, fill)


# -- model -------------------------------------------------------------------


def _head_init(rng, cin, num_anchors, num_classes) -> Params:
    return {
        "loc": _conv_init(rng, 3, 3, cin, num_anchors * 4),
        "cls": _conv_init(rng, 3, 3, cin, num_anchors * num_classes),
    }


def ssd_mobilenet_v2_init(key, num_classes: int = 91,
                          image_size: int = 300) -> Params:
    from .mobilenet import _rng_of

    rng = _rng_of(key)
    params: Params = {"backbone": mobilenet_v2_init(rng, num_classes=1)}
    del params["backbone"]["head"], params["backbone"]["last"]
    # extra pyramid layers off the final 320-ch stride-32 map
    extras, cin = [], 320
    for c in _EXTRA_CHANNELS:
        extras.append(_conv_init(rng, 3, 3, cin, c))
        cin = c
    params["extras"] = extras
    head_cins = [96, 320, *list(_EXTRA_CHANNELS)]
    params["heads"] = [
        _head_init(rng, c, _ANCHORS_PER_CELL, num_classes)
        for c in head_cins]
    params["num_classes"] = num_classes
    return params


def _ssd_head_maps(params: Params, x, train: bool, dtype):
    """Backbone, extras and heads: the six maps' raw head outputs where
    the convolutions leave them, ``locs[i]`` (N,h,w,A·4) and ``clss[i]``
    (N,h,w,A·C) with channel = anchor·C + class."""
    feat, taps = mobilenet_v2_backbone(
        params["backbone"], x, train=train, dtype=dtype, taps=(_TAP_BLOCK,))
    maps = [taps[0], feat]
    # stage scopes of the device trace (Documentation/observability.md)
    with jax.named_scope("extras"):
        h = feat
        for p in params["extras"]:
            h = _conv_bn(p, h, stride=2, train=train, dtype=dtype)
            maps.append(h)
    locs, clss = [], []
    with jax.named_scope("heads"):
        for fmap, head in zip(maps, params["heads"]):
            locs.append(_conv_bn(head["loc"], fmap, stride=1, relu6=False,
                                 train=train, dtype=dtype))
            clss.append(_conv_bn(head["cls"], fmap, stride=1, relu6=False,
                                 train=train, dtype=dtype))
    return locs, clss


def ssd_mobilenet_v2_apply(params: Params, x, train: bool = False,
                           dtype=jnp.bfloat16, cls_dtype=jnp.float32):
    """``x``: (N, H, W, 3) normalized NHWC → (loc (N,A,4), cls (N,A,C))
    raw head outputs in float32 (the "model output" schema the decoder layer
    consumes, matching the reference's filter/decoder split).

    The (N,A,C) tensor is this entry's OUTPUT, so here it is assembled;
    the fused detect path (``ssd_detect_apply``) never builds it.
    ``cls_dtype`` is its dtype; loc is float32 — box regression wants the
    mantissa, and at (N,A,4) it is ~23× smaller than cls."""
    num_classes = params["num_classes"]
    locs, clss = _ssd_head_maps(params, x, train, dtype)
    n = x.shape[0]
    with jax.named_scope("heads"):
        locs = [loc.reshape(n, -1, 4) for loc in locs]
        clss = [cls.reshape(n, -1, num_classes) for cls in clss]
    with jax.named_scope("decode"):
        return (jnp.concatenate(locs, axis=1).astype(jnp.float32),
                jnp.concatenate(clss, axis=1).astype(cls_dtype))


def _map_candidates(clss, num_classes: int, k: int):
    """Candidates first, for ONE image: from the six class maps (h,w,A·C)
    to the ``k`` candidates' anchor indices (k,) and foreground scores
    (k,C-1) — value for value the ``idx`` and ``cls[:, 1:][idx]`` of the
    assembled (A,C) tensor, which is never written.  Anchor index =
    cell·A + anchor over the maps' cells in order (``ssd_anchors``)."""
    A, C = _ANCHORS_PER_CELL, num_classes
    with jax.named_scope("topk"):
        # every anchor's best foreground class, reduced inside the A·C
        # channels by anchor segment: one read of the heads' output.
        # Static slices, not masks: a masked maximum fuses into the class
        # convolution's epilogue on the v5e, and that convolution then
        # rounds a few logits a window differently (PERF.md section 6)
        best = jnp.concatenate([
            jnp.stack([jnp.max(c[..., a * C + 1:(a + 1) * C], axis=-1)
                       for a in range(A)], axis=-1).reshape(-1)
            for c in clss])                               # (cells·A,)
        _, idx = _preselect_top_k(best, k)                # shared candidates
    with jax.named_scope("decode"):
        # the candidates' cells' rows (the cell axis concatenates without
        # reshaping the minor dimension), then each row's own anchor
        rows = jnp.concatenate([c.reshape(-1, A * C) for c in clss])[idx // A]
        s = rows[:, 1:C]
        for a in range(1, A):       # static lane slices and a select
            s = jnp.where((idx % A == a)[:, None],
                          rows[:, a * C + 1:(a + 1) * C], s)
    return idx, s


def ssd_detect_apply(params: Params, x, anchors, max_out: int = 100,
                     dtype=jnp.bfloat16, score_thresh: float = 0.25,
                     iou_thresh: float = 0.5):
    """End-to-end jittable detector: image → (boxes, scores, classes).
    Backbone + decode + NMS in ONE XLA computation.  Takes ``params`` as
    an argument (not a closure) so callers can device-place the weight
    pytree instead of baking it into the HLO as literals.

    The postprocess touches only candidates: the shared top-k runs on
    each anchor's best class, reduced from the heads' maps where they lie,
    and only the chosen cells' class rows are gathered and sliced to their
    anchor (``_map_candidates``); the (N,A,C) tensor of the wire schema is
    never assembled.  The detections are those of ``batched_nms`` over
    the assembled tensors, to the bit.

    It runs in LOGIT space on the bfloat16 class maps: sigmoid is
    monotonic, so ranking/thresholding on raw logits (with the threshold
    mapped through logit()) selects the same detections, and the
    probability sigmoid is applied only to the ``max_out`` survivors.
    Suppressed/padded slots carry -inf logits → exactly 0.0 scores."""
    locs, clss = _ssd_head_maps(params, x, False, dtype)
    n = x.shape[0]
    with jax.named_scope("decode"):
        # all A boxes, as ever: (N,A,4) is 1/23 of the class tensor, and
        # decoding before the gather keeps the anchor table a constant
        # operand, which XLA folds the coder's scales into — gathered
        # anchors round differently in the last bit
        loc = jnp.concatenate([m.reshape(n, -1, 4) for m in locs], axis=1)
        boxes = decode_boxes(loc.astype(jnp.float32), jnp.asarray(anchors))
    k = min(_PRE_TOPK, boxes.shape[1])
    lt = float(np.log(score_thresh / (1.0 - score_thresh)))

    # a Python int in the params: configuration, which the filter leaves
    # as it is (only array leaves are weights, placed and traced)
    num_classes = int(params["num_classes"])

    def per_image(boxes, clss):
        idx, s = _map_candidates(clss, num_classes, k)
        with jax.named_scope("nms"):
            return _suppress_candidates(boxes[idx], s, max_out, iou_thresh,
                                        lt, -np.inf)

    out_b, out_s, out_c = jax.vmap(per_image)(boxes, clss)
    with jax.named_scope("nms"):
        return out_b, jax.nn.sigmoid(out_s.astype(jnp.float32)), out_c


def ssd_detect_fn(params: Params, anchors, max_out: int = 100,
                  dtype=jnp.bfloat16):
    """Closure form of :func:`ssd_detect_apply` (x-only callable) for
    direct jit use in tests/scripts; prefer ``ssd_detect_apply`` with an
    explicit params argument when registering with the filter layer."""

    def fn(x):
        return ssd_detect_apply(params, x, anchors, max_out=max_out,
                                dtype=dtype)

    return fn


def register_ssd(name: str = "ssd_mobilenet_v2", num_classes: int = 91,
                 batch: int = 1, size: int = 300, max_out: int = 100,
                 seed: int = 0, end_to_end: bool = True) -> str:
    """Register SSD with the jax-xla filter.  ``end_to_end=True`` bakes
    decode+NMS into the model (TPU-resident post-processing);
    ``False`` outputs raw (loc, cls) for the bounding_boxes decoder element
    (reference-shaped pipeline)."""
    from ..filters.jax_xla import register_model

    params = ssd_mobilenet_v2_init(jax.random.PRNGKey(seed), num_classes)
    if end_to_end:
        feature_sizes = tuple(
            int(np.ceil(size / s)) for s in (16, 32, 64, 128, 256, 512))
        anchors = ssd_anchors(size, feature_sizes)
        # params passed as a pytree (NOT closed over) so the filter layer
        # device-places the weights instead of baking ~MBs of literals
        # into the jitted HLO, and accelerator= device pinning applies.
        return register_model(
            name,
            lambda p, x: ssd_detect_apply(p, x, anchors, max_out=max_out),
            params=params, in_shapes=[(batch, size, size, 3)],
            in_dtypes=np.float32)
    return register_model(
        name, lambda p, x: ssd_mobilenet_v2_apply(p, x), params=params,
        in_shapes=[(batch, size, size, 3)], in_dtypes=np.float32)
