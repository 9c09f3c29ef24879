"""Candidates-first SSD post-processing (PR 30) against the assembled
path it replaced.

The reference below is the parent's code kept verbatim: ``batched_nms``
with its pair preselect over all k·(C-1) pairs, and ``ssd_detect_apply``
as concatenate-then-select over the (N,A,C) tensor.  The program's
``ssd_detect_apply`` (which never builds that tensor) and ``batched_nms``
(whose pair preselect reads the first min(M,k) rows) must serve the same
boxes, scores and classes, bit for bit.

"Bit for bit" is a statement about the arithmetic, so the comparisons run
op by op (``jax.disable_jit``): every primitive is then one IEEE
operation on both sides.  Under ``jit`` XLA's CPU backend contracts a
multiply and an add of ``decode_boxes`` into one fused multiply-add or
not, depending on which fusion they land in, and the last bit of a box
follows the fusion and not the mathematics (eager and jitted
``decode_boxes`` differ in 484 of 12,000 coordinates on one input); the
jitted cases therefore hold scores and classes to the bit and boxes to
one part in a million.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.models import ssd, yolo

_STRIDES = (16, 32, 64, 128, 256, 512)


# -- the parent's assembled path, verbatim -----------------------------------


def _ref_batched_nms(boxes, class_scores, max_out=100, iou_thresh=0.5,
                     score_thresh=0.25, pre_topk=128, fill=0.0):
    fg = class_scores[:, 1:]                     # drop background
    num_fg = fg.shape[-1]
    k = min(pre_topk, boxes.shape[0])
    _, idx = ssd._preselect_top_k(fg.max(axis=-1), k)  # shared candidates
    b = boxes[idx]                                   # (k,4)
    s = fg[idx]                                      # (k,C-1)
    overlap = (ssd._iou_matrix(b) > iou_thresh) \
        & ~jnp.eye(k, dtype=bool)                    # (k,k), no self
    flat = s.reshape(-1)                             # candidate-major
    m = min(max_out, flat.shape[0])
    M = min(4 * max_out, flat.shape[0])
    raw, fidx = jax.lax.top_k(flat, M)               # (M,) pair preselect
    cand = fidx // num_fg                            # (M,) candidate row
    ccls = fidx % num_fg                             # (M,) class column
    sj = s.T[ccls]                               # (M,k) class scores ∀j
    beats = (sj > raw[:, None]) | \
        ((sj == raw[:, None]) & (jnp.arange(k)[None, :] < cand[:, None]))
    suppressed = jnp.any(overlap[cand] & beats, axis=-1)   # (M,)
    keep = (raw > score_thresh) & ~suppressed
    kept = jnp.where(keep, raw, jnp.asarray(fill, s.dtype))
    top_scores, sel = jax.lax.top_k(kept, m)         # final slate from M
    out_b = b[cand[sel]]
    out_s = top_scores
    out_c = (ccls[sel] + 1).astype(jnp.int32)        # back to class ids
    if m < max_out:                                  # fixed-shape contract
        pad = max_out - m
        out_b = jnp.pad(out_b, ((0, pad), (0, 0)))
        out_s = jnp.pad(out_s, (0, pad), constant_values=fill)
        out_c = jnp.pad(out_c, (0, pad))
    return out_b, out_s, out_c


def _ref_detect_from_maps(locs, clss, anchors, num_classes, max_out=100,
                          score_thresh=0.25, iou_thresh=0.5):
    """The parent's ``ssd_detect_apply`` from the heads' outputs on:
    reshape every map to (N,·,C), concatenate to (N,A,C), decode all A
    boxes, select."""
    n = clss[0].shape[0]
    loc = jnp.concatenate([m.reshape(n, -1, 4) for m in locs],
                          axis=1).astype(jnp.float32)
    cls = jnp.concatenate([m.reshape(n, -1, num_classes) for m in clss],
                          axis=1)
    boxes = ssd.decode_boxes(loc, jnp.asarray(anchors))
    lt = float(np.log(score_thresh / (1.0 - score_thresh)))
    out_b, out_s, out_c = jax.vmap(
        lambda b, s: _ref_batched_nms(b, s, max_out=max_out,
                                      iou_thresh=iou_thresh,
                                      score_thresh=lt,
                                      fill=-np.inf))(boxes, cls)
    return out_b, jax.nn.sigmoid(out_s.astype(jnp.float32)), out_c


# -- inputs ------------------------------------------------------------------


def _feature_sizes(size):
    return tuple(int(np.ceil(size / s)) for s in _STRIDES)


def _random_maps(rng, size, num_classes, batch, levels=None):
    """Head outputs as the convolutions leave them: bf16 (N,h,w,A·4) and
    (N,h,w,A·C).  ``levels`` draws the logits from that many distinct
    bf16 values (heavy ties) and repeats boxes."""
    A = ssd._ANCHORS_PER_CELL
    locs, clss = [], []
    for fs in _feature_sizes(size):
        if levels:
            cls = rng.integers(0, levels, (batch, fs, fs, A * num_classes))
            cls = cls.astype(np.float32) * 0.5 - 1.0
            loc = rng.integers(0, 2, (batch, fs, fs, A * 4)).astype(
                np.float32) * 0.25
        else:
            cls = rng.standard_normal((batch, fs, fs, A * num_classes)) * 2
            loc = rng.standard_normal((batch, fs, fs, A * 4))
        locs.append(jnp.asarray(loc, jnp.bfloat16))
        clss.append(jnp.asarray(cls, jnp.bfloat16))
    return locs, clss


def _detect_from_maps(monkeypatch, locs, clss, anchors, num_classes, **kw):
    """The program's ``ssd_detect_apply`` with the given head outputs in
    place of the network's (the maps are its only input to what follows)."""
    monkeypatch.setattr(ssd, "_ssd_head_maps",
                        lambda p, x, train, dtype: (locs, clss))
    return ssd.ssd_detect_apply(
        {"num_classes": num_classes}, jnp.zeros((clss[0].shape[0], 1, 1, 3)),
        anchors, **kw)


def _same(got, want, names=("boxes", "scores", "classes")):
    for g, w, name in zip(got, want, names):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype, name
        # bit for bit: equal as raw bytes (so -0.0 != 0.0, NaN == NaN)
        assert g.tobytes() == w.tobytes(), (
            f"{name} differ in {np.sum(g != w)} places")


# -- equivalence: one parametrised test, each case counts ---------------------


_MAP_CASES = {
    # name: (size, classes, batch, tie levels, max_out)
    "random_300px_91": (300, 91, 2, None, 10),
    "heavy_ties_300px_91": (300, 91, 2, 3, 10),
    "max_out_100_nothing_truncated": (300, 91, 2, None, 100),
    "ties_max_out_100": (300, 91, 2, 4, 100),
    "two_classes_few_anchors": (16, 2, 2, None, 100),
    "maps_of_1x1_64px": (64, 4, 3, None, 7),
    "maps_of_1x1_64px_ties": (64, 4, 3, 2, 7),
}


@pytest.mark.parametrize("case", sorted(_MAP_CASES))
def test_detect_from_maps_equals_assembled_path(case, monkeypatch):
    size, num_classes, batch, levels, max_out = _MAP_CASES[case]
    rng = np.random.default_rng(sorted(_MAP_CASES).index(case))
    locs, clss = _random_maps(rng, size, num_classes, batch, levels)
    anchors = ssd.ssd_anchors(size, _feature_sizes(size))
    assert anchors.shape[0] == sum(
        c.shape[1] * c.shape[2] for c in clss) * ssd._ANCHORS_PER_CELL
    with jax.disable_jit():
        want = _ref_detect_from_maps(locs, clss, anchors, num_classes,
                                     max_out=max_out)
        got = _detect_from_maps(monkeypatch, locs, clss, anchors,
                                num_classes, max_out=max_out)
    _same(got, want)
    if case == "two_classes_few_anchors":
        # min(pre_topk, A)·(C−1) < max_out: the slate is padded
        assert anchors.shape[0] * (num_classes - 1) < max_out
    if levels is None and num_classes > 2:
        assert (np.asarray(got[1]) > 0.25).any(), "nothing detected"


@pytest.mark.parametrize("size,num_classes", [(300, 91), (64, 4)])
def test_whole_detector_equals_assembled_path(size, num_classes):
    """Through the real network: the wire-schema entry's (loc, cls) into
    the reference selection against the fused detect entry."""
    params = ssd.ssd_mobilenet_v2_init(3, num_classes=num_classes)
    anchors = ssd.ssd_anchors(size, _feature_sizes(size))
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, size, size, 3)), jnp.float32)

    def ref(x):
        loc, cls = ssd.ssd_mobilenet_v2_apply(
            params, x, dtype=jnp.bfloat16, cls_dtype=jnp.bfloat16)
        boxes = ssd.decode_boxes(loc, jnp.asarray(anchors))
        lt = float(np.log(0.25 / 0.75))
        b, s, c = jax.vmap(lambda b, s: _ref_batched_nms(
            b, s, max_out=10, score_thresh=lt, fill=-np.inf))(boxes, cls)
        return b, jax.nn.sigmoid(s.astype(jnp.float32)), c

    got = jax.jit(lambda x: ssd.ssd_detect_apply(
        params, x, anchors, max_out=10))(x)
    want = jax.jit(ref)(x)
    _same(got[1:], want[1:], ("scores", "classes"))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-6, atol=1e-7)


_NMS_CASES = {
    # name: (anchors, classes, tie levels, kwargs)
    "random_91": (3000, 91, None, dict(max_out=10)),
    "ties_91": (3000, 91, 3, dict(max_out=10)),
    "ties_duplicate_boxes_max_out_100": (500, 21, 2, dict(max_out=100)),
    "fewer_anchors_than_pre_topk": (50, 5, 4, dict(max_out=10)),
    "two_classes_few_anchors": (2, 2, None, dict(max_out=100)),
    "logit_space_fill": (700, 11, 5, dict(
        max_out=10, score_thresh=-1.0, fill=-np.inf)),
    "pre_topk_16": (400, 8, 3, dict(max_out=10, pre_topk=16)),
}


@pytest.mark.parametrize("case", sorted(_NMS_CASES))
def test_batched_nms_equals_parent(case):
    num_anchors, num_classes, levels, kw = _NMS_CASES[case]
    rng = np.random.default_rng(100 + sorted(_NMS_CASES).index(case))
    if levels:
        scores = rng.integers(0, levels, (num_anchors, num_classes)) / levels
        tl = rng.integers(0, 3, (num_anchors, 2)) * 0.2
    else:
        scores = rng.random((num_anchors, num_classes))
        tl = rng.random((num_anchors, 2))
    boxes = jnp.asarray(np.concatenate([tl, tl + 0.3], axis=1), jnp.float32)
    scores = jnp.asarray(scores, jnp.bfloat16 if "logit" in case
                         else jnp.float32)
    got = jax.jit(lambda b, s: ssd.batched_nms(b, s, **kw))(boxes, scores)
    want = jax.jit(lambda b, s: _ref_batched_nms(b, s, **kw))(boxes, scores)
    _same(got, want)      # no arithmetic on the boxes here: exact under jit


def test_yolo_call_equals_parent(monkeypatch):
    """YOLO passes an assembled (A, 1+C) tensor: same entry, same slate."""
    params = yolo.yolo_init(jax.random.PRNGKey(0), num_classes=6, width=8)
    x = jnp.asarray(np.random.default_rng(2).random((2, 64, 64, 3)),
                    jnp.float32)
    got = jax.jit(lambda x: yolo.yolo_detect_apply(
        params, x, max_out=20, score_thresh=0.05))(x)
    monkeypatch.setattr(yolo, "batched_nms", _ref_batched_nms)
    want = jax.jit(lambda x: yolo.yolo_detect_apply(
        params, x, max_out=20, score_thresh=0.05))(x)
    _same(got, want, ("boxes", "classes", "scores", "num"))


# -- step 3's lemma ----------------------------------------------------------


@pytest.mark.parametrize("k,num_fg,M,levels", [
    (128, 90, 40, 3), (128, 90, 40, 50), (128, 90, 400, 4),
    (16, 5, 40, 2), (7, 3, 40, 3), (64, 1, 40, 2), (40, 9, 40, 1),
])
def test_top_m_pairs_lie_in_the_first_m_rows(k, num_fg, M, levels):
    """Rows sorted by non-increasing row maximum, ties everywhere: the
    top-M of the first min(M,k) rows IS the top-M of all rows, values and
    indices (``lax.top_k`` breaks ties by lower index)."""
    M = min(M, k * num_fg)
    rng = np.random.default_rng(k * 1000 + num_fg * 10 + levels)
    for _ in range(50):
        s = rng.integers(0, levels, (k, num_fg)).astype(np.float32)
        s = s[np.argsort(-s.max(axis=1), kind="stable")]
        assert (np.diff(s.max(axis=1)) <= 0).all()
        s = jnp.asarray(s, jnp.bfloat16)
        v_all, i_all = jax.lax.top_k(s.reshape(-1), M)
        v_head, i_head = ssd._best_pairs(s, M)
        np.testing.assert_array_equal(np.asarray(i_head), np.asarray(i_all))
        np.testing.assert_array_equal(
            np.asarray(v_head, np.float32), np.asarray(v_all, np.float32))


# -- the mechanism's evidence -------------------------------------------------


@pytest.mark.parametrize("max_out", [10, 100])
def test_detect_program_never_assembles_anchors_by_classes(max_out):
    """The lowered text of the fused detect path at the cell's size holds
    no value of (…,3000,91) or (…,3000,90), and the pair preselect sorts
    min(4·max_out, 128)·90 values a frame."""
    params = ssd.ssd_mobilenet_v2_init(0, num_classes=91)
    anchors = ssd.ssd_anchors(300, _feature_sizes(300))
    assert anchors.shape == (3000, 4)
    text = jax.jit(lambda p, x: ssd.ssd_detect_apply(
        {**p, "num_classes": 91}, x, anchors, max_out=max_out)).lower(
        {k: v for k, v in params.items() if k != "num_classes"},
        jax.ShapeDtypeStruct((2, 300, 300, 3), jnp.float32)).as_text()
    assert not re.search(r"3000x9[01]x", text), "anchors x classes is built"
    assert "tensor<2x3000xbf16>" in text      # what the shared top-k reads
    # the three top-k of a frame: the shared preselect over the anchors,
    # the pair preselect over min(M, 128) rows, the final slate from M
    M = 4 * max_out
    sorts = set(re.findall(r"top_k[^\n]*?tensor<2x(\d+)xbf16>", text))
    assert sorts == {"3000", str(min(M, 128) * 90), str(M)}, sorts


def test_wire_schema_entry_still_assembles_its_output():
    params = ssd.ssd_mobilenet_v2_init(0, num_classes=91)
    x = jnp.zeros((2, 300, 300, 3), jnp.float32)
    loc, cls = jax.eval_shape(
        lambda x: ssd.ssd_mobilenet_v2_apply(params, x), x)
    assert loc.shape == (2, 3000, 4) and loc.dtype == jnp.float32
    assert cls.shape == (2, 3000, 91) and cls.dtype == jnp.float32
    loc, cls = jax.eval_shape(lambda x: ssd.ssd_mobilenet_v2_apply(
        params, x, cls_dtype=jnp.bfloat16), x)
    assert cls.shape == (2, 3000, 91) and cls.dtype == jnp.bfloat16
