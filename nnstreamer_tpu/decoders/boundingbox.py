"""``bounding_boxes`` decoder: detection model output → box overlay video.

Parity target: /root/reference/ext/nnstreamer/tensor_decoder/
tensordec-boundingbox.cc (981 LoC) with per-model strategies in
box_properties/: mobilenetssd.cc (:420 — box priors + scaled decode),
mobilenetssdpp.cc (:296 — post-processed 4-tensor layout), yolo.cc (:384 —
v5 and v8 layouts).  Options follow the reference grammar:

- option1 — decoding scheme: ``mobilenet-ssd`` | ``mobilenet-ssd-postprocess``
  | ``yolov5`` | ``yolov8`` | ``ov-person-detection`` (OpenVINO 7-value
  descriptor rows) | ``mp-palm-detection`` (MediaPipe palm anchors +
  clamped-sigmoid scores)
- option2 — label file path
- option3 — scheme detail (mobilenet-ssd: box-priors file path or blank to
  synthesize SSD anchors; yolo: "<conf_thresh>:<iou_thresh>")
- option4 — output video size ``WIDTH:HEIGHT``
- option5 — model input size ``WIDTH:HEIGHT`` (yolo box scaling)
- option7 — render backend: ``host`` (default, numpy rasterization) |
  ``device`` (overlay computed ON the accelerator as one XLA program —
  boxutil.device_render_fn; mobilenet-ssd-postprocess batched layout only).
  The TPU-native answer to the reference's CPU ``draw()``: the canvas
  never crosses to the host, so the decode stage cannot bottleneck the
  device (round-2 verdict: one host overlay thread held the composite
  pipeline to 4.2k fps while the device sustained 10.7k).  Device-path
  trade-offs, by design: label text is NOT rasterized (text rendering is
  a host-font operation — configuring option2 together with
  option7=device logs a one-time warning), and the structured detections
  are attached as device arrays at ``meta["detections_device"]``
  instead of host ``meta["detections"]`` — pulling per-box python
  objects would reintroduce the host round-trip this path removes.

Output: RGBA overlay frame (video/x-raw) with the structured detections
attached at ``buffer.meta["detections"]`` (host path) or
``buffer.meta["detections_device"]`` (device path, see option7) — the
TPU-native addition so downstream logic does not have to re-parse pixels.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..core import Buffer, Caps, CapsStruct, Tensor, TensorSpec, TensorsSpec
from ..utils.stats import DISPATCH_STATS
from . import Decoder, JitFnCache, drain_once, register_decoder
from .boxutil import Detection, draw_boxes, load_labels, nms, sigmoid

_SCALE_XY = 10.0
_SCALE_WH = 5.0

#: yolo device pre-reduction keeps the top-K anchors by best class
#: score and drains only those (K, 6) rows — identical to the host
#: decode whenever the frame has <= K above-threshold candidates (a
#: realistic frame has tens; K bounds the worst case, e.g. noise)
_YOLO_TOPK = 512

#: (shape, v8, k) → jitted candidate filter (shared bounded cache)
_yolo_fns = JitFnCache()


def _yolo_prereduce_fn(shape, v8: bool, k: int):
    """Jitted yolo candidate filter: raw output → (K, 6) rows of
    [cx, cy, w, h, best_score, class], top-K by score, on device.  The
    full (A, 5+C) tensor never crosses to host — only the K candidate
    rows do, one packed drain (~25k x 85 floats down to 512 x 6)."""
    def build():
        import jax
        import jax.numpy as jnp

        def f(out):
            if v8:
                # (1, 4+C, A) → (A, 4+C); no objectness
                arr = out.reshape(out.shape[-2], out.shape[-1]).T
                boxes, scores = arr[:, :4], arr[:, 4:]
            else:
                # (1, A, 5+C): xywh + objectness + class confs
                arr = out.reshape(-1, out.shape[-1])
                boxes = arr[:, :4]
                scores = arr[:, 5:] * arr[:, 4:5]
            best = jnp.max(scores, axis=1)
            cls = jnp.argmax(scores, axis=1)
            kk = min(k, best.shape[0])
            val, idx = jax.lax.top_k(best, kk)
            return jnp.concatenate(
                [boxes[idx].astype(jnp.float32),
                 val[:, None].astype(jnp.float32),
                 cls[idx][:, None].astype(jnp.float32)], axis=1)

        return jax.jit(f)

    return _yolo_fns.get_or_build((tuple(shape), bool(v8), int(k)),
                                  build)


@register_decoder
class BoundingBoxes(Decoder):
    MODE = "bounding_boxes"

    def __init__(self):
        super().__init__()
        self.scheme = "mobilenet-ssd"
        self.labels: List[str] = []
        self.priors: Optional[np.ndarray] = None
        self.out_w, self.out_h = 300, 300
        self.in_w, self.in_h = 300, 300
        self.conf_thresh = 0.25
        self.iou_thresh = 0.5
        self.backend = "host"
        self._warned_device_labels = False
        #: set by the fusion pass when the device overlay program is
        #: compiled INTO the upstream jax-xla filter: decode() then
        #: consumes a ready canvas instead of rendering
        self.fused_upstream = False
        #: mp-palm score threshold (reference default 0.5), settable
        #: via option3 when the scheme is mp-palm-detection
        self._palm_thresh: Optional[float] = None
        self._palm_anchor_cache: Optional[np.ndarray] = None

    def options_updated(self) -> None:
        if self.options[6]:
            self.backend = self.options[6].strip().lower()
        if self.options[0]:
            self.scheme = self.options[0].strip().lower()
        if self.options[1]:
            self.labels = load_labels(self.options[1])
        self._interpret_opt3(self.options[2])
        if self.options[3]:
            w, _, h = self.options[3].partition(":")
            self.out_w, self.out_h = int(w), int(h or w)
        if self.options[4]:
            w, _, h = self.options[4].partition(":")
            self.in_w, self.in_h = int(w), int(h or w)

    def _interpret_opt3(self, o3: Optional[str]) -> None:
        """option3 is scheme-dependent: yolo → "<conf>:<iou>" thresholds;
        mobilenet-ssd → box-priors file path.  Interpreted against the
        *current* scheme on every options update, so the order in which
        option1/option3 arrive cannot mis-route it (and a priors path set
        before a scheme switch to yolo never reaches float())."""
        if not o3:
            return
        if self.scheme.startswith("yolo"):
            c, _, i = o3.partition(":")
            try:
                if c:
                    self.conf_thresh = float(c)
                if i:
                    self.iou_thresh = float(i)
            except ValueError:
                pass  # not a threshold pair (e.g. stale priors path)
        elif self.scheme == "mp-palm-detection":
            # reference grammar: threshold[:num_layers:min_scale:
            # max_scale:offset_x:offset_y:stride...]; the threshold is
            # the load-bearing field, the rest default to the palm
            # model's constants
            try:
                self._palm_thresh = float(o3.partition(":")[0])
            except ValueError:
                pass
        else:
            try:
                self.priors = np.loadtxt(o3, dtype=np.float32)
            except (OSError, ValueError):
                pass

    def out_caps(self, in_spec: TensorsSpec) -> Caps:
        # Batched postprocess input — boxes (B,N,4) from an on-device
        # decode+NMS head — yields one buffer of B overlay frames; the
        # ``frames`` field is this framework's batched-video extension
        # (the reference is strictly one frame per buffer).
        frames = 1
        if self.fused_upstream:
            # overlay fused into the upstream filter (runtime/fusion.py):
            # tensor 0 of the incoming schema IS the rendered canvas
            t0 = in_spec.tensors[0] if in_spec.tensors else None
            if t0 is not None and t0.rank == 4 and t0.shape[0] > 1:
                frames = t0.shape[0]
        elif in_spec.tensors and in_spec.tensors[0].rank == 3 \
                and self.scheme in ("mobilenet-ssd-postprocess",
                                    "mobilenetssd-pp"):
            frames = in_spec.tensors[0].shape[0]
        extra = {"frames": frames} if frames > 1 else {}
        return Caps.new(CapsStruct.make(
            "video/x-raw", format="RGBA", width=self.out_w,
            height=self.out_h, framerate=in_spec.rate, **extra))

    # -- schemes -------------------------------------------------------------

    def _anchors(self, num: int) -> np.ndarray:
        if self.priors is not None and len(self.priors) >= num:
            return self.priors[:num]
        from ..models.ssd import ssd_anchors

        # synthesize the standard SSD anchor table for the model input size
        fs = tuple(int(np.ceil(self.in_w / s))
                   for s in (16, 32, 64, 128, 256, 512))
        a = ssd_anchors(self.in_w, fs)
        if len(a) < num:
            a = np.vstack([a] * (num // len(a) + 1))
        return a[:num]

    def _decode_mobilenet_ssd(self, buf: Buffer) -> List[Detection]:
        """Raw 2-tensor layout: loc (A,4) or (1,A,4) + cls scores (A,C)."""
        loc = buf.tensors[0].np().reshape(-1, 4)
        cls = buf.tensors[1].np()
        cls = cls.reshape(-1, cls.shape[-1])
        anchors = self._anchors(loc.shape[0])
        cy = loc[:, 0] / _SCALE_XY * anchors[:, 2] + anchors[:, 0]
        cx = loc[:, 1] / _SCALE_XY * anchors[:, 3] + anchors[:, 1]
        h = np.exp(loc[:, 2] / _SCALE_WH) * anchors[:, 2]
        w = np.exp(loc[:, 3] / _SCALE_WH) * anchors[:, 3]
        scores = sigmoid(cls)
        dets = []
        for a in range(loc.shape[0]):
            c = int(scores[a, 1:].argmax()) + 1  # class 0 = background
            s = float(scores[a, c])
            if s < self.conf_thresh:
                continue
            dets.append(Detection(
                x=float(cx[a] - w[a] / 2), y=float(cy[a] - h[a] / 2),
                w=float(w[a]), h=float(h[a]), class_id=c, score=s))
        return nms(dets, self.iou_thresh)

    def _decode_ssd_postprocess(self, buf: Buffer):
        """Post-processed 4-tensor layout (mobilenetssdpp.cc): boxes
        (N,4 ymin,xmin,ymax,xmax normalized), classes (N,), scores (N,),
        num_detections (1,).  Batched model output — boxes (B,N,4) from an
        on-device decode+NMS head (models/ssd.py end_to_end) — yields a
        list of per-frame detection lists."""
        boxes_t = buf.tensors[0].np()
        # (1,N,4) is the canonical single-frame TFLite layout — flatten;
        # only a true multi-frame batch (B>1) takes the batched branch,
        # matching out_caps' frames= decision
        if boxes_t.ndim == 3 and boxes_t.shape[0] > 1:
            classes = buf.tensors[1].np()
            scores = buf.tensors[2].np()
            nums = buf.tensors[3].np().reshape(-1) \
                if buf.num_tensors > 3 else None
            return [
                self._ssd_pp_frame(boxes_t[b], classes[b], scores[b],
                                   int(nums[b]) if nums is not None
                                   else scores.shape[1])
                for b in range(boxes_t.shape[0])]
        boxes = boxes_t.reshape(-1, 4)
        classes = buf.tensors[1].np().reshape(-1)
        scores = buf.tensors[2].np().reshape(-1)
        n = int(buf.tensors[3].np().reshape(-1)[0]) \
            if buf.num_tensors > 3 else len(scores)
        return self._ssd_pp_frame(boxes, classes, scores, n)

    def _ssd_pp_frame(self, boxes, classes, scores, n) -> List[Detection]:
        dets = []
        for i in range(min(n, len(scores))):
            if scores[i] < self.conf_thresh:
                continue
            ymin, xmin, ymax, xmax = boxes[i]
            dets.append(Detection(
                x=float(xmin), y=float(ymin), w=float(xmax - xmin),
                h=float(ymax - ymin), class_id=int(classes[i]),
                score=float(scores[i])))
        return dets  # already NMS'd by the model

    def _decode_ov_detection(self, buf: Buffer) -> List[Detection]:
        """``ov-person-detection``: one (7, 200) tensor of rows
        [image_id, label, conf, x_min, y_min, x_max, y_max]; a negative
        image_id terminates the list, conf ≥ 0.8 keeps the row (parity:
        box_properties/ovdetection.cc — the OpenVINO person-detection
        descriptor layout)."""
        arr = buf.tensors[0].np().reshape(-1, 7)
        dets: List[Detection] = []
        for row in arr:
            if row[0] < 0:
                break
            if row[2] < 0.8:
                continue
            x0, y0, x1, y1 = (float(row[3]), float(row[4]),
                              float(row[5]), float(row[6]))
            dets.append(Detection(
                x=x0, y=y0, w=x1 - x0, h=y1 - y0,
                class_id=int(row[1]), score=float(row[2])))
        return dets

    # MediaPipe palm anchor defaults (box_properties/mppalmdetection.cc)
    _PALM_STRIDES = (8, 16, 16, 16)
    _PALM_MIN_SCALE = 1.0
    _PALM_MAX_SCALE = 1.0
    _PALM_OFFSET = 0.5
    _PALM_INPUT = 192

    def _palm_anchors(self) -> np.ndarray:
        """MediaPipe SSD anchor generation for the palm model: per run
        of equal strides, two unit-aspect anchors per layer in the run;
        centers at (cell + 0.5)/grid (parity:
        mp_palm_detection_generate_anchors).  Returns (A, 4) rows of
        [y_center, x_center, h, w]; built once and cached (the
        reference generates at option-set time)."""
        if self._palm_anchor_cache is not None:
            return self._palm_anchor_cache
        n = len(self._PALM_STRIDES)

        def scale(i):
            if n == 1:
                return (self._PALM_MIN_SCALE + self._PALM_MAX_SCALE) / 2
            return self._PALM_MIN_SCALE + \
                (self._PALM_MAX_SCALE - self._PALM_MIN_SCALE) * i / (n - 1)

        out: List[List[float]] = []
        layer = 0
        while layer < n:
            run_end = layer
            dims: List[float] = []
            while run_end < n and \
                    self._PALM_STRIDES[run_end] == self._PALM_STRIDES[layer]:
                dims.extend([scale(run_end), scale(run_end + 1)])
                run_end += 1
            grid = int(np.ceil(self._PALM_INPUT /
                               self._PALM_STRIDES[layer]))
            for y in range(grid):
                for x in range(grid):
                    cy = (y + self._PALM_OFFSET) / grid
                    cx = (x + self._PALM_OFFSET) / grid
                    for s in dims:
                        out.append([cy, cx, s, s])
            layer = run_end
        self._palm_anchor_cache = np.asarray(out, np.float32)
        return self._palm_anchor_cache

    def _decode_mp_palm(self, buf: Buffer) -> List[Detection]:
        """``mp-palm-detection``: boxes (18, A) + raw scores (A,);
        anchors regress MediaPipe-style (offsets scaled by the anchor
        box relative to the model input size), scores pass through a
        clamped sigmoid (parity: box_properties/mppalmdetection.cc
        _get_objects_mp_palm_detection)."""
        boxes = buf.tensors[0].np().reshape(-1, 18)  # (A, 18) rows
        scores = buf.tensors[1].np().ravel()
        anchors = self._palm_anchors()
        a = min(len(anchors), len(boxes), len(scores))
        s = 1.0 / (1.0 + np.exp(-np.clip(scores[:a], -100.0, 100.0)))
        thresh = 0.5 if self._palm_thresh is None else self._palm_thresh
        dets: List[Detection] = []
        for d in np.nonzero(s >= thresh)[0]:
            ay, ax, ah, aw = anchors[d]
            b = boxes[d]
            yc = b[0] / self.in_h * ah + ay
            xc = b[1] / self.in_w * aw + ax
            h = b[2] / self.in_h * ah
            w = b[3] / self.in_w * aw
            dets.append(Detection(
                x=max(float(xc - w / 2), 0.0),
                y=max(float(yc - h / 2), 0.0),
                w=float(w), h=float(h), class_id=0, score=float(s[d])))
        # the reference suppresses palms at a fixed 0.05 IoU
        # (mppalmdetection.cc nms(results, 0.05f)), far stricter than
        # the generic default
        return nms(dets, 0.05)

    def _decode_yolo(self, buf: Buffer, v8: bool) -> List[Detection]:
        t = buf.tensors[0]
        if t.is_device:
            # device pre-reduction: max/argmax/top-k run in HBM and only
            # the (K, 6) candidate rows drain — the NMS input set is
            # identical to the host decode for any frame with <= K
            # above-threshold anchors
            rows = np.asarray(Tensor(
                _yolo_prereduce_fn(t.spec.shape, v8, _YOLO_TOPK)(
                    t.jax())).np())
            DISPATCH_STATS.count("decoder")
            scale = np.array([self.in_w, self.in_h, self.in_w, self.in_h],
                             np.float32)
            dets = []
            for r in rows:
                if r[4] < self.conf_thresh:
                    break  # rows are score-sorted: nothing further passes
                cx, cy, w, h = r[:4] / scale
                dets.append(Detection(
                    x=float(cx - w / 2), y=float(cy - h / 2), w=float(w),
                    h=float(h), class_id=int(r[5]), score=float(r[4])))
            return nms(dets, self.iou_thresh)
        out = t.np()
        if v8:
            # (1, 4+C, A) → (A, 4+C); no objectness, scores are class confs
            arr = out.reshape(out.shape[-2], out.shape[-1]).T
            boxes, confs = arr[:, :4], arr[:, 4:]
            scores = confs
        else:
            # (1, A, 5+C): xywh + objectness + class confs
            arr = out.reshape(-1, out.shape[-1])
            boxes = arr[:, :4]
            scores = arr[:, 5:] * arr[:, 4:5]
        dets = []
        cand = np.nonzero(scores.max(axis=1) >= self.conf_thresh)[0]
        for a in cand:
            c = int(scores[a].argmax())
            cx, cy, w, h = boxes[a] / np.array(
                [self.in_w, self.in_h, self.in_w, self.in_h], np.float32)
            dets.append(Detection(
                x=float(cx - w / 2), y=float(cy - h / 2), w=float(w),
                h=float(h), class_id=c, score=float(scores[a, c])))
        return nms(dets, self.iou_thresh)

    # -- device render path --------------------------------------------------

    def _device_active(self) -> bool:
        return self.backend == "device" and self.scheme in (
            "mobilenet-ssd-postprocess", "mobilenetssd-pp")

    def device_post_program(self):
        """For the fusion pass (runtime/fusion.py): a jit-inlinable
        epilogue mapping the upstream filter's postprocess outputs
        (boxes, classes, scores, num) to (canvas, boxes, classes,
        scores, num) — the whole transform+model+NMS+overlay pipeline
        then compiles as ONE XLA program with a single dispatch.
        Returns None when this decoder configuration cannot render
        on-device."""
        if not self._device_active():
            return None
        import jax
        import jax.numpy as jnp

        from .boxutil import device_render_fn

        out_h, out_w, conf = self.out_h, self.out_w, self.conf_thresh

        def post(*outs):
            # accept every layout the unfused device path accepts:
            # boxes (B,N,4) or single-frame (N,4); optional num tensor
            boxes = outs[0]
            if boxes.ndim == 2:
                boxes = boxes[None]
            b, n = boxes.shape[0], boxes.shape[1]
            classes = outs[1].reshape(b, n)
            scores = outs[2].reshape(b, n)
            num = outs[3].reshape(b) if len(outs) > 3 \
                else jnp.full((b,), n, jnp.int32)
            render = device_render_fn(b, n, out_h, out_w, conf)
            # the stage the device trace shows as nns.post/overlay
            with jax.named_scope("overlay"):
                canvas = render(boxes, classes, scores, num)
            return (canvas, *outs)

        # persistent AOT cache identity (runtime/compilecache.py):
        # everything the traced epilogue depends on.  The render fn
        # itself is versioned code, covered by the cache's library
        # version salt like the model fn is.
        post.chain_digest = "bounding_boxes:%s:%dx%d:%s" % (
            self.scheme, out_w, out_h, conf)
        return post

    def _decode_fused(self, buf: Buffer) -> Buffer:
        """Consume the fused program's output: tensor 0 is the rendered
        canvas; 1.. are the model's original postprocess tensors, kept
        device-resident as ``meta["detections_device"]`` with the same
        normalization as the unfused device path."""
        import jax.numpy as jnp

        canvas = buf.tensors[0].jax()
        batched = canvas.ndim == 4 and canvas.shape[0] > 1
        if canvas.ndim == 4 and not batched:
            canvas = canvas[0]
        out = Buffer(
            tensors=[Tensor(canvas,
                            TensorSpec.from_shape(canvas.shape, np.uint8))],
            pts=buf.pts, duration=buf.duration, meta=dict(buf.meta))
        if buf.num_tensors >= 4:
            boxes = buf.tensors[1].jax()
            if boxes.ndim == 2:
                boxes = boxes[None]
            b, n = boxes.shape[0], boxes.shape[1]
            out.meta["detections_device"] = {
                "boxes": boxes,
                "classes": buf.tensors[2].jax().reshape(b, n),
                "scores": buf.tensors[3].jax().reshape(b, n),
                "num": buf.tensors[4].jax().reshape(b)
                if buf.num_tensors > 4
                else jnp.full((b,), n, jnp.int32)}
        return out

    def wants_host_input(self) -> bool:
        # the device renderer consumes boxes/classes/scores/num in HBM;
        # tensor_decoder must not prefetch them to host
        return not self._device_active()

    def prereduce_active(self, buf: Buffer) -> bool:
        # any device-resident frame either pre-reduces on device (yolo
        # top-k) or drains once as a single packed array (decode below)
        # — the per-tensor prefetch would transfer what the reduction
        # makes redundant
        return any(t.is_device for t in buf.tensors)

    def _decode_device(self, buf: Buffer) -> Buffer:
        """Rasterize the overlay ON the accelerator (option7=device): the
        four postprocess tensors stay device-resident, one jitted XLA
        program writes every frame's rectangles, and the (B,H,W,4) canvas
        is returned as a device tensor.  Structured detections stay
        available as device arrays at ``meta["detections_device"]``
        (pulling per-box python Detection objects would reintroduce the
        host round-trip this path exists to avoid)."""
        import jax.numpy as jnp

        from .boxutil import device_render_fn

        boxes = buf.tensors[0].jax()
        # single-frame layouts ((N,4) or canonical TFLite (1,N,4)) must
        # keep the host path's (H,W,4) output rank; only a true batch
        # (B>1) emits (B,H,W,4) — same rule as out_caps/_decode_ssd_pp
        batched = boxes.ndim == 3 and boxes.shape[0] > 1
        if boxes.ndim == 2:
            boxes = boxes[None]
        b, n = boxes.shape[0], boxes.shape[1]
        classes = buf.tensors[1].jax().reshape(b, n)
        scores = buf.tensors[2].jax().reshape(b, n)
        num = buf.tensors[3].jax().reshape(b) if buf.num_tensors > 3 \
            else jnp.full((b,), n, jnp.int32)
        render = device_render_fn(b, n, self.out_h, self.out_w,
                                  self.conf_thresh)
        canvas = render(boxes, classes, scores, num)
        DISPATCH_STATS.count("decoder")
        if not batched:
            canvas = canvas[0]
        out = Buffer(
            tensors=[Tensor(canvas,
                            TensorSpec.from_shape(canvas.shape, np.uint8))],
            pts=buf.pts, duration=buf.duration, meta=dict(buf.meta))
        out.meta["detections_device"] = {
            "boxes": boxes, "classes": classes, "scores": scores,
            "num": num}
        return out

    # -- decode --------------------------------------------------------------

    def decode(self, buf: Buffer, in_spec: Optional[TensorsSpec]) -> Buffer:
        scheme = self.scheme
        if self._device_active():
            if self.labels and not self._warned_device_labels:
                self._warned_device_labels = True
                from ..utils.log import logw

                logw("bounding_boxes: option7=device draws boxes only — "
                     "label text (option2) is not rasterized on-device; "
                     "use option7=host for labeled overlays")
            # fused path: tensor 0 must actually BE a canvas (uint8,
            # rank 3/4) — a withdrawn fusion (flexible stream) leaves
            # raw detection tensors, which route to the normal renderer
            if self.fused_upstream and buf.num_tensors >= 1 and \
                    buf.tensors[0].spec.rank >= 3 and \
                    buf.tensors[0].spec.dtype.np_dtype == np.uint8:
                return self._decode_fused(buf)
            return self._decode_device(buf)
        if scheme not in ("yolov5", "yolov8"):
            # host decoders below read every tensor: drain the device-
            # resident ones with ONE packed d2h crossing (and seed their
            # host caches) instead of one blocking .np() per tensor —
            # the boxes/classes/scores/num layout used to pay 4
            # crossings per frame here (yolo pre-reduces on device
            # instead and must NOT drain its raw tensor)
            drain_once(buf.tensors)
        if scheme == "mobilenet-ssd":
            dets = self._decode_mobilenet_ssd(buf)
        elif scheme in ("mobilenet-ssd-postprocess", "mobilenetssd-pp"):
            dets = self._decode_ssd_postprocess(buf)
        elif scheme == "yolov5":
            dets = self._decode_yolo(buf, v8=False)
        elif scheme == "yolov8":
            dets = self._decode_yolo(buf, v8=True)
        elif scheme == "ov-person-detection":
            dets = self._decode_ov_detection(buf)
        elif scheme == "mp-palm-detection":
            dets = self._decode_mp_palm(buf)
        else:
            raise ValueError(f"bounding_boxes: unknown scheme {scheme!r}")
        batched = bool(dets) and isinstance(dets[0], list)
        for d in (x for f in dets for x in f) if batched else dets:
            if d.class_id < len(self.labels):
                d.label = self.labels[d.class_id]
        if batched:
            frame = np.zeros((len(dets), self.out_h, self.out_w, 4),
                             np.uint8)
            for b, f in enumerate(dets):
                draw_boxes(f, self.out_w, self.out_h,
                           labels=bool(self.labels), out=frame[b])
        else:
            frame = draw_boxes(dets, self.out_w, self.out_h,
                               labels=bool(self.labels))
        out = Buffer(
            tensors=[Tensor(frame,
                            TensorSpec.from_shape(frame.shape, np.uint8))],
            pts=buf.pts, duration=buf.duration, meta=dict(buf.meta))
        out.meta["detections"] = dets
        return out
