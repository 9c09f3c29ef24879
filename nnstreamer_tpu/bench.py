#!/usr/bin/env python
"""Benchmark: the BASELINE.md composite workload plus the classify slice.

Headline (the JSON line's value): **MobileNetV2-SSD composite pipeline**
throughput through real elements end to end:

    device_src(uint8 300x300 frames staged in HBM)
        ! tensor_transform(typecast+normalize)      <- fused into filter
        ! tensor_filter framework=jax-xla model=ssd (backbone + box
              decode + class-aware NMS, ONE XLA computation on-device)
        ! tensor_decoder mode=bounding_boxes option1=mobilenet-ssd-postprocess
              option7=device (overlay rasterized ON the TPU — one XLA
              program writes the (B,H,W,4) canvas; nothing crosses to host)
        ! appsink

The transform element is separate in the pipeline string; the runtime
fusion pass (runtime/fusion.py) compiles it into the filter's program —
`composite_fused_vs_unfused` and `fused_vs_unfused` report the measured
speedup of that pass on the composite and classify workloads.  Extra
fields:

- p50/p99_frame_latency_ms: per-frame e2e latency, batch=1 composite
  pipeline, frames paced 10 ms apart, pts-stamped at the source and
  measured at the sink after blocking on the device result (annotated
  link- or device-dominated).
- p50/p99_device_ms: each frame is bracketed by trivial-jit probes
  (floor = min) whose round trip is subtracted; burst-contaminated
  frames are excluded from the tail and counted in
  tail_excluded_frames.
- mfu + roofline: composite FLOPs from XLA cost analysis of the exact
  compiled program; the roofline block reports the program's own
  bytes/flops, its intensity ceiling, and HBM utilization.
- device_time_breakdown: backbone / postprocess / overlay / dispatch
  gap per batch, chained-dispatch two-N estimator over DISTINCT staged
  inputs.
- classify_fps, vit_fps/vit_mfu (Pallas flash-attention engaged),
  yolo_fps/yolo_mfu, tflite_mobilenet_v2_fps (the reference's own
  pretrained quant model, imported and batched).
- --mesh: weak-scaling mode (writes MESH_SCALING.json).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Baseline: BASELINE.md composite target 10,000 fps on v5e-8 => 1,250
fps/chip, p50 < 5 ms.

The default mode reports rates of a chip, so it refuses to run on any
other backend (``_chip_spec``) and takes its peaks from the
``obs/hwspec.py`` table by the chip's ``device_kind``.  The flagged
modes are CPU CI gates on counts and exactness.
"""

import json
import os
import sys
import time

import numpy as np

# hardware peaks: ONE source of truth (obs/hwspec.py) shared with the
# registry's live MFU join
from nnstreamer_tpu.obs.hwspec import (
    V5E,
    V5E_ICI_BYTES_PER_S,
    spec_for_device_kind,
)
from nnstreamer_tpu.obs.xlacost import cost_of, flops_bytes
from nnstreamer_tpu.utils.hw import require_devices

SSD_BATCH = int(os.environ.get("BENCH_SSD_BATCH", "256"))
SSD_BUFFERS = int(os.environ.get("BENCH_SSD_BUFFERS", "20"))
CLS_BATCH = int(os.environ.get("BENCH_BATCH", "512"))
CLS_BUFFERS = int(os.environ.get("BENCH_BUFFERS", "30"))
WARMUP = int(os.environ.get("BENCH_WARMUP", "3"))
LAT_FRAMES = int(os.environ.get("BENCH_LAT_FRAMES", "60"))
SSD_SIZE = 300
CLS_SIZE = 224
BASELINE_FPS_PER_CHIP = 10_000 / 8.0

# ViT slice: config chosen so the Pallas flash-attention kernel engages
# (head dim 512/4=128, patch seq (256/16)²=256 — both multiples of the
# kernel's 128 tiling; ops/kernels.py flash_attention)
VIT_BATCH = int(os.environ.get("BENCH_VIT_BATCH", "64"))
VIT_BUFFERS = int(os.environ.get("BENCH_VIT_BUFFERS", "15"))
VIT_SIZE, VIT_PATCH, VIT_DIM = 256, 16, 512
VIT_DEPTH, VIT_HEADS, VIT_MLP = 6, 4, 2048

# YOLO slice: the third model family end to end — v8-style pyramid +
# on-device decode/NMS + device overlay (round-3 verdict #8)
YOLO_BATCH = int(os.environ.get("BENCH_YOLO_BATCH", "64"))
YOLO_BUFFERS = int(os.environ.get("BENCH_YOLO_BUFFERS", "15"))
YOLO_SIZE = int(os.environ.get("BENCH_YOLO_SIZE", "640"))
# width 64 / depth 2 at 640px: yolov8n-class work per frame — the one
# figure is what yolo_flops() computes (emitted as
# yolo_gflops_per_frame), not a number repeated in prose
YOLO_WIDTH = int(os.environ.get("BENCH_YOLO_WIDTH", "64"))
YOLO_DEPTH = int(os.environ.get("BENCH_YOLO_DEPTH", "2"))


_SSD_SHARED = {}


def _ssd_params_anchors(size: int = SSD_SIZE, num_classes: int = 91):
    """Init the SSD weights/anchors ONCE per process and size: several
    workloads register the same model under different names/batches."""
    key = (size, num_classes)
    if key not in _SSD_SHARED:
        import jax

        from nnstreamer_tpu.models.params_io import weights_to_bf16
        from nnstreamer_tpu.models.ssd import (
            ssd_anchors,
            ssd_mobilenet_v2_init,
        )

        fs = tuple(int(np.ceil(size / s))
                   for s in (16, 32, 64, 128, 256, 512))
        # bf16-RESIDENT weights (round-4 verdict #1a): halves the
        # weight-read traffic; compute consumed bf16 already
        _SSD_SHARED[key] = (
            weights_to_bf16(ssd_mobilenet_v2_init(
                jax.random.PRNGKey(0), num_classes=num_classes)),
            ssd_anchors(size, fs))
    return _SSD_SHARED[key]


def _register_ssd_pp(name: str, batch: int, size: int = SSD_SIZE,
                     num_classes: int = 91):
    """Register the composite SSD with outputs in the reference
    postprocess wire order (boxes, classes, scores, num) that the
    bounding_boxes mobilenet-ssd-postprocess decoder consumes
    (parity: mobilenetssdpp.cc).  ``size``/``num_classes`` default to
    the bench configuration; ``chip_smoke.py`` passes them so its CPU
    test can run the same builder at a toy size."""
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.models.ssd import ssd_detect_apply

    params, anchors = _ssd_params_anchors(size, num_classes)

    # max_out=10 ≈ a realistic per-frame detection count; random-weight
    # noise scores would otherwise flood the host overlay stage with the
    # full top-100 per frame, benchmarking python box-drawing instead of
    # the pipeline
    def detect(p, x):
        boxes, scores, classes = ssd_detect_apply(p, x, anchors, max_out=10)
        num = jnp.sum((scores > 0.25).astype(jnp.int32), axis=-1)
        return boxes, classes, scores, num

    register_model(name, detect, params=params,
                   in_shapes=[(batch, size, size, 3)],
                   in_dtypes=np.float32)
    return detect, params, anchors


def _pool_size(num_buffers: int, frame_bytes: int,
               budget_bytes: float = 2e9) -> int:
    """Distinct staged frames per pipeline, capped by an HBM budget:
    every buffer distinct at the standard bench sizes, bounded so
    oversized BENCH_*_BUFFERS runs don't exhaust device memory."""
    cap = max(int(budget_bytes // max(frame_bytes, 1)), 4)
    return min(num_buffers, cap)


def _pull(sink, what: str):
    b = sink.pull(timeout=600)
    if b is None:
        raise RuntimeError(f"bench: {what} stalled (no buffer in 600 s)")
    return b


def _fetch_sync_small(buf):
    """Per-frame completion sync for LATENCY runs: fetch the SMALLEST
    tensor of the buffer whole (all outputs of one program materialize
    together, so any of them proves completion).  A direct tiny
    transfer — no sliced-getitem program — keeps the per-frame cost
    identical in structure to the bracketing probe, so the derived
    device excess isn't padded by an extra dispatch."""
    t = min(buf.tensors, key=lambda x: x.nbytes)
    return np.asarray(t.jax())


def _fetch_sync(out):
    """Wait for DEVICE COMPLETION of ``out`` (and, because the device
    executes dispatches in order, of everything dispatched before it)
    by fetching ONE element of the last output to the host.

    Kept from an earlier backend; ``chip_smoke.py`` section D checks
    that ``block_until_ready`` is an equally good completion fence on
    the attached chip, after which this can become one.  NOTE the
    element-getitem compiles a small program on first use per shape —
    callers must place one _fetch_sync BEFORE their timing window (a
    warmup sync) so the compile stall cannot let the device drain
    prefetched timed work; the pipeline benches time their own
    compiled program via _program_fps (chained differential)."""
    import jax

    leaf = jax.tree_util.tree_leaves(out)[0]
    if hasattr(leaf, "jax"):
        leaf = leaf.jax()
    idx = (0,) * getattr(leaf, "ndim", 0)
    return np.asarray(leaf[idx] if idx else leaf)


def _program_fps(p, flt_name: str, src_name: str, batch: int,
                 n: int = 8, reps: int = 3, pre=None,
                 post=None) -> float:
    """Throughput of the pipeline's OWN compiled executable, timed by
    chained async dispatch over the source's freshly staged pool
    (distinct inputs) with a completion FETCH at each chain end:
    t = (T(2n) - T(n)) / n, min over reps.

    The timed object is the program, not the buffer stream: the
    pipeline still runs end to end first, so the element graph,
    negotiation and fusion pass stay validated, and the timed
    executable is bit-for-bit the one the pipeline dispatches.
    ``pre`` optionally prepends a per-dispatch program (e.g. the
    standalone transform for an unfused filter), so its device time
    counts inside the chain."""
    import itertools

    import jax

    jitted = p[flt_name].subplugin._compiled.jitted
    if pre is not None or post is not None:
        base = jitted

        def jitted(x):  # noqa: F811
            y = base(pre(x)) if pre is not None else base(x)
            return post(*y) if post is not None else y
    pool0 = [slot[0] for slot in p[src_name]._pool]
    n = max(2, min(n, len(pool0) // 2))
    # per-CHAIN pool refresh: every chain runs on freshly salted copies
    # (x + c, uint8 wraps / float shifts noise harmlessly) so no
    # (executable, argument) pair ever repeats across chains or reps
    # (kept from an earlier backend; harmless here)
    salt_fn = jax.jit(lambda x, c: x + c)
    chain_no = itertools.count(1)

    def fresh_pool():
        c = np.asarray(next(chain_no)).astype(
            np.asarray(pool0[0]).dtype if not hasattr(pool0[0], "dtype")
            else pool0[0].dtype)
        pool = [salt_fn(x, c) for x in pool0]
        _fetch_sync(pool[-1])
        return pool

    _fetch_sync(jitted(pool0[0]))
    ctr = itertools.count(1)

    def chain(k):
        pool = fresh_pool()
        out = None
        t0 = time.perf_counter()
        for _ in range(k):
            out = jitted(pool[next(ctr) % len(pool)])
        _fetch_sync(out)
        return time.perf_counter() - t0

    # PAIRED differencing: each rep measures T(n) and T(2n) back to
    # back and contributes one (T2-T1)/n sample, so slow drift cancels
    # within the pair; the median across reps rejects an outlier pair
    samples = []
    for _ in range(reps):
        t1 = chain(n)
        t2 = chain(2 * n)
        samples.append(max((t2 - t1) / n * 1e3, 1e-6))
    ms = float(np.median(samples))
    return batch / ms * 1000.0


def _composite_pipeline(batch: int, num_buffers: int, model: str,
                        fuse: bool = True, pool_size: int = 0,
                        flt_name: str = "net"):
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink
    from nnstreamer_tpu.elements.decoder import TensorDecoder
    from nnstreamer_tpu.elements.devicesrc import DeviceSrc
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.transform import TensorTransform
    from nnstreamer_tpu.runtime import Pipeline

    spec = TensorsSpec.from_shapes([(batch, SSD_SIZE, SSD_SIZE, 3)], np.uint8)
    p = Pipeline(fuse=fuse)
    src = DeviceSrc(name="src", spec=spec, pattern="noise",
                    pool_size=pool_size or _pool_size(
                        num_buffers, batch * SSD_SIZE * SSD_SIZE * 3),
                    num_buffers=num_buffers)
    tf = TensorTransform(name="norm", mode="arithmetic",
                         option="typecast:float32,add:-127.5,div:127.5")
    flt = TensorFilter(name=flt_name, framework="jax-xla", model=model)
    # option7=device: the overlay is rasterized ON the TPU by one XLA
    # program and never crosses to the host — round 2's ceiling was one
    # host thread box-drawing at 4.2k fps while the device sat at 4% MFU
    dec = TensorDecoder(name="overlay", mode="bounding_boxes",
                        option1="mobilenet-ssd-postprocess",
                        option4=f"{SSD_SIZE}:{SSD_SIZE}",
                        option5=f"{SSD_SIZE}:{SSD_SIZE}",
                        option7="device")
    sink = AppSink(name="out", max_buffers=num_buffers + 4)
    p.add(src, tf, flt, dec, sink).link(src, tf, flt, dec, sink)
    return p, sink


def _run_composite_once(fuse: bool, model: str):
    """One composite run: async dispatch end-to-end (src→…→sink), then a
    single device sync — the device executes dispatched programs in
    order, so blocking on the LAST overlay canvas bounds every frame's
    completion without a host round trip per buffer."""
    import jax.numpy as jnp

    from nnstreamer_tpu.obs import transfer as _xferled

    p, sink = _composite_pipeline(
        SSD_BATCH, max(WARMUP, 1) + 1, model, fuse=fuse, pool_size=16)
    # data-movement accounting over the streamed frames: ledger
    # crossings (input + drain; weights excluded — placement is
    # per-model, not per-frame) divided by buffers streamed
    x0 = _xferled.LEDGER.totals(reason="input")[0] \
        + _xferled.LEDGER.totals(reason="drain")[0]
    with p:
        for _ in range(max(WARMUP, 1) + 1):
            b = _pull(sink, "composite warmup")
        _fetch_sync(b.tensors[0])
        x1 = _xferled.LEDGER.totals(reason="input")[0] \
            + _xferled.LEDGER.totals(reason="drain")[0]
        xpf = (x1 - x0) / float(max(WARMUP, 1) + 1)
        fused = bool(p["net"]._fused_pre)
        pre = None
        post = None
        if not fused:
            # unfused mode runs THREE programs per buffer: standalone
            # transform, the filter, and the decoder's device render —
            # chain all three so the A/B compares total device time
            import jax

            from nnstreamer_tpu.decoders.boxutil import device_render_fn

            pre = jax.jit(
                lambda x: (x.astype(jnp.float32) - 127.5) / 127.5)
            post = device_render_fn(SSD_BATCH, 10, SSD_SIZE, SSD_SIZE,
                                    0.25)
        fps = _program_fps(p, "net", "src", SSD_BATCH, pre=pre,
                           post=post)
    return fps, fused, xpf


def _ab_aggregate(samples):
    """Median + relative spread of A/B samples.  Median (not best-of):
    max() would select exactly the outlier samples, in either
    direction."""
    med = float(np.median(samples))
    spread = (max(samples) - min(samples)) / med if med else 0.0
    return med, round(spread, 3)


def bench_composite(reps: int = 3):
    """Fused vs unfused composite, interleaved ``reps``x, MEDIAN per
    mode with the spread reported (see _ab_aggregate for why best-of
    is wrong here; three reps because a 2-sample median cannot reject
    one corrupted sample).  Returns
    (fps_fused, fps_unfused, fused, spreads)."""
    model = "bench_ssd_mobilenet_v2"
    _register_ssd_pp(model, SSD_BATCH)
    runs_f, runs_u, runs_x = [], [], []
    fused = False
    for _ in range(reps):
        fps, fused, xpf = _run_composite_once(True, model)
        runs_f.append(fps)
        runs_x.append(xpf)
        fps_u, _, _ = _run_composite_once(False, model)
        runs_u.append(fps_u)
    med_f, spread_f = _ab_aggregate(runs_f)
    med_u, spread_u = _ab_aggregate(runs_u)
    return med_f, med_u, fused, {"fused": spread_f, "unfused": spread_u,
                                 "samples_fused": [round(s, 1)
                                                   for s in runs_f],
                                 "samples_unfused": [round(s, 1)
                                                     for s in runs_u],
                                 # ledger crossings per streamed frame
                                 # (fused runs; main() lifts this to a
                                 # top-level scalar for the history)
                                 "crossings_per_frame": round(
                                     float(np.median(runs_x)), 3)}


def derive_latency_stats(lats, floors):
    """Pure derivation of the latency report from per-frame e2e
    latencies and their bracketing transport-probe floors (both ms).

    Semantics (pinned by tests/test_latency_report.py, parity with the
    reference's latency-reporting CI,
    /root/reference/tests/nnstreamer_latency/unittest_latency.cc):

    - raw p50/p99 are percentiles of the e2e latencies as measured;
    - per-frame device EXCESS is ``max(latency - floor, 0)``: the
      bracketing probes pay the same host<->device round trip, so the
      excess estimates device time;
    - frames whose excess exceeds ``3 x median_excess + 1 ms`` are
      bursts that hit the frame but neither probe — excluded from the
      device percentiles, counted in tail_excluded_frames;
    - the report is annotated link-dominated when the probe floor
      (median) exceeds the device p50 — i.e. the e2e number mostly
      measures the round trip, not the framework;
    - device percentiles are UPPER BOUNDS: per-frame round-trip jitter
      enters the excess additively (the bracketing probes bound the
      instant's round trip from below).
    """
    lats = np.asarray(lats, np.float64)
    floors_a = np.asarray(floors, np.float64)
    excess = np.maximum(lats - floors_a, 0.0)
    med = float(np.median(excess))
    clean = excess[excess <= 3.0 * med + 1.0]
    excluded = int(excess.size - clean.size)
    floor = float(np.median(floors_a))
    p50, p99 = (float(np.percentile(lats, 50)),
                float(np.percentile(lats, 99)))
    p50_dev = float(np.percentile(clean, 50))
    p99_dev = float(np.percentile(clean, 99))
    return {
        "p50_frame_latency_ms": round(p50, 3),
        "p99_frame_latency_ms": round(p99, 3),
        "p99_frame_latency_note": "link-dominated"
        if floor > p50_dev else "device-dominated",
        "p50_device_ms": round(p50_dev, 3),
        "p99_device_ms": round(p99_dev, 3),
        "tail_excluded_frames": excluded,
        "latency_probe_floor_ms": round(floor, 3),
        "p50_device_note": "upper bound (round-trip jitter adds to "
                           "excess)",
    }


def bench_latency():
    """Per-frame e2e latency: batch=1 composite, frames paced 10 ms
    apart (a 100 fps camera), pts stamped at push with the wall clock.

    Returns a dict: raw p50/p99 include one host<->device round trip;
    each frame is BRACKETED by trivial-jit round-trip probes (floor =
    min of the two — jitter is additive, so the smaller probe is the
    cleaner estimate of that instant's round trip) and the *device*
    percentiles are computed over per-frame (latency − floor) excess.
    Round-3 verdict #5 (tail honesty): a burst that hits the frame but
    neither probe is not device time — frames whose excess exceeds
    3×median + 1 ms are excluded from the device tail and counted in
    ``tail_excluded_frames``; the raw p99 is annotated as
    link-dominated when the probe floor itself exceeds the device
    excess."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.core import Buffer, Tensor, TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc
    from nnstreamer_tpu.elements.decoder import TensorDecoder
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.transform import TensorTransform
    from nnstreamer_tpu.runtime import Pipeline

    model = "bench_ssd_lat"
    _register_ssd_pp(model, 1)
    spec = TensorsSpec.from_shapes([(1, SSD_SIZE, SSD_SIZE, 3)], np.uint8)
    p = Pipeline()
    src = AppSrc(name="src", spec=spec, max_buffers=LAT_FRAMES + 8)
    tf = TensorTransform(name="norm", mode="arithmetic",
                         option="typecast:float32,add:-127.5,div:127.5")
    flt = TensorFilter(name="net", framework="jax-xla", model=model)
    dec = TensorDecoder(name="overlay", mode="bounding_boxes",
                        option1="mobilenet-ssd-postprocess",
                        option4=f"{SSD_SIZE}:{SSD_SIZE}",
                        option5=f"{SSD_SIZE}:{SSD_SIZE}",
                        option7="device")
    sink = AppSink(name="out", max_buffers=LAT_FRAMES + 8)
    p.add(src, tf, flt, dec, sink).link(src, tf, flt, dec, sink)

    rng = np.random.default_rng(0)
    # frames staged in HBM ahead of time: latency starts at "frame is in
    # device memory" (device_src semantics)
    frames = [jax.device_put(rng.integers(0, 255, (1, SSD_SIZE, SSD_SIZE, 3),
                                          np.uint8))
              for _ in range(LAT_FRAMES)]
    for fr in frames:
        _fetch_sync(fr)
    probe = jax.jit(lambda x: x.sum())
    px = jnp.zeros((8,), jnp.float32)
    _fetch_sync(probe(px))
    lats, floors = [], []
    with p:
        # warmup/compile
        src.push_buffer(Buffer.of(frames[0], pts=0))
        b = _pull(sink, "latency warmup")
        _fetch_sync_small(b)

        def probe_ms():
            # fetch-based: one execution + one tiny value round trip,
            # the same cost structure as the frame sync below
            f0 = time.perf_counter()
            _fetch_sync(probe(px))
            return (time.perf_counter() - f0) * 1e3

        pre = probe_ms()
        for i in range(LAT_FRAMES):
            t0 = time.perf_counter_ns()
            src.push_buffer(Buffer(
                tensors=[Tensor(frames[i % len(frames)])], pts=t0))
            b = _pull(sink, "latency")
            _fetch_sync_small(b)
            lats.append((time.perf_counter_ns() - b.pts) / 1e6)
            # bracketing probes: trivial jit round-trips under the SAME
            # conditions; the post-probe doubles as the next frame's
            # pre-probe
            post = probe_ms()
            floors.append(min(pre, post))
            pre = post
            time.sleep(0.01)
        src.end_of_stream()
    return derive_latency_stats(lats, floors)


def register_classify_model() -> str:
    """Init + register the classify model ONCE (the A/B loop reuses
    it)."""
    import jax

    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.models.mobilenet import (
        mobilenet_v1_apply,
        mobilenet_v1_init,
    )

    from nnstreamer_tpu.models.params_io import weights_to_bf16

    params = weights_to_bf16(
        mobilenet_v1_init(jax.random.PRNGKey(0), num_classes=1001))

    def classify(params, x):
        logits = mobilenet_v1_apply(params, x)
        return jax.numpy.argmax(logits, axis=-1).astype(jax.numpy.int32)

    return register_model("bench_mobilenet_v1", classify, params=params,
                          in_shapes=[(CLS_BATCH, CLS_SIZE, CLS_SIZE, 3)])


def bench_classify(fuse: bool, buffers: int, model: str):
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink
    from nnstreamer_tpu.elements.devicesrc import DeviceSrc
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.transform import TensorTransform
    from nnstreamer_tpu.runtime import Pipeline

    spec = TensorsSpec.from_shapes([(CLS_BATCH, CLS_SIZE, CLS_SIZE, 3)],
                                   np.uint8)
    warm = max(WARMUP, 1)
    p = Pipeline(fuse=fuse)
    src = DeviceSrc(name="src", spec=spec, pattern="noise",
                    pool_size=16, num_buffers=warm + 1)
    tf = TensorTransform(name="norm", mode="arithmetic",
                         option="typecast:float32,add:-127.5,div:127.5")
    flt = TensorFilter(name="net", framework="jax-xla", model=model)
    sink = AppSink(name="out", max_buffers=buffers + warm + 4)
    p.add(src, tf, flt, sink).link(src, tf, flt, sink)
    with p:
        for _ in range(warm + 1):
            b = _pull(sink, "classify warmup")
        _fetch_sync(b.tensors[0])
        pre = None
        if not p["net"]._fused_pre:
            import jax
            import jax.numpy as jnp

            pre = jax.jit(
                lambda x: (x.astype(jnp.float32) - 127.5) / 127.5)
        fps = _program_fps(p, "net", "src", CLS_BATCH, pre=pre)
    return fps


def register_vit_bench() -> str:
    from nnstreamer_tpu.models.vit import register_vit

    return register_vit("bench_vit", batch=VIT_BATCH, image_size=VIT_SIZE,
                        patch=VIT_PATCH, dim=VIT_DIM, depth=VIT_DEPTH,
                        heads=VIT_HEADS, mlp_dim=VIT_MLP, num_classes=1000)


def vit_flops_per_frame() -> float:
    """Analytic matmul FLOPs of one ViT forward (standard MFU
    accounting: embed conv + qkv/attn/proj/mlp matmuls + head; LN/gelu/
    softmax elementwise excluded).  Analytic rather than XLA cost
    analysis because the attention runs inside a Pallas kernel, whose
    inner dots the CPU-backend cost model does not see."""
    s = (VIT_SIZE // VIT_PATCH) ** 2
    d, m = VIT_DIM, VIT_MLP
    embed = 2 * s * (VIT_PATCH * VIT_PATCH * 3) * d
    per_block = (2 * s * d * 3 * d      # qkv
                 + 2 * 2 * s * s * d    # q·kᵀ and p·v
                 + 2 * s * d * d        # proj
                 + 2 * s * d * m * 2)   # mlp in+out
    head = 2 * d * 1000
    return float(embed + VIT_DEPTH * per_block + head)


def bench_vit(model: str) -> float:
    """ViT classify slice through the pipeline (flash-attention kernel on
    the hot path); classify-style async timing."""
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink
    from nnstreamer_tpu.elements.devicesrc import DeviceSrc
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.transform import TensorTransform
    from nnstreamer_tpu.runtime import Pipeline

    spec = TensorsSpec.from_shapes([(VIT_BATCH, VIT_SIZE, VIT_SIZE, 3)],
                                   np.uint8)
    warm = max(WARMUP, 1)
    p = Pipeline()
    src = DeviceSrc(name="src", spec=spec, pattern="noise",
                    pool_size=16, num_buffers=warm + 1)
    tf = TensorTransform(name="norm", mode="arithmetic",
                         option="typecast:float32,add:-127.5,div:127.5")
    flt = TensorFilter(name="net", framework="jax-xla", model=model)
    sink = AppSink(name="out", max_buffers=VIT_BUFFERS + warm + 4)
    p.add(src, tf, flt, sink).link(src, tf, flt, sink)
    with p:
        for _ in range(warm + 1):
            b = _pull(sink, "vit warmup")
        _fetch_sync(b.tensors[0])
        pre = None
        if not p["net"]._fused_pre:
            import jax
            import jax.numpy as jnp

            pre = jax.jit(
                lambda x: (x.astype(jnp.float32) - 127.5) / 127.5)
        fps = _program_fps(p, "net", "src", VIT_BATCH, pre=pre)
    return fps


def device_time_breakdown(spec, render_conf: float = 0.25):
    """Steady-state device time of the composite program, split into
    backbone / postprocess / overlay, plus an XLA cost-analysis roofline
    (round-3 verdict #2: explain the MFU, don't just assert fps).

    Methodology: each stage program is timed with chained async
    dispatches — T(n) = overhead + n·t, so t = (T(2n) − T(n))/n — and a
    min over repetitions, because jitter is strictly additive.
    The roofline comes from the compiled detect program's own cost
    analysis: arithmetic intensity (flops/byte) against the ridge of
    ``spec`` — the chip's own ``obs/hwspec.py`` entry — (peak_flops /
    HBM bandwidth) bounds the reachable MFU of THIS program independent
    of any runtime overhead.
    """
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.decoders.boxutil import device_render_fn
    from nnstreamer_tpu.models.ssd import ssd_mobilenet_v2_apply

    params, anchors = _ssd_params_anchors()
    detect, _, _ = _register_ssd_pp("bench_ssd_breakdown", SSD_BATCH)
    dev = jax.devices()[0]
    params_d = jax.device_put(params, dev)

    def norm(x):
        return (x.astype(jnp.float32) - 127.5) / 127.5

    # every dispatch carries a UNIQUE uint8 salt folded into the input,
    # so no (executable, argument) pair repeats within or across the
    # repetitions (kept from an earlier backend; harmless here)
    f_backbone = jax.jit(lambda x, i: ssd_mobilenet_v2_apply(
        params_d, norm(x + i), cls_dtype=jnp.bfloat16))
    f_detect = jax.jit(lambda x, i: detect(params_d, norm(x + i)))
    _render = device_render_fn(  # already jitted internally
        SSD_BATCH, 10, SSD_SIZE, SSD_SIZE, render_conf)
    f_render = jax.jit(lambda boxes, classes, scores, num, i:
                       _render(boxes + i * 1e-6, classes, scores, num))

    rng = np.random.default_rng(0)
    n_inputs = 32
    xs = [jax.device_put(rng.integers(
        0, 255, (SSD_BATCH, SSD_SIZE, SSD_SIZE, 3), dtype=np.uint8), dev)
        for _ in range(n_inputs)]
    salts_u8 = [jax.device_put(np.uint8(j)) for j in range(256)]
    salts_f32 = [jax.device_put(np.float32(j)) for j in range(256)]
    zero_u8 = salts_u8[0]
    det_outs = [f_detect(x, zero_u8) for x in xs]
    _fetch_sync(det_outs[-1])

    import itertools as _it

    _salt_i = _it.count()

    def chained(fn, argsets, n, salts):
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            c = next(_salt_i)
            out = fn(*argsets[c % len(argsets)], salts[c % 256])
        _fetch_sync(out)
        return time.perf_counter() - t0

    def per_call_ms(fn, argsets, n=16, reps=4, salts=None):
        # n chosen so n·t ≫ the jitter of one chained block; min over
        # reps because jitter is strictly additive
        salts = salts_u8 if salts is None else salts
        _fetch_sync(fn(*argsets[0], salts[255]))  # warm
        t1 = min(chained(fn, argsets, n, salts) for _ in range(reps))
        t2 = min(chained(fn, argsets, 2 * n, salts) for _ in range(reps))
        return max((t2 - t1) / n * 1e3, 0.0)

    backbone_ms = per_call_ms(f_backbone, [(x,) for x in xs])
    detect_ms = per_call_ms(f_detect, [(x,) for x in xs])
    render_ms = per_call_ms(f_render, det_outs, salts=salts_f32)

    # roofline of the exact detect computation (the pipeline's fused
    # transform+model program; overlay adds its canvas analytically)
    roofline = {}
    c = f_detect.lower(
        jax.ShapeDtypeStruct(xs[0].shape, xs[0].dtype),
        jax.ShapeDtypeStruct((), np.uint8)).compile()
    ca = cost_of(c)  # one extraction helper (obs/xlacost.py)
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    if flops and bytes_acc:
        intensity = flops / bytes_acc
        ridge = spec.ridge
        roofline = {
            "detect_gflops_per_batch": round(flops / 1e9, 1),
            "detect_gbytes_per_batch": round(bytes_acc / 1e9, 3),
            "intensity_flops_per_byte": round(intensity, 1),
            "ridge_flops_per_byte": round(ridge, 1),
            "mfu_ceiling": round(min(intensity / ridge, 1.0), 3),
            "bw_bound_ms": round(bytes_acc / spec.hbm_bw * 1e3, 3),
            "hbm_bw_util": round(
                (bytes_acc / spec.hbm_bw * 1e3) / detect_ms, 3)
            if detect_ms else None,
        }

    return {
        "backbone_ms": round(backbone_ms, 3),
        "postprocess_ms": round(max(detect_ms - backbone_ms, 0.0), 3),
        "overlay_ms": round(render_ms, 3),
        "compute_total_ms": round(detect_ms + render_ms, 3),
    }, roofline


_YOLO_MODEL = []


_TFLITE_MODEL = ("/root/reference/tests/test_models/models/"
                 "mobilenet_v2_1.0_224_quant.tflite")
TFLITE_BATCH = int(os.environ.get("BENCH_TFLITE_BATCH", "256"))
TFLITE_BUFFERS = int(os.environ.get("BENCH_TFLITE_BUFFERS", "15"))


#: what an import slice reports in place of a number when its model
#: file is absent (the reference's test models are not in this repo,
#: and the chip machine has no network)
NOT_RUN = "not run: model file not in the repository"


def bench_tflite():
    """Pretrained-import slice: the reference's OWN quantized
    mobilenet_v2 .tflite, imported (not interpreted) and run batched on
    the TPU through the full pipeline — the number the reference's
    tflite backend cannot reach on CPU delegates.  Returns fps; the
    caller skips the slice (``NOT_RUN``) when the asset is absent."""
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink
    from nnstreamer_tpu.elements.devicesrc import DeviceSrc
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.runtime import Pipeline

    spec = TensorsSpec.from_shapes(
        [(TFLITE_BATCH, 224, 224, 3)], np.uint8)
    warm = max(WARMUP, 1)
    p = Pipeline()
    src = DeviceSrc(name="src", spec=spec, pattern="noise",
                    pool_size=16, num_buffers=warm + 1)
    flt = TensorFilter(name="net", framework="tensorflow-lite",
                       model=_TFLITE_MODEL)
    sink = AppSink(name="out", max_buffers=TFLITE_BUFFERS + warm + 4)
    p.add(src, flt, sink).link(src, flt, sink)
    with p:
        for _ in range(warm + 1):
            b = _pull(sink, "tflite warmup")
        _fetch_sync(b.tensors[0])
        fps = _program_fps(p, "net", "src", TFLITE_BATCH)
    return fps


_ONNX_MODEL = ("/root/reference/tests/test_models/models/"
               "mobilenet_v2_quant.onnx")


def bench_onnx():
    """Imported-ONNX slice: the reference's own ORT-quantized
    mobilenet_v2 .onnx run batched through the pipeline in the exact
    bf16-code quantized execution mode.  Returns fps."""
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink
    from nnstreamer_tpu.elements.devicesrc import DeviceSrc
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.runtime import Pipeline

    spec = TensorsSpec.from_shapes(
        [(TFLITE_BATCH, 3, 224, 224)], np.float32)
    warm = max(WARMUP, 1)
    p = Pipeline()
    src = DeviceSrc(name="src", spec=spec, pattern="noise",
                    pool_size=12, num_buffers=warm + 1)
    flt = TensorFilter(name="net", framework="onnx", model=_ONNX_MODEL)
    sink = AppSink(name="out", max_buffers=TFLITE_BUFFERS + warm + 4)
    p.add(src, flt, sink).link(src, flt, sink)
    with p:
        for _ in range(warm + 1):
            b = _pull(sink, "onnx warmup")
        _fetch_sync(b.tensors[0])
        fps = _program_fps(p, "net", "src", TFLITE_BATCH, n=5)
    return fps


def onnx_flops() -> float:
    """Per-frame FLOPs of the imported onnx graph."""
    from nnstreamer_tpu.filters.onnx_import import OnnxModel, build_fn

    fn, weights, _, _ = build_fn(OnnxModel(_ONNX_MODEL))
    return _cpu_flops_per_frame(lambda x: fn(weights, x), (3, 224, 224),
                                dtype=np.float32)


def tflite_flops() -> float:
    """Per-frame FLOPs of the imported tflite graph (CPU cost
    analysis)."""
    from nnstreamer_tpu.filters.tflite_import import TFLiteModel, build_fn

    fn, weights, _, _ = build_fn(TFLiteModel(_TFLITE_MODEL))
    return _cpu_flops_per_frame(lambda x: fn(weights, x), (224, 224, 3))


def bench_yolo():
    """YOLO end-to-end slice: device_src ! transform(/255, fused) !
    jax-xla yolo(decode+NMS on device) ! bounding_boxes option7=device !
    sink — the same composite shape as SSD, third model family."""
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink
    from nnstreamer_tpu.elements.decoder import TensorDecoder
    from nnstreamer_tpu.elements.devicesrc import DeviceSrc
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.transform import TensorTransform
    from nnstreamer_tpu.models.yolo import register_yolo
    from nnstreamer_tpu.runtime import Pipeline

    if not _YOLO_MODEL:  # init + register once; the reps reuse it
        _YOLO_MODEL.append(register_yolo(
            "bench_yolo", batch=YOLO_BATCH, image_size=YOLO_SIZE,
            max_out=10, width=YOLO_WIDTH, depth=YOLO_DEPTH))
    model = _YOLO_MODEL[0]
    spec = TensorsSpec.from_shapes(
        [(YOLO_BATCH, YOLO_SIZE, YOLO_SIZE, 3)], np.uint8)
    warm = max(WARMUP, 1)
    p = Pipeline()
    src = DeviceSrc(name="src", spec=spec, pattern="noise",
                    pool_size=16, num_buffers=warm + 1)
    tf = TensorTransform(name="norm", mode="arithmetic",
                         option="typecast:float32,div:255.0")
    flt = TensorFilter(name="net", framework="jax-xla", model=model)
    dec = TensorDecoder(name="overlay", mode="bounding_boxes",
                        option1="mobilenet-ssd-postprocess",
                        option4=f"{YOLO_SIZE}:{YOLO_SIZE}",
                        option5=f"{YOLO_SIZE}:{YOLO_SIZE}",
                        option7="device")
    sink = AppSink(name="out", max_buffers=YOLO_BUFFERS + warm + 4)
    p.add(src, tf, flt, dec, sink).link(src, tf, flt, dec, sink)
    with p:
        for _ in range(warm + 1):
            b = _pull(sink, "yolo warmup")
        _fetch_sync(b.tensors[0])
        pre = None
        if not p["net"]._fused_pre:
            import jax
            import jax.numpy as jnp

            pre = jax.jit(lambda x: x.astype(jnp.float32) / 255.0)
        fps = _program_fps(p, "net", "src", YOLO_BATCH, pre=pre)
    return fps


def _cpu_flops_per_frame(full, shape, dtype=np.uint8, cb: int = 8) -> float:
    """Per-frame FLOPs of ``full`` via cost analysis on the CPU
    backend of this same process — FLOP count is computation-intrinsic,
    so no accelerator compile is spent on analysis.  ``shape`` excludes
    the batch dim.  A cost analysis that yields no flops is an error:
    an MFU must not quietly become null."""
    import jax

    x = jax.ShapeDtypeStruct((cb,) + tuple(shape), dtype)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        compiled = jax.jit(full).lower(x).compile()
    flops = flops_bytes(compiled)[0]  # obs/xlacost.py extraction
    if not flops:
        raise RuntimeError("bench: CPU cost analysis reported no flops")
    return flops / cb


def yolo_flops() -> float:
    """Per-frame FLOPs of the yolo slice (normalize + pyramid + decode +
    NMS) via CPU-backend cost analysis of the exact computation."""
    import jax

    from nnstreamer_tpu.models.yolo import yolo_detect_apply, yolo_init

    params = yolo_init(jax.random.PRNGKey(0), width=YOLO_WIDTH,
                       depth=YOLO_DEPTH)
    return _cpu_flops_per_frame(
        lambda x: yolo_detect_apply(params, x.astype(np.float32) / 255.0,
                                    max_out=10),
        (YOLO_SIZE, YOLO_SIZE, 3))


def composite_flops() -> float:
    """Per-frame FLOPs of the EXACT composite computation (normalize +
    backbone + decode + NMS) from XLA cost analysis."""
    import jax

    cost_batch = 8  # FLOPs/frame is batch-invariant; small batch keeps
    detect, params, anchors = _register_ssd_pp("bench_ssd_cost", cost_batch)

    def full(x):
        # params closed over (the filter's flat_fn path does the same):
        # pytree ints like num_classes stay concrete for tracing
        xf = (x.astype(np.float32) - 127.5) / 127.5
        return detect(params, xf)

    return _cpu_flops_per_frame(full, (SSD_SIZE, SSD_SIZE, 3),
                                cb=cost_batch)


def classify_flops() -> float:
    """Per-frame FLOPs of the classify slice (normalize+backbone+argmax)
    via CPU-backend cost analysis."""
    import jax

    from nnstreamer_tpu.models.mobilenet import (
        mobilenet_v1_apply,
        mobilenet_v1_init,
    )

    from nnstreamer_tpu.models.params_io import weights_to_bf16

    params = weights_to_bf16(
        mobilenet_v1_init(jax.random.PRNGKey(0), num_classes=1001))

    def full(x):
        xf = (x.astype(np.float32) - 127.5) / 127.5
        return jax.numpy.argmax(mobilenet_v1_apply(params, xf), -1)

    return _cpu_flops_per_frame(full, (CLS_SIZE, CLS_SIZE, 3))


def device_roundtrip_floor_ms() -> float:
    """Median latency of a trivial jitted computation fetched to the
    host: the host<->device round trip no per-frame fetch can beat."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x.sum())
    x = jnp.zeros((8,), jnp.float32)
    _fetch_sync(f(x))
    ts = []
    for _ in range(10):
        t0 = time.perf_counter()
        _fetch_sync(f(x))
        ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))


def _chip_spec():
    """The chip the default mode times and its ``obs/hwspec.py`` peaks.
    ``frames/sec/chip`` and MFU are statements about a TPU: on any
    other backend, or on a TPU kind whose peaks nobody entered, there
    is nothing true to print, so the run stops here."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: the default mode reports frames/sec/chip and MFU, "
            f"which only a TPU run can give; JAX found platform="
            f"{dev.platform!r} ({dev.device_kind}).  The flagged modes "
            f"(--composite, --serve, ...) are the CPU gates.")
    spec = spec_for_device_kind(dev.device_kind)
    if spec is None:
        raise SystemExit(
            f"bench: device kind {dev.device_kind!r} is not in the "
            f"obs/hwspec.py peak table; add its published peaks there")
    return dev, spec


def scaling_projection(fps_per_chip: float,
                       per_frame_flops: float,
                       handoff_bytes_per_frame: float,
                       n_chips: int = 8,
                       host_fanout_margin: float = 0.03):
    """MODEL-based projection of composite scaling to a v5e pod slice
    (round-4 verdict #8): the v5e-8 claim should rest on an explicit
    bandwidth model, not a pro-rating.

    Two deployment shapes:

    - ``data_parallel``: inference is embarrassingly parallel — params
      replicated, each chip streams its own batches, ZERO steady-state
      ICI traffic.  The only sub-linearity is host-side dispatch fanout
      (one process feeding n streams), modeled as a flat margin.
    - ``split_pipeline`` (the shipped two-stage devices= split, stage A
      backbone+detect on half the chips, stage B on the other half):
      per-frame handoff bytes cross ONE submesh boundary over ICI.
      Demand = projected fps x handoff bytes; supply = the boundary
      chips' aggregate ICI.  Efficiency = min(1, supply/demand) on top
      of the data-parallel projection.

    All inputs are MEASURED single-chip numbers; the output is labeled
    a projection and carries its own assumptions.
    """
    dp_fps = fps_per_chip * n_chips * (1.0 - host_fanout_margin)
    half = max(n_chips // 2, 1)
    # each stage runs data-parallel on half the chips and the SLOWER
    # stage paces the pipe.  With the shipped split (stage B is the
    # tiny overlay head) stage A is modeled as the full per-chip
    # program, so steady-state throughput is stage A's capacity:
    # fps_per_chip x n/2 — HALF the pure-data-parallel number.  (A
    # compute-balanced split would approach dp_fps; this split exists
    # for placement/memory, not throughput.)
    split_ideal = fps_per_chip * half * (1.0 - host_fanout_margin)
    ici_supply = half * V5E_ICI_BYTES_PER_S
    ici_demand = split_ideal * handoff_bytes_per_frame
    ici_eff = min(1.0, ici_supply / ici_demand) if ici_demand else 1.0
    return {
        "model": "scaling projection (NOT a measurement)",
        "inputs": {
            "fps_per_chip_measured": round(fps_per_chip, 1),
            "per_frame_gflops": round(per_frame_flops / 1e9, 3),
            "handoff_bytes_per_frame": int(handoff_bytes_per_frame),
            "n_chips": n_chips,
            "host_fanout_margin": host_fanout_margin,
            "v5e_ici_bytes_per_s_per_chip": V5E_ICI_BYTES_PER_S,
        },
        "data_parallel": {
            "projected_fps": round(dp_fps, 0),
            "ici_traffic": 0,
            "assumption": "params replicated; no steady-state "
                          "collectives in inference",
        },
        "split_pipeline": {
            "projected_fps": round(split_ideal * ici_eff, 0),
            "ici_demand_bytes_per_s": round(ici_demand, 0),
            "ici_supply_bytes_per_s": round(ici_supply, 0),
            "ici_efficiency": round(ici_eff, 3),
        },
        "vs_baseline_target_fps": 10000,
    }


def bench_project(out_path: str = "SCALING_MODEL.json"):
    """``--project``: write the v5e-8 scaling model from this chip's
    measured composite numbers + the split pipeline's actual handoff
    tensor sizes (jax.eval_shape over the real detect program)."""
    import jax

    model = "bench_ssd_project"
    detect, params, anchors = _register_ssd_pp(model, SSD_BATCH)
    outs = jax.eval_shape(
        lambda x: detect(params, x),
        jax.ShapeDtypeStruct((SSD_BATCH, SSD_SIZE, SSD_SIZE, 3),
                             np.float32))
    handoff = sum(int(np.prod(o.shape)) * o.dtype.itemsize
                  for o in jax.tree_util.tree_leaves(outs)) / SSD_BATCH
    fps, _, _, _ = bench_composite(reps=1)
    flops = composite_flops()
    proj = scaling_projection(fps, flops, handoff)
    with open(out_path, "w") as f:
        json.dump(proj, f, indent=1)
    print(json.dumps(proj))


MESH_FRAMES = int(os.environ.get("BENCH_MESH_FRAMES", "10"))
MESH_REPS = int(os.environ.get("BENCH_MESH_REPS", "3"))


def _mesh_sizes(n_devices: int):
    spec = os.environ.get("BENCH_MESH_SIZES", "1,2,4,8")
    return [n for n in (int(t) for t in spec.split(",") if t.strip())
            if n <= n_devices]


def _mesh_attribution(row: dict, base: dict) -> dict:
    """Decompose one weak-scaling leg's efficiency loss.  With one
    dispatch per buffer, ``eff = (h_1 + d_1) / (h_n + d_n)`` where h/d
    are the measured per-dispatch host/device phases — so the gap
    splits EXACTLY into host-phase growth and device-time growth.
    The measured device seconds already *contain* pad-slot execution
    and the wait for the slowest shard, so the mesh table's pad-waste
    (``pad_frac`` of the device time burns pad slots) and
    shard-imbalance (``1 - mean/max`` of it waits on the hottest
    shard) terms are carved OUT of the device growth, not added on
    top; what remains of the growth is true contention/collectives.
    Both carve-outs are 0.0 on an even-split leg by construction.
    ``residual`` is whatever the wall-clock efficiency lost beyond the
    phase accounting (scheduler noise between dispatches)."""
    h_n, d_n = row["host_s_per_dispatch"], row["device_s_per_dispatch"]
    h_1, d_1 = base["host_s_per_dispatch"], base["device_s_per_dispatch"]
    total = h_n + d_n
    gap = 1.0 - row["efficiency"]
    host_loss = (h_n - h_1) / total if total else 0.0
    sf = row.get("shard_frames") or [1]
    mean = sum(sf) / len(sf)
    dev_frac = d_n / total if total else 0.0
    imbalance_loss = (1.0 - (mean / max(sf)) if max(sf) else 0.0) \
        * dev_frac
    pad_loss = row.get("pad_frac", 0.0) * dev_frac
    device_loss = ((d_n - d_1) / total if total else 0.0) \
        - imbalance_loss - pad_loss
    explained = host_loss + device_loss + imbalance_loss + pad_loss
    terms = {"host_phase": host_loss,
             "device_contention": device_loss,
             "shard_imbalance": imbalance_loss,
             "pad_waste": pad_loss}
    dominant = max(terms, key=lambda k: terms[k]) \
        if any(v > 0 for v in terms.values()) else "none"
    return {
        **{k: round(v, 4) for k, v in terms.items()},
        "residual": round(gap - explained, 4),
        "dominant": dominant,
    }


def bench_meshscaling(out_path: str = "MESH_SCALING.json",
                      metrics: bool = False):
    """``--meshscaling`` (also ``--mesh``): weak-scaling of the
    mesh-sharded filter over n = 1,2,4,8 devices, through the REAL
    ``tensor_filter mesh=data:n`` element path with every dispatch
    stat-sampled — so each leg yields not just frames/s but the full
    efficiency decomposition: host-phase growth vs device-time growth
    (from PR 7's cost attribution), shard imbalance and pad waste
    (from the obs mesh table), and the executable's captured XLA cost
    cross-checked byte-for-byte against this bench's own lowering.
    Writes ``MESH_SCALING.json`` with a per-n ``attribution`` block
    that *explains* the efficiency cliff instead of footnoting it."""
    import jax

    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.models.mobilenet import (
        mobilenet_v1_apply,
        mobilenet_v1_init,
    )
    from nnstreamer_tpu.obs.meshstat import MESH_STATS
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.obs.xlacost import XLA_COST
    from nnstreamer_tpu.runtime import Pipeline

    devs = require_devices(2, "--meshscaling")
    sizes = _mesh_sizes(len(devs))
    params = mobilenet_v1_init(jax.random.PRNGKey(0), num_classes=16,
                               width=0.25)
    result = {
        "metric": "sharded-filter weak scaling (tensor_filter "
                  "mesh=data:n, batch=32n, every dispatch sampled)",
        "unit": "frames/sec",
        "platform": devs[0].platform,
        "devices_present": len(devs),
        "virtual_cpu_mesh": devs[0].platform == "cpu",
        "scaling": [],
    }
    if not sizes:
        raise SystemExit(
            f"--meshscaling: no mesh size in BENCH_MESH_SIZES="
            f"{os.environ.get('BENCH_MESH_SIZES', '1,2,4,8')!r} fits "
            f"the {len(devs)} visible device(s)")
    base_fps = base_n = None
    rows = []
    for n in sizes:
        batch = 32 * n
        name = register_model(f"bench_mesh_n{n}", mobilenet_v1_apply,
                              params=params,
                              in_shapes=[(batch, 64, 64, 3)],
                              in_dtypes=np.float32)
        spec = TensorsSpec.from_shapes([(batch, 64, 64, 3)], np.float32)
        frames = [Buffer.of(np.asarray(
            np.random.default_rng(i).standard_normal((batch, 64, 64, 3)),
            np.float32), pts=i) for i in range(MESH_FRAMES)]
        p = Pipeline(name=f"mesh{n}")
        src = AppSrc(name="src", spec=spec,
                     max_buffers=MESH_FRAMES + 4)
        q = Queue(name="q", max_size_buffers=MESH_FRAMES + 4)
        # per-leg element name: the registry's device-seconds series
        # and the MFU join key on the SOURCE label, so reusing one
        # name would merge the legs' measurement windows (and fire the
        # obs remap warning every leg)
        flt = TensorFilter(name=f"net{n}", framework="jax-xla",
                           model=name, mesh=f"data:{n}",
                           stat_sample_interval_ms=0)
        sink = AppSink(name="out", max_buffers=MESH_FRAMES + 4)
        p.add(src, q, flt, sink).link(src, q, flt, sink)
        best = None
        with p:
            # warmup: compile + first blocking sample outside the
            # timed/attributed region
            for b in frames[:2]:
                src.push_buffer(b)
            for _ in range(2):
                _pull(sink, "mesh warmup")
            s0 = flt.invoke_stats.snapshot()
            for _ in range(MESH_REPS):
                t0 = time.perf_counter()
                for b in frames:
                    src.push_buffer(b)
                for _ in range(MESH_FRAMES):
                    _pull(sink, "mesh")
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            s1 = flt.invoke_stats.snapshot()
            snap = REGISTRY.snapshot()
            src.end_of_stream()
            p.wait_eos(timeout=30)
        fps = batch * MESH_FRAMES / best
        if base_fps is None:
            base_fps, base_n = fps, n
        disp = s1["phase"]["samples"] - s0["phase"]["samples"]
        host_s = ((s1["phase"]["host_prep_s"] + s1["phase"]["host_drain_s"])
                  - (s0["phase"]["host_prep_s"]
                     + s0["phase"]["host_drain_s"])) / max(disp, 1)
        dev_s = (s1["phase"]["device_s"]
                 - s0["phase"]["device_s"]) / max(disp, 1)
        mrow = MESH_STATS.get(name) or {}
        erow = XLA_COST.get(name, 0) or {}
        # independent cross-check of the capture plumbing: this bench's
        # OWN lowering of the same computation must yield the same
        # flops the filter's compile seam captured
        flops_bench = flops_bytes(jax.jit(
            lambda x: mobilenet_v1_apply(params, x)).lower(
            jax.ShapeDtypeStruct((batch, 64, 64, 3), np.float32)))[0]
        exec_live = [r for r in snap.get("executables", [])
                     if r["source"] == name]
        row = {
            "n": n, "fps": round(fps, 1),
            "fps_per_shard": round(fps / n, 1),
            # weak-scaling efficiency: per-shard throughput vs the BASE
            # leg's per-shard throughput (base leg need not be n=1 —
            # e.g. BENCH_MESH_SIZES=2,4 on real hardware)
            "efficiency": round((fps / n) / (base_fps / base_n), 3),
            "host_s_per_dispatch": host_s,
            "device_s_per_dispatch": dev_s,
            "host_frac": round(host_s / (host_s + dev_s), 4)
            if host_s + dev_s else 0.0,
            "imbalance": mrow.get("imbalance", 0.0),
            "pad_frac": mrow.get("pad_frac", 0.0),
            "shard_frames": mrow.get("shard_frames", []),
            "replicated_dispatches": mrow.get(
                "replicated_dispatches", 0),
            "flops_registry": erow.get("flops", 0.0),
            "flops_bench": flops_bench,
            "flops_exact": erow.get("flops", 0.0) == flops_bench
            and flops_bench > 0,
            "mfu": next((r["mfu"] for r in exec_live if "mfu" in r),
                        None),
            "intensity_flops_per_byte": next(
                (round(r["intensity_flops_per_byte"], 2)
                 for r in exec_live
                 if "intensity_flops_per_byte" in r), None),
        }
        rows.append(row)
    for row in rows:
        row["attribution"] = _mesh_attribution(row, rows[0])
        # JSON hygiene: round the raw seconds after attribution used
        # them at full precision
        row["host_s_per_dispatch"] = round(row["host_s_per_dispatch"], 6)
        row["device_s_per_dispatch"] = round(
            row["device_s_per_dispatch"], 6)
        result["scaling"].append(row)
    result["value"] = result["scaling"][-1]["fps"]
    result["vs_baseline"] = round(
        result["scaling"][-1]["efficiency"], 3)
    by_n = {r["n"]: r for r in rows}
    # gate scalars (tests/bench_baselines/mesh_smoke.json): efficiency
    # lower-is-worse, imbalance/pad exact-0.0 on this even-split leg
    result["efficiency_n2"] = by_n[2]["efficiency"] if 2 in by_n \
        else None
    result["imbalance_even"] = max(r["imbalance"] for r in rows)
    result["pad_frac_even"] = max(r["pad_frac"] for r in rows)
    result["flops_exact"] = all(r["flops_exact"] for r in rows)
    if result["virtual_cpu_mesh"]:
        dom = rows[-1]["attribution"]["dominant"] if rows else "none"
        result["note"] = (
            "virtual devices share one physical CPU: the attribution "
            f"blocks show the loss (dominant term at n={rows[-1]['n']}: "
            f"{dom}) is host-side contention, not ICI — code-path "
            "sanity only; run on a real multi-chip host for true "
            "scaling")
    if metrics:
        result["metrics"] = REGISTRY.snapshot()
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


#: back-compat alias (the historical ``--mesh`` entry point)
bench_mesh = bench_meshscaling


MESH_SERVE_STREAMS = int(os.environ.get("BENCH_MESH_SERVE_STREAMS", "4"))
MESH_SERVE_FRAMES = int(os.environ.get("BENCH_MESH_SERVE_FRAMES", "48"))
MESH_SERVE_REPS = int(os.environ.get("BENCH_MESH_SERVE_REPS", "3"))
#: per-shard window share: each leg's pool batch is this x n, so the
#: per-chip work is constant across the ladder (weak scaling)
MESH_SERVE_BATCH_PER_SHARD = int(
    os.environ.get("BENCH_MESH_SERVE_BATCH_PER_SHARD", "8"))


def _mesh_serve_sizes(n_devices: int):
    spec = os.environ.get("BENCH_MESH_SERVE_SIZES",
                          os.environ.get("BENCH_MESH_SIZES", "1,2,4,8"))
    return [n for n in (int(t) for t in spec.split(",") if t.strip())
            if n <= n_devices]


def _mesh_row_delta(m0, m1) -> dict:
    """Per-leg mesh attribution over the TIMED region only: the
    MESH_STATS row is cumulative (warmup windows included), so the
    gate figures (imbalance/pad) derive from the delta."""
    if not m1:
        return {}
    m0 = m0 or {}
    sf0 = m0.get("shard_frames") or []
    sf = [b - (sf0[i] if i < len(sf0) else 0)
          for i, b in enumerate(m1.get("shard_frames") or [])]
    slots = m1.get("slots", 0) - m0.get("slots", 0)
    pads = m1.get("pad_slots", 0) - m0.get("pad_slots", 0)
    mean = sum(sf) / len(sf) if sf else 0.0
    return {
        "shard_frames": sf,
        "imbalance": (max(sf) / mean - 1.0) if mean > 0 else 0.0,
        "pad_frac": (pads / slots) if slots else 0.0,
        "replicated_dispatches": m1.get("replicated_dispatches", 0)
        - m0.get("replicated_dispatches", 0),
    }


def _meshserve_leg(n: int, params, apply_fn, shape):
    """One weak-scaling leg through the REAL shared-pool element path:
    MESH_SERVE_STREAMS pipelines x ``share-model=true`` on ONE model
    placed ``mesh=data:n``, closed-loop clients sized so only the
    CROSS-stream window can fill a batch — every dispatch is one
    stacked window sharded over the n-device data axis, every dispatch
    stat-sampled (phase split feeds the attribution)."""
    import threading

    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.obs.meshstat import MESH_STATS
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.runtime import Pipeline

    batch = MESH_SERVE_BATCH_PER_SHARD * n
    name = register_model(f"bench_meshserve_n{n}", apply_fn,
                          params=params, in_shapes=[shape],
                          in_dtypes=np.float32)
    spec = TensorsSpec.from_shapes([shape], np.float32)
    # total in-flight pinned to EXACTLY one window: every dispatch is a
    # full cross-stream window (inline flush on the batch-th frame) —
    # the ladder measures sharding, so pads would only measure client
    # scheduling noise.  Frames per client round up to a whole number
    # of refills so the rep's last window is full too.
    outstanding = max(batch // MESH_SERVE_STREAMS, 1)
    nframes = ((MESH_SERVE_FRAMES + outstanding - 1)
               // outstanding) * outstanding
    pipes = []
    for i in range(MESH_SERVE_STREAMS):
        p = Pipeline(name=f"meshserve{n}_{i}")
        src = AppSrc(name="src", spec=spec, max_buffers=outstanding + 4)
        q = Queue(name="q", max_size_buffers=MESH_SERVE_FRAMES + 4)
        flt = TensorFilter(name="net", framework="jax-xla", model=name,
                           mesh=f"data:{n}",
                           batch=batch, batch_timeout_ms=2.0,
                           batch_buckets=str(batch), share_model=True,
                           stat_sample_interval_ms=0)
        sink = AppSink(name="out", max_buffers=MESH_SERVE_FRAMES + 4)
        p.add(src, q, flt, sink).link(src, q, flt, sink)
        p.start()
        pipes.append((p, src, flt, sink))

    def run_client(src, sink, total, errs):
        sent = got = inflight = 0
        try:
            while got < total:
                while sent < total and inflight < outstanding:
                    src.push_buffer(Buffer.of(
                        np.full(shape, float(sent % 7), np.float32),
                        pts=sent))
                    sent += 1
                    inflight += 1
                if sink.pull(timeout=120) is None:
                    raise RuntimeError(
                        f"meshserve client stalled at {got}/{total}")
                got += 1
                inflight -= 1
        except Exception as e:  # noqa: BLE001 - surface on main thread
            errs.append(e)

    def run_round(total):
        errs: list = []
        threads = [threading.Thread(target=run_client,
                                    args=(src, sink, total, errs))
                   for _, src, _, sink in pipes]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        return time.perf_counter() - t0

    entry = pipes[0][2].pool
    # the adaptive idle-flush (1 ms settle) is the right default for
    # latency-sensitive serving, but here it races the clients' refill
    # and dispatches part-filled windows — which would measure Python
    # thread wakeups, not sharding.  Give the window time to refill;
    # full windows still dispatch INLINE the moment the last frame
    # lands, so steady-state throughput is unaffected.
    entry.batcher.settle_s = 0.2
    # the window's deadline must outlive a WHOLE sampled dispatch: the
    # next window parks while the previous one executes (flush lock
    # held), so a deadline shorter than the dispatch fires the moment
    # the lock frees and ships a part-filled window
    entry.batcher.timeout_s = 10.0
    run_round(outstanding)  # warmup: compile + settle (one full window)
    best = None
    s0 = entry.stats.snapshot()
    m0 = MESH_STATS.get(name)
    for _ in range(MESH_SERVE_REPS):
        dt = run_round(nframes)
        best = dt if best is None else min(best, dt)
    s1 = entry.stats.snapshot()
    snap = REGISTRY.snapshot()
    mrow = _mesh_row_delta(m0, MESH_STATS.get(name))
    pool_row = next((r for r in snap.get("pools", [])
                     if r.get("model") == name), {})
    for p, src, _, _ in pipes:
        src.end_of_stream()
    for p, _, _, _ in pipes:
        p.wait_eos(timeout=30)
        p.stop()
    frames_total = MESH_SERVE_STREAMS * nframes
    disp = s1["phase"]["samples"] - s0["phase"]["samples"]
    host_s = ((s1["phase"]["host_prep_s"] + s1["phase"]["host_drain_s"])
              - (s0["phase"]["host_prep_s"]
                 + s0["phase"]["host_drain_s"])) / max(disp, 1)
    dev_s = (s1["phase"]["device_s"]
             - s0["phase"]["device_s"]) / max(disp, 1)
    dispatches = s1["invokes"] - s0["invokes"]
    frames_served = s1["frames"] - s0["frames"]
    return {
        "name": name, "batch": batch,
        "fps": frames_total / best,
        "frames_total": frames_total,
        "dispatches": dispatches,
        "frames_per_dispatch": frames_served / max(dispatches, 1),
        "stream_occupancy": s1.get("avg_stream_occupancy", 0.0),
        "host_s_per_dispatch": host_s,
        "device_s_per_dispatch": dev_s,
        "mesh_row": mrow,
        "pool_mesh": pool_row.get("mesh"),
        "pool_placement": pool_row.get("placement"),
    }


def bench_meshserving(out_path: str = "BENCH_mesh_serving.json",
                      metrics: bool = False):
    """``--meshserving``: the headline gate of the mesh-native serving
    rework — the weak-scaling ladder (n = 1,2,4,8 data-axis devices)
    run through the REAL ``share-model=true`` shared-pool element path
    instead of a synthetic filter: N pipelines coalesce into ONE
    cross-stream window per leg, the window is stacked once and
    dispatched with the micro-batch axis sharded over ``mesh=data:n``,
    and every dispatch is stat-sampled so each leg carries the full
    efficiency decomposition (host_phase / device_contention /
    shard_imbalance / pad_waste) plus the registry-vs-bench flops
    cross-check.  Writes ``BENCH_mesh_serving.json`` and folds a
    ``measured`` block into ``SCALING_MODEL.json`` — the projection
    finally cross-references a measurement of the real serving path."""
    import jax

    from nnstreamer_tpu.models.mobilenet import (
        mobilenet_v1_apply,
        mobilenet_v1_init,
    )
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.obs.xlacost import XLA_COST

    devs = require_devices(2, "--meshserving")
    sizes = _mesh_serve_sizes(len(devs))
    if not sizes:
        raise SystemExit(
            f"--meshserving: no ladder size fits the {len(devs)} "
            f"visible device(s)")
    shape = (32, 32, 3)
    params = mobilenet_v1_init(jax.random.PRNGKey(0), num_classes=16,
                               width=0.25)

    def per_frame_apply(p, f):
        # the pool serves FRAMES; the window stacks them, so the model
        # fn is per-frame (the conv stack wants a batch dim back)
        return mobilenet_v1_apply(p, f[None])[0]
    result = {
        "metric": "mesh-native shared serving weak scaling "
                  f"({MESH_SERVE_STREAMS} share-model pipelines x one "
                  f"pool, window {MESH_SERVE_BATCH_PER_SHARD}*n stacked "
                  "once + sharded over mesh=data:n, every dispatch "
                  "sampled)",
        "unit": "frames/sec",
        "platform": devs[0].platform,
        "devices_present": len(devs),
        "virtual_cpu_mesh": devs[0].platform == "cpu",
        "streams": MESH_SERVE_STREAMS,
        "batch_per_shard": MESH_SERVE_BATCH_PER_SHARD,
        "scaling": [],
    }
    rows = []
    base_fps = base_n = None
    for n in sizes:
        leg = _meshserve_leg(n, params, per_frame_apply, shape)
        batch = leg["batch"]
        name = leg["name"]
        if base_fps is None:
            base_fps, base_n = leg["fps"], n
        mrow = leg["mesh_row"]
        erow = XLA_COST.get(name, batch) or {}
        # independent cross-check of the stacked-window capture: the
        # bench's OWN lowering of the same vmapped window program must
        # yield the flops the pool executable's compile seam captured
        flops_bench = flops_bytes(jax.jit(
            lambda x: jax.vmap(
                lambda f: per_frame_apply(params, f))(x)).lower(
            jax.ShapeDtypeStruct((batch,) + shape, np.float32)))[0]
        row = {
            "n": n, "batch": batch,
            "fps": round(leg["fps"], 1),
            "fps_per_shard": round(leg["fps"] / n, 1),
            "efficiency": round(
                (leg["fps"] / n) / (base_fps / base_n), 3),
            "dispatches": leg["dispatches"],
            "frames_per_dispatch": round(leg["frames_per_dispatch"], 2),
            "stream_occupancy": round(leg["stream_occupancy"], 2),
            "host_s_per_dispatch": leg["host_s_per_dispatch"],
            "device_s_per_dispatch": leg["device_s_per_dispatch"],
            "imbalance": mrow.get("imbalance", 0.0),
            "pad_frac": mrow.get("pad_frac", 0.0),
            "shard_frames": mrow.get("shard_frames", []),
            "replicated_dispatches": mrow.get("replicated_dispatches",
                                              0),
            "pool_placement": leg["pool_placement"],
            "pool_mesh": leg["pool_mesh"],
            "flops_registry": erow.get("flops", 0.0),
            "flops_bench": flops_bench,
            "flops_exact": erow.get("flops", 0.0) == flops_bench
            and flops_bench > 0,
        }
        rows.append(row)
    for row in rows:
        row["attribution"] = _mesh_attribution(row, rows[0])
        row["host_s_per_dispatch"] = round(row["host_s_per_dispatch"], 6)
        row["device_s_per_dispatch"] = round(
            row["device_s_per_dispatch"], 6)
        result["scaling"].append(row)
    by_n = {r["n"]: r for r in rows}
    result["value"] = rows[-1]["fps"]
    result["vs_baseline"] = rows[-1]["efficiency"]
    # gate scalars (tests/bench_baselines/mesh_serving_smoke.json):
    # n=2 efficiency lower-direction, imbalance/pad exact-0.0 on the
    # even ladder, flops + cross-stream coalescing exact
    result["efficiency_n2"] = by_n[2]["efficiency"] if 2 in by_n \
        else None
    result["imbalance_even"] = max(r["imbalance"] for r in rows)
    result["pad_frac_even"] = max(r["pad_frac"] for r in rows)
    result["flops_exact"] = all(r["flops_exact"] for r in rows)
    result["coalescing_cross_stream"] = all(
        r["frames_per_dispatch"] > 1.0 for r in rows)
    if result["virtual_cpu_mesh"]:
        dom = rows[-1]["attribution"]["dominant"] if rows else "none"
        result["note"] = (
            "virtual devices share one physical CPU: the attribution "
            f"blocks show the loss (dominant at n={rows[-1]['n']}: "
            f"{dom}) is host-side contention, not ICI — code-path "
            "measurement of the REAL shared-pool serving stack; run "
            "on a real multi-chip host for true scaling")
    if metrics:
        result["metrics"] = REGISTRY.snapshot()
    _scaling_model_measured(result)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


def _scaling_model_measured(result: dict,
                            path: str = "SCALING_MODEL.json") -> None:
    """Fold the meshserving ladder into ``SCALING_MODEL.json`` as a
    ``measured`` block: the projection stays labeled "NOT a
    measurement", but it now cross-references the bench that measures
    the same data-parallel serving claim through the real element
    path — closing (or honestly reporting) the claim/measurement
    gap."""
    try:
        with open(path) as f:
            sm = json.load(f)
    except (OSError, ValueError):
        return  # no projection file here (e.g. bare checkout): the
        # bench result stands alone
    last = result["scaling"][-1]
    sm["measured"] = {
        "bench": "BENCH_mesh_serving.json",
        "scenario": "meshserving",
        "path": "tensor_filter share-model=true mesh=data:n "
                "(shared-pool stacked window, sharded dispatch)",
        "platform": result["platform"],
        "virtual_cpu_mesh": result["virtual_cpu_mesh"],
        "n": last["n"],
        "fps": last["fps"],
        "fps_per_shard": last["fps_per_shard"],
        "efficiency_vs_linear": last["efficiency"],
        "dominant_loss": last["attribution"]["dominant"],
        "note": ("virtual CPU mesh: validates the code path, not the "
                 "silicon — the 8-chip projection remains a model "
                 "until this bench runs on a real slice"
                 if result["virtual_cpu_mesh"] else
                 "measured on real devices through the real serving "
                 "path"),
    }
    with open(path, "w") as f:
        json.dump(sm, f, indent=1)


# -- disaggregated pipeline split: conditional cascade (ISSUE 18) -------------

CASCADE_FRAMES = int(os.environ.get("BENCH_CASCADE_FRAMES", "96"))
CASCADE_REPS = int(os.environ.get("BENCH_CASCADE_REPS", "3"))
CASCADE_SHAPE = (32, 32, 3)
CASCADE_CROP = (24, 24)  # fixed region at (0,0): one static crop shape
CASCADE_PERIOD = 4       # frame values cycle 0..3 — the seeded predicate
CASCADE_THRESHOLD = 3.0  # detector adds 1: values {2,3} offload → ratio 1/2


def _cascade_leg(split: bool, det_model: str, cls_model: str,
                 frames_n: int):
    """One cascade run through the REAL element path: device_src →
    detector filter → tensor_crop → tensor_if (offload=then, seeded
    predicate) → classifier filter, both filters ``share-model=true``
    pools on ``mesh=data:4``.  ``split=True`` pins the stages on
    DISJOINT subsets (``devices=0-3`` / ``devices=4-7``) so every
    offloaded frame crosses the stage boundary through the device
    channel; ``split=False`` is the single-stage comparator (both pools
    on the default first-4 subset, no boundary).  The frame values
    cycle 0..3 (``device_src frames=`` pool), so the routing is exact:
    detector output ``v+1 >= 3`` offloads values {2,3} — HALF the
    stream, analytically."""
    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.condition import TensorIf
    from nnstreamer_tpu.elements.crop import TensorCrop
    from nnstreamer_tpu.elements.devicesrc import DeviceSrc
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.obs import transfer as _xferled
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.obs.stagestat import STAGE_STATS
    from nnstreamer_tpu.runtime import Pipeline

    ch, cw = CASCADE_CROP
    pname = "cascade_split" if split else "cascade_fused"
    pool = [np.full(CASCADE_SHAPE, float(k), np.float32)
            for k in range(CASCADE_PERIOD)]
    p = Pipeline(name=pname)
    src = DeviceSrc(name="src", frames=pool, pool_size=CASCADE_PERIOD,
                    num_buffers=frames_n)
    info = AppSrc(name="regions",
                  spec=TensorsSpec.from_shapes([(1, 4)], np.uint32),
                  max_buffers=frames_n + 8)
    q1 = Queue(name="q1", max_size_buffers=64)
    det = TensorFilter(name="det", framework="jax-xla", model=det_model,
                       mesh="data:4", devices="0-3" if split else "",
                       batch=4, batch_buckets="4", batch_timeout_ms=20.0,
                       share_model=True, stat_sample_interval_ms=0)
    crop = TensorCrop(name="crop")
    route = TensorIf(name="route", compared_value="A_VALUE",
                     compared_value_option="0:0",
                     supplied_value=str(CASCADE_THRESHOLD),
                     operator="ge", offload="then",
                     then="PASSTHROUGH", else_="PASSTHROUGH")
    q2 = Queue(name="q2", max_size_buffers=64)
    cls = TensorFilter(name="cls", framework="jax-xla", model=cls_model,
                       mesh="data:4", devices="4-7" if split else "",
                       batch=4, batch_buckets="4", batch_timeout_ms=20.0,
                       share_model=True, stat_sample_interval_ms=0)
    sink_off = AppSink(name="off", max_buffers=frames_n + 8)
    sink_keep = AppSink(name="keep", max_buffers=frames_n + 8)
    p.add(src, info, q1, det, crop, route, q2, cls, sink_off, sink_keep)
    p.link(src, q1, det)
    p.link_pads(det, "src", crop, "sink_raw")
    p.link_pads(info, "src", crop, "sink_info")
    p.link(crop, route)
    p.link_pads(route, "src_then", q2, "sink")
    p.link(q2, cls, sink_off)
    p.link_pads(route, "src_else", sink_keep, "sink")
    region = np.array([[0, 0, cw, ch]], np.uint32)
    # crossings accounting exactly like _run_composite_once: h2d input
    # + d2h drain rows over the run — d2d stage handoffs are tagged
    # reason="handoff" on the ledger and must NOT appear here
    x0 = _xferled.LEDGER.totals(reason="input")[0] \
        + _xferled.LEDGER.totals(reason="drain")[0]
    t0 = time.perf_counter()
    p.start()
    for i in range(frames_n):
        info.push_buffer(Buffer.of(region), timeout=120)
    info.end_of_stream()
    if not p.wait_eos(timeout=300):
        p.stop()
        raise RuntimeError(f"{pname}: pipeline did not reach EOS")
    dt = time.perf_counter() - t0
    x1 = _xferled.LEDGER.totals(reason="input")[0] \
        + _xferled.LEDGER.totals(reason="drain")[0]
    # pool occupancy while the pools are still attached (stop releases)
    stage_pools = [
        {"model": r.get("model"), "stage": r.get("stage", ""),
         "placement": r.get("placement"), "streams": r.get("streams"),
         "frames": (r.get("stats") or {}).get("frames"),
         "dispatches": (r.get("stats") or {}).get("invokes"),
         "occupancy": (r.get("stats") or {}).get(
             "avg_stream_occupancy")}
        for r in REGISTRY.snapshot().get("pools", [])
        if r.get("model") in (det_model, cls_model)]
    hrow = STAGE_STATS.get(pname, "cls")
    orow = STAGE_STATS.get(pname, "route")

    def _drain(sink):
        out = []
        while True:
            b = sink.pull(timeout=0.2)
            if b is None:
                return out
            out.append(b)

    off, keep = _drain(sink_off), _drain(sink_keep)
    # checksum of the offloaded-branch classifier outputs, in arrival
    # order — the split/fused parity surface (drains happen AFTER the
    # crossings figure is taken)
    digest = [round(float(np.sum(b.tensors[0].np())), 4) for b in off]
    p.stop()
    return {
        "fps": frames_n / dt,
        "crossings_per_frame": (x1 - x0) / float(frames_n),
        "offloaded": len(off), "kept": len(keep),
        "offload_row": orow, "handoff_row": hrow,
        "stage_pools": stage_pools, "digest": digest,
    }


def bench_cascade(out_path: str = "BENCH_cascade.json",
                  metrics: bool = False):
    """``--cascade``: the headline gate of disaggregated pipeline-split
    serving — a conditional cascade (detector → tensor_crop →
    tensor_if → classifier) run twice through the REAL element path:
    once SPLIT over disjoint device subsets (detector ``devices=0-3``,
    classifier ``devices=4-7``, every offloaded frame handed
    device-to-device through the device channel) and once single-stage
    (both pools on one subset).  Gates: stage-boundary
    ``crossings_per_frame`` EXACTLY 0.0 (the d2d handoff must never
    degrade to a drain/re-upload pair), the offload ratio EXACTLY the
    seeded predicate's analytic 1/2, byte-exact handoff accounting, and
    the split-vs-fused throughput ratio as an honest floor.  Writes
    ``BENCH_cascade.json`` and folds a ``measured`` block into
    ``SCALING_MODEL.json``'s ``split_pipeline`` object — the projection
    finally cross-references a measurement of the split serving path."""
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.obs.stagestat import STAGE_STATS

    # the split is two 4-chip stages
    devs = require_devices(8, "--cascade")
    frames_n = (max(CASCADE_FRAMES, 2 * CASCADE_PERIOD)
                // (2 * CASCADE_PERIOD)) * (2 * CASCADE_PERIOD)
    ch, cw = CASCADE_CROP

    def det_apply(prm, f):
        return f + prm

    def cls_apply(prm, f):
        return jnp.tanh(f * prm).sum(axis=(0, 1))

    det_model = register_model("bench_cascade_det", det_apply,
                               params=np.float32(1.0),
                               in_shapes=[CASCADE_SHAPE],
                               in_dtypes=np.float32)
    cls_model = register_model("bench_cascade_cls", cls_apply,
                               params=np.float32(1.0),
                               in_shapes=[(ch, cw, CASCADE_SHAPE[2])],
                               in_dtypes=np.float32)
    STAGE_STATS.reset()
    runs_s, runs_f, cross = [], [], []
    last_split = last_fused = None
    for _ in range(CASCADE_REPS):
        last_split = _cascade_leg(True, det_model, cls_model, frames_n)
        runs_s.append(last_split["fps"])
        cross.append(last_split["crossings_per_frame"])
        last_fused = _cascade_leg(False, det_model, cls_model, frames_n)
        runs_f.append(last_fused["fps"])
    med_s, spread_s = _ab_aggregate(runs_s)
    med_f, spread_f = _ab_aggregate(runs_f)
    hrow = last_split["handoff_row"] or {}
    orow = last_split["offload_row"] or {}
    expected_ratio = sum(
        1 for v in range(CASCADE_PERIOD)
        if v + 1.0 >= CASCADE_THRESHOLD) / CASCADE_PERIOD
    crop_bytes = ch * cw * CASCADE_SHAPE[2] * 4  # float32 crop payload
    result = {
        "metric": "conditional cascade over a pipeline split "
                  f"(detector devices=0-3 → tensor_crop → tensor_if "
                  f"offload=then → classifier devices=4-7, "
                  f"{frames_n} frames, share-model pools, batch=4 over "
                  "mesh=data:4 per stage)",
        "unit": "frames/sec",
        "platform": devs[0].platform,
        "devices_present": len(devs),
        "virtual_cpu_mesh": devs[0].platform == "cpu",
        "frames": frames_n,
        "value": round(med_s, 1),
        "fps_split": round(med_s, 1),
        "fps_fused": round(med_f, 1),
        "split_vs_fused": round(med_s / med_f, 3) if med_f else None,
        "ab_spread": {"split": spread_s, "fused": spread_f,
                      "samples_split": [round(s, 1) for s in runs_s],
                      "samples_fused": [round(s, 1) for s in runs_f]},
        # EXACT gates (tests/bench_baselines/cascade_smoke.json):
        # crossings 0.0 across the stage boundary, the analytic offload
        # ratio, byte-exact handoff accounting, drained depth
        "crossings_per_frame": max(cross),
        "offload_ratio": orow.get("ratio"),
        "offload_ratio_expected": expected_ratio,
        "offload_exact": orow.get("ratio") == expected_ratio,
        "handoff_frames": hrow.get("frames"),
        "handoff_bytes": hrow.get("bytes"),
        "handoff_bytes_per_frame":
            (hrow.get("bytes", 0) / max(hrow.get("frames", 0), 1))
            if hrow else None,
        "handoff_bytes_exact":
            bool(hrow) and hrow.get("frames", 0) > 0
            and hrow.get("bytes") == hrow.get("frames") * crop_bytes,
        "handoff_route": f"{hrow.get('from')}→{hrow.get('to')}"
        if hrow else None,
        "handoff_depth_end": hrow.get("depth"),
        "offload_parity":
            last_split is not None and last_fused is not None
            and last_split["digest"] == last_fused["digest"],
        "stage_pools": last_split["stage_pools"] if last_split else [],
    }
    if result["virtual_cpu_mesh"]:
        result["note"] = (
            "virtual devices share one physical CPU: the split/fused "
            "ratio measures the code path (handoff + per-stage pools), "
            "not ICI bandwidth — the split_pipeline projection remains "
            "a model until this bench runs on a real multi-chip host")
    if metrics:
        result["metrics"] = REGISTRY.snapshot()
    _scaling_split_measured(result)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


def _scaling_split_measured(result: dict,
                            path: str = "SCALING_MODEL.json") -> None:
    """Fold the cascade bench into ``SCALING_MODEL.json``'s
    ``split_pipeline`` object as a ``measured`` block — the projection
    (58k fps, ici_efficiency 1.0) stays labeled a model, but it now
    sits next to a measurement of the same pipeline-split claim through
    the real element path, mirroring what the data-parallel
    ``measured`` block did for the top-level projection."""
    try:
        with open(path) as f:
            sm = json.load(f)
    except (OSError, ValueError):
        return  # no projection file here: the bench result stands alone
    sp = sm.setdefault("split_pipeline", {})
    sp["measured"] = {
        "bench": "BENCH_cascade.json",
        "scenario": "cascade",
        "path": "detector devices=0-3 → tensor_crop → tensor_if "
                "offload=then → classifier devices=4-7 "
                "(share-model pools per stage, device-channel handoff)",
        "platform": result["platform"],
        "virtual_cpu_mesh": result["virtual_cpu_mesh"],
        "fps_split": result["fps_split"],
        "fps_fused": result["fps_fused"],
        "split_vs_fused": result["split_vs_fused"],
        "crossings_per_frame": result["crossings_per_frame"],
        "offload_ratio": result["offload_ratio"],
        "handoff_bytes_per_frame": result["handoff_bytes_per_frame"],
        "note": ("virtual CPU mesh: validates the split serving code "
                 "path (d2d handoff, per-stage pools), not the "
                 "silicon — the ici_efficiency=1.0 projection remains "
                 "a model until this bench runs on a real slice"
                 if result["virtual_cpu_mesh"] else
                 "measured on real devices through the real split "
                 "serving path"),
    }
    with open(path, "w") as f:
        json.dump(sm, f, indent=1)


BATCHING_FRAMES = int(os.environ.get("BENCH_BATCHING_FRAMES", "512"))
BATCHING_BATCH = int(os.environ.get("BENCH_BATCHING_BATCH", "16"))


def _batching_run(model: str, spec, n: int, batch: int,
                  capture_metrics: bool = False):
    """One micro-batching A/B leg: appsrc ! queue ! tensor_filter
    batch=N ! appsink on the CPU backend.  Frames are tiny, so the run
    is DISPATCH-bound — exactly the regime micro-batching coalesces.
    Returns (fps, dispatches, frames, occupancy)."""
    from nnstreamer_tpu.core import Buffer
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.runtime import Pipeline

    shape = spec.tensors[0].shape
    frames = [Buffer.of(np.full(shape, float(i % 7), np.float32), pts=i)
              for i in range(n)]
    p = Pipeline()
    src = AppSrc(name="src", spec=spec, max_buffers=n + batch + 4)
    q = Queue(name="q", max_size_buffers=n + batch + 4)
    # a single pinned bucket: partial windows (a scheduling hiccup can
    # deadline-close one mid-run) pad up to `batch` instead of JIT-ing
    # a smaller bucket's executable inside the timed region
    flt = TensorFilter(name="net", framework="jax-xla", model=model,
                       batch=batch, batch_timeout_ms=5.0,
                       batch_buckets=str(batch))
    sink = AppSink(name="out", max_buffers=n + batch + 4)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    with p:
        # warmup: one full window — with the pinned bucket this is the
        # ONLY executable any later window can need
        for i in range(batch):
            src.push_buffer(frames[i])
        _pull(sink, "batching warmup")
        for _ in range(batch - 1):
            _pull(sink, "batching warmup")
        d0 = flt.invoke_stats.total_invoke_num
        f0 = flt.invoke_stats.total_frame_num
        t0 = time.perf_counter()
        for b in frames:
            src.push_buffer(b)
        last = None
        for _ in range(n):
            last = _pull(sink, "batching")
        np.asarray(last.tensors[0].np())  # host fetch: completion
        dt = time.perf_counter() - t0
        dispatches = flt.invoke_stats.total_invoke_num - d0
        frames_done = flt.invoke_stats.total_frame_num - f0
        extras = {}
        if capture_metrics:
            from nnstreamer_tpu.obs.metrics import REGISTRY

            extras["metrics"] = REGISTRY.snapshot()
        src.end_of_stream()
        p.wait_eos(timeout=30)
    occ = frames_done / dispatches if dispatches else 0.0
    return n / dt, dispatches, frames_done, occ, extras


def bench_batching(out_path: str = "BENCH_batching.json",
                   metrics: bool = False):
    """``--batching``: dispatch-coalescing A/B on the CPU backend — the
    ISSUE-2 acceptance scenario.  A deliberately tiny model makes the
    per-dispatch Python+XLA overhead dominate; batch=1 pays it per
    frame, batch=N amortizes it N ways.  Reports frames/s AND
    dispatches/s for both legs and writes the JSON line to
    ``BENCH_batching.json``."""
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import register_model

    n, batch = BATCHING_FRAMES, BATCHING_BATCH
    model = register_model("bench_batching_tiny",
                           lambda x: x * 2.0 + 1.0,
                           in_shapes=[(16,)], in_dtypes=np.float32)
    spec = TensorsSpec.from_shapes([(16,)], np.float32)
    fps1, disp1, frames1, _, _ = _batching_run(model, spec, n, 1)
    fpsN, dispN, framesN, occ, extras = _batching_run(
        model, spec, n, batch, capture_metrics=metrics)
    result = {
        "metric": "micro-batched tensor_filter dispatch coalescing "
                  f"(CPU backend, {n} frames, dispatch-bound model, "
                  "appsrc ! queue ! jax-xla ! appsink)",
        "value": round(fpsN / fps1, 3) if fps1 else None,
        "unit": "x frames/s vs batch=1",
        "vs_baseline": round(fpsN / fps1, 3) if fps1 else None,
        "frames": n,
        "batch": batch,
        "batch1_fps": round(fps1, 1),
        "batch1_dispatches": disp1,
        "batched_fps": round(fpsN, 1),
        "batched_dispatches": dispN,
        "dispatch_reduction": round(framesN / dispN, 2) if dispN else None,
        "batch_occupancy": round(occ, 2),
        "coalescing": dispN < framesN,
        "note": "frames are 16-float vectors: per-dispatch overhead "
                "dominates by construction, isolating what coalescing "
                "buys independent of model compute",
    }
    if extras:
        result["metrics"] = extras["metrics"]
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


SERVE_PIPES = int(os.environ.get("BENCH_SERVE_PIPES", "8"))
SERVE_FRAMES = int(os.environ.get("BENCH_SERVE_FRAMES", "64"))
SERVE_BATCH = int(os.environ.get("BENCH_SERVE_BATCH", "16"))
SERVE_OUTSTANDING = int(os.environ.get("BENCH_SERVE_OUTSTANDING", "1"))
SERVE_TIMEOUT_MS = float(os.environ.get("BENCH_SERVE_TIMEOUT_MS", "2.0"))


def _serve_leg(model: str, spec, share: bool, capture_metrics: bool = False):
    """One shared-model serving A/B leg: SERVE_PIPES identical
    ``appsrc ! queue ! tensor_filter ! appsink`` pipelines on the SAME
    tiny model, each driven closed-loop by its own client with
    SERVE_OUTSTANDING frames in flight (the Clipper setting: N request
    streams, each with a small window of outstanding requests — no
    single stream can fill a batch window by itself).

    share=False is the per-element regime: every pipeline holds its own
    model instance and its own batch window, which closes on the
    batch-timeout deadline carrying only that client's few outstanding
    frames.  share=True pools them: one instance, one CROSS-pipeline
    window that the adaptive batcher flushes whenever the device goes
    idle.  Returns (fps, dispatches, frames_total, occupancy,
    stream_occupancy)."""
    import threading

    from nnstreamer_tpu.core import Buffer
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.runtime import Pipeline

    shape = spec.tensors[0].shape
    pipes = []
    for i in range(SERVE_PIPES):
        p = Pipeline(name=f"serve{i}")
        src = AppSrc(name="src", spec=spec,
                     max_buffers=SERVE_OUTSTANDING + 4)
        q = Queue(name="q", max_size_buffers=SERVE_FRAMES + 4)
        # one pinned bucket: every window pads to `batch`, so exactly
        # ONE executable exists per leg (compiled in warmup, shared by
        # every pipeline when share=True)
        flt = TensorFilter(name="net", framework="jax-xla", model=model,
                           batch=SERVE_BATCH,
                           batch_timeout_ms=SERVE_TIMEOUT_MS,
                           batch_buckets=str(SERVE_BATCH),
                           share_model=share)
        sink = AppSink(name="out", max_buffers=SERVE_FRAMES + 4)
        p.add(src, q, flt, sink).link(src, q, flt, sink)
        p.start()
        pipes.append((p, src, flt, sink))

    def run_client(src, sink, n, errs):
        sent = got = inflight = 0
        try:
            while got < n:
                while sent < n and inflight < SERVE_OUTSTANDING:
                    src.push_buffer(Buffer.of(
                        np.full(shape, float(sent % 7), np.float32),
                        pts=sent))
                    sent += 1
                    inflight += 1
                if sink.pull(timeout=60) is None:
                    raise RuntimeError(
                        f"serve client stalled at {got}/{n}")
                got += 1
                inflight -= 1
        except Exception as e:  # noqa: BLE001 - surface on the main thread
            errs.append(e)

    def run_round(n):
        errs: list = []
        threads = [threading.Thread(target=run_client,
                                    args=(src, sink, n, errs))
                   for _, src, _, sink in pipes]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        return time.perf_counter() - t0

    def dispatches():
        if share:
            return pipes[0][2].pool.stats.total_invoke_num
        return sum(flt.invoke_stats.total_invoke_num
                   for _, _, flt, _ in pipes)

    # warmup round: compiles the (single) bucket executable per instance
    # and settles the windows, outside the timed region
    run_round(max(SERVE_OUTSTANDING, 2))
    d0 = dispatches()
    dt = run_round(SERVE_FRAMES)
    disp = dispatches() - d0
    frames_total = SERVE_PIPES * SERVE_FRAMES
    occ = frames_total / disp if disp else 0.0
    stream_occ = pipes[0][2].pool_stream_occupancy if share else 1.0
    extras = {}
    if capture_metrics:
        # registry snapshot while the pipelines/pool are still live —
        # the ground-truth cross-check for `--metrics`: the exported
        # pool dispatch counter must equal the bench's own invoke count
        # read at the same (idle, settled) moment
        from nnstreamer_tpu.obs.metrics import REGISTRY

        extras["dispatches_total"] = dispatches()
        extras["metrics"] = REGISTRY.snapshot()
    for p, src, _, _ in pipes:
        src.end_of_stream()
    for p, _, _, _ in pipes:
        p.wait_eos(timeout=30)
        p.stop()
    return frames_total / dt, disp, frames_total, occ, stream_occ, extras


def bench_serving(out_path: str = "BENCH_serving.json",
                  metrics: bool = False):
    """``--serve``: cross-pipeline batch-coalescing A/B on the CPU
    backend — the ISSUE-3 acceptance scenario.  N concurrent pipelines
    serve the SAME dispatch-bound model; the unshared leg pays N model
    copies and N nearly-empty deadline-closed windows, the shared leg
    one pooled instance and one adaptive cross-stream window.  Writes
    ``BENCH_serving.json``."""
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import register_model

    model = register_model("bench_serving_tiny",
                           lambda x: x * 2.0 + 1.0,
                           in_shapes=[(16,)], in_dtypes=np.float32)
    spec = TensorsSpec.from_shapes([(16,)], np.float32)
    fps_u, disp_u, frames, _, _, _ = _serve_leg(model, spec, share=False)
    fps_s, disp_s, _, occ_s, streams_s, extras = _serve_leg(
        model, spec, share=True, capture_metrics=metrics)
    result = {
        "metric": "shared-model serving: cross-pipeline batch coalescing "
                  f"({SERVE_PIPES} concurrent pipelines x same model, "
                  f"closed-loop {SERVE_OUTSTANDING} outstanding/client, "
                  "CPU backend, dispatch-bound model)",
        "value": round(fps_s / fps_u, 3) if fps_u else None,
        "unit": f"x frames/s vs unshared batch={SERVE_BATCH}",
        "vs_baseline": round(fps_s / fps_u, 3) if fps_u else None,
        "pipes": SERVE_PIPES,
        "frames_total": frames,
        "batch": SERVE_BATCH,
        "outstanding_per_client": SERVE_OUTSTANDING,
        "batch_timeout_ms": SERVE_TIMEOUT_MS,
        "unshared_fps": round(fps_u, 1),
        "unshared_dispatches": disp_u,
        "shared_fps": round(fps_s, 1),
        "shared_dispatches": disp_s,
        "dispatch_reduction": round(disp_u / disp_s, 2) if disp_s else None,
        "shared_frames_per_dispatch": round(occ_s, 2),
        "shared_stream_occupancy": round(streams_s, 2),
        "coalescing_cross_stream": disp_s < frames,
        "note": "no client can fill a window alone (closed loop, few "
                "outstanding): the unshared leg deadline-flushes "
                "nearly-empty per-pipeline buckets while the shared leg "
                "coalesces all streams into one adaptive window — the "
                "regime of ISSUE-3 / Clipper NSDI'17",
    }
    if extras:
        # `--metrics`: embed the obs registry snapshot (the passive,
        # pull-time view) plus the bench's own cumulative dispatch count
        # read at the same moment, so CI can assert they agree
        result["shared_dispatches_total"] = extras["dispatches_total"]
        result["metrics"] = extras["metrics"]
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


EDGE_FRAMES = int(os.environ.get("BENCH_EDGE_FRAMES", "256"))
EDGE_OUTSTANDING = int(os.environ.get("BENCH_EDGE_OUTSTANDING", "8"))


def bench_edge(out_path: str = "BENCH_edge.json"):
    """``--edge``: loopback-TCP tensor_query round-trip bench — the
    ground truth for the ``nns_edge_*`` link metrics (ISSUE-5).  Runs a
    client pipeline against a serversrc→filter→serversink pipeline over
    real sockets, then cross-checks the exported per-link byte counters
    against independently re-packed frame sizes (exact equality: the
    wire codec is deterministic) and reports the RTT distribution the
    LINK row in ``nns-top`` renders."""
    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.edge.wire import MSG_QUERY, MSG_REPLY, EdgeMessage
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc
    from nnstreamer_tpu.filters.custom import register_custom_easy
    from nnstreamer_tpu.obs.metrics import REGISTRY, LinkMetrics
    from nnstreamer_tpu.runtime import Pipeline
    from nnstreamer_tpu.runtime.registry import make

    LinkMetrics.clear_all()
    spec = TensorsSpec.parse("16:1", "float32")
    register_custom_easy("bench_edge_x2", lambda xs: [xs[0] * 2.0],
                         in_spec=spec, out_spec=spec)
    srv = Pipeline(name="edge-bench-server")
    qsrc = make("tensor_query_serversrc", el_name="qsrc",
                connect_type="tcp", host="127.0.0.1", port=0, id=93)
    flt = make("tensor_filter", el_name="f", framework="custom-easy",
               model="bench_edge_x2")
    qsink = make("tensor_query_serversink", el_name="qsink", id=93)
    srv.add(qsrc, flt, qsink).link(qsrc, flt, qsink)
    srv.start()

    cli = Pipeline(name="edge-bench-client")
    src = AppSrc(name="src", spec=spec, max_buffers=EDGE_OUTSTANDING + 4)
    q = make("tensor_query_client", el_name="qcli", host="127.0.0.1",
             port=qsrc.port, connect_type="tcp", timeout=30000,
             max_request=EDGE_OUTSTANDING,
             caps="other/tensors,format=static,num_tensors=1,"
                  "dimensions=16:1,types=float32")
    sink = AppSink(name="out", max_buffers=EDGE_FRAMES + 4)
    cli.add(src, q, sink).link(src, q, sink)
    cli.start()
    frames = [Buffer.of(np.full((1, 16), float(i % 11), np.float32),
                        pts=i) for i in range(EDGE_FRAMES)]
    t0 = time.perf_counter()
    sent = got = 0
    while got < EDGE_FRAMES:
        while sent < EDGE_FRAMES and sent - got < EDGE_OUTSTANDING:
            src.push_buffer(frames[sent])
            sent += 1
        if sink.pull(timeout=60) is None:
            raise RuntimeError(f"edge bench stalled at {got}")
        got += 1
    dt = time.perf_counter() - t0
    snap = REGISTRY.snapshot()
    link = [r for r in snap["links"]
            if r["kind"] == "query" and r["link"] == "qcli"][0]
    src.end_of_stream()
    cli.wait_eos(timeout=30)
    cli.stop()
    srv.stop()
    # ground truth: re-pack the SAME messages the client/server framed
    # (4-byte length prefix + wire bytes); replies echo seq/client_id=1
    # and carry the same-sized float32 payload back
    tx_truth = sum(
        4 + len(EdgeMessage.from_buffer(MSG_QUERY, b, seq=i + 1).pack())
        for i, b in enumerate(frames))
    reply = EdgeMessage.from_buffer(MSG_REPLY, frames[0], client_id=1,
                                    seq=1)
    rx_truth = EDGE_FRAMES * (4 + len(reply.pack()))
    result = {
        "metric": "edge link observability: loopback-TCP tensor_query "
                  f"round-trips ({EDGE_FRAMES} frames, "
                  f"{EDGE_OUTSTANDING} outstanding)",
        "value": round(link["rtt"]["mean_us"], 1)
        if link["rtt"]["mean_us"] else None,
        "unit": "µs mean round-trip (client-observed, incl. server)",
        "frames": EDGE_FRAMES,
        "frames_per_s": round(EDGE_FRAMES / dt, 1),
        "tx_bytes": link["tx_bytes"],
        "rx_bytes": link["rx_bytes"],
        "tx_bytes_truth": tx_truth,
        "rx_bytes_truth": rx_truth,
        "bytes_exact": link["tx_bytes"] == tx_truth
        and link["rx_bytes"] == rx_truth,
        "tx_msgs": link["tx_msgs"],
        "rx_msgs": link["rx_msgs"],
        "timeouts": link["timeouts"],
        "reconnects": link["reconnects"],
        "rtt_mean_us": link["rtt"]["mean_us"],
        "link": link,
        "note": "tx/rx byte counters must EQUAL the re-packed framed "
                "sizes — the LinkMetrics hook sits at the socket "
                "framing layer, so any drift is an accounting bug "
                "(nns-top LINK rows render these numbers)",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


# -- open-loop SLO bench (--openloop → BENCH_slo.json) ------------------------

SLO_PIPES = int(os.environ.get("BENCH_SLO_PIPES", "6"))
SLO_HIGH = int(os.environ.get("BENCH_SLO_HIGH", "2"))
SLO_FRAMES = int(os.environ.get("BENCH_SLO_FRAMES", "240"))
SLO_BATCH = int(os.environ.get("BENCH_SLO_BATCH", "8"))
SLO_TIMEOUT_MS = float(os.environ.get("BENCH_SLO_TIMEOUT_MS", "2.0"))
#: how long each open-loop leg OFFERS load: frames per stream scale
#: with the arrival rate so overload lasts long enough for the
#: admission controller's latency window to see it
SLO_LEG_S = float(os.environ.get("BENCH_SLO_LEG_S", "5.0"))


def _slo_build_pipes(model, spec, slo_ms, prios, queue_size=64):
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.runtime import Pipeline

    pipes = []
    for i, prio in enumerate(prios):
        p = Pipeline(name=f"slo{i}-{prio}")
        src = AppSrc(name="src", spec=spec, max_buffers=queue_size)
        q = Queue(name="q", max_size_buffers=queue_size)
        # per-class EDF deadlines: the high class's tighter deadline
        # means window formation prefers it whenever the window is
        # contended, independent of the shedding decision
        dl = 0.0
        if slo_ms > 0:
            dl = 0.5 * slo_ms if prio == "high" else 2.0 * slo_ms
        flt = TensorFilter(name="net", framework="jax-xla", model=model,
                           batch=SLO_BATCH,
                           batch_timeout_ms=SLO_TIMEOUT_MS,
                           batch_buckets=str(SLO_BATCH), share_model=True,
                           slo_ms=slo_ms, priority=prio, deadline_ms=dl)
        sink = AppSink(name="out", max_buffers=8 * SLO_FRAMES + 16)
        p.add(src, q, flt, sink).link(src, q, flt, sink)
        p.start()
        pipes.append({"pipe": p, "src": src, "q": q, "flt": flt,
                      "sink": sink, "prio": prio})
    return pipes


def _slo_teardown(pipes):
    for e in pipes:
        e["src"].end_of_stream()
    for e in pipes:
        e["pipe"].wait_eos(timeout=30, raise_on_error=False)
        e["pipe"].stop()


def _slo_warmup(pipes, spec, rounds=2):
    """Compile the bucket executable and settle the windows OUTSIDE the
    timed region (a fresh pool entry pays XLA compile on its first
    window — that must not contaminate the latency signal or arm the
    admission controller spuriously)."""
    from nnstreamer_tpu.core import Buffer

    entry = pipes[0]["flt"].pool
    adm = entry.admission if entry is not None else None
    real_slo = None
    if adm is not None:
        # no shedding while the executable compiles: warmup frames must
        # all come back, and the compile stall must not arm the
        # controller before real traffic starts
        real_slo = adm.slo_s
        adm.slo_s = float("inf")
    shape = spec.tensors[0].shape
    arr = np.zeros(shape, np.float32)
    for _ in range(rounds):
        for e in pipes:
            for i in range(SLO_BATCH):
                e["src"].push_buffer(Buffer.of(arr, pts=i), timeout=10)
        for e in pipes:
            for _i in range(SLO_BATCH):
                if e["sink"].pull(timeout=60) is None:
                    raise RuntimeError("SLO bench warmup stalled")
    if adm is not None:
        # drop the compile-inflated latencies (deque AND the exported-
        # histogram delta window), restore the real SLO
        adm.reset_signal()
        adm.slo_s = real_slo


def _slo_closed_loop(model, spec, frames):
    """Sustainable-rate probe: every stream closed-loop (full-window
    outstanding, small queues, admission off).  Returns (total fps,
    p99 latency s)."""
    import threading

    from nnstreamer_tpu.core import Buffer

    shape = spec.tensors[0].shape
    pipes = _slo_build_pipes(model, spec, 0.0,
                             ["normal"] * SLO_PIPES, queue_size=8)
    _slo_warmup(pipes, spec)
    lats, errs = [], []
    lat_lock = threading.Lock()

    # enough outstanding per stream to FILL the shared windows: batch
    # capacity rises with occupancy, so a low-occupancy probe would
    # understate the sustainable rate by up to the batch factor
    outstanding = 2 * SLO_BATCH

    def client(e):
        try:
            sent = got = 0
            ts = {}
            while got < frames:
                while sent < frames and sent - got < outstanding:
                    ts[sent] = time.monotonic()
                    e["src"].push_buffer(Buffer.of(
                        np.zeros(shape, np.float32), pts=sent), timeout=10)
                    sent += 1
                b = e["sink"].pull(timeout=30)
                if b is None:
                    raise RuntimeError("closed-loop probe stalled")
                with lat_lock:
                    lats.append(time.monotonic() - ts.pop(b.pts))
                got += 1
        except Exception as exc:  # noqa: BLE001
            errs.append(exc)

    threads = [threading.Thread(target=client, args=(e,)) for e in pipes]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    _slo_teardown(pipes)
    if errs:
        raise errs[0]
    lats.sort()
    p99 = lats[min(int(0.99 * len(lats)), len(lats) - 1)] if lats else 0.0
    return SLO_PIPES * frames / dt, p99


def _slo_open_loop_leg(model, spec, slo_ms, prios, rates, frames,
                       seed, bursty=False):
    """One open-loop leg: per-stream Poisson (optionally bursty)
    arrivals — ``rates[i]`` / ``frames[i]`` for pipe ``i``.  Returns
    per-priority accounting + latency percentiles."""
    import queue as _pyq
    import random
    import threading

    from nnstreamer_tpu.core import Buffer

    shape = spec.tensors[0].shape
    pipes = _slo_build_pipes(model, spec, slo_ms, prios)
    _slo_warmup(pipes, spec)
    entry = pipes[0]["flt"].pool
    shed0 = entry.admission.snapshot() if entry.admission else None
    stop = threading.Event()
    max_qdepth = [0]

    for e, rate, n in zip(pipes, rates, frames):
        e.update(send_ts=[0.0] * n, lats=[], ingress_dropped=0,
                 delivered=0, rate=rate, frames=n)

    def producer(e, idx):
        rng = random.Random(seed + idx)
        arr = np.zeros(shape, np.float32)
        rate = e["rate"]
        # absolute arrival schedule: sleep-until-next (not
        # sleep-for-gap) so Python's sleep overhead cannot silently
        # deflate the offered rate — a producer that falls behind
        # catches up with back-to-back arrivals, like real traffic
        t_next = time.monotonic()
        for i in range(e["frames"]):
            if rate > 0:
                # Poisson gaps; in bursty mode every 40th arrival
                # opens a burst of 4 back-to-back frames
                if not (bursty and i % 40 and (i % 40) < 4):
                    t_next += rng.expovariate(rate)
                delay = t_next - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            e["send_ts"][i] = time.monotonic()
            try:
                # open loop: an arrival NEVER waits for the server —
                # a full ingress queue is a visible drop, not a stall
                e["src"].push_buffer(Buffer.of(arr, pts=i), timeout=0)
            except _pyq.Full:
                e["ingress_dropped"] += 1

    def consumer(e):
        while not stop.is_set():
            b = e["sink"].pull(timeout=0.1)
            if b is None:
                continue
            e["lats"].append(time.monotonic() - e["send_ts"][b.pts])
            e["delivered"] += 1

    producers = [threading.Thread(target=producer, args=(e, i))
                 for i, e in enumerate(pipes)]
    consumers = [threading.Thread(target=consumer, args=(e,))
                 for e in pipes]
    t0 = time.perf_counter()
    for t in consumers + producers:
        t.start()
    for t in producers:
        t.join()
    # drain: wait until every offered frame is accounted (delivered,
    # shed, or dropped at ingress) or the drain bound passes
    drain_deadline = time.monotonic() + 30.0
    while time.monotonic() < drain_deadline:
        max_qdepth[0] = max(max_qdepth[0],
                            max(e["q"].current_level_buffers
                                for e in pipes))
        shed_now = entry.admission.total_shed if entry.admission else 0
        shed_base = (sum(shed0["shed"].values())
                     + sum(shed0["shed_queue_full"].values())) \
            if shed0 else 0
        accounted = sum(e["delivered"] + e["ingress_dropped"]
                        for e in pipes) + (shed_now - shed_base)
        if accounted >= sum(frames):
            break
        time.sleep(0.05)
    stop.set()
    for t in consumers:
        t.join()
    wall = time.perf_counter() - t0
    shed1 = entry.admission.snapshot() if entry.admission else None
    _slo_teardown(pipes)

    slo_s = slo_ms / 1e3
    out = {}
    for prio in sorted(set(prios)):
        mine = [e for e in pipes if e["prio"] == prio]
        lats = sorted(x for e in mine for x in e["lats"])
        delivered = sum(e["delivered"] for e in mine)
        within = sum(1 for x in lats if x <= slo_s)
        shed = 0
        if shed0 is not None and shed1 is not None:
            for table in ("shed", "shed_queue_full"):
                shed += shed1[table].get(prio, 0) - \
                    shed0[table].get(prio, 0)
        out[prio] = {
            "streams": len(mine),
            "offered": sum(e["frames"] for e in mine),
            "rate_per_stream": round(mine[0]["rate"], 1),
            "delivered": delivered,
            "within_slo": within,
            "goodput_fps": round(within / wall, 1),
            "shed": shed,
            "ingress_dropped": sum(e["ingress_dropped"] for e in mine),
            "p50_ms": round(lats[len(lats) // 2] * 1e3, 2)
            if lats else None,
            "p99_ms": round(
                lats[min(int(0.99 * len(lats)), len(lats) - 1)] * 1e3, 2)
            if lats else None,
        }
        out[prio]["accounted"] = (
            out[prio]["delivered"] + out[prio]["shed"]
            + out[prio]["ingress_dropped"] >= out[prio]["offered"])
    return {"wall_s": round(wall, 2),
            "offered_fps": round(sum(rates), 1),
            "max_queue_depth": max_qdepth[0], "classes": out}


def bench_openloop(out_path: str = "BENCH_slo.json"):
    """``--openloop``: open-loop (Poisson/bursty) load against the
    SLO-aware shared serving path — goodput-under-SLO curves instead of
    closed-loop peak fps.  The acceptance shape: at 2x the sustainable
    arrival rate, load-shedding protects the high-priority class (its
    goodput stays near uncontended) while low-priority frames shed
    VISIBLY (counters nonzero) and queues stay bounded."""
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import register_model

    # a service-BOUND model (chained matmuls: real per-frame compute,
    # CPU-scaled): with the full-occupancy probe below, the measured
    # sustainable rate tracks true capacity closely enough that 2x is
    # genuine overload
    import jax.numpy as jnp

    w = np.asarray(
        np.random.RandomState(7).randn(512, 512) * 0.05, np.float32)

    def _slo_model(x):
        y = x
        for _ in range(40):
            y = jnp.tanh(y @ w)
        return y

    model = register_model("bench_slo_service", _slo_model,
                           in_shapes=[(512,)], in_dtypes=np.float32)
    spec = TensorsSpec.from_shapes([(512,)], np.float32)
    prios = ["high"] * SLO_HIGH + ["low"] * (SLO_PIPES - SLO_HIGH)

    sustainable_fps, p99_closed = _slo_closed_loop(
        model, spec, max(SLO_FRAMES // 4, 32))
    # SLO with generous headroom over the (occupancy-saturated)
    # closed-loop tail: sheds should begin only when overload — not
    # machine noise — pushes the p99 past it
    slo_ms = max(20.0, 3.0 * p99_closed * 1e3)

    # traffic shape: the HIGH class is a SMALL fixed slice of measured
    # capacity (10% per stream → 20% total here) and the LOW class
    # carries the overload multiplier — the realistic serving shape
    # (the premium class is small; overload comes from bulk traffic),
    # and the one that keeps the experiment meaningful on a noisy
    # host: the closed-loop probe can overestimate true open-loop
    # capacity by 2x on a contended container, and protection can
    # shed bulk load but cannot conjure capacity for a premium class
    # that is itself oversubscribed — at 20% the high class fits even
    # through that probe error
    high_rate = 0.10 * sustainable_fps
    n_low = SLO_PIPES - SLO_HIGH

    def leg_frames(rate):
        # offer load for ~SLO_LEG_S seconds (a fixed frame count at 2x
        # would finish offering before overload can even arm the
        # controller), floored so tiny rates still mean something
        return max(64, min(int(rate * SLO_LEG_S), 16 * SLO_FRAMES))

    def leg_rates(mult):
        low_total = max(mult * sustainable_fps
                        - SLO_HIGH * high_rate, 0.0)
        return [high_rate] * SLO_HIGH + [low_total / n_low] * n_low

    # uncontended reference: ONLY the high class, at the same
    # per-stream rate it sees in every leg (well under capacity → no
    # queueing, no shedding)
    uncontended = _slo_open_loop_leg(
        model, spec, slo_ms, ["high"] * SLO_HIGH,
        [high_rate] * SLO_HIGH,
        [leg_frames(high_rate)] * SLO_HIGH, seed=11)
    curve = {}
    # the top leg (4x) anchors the acceptance fields: it stays >= 2x
    # TRUE capacity even when the closed-loop probe mis-estimates by
    # 2x in either direction on a noisy host
    overload_mult = 4.0
    for mult in (0.5, 1.0, 2.0, overload_mult):
        rates = leg_rates(mult)
        curve[str(mult)] = _slo_open_loop_leg(
            model, spec, slo_ms, prios, rates,
            [leg_frames(r) for r in rates],
            seed=17 + int(mult * 10), bursty=(mult >= 2.0))

    top = curve[str(overload_mult)]
    high_ov = top["classes"]["high"]
    high_ref = uncontended["classes"]["high"]
    low_ov = top["classes"]["low"]
    goodput_ratio = high_ov["goodput_fps"] / high_ref["goodput_fps"] \
        if high_ref["goodput_fps"] else None
    result = {
        "metric": "open-loop SLO serving: goodput under p99 SLO with "
                  f"priority-aware load shedding ({SLO_PIPES} streams, "
                  f"{SLO_HIGH} high-priority, Poisson/bursty arrivals, "
                  "CPU backend)",
        "value": round(goodput_ratio, 3) if goodput_ratio else None,
        "unit": f"x high-priority goodput at {overload_mult:g}x "
                "overload vs uncontended",
        "sustainable_fps": round(sustainable_fps, 1),
        "closed_loop_p99_ms": round(p99_closed * 1e3, 2),
        "slo_ms": round(slo_ms, 1),
        "overload_mult": overload_mult,
        "uncontended_high": uncontended,
        "curve": curve,
        "high_goodput_ratio_at_overload": round(goodput_ratio, 3)
        if goodput_ratio else None,
        "shedding_active_at_overload": low_ov["shed"] > 0,
        "all_frames_accounted": all(
            c["accounted"]
            for leg in list(curve.values()) + [uncontended]
            for c in leg["classes"].values()),
        "note": "goodput = frames completing WITHIN the SLO per "
                f"second; at {overload_mult:g}x (>= 2x) the "
                "sustainable arrival rate the admission controller "
                "sheds low-priority frames (every shed counted + "
                "bus-warned) so the high class keeps its uncontended "
                "goodput; per-stream queues stay bounded "
                "(max_queue_depth)",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


# -- host-execution profiler bench (--hostprof → BENCH_hostprof.json) ---------

HOSTPROF_STREAMS = os.environ.get("BENCH_HOSTPROF_STREAMS", "1,2,4,6")
HOSTPROF_HZ = float(os.environ.get("BENCH_HOSTPROF_HZ", "47.0"))
HOSTPROF_AB_PAIRS = int(os.environ.get("BENCH_HOSTPROF_AB_PAIRS", "3"))
#: total offered load as a fraction of the closed-loop sustainable
#: rate at the TOP ladder step — under capacity on every step, so the
#: element threads show a real run/wait mix instead of saturation
HOSTPROF_LOAD_FRAC = float(os.environ.get("BENCH_HOSTPROF_LOAD_FRAC",
                                          "0.5"))
HOSTPROF_LEG_S = float(os.environ.get("BENCH_HOSTPROF_LEG_S", "2.5"))


def _hostprof_inject(pipes, spec, rate, frames, seed):
    """Open-loop Poisson injection over PREBUILT, warmed pipes — the
    measurement window proper.  Build/compile/warmup/teardown stay
    outside it, so per-leg process-CPU deltas compare steady-state
    against steady-state (the A/B overhead signal is ~1e-2; a compile
    path inside the window would bury it).  Returns (delivered,
    dropped, sorted latencies)."""
    import queue as _pyq
    import random
    import threading

    from nnstreamer_tpu.core import Buffer

    shape = spec.tensors[0].shape
    stop = threading.Event()
    for e in pipes:
        e.update(send_ts=[0.0] * frames, lats=[], dropped=0,
                 delivered=0)

    def producer(e, idx):
        rng = random.Random(seed + idx)
        arr = np.zeros(shape, np.float32)
        t_next = time.monotonic()
        for i in range(frames):
            t_next += rng.expovariate(rate)
            delay = t_next - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            e["send_ts"][i] = time.monotonic()
            try:
                e["src"].push_buffer(Buffer.of(arr, pts=i), timeout=0)
            except _pyq.Full:
                e["dropped"] += 1

    def consumer(e):
        while not stop.is_set():
            b = e["sink"].pull(timeout=0.1)
            if b is not None:
                e["lats"].append(time.monotonic() - e["send_ts"][b.pts])
                e["delivered"] += 1

    producers = [threading.Thread(target=producer, args=(e, i))
                 for i, e in enumerate(pipes)]
    consumers = [threading.Thread(target=consumer, args=(e,))
                 for e in pipes]
    for t in consumers + producers:
        t.start()
    for t in producers:
        t.join()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if sum(e["delivered"] + e["dropped"]
               for e in pipes) >= len(pipes) * frames:
            break
        time.sleep(0.02)
    stop.set()
    for t in consumers:
        t.join()
    lats = sorted(x for e in pipes for x in e["lats"])
    return (sum(e["delivered"] for e in pipes),
            sum(e["dropped"] for e in pipes), lats)


def _hostprof_leg(model, spec, n, rate, frames, seed, prof_hz=0.0,
                  pipes=None):
    """One open-loop leg over ``n`` streams with the sampling profiler
    on (``prof_hz`` > 0) or off.  Accounts and the profiler table are
    reset after warmup, so every number is exactly this leg's
    steady-state window.  Pass prebuilt ``pipes`` to share one set
    across legs (the A/B pairs)."""
    from nnstreamer_tpu.obs import prof as _prof

    own = pipes is None
    if own:
        pipes = _slo_build_pipes(model, spec, 0.0, ["normal"] * n)
        _slo_warmup(pipes, spec)
    try:
        # delta, not reset: the element loops hold their account
        # objects from thread start, so the leg's share is
        # (after - before) per (pipeline, element)
        rows0 = {(r["pipeline"], r["element"]): r
                 for r in _prof.account_rows()}
        prof = _prof.PROFILER
        prof.clear()
        started = prof_hz > 0 and prof.configure(prof_hz).start()
        cpu0 = time.process_time()
        t0 = time.perf_counter()
        delivered, dropped, lats = _hostprof_inject(
            pipes, spec, rate, frames, seed)
        wall = time.perf_counter() - t0
        process_cpu_s = time.process_time() - cpu0
        live = {e["pipe"].name for e in pipes}
        rows = []
        for r in _prof.account_rows():
            if r["pipeline"] not in live:
                continue
            base = rows0.get((r["pipeline"], r["element"]))
            if base is not None:
                r = dict(r, **{k: round(r[k] - base[k], 6)
                               for k in ("cpu_s", "run_s", "wait_s",
                                         "iters")})
            rows.append(r)
        if started:
            prof.stop()
    finally:
        if own:
            _slo_teardown(pipes)
    samples = {f"{p}:{e}": c
               for (p, e), c in prof.element_samples().items()}
    total_cpu = sum(r["cpu_s"] for r in rows)
    run = sum(r["run_s"] for r in rows)
    wait = sum(r["wait_s"] for r in rows)
    return {
        "streams": n,
        "rate_per_stream": round(rate, 1),
        "offered": n * frames,
        "delivered": delivered,
        "ingress_dropped": dropped,
        "wall_s": round(wall, 2),
        "p50_ms": round(lats[len(lats) // 2] * 1e3, 2)
        if lats else None,
        "p99_ms": round(
            lats[min(int(0.99 * len(lats)), len(lats) - 1)] * 1e3, 2)
        if lats else None,
        "process_cpu_s": round(process_cpu_s, 4),
        # per-element host-CPU + run/wait attribution (obs/prof.py
        # accounting), joined with the sampler's per-element counts
        "elements": [dict(r, samples=samples.get(
            f"{r['pipeline']}:{r['element']}", 0)) for r in rows],
        "element_cpu_s": round(total_cpu, 4),
        # what fraction of the whole process's CPU the element loops
        # themselves account for (the rest: pool workers, XLA compute,
        # producers/consumers of the generator, the sampler)
        "attribution_coverage": round(total_cpu / process_cpu_s, 4)
        if process_cpu_s > 0 else None,
        # exactness invariant: summed per-thread CPU can NEVER exceed
        # the process-wide CPU clock (small tolerance for clock
        # granularity at leg edges)
        "attribution_exact":
            total_cpu <= process_cpu_s * 1.02 + 0.005,
        "wait_share": round(wait / (run + wait), 4)
        if run + wait > 0 else None,
        "profiler": prof.summary() if started else None,
        "sampler_self_cpu_frac":
            round(prof.self_cpu_s / process_cpu_s, 5)
            if started and process_cpu_s > 0 else None,
    }


def bench_hostprof(out_path: str = "BENCH_hostprof.json"):
    """``--hostprof``: the host-execution profiler under an open-loop
    generator swept over 1/2/4/6 streams.  Three acceptance angles:
    per-element host-CPU + run/wait attribution on every ladder step
    (element threads of an under-capacity open-loop pipeline are
    wait-dominated), profiler overhead by interleaved A/B legs
    (< 3% extra process CPU, plus the sampler's own thread-time as a
    deterministic bound), and attribution exactness (the per-element
    CPU sum never exceeds the ``time.process_time()`` delta)."""
    import statistics

    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.obs import prof as _prof

    import jax.numpy as jnp

    w = np.asarray(
        np.random.RandomState(7).randn(512, 512) * 0.05, np.float32)

    def _slo_model(x):
        y = x
        for _ in range(40):
            y = jnp.tanh(y @ w)
        return y

    model = register_model("bench_slo_service", _slo_model,
                           in_shapes=[(512,)], in_dtypes=np.float32)
    spec = TensorsSpec.from_shapes([(512,)], np.float32)

    ladder = [int(x) for x in HOSTPROF_STREAMS.split(",") if x.strip()]
    sustainable_fps, _p99 = _slo_closed_loop(
        model, spec, max(SLO_FRAMES // 8, 16))
    # constant per-stream rate: total load scales with the ladder and
    # tops out at HOSTPROF_LOAD_FRAC of measured capacity
    rate = HOSTPROF_LOAD_FRAC * sustainable_fps / max(ladder)
    frames = max(48, int(rate * HOSTPROF_LEG_S))

    steps = {}
    for i, n in enumerate(ladder):
        steps[str(n)] = _hostprof_leg(model, spec, n, rate, frames,
                                      seed=23 + i, prof_hz=HOSTPROF_HZ)

    # interleaved A/B at the middle ladder step: ONE pipe set built
    # and warmed once, then per pair one profiler-on and one
    # profiler-off injection window, order alternating within pairs;
    # overhead = median extra process-CPU fraction (CPU, not wall: an
    # open-loop leg's wall clock is pinned by the arrival schedule and
    # cannot see overhead)
    n_ab = ladder[len(ladder) // 2]
    ratios, self_fracs = [], []
    ab_pipes = _slo_build_pipes(model, spec, 0.0, ["normal"] * n_ab)
    _slo_warmup(ab_pipes, spec)
    try:
        for pair in range(HOSTPROF_AB_PAIRS):
            order = ("on", "off") if pair % 2 else ("off", "on")
            cpu = {}
            for arm in order:
                leg = _hostprof_leg(
                    model, spec, n_ab, rate, frames, seed=101 + pair,
                    prof_hz=HOSTPROF_HZ if arm == "on" else 0.0,
                    pipes=ab_pipes)
                cpu[arm] = leg["process_cpu_s"]
                if arm == "on":
                    self_fracs.append(
                        leg["sampler_self_cpu_frac"] or 0.0)
            if cpu["off"] > 0:
                ratios.append(cpu["on"] / cpu["off"] - 1.0)
    finally:
        _slo_teardown(ab_pipes)
    ab_overhead_frac = max(0.0, statistics.median(ratios)) \
        if ratios else None
    sampler_self_cpu_frac = max(self_fracs) if self_fracs else None
    overhead_ok = (ab_overhead_frac is not None
                   and ab_overhead_frac < 0.03)

    top = steps[str(max(ladder))]
    elements = top["elements"]
    result = {
        "metric": "host-execution profiler: per-element CPU + "
                  "run/wait attribution, sampler overhead "
                  f"(open-loop generator, {HOSTPROF_STREAMS} streams, "
                  f"{HOSTPROF_HZ:g} Hz, CPU backend)",
        "value": top["wait_share"],
        "unit": "wait share of element threads at "
                f"{max(ladder)} streams",
        "sustainable_fps": round(sustainable_fps, 1),
        "rate_per_stream": round(rate, 1),
        "ladder": steps,
        "frames": sum(s["delivered"] for s in steps.values()),
        "wait_share": top["wait_share"],
        # every element row of the top step carries profiler samples:
        # the deterministic-thread-name registry join works
        "registry_join_ok": bool(elements) and all(
            r["samples"] > 0 for r in elements),
        "attribution_exact": all(
            s["attribution_exact"] for s in steps.values()),
        "attribution_coverage": top["attribution_coverage"],
        "ab_pairs": HOSTPROF_AB_PAIRS,
        "ab_overhead_frac": round(ab_overhead_frac, 4)
        if ab_overhead_frac is not None else None,
        "sampler_self_cpu_frac": sampler_self_cpu_frac,
        "overhead_ok": overhead_ok,
        "profiler_errors": _prof.PROFILER.errors_total,
        "note": "wait_share = wait/(run+wait) over the per-element "
                "accounts (queue-pop wait vs chain run); "
                "attribution_exact = per-element CPU sum <= "
                "process_time delta on every ladder step; overhead by "
                "interleaved A/B process-CPU pairs (median), with the "
                "sampler's own thread-time as a deterministic bound",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


# -- chaos soak (--chaos → BENCH_chaos.json) ----------------------------------

CHAOS_FRAMES = int(os.environ.get("BENCH_CHAOS_FRAMES", "96"))
CHAOS_SEED = int(os.environ.get("BENCH_CHAOS_SEED", "20260803"))
CHAOS_OUTSTANDING = int(os.environ.get("BENCH_CHAOS_OUTSTANDING", "8"))


def _chaos_query_script(name, plan_spec, timeout_ms=800.0,
                        expect_timeouts=None, expect_reconnects=None,
                        frames=None, warmup_frames=0,
                        warmup_pace_s=0.0, pace_s=0.0):
    """One seeded fault script against a loopback-TCP tensor_query
    round-trip.  Asserts the recovery contract: EOS (or a clean bus
    error) within a wall-clock bound, and every sent frame accounted —
    delivered, timed out, or dropped at max-request, never silently
    lost.

    ``warmup_frames`` run CLEAN before the plan installs (the watch
    bench needs a pre-fault baseline for its drift rules, and an
    honest install timestamp for detection latency — returned as
    ``_fault_ts_mono``); ``warmup_pace_s`` spaces the warmup sends so
    the baseline spans enough sampler ticks.  ``plan_spec=None`` runs
    the whole script clean (the zero-false-positive leg)."""
    from nnstreamer_tpu import chaos
    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc
    from nnstreamer_tpu.filters.custom import register_custom_easy
    from nnstreamer_tpu.runtime import Pipeline
    from nnstreamer_tpu.runtime.registry import make

    frames = int(frames or CHAOS_FRAMES)
    warmup_frames = min(int(warmup_frames), frames)
    spec = TensorsSpec.parse("16:1", "float32")
    register_custom_easy("bench_chaos_x2", lambda xs: [xs[0] * 2.0],
                         in_spec=spec, out_spec=spec)
    srv = Pipeline(name=f"chaos-srv-{name}")
    qsrc = make("tensor_query_serversrc", el_name="qsrc",
                connect_type="tcp", host="127.0.0.1", port=0, id=94)
    flt = make("tensor_filter", el_name="f", framework="custom-easy",
               model="bench_chaos_x2")
    qsink = make("tensor_query_serversink", el_name="qsink", id=94)
    srv.add(qsrc, flt, qsink).link(qsrc, flt, qsink)
    srv.start()

    cli = Pipeline(name=f"chaos-cli-{name}")
    src = AppSrc(name="src", spec=spec, max_buffers=frames + 4)
    q = make("tensor_query_client", el_name="qcli", host="127.0.0.1",
             port=qsrc.port, connect_type="tcp", timeout=timeout_ms,
             max_request=CHAOS_OUTSTANDING,
             caps="other/tensors,format=static,num_tensors=1,"
                  "dimensions=16:1,types=float32")
    sink = AppSink(name="out", max_buffers=frames + 4)
    cli.add(src, q, sink).link(src, q, sink)
    cli.start()

    plan = None
    fault_ts = None
    t0 = time.perf_counter()
    sent = got = 0
    hard_deadline = time.monotonic() + 120.0

    def lost():
        return q.timeouts + q.dropped

    def pump(until, pace_s=0.0):
        nonlocal sent, got
        while got + lost() < until and \
                time.monotonic() < hard_deadline:
            while sent < until and \
                    sent - got - lost() < CHAOS_OUTSTANDING:
                src.push_buffer(Buffer.of(
                    np.full((1, 16), float(sent % 5), np.float32),
                    pts=sent))
                sent += 1
                if pace_s > 0:
                    time.sleep(pace_s)
            if sink.pull(timeout=0.25) is not None:
                got += 1

    try:
        if warmup_frames > 0:
            pump(warmup_frames, pace_s=warmup_pace_s)
        if plan_spec is not None:
            plan = chaos.install_plan(chaos.FaultPlan.parse(plan_spec))
            fault_ts = time.monotonic()
        pump(frames, pace_s=pace_s)
        # stop injecting before teardown so EOS drain isn't itself
        # chaos'd (the script proved its point; teardown must be clean)
        chaos.uninstall_plan()
        src.end_of_stream()
        eos_clean = cli.wait_eos(timeout=30, raise_on_error=False) \
            or cli.error is not None
        # late frames may still have flushed during the EOS drain
        while sink.pull(timeout=0.05) is not None:
            got += 1
        wall = time.perf_counter() - t0
    finally:
        chaos.uninstall_plan()
        cli.stop()
        srv.stop()

    counts = plan.counts() if plan is not None else {}
    metrics = q._metrics.snapshot() if q._metrics is not None else {}
    row = {
        "script": name,
        "plan": plan_spec,
        "frames": frames,
        "warmup_frames": warmup_frames,
        "sent": sent,
        "delivered": got,
        "timeouts": q.timeouts,
        "dropped_max_request": q.dropped,
        "reconnects": metrics.get("reconnects", 0),
        "bad_frames": metrics.get("bad_frames", 0),
        "injected": counts,
        "injected_total": plan.total_injected if plan is not None else 0,
        "wall_s": round(wall, 2),
        "eos_or_clean_error": bool(eos_clean),
        "hang": not eos_clean,
        "accounted": got + q.timeouts + q.dropped >= sent,
        "_fault_ts_mono": fault_ts,
    }
    if expect_timeouts is not None:
        row["expected_timeouts_seen"] = q.timeouts > 0
    if expect_reconnects is not None:
        row["expected_reconnects_seen"] = \
            metrics.get("reconnects", 0) > 0
    return row


def _chaos_invoke_script(name, plan_spec, expect_errors=False,
                         frames=None, warmup_frames=0, stat_ms=None,
                         pace_s=0.0):
    """Seeded model-path fault script against the shared serving pool:
    slow-invoke must lose nothing; fail-invoke must surface on EVERY
    sharing pipeline's bus (the _error_all / per-owner routing
    contract), with the lost windows visible as bus errors.

    ``warmup_frames`` per pipe run clean before the plan installs (see
    ``_chaos_query_script``); ``stat_ms`` tightens the filters'
    ``stat-sample-interval-ms`` so the pool latency gauge updates fast
    enough for the watch bench's drift rule to see the fault."""
    import threading

    from nnstreamer_tpu import chaos
    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.runtime import Pipeline
    from nnstreamer_tpu.runtime.events import MessageKind
    from nnstreamer_tpu.filters.jax_xla import register_model

    model = register_model("bench_chaos_pool", lambda x: x + 1.0,
                           in_shapes=[(8,)], in_dtypes=np.float32)
    spec = TensorsSpec.from_shapes([(8,)], np.float32)
    n_pipes, frames = 3, int(frames or CHAOS_FRAMES // 2)
    warmup_frames = min(int(warmup_frames), frames)
    errors = []
    pipes = []
    for i in range(n_pipes):
        p = Pipeline(name=f"chaos-pool{i}")
        src = AppSrc(name="src", spec=spec, max_buffers=frames + 4)
        qe = Queue(name="q", max_size_buffers=frames + 4)
        flt = TensorFilter(name="net", framework="jax-xla", model=model,
                           batch=4, batch_timeout_ms=2.0,
                           batch_buckets="4", share_model=True,
                           stat_sample_interval_ms=stat_ms)
        sink = AppSink(name="out", max_buffers=frames + 4)
        p.add(src, qe, flt, sink).link(src, qe, flt, sink)
        p.bus.add_watch(
            lambda m: errors.append(m) if m.kind == MessageKind.ERROR
            else None)
        p.start()
        pipes.append((p, src, flt, sink))

    t0 = time.perf_counter()
    delivered = [0] * n_pipes
    fault_ts = None

    if warmup_frames > 0:
        # clean pre-fault traffic: pool opens, executables compile,
        # the latency gauge settles to its baseline (paced so the
        # rolling latency window flushes the compile spike and a
        # watchdog's sampler sees enough clean ticks)
        for i in range(n_pipes):
            _p, src, _f, _s = pipes[i]
            for n in range(warmup_frames):
                src.push_buffer(
                    Buffer.of(np.zeros((8,), np.float32), pts=n),
                    timeout=10)
                if pace_s > 0:
                    time.sleep(pace_s)
        deadline = time.monotonic() + 60.0
        for i in range(n_pipes):
            _p, _src, _f, sink = pipes[i]
            while delivered[i] < warmup_frames and \
                    time.monotonic() < deadline:
                if sink.pull(timeout=0.25) is not None:
                    delivered[i] += 1

    plan = chaos.install_plan(chaos.FaultPlan.parse(plan_spec))
    fault_ts = time.monotonic()

    def run(i):
        _p, src, _f, sink = pipes[i]
        for n in range(warmup_frames, frames):
            src.push_buffer(Buffer.of(np.zeros((8,), np.float32), pts=n),
                            timeout=10)
            if pace_s > 0:
                time.sleep(pace_s)
        deadline = time.monotonic() + 60.0
        while delivered[i] < frames and time.monotonic() < deadline:
            if sink.pull(timeout=0.25) is not None:
                delivered[i] += 1
            elif errors and expect_errors:
                # errored windows never demux: drain what's coming and
                # account the rest to the (visible) bus errors
                if sink.pull(timeout=1.0) is None:
                    break

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_pipes)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    chaos.uninstall_plan()
    eos_clean = True
    for p, src, _f, _s in pipes:
        src.end_of_stream()
    for p, *_ in pipes:
        ok = p.wait_eos(timeout=30, raise_on_error=False)
        eos_clean = eos_clean and (ok or p.error is not None
                                   or bool(errors))
        p.stop()
    wall = time.perf_counter() - t0
    counts = plan.counts()
    total_delivered = sum(delivered)
    total_sent = n_pipes * frames
    row = {
        "script": name,
        "plan": plan_spec,
        "warmup_frames": warmup_frames,
        "sent": total_sent,
        "delivered": total_delivered,
        "bus_errors": len(errors),
        "_fault_ts_mono": fault_ts,
        "injected": counts,
        "injected_total": plan.total_injected,
        "wall_s": round(wall, 2),
        "eos_or_clean_error": bool(eos_clean),
        "hang": not eos_clean,
        # slow-invoke loses nothing; fail-invoke loses whole windows
        # but every loss maps to a bus error the apps saw
        "accounted": total_delivered >= total_sent
        if not expect_errors else
        (total_delivered < total_sent) == (len(errors) > 0),
    }
    if expect_errors:
        # how many distinct pipelines saw the error.  The poisoned
        # window errors on every owner that parked a frame in it —
        # how many owners that IS depends on window composition, so
        # the strict every-sharing-bus fan-out contract is proven by
        # the deterministic test instead
        # (tests/test_chaos.py::TestPoolFaults::
        #  test_fail_invoke_fans_out_to_every_sharing_bus)
        row["bus_error_sources"] = len({m.source for m in errors})
    return row


def bench_chaos(out_path: str = "BENCH_chaos.json"):
    """``--chaos``: the seeded fault-script soak — drop, delay,
    disconnect-flap, partition on the edge wire; slow-invoke and
    fail-invoke on the model path.  The contract under EVERY script:
    the pipelines reach EOS (or a clean bus error) within a bounded
    wall clock — zero hangs — and every frame is accounted for by a
    counter (delivered / timeout / max-request drop / bus error) —
    zero silent drops."""
    from nnstreamer_tpu.obs.metrics import REGISTRY, LinkMetrics

    LinkMetrics.clear_all()
    s = CHAOS_SEED
    scripts = [
        _chaos_query_script(
            "wire-drop", f"seed={s};drop:p=0.12,dir=tx,match=qcli",
            timeout_ms=600.0, expect_timeouts=True),
        _chaos_query_script(
            "wire-delay", f"seed={s + 1};delay:ms=20,p=0.3",
            timeout_ms=5000.0),
        _chaos_query_script(
            "disconnect-flap",
            f"seed={s + 2};disconnect:every=40,dir=tx,match=qcli",
            timeout_ms=2000.0, expect_reconnects=True),
        _chaos_query_script(
            "partition",
            f"seed={s + 3};partition:ms=400,every=50,match=qcli",
            timeout_ms=1500.0, expect_timeouts=True),
        _chaos_query_script(
            "wire-corrupt", f"seed={s + 4};corrupt:p=0.1,dir=tx",
            timeout_ms=800.0),
        _chaos_query_script(
            "wire-reorder",
            f"seed={s + 7};reorder:every=6,dir=tx,match=qcli",
            timeout_ms=800.0),
        _chaos_invoke_script(
            "slow-invoke", f"seed={s + 5};slow-invoke:ms=25,p=0.2"),
        _chaos_invoke_script(
            "fail-invoke", f"seed={s + 6};fail-invoke:every=12",
            expect_errors=True),
    ]
    for r in scripts:  # watch-bench plumbing, not a soak result
        r.pop("_fault_ts_mono", None)
    snap = REGISTRY.snapshot()
    chaos_metric = snap["metrics"].get("nns_chaos_injected_total", {})
    injected_exported = sum(
        x["value"] for x in chaos_metric.get("samples", []))
    result = {
        "metric": "chaos soak: seeded fault scripts vs the recovery "
                  "machinery (retry/backoff, failover resend-once, "
                  "timeout accounting, pool error fan-out)",
        "value": sum(1 for r in scripts if not r["hang"]
                     and r["accounted"]),
        "unit": f"of {len(scripts)} scripts with zero hangs AND zero "
                "silent drops",
        "seed": s,
        "scripts": scripts,
        "zero_hangs": all(not r["hang"] for r in scripts),
        "zero_silent_drops": all(r["accounted"] for r in scripts),
        "injected_total": sum(r["injected_total"] for r in scripts),
        "nns_chaos_injected_total_exported": injected_exported,
        "note": "each script runs under a hard wall-clock bound; "
                "'accounted' means delivered + timeouts + max-request "
                "drops (+ bus-errored windows for fail-invoke) covers "
                "every sent frame — the counters in the obs registry "
                "tell the whole story, nothing vanishes silently",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


# -- chaos-detection bench (--watch → BENCH_watch.json) -----------------------

WATCH_FRAMES = int(os.environ.get("BENCH_WATCH_FRAMES", "96"))
WATCH_INTERVAL_S = float(os.environ.get("BENCH_WATCH_INTERVAL", "0.05"))


def _watched_script(script_fn, expect_rule, *args, **kwargs):
    """Run one chaos script with a fresh watchdog attached (default
    rule pack, in-process registry) and grade the detection: did ANY
    alert fire after the fault installed, how long did it take, and —
    the honesty checks — which rules fired, whether the EXPECTED one
    did, and how many alerts fired while traffic was still clean
    (pre-fault alerts are false positives, same as the clean leg's)."""
    from nnstreamer_tpu.obs.watch import Watch, default_rules

    w = Watch(rules=default_rules(), interval_s=WATCH_INTERVAL_S)
    w.start()
    try:
        row = script_fn(*args, **kwargs)
        # settle: a counter bumped in the script's last moments still
        # needs a sampler tick to become a rate
        time.sleep(max(0.2, 4 * WATCH_INTERVAL_S))
    finally:
        w.stop()
    fault_ts = row.pop("_fault_ts_mono", None)
    alerts = [dict(ev) for ev in w.alert_log]
    row["expected_rule"] = expect_rule
    if fault_ts is None:  # the clean leg: every alert is a lie
        row["alerts_fired"] = sorted({ev["rule"] for ev in alerts})
        row["false_positives"] = len(alerts)
        row["detected"] = None
        return row
    post = [ev for ev in alerts if ev["ts"] >= fault_ts]
    row["detected"] = bool(post)
    row["detection_latency_s"] = round(post[0]["ts"] - fault_ts, 3) \
        if post else None
    row["alerts_fired"] = sorted({ev["rule"] for ev in post})
    row["expected_rule_fired"] = expect_rule in row["alerts_fired"]
    row["pre_fault_alerts"] = len(alerts) - len(post)
    return row


def bench_watch(out_path: str = "BENCH_watch.json"):
    """``--watch``: chaos detection as a regression-gated number.  The
    seeded fault scripts of the chaos soak replay with an ``nns-watch``
    watchdog attached (default rule pack, nothing tuned per script),
    each with a clean warmup so drift rules have an honest baseline and
    detection latency an honest zero point.  The contract: every fault
    class is DETECTED (an alert fires after the fault installs, 7/7),
    with recorded per-fault detection latency — and a full clean run
    fires NOTHING (zero false positives).  Detection without a false-
    positive bound is an alarm bell taped down; this bench gates both.

    The wire-reorder script is the deliberate exclusion: delivery-order
    faults change no rate/level/quantile series (frames still arrive,
    on time, intact), so they are invisible to metric-space alerting by
    construction — the chaos soak's per-frame accounting
    (BENCH_chaos.json) covers them instead."""
    from nnstreamer_tpu.obs.metrics import LinkMetrics

    LinkMetrics.clear_all()
    s = CHAOS_SEED
    frames = WATCH_FRAMES
    warmup = max(frames // 4, 12)
    pace = 0.025  # spread the warmup over >= min_samples sampler ticks
    scripts = [
        _watched_script(
            _chaos_query_script, "edge-timeouts",
            "wire-drop", f"seed={s};drop:p=0.12,dir=tx,match=qcli",
            timeout_ms=600.0, expect_timeouts=True, frames=frames,
            warmup_frames=warmup, warmup_pace_s=pace),
        # drift detection needs a baseline: the rtt rule's min_samples
        # requires ~11 windowed-p95 points before the fault, so this
        # leg warms up longer than the others (40 frames at 25ms ≈ 20
        # sampler ticks) and injects a decisively-out-of-regime delay
        _watched_script(
            _chaos_query_script, "edge-rtt-drift",
            "wire-delay", f"seed={s + 1};delay:ms=40,p=0.4",
            timeout_ms=5000.0, frames=frames,
            warmup_frames=max(warmup, 40), warmup_pace_s=pace,
            pace_s=0.015),
        _watched_script(
            _chaos_query_script, "edge-reconnect-flap",
            "disconnect-flap",
            f"seed={s + 2};disconnect:every=40,dir=tx,match=qcli",
            timeout_ms=2000.0, expect_reconnects=True, frames=frames,
            warmup_frames=warmup, warmup_pace_s=pace),
        _watched_script(
            _chaos_query_script, "edge-timeouts",
            "partition",
            f"seed={s + 3};partition:ms=400,every=50,match=qcli",
            timeout_ms=1500.0, expect_timeouts=True, frames=frames,
            warmup_frames=warmup, warmup_pace_s=pace),
        _watched_script(
            _chaos_query_script, "edge-bad-frames",
            "wire-corrupt", f"seed={s + 4};corrupt:p=0.1,dir=tx",
            timeout_ms=800.0, frames=frames, warmup_frames=warmup,
            warmup_pace_s=pace),
        # ms=80,p=0.3 (vs the soak's 25/0.2): the clean pool latency
        # mean legitimately swings 0.5-5ms under paced multi-stream
        # traffic, and a drift detector that pages inside that band is
        # a pager, not a detector — the detection target is a stall
        # decisively outside the baseline regime
        _watched_script(
            _chaos_invoke_script, "pool-latency-drift",
            "slow-invoke", f"seed={s + 5};slow-invoke:ms=80,p=0.3",
            frames=frames, warmup_frames=2 * frames // 3, stat_ms=50.0,
            pace_s=0.01),
        _watched_script(
            _chaos_invoke_script, "element-errors",
            "fail-invoke", f"seed={s + 6};fail-invoke:every=12",
            expect_errors=True, frames=frames // 2,
            warmup_frames=max(warmup // 2, 8), stat_ms=50.0),
    ]
    clean = _watched_script(
        _chaos_query_script, None, "clean", None, timeout_ms=2000.0,
        frames=frames, warmup_frames=0)
    detected = sum(1 for r in scripts if r["detected"])
    false_positives = clean["false_positives"] \
        + sum(r.get("pre_fault_alerts", 0) for r in scripts)
    latencies = [r["detection_latency_s"] for r in scripts
                 if r.get("detection_latency_s") is not None]
    result = {
        "metric": "chaos-detection coverage: seeded fault scripts the "
                  "watchdog (default rule pack) must alarm on, plus a "
                  "clean leg it must stay silent through",
        "value": detected,
        "unit": f"of {len(scripts)} fault scripts detected",
        "seed": s,
        "coverage": f"{detected}/{len(scripts)}",
        "detected_all": detected == len(scripts),
        "false_positives": false_positives,
        "clean_leg_false_positives": clean["false_positives"],
        "detection_latency_max_s": max(latencies) if latencies else None,
        "detection_latency_mean_s": round(
            sum(latencies) / len(latencies), 3) if latencies else None,
        "watch_interval_s": WATCH_INTERVAL_S,
        "scripts": scripts,
        "clean": clean,
        "excluded": {"wire-reorder": "delivery-order faults change no "
                                     "exported series (covered by the "
                                     "chaos soak's accounting)"},
        "note": "detection = any default-pack alert firing AFTER the "
                "fault installs (expected_rule_fired records whether "
                "the symptom-matched rule was among them); detection "
                "latency = fault install -> first alert; false "
                "positives = clean-leg alerts + pre-fault alerts "
                "across every script, gated at 0",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


# -- capacity / tenancy bench (--capacity → BENCH_capacity.json) --------------

#: total frame budget for the mixed-tenant trace; every arrival rate
#: scales linearly with it and the injected per-dispatch cost scales
#: inversely, so a smaller budget replays the SAME trace geometry
#: (identical leg timings, identical overload ratio) with fewer frames
CAPACITY_FRAMES = int(os.environ.get("BENCH_CAPACITY_FRAMES", "24000"))
CAPACITY_NOMINAL_FRAMES = 24000
CAPACITY_INTERVAL_S = float(
    os.environ.get("BENCH_CAPACITY_INTERVAL", "0.2"))
CAPACITY_CLEAN_S = 4.0
CAPACITY_RAMP_S = 12.0
CAPACITY_HOLD_S = 4.0
CAPACITY_HORIZON_S = 8.0


def _capacity_build_pipes(model, spec, slo_ms, tenants,
                          queue_size=64):
    """One share-model stream per tenant over ONE shared pool — the
    ``tenant=`` property is the whole point: every dispatch's
    device-seconds split across these labels by useful-frame
    occupancy (obs/tenantstat.py)."""
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.runtime import Pipeline

    pipes = []
    for i, tenant in enumerate(tenants):
        p = Pipeline(name=f"cap{i}-{tenant or 'default'}")
        src = AppSrc(name="src", spec=spec, max_buffers=queue_size)
        q = Queue(name="q", max_size_buffers=queue_size)
        flt = TensorFilter(name="net", framework="jax-xla",
                           model=model, batch=SLO_BATCH,
                           batch_timeout_ms=SLO_TIMEOUT_MS,
                           batch_buckets=str(SLO_BATCH),
                           share_model=True, slo_ms=slo_ms,
                           # deep enough that ONE stream's parked depth
                           # can cross the slo_ms-equivalent depth at
                           # any scale (slo_depth = slo_ms/1e3 *
                           # capacity_fps; the default 16x batch caps
                           # below it at scale 1, so the admission
                           # controller would idle and the reactive leg
                           # of the lead gate would never arm)
                           queue_limit=64 * SLO_BATCH,
                           tenant=tenant, stat_sample_interval_ms=20.0)
        sink = AppSink(name="out", max_buffers=4096)
        p.add(src, q, flt, sink).link(src, q, flt, sink)
        p.start()
        pipes.append({"pipe": p, "src": src, "q": q, "flt": flt,
                      "sink": sink, "tenant": tenant or "default"})
    return pipes


def bench_capacity(out_path: str = "BENCH_capacity.json"):
    """``--capacity``: the predictive-alerting + tenancy gate — a
    diurnal-plus-burst mixed-tenant trace (open loop) against the
    shared serving path with a watchdog running a ``forecast`` rule
    (obs/forecast.py) next to the reactive ``slo_burn`` pack.

    The trace: three tenants (alpha/beta/default) share one pool at a
    flat healthy rate (the clean leg), then tenant alpha's arrivals
    ramp linearly to ~2.5x the pool's capacity and hold (the surge
    leg).  Capacity is pinned, machine-independently, by a seeded
    chaos ``slow-invoke`` per-dispatch cost — the sleep dominates the
    trivial model, so capacity = batch / cost by construction and the
    overload geometry replays identically everywhere.

    The contracts, each a top-level gated scalar:

    - EXACTLY zero forecast firings on the clean leg (a predictor
      that cries wolf on flat traffic is worse than none);
    - on the surge leg the forecast rule fires >= 2 s BEFORE the
      reactive slo-burn (else prediction bought nothing);
    - tenant attribution is EXACT: the sum over tenants of attributed
      device-ns equals the pool's own device-ns — same integer
      nanoseconds, not approximately (obs/tenantstat.py);
    - every tenant gets a $/kframe figure derived from the attributed
      device-seconds at the obs/hwspec.py chip-hour price."""
    import threading

    from nnstreamer_tpu import chaos
    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.obs.forecast import FORECASTS
    from nnstreamer_tpu.obs.tenantstat import TENANT_STATS
    from nnstreamer_tpu.obs.watch import AlertRule, Watch

    scale = min(max(CAPACITY_FRAMES / CAPACITY_NOMINAL_FRAMES, 0.15),
                4.0)
    # capacity = SLO_BATCH / cost; at scale 1: 8 / 8 ms = 1000 fps
    cost_ms = max(2, round(8.0 / scale))
    capacity_fps = SLO_BATCH / (cost_ms / 1e3)
    rates = {"alpha": 0.075, "beta": 0.05,
             "default": 0.025}  # clean, as fractions of capacity
    clean_fps = {t: f * capacity_fps for t, f in rates.items()}
    peak_total = 2.5 * capacity_fps
    # the surge is alpha's alone — beta/default stay flat, so the
    # per-tenant bill pins the overload on the tenant that caused it
    alpha_peak = peak_total - clean_fps["beta"] - clean_fps["default"]
    # near capacity, well above the clean plateau: the forecast
    # must predict the crossing while the level is still clearly
    # below it (once the level itself is over, the crossing is
    # reactive territory and the forecast stands down)
    thresh_fps = 0.7 * capacity_fps
    slo_ms = 300.0

    model = register_model("bench_capacity_service",
                           lambda x: x - 1.0, in_shapes=[(8,)],
                           in_dtypes=np.float32)
    spec = TensorsSpec.from_shapes([(8,)], np.float32)

    TENANT_STATS.reset()
    FORECASTS.reset()
    chaos.install_plan(chaos.FaultPlan.parse(
        f"seed={CHAOS_SEED + 8};slow-invoke:ms={cost_ms},p=1,"
        f"match=pool:"))
    pipes = _capacity_build_pipes(model, spec, slo_ms,
                                  ["alpha", "beta", "default"])
    rules = [
        # for=0.5: a trend fit over the first handful of points can be
        # confidently wrong (4 nearly-collinear noisy points have ~no
        # MAD); the sustain clause is the designed guard against it
        AlertRule(name="capacity-surge", kind="forecast",
                  metric="nns_pool_frames_total", op=">=",
                  value=thresh_fps, horizon_s=CAPACITY_HORIZON_S,
                  for_s=0.5),
        # reactive comparators at their honest best (short windows,
        # not production sizes): the latency burn — which the shed
        # ramp DEFENDS, so under graded overload it may stay quiet
        # while attainment holds — and the shed-vs-submitted error
        # budget, which is where a working admission controller
        # makes overload visible.  Lead is graded against whichever
        # reactive signal fires FIRST.
        AlertRule(name="slo-burn", kind="slo_burn",
                  metric="nns_admission_latency_seconds",
                  fast_s=1.0, slow_s=4.0, budget=0.02, burn=2.0,
                  severity="critical"),
        AlertRule(name="shed-burn", kind="slo_burn",
                  metric="nns_admission_shed_total",
                  per="nns_admission_submitted_total",
                  fast_s=1.0, slow_s=4.0, budget=0.05, burn=2.0,
                  severity="critical"),
    ]
    stop = threading.Event()
    quiesce = threading.Event()

    def alpha_rate(t):  # t: seconds since the surge leg began
        if t < 0:
            return clean_fps["alpha"]
        ramp = min(t / CAPACITY_RAMP_S, 1.0)
        return clean_fps["alpha"] + ramp * (alpha_peak
                                            - clean_fps["alpha"])

    try:
        _slo_warmup(pipes, spec)
        arr = np.zeros((8,), np.float32)
        t0 = time.monotonic()
        surge_at = [None]  # monotonic ts the surge leg begins

        def producer(e):
            # open loop on an absolute schedule: each wake pushes the
            # deficit between the integrated arrival curve and what
            # was already offered — Python sleep jitter becomes a
            # burst of back-to-back arrivals, not a deflated rate
            tenant, pushed, dropped, acc = e["tenant"], 0, 0, 0.0
            last = time.monotonic()
            while not stop.is_set():
                time.sleep(0.005)
                now = time.monotonic()
                if quiesce.is_set():
                    break
                if tenant == "alpha" and surge_at[0] is not None:
                    r = alpha_rate(now - surge_at[0])
                else:
                    r = clean_fps[tenant]
                acc += (now - last) * r
                last = now
                n = min(int(acc), 64)
                acc -= n
                for _ in range(n):
                    try:
                        e["src"].push_buffer(
                            Buffer.of(arr, pts=pushed), timeout=0)
                        pushed += 1
                    except Exception:  # noqa: BLE001 - full ingress
                        dropped += 1  # queue = a visible drop
                e["offered"] = pushed + dropped
                e["pushed"] = pushed
                e["dropped"] = dropped

        def consumer(e):
            got = 0
            while not stop.is_set():
                if e["sink"].pull(timeout=0.05) is not None:
                    got += 1
                    e["delivered"] = got

        for e in pipes:
            e.update(offered=0, pushed=0, dropped=0, delivered=0)
        threads = [threading.Thread(target=producer, args=(e,),
                                    daemon=True) for e in pipes] + \
                  [threading.Thread(target=consumer, args=(e,),
                                    daemon=True) for e in pipes]
        for t in threads:
            t.start()
        time.sleep(1.0)  # settle: the store's first points must
        # already sit on the clean plateau, not the spin-up edge
        w = Watch(rules=rules, interval_s=CAPACITY_INTERVAL_S)
        clean_end = time.monotonic() + CAPACITY_CLEAN_S
        surge_end = clean_end + CAPACITY_RAMP_S + CAPACITY_HOLD_S
        while time.monotonic() < surge_end:
            tick = time.monotonic()
            if tick >= clean_end and surge_at[0] is None:
                surge_at[0] = tick
            w.sample_once()
            time.sleep(max(
                0.0, CAPACITY_INTERVAL_S - (time.monotonic() - tick)))
        quiesce.set()
        time.sleep(0.3)
        adm = pipes[0]["flt"].pool.admission
        shed_total = adm.total_shed if adm is not None else 0
        _slo_teardown(pipes)
        time.sleep(0.2)
    finally:
        stop.set()
        chaos.uninstall_plan()

    alerts = [dict(ev) for ev in w.alert_log]
    surge_ts = surge_at[0]
    clean_fc = [ev for ev in alerts if ev["rule"] == "capacity-surge"
                and ev["ts"] < surge_ts]
    fc = [ev for ev in alerts if ev["rule"] == "capacity-surge"
          and ev["ts"] >= surge_ts]
    reactive = sorted((ev for ev in alerts
                       if ev["rule"] in ("slo-burn", "shed-burn")),
                      key=lambda ev: ev["ts"])
    lead = round(reactive[0]["ts"] - fc[0]["ts"], 3) \
        if fc and reactive else None

    tenants = {r["tenant"]: r for r in TENANT_STATS.snapshot()}
    pool_label = next(iter(TENANT_STATS.snapshot()), {}).get("pool", "")
    tenant_ns, pool_ns = TENANT_STATS.exactness(pool_label)
    dpk = {t: round(r["dollars"] / r["frames"] * 1e3, 6)
           for t, r in tenants.items() if r["frames"]}
    cap_rows = FORECASTS.snapshot()["capacity"]
    headroom = cap_rows[0]["headroom"] if cap_rows else None

    offered = sum(e["offered"] for e in pipes)
    delivered = sum(e["delivered"] for e in pipes)
    result = {
        "metric": "predictive capacity alerting + per-tenant cost "
                  "attribution on a diurnal+burst mixed-tenant trace "
                  "(3 tenants, one shared pool, open loop, pinned "
                  "capacity via seeded slow-invoke)",
        "value": lead,
        "unit": "s of forecast lead over the reactive slo-burn",
        "scale": round(scale, 3),
        "capacity_fps": round(capacity_fps, 1),
        "clean_fps": round(sum(clean_fps.values()), 1),
        "peak_fps": round(peak_total, 1),
        "forecast_threshold_fps": round(thresh_fps, 1),
        "horizon_s": CAPACITY_HORIZON_S,
        "slo_ms": slo_ms,
        "offered": offered,
        "delivered": delivered,
        "shed": shed_total,
        "ingress_dropped": sum(e["dropped"] for e in pipes),
        "forecast_fired": bool(fc),
        "reactive_fired": bool(reactive),
        "reactive_rule": reactive[0]["rule"] if reactive else None,
        "forecast_lead_s": lead,
        "lead_ok": lead is not None and lead >= 2.0,
        "forecast_false_positives": len(clean_fc),
        "clean_leg_alerts": sum(1 for ev in alerts
                                if ev["ts"] < surge_ts),
        "tenant_device_ns": tenant_ns,
        "pool_device_ns": pool_ns,
        "tenant_sum_exact": tenant_ns == pool_ns and pool_ns > 0,
        "tenants_billed": len(tenants),
        "dollars_total": round(sum(r["dollars"]
                                   for r in tenants.values()), 6),
        "dollars_per_kframe_alpha": dpk.get("alpha"),
        "dollars_per_kframe_beta": dpk.get("beta"),
        "dollars_per_kframe_default": dpk.get("default"),
        "slo_attainment_alpha":
            round(tenants["alpha"]["slo_attainment"], 4)
            if tenants.get("alpha", {}).get("slo_attainment")
            is not None else None,
        "headroom_at_peak": round(headroom, 3)
        if headroom is not None else None,
        "tenants": list(tenants.values()),
        "note": "lead = first reactive burn firing (slo-burn or "
                "shed-burn, whichever first) - first forecast "
                "firing on the surge leg, gated >= 2 s; "
                "forecast_false_positives counts capacity-surge "
                "firings on the clean leg, gated EXACT 0; "
                "tenant_sum_exact compares integer nanoseconds "
                "(same clock reads as nns_invoke_device_seconds), "
                "gated EXACT; $/kframe = attributed device-seconds "
                "x chip-hour price (NNS_TPU_CHIP_HOUR_USD)",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


# -- closed-loop MTTR bench (--mttr → BENCH_mttr.json) ------------------------

MTTR_INTERVAL_S = float(os.environ.get("BENCH_MTTR_INTERVAL", "0.05"))
MTTR_DETECT_DEADLINE_S = float(
    os.environ.get("BENCH_MTTR_DETECT_DEADLINE", "20"))
MTTR_RECOVER_DEADLINE_S = float(
    os.environ.get("BENCH_MTTR_RECOVER_DEADLINE", "30"))


class _MttrPoolRig:
    """N share-model pipelines + paced open-loop pumps — the serving
    fixture every pool-side MTTR script steers.  Pumps push frames at
    a fixed pace and drain their sinks aggressively (a full sink would
    wedge the pool's demux), from warmup through fault and recovery —
    open-loop traffic does not pause because the server is sick."""

    def __init__(self, name, model_fn, n_pipes=3, batch=8,
                 timeout_ms=3.0, slo_ms=0.0, priorities=None,
                 pace_s=0.002, burst=1, canary="",
                 stat_sample_interval_ms=50.0):
        import threading

        from nnstreamer_tpu.core import Buffer, TensorsSpec
        from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
        from nnstreamer_tpu.elements.filter import TensorFilter
        from nnstreamer_tpu.filters.jax_xla import register_model
        from nnstreamer_tpu.runtime import Pipeline

        self._threading = threading
        self._Buffer = Buffer
        self.model = register_model(f"mttr_{name}", model_fn,
                                    in_shapes=[(8,)],
                                    in_dtypes=np.float32)
        spec = TensorsSpec.from_shapes([(8,)], np.float32)
        self.pace_s = pace_s
        # frames pushed back-to-back per pump wake: bursty arrivals
        # keep window occupancy high THROUGH scheduler lulls on a
        # loaded runner, so occupancy-shaped rule signals (dispatch/
        # frame ratios) reflect the window config, not pump timing
        self.burst = int(burst)
        self.delivered = [0] * n_pipes
        # exact pushed-frame accounting (the lifecycle bench's
        # dropped-frames-==-0 gate is pushed - delivered after drain)
        self.pushed = [0] * n_pipes
        # last output scalar each pump saw — cheap probe that a hot
        # swap actually flipped the serving function
        self.last_value = [None] * n_pipes
        self.pipes = []
        for i in range(n_pipes):
            prio = (priorities[i] if priorities else "normal")
            p = Pipeline(name=f"mttr-{name}-{i}")
            src = AppSrc(name="src", spec=spec, max_buffers=256)
            q = Queue(name="q", max_size_buffers=256)
            flt = TensorFilter(
                name="net", framework="jax-xla", model=self.model,
                batch=batch, batch_timeout_ms=timeout_ms,
                batch_buckets=str(batch), share_model=True,
                slo_ms=slo_ms, priority=prio, canary=canary,
                stat_sample_interval_ms=stat_sample_interval_ms)
            sink = AppSink(name="out", max_buffers=512)
            p.add(src, q, flt, sink).link(src, q, flt, sink)
            self.pipes.append((p, src, flt, sink))
        self._stop = threading.Event()
        self._quiesce = threading.Event()  # stop pushing, keep draining
        self._threads = []

    @property
    def entry(self):
        return self.pipes[0][2].pool

    def start(self):
        for p, *_ in self.pipes:
            p.start()
        for i, (_p, src, _f, sink) in enumerate(self.pipes):
            t = self._threading.Thread(
                target=self._pump, args=(i, src, sink), daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def _pump(self, i, src, sink):
        n = 0
        frame = np.zeros((8,), np.float32)
        while not self._stop.is_set():
            for _ in range(self.burst):
                if self._quiesce.is_set():
                    break
                try:
                    src.push_buffer(self._Buffer.of(frame, pts=n),
                                    timeout=0.5)
                    n += 1
                    self.pushed[i] += 1
                except Exception:  # noqa: BLE001 - a full source
                    # under a stalled window is backpressure, not a
                    # bench bug; keep draining and retry
                    break
            while True:
                buf = sink.pull(timeout=0)
                if buf is None:
                    break
                self.delivered[i] += 1
                try:
                    self.last_value[i] = float(
                        np.asarray(buf.tensors[0].np()).ravel()[0])
                except Exception:  # noqa: BLE001 - probe only
                    pass
            time.sleep(self.pace_s)

    def quiesce(self, timeout_s: float = 10.0) -> bool:
        """Stop pushing, keep draining, wait until every pushed frame
        reached a sink — the exact-frame-accounting gate (dropped == 0)
        measures the SWAP, not shutdown truncation of in-flight
        frames.  The window's deadline flush drains the tail."""
        self._quiesce.set()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if sum(self.delivered) >= sum(self.pushed):
                return True
            time.sleep(0.02)
        return False

    def stop(self):
        # pipes first: their stop-path flush pushes every parked frame
        # to the sinks, and the pumps must still be DRAINING those
        # sinks — joining the pumps first would wedge the flush of a
        # backed-up window against a full sink
        for p, *_ in self.pipes:
            p.stop()
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)
        # final drain: the stop-path flush may land frames after a
        # pump's last pull — the exact-accounting gate needs them
        for i, (_p, _src, _f, sink) in enumerate(self.pipes):
            while sink.pull(timeout=0) is not None:
                self.delivered[i] += 1


def _actuate_retry(act, value, attempts=8, wait_s=0.3):
    """Seed a fault through an actuator, riding out its cooldown (a
    controller that legitimately steered the knob moments earlier must
    not crash the bench — the pre-fault-alert gate still reports that
    run honestly)."""
    from nnstreamer_tpu.runtime.actuators import CooldownActive

    for i in range(attempts):
        try:
            return act.actuate(value)
        except CooldownActive:
            if i == attempts - 1:
                raise
            time.sleep(wait_s)


def _mttr_run(name, expect_rule, rules, playbooks, fault_fn,
              recovered_fn, warmup_s=1.0, teardown_fn=None):
    """One closed-loop script: clean warmup → seeded fault → alert →
    controller actuation → recovered SLO.  Per-phase timestamps come
    from polling the SAME state the operator tools read (the watch's
    alert log / rule states, the controller's audit ring)."""
    from nnstreamer_tpu.obs.control import Controller
    from nnstreamer_tpu.obs.watch import Watch

    w = Watch(rules=rules, interval_s=MTTR_INTERVAL_S)
    ctl = Controller(playbooks=playbooks, watch=w,
                     interval_s=MTTR_INTERVAL_S)
    w.start()
    ctl.start()
    row = {"script": name, "expected_rule": expect_rule,
           "detected": False, "actuated": False, "recovered": False,
           "detect_s": None, "actuate_s": None, "mttr_s": None,
           "pre_fault_alerts": 0, "actions": 0}
    try:
        time.sleep(warmup_s)
        row["pre_fault_alerts"] = len(w.alert_log)
        t_fault = time.monotonic()
        fault_fn()

        def _firing(rule):
            return any(a["rule"] == rule and a["firing"]
                       for a in w.alerts())

        deadline = t_fault + MTTR_DETECT_DEADLINE_S
        while time.monotonic() < deadline:
            post = [ev for ev in w.alert_log if ev["ts"] >= t_fault]
            if post:
                row["detected"] = True
                row["detect_s"] = round(post[0]["ts"] - t_fault, 3)
                row["rules_fired"] = sorted({ev["rule"]
                                             for ev in post})
                break
            time.sleep(MTTR_INTERVAL_S / 2)
        while time.monotonic() < deadline:
            acted = [d for d in ctl.audit
                     if d["outcome"] in ("applied", "reverted")
                     and d["ts"] >= t_fault]
            if acted:
                row["actuated"] = True
                row["actuate_s"] = round(acted[0]["ts"] - t_fault, 3)
                break
            time.sleep(MTTR_INTERVAL_S / 2)
        # recovery must HOLD (0.5 s), not flicker: an oscillating
        # remediation that clears the symptom for one poll has not
        # recovered the SLO.  MTTR is stamped at the START of the
        # sustained-good window — the moment service was back.
        deadline = t_fault + MTTR_RECOVER_DEADLINE_S
        good_since = None
        while time.monotonic() < deadline:
            ok = row["detected"] and row["actuated"] \
                and not _firing(expect_rule) and recovered_fn()
            now = time.monotonic()
            if not ok:
                good_since = None
            elif good_since is None:
                good_since = now
            elif now - good_since >= 0.5:
                row["recovered"] = True
                row["mttr_s"] = round(good_since - t_fault, 3)
                break
            time.sleep(MTTR_INTERVAL_S / 2)
    finally:
        if teardown_fn is not None:
            teardown_fn()
        ctl.stop()
        w.stop()
    row["actions"] = ctl.actions_total
    row["audit"] = [
        {k: d.get(k) for k in ("playbook", "actuator", "target",
                               "applied", "prior", "outcome")}
        for d in ctl.audit]
    row["expected_rule_fired"] = expect_rule in row.get(
        "rules_fired", [])
    return row, ctl


def _mttr_window_stall():
    """Fault: the cross-stream window's coalescing is PAUSED (a
    misconfigured/steered-wrong window — injected through the same
    actuator seam the controller steers).  Frames park, nothing
    dispatches, nns_pool_pending climbs.  Remediation: the pool-stall
    rule trips the resume-coalescing playbook."""
    from nnstreamer_tpu.obs.watch import AlertRule
    from nnstreamer_tpu.obs.control import Playbook

    rig = _MttrPoolRig("stall", lambda x: x + 1.0, n_pipes=2,
                       batch=8, pace_s=0.002).start()
    time.sleep(1.0)  # XLA compile + first windows settle BEFORE the
    # watchdog attaches: its baseline must be steady state
    rules = [AlertRule(name="pool-stall", kind="threshold",
                       metric="nns_pool_pending", op=">=", value=16.0,
                       for_s=0.1, severity="critical")]
    playbooks = [Playbook(name="resume-coalescing", rule="pool-stall",
                          kind="pool", actuator="coalescing",
                          action="set", value=1.0, cooldown_s=0.5)]

    def fault():
        _actuate_retry(rig.entry.actuators()["coalescing"], 0.0)

    def recovered():
        b = rig.entry.batcher
        return b is not None and b.pending < 8 and not b.paused

    try:
        row, _ctl = _mttr_run("window-stall", "pool-stall", rules,
                              playbooks, fault, recovered)
    finally:
        rig.stop()
    return row


def _mttr_window_collapse():
    """Fault: the window collapses to 1 frame/dispatch on a device
    with a real per-dispatch cost (seeded slow-invoke shim, ms=2 on
    every window) — dispatch rate explodes past service capacity.
    Remediation: the dispatch-amplification rule (dispatches ≈ frames)
    reverts the max-batch knob to its pre-steering width."""
    from nnstreamer_tpu import chaos
    from nnstreamer_tpu.obs.watch import AlertRule
    from nnstreamer_tpu.obs.control import Playbook

    rig = _MttrPoolRig("collapse", lambda x: x * 2.0, n_pipes=3,
                       batch=8, pace_s=0.008, burst=4).start()
    chaos.install_plan(chaos.FaultPlan.parse(
        f"seed={CHAOS_SEED};slow-invoke:ms=2,p=1,match=pool:"))
    time.sleep(1.0)  # compile + shimmed service time settle pre-watch
    rules = [AlertRule(name="dispatch-amplification",
                       kind="threshold",
                       metric="nns_pool_dispatches_total",
                       per="nns_pool_frames_total", op=">=",
                       value=0.7, for_s=0.25, severity="warning")]
    playbooks = [Playbook(name="widen-window",
                          rule="dispatch-amplification", kind="pool",
                          actuator="max-batch", action="revert",
                          cooldown_s=0.5)]

    def fault():
        _actuate_retry(rig.entry.actuators()["max-batch"], 1.0)

    def recovered():
        b = rig.entry.batcher
        return b is not None and b.max_batch == 8 and b.pending < 32

    try:
        row, _ctl = _mttr_run("window-collapse",
                              "dispatch-amplification", rules,
                              playbooks, fault, recovered,
                              warmup_s=1.2)
    finally:
        rig.stop()
        chaos.uninstall_plan()
    return row


def _mttr_slo_burn():
    """Fault: the window is mis-tuned NARROW (max-batch 16→1) while the
    device pays a real per-dispatch cost — service capacity drops
    under the open-loop arrival rate, backlog queues, and the
    admission latency histogram burns through the pool's 250 ms SLO
    (wide enough that a shared runner's scheduler stalls never graze
    it — with a tighter SLO a legitimate 150 ms CPU stall IS a mini
    burn, and the pre-fault-alert gate demands a decisively quiet
    baseline; the fault's latencies are SECONDS, so detection stays
    decisive).
    Remediation: the slo-burn rule steps the window back open (MFU
    headroom is exactly what a wider window converts into capacity)
    and tightens the shed ramp — sticky, by design: reverting the
    ramp the instant the burn clears re-admits the traffic that
    burned it (remediation flap)."""
    from nnstreamer_tpu import chaos
    from nnstreamer_tpu.obs.watch import AlertRule
    from nnstreamer_tpu.obs.control import Playbook

    # a window of 16 on a device paying a real ~8 ms per-dispatch cost:
    # wide window → ~1700 fps capacity >> the ~1000 fps arrivals;
    # collapsed to 1 → ~110 fps, under even the HIGH class's share, so
    # the graded shed ramp cannot save the SLO and the budget burns —
    # exactly the regime where only re-widening the window helps
    rig = _MttrPoolRig("sloburn", lambda x: x - 1.0, n_pipes=3,
                       batch=16, slo_ms=250.0,
                       priorities=["high", "low", "low"],
                       pace_s=0.012, burst=4).start()
    chaos.install_plan(chaos.FaultPlan.parse(
        f"seed={CHAOS_SEED + 1};slow-invoke:ms=8,p=1,match=pool:"))
    time.sleep(1.5)  # compile spike must age out of the burn windows
    # BEFORE the watchdog attaches (honest zero-false-positive leg)
    rules = [AlertRule(name="slo-burn", kind="slo_burn",
                       metric="nns_admission_latency_seconds",
                       fast_s=0.4, slow_s=1.6, budget=0.05, burn=2.0,
                       severity="critical")]
    playbooks = [
        Playbook(name="widen-window", rule="slo-burn", kind="pool",
                 actuator="max-batch", action="step", value=15.0,
                 cooldown_s=1.0),
        # deliberately STICKY (no on_resolve revert): reverting a shed
        # ramp the instant the burn clears re-admits the very traffic
        # that burned it — a textbook remediation flap.  The graded
        # ramp at 0.5 is self-stabilizing; the revert-on-resolve
        # behavior is covered by tests/test_control.py instead.
        Playbook(name="tighten-admission", rule="slo-burn",
                 kind="pool", actuator="ramp-start", action="set",
                 value=0.5, cooldown_s=1.0),
    ]

    def fault():
        _actuate_retry(rig.entry.actuators()["max-batch"], 1.0)

    def recovered():
        adm = rig.entry.admission
        b = rig.entry.batcher
        return adm is not None and b is not None \
            and b.max_batch == 16 and adm.p99_s < 0.25

    try:
        row, _ctl = _mttr_run("slo-burn-overload", "slo-burn", rules,
                              playbooks, fault, recovered,
                              warmup_s=1.5)
    finally:
        rig.stop()
        chaos.uninstall_plan()
    return row


def _mttr_breaker_stuck():
    """Fault: the publisher dies; the subscriber's re-dial loop fails
    until its circuit breaker opens — with a production-grade LONG
    open window (8 s), the link would sit dark long after the
    publisher returns (1 s).  Remediation: the breaker-open rule
    forces the half-open probe (re-dial NOW), kicking the sleeping
    reconnect loop — recovery lands in ~1-2 s instead of 8+."""
    import threading

    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc
    from nnstreamer_tpu.obs.watch import AlertRule
    from nnstreamer_tpu.obs.control import Playbook
    from nnstreamer_tpu.runtime import Pipeline
    from nnstreamer_tpu.runtime.registry import make

    spec = TensorsSpec.parse("4:1", "float32")

    def publisher(port):
        p = Pipeline(name="mttr-pub")
        src = AppSrc(name="src", spec=spec, max_buffers=64)
        sink = make("edgesink", el_name="esink", host="127.0.0.1",
                    port=port, topic="mttr")
        p.add(src, sink).link(src, sink)
        p.start()
        return p, src, sink

    ppub, psrc, esink = publisher(0)
    port = esink.port
    psub = Pipeline(name="mttr-sub")
    esrc = make("edgesrc", el_name="esrc", dest_host="127.0.0.1",
                dest_port=port, topic="mttr",
                caps="other/tensors,format=static,num_tensors=1,"
                     "dimensions=4:1,types=float32",
                reconnect_timeout_s=60.0)
    outs = AppSink(name="out", max_buffers=256)
    psub.add(esrc, outs).link(esrc, outs)
    psub.start()
    # the production-shaped policy this script is ABOUT: fail fast to
    # the breaker, then a long open window (the cost the controller's
    # forced probe eliminates)
    esrc._retry.base_s = 0.05
    esrc._retry.max_s = 0.2
    esrc._retry.fail_threshold = 3
    esrc._retry.open_s = 8.0

    state = {"stop": False, "pub": (ppub, psrc), "sent": 0, "got": 0}
    lock = threading.Lock()

    def pump():
        n = 0
        while not state["stop"]:
            with lock:
                _p, src = state["pub"]
            try:
                src.push_buffer(Buffer.of(
                    np.full((1, 4), 1.0, np.float32), pts=n),
                    timeout=0.2)
                state["sent"] += 1
                n += 1
            except Exception:  # noqa: BLE001 - publisher down mid-
                # fault: open-loop traffic keeps trying
                pass
            while outs.pull(timeout=0) is not None:
                state["got"] += 1
            time.sleep(0.005)

    pump_t = threading.Thread(target=pump, daemon=True)
    pump_t.start()

    rules = [AlertRule(name="breaker-open", kind="threshold",
                       metric="nns_edge_breaker_state", op=">=",
                       value="open", severity="critical")]
    playbooks = [Playbook(name="redial-link", rule="breaker-open",
                          kind="link", actuator="breaker",
                          action="set", value=1.0, cooldown_s=0.3)]

    def fault():
        state["got_at_fault"] = state["got"]
        with lock:
            p, _src = state["pub"]
        p.stop()

        def _restart():
            time.sleep(1.0)
            with lock:
                state["pub"] = publisher(port)[:2]

        threading.Thread(target=_restart, daemon=True).start()

    def recovered():
        # breaker closed AND fresh frames delivered since the fault —
        # a closed breaker on a dead data path is not recovery
        return esrc._retry.state == 0 \
            and state["got"] > state.get("got_at_fault", 0)

    try:
        row, _ctl = _mttr_run("breaker-stuck-open", "breaker-open",
                              rules, playbooks, fault, recovered,
                              warmup_s=1.0)
    finally:
        # subscriber first while the pump still drains its sink (a
        # full sink would block the edgesrc chain against stop)
        psub.stop()
        state["stop"] = True
        pump_t.join(timeout=5)
        with lock:
            state["pub"][0].stop()
    row["open_window_s"] = 8.0
    return row


def _control_counter_total():
    from nnstreamer_tpu.obs.metrics import REGISTRY

    fam = REGISTRY.collect().get("nns_control_actions_total", {})
    return sum(s["value"] for s in fam.get("samples", []))


def _controller_inert_check() -> bool:
    """The whole controller must be strictly inert under
    NNS_TPU_OBS_DISABLE: no thread, no actuation, no audit, no
    registration (the PR-8 kill-switch contract, extended to the
    actuation plane)."""
    from nnstreamer_tpu.obs import hooks as _hooks
    from nnstreamer_tpu.obs.control import Controller, control_table

    before = control_table()["controllers"]
    saved = _hooks.DISABLED
    _hooks.DISABLED = True
    try:
        ctl = Controller()
        inert = (ctl.start() is False and ctl.tick() == []
                 and ctl.apply("pool", "*", "window-ms",
                               value=5.0) == []
                 and ctl.actions_total == 0
                 and control_table()["controllers"] == before)
    finally:
        _hooks.DISABLED = saved
    return inert


def bench_mttr(out_path: str = "BENCH_mttr.json"):
    """``--mttr``: closed-loop recovery as a regression-gated number.
    Four seeded fault scripts run end to end — fault → watch alert →
    controller actuation (through the bounded actuator API) →
    recovered SLO — with per-fault MTTR (fault install → rule
    resolved + SLO predicate true) recorded, pre-fault alerts gated
    at zero, and the decision accounting cross-checked: every
    actuation taken anywhere in the bench must appear in BOTH the
    exported ``nns_control_actions_total`` counter and the decision
    audit ring, with equal counts."""
    from nnstreamer_tpu.obs.metrics import LinkMetrics

    LinkMetrics.clear_all()
    counter_before = _control_counter_total()
    scripts = [
        _mttr_window_stall(),
        _mttr_window_collapse(),
        _mttr_slo_burn(),
        _mttr_breaker_stuck(),
    ]
    counter_delta = _control_counter_total() - counter_before
    audit_total = sum(r["actions"] for r in scripts)
    recovered = sum(1 for r in scripts if r["recovered"])
    mttrs = [r["mttr_s"] for r in scripts if r["mttr_s"] is not None]
    result = {
        "metric": "closed-loop MTTR: seeded fault scripts the "
                  "controller must detect, actuate on and recover "
                  "(fault install -> alert resolved + SLO predicate)",
        "value": recovered,
        "unit": f"of {len(scripts)} fault scripts recovered",
        "coverage": f"{recovered}/{len(scripts)}",
        "recovered_all": recovered == len(scripts),
        "detected_all": all(r["detected"] for r in scripts),
        "actuated_all": all(r["actuated"] for r in scripts),
        "pre_fault_alerts": sum(r["pre_fault_alerts"]
                                for r in scripts),
        "mttr_max_s": max(mttrs) if mttrs else None,
        "mttr_mean_s": round(sum(mttrs) / len(mttrs), 3)
        if mttrs else None,
        "control_interval_s": MTTR_INTERVAL_S,
        "actions_audit_total": audit_total,
        "actions_counter_total": counter_delta,
        "audit_equals_counter": audit_total == counter_delta,
        "controller_inert_under_obs_disable":
            _controller_inert_check(),
        "scripts": scripts,
        "note": "MTTR = fault install -> expected rule RESOLVED and "
                "the script's recovery predicate true (pending "
                "drained / window restored / p99 under SLO / breaker "
                "closed with frames flowing); every decision — "
                "applied, clamped, rejected — lands in both the "
                "audit ring and nns_control_actions_total, asserted "
                "equal",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


# -- model lifecycle bench (--lifecycle → BENCH_lifecycle.json) --------------

LIFECYCLE_WINDOW_MS = float(
    os.environ.get("BENCH_LIFECYCLE_WINDOW_MS", "25.0"))
LIFECYCLE_CACHE_LAYERS = int(
    os.environ.get("BENCH_LIFECYCLE_CACHE_LAYERS", "24"))


def _lifecycle_swap_leg():
    """Live hot-swap on a share-model pool under open-loop load: the
    replacement stages + warms OFF the dispatch path, the flip lands
    at a window boundary — dropped frames must be EXACTLY 0 (pushed ==
    delivered after drain) and the measured flip stall must fit inside
    one window deadline."""
    from nnstreamer_tpu.filters.jax_xla import register_model

    rig = _MttrPoolRig("lcswap", lambda x: x + 1.0, n_pipes=3,
                       batch=8, timeout_ms=LIFECYCLE_WINDOW_MS,
                       pace_s=0.002, burst=2).start()
    try:
        time.sleep(1.0)  # compile + steady state before the swap
        v2 = register_model("mttr_lcswap_v2", lambda x: x + 3.0,
                            in_shapes=[(8,)], in_dtypes=np.float32)
        entry = rig.entry
        t0 = time.perf_counter()
        res = entry.reload_model(v2, version="v2")
        stage_to_live_s = time.perf_counter() - t0
        lc = entry.lifecycle
        stall_ms = lc.last_swap_stall_s * 1e3
        time.sleep(0.6)  # serve on the new version
        drained = rig.quiesce()
        flipped = all(v == 3.0 for v in rig.last_value
                      if v is not None) and any(
            v is not None for v in rig.last_value)
    finally:
        rig.stop()
    assert drained, "lifecycle swap leg: pipeline did not drain"
    pushed, delivered = sum(rig.pushed), sum(rig.delivered)
    return {
        "frames_pushed": pushed,
        "frames_delivered": delivered,
        "dropped_frames": pushed - delivered,
        "swap_stall_ms": round(stall_ms, 4),
        "window_ms": LIFECYCLE_WINDOW_MS,
        "stall_within_window": stall_ms <= LIFECYCLE_WINDOW_MS,
        "stage_to_live_s": round(stage_to_live_s, 4),
        "outputs_flipped": bool(flipped),
        "swapped_version": res.get("version"),
        "swaps": lc.swaps,
    }


def _lifecycle_cache_leg(cache_dir):
    """Warm-process cold start with the persistent AOT cache: the same
    model's executables (single-frame + one window bucket) built by a
    FRESH instance, cache-off vs cache-on-and-warm.  The win must be
    >= 2x, and the CompileStats ``persist_hit`` count must equal the
    executables actually loaded — asserted against BOTH the bench's
    own counter and the registry export."""
    from nnstreamer_tpu.filters.api import FilterProps
    from nnstreamer_tpu.filters.jax_xla import JaxXlaFilter, \
        register_model
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.runtime.compilecache import CACHE_STATS
    from nnstreamer_tpu.utils.stats import COMPILE_STATS

    rng = np.random.default_rng(7)
    w = rng.standard_normal((128, 128)).astype(np.float32)

    def heavy(x):
        import jax.numpy as jnp

        for _ in range(LIFECYCLE_CACHE_LAYERS):
            x = jnp.tanh(x @ w)
        return x

    register_model("lc_cache_model", heavy, in_shapes=[(128,)],
                   in_dtypes=np.float32)

    def cold_start():
        # a fresh instance = a fresh process's compile work: new jit
        # closures, empty executable cache (jax memoizes per function
        # object, so nothing carries over except the persistent cache)
        sp = JaxXlaFilter()
        sp.configure(FilterProps(framework="jax-xla",
                                 model="lc_cache_model"))
        t0 = time.perf_counter()
        x = np.zeros((128,), np.float32)
        _fetch_sync(sp.invoke([x]))
        outs = sp.invoke_batched([[x]] * 4, 4)
        for fo in outs:
            _fetch_sync(fo)
        dt = time.perf_counter() - t0
        sp.close()
        return dt

    def persist_hits():
        return sum(r["count"] for r in COMPILE_STATS.snapshot()
                   if r["kind"] == "persist_hit")

    prev = os.environ.pop("NNS_TPU_COMPILE_CACHE_DIR", None)
    try:
        t_off = cold_start()  # no cache armed: full trace + XLA build
        os.environ["NNS_TPU_COMPILE_CACHE_DIR"] = cache_dir
        before_stats = CACHE_STATS.snapshot()
        cold_start()  # populate (misses + stores)
        hits0 = persist_hits()
        t_warm = cold_start()  # warm-process cold start: deserialize
        hits = persist_hits() - hits0
        stats = CACHE_STATS.snapshot()
        loaded = stats["hits"] - before_stats["hits"]
        fam = REGISTRY.collect().get("nns_compiles_total", {})
        exported = sum(
            s["value"] for s in fam.get("samples", [])
            if s["labels"].get("kind") == "persist_hit")
        truth = persist_hits()
    finally:
        if prev is None:
            os.environ.pop("NNS_TPU_COMPILE_CACHE_DIR", None)
        else:
            os.environ["NNS_TPU_COMPILE_CACHE_DIR"] = prev
    return {
        "cold_start_off_s": round(t_off, 4),
        "cold_start_warm_s": round(t_warm, 4),
        "speedup": round(t_off / t_warm, 2) if t_warm > 0 else None,
        # the warm run loaded exactly its two executables (single-frame
        # + the bucket-4 window) from disk, nothing compiled
        "executables_loaded": loaded,
        "persist_hits": hits,
        "persist_hits_equal": hits == loaded == 2,
        # registry-vs-bench equality: the exported counter is the same
        # number the bench derived from the pull source
        "registry_equals_bench": exported == truth,
        "cache_stats": stats,
    }


def _lifecycle_canary_leg():
    """Seeded bad canary, automatic verdict: a pool declaring
    ``canary=next:1/2`` reloads into a deliberately slow model; the
    watch comparator (canary latency vs baseline latency via per=)
    fires, the playbook actuates ``model:*:rollback``, and the pool
    recovers to baseline-only serving — detection/actuation/recovery
    measured exactly like the --mttr scripts, pre-fault alerts gated
    at zero."""
    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.obs.watch import AlertRule
    from nnstreamer_tpu.obs.control import Playbook

    rig = _MttrPoolRig("lccanary", lambda x: x + 1.0, n_pipes=4,
                       batch=8, timeout_ms=5.0, pace_s=0.004,
                       burst=2, canary="next:1/2",
                       stat_sample_interval_ms=20.0).start()

    def bad(x):
        # ~1000x the baseline's work: the canary latency series
        # leaves the baseline's by far more than the 3x comparator
        import jax
        import jax.numpy as jnp

        def body(_i, v):
            return jnp.tanh(v * 1.0001)

        return jax.lax.fori_loop(0, 2000, body, x)

    bad_model = register_model("mttr_lccanary_bad", bad,
                               in_shapes=[(8,)],
                               in_dtypes=np.float32)
    rules = [
        # the comparator pair: latency ratio + canary error rate
        AlertRule(name="canary-regressed", kind="threshold",
                  metric="nns_model_canary_latency_us",
                  per="nns_model_baseline_latency_us",
                  op=">", value=3.0, for_s=0.1, severity="critical"),
        AlertRule(name="canary-errors", kind="threshold",
                  metric="nns_model_canary_errors_total",
                  op=">", value=0.0, severity="critical"),
    ]
    playbooks = [
        Playbook(name="canary-rollback", rule="canary-regressed",
                 kind="model", actuator="rollback", action="set",
                 value=1.0, cooldown_s=1.0),
        Playbook(name="canary-errors-rollback", rule="canary-errors",
                 kind="model", actuator="rollback", action="set",
                 value=1.0, cooldown_s=1.0),
    ]
    entry = rig.entry

    def fault():
        entry.reload_model(bad_model, version="v2-bad")

    def recovered():
        lc = entry._lifecycle
        return lc is not None and not lc.canary_active \
            and lc.rollbacks >= 1

    try:
        row, _ctl = _mttr_run("bad-canary", "canary-regressed",
                              rules, playbooks, fault, recovered,
                              warmup_s=1.5)
    finally:
        rig.stop()
    lc = entry._lifecycle
    row["rolled_back"] = bool(lc is not None and lc.rollbacks >= 1)
    row["canary_frames_served"] = (
        lc.summary().get("canary_frames", 0) if lc is not None else 0)
    return row


def bench_lifecycle(out_path: str = "BENCH_lifecycle.json",
                    metrics: bool = False):
    """``--lifecycle``: the zero-downtime model lifecycle as three
    regression-gated legs — live hot-swap (0 dropped frames, flip
    stall inside one window), persistent-AOT-cache warm-process cold
    start (>= 2x, persist_hit accounting exact), and a seeded bad
    canary that the watch comparator + rollback playbook must catch
    automatically (recovery recorded, zero pre-fault alerts)."""
    import tempfile

    from nnstreamer_tpu.obs.metrics import REGISTRY

    swap = _lifecycle_swap_leg()
    with tempfile.TemporaryDirectory(prefix="nns_aot_bench_") as d:
        cache = _lifecycle_cache_leg(d)
    canary = _lifecycle_canary_leg()
    # per-leg verdicts: the headline `value` counts legs within gate,
    # so partial regressions stay visible in the history trend
    legs_ok = [
        swap["dropped_frames"] == 0 and swap["stall_within_window"]
        and swap["outputs_flipped"],
        (cache["speedup"] or 0) >= 2.0 and cache["persist_hits_equal"]
        and cache["registry_equals_bench"],
        canary["recovered"] and canary["rolled_back"]
        and canary["pre_fault_alerts"] == 0,
    ]
    result = {
        "metric": "zero-downtime model lifecycle: hot-swap a live "
                  "share-model pool (0 dropped frames, flip at a "
                  "window boundary), warm-process cold start via the "
                  "persistent AOT cache, bad-canary auto-rollback "
                  "through watch comparator + playbook",
        "value": sum(legs_ok),
        "unit": "of 3 lifecycle legs within gate",
        "dropped_frames": swap["dropped_frames"],
        "swap_stall_ms": swap["swap_stall_ms"],
        "stall_within_window": swap["stall_within_window"],
        "outputs_flipped": swap["outputs_flipped"],
        "cold_start_speedup": cache["speedup"],
        "persist_hits_equal": cache["persist_hits_equal"],
        "registry_equals_bench": cache["registry_equals_bench"],
        "canary_rolled_back": canary["rolled_back"],
        "canary_detected": canary["detected"],
        "canary_pre_fault_alerts": canary["pre_fault_alerts"],
        "canary_recovery_s": canary["mttr_s"],
        "swap": swap,
        "cold_start": cache,
        "canary": canary,
        "note": "dropped frames = pushed - delivered after full "
                "drain, EXACT; swap stall = wall time the flip held "
                "the window-boundary lock; cold-start speedup = "
                "fresh-instance executable build time cache-off vs "
                "warm persistent cache (persist_hit count must equal "
                "executables loaded, registry export must equal the "
                "bench's own pull-source read); canary leg reuses "
                "the --mttr fault->alert->actuation->recovery "
                "machinery with the comparator rule pair as judge",
    }
    if metrics:
        result["metrics"] = REGISTRY.snapshot()
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


# -- data-movement observability bench (--transfer → BENCH_transfer.json) ----

TRANSFER_FRAMES = int(os.environ.get("BENCH_TRANSFER_FRAMES", "256"))
TRANSFER_REPS = int(os.environ.get("BENCH_TRANSFER_REPS", "5"))


def _transfer_leg(model: str, spec, n: int, name: str = "xfer",
                  warmup: int = 0):
    """One run of the seed single-filter pipeline (appsrc ! queue !
    jax-xla ! appsink), every output drained to host.  ``warmup``
    frames run (and drain) before the timed window so XLA compile and
    the first blocking stat sample stay outside it.  Returns fps
    (push → all pulled+drained) over the timed frames only."""
    from nnstreamer_tpu.core import Buffer
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.runtime import Pipeline

    shape = spec.tensors[0].shape
    total = n + warmup
    frames = [Buffer.of(np.full(shape, float(i % 7), np.float32), pts=i)
              for i in range(total)]
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=spec, max_buffers=total + 4)
    q = Queue(name="q", max_size_buffers=total + 4)
    flt = TensorFilter(name="net", framework="jax-xla", model=model)
    sink = AppSink(name="out", max_buffers=total + 4)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    with p:
        for b in frames[:warmup]:
            src.push_buffer(b)
        for _ in range(warmup):
            out = _pull(sink, "transfer warmup")
            for t in out.tensors:
                t.np()
        t0 = time.perf_counter()
        for b in frames[warmup:]:
            src.push_buffer(b)
        for _ in range(n):
            out = _pull(sink, "transfer")
            for t in out.tensors:
                t.np()  # device→host drain: the d2h leg of the ledger
        dt = time.perf_counter() - t0
        src.end_of_stream()
        p.wait_eos(timeout=30)
    return n / dt


def bench_transfer(out_path: str = "BENCH_transfer.json",
                   metrics: bool = False):
    """``--transfer``: data-movement observability acceptance (ISSUE 8).

    Three claims on the seed single-filter pipeline, CPU backend:

    - **byte-exact ledger**: exported ``nns_transfer_bytes_total``
      equals the analytically known input+output nbytes per frame
      (h2d at the filter's upload, d2h at the sink-side drain);
    - **crossings-per-frame**: the tracer's residency-flip figure for
      the host→device→host shape of this pipeline is exactly 1 flip
      at the filter boundary (the drain happens past the sink);
    - **zero measurable overhead**: interleaved on/off A/B (ledger +
      flight recorder armed vs fully disabled), medians within the
      PR 4 tolerance (<3%)."""
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.obs import transfer as xfer
    from nnstreamer_tpu.obs.flightrec import FLIGHT
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.obs.tracer import LatencyTracer

    n = TRANSFER_FRAMES
    shape = (16,)
    frame_bytes = int(np.dtype(np.float32).itemsize * np.prod(shape))
    model = register_model("bench_transfer_tiny",
                           lambda x: x * 2.0 + 1.0,
                           in_shapes=[shape], in_dtypes=np.float32)
    spec = TensorsSpec.from_shapes([shape], np.float32)
    # -- leg 1: byte-exactness on a fresh ledger (warmup included in
    # the analytic expectation: every pushed frame crosses once up,
    # once down)
    xfer.set_enabled(True)
    xfer.LEDGER.clear()
    fps_exact = _transfer_leg(model, spec, n, name="xferpipe")
    h2d_count, h2d_bytes = xfer.LEDGER.totals(
        pipeline="xferpipe", direction="h2d", reason="input")
    d2h_count, d2h_bytes = xfer.LEDGER.totals(
        direction="d2h", reason="drain")
    expected = n * frame_bytes
    byte_exact = (h2d_bytes == expected and d2h_bytes == expected
                  and h2d_count == n and d2h_count == n)
    # the registry export must agree with the ledger it derives from
    snap = REGISTRY.snapshot()
    fam = snap["metrics"].get("nns_transfer_bytes_total", {})
    exported_h2d = sum(
        s["value"] for s in fam.get("samples", [])
        if s["labels"].get("pipeline") == "xferpipe"
        and s["labels"].get("direction") == "h2d"
        and s["labels"].get("reason") == "input")
    byte_exact = byte_exact and exported_h2d == expected
    # -- leg 2: crossings-per-frame via the tracer's residency flips
    with LatencyTracer(sample_every=1, max_records=64) as tr:
        _transfer_leg(model, spec, 32, name="xfertrace")
    xpf = tr.summary().get("crossings_per_frame", 0.0)
    # -- leg 3: the <3% overhead claim, two estimators.
    # (a) DETERMINISTIC seam-cost bound: the obs-on/obs-off delta is
    # exactly the gated operations — 2 ledger records + the per-element
    # context pushes per frame.  Microbench them in a tight loop
    # (stable to well under a µs) and divide by the measured per-frame
    # budget: an upper bound on the overhead fraction that does not
    # depend on shared-runner scheduler noise.
    # (b) interleaved on/off A/B over the real threaded pipeline —
    # the PR 4 methodology — reported alongside (median of per-pair
    # ratios; on a noisy runner this carries the scheduler's jitter,
    # which is why the gate reads (a)).
    # A/B frames are realistically sized (16 KiB, a small image tile):
    # the overhead bound is about a production frame's budget, not the
    # 64-byte toy vector the byte-exact leg uses for easy arithmetic.
    ab_shape = (64, 64)
    ab_bytes = int(np.dtype(np.float32).itemsize * np.prod(ab_shape))
    ab_model = register_model("bench_transfer_ab",
                              lambda x: x * 2.0 + 1.0,
                              in_shapes=[ab_shape],
                              in_dtypes=np.float32)
    ab_spec = TensorsSpec.from_shapes([ab_shape], np.float32)
    on_fps, off_fps = [], []
    rec_enabled = FLIGHT.enabled

    def _ab_leg(enabled):
        xfer.set_enabled(enabled)
        FLIGHT.enabled = enabled
        fps = _transfer_leg(ab_model, ab_spec, n,
                            name="xfer-on" if enabled else "xfer-off",
                            warmup=16)
        (on_fps if enabled else off_fps).append(fps)

    try:
        for rep in range(TRANSFER_REPS):
            # alternate within-pair order so a transient that lands on
            # "the first leg after the previous pair" (thread teardown
            # debt, GC) doesn't bias one mode systematically
            first_on = rep % 2 == 0
            _ab_leg(first_on)
            _ab_leg(not first_on)
    finally:
        xfer.set_enabled(True)
        FLIGHT.enabled = rec_enabled
    on_med = float(np.median(on_fps))
    off_med = float(np.median(off_fps))
    ratios = [a / b for a, b in zip(on_fps, off_fps) if b]
    ab_overhead = 1.0 - float(np.median(ratios)) if ratios else 0.0
    # (a) the deterministic bound: per-frame gated work = 2 records
    # (h2d + d2h, timed) + one context push/pop per element the buffer
    # chains through (3 in the seed pipeline: queue, filter, sink)
    reps_us = 20000
    t0 = time.perf_counter()
    for _ in range(reps_us):
        ts = time.perf_counter()
        xfer.record("h2d", "input", ab_bytes,
                    time.perf_counter() - ts, source="bench-seam",
                    pipeline="xfer-seam")
        ts = time.perf_counter()
        xfer.record("d2h", "drain", ab_bytes,
                    time.perf_counter() - ts, source="bench-seam",
                    pipeline="xfer-seam")
        for _e in range(3):
            prev = xfer.push_context("xfer-seam", "bench-seam", None)
            xfer.pop_context(prev)
    seam_us = (time.perf_counter() - t0) / reps_us * 1e6
    frame_us = 1e6 / off_med if off_med else 0.0
    overhead = seam_us / frame_us if frame_us else 0.0
    result = {
        "metric": "data-movement observability: byte-exact transfer "
                  f"ledger + crossings/frame + on/off overhead A/B "
                  f"({n} frames, seed single-filter pipeline, CPU)",
        "value": round(xpf, 3),
        "unit": "host<->device crossings per frame (tracer residency "
                "flips)",
        "frames": n,
        "frame_bytes": frame_bytes,
        "h2d_bytes": h2d_bytes,
        "d2h_bytes": d2h_bytes,
        "expected_bytes_each_way": expected,
        "byte_exact": byte_exact,
        "ledger_fps": round(fps_exact, 1),
        "obs_on_fps": round(on_med, 1),
        "obs_off_fps": round(off_med, 1),
        "seam_cost_us_per_frame": round(seam_us, 3),
        "frame_us": round(frame_us, 1),
        "overhead_frac": round(overhead, 4),
        "overhead_ok": overhead < 0.03,
        "ab_overhead_frac": round(ab_overhead, 4),
        "ab_on_samples": [round(s, 1) for s in on_fps],
        "ab_off_samples": [round(s, 1) for s in off_fps],
        "note": "ledger bytes are exact nbytes sums, not estimates. "
                "overhead_frac is the deterministic bound (microbenched "
                "gated seam work / measured per-frame budget); "
                "ab_overhead_frac is the interleaved pipeline A/B "
                "(median of per-pair ratios), which on a shared runner "
                "carries scheduler jitter either direction",
    }
    if metrics:
        result["metrics"] = snap
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


def _composite_live_mfu():
    """ISSUE-9 acceptance: the registry's LIVE MFU (scrape-time join of
    captured executable cost with measured ``nns_invoke_device_
    seconds`` deltas) must agree with a one-shot MFU computed by hand
    from this bench's own independent lowering and the same run's
    phase stats — and the flops figures must match byte-for-byte.

    A dedicated fused composite pipeline runs with EVERY dispatch
    sampled; the join's delta window is primed after the first
    (compile-polluted) dispatch so both sides see only clean
    steady-state device time."""
    import jax

    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.decoders.boxutil import device_render_fn
    from nnstreamer_tpu.elements.transform import _OpChain
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.obs.xlacost import XLA_COST

    model = "bench_ssd_live"
    detect, params, _anchors = _register_ssd_pp(model, SSD_BATCH)
    bufs = max(WARMUP, 1) + 5
    # distinct element name: the A/B composite legs already measured
    # their device-seconds series under source="net" for a DIFFERENT
    # model — reusing the name would merge the series (and fire the
    # obs remap warning)
    p, sink = _composite_pipeline(SSD_BATCH, bufs, model, fuse=True,
                                  pool_size=4, flt_name="net_live")
    p["net_live"].stat_sample_interval_ms = 0  # sample EVERY dispatch
    with p:
        b = _pull(sink, "live-mfu warmup")
        _fetch_sync_small(b)
        # prime the join's delta window AFTER the compile dispatch
        REGISTRY.snapshot()
        s0 = p["net_live"].invoke_stats.snapshot()["phase"]
        for _ in range(bufs - 1):
            b = _pull(sink, "live-mfu")
            _fetch_sync_small(b)
        s1 = p["net_live"].invoke_stats.snapshot()["phase"]
        snap = REGISTRY.snapshot()
    # the bench's OWN lowering of the exact fused program (normalize +
    # detect + device overlay): lowered OUTSIDE the filter's compile
    # seam, so it cross-checks the capture plumbing end to end.  The
    # reconstruction must match the installed program STRUCTURALLY, not
    # just mathematically, because unoptimized-HLO cost analysis counts
    # per-op buffer traffic: the decoder's epilogue returns
    # (canvas, *outs) (slicing the canvas instead re-reads it:
    # +B*H*W*4 bytes), and the normalize stage must be the transform
    # grammar's own fn — hand-inlining `(x-127.5)/127.5` lowers with
    # one fewer full-image operand read than `add:-127.5` does.
    post = device_render_fn(SSD_BATCH, 10, SSD_SIZE, SSD_SIZE, 0.25)
    norm = _OpChain("arithmetic",
                    "typecast:float32,add:-127.5,div:127.5").fn_for(
        TensorsSpec.from_shapes([(SSD_BATCH, SSD_SIZE, SSD_SIZE, 3)],
                                np.uint8).tensors[0])

    def full(x):
        outs = detect(params, norm(x))
        return (post(*outs), *outs)

    flops_bench, bytes_bench = flops_bytes(jax.jit(full).lower(
        jax.ShapeDtypeStruct((SSD_BATCH, SSD_SIZE, SSD_SIZE, 3),
                             np.uint8)))
    erow = XLA_COST.get(model, 0) or {}
    live = next((r for r in snap.get("executables", [])
                 if r["source"] == model and r["bucket"] == 0), {})
    dsum = s1["device_s"] - s0["device_s"]
    dcount = s1["samples"] - s0["samples"]
    # cost-attribution split over the same clean steady-state window:
    # host = prep + drain, device = the fenced execution phase.  The
    # device-resident dataflow gate (ISSUE 15) requires the composite
    # dispatch to be device-time-dominated — host phase < device phase
    hsum = (s1["host_prep_s"] - s0["host_prep_s"]) \
        + (s1["host_drain_s"] - s0["host_drain_s"])
    mfu_one_shot = flops_bench * dcount / (dsum * V5E.peak_flops) \
        if dsum > 0 else None
    mfu_live = live.get("mfu")
    agreement = abs(mfu_live - mfu_one_shot) / mfu_one_shot \
        if mfu_live is not None and mfu_one_shot else None
    return {
        "registry_flops": erow.get("flops"),
        "bench_flops": flops_bench,
        "registry_bytes": erow.get("bytes"),
        "bench_bytes": bytes_bench,
        "flops_exact": erow.get("flops") == flops_bench
        and flops_bench > 0,
        "bytes_exact": erow.get("bytes") == bytes_bench,
        "mfu_live_registry": mfu_live,
        "mfu_one_shot": mfu_one_shot,
        "mfu_agreement_frac": round(agreement, 4)
        if agreement is not None else None,
        "mfu_within_5pct": agreement is not None and agreement <= 0.05,
        "sampled_dispatches": dcount,
        "host_phase_us_per_dispatch": round(hsum / dcount * 1e6, 1)
        if dcount else None,
        "device_phase_us_per_dispatch": round(dsum / dcount * 1e6, 1)
        if dcount else None,
        "device_time_dominated": bool(dcount and dsum > hsum),
    }


def _composite_dispatch_overhead():
    """ISSUE-17 acceptance: the fused composite issues exactly ONE XLA
    dispatch per window — counted at the dispatch sites themselves
    (DISPATCH_STATS), cross-checked against CompileStats — and the
    python-side cost per window (pipeline wall minus the same compiled
    program chained back-to-back without any element plumbing) stays
    under a gated ceiling.

    Runs under NNS_TPU_OBS_DISABLE so the hot path is the fully async
    one: no sampling fences, no ``_last_out`` retention — what is
    measured is element plumbing + dispatch enqueue, not
    observability.  Timing starts AFTER the first (compile-polluted)
    window; the dispatch count covers the whole run, because every
    window — warmup included — must cost exactly one dispatch."""
    from nnstreamer_tpu.obs import hooks as _hooks
    from nnstreamer_tpu.utils.stats import COMPILE_STATS, DISPATCH_STATS

    model = "bench_ssd_dispatch"
    _register_ssd_pp(model, SSD_BATCH)
    bufs = max(WARMUP, 1) + 8
    saved = _hooks.DISABLED
    _hooks.DISABLED = True
    try:
        p, sink = _composite_pipeline(SSD_BATCH, bufs, model, fuse=True,
                                      pool_size=16, flt_name="net_ds")
        d0 = DISPATCH_STATS.snapshot()
        with p:
            b = _pull(sink, "dispatch warmup")  # the compile window
            _fetch_sync_small(b)
            c_after_warm = COMPILE_STATS.total_compiles
            t0 = time.perf_counter()
            for _ in range(bufs - 1):
                b = _pull(sink, "dispatch")
            _fetch_sync_small(b)
            wall_us = (time.perf_counter() - t0) / (bufs - 1) * 1e6
            d1 = DISPATCH_STATS.snapshot()
            c_end = COMPILE_STATS.total_compiles
            # the SAME executable the pipeline just dispatched, chained
            # from a bare python loop over the source's staged pool —
            # the floor the element plumbing is measured against
            jitted = p["net_ds"].subplugin._compiled.jitted
            pool = [slot[0] for slot in p["src"]._pool]
            _fetch_sync(jitted(pool[0]))
            t1 = time.perf_counter()
            out = None
            for i in range(bufs - 1):
                out = jitted(pool[i % len(pool)])
            _fetch_sync(out)
            prog_us = (time.perf_counter() - t1) / (bufs - 1) * 1e6
        overhead_us = _composite_python_overhead_us()
    finally:
        _hooks.DISABLED = saved
    delta = {k: d1.get(k, 0) - d0.get(k, 0)
             for k in set(d0) | set(d1)
             if d1.get(k, 0) - d0.get(k, 0)}
    dpf = sum(delta.values()) / float(bufs)
    return {
        "dispatches_per_frame": dpf,
        # the fused segment is ONE program: only the filter site may
        # count, exactly once per window, compiled exactly once (no
        # steady-state recompiles after the warmup window)
        "single_program_per_window": (set(delta) == {"filter"}
                                      and dpf == 1.0
                                      and c_end == c_after_warm),
        "python_overhead_per_frame_us": overhead_us,
        "ssd_wall_minus_program_us": round(max(wall_us - prog_us, 0.0),
                                           1),
        "dispatch_sites": delta,
    }


def _composite_python_overhead_us(windows: int = 128,
                                  reps: int = 3) -> float:
    """Per-window python cost of the composite element plumbing:
    pipeline wall minus the same fused program chained from a bare
    loop, on a composite-shaped pipeline whose program is tiny — with
    the SSD model the ~seconds of device time per window drowns the
    python term in run-to-run noise; with a trivial detect model the
    plumbing IS the measurement.  Median of ``reps`` fresh pipeline
    runs (a GC or scheduler burst inside one 40 ms window skews a
    single sample by 2x).  Caller holds NNS_TPU_OBS_DISABLE, so this
    times the fully-async hot path the PR ships (any synchronous
    fence or per-window retention creeping back in lands directly on
    this gated number)."""
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_xla import register_model

    size, b = 32, SSD_BATCH

    def detect(x):
        m = jnp.mean(x, axis=(1, 2, 3), keepdims=False)
        boxes = jnp.tile(jnp.asarray([[0.1, 0.1, 0.5, 0.5]],
                                     jnp.float32)[None], (b, 10, 1)) \
            + m[:, None, None] * 0.0
        scores = jnp.full((b, 10), 0.9, jnp.float32)
        classes = jnp.ones((b, 10), jnp.float32)
        num = jnp.full((b,), 10, jnp.int32)
        return boxes, classes, scores, num

    register_model("bench_plumbing", detect,
                   in_shapes=[(b, size, size, 3)], in_dtypes=np.float32)
    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink
    from nnstreamer_tpu.elements.decoder import TensorDecoder
    from nnstreamer_tpu.elements.devicesrc import DeviceSrc
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.transform import TensorTransform
    from nnstreamer_tpu.runtime import Pipeline

    bufs = windows + 1
    spec = TensorsSpec.from_shapes([(b, size, size, 3)], np.uint8)
    samples = []
    for rep in range(reps):
        p = Pipeline(fuse=True)
        src = DeviceSrc(name="src", spec=spec, pattern="noise",
                        pool_size=16, num_buffers=bufs)
        tf = TensorTransform(
            name="norm", mode="arithmetic",
            option="typecast:float32,add:-127.5,div:127.5")
        flt = TensorFilter(name=f"net_pl{rep}", framework="jax-xla",
                           model="bench_plumbing")
        dec = TensorDecoder(name="overlay", mode="bounding_boxes",
                            option1="mobilenet-ssd-postprocess",
                            option4=f"{size}:{size}",
                            option5=f"{size}:{size}", option7="device")
        sink = AppSink(name="out", max_buffers=bufs + 4)
        p.add(src, tf, flt, dec, sink).link(src, tf, flt, dec, sink)
        with p:
            buf = _pull(sink, "plumbing warmup")  # the compile window
            _fetch_sync_small(buf)
            t0 = time.perf_counter()
            for _ in range(windows):
                buf = _pull(sink, "plumbing")
            _fetch_sync_small(buf)
            wall_us = (time.perf_counter() - t0) / windows * 1e6
            jitted = p[f"net_pl{rep}"].subplugin._compiled.jitted
            pool = [slot[0] for slot in p["src"]._pool]
            _fetch_sync(jitted(pool[0]))
            t1 = time.perf_counter()
            out = None
            for i in range(windows):
                out = jitted(pool[i % len(pool)])
            _fetch_sync(out)
            prog_us = (time.perf_counter() - t1) / windows * 1e6
        samples.append(max(wall_us - prog_us, 0.0))
    return round(float(np.median(samples)), 1)


def bench_composite_only(out_path: str = "BENCH_composite.json"):
    """``--composite``: the composite workload alone (no model zoo) —
    fast enough to regenerate the headline fps AND the data-movement
    crossings-per-frame figure for the bench history, plus the ISSUE-9
    live-MFU acceptance block (registry join vs one-shot roofline)."""
    from nnstreamer_tpu.obs import hwspec

    reps = int(os.environ.get("BENCH_COMPOSITE_REPS", "3"))
    # the composite MFU figures have always been quoted against the
    # v5e peaks, whatever backend runs the dry run — pin the spec so
    # the registry join derives utilization on CPU hosts too
    prev_spec = hwspec.set_override(V5E)
    try:
        fps, fps_u, fused, ab = bench_composite(reps=reps)
        live = _composite_live_mfu()
        # ISSUE-17: single-dispatch + async hot-path acceptance —
        # dispatches_per_frame (exact 1.0) and the python-overhead
        # ceiling are gated rows in composite_smoke.json
        dispatch = _composite_dispatch_overhead()
        # the floor below which no per-frame host round-trip can go:
        # the ISSUE-15 gate keeps a lower-direction ceiling on it so a
        # regression that re-introduces host hops into the composite
        # dataflow cannot hide behind a faster round trip
        floor_ms = device_roundtrip_floor_ms()
    finally:
        hwspec.set_override(prev_spec)
    crossings = ab.pop("crossings_per_frame", None)
    result = {
        "metric": "composite MobileNetV2-SSD pipeline throughput "
                  f"(batch={SSD_BATCH}; --composite slice)",
        "value": round(fps, 1),
        "unit": "frames/sec/chip",
        "composite_fps_unfused": round(fps_u, 1),
        "fusion_active": fused,
        "crossings_per_frame": crossings,
        "device_roundtrip_floor_ms": round(floor_ms, 3),
        "composite_ab": ab,
        **live,
        **dispatch,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return result


def main():
    # --metrics (with --batching/--serve): embed an obs registry
    # snapshot into the emitted BENCH json — resolved ONCE here so the
    # bench functions stay argv-free for programmatic callers.
    # --history: additionally append a normalized record (scenario, key
    # scalars, git sha, registry digest) to BENCH_history.jsonl — the
    # trajectory `tools/nns_bench_diff` gates CI on.
    metrics = "--metrics" in sys.argv[1:]
    history = "--history" in sys.argv[1:]

    def record(scenario, result):
        if history and result:
            from nnstreamer_tpu.obs.benchgate import append_history

            append_history(scenario, result,
                           snapshot=result.get("metrics"))

    if "--batching" in sys.argv[1:]:
        record("batching", bench_batching(metrics=metrics))
        return
    if "--serve" in sys.argv[1:]:
        record("serving", bench_serving(metrics=metrics))
        return
    if "--edge" in sys.argv[1:]:
        record("edge", bench_edge())
        return
    if "--openloop" in sys.argv[1:]:
        record("openloop", bench_openloop())
        return
    if "--hostprof" in sys.argv[1:]:
        record("hostprof", bench_hostprof())
        return
    if "--chaos" in sys.argv[1:]:
        record("chaos", bench_chaos())
        return
    if "--watch" in sys.argv[1:]:
        record("watch", bench_watch())
        return
    if "--mttr" in sys.argv[1:]:
        record("mttr", bench_mttr())
        return
    if "--lifecycle" in sys.argv[1:]:
        record("lifecycle", bench_lifecycle(metrics=metrics))
        return
    if "--transfer" in sys.argv[1:]:
        record("transfer", bench_transfer(metrics=metrics))
        return
    if "--composite" in sys.argv[1:]:
        record("composite", bench_composite_only())
        return
    if "--meshserving" in sys.argv[1:]:
        record("meshserving", bench_meshserving(metrics=metrics))
        return
    if "--cascade" in sys.argv[1:]:
        record("cascade", bench_cascade(metrics=metrics))
        return
    if "--capacity" in sys.argv[1:]:
        record("capacity", bench_capacity())
        return
    if "--mesh" in sys.argv[1:] or "--meshscaling" in sys.argv[1:]:
        record("meshscaling", bench_meshscaling(metrics=metrics))
        return
    if "--project" in sys.argv[1:]:
        bench_project()
        return
    # the persistent compile cache goes on BEFORE the first compile,
    # then the chip is identified: no chip, no rates
    from nnstreamer_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    dev, spec = _chip_spec()
    import jax

    peak = spec.peak_flops
    # cost analyses on the CPU backend of this same process
    per_frame_flops = composite_flops()
    cls_flops = classify_flops()
    yolo_gflops = yolo_flops()
    composite_fps, composite_fps_unfused, fused, ab_spread = \
        bench_composite()
    composite_xpf = ab_spread.pop("crossings_per_frame", None)
    lat = bench_latency()
    rtt_floor = device_roundtrip_floor_ms()
    breakdown, roofline = device_time_breakdown(spec)
    batch_period_ms = SSD_BATCH / composite_fps * 1e3
    breakdown["dispatch_gap_ms"] = round(
        max(batch_period_ms - breakdown["compute_total_ms"], 0.0), 3)
    # fusion A/B interleaved (compiles hit the persistent cache):
    # MEDIAN per mode — see _ab_aggregate for why not best-of
    cls_model = register_classify_model()
    runs_f, runs_u = [], []
    for _ in range(3):
        runs_f.append(bench_classify(fuse=True, buffers=15,
                                     model=cls_model))
        runs_u.append(bench_classify(fuse=False, buffers=15,
                                     model=cls_model))
    cls_fps, _cls_spread = _ab_aggregate(runs_f)
    cls_fps_unfused, _ = _ab_aggregate(runs_u)
    vit_model = register_vit_bench()
    vit_fps, _ = _ab_aggregate([bench_vit(vit_model)
                                for _ in range(3)])
    vit_flops = vit_flops_per_frame()
    yolo_fps, _ = _ab_aggregate([bench_yolo() for _ in range(3)])
    # the two import slices need the reference's own model files
    if os.path.isfile(_TFLITE_MODEL):
        tflite_fps = round(bench_tflite(), 1)
        tflite_mfu = round(tflite_fps * tflite_flops() / peak, 4)
    else:
        tflite_fps = tflite_mfu = NOT_RUN
    if os.path.isfile(_ONNX_MODEL):
        onnx_fps = round(bench_onnx(), 1)
        onnx_mfu = round(onnx_fps * onnx_flops() / peak, 4)
    else:
        onnx_fps = onnx_mfu = NOT_RUN
    print(json.dumps({
        "metric": "composite MobileNetV2-SSD pipeline throughput "
                  f"(batch={SSD_BATCH}, device_src ! transform[fused] ! "
                  "jax-xla ssd+NMS ! bounding_boxes decoder ! sink)",
        "value": round(composite_fps, 1),
        "unit": "frames/sec/chip",
        # the device every number below was taken on, as JAX reports it
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "peak_spec": spec.name,
        "vs_baseline": round(composite_fps / BASELINE_FPS_PER_CHIP, 3),
        "composite_fps_unfused": round(composite_fps_unfused, 1),
        "composite_fused_vs_unfused":
            round(composite_fps / composite_fps_unfused, 3)
            if composite_fps_unfused else None,
        "composite_ab": ab_spread,
        # data-movement observability (ISSUE 8): ledger crossings per
        # streamed frame on the composite pipeline — the figure the
        # device-resident-dataflow rework must hold at/near zero
        "crossings_per_frame": composite_xpf,
        **lat,
        "device_roundtrip_floor_ms": round(rtt_floor, 3),
        "device_time_breakdown": breakdown,
        "roofline": roofline,
        "mfu": round(composite_fps * per_frame_flops / peak, 4),
        "gflops_per_frame": round(per_frame_flops / 1e9, 3),
        "fusion_active": fused,
        "classify_fps": round(cls_fps, 1),
        "classify_mfu": round(cls_fps * cls_flops / peak, 4),
        "classify_fps_unfused": round(cls_fps_unfused, 1),
        "fused_vs_unfused": round(cls_fps / cls_fps_unfused, 3)
        if cls_fps_unfused else None,
        "vit_fps": round(vit_fps, 1),
        "vit_mfu": round(vit_fps * vit_flops / peak, 4),
        "vit_gflops_per_frame": round(vit_flops / 1e9, 3),
        "yolo_fps": round(yolo_fps, 1),
        "yolo_mfu": round(yolo_fps * yolo_gflops / peak, 4),
        "yolo_gflops_per_frame": round(yolo_gflops / 1e9, 3),
        # pretrained-import slice: the reference's own quantized
        # mobilenet_v2 .tflite, imported and batched on the TPU
        "tflite_mobilenet_v2_fps": tflite_fps,
        "tflite_mobilenet_v2_mfu": tflite_mfu,
        # imported-onnx slice: the reference's ORT-quantized model in
        # exact bf16-code quantized execution
        "onnx_mobilenet_v2_fps": onnx_fps,
        "onnx_mobilenet_v2_mfu": onnx_mfu,
        "measurement_note": (
            "every timing boundary is a one-element host fetch "
            "(_fetch_sync)"),
    }))


if __name__ == "__main__":
    main()
