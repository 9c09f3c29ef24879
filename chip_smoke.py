#!/usr/bin/env python
"""chip_smoke.py — does the element pipeline still start on the chip?

Drives the system's main path once, through the entry points a user
calls (``parse_launch``, ``register_model``, element properties), on a
directly attached TPU, at the sizes below (``SSD_*``, ``VIT_*``), with
random weights made from a seed.  Every section compares what came out
of the pipeline with the same function jitted directly on the same
frames.

    A  device-resident composite: device_src ! transform ! jax-xla SSD
       (300x300, 91 classes, width 1.0, batch 256, bf16 weights) !
       bounding_boxes option7=device ! appsink — one fused program
    B  the serving path: four appsrc streams of host uint8 frames into
       one share-model pool (ModelPool / SharedBatcher), batch 8
    C  the Pallas kernels: the ViT pipeline with its attention kernel, and a
       standalone tensor_transform backend=pallas — Mosaic, not the
       interpreter and not the jnp fallback
    D  the fence: block_until_ready, a host fetch and a tensor_sink's
       wait_eos() all wait out a program of known duration
    E  four chips (when there are four): mesh=data:4, a two-stage
       devices=0-1 / devices=2-3 split, and the mesh shared pool

It prints the device, the versions, the compile-cache directory and,
per section, compile seconds and PASS/FAIL — never a rate.  The last
line of standard output is one JSON object naming the device.  Without
a TPU it exits non-zero and prints no result.  One process; nothing it
starts outlives it.  ``tests/test_chip_smoke.py`` runs these same
section functions at a toy size on the CPU.
"""

import functools
import json
import os
import threading
import time

import numpy as np

#: the transform every SSD/ViT pipeline below carries, and the same
#: arithmetic for the directly jitted references
NORM = "typecast:float32,add:-127.5,div:127.5"
SEED = 20260926
#: bounding_boxes' default confidence threshold
CONF = 0.25
#: detections per frame the smoke SSD emits (_register_ssd_pp)
MAX_OUT = 10
#: the chip run's sizes: the SSD composite's window and frame side, and
#: a ViT at which the blockwise flash-attention kernel engages (head dim
#: 512/4 = 128, (256/16)^2 = 256 positions: whole 128-tiles both)
SSD_BATCH, SSD_SIZE = 256, 300
VIT_BATCH, VIT_SIZE, VIT_PATCH, VIT_DIM = 64, 256, 16, 512
VIT_DEPTH, VIT_HEADS, VIT_MLP = 6, 4, 2048
#: The SSD runs bf16 activations through ~50 layers of random weights.
#: Two programs that tile a convolution differently (batch 1 against a
#: window of 8, a 64-frame shard against 256 frames) round differently;
#: measured on the v5e, the same frame's boxes then move by a median of
#: 0.01 of the image side and a tenth of the top-10 changes membership,
#: while programs of one shape agree bit for bit.  So: a detection
#: matches when the reference holds one of the same class within
#: BOX_ATOL in ANY slot, and a comparison passes when at least
#: MATCHED_MIN of the detections match.  Against the WRONG frame the
#: matched share measured 0.00 — the comparison tells frames apart.
BOX_ATOL = 0.05
MATCHED_MIN = 0.75
SCORE_ATOL = 0.03
PULL_TIMEOUT_S = 600.0


class SmokeError(RuntimeError):
    """A section's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def norm(x):
    import jax.numpy as jnp

    return (x.astype(jnp.float32) + (-127.5)) / 127.5


# -- what the sections share ---------------------------------------------------


class CompileMeter:
    """Seconds JAX spent in backend compiles — on a persistent-cache hit
    that is the time to fetch the program — read off ``jax.monitoring``,
    so it covers every program of a section, references included."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += seconds
                self.programs += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def read(self):
        with self._lock:
            return self.seconds, self.programs, self.cache_hits


def aot_fallbacks() -> int:
    """AOT executables that rejected their arguments and were rebuilt
    through jit (filters/jax_xla.py ``_aot_call``), process-wide."""
    from nnstreamer_tpu.utils.stats import COMPILE_STATS

    return sum(r["count"] for r in COMPILE_STATS.snapshot()
               if r["kind"] == "aot_fallback")


def pull(sink, what: str):
    buf = sink.pull(timeout=PULL_TIMEOUT_S)
    check(buf is not None, f"{what}: no buffer in {PULL_TIMEOUT_S:.0f} s")
    return buf


def host(tree):
    """Device pytree -> numpy, float leaves (bf16 included) as float32."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else np.asarray(a), tree)


def on_devices(arr, devices, what: str) -> None:
    check(arr.sharding.device_set == set(devices),
          f"{what} lives on {sorted(d.id for d in arr.sharding.device_set)}"
          f", expected {sorted(d.id for d in devices)}")


def matched_share(got_boxes, got_classes, want_boxes, want_classes) -> float:
    """Share of the (B,N) detections in ``got`` for which ``want``
    holds, in the same frame and in any slot, a detection of the same
    class whose box lies within ``BOX_ATOL``."""
    near = (np.abs(got_boxes[:, :, None, :] - want_boxes[:, None, :, :])
            .max(axis=-1) <= BOX_ATOL)
    same = got_classes[:, :, None] == want_classes[:, None, :]
    return float((near & same).any(axis=-1).mean())


def detections_agree(got, want, what: str) -> dict:
    """``got``/``want``: (boxes (B,N,4), classes (B,N), scores (B,N),
    num (B,)) as numpy.  Finite, same shapes, every frame's scores equal
    to ``SCORE_ATOL`` once sorted, and at least ``MATCHED_MIN`` of the
    detections matched (:func:`matched_share`).  Returns the measured
    agreement, and the share that matches the NEXT frame's reference —
    what a pipeline that mixed frames up would score."""
    names = ("boxes", "classes", "scores", "num")
    for n, g, w in zip(names, got, want):
        check(g.shape == w.shape,
              f"{what}: {n} shape {g.shape}, reference {w.shape}")
        check(np.isfinite(g).all(), f"{what}: non-finite {n}")
    b = got[0].shape[0]
    gb, wb = got[0].reshape(b, -1, 4), want[0].reshape(b, -1, 4)
    gc, wc = got[1].reshape(b, -1), want[1].reshape(b, -1)
    gs, ws = got[2].reshape(b, -1), want[2].reshape(b, -1)
    score_diff = float(np.max(np.abs(np.sort(gs, axis=1)
                                     - np.sort(ws, axis=1))))
    check(score_diff <= SCORE_ATOL,
          f"{what}: sorted scores differ from the reference by "
          f"{score_diff:.4f} (allowed {SCORE_ATOL})")
    matched = matched_share(gb, gc, wb, wc)
    facts = {"matched": round(matched, 4),
             "identical": round(float(
                 ((gc == wc).all(axis=1) & (gb == wb).all(axis=(1, 2))
                  & (got[3].reshape(b) == want[3].reshape(b))).mean()), 4)}
    if b > 1:
        facts["wrong_frame_matched"] = round(matched_share(
            gb, gc, np.roll(wb, 1, axis=0), np.roll(wc, 1, axis=0)), 4)
    check(matched >= MATCHED_MIN,
          f"{what}: only {matched:.3f} of the detections match the "
          f"reference (need {MATCHED_MIN}); {facts}")
    return facts


def ssd_reference(model: str, device):
    """The registered detect function jitted directly on ``device``
    with the transform's arithmetic in front — what the pipelines'
    results are compared with.  ``placed`` hands back the function and
    the weights the filter placed on that device, so HBM holds one
    copy; they are arguments of this program too."""
    import jax

    from nnstreamer_tpu.filters.jax_xla import get_model

    fn, weights = get_model(model).placed(device)
    return functools.partial(
        jax.jit(lambda w, x: fn(w, norm(x))), weights)


def kernel_evidence(fn, *avals):
    """(``pallas_call`` equations in the traced program, whether its
    lowering carries the Mosaic custom call).  The first says the shape
    took the kernel and not the jnp fallback; the second says the
    kernel compiles for the chip instead of being interpreted."""
    import jax

    calls = str(jax.make_jaxpr(fn)(*avals)).count("pallas_call[")
    mosaic = "tpu_custom_call" in jax.jit(fn).lower(*avals).as_text()
    return calls, mosaic


def composite_launch(model: str, size: int, n_buffers: int,
                     filter_props: str = "") -> str:
    """The composite launch line.  The filter element is named after
    its model: the obs layer joins dispatch sources to models by
    element name, and this process runs several models."""
    return (
        f"device_src name=src num_buffers={n_buffers} ! "
        f"tensor_transform name=norm mode=arithmetic option={NORM} ! "
        f"tensor_filter name=net_{model} framework=jax-xla model={model} "
        f"{filter_props} ! "
        "tensor_decoder name=overlay mode=bounding_boxes "
        "option1=mobilenet-ssd-postprocess "
        f"option4={size}:{size} option5={size}:{size} option7=device ! "
        f"appsink name=out max_buffers={n_buffers + 4}")


def run_composite(desc: str, model: str, frames, n_buffers: int,
                  what: str):
    """Stream ``n_buffers`` buffers of ``frames`` through a composite
    launch line to EOS.  Returns the pulled buffers, the staged device
    frames, and what the live pipeline said about itself."""
    from nnstreamer_tpu.runtime import parse_launch
    from nnstreamer_tpu.utils.stats import DISPATCH_STATS

    p = parse_launch(desc)
    src = p["src"]
    src.frames, src.pool_size = frames, len(frames)
    d0 = DISPATCH_STATS.snapshot()
    with p:
        got = [pull(p["out"], what) for _ in range(n_buffers)]
        check(p.wait_eos(timeout=PULL_TIMEOUT_S), f"{what}: no EOS")
        net = p[f"net_{model}"]
        info = {
            "fused_pre": bool(net._fused_pre),
            "with_post": bool(net.subplugin._compiled.with_post),
            "mesh": net.subplugin._mesh,
        }
        staged = [slot[0] for slot in src._pool]
    check(p["out"].pull(timeout=0.2) is None,
          f"{what}: more than the {n_buffers} buffers sent came out")
    d1 = DISPATCH_STATS.snapshot()
    info["dispatches"] = {k: d1.get(k, 0) - d0.get(k, 0) for k in d1
                          if d1.get(k, 0) != d0.get(k, 0)}
    return got, staged, info


def canvas_and_detections(buf):
    det = buf.meta["detections_device"]
    return buf.tensors[0].jax(), (det["boxes"], det["classes"],
                                  det["scores"], det["num"])


def stack_frames(per_call):
    """[(boxes, classes, scores, num), ...] -> one such tuple over all
    the frames, for a single :func:`detections_agree`."""
    return [np.concatenate([d[j] for d in per_call]) for j in range(4)]


def run_streams(launch: str, filter_name: str, spec, frames, what: str,
                inspect):
    """One pipeline per stream of ``frames`` (streams, n, ...) from the
    same ``launch`` line, all pushing at once — no single stream can
    fill a window, so coalescing has to cross streams.  Returns each
    stream's buffers in arrival order and ``inspect(pool entry)`` taken
    while the streams are still attached.  Checks order-independent
    things here: nothing missing, nothing extra, EOS everywhere."""
    from nnstreamer_tpu.core import Buffer
    from nnstreamer_tpu.runtime import Pipeline, parse_launch

    n = frames.shape[1]
    pipes = []
    try:
        for i in range(frames.shape[0]):
            p = parse_launch(launch, pipeline=Pipeline(
                name=f"smoke_{filter_name}{i}"))
            p["src"].spec = spec
            p.start()
            pipes.append(p)
        for k in range(n):
            for i, p in enumerate(pipes):
                p["src"].push_buffer(Buffer.of(frames[i, k], pts=k))
        outs = [[pull(p["out"], f"{what}: stream {i}") for _ in range(n)]
                for i, p in enumerate(pipes)]
        pool = pipes[0][filter_name].pool
        check(all(p[filter_name].pool is pool for p in pipes),
              f"{what}: the streams did not share one pool entry")
        facts = inspect(pool)
        for p in pipes:
            p["src"].end_of_stream()
        for i, p in enumerate(pipes):
            check(p.wait_eos(timeout=PULL_TIMEOUT_S),
                  f"{what}: stream {i} did not reach EOS")
            check(p["out"].pull(timeout=0.2) is None,
                  f"{what}: stream {i} rendered more than it was sent")
    finally:
        for p in pipes:
            p.stop()
    for i, bufs in enumerate(outs):
        for k, buf in enumerate(bufs):
            check(buf.pts == k,
                  f"{what}: stream {i} buffer {k} carries pts {buf.pts}")
    return outs, facts


# -- the SSD every section registers ------------------------------------------


@functools.cache
def _ssd_params_anchors(size: int, num_classes: int):
    """The SSD's weights and anchors, made ONCE per process and size:
    the sections register the same model under different names and
    batches."""
    import jax

    from nnstreamer_tpu.models.params_io import weights_to_bf16
    from nnstreamer_tpu.models.ssd import ssd_anchors, ssd_mobilenet_v2_init

    fs = tuple(int(np.ceil(size / s)) for s in (16, 32, 64, 128, 256, 512))
    return (weights_to_bf16(ssd_mobilenet_v2_init(
                jax.random.PRNGKey(0), num_classes=num_classes)),
            ssd_anchors(size, fs))


def _register_ssd_pp(name: str, batch: int, size: int, num_classes: int):
    """Register the composite SSD with outputs in the reference
    postprocess wire order (boxes, classes, scores, num) that the
    bounding_boxes mobilenet-ssd-postprocess decoder consumes
    (parity: mobilenetssdpp.cc)."""
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.models.ssd import ssd_detect_apply

    params, anchors = _ssd_params_anchors(size, num_classes)

    def detect(p, x):
        boxes, scores, classes = ssd_detect_apply(p, x, anchors,
                                                  max_out=MAX_OUT)
        num = jnp.sum((scores > CONF).astype(jnp.int32), axis=-1)
        return boxes, classes, scores, num

    register_model(name, detect, params=params,
                   in_shapes=[(batch, size, size, 3)],
                   in_dtypes=np.float32)


# -- A: device-resident composite ---------------------------------------------


def section_a(batch: int, size: int, num_classes: int,
              n_buffers: int = 4, n_pool: int = 2) -> dict:
    import jax

    from nnstreamer_tpu.decoders.boxutil import device_render_fn
    from nnstreamer_tpu.filters.jax_xla import unregister_model

    dev = jax.devices()[0]
    model = f"smoke_ssd_b{batch}"
    _register_ssd_pp(model, batch, size, num_classes)
    rng = np.random.default_rng(SEED)
    frames = [rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8)
              for _ in range(n_pool)]
    fb0 = aot_fallbacks()
    try:
        got, staged, info = run_composite(
            composite_launch(model, size, n_buffers), model, frames,
            n_buffers, "A")
        check(info["fused_pre"] and info["with_post"],
              "A: transform and decoder did not fuse into the filter "
              f"(fused_pre={info['fused_pre']}, "
              f"with_post={info['with_post']})")
        check(info["dispatches"] == {"filter": n_buffers},
              f"A: expected one program per window, {n_buffers} filter "
              f"dispatches and nothing else; counted {info['dispatches']}")
        check(aot_fallbacks() == fb0, "A: an AOT executable fell back to jit")
        ref = ssd_reference(model, dev)
        render = device_render_fn(batch, MAX_OUT, size, size, CONF)
        dets, wants = [], []
        for i, buf in enumerate(got):
            canvas, det = canvas_and_detections(buf)
            for arr in (canvas, *det):
                on_devices(arr, [dev], f"A: buffer {i} output")
            check(canvas.shape == (batch, size, size, 4)
                  and canvas.dtype == np.uint8,
                  f"A: canvas {canvas.shape} {canvas.dtype}")
            # the overlay is integer rasterization of the detections the
            # SAME program computed: exact, whatever the rounding above
            check(bool((canvas == render(*det)).all()),
                  f"A: buffer {i} canvas is not the render of its own "
                  "detections")
            dets.append(host(det))
            wants.append(host(ref(staged[i % n_pool])))
        agree = detections_agree(stack_frames(dets), stack_frames(wants), "A")
    finally:
        unregister_model(model)
    return {"buffers": n_buffers, **agree}


# -- B: the serving path --------------------------------------------------------


def section_b(streams: int, frames_per_stream: int, batch: int, size: int,
              num_classes: int) -> dict:
    import jax

    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import unregister_model

    dev = jax.devices()[0]
    model = "smoke_ssd_frame"
    _register_ssd_pp(model, 1, size, num_classes)
    depth = frames_per_stream + 4
    frames = np.random.default_rng(SEED + 1).integers(
        0, 256, (streams, frames_per_stream, 1, size, size, 3),
        dtype=np.uint8)
    fb0 = aot_fallbacks()
    try:
        outs, pool = run_streams(
            f"appsrc name=src max_buffers={depth} ! "
            f"tensor_transform mode=arithmetic option={NORM} ! "
            f"queue max_size_buffers={depth} ! "
            f"tensor_filter name=pool_b framework=jax-xla model={model} "
            f"share-model=true batch={batch} batch-timeout-ms=2 ! "
            f"appsink name=out max_buffers={depth}",
            "pool_b", TensorsSpec.from_shapes([(1, size, size, 3)], np.uint8),
            frames, "B",
            lambda pool: {
                "dispatches": pool.stats.total_invoke_num,
                "stream_slots": pool.stats.total_stream_num,
                "frames": pool.stats.total_frame_num,
                "cache": pool.subplugin.cache_snapshot()["by_bucket"],
                "buckets": tuple(pool.buckets)})
        ref = ssd_reference(model, dev)
    finally:
        unregister_model(model)
    cache = pool["cache"]
    check(pool["frames"] == streams * frames_per_stream,
          f"B: pool served {pool['frames']} frames, "
          f"{streams * frames_per_stream} were sent")
    check(pool["stream_slots"] > pool["dispatches"],
          f"B: no window carried frames of more than one stream "
          f"({pool['dispatches']} dispatches, {pool['stream_slots']} "
          "stream slots)")
    check(set(int(b) for b in cache) <= set(pool["buckets"]),
          f"B: compiled window sizes {sorted(cache)} outside the "
          f"buckets {pool['buckets']}")
    check(all(v["misses"] == 1 for v in cache.values()),
          f"B: a bucket compiled more than once: {cache}")
    check(aot_fallbacks() == fb0, "B: an AOT executable fell back to jit")
    got, want = [], []
    for i in range(streams):
        for k, buf in enumerate(outs[i]):
            check(buf.num_tensors == 4,
                  f"B: stream {i} buffer {k} has {buf.num_tensors} tensors")
            for t in buf.tensors:
                on_devices(t.jax(), [dev], f"B: stream {i} buffer {k}")
            got.append(host([t.jax() for t in buf.tensors]))
            want.append(host(ref(frames[i, k])))
    agree = detections_agree(stack_frames(got), stack_frames(want), "B")
    return {"dispatches": pool["dispatches"], "frames": pool["frames"],
            "buckets_compiled": sorted(int(b) for b in cache), **agree}


# -- C: the kernels --------------------------------------------------------------


def section_c(batch: int, image: int, patch: int, dim: int, depth: int,
              heads: int, mlp: int, num_classes: int,
              frame_shape: tuple, n_buffers: int = 2,
              short: tuple = (196, 12, 64)) -> dict:
    """``short`` is the second attention shape proved: positions, heads
    and head size of a ViT whose attention takes ``short_attention``
    (ViT-B/16's own unless a test says otherwise)."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import get_model, unregister_model
    from nnstreamer_tpu.models.vit import register_vit, vit_apply, vit_init
    from nnstreamer_tpu.ops import (flash_attention,
                                    flash_attention_reference,
                                    short_attention,
                                    short_attention_reference)
    from nnstreamer_tpu.ops.kernels import _interpret
    from nnstreamer_tpu.runtime import parse_launch

    dev = jax.devices()[0]
    compiled_for_chip = not _interpret()
    rng = np.random.default_rng(SEED + 2)

    # the ViT pipeline, flash attention on the path
    model = register_vit("smoke_vit", batch=batch, image_size=image,
                         patch=patch, dim=dim, depth=depth, heads=heads,
                         mlp_dim=mlp, num_classes=num_classes)
    frames = [rng.integers(0, 256, (batch, image, image, 3), dtype=np.uint8)
              for _ in range(2)]
    try:
        p = parse_launch(
            f"device_src name=src num_buffers={n_buffers} ! "
            f"tensor_transform mode=arithmetic option={NORM} ! "
            f"tensor_filter name=vit framework=jax-xla model={model} ! "
            f"appsink name=out max_buffers={n_buffers + 4}")
        p["src"].frames, p["src"].pool_size = frames, len(frames)
        with p:
            got = [pull(p["out"], "C: vit") for _ in range(n_buffers)]
            check(p.wait_eos(timeout=PULL_TIMEOUT_S), "C: vit: no EOS")
            sp = p["vit"].subplugin
            check(bool(p["vit"]._fused_pre), "C: vit transform did not fuse")
            # the pipeline's own program, traced again: no compile
            program = sp._compiled.program
            calls, mosaic = kernel_evidence(
                program.fn, program.weights,
                jax.ShapeDtypeStruct(frames[0].shape, np.uint8))
            staged = [slot[0] for slot in p["src"]._pool]
        check(calls == depth,
              f"C: the ViT program holds {calls} pallas_call(s) for "
              f"{depth} attention layers: a shape took the jnp fallback")
        check(mosaic == compiled_for_chip,
              f"C: ViT lowering has the Mosaic call: {mosaic}; kernels "
              f"compiled for the chip: {compiled_for_chip}")
        vit, weights = get_model(model).placed(dev)
        direct = functools.partial(
            jax.jit(lambda w, x: vit(w, norm(x))), weights)
        for i, buf in enumerate(got):
            logits = buf.tensors[0].jax()
            on_devices(logits, [dev], f"C: vit buffer {i}")
            check(logits.shape == (batch, num_classes),
                  f"C: vit logits {logits.shape}")
            a, b = host(logits), host(direct(staged[i % 2]))
            check(np.isfinite(a).all(), "C: non-finite vit logits")
            check(np.allclose(a, b, rtol=2e-2, atol=2e-2),
                  f"C: vit buffer {i} differs from the direct jit by "
                  f"{np.max(np.abs(a - b)):.4f}")
    finally:
        unregister_model(model)

    # the attention kernel against its jnp reference, at the shape and
    # dtype the ViT hands it
    seq, dh = (image // patch) ** 2, dim // heads
    q, k, v = (jnp.asarray(rng.standard_normal((batch, heads, seq, dh)),
                           jnp.bfloat16) for _ in range(3))
    calls, mosaic = kernel_evidence(flash_attention, q, k, v)
    check(calls == 1 and mosaic == compiled_for_chip,
          f"C: flash_attention at {q.shape}: pallas_calls={calls}, "
          f"mosaic={mosaic}")
    o = host(jax.jit(flash_attention)(q, k, v))
    o_ref = host(jax.jit(flash_attention_reference)(q, k, v))
    check(np.isfinite(o).all(), "C: non-finite attention output")
    attn_diff = float(np.max(np.abs(o - o_ref)))
    check(np.allclose(o, o_ref, rtol=2e-2, atol=2e-2),
          f"C: flash_attention differs from the jnp reference by "
          f"{attn_diff:.4f}")

    # the short-sequence kernel at the shape and dtype ViT-B/16 hands
    # it, [B, 196, 2304] bf16: one call a layer in the model's program,
    # and the kernel against its jnp reference
    seq, n_heads, dh = short
    side = int(round(seq ** 0.5))
    check(side * side == seq, f"C: {seq} positions are no square of patches")
    params = jax.eval_shape(lambda: vit_init(
        jax.random.PRNGKey(0), image_size=side * patch, patch=patch,
        dim=n_heads * dh, depth=depth, heads=n_heads, mlp_dim=mlp,
        num_classes=num_classes))
    calls, mosaic = kernel_evidence(
        lambda p, x: vit_apply(p, x, heads=n_heads), params,
        jax.ShapeDtypeStruct((batch, side * patch, side * patch, 3),
                             np.float32))
    check(calls == depth and mosaic == compiled_for_chip,
          f"C: a ViT of {seq} positions and {n_heads} heads of {dh} holds "
          f"{calls} pallas_call(s) for {depth} attention layers, "
          f"mosaic={mosaic}")
    qkv = jnp.asarray(rng.standard_normal((batch, seq, 3 * n_heads * dh)),
                      jnp.bfloat16)

    o = host(jax.jit(lambda x: short_attention(x, n_heads))(qkv))
    o_ref = host(jax.jit(
        lambda x: short_attention_reference(x, n_heads))(qkv))
    check(np.isfinite(o).all(), "C: non-finite short_attention output")
    short_diff = float(np.max(np.abs(o - o_ref)))
    print(f"C: short_attention at {qkv.shape} {qkv.dtype}: largest "
          f"difference from the jnp reference {short_diff:.5f}", flush=True)
    check(np.allclose(o, o_ref, rtol=2e-2, atol=2e-2),
          f"C: short_attention differs from the jnp reference by "
          f"{short_diff:.4f}")

    # the standalone Pallas transform on a host uint8 frame
    frame = rng.integers(0, 256, frame_shape, dtype=np.uint8)
    p = parse_launch(
        "appsrc name=src ! tensor_transform name=norm backend=pallas "
        f"mode=arithmetic option={NORM} ! appsink name=out")
    p["src"].spec = TensorsSpec.from_shapes([frame_shape], np.uint8)
    with p:
        p["src"].push_buffer(Buffer.of(frame))
        out = pull(p["out"], "C: transform").tensors[0].jax()
        p["src"].end_of_stream()
        check(p.wait_eos(timeout=PULL_TIMEOUT_S), "C: transform: no EOS")
        spec = p["src"].spec.tensors[0]
        calls, mosaic = kernel_evidence(
            p["norm"]._opchain().fn_for(spec),
            jax.ShapeDtypeStruct(frame_shape, np.uint8))
    check(calls == 1 and mosaic == compiled_for_chip,
          f"C: tensor_transform backend=pallas at {frame_shape}: "
          f"pallas_calls={calls}, mosaic={mosaic}")
    on_devices(out, [dev], "C: transform output")
    want = (frame.astype(np.float32) - 127.5) / 127.5
    check(out.dtype == np.float32 and np.allclose(
        np.asarray(out), want, rtol=1e-6, atol=1e-6),
        "C: Pallas transform differs from numpy")
    return {"mosaic": compiled_for_chip,
            "attention_max_abs_diff": round(attn_diff, 5),
            "short_attention_max_abs_diff": round(short_diff, 5)}


# -- D: the fence ---------------------------------------------------------------


def fence_verdict(durations: dict, lower_bound_s: float) -> None:
    """All three waits lasted at least as long as the program can
    possibly take, and agree with each other within 10 %."""
    for how, seconds in durations.items():
        check(seconds >= lower_bound_s,
              f"D: {how} returned after {seconds:.3f} s, before the "
              f"program can have finished ({lower_bound_s:.3f} s at "
              "peak): not a completion fence")
    lo, hi = min(durations.values()), max(durations.values())
    check(hi <= 1.10 * lo,
          f"D: the three waits disagree by more than 10 %: {durations}")


def measure_fence(n: int, target_s: float, peak_flops: float):
    """One program of known long duration — ``iters`` dependent
    (n,n)x(n,n) bf16 matmuls, ``iters`` sized from the chip's peak so
    that even at peak it runs ``target_s`` — waited out three ways.
    Returns (seconds per way, the program's analytic lower bound)."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.filters.jax_xla import (
        register_model,
        unregister_model,
    )
    from nnstreamer_tpu.runtime import parse_launch

    iters = max(int(np.ceil(target_s * peak_flops / (2.0 * n ** 3))), 1)
    lower_bound_s = 2.0 * n ** 3 * iters / peak_flops
    # Sylvester Hadamard / sqrt(n): orthogonal with entries exact in
    # bf16, so the iterate keeps its norm however long the loop runs
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    check(h.shape[0] == n, f"D: n={n} is not a power of two")
    w = jax.device_put(jnp.asarray(h / np.sqrt(n), jnp.bfloat16))
    x = jax.device_put(np.random.default_rng(SEED + 3).standard_normal(
        (n, n)).astype(np.float32))

    def program(x):
        def body(_, y):
            return jnp.dot(y, w, preferred_element_type=jnp.float32
                           ).astype(jnp.bfloat16)
        return jax.lax.fori_loop(0, iters, body, x.astype(jnp.bfloat16)
                                 ).astype(jnp.float32)

    f = jax.jit(program)
    y = f(x)
    check(bool(np.isfinite(np.asarray(y[0, 0]))), "D: non-finite result")
    durations = {}

    t0 = time.perf_counter()
    f(x).block_until_ready()
    durations["block_until_ready"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    np.asarray(f(x)[0, 0])
    durations["host_fetch"] = time.perf_counter() - t0

    model = register_model("smoke_fence", program, in_shapes=[(n, n)],
                           in_dtypes=np.float32)
    try:
        # only the warm-up dispatch is a blocking stats sample: the
        # timed one stays asynchronous up to the sink's EOS fence
        p = parse_launch(
            "appsrc name=src ! tensor_filter name=fence framework=jax-xla "
            f"model={model} stat-sample-interval-ms=3600000 ! "
            "tensor_sink name=out")
        p["src"].spec = TensorsSpec.from_shapes([(n, n)], np.float32)
        with p:
            p["src"].push_buffer(Buffer.of(x))
            deadline = time.monotonic() + PULL_TIMEOUT_S
            while p["out"].buffers_rendered < 1:
                check(time.monotonic() < deadline, "D: warm-up stalled")
                time.sleep(0.005)
            np.asarray(p["out"].last_buffer.tensors[0].jax()[0, 0])
            t0 = time.perf_counter()
            p["src"].push_buffer(Buffer.of(x))
            p["src"].end_of_stream()
            check(p.wait_eos(timeout=PULL_TIMEOUT_S), "D: no EOS")
            durations["sink_wait_eos"] = time.perf_counter() - t0
            check(p["out"].buffers_rendered == 2, "D: sink rendered "
                  f"{p['out'].buffers_rendered} of 2 buffers")
    finally:
        unregister_model(model)
    return durations, lower_bound_s


def section_d(n: int, target_s: float, peak_flops: float) -> dict:
    durations, lower_bound_s = measure_fence(n, target_s, peak_flops)
    out = {"lower_bound_s": round(lower_bound_s, 4),
           **{k: round(v, 4) for k, v in durations.items()}}
    # set-up facts, printed before the verdict so a FAIL still shows them
    print(f"D: seconds waited: {json.dumps(out)}", flush=True)
    fence_verdict(durations, lower_bound_s)
    return out


# -- E: four chips ----------------------------------------------------------------


def section_e(batch: int, size: int, num_classes: int, pool_batch: int,
              frames_per_stream: int, n_buffers: int = 2) -> dict:
    import jax

    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.decoders.boxutil import device_render_fn
    from nnstreamer_tpu.filters.jax_xla import (
        register_model,
        unregister_model,
    )
    from nnstreamer_tpu.obs.stagestat import STAGE_STATS
    from nnstreamer_tpu.obs.transfer import LEDGER
    from nnstreamer_tpu.runtime import Pipeline, parse_launch

    devs = jax.devices()[:4]
    check(len(set(devs)) == 4 and len({d.platform for d in devs}) == 1,
          f"E: devices {devs}")
    model = f"smoke_ssd_mesh_b{batch}"
    _register_ssd_pp(model, batch, size, num_classes)
    frame_model = "smoke_ssd_mesh_frame"
    _register_ssd_pp(frame_model, 1, size, num_classes)
    render = device_render_fn(batch, MAX_OUT, size, size, CONF)
    overlay = register_model(
        "smoke_overlay", lambda b, c, s, n: (render(b, c, s, n), b, c, s, n),
        in_shapes=[(batch, MAX_OUT, 4), (batch, MAX_OUT),
                   (batch, MAX_OUT), (batch,)],
        in_dtypes=[np.float32, np.int32, np.float32, np.int32])
    rng = np.random.default_rng(SEED + 4)
    frames = [rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8)
              for _ in range(2)]
    one_chip = ssd_reference(model, devs[0])
    want = [host(one_chip(jax.device_put(f, devs[0]))) for f in frames]
    agree = {}
    try:
        # 1. the composite over all four chips, its frames staged where
        #    the mesh filter reads them: no window is placed again
        placed0 = LEDGER.totals(direction="d2d", reason="input")[1]
        got, staged, info = run_composite(
            composite_launch(model, size, n_buffers, "mesh=data:4"),
            model, frames, n_buffers, "E: mesh")
        check(info["mesh"] is not None
              and set(info["mesh"].devices.flat) == set(devs),
              "E: mesh=data:4 is not laid over the four chips")
        for i, arr in enumerate(staged):
            check(len({s.device for s in arr.addressable_shards}) == 4,
                  f"E: mesh: staged frame {i} does not sit a quarter on "
                  "each chip")
        placed = LEDGER.totals(direction="d2d", reason="input")[1] - placed0
        check(placed == 0,
              f"E: mesh: {placed} bytes placed again over {n_buffers} "
              "windows (d2d.input), expected 0: placement not negotiated")
        dets = []
        for i, buf in enumerate(got):
            canvas, det = canvas_and_detections(buf)
            on_devices(canvas, devs, f"E: mesh buffer {i} canvas")
            check(len({s.device for s in canvas.addressable_shards}) == 4,
                  f"E: mesh buffer {i}: shards do not sit one per chip")
            dets.append(host(det))
        want_seq = stack_frames([want[i % 2] for i in range(n_buffers)])
        agree["mesh"] = detections_agree(stack_frames(dets), want_seq,
                                         "E: mesh")

        # 2. two stages on disjoint submeshes, HBM handoff between them
        pname = "smoke_split"
        p = parse_launch(
            f"device_src name=src num_buffers={n_buffers} ! "
            f"tensor_transform mode=arithmetic option={NORM} ! "
            f"tensor_filter name=a framework=jax-xla model={model} "
            "mesh=data:2 devices=0-1 ! "
            f"tensor_filter name=b framework=jax-xla model={overlay} "
            "mesh=data:2 devices=2-3 ! tensor_sink name=out",
            pipeline=Pipeline(name=pname))
        p["src"].frames, p["src"].pool_size = frames, len(frames)
        seen = []
        p["out"].connect(seen.append)
        with p:
            check(p.wait_eos(timeout=PULL_TIMEOUT_S), "E: split: no EOS")
            mesh_a = set(p["a"].subplugin._mesh.devices.flat)
            mesh_b = set(p["b"].subplugin._mesh.devices.flat)
        check(mesh_a == set(devs[:2]) and mesh_b == set(devs[2:]),
              f"E: stages landed on {sorted(d.id for d in mesh_a)} / "
              f"{sorted(d.id for d in mesh_b)}")
        check(len(seen) == n_buffers,
              f"E: split rendered {len(seen)} of {n_buffers} buffers")
        into_b = [r for r in LEDGER.snapshot()
                  if r["pipeline"] == pname and r["source"] == "b"]
        hops = sum(r["count"] for r in into_b
                   if r["direction"] in ("h2d", "d2h")
                   and r["reason"] in ("input", "drain"))
        check(hops == 0,
              f"E: {hops} host crossings at the stage boundary "
              f"({hops / n_buffers:.2f} per frame), expected 0")
        row = STAGE_STATS.get(pname, "b")
        check(row is not None and row["frames"] == n_buffers,
              f"E: handoff row {row}, expected {n_buffers} frames")
        dets = []
        for i, buf in enumerate(seen):
            arrs = [t.jax() for t in buf.tensors]
            for arr in arrs:
                on_devices(arr, devs[2:], f"E: split buffer {i} output")
            check(bool((arrs[0] == render(*arrs[1:])).all()),
                  f"E: split buffer {i} canvas is not the render of "
                  "its own detections")
            dets.append(host(arrs[1:]))
        agree["split"] = detections_agree(stack_frames(dets), want_seq,
                                          "E: split")

        # 3. the mesh shared pool: host frames, windows stacked once
        #    and sharded over the four chips
        depth = frames_per_stream + 4
        hframes = rng.integers(0, 256, (4, frames_per_stream,
                                        1, size, size, 3), dtype=np.uint8)
        outs, pool = run_streams(
            f"appsrc name=src max_buffers={depth} ! "
            f"queue max_size_buffers={depth} ! "
            f"tensor_filter name=pool_e framework=jax-xla "
            f"model={frame_model} share-model=true batch={pool_batch} "
            f"batch-buckets={pool_batch} batch-timeout-ms=2 mesh=data:4 ! "
            f"appsink name=out max_buffers={depth}",
            "pool_e",
            TensorsSpec.from_shapes([(1, size, size, 3)], np.float32),
            (hframes.astype(np.float32) - 127.5) / 127.5, "E: pool",
            lambda pool: {
                "devices": set(pool.subplugin._mesh.devices.flat),
                "stacked": any("stacked" in key
                               for key in pool.subplugin._batch_exec)})
        check(pool["devices"] == set(devs),
              "E: the pool's mesh is not the four chips")
        check(pool["stacked"],
              "E: the pool did not take the stacked window path")
        frame_ref = ssd_reference(frame_model, devs[0])
        got, ref_out = [], []
        for i, bufs in enumerate(outs):
            for k, buf in enumerate(bufs):
                got.append(host([t.jax() for t in buf.tensors]))
                ref_out.append(host(frame_ref(
                    jax.device_put(hframes[i, k], devs[0]))))
        agree["pool"] = detections_agree(
            stack_frames(got), stack_frames(ref_out), "E: pool")
    finally:
        for name in (model, frame_model, overlay):
            unregister_model(name)
    return agree


# -- the run ---------------------------------------------------------------------


def main() -> None:
    t_start = time.perf_counter()
    # the native wire codec is not on this path, and whatever .so lies
    # in the working tree was not built from the committed source here
    os.environ["NNS_TPU_NO_NATIVE"] = "1"
    import jax
    import jaxlib

    from nnstreamer_tpu.utils.jaxcache import enable_compile_cache

    cache_dir = enable_compile_cache()  # before the first compile
    dev = jax.devices()[0]
    count = len(jax.devices())
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no accelerator: JAX found platform="
            f"{dev.platform!r} ({dev.device_kind}, {count} device(s)); "
            "this script only runs on a TPU")

    from nnstreamer_tpu.obs.hwspec import spec_for_device_kind
    from nnstreamer_tpu.ops.kernels import _interpret

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # a label only; absent is a fact too
        libtpu = "not installed"
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"devices={count}")
    print(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}")
    print(f"compile cache: {cache_dir}")
    print("native codec: off (NNS_TPU_NO_NATIVE=1; not on this path)")
    spec = spec_for_device_kind(dev.device_kind)
    if spec is None:
        raise SystemExit(
            f"chip_smoke: FAIL: device kind {dev.device_kind!r} is not in "
            "the obs/hwspec.py peak table")
    if _interpret():
        raise SystemExit(
            "chip_smoke: FAIL: on a TPU, yet ops.kernels._interpret() is "
            "True: the Pallas kernels would run interpreted")
    print(f"peaks: {spec.name} ({spec.peak_flops / 1e12:.0f} TFLOP/s bf16)"
          f"; kernels: Mosaic; SSD preselect: approx top-k", flush=True)

    sections = [
        ("A", lambda: section_a(SSD_BATCH, SSD_SIZE, 91)),
        ("B", lambda: section_b(4, 24, 8, SSD_SIZE, 91)),
        ("C", lambda: section_c(
            VIT_BATCH, VIT_SIZE, VIT_PATCH, VIT_DIM,
            VIT_DEPTH, VIT_HEADS, VIT_MLP, 1000,
            (VIT_SIZE, VIT_SIZE, 3))),
        ("D", lambda: section_d(4096, 0.5, spec.peak_flops)),
    ]
    if count >= 4:
        sections.append(
            ("E", lambda: section_e(SSD_BATCH, SSD_SIZE, 91,
                                    8, 16)))
    meter = CompileMeter()
    for name, run in sections:
        s0, p0, h0 = meter.read()
        t0 = time.perf_counter()
        try:
            facts = run()
        except Exception as e:
            print(f"section {name}: FAIL after "
                  f"{time.perf_counter() - t0:.1f} s: "
                  f"{type(e).__name__}: {e}", flush=True)
            raise
        s1, p1, h1 = meter.read()
        print(f"section {name}: PASS  compile_s={s1 - s0:.1f} "
              f"programs={p1 - p0} cache_hits={h1 - h0} "
              f"wall_s={time.perf_counter() - t0:.1f}  "
              f"{json.dumps(facts)}", flush=True)
    if count < 4:
        print(f"section E: SKIP ({count} devices)")
    print(f"chip_smoke: all sections passed in "
          f"{time.perf_counter() - t_start:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
