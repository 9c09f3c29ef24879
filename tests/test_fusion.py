"""Transform↔filter fusion pass (runtime/fusion.py, SURVEY §7 stage 4):
a run of tensor_transform elements + a jax-xla tensor_filter compiles
into one XLA computation, with outputs identical to the unfused pipeline.
"""

from fractions import Fraction

import numpy as np
import pytest

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.transform import TensorTransform
from nnstreamer_tpu.filters.jax_xla import register_model, unregister_model
from nnstreamer_tpu.runtime import Pipeline


@pytest.fixture
def linear_model():
    import jax.numpy as jnp

    w = np.linspace(-1, 1, 12, dtype=np.float32).reshape(4, 3)

    def fn(params, x):
        return jnp.dot(x, params)

    name = register_model("fusion_linear", fn, params=w,
                          in_shapes=[(2, 4)], in_dtypes=np.float32)
    yield name
    unregister_model(name)


def run_pipeline(fuse: bool, model: str, arr: np.ndarray,
                 transforms=None):
    spec = TensorsSpec.from_shapes([arr.shape], arr.dtype,
                                   rate=Fraction(30))
    p = Pipeline(fuse=fuse)
    src = AppSrc(name="src", spec=spec)
    ts = transforms or [TensorTransform(
        name="norm", mode="arithmetic",
        option="typecast:float32,add:-127.5,div:127.5")]
    flt = TensorFilter(name="net", framework="jax-xla", model=model)
    sink = AppSink(name="out")
    p.add(src, *ts, flt, sink).link(src, *ts, flt, sink)
    with p:
        src.push_buffer(Buffer.of(arr, pts=0))
        src.end_of_stream()
        assert p.wait_eos(timeout=120)
        got = sink.pull(timeout=1)
    return got, ts, flt


class TestFusionCorrectness:
    def test_fused_matches_unfused(self, linear_model):
        arr = np.arange(8, dtype=np.uint8).reshape(2, 4)
        fused, ts_f, flt_f = run_pipeline(True, linear_model, arr)
        unfused, ts_u, flt_u = run_pipeline(False, linear_model, arr)
        assert all(t._fused for t in ts_f)
        assert flt_f._fused_pre and not flt_u._fused_pre
        assert not any(t._fused for t in ts_u)
        np.testing.assert_allclose(fused.tensors[0].np(),
                                   unfused.tensors[0].np(), rtol=1e-6)

    def test_multi_transform_run_fuses(self, linear_model):
        # transpose (2,4)<-(4,2) then normalize: two transforms, one program
        arr = np.arange(8, dtype=np.uint8).reshape(4, 2)
        ts = [
            TensorTransform(name="tr", mode="transpose", option="1:0:2:3"),
            TensorTransform(name="norm", mode="arithmetic",
                            option="typecast:float32,add:-127.5,div:127.5"),
        ]
        fused, ts_f, flt = run_pipeline(True, linear_model, arr,
                                        transforms=ts)
        assert len(flt._fused_pre) == 2
        ts_u = [
            TensorTransform(name="tr", mode="transpose", option="1:0:2:3"),
            TensorTransform(name="norm", mode="arithmetic",
                            option="typecast:float32,add:-127.5,div:127.5"),
        ]
        unfused, _, _ = run_pipeline(False, linear_model, arr,
                                     transforms=ts_u)
        # same program modulo fusion; matmul precision (bf16 on TPU)
        # is identical on both paths
        np.testing.assert_allclose(fused.tensors[0].np(),
                                   unfused.tensors[0].np(), rtol=1e-6)

    def test_same_dtype_chain_still_recompiles(self, linear_model):
        # float32→float32 chain: raw spec is caps-compatible with the
        # model's declared input, fusion must still specialize (the
        # compatible-spec shortcut would silently skip the prologue)
        arr = np.full((2, 4), 127.5 + 12.75, np.float32)
        fused, _, flt = run_pipeline(
            True, linear_model, arr,
            transforms=[TensorTransform(name="n", mode="arithmetic",
                                        option="add:-127.5,div:127.5")])
        assert flt._fused_pre
        unfused, _, _ = run_pipeline(
            False, linear_model, arr,
            transforms=[TensorTransform(name="n", mode="arithmetic",
                                        option="add:-127.5,div:127.5")])
        np.testing.assert_allclose(fused.tensors[0].np(),
                                   unfused.tensors[0].np(), rtol=1e-6)
        # and the prologue really ran: output differs from the un-normalized
        raw, _, _ = run_pipeline(False, linear_model, arr, transforms=[
            TensorTransform(name="n", mode="arithmetic", option="mul:1.0")])
        assert not np.allclose(fused.tensors[0].np(), raw.tensors[0].np())


def _two_boxes(x):
    """A deterministic toy detector: 2 boxes per frame."""
    import jax.numpy as jnp

    b = x.shape[0]
    boxes = jnp.tile(jnp.asarray(
        [[0.1, 0.1, 0.5, 0.5], [0.4, 0.4, 0.9, 0.9]],
        jnp.float32)[None], (b, 1, 1))
    classes = jnp.tile(jnp.asarray([1.0, 2.0])[None], (b, 1))
    scores = jnp.tile(jnp.asarray([0.9, 0.8])[None], (b, 1))
    num = jnp.full((b,), 2, jnp.int32)
    return boxes, classes, scores, num


class TestDecoderOverlayFusion:
    """Filter→decoder fusion (round-3 verdict #10): the bounding-box
    device overlay compiles INTO the filter's program — one dispatch
    for transform+model+NMS+overlay — with bytes identical to the
    unfused device path."""

    @pytest.fixture
    def detect_model(self):
        name = register_model("fusion_detect", _two_boxes,
                              in_shapes=[(2, 16, 16, 3)],
                              in_dtypes=np.float32)
        yield name
        unregister_model(name)

    def _run(self, fuse, model):
        from nnstreamer_tpu.elements.decoder import TensorDecoder

        spec = TensorsSpec.from_shapes([(2, 16, 16, 3)], np.float32,
                                       rate=Fraction(30))
        p = Pipeline(fuse=fuse)
        src = AppSrc(name="src", spec=spec)
        flt = TensorFilter(name="net", framework="jax-xla", model=model)
        dec = TensorDecoder(name="dec", mode="bounding_boxes",
                            option1="mobilenet-ssd-postprocess",
                            option4="32:32", option5="32:32",
                            option7="device")
        sink = AppSink(name="out")
        p.add(src, flt, dec, sink).link(src, flt, dec, sink)
        with p:
            src.push_buffer(Buffer.of(
                np.zeros((2, 16, 16, 3), np.float32)))
            src.end_of_stream()
            assert p.wait_eos(timeout=120)
            got = sink.pull(timeout=1)
            post_active = bool(flt._fused_post)
        return got, post_active

    def test_fused_matches_unfused_device_overlay(self, detect_model):
        fused, on = self._run(True, detect_model)
        unfused, off = self._run(False, detect_model)
        assert on and not off
        np.testing.assert_array_equal(fused[0].np(), unfused[0].np())
        assert fused[0].np().shape == (2, 32, 32, 4)
        # structured detections survive fusion as device arrays
        assert "detections_device" in fused.meta
        dd = fused.meta["detections_device"]
        assert np.asarray(dd["num"]).tolist() == [2, 2]

    def test_tee_between_filter_and_decoder_blocks_fusion(
            self, detect_model):
        from nnstreamer_tpu.elements.decoder import TensorDecoder
        from nnstreamer_tpu.runtime.registry import make

        spec = TensorsSpec.from_shapes([(2, 16, 16, 3)], np.float32,
                                       rate=Fraction(30))
        p = Pipeline(fuse=True)
        src = AppSrc(name="src", spec=spec)
        flt = TensorFilter(name="net", framework="jax-xla",
                           model=detect_model)
        tee = make("tee", el_name="t")
        dec = TensorDecoder(name="dec", mode="bounding_boxes",
                            option1="mobilenet-ssd-postprocess",
                            option4="32:32", option5="32:32",
                            option7="device")
        sink = AppSink(name="out")
        sink2 = AppSink(name="raw")
        p.add(src, flt, tee, dec, sink, sink2)
        p.link(src, flt, tee)
        p.link(tee, dec, sink)
        p.link(tee, sink2)
        with p:
            src.push_buffer(Buffer.of(
                np.zeros((2, 16, 16, 3), np.float32)))
            src.end_of_stream()
            assert p.wait_eos(timeout=120)
            assert not flt._fused_post  # tee consumer blocks fusion
            out = sink.pull(timeout=1)
        assert out[0].np().shape == (2, 32, 32, 4)

    def test_single_frame_no_num_model_fuses(self):
        """The epilogue accepts every layout the unfused device path
        accepts: single-frame (N,4) boxes and 3-output (no num) models
        (review finding: fusion must not reject what unfused ran)."""
        import jax.numpy as jnp

        from nnstreamer_tpu.elements.decoder import TensorDecoder

        def fn(x):
            boxes = jnp.asarray([[0.2, 0.2, 0.6, 0.6]], jnp.float32)
            return boxes, jnp.asarray([1.0]), jnp.asarray([0.9])

        register_model("fusion_detect_n4", fn, in_shapes=[(1, 8, 8, 3)],
                       in_dtypes=np.float32)
        try:
            outs = {}
            for fuse in (True, False):
                spec = TensorsSpec.from_shapes([(1, 8, 8, 3)], np.float32,
                                               rate=Fraction(30))
                p = Pipeline(fuse=fuse)
                src = AppSrc(name="src", spec=spec)
                flt = TensorFilter(name="net", framework="jax-xla",
                                   model="fusion_detect_n4")
                dec = TensorDecoder(name="dec", mode="bounding_boxes",
                                    option1="mobilenet-ssd-postprocess",
                                    option4="32:32", option5="32:32",
                                    option7="device")
                sink = AppSink(name="out")
                p.add(src, flt, dec, sink).link(src, flt, dec, sink)
                with p:
                    src.push_buffer(Buffer.of(
                        np.zeros((1, 8, 8, 3), np.float32)))
                    src.end_of_stream()
                    assert p.wait_eos(timeout=120)
                    outs[fuse] = sink.pull(timeout=1)
                    if fuse:
                        assert flt._fused_post
            np.testing.assert_array_equal(outs[True][0].np(),
                                          outs[False][0].np())
            assert outs[True][0].np().shape == (32, 32, 4)  # unbatched
        finally:
            unregister_model("fusion_detect_n4")

    def test_flexible_stream_withdraws_decoder_fusion(self, detect_model):
        """Per-buffer schemas can't pre-compile an overlay epilogue: the
        filter must withdraw the decoder fusion at negotiation and the
        decoder must render for itself (review finding: a stale
        fused_upstream flag would emit raw boxes as 'video')."""
        from nnstreamer_tpu.core import TensorFormat
        from nnstreamer_tpu.elements.decoder import TensorDecoder

        flex = TensorsSpec(format=TensorFormat.FLEXIBLE, rate=Fraction(30))
        p = Pipeline(fuse=True)
        src = AppSrc(name="src", spec=flex)
        flt = TensorFilter(name="net", framework="jax-xla",
                           model=detect_model, invoke_dynamic=False)
        dec = TensorDecoder(name="dec", mode="bounding_boxes",
                            option1="mobilenet-ssd-postprocess",
                            option4="32:32", option5="32:32",
                            option7="device")
        sink = AppSink(name="out")
        p.add(src, flt, dec, sink).link(src, flt, dec, sink)
        with p:
            src.push_buffer(Buffer.of(
                np.zeros((2, 16, 16, 3), np.float32)))
            src.end_of_stream()
            assert p.wait_eos(timeout=120)
            got = sink.pull(timeout=1)
            assert not flt._fused_post       # withdrew at negotiation
            assert not dec._decoder().fused_upstream
        # the decoder rendered for itself: real canvas, right dtype
        assert got[0].np().shape == (2, 32, 32, 4)
        assert got[0].np().dtype == np.uint8
        assert "detections_device" in got.meta

    def test_host_backend_not_fused(self, detect_model):
        from nnstreamer_tpu.elements.decoder import TensorDecoder

        spec = TensorsSpec.from_shapes([(2, 16, 16, 3)], np.float32,
                                       rate=Fraction(30))
        p = Pipeline(fuse=True)
        src = AppSrc(name="src", spec=spec)
        flt = TensorFilter(name="net", framework="jax-xla",
                           model=detect_model)
        dec = TensorDecoder(name="dec", mode="bounding_boxes",
                            option1="mobilenet-ssd-postprocess",
                            option4="32:32", option5="32:32")
        sink = AppSink(name="out")
        p.add(src, flt, dec, sink).link(src, flt, dec, sink)
        with p:
            src.push_buffer(Buffer.of(
                np.zeros((2, 16, 16, 3), np.float32)))
            src.end_of_stream()
            assert p.wait_eos(timeout=120)
            assert not flt._fused_post


class TestFusionGuards:
    def test_flexible_stream_unfuses(self, linear_model):
        """Per-buffer schemas can't pre-compile a prologue: the transform
        must withdraw from fusion at negotiation and run its chain itself
        (silent-drop regression: review finding r2)."""
        from nnstreamer_tpu.core import TensorFormat

        flex = TensorsSpec(format=TensorFormat.FLEXIBLE, rate=Fraction(30))
        p = Pipeline(fuse=True)
        src = AppSrc(name="src", spec=flex)
        t = TensorTransform(name="n", mode="arithmetic",
                            option="typecast:float32,add:-127.5,div:127.5")
        flt = TensorFilter(name="net", framework="jax-xla",
                           model=linear_model)
        sink = AppSink(name="out")
        p.add(src, t, flt, sink).link(src, t, flt, sink)
        arr = np.arange(8, dtype=np.uint8).reshape(2, 4)
        with p:
            src.push_buffer(Buffer.of(arr))
            src.end_of_stream()
            assert p.wait_eos(timeout=120)
            got = sink.pull(timeout=1)
        assert not t._fused           # withdrew during negotiation
        assert not flt._fused_pre     # chain returned to the transform
        # the normalize REALLY ran (raw uint8 would give a far bigger dot)
        unfused, _, _ = run_pipeline(False, linear_model,
                                     arr.astype(np.uint8))
        np.testing.assert_allclose(got.tensors[0].np(),
                                   unfused.tensors[0].np(), rtol=1e-6)

    def test_restart_rederives_fusion_state(self, linear_model):
        """Marks are reset each start: a transform reused in a fuse=False
        pipeline must not stay passthrough (one-way-latch regression)."""
        arr = np.arange(8, dtype=np.uint8).reshape(2, 4)
        fused, ts, _ = run_pipeline(True, linear_model, arr)
        t = ts[0]
        assert t._fused
        # reuse the same transform element in a fresh unfused pipeline
        t.sinkpad.unlink()
        t.srcpad.unlink()
        spec = TensorsSpec.from_shapes([arr.shape], arr.dtype,
                                       rate=Fraction(30))
        p = Pipeline(fuse=False)
        src = AppSrc(name="src", spec=spec)
        sink = AppSink(name="out")
        p.add(src, t, sink).link(src, t, sink)
        with p:
            src.push_buffer(Buffer.of(arr))
            src.end_of_stream()
            assert p.wait_eos(timeout=90)  # first jit can queue on device
            got = sink.pull(timeout=1)
        assert not t._fused
        want = (arr.astype(np.float32) - 127.5) / 127.5
        np.testing.assert_allclose(got.tensors[0].np(), want, rtol=1e-6)

    def test_custom_framework_not_fused(self):
        from nnstreamer_tpu.filters.custom import register_custom_easy

        register_custom_easy("fusion_passthrough", lambda xs: xs,
                             in_spec=TensorsSpec.from_shapes(
                                 [(2, 4)], np.float32),
                             out_spec=TensorsSpec.from_shapes(
                                 [(2, 4)], np.float32))
        spec = TensorsSpec.from_shapes([(2, 4)], np.float32,
                                       rate=Fraction(30))
        p = Pipeline(fuse=True)
        src = AppSrc(name="src", spec=spec)
        t = TensorTransform(name="n", mode="arithmetic", option="mul:2.0")
        flt = TensorFilter(name="net", framework="custom-easy",
                           model="fusion_passthrough")
        sink = AppSink(name="out")
        p.add(src, t, flt, sink).link(src, t, flt, sink)
        arr = np.ones((2, 4), np.float32)
        with p:
            src.push_buffer(Buffer.of(arr))
            src.end_of_stream()
            assert p.wait_eos(timeout=10)
            got = sink.pull(timeout=1)
        assert not t._fused and not flt._fused_pre
        np.testing.assert_allclose(got.tensors[0].np(), arr * 2.0)

    def test_tee_mid_run_limits_fusion(self, linear_model):
        """A transform whose OUTPUT also feeds a second consumer cannot
        be folded away; the pass must stop the run there."""
        from nnstreamer_tpu.elements.basic import Tee

        spec = TensorsSpec.from_shapes([(2, 4)], np.uint8,
                                       rate=Fraction(30))
        p = Pipeline(fuse=True)
        src = AppSrc(name="src", spec=spec)
        t1 = TensorTransform(name="t1", mode="arithmetic",
                             option="typecast:float32,div:127.5")
        tee = Tee(name="tee")
        t2 = TensorTransform(name="t2", mode="arithmetic",
                             option="mul:1.0")
        flt = TensorFilter(name="net", framework="jax-xla",
                           model=linear_model)
        sink = AppSink(name="out")
        side = AppSink(name="side")
        p.add(src, t1, tee, t2, flt, sink, side)
        p.link(src, t1, tee)
        p.link_pads("tee", "src_0", "t2", "sink")
        p.link(t2, flt, sink)
        p.link_pads("tee", "src_1", "side", "sink")
        arr = np.arange(8, dtype=np.uint8).reshape(2, 4)
        with p:
            src.push_buffer(Buffer.of(arr))
            src.end_of_stream()
            assert p.wait_eos(timeout=120)
            got = sink.pull(timeout=1)
        # t2 (downstream of the tee) may fuse; t1 must NOT
        assert not t1._fused
        assert got is not None


class TestFusedSegmentCapture:
    """Whole-graph capture: Pipeline.start() records a FusedSegment
    descriptor per collapsed segment, carrying the ordered chain digest
    the persistent compile cache keys on."""

    def test_prologue_segment_descriptor(self, linear_model):
        arr = np.arange(8, dtype=np.uint8).reshape(2, 4)
        _, ts, flt = run_pipeline(True, linear_model, arr)
        p = flt.pipeline
        assert len(p.fused_segments) == 1
        seg = p.fused_segments[0]
        assert seg.filter == "net"
        assert seg.transforms == ("norm",)
        assert seg.decoder is None
        assert seg.stages == 2
        assert seg.chain_digest.startswith("pre:arithmetic|")

    def test_unfused_pipeline_has_no_segments(self, linear_model):
        arr = np.arange(8, dtype=np.uint8).reshape(2, 4)
        _, _, flt = run_pipeline(False, linear_model, arr)
        assert flt.pipeline.fused_segments == []

    def test_full_segment_descriptor(self):
        import jax.numpy as jnp

        from nnstreamer_tpu.elements.decoder import TensorDecoder

        def fn(x):
            b = x.shape[0]
            boxes = jnp.tile(jnp.asarray(
                [[0.1, 0.1, 0.5, 0.5]], jnp.float32)[None], (b, 1, 1))
            classes = jnp.ones((b, 1), jnp.float32)
            scores = jnp.full((b, 1), 0.9, jnp.float32)
            num = jnp.ones((b,), jnp.int32)
            return boxes, classes, scores, num

        name = register_model("_t_seg_detect", fn,
                              in_shapes=[(2, 8, 8, 3)],
                              in_dtypes=np.float32)
        try:
            spec = TensorsSpec.from_shapes([(2, 8, 8, 3)], np.uint8,
                                           rate=Fraction(30))
            p = Pipeline(fuse=True)
            src = AppSrc(name="src", spec=spec)
            tr = TensorTransform(
                name="norm", mode="arithmetic",
                option="typecast:float32,div:255.0")
            flt = TensorFilter(name="net", framework="jax-xla",
                               model=name)
            dec = TensorDecoder(name="dec", mode="bounding_boxes",
                                option1="mobilenet-ssd-postprocess",
                                option4="16:16", option5="16:16",
                                option7="device")
            sink = AppSink(name="out")
            p.add(src, tr, flt, dec, sink).link(src, tr, flt, dec, sink)
            with p:
                src.push_buffer(Buffer.of(
                    np.zeros((2, 8, 8, 3), np.uint8)))
                src.end_of_stream()
                assert p.wait_eos(timeout=120)
                segs = list(p.fused_segments)
            assert len(segs) == 1
            seg = segs[0]
            assert (seg.filter, seg.transforms, seg.decoder) == \
                ("net", ("norm",), "dec")
            assert seg.stages == 3
            assert "pre:arithmetic|" in seg.chain_digest
            assert "post:bounding_boxes:mobilenet-ssd-postprocess" \
                in seg.chain_digest
        finally:
            unregister_model(name)


class TestFusedChainPersistCache:
    """PR-14 exclusion lifted: fused whole-graph programs participate
    in the persistent AOT cache, keyed by model digest + ordered chain
    digest — warm-process runs get persist_hit rows, and a changed
    stage config misses instead of wrongly hitting."""

    @staticmethod
    def _persist_hits():
        from nnstreamer_tpu.utils.stats import COMPILE_STATS

        return sum(r["count"] for r in COMPILE_STATS.snapshot()
                   if r["kind"] == "persist_hit")

    def test_fused_chain_warm_process_hits(self, tmp_path, monkeypatch,
                                           linear_model):
        from nnstreamer_tpu.runtime import compilecache

        monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
        arr = np.arange(8, dtype=np.uint8).reshape(2, 4)
        before = compilecache.CACHE_STATS.snapshot()
        hits0 = self._persist_hits()
        run_pipeline(True, linear_model, arr)  # cold: store
        mid = compilecache.CACHE_STATS.snapshot()
        assert mid["stores"] > before["stores"]
        assert self._persist_hits() == hits0
        run_pipeline(True, linear_model, arr)  # fresh filter: pure load
        after = compilecache.CACHE_STATS.snapshot()
        assert after["hits"] > mid["hits"]
        assert self._persist_hits() > hits0

    def test_changed_chain_config_misses(self, tmp_path, monkeypatch,
                                         linear_model):
        from nnstreamer_tpu.runtime import compilecache

        monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
        arr = np.full((2, 4), 4, np.float32)
        t1 = [TensorTransform(name="n", mode="arithmetic",
                              option="div:2.0")]
        run_pipeline(True, linear_model, arr, transforms=t1)
        mid = compilecache.CACHE_STATS.snapshot()
        # same model, different op chain: a new entry must be BUILT
        # (a wrong hit here would silently run the old prologue)
        t2 = [TensorTransform(name="n", mode="arithmetic",
                              option="div:4.0")]
        out, _, _ = run_pipeline(True, linear_model, arr, transforms=t2)
        after = compilecache.CACHE_STATS.snapshot()
        assert after["stores"] > mid["stores"]
        assert after["hits"] == mid["hits"]
        ref, _, _ = run_pipeline(False, linear_model, arr, transforms=[
            TensorTransform(name="n", mode="arithmetic",
                            option="div:4.0")])
        np.testing.assert_allclose(out.tensors[0].np(),
                                   ref.tensors[0].np(), rtol=1e-6)

    def test_undigestable_post_stays_out_of_cache(self, tmp_path,
                                                  monkeypatch,
                                                  linear_model):
        from nnstreamer_tpu.filters.api import FilterProps
        from nnstreamer_tpu.filters.jax_xla import JaxXlaFilter
        from nnstreamer_tpu.runtime import compilecache

        monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
        sp = JaxXlaFilter()
        sp.set_fused_post([lambda *outs: outs])  # no chain_digest
        before = compilecache.CACHE_STATS.snapshot()
        sp.configure(FilterProps(framework="jax-xla",
                                 model=linear_model))
        sp.invoke([np.zeros((2, 4), np.float32)])
        sp.close()
        after = compilecache.CACHE_STATS.snapshot()
        assert after["stores"] == before["stores"]


# -- one dispatch a window ----------------------------------------------------

#: windows streamed before the count opens, and windows counted
WARMUP, WINDOWS = 2, 6

_TRANSFORM = ("tensor_transform name=norm mode=arithmetic "
              "option=typecast:float32,add:-127.5,div:127.5 ! ")
_DECODER = ("tensor_decoder name=overlay mode=bounding_boxes "
            "option1=mobilenet-ssd-postprocess option4=16:16 option5=16:16 "
            "option7=device ! ")


def _toy_detector(name):
    register_model(name, _two_boxes, in_shapes=[(4, 16, 16, 3)],
                   in_dtypes=np.float32)
    return [np.full((4, 16, 16, 3), k, np.uint8) for k in range(2)]


def _toy_classifier(name):
    import jax.numpy as jnp

    w = np.linspace(-1, 1, 8 * 3 * 5, dtype=np.float32).reshape(-1, 5)
    register_model(name, lambda p, x: jnp.dot(x.reshape(4, -1), p),
                   params=w, in_shapes=[(4, 8, 3)], in_dtypes=np.float32)
    return [np.full((4, 8, 3), k, np.uint8) for k in range(2)]


def _toy_stateful(name):
    from tests.test_stateful_filter import ONES, _register

    _register(name)
    return [ONES]


#: the chain shapes of the benchmark's four cells (the SSD line, the ViT
#: line, the dsv2 line, the SSD line over a mesh): model maker, what
#: stands before and after the filter, the filter's own properties
_CELL_CHAINS = {
    "transform-filter-decoder": (_toy_detector, _TRANSFORM, _DECODER, ""),
    "transform-filter": (_toy_classifier, _TRANSFORM, "", ""),
    "stateful-filter": (_toy_stateful, "", "", ""),
    "transform-filter-decoder-mesh2": (_toy_detector, _TRANSFORM, _DECODER,
                                       " mesh=data:2"),
}


@pytest.mark.parametrize("chain", sorted(_CELL_CHAINS))
def test_fused_window_is_one_dispatch(chain):
    """What ``dispatches_per_window`` = 1.00 on the chip rests on,
    counted as ``benchmark/readers/dispatches_per_window.py`` counts
    it: every launch site of ``DISPATCH_STATS``, after the warm-up
    windows, over windows that were fenced.  The sink's callback runs
    on the streaming thread, so at the render of window k exactly the
    dispatches of windows 0..k have been counted."""
    import jax

    from nnstreamer_tpu.obs.transfer import LEDGER
    from nnstreamer_tpu.runtime import parse_launch
    from nnstreamer_tpu.utils.stats import COMPILE_STATS, DISPATCH_STATS

    make_model, pre, post, mesh = _CELL_CHAINS[chain]
    if mesh and jax.device_count() < 2:
        pytest.skip("needs two (virtual) devices")
    model = "fusion_" + chain.replace("-", "_")
    frames = make_model(model)
    marks = []      # one reading a window, taken as it is rendered

    def on_window(buf):
        jax.block_until_ready([t.jax() for t in buf.tensors])
        marks.append((DISPATCH_STATS.snapshot(),
                      COMPILE_STATS.total_compiles,
                      LEDGER.totals(reason="input")[0]
                      + LEDGER.totals(reason="drain")[0]))

    try:
        p = parse_launch(
            f"device_src name=src num_buffers={WARMUP + WINDOWS} ! {pre}"
            f"tensor_filter name=net framework=jax-xla model={model}{mesh}"
            f" ! {post}tensor_sink name=out")
        p["src"].frames, p["src"].pool_size = frames, len(frames)
        p["out"].connect(on_window)
        with p:
            assert p.wait_eos(timeout=120)
            segments = [(s.filter, s.transforms, s.decoder)
                        for s in p.fused_segments]
            assert (p["net"].subplugin._mesh is not None) == bool(mesh)
    finally:
        unregister_model(model)
    assert len(marks) == WARMUP + WINDOWS
    (d0, c0, x0), (d1, c1, x1) = marks[WARMUP - 1], marks[-1]
    delta = {site: d1[site] - d0.get(site, 0) for site in d1
             if d1[site] != d0.get(site, 0)}
    # all sites: a transform or a decoder that launched its own program
    # would show beside the filter's
    assert delta == {"filter": WINDOWS}
    assert c1 == c0, "a program was built inside the counted windows"
    assert x1 == x0, "a counted window crossed between host and device"
    # what stands around the filter went into its one segment
    assert segments == ([("net", ("norm",), "overlay" if post else None)]
                        if pre else [])
