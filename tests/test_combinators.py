"""Graph combinator tests: mux/merge/demux/split/join, sync policies,
aggregator, tensor_if, rate, repo loops, sparse enc/dec, crop.

Modeled on the reference suites tests/nnstreamer_mux, tests/nnstreamer_demux,
tests/nnstreamer_if, tests/nnstreamer_rate, tests/nnstreamer_repo_*,
tests/transform_* (SSAT golden pipelines → programmatic equivalents here).
"""

import numpy as np
import pytest
from fractions import Fraction

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc
from nnstreamer_tpu.elements.sync import Collector, SyncPolicy
from nnstreamer_tpu.runtime import Pipeline, make, parse_launch


SPEC = TensorsSpec.parse("4", "float32")


def frame(v, pts=None, n=4):
    return Buffer.of(np.full((n,), v, dtype=np.float32), pts=pts)


def two_in_one_out(factory, **props):
    p = Pipeline()
    a = AppSrc(name="a", spec=SPEC)
    b = AppSrc(name="b", spec=SPEC)
    el = make(factory, el_name="x", **props)
    sink = AppSink(name="out")
    p.add(a, b, el, sink)
    p.link_pads(a, "src", el, "sink_0")
    p.link_pads(b, "src", el, "sink_1")
    p.link(el, sink)
    return p, a, b, sink


def drain(sink):
    out = []
    while True:
        buf = sink.pull(timeout=0.2)
        if buf is None:
            return out
        out.append(buf)


class TestMux:
    def test_two_streams_become_two_tensor_frames(self):
        p, a, b, sink = two_in_one_out("tensor_mux")
        with p:
            for i in range(3):
                a.push_buffer(frame(i, pts=i * 100))
                b.push_buffer(frame(10 + i, pts=i * 100))
            a.end_of_stream()
            b.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        assert len(out) == 3
        assert out[0].num_tensors == 2
        assert out[2].tensors[1].np()[0] == 12.0

    def test_slowest_policy_drops_fast_pad_backlog(self):
        c = Collector(SyncPolicy.parse("slowest"), ["sink_0", "sink_1"])
        # fast pad: pts 0,10,20,30; slow pad arrives at pts 30
        for t in (0, 10, 20, 30):
            assert c.deposit("sink_0", frame(t, pts=t)) == []
        sets = c.deposit("sink_1", frame(99, pts=30))
        assert len(sets) == 1
        assert sets[0]["sink_0"].pts == 30  # older fast buffers dropped
        assert sets[0]["sink_1"].pts == 30

    def test_refresh_policy_reuses_quiet_pad(self):
        c = Collector(SyncPolicy.parse("refresh"), ["sink_0", "sink_1"])
        assert c.deposit("sink_0", frame(1, pts=0)) == []
        s1 = c.deposit("sink_1", frame(2, pts=0))
        assert len(s1) == 1
        # new data only on pad 0: pad 1's last buffer is reused
        s2 = c.deposit("sink_0", frame(3, pts=10))
        assert len(s2) == 1
        assert s2[0]["sink_1"].tensors[0].np()[0] == 2.0

    def test_basepad_policy(self):
        c = Collector(SyncPolicy.parse("basepad", "1:0"),
                      ["sink_0", "sink_1"])
        c.deposit("sink_0", frame(1, pts=0))
        c.deposit("sink_0", frame(2, pts=50))
        sets = c.deposit("sink_1", frame(9, pts=40))
        assert len(sets) == 1
        # base time 40 (pad 1): pad 0 contributes its pts<=40 buffer
        assert sets[0]["sink_0"].pts == 0


class TestMerge:
    def test_concat_innermost_dim(self):
        p, a, b, sink = two_in_one_out("tensor_merge", mode="linear",
                                       option="0")
        with p:
            a.push_buffer(frame(1))
            b.push_buffer(frame(2))
            a.end_of_stream()
            b.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        assert len(out) == 1
        got = out[0].tensors[0].np()
        np.testing.assert_array_equal(
            got, np.array([1, 1, 1, 1, 2, 2, 2, 2], np.float32))


class TestDemuxSplit:
    def test_demux_tensorpick_reorder(self):
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse(
            "4,4,4", "float32,float32,float32"))
        dm = make("tensor_demux", el_name="d", tensorpick="2,0")
        s0, s1 = AppSink(name="o0"), AppSink(name="o1")
        p.add(src, dm, s0, s1)
        p.link(src, dm)
        p.link_pads(dm, "src_0", s0, "sink")
        p.link_pads(dm, "src_1", s1, "sink")
        with p:
            src.push_buffer(Buffer.of(
                *[np.full((4,), i, np.float32) for i in range(3)]))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            b0, b1 = drain(s0), drain(s1)
        assert b0[0].tensors[0].np()[0] == 2.0  # pick 2 first
        assert b1[0].tensors[0].np()[0] == 0.0

    def test_split_by_tensorseg(self):
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse("6", "float32"))
        sp = make("tensor_split", el_name="s", tensorseg="2:4", dimension="0")
        s0, s1 = AppSink(name="o0"), AppSink(name="o1")
        p.add(src, sp, s0, s1)
        p.link(src, sp)
        p.link_pads(sp, "src_0", s0, "sink")
        p.link_pads(sp, "src_1", s1, "sink")
        with p:
            src.push_buffer(Buffer.of(
                np.arange(6, dtype=np.float32)))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            b0, b1 = drain(s0), drain(s1)
        np.testing.assert_array_equal(b0[0].tensors[0].np(), [0, 1])
        np.testing.assert_array_equal(b1[0].tensors[0].np(), [2, 3, 4, 5])

    def test_join_first_come_forward(self):
        p, a, b, sink = two_in_one_out("join")
        with p:
            a.push_buffer(frame(1))
            b.push_buffer(frame(2))
            a.push_buffer(frame(3))
            a.end_of_stream()
            b.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        # arrival order across the two source threads is not deterministic;
        # join must forward every buffer exactly once
        assert sorted(int(o.tensors[0].np()[0]) for o in out) == [1, 2, 3]


class TestAggregator:
    def test_batch_4_frames(self):
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse(
            "8:1", "float32", rate=Fraction(30)))
        ag = make("tensor_aggregator", el_name="agg", frames_in=1,
                  frames_out=4, frames_dim=0)
        sink = AppSink(name="out")
        p.add(src, ag, sink).link(src, ag, sink)
        with p:
            for i in range(8):
                src.push_buffer(Buffer.of(
                    np.full((1, 8), i, np.float32), pts=i))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        assert len(out) == 2
        assert out[0].tensors[0].shape == (1, 32)
        assert out[1].tensors[0].np()[0, 8] == 5.0

    def test_sliding_window_flush(self):
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse("2:1", "float32"))
        ag = make("tensor_aggregator", el_name="agg", frames_in=1,
                  frames_out=2, frames_flush=1, frames_dim=0)
        sink = AppSink(name="out")
        p.add(src, ag, sink).link(src, ag, sink)
        with p:
            for i in range(3):
                src.push_buffer(Buffer.of(np.full((1, 2), i, np.float32)))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        # windows: [0,1], [1,2] (overlap via flush=1)
        assert len(out) == 2
        np.testing.assert_array_equal(
            out[1].tensors[0].np(), [[1, 1, 2, 2]])


class TestIf:
    def _run_if(self, frames, **props):
        p = Pipeline()
        src = AppSrc(name="src", spec=SPEC)
        tif = make("tensor_if", el_name="i", **props)
        then_s, else_s = AppSink(name="t"), AppSink(name="e")
        p.add(src, tif, then_s, else_s)
        p.link(src, tif)
        p.link_pads(tif, "src_then", then_s, "sink")
        p.link_pads(tif, "src_else", else_s, "sink")
        with p:
            for f in frames:
                src.push_buffer(f)
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            return drain(then_s), drain(else_s)

    def test_average_threshold_routes_branches(self):
        t, e = self._run_if(
            [frame(1), frame(5), frame(2)],
            compared_value="TENSOR_AVERAGE_VALUE",
            compared_value_option="0", operator="ge", supplied_value="3",
            then="PASSTHROUGH", else_="PASSTHROUGH")
        assert [int(b.tensors[0].np()[0]) for b in t] == [5]
        assert [int(b.tensors[0].np()[0]) for b in e] == [1, 2]

    def test_else_fill_zero(self):
        t, e = self._run_if(
            [frame(5), frame(1)],
            compared_value="A_VALUE", compared_value_option="0:0",
            operator="gt", supplied_value="3",
            then="PASSTHROUGH", else_="FILL_ZERO")
        assert len(t) == 1 and len(e) == 1
        np.testing.assert_array_equal(e[0].tensors[0].np(), np.zeros(4))

    def test_custom_callback(self):
        from nnstreamer_tpu.elements.condition import (
            register_if_callback,
            unregister_if_callback,
        )

        register_if_callback("odd", lambda b: int(b.tensors[0].np()[0]) % 2)
        try:
            t, e = self._run_if(
                [frame(1), frame(2), frame(3)],
                compared_value="CUSTOM", compared_value_option="odd",
                then="PASSTHROUGH", else_="PASSTHROUGH")
            assert [int(b.tensors[0].np()[0]) for b in t] == [1, 3]
            assert [int(b.tensors[0].np()[0]) for b in e] == [2]
        finally:
            unregister_if_callback("odd")

    def test_range_operator_and_repeat_prev(self):
        t, e = self._run_if(
            [frame(5), frame(50), frame(7)],
            compared_value="A_VALUE", compared_value_option="0:0",
            operator="range_inclusive", supplied_value="0:10",
            then="PASSTHROUGH", else_="REPEAT_PREVIOUS_FRAME")
        assert [int(b.tensors[0].np()[0]) for b in t] == [5, 7]
        # else branch repeated nothing (no prior else frame) → empty
        assert e == []


class TestRate:
    def test_downsample_drops(self):
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse(
            "4", "float32", rate=Fraction(10)))
        rt = make("tensor_rate", el_name="r", framerate="5/1")
        sink = AppSink(name="out")
        p.add(src, rt, sink).link(src, rt, sink)
        SEC = 1_000_000_000
        with p:
            for i in range(10):  # 10 fps for 1s
                src.push_buffer(frame(i, pts=i * SEC // 10))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        assert len(out) == 5  # halved
        assert rt.drop_count == 5

    def test_upsample_duplicates(self):
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse(
            "4", "float32", rate=Fraction(5)))
        rt = make("tensor_rate", el_name="r", framerate="10/1")
        sink = AppSink(name="out")
        p.add(src, rt, sink).link(src, rt, sink)
        SEC = 1_000_000_000
        with p:
            for i in range(5):
                src.push_buffer(frame(i, pts=i * SEC // 5))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        assert len(out) == 9  # last slot has no following frame
        assert rt.dup_count == 4


class TestRepoLoop:
    def test_accumulator_feedback(self):
        """reposrc → transform(add 1) → tee → reposink + sink: a counter
        loop (parity: tests/nnstreamer_repo_dynamicity)."""
        from nnstreamer_tpu.elements.repo import REPO

        REPO.reset()
        p = parse_launch(
            "tensor_reposrc name=loop slot=0 num_buffers=5 "
            "caps=other/tensors,format=static,num_tensors=1,"
            "dimensions=1,types=float32,framerate=0/1 ! "
            "tensor_transform mode=arithmetic option=add:1 ! "
            "tee name=t ! tensor_reposink slot=0 t. ! appsink name=out")
        sink = p["out"]
        with p:
            # generous timeout: the transform's first jit can queue behind
            # other tests' work on a loaded host
            assert p.wait_eos(timeout=90)
            out = drain(sink)
        vals = [float(b.tensors[0].np().ravel()[0]) for b in out]
        assert vals == [1.0, 2.0, 3.0, 4.0, 5.0]


class TestSparse:
    def test_roundtrip_through_pipeline(self):
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse("8", "float32"))
        enc = make("tensor_sparse_enc", el_name="enc")
        dec = make("tensor_sparse_dec", el_name="dec")
        sink = AppSink(name="out")
        p.add(src, enc, dec, sink).link(src, enc, dec, sink)
        x = np.array([0, 0, 3, 0, 0, 0, 7, 0], np.float32)
        with p:
            src.push_buffer(Buffer.of(x))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        np.testing.assert_array_equal(out[0].tensors[0].np(), x)

    def test_sparse_payload_smaller_for_sparse_data(self):
        from nnstreamer_tpu.core.buffer import sparse_from_dense
        from nnstreamer_tpu.core import Tensor

        dense = np.zeros((1000,), np.float32)
        dense[3] = 1.0
        assert len(sparse_from_dense(Tensor(dense))) < dense.nbytes // 4


class TestCrop:
    def test_crop_regions(self):
        p = Pipeline()
        raw = AppSrc(name="raw", spec=TensorsSpec.parse("3:8:8", "uint8"))
        info = AppSrc(name="info", spec=TensorsSpec.parse("4:2", "uint32"))
        crop = make("tensor_crop", el_name="c")
        sink = AppSink(name="out")
        p.add(raw, info, crop, sink)
        p.link_pads(raw, "src", crop, "sink_raw")
        p.link_pads(info, "src", crop, "sink_info")
        p.link(crop, sink)
        img = np.arange(8 * 8 * 3, dtype=np.uint8).reshape(8, 8, 3)
        regions = np.array([[1, 2, 4, 3], [0, 0, 2, 2]], np.uint32)
        with p:
            raw.push_buffer(Buffer.of(img))
            info.push_buffer(Buffer.of(regions))
            raw.end_of_stream()
            info.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        assert len(out) == 1 and out[0].num_tensors == 2
        np.testing.assert_array_equal(
            out[0].tensors[0].np(), img[2:5, 1:5, :])
        np.testing.assert_array_equal(
            out[0].tensors[1].np(), img[0:2, 0:2, :])


class TestCapsScalarDims:
    def test_scalar_dimensions_caps_string_intersects(self):
        """Regression: dimensions=1 in a caps string must stay a string so
        the dimensions special-case in intersection applies."""
        from nnstreamer_tpu.core import Caps
        from nnstreamer_tpu.runtime.parser import parse_caps_string

        a = parse_caps_string(
            "other/tensors,format=static,num_tensors=1,dimensions=1,"
            "types=uint8,framerate=0/1")
        b = Caps.from_spec(TensorsSpec.parse("1", "uint8"))
        assert a.can_intersect(b)
        assert a.fixate().to_spec().tensors[0].dims == (1,)


class TestAggregatorBacklog:
    def test_fin_gt_fout_emits_all_windows(self):
        """Regression: frames_in > frames_out must emit every window, not
        one per input buffer."""
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse("4:1", "float32"))
        ag = make("tensor_aggregator", el_name="agg", frames_in=4,
                  frames_out=2, frames_dim=0)
        sink = AppSink(name="out")
        p.add(src, ag, sink).link(src, ag, sink)
        with p:
            for i in range(2):  # 8 frames total
                src.push_buffer(Buffer.of(
                    np.arange(4 * i, 4 * i + 4, dtype=np.float32
                              ).repeat(1).reshape(1, 4)))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        assert len(out) == 4  # 8 frames / 2 per window
        np.testing.assert_array_equal(out[3].tensors[0].np(), [[6, 7]])

    def test_concat_false_caps_match_payload(self):
        """Regression: concat=False must negotiate fout per-frame tensors."""
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse("4:1", "float32"))
        ag = make("tensor_aggregator", el_name="agg", frames_in=1,
                  frames_out=2, frames_dim=0, concat=False)
        sink = AppSink(name="out")
        p.add(src, ag, sink).link(src, ag, sink)
        with p:
            for i in range(2):
                src.push_buffer(Buffer.of(np.full((1, 4), i, np.float32)))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
            spec = ag.srcpad.spec  # read before stop clears pad caps
        assert spec.num_tensors == 2
        assert out[0].num_tensors == 2
        assert out[0].tensors[0].shape == (1, 4)


class TestRatePrevFrameSemantics:
    def test_gap_slots_carry_previous_frame(self):
        """Regression: upsampling duplicates the PREVIOUS frame into gap
        slots — content never appears earlier than its own pts."""
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse(
            "4", "float32", rate=Fraction(5)))
        rt = make("tensor_rate", el_name="r", framerate="10/1")
        sink = AppSink(name="out")
        p.add(src, rt, sink).link(src, rt, sink)
        SEC = 1_000_000_000
        with p:
            src.push_buffer(frame(0, pts=0))
            src.push_buffer(frame(1, pts=SEC // 5))
            src.end_of_stream()
            assert p.wait_eos(timeout=5)
            out = drain(sink)
        # slots: 0 (frame0), 0.1s (dup of frame0), 0.2s (frame1)
        vals = [(b.pts, int(b.tensors[0].np()[0])) for b in out]
        assert vals == [(0, 0), (SEC // 10, 0), (SEC // 5, 1)]
