"""Disaggregated pipeline-split serving (stage placement over the
device channel + conditional cascade offload).

Covers the ISSUE-18 acceptance surface: split-vs-fused parity
frame-for-frame through the REAL cascade element path (device_src →
detector → tensor_crop → tensor_if offload=then → classifier),
crossings staying at exactly 0.0 across the stage boundary with a
byte-exact ``d2d``/``handoff`` transfer-ledger row, tensor_if
FIFO/pts integrity under concurrent streams with mixed offload
decisions, and the PR-10/11-style race harness on stage-pool
start/stop churn.
"""

import threading

import numpy as np
import pytest

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
from nnstreamer_tpu.elements.condition import TensorIf
from nnstreamer_tpu.elements.crop import TensorCrop
from nnstreamer_tpu.elements.devicesrc import DeviceSrc
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.filters.jax_xla import (
    JaxXlaFilter,
    register_model,
    unregister_model,
)
from nnstreamer_tpu.obs.stagestat import STAGE_STATS
from nnstreamer_tpu.obs.transfer import LEDGER
from nnstreamer_tpu.parallel.placement import reset_subsets
from nnstreamer_tpu.runtime import MODEL_POOL, Pipeline

jax = pytest.importorskip("jax")

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="stage split needs the 8-chip (virtual) inventory")

SHAPE = (8, 8, 3)
CROP = (6, 6)                       # fixed region at (0,0)
CROP_SHAPE = (CROP[0], CROP[1], SHAPE[2])
CROP_BYTES = CROP[0] * CROP[1] * SHAPE[2] * 4
PERIOD = 4                          # frame values cycle 0..3
THRESHOLD = 3.0                     # det adds 1: {2,3} offload — half


@pytest.fixture(scope="module", autouse=True)
def _models():
    register_model("_t_stage_det", lambda x: x + 1.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    register_model("_t_stage_cls",
                   lambda x: (x * 2.0 + 1.0).sum(axis=(0, 1)),
                   in_shapes=[CROP_SHAPE], in_dtypes=np.float32)
    register_model("_t_stage_id", lambda x: x * 1.0,
                   in_shapes=[CROP_SHAPE], in_dtypes=np.float32)
    yield
    for n in ("_t_stage_det", "_t_stage_cls", "_t_stage_id"):
        unregister_model(n)


@pytest.fixture(autouse=True)
def _clean():
    yield
    # a failed test must not leak pool refcounts, claimed subsets or
    # stage rows into the next one
    MODEL_POOL.clear()
    with JaxXlaFilter._shared_lock:
        JaxXlaFilter._shared_instances.clear()
    STAGE_STATS.reset()
    reset_subsets()


def _drain(sink):
    out = []
    while True:
        b = sink.pull(timeout=0.2)
        if b is None:
            return out
        out.append(b)


# -- the miniature cascade --------------------------------------------------


def _cascade(tag, split, frames_n):
    """device_src (values cycling 0..3) → det (devices=0-3 when split)
    → tensor_crop → tensor_if (offload=then, ge 3.0) → cls
    (devices=4-7 when split) → off/keep sinks."""
    pname = f"stagesplit_{tag}"
    pool = [np.full(SHAPE, float(k), np.float32) for k in range(PERIOD)]
    p = Pipeline(name=pname)
    src = DeviceSrc(name="src", frames=pool, pool_size=PERIOD,
                    num_buffers=frames_n)
    info = AppSrc(name="regions",
                  spec=TensorsSpec.from_shapes([(1, 4)], np.uint32),
                  max_buffers=frames_n + 8)
    q1 = Queue(name="q1", max_size_buffers=64)
    det = TensorFilter(name="det", framework="jax-xla",
                       model="_t_stage_det", mesh="data:4",
                       devices="0-3" if split else "", batch=4,
                       batch_buckets="4", batch_timeout_ms=20.0,
                       share_model=True, stat_sample_interval_ms=0)
    crop = TensorCrop(name="crop")
    route = TensorIf(name="route", compared_value="A_VALUE",
                     compared_value_option="0:0",
                     supplied_value=str(THRESHOLD), operator="ge",
                     offload="then", then="PASSTHROUGH",
                     else_="PASSTHROUGH")
    q2 = Queue(name="q2", max_size_buffers=64)
    cls = TensorFilter(name="cls", framework="jax-xla",
                       model="_t_stage_cls", mesh="data:4",
                       devices="4-7" if split else "", batch=4,
                       batch_buckets="4", batch_timeout_ms=20.0,
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
    return p, info, sink_off, sink_keep, pname


def _feed(p, info, frames_n):
    region = np.array([[0, 0, CROP[1], CROP[0]]], np.uint32)
    p.start()
    for _ in range(frames_n):
        info.push_buffer(Buffer.of(region), timeout=60)
    info.end_of_stream()
    assert p.wait_eos(timeout=120), "cascade did not reach EOS"


def test_split_vs_fused_parity_frame_for_frame():
    """The split leg's outputs equal the fused leg's frame-for-frame —
    on BOTH branches — and match the analytic cascade exactly."""
    frames_n = 16
    outs = {}
    for tag, split in (("parity_split", True), ("parity_fused", False)):
        p, info, sink_off, sink_keep, _ = _cascade(tag, split, frames_n)
        try:
            _feed(p, info, frames_n)
            outs[tag] = (_drain(sink_off), _drain(sink_keep))
        finally:
            p.stop()
    off_s, keep_s = outs["parity_split"]
    off_f, keep_f = outs["parity_fused"]
    assert len(off_s) == len(off_f) == frames_n // 2
    assert len(keep_s) == len(keep_f) == frames_n // 2
    for a, b in zip(off_s + keep_s, off_f + keep_f):
        np.testing.assert_array_equal(a.tensors[0].np(), b.tensors[0].np())
    # analytic ground truth: values {2,3} offload, det adds 1, the
    # classifier sums (2v+1) over the 6x6 crop per channel — FIFO
    # order alternates 252, 324
    n = CROP[0] * CROP[1]
    want = [float((2 * (v + 1.0) + 1.0) * n) for v in (2.0, 3.0)]
    got = [float(b.tensors[0].np()[0]) for b in off_s]
    assert got == want * (frames_n // PERIOD)
    for i, b in enumerate(keep_s):  # kept frames: cropped det outs 1, 2
        np.testing.assert_array_equal(
            b.tensors[0].np(),
            np.full(CROP_SHAPE, float(i % 2 + 1.0), np.float32))


def test_split_crossings_zero_and_handoff_row_byte_exact():
    """The stage boundary never degrades to a drain/re-upload pair —
    crossings stay at exactly 0.0 — and the handoff leaves a
    byte-exact d2d ledger row plus a matching stage-stats row."""
    frames_n = 16
    p, info, sink_off, sink_keep, pname = _cascade("xzero", True, frames_n)
    x0 = LEDGER.totals(reason="input")[0] \
        + LEDGER.totals(reason="drain")[0]
    h0c, h0b = LEDGER.totals(direction="d2d", reason="handoff")
    try:
        _feed(p, info, frames_n)
        # measure BEFORE draining the sinks: pulling device-resident
        # frames to host np() records legitimate d2h drain rows
        x1 = LEDGER.totals(reason="input")[0] \
            + LEDGER.totals(reason="drain")[0]
        h1c, h1b = LEDGER.totals(direction="d2d", reason="handoff")
        assert x1 - x0 == 0, "stage handoff leaked a host crossing"
        assert h1c - h0c == frames_n // 2
        assert h1b - h0b == (frames_n // 2) * CROP_BYTES
        row = STAGE_STATS.get(pname, "cls")
        assert row is not None
        assert (row["from"], row["to"]) == ("0-3", "4-7")
        assert row["frames"] == frames_n // 2
        assert row["bytes"] == (frames_n // 2) * CROP_BYTES
        assert row["depth"] == 0, "inter-stage depth must drain to zero"
        orow = STAGE_STATS.get(pname, "route")
        assert orow["offloaded"] == frames_n // 2
        assert orow["kept"] == frames_n // 2
        assert orow["ratio"] == 0.5
        off, keep = _drain(sink_off), _drain(sink_keep)
        assert len(off) == len(keep) == frames_n // 2
    finally:
        p.stop()


def test_first_stage_frames_are_staged_on_its_own_chips():
    """Two plain stages behind ``device_src`` (chip_smoke.py section E's
    split path): the FIRST stage asks the source for its input layout,
    so its windows are staged on its own two chips and pass
    ``_stage_ingress`` and ``invoke`` untouched; the second stage's
    upstream is a filter, which drops the request, so the handoff
    between the stages is as it was."""
    from nnstreamer_tpu.elements.basic import TensorSink

    pname, frames_n, window = "stagesplit_first", 6, (4, 8, 8, 3)
    register_model("_t_stage_w", lambda x: x + 1.0,
                   in_shapes=[window], in_dtypes=np.float32)
    pool = [np.full(window, float(k), np.float32) for k in range(2)]
    p = Pipeline(name=pname)
    src = DeviceSrc(name="src", frames=pool, pool_size=2,
                    num_buffers=frames_n)
    a = TensorFilter(name="a", framework="jax-xla", model="_t_stage_w",
                     mesh="data:2", devices="0-1")
    b = TensorFilter(name="b", framework="jax-xla", model="_t_stage_w",
                     mesh="data:2", devices="2-3")
    out = TensorSink(name="out")
    seen = []
    out.connect(lambda buf: seen.append(buf.tensors[0].np()))
    p.add(src, a, b, out)
    p.link(src, a, b, out)
    LEDGER.clear()
    try:
        with p:
            assert p.wait_eos(timeout=120)
            staged = [slot[0] for slot in src._pool]
            want = a.subplugin._compiled.in_shardings[0]
        assert all(sorted(d.id for d in s.devices()) == [0, 1]
                   and want.is_equivalent_to(s.sharding, s.ndim)
                   for s in staged)
        rows = [(r["source"], r["direction"], r["reason"], r["count"])
                for r in LEDGER.snapshot() if r["pipeline"] == pname
                and r["reason"] in ("input", "handoff")]
        # nothing placed into the first stage; one handoff a window
        # into the second, and its reshard onto its own layout
        assert ("a", "d2d", "input", frames_n) not in rows
        assert not [r for r in rows if r[0] == "a"]
        assert ("b", "d2d", "handoff", frames_n) in rows
        assert STAGE_STATS.get(pname, "b")["frames"] == frames_n
        assert STAGE_STATS.get(pname, "a") is None
        assert len(seen) == frames_n
        for i, got in enumerate(seen):
            np.testing.assert_array_equal(
                got, np.full(window, float(i % 2) + 2.0, np.float32))
    finally:
        unregister_model("_t_stage_w")


def test_tensor_if_fifo_pts_concurrent_streams_mixed_offload():
    """Two concurrent streams route through tensor_if into ONE shared
    classifier pool on the 4-7 subset: per-stream FIFO order, pts and
    payload identity survive the mixed offload decisions."""
    frames_n = 24

    spec = TensorsSpec.from_shapes([CROP_SHAPE], np.float32)

    def _build(stream):
        p = Pipeline(name=f"stagesplit_if_{stream}")
        src = AppSrc(name="src", spec=spec, max_buffers=frames_n + 4)
        route = TensorIf(name="route", compared_value="A_VALUE",
                         compared_value_option="0:0",
                         supplied_value="2.0", operator="ge",
                         offload="then", then="PASSTHROUGH",
                         else_="PASSTHROUGH")
        q = Queue(name="q", max_size_buffers=frames_n + 4)
        cls = TensorFilter(name="cls", framework="jax-xla",
                           model="_t_stage_id", mesh="data:4",
                           devices="4-7", batch=4, batch_buckets="4",
                           batch_timeout_ms=20.0, share_model=True,
                           stat_sample_interval_ms=0)
        sink_off = AppSink(name="off", max_buffers=frames_n + 4)
        sink_keep = AppSink(name="keep", max_buffers=frames_n + 4)
        p.add(src, route, q, cls, sink_off, sink_keep)
        p.link(src, route)
        p.link_pads(route, "src_then", q, "sink")
        p.link(q, cls, sink_off)
        p.link_pads(route, "src_else", sink_keep, "sink")
        return p, src, sink_off, sink_keep

    def _frame(stream, i):
        # flat[0] routes (values {2,3} offload under ge 2.0); flat[1]
        # is a stream watermark so demux mixups are detectable, not
        # just ordering slips
        a = np.full(CROP_SHAPE, float(i % 4), np.float32)
        a.flat[1] = stream * 1000.0 + i
        return Buffer.of(a, pts=i)

    pipes = {s: _build(s) for s in (1, 2)}
    errors = []

    def pusher(stream):
        try:
            _, src, _, _ = pipes[stream]
            for i in range(frames_n):
                src.push_buffer(_frame(stream, i), timeout=60)
            src.end_of_stream()
        except Exception as exc:  # noqa: BLE001 - the assertion
            errors.append(exc)

    for p, *_ in pipes.values():
        p.start()
    try:
        threads = [threading.Thread(target=pusher, args=(s,))
                   for s in pipes]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        for p, *_ in pipes.values():
            assert p.wait_eos(timeout=120)
        exp_off = [i for i in range(frames_n) if i % 4 >= 2]
        exp_keep = [i for i in range(frames_n) if i % 4 < 2]
        for stream, (_, _, sink_off, sink_keep) in pipes.items():
            off, keep = _drain(sink_off), _drain(sink_keep)
            assert [b.pts for b in off] == exp_off
            assert [b.pts for b in keep] == exp_keep
            for b, i in zip(off, exp_off):
                assert float(b.tensors[0].np().flat[1]) \
                    == stream * 1000.0 + i
            for b, i in zip(keep, exp_keep):
                assert float(b.tensors[0].np().flat[1]) \
                    == stream * 1000.0 + i
    finally:
        for p, *_ in pipes.values():
            p.stop()


def test_stage_pool_start_stop_race_three_threads():
    """The PR-10/11 race harness on stage pools: 3 threads churning
    start/push/EOS/stop on the SAME staged subset while a keeper
    pipeline holds the pool entry alive — never a crash, never a lost
    frame."""
    spec = TensorsSpec.from_shapes([SHAPE], np.float32)

    def _stage_pipe(tag):
        p = Pipeline(name=f"stagesplit_race_{tag}")
        src = AppSrc(name="src", spec=spec, max_buffers=32)
        q = Queue(name="q", max_size_buffers=32)
        det = TensorFilter(name="det", framework="jax-xla",
                           model="_t_stage_det", mesh="data:4",
                           devices="0-3", batch=4, batch_buckets="4",
                           batch_timeout_ms=10.0, share_model=True,
                           stat_sample_interval_ms=0)
        sink = AppSink(name="sink", max_buffers=32)
        p.add(src, q, det, sink)
        p.link(src, q, det, sink)
        return p, src, sink

    rounds, per_round = 5, 4
    errors = []
    outcomes = {"frames": 0}
    lock = threading.Lock()

    def churn(tid):
        try:
            for r in range(rounds):
                p, src, sink = _stage_pipe(f"t{tid}_{r}")
                p.start()
                for i in range(per_round):
                    src.push_buffer(
                        Buffer.of(np.full(SHAPE, float(i), np.float32)),
                        timeout=30)
                src.end_of_stream()
                p.wait_eos(timeout=60, raise_on_error=False)
                got = len(_drain(sink))
                p.stop()
                with lock:
                    outcomes["frames"] += got
        except Exception as exc:  # noqa: BLE001 - the assertion
            errors.append(exc)

    # the keeper holds the staged pool entry (and its subset claim)
    # alive across rounds, so attach/detach races against a LIVE
    # entry, not just create/destroy cycles
    keeper, ksrc, ksink = _stage_pipe("keeper")
    keeper.start()
    threads = [threading.Thread(target=churn, args=(t,))
               for t in range(3)]
    for t in threads:
        t.start()
    try:
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        keeper.stop()
    assert not errors, errors
    assert outcomes["frames"] == 3 * rounds * per_round
