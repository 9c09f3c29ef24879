"""Shared-model serving runtime (`runtime/serving.py` +
`tensor_filter share-model=true`).

Covers the ISSUE-3 acceptance surface: per-stream FIFO order and pts
integrity under concurrent streams with cross-stream dispatch
coalescing, pool refcount lifecycle (one pipeline stopping mid-stream
while the survivor keeps dispatching, restart-after-stop reattaching),
the SUPPORTS_BATCH-less shared-instance/per-frame fallback without
frame loss, pool-level batch-property conflict detection, per-stream
EOS flushing only that stream's parked frames, the adaptive idle-flush
window, and the satellite timing fixes (`_record_dispatch` blocking on
ALL outputs of a sampled dispatch).
"""

import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.filters.custom import (
    register_custom_easy,
    unregister_custom_easy,
)
from nnstreamer_tpu.filters.jax_xla import (
    JaxXlaFilter,
    register_model,
    unregister_model,
)
from nnstreamer_tpu.runtime import MODEL_POOL, Pipeline
from nnstreamer_tpu.runtime.serving import SharedBatcher
from nnstreamer_tpu.utils.stats import InvokeStats

SHAPE = (4,)
SPEC = TensorsSpec.from_shapes([SHAPE], np.float32)


@pytest.fixture(scope="module", autouse=True)
def _model():
    register_model("_t_serving", lambda x: x * 2.0 + 1.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    yield
    unregister_model("_t_serving")


@pytest.fixture(autouse=True)
def _pool_clean():
    yield
    # a failed test must not leak refcounts into the next one
    MODEL_POOL.clear()
    with JaxXlaFilter._shared_lock:
        JaxXlaFilter._shared_instances.clear()


def _frame(stream: int, i: int) -> Buffer:
    # stream-tagged values so demux mixups are detectable, not just
    # ordering slips
    return Buffer.of(np.full(SHAPE, stream * 1000.0 + i, np.float32),
                     pts=i)


def _pipeline(tag: str, share=True, batch=8, timeout_ms=50.0, n_bufs=64,
              framework="jax-xla", model="_t_serving", buckets=""):
    p = Pipeline(name=f"p_{tag}")
    src = AppSrc(name="src", spec=SPEC, max_buffers=n_bufs + 4)
    q = Queue(name="q", max_size_buffers=n_bufs + 4)
    flt = TensorFilter(name="net", framework=framework, model=model,
                       batch=batch, batch_timeout_ms=timeout_ms,
                       batch_buckets=buckets, share_model=share)
    sink = AppSink(name="out", max_buffers=n_bufs + 4)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    return p, src, flt, sink


def _pull_all(sink, n, timeout=10.0):
    out = []
    for _ in range(n):
        b = sink.pull(timeout=timeout)
        assert b is not None, f"stream stalled after {len(out)}/{n} buffers"
        out.append(b)
    return out


def _check_stream(bufs, stream: int):
    """Per-stream FIFO + pts + value integrity."""
    for i, b in enumerate(bufs):
        assert b.pts == i, f"stream {stream}: pts {b.pts} at slot {i}"
        np.testing.assert_allclose(
            b.tensors[0].np(),
            np.full(SHAPE, (stream * 1000.0 + i) * 2.0 + 1.0),
            err_msg=f"stream {stream} frame {i}: wrong payload (demux "
                    f"mixed streams?)")


# -- acceptance: FIFO/pts under concurrent streams + coalescing --------------


def test_concurrent_streams_fifo_pts_and_cross_stream_coalescing():
    n_streams, n = 4, 40
    pipes = [_pipeline(str(s)) for s in range(n_streams)]
    for p, *_ in pipes:
        p.start()
    flt0 = pipes[0][2]
    assert flt0.pool_streams == n_streams
    # every filter shares ONE sub-plugin instance (one params copy)
    assert all(p[2].subplugin is flt0.subplugin for p in pipes)

    def produce(s):
        _, src, _, _ = pipes[s]
        for i in range(n):
            src.push_buffer(_frame(s, i))
        src.end_of_stream()

    threads = [threading.Thread(target=produce, args=(s,))
               for s in range(n_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for p, *_ in pipes:
        assert p.wait_eos(timeout=30)
    st = flt0.pool.stats
    assert st.total_frame_num == n_streams * n
    # cross-stream coalescing actually happened
    assert st.total_invoke_num < n_streams * n
    assert st.avg_stream_occupancy > 1.0
    for s, (p, _, flt, sink) in enumerate(pipes):
        outs = _pull_all(sink, n)
        _check_stream(outs, s)
        # the element's own frame count stays per-stream exact
        assert flt.invoke_stats.total_frame_num == n
        p.stop()
    assert len(MODEL_POOL) == 0


def test_mesh_pool_window_crosses_streams_and_splits_evenly():
    """A pool over ``mesh=data:2``: with coalescing paused, two frames
    from each of two streams park in ONE window; resumed, it is one
    dispatch of four frames, two a shard, no pad slot — and each
    stream gets its own frames back."""
    import jax

    from nnstreamer_tpu.obs.meshstat import MESH_STATS

    if jax.device_count() < 2:
        pytest.skip("needs two (virtual) devices")
    pipes = []
    for s in range(2):
        p = Pipeline(name=f"p_mesh{s}")
        src = AppSrc(name="src", spec=SPEC, max_buffers=8)
        flt = TensorFilter(name="net", framework="jax-xla",
                           model="_t_serving", batch=4,
                           batch_timeout_ms=50.0, batch_buckets="4",
                           share_model=True, mesh="data:2")
        sink = AppSink(name="out", max_buffers=8)
        p.add(src, flt, sink).link(src, flt, sink)
        p.start()
        pipes.append((p, src, flt, sink))
    try:
        entry = pipes[0][2].pool
        row0 = dict(MESH_STATS.get("_t_serving") or {})
        pause = entry.actuators()["coalescing"]
        pause.actuate(0.0)
        for i in range(2):
            for s, (_p, src, _f, _k) in enumerate(pipes):
                src.push_buffer(_frame(s, i))
        deadline = time.monotonic() + 10
        while entry.batcher.pending < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert entry.batcher.pending == 4
        pause.revert()
        for s, (_p, _src, _f, sink) in enumerate(pipes):
            _check_stream(_pull_all(sink, 2), s)
        st = entry.stats
        assert (st.total_invoke_num, st.total_frame_num) == (1, 4)
        assert st.avg_stream_occupancy == 2.0
        row = MESH_STATS.get("_t_serving")
        assert row["shards"] == 2 and row["imbalance"] == 0.0
        assert row["dispatches"] - row0.get("dispatches", 0) == 1
        assert row["frames"] - row0.get("frames", 0) == 4
        assert row["pad_slots"] - row0.get("pad_slots", 0) == 0
    finally:
        for p, *_ in pipes:
            p.stop()


# -- pool lifecycle edges ----------------------------------------------------


def test_one_pipeline_stops_midstream_survivor_keeps_dispatching():
    p1, s1, f1, k1 = _pipeline("a")
    p2, s2, f2, k2 = _pipeline("b")
    p1.start()
    p2.start()
    assert f1.subplugin is f2.subplugin
    assert f1.pool.refcount == 2
    n = 10
    for i in range(n):
        s1.push_buffer(_frame(1, i))
        s2.push_buffer(_frame(2, i))
    _check_stream(_pull_all(k1, n), 1)
    entry = f2.pool
    p1.stop()  # refcount drops, entry survives for the survivor
    assert len(MODEL_POOL) == 1
    assert entry.refcount == 1
    assert entry.attached_streams == 1
    for i in range(n, 2 * n):
        s2.push_buffer(_frame(2, i))
    s2.end_of_stream()
    assert p2.wait_eos(timeout=30)
    _check_stream(_pull_all(k2, 2 * n), 2)
    p2.stop()
    assert len(MODEL_POOL) == 0


def test_restart_after_stop_reattaches_cleanly():
    p1, s1, f1, k1 = _pipeline("a")
    p2, s2, f2, k2 = _pipeline("b")
    p1.start()
    p2.start()
    p1.stop()
    assert f1.subplugin is None and f1.pool is None
    p1.start()  # re-acquires the (still alive) entry and reattaches
    assert f1.subplugin is f2.subplugin
    assert f1.pool is f2.pool and f1.pool.refcount == 2
    assert f1.pool.attached_streams == 2
    n = 6
    for i in range(n):
        s1.push_buffer(_frame(1, i))
    s1.end_of_stream()
    assert p1.wait_eos(timeout=30)
    _check_stream(_pull_all(k1, n), 1)
    p1.stop()
    p2.stop()
    assert len(MODEL_POOL) == 0


def test_framework_without_supports_batch_falls_back_per_frame():
    """share-model on a SUPPORTS_BATCH-less framework: the instance is
    shared (one user object) but frames dispatch per-frame — none are
    parked, none are lost."""
    register_custom_easy("_t_serving_easy",
                         lambda ins: [ins[0] * 2.0 + 1.0],
                         in_spec=SPEC, out_spec=SPEC)
    try:
        p1, s1, f1, k1 = _pipeline("a", framework="custom-easy",
                                   model="_t_serving_easy", batch=4)
        p2, s2, f2, k2 = _pipeline("b", framework="custom-easy",
                                   model="_t_serving_easy", batch=4)
        p1.start()
        p2.start()
        assert f1.subplugin is f2.subplugin  # shared instance
        assert f1._pool_batched is False     # but no shared window
        assert f1.pool.batcher is None
        n = 8
        for i in range(n):
            s1.push_buffer(_frame(1, i))
            s2.push_buffer(_frame(2, i))
        s1.end_of_stream()
        s2.end_of_stream()
        assert p1.wait_eos(timeout=30) and p2.wait_eos(timeout=30)
        _check_stream(_pull_all(k1, n), 1)  # every frame arrived
        _check_stream(_pull_all(k2, n), 2)
        assert f1.invoke_stats.total_invoke_num == n  # per-frame dispatch
        p1.stop()
        p2.stop()
        assert len(MODEL_POOL) == 0
    finally:
        unregister_custom_easy("_t_serving_easy")


# -- pool-level property validation ------------------------------------------


def test_conflicting_batch_settings_across_sharers_rejected():
    p1, s1, f1, k1 = _pipeline("a", batch=4)
    p2, s2, f2, k2 = _pipeline("b", batch=8)  # disagrees with the pool
    p1.start()
    with pytest.raises(ValueError, match="conflict"):
        p2.start()
    p2.stop()
    p1.stop()
    assert len(MODEL_POOL) == 0


def test_sharer_with_incompatible_caps_rejected_not_reshaped():
    """A second sharer whose upstream caps mismatch the pooled model
    must fail ITS negotiation — not recompile the shared executable
    under the first sharer's feet — and its failed start must roll the
    pool refcount back without an explicit stop()."""
    from nnstreamer_tpu.runtime import NegotiationError

    p1, s1, f1, k1 = _pipeline("a")
    p1.start()
    wide = TensorsSpec.from_shapes([(8,)], np.float32)  # model wants (4,)
    p2 = Pipeline(name="p_bad")
    src2 = AppSrc(name="src", spec=wide, max_buffers=8)
    q2 = Queue(name="q")
    f2 = TensorFilter(name="net", framework="jax-xla", model="_t_serving",
                      batch=8, batch_timeout_ms=50.0, share_model=True)
    k2 = AppSink(name="out")
    p2.add(src2, q2, f2, k2).link(src2, q2, f2, k2)
    with pytest.raises(NegotiationError, match="identical input"):
        p2.start()
    # failed start released p2's acquisition (no leak, no stop() needed)
    assert f1.pool.refcount == 1
    # the survivor still dispatches on the untouched (4,) executable
    n = 5
    for i in range(n):
        s1.push_buffer(_frame(1, i))
    s1.end_of_stream()
    assert p1.wait_eos(timeout=30)
    _check_stream(_pull_all(k1, n), 1)
    p1.stop()
    assert len(MODEL_POOL) == 0


def test_share_model_rejects_invoke_dynamic_but_allows_updatable():
    # invoke-dynamic still conflicts (per-buffer reshapes under every
    # sharer); is-updatable is ALLOWED since the lifecycle layer —
    # reloads route through PoolEntry.reload_model (runtime/lifecycle)
    flt = TensorFilter(name="net", framework="jax-xla",
                       model="_t_serving", share_model=True,
                       invoke_dynamic=True)
    with pytest.raises(ValueError, match="share-model"):
        flt.open_fw()
    assert len(MODEL_POOL) == 0
    upd = TensorFilter(name="net2", framework="jax-xla",
                       model="_t_serving", share_model=True,
                       is_updatable=True)
    upd.open_fw()
    assert upd.pool is not None
    upd._pool_entry = None  # release without start/stop machinery
    MODEL_POOL.clear()
    assert len(MODEL_POOL) == 0


# -- SharedBatcher unit: per-stream flush ------------------------------------


def test_flush_stream_drains_only_that_streams_parked_frames():
    flushed = []
    sb = SharedBatcher(max_batch=4, timeout_s=1000.0,
                       flush_fn=flushed.extend, adaptive=False)
    # no start(): no timer, windows only move when we say so
    sb.submit_from("A", 1)
    sb.submit_from("B", 2)
    sb.submit_from("A", 3)
    sb.flush_stream("A")
    # items are (stream, frame, deadline, enqueue-ts) tuples
    # B's frame 2 arrived BEFORE A's last frame: it rides along (FIFO)
    assert [it[:2] for it in flushed] == [("A", 1), ("B", 2), ("A", 3)]
    sb.submit_from("B", 4)
    sb.flush_stream("A")  # nothing of A parked: B's window is untouched
    assert [it[:2] for it in flushed] == [("A", 1), ("B", 2), ("A", 3)]
    assert sb.pending_of("B") == 1
    sb.flush_stream("B")
    assert flushed[-1][:2] == ("B", 4)


def test_shared_batcher_preserves_per_stream_order_across_windows():
    flushed = []
    sb = SharedBatcher(max_batch=3, timeout_s=1000.0,
                       flush_fn=flushed.extend, adaptive=False)
    sb.start()
    n_producers, per = 4, 30

    def produce(pid):
        for i in range(per):
            sb.submit_from(pid, i)

    threads = [threading.Thread(target=produce, args=(pid,))
               for pid in range(n_producers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sb.flush()
    sb.stop()
    assert len(flushed) == n_producers * per
    for pid in range(n_producers):
        seq = [it[1] for it in flushed if it[0] == pid]
        assert seq == sorted(seq), f"stream {pid} reordered"


# -- adaptive window ----------------------------------------------------------


def test_adaptive_window_flushes_on_idle_device_before_deadline():
    """With a 60 s deadline a lone frame must still come out promptly:
    the idle device triggers the flush, not the timeout."""
    p, src, flt, sink = _pipeline("a", timeout_ms=60_000.0)
    with p:
        t0 = time.monotonic()
        src.push_buffer(_frame(0, 0))
        b = sink.pull(timeout=10.0)
        took = time.monotonic() - t0
        assert b is not None and b.pts == 0
        assert took < 5.0  # far below the 60 s deadline
        assert flt.pool.batcher.flushes_adaptive >= 1
        src.end_of_stream()
        assert p.wait_eos(timeout=30)
    assert len(MODEL_POOL) == 0


def test_plain_microbatcher_default_stays_deadline_driven():
    from nnstreamer_tpu.runtime.batching import MicroBatcher

    mb = MicroBatcher(max_batch=4, timeout_s=0.01, flush_fn=lambda b: None)
    assert mb.adaptive is False  # per-element batching is unchanged


# -- stats --------------------------------------------------------------------


def test_invoke_stats_stream_occupancy():
    st = InvokeStats()
    st.count(frames=8, streams=4)
    st.record(0.001, frames=2, streams=2)
    assert st.total_stream_num == 6
    assert st.avg_stream_occupancy == pytest.approx(3.0)
    assert st.avg_batch_occupancy == pytest.approx(5.0)
    empty = InvokeStats()
    assert empty.avg_stream_occupancy == 0.0


def test_pool_entry_stats_visible_on_element():
    p1, s1, f1, k1 = _pipeline("a")
    p2, s2, f2, k2 = _pipeline("b")
    p1.start()
    p2.start()
    n = 12
    for i in range(n):
        s1.push_buffer(_frame(1, i))
        s2.push_buffer(_frame(2, i))
    s1.end_of_stream()
    s2.end_of_stream()
    assert p1.wait_eos(timeout=30) and p2.wait_eos(timeout=30)
    _pull_all(k1, n)
    _pull_all(k2, n)
    assert f1.pool.stats is f2.pool.stats
    assert f1.pool.stats.total_frame_num == 2 * n
    assert f1.pool.stats.attached_streams == 2
    assert f1.pool_stream_occupancy >= 1.0
    p1.stop()
    p2.stop()


# -- satellite: sampled dispatch blocks on ALL outputs ------------------------


class _FakeOut:
    def __init__(self):
        self.blocked = 0

    def block_until_ready(self):
        self.blocked += 1


def test_record_dispatch_blocks_every_output_of_sampled_window():
    """The old micro-batch path blocked only on the LAST frame's outputs;
    on multi-output models the recorded latency could miss still-enqueued
    earlier outputs.  `_record_dispatch` drains the whole window."""
    flt = TensorFilter(name="net", framework="jax-xla", model="_t_serving")
    outs = [_FakeOut() for _ in range(6)]  # 3 frames x 2 outputs, flat
    flt._record_dispatch(list(outs), time.monotonic(), frames=3,
                         sample=True)
    assert all(o.blocked == 1 for o in outs)
    assert flt.invoke_stats.total_frame_num == 3
    assert flt.invoke_stats.total_invoke_num == 1
    assert flt._last_out is outs[-1]


def test_record_dispatch_unsampled_counts_without_blocking():
    flt = TensorFilter(name="net", framework="jax-xla", model="_t_serving")
    outs = [_FakeOut(), _FakeOut()]
    flt._record_dispatch(list(outs), time.monotonic(), frames=2,
                         sample=False)
    assert all(o.blocked == 0 for o in outs)
    assert flt.invoke_stats.total_frame_num == 2
    assert flt.invoke_stats.latency_us == -1  # no sample recorded
