"""Data-movement observability tests — ISSUE-8 surface.

Transfer-ledger byte-exactness (h2d and d2h, element and pool paths),
weight-placement accounting, pad-slot crossings, residency tagging and
the tracer's crossings-per-frame figure, Chrome-trace xfer sub-spans,
device-memory accounting (CPU-backend graceful fallback included),
flight-recorder trigger paths (element error, breaker open, admission
hard-shed, /dump endpoint), the snapshot-v6 shape and nns-top
XFER/DEVICE rendering.
"""

import json
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu.core import Buffer, Tensor, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.filters.jax_xla import register_model, unregister_model
from nnstreamer_tpu.obs import REGISTRY, LatencyTracer, hooks
from nnstreamer_tpu.obs import transfer as xfer
from nnstreamer_tpu.obs.devicemem import (
    device_memory_summary,
    device_memory_table,
)
from nnstreamer_tpu.obs.flightrec import FLIGHT, FlightRecorder
from nnstreamer_tpu.runtime import Pipeline

SHAPE = (4,)
FRAME_BYTES = 16  # 4 x float32


@pytest.fixture(scope="module", autouse=True)
def _model():
    register_model("_t_xfer", lambda x: x * 2.0 + 1.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    yield
    unregister_model("_t_xfer")


@pytest.fixture(autouse=True)
def _fresh_obs():
    xfer.set_enabled(True)
    xfer.LEDGER.clear()
    FLIGHT.clear()
    yield
    hooks.detach()
    xfer.set_enabled(True)
    FLIGHT.disarm()
    FLIGHT.min_dump_interval_s = 5.0


def _pipeline(name, batch=1, n=32, model="_t_xfer", buckets=""):
    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=spec, max_buffers=n + 4)
    q = Queue(name="q", max_size_buffers=n + 4)
    flt = TensorFilter(name="net", framework="jax-xla", model=model,
                       batch=batch, batch_timeout_ms=5.0,
                       batch_buckets=buckets)
    sink = AppSink(name="out", max_buffers=n + 4)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    return p, src, flt, sink


def _run(p, src, sink, n=16, drain=True):
    outs = []
    for i in range(n):
        src.push_buffer(Buffer.of(
            np.full(SHAPE, float(i), np.float32), pts=i))
    for _ in range(n):
        b = sink.pull(timeout=10)
        assert b is not None, f"stalled after {len(outs)}"
        if drain:
            for t in b.tensors:
                t.np()
        outs.append(b)
    src.end_of_stream()
    assert p.wait_eos(timeout=10)
    return outs


# -- ledger byte-exactness ----------------------------------------------------


def test_ledger_byte_exact_h2d_and_d2h():
    """Seed single-filter pipeline: h2d input bytes == N x frame
    nbytes (upload at the filter), d2h drain bytes == N x output
    nbytes — exact, warmup-free, and the registry export agrees."""
    n = 16
    p, src, flt, sink = _pipeline("xt_exact", n=n)
    p.start()
    try:
        _run(p, src, sink, n=n)
    finally:
        p.stop()
    h2d_count, h2d_bytes = xfer.LEDGER.totals(
        pipeline="xt_exact", direction="h2d", reason="input")
    assert (h2d_count, h2d_bytes) == (n, n * FRAME_BYTES)
    d2h_count, d2h_bytes = xfer.LEDGER.totals(
        direction="d2h", reason="drain")
    assert (d2h_count, d2h_bytes) == (n, n * FRAME_BYTES)
    # label context: the upload happened while the FILTER owned the buf
    rows = {(r["pipeline"], r["source"]): r
            for r in xfer.LEDGER.snapshot()
            if r["direction"] == "h2d" and r["reason"] == "input"}
    assert ("xt_exact", "net") in rows
    # exported flat counters derive from the same table
    snap = REGISTRY.snapshot()
    fam = snap["metrics"]["nns_transfer_bytes_total"]
    exported = sum(s["value"] for s in fam["samples"]
                   if s["labels"]["pipeline"] == "xt_exact"
                   and s["labels"]["direction"] == "h2d")
    assert exported == n * FRAME_BYTES
    assert "nns_transfer_seconds" in snap["metrics"]
    expo = REGISTRY.exposition()
    assert 'nns_transfer_bytes_total{direction="h2d"' in expo


def test_ledger_batched_feed_and_pad():
    """Micro-batched path: host frames fed to the batched executable
    count as h2d input; a partial window's pad-slot replays count
    under reason=pad."""
    n = 6  # batch=4, pinned bucket → one full window + one padded
    p, src, flt, sink = _pipeline("xt_batch", batch=4, n=n,
                                  buckets="4")
    p.start()
    try:
        _run(p, src, sink, n=n, drain=False)
    finally:
        p.stop()
    c_in, b_in = xfer.LEDGER.totals(
        pipeline="xt_batch", direction="h2d", reason="input")
    assert (c_in, b_in) == (n, n * FRAME_BYTES)
    c_pad, b_pad = xfer.LEDGER.totals(
        pipeline="xt_batch", direction="h2d", reason="pad")
    assert c_pad >= 1 and b_pad == c_pad * FRAME_BYTES


def test_ledger_weights_recorded():
    """Param placement (ModelDef device_put) records reason=weights
    with the exact pytree payload size."""
    w = np.ones((8,), np.float32)
    register_model("_t_xfer_w", lambda p, x: x * p["w"][0],
                   params={"w": w}, in_shapes=[SHAPE],
                   in_dtypes=np.float32)
    try:
        p, src, flt, sink = _pipeline("xt_w", model="_t_xfer_w", n=4)
        p.start()
        try:
            _run(p, src, sink, n=4, drain=False)
        finally:
            p.stop()
        c, b = xfer.LEDGER.totals(direction="h2d", reason="weights")
        assert c == 1 and b == w.nbytes
        assert flt.subplugin is None or True  # stopped; checked via pool
    finally:
        unregister_model("_t_xfer_w")


def test_ledger_disabled_records_nothing():
    xfer.set_enabled(False)
    t = Tensor(np.ones(SHAPE, np.float32))
    t.jax()
    assert xfer.LEDGER.snapshot() == []


# -- residency + tracer crossings --------------------------------------------


def test_buffer_residency_tagging():
    host = Buffer.of(np.ones(SHAPE, np.float32))
    assert host.residency == "host"
    t = Tensor(np.ones(SHAPE, np.float32))
    dev = Buffer(tensors=[Tensor(t.jax())])
    assert dev.residency == "device"
    mixed = Buffer(tensors=[Tensor(np.ones(SHAPE, np.float32)),
                            Tensor(t.jax())])
    assert mixed.residency == "mixed"


def test_tracer_crossings_per_frame_and_xfer_spans():
    """Host source → device filter output: exactly one residency flip
    per frame at the sink boundary, and the sampled frames carry
    ledger xfer sub-spans into the Chrome trace."""
    n = 8
    p, src, flt, sink = _pipeline("xt_trace", n=n)
    with LatencyTracer(sample_every=1) as tr:
        p.start()
        try:
            _run(p, src, sink, n=n, drain=False)
        finally:
            p.stop()
    s = tr.summary()
    assert s["count"] == n
    assert s["crossings_per_frame"] == pytest.approx(1.0)
    recs = tr.records()
    assert all(r["crossings"] == 1 for r in recs)
    assert any(r["xfers"] for r in recs)
    doc = tr.chrome_trace()
    cats = {e["cat"] for e in doc["traceEvents"]}
    assert "xfer" in cats
    names = {e["name"] for e in doc["traceEvents"]
             if e["cat"] == "xfer"}
    assert any(nm.startswith("net:h2d:input") for nm in names)
    assert any("residency host->device" in nm for nm in names)


# -- device memory ------------------------------------------------------------


class _FakeDev:
    def __init__(self, stats):
        self._stats = stats

    def __str__(self):
        return "FakeTPU:0"

    def memory_stats(self):
        if isinstance(self._stats, BaseException):
            raise self._stats
        return self._stats


def test_device_memory_table_fake_device():
    rows = device_memory_table(devices=[_FakeDev(
        {"bytes_in_use": 100, "peak_bytes_in_use": 200,
         "bytes_limit": 400})])
    assert rows == [{"device": "FakeTPU:0", "in_use": 100,
                     "peak": 200, "limit": 400}]
    summary = device_memory_summary(devices=[_FakeDev(
        {"bytes_in_use": 7})])
    assert summary == [{"device": "FakeTPU:0", "in_use": 7}]


def test_device_memory_cpu_backend_graceful():
    """The CPU backend reports None / raises — the table must degrade
    to empty, never error (and the real backend here IS cpu)."""
    assert device_memory_table(devices=[_FakeDev(None)]) == []
    assert device_memory_table(
        devices=[_FakeDev(NotImplementedError())]) == []
    import jax

    assert device_memory_table(devices=jax.devices()) in ([], [
        r for r in device_memory_table(devices=jax.devices())])
    # the registry snapshot carries the table either way
    assert isinstance(REGISTRY.snapshot()["device_memory"], list)


def test_pool_weight_bytes_exported():
    """share-model pool entries export their weight footprint."""
    w = np.ones((16,), np.float32)
    register_model("_t_xfer_pool", lambda p, x: x + p["w"][0],
                   params={"w": w}, in_shapes=[SHAPE],
                   in_dtypes=np.float32)
    try:
        spec = TensorsSpec.from_shapes([SHAPE], np.float32)
        p = Pipeline(name="xt_pool")
        src = AppSrc(name="src", spec=spec, max_buffers=8)
        flt = TensorFilter(name="net", framework="jax-xla",
                           model="_t_xfer_pool", share_model=True)
        sink = AppSink(name="out", max_buffers=8)
        p.add(src, flt, sink).link(src, flt, sink)
        p.start()
        try:
            snap = REGISTRY.snapshot()
            pool = [r for r in snap["pools"]
                    if "_t_xfer_pool" in r["pool"]][0]
            assert pool["weights"]["bytes"] == w.nbytes
            assert pool["weights"]["placement"] in (
                "host", "device", "mesh")
            fam = snap["metrics"]["nns_model_weight_bytes"]
            assert any(s["value"] == w.nbytes for s in fam["samples"])
        finally:
            p.stop()
    finally:
        unregister_model("_t_xfer_pool")


# -- flight recorder ----------------------------------------------------------


def _wait_dumps(n=1, deadline_s=10.0):
    """Dump writes are offloaded off the triggering thread
    (trigger_async) — poll for the files."""
    import time as _time

    t0 = _time.monotonic()
    while len(FLIGHT.dumps) < n and _time.monotonic() - t0 < deadline_s:
        _time.sleep(0.01)
    return FLIGHT.dumps


def _valid_dump(trace_path, snap_path):
    with open(trace_path) as f:
        trace = json.load(f)
    assert isinstance(trace["traceEvents"], list)
    with open(snap_path) as f:
        snap = json.load(f)
    assert snap["snapshot"]["version"] == 10
    return trace, snap


def test_flightrec_element_error_trigger(tmp_path):
    """An uncaught chain error reaching the bus dumps the black box."""
    from nnstreamer_tpu.runtime.element import TransformElement

    FLIGHT.arm(str(tmp_path))
    FLIGHT.min_dump_interval_s = 0.0

    class Boom(TransformElement):
        FACTORY = "t_boom"

        def transform(self, buf):
            raise RuntimeError("injected chain failure")

    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name="xt_err")
    src = AppSrc(name="src", spec=spec, max_buffers=8)
    boom = Boom(name="boom")
    sink = AppSink(name="out", max_buffers=8)
    p.add(src, boom, sink).link(src, boom, sink)
    p.start()
    try:
        src.push_buffer(Buffer.of(np.ones(SHAPE, np.float32), pts=0))
        deadline = 10.0
        import time as _time

        t0 = _time.monotonic()
        # the error dump is offloaded off the streaming thread — poll
        # for the written files, not just the trigger count
        while not FLIGHT.dumps \
                and _time.monotonic() - t0 < deadline:
            _time.sleep(0.01)
    finally:
        p.stop()
    assert FLIGHT.triggers.get("element-error", 0) >= 1
    assert FLIGHT.dumps, "armed trigger must write a dump"
    _valid_dump(*FLIGHT.dumps[0])
    kinds = {e["kind"] for e in FLIGHT.events()}
    assert "error" in kinds and "trigger" in kinds


def test_flightrec_breaker_open_trigger(tmp_path):
    from nnstreamer_tpu.chaos.retrypolicy import RetryPolicy

    FLIGHT.arm(str(tmp_path))
    FLIGHT.min_dump_interval_s = 0.0
    pol = RetryPolicy(name="t-link", fail_threshold=2, seed=1)
    pol.failure(RuntimeError("x"), what="dial")
    assert FLIGHT.triggers.get("breaker-open", 0) == 0
    pol.failure(RuntimeError("x"), what="dial")
    assert FLIGHT.triggers.get("breaker-open", 0) == 1
    assert _wait_dumps(), "armed trigger must write a dump"
    _valid_dump(*FLIGHT.dumps[-1])


def test_flightrec_hard_shed_trigger(tmp_path):
    """The shed feeder triggers a dump exactly when the ramp is at
    1.0 (hard shed)."""
    FLIGHT.arm(str(tmp_path))
    FLIGHT.min_dump_interval_s = 0.0
    FLIGHT.shed("jax-xla:m", "low", "slo", total_shed=3, hard=False)
    assert FLIGHT.triggers.get("admission-hard-shed", 0) == 0
    FLIGHT.shed("jax-xla:m", "low", "slo", total_shed=9, hard=True)
    assert FLIGHT.triggers.get("admission-hard-shed", 0) == 1
    assert _wait_dumps(), "armed trigger must write a dump"
    trace, snap = _valid_dump(*FLIGHT.dumps[-1])
    shed_marks = [e for e in trace["traceEvents"]
                  if e["name"].startswith("shed")]
    assert shed_marks and shed_marks[-1]["args"]["total_shed"] == 9


def test_flightrec_warn_shed_wiring(tmp_path):
    """serving._warn_shed feeds the recorder (hard=ramp saturated)."""
    from nnstreamer_tpu.runtime.admission import (
        AdmissionController,
        StreamPolicy,
    )
    from nnstreamer_tpu.runtime.serving import ModelPool, PoolEntry

    FLIGHT.arm(str(tmp_path))
    FLIGHT.min_dump_interval_s = 0.0

    class Owner:
        name = "own"

        def post_message(self, msg):
            self.last = msg

    entry = PoolEntry(ModelPool(), ("jax-xla", "m", ""), object(),
                      lambda sp: None)
    adm = AdmissionController(slo_s=0.001)
    for _ in range(64):
        adm.observe(1.0)  # p99 far past the SLO → ramp saturates
    assert adm.shed_probability >= 1.0
    entry.admission = adm
    owner = Owner()
    entry._warn_shed(owner, StreamPolicy(priority=2), adm,
                     reason="slo")
    assert FLIGHT.triggers.get("admission-hard-shed", 0) >= 1


def test_flightrec_dump_endpoint():
    from nnstreamer_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    srv = reg.serve(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/dump", timeout=5) as r:
            doc = json.loads(r.read().decode())
        assert isinstance(doc["trace"]["traceEvents"], list)
        assert doc["snapshot"]["version"] == 10
        assert FLIGHT.triggers.get("endpoint", 0) >= 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            hz = json.loads(r.read().decode())
        assert "device_memory" in hz
    finally:
        srv.close()


def test_flightrec_rate_limit_and_horizon():
    rec = FlightRecorder(max_events=4, horizon_s=0.0,
                         min_dump_interval_s=3600.0)
    for i in range(8):
        rec.note("k", f"e{i}")
    assert len(rec._events) == 4  # bounded ring
    assert rec.events() == []     # horizon 0: nothing recent enough
    assert rec.trigger("x") is None  # unarmed: no files
    assert rec.triggers["x"] == 1


# -- snapshot v6 + nns-top ----------------------------------------------------


def test_snapshot_v8_shape_golden():
    """The exact top-level snapshot shape: adding a table is a
    deliberate version bump, not a silent append (ISSUE-8 satellite;
    v5 added ``executables`` + ``mesh``, ISSUE-9; v6 added the
    ``control`` table, ISSUE-11; v7 added the ``models`` table —
    the lifecycle version registry, ISSUE-14; v8 adds the ``stages``
    table — pipeline-split handoff/offload rows, ISSUE-18; v9 adds
    ``tenants`` — per-tenant device-second/cost attribution — and
    ``forecasts`` — trend-forecast rule rows + capacity headroom,
    ISSUE-19; v10 adds ``profile`` — the host-execution profiler's
    per-element CPU/run/wait accounts + top stacks, ISSUE-20)."""
    snap = REGISTRY.snapshot()
    assert snap["version"] == 10
    assert sorted(snap.keys()) == [
        "compiles", "control", "device_memory", "executables",
        "forecasts", "host", "links", "mesh", "metrics", "models",
        "pipelines", "pools", "profile", "stages", "tenants", "time",
        "transfers", "version"]
    assert sorted(snap["profile"].keys()) == [
        "elements", "gil_waiters", "profiler", "stacks"]
    assert sorted(snap["control"].keys()) == [
        "actions_total", "audit", "controllers", "last_action",
        "playbooks"]
    for row in snap["transfers"]:
        assert sorted(row.keys()) == [
            "buckets", "bytes", "count", "direction", "pipeline",
            "reason", "seconds", "source"]


def test_nns_top_renders_xfer_and_devicemem():
    from nnstreamer_tpu.obs.top import render

    base = {"time": 100.0, "pipelines": [{
        "pipeline": "p", "playing": True, "elements": [{
            "element": "net", "factory": "tensor_filter",
            "stats": {"buffers_in": 10, "buffers_out": 10}}]}],
        "pools": [], "links": [], "compiles": [],
        "transfers": [{"pipeline": "p", "source": "net",
                       "direction": "h2d", "reason": "input",
                       "count": 10, "bytes": 640, "seconds": 0.0,
                       "buckets": []}],
        "device_memory": [{"device": "TPU:0", "in_use": 2_000_000,
                           "peak": 3_000_000, "limit": 8_000_000}]}
    cur = json.loads(json.dumps(base))
    cur["time"] = 101.0
    cur["pipelines"][0]["elements"][0]["stats"] = {
        "buffers_in": 20, "buffers_out": 20}
    cur["transfers"][0].update(count=20, bytes=1280)
    out = render(cur, base)
    assert "XFER B/s" in out and "X/FRAME" in out
    assert "DEVICE" in out and "TPU:0" in out
    row = [ln for ln in out.splitlines() if "net" in ln][0]
    # 640 B over 1 s, 10 crossings over 10 frames
    assert "640" in row and "1.00" in row
