"""Performance observability (ISSUE 7): dispatch cost attribution,
and compile & executable-cache telemetry.

- phase-split exactness: host-prep + device + host-drain partitions the
  dispatch at shared clock reads, and prep + device IS the recorded
  invoke latency (same block_until_ready fence);
- compile counters: ``nns_compiles_total`` equals the true number of
  ``_compile`` / ``_compile_batched`` calls across the cold, reshape,
  reload and bucket paths;
- executable-cache export: a warm re-run scrapes ZERO new misses;
- the admission controller's p99 derives from the registry's exported
  latency histogram (private window only as detached-registry
  fallback).
"""

import io
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.filters.api import FilterProps
from nnstreamer_tpu.filters.jax_xla import JaxXlaFilter, register_model
from nnstreamer_tpu.obs.metrics import (
    ADMISSION_LATENCY_BUCKETS,
    REGISTRY,
    MetricsRegistry,
)
from nnstreamer_tpu.obs.tracer import LatencyTracer
from nnstreamer_tpu.runtime import Pipeline
from nnstreamer_tpu.runtime.admission import AdmissionController
from nnstreamer_tpu.runtime.events import Event, EventKind
from nnstreamer_tpu.runtime.serving import MODEL_POOL
from nnstreamer_tpu.utils.stats import COMPILE_STATS

SHAPE = (8,)


@pytest.fixture(autouse=True)
def _model():
    register_model("_t_cost", lambda x: x * 2.0 + 1.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    yield
    MODEL_POOL.clear()


def _pipeline(batch=1, name="cost", **flt_kw):
    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=spec, max_buffers=256)
    q = Queue(name="q", max_size_buffers=256)
    flt = TensorFilter(name="net", framework="jax-xla", model="_t_cost",
                       batch=batch, batch_timeout_ms=2.0,
                       batch_buckets=str(batch) if batch > 1 else "",
                       latency=1, **flt_kw)
    sink = AppSink(name="out", max_buffers=256)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    return p, src, flt, sink


def _run(src, sink, n):
    for i in range(n):
        src.push_buffer(Buffer.of(
            np.full(SHAPE, float(i % 5), np.float32), pts=i))
    for _ in range(n):
        assert sink.pull(timeout=30) is not None


# -- phase-split exactness ----------------------------------------------------


def test_phase_split_sums_to_invoke_latency_single_frame():
    """latency=1 samples every dispatch: the cumulative phase split
    must (a) partition each dispatch exactly (shared clock reads) and
    (b) have prep + device equal the recorded invoke latency within
    the 5% acceptance tolerance (the int-µs truncation of the latency
    accumulator is the only slack)."""
    p, src, flt, sink = _pipeline(name="cost_phase1")
    with p:
        _run(src, sink, 20)
        s = flt.invoke_stats.snapshot()
    ph = s["phase"]
    assert ph["samples"] == s["invokes"] == 20
    assert s["host_prep_us"] >= 0
    assert s["device_us"] > 0
    assert s["host_drain_us"] >= 0
    lat_total_s = flt.invoke_stats.total_invoke_latency_us / 1e6
    prep_dev = ph["host_prep_s"] + ph["device_s"]
    assert prep_dev == pytest.approx(lat_total_s, rel=0.05)
    # drain is real and separate: the full split covers more than the
    # recorded latency, by exactly the drain term
    full = prep_dev + ph["host_drain_s"]
    assert full >= lat_total_s


def test_phase_split_batched_and_registry_histograms():
    """The micro-batched path attributes phases per window and exports
    them as nns_invoke_{device,host}_seconds histograms whose sums
    agree with the element's own InvokeStats phase accumulators."""
    fam_dev = REGISTRY.collect().get("nns_invoke_device_seconds", {})
    before = sum(s["value"] for s in fam_dev.get("samples", [])
                 if s.get("name", "").endswith("_sum")
                 and s["labels"].get("source") == "net_cost_b")
    p, src, flt, sink = _pipeline(batch=4, name="cost_phaseb")
    flt.name = "net_cost_b"  # unique registry label for this test
    with p:
        _run(src, sink, 32)
        s = flt.invoke_stats.snapshot()
        fams = REGISTRY.collect()
    ph = s["phase"]
    assert ph["samples"] == s["invokes"] > 0
    assert s["frames"] == 32

    def hist_sum(name, **match):
        total = 0.0
        for sample in fams[name]["samples"]:
            if not sample.get("name", "").endswith("_sum"):
                continue
            if all(sample["labels"].get(k) == v
                   for k, v in match.items()):
                total += sample["value"]
        return total

    dev = hist_sum("nns_invoke_device_seconds", source="net_cost_b",
                   kind="element", bucket="4") - before
    host_prep = hist_sum("nns_invoke_host_seconds",
                         source="net_cost_b", phase="prep")
    host_drain = hist_sum("nns_invoke_host_seconds",
                          source="net_cost_b", phase="drain")
    assert dev == pytest.approx(ph["device_s"], rel=0.05)
    assert host_prep == pytest.approx(ph["host_prep_s"], rel=0.05) \
        or ph["host_prep_s"] < 1e-4
    assert host_drain == pytest.approx(ph["host_drain_s"], rel=0.05) \
        or ph["host_drain_s"] < 1e-4


def test_pool_dispatch_phase_split():
    """SharedBatcher dispatches attribute phases on the POOL stats."""
    p1, s1, f1, k1 = _pipeline(batch=4, name="cost_poolA",
                               share_model=True)
    p2, s2, f2, k2 = _pipeline(batch=4, name="cost_poolB",
                               share_model=True)
    p1.start()
    p2.start()
    try:
        for i in range(8):
            s1.push_buffer(Buffer.of(np.zeros(SHAPE, np.float32), pts=i))
            s2.push_buffer(Buffer.of(np.zeros(SHAPE, np.float32), pts=i))
        got = 0
        deadline = time.monotonic() + 20
        while got < 16 and time.monotonic() < deadline:
            if k1.pull(timeout=0.2) is not None:
                got += 1
            if k2.pull(timeout=0.2) is not None:
                got += 1
        assert got == 16
        stats = f1.pool.stats.snapshot()
        assert stats["phase"]["samples"] > 0
        assert stats["device_us"] > 0
    finally:
        p1.stop()
        p2.stop()


def test_chrome_trace_carries_invoke_subphases():
    """The Perfetto export nests host-prep/device/host-drain spans
    inside the frame lane, contained by the frame span."""
    p, src, flt, sink = _pipeline(batch=4, name="cost_trace")
    with LatencyTracer(sample_every=1) as tr:
        with p:
            _run(src, sink, 16)
    ct = tr.chrome_trace()
    names = {e["name"] for e in ct["traceEvents"]}
    assert {"net:host-prep", "net:device", "net:host-drain"} <= names
    by_tid = {}
    for e in ct["traceEvents"]:
        by_tid.setdefault(e["tid"], []).append(e)
    checked = 0
    for evs in by_tid.values():
        frames = [e for e in evs if e["cat"] == "frame"]
        phases = [e for e in evs if e["cat"] == "phase"
                  and e["name"].startswith("net:")]
        if not frames or not phases:
            continue
        f = frames[0]
        for e in phases:
            assert e["ts"] >= f["ts"] - 1
            assert e["ts"] + e["dur"] <= f["ts"] + f["dur"] + 1
        checked += 1
    assert checked > 0


# -- compile telemetry --------------------------------------------------------


def _totals():
    rows = COMPILE_STATS.snapshot()
    return {(r["kind"], r["bucket"]): r["count"] for r in rows
            if r["framework"] == "jax-xla"}


def test_compile_counter_matches_compile_calls():
    """One count per _compile/_compile_batched call, labeled by path:
    cold (configure), reshape (set_input_info), reload (hot swap),
    bucket (micro-batch executable) — and the registry exports the
    same totals."""
    register_model("_t_cost_b", lambda x: x - 1.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    before = _totals()
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model="_t_cost",
                             is_updatable=True))
    sp.set_input_info(TensorsSpec.from_shapes([(4,)], np.float32))
    sp.invoke_batched([[np.zeros((4,), np.float32)]] * 2, 2)
    sp.invoke_batched([[np.zeros((4,), np.float32)]] * 2, 2)  # cache hit
    sp.invoke_batched([[np.zeros((4,), np.float32)]], 1)
    sp.handle_event(Event(EventKind.RELOAD_MODEL,
                          data={"model": "_t_cost_b"}))
    sp.invoke_batched([[np.zeros((4,), np.float32)]] * 2, 2)  # warm hit
    after = _totals()

    def delta(kind, bucket="0"):
        return after.get((kind, bucket), 0) - before.get((kind, bucket), 0)

    assert delta("cold") == 1
    assert delta("reshape") == 1
    assert delta("reload") == 1
    # the double-buffered reload (runtime/lifecycle.py) pre-compiles
    # every HOT bucket off the dispatch path, so both live buckets
    # recompile at reload time and the post-reload window is a cache
    # hit instead of an on-path build
    assert delta("bucket", "2") == 2  # initial + off-path reload warm
    assert delta("bucket", "1") == 2  # initial + off-path reload warm
    # registry export agrees with the pull source
    fam = REGISTRY.collect()["nns_compiles_total"]
    exported = sum(s["value"] for s in fam["samples"]
                   if s["labels"]["framework"] == "jax-xla")
    assert exported == COMPILE_STATS.total_compiles \
        - sum(r["count"] for r in COMPILE_STATS.snapshot()
              if r["framework"] != "jax-xla")
    assert COMPILE_STATS.total_seconds > 0
    sp.close()


def test_compile_seconds_include_first_call():
    """The lazy XLA build lands on the executable's first invocation;
    the wrapper attributes it to the compile row (seconds strictly
    grow after the first invoke)."""
    before = {(r["kind"], r["bucket"]): r["seconds"]
              for r in COMPILE_STATS.snapshot()}
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model="_t_cost"))
    mid = {(r["kind"], r["bucket"]): r["seconds"]
           for r in COMPILE_STATS.snapshot()}
    sp.invoke([np.zeros(SHAPE, np.float32)])
    after = {(r["kind"], r["bucket"]): r["seconds"]
             for r in COMPILE_STATS.snapshot()}
    key = ("cold", "0")
    assert mid[key] > before.get(key, 0.0)
    assert after[key] > mid[key]
    sp.close()


# -- executable-cache export --------------------------------------------------


def test_executable_cache_export_warm_rerun_zero_misses():
    """The per-bucket hit/miss counters scrape through the registry;
    a warm re-run adds hits but ZERO new misses."""
    p, src, flt, sink = _pipeline(batch=4, name="cost_cache")

    def scrape():
        fams = REGISTRY.collect()
        out = {}
        for metric in ("nns_executable_cache_hits_total",
                       "nns_executable_cache_misses_total"):
            total = 0
            for s in fams.get(metric, {}).get("samples", []):
                if s["labels"].get("element") == "net" and \
                        s["labels"].get("pipeline") == "cost_cache":
                    total += s["value"]
            out[metric] = total
        return out

    with p:
        _run(src, sink, 16)
        warm = scrape()
        assert warm["nns_executable_cache_misses_total"] == 1
        _run(src, sink, 16)
        rerun = scrape()
    assert rerun["nns_executable_cache_misses_total"] == \
        warm["nns_executable_cache_misses_total"]  # 0 NEW misses
    assert rerun["nns_executable_cache_hits_total"] > \
        warm["nns_executable_cache_hits_total"]


# -- admission: p99 from the exported histogram ------------------------------


def test_admission_p99_reads_exported_histogram():
    reg = MetricsRegistry()
    hist = reg.histogram("nns_admission_latency_seconds", "t",
                         labelnames=("pool",),
                         buckets=ADMISSION_LATENCY_BUCKETS
                         ).labels(pool="t")
    adm = AdmissionController(slo_s=0.03, hist=hist)
    for _ in range(64):
        adm.observe(0.012)
    # bucket-derived estimate: inside the (0.01, 0.015] bucket
    assert 0.010 <= adm.p99_s <= 0.015
    assert not adm.at_risk
    # the exported exposition carries the SAME signal
    expo = reg.exposition()
    assert 'nns_admission_latency_seconds_bucket' in expo
    assert 'pool="t"' in expo
    # tail into the ramp -> sheds arm, from histogram-derived p99
    adm.reset_signal()
    for _ in range(64):
        adm.observe(0.028)
    assert adm.at_risk and adm.shed_probability > 0.5


def test_admission_fallbacks():
    # detached registry: the private window is the signal (unchanged
    # legacy behavior)
    adm = AdmissionController(slo_s=0.1)
    for _ in range(64):
        adm.observe(0.5)
    assert adm.p99_s == 0.5
    # latencies past the last finite bucket: fall back to the window
    reg = MetricsRegistry()
    hist = reg.histogram("nns_admission_latency_seconds", "t",
                         labelnames=("pool",),
                         buckets=ADMISSION_LATENCY_BUCKETS
                         ).labels(pool="x")
    adm2 = AdmissionController(slo_s=0.05, hist=hist)
    for _ in range(64):
        adm2.observe(10.0)
    assert adm2.p99_s == 10.0
    assert adm2.shed_probability == 1.0


def test_pool_admission_feeds_registry_histogram():
    """The wired-up path: a share-model pool with slo-ms exports its
    serve latencies as nns_admission_latency_seconds{pool=...}."""
    p, src, flt, sink = _pipeline(batch=2, name="cost_adm",
                                  share_model=True, slo_ms=500.0)
    with p:
        _run(src, sink, 8)
        assert flt.pool.admission is not None
        assert flt.pool.admission._hist is not None
        fams = REGISTRY.collect()
        fam = fams["nns_admission_latency_seconds"]
        counts = [s["value"] for s in fam["samples"]
                  if s.get("name", "").endswith("_count")
                  and "jax-xla:_t_cost" in s["labels"].get("pool", "")]
    assert counts and max(counts) >= 8


# -- rendering -----------------------------------------------------------------


def test_nns_top_renders_dev_host_and_compile(capsys):
    from nnstreamer_tpu.obs.top import main as top_main

    p, src, flt, sink = _pipeline(name="cost_top")
    out = io.StringIO()
    with p:
        _run(src, sink, 8)
        rc = top_main(["--once", "--interval", "0.05",
                       "--connect", ""], out=out)
    text = out.getvalue()
    assert rc == 0
    for col in ("DEV µs", "HOST µs", "COMPILE", "KIND", "TOTAL ms"):
        assert col in text
    assert "jax-xla" in text  # the COMPILE section has rows
