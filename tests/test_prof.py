"""Host-execution profiler (``obs/prof.py``, ISSUE 20).

Covers the three pieces and their surfaces: the deterministic
thread-name registry (coverage on a RUNNING composite pipeline), the
sampling profiler (bounded table + eviction, registry attribution,
collapsed/Perfetto goldens via ``_record`` injection), the exact
per-element run/wait/CPU accounting (crafted slow-chain element;
cpu-sum vs ``time.process_time()``), alert-triggered deep profiles
(once per episode, rate-limited, disabled-inert), and the export
surfaces (snapshot-v10 ``profile`` table, flat families, ``/prof``
endpoint, flight-recorder ``host_stacks`` embed, nns-top PROF section,
the ``nns-prof`` CLI).
"""

import io
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
from nnstreamer_tpu.obs import REGISTRY
from nnstreamer_tpu.obs import prof
from nnstreamer_tpu.runtime import Pipeline

SHAPE = (4,)


@pytest.fixture(autouse=True)
def _clean_profiler_state():
    yield
    prof.PROFILER.stop()
    prof.PROFILER.clear()
    prof.PROFILER.configure(0.0)
    prof.DEEP.disarm()
    prof.DEEP.clear()
    prof._reset_accounts()


def _spec():
    return TensorsSpec.from_shapes([SHAPE], np.float32)


class SlowSink(AppSink):
    """Crafted run-side load: the chain spins ~spin_s of real CPU in
    the UPSTREAM element's loop thread before queueing the buffer."""

    spin_s = 0.01

    def chain(self, pad, buf):
        t0 = time.monotonic()
        while time.monotonic() - t0 < self.spin_s:
            pass
        return super().chain(pad, buf)


def _slow_pipeline(name):
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=_spec(), max_buffers=64)
    q = Queue(name="q", max_size_buffers=64)
    sink = SlowSink(name="out", max_buffers=64)
    p.add(src, q, sink).link(src, q, sink)
    return p, src, sink


# -- thread names + registry --------------------------------------------------


def test_thread_name_scheme():
    assert prof.thread_name("watch", "sampler") == "nns:watch:sampler"
    assert prof.thread_name("prof") == "nns:prof"
    assert prof.thread_name("src", "s", pipeline="p", element="e") \
        == "nns:p:e"


def test_named_thread_registers_and_unregisters():
    seen = {}
    release = threading.Event()

    def work():
        seen["info"] = prof.THREADS.lookup(threading.get_ident())
        seen["name"] = threading.current_thread().name
        release.wait(timeout=5)

    t = prof.named_thread("watch", "sampler", work)
    t.start()
    deadline = time.monotonic() + 5
    while "info" not in seen and time.monotonic() < deadline:
        time.sleep(0.01)
    assert seen["name"] == "nns:watch:sampler"
    assert seen["info"]["role"] == "watch"
    assert seen["info"]["owner"] == "sampler"
    assert seen["info"]["name"] == "nns:watch:sampler"
    ident = t.ident
    release.set()
    t.join(timeout=5)
    assert prof.THREADS.lookup(ident) is None  # gone with the thread


def test_registry_coverage_on_running_composite_pipeline():
    """Every runtime thread of a RUNNING composite pipeline carries
    the deterministic ``nns:`` name AND a registry entry — the join
    the profiler, lockdep labels and py-spy output all rely on."""
    p = Pipeline(name="profcov")
    src = AppSrc(name="src", spec=_spec(), max_buffers=32)
    q1 = Queue(name="q1", max_size_buffers=32)
    q2 = Queue(name="q2", max_size_buffers=32)
    sink = AppSink(name="out", max_buffers=32)
    p.add(src, q1, q2, sink).link(src, q1, q2, sink)
    p.start()
    try:
        live = {t.ident: t.name for t in threading.enumerate()
                if t.name.startswith("nns:")}
        assert {"nns:profcov:src", "nns:profcov:q1",
                "nns:profcov:q2"} <= set(live.values())
        for ident, name in live.items():
            info = prof.THREADS.lookup(ident)
            assert info is not None, f"unregistered nns thread {name}"
            assert info["name"] == name
        # element loops carry the (pipeline, element) join key
        by_name = {v["name"]: v for v in prof.THREADS.snapshot()}
        assert by_name["nns:profcov:q1"]["pipeline"] == "profcov"
        assert by_name["nns:profcov:q1"]["element"] == "q1"
    finally:
        src.end_of_stream()
        p.wait_eos(timeout=10)
        p.stop()


def test_registry_inert_when_disabled(monkeypatch):
    monkeypatch.setattr(prof._hooks, "DISABLED", True)
    prof.THREADS.register("x", "y")
    assert prof.THREADS.lookup(threading.get_ident()) is None
    assert prof.element_account("p", "e") is None


# -- sampling profiler --------------------------------------------------------


def test_bounded_table_lowest_count_eviction():
    sp = prof.SamplingProfiler(max_stacks=3)
    for _ in range(5):
        sp._record("a", "f.py:hot")
    for _ in range(3):
        sp._record("b", "f.py:warm")
    sp._record("c", "f.py:cold")
    assert sp.evicted_total == 0
    sp._record("d", "f.py:new")  # 4th stack: the cold one is evicted
    assert sp.evicted_total == 1
    labels = {label for label, _ in sp._table}
    assert labels == {"a", "b", "d"}
    assert sp.samples_total == 10


def test_tick_attributes_samples_through_registry():
    sp = prof.SamplingProfiler()
    release = threading.Event()

    def element_loop_body():
        release.wait(timeout=10)

    t = prof.named_thread("queue", "q0", element_loop_body,
                          pipeline="pipeA", element="q0")
    t.start()
    try:
        deadline = time.monotonic() + 5
        while prof.THREADS.lookup(t.ident) is None \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        sampled = sp.tick()
        assert sampled >= 1 and sp.ticks_total == 1
        assert sp.errors_total == 0
        assert sp.element_samples().get(("pipeA", "q0"), 0) >= 1
        labels = {label for label, _ in sp._table}
        assert "pipeA:q0" in labels  # pipeline:element, not tid-...
        stack = next(s for (lb, s) in sp._table if lb == "pipeA:q0")
        assert "element_loop_body" in stack  # root-first frames
    finally:
        release.set()
        t.join(timeout=5)


def test_gil_proxy_counts_runnable_threads():
    sp = prof.SamplingProfiler()
    stop = [False]  # plain flag: the spin leaf frame stays `spin`

    def spin():
        n = 0
        while not stop[0]:
            n += 1

    threads = [threading.Thread(target=spin, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.05)
        sp.tick()
        assert sp.runnable_last >= 2
        assert sp.gil_waiters >= 1  # at most one of them holds the GIL
    finally:
        stop[0] = True
        for t in threads:
            t.join(timeout=5)


def test_start_refuses_unconfigured_and_disabled(monkeypatch):
    sp = prof.SamplingProfiler()
    assert sp.start() is False  # hz 0: unconfigured
    monkeypatch.setenv("NNS_TPU_OBS_DISABLE", "1")
    assert sp.configure(50).start() is False  # kill switch: inert
    assert sp._thread is None and not sp.running
    monkeypatch.delenv("NNS_TPU_OBS_DISABLE")
    assert sp.start() is True
    try:
        assert threading.current_thread().name != sp._thread.name
        assert sp._thread.name == "nns:prof:sampler"
        assert sp.start() is False  # already running
    finally:
        sp.stop()
    assert sp.ticks_total > 0


def test_collapsed_and_ring_goldens():
    sp = prof.SamplingProfiler()
    sp._record("p:q", "a.py:main;a.py:loop", ts=10.0)
    sp._record("p:q", "a.py:main;a.py:loop", ts=11.0)
    sp._record("watch:sampler", "w.py:run", ts=12.0)
    assert sp.collapsed() == (
        "p:q;a.py:main;a.py:loop 2\n"
        "watch:sampler;w.py:run 1")
    # the ring honors its cutoff: only samples newer than now - last_s
    assert sp.ring_collapsed(last_s=1.5, now=12.0) == (
        "p:q;a.py:main;a.py:loop 1\n"
        "watch:sampler;w.py:run 1")
    assert sp.ring_collapsed(last_s=0.5, now=20.0) == ""


def test_chrome_trace_golden_merges_consecutive_samples():
    sp = prof.SamplingProfiler(hz=10.0)
    sp._record("p:q", "a.py:main;a.py:work", ts=1.0)
    sp._record("p:q", "a.py:main;a.py:work", ts=1.1)
    sp._record("p:q", "a.py:main;a.py:idle", ts=1.2)
    doc = sp.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 1 and meta[0]["args"]["name"] == "p:q"
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [s["name"] for s in slices] == ["a.py:work", "a.py:idle"]
    assert slices[0]["args"]["samples"] == 2
    assert slices[0]["dur"] == 200000.0  # 2 samples at 10 Hz, in us
    assert slices[0]["ts"] == 1000000.0
    assert slices[1]["args"]["stack"] == "a.py:main;a.py:idle"


def test_top_stacks_and_summary():
    sp = prof.SamplingProfiler(hz=10.0)
    for _ in range(3):
        sp._record("a", "f.py:hot")
    sp._record("b", "f.py:cold")
    top = sp.top_stacks(1)
    assert top == [{"label": "a", "stack": "f.py:hot", "count": 3}]
    s = sp.summary()
    assert s["samples"] == 4 and s["stacks"] == 2
    assert s["running"] is False and s["hz"] == 10.0


# -- exact run/wait/CPU accounting --------------------------------------------


def test_run_wait_split_on_crafted_element():
    """Gapped arrivals + a spinning downstream chain: the queue loop's
    wait side sees the arrival gaps, its run side sees the spin (the
    whole downstream chain runs in the queue's thread), and the CPU
    side stays near the spin (the waits are blocking, not burning)."""
    p, src, sink = _slow_pipeline("profsplit")
    p.start()
    try:
        n, gap = 8, 0.03
        for i in range(n):
            src.push_buffer(Buffer.of(
                np.zeros(SHAPE, np.float32), pts=i))
            time.sleep(gap)
        for _ in range(n):
            assert sink.pull(timeout=10) is not None
        rows = {(r["pipeline"], r["element"]): r
                for r in prof.account_rows()}
        q = rows[("profsplit", "q")]
        assert q["iters"] >= n
        # run >= the spins the chain burned; wait >= the gaps minus
        # scheduling slack; the split must not blur the two
        assert q["run_s"] >= n * SlowSink.spin_s * 0.8, q
        assert q["wait_s"] >= (n - 1) * gap * 0.5, q
        assert q["wait_s"] > q["run_s"], q
        # the source thread waited for pushes and ran ~nothing
        s = rows[("profsplit", "src")]
        assert s["wait_s"] > s["run_s"], s
    finally:
        src.end_of_stream()
        p.wait_eos(timeout=10)
        p.stop()


def test_cpu_sum_stays_within_process_time():
    """The attribution-exactness invariant:
    summed per-element thread CPU can never exceed the process-wide
    ``time.process_time()`` delta over the same window."""
    before = {(r["pipeline"], r["element"]): r["cpu_s"]
              for r in prof.account_rows()}
    cpu0 = time.process_time()
    p, src, sink = _slow_pipeline("profexact")
    p.start()
    try:
        for i in range(16):
            src.push_buffer(Buffer.of(
                np.zeros(SHAPE, np.float32), pts=i))
        for _ in range(16):
            assert sink.pull(timeout=10) is not None
    finally:
        src.end_of_stream()
        p.wait_eos(timeout=10)
        p.stop()
    process_delta = time.process_time() - cpu0
    acct = sum(r["cpu_s"] - before.get(
        (r["pipeline"], r["element"]), 0.0)
        for r in prof.account_rows())
    assert acct > 0  # the spins are real CPU, and they were accounted
    assert acct <= process_delta * 1.02 + 0.005, \
        (acct, process_delta)


def test_element_account_single_writer_math():
    a = prof.ElementAccount("p", "e")
    a.add(0.5, 0.25, 0.1)
    a.add(-0.1, 0.0, -0.2)  # clock hiccups never go negative
    assert a.wait_s == 0.5 and a.run_s == 0.25 and a.cpu_s == 0.1
    assert a.iters == 2


# -- deep profiles ------------------------------------------------------------


def _wait_captures(deep, n, timeout=10.0):
    deadline = time.monotonic() + timeout
    while len(deep.captures) < n and time.monotonic() < deadline:
        time.sleep(0.02)
    return list(deep.captures)


def test_deep_profile_once_per_episode_and_rate_limited(tmp_path):
    d = prof.DeepProfiler()
    d.arm(str(tmp_path), seconds=0.2, hz=100.0, min_interval_s=60.0)
    assert d.trigger("qfull") is True
    # the SAME episode cannot double-capture: rate-limited out
    assert d.trigger("qfull") is False
    assert d.episodes == 1 and d.skipped == 1
    caps = _wait_captures(d, 1)
    assert len(caps) == 1
    text = open(caps[0]).read()
    first = text.splitlines()[0]
    assert first.startswith("# nns-prof deep capture: reason=qfull")
    assert "seconds=0.2" in first and "hz=100" in first
    # dense host sampling really ran: collapsed lines follow the header
    assert len(text.splitlines()) > 1
    assert os.path.basename(caps[0]) == "deepprof-001-qfull.txt"


def test_deep_profile_interval_elapses_then_fires_again(tmp_path):
    d = prof.DeepProfiler()
    d.arm(str(tmp_path), seconds=0.05, hz=50.0, min_interval_s=0.1)
    assert d.trigger("a") is True
    _wait_captures(d, 1)
    time.sleep(0.15)  # past min_interval: the next episode may fire
    assert d.trigger("b") is True
    caps = _wait_captures(d, 2)
    assert [os.path.basename(c) for c in caps] == [
        "deepprof-001-a.txt", "deepprof-002-b.txt"]


def test_deep_profile_unarmed_and_disabled_inert(tmp_path, monkeypatch):
    d = prof.DeepProfiler()
    assert d.trigger("x") is False  # unarmed: strict no-op
    d.arm(str(tmp_path), seconds=0.05)
    monkeypatch.setenv("NNS_TPU_OBS_DISABLE", "1")
    assert d.trigger("x") is False  # kill switch: inert even armed
    assert d.episodes == 0 and d.captures == []


def test_deep_capture_runs_off_the_calling_thread(tmp_path):
    d = prof.DeepProfiler()
    d.arm(str(tmp_path), seconds=0.3, hz=50.0)
    t0 = time.monotonic()
    assert d.trigger("slow") is True
    # trigger returns immediately; the 0.3 s capture is elsewhere
    assert time.monotonic() - t0 < 0.2
    assert _wait_captures(d, 1)


# -- env activation -----------------------------------------------------------


def test_maybe_start_from_env(tmp_path, monkeypatch):
    monkeypatch.setattr(prof, "_env_checked", False)
    monkeypatch.setenv("NNS_TPU_PROF", "50")
    monkeypatch.setenv("NNS_TPU_PROF_DEEP_DIR", str(tmp_path / "deep"))
    monkeypatch.setenv("NNS_TPU_PROF_DEEP_SECONDS", "0.5")
    monkeypatch.setenv("NNS_TPU_PROF_DEEP_HZ", "75")
    prof.maybe_start_from_env()
    try:
        assert prof.PROFILER.running and prof.PROFILER.hz == 50.0
        assert prof.DEEP.armed and prof.DEEP.seconds == 0.5
        assert prof.DEEP.hz == 75.0
        assert os.path.isdir(tmp_path / "deep")
        # second call is a no-op (one-shot hook, like the watchdog's)
        prof.maybe_start_from_env()
    finally:
        prof.PROFILER.stop()


def test_env_hook_inert_under_obs_disable(tmp_path, monkeypatch):
    monkeypatch.setattr(prof, "_env_checked", False)
    monkeypatch.setenv("NNS_TPU_PROF", "50")
    monkeypatch.setenv("NNS_TPU_PROF_DEEP_DIR", str(tmp_path / "d2"))
    monkeypatch.setenv("NNS_TPU_OBS_DISABLE", "1")
    prof.maybe_start_from_env()
    assert not prof.PROFILER.running
    assert not prof.DEEP.armed
    assert not os.path.exists(tmp_path / "d2")  # no dir, no thread


def test_env_hook_bad_rate_does_not_start(monkeypatch):
    monkeypatch.setattr(prof, "_env_checked", False)
    monkeypatch.setenv("NNS_TPU_PROF", "not-a-rate")
    prof.maybe_start_from_env()
    assert not prof.PROFILER.running


# -- export surfaces ----------------------------------------------------------


def test_snapshot_profile_table_and_flat_families():
    from nnstreamer_tpu.obs.metrics import SNAPSHOT_VERSION

    assert SNAPSHOT_VERSION == 10
    p, src, sink = _slow_pipeline("profsnap")
    p.start()
    try:
        for i in range(4):
            src.push_buffer(Buffer.of(
                np.zeros(SHAPE, np.float32), pts=i))
        for _ in range(4):
            assert sink.pull(timeout=10) is not None
        snap = REGISTRY.snapshot()
        assert snap["version"] == 10
        table = snap["profile"]
        assert sorted(table.keys()) == [
            "elements", "gil_waiters", "profiler", "stacks"]
        rows = {(r["pipeline"], r["element"]): r
                for r in table["elements"]}
        q = rows[("profsnap", "q")]
        assert q["iters"] >= 4 and 0.0 <= q["wait_share"] <= 1.0
        assert {"cpu_s", "run_s", "wait_s", "samples",
                "sample_share"} <= set(q)
        # flat families ride the single collection walk
        fams = {s["name"]: s
                for s in snap["metrics"]["families"]} \
            if isinstance(snap["metrics"], dict) \
            and "families" in snap["metrics"] else None
        text_names = [f for f in (
            "nns_element_cpu_seconds_total",
            "nns_element_run_seconds_total",
            "nns_element_wait_seconds_total")]
        if fams is not None:
            assert all(n in fams for n in text_names)
    finally:
        src.end_of_stream()
        p.wait_eos(timeout=10)
        p.stop()


def test_prof_endpoint_and_healthz_and_families():
    from nnstreamer_tpu.obs.metrics import serve_metrics

    p, src, sink = _slow_pipeline("profhttp")
    p.start()
    srv = serve_metrics(port=0)
    try:
        for i in range(4):
            src.push_buffer(Buffer.of(
                np.zeros(SHAPE, np.float32), pts=i))
        for _ in range(4):
            assert sink.pull(timeout=10) is not None
        prof.PROFILER.clear()
        prof.PROFILER._record(
            "profhttp:q", "x.py:main;x.py:loop",
            ts=time.monotonic())
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/prof").read().decode()
        assert "profhttp:q;x.py:main;x.py:loop 1" in text
        doc = json.loads(urllib.request.urlopen(
            f"{base}/prof?format=trace").read().decode())
        assert any(e.get("args", {}).get("name") == "profhttp:q"
                   for e in doc["traceEvents"])
        ring = urllib.request.urlopen(
            f"{base}/prof?last=60").read().decode()
        assert "profhttp:q" in ring
        metrics = urllib.request.urlopen(
            f"{base}/metrics").read().decode()
        assert "nns_element_cpu_seconds_total" in metrics
        assert 'pipeline="profhttp"' in metrics
        health = json.loads(urllib.request.urlopen(
            f"{base}/healthz").read().decode())
        assert "prof" in health
        assert {"running", "deep_armed",
                "deep_episodes"} <= set(health["prof"])
    finally:
        srv.close()
        src.end_of_stream()
        p.wait_eos(timeout=10)
        p.stop()


def test_flightrec_dump_embeds_profiler_ring(tmp_path):
    from nnstreamer_tpu.obs.flightrec import FlightRecorder

    rec = FlightRecorder()
    rec.arm(str(tmp_path))
    prof.PROFILER.clear()
    prof.PROFILER.configure(50.0)
    assert prof.PROFILER.start()
    try:
        time.sleep(0.1)  # a few real ticks into the ring
        doc = rec.dump_json("test")
        assert "host_stacks" in doc
        assert doc["host_stacks"].count("\n") >= 0
        assert doc["host_stacks"]  # the ring had samples
    finally:
        prof.PROFILER.stop()
    # not running: no embed key at all (absent, not empty)
    doc = rec.dump_json("test2")
    assert "host_stacks" not in doc


def test_nns_top_renders_prof_section():
    from nnstreamer_tpu.obs.top import render

    def snap(t, cpu, run, wait):
        return {
            "time": t, "pipelines": [], "pools": [], "links": [],
            "compiles": [],
            "profile": {
                "elements": [{
                    "pipeline": "p", "element": "q", "cpu_s": cpu,
                    "run_s": run, "wait_s": wait, "iters": 100,
                    "samples": 40, "sample_share": 0.5,
                    "wait_share": 0.8}],
                "stacks": [{"label": "p:q",
                            "stack": "a.py:main;a.py:loop",
                            "count": 40}],
                "gil_waiters": 2,
                "profiler": {"running": True, "hz": 47.0,
                             "ticks": 80, "samples": 160,
                             "stacks": 12, "evicted": 0, "errors": 0,
                             "gil_waiters": 2, "runnable": 3,
                             "self_cpu_s": 0.01}}}

    prev = snap(100.0, 1.0, 2.0, 8.0)
    cur = snap(101.0, 1.1, 2.2, 8.8)
    out = render(cur, prev)
    assert "PROF ELEMENT" in out and "WAIT%" in out
    row = [ln for ln in out.splitlines()
           if ln.startswith("q") and "p" in ln][0]
    # 0.1 s CPU over the 1 s window -> 10.0%; wait 0.8 s -> 80.0%
    assert "10.0" in row and "80.0" in row
    assert "top stack: p:q a.py:loop x40" in out
    assert "profiler: 47 Hz" in out and "gil_waiters 2" in out


def test_nns_prof_cli_in_process_and_file_out(tmp_path, monkeypatch):
    monkeypatch.delenv("NNS_TPU_METRICS_PORT", raising=False)
    prof.PROFILER.clear()
    prof.PROFILER._record("p:e", "m.py:main;m.py:step",
                          ts=time.monotonic())
    buf = io.StringIO()
    assert prof.main([], out=buf) == 0
    assert "p:e;m.py:main;m.py:step 1" in buf.getvalue()
    buf = io.StringIO()
    assert prof.main(["--format", "trace"], out=buf) == 0
    doc = json.loads(buf.getvalue())
    assert doc["traceEvents"]
    out_file = tmp_path / "stacks.txt"
    assert prof.main(["--out", str(out_file)]) == 0
    assert "p:e;m.py:main;m.py:step 1" in out_file.read_text()
    # a dead endpoint is a clean failure, not a traceback
    assert prof.main(["--connect", "127.0.0.1:1"],
                     out=io.StringIO()) == 1
