"""Distributed observability (ISSUE-5): cross-device trace propagation,
per-link edge metrics, fleet ``nns-top``.

In-process client+server pipelines over REAL TCP sockets exercise the
full wire path: trace context injection/extraction, the 4-timestamp
clock alignment that nests the server's spans inside the client's
network span, byte-exact ``nns_edge_*`` link counters, the ``/healthz``
probe, multi-endpoint ``nns-top`` with LINK rows and unreachable-
endpoint resilience, and the jax-profiler trace-id correlation marker.
The true two-process variant lives in ``tests/test_crossprocess.py``.
"""

import io
import json
import time
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.edge.wire import MSG_QUERY, MSG_REPLY, EdgeMessage
from nnstreamer_tpu.elements.basic import AppSink, AppSrc
from nnstreamer_tpu.filters.custom import register_custom_easy
from nnstreamer_tpu.obs import REGISTRY, TRACE_META_KEY, LatencyTracer, hooks
from nnstreamer_tpu.obs.metrics import LinkMetrics, MetricsRegistry
from nnstreamer_tpu.obs.top import fetch_fleet, render_fleet
from nnstreamer_tpu.obs.top import main as top_main
from nnstreamer_tpu.runtime import Pipeline
from nnstreamer_tpu.runtime.registry import make

SHAPE_SPEC = "4:1"
CAPS = ("other/tensors,format=static,num_tensors=1,dimensions=4:1,"
        "types=float32")


@pytest.fixture(autouse=True)
def _clean():
    LinkMetrics.clear_all()
    yield
    hooks.detach()
    LinkMetrics.clear_all()


@pytest.fixture(scope="module", autouse=True)
def _model():
    spec = TensorsSpec.parse(SHAPE_SPEC, "float32")
    register_custom_easy("dobs_x3", lambda xs: [xs[0] * 3.0],
                         in_spec=spec, out_spec=spec)
    yield


def _server(server_id=81):
    srv = Pipeline(name=f"dobs-server-{server_id}")
    qsrc = make("tensor_query_serversrc", el_name="qsrc",
                connect_type="tcp", host="127.0.0.1", port=0,
                id=server_id)
    flt = make("tensor_filter", el_name="srvnet", framework="custom-easy",
               model="dobs_x3")
    qsink = make("tensor_query_serversink", el_name="qsink", id=server_id)
    srv.add(qsrc, flt, qsink).link(qsrc, flt, qsink)
    srv.start()
    return srv, qsrc.port


def _client(port, name="dobs-client", **cli_props):
    spec = TensorsSpec.parse(SHAPE_SPEC, "float32")
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=spec, max_buffers=64)
    cli = make("tensor_query_client", el_name="qcli", host="127.0.0.1",
               port=port, connect_type="tcp", timeout=30000, caps=CAPS,
               **cli_props)
    sink = AppSink(name="out", max_buffers=64)
    p.add(src, cli, sink).link(src, cli, sink)
    return p, src, cli, sink


def _roundtrip(p, src, sink, n=6):
    outs = []
    with p:
        for i in range(n):
            src.push_buffer(Buffer.of(
                np.full((1, 4), float(i + 1), np.float32), pts=i))
        for _ in range(n):
            b = sink.pull(timeout=30)
            assert b is not None, f"stalled after {len(outs)}"
            outs.append(b)
        src.end_of_stream()
        assert p.wait_eos(timeout=30)
    return outs


# -- trace propagation + clock alignment --------------------------------------


def test_query_trace_crosses_tcp_and_nests():
    """The acceptance shape, in-process: every client record gains a
    remote entry whose offset-mapped server spans nest inside the
    client's network span, which nests inside the client element's
    residency — and local exactness (sum(residency) == e2e) still
    holds."""
    srv, port = _server(81)
    try:
        p, src, cli, sink = _client(port)
        with LatencyTracer(sample_every=1) as tr:
            outs = _roundtrip(p, src, sink, n=6)
        for i, b in enumerate(outs):
            np.testing.assert_array_equal(
                b.tensors[0].np(),
                np.full((1, 4), 3.0 * (i + 1), np.float32))
    finally:
        srv.stop()
    recs = [r for r in tr.records() if r.get("origin") != "remote"]
    assert len(recs) == 6
    for r in recs:
        # local exactness guarantee is untouched by absorption
        assert sum(r["residency_s"].values()) == pytest.approx(
            r["e2e_s"], abs=1e-6)
        assert r.get("remote"), r
        hop = r["remote"][0]
        assert hop["link"] == "qcli"
        # the client element's residency span brackets the network span
        marks = r["marks"]
        cli_in = min(t for t, name, ph in marks
                     if name == "qcli" and ph == "chain-in")
        out_in = min(t for t, name, ph in marks
                     if name == "out" and ph == "chain-in")
        assert cli_in <= hop["t_out"] <= hop["t_in"] <= out_in
        # mapped server window nests inside the network span (the
        # offset_and_delay containment property)
        assert hop["t_out"] <= hop["t2"] <= hop["t3"] <= hop["t_in"]
        assert hop["rtt_s"] >= 0
        # server marks cover the server pipeline and sit in the window
        names = {name for _, name, _ in hop["marks"]}
        assert {"qsrc", "srvnet", "qsink"} <= names
        eps = 5e-4
        for t, _, _ in hop["marks"]:
            assert hop["t_out"] - eps <= t <= hop["t_in"] + eps
    # server-side views were recorded too, tagged remote-origin
    remote_recs = [r for r in tr.records() if r.get("origin") == "remote"]
    assert len(remote_recs) == 6
    # and the traced round-trips fed the per-peer clock
    # (the client element object is gone with the pipeline; the record
    # count above already proves absorption ran)


def test_merged_chrome_trace_one_timeline():
    srv, port = _server(82)
    try:
        p, src, cli, sink = _client(port, name="dobs-ct")
        with LatencyTracer(sample_every=1) as tr:
            _roundtrip(p, src, sink, n=4)
        assert len(cli.peer_clock) > 0  # round-trips fed the PeerClock
    finally:
        srv.stop()
    doc = json.loads(json.dumps(tr.chrome_trace()))
    events = doc["traceEvents"]
    # remote-origin (server-view) records are excluded by default...
    frames = [e for e in events if e["cat"] == "frame"]
    assert len(frames) == 4
    by_tid = {e["tid"]: e for e in frames}
    nets = [e for e in events if e["cat"] == "net"]
    assert len(nets) == 4
    for net in nets:
        frame = by_tid[net["tid"]]
        assert net["ts"] >= frame["ts"] - 1e-3
        assert net["ts"] + net["dur"] <= frame["ts"] + frame["dur"] + 1e-3
        # the server's element spans nest inside THIS network span
        host = net["args"]["host"]
        remote_els = [e for e in events if e["cat"] == "element"
                      and e["tid"] == net["tid"]
                      and e["name"].startswith(f"{host}/")]
        assert {e["name"].split("/", 1)[1] for e in remote_els} \
            >= {"qsrc", "srvnet", "qsink"}
        for e in remote_els:
            assert e["ts"] >= net["ts"] - 1e-3
            assert e["ts"] + e["dur"] <= net["ts"] + net["dur"] + 1e-3
        # the client element span = server residency + network time
        cli_span = [e for e in events if e["cat"] == "element"
                    and e["tid"] == net["tid"] and e["name"] == "qcli"][0]
        assert cli_span["ts"] - 1e-3 <= net["ts"]
        assert net["ts"] + net["dur"] <= \
            cli_span["ts"] + cli_span["dur"] + 1e-3
    # opting in renders the server-view lanes as well
    full = tr.chrome_trace(include_remote_origin=True)
    assert len([e for e in full["traceEvents"]
                if e["cat"] == "frame"]) == 8


def test_trace_false_propagates_nothing():
    srv, port = _server(83)
    try:
        p, src, cli, sink = _client(port, name="dobs-notrace",
                                    trace=False)
        with LatencyTracer(sample_every=1) as tr:
            _roundtrip(p, src, sink, n=3)
    finally:
        srv.stop()
    # client-side records: no remote entries absorbed
    recs = [r for r in tr.records()
            if any(name == "out" for _, name, _ in r["marks"])]
    assert len(recs) == 3
    assert all(not r.get("remote") for r in recs)
    # no propagated context reached the server: its (locally sampled)
    # records are plain, never remote-origin
    assert all(r.get("origin") != "remote" for r in tr.records())


def test_edge_pubsub_oneway_trace():
    """edgesink → edgesrc over TCP: the subscriber's new trace carries
    the publisher's offset-mapped marks as a remote entry."""
    pub = Pipeline(name="dobs-pub")
    spec = TensorsSpec.parse(SHAPE_SPEC, "float32")
    psrc = AppSrc(name="psrc", spec=spec, max_buffers=32)
    esink = make("edgesink", el_name="esink", host="127.0.0.1", port=0,
                 connect_type="tcp", topic="t5")
    pub.add(psrc, esink).link(psrc, esink)
    with LatencyTracer(sample_every=1) as tr:
        pub.start()
        sub = Pipeline(name="dobs-sub")
        esrc = make("edgesrc", el_name="esrc", dest_host="127.0.0.1",
                    dest_port=esink.port, connect_type="tcp", topic="t5",
                    caps=CAPS, num_buffers=3)
        ssink = AppSink(name="ssink", max_buffers=32)
        sub.add(esrc, ssink).link(esrc, ssink)
        sub.start()
        try:
            time.sleep(0.3)  # let the subscription land
            for i in range(3):
                psrc.push_buffer(Buffer.of(
                    np.full((1, 4), float(i), np.float32), pts=i))
            got = [ssink.pull(timeout=10) for _ in range(3)]
            assert all(b is not None for b in got)
            assert sub.wait_eos(timeout=10)
        finally:
            sub.stop()
            pub.stop()
    # subscriber-side records carry the publisher's marks
    sub_recs = [r for r in tr.records()
                if any(name == "ssink" for _, name, _ in r["marks"])]
    assert len(sub_recs) == 3
    for r in sub_recs:
        hop = r["remote"][0]
        assert hop["link"] == "esrc"
        assert {name for _, name, _ in hop["marks"]} >= {"psrc"}
        assert hop["t_in"] <= r["end"]
    # link metrics exist for both directions
    kinds = {row["kind"] for row in REGISTRY.snapshot()["links"]}
    assert {"edge-pub", "edge-sub"} <= kinds


# -- link metrics --------------------------------------------------------------


def test_link_byte_counters_exact():
    """The acceptance bound: exported nns_edge_* byte counters EQUAL
    the ground-truth framed sizes (4-byte length prefix + wire bytes),
    both directions.  Trace off, caps pinned AND the device-channel
    probe off, so every byte on the link is one of the N query/reply
    frames."""
    srv, port = _server(84)
    n = 5
    try:
        p, src, cli, sink = _client(port, name="dobs-bytes", trace=False,
                                    device_channel=False)
        outs = _roundtrip(p, src, sink, n=n)
    finally:
        srv.stop()
    ins = [Buffer.of(np.full((1, 4), float(i + 1), np.float32), pts=i)
           for i in range(n)]
    tx_truth = sum(
        4 + len(EdgeMessage.from_buffer(MSG_QUERY, b, seq=i + 1).pack())
        for i, b in enumerate(ins))
    rx_truth = sum(
        4 + len(EdgeMessage.from_buffer(MSG_REPLY, b, client_id=1,
                                        seq=i + 1).pack())
        for i, b in enumerate(outs))
    rows = {(r["kind"], r["link"]): r
            for r in REGISTRY.snapshot()["links"]}
    cli_row = rows[("query", "qcli")]
    assert cli_row["tx_bytes"] == tx_truth
    assert cli_row["rx_bytes"] == rx_truth
    assert cli_row["tx_msgs"] == n and cli_row["rx_msgs"] == n
    assert cli_row["rtt"]["count"] == n
    assert cli_row["rtt"]["mean_us"] > 0
    assert cli_row["inflight"] == 0 and cli_row["timeouts"] == 0
    assert cli_row["reconnects"] == 0
    # the server side mirrors the link (rx of queries, tx of replies)
    srv_row = rows[("query-server", "qsrc")]
    assert srv_row["rx_bytes"] == tx_truth
    assert srv_row["tx_bytes"] == rx_truth
    # and the flat exposition carries the same numbers (labels render
    # sorted: kind, link, peer)
    expo = REGISTRY.exposition()
    line = [ln for ln in expo.splitlines()
            if ln.startswith('nns_edge_tx_bytes_total{kind="query",'
                             'link="qcli"')][0]
    assert line.endswith(f" {tx_truth}")
    assert "# TYPE nns_edge_rtt_seconds histogram" in expo
    assert "nns_edge_rtt_seconds_bucket" in expo
    assert f'nns_edge_rtt_seconds_count{{kind="query",link="qcli",' \
           f'peer="{cli_row["peer"]}"}} {n}' in expo


def test_link_timeout_counter():
    """A server that never answers surfaces as nns_edge timeouts."""
    from nnstreamer_tpu.edge.transport import TcpServer

    black_hole = TcpServer("127.0.0.1", 0)
    black_hole.start()
    try:
        p, src, cli, sink = _client(black_hole.port, name="dobs-to",
                                    trace=False)
        p.start()
        try:
            src.push_buffer(Buffer.of(np.zeros((1, 4), np.float32)))
            cli.timeout = 100  # shrink AFTER start: fast expiry
            deadline = time.monotonic() + 10
            while cli.timeouts == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
        finally:
            p.stop()
        row = [r for r in REGISTRY.snapshot()["links"]
               if r["link"] == "qcli" and r["kind"] == "query"][0]
        assert row["timeouts"] >= 1
    finally:
        black_hole.stop()


# -- /healthz ------------------------------------------------------------------


def test_healthz_endpoint():
    reg = MetricsRegistry()
    p = Pipeline(name="dobs-hz")
    reg.register_pipeline(p)
    srv = reg.serve(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            assert r.status == 200
            doc = json.loads(r.read().decode())
        assert doc["status"] == "ok"
        assert doc["pipelines"] == 1
        assert "pools" in doc and "links" in doc and "time" in doc
        assert doc["host"]
    finally:
        srv.close()


# -- fleet nns-top -------------------------------------------------------------


def _registry_with_pipeline(name, collect_links=False):
    reg = MetricsRegistry(collect_links=collect_links)
    spec = TensorsSpec.parse(SHAPE_SPEC, "float32")
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=spec)
    sink = AppSink(name="out")
    p.add(src, sink).link(src, sink)
    reg.register_pipeline(p)
    return reg, p


def test_nns_top_fleet_two_endpoints():
    """--connect twice: one table, sectioned per endpoint, both hosts'
    PIPELINE rows visible; LINK rows render from the links table."""
    LinkMetrics.get("qcli", "10.0.0.7:9000", kind="query").on_tx(128)
    rega, pa = _registry_with_pipeline("fleet-a", collect_links=True)
    regb, pb = _registry_with_pipeline("fleet-b")
    sa, sb = rega.serve(port=0), regb.serve(port=0)
    try:
        buf = io.StringIO()
        rc = top_main(["--once", "--interval", "0.05",
                       "--connect", f"127.0.0.1:{sa.port}",
                       "--connect", f"127.0.0.1:{sb.port}"], out=buf)
        text = buf.getvalue()
        assert rc == 0
        assert f"endpoint 127.0.0.1:{sa.port}" in text
        assert f"endpoint 127.0.0.1:{sb.port}" in text
        assert "pipeline fleet-a" in text
        assert "pipeline fleet-b" in text
        assert "LINK" in text and "10.0.0.7:9000" in text
        assert "RTT µs" in text and "RECON" in text
        # comma-separated form is equivalent
        buf2 = io.StringIO()
        rc = top_main(["--once", "--interval", "0.05", "--connect",
                       f"127.0.0.1:{sa.port},127.0.0.1:{sb.port}"],
                      out=buf2)
        assert rc == 0
        assert "pipeline fleet-a" in buf2.getvalue()
        assert "pipeline fleet-b" in buf2.getvalue()
    finally:
        sa.close()
        sb.close()


def test_nns_top_partial_outage_keeps_rendering():
    """One live endpoint + one dead: --once still renders the live one
    (rc 0) and marks the dead one; a fully dead fleet is rc 1."""
    reg, p = _registry_with_pipeline("fleet-live")
    srv = reg.serve(port=0)
    try:
        buf = io.StringIO()
        rc = top_main(["--once", "--interval", "0.05",
                       "--connect", f"127.0.0.1:{srv.port}",
                       "--connect", "127.0.0.1:1"], out=buf)
        text = buf.getvalue()
        assert rc == 0
        assert "pipeline fleet-live" in text
        assert "unreachable (retrying)" in text
    finally:
        srv.close()
    buf = io.StringIO()
    rc = top_main(["--once", "--interval", "0.05",
                   "--connect", "127.0.0.1:1"], out=buf)
    assert rc == 1


def test_fetch_fleet_and_render_survive_dead_endpoint():
    """The live-mode resilience primitive: a scrape failure becomes a
    rendered 'unreachable (retrying)' line, never an exception — so a
    restarting server can't kill the dashboard loop."""
    samples = fetch_fleet(["127.0.0.1:1"])
    assert samples[0]["snap"] is None
    assert samples[0]["error"]
    text = render_fleet(samples, {}, show_host=True)
    assert "unreachable (retrying)" in text
    # recovery: same endpoint answering again renders normally
    reg, p = _registry_with_pipeline("fleet-back")
    srv = reg.serve(port=0)
    try:
        again = fetch_fleet([f"127.0.0.1:{srv.port}"])
        assert again[0]["snap"] is not None
        assert "pipeline fleet-back" in render_fleet(again, {}, True)
    finally:
        srv.close()


def test_fetch_fleet_captures_non_oserror_failures(monkeypatch):
    """A process dying mid-response raises HTTPException/ValueError,
    not OSError — the fleet loop must survive those identically."""
    from http.client import IncompleteRead

    from nnstreamer_tpu.obs import top as top_mod

    for exc in (IncompleteRead(b""), ValueError("truncated json")):
        def boom(ep, _e=exc):
            raise _e
        monkeypatch.setattr(top_mod, "fetch_snapshot", boom)
        samples = top_mod.fetch_fleet(["127.0.0.1:9"])
        assert samples[0]["snap"] is None and samples[0]["error"]
        assert "unreachable (retrying)" in \
            render_fleet(samples, {}, show_host=True)


def test_async_ntp_epoch_fn_never_blocks():
    """The element-facing epoch callable must stay hot-path safe even
    with unreachable NTP servers: first call returns the local clock
    immediately; the SNTP walk happens on the refresh thread."""
    from nnstreamer_tpu.edge.ntputil import async_ntp_epoch_fn

    fn = async_ntp_epoch_fn([("127.0.0.1", 1)])
    try:
        t0 = time.monotonic()
        us = fn()
        assert time.monotonic() - t0 < 0.25  # no 2s SNTP timeout inline
        assert abs(us - time.time() * 1e6) < 5e6
    finally:
        fn.stop()


def test_clock_cross_check_warns_on_persistent_disagreement(caplog):
    """ntp-servers= is a REAL cross-check: a server epoch that
    persistently disagrees with the in-band half-RTT placement logs a
    skew warning; an agreeing one resets the streak."""
    import logging

    cli = make("tensor_query_client", el_name="xchk",
               ntp_servers="198.51.100.9")
    cli._epoch_fn = lambda: 1_000_000_000  # stub: no network
    est = (0.0, 0.010)  # delay 10ms → expected lag_wall ≈ 5ms
    agree = {"epoch3_us": 1_000_000_000 - 5_000}
    skewed = {"epoch3_us": 1_000_000_000 - 80_000}  # 80ms lag: way off
    with caplog.at_level(logging.WARNING, logger="nnstreamer_tpu"):
        for _ in range(4):
            cli._clock_cross_check(skewed, est)
        assert cli._clock_disagree == 4
        cli._clock_cross_check(agree, est)
        assert cli._clock_disagree == 0  # one good sample resets
        assert not caplog.records
        for _ in range(5):
            cli._clock_cross_check(skewed, est)
    assert any("disagree" in r.getMessage() for r in caplog.records)
    assert cli._clock_disagree == 0  # warned once, streak reset


def test_inflight_gauge_counts_only_unanswered():
    """One definition everywhere: the gauge counts entries awaiting a
    reply — an answered-but-not-yet-popped entry is excluded whether
    the writer was chain() or the flush path."""
    cli = make("tensor_query_client", el_name="ifl")
    cli._metrics = LinkMetrics.get("ifl", "x:1", kind="query")
    with cli._iflock:
        cli._inflight[1] = [object(), None, 0.0, None, 0.0]
        cli._inflight[2] = [object(), object(), 0.0, None, 0.0]  # answered
        cli._inflight[3] = [None, None, 0.0, None, 0.0]          # tombstone
        cli._update_inflight_locked()
    assert cli._metrics.snapshot()["inflight"] == 1


# -- device-trace correlation marker -------------------------------------------


def test_frame_annotation_marker(monkeypatch):
    from nnstreamer_tpu.utils import profile

    seen = []

    class FakeAnnotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    # inactive profiler: no-op regardless of ids
    with profile.frame_annotation(["aa-1"]):
        pass
    assert seen == []
    profile._active.set()
    try:
        with profile.frame_annotation([]):
            pass
        assert seen == []  # no sampled frames: still no annotation
        with profile.frame_annotation(["aa-1", "bb-2"]):
            pass
        assert seen == ["nns:frames:aa-1,bb-2"]
    finally:
        profile._active.clear()


def test_dispatch_carries_trace_id_to_annotation(monkeypatch):
    """End to end: a traced frame through tensor_filter under an
    active profiler wraps the invoke in nns:frames:<id>."""
    from nnstreamer_tpu.utils import profile

    seen = []

    class FakeAnnotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    spec = TensorsSpec.parse(SHAPE_SPEC, "float32")
    p = Pipeline(name="dobs-ann")
    src = AppSrc(name="src", spec=spec, max_buffers=8)
    flt = make("tensor_filter", el_name="net", framework="custom-easy",
               model="dobs_x3")
    sink = AppSink(name="out", max_buffers=8)
    p.add(src, flt, sink).link(src, flt, sink)
    profile._active.set()
    try:
        with LatencyTracer(sample_every=1) as tr:
            with p:
                src.push_buffer(Buffer.of(
                    np.ones((1, 4), np.float32), pts=0))
                src.end_of_stream()
                assert p.wait_eos(timeout=10)
        rid = tr.records()[0]["id"]
    finally:
        profile._active.clear()
    # per-element span() annotations record too; the frame marker is the
    # one carrying the trace id
    assert f"nns:frames:{rid}" in seen
