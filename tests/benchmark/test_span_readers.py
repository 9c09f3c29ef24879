"""The readers of the program's phase spans and the four-chip replay
cell, on the CPU: the span arithmetic on made-up spans, every new reader
on a traced toy rehearsal, what a program without spans gives, and the
existing trace reduction naming a phase span over its element's.  No
number here is a rate."""

import json
import os
import sys
from collections import namedtuple

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for _p in (REPO, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import toyroot  # noqa: E402
from benchmark import spans as S  # noqa: E402
from benchmark import trace  # noqa: E402
from benchmark.run import Loader, _read_metric, run_cell  # noqa: E402

Span = namedtuple("Span", "name start_ns end_ns thread window kind note")
MS = 1_000_000
NEW_METRICS = ["host_ms_per_window", "fence_wait_ms_per_window",
               "place_ms_per_window", "reshard_bytes_per_frame",
               "slow_host_ms", "program_load_s", "staging_s"]


def _window(t0, w, thread=1):
    """One replay window on a streaming thread, in ms from ``t0``:
    create 0-1, el_norm 1-20 holding el_net 2-19 (place 3-5, dispatch
    5-6, sample_fence 6-8) holding el_sink 9-18 (fence 9-16, render
    16-18 with a render_wait 17-18)."""
    rows = [("el_src/create", 0, 1), ("el_norm", 1, 20), ("el_net", 2, 19),
            ("el_net/place", 3, 5), ("el_net/dispatch", 5, 6),
            ("el_net/sample_fence", 6, 8), ("el_sink", 9, 18),
            ("el_sink/fence", 9, 16), ("el_sink/render", 16, 18),
            ("el_sink/render_wait", 17, 18)]
    return [Span(n, (t0 + a) * MS, (t0 + b) * MS, thread, w, "window", None)
            for n, a, b in rows]


def _kept():
    return ([Span("el_net/trace_lower", 0, 2_000 * MS, 9, None, "setup",
                  None),
             Span("el_net/first_call", 3_000 * MS, 9_000 * MS, 1, 0,
                  "setup", None),
             Span("el_net/load_or_compile", 3_500 * MS, 8_000 * MS, 1, 0,
                  "setup", "miss"),
             Span("el_src/stage", 2_000 * MS, 2_500 * MS, 9, None, "setup",
                  None),
             # warm-up, before the capture: slow and out
             Span("el_net/dispatch", 3_000 * MS, 9_000 * MS, 1, 0, "slow",
                  None),
             Span("trace/start", 9_900 * MS, 10_000 * MS, 9, None, "trace",
                  "d"),
             Span("trace/capture", 10_000 * MS, 10_100 * MS, 9, None,
                  "trace", "d"),
             Span("trace/stop", 10_100 * MS, 11_000 * MS, 9, None, "trace",
                  "d")]
            + _window(10_000, 1) + _window(10_020, 2) + _window(10_040, 3)
            # after the capture: a host pause in dispatch, seen through
            # its parents too, and a long fence
            + [Span("el_norm", 12_000 * MS, 12_200 * MS, 1, 9, "slow", None),
               Span("el_net", 12_001 * MS, 12_199 * MS, 1, 9, "slow", None),
               Span("el_net/dispatch", 12_010 * MS, 12_140 * MS, 1, 9,
                    "slow", None),
               Span("el_sink/fence", 12_300 * MS, 12_400 * MS, 1, 10,
                    "slow", None)])


def test_span_arithmetic_on_made_up_spans():
    kept = _kept()
    assert S.last_capture(kept) == (10_000 * MS, 10_100 * MS, 0)
    rows = S.captured(kept)
    assert len(rows) == 30 and S.windows(rows) == 3
    self_ms = {}
    for s, ns in S.self_ns(rows):
        self_ms[s.name] = self_ms.get(s.name, 0) + ns / MS
    # a span's self time is its length less what its children cover
    assert self_ms["el_norm"] == 3 * 2 and self_ms["el_net"] == 3 * 3
    assert self_ms["el_sink"] == 0 and self_ms["el_sink/render"] == 3 * 1
    assert self_ms["el_sink/fence"] == 3 * 7
    # the thread's time from the first to the last dispatch is covered
    total = sum(ns for _s, ns in S.self_ns(rows))
    assert total == 3 * 20 * MS
    assert S.is_wait(rows[7]) and not S.is_wait(rows[4])
    slow = S.innermost_slow(kept, 10_000 * MS)
    assert [s.name for s in slow] == ["el_net/dispatch", "el_sink/fence"]
    assert S.union_ns([s for s in kept if s.kind == "setup"
                       and S.phase(s) in S.LOAD_PHASES]) == 8_000 * MS
    assert S.last_capture([s for s in kept if s.kind != "trace"]) is None


@pytest.fixture
def made_up(monkeypatch):
    monkeypatch.setattr(S, "program_spans", _kept)
    return Loader(REPO)


@pytest.mark.parametrize("metric,value", [
    # (1 + 2 + 3 + 2 + 1 + 1) ms of self time outside the waits, a window
    ("host_ms_per_window", 10.0),
    ("fence_wait_ms_per_window", 9.0),          # fence 7 + sample_fence 2
    ("place_ms_per_window", 2.0),
    ("slow_host_ms", 130.0),                    # the dispatch, not the fence
    ("program_load_s", 8.0),                    # a union, not a sum
    ("staging_s", 0.5),
])
def test_reader_values_on_made_up_spans(made_up, metric, value):
    obs = {"frames": 30, "window": {"ledger": {}}}
    assert _read_metric(made_up, "readers", metric, obs) == \
        pytest.approx(value)


@pytest.mark.parametrize("metric", [m for m in NEW_METRICS
                                    if m != "reshard_bytes_per_frame"])
def test_span_readers_give_none_without_spans_or_capture(monkeypatch,
                                                         metric):
    """The parent commit's program keeps no spans: the reader returns
    nothing and does not raise, and the line leaves the metric out."""
    loader = Loader(REPO)
    obs = {"frames": 30, "window": {"ledger": {}}}
    monkeypatch.setattr(S, "program_spans", lambda: None)
    assert _read_metric(loader, "readers", metric, obs) is None
    monkeypatch.setattr(S, "program_spans", lambda: [
        s for s in _kept() if s.kind != "trace"])
    assert _read_metric(loader, "readers", metric, obs) is None


def test_program_spans_is_none_for_a_program_without_the_trace_layer(
        monkeypatch):
    from nnstreamer_tpu.utils import profile

    assert S.program_spans() is not None
    monkeypatch.delattr(profile, "spans")
    assert S.program_spans() is None


def test_reshard_bytes_reads_the_d2d_input_row():
    loader = Loader(REPO)
    obs = {"frames": 512,
           "window": {"ledger": {"d2d.input.bytes": 512 * 270_000}}}
    assert _read_metric(loader, "readers", "reshard_bytes_per_frame",
                        obs) == 270_000.0
    obs["window"]["ledger"] = {}
    assert _read_metric(loader, "readers", "reshard_bytes_per_frame",
                        obs) == 0.0
    obs["frames"] = 0
    assert _read_metric(loader, "readers", "reshard_bytes_per_frame",
                        obs) is None


# -- a traced toy rehearsal ---------------------------------------------------


@pytest.fixture(scope="module")
def traced_line(tmp_path_factory):
    root = toyroot.build(str(tmp_path_factory.mktemp("toyspans")))
    return run_cell("toy_ssd.replay", 2 ** 31 + 24, 0.6, True, root=root,
                    rehearsal=True)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_reader_returns_a_number_on_a_toy_rehearsal(traced_line,
                                                             metric):
    assert traced_line["correct"] is True and traced_line["failed"] == 0
    got = traced_line["metrics"][metric]
    assert isinstance(got["value"], float) and np.isfinite(got["value"])
    assert got["value"] >= 0.0
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == metric)
    assert got["unit"] == entry["unit"]
    if metric in ("host_ms_per_window", "fence_wait_ms_per_window",
                  "program_load_s", "staging_s"):
        assert got["value"] > 0.0
    if metric in ("place_ms_per_window", "reshard_bytes_per_frame"):
        assert got["value"] == 0.0      # one device: nothing is placed


def test_toy_capture_covers_the_streaming_thread(traced_line):
    """Between the first and the last dispatch of the capture the
    streaming thread's spans leave little uncovered (the glue of the
    source's loop), even at a toy window of a fraction of a
    millisecond."""
    rows = S.captured(S.program_spans())
    calls = [s for s in rows if S.phase(s) == S.WINDOW_PHASE]
    thread = calls[0].thread
    begin, end = calls[0].start_ns, calls[-1].end_ns
    mine = [s for s in rows if s.thread == thread
            and s.start_ns >= begin and s.end_ns <= end]
    covered = sum(ns for _s, ns in S.self_ns(mine))
    assert covered / (end - begin) > 0.8
    assert {"el_src/create", "el_norm", "el_net", "el_net/prep",
            "el_net/dispatch", "el_sink", "el_sink/fence",
            "el_sink/render"} <= {s.name for s in mine}


# -- the existing reduction names phases --------------------------------------


def _planes(spans):
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", 0.0, 100.0),
           ("%fusion.1 = f32[8]{0} fusion(%p)", 1100.0, 100.0),
           ("%fusion.1 = f32[8]{0} fusion(%p)", 1700.0, 100.0)]
    return [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops},
                {"name": "XLA Modules", "events": [
                    ("jit_normalized(1)", 0.0, 100.0),
                    ("jit_normalized(1)", 1100.0, 100.0),
                    ("jit_normalized(1)", 1700.0, 100.0)]}]},
            {"name": "/host:CPU", "lines": [
                {"name": "python", "events": spans}]}]


def test_idle_gaps_are_put_down_to_the_innermost_phase_span():
    """``benchmark/trace.py`` matches spans by the element prefix and
    takes, of the spans that cover a gap, the innermost: a phase span
    (``el_net/place``) wins over its element's chain span (``el_net``),
    which wins over the upstream element's (``el_norm``)."""
    spans = [("el_norm", 50.0, 1900.0), ("el_net", 60.0, 1800.0),
             ("el_net/place", 90.0, 1020.0),        # covers the first gap
             ("el_sink", 1150.0, 600.0),
             ("el_sink/fence", 1190.0, 520.0)]      # covers the second
    gap = (100.0, 1100.0)
    assert trace._attribute(gap, spans) == "el_net/place"
    assert trace._attribute(gap, spans[:2]) == "el_net"
    assert trace._attribute(gap, spans[:1]) == "el_norm"
    out = trace.reduce_trace(_planes(spans), 1, "el_")
    assert out["idle_gaps"] == [["el_net/place", pytest.approx(1000e-9)],
                                ["el_sink/fence", pytest.approx(500e-9)]]


# -- the four-chip replay cell ------------------------------------------------


def test_mesh4_cell_is_the_replay_cell_over_a_data_mesh():
    loader = Loader(REPO)
    entry = loader.entry("workloads", "ssd300.replay.mesh4")
    like = loader.entry("workloads", "ssd300.replay")
    assert entry["chips"] == 4 and entry["config"] == like["config"]
    mix = loader.json("traffic", entry["traffic"])
    base = loader.json("traffic", like["traffic"])
    assert {k: v for k, v in mix.items() if k != "why"} == \
        {k: v for k, v in base.items() if k != "why"}
    line = loader.json("workloads", entry["name"])["launch"]
    assert line.replace(" mesh=data:4", "") == \
        loader.json("workloads", like["name"])["launch"]
    assert "model={model} mesh=data:4 !" in line
    # the only four-chip cell, and every new per-layer metric reaches it
    # without a list of cells (toyroot.py maps every listed cell to a
    # toy one, and a four-chip cell has none)
    per_layer = {m["name"]: m for m in loader.manifest["per_layer"]}
    for name in NEW_METRICS:
        assert "workloads" not in per_layer[name]
        assert loader.reports(per_layer[name], entry["name"])
        assert per_layer[name]["source"] in ("program_span",
                                             "program_counter")
