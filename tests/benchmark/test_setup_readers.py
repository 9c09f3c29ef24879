"""The readers of the set-up timeline (PR 37) on the CPU: the partition
of ``setup_s`` on made-up spans, the slow fences noted host-late, what a
program without the spans gives, that every new metric resolves through
the loader as the accepted ones do, and the readers on a traced toy
rehearsal.  No number here is a rate."""

import json
import os
import sys
import types
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
from benchmark.run import Loader, _read_metric, run_cell  # noqa: E402

Span = namedtuple("Span", "name start_ns end_ns thread window kind note")
MS = 1_000_000
ORIGIN_S = 100.0                    # the made-up run's T_PROCESS_START
SETUP_S = 30.0
SPAN_METRICS = {
    "pipeline_start_s": ["parse", "start"],
    "first_window_s": ["first_window"],
    "model_register_s": ["register"],
    "negotiate_s": ["fuse", "negotiate"],
    "backend_compile_s": ["compile_or_load"],
    "trace_lower_s": ["trace", "lower"],
    "cost_capture_s": ["cost_capture"],
    "state_init_s": ["state_init"],
}
GAP_METRICS = {"setup_before_program_s": "before_program",
               "setup_unnamed_s": "unnamed"}
NEW_METRICS = sorted(SPAN_METRICS) + sorted(GAP_METRICS) + ["host_late_ms"]


def _setup(name, a_s, b_s, thread=9, note=None, kind="setup"):
    at = int(ORIGIN_S * 1e9)
    return Span(name, at + int(a_s * 1e9), at + int(b_s * 1e9), thread,
                None, kind, note)


def _kept():
    """A made-up run of 30 s of set-up: 8 s before the program's first
    span; register; 3 s of the application's ring; parse and start
    (negotiate, open, a build, activate and stage inside it, with 0.3 s
    of start that nothing names); first_window on the streaming thread,
    overlapped by a first_call that began before start returned; 1 s of
    the application's warm-up before the window opens; a capture and two
    slow fences after it."""
    return [
        _setup("m/register", 8.0, 8.001),
        _setup("pipeline/parse", 11.001, 11.5),
        _setup("pipeline/start", 11.5, 20.0),
        _setup("pipeline/fuse", 11.5, 11.6),
        _setup("pipeline/negotiate", 11.6, 17.0),
        _setup("el_net/open", 11.6, 15.0),
        _setup("el_net/state_init", 11.7, 13.0),
        _setup("el_net/trace_lower", 13.0, 14.0),
        _setup("jax/trace", 13.0, 13.6, note="step"),
        _setup("jax/lower", 13.6, 14.0, note="jit(step)"),
        _setup("el_net/cost_capture", 14.0, 15.0),
        _setup("el_net/trace_lower", 15.0, 17.0),
        _setup("el_src/activate", 17.3, 19.9),
        _setup("el_src/stage", 17.3, 19.9),
        _setup("el_net/first_call", 19.95, 28.0, thread=1),
        _setup("jax/compile_or_load", 20.5, 27.0, thread=1),
        _setup("pipeline/first_window", 20.0, 29.0, thread=1),
        # after window open: the program's text built again (clipped)
        _setup("el_net/trace_lower", 80.0, 82.0),
        _setup("trace/start", 30.9, 31.0, kind="trace"),
        _setup("trace/capture", 31.0, 34.0, kind="trace"),
        _setup("trace/stop", 34.0, 35.0, kind="trace"),
        _setup("el_sink/fence", 32.0, 32.08, 1, "next window done: host late",
               "window"),
        _setup("el_sink/fence", 32.5, 32.51, 1, None, "window"),
        _setup("el_sink/fence", 40.0, 40.1, 1,
               "next window running: device late", "slow"),
        _setup("el_sink/fence", 41.0, 41.06, 1, "next window done: host late",
               "slow"),
        # before the capture: warm-up, out
        _setup("el_sink/fence", 29.5, 29.7, 1, "next window done: host late",
               "slow"),
    ]


@pytest.fixture
def made_up(monkeypatch):
    monkeypatch.setattr(S, "program_spans", _kept)
    # the harness module that is already loaded gives the origin
    monkeypatch.setitem(sys.modules, "__main__", types.SimpleNamespace(
        T_PROCESS_START=ORIGIN_S))
    return Loader(REPO), {"setup_s": SETUP_S}


@pytest.mark.parametrize("metric,value", [
    ("setup_before_program_s", 8.0),
    # the ring 3.0 and the warm-up 1.0: what lies under start and
    # first_window is named, whatever is inside them
    ("setup_unnamed_s", 4.0),
    ("pipeline_start_s", 8.999),                # parse 0.499 + start 8.5
    ("first_window_s", 9.0),
    ("model_register_s", 0.001),
    ("negotiate_s", 5.5),
    ("backend_compile_s", 6.5),
    ("trace_lower_s", 1.0),                     # jax's parts, not the span
    ("cost_capture_s", 1.0),
    ("state_init_s", 1.3),
    ("host_late_ms", 140.0),                    # 80 + 60; the device's 100 out
])
def test_reader_values_on_made_up_spans(made_up, metric, value):
    loader, obs = made_up
    assert _read_metric(loader, "readers", metric, obs) == \
        pytest.approx(value, abs=1e-6)


def test_the_partition_adds_up_to_setup_s(made_up, capsys):
    loader, obs = made_up
    before = _read_metric(loader, "readers", "setup_before_program_s", obs)
    unnamed = _read_metric(loader, "readers", "setup_unnamed_s", obs)
    begin = int(ORIGIN_S * 1e9)
    end = begin + int(SETUP_S * 1e9)
    clipped = [s._replace(start_ns=max(s.start_ns, begin),
                          end_ns=min(s.end_ns, end))
               for s in _kept() if s.kind == "setup" and s.start_ns < end]
    union = S.union_ns(clipped) * 1e-9
    assert before + unnamed + union == pytest.approx(SETUP_S, abs=1e-6)
    said = capsys.readouterr().out
    # each stretch of 0.5 s or more by the spans around it
    assert "unnamed stretch: 3.000 s from 8.001 s, after m/register, " \
        "before pipeline/parse" in said
    assert "unnamed stretch: 1.000 s from 29.000 s, after " \
        "pipeline/first_window, before window open" in said
    # and what no child names inside a root, from 0.25 s
    assert "inside pipeline/start: 0.300 s from 17.000 s that no span " \
        "names, after pipeline/negotiate, before el_src/activate" in said
    assert "inside pipeline/first_window: 1.000 s from 28.000 s" in said
    assert said.count("inside pipeline/") == 2
    assert "pushed out: {'setup': 0, 'window': 0, 'slow': 0}" in said


def test_overlapping_and_multi_thread_spans_count_once():
    gap = Loader(REPO).module("readers", "setup_gap_s")
    rows = [Span("a/x", 10, 50, 1, None, "setup", None),
            Span("b/x", 20, 30, 1, None, "setup", None),     # nested
            Span("c/x", 40, 70, 2, None, "setup", None),     # another thread
            Span("d/x", 90, 130, 2, None, "setup", None)]    # past the end
    before, unnamed, named, stretches = gap.partition(rows, 0, 100)
    assert (before, unnamed, named) == (10, 20, 70)
    assert stretches == [(70, 90, "c/x", "d/x")]
    assert gap.partition([], 0, 100) is None
    assert gap.partition([Span("e/x", 100, 120, 1, None, "setup", None)],
                         0, 100) is None
    # a root's own stretches: other roots do not name them
    rows = [Span("pipeline/start", 0, 100, 1, None, "setup", None),
            Span("pipeline/first_window", 50, 100, 2, None, "setup", None),
            Span("el/activate", 10, 60, 1, None, "setup", None)]
    assert gap.inside_roots(rows, 0, 100) == [
        ("pipeline/start", 0, 10, None, "el/activate"),
        ("pipeline/start", 60, 100, "el/activate", None),
        ("pipeline/first_window", 60, 100, "el/activate", None)]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_give_none_without_spans(made_up, monkeypatch, metric):
    loader, obs = made_up
    monkeypatch.setattr(S, "program_spans", lambda: None)
    assert _read_metric(loader, "readers", metric, obs) is None
    # spans, and none of set-up (and no capture)
    monkeypatch.setattr(S, "program_spans", lambda: [
        s for s in _kept() if s.kind in ("window", "slow")])
    assert _read_metric(loader, "readers", metric, obs) is None


def test_the_partition_needs_the_loaded_harness_origin(made_up, monkeypatch):
    loader, obs = made_up
    gap = loader.module("readers", "setup_gap_s")
    assert gap.origin_s() == ORIGIN_S
    # `python3 -m benchmark.run`: __main__ wins over a second import
    monkeypatch.setitem(sys.modules, "benchmark.run", types.SimpleNamespace(
        T_PROCESS_START=ORIGIN_S + 5))
    assert gap.origin_s() == ORIGIN_S
    monkeypatch.setitem(sys.modules, "__main__", types.SimpleNamespace())
    assert gap.origin_s() == ORIGIN_S + 5
    monkeypatch.delitem(sys.modules, "benchmark.run")
    assert gap.origin_s() is None
    for metric in GAP_METRICS:
        assert _read_metric(loader, "readers", metric, obs) is None


def test_host_late_is_none_where_fences_carry_no_note(made_up, monkeypatch):
    """The parent commit's fences say nothing of who was late: no 0 is
    reported for it."""
    from nnstreamer_tpu.utils import profile

    loader, obs = made_up
    monkeypatch.delattr(profile, "HOST_LATE")
    assert _read_metric(loader, "readers", "host_late_ms", obs) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_resolves_as_the_accepted_ones_do(metric):
    loader = Loader(REPO)
    entry = loader.entry("per_layer", metric)
    assert entry["source"] == "program_span"
    assert loader.entry("end_to_end", entry["moves"])
    spec_path = os.path.join(loader.dir, "layer_metrics", metric + ".json")
    if metric == "host_late_ms":
        assert not os.path.isfile(spec_path)
        assert (entry["moves"], entry["unit"]) == ("fps_per_chip", "ms")
        assert callable(loader.module("readers", metric).read)
        return
    spec = loader.json("layer_metrics", metric)
    assert spec["name"] == metric and entry["moves"] == "setup_s"
    assert entry["unit"] == "s" and entry["better"] == "lower"
    if metric in SPAN_METRICS:
        assert spec == {"name": metric, "reader": "setup_span_s",
                        "args": {"phases": SPAN_METRICS[metric]}}
    else:
        assert spec == {"name": metric, "reader": "setup_gap_s",
                        "args": {"part": GAP_METRICS[metric]}}
    assert callable(loader.module("readers", spec["reader"]).read)
    # every cell: the accepted tests of the stateful cells hold each
    # cell's list of metrics closed, so none of these names a cell
    assert "workloads" not in entry
    cells = [w["name"] for w in loader.manifest["workloads"]]
    assert [c for c in cells if loader.reports(entry, c)] == cells


def test_new_entries_are_appended_and_nothing_else_moved():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-11:] == [
        "setup_before_program_s", "setup_unnamed_s", "pipeline_start_s",
        "first_window_s", "model_register_s", "negotiate_s",
        "backend_compile_s", "trace_lower_s", "cost_capture_s",
        "state_init_s", "host_late_ms"]
    assert len(names) == len(set(names)) <= 128
    assert len(manifest["workloads"]) == 6 and len(manifest["configs"]) == 5


# -- a traced toy rehearsal ---------------------------------------------------


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The toy root as it is, built with the new entries, and one traced
    run of its replay cell."""
    root = toyroot.build(str(tmp_path_factory.mktemp("toysetup")))
    details: dict = {}
    line = run_cell("toy_ssd.replay", 2 ** 31 + 37, 0.6, True, root=root,
                    rehearsal=True, details=details)
    return root, line, details


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_reader_returns_a_number_on_a_toy_rehearsal(traced, metric):
    _root, line, _details = traced
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"][metric]
    assert isinstance(got["value"], float) and np.isfinite(got["value"])
    assert got["value"] >= 0.0
    if metric not in ("host_late_ms", "setup_unnamed_s"):
        assert got["value"] > 0.0


def test_a_stateless_filter_keeps_state_init_for_its_weights(traced):
    """``state_init_s`` is read in every cell: a stateless model's
    weights go onto the device under the same span a stateful model's
    weights and state do."""
    _root, _line, _details = traced
    rows = [s for s in S.program_spans() if s.name == "el_net/state_init"]
    assert rows and any("B of weights put on" in (s.note or "")
                        for s in rows)


def test_toy_set_up_adds_up_and_leaves_nothing_inside_a_root(traced):
    _root, line, details = traced
    values = {k: v["value"] for k, v in line["metrics"].items()}
    setup_s = details["obs"]["setup_s"]
    gap = Loader(REPO).module("readers", "setup_gap_s")
    begin = int(gap.origin_s() * 1e9)
    end = begin + int(setup_s * 1e9)
    rows = [s for s in S.program_spans() if s.kind == "setup"
            and s.end_ns > begin and s.start_ns < end]
    before, unnamed, named, _stretches = gap.partition(rows, begin, end)
    assert before * 1e-9 == pytest.approx(values["setup_before_program_s"])
    assert unnamed * 1e-9 == pytest.approx(values["setup_unnamed_s"])
    assert (before + unnamed + named) * 1e-9 == pytest.approx(setup_s,
                                                              abs=1e-6)
    # the roots and what the application does between them are all
    # there is: the named part is what start, parse, register and
    # first_window cover
    roots = [s for s in rows if S.phase(s) in (
        "register", "parse", "start", "first_window")]
    assert S.union_ns(roots) >= 0.97 * named
    # the program's first span is the application's first call into it
    assert min(rows, key=lambda s: s.start_ns).name.endswith("/register")
