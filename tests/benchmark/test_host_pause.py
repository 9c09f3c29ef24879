"""The reader of the program's pause ledger (PR 53) on the CPU:
``host_pause_ms`` on made-up pauses (the window's edges, the profiler's
own stop, innermost only, a program without the ledger), that its
metric resolves through the loader for every cell and was appended, and
that a traced toy rehearsal's line holds it.  No number here is a
rate."""

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
Pause = namedtuple("Pause", "name thread window start_ns end_ns note "
                   "baseline_age_ns deltas cause")
ORIGIN_S, SETUP_S, WINDOW_S = 100.0, 30.0, 45.0
METRIC = "host_pause_unexplained_ms"
HOST_LATE = "next window done: host late"


def _at(a_s, b_s):
    """Seconds of the made-up WINDOW to the spans' clock."""
    at = int((ORIGIN_S + SETUP_S) * 1e9)
    return at + int(round(a_s * 1e9)), at + int(round(b_s * 1e9))


def _pause(name, a_s, b_s, cause, thread=1, note=None, **deltas):
    start, end = _at(a_s, b_s)
    return Pause(name, thread, 7, start, end, note, 40_000_000,
                 deltas, cause)


def _pauses():
    return [
        # before the window opens: warm-up, out
        _pause("el_sink/fence", -2.0, -1.8, "unexplained", note=HOST_LATE),
        # over the window's opening edge: 30 of its 100 ms are inside
        _pause("el_sink/fence", -0.07, 0.03, "unexplained", note=HOST_LATE,
               process_cpu_ns=38_000_000),
        # a slow fence, and the chain spans around it: counted once
        _pause("el_norm", 5.0, 5.13, "unexplained"),
        _pause("el_net", 5.005, 5.125, "unexplained"),
        _pause("el_sink/fence", 5.01, 5.12, "unexplained", note=HOST_LATE,
               thread_cpu_ns=12_000_000, process_cpu_ns=40_000_000,
               gc_collections=0),
        # the same stretch on another thread is its own pause
        _pause("q/push", 5.02, 5.08, "gc", thread=2),
        # what the profiler's own stop did to the stream: left out
        _pause("el_net/dispatch", 7.5, 8.4, "on_cpu"),
        _pause("el_sink/fence", 7.2, 7.9, "unexplained", thread=2),
        # nothing accounts for these
        _pause("el_sink/fence", 20.0, 20.11, "unexplained",
               note="next window running: device late", thread_cpu_ns=100),
        _pause("el_net/dispatch", 21.0, 21.06, "unexplained"),
        # the collector's and the thread's own: logged, in no entry
        _pause("el_net/prep", 30.0, 30.09, "gc", gc_ns=80_000_000),
        _pause("el_net/prep", 31.0, 31.07, "on_cpu"),
        # over the closing edge: 20 of 60 ms inside
        _pause("el_sink/fence", 44.98, 45.04, "unexplained"),
        # after the window: the drain, out
        _pause("el_sink/fence", 46.0, 46.2, "unexplained"),
    ]


def _spans():
    return [Span("trace/start", *_at(0.9, 1.0), 9, None, "trace", None),
            Span("trace/capture", *_at(1.0, 4.0), 9, None, "trace", None),
            Span("trace/stop", *_at(7.0, 7.6), 9, None, "trace", None)]


@pytest.fixture
def made_up(monkeypatch):
    from nnstreamer_tpu.utils import profile

    monkeypatch.setattr(S, "program_spans", _spans)
    monkeypatch.setattr(profile, "pauses", _pauses)
    monkeypatch.setattr(profile, "pauses_dropped", lambda: 3)
    monkeypatch.setitem(sys.modules, "__main__", types.SimpleNamespace(
        T_PROCESS_START=ORIGIN_S))
    loader = Loader(REPO)
    loader.module("readers", "host_pause_ms")._SAID.clear()
    return loader, {"setup_s": SETUP_S, "window_s": WINDOW_S}


def test_the_metric_on_made_up_pauses(made_up):
    # 30 of the opening edge's 100 + the fence 110 (not el_net, not
    # el_norm) + 110 + 60 + 20 of the closing edge's 60; thread 2's 700 ms
    # overlap the profiler's stop
    loader, obs = made_up
    assert _read_metric(loader, "readers", METRIC, obs) == \
        pytest.approx(330.0, abs=1e-6)


@pytest.mark.parametrize("causes,value", [
    (["gc"], 150.0),                     # the other thread's 60 + 90
    (["on_cpu"], 70.0),                  # the profiler's 900 left out
    (["gc", "on_cpu", "unexplained"], 550.0),
    ([], 0.0),
])
def test_the_reader_sums_the_causes_it_is_given(made_up, causes, value):
    loader, obs = made_up
    read = loader.module("readers", "host_pause_ms").read
    assert read(obs, causes=causes) == pytest.approx(value, abs=1e-6)


def test_every_pause_is_logged_once_with_its_cause_and_deltas(
        made_up, capsys):
    loader, obs = made_up
    read = loader.module("readers", "host_pause_ms").read
    for causes in (["unexplained"], ["gc"]):
        read(obs, causes=causes)
    said = capsys.readouterr().out
    assert said.count("[bench] pauses the program's list pushed "
                      "out: 3") == 1
    lines = [ln for ln in said.splitlines()
             if ln.startswith("[bench] pause el_") or "pause q/" in ln]
    # the twelve innermost, once each, though read twice
    assert len(lines) == 12
    fence = next(ln for ln in lines if "at +5.010 s" in ln)
    assert "el_sink/fence: 110.0 ms" in fence and "cause unexplained" in fence
    assert "(in the window), window 7, note " + HOST_LATE in fence
    assert "baseline 40.0 ms before" in fence
    assert "gc_collections 0, process_cpu 40.0 ms, thread_cpu 12.0 ms" \
        in fence
    assert "inside ['el_norm', 'el_net']" in fence
    assert sum("the profiler's own, left out" in ln for ln in lines) == 2
    assert sum("outside the window" in ln for ln in lines) == 2
    for cause in ("gc", "on_cpu"):
        assert any(f"cause {cause};" in ln for ln in lines)


def test_reader_gives_none_without_the_ledger_and_zero_without_a_pause(
        made_up, monkeypatch):
    from nnstreamer_tpu.utils import profile

    loader, obs = made_up
    monkeypatch.setattr(profile, "pauses", lambda: [])
    assert _read_metric(loader, "readers", METRIC, obs) == 0.0
    # a program that keeps spans and no ledger, as the parent does: the
    # readers beside this one still read, this one says nothing
    monkeypatch.delattr(profile, "pauses")
    monkeypatch.delattr(profile, "pauses_dropped")
    assert _read_metric(loader, "readers", METRIC, obs) is None
    assert _read_metric(loader, "readers", "slow_host_ms", obs) is not None


def test_reader_gives_none_where_the_process_start_is_not_known(
        made_up, monkeypatch):
    loader, obs = made_up
    monkeypatch.setitem(sys.modules, "__main__", types.SimpleNamespace())
    monkeypatch.delitem(sys.modules, "benchmark.run", raising=False)
    assert _read_metric(loader, "readers", METRIC, obs) is None


def test_innermost_keeps_what_holds_no_other_of_its_thread(made_up):
    loader, _obs = made_up
    reader = loader.module("readers", "host_pause_ms")
    rows = [Pause("a", 1, None, 0, 100, None, None, {}, "x"),
            Pause("a/b", 1, None, 10, 90, None, None, {}, "x"),
            Pause("c", 2, None, 20, 80, None, None, {}, "x"),   # thread 2
            Pause("a/d", 1, None, 200, 300, None, None, {}, "x")]
    assert [(p.name, around) for p, around in reader.innermost(rows)] == [
        ("a/b", ["a"]), ("c", []), ("a/d", [])]


def test_the_pause_metric_resolves_for_every_cell():
    loader = Loader(REPO)
    entry = loader.entry("per_layer", METRIC)
    assert entry == {"name": METRIC, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "element runtime",
                     "moves": "fps_per_chip"}
    assert loader.json("layer_metrics", METRIC) == {
        "name": METRIC, "reader": "host_pause_ms",
        "args": {"causes": ["unexplained"]}}
    assert callable(loader.module("readers", "host_pause_ms").read)
    cells = [w["name"] for w in loader.manifest["workloads"]]
    assert len(cells) >= 10
    assert [c for c in cells if loader.reports(entry, c)] == cells
    # it times what slow_host_ms times from outside: the same layer
    assert loader.entry("per_layer", "slow_host_ms")["layer"] == \
        entry["layer"]


def test_the_entry_was_appended_after_everything_that_was_there():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(METRIC)
    assert at >= 125 and len(names) == len(set(names)) <= 128
    assert "host_late_ms" in names[:at] and "slow_host_ms" in names[:at]
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "layer_metrics", METRIC + ".json"))
    # no entry for a cause no host the benchmark reaches can read
    assert not [n for n in names if n.startswith("host_pause_")
                and n != METRIC]


# -- a traced toy rehearsal ---------------------------------------------------


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = toyroot.build(str(tmp_path_factory.mktemp("toypause")))
    details: dict = {}
    line = run_cell("toy_vit.replay", 2 ** 31 + 53, 0.6, True, root=root,
                    rehearsal=True, details=details)
    return root, line, details


def test_the_pause_metric_is_in_the_line_of_a_toy_rehearsal(traced):
    _root, line, _details = traced
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"][METRIC]
    assert got["unit"] == "ms" and np.isfinite(got["value"])
    assert 0.0 <= got["value"] <= 600.0          # no more than the window


def test_the_toy_runs_pauses_are_the_programs_slow_spans(traced):
    """Every pause of the rehearsal is one of its slow per-window spans,
    end for end, and the accepted readers saw no new kind of span."""
    from nnstreamer_tpu.utils import profile

    _root, line, _details = traced
    kept = S.program_spans()
    assert {s.kind for s in kept} <= {"setup", "window", "slow", "trace"}
    slow = {(s.name, s.thread, s.start_ns, s.end_ns) for s in kept
            if s.kind in ("window", "slow")
            and s.end_ns - s.start_ns >= S.SLOW_NS}
    for p in profile.pauses():
        assert (p.name, p.thread, p.start_ns, p.end_ns) in slow
        assert p.cause in ("gc", "on_cpu", "unexplained")
        assert set(p.deltas) <= {
            "thread_cpu_ns", "process_cpu_ns", "gc_ns", "gc_collections",
            "gc_generation", "gc_other_thread"}
    assert "host_late_ms" in line["metrics"]
    assert "slow_host_ms" in line["metrics"]
