"""The program's trace layer (``utils/profile.py``) on the CPU: what a
span keeps and when, what ``pipeline_trace`` hands the profiler, the
stage vocabulary inside the fused programs, and ``stage_seconds`` on a
CPU capture.  No number here is a rate."""

import glob
import os
import re
import time
import timeit

import numpy as np
import pytest

from nnstreamer_tpu.obs import hooks
from nnstreamer_tpu.utils import profile
from nnstreamer_tpu.utils.profile import span, stage_of


@pytest.fixture(autouse=True)
def _clean():
    profile.clear()
    yield
    profile._active.clear()
    profile.clear()


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    entered: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def capture(monkeypatch):
    """A capture that is active without a profiler behind it."""
    import jax

    _Annotation.entered = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    profile._active.set()
    yield _Annotation.entered
    profile._active.clear()


# -- what a span keeps --------------------------------------------------------


def test_nothing_kept_and_no_annotation_without_a_capture(monkeypatch):
    import jax

    def boom(*_a, **_k):
        raise AssertionError("the profiler was touched with no capture")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    with span("el_net", "dispatch", 3):
        pass
    with span("el_net"):
        pass
    assert profile.spans() == []
    assert not any(profile.spans_dropped().values())


def test_capture_keeps_every_span_under_its_name_on_both_clocks(capture):
    with span("el_norm", None, 7):
        with span("el_net", None, 7):
            with span("el_net", "dispatch"):
                pass
    rows = profile.spans()
    assert [s.name for s in rows] == ["el_norm", "el_net", "el_net/dispatch"]
    assert capture == ["el_norm", "el_net", "el_net/dispatch"]
    assert {s.kind for s in rows} == {"window"}
    # the site that did not know its window takes its parent's
    assert [s.window for s in rows] == [7, 7, 7]
    assert len({s.thread for s in rows}) == 1
    for outer, inner in zip(rows, rows[1:]):
        assert outer.start_ns <= inner.start_ns <= inner.end_ns \
            <= outer.end_ns


@pytest.mark.parametrize("kind,seconds,kept", [
    ("window", 0.0, 0),            # fast, no capture: gone
    ("window", 0.06, 1),           # slow, no capture: the slow list
    ("setup", 0.0, 1),             # set-up: always
])
def test_what_is_kept_outside_a_capture(kind, seconds, kept):
    with span("el_src", "stage", setup=kind == "setup") as s:
        s.note = "a note"
        time.sleep(seconds)
    rows = profile.spans()
    assert len(rows) == kept
    if kept:
        assert rows[0].name == "el_src/stage" and rows[0].note == "a note"
        assert rows[0].kind == ("setup" if kind == "setup" else "slow")
        assert rows[0].end_ns - rows[0].start_ns >= seconds * 1e9


def test_slow_threshold_is_fifty_milliseconds(monkeypatch):
    assert profile.SLOW_NS == 50_000_000
    ticks = iter([0, 49_999_999, 100, 100 + 50_000_000])
    monkeypatch.setattr(profile.time, "perf_counter_ns", lambda: next(ticks))
    with span("a", "x"):
        pass
    with span("b", "x"):
        pass
    assert [s.name for s in profile.spans()] == ["b/x"]


@pytest.mark.parametrize("which", ["slow", "window", "setup"])
def test_lists_are_bounded_and_keep_the_newest(monkeypatch, which):
    assert (profile.SLOW_MAX, profile._REC.lists["slow"].maxlen) == \
        (1024, 1024)
    rec = profile._Recorder({"setup": 3, "window": 3, "slow": 3})
    monkeypatch.setattr(profile, "_REC", rec)
    for i in range(5):
        rec.keep(f"s{i}", i, i + 1, None, which)
    assert [s.name for s in profile.spans()] == ["s2", "s3", "s4"]
    # read per list: the other two lost nothing
    assert profile.spans_dropped() == {
        "setup": 0, "window": 0, "slow": 0, which: 2}
    profile.clear()
    assert profile.spans() == []
    assert not any(profile.spans_dropped().values())


def test_jax_build_steps_are_kept_inside_a_set_up_span_only(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(profile, "JAX_PART_MIN_S", 0.0)

    def f(x):
        return jnp.tanh(x) * 3.0

    jax.jit(f)(jnp.ones(7)).block_until_ready()     # outside: not kept
    with span("el_net", "first_call", setup=True):
        jax.jit(f)(jnp.ones(11)).block_until_ready()
    rows = profile.spans()
    outer = next(s for s in rows if s.name == "el_net/first_call")
    parts = [s for s in rows if s.name.startswith("jax/")]
    assert {"jax/trace", "jax/lower", "jax/compile_or_load"} <= {
        s.name for s in parts}
    for s in parts:
        assert s.kind == "setup" and s.thread == outer.thread
        assert s.end_ns <= outer.end_ns
        assert s.end_ns - s.start_ns <= outer.end_ns - outer.start_ns
    assert len(rows) == 1 + len(parts)


def test_everything_off_under_the_kill_switch(monkeypatch):
    import jax

    monkeypatch.setattr(hooks, "DISABLED", True)
    monkeypatch.setattr(profile.time, "perf_counter_ns",
                        lambda: pytest.fail("a clock was read"))
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda *_a: pytest.fail("the profiler was touched"))
    profile._active.set()
    with span("el_net", "dispatch"):
        pass
    with span("el_src", "stage", setup=True):
        pass
    assert profile.spans() == []


def test_report_slow_logs_each_name_once(monkeypatch):
    rec = profile._Recorder()
    monkeypatch.setattr(profile, "_REC", rec)
    rec.keep("old", 0, 60_000_000, 1, "slow")
    assert profile.report_slow(lambda *a: None) == 1
    rec.keep("el_net/dispatch", 0, 60_000_000, 1, "slow")
    rec.keep("el_net/dispatch", 0, 130_000_000, 2, "slow")
    rec.keep("el_sink/fence", 0, 70_000_000, 2, "slow")
    lines = []
    assert profile.report_slow(lambda msg, *a: lines.append(msg % a)) == 3
    assert len(lines) == 2
    assert "el_net/dispatch: 2 over 50 ms, 190.0 ms in all, longest " \
        "130.0 ms" in lines[0]
    assert profile.report_slow(lambda msg, *a: lines.append(msg % a)) == 0


def test_off_cost_of_a_span_is_well_under_a_microsecond_scale():
    """Prints the cost with no capture (the module docstring quotes it);
    asserts only an order of magnitude, the CI host being shared."""
    def one():
        with span("el_net", "dispatch", 1):
            pass

    n = 20000
    per = min(timeit.repeat(one, number=n, repeat=5)) / n
    print(f"span with no capture: {per * 1e6:.3f} us")
    assert per < 20e-6
    assert profile.spans() == []


# -- pipeline_trace -----------------------------------------------------------


@pytest.mark.parametrize("given", [False, True])
def test_pipeline_trace_forwards_options_only_when_given(monkeypatch, given):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append((a, k)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    options = object()
    kw = {"profiler_options": options} if given else {}
    with profile.pipeline_trace("/tmp/x", **kw) as where:
        assert where == "/tmp/x" and profile.trace_active()
    assert not profile.trace_active()
    assert calls == [(("/tmp/x",), kw)]
    names = [s.name for s in profile.spans()]
    assert sorted(names) == ["trace/capture", "trace/start", "trace/stop"]
    by = {s.name: s for s in profile.spans()}
    assert by["trace/start"].end_ns == by["trace/capture"].start_ns
    assert by["trace/capture"].end_ns == by["trace/stop"].start_ns
    assert by["trace/capture"].note == "/tmp/x"


def test_removed_hooks_stay_removed():
    assert not hasattr(profile, "step_marker")
    import inspect

    assert "create_perfetto_link" not in inspect.signature(
        profile.pipeline_trace).parameters
    assert not hasattr(profile, "annotate")      # PR 37: no caller
    # the names other callers import are still there
    assert callable(profile.frame_annotation) and callable(
        profile.trace_active)


# -- the stage vocabulary -----------------------------------------------------


@pytest.mark.parametrize("op_name,stage", [
    ("jit(normalized)/nns.pre/el_norm/sub", "nns.pre/el_norm"),
    ("jit(f)/nns.model/backbone/block03/conv_general_dilated",
     "nns.model/backbone/block03"),
    ("jit(f)/nns.model/vmap(nms)/jit(_where)/select_n", "nns.model/nms"),
    ("jit(f)/nns.model/vmap(topk)/top_k", "nns.model/topk"),
    ("jit(f)/nns.model/layer00/attn/...qd,...kd->...qk/dot_general",
     "nns.model/layer00/attn"),
    ("jit(f)/nns.model/heads/jit(relu6)/jit(clip)/max", "nns.model/heads"),
    ("jit(f)/nns.model/reduce_sum", "nns.model"),
    ("jit(f)/nns.post/overlay/transpose(jvp(inner))/mul",
     "nns.post/overlay/inner"),
    ("jit(f)/mul", profile.NO_SCOPE),
])
def test_stage_of(op_name, stage):
    assert stage_of(op_name) == stage


# appsink drop=true: a source that free-runs into a full appsink nobody
# drains would block in render, and stop() would wait behind it
SSD_LINE = ("device_src name=el_src num_buffers=-1 fps=1000000000 ! "
            "tensor_transform name=el_norm mode=arithmetic "
            "option=typecast:float32,add:-127.5,div:127.5 ! "
            "tensor_filter name=el_net framework=jax-xla model={model} "
            "{batch} ! tensor_decoder name=el_overlay mode=bounding_boxes "
            "option1=mobilenet-ssd-postprocess option4=64:64 option5=64:64 "
            "option7=device ! appsink name=el_sink max_buffers=4 drop=true")
VIT_LINE = ("device_src name=el_src num_buffers=-1 fps=1000000000 ! "
            "tensor_transform name=el_norm mode=arithmetic "
            "option=typecast:float32,add:-127.5,div:127.5 ! "
            "tensor_filter name=el_net framework=jax-xla model={model} "
            "{batch} ! appsink name=el_sink max_buffers=4 drop=true")
SSD_STAGES = {"nns.pre/el_norm", "nns.model/backbone/stem",
              "nns.model/backbone/block00", "nns.model/backbone/block16",
              "nns.model/extras", "nns.model/heads", "nns.model/decode",
              "nns.model/topk", "nns.model/nms", "nns.post/overlay"}
VIT_STAGES = {"nns.pre/el_norm", "nns.model/embed",
              "nns.model/layer00/ln1", "nns.model/layer00/attn",
              "nns.model/layer00/ln2", "nns.model/layer01/mlp",
              "nns.model/head"}


def _toy_models():
    from nnstreamer_tpu.models.ssd import register_ssd
    from nnstreamer_tpu.models.vit import register_vit

    register_ssd("spans_toy_ssd", batch=2, size=64, max_out=10)
    register_vit("spans_toy_vit", batch=2, image_size=32, dim=64, depth=2,
                 heads=2, mlp_dim=128, num_classes=10)
    register_ssd("spans_toy_ssd1", batch=1, size=64, max_out=10)
    register_vit("spans_toy_vit1", batch=1, image_size=32, dim=64, depth=2,
                 heads=2, mlp_dim=128, num_classes=10)


def _toy_pipe(line, shape):
    from nnstreamer_tpu.runtime import parse_launch

    rng = np.random.default_rng(5)
    pipe = parse_launch(line)
    pipe["el_src"].frames = [rng.integers(0, 255, shape, dtype=np.uint8)
                             for _ in range(2)]
    pipe["el_src"].pool_size = 2
    return pipe


def _program_text(line, shape, bucket):
    pipe = _toy_pipe(line, shape)
    pipe.start()
    try:
        for _ in range(max(bucket, 1) + 1):
            assert pipe["el_sink"].pull(timeout=120) is not None
        return pipe["el_net"].subplugin.executable_text(bucket)
    finally:
        pipe.stop()


@pytest.mark.parametrize("model,line,shape,bucket,stages", [
    ("spans_toy_ssd", SSD_LINE, (2, 64, 64, 3), 0, SSD_STAGES),
    ("spans_toy_vit", VIT_LINE, (2, 32, 32, 3), 0, VIT_STAGES),
    # the bucket compile of a micro-batching filter: no fusion pass
    # there (nothing fused into a window program), the model's stages
    ("spans_toy_ssd1", SSD_LINE, (1, 64, 64, 3), 2,
     SSD_STAGES - {"nns.pre/el_norm", "nns.post/overlay"}),
    ("spans_toy_vit1", VIT_LINE, (1, 32, 32, 3), 2,
     VIT_STAGES - {"nns.pre/el_norm"}),
])
def test_optimised_hlo_carries_the_stage_of_every_instruction(
        model, line, shape, bucket, stages):
    _toy_models()
    batch = f"batch={bucket} batch-timeout-ms=50" if bucket else ""
    text = _program_text(line.format(model=model, batch=batch), shape,
                         bucket)
    seen = profile.stage_map(text)
    assert stages <= set(seen.values())
    # every instruction that has metadata is under an nns scope, apart
    # from the parameters and the reducers' own little computations
    # (named after the argument or the primitive alone)
    for op_name in profile._OP_NAME.findall(text):
        if stage_of(op_name) == profile.NO_SCOPE:
            assert "/" not in op_name, op_name
    # fusions carry it too: the device events of a chip are fusions
    fusions = [name for name in seen if "fusion" in name]
    assert fusions and all(seen[f].startswith("nns.") for f in fusions)


# -- stage_seconds on a CPU capture -------------------------------------------


def test_stage_seconds_sums_to_the_programs_busy_time(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    def f(x):
        with jax.named_scope("nns.pre"):
            with jax.named_scope("el_norm"):
                x = (x - 1.0) * 2.0
        with jax.named_scope("nns.model"):
            with jax.named_scope("backbone/stem"):
                x = jnp.tanh(x @ x.T)
            with jax.named_scope("heads"):
                x = jax.nn.softmax(x @ x, axis=-1)
        return x

    x = jnp.ones((256, 256))
    g = jax.jit(f)
    g(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with profile.pipeline_trace(str(tmp_path), profiler_options=options):
        for _ in range(4):
            with span("el_net", "dispatch"):
                g(x).block_until_ready()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    text = g.lower(x).compile().as_text()
    stages = profile.stage_seconds(path, text)
    assert {"nns.pre/el_norm", "nns.model/backbone/stem",
            "nns.model/heads"} <= set(stages)
    assert profile.NO_METADATA not in stages
    # the same events, summed without the map: the program's busy time
    busy = 0.0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            busy += sum(ev.duration_ns for ev in line.events
                        if "hlo_op" in dict(ev.stats)) * 1e-9
    assert busy > 0
    assert sum(stages.values()) == pytest.approx(busy, rel=1e-6)
    # without the executable the CPU's events carry no op_name
    assert set(profile.stage_seconds(path)) == {profile.NO_METADATA}
    # the capture's own spans are on the profiler's clock too
    names = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names |= {ev.name for ev in line.events
                      if ev.name.startswith("el_net")}
    assert names == {"el_net/dispatch"}
    assert profile.main([path, "--hlo", _write(tmp_path, text)]) == 0


def _write(tmp_path, text):
    path = os.path.join(str(tmp_path), "program.txt")
    with open(path, "w") as f:
        f.write(text)
    return path


def test_nested_device_events_count_once():
    events = [("a", 0.0, 100.0), ("b", 10.0, 30.0), ("b", 50.0, 20.0),
              ("c", 200.0, 50.0)]
    got = {}
    for (stage, _s, _d), ns in profile._self_ns(events):
        got[stage] = got.get(stage, 0.0) + ns
    assert got == {"a": 50.0, "b": 50.0, "c": 50.0}


# -- the sites ----------------------------------------------------------------


def test_a_traced_pipeline_names_its_phases_and_set_up(capture):
    """One toy line under a capture: every phase span of the table in
    Documentation/observability.md that this line has appears, nested in
    its element's chain span, with the window id of its buffer."""
    _toy_models()
    _program_text(SSD_LINE.format(model="spans_toy_ssd", batch=""),
                  (2, 64, 64, 3), 0)
    rows = profile.spans()
    names = {s.name for s in rows}
    assert {"pipeline/fuse", "pipeline/negotiate", "el_net/trace_lower",
            "el_net/first_call", "el_src/stage"} <= {
        s.name for s in rows if s.kind == "setup"}
    assert {"el_src/create", "el_norm", "el_net", "el_net/prep",
            "el_net/dispatch", "el_net/sample_fence", "el_overlay",
            "el_sink", "el_sink/fence", "el_sink/render"} <= names
    window = [s for s in rows if s.kind == "window"]
    by_window = {}
    for s in window:
        by_window.setdefault(s.window, set()).add(s.name)
    assert {"el_norm", "el_net/dispatch", "el_sink/render"} <= by_window[1]
    # the chain spans nest: el_norm holds el_net holds el_sink
    one = {s.name: s for s in window if s.window == 1}
    assert one["el_norm"].start_ns <= one["el_net"].start_ns \
        <= one["el_sink"].start_ns <= one["el_sink"].end_ns \
        <= one["el_net"].end_ns <= one["el_norm"].end_ns
    assert re.fullmatch(r"el_\w+(/\w+)?", one["el_sink/render"].name)


# -- set-up as one tree (PR 37) -----------------------------------------------


def _toy_start(pulls=3, restart=False):
    """The toy SSD line parsed, started and pulled from; returns the
    set-up spans kept, by name (a list each)."""
    _toy_models()
    pipe = _toy_pipe(SSD_LINE.format(model="spans_toy_ssd", batch=""),
                     (2, 64, 64, 3))
    for _ in range(2 if restart else 1):
        pipe.start()
        try:
            for _ in range(pulls):
                assert pipe["el_sink"].pull(timeout=120) is not None
        finally:
            pipe.stop()
    by_name = {}
    for s in profile.spans():
        if s.kind == "setup":
            by_name.setdefault(s.name, []).append(s)
    return by_name


@pytest.fixture(scope="module")
def toy_set_up():
    """The set-up spans of one start of the toy line, by name."""
    profile.clear()
    return _toy_start()


def _inside(inner, outer):
    return outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_set_up_has_its_root_spans(toy_set_up):
    by_name = toy_set_up
    assert {"spans_toy_ssd/register", "pipeline/parse", "pipeline/start",
            "pipeline/first_window", "el_net/open", "el_net/cost_capture",
            "el_net/activate", "el_src/activate", "el_sink/activate",
            "el_norm/activate", "el_overlay/activate"} <= set(by_name)
    register, parse, start, first = (
        by_name[n][-1] for n in ("spans_toy_ssd/register", "pipeline/parse",
                                 "pipeline/start", "pipeline/first_window"))
    # one after the other on the application's thread, first_window on
    # the thread that fenced
    assert register.end_ns <= parse.start_ns <= parse.end_ns \
        <= start.start_ns
    assert register.thread == parse.thread == start.thread != first.thread


@pytest.mark.parametrize("inner,outer", [
    ("pipeline/fuse", "pipeline/start"),
    ("pipeline/negotiate", "pipeline/start"),
    ("el_net/activate", "pipeline/start"),
    ("el_src/activate", "pipeline/start"),
    ("el_src/stage", "el_src/activate"),
    ("el_net/open", "pipeline/negotiate"),
    ("el_net/trace_lower", "pipeline/negotiate"),
    ("el_net/cost_capture", "pipeline/negotiate"),
])
def test_set_up_spans_nest(toy_set_up, inner, outer):
    by_name = toy_set_up
    assert by_name[inner] and len(by_name[outer]) == 1
    for s in by_name[inner]:
        assert _inside(s, by_name[outer][0]), (s, by_name[outer][0])
        assert s.thread == by_name[outer][0].thread


def test_first_window_runs_from_start_to_the_first_fence(monkeypatch):
    from nnstreamer_tpu.runtime.element import SinkElement

    fenced = []
    plain = SinkElement._fence

    def watched(self, arr):
        plain(self, arr)
        if arr is not None:
            fenced.append(time.perf_counter_ns())

    monkeypatch.setattr(SinkElement, "_fence", watched)
    by_name = _toy_start(pulls=4)
    (first,) = by_name["pipeline/first_window"]
    (start,) = by_name["pipeline/start"]
    assert len(fenced) >= 2
    # from start() returning, to the first fence and not the second
    assert start.end_ns <= first.start_ns
    assert first.start_ns - start.end_ns < 50_000_000
    assert first.end_ns <= fenced[0] < fenced[1]
    assert first.end_ns > first.start_ns
    # the lazy build of the streaming thread ends inside it
    call = by_name["el_net/first_call"][-1]
    assert call.thread == first.thread and call.end_ns <= first.end_ns


def test_first_window_is_kept_once_a_start():
    by_name = _toy_start(restart=True)
    assert len(by_name["pipeline/start"]) == 2
    assert len(by_name["pipeline/first_window"]) == 2
    for start, first in zip(by_name["pipeline/start"],
                            by_name["pipeline/first_window"]):
        assert start.end_ns <= first.start_ns


def test_no_first_window_for_a_stream_that_fences_nothing():
    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc
    from nnstreamer_tpu.runtime import Pipeline

    pipe = Pipeline()
    src = AppSrc(name="src", spec=TensorsSpec.parse("4", "float32"))
    sink = AppSink(name="out")
    pipe.add(src, sink).link(src, sink)
    with pipe:
        for _ in range(3):
            src.push_buffer(Buffer.of(np.ones(4, np.float32)))
        src.end_of_stream()
        assert pipe.wait_eos(timeout=60)
    names = [s.name for s in profile.spans() if s.kind == "setup"]
    assert "pipeline/start" in names       # host buffers: no fence
    assert "pipeline/first_window" not in names


def test_set_up_spans_dropped_reads_zero_then_what_overflowed(
        toy_set_up, monkeypatch):
    kept = sum(len(rows) for rows in toy_set_up.values())
    assert kept > 10 and profile.spans_dropped()["setup"] == 0
    assert profile.SETUP_MAX >= 1 << 14
    # room for every per-window span: on a loaded machine a toy window
    # can last the 50 ms that make its spans slow ones, and this test is
    # about the set-up list
    rec = profile._Recorder({"setup": 8, "window": 1 << 10,
                             "slow": 1 << 10})
    kinds, plain = [], rec.keep
    rec.keep = lambda *a, **k: (kinds.append(a[4]), plain(*a, **k))[1]
    monkeypatch.setattr(profile, "_REC", rec)
    _toy_start()
    assert kinds.count("setup") > 8
    assert profile.spans_dropped() == {
        "setup": kinds.count("setup") - 8, "window": 0, "slow": 0}
    assert len([s for s in profile.spans() if s.kind == "setup"]) == 8


def test_keep_setup_is_off_under_the_kill_switch(monkeypatch):
    profile.keep_setup("pipeline/first_window", 10, 20, "n")
    (row,) = profile.spans()
    assert (row.name, row.start_ns, row.end_ns, row.kind, row.note) == (
        "pipeline/first_window", 10, 20, "setup", "n")
    profile.clear()
    monkeypatch.setattr(hooks, "DISABLED", True)
    profile.keep_setup("pipeline/first_window", 10, 20)
    assert profile.spans() == []


# -- a slow fence says who was late -------------------------------------------


class _Array:
    """Stands in for a device array at the sink's fence."""

    def __init__(self, ready, wait_s=0.0, asked=None):
        self.ready, self.wait_s, self.asked = ready, wait_s, asked

    def block_until_ready(self):
        time.sleep(self.wait_s)

    def is_ready(self):
        if self.asked is not None:
            self.asked.append(self)
        return self.ready


@pytest.mark.parametrize("next_ready,note", [
    (True, profile.HOST_LATE), (False, profile.DEVICE_LATE),
    (None, "no next window")])
def test_a_slow_fence_says_who_was_late(next_ready, note):
    from nnstreamer_tpu.elements.basic import AppSink

    sink = AppSink(name="el_sink")
    sink._pending_fence = None if next_ready is None \
        else _Array(next_ready)
    sink._fence(_Array(True, wait_s=0.06))
    (row,) = profile.spans()
    assert (row.name, row.kind, row.note) == ("el_sink/fence", "slow", note)
    assert (profile.HOST_LATE, profile.DEVICE_LATE) == (
        "next window done: host late", "next window running: device late")


def test_a_fast_fence_asks_the_array_nothing(capture):
    from nnstreamer_tpu.elements.basic import AppSink

    asked = []
    sink = AppSink(name="el_sink")
    sink._pending_fence = _Array(True, asked=asked)
    sink._fence(_Array(True))
    (row,) = profile.spans()            # kept: a capture is on
    assert (row.name, row.kind, row.note) == ("el_sink/fence", "window",
                                              None)
    assert asked == []


def test_if_slow_is_called_on_a_slow_span_only(monkeypatch):
    ticks = iter([0, 49_999_999, 100, 100 + 50_000_000])
    monkeypatch.setattr(profile.time, "perf_counter_ns", lambda: next(ticks))
    calls = []
    for name in ("a", "b"):
        with span(name, "x") as s:
            s.if_slow = lambda name=name: calls.append(name) or "late"
    assert calls == ["b"]
    assert [(s.name, s.note) for s in profile.spans()] == [("b/x", "late")]


# -- the pause ledger: what the process did around a slow span (PR 53) -------

MS = 1_000_000


class _Host:
    """Stands in for ``profile._reading``: counts the readings asked of
    it and hands out made-up cumulative CPU clocks, each reading
    ``gains`` ahead of the one before."""

    def __init__(self, **gains_a_reading):
        self.gains = gains_a_reading
        self.reads = []
        self.now = {}

    def read(self, t_ns):
        self.reads.append(t_ns)
        for key, gain in self.gains.items():
            self.now[key] = self.now.get(key, 0) + gain
        return dict(self.now, t_ns=t_ns)


@pytest.fixture
def host(monkeypatch):
    """Made-up CPU clocks, no baseline on this thread yet, the gate
    open."""
    fake = _Host(thread_cpu_ns=0, process_cpu_ns=0)
    monkeypatch.setattr(profile, "_reading", fake.read)
    monkeypatch.setattr(profile, "_gate_ns", 0)
    monkeypatch.setattr(profile, "_slot_ends_ns", 0)
    if hasattr(profile._tls, "baselines"):
        monkeypatch.delattr(profile._tls, "baselines")
    yield fake
    if hasattr(profile._tls, "baselines"):
        del profile._tls.baselines


def _benchmark_spans():
    """The benchmark's span arithmetic (``benchmark/spans.py``), from
    the checkout's root."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import spans as bench_spans

    return bench_spans


def _clock(monkeypatch, ticks):
    ticks = iter(ticks)
    monkeypatch.setattr(profile.time, "perf_counter_ns", lambda: next(ticks))


@pytest.mark.parametrize("deltas,age,cause", [
    ({"gc_ns": 70 * MS, "gc_generation": 2, "gc_other_thread": 1,
      "thread_cpu_ns": 2 * MS}, 0, "gc"),
    ({"thread_cpu_ns": 99 * MS, "process_cpu_ns": 99 * MS}, 0, "on_cpu"),
    # the larger of two that each cover half
    ({"thread_cpu_ns": 80 * MS, "gc_ns": 60 * MS}, 0, "on_cpu"),
    # busy BEFORE the span, for all the record can tell: the baseline is
    # 60 ms old, so only 39 ms were surely computed inside the span
    ({"thread_cpu_ns": 99 * MS, "gc_ns": 0}, 60 * MS, profile.UNEXPLAINED),
    ({"thread_cpu_ns": 160 * MS, "gc_ns": 0}, 100 * MS, "on_cpu"),
    # a stale baseline (a thread that met no slot of the gate) proves
    # nothing of the span
    ({"thread_cpu_ns": 900 * MS, "gc_ns": 0}, 5000 * MS,
     profile.UNEXPLAINED),
    # the only baseline is from inside the span: all of it counts
    ({"thread_cpu_ns": 70 * MS}, -20 * MS, "on_cpu"),
    # a cause present and still short of half: what it was NOT stays
    ({"gc_ns": 49 * MS, "gc_collections": 1, "thread_cpu_ns": MS,
      "process_cpu_ns": 3 * MS}, 0, profile.UNEXPLAINED),
    # nobody watched the collections: no key, no guess
    ({"thread_cpu_ns": MS, "process_cpu_ns": 40 * MS}, 0,
     profile.UNEXPLAINED),
    # the thread had no baseline
    ({}, None, profile.UNEXPLAINED),
])
def test_the_cause_rule_on_made_up_deltas(deltas, age, cause):
    assert profile.cause_of(100 * MS, deltas, age) == cause
    timed = profile.charges(deltas, age)
    assert set(timed) <= {"gc", "on_cpu"}
    assert ("gc" in timed) == ("gc_ns" in deltas)
    assert all(0 <= ns for ns in timed.values())


@pytest.mark.parametrize("next_ready,note", [
    (True, profile.HOST_LATE), (False, profile.DEVICE_LATE)])
def test_a_slow_fence_keeps_a_pause_and_its_note_says_what_it_said(
        host, next_ready, note):
    from nnstreamer_tpu.elements.basic import AppSink

    bench_spans = _benchmark_spans()

    host.gains = {"thread_cpu_ns": 2 * MS, "process_cpu_ns": 5 * MS}
    with span("warm"):                  # the thread's baseline
        pass
    sink = AppSink(name="el_sink")
    sink._pending_fence = _Array(next_ready)
    with span("el_sink", None, 41):
        sink._fence(_Array(True, wait_s=0.06))
    fence, chain = profile.pauses()[1], profile.pauses()[0]
    assert (chain.name, fence.name) == ("el_sink", "el_sink/fence")
    assert fence.note == note and fence.cause == profile.UNEXPLAINED
    assert fence.window == 41            # from the pause around it
    assert fence.deltas == {"thread_cpu_ns": 2 * MS,
                            "process_cpu_ns": 5 * MS}
    assert 0 <= fence.baseline_age_ns < 50 * MS
    # the spans say what they said before the ledger: no cause in a
    # note, no new kind, and the readers' filter still finds the fence
    rows = profile.spans()
    assert [(s.name, s.kind, s.note) for s in rows] == [
        ("el_sink", "slow", None), ("el_sink/fence", "slow", note)]
    (inner,) = bench_spans.innermost_slow(rows, 0)
    assert inner.name == "el_sink/fence" and inner.note == note
    assert (inner.start_ns, inner.end_ns) == (fence.start_ns, fence.end_ns)
    # the span around the fence began before the fence's baseline was
    # stale: it is measured from the same reading, not from the fence's
    assert chain.deltas["process_cpu_ns"] == 2 * 5 * MS
    assert chain.baseline_age_ns >= 0


def test_a_fast_span_reads_no_clock_but_its_own_two(host, monkeypatch):
    _clock(monkeypatch, range(0, 10 ** 9, 1000))      # 1 us a clock read
    for what in ("thread_time_ns", "process_time_ns"):
        monkeypatch.setattr(profile.time, what,
                            lambda what=what: pytest.fail(what))
    monkeypatch.setattr(os, "open", lambda *a, **k: pytest.fail("a file"))
    for _ in range(1000):                # 2 ms of spans
        with span("el_net", "dispatch", 1):
            pass
    # the thread's first exit read its baseline; no other span did
    assert len(host.reads) == 1
    assert profile.pauses() == [] and profile.spans() == []


def test_the_baseline_is_read_at_most_once_in_100_ms_a_thread(
        host, monkeypatch):
    assert profile.BASELINE_NS == 100 * MS
    _clock(monkeypatch, range(0, 10 ** 12, 5 * MS))   # a span of 5 ms each
    for _ in range(100):                 # 1 s of spans, none slow
        with span("el_net", "dispatch"):
            pass
    assert 9 <= len(host.reads) <= 11
    assert all(b - a >= profile.BASELINE_NS
               for a, b in zip(host.reads, host.reads[1:]))
    assert len(profile._tls.baselines) <= profile.BASELINES_KEPT
    # another thread has baselines of its own, at the same pace: its
    # first span waits for the gate, as every span does
    import threading

    mine = list(profile._tls.baselines)

    def other():
        for _ in range(60):              # 0.6 s of spans
            with span("el_q", "push"):
                pass
        theirs.extend(profile._tls.baselines)

    before, theirs = len(host.reads), []
    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and 5 <= len(host.reads) - before <= 7
    assert len(theirs) == len(host.reads) - before
    assert profile._tls.baselines == mine


def test_the_gate_shuts_after_its_slot_whoever_has_ended_or_idles(
        host, monkeypatch):
    """The fast path's one compare stays one compare: no thread that
    ended, and none that makes a span a second, keeps the gate open for
    the others.  Of 2,000 span exits in 4 s only those inside a slot
    (5 ms in 100) look past the compare."""
    import threading

    _clock(monkeypatch, range(0, 10 ** 13, MS))       # 1 ms a clock read
    through = []
    gate = profile._gate
    monkeypatch.setattr(profile, "_gate",
                        lambda t1: through.append(t1) or gate(t1))

    def other():
        with span("el_q", "push"):
            pass

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    for _ in range(2000):
        with span("el_net", "dispatch"):
            pass
    assert len(through) <= 200 and len(host.reads) <= 2 + 40
    # shut, and ahead of the clock, once the last slot was over
    assert profile._gate_ns > through[-1] >= profile._slot_ends_ns


def test_many_threads_share_the_gate_and_each_keeps_its_own_pace(host):
    """More threads than cores, each making spans for a quarter of a
    second under a short switch interval: no thread reads more than one
    baseline in 100 ms, each reading lands in its own thread's list and
    nowhere else, nothing raises.  (A thread that meets no slot of the
    gate in so short a life has none yet: its first pause then has no
    CPU delta, and closes with the baseline of its second.)"""
    import sys
    import threading

    counts, errors = {}, []
    go = threading.Event()

    def stream():
        try:
            go.wait(timeout=30)
            until = time.perf_counter() + 0.25
            while time.perf_counter() < until:
                with span("el_net", "dispatch"):
                    pass
            counts[threading.get_ident()] = [
                b["t_ns"] for b in getattr(profile._tls, "baselines", [])]
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=stream) for _ in range(24)]
        for t in threads:
            t.start()
        go.set()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(counts) == 24
    assert sum(1 for times in counts.values() if times) >= 4
    for times in counts.values():
        assert len(times) <= 4
        assert all(b - a >= profile.BASELINE_NS
                   for a, b in zip(times, times[1:]))
    assert len(host.reads) == sum(len(v) for v in counts.values())


def test_a_slow_span_is_measured_from_the_baseline_before_it(
        host, monkeypatch):
    host.gains = {"thread_cpu_ns": 10 * MS}
    #       warm    | an outer span holding a refresh, then a slow one
    _clock(monkeypatch, [0, 1,
                         20 * MS,                       # outer in
                         130 * MS, 131 * MS,            # fast: refresh due
                         140 * MS, 200 * MS,            # slow inner
                         201 * MS])                     # outer out
    with span("warm"):
        pass
    with span("el_norm", None, 7):
        with span("el_net", "prep"):
            pass
        with span("el_net", "dispatch"):
            pass
    outer, inner = profile.pauses()
    assert host.reads == [1, 131 * MS, 200 * MS, 201 * MS]
    # the inner from the refresh 9 ms before it, the outer from the
    # reading before ITS start: the refresh and the inner's lie inside it
    assert (inner.name, inner.baseline_age_ns) == ("el_net/dispatch", 9 * MS)
    assert inner.deltas == {"thread_cpu_ns": 10 * MS}
    assert (outer.name, outer.baseline_age_ns) == ("el_norm", 20 * MS - 1)
    assert outer.deltas == {"thread_cpu_ns": 30 * MS}
    assert inner.window == outer.window == 7
    # what was read inside the outer serves no later span
    assert [b["t_ns"] for b in profile._tls.baselines] == [1, 201 * MS]


def test_a_thread_without_a_baseline_says_so_and_its_pause_leaves_one(
        host, monkeypatch):
    # the gate is shut: this thread's fast spans read nothing
    monkeypatch.setattr(profile, "_gate_ns", 10 ** 15)
    host.gains = {"thread_cpu_ns": 3 * MS, "process_cpu_ns": 4 * MS}
    _clock(monkeypatch, [0, 1, 10 * MS, 80 * MS, 90 * MS, 150 * MS])
    with span("el_net", "prep"):
        pass
    assert host.reads == []
    for _ in range(2):
        with span("el_sink", "fence"):
            pass
    first, second = profile.pauses()
    assert (first.baseline_age_ns, first.deltas) == (None, {})
    assert first.cause == profile.UNEXPLAINED
    # the reading that closed the first opens the second
    assert second.baseline_age_ns == 10 * MS
    assert second.deltas == {"thread_cpu_ns": 3 * MS,
                             "process_cpu_ns": 4 * MS}


def test_pauses_are_bounded_and_the_dropped_are_counted(host, monkeypatch):
    assert profile._REC.pauses.maxlen == profile.SLOW_MAX == 1024
    rec = profile._Recorder({"setup": 3, "window": 3, "slow": 9, "pause": 3})
    monkeypatch.setattr(profile, "_REC", rec)
    _clock(monkeypatch, (i * 60 * MS for i in range(100)))
    for i in range(5):
        with span(f"s{i}", "x"):
            pass
    assert [p.name for p in profile.pauses()] == ["s2/x", "s3/x", "s4/x"]
    assert profile.pauses_dropped() == 2
    # the spans' own count says what it said: three lists, none lost
    assert profile.spans_dropped() == {"setup": 0, "window": 0, "slow": 0}
    assert len(profile.spans()) == 5
    profile.clear()
    assert profile.pauses() == [] and profile.pauses_dropped() == 0


def test_a_collection_on_another_thread_is_charged_to_the_pause_it_overlaps(
        host):
    import gc
    import threading

    owner = type("Owner", (), {})()
    profile.watch_collections(owner)
    started = threading.Event()

    def collect():
        started.wait(timeout=30)
        time.sleep(0.01)
        gc.collect()

    t = threading.Thread(target=collect)
    t.start()
    try:
        with span("warm"):
            pass
        with span("el_sink", "fence"):
            started.set()
            t.join(timeout=30)
            time.sleep(0.06)
    finally:
        profile.unwatch_collections(owner)
    assert not t.is_alive()
    (pause,) = profile.pauses()
    assert pause.deltas["gc_collections"] >= 1
    assert pause.deltas["gc_generation"] == 2
    assert pause.deltas["gc_other_thread"] == 1
    assert 0 < pause.deltas["gc_ns"] <= pause.end_ns - pause.start_ns
    # a pause that no collection overlaps is charged none
    with span("el_sink", "fence"):
        time.sleep(0.06)
    assert "gc_ns" not in profile.pauses()[-1].deltas   # nobody watches now


def test_overlapping_collections_are_clipped_to_the_pause():
    watch = profile._Collections()
    watch.recent.extend([(0, 10, 0, 1), (90, 120, 2, 2), (190, 260, 1, 1),
                         (300, 400, 2, 1)])
    assert watch.overlapping(100, 200) == [(100, 120, 2, 2),
                                           (190, 200, 1, 1)]
    watch._began = (150, 3)              # one still running
    assert watch.overlapping(100, 200)[-1] == (150, 200, -1, 3)
    assert watch.recent.maxlen == profile.COLLECTIONS_KEPT


def test_the_gc_callback_lives_from_the_first_start_to_the_last_stop(
        monkeypatch):
    import gc

    from nnstreamer_tpu.core import TensorsSpec
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc
    from nnstreamer_tpu.runtime import Pipeline

    def line():
        pipe = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse("4", "float32"))
        sink = AppSink(name="out")
        pipe.add(src, sink).link(src, sink)
        return pipe

    ours = profile._GC._callback
    assert ours not in gc.callbacks
    a, b = line(), line()
    a.start()
    b.start()
    b.start()                            # playing already: nothing new
    assert gc.callbacks.count(ours) == 1
    a.stop()
    assert gc.callbacks.count(ours) == 1
    b.stop()
    b.stop()
    assert ours not in gc.callbacks
    monkeypatch.setattr(hooks, "DISABLED", True)
    a.start()
    try:
        assert ours not in gc.callbacks
    finally:
        a.stop()


def test_report_slow_names_the_cause_once_a_name_and_cause(host, monkeypatch):
    host.gains = {"thread_cpu_ns": 108 * MS, "process_cpu_ns": 120 * MS}
    _clock(monkeypatch, [0, 1,
                         10 * MS, 120 * MS, 130 * MS, 250 * MS,
                         300 * MS, 360 * MS])
    with span("warm"):
        pass
    for _ in range(2):
        with span("el_sink", "fence"):
            pass
    host.gains = {"thread_cpu_ns": 0, "process_cpu_ns": MS}
    with span("el_sink", "fence"):
        pass
    lines = []
    assert profile.report_slow(lambda msg, *a: lines.append(msg % a)) == 3
    assert len(lines) == 2
    assert "el_sink/fence: 2 over 50 ms, 230.0 ms in all, longest " \
        "120.0 ms; cause on_cpu (process_cpu 240.0 ms, thread_cpu " \
        "216.0 ms)" in lines[0]
    assert "el_sink/fence: 1 over 50 ms" in lines[1]
    assert "; cause unexplained (process_cpu 1.0 ms, thread_cpu 0.0 ms)" \
        in lines[1]
    # said once: nothing is fresh at the next stop
    assert profile.report_slow(lambda msg, *a: lines.append(msg % a)) == 0


def test_this_hosts_own_clocks_only_grow_and_name_a_thread_that_computes():
    a = profile._reading(1)
    sum(i * i for i in range(200000))
    b = profile._reading(2)
    assert a.keys() == b.keys() == {"t_ns", "thread_cpu_ns",
                                    "process_cpu_ns"}
    assert all(b[k] >= a[k] for k in a)
    assert b["thread_cpu_ns"] > a["thread_cpu_ns"]
    # a span that computes for its whole length is the thread's own
    # doing, by this host's clocks (the gate is wherever it is: the
    # pause's own closing reading is the second one's baseline)
    for _ in range(2):
        with span("el_net", "prep"):
            until = time.thread_time_ns() + 80 * MS
            while time.thread_time_ns() < until:
                pass
    pause = profile.pauses()[-1]
    length = pause.end_ns - pause.start_ns
    timed = profile.charges(pause.deltas, pause.baseline_age_ns)
    assert timed["on_cpu"] >= 70 * MS
    assert pause.cause == ("on_cpu" if 2 * timed["on_cpu"] >= length
                           else profile.UNEXPLAINED)


def test_a_pause_is_kept_under_a_capture_too_and_spans_gain_no_kind(
        capture, host):
    with span("warm"):
        pass
    with span("el_net", "dispatch", 3):
        time.sleep(0.06)
    (pause,) = profile.pauses()
    assert (pause.name, pause.window) == ("el_net/dispatch", 3)
    assert {s.kind for s in profile.spans()} == {"window"}
    assert [s.name for s in profile.spans()] == ["warm", "el_net/dispatch"]
