"""The benchmark's files and arithmetic, on the CPU, without running a
cell: everything ``BENCHMARK.json`` names resolves, and the yardstick's
own arithmetic (schedules, percentiles, trace reduction, cost counts)
gives hand-checked answers.  No number here is a rate."""

import json
import math
import os
import statistics
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import BenchmarkError, stages, stats, trace  # noqa: E402
from benchmark.run import Loader, cut_faults, launch_line  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                 "0123456789_.-")


@pytest.fixture(scope="module")
def loader():
    return Loader(REPO)


def test_manifest_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(cells) // 4)
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_resolves(loader, config):
    entry = loader.entry("configs", config)
    assert entry["file"].startswith("benchmark/configs/")
    cfg = loader.config(config)
    assert cfg["name"] == config and cfg["source"] == entry["source"]
    # a cut is stated, not forbidden: what holds it together is below
    assert cfg["reduced"] == entry["reduced"]
    assert cut_faults(cfg, entry["reduced"]) == []
    assert cfg["assumed"] and cfg["limits"]
    # a configuration that is not its source's says so where it is named
    if "not_the_published_detector" in cfg:
        assert cfg["head"] and "own" in entry["source"]
    assert cfg["precision"] == "bfloat16"
    for kind, key in (("weights", "weights"), ("models", "model"),
                      ("reference", "reference"), ("costs", "costs")):
        assert loader.module(kind, cfg[key]) is not None
    assert callable(loader.module("reference", cfg["reference"]).check)
    assert callable(loader.module("reference", cfg["reference"]).control)
    assert any(w["config"] == config for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_resolves(loader, cell):
    entry = loader.entry("workloads", cell)
    assert set(cell) <= NAME_CHARS and len(entry["why"]) <= 200
    work = loader.json("workloads", cell)
    mix = loader.json("traffic", entry["traffic"])
    assert work["config"] == entry["config"]
    assert work["traffic"] == entry["traffic"]
    assert work["chips"] == entry["chips"]
    assert callable(loader.module("traffic", mix["kind"]).run)
    cfg = loader.config(entry["config"])
    line = launch_line(work, cfg, mix, model="m")
    prefix = work["element_prefix"]
    for element in ("src", "net", "sink"):
        assert f"name={prefix}{element}" in line
    assert ("mesh=" in line) == (entry["chips"] == 4)
    # every cell reports setup_s, another end-to-end and a per-layer metric
    e2e = [m["name"] for m in MANIFEST["end_to_end"]
           if loader.reports(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(loader.reports(m, cell) for m in MANIFEST["per_layer"])


# -- a stated cut ------------------------------------------------------------------

CUT = {"name": "cut", "reduced": ["num_hidden_layers", "vocab_size"],
       "num_hidden_layers": 5, "vocab_size": 25600, "hidden_size": 5120,
       "published": {"num_hidden_layers": 60, "vocab_size": 102400,
                     "hidden_size": 5120},
       "deployment": {"chips_per_layer": 4,
                      "this_chip": "a quarter of the vocabulary"}}


def _without(cfg, *keys):
    return {k: v for k, v in cfg.items() if k not in keys}


def test_a_stated_cut_holds_together():
    assert cut_faults(CUT, CUT["reduced"]) == []
    assert cut_faults({"reduced": []}, []) == []
    # the accepted configurations state none, and need nothing more
    for entry in MANIFEST["configs"]:
        assert entry["reduced"] == []


@pytest.mark.parametrize("cfg,listed,says", [
    (CUT, ["num_hidden_layers"], "BENCHMARK.json lists"),
    (_without(CUT, "reduced"), CUT["reduced"], "BENCHMARK.json lists"),
    (_without(CUT, "vocab_size"), CUT["reduced"],
     "'vocab_size' is not in the file"),
    (_without(CUT, "published"), CUT["reduced"], "no `published`"),
    ({**CUT, "published": {"num_hidden_layers": 60}}, CUT["reduced"],
     "`published` lacks the reduced key 'vocab_size'"),
    ({**CUT, "hidden_size": 2560}, CUT["reduced"],
     "'hidden_size' is 2560, published 5120"),
    (_without(CUT, "deployment"), CUT["reduced"], "no `deployment`"),
    ({**CUT, "deployment": {"chips_per_layer": 4}}, CUT["reduced"],
     "no `deployment`"),
    ({**CUT, "deployment": {"chips_per_layer": "four", "this_chip": "x"}},
     CUT["reduced"], "no `deployment`"),
], ids=["lists-differ", "file-lists-none", "key-absent", "no-published",
        "published-lacks-key", "width-differs-unlisted", "no-deployment",
        "deployment-holds-nothing", "chips-not-a-number"])
def test_a_cut_that_does_not_hold_together_is_named(cfg, listed, says):
    faults = cut_faults(cfg, listed)
    assert faults and any(says in f for f in faults), faults


# -- launch fields -----------------------------------------------------------------


def test_launch_fields_come_from_the_files():
    work = {"name": "c", "launch": "src fps={stamp} ! net model={model} "
                                   "size={size} ! sink max={sink_depth}"}
    cfg = {"name": "k", "image_size": 300, "launch_fields": {"stamp": 7}}
    line = launch_line(work, cfg, {"sink_depth": 4}, model="m")
    assert line == "src fps=7 ! net model=m size=300 ! sink max=4"
    # the later named wins: caller over mix over launch_fields over size
    assert launch_line({"launch": "{size} {stamp}"},
                       {"image_size": 1, "launch_fields": {"size": 2,
                                                           "stamp": 3}},
                       {"stamp": 4}, stamp=5) == "2 5"
    # a configuration with neither image_size nor transform is fine
    assert launch_line({"launch": "net model={model}"}, {"name": "k"}, {},
                       model="m") == "net model=m"


def test_a_placeholder_nobody_provides_is_named():
    work = {"name": "c", "launch": "net model={model} option={transform}"}
    with pytest.raises(BenchmarkError, match=r"\{transform\}"):
        launch_line(work, {"name": "tokens"}, {}, model="m")


@pytest.mark.parametrize("metric",
                         [m["name"] for m in MANIFEST["end_to_end"]])
def test_end_to_end_metric_resolves(loader, metric):
    assert callable(loader.module("end_to_end", metric).read)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_metric_resolves(loader, metric):
    entry = loader.entry("per_layer", metric)
    # a reader of its own name, or a data file that names one
    spec_path = os.path.join(loader.dir, "layer_metrics", metric + ".json")
    reader = metric
    if os.path.isfile(spec_path):
        reader = loader.json("layer_metrics", metric)["reader"]
    assert callable(loader.module("readers", reader).read)
    assert loader.entry("end_to_end", entry["moves"])
    for cell in entry.get("workloads", []):
        assert loader.reports(loader.entry("end_to_end", entry["moves"]),
                              cell)
    if metric.endswith("_roofline"):
        assert entry["unit"] == "%"


with open(os.path.join(HERE, "data", "toy_pool_entries.json")) as _f:
    POOL_ENTRIES = json.load(_f)


@pytest.mark.parametrize("section,metric", [
    (section, m["name"]) for section, rows in POOL_ENTRIES.items()
    for m in rows])
def test_open_loop_metric_files_resolve(loader, section, metric):
    """The open-loop kind's latency and layer metrics have their readers
    under ``benchmark/`` although no cell of ``BENCHMARK.json`` reports
    them yet: a later PR adds the cell and these entries, no code."""
    assert metric not in [m["name"] for m in MANIFEST[section]]
    if section == "end_to_end":
        assert callable(loader.module("end_to_end", metric).read)
        return
    spec_path = os.path.join(loader.dir, "layer_metrics", metric + ".json")
    reader = metric
    if os.path.isfile(spec_path):
        reader = loader.json("layer_metrics", metric)["reader"]
    assert callable(loader.module("readers", reader).read)
    entry = next(m for m in POOL_ENTRIES[section] if m["name"] == metric)
    assert entry["moves"] in [m["name"] for m in POOL_ENTRIES["end_to_end"]]


def test_peaks_table():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["device_kinds"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["peak_flops_bf16"] == 197e12
    assert v5e["peak_hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


# -- arithmetic ---------------------------------------------------------------


def test_percentile_arithmetic():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 0) == 1.0 and stats.percentile(xs, 100) == 5.0
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile(list(range(101)), 95) == 95.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_quartile_spread_is_the_drivers():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))


def _schedule(loader):
    return loader.module("traffic", "openloop_streams").schedule


def test_schedule_is_deterministic_per_seed(loader):
    schedule = _schedule(loader)
    a = schedule(2 ** 31 + 5, 8, 30.0, 2.0, 20)
    b = schedule(2 ** 31 + 5, 8, 30.0, 2.0, 20)
    c = schedule(2 ** 31 + 6, 8, 30.0, 2.0, 20)
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def _phase_and_jitter(due, fps, streams):
    interval = 1.0 / fps
    base = due - np.arange(due.shape[1])[None, :] * interval
    step = interval / streams
    phase = np.round(np.median(base, axis=1) / step).astype(int)
    return phase, base - (phase * step)[:, None]


def test_schedule_due_time_arithmetic(loader):
    schedule = _schedule(loader)
    # frame k of a stream is due k intervals after its phase, +- jitter
    phase, jitter = _phase_and_jitter(schedule(7, 4, 30.0, 2.0, 50), 30.0, 4)
    assert sorted(phase.tolist()) == [0, 1, 2, 3]       # evenly spread
    assert np.abs(jitter).max() <= 2.0e-3
    assert abs(jitter.mean()) < 1e-9
    # every seed deals the same set of phases and jitters, in another order
    phase8, jitter8 = _phase_and_jitter(schedule(8, 4, 30.0, 2.0, 50),
                                        30.0, 4)
    assert sorted(phase8.tolist()) == [0, 1, 2, 3]
    assert np.allclose(np.sort(jitter.ravel()), np.sort(jitter8.ravel()),
                       atol=1e-9)
    assert not np.allclose(jitter, jitter8)


def test_frames_from_seed():
    from benchmark.frames import coarse_block, make_ring

    assert coarse_block(300) == 15 and coarse_block(224) == 16
    a = make_ring(2 ** 31 + 9, 3, 2, 60)
    b = make_ring(2 ** 31 + 9, 3, 2, 60)
    assert len(a) == 3 and a[0].shape == (2, 60, 60, 3)
    assert a[0].dtype == np.uint8
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a[0][0], a[0][1])
    assert not np.array_equal(a[0], make_ring(2 ** 31 + 10, 3, 2, 60)[0])


def test_image_frames_are_the_bytes_frames_makes(loader):
    from benchmark.frames import make_ring
    from benchmark.inputs import sampled, tensors

    inputs = loader.module("inputs", "image_frames")
    cfg, seed = {"image_size": 60}, 2 ** 31 + 9
    ring = inputs.make_ring(cfg, {"batch": 99}, seed, 3, 2)
    plain = make_ring(seed, 3, 2, 60)
    assert len(ring) == 3
    assert all(a.tobytes() == b.tobytes() and a.dtype == b.dtype
               and a.shape == b.shape for a, b in zip(ring, plain))
    # a slot is one array or a tuple of arrays; a sample keeps the shape
    assert tensors(ring[0]) == (ring[0],)
    picks = [(2, 1), (0, 0)]
    got = sampled(ring, picks)
    assert np.array_equal(got, np.stack([plain[2][1], plain[0][0]]))
    pair = [(np.arange(4, dtype=np.int32).reshape(2, 2) + k,
             np.full((2, 1), k, np.int32)) for k in range(3)]
    tokens, positions = sampled(pair, picks)
    assert tokens.tolist() == [[4, 5], [0, 1]]
    assert positions.tolist() == [[2], [0]] and positions.dtype == np.int32


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_accepted_configurations_name_no_inputs_file(loader, config):
    """They get ``image_frames``: same frame bytes from the same seed."""
    assert "inputs" not in loader.config(config)
    assert callable(loader.module("inputs", "image_frames").make_ring)


# -- the trace reduction, on a synthetic trace -----------------------------------


def _synthetic():
    ms = 1e6
    ops0 = [("fusion.1", 0 * ms, 4 * ms), ("conv.2", 3 * ms, 3 * ms),
            ("fusion.1", 10 * ms, 4 * ms), ("conv.2", 13 * ms, 3 * ms)]
    ops1 = [("fusion.1", 0 * ms, 8 * ms), ("fusion.1", 10 * ms, 8 * ms)]
    host = [("el_net", 5 * ms, 6 * ms), ("el_sink", 6.5 * ms, 1 * ms),
            ("other", 0, 20 * ms)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops0},
            {"name": "XLA Modules", "events": [
                ("jit_f(1)", 0, 6 * ms), ("jit_f(2)", 10 * ms, 6 * ms),
                ("jit_pre(3)", 6 * ms, 0.1 * ms)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [{"name": "nns:p:net",
                                         "events": host}]},
    ]


def test_trace_busy_union_and_idle_share():
    out = trace.reduce_trace(_synthetic(), 2, "el_")
    # chip 0: [0,6] and [10,16] -> 12 ms; chip 1: 16 ms; mean 14 ms
    assert out["busy_s_per_chip"] == pytest.approx([0.012, 0.016])
    assert out["busy_s"] == pytest.approx(0.014)
    assert out["window_s"] == pytest.approx(0.018)
    # two executions of the program with most device time, both shapes;
    # chip 1's lines do not name it, so time and count are chip 0's:
    # busy [0,6] + [10,16] inside the span [0,16] of the two executions
    assert out["main_program"] == "jit_f" and out["windows"] == 2
    assert out["programs"] == {"jit_f": 2, "jit_pre": 1}
    assert out["program_busy_s"] == pytest.approx(0.012)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(4 / 18)


def test_trace_ops_and_gap_attribution():
    out = trace.reduce_trace(_synthetic(), 2, "el_")
    ops = dict((name, s) for name, s in out["device_ops"])
    # summed over both chips, divided by the chips
    assert ops["fusion.1"] == pytest.approx((0.008 + 0.016) / 2)
    assert ops["conv.2"] == pytest.approx(0.006 / 2)
    # chip 0's one gap, [6,10] ms, lies wholly under el_net (el_sink
    # covers a quarter of it; "other" is not an element span)
    assert out["idle_gaps"] == [["el_net", pytest.approx(0.004)]]
    assert out["longest_gap_s"] == pytest.approx(0.004)


def test_trace_gap_without_element_and_missing_chip():
    planes = _synthetic()
    planes[2]["lines"][0]["events"] = [("other", 0, 2e7)]
    out = trace.reduce_trace(planes, 2, "el_")
    assert out["idle_gaps"][0][0] == trace.NO_ELEMENT
    with pytest.raises(ValueError):
        trace.reduce_trace(planes, 4, "el_")
    with pytest.raises(ValueError):
        trace.reduce_trace(planes[2:], 1, "el_")


def test_program_span_clips_what_lies_outside_the_executions():
    """Operations before the first and after the last execution of the
    filter program (a window cut by the trace's edge) are in ``busy_s``
    and not in ``program_busy_s``: time and count are of one span."""
    ms = 1e6
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ("tail", 0, 2 * ms), ("a", 3 * ms, 4 * ms), ("a", 8 * ms, 4 * ms),
            ("head", 13 * ms, 3 * ms)]},
        {"name": "XLA Modules", "events": [
            ("jit_f(1)", 3 * ms, 4 * ms), ("jit_f(1)", 8 * ms, 4 * ms),
            ("jit_small(2)", 13 * ms, 1 * ms)]}]}]
    out = trace.reduce_trace(planes, 1)
    assert out["busy_s"] == pytest.approx(0.013)
    assert out["windows"] == 2
    assert out["program_busy_s"] == pytest.approx(0.008)
    # on two chips each chip's own span and count are taken, then averaged
    second = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [("a", 3 * ms, 2 * ms),
                                       ("a", 8 * ms, 2 * ms)]},
        {"name": "XLA Modules", "events": [("jit_f(1)", 3 * ms, 2 * ms),
                                           ("jit_f(1)", 8 * ms, 2 * ms)]}]}
    both = trace.reduce_trace(planes + [second], 2)
    assert both["windows"] == 2
    assert both["program_busy_s"] == pytest.approx((0.008 + 0.004) / 2)


def test_per_window_program_time_and_roofline(loader):
    obs = {"chips": 2, "frames_per_window": 256.0, "trace": {
        "busy_s": 0.015, "program_busy_s": 0.014, "windows": 2},
        "cost": {"flops_per_frame": 2e9, "in_bytes_per_frame": 270000.0,
                 "weight_bytes": 27e6},
        "out_bytes_per_frame": 360000.0,
        "peaks": {"peak_flops_bf16": 197e12, "peak_hbm_bytes_per_s": 819e9}}
    per_window = loader.module("readers", "program_ms_per_window").read(obs)
    assert per_window == pytest.approx(7.0)
    roof = loader.module("readers", "filter_program_roofline")
    least, which = roof.bound(obs)
    t_compute = 512 * 2e9 / (2 * 197e12)
    t_memory = (512 * 630000.0 + 2 * 27e6) / (2 * 819e9)
    assert which == "compute" and least == pytest.approx(t_compute)
    assert t_memory < t_compute
    assert roof.read(obs) == pytest.approx(100 * t_compute / 0.014)
    assert roof.read({"trace": None}) is None
    # a traffic kind whose windows vary gives no frames a window: no share
    assert roof.read({**obs, "frames_per_window": None}) is None


# -- stage time ----------------------------------------------------------------------

PROGRAM_TEXT = """HloModule jit_f

%fused_a (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %t = f32[8] tanh(%p), metadata={op_name="jit(f)/nns.model/backbone/block00/tanh"}
}

%fused_made (p: f32[8]) -> f32[8] {
  %q = f32[8] parameter(0)
  %m1 = f32[8] multiply(%q, %q), metadata={op_name="jit(f)/nns.model/vmap(nms)/jit(_where)/mul"}
  ROOT %m2 = f32[8] add(%m1, %q), metadata={op_name="jit(f)/nns.model/vmap(nms)/add"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%x), kind=kLoop, calls=%fused_a, metadata={op_name="jit(f)/nns.model/backbone/block00/tanh"}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%fused_made
  %attn.3 = f32[8] exponential(%fusion.2), metadata={op_name="jit(f)/nns.model/layer07/attn/exp"}
  %outside.4 = f32[8] negate(%attn.3), metadata={op_name="jit(f)/neg"}
  ROOT %copy.5 = f32[8] copy(%outside.4)
}
"""


def test_stage_names_from_op_names_and_program_text():
    of = stages.stage_of
    assert of("jit(f)/nns.model/vmap(nms)/jit(_where)/select_n") \
        == "nns.model/nms"
    assert of("jit(f)/nns.model/layer07/attn/bhqk,bhkd->bhqd/dot_general") \
        == "nns.model/layer07/attn"
    assert of("jit(f)/nns.post/overlay/add") == "nns.post/overlay"
    assert of("jit(f)/vmap(nns.model)/heads/conv") == "nns.model/heads"
    assert of("jit(f)/mul") == stages.NO_SCOPE
    by_name = stages.stage_map(PROGRAM_TEXT)
    assert by_name["fusion.1"] == "nns.model/backbone/block00"
    # no metadata of its own: the stage of what it calls
    assert by_name["fusion.2"] == "nns.model/nms"
    assert by_name["attn.3"] == "nns.model/layer07/attn"
    assert by_name["outside.4"] == stages.NO_SCOPE
    assert "copy.5" not in by_name


def test_stage_seconds_on_a_synthetic_trace():
    ms = 1e6
    chip0 = [("%fusion.1 = f32[8] fusion(%x)", 0, 4 * ms),
             ("%fusion.2 = f32[8] fusion(...)", 4 * ms, 2 * ms),
             ("%attn.3 = f32[8] exponential(...)", 6 * ms, 3 * ms),
             # nested in attn.3: counts once, for itself
             ("%fusion.1 = f32[8] fusion(%x)", 7 * ms, 1 * ms),
             ("%copy.5 = f32[8] copy(...)", 10 * ms, 1 * ms),
             ("%outside.4 = f32[8] negate(...)", 11 * ms, 1 * ms)]
    chip1 = [("%fusion.1 = f32[8] fusion(%x)", 0, 2 * ms)]
    planes = [
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": chip1}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": chip0},
            {"name": "XLA Modules", "events": [("jit_f(1)", 0, 12 * ms)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "nns:p:net", "events": [("el_net", 0, 12 * ms)]}]}]
    one = stages.stage_seconds(planes, PROGRAM_TEXT, 1, "/device:TPU:",
                               "XLA Ops")
    assert one == {"nns.model/backbone/block00": pytest.approx(0.005),
                   "nns.model/nms": pytest.approx(0.002),
                   "nns.model/layer07/attn": pytest.approx(0.002),
                   stages.NO_METADATA: pytest.approx(0.001),
                   stages.NO_SCOPE: pytest.approx(0.001)}
    # the stages sum to the union of the operation intervals
    assert sum(one.values()) == pytest.approx(0.011)
    # per chip: the mean over the chips the cell uses
    two = stages.stage_seconds(planes, PROGRAM_TEXT, 2, "/device:TPU:",
                               "XLA Ops")
    assert two["nns.model/backbone/block00"] == pytest.approx(0.0035)
    assert two["nns.model/nms"] == pytest.approx(0.001)
    assert stages.stage_seconds(planes[2:], PROGRAM_TEXT, 1,
                                "/device:TPU:", "XLA Ops") == {}


def test_stage_ms_per_window_matches_by_prefix_and_last_part(loader):
    read = loader.module("readers", "stage_ms_per_window").read
    obs = {"trace": {"windows": 4, "stage_s": {
        "nns.model/backbone/stem": 0.004, "nns.model/backbone/block00": 0.008,
        "nns.model/heads": 0.002, "nns.model/nms": 0.001,
        "nns.model/topk": 0.001, "nns.post/overlay": 0.002,
        "nns.model/layer00/attn": 0.006, "nns.model/layer01/attn": 0.006,
        "nns.model/layer00/mlp": 0.004, "nns.model/layer00/ln1": 0.001,
        "(no metadata)": 0.5}}}
    assert read(obs, starts=["nns.model/backbone"]) == pytest.approx(3.0)
    assert read(obs, starts=["nns.model/decode", "nns.model/topk",
                             "nns.model/nms", "nns.post"]) \
        == pytest.approx(1.0)
    assert read(obs, starts=["nns.model/layer"], ends=["/attn"]) \
        == pytest.approx(3.0)
    assert read(obs, starts=["nns.model/layer"], ends=["/mlp"]) \
        == pytest.approx(1.0)
    # nothing to read is nothing, never 0
    assert read(obs, starts=["nns.model/extras"]) is None
    assert read(obs, starts=["nns.model/layer"], ends=["/moe"]) is None
    assert read({"trace": None}, starts=["nns."]) is None
    assert read({}, starts=["nns."]) is None
    assert read({"trace": {"windows": 4, "stage_s": None}},
                starts=["nns."]) is None
    assert read({"trace": {"windows": 0, "stage_s": {"nns.model/x": 1.0}}},
                starts=["nns."]) is None


STAGE_METRICS = ["backbone_ms_per_window", "postprocess_ms_per_window",
                 "attn_ms_per_window", "mlp_ms_per_window"]


@pytest.mark.parametrize("metric", STAGE_METRICS)
def test_stage_metrics_are_data_files_of_one_reader(loader, metric):
    spec = loader.json("layer_metrics", metric)
    assert spec["name"] == metric
    assert spec["reader"] == "stage_ms_per_window"
    assert set(spec["args"]) <= {"starts", "ends"} and spec["args"]["starts"]
    entry = loader.entry("per_layer", metric)
    assert entry["source"] == "device_trace" and entry["unit"] == "ms"
    assert entry["layer"] == "filter program"
    assert entry["moves"] == "fps_per_chip" and entry["workloads"]


def test_toy_root_drops_a_listed_cell_without_a_toy_twin(tmp_path):
    sys.path.insert(0, HERE)
    import toyroot

    root = toyroot.build(str(tmp_path / "toy"))
    toy = Loader(root)
    listed = {m["name"]: m.get("workloads")
              for m in toy.manifest["per_layer"]}
    assert "ssd300.replay.mesh4" in Loader(REPO).entry(
        "per_layer", "backbone_ms_per_window")["workloads"]
    assert listed["backbone_ms_per_window"] == ["toy_ssd.replay"]
    assert listed["attn_ms_per_window"] == ["toy_vit.replay"]
    assert toy.reports(toy.entry("per_layer", "backbone_ms_per_window"),
                       "toy_ssd.replay")
    assert not toy.reports(toy.entry("per_layer", "attn_ms_per_window"),
                           "toy_ssd.replay")


def test_stalled_ms_is_the_time_beyond_the_median_interval(loader):
    read = loader.module("readers", "stalled_ms").read
    # median 10: one stall of 130 (120 beyond), a spell of three slow
    # windows of 15 (5 beyond each); 12 is jitter and does not count
    gaps = [10.0] * 20 + [130.0, 15.0, 15.0, 15.0, 12.0]
    assert read({"window_gaps_ms": gaps}) == pytest.approx(135.0)
    assert read({"window_gaps_ms": [10.0, 10.2, 9.9]}) == 0.0
    assert read({}) is None


# -- the cost counts, against hand counts ----------------------------------------


def test_vit_cost_against_hand_count(loader):
    cfg = {"image_size": 32, "patch_size": 16, "hidden_size": 8,
           "num_hidden_layers": 1, "intermediate_size": 16,
           "num_classes": 5}
    cost = loader.module("costs", "vit_b16_224").frame_cost(cfg)
    s, d = 4, 8
    macs = (s * 16 * 16 * 3 * d            # patch projection
            + s * d * 3 * d + 2 * s * s * d + s * d * d   # attention
            + 2 * s * d * 16                 # mlp
            + d * 5)                         # head
    assert cost["flops_per_frame"] == 2 * macs
    assert cost["in_bytes_per_frame"] == 32 * 32 * 3
    matrices = 16 * 16 * 3 * d + s * d + 3 * d * d + d * d + 2 * d * 16 + d * 5
    vectors = d + (4 * d + 3 * d + d + 16 + d) + 2 * d + 5
    assert cost["weight_bytes"] == matrices * 2 + vectors * 4


def test_ssd_cost_against_hand_count(loader):
    costs = loader.module("costs", "nns_ssd_mobilenet_v2_300")
    cfg = {"image_size": 8, "stem_channels": 4, "num_classes": 3,
           "backbone_blocks": [[1, 2, 1, 1], [2, 4, 1, 2]], "tap_block": 0,
           "extra_channels": [6], "anchors_per_cell": 2}
    layers = costs.conv_layers(cfg)
    want = [(3, 3, 4, 1, 4),                  # stem, 8 -> 4
            (3, 4, 4, 4, 4), (1, 4, 2, 1, 4),  # block 0 (t=1): dw, project
            (1, 2, 4, 1, 4), (3, 4, 4, 4, 2), (1, 4, 4, 1, 2),  # block 1
            (3, 4, 6, 1, 1),                  # extra, 2 -> 1
            (3, 2, 8, 1, 4), (3, 2, 6, 1, 4),  # heads on the tap (4x4x2)
            (3, 4, 8, 1, 2), (3, 4, 6, 1, 2),  # heads on the last map
            (3, 6, 8, 1, 1), (3, 6, 6, 1, 1)]  # heads on the extra
    assert layers == want
    macs = sum(side * side * k * k * (cin // g) * cout
               for k, cin, cout, g, side in want)
    cost = costs.frame_cost(cfg)
    assert cost["flops_per_frame"] == 2 * macs
    assert cost["in_bytes_per_frame"] == 8 * 8 * 3
    # the real configuration: about 1 GMAC a frame, 3000 anchors
    real = Loader(REPO).config("nns_ssd_mobilenet_v2_300")
    assert 1.9e9 < costs.frame_cost(real)["flops_per_frame"] < 2.1e9
    ref = Loader(REPO).module("reference", "nns_ssd_mobilenet_v2_300")
    assert ref.anchors(real).shape == (3000, 4)
    assert sum(f * f for f in real["feature_maps"]) * 6 == 3000
    assert math.ceil(300 / 16) == real["feature_maps"][0]


def test_window_wait_reader_pairs_park_with_dispatch(loader):
    read = loader.module("readers", "window_wait_p95_ms").read
    records = [{"marks": [(0.0, "src", "source"), (0.010, "net", "park"),
                          (0.012, "net", "dispatch")]},
               {"marks": [(1.0, "net", "park"), (1.004, "net", "dispatch")]}]
    got = read({"trace": {"tracer_records": records}})
    assert got == pytest.approx(stats.percentile([2.0, 4.0], 95))
    assert read({"trace": None}) is None


# -- the open-loop traffic kind, driven at toy size -------------------------------


@pytest.fixture(scope="module")
def pool_run(tmp_path_factory):
    """One traced run of the toy camera pool on the CPU (the look for a
    chip skipped): two 20-fps streams into one share-model pool."""
    sys.path.insert(0, HERE)
    import toyroot
    from benchmark.run import run_cell

    root = toyroot.build(str(tmp_path_factory.mktemp("toypool")))
    details: dict = {}
    line = run_cell("toy_ssd.pool", 2 ** 31 + 77, 1.2, True, root=root,
                    rehearsal=True, details=details)
    return root, line, details


def test_pool_latency_failed_attempted_arithmetic(pool_run):
    root, line, details = pool_run
    obs = details["obs"]
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    mix = Loader(root).json("traffic", "toy_pool")
    offered = mix["streams"] * mix["fps"] * 0.9
    assert abs(line["attempted"] - offered) <= mix["streams"]
    # a traced run reports the part of the window before the capture
    # starts (three quarters of this toy window)
    assert len(obs["latencies_ms"]) == line["attempted"] - line["failed"]
    assert obs["window_s"] == pytest.approx(0.9)
    assert min(obs["latencies_ms"]) > 0
    loader = Loader(root)
    p50 = loader.module("end_to_end", "latency_p50_ms").read(obs)
    p95 = loader.module("end_to_end", "latency_p95_ms").read(obs)
    assert p50 == stats.percentile(obs["latencies_ms"], 50)
    assert 0 < p50 <= p95 <= max(obs["latencies_ms"])
    assert len(obs["gen_lag_ms"]) == len(obs["latencies_ms"])


def test_pool_traced_run_reads_every_layer(pool_run):
    root, line, _details = pool_run
    assert {"gen_lag_p95_ms", "h2d_bytes_per_frame", "window_frames",
            "window_wait_p95_ms", "program_ms_per_window"} \
        <= set(line["metrics"])
    # pool windows vary in size: no roofline share from this traffic kind
    assert "filter_program_roofline" not in line["metrics"]
    size = Loader(root).config("toy_ssd")["image_size"]
    # one uint8 frame a frame crosses to the device, nothing else; on a
    # loaded test machine some of these few dozen frames are fenced after
    # the counted part closes, with their bytes already booked
    per_frame = line["metrics"]["h2d_bytes_per_frame"]["value"]
    assert 0.95 * size * size * 3 <= per_frame <= 4 * size * size * 3
    assert 1.0 <= line["metrics"]["window_frames"]["value"] <= 2.0


