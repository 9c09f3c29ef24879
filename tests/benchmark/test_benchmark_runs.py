"""The harness driven end to end on the CPU at toy size: the replay
traffic through ``run_cell`` (the look for a chip skipped; the open-loop
kind is in ``test_benchmark_files.py``, so that two workers share the
compiles), the output check
with its control and with the timed path broken underneath, the command's
refusal to run without a chip, and the add-by-files property.

The toy cells, configurations and traffic mixes are ADDED as files to a
temporary copy of ``benchmark/`` (``toyroot.py``); no file of the harness
is edited for them.  No number here is a rate: ``platform`` is ``cpu`` in
every result line, and the assertions are about keys, counts and the
verdict of the check."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for _p in (REPO, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import toyroot  # noqa: E402
from benchmark.run import Loader, cut_faults, run_cell  # noqa: E402

SEED = 2 ** 31 + 20260927          # the driver's seeds pass 32 signed bits
_RUNS: dict = {}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toyroot.build(str(tmp_path_factory.mktemp("toybench")))


def _run(root, cell, trace, seed=SEED):
    key = (cell, trace, seed)
    if key not in _RUNS:
        details: dict = {}
        line = run_cell(cell, seed, 0.6, trace, root=root, rehearsal=True,
                        details=details)
        _RUNS[key] = (line, details)
    return _RUNS[key]


CASES = [("toy_ssd.replay", False), ("toy_vit.replay", True)]


@pytest.mark.parametrize("cell,trace", CASES)
def test_cell_function_returns_the_contract_keys(root, cell, trace):
    line, _ = _run(root, cell, trace)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"           # never a rate
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    manifest = Loader(root).manifest
    loader = Loader(root)
    section = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in manifest[section]}
    assert line["metrics"], "no metric reported"
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name]
        assert isinstance(m["value"], float) and np.isfinite(m["value"])
        assert loader.reports(loader.entry(section, name), cell)
    if trace:
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] >= line["device"]["busy_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
        assert "program_ms_per_window" in line["metrics"]
        # a roofline share needs the chip's peaks: none on the CPU
        assert "filter_program_roofline" not in line["metrics"]
    else:
        assert {"fps_per_chip", "setup_s"} <= set(line["metrics"])
        assert "breakdown" not in line
    # every number compared, beside its limit, as the line's last key
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {n["name"] for n in
                                     _run(root, cell, trace)[1]["numbers"]}
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}
        assert row["value"] <= row["limit"]
    json.dumps(line)                                       # one JSON line


def test_traced_rehearsals_read_their_own_stages_and_not_the_others(root):
    """``stage_s`` of a traced run names the stages of the cell's own
    fused program (the CPU's thunk events are named by instruction, like
    the TPU's operations), and each cell reports the stage metrics that
    list it.  The values are CPU time: counts of nothing."""
    ssd, ssd_details = _run(root, "toy_ssd.replay", True)
    vit, vit_details = _run(root, "toy_vit.replay", True)
    ssd_stages = ssd_details["obs"]["trace"]["stage_s"]
    vit_stages = vit_details["obs"]["trace"]["stage_s"]
    assert {"nns.model/backbone/stem", "nns.model/backbone/block00",
            "nns.model/heads", "nns.model/nms", "nns.model/topk",
            "nns.post/overlay"} <= set(ssd_stages)
    assert {"nns.model/embed", "nns.model/layer00/attn",
            "nns.model/layer01/mlp", "nns.model/head"} <= set(vit_stages)
    assert not any("/layer0" in name for name in ssd_stages)
    assert not any("/backbone" in name for name in vit_stages)
    for name in ("backbone_ms_per_window", "postprocess_ms_per_window"):
        assert ssd["metrics"][name]["value"] > 0
        assert ssd["metrics"][name]["unit"] == "ms"
        assert name not in vit["metrics"]
    for name in ("attn_ms_per_window", "mlp_ms_per_window"):
        assert vit["metrics"][name]["value"] > 0
        assert name not in ssd["metrics"]
    # the parts are parts: no more than the program's time a window
    for line, parts in ((ssd, ("backbone_ms_per_window",
                               "postprocess_ms_per_window")),
                        (vit, ("attn_ms_per_window", "mlp_ms_per_window"))):
        whole = sum(line["metrics"][p]["value"] for p in parts)
        assert whole <= 1.05 * line["metrics"]["program_ms_per_window"][
            "value"]
    # an untraced run asks for no program text and has no trace
    _plain, plain_details = _run(root, "toy_ssd.replay", False)
    assert plain_details["obs"]["trace"] is None


def test_replay_counts_whole_windows_and_one_program_each(root):
    line, details = _run(root, "toy_ssd.replay", False)
    obs = details["obs"]
    assert obs["frames"] == obs["windows"] * obs["batch"] == line["attempted"]
    # the window opens at a fence, so every counted window has an
    # interval, and the one that outlasts the window is there too
    assert len(obs["window_gaps_ms"]) == obs["windows"] + 1
    assert sum(obs["window_gaps_ms"]) >= 600.0          # the toy window, ms
    assert obs["window"]["compiles"] == 0
    assert obs["window"]["aot_fallback"] == 0
    assert obs["window"]["xla_compiles"] == 0
    # transform + filter + decoder fused: one launch a window (the
    # windows in flight at the edges make the ratio inexact)
    launches = sum(obs["window"]["dispatch"].values())
    assert abs(launches - obs["windows"]) <= 6
    assert set(obs["window"]["dispatch"]) <= {"filter", "transform",
                                              "decoder", "decoder_pack"}
    assert obs["window"]["dispatch"].get("transform", 0) == 0


# -- the output check: its control, and a broken timed path ----------------------


@pytest.mark.parametrize("cell", ["toy_ssd.replay", "toy_vit.replay"])
def test_check_fails_the_lower_precision(root, cell):
    """The control: the reference computed in float8_e4m3fn, the nearest
    precision below the configuration's bfloat16, put in the program's
    place on the run's own sampled frames.  The program passed; the
    control has to fail one of the cell's numbers."""
    line, details = _run(root, cell, cell == "toy_vit.replay")
    assert line["correct"] is True
    cfg = details["cfg"]
    reference = Loader(root).module("reference", cfg["reference"])
    numbers = reference.control(cfg, SEED, details["frames"])
    sound = {n["name"]: n["value"] for n in details["numbers"]}
    failed = [n["name"] for n in numbers if n["value"] > n["limit"]]
    assert failed, f"the control passed: {numbers}"
    for n in numbers:
        if n["name"] in failed:
            assert n["value"] > 3 * sound[n["name"]]


def test_check_fails_a_broken_timed_path(root, monkeypatch):
    """The rest of a run with the timed path broken underneath: the
    program's ViT hands every frame its neighbour's logits (an answer
    altered where it is produced), and ``correct`` comes out false."""
    from nnstreamer_tpu.models import vit

    plain = vit.vit_apply
    monkeypatch.setattr(
        vit, "vit_apply",
        lambda *a, **kw: __import__("jax").numpy.roll(plain(*a, **kw), 1, 0))
    details: dict = {}
    line = run_cell("toy_vit.replay", SEED + 1, 0.4, False, root=root,
                    rehearsal=True, details=details)
    assert line["correct"] is False
    bad = {n["name"] for n in details["numbers"] if n["value"] > n["limit"]}
    assert bad == {"logits_rel_l2"}


def test_reference_agrees_with_the_program_in_float32(root):
    """The reference is written from the configuration file, not by
    calling the program: in float32 the two agree to rounding, so the
    wiring (taps, extras, anchors, batch norm) is the program's."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models.ssd import ssd_anchors, ssd_mobilenet_v2_apply

    loader = Loader(root)
    cfg = loader.config("toy_ssd")
    params = loader.module("weights", cfg["weights"]).make(cfg, SEED)
    ref = loader.module("reference", cfg["reference"])
    size = cfg["image_size"]
    frames = np.random.default_rng(3).integers(
        0, 256, (2, size, size, 3), dtype=np.uint8)
    loc, cls = ref.raw_outputs(cfg, SEED, frames, block=2)
    x = (frames.astype(np.float32) - 127.5) / 127.5
    loc_p, cls_p = jax.jit(lambda v: ssd_mobilenet_v2_apply(
        params, v, dtype=jnp.float32))(x)
    assert np.allclose(np.asarray(loc_p), loc, atol=1e-4)
    assert np.allclose(np.asarray(cls_p), cls, atol=1e-4)
    assert np.array_equal(ref.anchors(cfg),
                          ssd_anchors(size, tuple(cfg["feature_maps"])))


# -- the command -------------------------------------------------------------------


def _command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_command_prints_the_line_last_and_the_numbers_compared(
        monkeypatch, capsys):
    from benchmark import run as harness

    canned = {"correct": True, "attempted": 2, "failed": 0, "metrics": {},
              "device": {}, "compared": {
                  "logits_rel_l2": {"value": 0.0021, "limit": 0.014},
                  "order_errors": {"value": 0.0, "limit": 0.0}}}
    monkeypatch.setattr(harness, "run_cell", lambda *a, **kw: canned)
    assert harness.main(["--workload", "c", "--seed", "1", "--seconds",
                         "1"]) == 0
    out, err = capsys.readouterr()
    assert json.loads(out.splitlines()[-1]) == canned
    assert err.splitlines()[-2:] == [
        "compared logits_rel_l2: 0.0021 (limit 0.014)",
        "compared order_errors: 0 (limit 0)"]


def test_command_refuses_the_cpu_backend():
    out = _command(REPO, "--workload", "ssd300.replay", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


def test_command_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "ssd300.replay", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


# -- added by files -----------------------------------------------------------------


def _snapshot(directory: str) -> dict:
    """{path: bytes} of every file under ``directory``."""
    before = {}
    for base, _dirs, files in os.walk(directory):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                before[path] = f.read()
    return before


def _assert_unedited(before: dict) -> None:
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, f"{path} was edited"


def test_cell_config_traffic_and_metric_are_added_as_files(root, tmp_path):
    """A later PR adds a configuration, a cell, a traffic kind and a
    per-layer metric as new files plus entries in BENCHMARK.json; the
    harness takes them unedited."""
    new = str(tmp_path / "added")
    shutil.copytree(root, new)
    bench = os.path.join(new, "benchmark")
    before = _snapshot(bench)
    # a configuration: its file of sizes (the reference beside it is the
    # ViT's, named in the file)
    with open(os.path.join(bench, "configs", "toy_vit.json")) as f:
        cfg = json.load(f)
    cfg.update(name="added_vit", num_hidden_layers=1)
    with open(os.path.join(bench, "configs", "added_vit.json"), "w") as f:
        json.dump(cfg, f)
    # a traffic kind: new generator code, and a mix that names it
    with open(os.path.join(bench, "traffic", "added_kind.py"), "w") as f:
        f.write("from benchmark.traffic import replay\n\n\n"
                "def run(run):\n"
                "    obs = replay.run(run)\n"
                "    obs['added_reading'] = 42.0\n"
                "    return obs\n")
    with open(os.path.join(bench, "traffic", "toy_replay.json")) as f:
        mix = json.load(f)
    mix["kind"] = "added_kind"
    with open(os.path.join(bench, "traffic", "added_mix.json"), "w") as f:
        json.dump(mix, f)
    # a cell
    with open(os.path.join(bench, "workloads", "toy_vit.replay.json")) as f:
        work = json.load(f)
    work.update(name="added.cell", config="added_vit", traffic="added_mix")
    with open(os.path.join(bench, "workloads", "added.cell.json"), "w") as f:
        json.dump(work, f)
    # a per-layer metric: a reader of its own
    with open(os.path.join(bench, "readers", "added_metric.py"), "w") as f:
        f.write("def read(obs):\n    return obs.get('added_reading')\n")
    # and one that reuses a reader with arguments of its own
    with open(os.path.join(bench, "layer_metrics", "added_bytes.json"),
              "w") as f:
        json.dump({"name": "added_bytes", "reader": "ledger_bytes_per_frame",
                   "args": {"key": "no.such.bytes"}}, f)
    with open(os.path.join(new, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "added_vit", "source": "test",
        "file": "benchmark/configs/added_vit.json", "reduced": [],
        "why": "added"})
    manifest["workloads"].append({
        "name": "added.cell", "config": "added_vit", "traffic": "added_mix",
        "chips": 1, "why": "added"})
    manifest["per_layer"].append({
        "name": "added_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "fps_per_chip", "workloads": ["added.cell"]})
    manifest["per_layer"].append({
        "name": "added_bytes", "unit": "B/frame", "better": "lower",
        "source": "program_counter", "layer": "source staging",
        "moves": "fps_per_chip", "workloads": ["added.cell"]})
    with open(os.path.join(new, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    line = run_cell("added.cell", SEED + 2, 0.4, True, root=new,
                    rehearsal=True)
    assert line["correct"] is True
    assert line["metrics"]["added_metric"] == {"value": 42.0,
                                               "unit": "count"}
    # its reader finds no such row in the ledger and returns nothing,
    # so the harness leaves the metric out of the line
    assert "added_bytes" not in line["metrics"]
    assert "program_ms_per_window" in line["metrics"]     # the old ones too
    # a cell that does not list the metric does not report it
    other = run_cell("toy_vit.replay", SEED + 2, 0.4, True, root=new,
                     rehearsal=True)
    assert "added_metric" not in other["metrics"]
    _assert_unedited(before)


ADDED = os.path.join(HERE, "data", "added_tokens")


def test_a_cut_token_configuration_is_added_as_files(root, tmp_path):
    """The hard case, as a ``model_config`` PR would bring it: a
    configuration with no ``image_size`` and a stated cut (``reduced``,
    ``published``, ``deployment``), an ``inputs`` file that makes int32
    token windows as a tuple of two arrays, its own weights, glue,
    reference and costs, a launch line with no ``tensor_transform`` and a
    field of the configuration's own, a stage metric as a data file, and
    per-layer metrics that list the added cell alone.  All of it is files
    (``tests/benchmark/data/added_tokens``) plus entries; the harness
    takes it unedited and the run is ``correct``."""
    new = str(tmp_path / "added")
    shutil.copytree(root, new)
    bench = os.path.join(new, "benchmark")
    before = _snapshot(bench)
    with open(os.path.join(ADDED, "manifest_entries.json")) as f:
        entries = json.load(f)
    for kind in sorted(os.listdir(ADDED)):
        if not os.path.isdir(os.path.join(ADDED, kind)):
            continue
        for name in os.listdir(os.path.join(ADDED, kind)):
            if name == "__pycache__":
                continue
            target = os.path.join(bench, kind, name)
            assert not os.path.exists(target), f"{target} is there already"
            shutil.copy(os.path.join(ADDED, kind, name), target)
    with open(os.path.join(new, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for section, rows in entries.items():
        manifest[section] += rows
    with open(os.path.join(new, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    loader = Loader(new)
    cfg = loader.config("added_tokens")
    assert "image_size" not in cfg and "transform" not in cfg
    assert cfg["reduced"] and cut_faults(
        cfg, loader.entry("configs", "added_tokens")["reduced"]) == []
    details: dict = {}
    line = run_cell("added.tokens", SEED + 3, 0.4, True, root=new,
                    rehearsal=True, details=details)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # what went in and what the reference was handed: two int32 tensors
    tokens, positions = details["frames"]
    assert tokens.dtype == positions.dtype == np.int32
    assert tokens.shape == positions.shape == (cfg["check_frames"], 1)
    assert tokens.max() < cfg["vocab_size"]
    assert "tensor_transform" not in loader.json("workloads",
                                                 "added.tokens")["launch"]
    # its own stage, by a data file of the stage reader; its own reader;
    # and the metrics every cell reports
    assert {"nns.model/layer00/mix", "nns.model/layer03/mix"} \
        <= set(details["obs"]["trace"]["stage_s"])
    assert line["metrics"]["added_mix_ms_per_window"]["value"] > 0
    assert line["metrics"]["added_token_bytes_per_frame"] == {
        "value": 8.0, "unit": "bytes"}
    assert {"program_ms_per_window", "host_ms_per_window"} \
        <= set(line["metrics"])
    # the image cells' stage metrics list other cells: not read here
    assert not {"backbone_ms_per_window", "attn_ms_per_window"} \
        & set(line["metrics"])
    # a cell that the new metrics do not list does not report them
    other = run_cell("toy_vit.replay", SEED + 3, 0.4, True, root=new,
                     rehearsal=True)
    assert other["correct"] is True
    assert not {"added_mix_ms_per_window",
                "added_token_bytes_per_frame"} & set(other["metrics"])
    # the control: the tables in float8 fail the cell's number
    reference = loader.module("reference", cfg["reference"])
    numbers = reference.control(cfg, SEED + 3, details["frames"])
    assert [n["name"] for n in numbers if n["value"] > n["limit"]] \
        == ["sum_abs_err"]
    _assert_unedited(before)
