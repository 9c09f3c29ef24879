"""The cached-decode cell of Phi-4-mini-flash-reasoning on the CPU: its
files resolve and the configuration is the catalog row UNCUT, its cost
functions agree with a count by hand, its readers do their arithmetic on
made-up observations, and a toy twin of the cell runs end to end through
``run_cell(..., rehearsal=True)``: two ``tensor_filter`` lines on one
state in which half the layers own nothing (three Mamba-1 states with
snapshots, two rings, ONE cache that one layer writes and two read),
a prefill that stops at the cache's writer, the window (every pass of
the ring a rewind to the prompts' ends), the reference (every layer on
every token, head by head), and the check failing a stale memory, a
cross layer on the wrong rows, a dropped ``lambda``, a state not
restored and the float8 control.  No number here is a rate."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import toyroot  # noqa: E402
from benchmark.run import Loader, cut_faults, launch_line, run_cell  # noqa: E402

SEED = 3000000019          # more than 32 signed bits hold
CELL, CONFIG = "phi4flash.decode16k", "phi4_mini_flash_reasoning"
TOY, TOY_CONFIG = "toy_phi4flash.decode", "toy_phi4flash"

STAGE_METRICS = {f"phi4flash_{n}_ms_per_window" for n in (
    "mamba", "window_attn", "shared_attn", "gmu", "mlp", "head",
    "unattributed")}
ROOFLINES = {"phi4flash_decode_step_roofline",
             "phi4flash_decode_attention_roofline", "phi4flash_mlp_roofline"}
COUNTER_METRICS = {"phi4flash_shared_kv_bytes_per_frame",
                   "phi4flash_ring_kv_bytes_per_frame",
                   "phi4flash_kv_bytes_fetched_per_frame",
                   "phi4flash_ssm_state_bytes_per_frame",
                   "phi4flash_restores_per_window"}
SETUP_METRICS = {"phi4flash_prefill_s", "phi4flash_cross_tokens_share"}
NEW_METRICS = STAGE_METRICS | ROOFLINES | COUNTER_METRICS | SETUP_METRICS


@pytest.fixture(scope="module")
def loader():
    return Loader(REPO)


@pytest.fixture(scope="module")
def cfg(loader):
    return loader.config(CONFIG)


# -- the files ------------------------------------------------------------------------


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning")


def test_nothing_is_cut(loader, cfg):
    entry = loader.entry("configs", CONFIG)
    assert entry["reduced"] == [] == cfg["reduced"]
    assert cut_faults(cfg, entry["reduced"]) == []
    assert "published" not in cfg and "deployment" not in cfg
    # every width, head count, window, layer count and the whole vocabulary
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["num_hidden_layers"], cfg["sliding_window"],
            cfg["mb_per_layer"], cfg["vocab_size"],
            cfg["tie_word_embeddings"], cfg["layer_norm_eps"]) \
        == (2560, 10240, 40, 20, 32, 512, 2, 200064, True, 1e-5)
    assert not any("rope" in key or "rotary" in key for key in cfg)
    assert (cfg["mamba_expand"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_dt_rank"]) == (2, 16, 4, 160)
    assert entry["source"] == cfg["source"] and entry["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    for key in ("assumed", "serving", "limits", "limits_why", "init"):
        assert cfg[key], key
    assert (cfg["precision"], cfg["control_precision"]) \
        == ("bfloat16", "float8_e4m3fn")
    # what the row leaves open is under `assumed`, with its alternative
    assumed = " ".join(cfg["assumed"])
    for words in ("dt_rank = ceil(2,560 / 16) = 160", "WITH its D c_t term",
                  "j = i // 2", "carry a bias", "no rotary key",
                  "log-uniform in 0.001-0.1", "forced from the ring",
                  "float32 [16, 5,120]", "LayerNorm"):
        assert words in assumed, words
    assert assumed.count("alternative") >= 10
    serving = cfg["serving"]
    assert (serving["streams"], serving["prompt_tokens"],
            serving["answer_tokens"]) == (32, [8192, 16128], 256)
    # the ring's arithmetic: window + chunk, in whole cells of 128
    chunk = serving["prefill_chunk"]
    assert serving["ring_positions"] \
        == -(-(cfg["sliding_window"] + chunk) // 128) * 128 == 1536
    assert "window + rewind" in serving["why"]
    assert cfg["inputs"] == "nemotron3_nano_share8"    # accepted, unchanged
    row = _catalog_row()
    if row is not None:
        for key, value in row["config"].items():
            assert cfg[key] == value, key
        assert cfg["source"] == row["source_url"]


def test_the_cell_runs_on_the_traffic_that_is_there(loader, cfg):
    entry = loader.entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "cached_decode32", 1)
    work = loader.json("workloads", CELL)
    mix = loader.json("traffic", entry["traffic"])
    assert mix == dict(mix, kind="cached_replay", batch=32,
                       ring_buffers=256, sink_depth=4)
    # the launch lines are falconh1.decode4k's
    theirs = loader.json("workloads", "falconh1.decode4k")
    assert (work["launch"], work["prefill_launch"]) \
        == (theirs["launch"], theirs["prefill_launch"])
    lines = [launch_line({"launch": work[key], "name": CELL}, cfg, mix,
                         model="m") for key in ("launch", "prefill_launch")]
    for line, prefix in zip(lines, ("el_", "pf_")):
        assert f"tensor_filter name={prefix}net framework=jax-xla model=m " \
               "shared-tensor-filter-key=m" in line
    serving = cfg["serving"]
    assert (serving["streams"], serving["answer_tokens"]) \
        == (mix["batch"], mix["ring_buffers"])
    inputs = loader.module("inputs", cfg["inputs"])
    assert inputs.cache_positions(cfg) == 16384
    lengths = [len(p) for p in inputs.prompts(cfg, SEED)]
    assert min(lengths) >= 8192 and max(lengths) <= 16128
    assert abs(sum(lengths) - 389_000) < 3_000
    ring = inputs.make_ring(cfg, mix, SEED, 256, 32)
    assert all(len(slot) == 2 for slot in ring)
    assert max(int(slot[1].max()) for slot in ring) <= 16383
    assert max(int(slot[0].max()) for slot in ring) < 200064
    # a prefill frame says how many of its ids are real
    chunk = serving["prefill_chunk"]
    chunks = inputs.prefill_chunks(cfg, SEED)
    assert all(len(c) == 4 and c[0].shape == (chunk,) for c in chunks)
    assert len(chunks) == sum(-(-n // chunk) for n in lengths)


def test_new_metrics_list_the_new_cell(loader):
    """This PR's metrics are there and list the cell (a later PR may add
    metrics of its own to the cell, or cells to these: neither is pinned
    here), each on a reader the benchmark already had but the share of
    two counters, which no accepted reader computes."""
    listing = {m["name"]: m for m in loader.manifest["per_layer"]
               if CELL in m.get("workloads", ())}
    assert NEW_METRICS <= set(listing)
    layers = {m["layer"] for m in loader.manifest["per_layer"]
              if m["name"] not in NEW_METRICS}
    readers = {}
    for name in NEW_METRICS:
        m = listing[name]
        spec = loader.json("layer_metrics", name)
        assert spec["name"] == name
        readers[name] = spec["reader"]
        assert m["moves"] == ("setup_s" if name in SETUP_METRICS
                              else "fps_per_chip")
        assert m["layer"] in layers            # a layer PERF.md has
        assert (m["unit"] == "%") == (name in ROOFLINES)
    assert set(readers.values()) == {
        "stage_ms_per_window", "state_counter_ratio", "setup_span_s",
        "dense_decode_step_roofline", "gqa_decode_attention_roofline",
        "stage_roofline", "counter_share"}
    assert CELL in [w["name"] for w in loader.manifest["workloads"]]
    assert CONFIG in [c["name"] for c in loader.manifest["configs"]]


# -- costs against a count by hand ---------------------------------------------------


def test_costs_against_a_hand_count(loader, cfg):
    """The numbers the issue reckoned the model with, recounted."""
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    mlp = 3 * 2560 * 10240
    assert round(mlp / 1e6, 1) == 78.6
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    mamba_vectors = 4 * 5120 + 3 * 5120 + 16 * 5120
    assert round((mamba + mamba_vectors) / 1e6, 1) == 41.2
    own = 2560 * 5120 + 2560 * 2560                  # W_q, W_kv, W_o
    assert round(own / 1e6, 1) == 19.7
    cross, gmu = 2 * 2560 * 2560, 2 * 2560 * 5120
    assert (round(cross / 1e6, 1), round(gmu / 1e6, 1)) == (13.1, 26.2)
    embedding = 200064 * 2560
    assert round(embedding / 1e6) == 512
    matrices = 32 * mlp + 9 * mamba + 9 * own + 7 * cross + 7 * gmu \
        + embedding
    assert round(matrices / 1e9, 2) == 3.85          # the published 3.8 B
    attn_vectors = 2 * 2560 + 4 * 64 + 128
    vectors = 9 * mamba_vectors + 16 * attn_vectors + 9 * 2560 \
        + 32 * 4 * 2560 + 2 * 2560
    assert cost["weight_bytes"] == matrices * 2 + vectors * 4
    assert round(cost["weight_bytes"] / 1e9, 2) == 7.71
    assert cost["dense_mlp_bytes"] == 32 * mlp * 2
    assert cost["head_bytes"] == embedding * 2
    assert round(cost["head_bytes"] / 1e9, 2) == 1.02
    assert cost["mamba_weight_bytes"] == 9 * (mamba * 2 + mamba_vectors * 4)
    # a token's K and V of one layer: 20 heads of 64, twice, bf16
    assert cost["cache_row_bytes"] == 2 * 20 * 64 * 2 == 5120
    state = 16 * 5120 * 4
    assert cost["ssm_row_bytes"] == 2 * (state + 3 * 5120 * 2)
    # nine layers own a cache row, nine a recurrent state
    assert cost["in_bytes_per_frame"] == 8 + 2560 * 2 + 9 * 5120 \
        + 9 * cost["ssm_row_bytes"]
    assert cost["out_bytes_per_frame"] == 200064 * 4 + 4
    assert cost["flops_per_frame"] == 2 * matrices + 9 * 6 * 16 * 5120
    # the PUBLISHED heads of 64 against a 128-wide value row, not the
    # zero-padded 128-wide queries the kernels are handed
    assert cost["flops_per_cache_row"] == 2 * 40 * (64 + 128)
    assert cost["attn_io_bytes_per_frame"] == 16 * 40 * (64 * 2 + 128 * 4)
    assert cost["layers"] == 32
    # what the state holds, as the issue reckoned it
    held = 32 * (16384 * 5120 + 8 * 1536 * 5120
                 + 9 * cost["ssm_row_bytes"])
    assert round(held / 1e9, 1) == 4.9
    # a step's compulsory bytes at the mean position (12.3 k): 24.7 GB,
    # two thirds of it the one cache's eight reads
    shared = 32 * 12288 * 5120 * 8
    rings = 32 * 512 * 5120 * 8
    step = cost["weight_bytes"] + shared + rings \
        + 32 * (cost["in_bytes_per_frame"] + cost["out_bytes_per_frame"])
    assert round(step / 1e9, 1) == 24.7
    assert round(100 * shared / step) == 65


# -- the readers' arithmetic ----------------------------------------------------------


def _stages():
    stages = {"nns.model/embed": 0.02, "nns.model/state": 0.01,
              "(no nns scope)": 0.01, "nns.model/head": 0.3,
              "nns.model/ssm_restore/while/body": 0.02}
    for layer, kind in ((0, "mamba"), (1, "attn_window"), (17, "attn_full"),
                        (18, "gmu"), (19, "attn_cross")):
        at = f"nns.model/layer{layer:02d}"
        stages[at + "/mlp"] = 0.2
        if kind == "mamba":
            for part, s in (("in_proj", 0.05), ("conv", 0.01),
                            ("x_proj", 0.01), ("step", 0.03),
                            ("gate", 0.01), ("out_proj", 0.02)):
                stages[f"{at}/mamba/{part}"] = s
        elif kind == "gmu":
            stages[at + "/gmu"] = 0.07
        else:
            for part, s in (("qkv", 0.02), ("gqa_decode_attention", 0.5),
                            ("diff", 0.01), ("o", 0.02)):
                stages[f"{at}/{kind}/{part}"] = s
            if kind != "attn_cross":
                stages[f"{at}/{kind}/cache_write"] = 0.01
    return stages


def _obs(loader, cfg, state):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    stages = _stages()
    return {"batch": 32, "cost": cost, "window": {"state": state},
            "peaks": {"peak_flops_bf16": 197e12,
                      "peak_hbm_bytes_per_s": 819e9},
            "trace": {"windows": 100.0,
                      "program_busy_s": sum(stages.values()),
                      "stage_s": stages}}


def _state(steps=1000):
    shared, ring = 32 * 12288, 32 * 512
    return {"steps": steps, "ssm_rows": steps * 32,
            "shared_rows_read": steps * shared,
            "ring_rows_read": steps * ring,
            "shared_kv_bytes_read": steps * shared * 5120 * 8,
            "ring_kv_bytes_read": steps * ring * 5120 * 8,
            "cache_bytes_read": steps * (shared + ring) * 5120 * 8,
            "kv_bytes_read": steps * (shared + ring) * 5120 * 8,
            "kv_bytes_fetched": steps * 32 * (12352 + 640) * 5120 * 8,
            "cache_bytes_fetched": steps * 32 * (12352 + 640) * 5120 * 8,
            "ssm_bytes": steps * 32 * 9 * 2 * (16 * 5120 * 4 + 3 * 5120 * 2),
            "restores": steps // 8, "position_faults": 0}


def _read(loader, name, obs):
    spec = loader.json("layer_metrics", name)
    return loader.module("readers", spec["reader"]).read(obs, **spec["args"])


def test_counter_readers(loader, cfg):
    obs = _obs(loader, cfg, _state())
    got = {name: _read(loader, name, obs) for name in COUNTER_METRICS}
    # every reader of the one cache: 8 x 5,120 B a row in use
    assert got["phi4flash_shared_kv_bytes_per_frame"] == 12288 * 5120 * 8
    assert got["phi4flash_ring_kv_bytes_per_frame"] == 512 * 5120 * 8
    assert got["phi4flash_kv_bytes_fetched_per_frame"] \
        == (12352 + 640) * 5120 * 8
    assert got["phi4flash_ssm_state_bytes_per_frame"] \
        == 9 * 2 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert got["phi4flash_restores_per_window"] == 0.125
    for name in COUNTER_METRICS:       # a program without the counters
        assert _read(loader, name, {"window": {}}) is None


def test_the_share_of_two_counters(loader, monkeypatch):
    """``cross_tokens`` over ``prefill_tokens`` from the program's own
    totals; nothing where the program keeps neither."""
    from nnstreamer_tpu.utils import stats

    fresh = stats.StateStats()
    monkeypatch.setattr(stats, "STATE_STATS", fresh)
    assert _read(loader, "phi4flash_cross_tokens_share", {}) is None
    fresh.add("steps", 5)
    assert _read(loader, "phi4flash_cross_tokens_share", {}) is None
    fresh.add("prefill_tokens", 389120)
    fresh.add("cross_tokens", 395)
    assert _read(loader, "phi4flash_cross_tokens_share", {}) \
        == 395 / 389120
    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.utils.stats", None)
    assert _read(loader, "phi4flash_cross_tokens_share", {}) is None


def test_stage_and_roofline_readers_count_what_they_say(loader, cfg):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    state = _state()
    obs = _obs(loader, cfg, state)
    mixer = {"mamba": 0.13 + 0.02, "window_attn": 0.56,
             "shared_attn": 0.56 + 0.55, "gmu": 0.07, "mlp": 1.0,
             "head": 0.3, "unattributed": 0.04}
    assert {f"phi4flash_{n}_ms_per_window" for n in mixer} == STAGE_METRICS
    for name, seconds in mixer.items():
        got = _read(loader, f"phi4flash_{name}_ms_per_window", obs)
        assert got == pytest.approx(seconds / 100 * 1e3), name
    # the seven stage metrics cover every stage once
    assert sum(mixer.values()) == pytest.approx(
        sum(obs["trace"]["stage_s"].values()))
    reads = state["cache_bytes_read"] / 1000
    nbytes = cost["weight_bytes"] + reads \
        + 32 * (cost["in_bytes_per_frame"] + cost["out_bytes_per_frame"])
    assert 24.6e9 < nbytes < 24.8e9
    busy = obs["trace"]["program_busy_s"]
    assert _read(loader, "phi4flash_decode_step_roofline", obs) \
        == pytest.approx(100 * nbytes / 819e9 * 100 / busy)
    attn = reads + 32 * cost["attn_io_bytes_per_frame"]
    assert _read(loader, "phi4flash_decode_attention_roofline", obs) \
        == pytest.approx(100 * attn / 819e9 * 100 / 1.5)
    assert _read(loader, "phi4flash_mlp_roofline", obs) == pytest.approx(
        100 * cost["dense_mlp_bytes"] / 819e9 * 100 / 1.0)
    # nothing to read: no trace, no counters, no peaks (the parent, a CPU)
    for name in ROOFLINES:
        assert _read(loader, name, dict(obs, trace=None)) is None
        assert _read(loader, name, dict(obs, window={})) is None
        assert _read(loader, name, dict(obs, peaks=None)) is None


def test_preflight_fails_a_refused_shape_on_the_chip_only(loader, cfg,
                                                          monkeypatch):
    """On the chip a shape a kernel refuses ends the run before weights
    are made; the cell's own shapes are taken; a CPU rehearsal (nothing
    timed) is let through."""
    from benchmark import BenchmarkError
    from nnstreamer_tpu.ops import kernels

    glue = loader.module("models", cfg["model"])
    odd = dict(cfg, num_attention_heads=80, num_key_value_heads=40)
    glue.preflight(odd)                               # the CPU: no question
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    glue.preflight(cfg)
    with pytest.raises(BenchmarkError, match="whole lanes"):
        glue.preflight(odd)                           # heads of 32
    with pytest.raises(BenchmarkError, match="whole tiles"):
        glue.preflight(dict(cfg, mamba_d_state=12))


# -- the toy twin, end to end ---------------------------------------------------------


def _add_toy_cell(root: str) -> str:
    """The toy root of the other tests plus a twin of the new cell: the
    configuration's structure at hidden 512 (eight layers: three Mamba-1
    mixers of 1,024 channels, two rings of a window of 32, the cache's
    owner, a gated memory unit and a cross layer; heads of 64, so the
    paired rows are 128 wide and both GQA kernels and the scan kernel
    take the shapes, interpreted), the cell's own two launch lines, a
    ring of 6 steps of 4 streams on prompts of 70-122 tokens."""
    toyroot.build(root)
    bench = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(toyroot.DATA, TOY_CONFIG + ".json"),
                os.path.join(bench, "configs", TOY_CONFIG + ".json"))
    shutil.copy(os.path.join(toyroot.DATA, "toy_cached.json"),
                os.path.join(bench, "traffic", "toy_cached.json"))
    with open(os.path.join(bench, "workloads", CELL + ".json")) as f:
        work = json.load(f)
    work.update(name=TOY, config=TOY_CONFIG, traffic="toy_cached")
    with open(os.path.join(bench, "workloads", TOY + ".json"), "w") as f:
        json.dump(work, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(toyroot.DATA, TOY_CONFIG + ".json")) as f:
        toy_cfg = json.load(f)
    manifest["configs"].append({
        "name": TOY_CONFIG, "source": toy_cfg["source"],
        "file": f"benchmark/configs/{TOY_CONFIG}.json",
        "reduced": toy_cfg["reduced"], "why": "toy"})
    manifest["workloads"].append({
        "name": TOY, "config": TOY_CONFIG, "traffic": "toy_cached",
        "chips": 1, "why": "toy"})
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"] = [TOY]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return root


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = _add_toy_cell(str(tmp_path_factory.mktemp("phi4flash_root")))
    details: dict = {}
    line = run_cell(TOY, SEED, 0.6, True, root=root, rehearsal=True,
                    details=details)
    return root, line, details


def test_toy_twin_runs_end_to_end_and_is_correct(traced):
    _root, line, details = traced
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 4 == 0
    compared = line["compared"]
    assert set(compared) == {
        "logits_rel_l2_lower_median", "logits_rel_l2_worst",
        "greedy_mismatch", "order_errors"}
    assert 0 < compared["logits_rel_l2_lower_median"]["value"] < 0.02
    assert compared["greedy_mismatch"]["value"] == 0
    assert compared["order_errors"]["value"] == 0
    cfg = details["cfg"]
    assert cfg["reduced"] == [] and cut_faults(cfg, []) == []
    assert len(details["frames"]) == 2
    obs = details["obs"]
    assert obs["out_bytes_per_frame"] == 64 * 4 + 4
    assert obs["window"]["compiles"] == 0
    assert obs["window"]["xla_compiles"] == 0
    state = obs["window"]["state"]
    assert state["steps"] > 6 and state["position_faults"] == 0
    # every pass of the ring of 6 begins with 4 restores
    assert state["restores"] >= 4 * (state["steps"] // 6)
    # three Mamba-1 layers; a row is a stream's state and conv inputs
    row = 16 * 1024 * 4 + 3 * 1024 * 2
    assert state["ssm_bytes"] == state["ssm_rows"] * 2 * row * 3
    assert state["ssm_rows"] == state["steps"] * 4
    # a row of 2 pairs x 128 x K and V x bf16; the one cache has TWO
    # readers in the toy (its writer and one cross layer), the rings two
    assert state["shared_kv_bytes_read"] \
        == state["shared_rows_read"] * 1024 * 2
    assert state["ring_kv_bytes_read"] == state["ring_rows_read"] * 1024 * 2
    assert state["cache_bytes_read"] == state["kv_bytes_read"] \
        == state["shared_kv_bytes_read"] + state["ring_kv_bytes_read"]
    # a window of 32: every stream is past it
    assert state["ring_rows_read"] == state["steps"] * 4 * 32
    # the toy's caches and rings are one lattice cell of 128 rows
    assert state["shared_rows_fetched"] == state["steps"] * 4 * 128
    # the prefill ran before the window
    assert state.get("prefill_tokens", 0) == 0


@pytest.mark.parametrize("metric", [
    "program_ms_per_window", "host_ms_per_window",
    "fence_wait_ms_per_window", "place_ms_per_window",
    "reshard_bytes_per_frame", "slow_host_ms", "program_load_s",
    "staging_s", "trace_lower_s"] + sorted(NEW_METRICS - ROOFLINES))
def test_toy_twin_reads_every_per_layer_metric(traced, metric):
    """Metrics without a ``workloads`` list and the new ones (but the
    roofline shares: a CPU has no peak) each read a number in the cell's
    traced run."""
    _root, line, details = traced
    assert metric in line["metrics"], sorted(line["metrics"])
    value = line["metrics"][metric]["value"]
    assert np.isfinite(value) and value >= 0
    if metric in NEW_METRICS - {"phi4flash_unattributed_ms_per_window"}:
        assert value > 0
    if metric == "phi4flash_ssm_state_bytes_per_frame":
        assert value == 3 * 2 * (16 * 1024 * 4 + 3 * 1024 * 2)
    if metric == "phi4flash_restores_per_window":
        assert 0.5 < value <= 1.0              # 4 in every 6 steps
    if metric == "phi4flash_cross_tokens_share":
        # one token a chunk: chunks over prompt tokens, about 1 / 32
        inputs = Loader(_root).module("inputs", details["cfg"]["inputs"])
        chunks = inputs.prefill_chunks(details["cfg"], SEED)
        tokens = sum(int(c[3][0]) for c in chunks)
        assert value == len(chunks) / tokens and 1 / 32 <= value < 1 / 24


def test_toy_twin_stage_metrics_cover_the_program(traced):
    _root, line, details = traced
    m = line["metrics"]
    parts = sum(m[k]["value"] for k in STAGE_METRICS)
    assert parts > 0 and m["program_ms_per_window"]["value"] > 0
    stages = details["obs"]["trace"]["stage_s"]
    covered = sum(stages.values()) / details["obs"]["trace"]["windows"] * 1e3
    assert parts == pytest.approx(covered, rel=1e-6), sorted(stages)
    for scope in ("layer00/mamba/in_proj", "layer04/mamba/step",
                  "layer01/attn_window/qkv", "layer05/attn_full/o",
                  "layer06/gmu", "layer07/attn_cross/diff", "layer07/mlp",
                  "nns.model/head"):
        assert any(scope in s for s in stages), (scope, sorted(stages))
    for kind in ("attn_window", "attn_full", "attn_cross"):
        assert any(s.endswith(f"/{kind}/gqa_decode_attention")
                   for s in stages), kind
    # no layer above the cache's writer writes a row
    assert not any("attn_cross/cache_write" in s for s in stages)
    assert not ROOFLINES & set(m)                    # a CPU has no peak


def _no_restore(monkeypatch):
    """A book that restores no stream: each pass goes on from where the
    last one left the recurrent states (and says nothing of it)."""
    from nnstreamer_tpu.models import streams

    real = streams.book_step

    def book_step(state, positions, room=None):
        restore, fault, out = real(state, positions, room)
        return restore & False, fault & False, out

    monkeypatch.setattr(streams, "book_step", book_step)


def _stale_memory(monkeypatch):
    """The gated memory unit reads another stream's memory: what a
    buffer kept from another step holds."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models import phi4_flash

    real = phi4_flash._gmu
    monkeypatch.setattr(phi4_flash, "_gmu", lambda p, u, m: real(
        p, u, jnp.roll(m, 1, axis=0) if m.shape[0] > 1 else m))


def _cross_reads_few_rows(monkeypatch):
    """The cross layer's decode step sees the last 32 positions of the
    cache only, as if it read a ring."""
    from nnstreamer_tpu.models import attention

    real, write = attention.attend_step, attention.write_step
    wrote = []

    def write_step(k, v, cache, positions):
        wrote.append(None)
        return write(k, v, cache, positions)

    def attend_step(q, cache, positions, window, scale):
        # the writer's own attention follows its write; a cross layer's
        # does not
        own = bool(wrote) and wrote.pop() is None
        if not own and q.shape[0] > 1:
            window = 32
        return real(q, cache, positions, window, scale)

    monkeypatch.setattr(attention, "write_step", write_step)
    monkeypatch.setattr(attention, "attend_step", attend_step)


def _no_lambda(monkeypatch):
    from nnstreamer_tpu.models import phi4_flash

    monkeypatch.setattr(phi4_flash, "_lambda", lambda cfg, p, layer: 0.0)


@pytest.mark.parametrize("fault", [_no_restore, _stale_memory,
                                   _cross_reads_few_rows, _no_lambda],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_check_fails_a_faulty_program(traced, monkeypatch, fault):
    """The toy cell run again on a program with one fault.  Each must
    come out not correct, by the limit half the sample may not pass."""
    from nnstreamer_tpu.models import streams

    root, sound, _details = traced
    streams.entries.cache_clear()      # the filter keys a program by these
    fault(monkeypatch)
    try:
        line = run_cell(TOY, SEED, 0.4, False, root=root, rehearsal=True)
    finally:
        monkeypatch.undo()
        streams.entries.cache_clear()
    assert line["correct"] is False
    got = line["compared"]["logits_rel_l2_lower_median"]
    assert got["value"] > got["limit"], line["compared"]
    assert got["value"] > 3 * sound["compared"][
        "logits_rel_l2_lower_median"]["value"]
    assert sound["correct"] is True


def test_the_reference_tells_its_own_faults(traced):
    """The reference with one of its named faults, against itself sound,
    on the run's own sampled frames: each reads over the limit."""
    root, _line, details = traced
    cfg = details["cfg"]
    loader = Loader(root)
    reference = loader.module("reference", cfg["reference"])
    inputs = loader.module("inputs", cfg["inputs"])
    frames = details["frames"]
    where = inputs.locate(cfg, SEED, frames[0], frames[1])
    histories = [inputs.history(cfg, SEED, j, r) for j, r in where]
    sound = reference.forward_last(cfg, SEED, histories)
    limit = cfg["limits"]["logits_rel_l2_lower_median"]
    for fault in ("stale_memory", "cross_reads_window", "no_lambda"):
        got = reference.forward_last(cfg, SEED, histories, faults=(fault,))
        each = np.linalg.norm(got - sound, axis=-1) \
            / np.linalg.norm(sound, axis=-1)
        assert np.sort(each)[(len(each) - 1) // 2] > limit, (fault, each)


def test_toy_twin_control_fails(traced):
    """The reference computed in float8_e4m3fn, the nearest precision
    below the configuration's bfloat16, on the run's own sampled frames:
    it has to fail the cell's numbers."""
    root, _line, details = traced
    cfg = details["cfg"]
    reference = Loader(root).module("reference", cfg["reference"])
    numbers = reference.control(cfg, SEED, details["frames"])
    sound = {n["name"]: n["value"] for n in details["numbers"]}
    failed = [n["name"] for n in numbers if n["value"] > n["limit"]]
    assert "logits_rel_l2_lower_median" in failed, numbers
    for n in numbers:
        if n["name"] in failed:
            assert n["value"] > 3 * sound[n["name"]]


def test_a_program_without_the_model_fails_at_once(traced, monkeypatch):
    """What the parent commit does with this cell: the glue's preflight
    raises ``ImportError`` before any weight is made, and the command
    turns that into exit code 1."""
    root, _line, _details = traced
    import nnstreamer_tpu.models as models_pkg
    from benchmark import run as harness

    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.models.phi4_flash", None)
    monkeypatch.delattr(models_pkg, "phi4_flash", raising=False)
    with pytest.raises(ImportError):
        run_cell(TOY, SEED, 0.3, False, root=root, rehearsal=True)
    monkeypatch.setattr(harness, "run_cell",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            ImportError("no phi4_flash")))
    assert harness.main(["--workload", TOY, "--seed", "1", "--seconds",
                         "1"]) == 1
