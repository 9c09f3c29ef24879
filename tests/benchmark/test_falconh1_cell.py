"""The cached-decode cell of the Falcon-H1-34B stage on the CPU: its
files resolve and hold together, its cost functions agree with a count
by hand, its readers do their arithmetic on made-up observations, and a
toy twin of the cell runs end to end through ``run_cell(...,
rehearsal=True)``: two ``tensor_filter`` lines on one state in which
every layer holds a recurrent state, its snapshot and a K/V cache,
prefill in set-up (padded last chunks that say their count), the window
(every pass of the ring a rewind to the prompts' ends), the reference,
and the check failing the snapshot not restored, one layer's K/V rows
written a position late, ``m_ao`` dropped and the float8 control.  No
number here is a rate."""

import dataclasses
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
CELL, CONFIG = "falconh1.decode4k", "falcon_h1_34b_stage4_vocab8"
TOY, TOY_CONFIG = "toy_falconh1.decode", "toy_falconh1"

STAGE_METRICS = {"falconh1_ssm_ms_per_window", "falconh1_attn_ms_per_window",
                 "falconh1_mlp_ms_per_window", "falconh1_head_ms_per_window",
                 "falconh1_unattributed_ms_per_window"}
ROOFLINES = {"falconh1_decode_step_roofline",
             "falconh1_ssm_state_update_roofline",
             "falconh1_decode_attention_roofline", "falconh1_mlp_roofline"}
COUNTER_METRICS = {"falconh1_ssm_state_bytes_per_frame",
                   "falconh1_kv_bytes_per_frame",
                   "falconh1_kv_bytes_fetched_per_frame",
                   "falconh1_restores_per_window"}
NEW_METRICS = STAGE_METRICS | ROOFLINES | COUNTER_METRICS \
    | {"falconh1_prefill_s"}


@pytest.fixture(scope="module")
def loader():
    return Loader(REPO)


@pytest.fixture(scope="module")
def cfg(loader):
    return loader.config(CONFIG)


# -- the files ------------------------------------------------------------------------

CUT = {"num_hidden_layers": 4, "vocab_size": 32640}


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")


def test_the_stated_cut_holds_together(loader, cfg):
    entry = loader.entry("configs", CONFIG)
    assert entry["reduced"] == list(CUT)
    assert cut_faults(cfg, entry["reduced"]) == []
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["vocab_size"]) \
        == (72, 261120)
    for key, value in published.items():
        assert cfg[key] == CUT.get(key, value), key
    # every width, head count and multiplier as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (5120, 21504, 20, 4, 128)
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_ssm"],
            cfg["mamba_n_groups"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"]) == (32, 128, 4096, 2, 256, 4)
    assert cfg["ssm_multipliers"] == pytest.approx(
        [0.35355, 0.25, 0.17678, 0.5, 0.35355], rel=1e-4)
    assert (cfg["ssm_in_multiplier"], cfg["lm_head_multiplier"],
            cfg["attention_out_multiplier"]) == (0.25, 0.0078125, 0.0375)
    assert cfg["deployment"]["chips_per_layer"] == 1
    for words in ("16 stages", "over 8 of the chips", "micro-batches of 8"):
        assert words in cfg["deployment"]["this_chip"], words
    assert cfg["share"] == {"vocab0": 0}
    # the guide's floors: four layers (a period is one layer, none is a
    # leading dense one) and an eighth of the vocabulary, in whole lanes
    assert cfg["num_hidden_layers"] >= 4
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["vocab_size"] % 128 == 0
    assert entry["source"] == cfg["source"] and entry["source"].endswith(
        "tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json")
    for key in ("assumed", "serving", "limits", "limits_why", "init"):
        assert cfg[key], key
    # what the config leaves open is under `assumed`, with its alternative
    assumed = " ".join(cfg["assumed"])
    for words in ("mamba_d_ssm = 4,096", "[z | x | B | C | dt]",
                  "not over z", "contiguous blocks", "float32",
                  "log-uniform in 0.001-0.1", "half-split", "silu",
                  "attn_layer_indices null", "forced from the ring"):
        assert words in assumed, words
    assert assumed.count("alternative") >= 8
    serving = cfg["serving"]
    assert (serving["streams"], serving["prompt_tokens"],
            serving["answer_tokens"], serving["prefill_chunk"]) \
        == (128, [2048, 3840], 256, 2048)
    assert cfg["inputs"] == "nemotron3_nano_share8"    # accepted, unchanged
    # the seeded law absorbs the multipliers: nothing vanishes under one
    assert "ABSORBS the multipliers" in cfg["init"]["why"]
    row = _catalog_row()
    if row is not None:
        assert published == row["config"]
        assert cfg["source"] == row["source_url"]


def test_the_cell_runs_on_the_traffic_that_is_there(loader, cfg):
    entry = loader.entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "cached_decode128", 1)
    assert "micro-batch" in entry["why"] and "4 of 72" in entry["why"]
    work = loader.json("workloads", CELL)
    mix = loader.json("traffic", entry["traffic"])
    assert mix == dict(mix, kind="cached_replay", batch=128,
                       ring_buffers=256, sink_depth=4)
    # the launch lines are nemotron3.decode4k's
    theirs = loader.json("workloads", "nemotron3.decode4k")
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
    assert inputs.cache_positions(cfg) == 4096
    lengths = [len(p) for p in inputs.prompts(cfg, SEED)]
    assert min(lengths) >= 2048 and max(lengths) <= 3840
    assert abs(sum(lengths) - 377_000) < 1_500
    ring = inputs.make_ring(cfg, mix, SEED, 256, 128)
    assert all(len(slot) == 2 for slot in ring)
    assert max(int(slot[1].max()) for slot in ring) <= 4095
    assert max(int(slot[0].max()) for slot in ring) < 32640
    # a prefill frame says how many of its ids are real
    chunks = inputs.prefill_chunks(cfg, SEED)
    assert all(len(c) == 4 and c[0].shape == (2048,) for c in chunks)
    assert len(chunks) == sum(-(-n // 2048) for n in lengths)


def test_new_metrics_list_the_new_cell(loader):
    """This PR's metrics are there and list the cell (a later PR may add
    metrics of its own to the cell, or cells to these: neither is pinned
    here), each on a reader the benchmark already had but the share of
    the whole step, whose accepted reader indexes expert counters a
    dense model does not keep."""
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
        assert m["moves"] == ("setup_s" if name == "falconh1_prefill_s"
                              else "fps_per_chip")
        assert m["layer"] in layers            # a layer PERF.md has
        assert (m["unit"] == "%") == (name in ROOFLINES)
    assert set(readers.values()) == {
        "stage_ms_per_window", "state_counter_ratio", "setup_span_s",
        "dense_decode_step_roofline", "gqa_decode_attention_roofline",
        "stage_roofline"}
    assert readers["falconh1_decode_step_roofline"] \
        == "dense_decode_step_roofline"
    assert readers["falconh1_decode_attention_roofline"] \
        == "gqa_decode_attention_roofline"
    assert CELL in [w["name"] for w in loader.manifest["workloads"]]
    assert CONFIG in [c["name"] for c in loader.manifest["configs"]]


# -- costs against a count by hand ---------------------------------------------------


def test_costs_against_a_hand_count(loader, cfg):
    """The numbers the issue reckoned the cut with, recounted."""
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    attn = 5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120
    assert round(attn / 1e6, 2) == 31.46
    mamba = 5120 * (4096 + 5120 + 32) + 4096 * 5120
    mamba_vectors = 4 * 5120 + 5120 + 3 * 32 + 4096
    assert round((mamba + mamba_vectors) / 1e6, 2) == 68.35
    mlp = 3 * 5120 * 21504
    assert round(mlp / 1e6, 2) == 330.30
    layer = attn + mamba + mamba_vectors + mlp + 2 * 5120
    assert round(layer / 1e6, 2) == 430.12
    ends = 2 * 32640 * 5120
    assert round((4 * layer * 2 + ends * 2) / 1e9, 2) == 4.11
    matrices = 4 * (attn + mamba + mlp) + 32640 * 5120
    vectors = 4 * (mamba_vectors + 2 * 5120) + 5120
    assert cost["weight_bytes"] == matrices * 2 + vectors * 4
    assert round(cost["weight_bytes"] / 1e9, 2) == 3.78      # 3.44 + 0.33
    assert cost["mamba_weight_bytes"] == 4 * (mamba * 2 + mamba_vectors * 4)
    assert round(cost["mamba_weight_bytes"] / 1e9, 2) == 0.55
    assert cost["dense_mlp_bytes"] == 4 * mlp * 2
    assert round(cost["dense_mlp_bytes"] / 1e9, 2) == 2.64
    # a token's K and V of one layer; a stream's recurrent state and
    # convolution inputs of one layer, read and written
    assert cost["cache_row_bytes"] == 2 * 4 * 128 * 2 == 2048
    state = 32 * 128 * 256 * 4
    assert round(state / 1e6, 2) == 4.19
    assert cost["ssm_row_bytes"] == 2 * (state + 3 * 5120 * 2)
    assert cost["in_bytes_per_frame"] == 8 + 5120 * 2 + 4 * 2048 \
        + 4 * cost["ssm_row_bytes"]
    assert cost["out_bytes_per_frame"] == 32640 * 4 + 4
    assert cost["flops_per_frame"] == 2 * matrices + 4 * 5 * 32 * 128 * 256
    # the PUBLISHED five heads a group, not the tile of 16 that holds them
    assert cost["flops_per_cache_row"] == 2 * 20 * 2 * 128
    assert cost["attn_io_bytes_per_frame"] == 4 * 20 * (128 * 2 + 128 * 4)
    assert cost["layers"] == 4
    assert not {"expert_bytes", "flops_per_expert_hit"} & set(cost)
    # what the state holds, as the issue reckoned it
    held = 4 * 128 * (2 * (state + 3 * 5120 * 2) + 4096 * 2048)
    assert round(held / 1e9, 2) == 8.62
    # a step's compulsory bytes at the mean position (3.07 k): 11.3 GB,
    # two thirds of it the two mixers' state
    rows = 128 * 3072 * 2048 * 4
    step = cost["weight_bytes"] + rows \
        + 128 * (cost["in_bytes_per_frame"] + cost["out_bytes_per_frame"])
    assert round(step / 1e9, 1) == 11.3
    assert round(100 * (rows + 128 * 4 * cost["ssm_row_bytes"]) / step) == 67


# -- the readers' arithmetic ----------------------------------------------------------


def _obs(loader, cfg, state):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    return {"batch": 128, "cost": cost, "window": {"state": state},
            "peaks": {"peak_flops_bf16": 197e12,
                      "peak_hbm_bytes_per_s": 819e9},
            "trace": {"windows": 200.0, "program_busy_s": 3.6,
                      "stage_s": {
                          "nns.model/layer00/norm": 0.01,
                          "nns.model/layer00/mamba/in_proj": 0.1,
                          "nns.model/layer00/mamba/conv": 0.02,
                          "nns.model/layer00/mamba/step": 1.1,
                          "nns.model/layer00/mamba/gate_norm": 0.02,
                          "nns.model/layer00/mamba/out_proj": 0.06,
                          "nns.model/layer00/attn/qkv": 0.05,
                          "nns.model/layer00/attn/cache_write": 0.01,
                          "nns.model/layer00/attn/"
                          "gqa_decode_attention": 0.9,
                          "nns.model/layer00/attn/o": 0.04,
                          "nns.model/layer00/mix": 0.01,
                          "nns.model/layer00/mlp": 1.0,
                          "nns.model/head": 0.1, "nns.model/state": 0.01,
                          "nns.model/embed": 0.02,
                          "(no nns scope)": 0.01}}}


def _state(cost, steps=1000):
    rows = 128 * 3072
    return {"steps": steps, "ssm_rows": steps * 128,
            "kv_rows_read": steps * rows,
            "kv_rows_fetched": steps * 128 * 3136,
            "ssm_bytes": steps * 128 * 4 * cost["ssm_row_bytes"],
            "kv_bytes_read": steps * rows * 2048 * 4,
            "cache_bytes_read": steps * rows * 2048 * 4,
            "kv_bytes_fetched": steps * 128 * 3136 * 2048 * 4,
            "cache_bytes_fetched": steps * 128 * 3136 * 2048 * 4,
            "restores": steps // 2, "position_faults": 0}


def test_counter_readers(loader, cfg):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    obs = _obs(loader, cfg, _state(cost))
    got = {}
    for name in COUNTER_METRICS:
        spec = loader.json("layer_metrics", name)
        got[name] = loader.module("readers", spec["reader"]).read(
            obs, **spec["args"])
    # FOUR layers of each kind: 4 x (2 x 4.19 MB + the convolution's)
    assert got["falconh1_ssm_state_bytes_per_frame"] \
        == 4 * 2 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    assert got["falconh1_kv_bytes_per_frame"] == 3072 * 2048 * 4
    assert got["falconh1_kv_bytes_fetched_per_frame"] == 3136 * 2048 * 4
    assert got["falconh1_restores_per_window"] == 0.5
    ratio = loader.module("readers", "state_counter_ratio").read
    for name in COUNTER_METRICS:       # a program without the counters
        args = loader.json("layer_metrics", name)["args"]
        assert ratio({"window": {}}, **args) is None


def test_stage_and_roofline_readers_count_what_they_say(loader, cfg):
    stage = loader.module("readers", "stage_ms_per_window").read
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    state = _state(cost)
    obs = _obs(loader, cfg, state)
    want = {"falconh1_ssm_ms_per_window": 1.3,
            "falconh1_attn_ms_per_window": 1.0,
            "falconh1_mlp_ms_per_window": 1.0,
            "falconh1_head_ms_per_window": 0.1,
            "falconh1_unattributed_ms_per_window": 0.06}
    assert set(want) == STAGE_METRICS
    for name, seconds in want.items():
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "stage_ms_per_window"
        assert stage(obs, **spec["args"]) == pytest.approx(
            seconds / 200 * 1e3), name
    # the five stage metrics cover every stage once
    assert sum(want.values()) == pytest.approx(
        sum(obs["trace"]["stage_s"].values()))

    def read(name, obs=obs):
        spec = loader.json("layer_metrics", name)
        return loader.module("readers", spec["reader"]).read(
            obs, **spec["args"])

    rows = state["cache_bytes_read"] / 1000
    nbytes = cost["weight_bytes"] + rows \
        + 128 * (cost["in_bytes_per_frame"] + cost["out_bytes_per_frame"])
    assert 11.2e9 < nbytes < 11.4e9
    assert read("falconh1_decode_step_roofline") == pytest.approx(
        100 * nbytes / 819e9 * 200 / 3.6)
    # the accepted reader's floor without its expert terms: the same share
    theirs = loader.module("readers", "decode_step_roofline").read(dict(
        obs, cost=dict(cost, expert_bytes=0.0, flops_per_expert_hit=0.0),
        window={"state": dict(state, experts_touched=0, expert_hits=0)}))
    assert read("falconh1_decode_step_roofline") == pytest.approx(theirs)
    ssm = cost["mamba_weight_bytes"] + 128 * 4 * cost["ssm_row_bytes"]
    assert read("falconh1_ssm_state_update_roofline") == pytest.approx(
        100 * ssm / 819e9 * 200 / 1.3)
    attn = rows + 128 * cost["attn_io_bytes_per_frame"]
    assert read("falconh1_decode_attention_roofline") == pytest.approx(
        100 * attn / 819e9 * 200 / 0.9)
    assert read("falconh1_mlp_roofline") == pytest.approx(
        100 * cost["dense_mlp_bytes"] / 819e9 * 200 / 1.0)
    # nothing to read: no trace, no counters, no peaks (the parent, a CPU)
    for name in ROOFLINES:
        assert read(name, dict(obs, trace=None)) is None
        assert read(name, dict(obs, window={})) is None
        assert read(name, dict(obs, peaks=None)) is None
    # a program that keeps other counters (an accepted model's) and no
    # `cache_bytes_read`: the new reader returns nothing and does not raise
    assert read("falconh1_decode_step_roofline",
                dict(obs, window={"state": {"steps": 5}})) is None


def test_preflight_fails_a_refused_shape_on_the_chip_only(loader, cfg,
                                                          monkeypatch):
    """On the chip a state the step's kernel refuses ends the run before
    weights are made; the cell's own shape is taken; a CPU rehearsal
    (nothing timed) is let through."""
    from benchmark import BenchmarkError
    from nnstreamer_tpu.ops import kernels

    glue = loader.module("models", cfg["model"])
    glue.preflight(dict(cfg, mamba_d_state=80))       # the CPU: no question
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    glue.preflight(cfg)
    with pytest.raises(BenchmarkError, match="whole lanes"):
        glue.preflight(dict(cfg, mamba_d_state=80))
    with pytest.raises(BenchmarkError, match="32 MiB"):
        glue.preflight(dict(cfg, mamba_d_state=4096))


# -- the toy twin, end to end ---------------------------------------------------------


def _add_toy_cell(root: str) -> str:
    """The toy root of the other tests plus a twin of the new cell: the
    configuration's structure at hidden 128 (two layers, each a Mamba-2
    mixer of 4 heads of 64 with a state of 128 beside 10 query heads
    over 2 key/value heads of 128: both decode kernels take the shapes,
    interpreted), the cell's own two launch lines, a ring of 6 steps of
    4 streams on prompts of 70-122 tokens."""
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
    root = _add_toy_cell(str(tmp_path_factory.mktemp("falconh1_root")))
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
    assert cut_faults(cfg, cfg["reduced"]) == []
    assert len(details["frames"]) == 2
    obs = details["obs"]
    assert obs["out_bytes_per_frame"] == 64 * 4 + 4
    assert obs["window"]["compiles"] == 0
    assert obs["window"]["xla_compiles"] == 0
    state = obs["window"]["state"]
    assert state["steps"] > 6 and state["position_faults"] == 0
    # every pass of the ring of 6 begins with 4 restores
    assert state["restores"] >= 4 * (state["steps"] // 6)
    # two layers, each counted in BOTH kinds
    row = 4 * 64 * 128 * 4 + 3 * 768 * 2
    assert state["ssm_bytes"] == state["ssm_rows"] * 2 * row * 2
    assert state["ssm_rows"] == state["steps"] * 4
    assert state["kv_bytes_read"] == state["cache_bytes_read"] \
        == state["kv_rows_read"] * 2 * 2 * 128 * 2 * 2
    # the toy's caches are one lattice cell of 128 rows
    assert state["kv_rows_fetched"] == state["steps"] * 4 * 128
    assert not {"experts_touched", "expert_hits"} & set(state)


@pytest.mark.parametrize("metric", [
    "program_ms_per_window", "host_ms_per_window",
    "fence_wait_ms_per_window", "place_ms_per_window",
    "reshard_bytes_per_frame", "slow_host_ms", "program_load_s",
    "staging_s", "trace_lower_s"] + sorted(NEW_METRICS - ROOFLINES))
def test_toy_twin_reads_every_per_layer_metric(traced, metric):
    """Metrics without a ``workloads`` list and the new ones (but the
    roofline shares: a CPU has no peak) each read a number in the cell's
    traced run."""
    _root, line, _details = traced
    assert metric in line["metrics"], sorted(line["metrics"])
    value = line["metrics"][metric]["value"]
    assert np.isfinite(value) and value >= 0
    if metric in NEW_METRICS - {"falconh1_unattributed_ms_per_window"}:
        assert value > 0
    if metric == "falconh1_ssm_state_bytes_per_frame":
        assert value == 2 * 2 * (4 * 64 * 128 * 4 + 3 * 768 * 2)
    if metric == "falconh1_restores_per_window":
        assert 0.5 < value <= 1.0              # 4 in every 6 steps


def test_toy_twin_stage_metrics_cover_the_program(traced):
    _root, line, details = traced
    m = line["metrics"]
    parts = sum(m[k]["value"] for k in STAGE_METRICS)
    # every stage once (the CPU's thread-pool lines stand in for a device
    # plane here and run side by side, so how the stages compare with the
    # program's busy time says nothing)
    assert parts > 0 and m["program_ms_per_window"]["value"] > 0
    stages = details["obs"]["trace"]["stage_s"]
    covered = sum(stages.values()) / details["obs"]["trace"]["windows"] * 1e3
    assert parts == pytest.approx(covered, rel=1e-6), sorted(stages)
    for scope in ("layer00/mamba/in_proj", "layer01/mamba/step",
                  "layer00/attn/qkv", "layer01/attn/o", "layer01/mlp",
                  "nns.model/head"):
        assert any(scope in s for s in stages), (scope, sorted(stages))
    assert any(s.endswith("/attn/gqa_decode_attention") for s in stages)
    assert not any("ssm_restore" in s for s in stages)   # the step's kernel
    assert not ROOFLINES & set(m)                    # a CPU has no peak


def _no_restore(monkeypatch):
    """A book that restores no stream: each pass goes on from where the
    last one left the recurrent state (and says nothing of it)."""
    from nnstreamer_tpu.models import streams

    real = streams.book_step

    def book_step(state, positions, room=None):
        restore, fault, out = real(state, positions, room)
        return restore & False, fault & False, out

    monkeypatch.setattr(streams, "book_step", book_step)


def _kv_late(monkeypatch):
    """The second layer's decode step runs one position late: it writes
    its K and V row at ``position + 1``, rotated and masked to match.
    (A row moved alone reads mild, since attention does not care which
    row a rotated key lies in: a step then only misses its own token,
    and half the sample stays within the limit.)"""
    from nnstreamer_tpu.models import falcon_h1

    real, calls = falcon_h1.attn_decode, []

    def attn_decode(cfg, p, z, cache, positions):
        calls.append(None)
        return real(cfg, p, z, cache, positions + (len(calls) % 2 == 0))

    monkeypatch.setattr(falcon_h1, "attn_decode", attn_decode)


def _no_m_ao(monkeypatch):
    from nnstreamer_tpu.models import falcon_h1

    real = falcon_h1.FalconH1Config.from_dict.__func__

    def from_dict(cls, raw):
        return dataclasses.replace(real(cls, raw),
                                   attention_out_multiplier=1.0)

    monkeypatch.setattr(falcon_h1.FalconH1Config, "from_dict",
                        classmethod(from_dict))


@pytest.mark.parametrize("fault", [_no_restore, _kv_late, _no_m_ao],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_check_fails_a_faulty_program(traced, monkeypatch, fault):
    """The toy cell run again on a program with one fault: the snapshot
    not restored at a pass's first step, one layer's K/V rows written a
    position late, ``m_ao`` dropped.  Each must come out not correct, by
    the limit half the sample may not pass."""
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

    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.models.falcon_h1", None)
    monkeypatch.delattr(models_pkg, "falcon_h1", raising=False)
    with pytest.raises(ImportError):
        run_cell(TOY, SEED, 0.3, False, root=root, rehearsal=True)
    monkeypatch.setattr(harness, "run_cell",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            ImportError("no falcon_h1")))
    assert harness.main(["--workload", TOY, "--seed", "1", "--seconds",
                         "1"]) == 1
