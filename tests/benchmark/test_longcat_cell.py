"""The cached-decode cell of the LongCat-Flash share on the CPU: its files
resolve and hold together, its cost functions agree with a count by
hand, its readers do their arithmetic on made-up observations, and a toy
twin of the cell runs end to end through ``run_cell(...,
rehearsal=True)``: two ``tensor_filter`` lines on one state of two
latent caches a layer, prefill in set-up, the window (every pass of the
ring a rewind to the prompts' ends), the reference, and the check
failing the held experts' part dropped, the zero-compute picks' term
dropped, sub-block 1 reading sub-block 0's cache and the float8 control.
No number here is a rate."""

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
CELL, CONFIG = "longcat.decode4k", "longcat_flash_omni_share64"
TOY, TOY_CONFIG = "toy_longcat.decode", "toy_longcat"

STAGE_METRICS = {"longcat_attn_ms_per_window",
                 "longcat_dense_mlp_ms_per_window",
                 "longcat_experts_ms_per_window",
                 "longcat_route_ms_per_window", "longcat_head_ms_per_window",
                 "longcat_unattributed_ms_per_window"}
ROOFLINES = {"longcat_decode_step_roofline",
             "longcat_latent_attention_roofline", "longcat_experts_roofline",
             "longcat_dense_mlp_roofline"}
COUNTER_METRICS = {"longcat_cache_bytes_per_frame",
                   "longcat_cache_bytes_fetched_per_frame",
                   "longcat_experts_touched_share", "zero_expert_pick_share",
                   "held_expert_pick_share"}
NEW_METRICS = STAGE_METRICS | ROOFLINES | COUNTER_METRICS \
    | {"longcat_prefill_s"}


@pytest.fixture(scope="module")
def loader():
    return Loader(REPO)


@pytest.fixture(scope="module")
def cfg(loader):
    return loader.config(CONFIG)


# -- the files ------------------------------------------------------------------------

CUT = {"num_layers": 4, "n_routed_experts": 8, "vocab_size": 16384}


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "LongCat-Flash-Omni")


def test_the_stated_cut_holds_together(loader, cfg):
    entry = loader.entry("configs", CONFIG)
    assert entry["reduced"] == list(CUT)
    assert cut_faults(cfg, entry["reduced"]) == []
    published = cfg["published"]
    assert (published["num_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (28, 512, 131072)
    for key, value in published.items():
        assert cfg[key] == CUT.get(key, value), key
    # every width as published; the router stays 768 wide
    assert (cfg["hidden_size"], cfg["ffn_hidden_size"],
            cfg["expert_ffn_hidden_size"], cfg["num_attention_heads"]) \
        == (6144, 12288, 2048, 64)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) \
        == (1536, 512, 128, 64, 128)
    assert (cfg["moe_topk"], cfg["zero_expert_num"],
            cfg["routed_scaling_factor"]) == (12, 256, 6)
    assert cfg["deployment"]["chips_per_layer"] == 64
    assert cfg["share"] == {"expert0": 0, "vocab0": 0}
    # the guide's floors: four layers (a period is one layer, none is a
    # leading dense one), 8 experts, an eighth of the vocabulary
    assert cfg["num_layers"] >= 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= published["vocab_size"]
    assert entry["source"] == cfg["source"] and entry["source"].endswith(
        "meituan-longcat/LongCat-Flash-Omni/blob/main/config.json")
    for key in ("assumed", "serving", "limits", "limits_why", "init",
                "scope"):
        assert cfg[key], key
    # what the config leaves open is under `assumed`, with its alternative
    assumed = " ".join(cfg["assumed"])
    for words in ("rejoining the stream at the layer's end",
                  "NOT renormalised", "NORMED low-rank streams", "silu",
                  "half-split", "not tied", "float32",
                  "correction bias b is zero", "multi-token-prediction",
                  "forced from the ring"):
        assert words in assumed, words
    assert assumed.count("alternative") >= 6
    serving = cfg["serving"]
    assert (serving["streams"], serving["prompt_tokens"],
            serving["answer_tokens"], serving["prefill_chunk"]) \
        == (128, [2048, 3840], 256, 2048)
    assert cfg["inputs"] == "deepseek_v2_share4"       # accepted, unchanged
    row = _catalog_row()
    if row is not None:
        assert published == row["config"]
        assert cfg["source"] == row["source_url"]


def test_the_cell_runs_on_the_traffic_that_is_there(loader, cfg):
    entry = loader.entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "cached_decode128", 1)
    work = loader.json("workloads", CELL)
    mix = loader.json("traffic", entry["traffic"])
    assert mix == dict(mix, kind="cached_replay", batch=128,
                       ring_buffers=256, sink_depth=4)
    # the launch lines are dsv2.decode16k's
    theirs = loader.json("workloads", "dsv2.decode16k")
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
    assert max(int(slot[0].max()) for slot in ring) < 16384
    chunks = inputs.prefill_chunks(cfg, SEED)
    assert all(len(c) == 3 and c[0].shape == (2048,) for c in chunks)
    assert len(chunks) == sum(-(-n // 2048) for n in lengths)


def test_new_metrics_list_the_new_cell(loader):
    """This PR's metrics are there and list the cell (a later PR may add
    metrics of its own to the cell, or cells to these: neither is pinned
    here), each on a reader the benchmark already had."""
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
        assert m["moves"] == ("setup_s" if name == "longcat_prefill_s"
                              else "fps_per_chip")
        assert m["layer"] in layers            # a layer PERF.md has
        assert (m["unit"] == "%") == (name in ROOFLINES)
    # no reader of this PR's own: the accepted ones compute every metric
    assert set(readers.values()) == {
        "stage_ms_per_window", "state_counter_ratio", "setup_span_s",
        "decode_step_roofline", "latent_decode_attention_roofline",
        "stage_roofline"}
    for name in ("zero_expert_pick_share", "held_expert_pick_share"):
        assert loader.json("layer_metrics", name)["args"] == dict(
            counter={"zero_expert_pick_share": "zero_picks",
                     "held_expert_pick_share": "expert_hits"}[name],
            per="frame", of_cost="picks_per_frame")
    assert readers["longcat_decode_step_roofline"] == "decode_step_roofline"
    assert readers["longcat_latent_attention_roofline"] \
        == "latent_decode_attention_roofline"
    assert CELL in [w["name"] for w in loader.manifest["workloads"]]
    assert CONFIG in [c["name"] for c in loader.manifest["configs"]]


# -- costs against a count by hand ---------------------------------------------------


def test_costs_against_a_hand_count(loader, cfg):
    """The numbers the issue reckoned the cut with, recounted."""
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    attn = 6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 \
        + 8192 * 6144
    assert round(attn / 1e6, 1) == 90.6
    mlp = 3 * 6144 * 12288
    assert round(mlp / 1e6, 1) == 226.5
    router = 6144 * 768
    outside = 2 * attn + 2 * mlp + router
    assert round(outside / 1e6, 1) == 638.8
    expert = 3 * 6144 * 2048
    assert round(expert / 1e6, 2) == 37.75
    ends = 2 * 16384 * 6144
    assert round(ends / 1e6, 1) == 201.3
    weights = 4 * (outside + 8 * expert) + ends
    assert round(weights * 2 / 1e9, 2) == 7.93
    vectors = 4 * (4 * 6144 + 2 * (1536 + 512) + 768) + 6144
    # the routed experts go by the slot touched, the embedding by the row
    fixed = 4 * outside + 16384 * 6144
    assert cost["weight_bytes"] == fixed * 2 + vectors * 4
    assert round(cost["weight_bytes"] / 1e9, 2) == 5.31
    assert cost["dense_mlp_bytes"] == 8 * mlp * 2
    assert round(cost["dense_mlp_bytes"] / 1e9, 2) == 3.62
    assert cost["expert_bytes"] == expert * 2 == 75_497_472
    # a row is its 576 values, not the 640 it is stored in
    assert cost["cache_row_bytes"] == 576 * 2
    assert cost["in_bytes_per_frame"] == 8 + 6144 * 2 + 8 * 1152
    assert cost["out_bytes_per_frame"] == 16384 * 4 + 4
    assert cost["expert_slots"] == 32 and cost["expert_layers"] == 4
    assert cost["picks_per_frame"] == 12 * 4
    assert cost["caches"] == 8 and cost["layers"] == 4
    assert cost["flops_per_expert_hit"] == 2 * expert
    assert cost["flops_per_cache_row"] == 2 * 64 * (576 + 512)
    assert cost["attn_io_bytes_per_frame"] == 8 * 64 * (576 * 2 + 512 * 4)
    # the zero-compute picks' term: operations counted, no bytes
    assert cost["flops_per_frame"] == 2 * (fixed + 4 * 6144)
    # what the state holds, as the issue reckoned it
    assert round(128 * 4096 * 1280 * 8 / 1e9, 2) == 5.37
    # a step's compulsory bytes at the mean position (3.07 k), 7.1 of 8
    # experts touched a layer: the issue's 11.1 GB
    rows = 128 * 3072 * 1152 * 8
    step = cost["weight_bytes"] + 4 * 7.1 * cost["expert_bytes"] + rows \
        + 128 * (cost["in_bytes_per_frame"] + cost["out_bytes_per_frame"])
    assert round(step / 1e9, 1) == 11.1
    assert round(100 * rows / step) == 33
    assert round(100 * cost["dense_mlp_bytes"] / step) == 33


# -- the readers' arithmetic ----------------------------------------------------------


def _obs(loader, cfg, state):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    return {"batch": 128, "cost": cost, "window": {"state": state},
            "peaks": {"peak_flops_bf16": 197e12,
                      "peak_hbm_bytes_per_s": 819e9},
            "trace": {"windows": 200.0, "program_busy_s": 3.6,
                      "stage_s": {
                          "nns.model/layer00/s0/attn": 0.2,
                          "nns.model/layer00/s0/attn/cache_write": 0.01,
                          "nns.model/layer00/s0/attn/"
                          "latent_decode_attention": 0.5,
                          "nns.model/layer00/s1/attn": 0.2,
                          "nns.model/layer00/s1/attn/"
                          "latent_decode_attention": 0.5,
                          "nns.model/layer00/s0/mlp": 0.6,
                          "nns.model/layer00/s1/mlp": 0.6,
                          "nns.model/layer00/moe": 0.01,
                          "nns.model/layer00/moe/router": 0.04,
                          "nns.model/layer00/moe/dispatch": 0.03,
                          "nns.model/layer00/moe/experts": 0.6,
                          "nns.model/layer00/moe/zero": 0.01,
                          "nns.model/layer00/moe/combine": 0.02,
                          "nns.model/head": 0.1, "nns.model/state": 0.01,
                          "nns.model/embed": 0.02,
                          "(no nns scope)": 0.01}}}


def _state(steps=1000):
    rows = 128 * 3072
    return {"steps": steps, "cache_rows_read": steps * rows,
            "cache_rows_fetched": steps * 128 * 3136,
            "cache_bytes_read": steps * rows * 1152 * 8,
            "cache_bytes_fetched": steps * 128 * 3136 * 1280 * 8,
            "experts_touched": steps * 28, "expert_hits": steps * 64,
            "zero_picks": steps * 2048}


def test_counter_readers(loader, cfg):
    obs = _obs(loader, cfg, _state())
    got = {}
    for name in COUNTER_METRICS:
        spec = loader.json("layer_metrics", name)
        got[name] = loader.module("readers", spec["reader"]).read(
            obs, **spec["args"])
    assert got["longcat_cache_bytes_per_frame"] == 3072 * 1152 * 8
    assert got["longcat_cache_bytes_fetched_per_frame"] == 3136 * 1280 * 8
    assert got["longcat_experts_touched_share"] == pytest.approx(28 / 32)
    assert got["zero_expert_pick_share"] == pytest.approx(1 / 3)
    assert got["held_expert_pick_share"] == pytest.approx(8 / 768)
    # a program without the counters (the parent) reads nothing
    ratio = loader.module("readers", "state_counter_ratio").read
    old = {"steps": 5, "cache_bytes_read": 1}
    for name in ("zero_expert_pick_share", "held_expert_pick_share"):
        args = loader.json("layer_metrics", name)["args"]
        assert ratio(_obs(loader, cfg, old), **args) is None
        assert ratio({"window": {}}, **args) is None


def test_stage_and_roofline_readers_count_what_they_say(loader, cfg):
    stage = loader.module("readers", "stage_ms_per_window").read
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    state = _state()
    obs = _obs(loader, cfg, state)
    want = {"longcat_attn_ms_per_window": 1.41,
            "longcat_dense_mlp_ms_per_window": 1.2,
            "longcat_experts_ms_per_window": 0.6,
            "longcat_route_ms_per_window": 0.11,
            "longcat_head_ms_per_window": 0.1,
            "longcat_unattributed_ms_per_window": 0.04}
    assert set(want) == STAGE_METRICS
    for name, seconds in want.items():
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "stage_ms_per_window"
        assert stage(obs, **spec["args"]) == pytest.approx(
            seconds / 200 * 1e3), name
    # the six stage metrics cover every stage once
    assert sum(want.values()) == pytest.approx(
        sum(obs["trace"]["stage_s"].values()))
    rows = state["cache_bytes_read"] / 1000

    def read(name):
        spec = loader.json("layer_metrics", name)
        return loader.module("readers", spec["reader"]).read(
            obs, **spec["args"])

    nbytes = (cost["weight_bytes"] + 28 * cost["expert_bytes"] + rows
              + 128 * (cost["in_bytes_per_frame"]
                       + cost["out_bytes_per_frame"]))
    assert 11.0e9 < nbytes < 11.2e9
    assert read("longcat_decode_step_roofline") == pytest.approx(
        100 * nbytes / 819e9 * 200 / 3.6)
    attn = rows + 128 * cost["attn_io_bytes_per_frame"]
    assert read("longcat_latent_attention_roofline") == pytest.approx(
        100 * attn / 819e9 * 200 / 1.0)
    assert read("longcat_experts_roofline") == pytest.approx(
        100 * 28 * cost["expert_bytes"] / 819e9 * 200 / 0.6)
    assert read("longcat_dense_mlp_roofline") == pytest.approx(
        100 * cost["dense_mlp_bytes"] / 819e9 * 200 / 1.2)
    # nothing to read: no trace, no counters, no such stage (the parent)
    for name in ROOFLINES:
        spec = loader.json("layer_metrics", name)
        fn = loader.module("readers", spec["reader"]).read
        assert fn(dict(obs, trace=None), **spec["args"]) is None
        assert fn(dict(obs, window={}), **spec["args"]) is None
        assert fn(dict(obs, peaks=None), **spec["args"]) is None


def test_preflight_fails_a_refused_shape_on_the_chip_only(loader, cfg,
                                                          monkeypatch):
    """On the chip a shape the experts' kernel refuses ends the run
    before weights are made; the cell's own shapes are taken; a CPU
    rehearsal (toy shapes, nothing timed) is let through."""
    from benchmark import BenchmarkError
    from nnstreamer_tpu.ops import kernels

    glue = loader.module("models", cfg["model"])
    with open(os.path.join(toyroot.DATA, TOY_CONFIG + ".json")) as f:
        toy = json.load(f)
    glue.preflight(toy)
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    asked = []
    real = kernels.grouped_gated_product_refusal

    def refusal(x, up, down, dtypes, blk, *more):
        asked.append((tuple(x), tuple(up), blk))
        return real(x, up, down, dtypes, blk, *more)

    monkeypatch.setattr(kernels, "grouped_gated_product_refusal", refusal)
    glue.preflight(cfg)
    # the held experts' at a step's and a chunk's rows, and a dense MLP
    # of a decode step as one group of one expert (30 % of the step)
    assert asked == [((128, 6144), (8, 6144, 2048), 128),
                     ((2048, 6144), (8, 6144, 2048), 256),
                     ((128, 6144), (1, 6144, 12288), 128)]
    with pytest.raises(BenchmarkError, match="grouped_gated_product"):
        glue.preflight(toy)


# -- the toy twin, end to end ---------------------------------------------------------


def _add_toy_cell(root: str) -> str:
    """The toy root of the other tests plus a twin of the new cell: the
    configuration's structure at hidden 64 (two layers of two sub-blocks
    each, a router 24 wide over 16 real and 8 zero-compute experts of
    which 4 real ones are held), the cell's own two launch lines, a ring
    of 6 steps of 4 streams on prompts of 12-30 tokens."""
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
    root = _add_toy_cell(str(tmp_path_factory.mktemp("longcat_root")))
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
        "held_experts_part_off", "greedy_mismatch", "order_errors"}
    assert 0 < compared["logits_rel_l2_lower_median"]["value"] < 0.03
    # the held experts' part of what was served, along the reference's
    assert 0 <= compared["held_experts_part_off"]["value"] < 0.1
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
    assert state["steps"] > 0 and state.get("state_bytes", 0) == 0
    # two caches a layer: a row in use is read four times a step
    row = (16 + 8) * 2
    assert state["cache_rows_read"] > state["steps"] * 4 * 12
    assert state["cache_bytes_read"] == state["cache_rows_read"] * row * 4
    # the toy's caches are one lattice cell of 128 rows, stored 128 wide
    assert state["cache_rows_fetched"] == state["steps"] * 4 * 128
    assert state["cache_bytes_fetched"] \
        == state["cache_rows_fetched"] * 128 * 2 * 4
    # how a step's picks divide: 4 streams x 4 picks x 2 layers
    picks = state["steps"] * 32
    assert "picks" not in state                  # a constant: no counter
    assert 0 < state["zero_picks"] < picks
    assert 0 < state["expert_hits"] < picks - state["zero_picks"]
    assert 0 < state["experts_touched"] <= min(state["expert_hits"],
                                                state["steps"] * 8)


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
    if metric in NEW_METRICS - {"longcat_unattributed_ms_per_window"}:
        assert value > 0
    if metric.endswith("_share"):
        assert value <= 1
    if metric == "zero_expert_pick_share":
        assert 0.15 < value < 0.55             # 8 of the router's 24
    if metric == "held_expert_pick_share":
        assert 0.05 < value < 0.35             # 4 of the router's 24


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
    for scope in ("layer00/s0/attn", "layer01/s1/attn", "/s0/mlp",
                  "/s1/mlp", "/moe/router", "/moe/dispatch", "/moe/experts",
                  "/moe/zero", "nns.model/head"):
        assert any(scope in s for s in stages), (scope, sorted(stages))
    assert any(s.endswith("/attn/latent_decode_attention") for s in stages)
    assert not ROOFLINES & set(m)                    # a CPU has no peak


def _sampled_histories(root, details):
    cfg = details["cfg"]
    reference = Loader(root).module("reference", cfg["reference"])
    inputs = Loader(root).module("inputs", cfg["inputs"])
    ids, positions = details["frames"]
    where = inputs.locate(cfg, SEED, ids, positions)
    return cfg, reference, [inputs.history(cfg, SEED, j, r)
                            for j, r in where]


@pytest.mark.parametrize("fault", ["no_held_experts", "no_zero_term",
                                   "one_cache"])
def test_the_check_fails_a_part_left_out(traced, fault):
    """The served logits' reference against one with the held experts'
    part dropped, with the zero-compute picks' term dropped, and with
    sub-block 1 reading sub-block 0's cache: the check must tell each
    from the sound one by the limit half the sample may not pass, and
    the held experts' part dropped by the number that reads that part
    alone as well (at the cell's size by that number only)."""
    root, _line, details = traced
    cfg, reference, histories = _sampled_histories(root, details)
    sound = reference.forward_last(cfg, SEED, histories)
    wrong = reference.forward_last(cfg, SEED, histories, faults=(fault,))
    ref0 = wrong if fault == "no_held_experts" else reference.forward_last(
        cfg, SEED, histories, faults=("no_held_experts",))
    numbers = reference.compare_numbers(cfg, sound, {"logits": wrong}, ref0)
    assert numbers["logits_rel_l2_lower_median"] \
        > cfg["limits"]["logits_rel_l2_lower_median"], numbers
    if fault == "no_held_experts":
        assert numbers["held_experts_part_off"] == pytest.approx(1.0)
        assert cfg["limits"]["held_experts_part_off"] < 0.75
    # and what the run served passes against the sound one
    served = {n["name"]: n["value"] for n in details["numbers"]}
    assert served["logits_rel_l2_lower_median"] \
        < cfg["limits"]["logits_rel_l2_lower_median"] / 1.5
    assert served["held_experts_part_off"] \
        < cfg["limits"]["held_experts_part_off"] / 1.5


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

    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.models.longcat_flash",
                        None)
    monkeypatch.delattr(models_pkg, "longcat_flash", raising=False)
    with pytest.raises(ImportError):
        run_cell(TOY, SEED, 0.3, False, root=root, rehearsal=True)
    monkeypatch.setattr(harness, "run_cell",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            ImportError("no longcat_flash")))
    assert harness.main(["--workload", TOY, "--seed", "1", "--seconds",
                         "1"]) == 1
