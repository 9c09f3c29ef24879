"""The cached-decode cell of the K-EXAONE share on the CPU: its files
resolve and hold together, its cost functions agree with a count by
hand, its readers do their arithmetic on made-up observations, and a toy
twin of the cell runs end to end through ``run_cell(...,
rehearsal=True)``: two ``tensor_filter`` lines on one state of rings,
full caches and the prediction module's cache, prefill in set-up (padded
last chunks that say their count, rings shorter than a chunk), the
window (frames of three tensors, two logits tensors served, every pass
of the ring a rewind to the prompts' ends), the reference, and the check
failing dropped module cache rows, a window of 127 and the float8
control.  No number here is a rate."""

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
CELL, CONFIG = "kexaone.decode16k", "kexaone_236b_share8"
TOY, TOY_CONFIG = "toy_kexaone.decode", "toy_kexaone"

STAGE_METRICS = {"swa128_attn_ms_per_window", "global_attn_ms_per_window",
                 "mtp_ms_per_window", "swiglu_experts_ms_per_window",
                 "kexaone_dense_ms_per_window", "kexaone_head_ms_per_window",
                 "kexaone_unattributed_ms_per_window"}
ROOFLINES = {"kexaone_decode_step_roofline",
             "kexaone_decode_attention_roofline", "swiglu_experts_roofline"}
COUNTER_METRICS = {"swa128_kv_bytes_per_frame",
                   "swa128_kv_bytes_fetched_per_frame",
                   "global_kv_bytes_per_frame", "mtp_kv_bytes_per_frame",
                   "swiglu_experts_touched_share"}
NEW_METRICS = STAGE_METRICS | ROOFLINES | COUNTER_METRICS \
    | {"kexaone_prefill_s"}


@pytest.fixture(scope="module")
def loader():
    return Loader(REPO)


@pytest.fixture(scope="module")
def cfg(loader):
    return loader.config(CONFIG)


# -- the files ------------------------------------------------------------------------

CUT = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 19200}


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")


def test_the_stated_cut_holds_together(loader, cfg):
    entry = loader.entry("configs", CONFIG)
    assert entry["reduced"] == list(CUT)
    assert cut_faults(cfg, entry["reduced"]) == []
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (48, 128, 153600)
    for key, value in published.items():
        assert cfg[key] == CUT.get(key, value), key
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (6144, 64, 8, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"]) \
        == (18432, 2048, 8, 128)
    # the layouts keep their published length; the leading entries are used
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 48
    assert cfg["layer_types"][:5] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert cfg["num_nextn_predict_layers"] == 1
    assert cfg["deployment"]["chips_per_layer"] == 8
    assert cfg["share"] == {"expert0": 0, "vocab0": 0}
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 1 + 4
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= published["vocab_size"]
    assert entry["source"] == cfg["source"] and entry["source"].endswith(
        "LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json")
    for key in ("assumed", "serving", "limits", "limits_why", "init"):
        assert cfg[key], key
    # what the config leaves open is under `assumed`, with its alternative
    assumed = " ".join(cfg["assumed"])
    for words in ("pre-norm", "EXAONE 4.0", "BEFORE the final norm",
                  "sparse one", "alternative"):
        assert words in assumed, words
    serving = cfg["serving"]
    assert serving["prompt_tokens"][1] + serving["answer_tokens"] == 16384
    row = _catalog_row()
    if row is not None:
        assert published == row["config"]
        assert cfg["source"] == row["source_url"]


def test_the_cell_runs_on_the_traffic_that_is_there(loader, cfg):
    entry = loader.entry("workloads", CELL)
    assert (entry["traffic"], entry["chips"]) == ("cached_decode32", 1)
    work = loader.json("workloads", CELL)
    mix = loader.json("traffic", entry["traffic"])
    assert mix == dict(mix, kind="cached_replay", batch=32,
                       ring_buffers=256, sink_depth=4)
    lines = [launch_line({"launch": work[key], "name": CELL}, cfg, mix,
                         model="m") for key in ("launch", "prefill_launch")]
    for line, prefix in zip(lines, ("el_", "pf_")):
        assert f"tensor_filter name={prefix}net framework=jax-xla model=m " \
               "shared-tensor-filter-key=m" in line
        assert line.startswith(f"device_src name={prefix}src ")
    serving = cfg["serving"]
    assert (serving["streams"], serving["answer_tokens"]) \
        == (mix["batch"], mix["ring_buffers"])
    inputs = loader.module("inputs", cfg["inputs"])
    assert inputs.cache_positions(cfg) == 16384
    prompts = inputs.prompts(cfg, SEED)
    lengths = [len(p) for p in prompts]
    assert min(lengths) >= 8192 and max(lengths) <= 16128
    assert abs(sum(lengths) - 389_000) < 1_500
    ring = inputs.make_ring(cfg, mix, SEED, 256, 32)
    assert all(len(slot) == 3 for slot in ring)
    assert max(int(slot[2].max()) for slot in ring) <= 16383
    assert max(int(slot[a].max()) for slot in ring for a in (0, 1)) < 19200
    # slot k's next ids are slot k + 1's ids; the last has its own
    for k in range(255):
        assert (ring[k][1] == ring[k + 1][0]).all()
    assert ring[255][1].shape == (32,)
    assert (ring[0][2] == lengths).all()
    # the dsv2 cell's ring, with the ids that follow beside it
    plain = loader.module("inputs", "deepseek_v2_share4").make_ring(
        cfg, mix, SEED, 256, 32)
    assert all((a[0] == b[0]).all() and (a[2] == b[1]).all()
               for a, b in zip(ring, plain))
    # a prefill frame carries the shifted ids and says how many are real
    chunks = inputs.prefill_chunks(cfg, SEED)
    assert all(len(c) == 5 and c[0].shape == c[1].shape == (2048,)
               for c in chunks)
    by_slot = {}
    for ids, next_ids, slot, start, count in chunks:
        r, at, n = int(slot[0]), int(start[0]), int(count[0])
        assert 1 <= n <= 2048 and at == by_slot.get(r, 0)
        assert (ids[:n] == prompts[r][at:at + n]).all()
        assert (next_ids[:n - 1] == ids[1:n]).all()
        last = prompts[r][at + n] if at + n < lengths[r] else ring[0][0][r]
        assert next_ids[n - 1] == last
        by_slot[r] = at + n
    assert [by_slot[r] for r in range(32)] == lengths
    # a sampled frame's history and what follows each of its ids
    fed = inputs.history(cfg, SEED, 255, 7)
    follows = inputs.next_history(cfg, SEED, 255, 7)
    assert len(fed) == len(follows) == lengths[7] + 256
    assert (follows[:-1] == fed[1:]).all() and follows[-1] == ring[255][1][7]
    assert inputs.next_history(cfg, SEED, 3, 7)[-1] == ring[4][0][7]


def test_new_metrics_list_the_new_cell_alone(loader):
    new = [m for m in loader.manifest["per_layer"]
           if CELL in m.get("workloads", ())]
    assert {m["name"] for m in new} == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in new)
    layers = {m["layer"] for m in loader.manifest["per_layer"]
              if CELL not in m.get("workloads", ())}
    for m in new:
        spec = loader.json("layer_metrics", m["name"])
        assert spec["name"] == m["name"]
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "readers", spec["reader"] + ".py"))
        assert m["moves"] == ("setup_s" if m["name"] == "kexaone_prefill_s"
                              else "fps_per_chip")
        assert m["layer"] in layers            # a layer PERF.md has
        assert (m["unit"] == "%") == (m["name"] in ROOFLINES)
    # (no assertion on WHERE the entries stand: a later PR appends its own)
    assert CELL in [w["name"] for w in loader.manifest["workloads"]]
    assert CONFIG in [c["name"] for c in loader.manifest["configs"]]


# -- costs against a count by hand ---------------------------------------------------


def test_costs_against_a_hand_count(loader, cfg):
    """The numbers the issue reckoned the cut with, recounted."""
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144
    assert round(attn / 1e6, 1) == 113.2
    expert = 3 * 6144 * 2048
    assert round(expert / 1e6, 1) == 37.7
    router = 6144 * 128
    sparse = attn + 16 * expert + expert + router
    assert round(sparse / 1e6, 1) == 755.8
    dense = attn + 3 * 6144 * 18432
    assert round(dense / 1e6, 1) == 453.0
    ends = 2 * 19200 * 6144
    assert round(ends * 2 / 1e9, 3) == 0.472
    module = sparse + 2 * 6144 * 6144
    assert round(module / 1e6, 1) == 831.3
    weights = dense + 4 * sparse + ends + module
    assert round(weights * 2 / 1e9, 2) == 9.09
    vectors = 5 * (2 * 6144 + 256) + 4 * 128 + 6144 \
        + (2 * 6144 + 256 + 128 + 3 * 6144)
    # the routed experts go by the slot touched, the embedding by the
    # row; the head is read twice
    fixed = weights - 5 * 16 * expert - 19200 * 6144 + 19200 * 6144
    assert cost["weight_bytes"] == fixed * 2 + vectors * 4
    assert cost["mtp_weight_bytes"] == (
        attn + expert + router + 2 * 6144 * 6144 + 6144 * 19200) * 2 \
        + (2 * 6144 + 256 + 128 + 3 * 6144) * 4
    assert cost["expert_bytes"] == expert * 2 == 75_497_472
    assert cost["cache_row_bytes"] == 2 * 8 * 128 * 2 == 4096
    assert cost["in_bytes_per_frame"] == 12 + 2 * 6144 * 2 + 6 * 4096
    assert cost["out_bytes_per_frame"] == 2 * (19200 * 4 + 4)
    assert cost["expert_slots"] == 80 and cost["expert_layers"] == 5
    assert (cost["window_layers"], cost["full_layers"], cost["mtp_layers"]) \
        == (4, 1, 1)
    assert cost["flops_per_expert_hit"] == 2 * expert
    assert cost["flops_per_cache_row"] == 2 * 64 * 256
    assert cost["attn_io_bytes_per_frame"] == 6 * 64 * (128 * 2 + 128 * 4)
    # what the state holds, as the issue reckoned it
    assert round(2 * 32 * 16384 * 4096 / 1e9, 2) == 4.29
    assert round(4 * 32 * 384 * 4096 / 1e9, 2) == 0.20
    # a step's compulsory bytes at the mean position (12.3 k), 14 of 16
    # experts touched: the issue's 11.5 GB; the module 29 % of it (the
    # issue: 30 %), the two full caches 28 %, the rings under 1 %
    kv = 32 * 12_288 * 4096
    step = cost["weight_bytes"] + 5 * 14 * cost["expert_bytes"] + 2 * kv \
        + 32 * 128 * 4 * 4096 + 32 * (cost["in_bytes_per_frame"]
                                     + cost["out_bytes_per_frame"])
    assert round(step / 1e9, 1) == 11.6
    mtp = cost["mtp_weight_bytes"] + 14 * cost["expert_bytes"] + kv
    assert round(100 * mtp / step) == 29
    assert round(100 * 2 * kv / step) == 28
    assert 100 * 32 * 128 * 4 * 4096 / step < 1


# -- the readers' arithmetic ----------------------------------------------------------


def _obs(loader, cfg, state):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    return {"batch": 32, "cost": cost, "window": {"state": state},
            "peaks": {"peak_flops_bf16": 197e12,
                      "peak_hbm_bytes_per_s": 819e9},
            "trace": {"windows": 200.0, "program_busy_s": 3.2,
                      "stage_s": {
                          "nns.model/layer01/attn_window": 0.02,
                          "nns.model/layer01/attn_window/cache_write": 0.01,
                          "nns.model/layer01/attn_window/"
                          "gqa_decode_attention": 0.04,
                          "nns.model/layer03/attn_full": 0.05,
                          "nns.model/layer03/attn_full/"
                          "gqa_decode_attention": 0.4,
                          "nns.model/layer00/mlp": 0.3,
                          "nns.model/layer01/moe": 0.02,
                          "nns.model/layer01/moe/experts": 1.0,
                          "nns.model/layer01/moe/shared": 0.1,
                          "nns.model/layer01/moe/router": 0.03,
                          "nns.model/layer01/moe/combine": 0.01,
                          "nns.model/mtp/merge": 0.03,
                          "nns.model/mtp/attn_full/"
                          "gqa_decode_attention": 0.4,
                          "nns.model/mtp/moe/experts": 0.25,
                          "nns.model/mtp/moe/shared": 0.03,
                          "nns.model/mtp/head": 0.1,
                          "nns.model/head": 0.1, "nns.model/state": 0.01,
                          "nns.model/embed": 0.02,
                          "(no nns scope)": 0.01}}}


def _state(steps=1000):
    row, kv = 4096, 32 * 12_288
    window, fetched = 32 * 128, 32 * 250
    state = {"steps": steps, "window_rows_read": steps * window,
             "window_rows_fetched": steps * fetched,
             "full_rows_read": steps * kv, "mtp_rows_read": steps * kv,
             "window_bytes_read": steps * window * 4 * row,
             "window_bytes_fetched": steps * fetched * 4 * row,
             "full_bytes_read": steps * kv * row,
             "mtp_bytes_read": steps * kv * row,
             "experts_touched": steps * 70, "mtp_experts_touched": steps * 14,
             "expert_hits": steps * 160, "position_faults": 0}
    state["cache_bytes_read"] = state["window_bytes_read"] \
        + state["full_bytes_read"] + state["mtp_bytes_read"]
    return state


def test_counter_readers(loader, cfg):
    ratio = loader.module("readers", "state_counter_ratio").read
    obs = _obs(loader, cfg, _state())
    got = {}
    for name in COUNTER_METRICS:
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "state_counter_ratio"
        got[name] = ratio(obs, **spec["args"])
    assert got["swa128_kv_bytes_per_frame"] == 128 * 4 * 4096
    assert got["swa128_kv_bytes_fetched_per_frame"] == 250 * 4 * 4096
    assert got["global_kv_bytes_per_frame"] == 12_288 * 4096 \
        == got["mtp_kv_bytes_per_frame"]
    assert got["swiglu_experts_touched_share"] == pytest.approx(70 / 80)
    # a program without the counters (the parent) reads nothing
    old = {"steps": 5, "cache_bytes_read": 1}
    for name in COUNTER_METRICS - {"swiglu_experts_touched_share"}:
        args = loader.json("layer_metrics", name)["args"]
        assert ratio(_obs(loader, cfg, old), **args) is None


def test_stage_and_roofline_readers_count_what_they_say(loader, cfg):
    stage = loader.module("readers", "stage_ms_per_window").read
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    state = _state()
    obs = _obs(loader, cfg, state)
    want = {"swa128_attn_ms_per_window": 0.07,
            "global_attn_ms_per_window": 0.45, "mtp_ms_per_window": 0.81,
            "swiglu_experts_ms_per_window": 1.06,
            "kexaone_dense_ms_per_window": 0.4,
            "kexaone_head_ms_per_window": 0.1,
            "kexaone_unattributed_ms_per_window": 0.04}
    assert set(want) == STAGE_METRICS
    for name, seconds in want.items():
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "stage_ms_per_window"
        assert stage(obs, **spec["args"]) == pytest.approx(
            seconds / 200 * 1e3), name
    # the seven stage metrics cover every stage once
    assert sum(want.values()) == pytest.approx(
        sum(obs["trace"]["stage_s"].values()))
    kv = state["cache_bytes_read"] / 1000
    spec = loader.json("layer_metrics", "kexaone_decode_step_roofline")
    step = loader.module("readers", spec["reader"]).read
    nbytes = (cost["weight_bytes"] + 70 * cost["expert_bytes"] + kv
              + 32 * (cost["in_bytes_per_frame"]
                      + cost["out_bytes_per_frame"]))
    assert 11.5e9 < nbytes < 11.7e9
    assert step(obs, **spec["args"]) == pytest.approx(
        100 * nbytes / 819e9 * 200 / 3.2)
    spec = loader.json("layer_metrics", "kexaone_decode_attention_roofline")
    kernel = loader.module("readers", spec["reader"]).read
    attn = kv + 32 * cost["attn_io_bytes_per_frame"]
    assert kernel(obs, **spec["args"]) == pytest.approx(
        100 * attn / 819e9 * 200 / 0.84)
    roofline = loader.module("readers", "stage_roofline").read
    spec = loader.json("layer_metrics", "swiglu_experts_roofline")
    assert spec["reader"] == "stage_roofline"
    args = spec["args"]
    # the layers' and the module's experts, by the slots both touched
    assert roofline(obs, **args) == pytest.approx(
        100 * 70 * cost["expert_bytes"] / 819e9 * 200 / 1.25)
    # nothing to read: no trace, no counters, no such stage (the parent)
    assert roofline(dict(obs, trace=None), **args) is None
    assert roofline(dict(obs, window={}), **args) is None
    assert roofline(dict(obs, window={"state": {"steps": 5}}), **args) is None
    assert roofline(dict(obs, peaks=None), **args) is None
    assert roofline(dict(obs, trace=dict(obs["trace"], stage_s={
        "nns.model/layer00/attn/latent_decode_attention": 1.0})),
        **args) is None


def test_preflight_fails_a_refused_shape_on_the_chip_only(loader, cfg,
                                                          monkeypatch):
    """On the chip a shape a kernel refuses ends the run before weights
    are made; the cell's own shapes are taken; a CPU rehearsal (toy
    shapes, nothing timed) is let through."""
    from benchmark import BenchmarkError
    from nnstreamer_tpu.ops import kernels

    glue = loader.module("models", cfg["model"])
    with open(os.path.join(toyroot.DATA, TOY_CONFIG + ".json")) as f:
        toy = json.load(f)
    glue.preflight(toy)
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    glue.preflight(cfg)
    with pytest.raises(BenchmarkError, match="gqa_decode_attention.*"
                       "grouped_gated_product"):
        glue.preflight(toy)


# -- the toy twin, end to end ---------------------------------------------------------


def _add_toy_cell(root: str) -> str:
    """The toy root of the other tests plus a twin of the new cell: the
    configuration's structure at hidden 64 (five layers and the module,
    a window of 4 in rings of 12), the cell's own two launch lines, a
    ring of 6 steps of 4 streams on prompts of 9-30 tokens."""
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
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
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
    mine = {m["name"] for m in real["per_layer"]
            if m.get("workloads") == [CELL]}
    for m in manifest["per_layer"]:
        if m["name"] in mine:
            m["workloads"] = [TOY]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return root


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = _add_toy_cell(str(tmp_path_factory.mktemp("kexaone_root")))
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
        "mtp_logits_rel_l2_lower_median", "mtp_logits_rel_l2_worst",
        "greedy_mismatch", "order_errors"}
    for name in ("logits_rel_l2_lower_median",
                 "mtp_logits_rel_l2_lower_median"):
        assert 0 < compared[name]["value"] < 0.03
    assert compared["greedy_mismatch"]["value"] == 0
    assert compared["order_errors"]["value"] == 0
    assert cut_faults(details["cfg"], details["cfg"]["reduced"]) == []
    # a frame went in as three tensors and came out as four
    assert len(details["frames"]) == 3
    obs = details["obs"]
    assert obs["out_bytes_per_frame"] == 2 * (32 * 4 + 4)
    assert obs["window"]["compiles"] == 0
    assert obs["window"]["xla_compiles"] == 0
    state = obs["window"]["state"]
    assert state["steps"] > 0 and state.get("state_bytes", 0) == 0
    assert state["position_faults"] == 0
    row = 2 * 2 * 16 * 2
    # every stream is past the window of 4: a ring's 4 rows in use
    assert state["window_rows_read"] == state["steps"] * 4 * 4
    assert state["window_bytes_read"] == state["window_rows_read"] * 4 * row
    assert state["full_rows_read"] == state["mtp_rows_read"] \
        > state["steps"] * 4 * 9
    assert state["full_bytes_read"] == state["mtp_bytes_read"] \
        == state["full_rows_read"] * row
    assert state["cache_bytes_read"] == state["window_bytes_read"] \
        + state["full_bytes_read"] + state["mtp_bytes_read"]
    # the toy's shapes are refused by the kernel: the caches are read whole
    assert state["window_rows_fetched"] == state["steps"] * 4 * 12
    assert state["full_rows_fetched"] == state["steps"] * 4 * 36
    assert 0 < state["mtp_experts_touched"] < state["experts_touched"] \
        <= state["steps"] * 5 * 4
    assert 0 < state["expert_hits"] < state["steps"] * 4 * 5 * 3


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
    if metric in NEW_METRICS - {"kexaone_unattributed_ms_per_window"}:
        assert value > 0
    if metric == "swiglu_experts_touched_share":
        assert value <= 1
    if metric == "swa128_kv_bytes_per_frame":
        assert value == 4 * 4 * 2 * 2 * 16 * 2


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
    for scope in ("/attn_window", "/attn_full", "layer00/mlp",
                  "/moe/experts/while/body", "nns.model/mtp/merge",
                  "nns.model/mtp/attn_full", "nns.model/mtp/moe/",
                  "nns.model/mtp/head", "nns.model/head"):
        assert any(scope in s for s in stages), (scope, sorted(stages))
    assert not ROOFLINES & set(m)                    # a CPU has no peak


def _sampled_histories(root, details):
    """The reference, and the run's own sampled frames as it reads
    them: each frame's history and the id that follows each of its
    ids."""
    cfg = details["cfg"]
    reference = Loader(root).module("reference", cfg["reference"])
    inputs = Loader(root).module("inputs", cfg["inputs"])
    ids, _next_ids, positions = details["frames"]
    where = inputs.locate(cfg, SEED, ids, positions)
    return (cfg, reference,
            [inputs.history(cfg, SEED, j, r) for j, r in where],
            [inputs.next_history(cfg, SEED, j, r) for j, r in where])


def test_the_check_fails_a_window_of_127(traced):
    """The served logits against a reference whose window is one
    position short: the check must tell them apart."""
    root, _line, details = traced
    cfg, reference, histories, follows = _sampled_histories(root, details)
    short = reference.forward_last(dict(cfg, sliding_window=3), SEED,
                                   histories, follows)
    sound = reference.forward_last(cfg, SEED, histories, follows)
    numbers = reference.compare_numbers(
        cfg, sound, {"logits": short[0], "logits_mtp": short[1]})
    assert numbers["logits_rel_l2_lower_median"] \
        > cfg["limits"]["logits_rel_l2_lower_median"]
    assert numbers["mtp_logits_rel_l2_lower_median"] \
        > cfg["limits"]["mtp_logits_rel_l2_lower_median"]


def test_the_check_fails_dropped_module_cache_rows(traced):
    """The module's logits when its cache lacks the rows of one prefill
    chunk (eight positions whose ``u`` was made from another next id):
    the main logits stay, the module's fail."""
    root, _line, details = traced
    cfg, reference, histories, follows = _sampled_histories(root, details)
    sound = reference.forward_last(cfg, SEED, histories, follows)
    v0, chunk = cfg["share"]["vocab0"], cfg["serving"]["prefill_chunk"]
    wrong = [f.copy() for f in follows]
    for f in wrong:
        rows = slice(len(f) // 2, len(f) // 2 + chunk)
        f[rows] = v0 + (f[rows] - v0 + 1) % cfg["vocab_size"]
    dropped = reference.forward_last(cfg, SEED, histories, wrong)
    numbers = reference.compare_numbers(
        cfg, sound, {"logits": dropped[0], "logits_mtp": dropped[1]})
    assert numbers["logits_rel_l2_worst"] == 0
    assert numbers["mtp_logits_rel_l2_lower_median"] \
        > cfg["limits"]["mtp_logits_rel_l2_lower_median"]


def test_toy_twin_control_fails(traced):
    """The reference computed in float8_e4m3fn, the nearest precision
    below the configuration's bfloat16, on the run's own sampled frames:
    it has to fail one of the cell's numbers."""
    root, _line, details = traced
    cfg = details["cfg"]
    reference = Loader(root).module("reference", cfg["reference"])
    numbers = reference.control(cfg, SEED, details["frames"])
    sound = {n["name"]: n["value"] for n in details["numbers"]}
    failed = [n["name"] for n in numbers if n["value"] > n["limit"]]
    assert failed, f"the control passed: {numbers}"
    for n in numbers:
        if n["name"] in failed:
            assert n["value"] > 3 * sound[n["name"]]
    assert "logits_rel_l2_lower_median" in failed
    assert "mtp_logits_rel_l2_lower_median" in failed


def test_the_fence_fails_a_run_on_a_position_fault(traced):
    """The glue's fence reads the program's published counters: a
    position the rings could not serve ends the run."""
    from benchmark import BenchmarkError
    from nnstreamer_tpu.utils.stats import STATE_STATS

    root, _line, _details = traced
    glue = Loader(root).module("models", CONFIG)

    class Buf:
        tensors, meta = [], {}

    STATE_STATS.reset()
    glue.fence(Buf())
    STATE_STATS.add("position_faults", 2)
    try:
        with pytest.raises(BenchmarkError, match="2 decode position"):
            glue.fence(Buf())
    finally:
        STATE_STATS.reset()


def test_a_program_without_the_model_fails_at_once(traced, monkeypatch):
    """What the parent commit does with this cell: the glue's preflight
    raises ``ImportError`` before any weight is made, and the command
    turns that into exit code 1."""
    root, _line, _details = traced
    import nnstreamer_tpu.models as models_pkg
    from benchmark import run as harness

    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.models.exaone_moe",
                        None)
    monkeypatch.delattr(models_pkg, "exaone_moe", raising=False)
    with pytest.raises(ImportError):
        run_cell(TOY, SEED, 0.3, False, root=root, rehearsal=True)
    monkeypatch.setattr(harness, "run_cell",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            ImportError("no exaone_moe")))
    assert harness.main(["--workload", TOY, "--seed", "1", "--seconds",
                         "1"]) == 1
