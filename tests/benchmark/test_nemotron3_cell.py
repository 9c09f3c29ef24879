"""The cached-decode cell of the Nemotron-3-Nano share on the CPU: its
files resolve and hold together, its cost functions agree with a count
by hand, its readers do their arithmetic on made-up observations, and a
toy twin of the cell runs end to end through ``run_cell(...,
rehearsal=True)``: two ``tensor_filter`` lines on one state of recurrent
states, snapshots and a cache, prefill in set-up (padded last chunks
that say their count), the window (every pass of the ring a rewind to
the prompts' ends), the reference, and the float8 control failing.  No
number here is a rate."""

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
CELL, CONFIG = "nemotron3.decode4k", "nemotron3_nano_share8"
TOY, TOY_CONFIG = "toy_nemotron3.decode", "toy_nemotron3"

STAGE_METRICS = {"ssm_ms_per_window", "hybrid_attn_ms_per_window",
                 "relu2_experts_ms_per_window",
                 "ssm_unattributed_ms_per_window"}
ROOFLINES = {"ssm_decode_step_roofline", "ssm_state_update_roofline",
             "hybrid_decode_attention_roofline", "relu2_experts_roofline"}
NEW_METRICS = STAGE_METRICS | ROOFLINES | {
    "ssm_state_bytes_per_frame", "hybrid_kv_bytes_per_frame",
    "relu2_experts_touched_share", "ssm_restores_per_window",
    "ssm_prefill_s"}


@pytest.fixture(scope="module")
def loader():
    return Loader(REPO)


@pytest.fixture(scope="module")
def cfg(loader):
    return loader.config(CONFIG)


# -- the files ------------------------------------------------------------------------

# the catalog row's ``config``: every key, as published
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
CUT = {"num_hidden_layers": 20,
       "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*",
       "n_routed_experts": 16, "vocab_size": 16384}


def test_the_stated_cut_holds_together(loader, cfg):
    entry = loader.entry("configs", CONFIG)
    assert entry["reduced"] == list(CUT)
    assert cut_faults(cfg, entry["reduced"]) == []
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
        assert cfg["published"][key] == value, key
    assert PUBLISHED["hybrid_override_pattern"].startswith(
        CUT["hybrid_override_pattern"])
    assert [PUBLISHED["hybrid_override_pattern"].count(k) for k in "ME*"] \
        == [23, 23, 6]
    assert [CUT["hybrid_override_pattern"].count(k) for k in "ME*"] \
        == [9, 8, 3]
    assert cfg["deployment"]["chips_per_layer"] == 8
    assert cfg["share"] == {"expert0": 0, "vocab0": 0}
    # the guide's floors: 8 experts, an eighth of the vocabulary
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert entry["source"] == cfg["source"] and entry["source"].endswith(
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    for key in ("assumed", "serving", "limits", "limits_why", "init"):
        assert cfg[key], key
    # whole lanes for the kernel, and no narrower than published
    assert cfg["expert_columns_stored"] == 1920 == 15 * 128
    serving = cfg["serving"]
    assert serving["prompt_tokens"][1] + serving["answer_tokens"] == 4096


def test_the_cell_runs_on_the_traffic_that_is_there(loader, cfg):
    entry = loader.entry("workloads", CELL)
    assert (entry["traffic"], entry["chips"]) == ("cached_decode128", 1)
    work = loader.json("workloads", CELL)
    mix = loader.json("traffic", entry["traffic"])
    assert mix == dict(mix, kind="cached_replay", batch=128,
                       ring_buffers=256, sink_depth=4, warmup_windows=3,
                       check_windows=4, trace_seconds=3.0)
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
    assert inputs.cache_positions(cfg) == 4096
    lengths = [len(p) for p in inputs.prompts(cfg, SEED)]
    assert min(lengths) >= 2048 and max(lengths) <= 3840
    assert abs(sum(lengths) - 377_000) < 1_500
    ring = inputs.make_ring(cfg, mix, SEED, 256, 128)
    assert max(int(slot[1].max()) for slot in ring) <= 4095
    assert max(int(slot[0].max()) for slot in ring) < 16384
    # a prefill frame says how many of its ids are real
    chunks = inputs.prefill_chunks(cfg, SEED)
    assert all(len(c) == 4 and c[0].shape == (2048,) for c in chunks)
    by_slot = {}
    for ids, slot, start, count in chunks:
        assert 1 <= int(count[0]) <= 2048
        assert int(start[0]) == by_slot.get(int(slot[0]), 0)
        by_slot[int(slot[0])] = int(start[0]) + int(count[0])
    assert [by_slot[r] for r in range(128)] == lengths


def test_new_metrics_list_the_new_cell_alone(loader):
    new = [m for m in loader.manifest["per_layer"]
           if CELL in m.get("workloads", ())]
    assert {m["name"] for m in new} == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in new)
    for m in new:
        spec = loader.json("layer_metrics", m["name"])
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "readers", spec["reader"] + ".py"))
        assert m["moves"] == ("setup_s" if m["name"] == "ssm_prefill_s"
                              else "fps_per_chip")
    # this PR adds to the benchmark and edits nothing that was there
    assert [w["name"] for w in loader.manifest["workloads"]][-1] == CELL
    assert [c["name"] for c in loader.manifest["configs"]][-1] == CONFIG


# -- costs against a count by hand ---------------------------------------------------


def test_costs_against_a_hand_count(loader, cfg):
    """The numbers the issue reckoned the cut with, recounted."""
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    mamba = 2688 * (4096 + 6144 + 64) + 4096 * 2688
    assert round(mamba / 1e6, 1) == 38.7
    expert = 2 * 2688 * 1856
    assert round(expert / 1e6, 2) == 9.98
    attn = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
    assert round(attn / 1e6, 1) == 23.4
    shared = 2 * 2688 * 3712
    moe = 16 * expert + shared + 2688 * 128
    assert round(moe * 2 / 1e6) == 360
    ends = 2 * 16384 * 2688
    weights = 9 * mamba + 8 * moe + 3 * attn + ends
    assert round(weights * 2 / 1e9, 1) == 3.9
    vectors = 9 * (2688 + 4 * 6144 + 6144 + 3 * 64 + 4096) \
        + 3 * 2688 + 8 * (2688 + 128) + 2688
    # the routed experts go by the slot touched, the embedding by the row
    assert cost["weight_bytes"] == (weights - 8 * 16 * expert
                                    - 16384 * 2688) * 2 + vectors * 4
    assert cost["expert_bytes"] == expert * 2 == 19_955_712
    assert cost["cache_row_bytes"] == 2 * 2 * 128 * 2 == 1024
    state = 64 * 64 * 128 * 4
    assert state == 2_097_152
    assert cost["ssm_row_bytes"] == 2 * (state + 3 * 6144 * 2)
    assert cost["in_bytes_per_frame"] == 8 + 2688 * 2 + 3 * 1024 \
        + 9 * cost["ssm_row_bytes"]
    assert cost["out_bytes_per_frame"] == 16384 * 4 + 4
    assert cost["expert_slots"] == 128 and cost["expert_layers"] == 8
    assert (cost["mamba_layers"], cost["attn_layers"]) == (9, 3)
    assert cost["flops_per_expert_hit"] == 2 * expert
    assert cost["flops_per_cache_row"] == 2 * 32 * 256
    assert cost["attn_io_bytes_per_frame"] == 3 * 32 * (128 * 2 + 128 * 4)
    # what the state holds, as the issue reckoned it
    live = 128 * 9 * (state + 3 * 6144 * 2)
    assert round(2 * live / 1e9, 1) == 4.9
    assert round(128 * 3 * 4096 * 1024 / 1e9, 1) == 1.6
    # and a step's compulsory bytes at the mean position: 9.9 GB, 57 % of
    # it the state-space layers'
    ssm = cost["mamba_weight_bytes"] + 128 * 9 * cost["ssm_row_bytes"]
    step = cost["weight_bytes"] + 128 * cost["expert_bytes"] \
        + 128 * 3070 * 3 * 1024 + 128 * (cost["in_bytes_per_frame"]
                                          + cost["out_bytes_per_frame"])
    assert round(step / 1e9, 1) == 9.9 and round(ssm / 1e9, 1) == 5.6
    assert round(100 * ssm / step) == 56


# -- the readers' arithmetic ----------------------------------------------------------


def _obs(loader, cfg, state):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    return {"batch": 128, "cost": cost, "window": {"state": state},
            "peaks": {"peak_flops_bf16": 197e12,
                      "peak_hbm_bytes_per_s": 819e9},
            "trace": {"windows": 200.0, "program_busy_s": 3.0,
                      "stage_s": {
                          "nns.model/layer00/mamba/step": 1.0,
                          "nns.model/layer00/mamba/in_proj": 0.2,
                          "nns.model/layer00/mamba": 0.1,
                          "nns.model/ssm_restore/while/body": 0.05,
                          "nns.model/layer05/attn/gqa_decode_attention": 0.3,
                          "nns.model/layer05/attn/cache_write": 0.05,
                          "nns.model/layer01/moe/experts": 0.6,
                          "nns.model/layer01/moe/experts/while/body": 0.1,
                          "nns.model/layer01/moe/shared": 0.1,
                          "nns.model/layer01/moe/router": 0.1,
                          "nns.model/head": 0.2, "nns.model/state": 0.02,
                          "nns.model/embed": 0.03,
                          "(no nns scope)": 0.01}}}


def _state(cfg, cost, steps=1000):
    kv = 128 * 3070 * 3 * 1024
    return {"steps": steps, "ssm_rows": steps * 128,
            "ssm_bytes": steps * 128 * 9 * cost["ssm_row_bytes"],
            "kv_bytes_read": steps * kv, "cache_bytes_read": steps * kv,
            "experts_touched": steps * 127, "expert_hits": steps * 768,
            "restores": steps // 2, "position_faults": 0}


def test_counter_readers(loader, cfg):
    ratio = loader.module("readers", "state_counter_ratio").read
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    obs = _obs(loader, cfg, _state(cfg, cost))
    got = {}
    for name in ("ssm_state_bytes_per_frame", "hybrid_kv_bytes_per_frame",
                 "relu2_experts_touched_share", "ssm_restores_per_window"):
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "state_counter_ratio"
        got[name] = ratio(obs, **spec["args"])
    assert got["ssm_state_bytes_per_frame"] == 9 * cost["ssm_row_bytes"]
    assert got["hybrid_kv_bytes_per_frame"] == 3070 * 3 * 1024
    assert got["relu2_experts_touched_share"] == pytest.approx(127 / 128)
    assert got["ssm_restores_per_window"] == 0.5
    # a program without the counters (the parent) reads nothing
    old = {"steps": 5, "cache_bytes_read": 1}
    assert ratio(_obs(loader, cfg, old), "ssm_bytes", "frame") is None
    assert ratio(_obs(loader, cfg, old), "restores", "step") is None


def test_stage_and_roofline_readers_count_what_they_say(loader, cfg):
    stage = loader.module("readers", "stage_ms_per_window").read
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    state = _state(cfg, cost)
    obs = _obs(loader, cfg, state)
    want = {"ssm_ms_per_window": 1.35, "hybrid_attn_ms_per_window": 0.35,
            "relu2_experts_ms_per_window": 0.9,
            "ssm_unattributed_ms_per_window": 0.26}
    for name, seconds in want.items():
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "stage_ms_per_window"
        assert stage(obs, **spec["args"]) == pytest.approx(
            seconds / 200 * 1e3), name
    # the four stage metrics cover every stage once
    assert sum(want.values()) == pytest.approx(
        sum(obs["trace"]["stage_s"].values()))
    kv = 128 * 3070 * 3 * 1024
    spec = loader.json("layer_metrics", "ssm_decode_step_roofline")
    step = loader.module("readers", spec["reader"]).read
    nbytes = (cost["weight_bytes"] + 127 * cost["expert_bytes"] + kv
              + 128 * (cost["in_bytes_per_frame"]
                       + cost["out_bytes_per_frame"]))
    assert 9.8e9 < nbytes < 10.0e9
    assert step(obs, **spec["args"]) == pytest.approx(
        100 * nbytes / 819e9 * 200 / 3.0)
    spec = loader.json("layer_metrics", "hybrid_decode_attention_roofline")
    kernel = loader.module("readers", spec["reader"]).read
    attn = kv + 128 * cost["attn_io_bytes_per_frame"]
    assert kernel(obs, **spec["args"]) == pytest.approx(
        100 * attn / 819e9 * 200 / 0.3)
    roofline = loader.module("readers", "stage_roofline").read
    spec = loader.json("layer_metrics", "ssm_state_update_roofline")
    assert spec["reader"] == "stage_roofline"
    ssm = cost["mamba_weight_bytes"] + 128 * 9 * cost["ssm_row_bytes"]
    assert roofline(obs, **spec["args"]) == pytest.approx(
        100 * ssm / 819e9 * 200 / 1.35)
    spec = loader.json("layer_metrics", "relu2_experts_roofline")
    assert roofline(obs, **spec["args"]) == pytest.approx(
        100 * 127 * cost["expert_bytes"] / 819e9 * 200 / 0.6)
    # nothing to read: no trace, no counters, no such stage (the parent)
    args = spec["args"]
    assert roofline(dict(obs, trace=None), **args) is None
    assert roofline(dict(obs, window={}), **args) is None
    assert roofline(dict(obs, window={"state": {"steps": 5}}), **args) is None
    assert roofline(dict(obs, peaks=None), **args) is None
    assert roofline(dict(obs, trace=dict(obs["trace"], stage_s={
        "nns.model/layer00/attn/latent_decode_attention": 1.0})),
        **args) is None


# -- the toy twin, end to end ---------------------------------------------------------


def _add_toy_cell(root: str) -> str:
    """The toy root of the other tests plus a twin of the new cell: the
    configuration's structure at hidden 64 (seven layers of all three
    kinds), the cell's own two launch lines, a ring of 6 steps of 4
    streams on prompts of 9-30 tokens."""
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
    root = _add_toy_cell(str(tmp_path_factory.mktemp("nemotron3_root")))
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
    assert 0 < compared["logits_rel_l2_lower_median"]["value"] < 0.03
    assert compared["greedy_mismatch"]["value"] == 0
    assert compared["order_errors"]["value"] == 0
    assert cut_faults(details["cfg"], details["cfg"]["reduced"]) == []
    obs = details["obs"]
    assert obs["window"]["compiles"] == 0
    assert obs["window"]["xla_compiles"] == 0
    state = obs["window"]["state"]
    assert state["steps"] > 0 and state.get("state_bytes", 0) == 0
    assert state["position_faults"] == 0
    # a pass of the ring is 6 steps and begins with 4 restores
    assert abs(state["restores"] - state["steps"] * 4 / 6) <= 4
    assert state["ssm_rows"] == state["steps"] * 4
    row = 2 * (8 * 8 * 16 * 4 + 3 * 128 * 2)     # read and written
    assert state["ssm_bytes"] == state["steps"] * 4 * 3 * row
    assert state["cache_bytes_read"] == state["kv_bytes_read"] \
        > state["steps"] * 4 * 9 * 2 * 2 * 16 * 2
    assert 0 < state["expert_hits"] < state["steps"] * 4 * 3 * 3


@pytest.mark.parametrize("metric", [
    "program_ms_per_window", "host_ms_per_window",
    "fence_wait_ms_per_window", "place_ms_per_window",
    "reshard_bytes_per_frame", "slow_host_ms", "program_load_s",
    "staging_s"] + sorted(NEW_METRICS - ROOFLINES))
def test_toy_twin_reads_every_per_layer_metric(traced, metric):
    """The eight metrics without a ``workloads`` list and the new ones
    (but the roofline shares: a CPU has no peak) each read a number in
    the cell's traced run."""
    _root, line, _details = traced
    assert metric in line["metrics"], sorted(line["metrics"])
    value = line["metrics"][metric]["value"]
    assert np.isfinite(value) and value >= 0
    if metric in NEW_METRICS - {"ssm_unattributed_ms_per_window"}:
        assert value > 0
    if metric == "relu2_experts_touched_share":
        assert value <= 1
    if metric == "ssm_state_bytes_per_frame":
        assert value == 3 * 2 * (8 * 8 * 16 * 4 + 3 * 128 * 2)
    if metric == "ssm_restores_per_window":
        assert 0.5 <= value <= 0.8        # 4 in 6 steps, less edge effects


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
    assert any(s.endswith("/mamba/step") for s in stages)
    assert any(s.endswith("/moe/experts/while/body") for s in stages)
    assert any("/attn" in s for s in stages)
    assert not ROOFLINES & set(m)                    # a CPU has no peak


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


def test_the_fence_fails_a_run_on_a_position_fault(traced, monkeypatch):
    """The glue's fence reads the program's published counters: a
    position the recurrent state could not serve ends the run."""
    from benchmark import BenchmarkError
    from nnstreamer_tpu.utils.stats import STATE_STATS

    root, _line, _details = traced
    glue = Loader(root).module("models", "nemotron3_nano_share8")

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

    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.models.nemotron_h",
                        None)
    monkeypatch.delattr(models_pkg, "nemotron_h", raising=False)
    with pytest.raises(ImportError):
        run_cell(TOY, SEED, 0.3, False, root=root, rehearsal=True)
    monkeypatch.setattr(harness, "run_cell",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            ImportError("no nemotron_h")))
    assert harness.main(["--workload", TOY, "--seed", "1", "--seconds",
                         "1"]) == 1
