"""The cached-decode cell of the SmallThinker stage on the CPU: its files
resolve and hold together, its cost functions agree with a count by
hand, its readers do their arithmetic on made-up observations, and a toy
twin of the cell runs end to end through ``run_cell(...,
rehearsal=True)``: two ``tensor_filter`` lines on one state of rings and
full caches, prefill in set-up (padded last chunks, rings that wrap),
the window, the reference, and the float8 control failing.  No number
here is a rate."""

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
CELL, CONFIG = "smallthinker.decode16k", "smallthinker_21b_stage8"
TOY, TOY_CONFIG = "toy_smallthinker.decode", "toy_smallthinker"

NEW_METRICS = {
    "window_attn_ms_per_window", "full_attn_ms_per_window",
    "relu_experts_ms_per_window", "vocab_head_ms_per_window",
    "kv_unattributed_ms_per_window", "window_kv_bytes_per_frame",
    "full_kv_bytes_per_frame", "relu_experts_touched_share",
    "gqa_prefill_s", "gqa_decode_step_roofline",
    "gqa_decode_attention_roofline"}


@pytest.fixture(scope="module")
def loader():
    return Loader(REPO)


@pytest.fixture(scope="module")
def cfg(loader):
    return loader.config(CONFIG)


# -- the files ------------------------------------------------------------------------

# the catalog row's ``config``: every key, as published
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def test_the_stated_cut_holds_together(loader, cfg):
    entry = loader.entry("configs", CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cut_faults(cfg, entry["reduced"]) == []
    for key, value in PUBLISHED.items():
        assert cfg[key] == (8 if key == "num_hidden_layers" else value), key
    assert cfg["published"]["num_hidden_layers"] == 52
    assert cfg["deployment"]["chips_per_layer"] == 1
    assert entry["source"] == cfg["source"] and entry["source"].endswith(
        "SmallThinker-21BA3B-Instruct/blob/main/config.json")
    for key in ("assumed", "serving", "limits", "limits_why", "init"):
        assert cfg[key], key
    # two whole periods are held: full attention at 0 and 4
    assert cfg["sliding_window_layout"][:8] == [0, 1, 1, 1, 0, 1, 1, 1]
    serving = cfg["serving"]
    assert serving["prompt_tokens"][1] + serving["answer_tokens"] \
        == cfg["max_position_embeddings"]
    assert serving["prompt_tokens"][0] > cfg["sliding_window_size"]


def test_the_cell_runs_on_the_traffic_that_is_there(loader, cfg):
    entry = loader.entry("workloads", CELL)
    assert (entry["traffic"], entry["chips"]) == ("cached_decode32", 1)
    work = loader.json("workloads", CELL)
    mix = loader.json("traffic", entry["traffic"])
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
    assert inputs.cache_positions(cfg) == cfg["max_position_embeddings"]
    lengths = [len(p) for p in inputs.prompts(cfg, SEED)]
    assert min(lengths) >= 8192 and max(lengths) <= 16128
    ring = inputs.make_ring(cfg, mix, SEED, 256, 32)
    assert max(int(slot[1].max()) for slot in ring) <= 16383
    assert max(int(slot[0].max()) for slot in ring) < 151936


def test_new_metrics_list_the_new_cell_alone(loader):
    new = [m for m in loader.manifest["per_layer"]
           if CELL in m.get("workloads", ())]
    assert {m["name"] for m in new} == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in new)
    for m in new:
        if m["name"] == "gqa_decode_attention_roofline":
            assert os.path.isfile(os.path.join(
                REPO, "benchmark", "readers", m["name"] + ".py"))
        else:
            spec = loader.json("layer_metrics", m["name"])
            assert os.path.isfile(os.path.join(
                REPO, "benchmark", "readers", spec["reader"] + ".py"))


# -- costs against a count by hand ---------------------------------------------------


def test_costs_against_a_hand_count(loader, cfg):
    """The numbers the issue reckoned the cut with, recounted."""
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    attn = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560
    assert attn == 20_971_520
    expert = 3 * 2560 * 768
    assert expert == 5_898_240 and 64 * expert == 377_487_360
    layer = attn + 64 * expert + 2560 * 64
    assert round(layer / 1e6, 1) == 398.6
    ends = 2 * 151_936 * 2560
    assert round(ends / 1e6, 1) == 777.9
    assert round((8 * layer + ends) * 2 / 1e9, 2) == 7.93
    matrices = 8 * (attn + 2560 * 64) + 2560 * 151_936
    vectors = 8 * 2 * 2560 + 2560
    assert cost["weight_bytes"] == matrices * 2 + vectors * 4
    assert cost["expert_bytes"] == expert * 2 == 11_796_480
    assert cost["cache_row_bytes"] == 2 * 4 * 128 * 2 == 2048
    assert cost["expert_slots"] == 512 and cost["expert_layers"] == 8
    assert (cost["window_layers"], cost["full_layers"]) == (6, 2)
    assert cost["out_bytes_per_frame"] == 151_936 * 4 + 4
    assert cost["in_bytes_per_frame"] == 8 + 2560 * 2 + 8 * 2048
    assert cost["flops_per_frame"] == 2 * matrices
    assert cost["flops_per_expert_hit"] == 2 * expert
    assert cost["flops_per_cache_row"] == 2 * 28 * 256
    assert cost["attn_io_bytes_per_frame"] == 8 * 28 * (128 * 2 + 128 * 4)
    # what the state holds: the rings are window + chunk wide
    serving = cfg["serving"]
    ring = cfg["sliding_window_size"] + serving["prefill_chunk"]
    stream = (2 * 16384 + 6 * ring) * 2048
    assert ring == 6144 and round(stream / 1e6, 1) == 142.6
    assert round(32 * stream / 1e9, 2) == 4.56
    # and what a step reads of it at the mean position: half a ring's
    # slots more than the issue's 4,096 would be wrong to count
    assert 6 * 4096 * 2048 == 50_331_648


# -- the readers' arithmetic ----------------------------------------------------------


def _obs(loader, cfg, state):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    return {"batch": 32, "cost": cost, "window": {"state": state},
            "peaks": {"peak_flops_bf16": 197e12,
                      "peak_hbm_bytes_per_s": 819e9},
            "trace": {"windows": 200.0, "program_busy_s": 3.0,
                      "stage_s": {
                          "nns.model/layer00/attn_full/gqa_decode_attention":
                              0.3,
                          "nns.model/layer01/attn_window/gqa_decode_attention":
                              0.2,
                          "nns.model/layer01/attn_window/cache_write": 0.05,
                          "nns.model/layer01/attn_window": 0.05,
                          "nns.model/layer01/moe/experts/while/body": 1.0,
                          "nns.model/layer01/moe/router": 0.1,
                          "nns.model/head": 0.4,
                          "(no nns scope)": 0.01}}}


def test_counter_readers_tell_the_two_caches_apart(loader, cfg):
    ratio = loader.module("readers", "state_counter_ratio").read
    steps = 1000
    window = 32 * 6 * 4096 * 2048
    full = 32 * 2 * 12_300 * 2048
    state = {"steps": steps, "window_bytes_read": steps * window,
             "full_bytes_read": steps * full,
             "cache_bytes_read": steps * (window + full),
             "experts_touched": steps * 490, "expert_hits": steps * 32 * 48}
    obs = _obs(loader, cfg, state)
    got = {}
    for name in ("window_kv_bytes_per_frame", "full_kv_bytes_per_frame",
                 "relu_experts_touched_share"):
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "state_counter_ratio"
        got[name] = ratio(obs, **spec["args"])
    assert got["window_kv_bytes_per_frame"] == 6 * 4096 * 2048
    assert got["full_kv_bytes_per_frame"] == 2 * 12_300 * 2048
    assert got["relu_experts_touched_share"] == pytest.approx(490 / 512)
    # a program without the counters (the parent) reads nothing
    old = {"steps": steps, "cache_bytes_read": 1}
    assert ratio(_obs(loader, cfg, old), "window_bytes_read",
                 "frame") is None


def test_stage_and_roofline_readers_count_what_they_say(loader, cfg):
    stage = loader.module("readers", "stage_ms_per_window").read
    steps = 1000
    cache = 32 * (6 * 4096 + 2 * 12_300) * 2048
    state = {"steps": steps, "cache_bytes_read": steps * cache,
             "experts_touched": steps * 490, "expert_hits": steps * 192 * 8}
    obs = _obs(loader, cfg, state)
    want = {"window_attn_ms_per_window": 0.3, "full_attn_ms_per_window": 0.3,
            "relu_experts_ms_per_window": 1.1,
            "vocab_head_ms_per_window": 0.4,
            "kv_unattributed_ms_per_window": 0.01}
    for name, seconds in want.items():
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "stage_ms_per_window"
        assert stage(obs, **spec["args"]) == pytest.approx(
            seconds / 200 * 1e3), name
    cost = obs["cost"]
    spec = loader.json("layer_metrics", "gqa_decode_step_roofline")
    step = loader.module("readers", spec["reader"]).read
    nbytes = (cost["weight_bytes"] + 490 * 11_796_480 + cache
              + 32 * (cost["in_bytes_per_frame"]
                      + cost["out_bytes_per_frame"]))
    # about the 10.1 GB the issue reckoned a step's compulsory bytes at
    assert 9.5e9 < nbytes < 10.5e9
    assert step(obs, **spec["args"]) == pytest.approx(
        100 * nbytes / 819e9 * 200 / 3.0)
    kernel = loader.module("readers", "gqa_decode_attention_roofline").read
    attn = cache + 32 * cost["attn_io_bytes_per_frame"]
    assert kernel(obs) == pytest.approx(100 * attn / 819e9 * 200 / 0.5)
    # nothing to read: no trace, no counters, no such stage (the parent)
    assert kernel(dict(obs, trace=None)) is None
    assert kernel(dict(obs, window={})) is None
    assert kernel(dict(obs, window={"state": {"steps": 5}})) is None
    assert kernel(dict(obs, trace=dict(obs["trace"], stage_s={
        "nns.model/layer00/attn/latent_decode_attention": 1.0}))) is None


# -- the toy twin, end to end ---------------------------------------------------------


def _add_toy_cell(root: str) -> str:
    """The toy root of the other tests plus a twin of the new cell: the
    configuration's structure at hidden 64 (six layers: both kinds, a
    window of 8 in a ring of 16), the cell's own two launch lines, a
    ring of 6 steps of 4 streams on prompts of 12-30 tokens."""
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
    root = _add_toy_cell(str(tmp_path_factory.mktemp("smallthinker_root")))
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
    # every stream is past the window of 8: four ring layers read 8 rows
    # a stream a step, two full layers every position, their sum is published
    row = 2 * 2 * 16 * 2                 # K and V, 2 heads of 16, bf16
    assert state["window_bytes_read"] == state["steps"] * 4 * 4 * 8 * row
    assert state["full_bytes_read"] > state["steps"] * 4 * 2 * 12 * row
    assert state["cache_bytes_read"] == state["window_bytes_read"] \
        + state["full_bytes_read"]
    assert state["expert_hits"] == state["steps"] * 4 * 6 * 3


@pytest.mark.parametrize("metric", [
    "program_ms_per_window", "host_ms_per_window",
    "fence_wait_ms_per_window", "place_ms_per_window",
    "reshard_bytes_per_frame", "slow_host_ms", "program_load_s",
    "staging_s"] + sorted(NEW_METRICS - {"gqa_decode_step_roofline",
                                         "gqa_decode_attention_roofline"}))
def test_toy_twin_reads_every_per_layer_metric(traced, metric):
    """The eight metrics without a ``workloads`` list and the new ones
    (but the two roofline shares: a CPU has no peak, and the toy's heads
    of 16 take the kernel's ``jnp`` reference) each read a number in the
    cell's traced run."""
    _root, line, _details = traced
    assert metric in line["metrics"], sorted(line["metrics"])
    value = line["metrics"][metric]["value"]
    assert np.isfinite(value) and value >= 0
    if metric in NEW_METRICS - {"kv_unattributed_ms_per_window"}:
        assert value > 0
    if metric == "relu_experts_touched_share":
        assert value <= 1
    if metric == "window_kv_bytes_per_frame":
        assert value == 4 * 8 * 128       # four ring layers of six held


def test_toy_twin_stage_metrics_cover_the_program(traced):
    _root, line, details = traced
    m = line["metrics"]
    parts = sum(m[k]["value"] for k in (
        "window_attn_ms_per_window", "full_attn_ms_per_window",
        "relu_experts_ms_per_window", "vocab_head_ms_per_window"))
    # every layer's stages and the head: part of the program, and no more
    # than it (the CPU's thread-pool lines stand in for a device plane
    # here, so how large a part says nothing, and what they book to no
    # stage is not the device's)
    assert 0 < parts <= 1.05 * m["program_ms_per_window"]["value"]
    stages = details["obs"]["trace"]["stage_s"]
    assert any(s.endswith("/moe/experts/while/body") for s in stages)
    assert any(s.endswith("/attn_window/cache_write") for s in stages)
    assert any("/attn_full" in s for s in stages)
    assert "gqa_decode_step_roofline" not in m       # a CPU has no peak


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


def test_reference_is_the_program_in_float32(traced):
    """The float32 program against the reference over the toy's own
    prompts: prefill in padded chunks through rings that wrap, then
    decode, logits and not ids."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models import smallthinker

    root, _line, details = traced
    cfg = details["cfg"]
    loader = Loader(root)
    inputs = loader.module("inputs", cfg["inputs"])
    reference = loader.module("reference", cfg["reference"])
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        loader.module("weights", cfg["weights"]).make(cfg, SEED))
    model = smallthinker.SmallThinkerConfig.from_dict(cfg)
    state = smallthinker.init_state(model, params, 4,
                                    inputs.cache_positions(cfg), 8)
    prefill = jax.jit(lambda p, s, *x: smallthinker.prefill(model, p, s, *x))
    decode = jax.jit(lambda p, s, *x: smallthinker.decode(model, p, s, *x))
    for chunk in inputs.prefill_chunks(cfg, SEED):
        state, _ = prefill(params, state, *chunk)
    ring = inputs.make_ring(cfg, {}, SEED, 6, 4)
    for j, (ids, positions) in enumerate(ring):
        state, (logits, _greedy) = decode(params, state, ids, positions)
        ref = reference.forward_last(
            cfg, SEED, [inputs.history(cfg, SEED, j, r) for r in range(4)])
        assert np.abs(np.asarray(logits) - ref).max() \
            <= 3e-5 * max(1.0, np.abs(ref).max()), j


def test_a_program_without_the_model_fails_at_once(traced, monkeypatch):
    """What the parent commit does with this cell: the glue's preflight
    raises ``ImportError`` before any weight is made, and the command
    turns that into exit code 1."""
    root, _line, _details = traced
    import nnstreamer_tpu.models as models_pkg
    from benchmark import run as harness

    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.models.smallthinker",
                        None)
    monkeypatch.delattr(models_pkg, "smallthinker", raising=False)
    with pytest.raises(ImportError):
        run_cell(TOY, SEED, 0.3, False, root=root, rehearsal=True)
    monkeypatch.setattr(harness, "run_cell",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            ImportError("no smallthinker")))
    assert harness.main(["--workload", TOY, "--seed", "1", "--seconds",
                         "1"]) == 1
