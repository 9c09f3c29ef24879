"""The cached-decode cell of the DeepSeek-V2 share on the CPU: its files
resolve and hold together, its inputs say where a sampled frame came
from, its cost functions agree with a count by hand, its readers do
their arithmetic on made-up observations, and a toy twin of the cell
runs end to end through ``run_cell(..., rehearsal=True)``: two
``tensor_filter`` lines on one cache, prefill in set-up, the window, the
reference, and the float8 control failing.  No number here is a rate."""

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
CELL, CONFIG = "dsv2.decode16k", "deepseek_v2_share4"
TOY = "toy_dsv2.decode"


@pytest.fixture(scope="module")
def loader():
    return Loader(REPO)


@pytest.fixture(scope="module")
def cfg(loader):
    return loader.config(CONFIG)


# -- the files ------------------------------------------------------------------------

# the published config.json, the keys that say something of the shape
PUBLISHED = {
    "first_k_dense_replace": 1, "hidden_size": 5120,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "moe_intermediate_size": 1536, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "num_attention_heads": 128,
    "num_experts_per_tok": 6, "num_hidden_layers": 60,
    "num_key_value_heads": 128, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "routed_scaling_factor": 16, "topk_group": 3,
    "v_head_dim": 128, "vocab_size": 102400,
    "max_position_embeddings": 163840}
HELD = {"num_hidden_layers": 5, "num_attention_heads": 32,
        "num_key_value_heads": 32, "n_routed_experts": 40,
        "vocab_size": 25600}


def test_the_stated_cut_holds_together(loader, cfg):
    entry = loader.entry("configs", CONFIG)
    assert cut_faults(cfg, entry["reduced"]) == []
    assert cut_faults({"reduced": []}, []) == []
    assert sorted(entry["reduced"]) == sorted(HELD)
    for key, value in PUBLISHED.items():
        assert cfg[key] == HELD.get(key, value), key
        if key in HELD:
            assert cfg["published"][key] == value
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert cfg["deployment"]["chips_per_layer"] == 4
    # no width is named as reduced
    assert not [k for k in entry["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert entry["source"].endswith("DeepSeek-V2/blob/main/config.json")


def test_the_cell_launches_two_lines_on_one_key(loader, cfg):
    work = loader.json("workloads", CELL)
    mix = loader.json("traffic", loader.entry("workloads", CELL)["traffic"])
    assert (mix["kind"], mix["batch"], mix["ring_buffers"]) \
        == ("cached_replay", 32, 256)
    assert (mix["sink_depth"], mix["warmup_windows"], mix["check_windows"],
            mix["trace_seconds"]) == (4, 3, 4, 3.0)
    lines = [launch_line({"launch": work[key], "name": CELL}, cfg, mix,
                         model="m") for key in ("launch", "prefill_launch")]
    for line, prefix in zip(lines, ("el_", "pf_")):
        assert f"tensor_filter name={prefix}net framework=jax-xla model=m " \
               "shared-tensor-filter-key=m" in line
        assert line.startswith(f"device_src name={prefix}src ")
        assert "mesh=" not in line and "tensor_transform" not in line
    serving = cfg["serving"]
    assert (serving["streams"], serving["answer_tokens"]) \
        == (mix["batch"], mix["ring_buffers"])


def test_new_metrics_list_the_new_cell_alone(loader):
    new = [m for m in loader.manifest["per_layer"]
           if CELL in m.get("workloads", ())]
    assert {m["name"] for m in new} == {
        "latent_attn_ms_per_window", "experts_ms_per_window",
        "dense_mlp_ms_per_window", "unattributed_ms_per_window",
        "cache_bytes_per_frame", "experts_touched_share",
        "expert_hits_per_frame", "prefill_s", "decode_step_roofline",
        "latent_decode_attention_roofline"}
    assert all(m["workloads"] == [CELL] for m in new)
    # and every metric without a list is this cell's too
    listless = [m["name"] for m in loader.manifest["per_layer"]
                if "workloads" not in m]
    assert len(listless) == 8
    assert all(loader.reports(m, CELL) for m in loader.manifest["per_layer"]
               if "workloads" not in m)


# -- the inputs -----------------------------------------------------------------------


def test_a_sampled_frame_says_where_it_came_from(loader, cfg):
    inputs = loader.module("inputs", cfg["inputs"])
    prompts = inputs.prompts(cfg, SEED)
    lengths = np.array([len(p) for p in prompts])
    assert len(prompts) == 32 and lengths.min() >= 8192 \
        and lengths.max() <= 16384 and len(set(lengths)) > 16
    ring = inputs.make_ring(cfg, {}, SEED, 256, 32)
    assert len(ring) == 256 and all(
        a.dtype == np.int32 and a.shape == (32,) for slot in ring
        for a in slot)
    for j in (0, 100, 255):
        assert np.array_equal(ring[j][1], lengths + j)
    ids = np.stack([slot[0] for slot in ring])
    assert 0 <= ids.min() and ids.max() < 25600
    # the same seed, the same bytes; another seed, other bytes
    again = inputs.make_ring(cfg, {}, SEED, 256, 32)
    assert all(np.array_equal(a, b) for x, y in zip(ring, again)
               for a, b in zip(x, y))
    assert not np.array_equal(inputs.make_ring(cfg, {}, SEED + 1, 256,
                                               32)[0][0], ring[0][0])
    picks = [(3, 7), (255, 0), (128, 31)]
    found = inputs.locate(cfg, SEED, [ring[j][0][r] for j, r in picks],
                          [ring[j][1][r] for j, r in picks])
    assert found == picks
    history = inputs.history(cfg, SEED, 3, 7)
    assert len(history) == lengths[7] + 4
    assert np.array_equal(history[:lengths[7]], prompts[7])
    assert np.array_equal(history[lengths[7]:], ids[:4, 7])
    with pytest.raises(ValueError, match="ring of 4 x 32"):
        inputs.make_ring(cfg, {}, SEED, 4, 32)


def test_prompts_are_prefilled_in_whole_chunks(loader, cfg):
    inputs = loader.module("inputs", cfg["inputs"])
    prompts = inputs.prompts(cfg, SEED)
    chunks = inputs.prefill_chunks(cfg, SEED)
    assert len(chunks) == sum(-(-len(p) // 2048) for p in prompts)
    at = 0
    for slot, prompt in enumerate(prompts):
        for start in range(0, len(prompt), 2048):
            ids, where, first = chunks[at]
            at += 1
            assert (int(where[0]), int(first[0])) == (slot, start)
            assert ids.shape == (2048,) and ids.dtype == np.int32
            part = prompt[start:start + 2048]
            assert np.array_equal(ids[:len(part)], part)
    assert inputs.cache_positions(cfg) == 16384 + 256


# -- costs against a count by hand ---------------------------------------------------


def test_costs_against_a_hand_count(loader, cfg):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    attn = (5120 * 1536 + 1536 * 32 * 192 + 5120 * 576 + 512 * 32 * 256
            + 32 * 128 * 5120)
    assert attn == 45_416_448
    matrices = (5 * attn + 3 * 5120 * 12288 + 4 * (5120 * 160
                                                   + 3 * 5120 * 3072)
                + 5120 * 25600)
    vectors = 5 * (2 * 5120 + 1536 + 512) + 5120
    assert cost["weight_bytes"] == matrices * 2 + vectors * 4
    assert round(cost["weight_bytes"] / 1e9, 3) == 1.478
    assert cost["expert_bytes"] == 3 * 5120 * 1536 * 2 == 47_185_920
    assert cost["cache_row_bytes"] == 1152
    assert cost["expert_slots"] == 160 and cost["expert_layers"] == 4
    assert cost["out_bytes_per_frame"] == 25600 * 4 + 4
    assert cost["in_bytes_per_frame"] == 8 + 5120 * 2 + 5 * 1152
    assert cost["flops_per_frame"] == 2 * matrices
    assert cost["flops_per_expert_hit"] == 2 * 3 * 5120 * 1536
    assert cost["flops_per_cache_row"] == 2 * 32 * (576 + 512)
    assert cost["attn_io_bytes_per_frame"] == 5 * 32 * (576 * 2 + 512 * 4)


# -- the readers' arithmetic ----------------------------------------------------------


def _obs(loader, cfg, state):
    cost = loader.module("costs", cfg["costs"]).frame_cost(cfg)
    return {"batch": 32, "cost": cost, "window": {"state": state},
            "peaks": {"peak_flops_bf16": 197e12,
                      "peak_hbm_bytes_per_s": 819e9},
            "trace": {"windows": 200.0, "program_busy_s": 3.0,
                      "stage_s": {
                          "nns.model/layer00/attn/latent_decode_attention":
                              0.2,
                          "nns.model/layer01/attn/latent_decode_attention":
                              0.3,
                          "nns.model/layer01/moe/experts/while/body": 1.0}}}


def test_counter_readers_take_ratios_over_the_window(loader, cfg):
    ratio = loader.module("readers", "state_counter_ratio").read
    steps = 1000
    state = {"steps": steps, "cache_bytes_read": steps * 32 * 70_000_000,
             "experts_touched": steps * 112, "expert_hits": steps * 32 * 6}
    obs = _obs(loader, cfg, state)
    assert ratio(obs, "cache_bytes_read", "frame") == 70_000_000
    assert ratio(obs, "experts_touched", "step", "expert_slots") == 0.7
    assert ratio(obs, "expert_hits", "frame", "expert_layers") == 1.5
    # a program without the counters, or a window without a sample
    for window in ({}, {"state": {}}, {"state": {"steps": 0}}):
        assert ratio(dict(obs, window=window), "cache_bytes_read",
                     "frame") is None
    for name in ("cache_bytes_per_frame", "experts_touched_share",
                 "expert_hits_per_frame"):
        spec = loader.json("layer_metrics", name)
        assert spec["reader"] == "state_counter_ratio"
        assert ratio(obs, **spec["args"]) > 0


def test_roofline_readers_count_a_floor(loader, cfg):
    step = loader.module("readers", "decode_step_roofline").read
    kernel = loader.module("readers", "latent_decode_attention_roofline").read
    steps = 1000
    state = {"steps": steps, "cache_bytes_read": steps * 2_200_000_000,
             "experts_touched": steps * 112, "expert_hits": steps * 192}
    obs = _obs(loader, cfg, state)
    cost = obs["cost"]
    nbytes = (cost["weight_bytes"] + 112 * 47_185_920 + 2_200_000_000
              + 32 * (cost["in_bytes_per_frame"]
                      + cost["out_bytes_per_frame"]))
    assert step(obs) == pytest.approx(100 * nbytes / 819e9 * 200 / 3.0)
    assert 50 < step(obs) < 100
    attn = 2_200_000_000 + 32 * cost["attn_io_bytes_per_frame"]
    assert kernel(obs) == pytest.approx(100 * attn / 819e9 * 200 / 0.5)
    # nothing to read: no trace, no counters, no such stage
    assert step(dict(obs, trace=None)) is None
    assert step(dict(obs, window={})) is None
    assert kernel(dict(obs, window={})) is None
    assert kernel(dict(obs, trace=dict(obs["trace"], stage_s={
        "nns.model/layer00/attn": 1.0}))) is None


def test_state_counters_ride_in_the_run_counters(loader):
    from nnstreamer_tpu.utils.stats import STATE_STATS

    kind = loader.module("traffic", "cached_replay")

    class Inner:
        def snapshot(self):
            return {"t": 0.0}

        @staticmethod
        def delta(a, b):
            return {"seconds": 1.0}

    both = kind._WithState(Inner())
    STATE_STATS.reset()
    a = both.snapshot()
    STATE_STATS.add("steps", 5)
    b = both.snapshot()
    assert both.delta(a, b) == {"seconds": 1.0, "state": {"steps": 5}}
    assert both.delta({"t": 0.0}, {"t": 1.0}) == {"seconds": 1.0}
    STATE_STATS.reset()


# -- the toy twin, end to end ---------------------------------------------------------


def _add_toy_cell(root: str) -> str:
    """The toy root of the other tests plus a twin of the new cell: the
    configuration's structure at hidden 64, the cell's own two launch
    lines, a ring of 6 steps of 4 streams."""
    toyroot.build(root)
    bench = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(toyroot.DATA, "toy_dsv2.json"),
                os.path.join(bench, "configs", "toy_dsv2.json"))
    shutil.copy(os.path.join(toyroot.DATA, "toy_cached.json"),
                os.path.join(bench, "traffic", "toy_cached.json"))
    with open(os.path.join(bench, "workloads", CELL + ".json")) as f:
        work = json.load(f)
    work.update(name=TOY, config="toy_dsv2", traffic="toy_cached")
    with open(os.path.join(bench, "workloads", TOY + ".json"), "w") as f:
        json.dump(work, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(toyroot.DATA, "toy_dsv2.json")) as f:
        toy_cfg = json.load(f)
    manifest["configs"].append({
        "name": "toy_dsv2", "source": toy_cfg["source"],
        "file": "benchmark/configs/toy_dsv2.json",
        "reduced": toy_cfg["reduced"], "why": "toy"})
    manifest["workloads"].append({
        "name": TOY, "config": "toy_dsv2", "traffic": "toy_cached",
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
    root = _add_toy_cell(str(tmp_path_factory.mktemp("dsv2_root")))
    details: dict = {}
    line = run_cell(TOY, SEED, 0.6, True, root=root, rehearsal=True,
                    details=details)
    return root, line, details


def test_toy_twin_runs_end_to_end_and_is_correct(traced):
    root, line, details = traced
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 4 == 0
    compared = line["compared"]
    assert set(compared) == {
        "logits_rel_l2_lower_median", "logits_rel_l2_worst",
        "greedy_mismatch", "order_errors"}
    assert 0 < compared["logits_rel_l2_lower_median"]["value"] < 0.03
    assert compared["greedy_mismatch"]["value"] == 0
    assert compared["order_errors"]["value"] == 0
    # a toy cut states itself like the real one
    assert cut_faults(details["cfg"], details["cfg"]["reduced"]) == []
    obs = details["obs"]
    assert obs["window"]["compiles"] == 0
    assert obs["window"]["xla_compiles"] == 0
    # one program a window, and the state counted what its steps read
    state = obs["window"]["state"]
    assert state["steps"] > 0 and state.get("state_bytes", 0) == 0
    assert state["cache_bytes_read"] > 0 and state["expert_hits"] > 0


@pytest.mark.parametrize("metric", [
    "program_ms_per_window", "host_ms_per_window",
    "fence_wait_ms_per_window", "place_ms_per_window",
    "reshard_bytes_per_frame", "slow_host_ms", "program_load_s",
    "staging_s", "latent_attn_ms_per_window", "experts_ms_per_window",
    "dense_mlp_ms_per_window", "unattributed_ms_per_window",
    "cache_bytes_per_frame", "experts_touched_share",
    "expert_hits_per_frame", "prefill_s"])
def test_toy_twin_reads_every_per_layer_metric(traced, metric):
    """The eight metrics without a ``workloads`` list and the new ones
    (but the two roofline shares: a CPU has no peak) each read a number
    in the cell's traced run."""
    _root, line, _details = traced
    assert metric in line["metrics"], sorted(line["metrics"])
    value = line["metrics"][metric]["value"]
    assert np.isfinite(value) and value >= 0
    if metric in ("latent_attn_ms_per_window", "experts_ms_per_window",
                  "dense_mlp_ms_per_window", "prefill_s",
                  "cache_bytes_per_frame", "program_load_s"):
        assert value > 0
    if metric == "experts_touched_share":
        assert 0 < value <= 1
    if metric == "expert_hits_per_frame":
        assert 0 < value <= 3          # of the toy's 3 experts a token


def test_toy_twin_stage_metrics_cover_the_program(traced):
    _root, line, details = traced
    m = line["metrics"]
    parts = sum(m[k]["value"] for k in (
        "latent_attn_ms_per_window", "experts_ms_per_window",
        "dense_mlp_ms_per_window"))
    # every layer's stages: part of the program, and no more than it
    # (the CPU's thread-pool lines stand in for a device plane here, so
    # how large a part says nothing)
    assert 0 < parts <= 1.05 * m["program_ms_per_window"]["value"]
    stages = details["obs"]["trace"]["stage_s"]
    assert any(s.endswith("/moe/experts/while/body") for s in stages)
    assert "decode_step_roofline" not in m       # a CPU has no peak


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
    # by the lower median, which a lower precision moves; the cap on the
    # worst frame is for a wrong frame, and the control stays under it
    assert failed == ["logits_rel_l2_lower_median"]


def test_a_wrong_frame_fails_the_cap(traced):
    """Another frame's logits in a frame's place: the cap on the worst
    frame catches what the lower median, robust to a few frames, lets by."""
    root, _line, details = traced
    cfg = details["cfg"]
    reference = Loader(root).module("reference", cfg["reference"])
    ref = reference.raw_outputs(cfg, SEED, details["frames"])
    positions = np.asarray(details["frames"][1]).reshape(-1)
    other = next(i for i in range(1, len(ref))
                 if positions[i] != positions[0])
    swapped = np.array(ref)
    swapped[0] = ref[other]
    numbers = reference.compare_numbers(cfg, ref, {"logits": swapped})
    assert numbers["logits_rel_l2_lower_median"] == 0.0
    assert numbers["logits_rel_l2_worst"] > cfg["limits"][
        "logits_rel_l2_worst"]


@pytest.mark.parametrize("off,by,fails", [
    (4, 0.3, []),
    (5, 0.3, ["logits_rel_l2_lower_median"]),
    (5, 0.05, ["logits_rel_l2_lower_median"]),
    (1, 0.7, []),
    (1, 0.9, ["logits_rel_l2_worst"]),
    (1, "zeros", ["logits_rel_l2_worst"]),
], ids=["four-flipped-frames-pass", "five-frames-off-fail",
        "five-frames-slightly-off-fail", "one-frame-under-the-cap",
        "one-frame-over-the-cap", "one-frame-of-zeros"])
def test_how_many_frames_may_be_off_and_by_how_much(off, by, fails):
    """The real cell's limits on eight synthetic frames: at most half
    the sample over ``logits_rel_l2_lower_median``'s limit, none over
    the cap; a frame of zeros reads 1.0."""
    loader = Loader(REPO)
    cfg = loader.config("deepseek_v2_share4")
    reference = loader.module("reference", cfg["reference"])
    rng = np.random.default_rng(5)
    ref = rng.normal(size=(8, 64)).astype(np.float32)
    served = ref * (1 + 0.008)                  # a sound frame
    for i in range(off):
        if by == "zeros":
            served[i] = 0.0
        else:
            step = rng.normal(size=64).astype(np.float32)
            served[i] = ref[i] + step * (
                by * np.linalg.norm(ref[i]) / np.linalg.norm(step))
    numbers = reference.compare_numbers(cfg, ref, {"logits": served})
    assert [k for k, v in numbers.items()
            if v > cfg["limits"][k]] == fails, numbers


def test_a_program_without_the_model_fails_at_once(traced, monkeypatch):
    """What the parent commit does with this cell: the glue's preflight
    raises ``ImportError`` before any weight is made, and the command
    turns that into exit code 1."""
    root, _line, _details = traced
    import nnstreamer_tpu.models as models_pkg
    from benchmark import run as harness

    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.models.deepseek_v2",
                        None)
    monkeypatch.delattr(models_pkg, "deepseek_v2", raising=False)
    with pytest.raises(ImportError):
        run_cell(TOY, SEED, 0.3, False, root=root, rehearsal=True)
    monkeypatch.setattr(harness, "run_cell",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            ImportError("no deepseek_v2")))
    assert harness.main(["--workload", TOY, "--seed", "1", "--seconds",
                         "1"]) == 1
