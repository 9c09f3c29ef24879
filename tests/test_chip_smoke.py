"""``chip_smoke.py`` on the CPU: the script refuses to run without a
TPU, and its section functions — the very ones the chip run calls, with
their sizes as arguments — pass at a toy size with the Pallas kernels
interpreted, so the control flow is proven before chip time is spent.
Plus the compile-cache placement rule both it and the benchmark use.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

jax = pytest.importorskip("jax")


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert "platform='cpu'" in r.stderr
    # no result line, no section ran
    assert '"ok"' not in r.stdout and "PASS" not in r.stdout


# -- the sections, toy-sized ---------------------------------------------------


def test_section_a_device_resident_composite():
    facts = chip_smoke.section_a(batch=2, size=64, num_classes=5,
                                 n_buffers=3)
    assert facts["buffers"] == 3
    assert facts["matched"] >= chip_smoke.MATCHED_MIN


def test_section_b_serving_path():
    facts = chip_smoke.section_b(streams=3, frames_per_stream=6, batch=4,
                                 size=64, num_classes=5)
    assert facts["frames"] == 18
    assert facts["dispatches"] < 18            # windows coalesced
    assert set(facts["buckets_compiled"]) <= {1, 2, 4}


def test_section_c_kernels_engage_interpreted():
    # 64/4 = 16 patches a side -> 256 positions, head dim 128: the
    # blockwise kernel's smallest shape (the ViT itself takes
    # short_attention there); beside it ViT-B/16's 196 positions with
    # heads of 64
    facts = chip_smoke.section_c(batch=2, image=64, patch=4, dim=128,
                                 depth=2, heads=1, mlp=256, num_classes=10,
                                 frame_shape=(32, 128, 3),
                                 short=(196, 2, 64))
    assert facts["mosaic"] is False            # CPU: the interpreter
    assert facts["short_attention_max_abs_diff"] < 0.02


def test_section_c_catches_a_silent_jnp_fallback():
    # one head of 64 neither pairs up on a lane block nor fills one: no
    # kernel is eligible, the ViT quietly runs flash_attention_reference
    # — which is exactly what the section exists to refuse
    with pytest.raises(chip_smoke.SmokeError, match="jnp fallback"):
        chip_smoke.section_c(batch=2, image=64, patch=16, dim=64,
                             depth=1, heads=1, mlp=256, num_classes=10,
                             frame_shape=(32, 128, 3))


def test_section_d_measures_three_waits_and_judges_them():
    durations, lower = chip_smoke.measure_fence(n=128, target_s=0.01,
                                                peak_flops=1e10)
    assert set(durations) == {"block_until_ready", "host_fetch",
                              "sink_wait_eos"}
    assert lower >= 0.01 and all(v > 0 for v in durations.values())
    # the verdict itself, on made-up seconds (CPU timings prove nothing)
    ok = {"block_until_ready": 0.53, "host_fetch": 0.54,
          "sink_wait_eos": 0.55}
    chip_smoke.fence_verdict(ok, 0.5)
    with pytest.raises(chip_smoke.SmokeError, match="completion fence"):
        chip_smoke.fence_verdict(dict(ok, block_until_ready=0.004), 0.5)
    with pytest.raises(chip_smoke.SmokeError, match="10 %"):
        chip_smoke.fence_verdict(dict(ok, sink_wait_eos=0.7), 0.5)


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs the (virtual) four-device inventory")
def test_section_e_four_devices():
    facts = chip_smoke.section_e(batch=4, size=64, num_classes=5,
                                 pool_batch=4, frames_per_stream=4)
    assert set(facts) == {"mesh", "split", "pool"}


def test_detections_agree_tolerates_rounding_and_rejects_wrong_answers():
    rng = np.random.default_rng(0)
    boxes = rng.random((4, 10, 4)).astype(np.float32)
    classes = rng.integers(1, 50, (4, 10)).astype(np.int32)
    scores = np.sort(rng.random((4, 10)).astype(np.float32))[:, ::-1]
    num = np.full((4,), 10, np.int32)
    want = (boxes, classes, scores, num)
    assert chip_smoke.detections_agree(want, want, "t") == {
        "matched": 1.0, "identical": 1.0, "wrong_frame_matched": 0.0}
    # what differently tiled bf16 programs do: boxes move a little and
    # near-ties swap slots — still the same detections
    swapped = (boxes[:, ::-1] + 0.01, classes[:, ::-1], scores, num)
    facts = chip_smoke.detections_agree(swapped, want, "t")
    assert facts["matched"] == 1.0 and facts["identical"] == 0.0
    # frames handed to the wrong stream
    mixed = (np.roll(boxes, 1, axis=0), np.roll(classes, 1, axis=0),
             scores, num)
    with pytest.raises(chip_smoke.SmokeError, match="match the reference"):
        chip_smoke.detections_agree(mixed, want, "t")
    with pytest.raises(chip_smoke.SmokeError, match="match the reference"):
        chip_smoke.detections_agree(
            (boxes + 0.2, classes, scores, num), want, "t")
    with pytest.raises(chip_smoke.SmokeError, match="scores differ"):
        chip_smoke.detections_agree(
            (boxes, classes, scores + 0.2, num), want, "t")
    with pytest.raises(chip_smoke.SmokeError, match="non-finite"):
        chip_smoke.detections_agree(
            (boxes * np.nan, classes, scores, num), want, "t")


# -- where the compile cache goes ----------------------------------------------


@pytest.fixture
def _jax_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_helper_leaves_a_placed_cache_alone(monkeypatch, tmp_path,
                                                  _jax_cache_config):
    from nnstreamer_tpu.utils.jaxcache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_defaults_to_the_checkout(monkeypatch,
                                               _jax_cache_config):
    from nnstreamer_tpu.utils.jaxcache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert enable_compile_cache() == want      # fixed: same path again
