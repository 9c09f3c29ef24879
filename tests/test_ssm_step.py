"""The Mamba-2 decode step as a kernel (``ops/kernels.py``
``ssm_decode_step``) against the ``jnp`` step behind the restore loop,
interpreted on the CPU: who starts from its snapshot, what the snapshot
is left as, which shapes the kernel refuses and that those take the
``jnp`` step.  No number here is a rate."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import mamba2
from nnstreamer_tpu.models import nemotron_h as nh
from nnstreamer_tpu.ops import kernels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STREAMS, POSITIONS = 5, 16
#: Mamba-2 sizes whose state the kernel takes: 2 groups of 2 heads of
#: 64 (128 lanes) over a state of 128
TAKEN = dict(mamba_heads=4, mamba_head_dim=64, groups=2, state_size=128)
PATTERNS = {"none": [0, 0, 0, 0, 0], "all": [1, 1, 1, 1, 1],
            "first_and_last": [1, 0, 0, 1, 1], "inner": [0, 1, 1, 0, 0]}


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_nemotron3.json")) as f:
        return nh.NemotronHConfig.from_dict(json.load(f))


def _model(toy, pattern="M", streams=STREAMS, **sizes):
    """The toy configuration with other Mamba-2 sizes, its float32
    weights, and a state of ``streams`` streams whose every leaf is
    random: a stale live state shows wherever it is read."""
    cfg = dataclasses.replace(toy, pattern=pattern, **sizes)
    params = nh.init_params(cfg, 3, jnp.float32)
    state = nh.init_state(cfg, params, streams, POSITIONS)
    rng = np.random.default_rng(7)
    state["mamba"] = [
        {name: jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)
         for name, leaf in layer.items()} for layer in state["mamba"]]
    return cfg, params, state


def _close(got, want, tol=2e-6):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("streams", [5, 6, 8])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_the_kernel_is_the_jnp_step_behind_the_restore_loop(
        toy, pattern, streams):
    """State and ``y`` equal the ``jnp`` step's on the states
    ``restored`` leaves, to float32 rounding, at one, three and four
    streams a grid step; a restoring stream's new state is the step from
    its snapshot, whatever its live state held."""
    _, _, state = _model(toy, streams=streams, **TAKEN)
    layer = state["mamba"][0]
    anew = (PATTERNS[pattern] * 2)[:streams - 1] + PATTERNS[pattern][-1:]
    restore = jnp.asarray(anew, bool)
    groups, n, lanes = layer["ssm"].shape[1:]
    assert kernels.ssm_step_streams(streams, groups * n * lanes * 4) \
        == {5: 1, 6: 3, 8: 4}[streams]
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.uniform(0.5, 1.0, (streams, groups, lanes)),
                    jnp.float32)
    dx, b, c = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                for shape in ((streams, groups, lanes),
                              (streams, groups, n), (streams, groups, n)))
    assert mamba2.step_refusal(layer) is None
    start = mamba2.restored([layer], restore)[0]["ssm"]
    want, want_y = kernels.ssm_decode_step_reference(start, a, dx, b, c)
    # what a stream that restores had live is never read
    stale = jnp.where(restore[:, None, None, None], jnp.nan, layer["ssm"])
    got, y = jax.jit(kernels.ssm_decode_step)(
        stale, layer["ssm_snap"], restore, a, dx, b, c)
    assert _close(got, want) and _close(y, want_y)
    from_snap, _ = kernels.ssm_decode_step_reference(
        layer["ssm_snap"], a, dx, b, c)
    from_live, _ = kernels.ssm_decode_step_reference(
        layer["ssm"], a, dx, b, c)
    for row, snapshot in enumerate(anew):
        assert _close(got[row], (from_snap if snapshot else from_live)[row])
        assert not _close(got[row],
                          (from_live if snapshot else from_snap)[row])


@pytest.mark.parametrize("pattern", ["first_and_last", "none"])
def test_the_kernel_at_a_state_of_two_lane_tiles(pattern):
    """`falconh1.decode4k`'s geometry, 2 groups of a state of 256 (two
    lane tiles: ``B | C`` is 4 rows of 256 a stream) over 2,048 lanes
    (16 heads of 128), 4 MiB a stream, at 8 streams: four a grid step
    take both sets of buffers to exactly the budget.  State and ``y``
    against the ``jnp`` step from each stream's own source."""
    streams, groups, n, lanes = shape = (8, 2, 256, 2048)
    assert kernels.ssm_decode_step_refusal(
        shape, {jnp.dtype(jnp.float32)}) is None
    assert kernels.ssm_step_streams(streams, groups * n * lanes * 4) == 4
    assert 2 * 4 * groups * n * lanes * 4 == kernels._SSM_VMEM_BUDGET
    rng = np.random.default_rng(5)
    live, snap = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                  for _ in range(2))
    restore = jnp.asarray((PATTERNS[pattern] * 2)[:streams], bool)
    a = jnp.asarray(rng.uniform(0.5, 1.0, (streams, groups, lanes)),
                    jnp.float32)
    dx, b, c = (jnp.asarray(rng.standard_normal(dims), jnp.float32)
                for dims in ((streams, groups, lanes),
                             (streams, groups, n), (streams, groups, n)))
    start = jnp.where(restore[:, None, None, None], snap, live)
    want, want_y = kernels.ssm_decode_step_reference(start, a, dx, b, c)
    stale = jnp.where(restore[:, None, None, None], jnp.nan, live)
    got, y = jax.jit(kernels.ssm_decode_step)(stale, snap, restore, a, dx,
                                              b, c)
    # y sums 256 products a lane: float32 rounding in another order
    assert _close(got, want) and _close(y, want_y, tol=2e-5)


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_decode_with_the_kernel_is_decode_with_the_loop(
        toy, monkeypatch, pattern):
    """Two steps of a model of three layers (``M*M``) whose Mamba-2
    state the kernel takes, against the same steps with the kernel
    refused: logits, states and counters alike; the snapshots bit for
    bit what they were; no ``ssm_restore`` in the kernel's program."""
    cfg, params, state = _model(toy, pattern="M*M", **TAKEN)
    at = np.array([3, 5, 2, 7, 4], np.int32)
    state["prompt_end"] = jnp.asarray(
        np.where(PATTERNS[pattern], at, at - 2).astype(np.int32))
    state["last"] = jnp.asarray(at - 1)
    ids = np.arange(STREAMS, dtype=np.int32) + cfg.vocab0

    def two_steps(jit):
        s, (first, _) = jit(params, state, ids, at)
        s, (second, _) = jit(params, s, ids, at + 1)
        return s, np.stack([first, second])

    def scoped():
        # a function of its own each time: jit keeps what it traced
        def step(p, s, *x):
            with jax.named_scope("nns.model"):
                return nh.decode(cfg, p, s, *x)
        return step

    kernel = jax.jit(scoped())
    text = kernel.lower(params, state, ids, at).as_text(debug_info=True)
    assert "nns.model/ssm_restore" not in text
    assert "nns.model/layer00/mamba/step" in text
    # one kernel, built once, called by both M layers
    jaxpr = str(jax.make_jaxpr(scoped())(params, state, ids, at))
    assert jaxpr.count("pallas_call") == 1
    assert jaxpr.count("name=ssm_decode_step") == 2
    got, logits = two_steps(kernel)

    monkeypatch.setattr(kernels, "ssm_decode_step_refusal",
                        lambda *a: "refused by the test")
    loop = jax.jit(scoped())
    assert "nns.model/ssm_restore" in loop.lower(
        params, state, ids, at).as_text(debug_info=True)
    want, want_logits = two_steps(loop)
    assert _close(logits, want_logits, 1e-5)
    for new, ref, old in zip(got["mamba"], want["mamba"], state["mamba"]):
        assert _close(new["ssm"], ref["ssm"], 1e-5)
        assert _close(new["conv"], ref["conv"], 1e-5)
        for name in ("ssm_snap", "conv_snap"):
            assert np.array_equal(new[name], old[name])
    assert jax.device_get(got["counters"]) == jax.device_get(want["counters"])
    assert got["counters"]["restores"] == sum(PATTERNS[pattern])
    assert got["counters"]["position_faults"] == 0


@pytest.mark.parametrize("sizes,dtype,reason", [
    (dict(TAKEN, mamba_head_dim=16), jnp.float32, "not whole lanes"),
    (dict(TAKEN, state_size=16), jnp.float32, "not whole lanes"),
    (TAKEN, jnp.bfloat16, "the recurrence is float32"),
    (dict(TAKEN, state_size=1024), jnp.float32, "2 MiB, is over 1 MiB"),
], ids=["lanes", "state", "dtype", "fast_memory"])
def test_a_refused_shape_takes_the_jnp_step(toy, monkeypatch, sizes, dtype,
                                            reason):
    """Each reason the kernel gives for not taking a layer's state, and
    the step such a state takes: the ``jnp`` one behind the restore
    loop, which starts a restoring stream from its snapshot.  (The
    budget is cut to 1 MiB here, so that a stream's 1 MiB twice is over
    it.)"""
    monkeypatch.setattr(kernels, "_SSM_VMEM_BUDGET", 1 << 20)
    cfg, params, state = _model(toy, **sizes)
    state["mamba"] = [dict(layer, ssm=layer["ssm"].astype(dtype),
                           ssm_snap=layer["ssm_snap"].astype(dtype))
                      for layer in state["mamba"]]
    assert reason in mamba2.step_refusal(state["mamba"][0])

    def gone(*a, **k):
        raise AssertionError("the kernel was asked")

    monkeypatch.setattr(kernels, "ssm_decode_step", gone)
    at = np.array([3, 5, 2, 7, 4], np.int32)
    restore = np.array(PATTERNS["first_and_last"], bool)
    state["prompt_end"] = jnp.asarray(
        np.where(restore, at, 0).astype(np.int32))
    state["last"] = jnp.asarray(at - 1)
    ids = np.arange(STREAMS, dtype=np.int32) + cfg.vocab0
    decode = jax.jit(lambda s: nh.decode(cfg, params, s, ids, at))
    got, (logits, _) = decode(state)
    # the same step from a state whose restoring streams are live at
    # their snapshots already
    mask = jnp.asarray(restore)
    fresh = dict(state, mamba=[dict(
        layer, ssm=jnp.where(mask[:, None, None, None], layer["ssm_snap"],
                             layer["ssm"]),
        conv=jnp.where(mask[:, None, None], layer["conv_snap"],
                       layer["conv"])) for layer in state["mamba"]])
    want, (want_logits, _) = decode(fresh)
    assert np.array_equal(logits, want_logits)
    assert np.array_equal(got["mamba"][0]["ssm"], want["mamba"][0]["ssm"])
    assert got["counters"]["restores"] == 3


@pytest.mark.parametrize("shape,dtypes,reason", [
    ((4, 8, 128), {jnp.float32}, "is not [streams, groups"),
    ((4, 8, 128, 512), {jnp.float32, jnp.bfloat16}, "bfloat16, float32"),
    ((4, 8, 128, 64), {jnp.float32}, "[128, 64]"),
    ((4, 8, 64, 512), {jnp.float32}, "[64, 512]"),
    ((4, 80, 128, 512), {jnp.float32}, "40 MiB, is over 32 MiB"),
], ids=["rank", "dtype", "lanes", "state", "fast_memory"])
def test_the_kernel_raises_what_its_refusal_says(shape, dtypes, reason):
    said = kernels.ssm_decode_step_refusal(shape, dtypes)
    assert reason in said
    assert kernels.ssm_decode_step_refusal(
        (128, 8, 128, 512), {jnp.dtype(jnp.float32)}) is None
    # shapes alone: nothing is allocated, the refusal comes at the trace
    dtype = min(dtypes, key=lambda d: jnp.dtype(d).itemsize)
    ssm = jax.ShapeDtypeStruct(shape, dtype)
    row = jax.ShapeDtypeStruct(shape[:2] + shape[3:], jnp.float32)
    col = jax.ShapeDtypeStruct(shape[:3], jnp.float32)
    with pytest.raises(ValueError, match="ssm_decode_step"):
        jax.eval_shape(kernels.ssm_decode_step, ssm, ssm,
                       jax.ShapeDtypeStruct(shape[:1], jnp.bool_),
                       row, row, col, col)
