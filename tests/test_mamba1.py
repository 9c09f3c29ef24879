"""``models/mamba1.py`` and ``ops/kernels.py`` ``selective_scan`` on the
CPU (the kernel interpreted): the chunk form against the token-by-token
recurrence, a ragged ``count``, chunks that follow one another, the
snapshot a prefill leaves and the restore a decode step starts from."""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models import mamba1, mamba2  # noqa: E402
from nnstreamer_tpu.models import streams as stream  # noqa: E402
from nnstreamer_tpu.ops import kernels  # noqa: E402

HIDDEN = 64
#: whole lanes of channels (the kernel takes it) and not (the lax.scan)
GEOMETRIES = {"kernel": mamba1.Geometry(d_inner=256, state_size=16,
                                        conv_kernel=4, dt_rank=8),
              "scan": mamba1.Geometry(d_inner=96, state_size=8,
                                      conv_kernel=4, dt_rank=4)}


def _operands(rng, tokens, channels, state, real=None):
    delta = rng.uniform(1e-3, 0.3, (tokens, channels))
    if real is not None:
        delta[real:] = 0.0
    x = rng.normal(size=(tokens, channels))
    f32 = lambda a: jnp.asarray(a, jnp.float32)              # noqa: E731
    return (f32(delta), f32(delta * x), f32(rng.normal(size=(tokens, state))),
            f32(rng.normal(size=(tokens, state))),
            f32(-rng.uniform(1, 16, (state, channels))),
            f32(rng.normal(size=(state, channels))))


@pytest.mark.parametrize("tokens,channels,state", [
    (64, 256, 16), (256, 512, 16), (512, 128, 8), (8, 640, 16)])
def test_the_kernel_is_the_recurrence_a_token_at_a_time(tokens, channels,
                                                        state):
    args = _operands(np.random.default_rng(tokens), tokens, channels, state)
    assert kernels.selective_scan_refusal(tokens, (state, channels),
                                          {jnp.dtype(jnp.float32)}) is None
    y, h = kernels.selective_scan(*args)
    want_y, want_h = kernels.selective_scan_reference(*args)
    assert np.allclose(y, want_y, atol=2e-5, rtol=1e-5)
    assert np.allclose(h, want_h, atol=2e-5, rtol=1e-5)


def test_padded_tokens_leave_the_state_exactly_as_it_was():
    args = _operands(np.random.default_rng(1), 64, 256, 16, real=41)
    _y, h = kernels.selective_scan(*args)
    short = tuple(a[:48] if a.shape[0] == 64 else a for a in args)
    _y, h48 = kernels.selective_scan(*short)
    assert np.array_equal(h, h48)            # 41 real of 48, or of 64


@pytest.mark.parametrize("why,tokens,shape,dtypes", [
    ("whole tiles of 8 x 128", 64, (16, 96), {jnp.dtype(jnp.float32)}),
    ("whole tiles of 8 x 128", 64, (12, 256), {jnp.dtype(jnp.float32)}),
    ("whole tiles of 8", 60, (16, 256), {jnp.dtype(jnp.float32)}),
    ("float32", 64, (16, 256), {jnp.dtype(jnp.bfloat16)}),
    ("[state, channels]", 64, (2, 16, 256), {jnp.dtype(jnp.float32)})])
def test_the_kernel_refuses_what_it_cannot_take(why, tokens, shape, dtypes):
    assert why in kernels.selective_scan_refusal(tokens, shape, dtypes)
    if len(shape) == 2 and why != "float32":
        args = _operands(np.random.default_rng(0), tokens, shape[1], shape[0])
        with pytest.raises(ValueError, match="selective_scan"):
            kernels.selective_scan(*args)


def _layer(geo, dtype=jnp.float32):
    return stream.seeded_params(
        mamba1.param_shapes(geo, HIDDEN), 3, dtype, ones=("D",),
        halved=("out_proj",), special=mamba1.seeded_laws())


def _token_by_token(geo, p, u):
    """``(mixer output, y, conv inputs, h)`` after every token of ``u``:
    the module's equations, one token at a time."""
    d, n, r, k = geo.d_inner, geo.state_size, geo.dt_rank, geo.conv_kernel
    sz = u @ p["in_proj"]
    s, z = sz[:, :d], sz[:, d:]
    past = np.concatenate([np.zeros((k - 1, d), np.float32), s])
    h = np.zeros((n, d), np.float32)
    a = -np.exp(p["A_log"])
    outs, ys = [], []
    for t in range(len(u)):
        c = past[t:t + k]
        c = np.asarray(jax.nn.silu(p["conv_b"] + (c * p["conv_w"]).sum(0)))
        dbc = c @ p["x_proj"]
        delta = np.asarray(jax.nn.softplus(dbc[:r] @ p["dt_proj"]
                                           + p["dt_bias"]))
        h = np.exp(delta[None] * a) * h + dbc[r:r + n, None] * (delta * c)
        y = (h * dbc[r + n:, None]).sum(0) + p["D"] * c
        ys.append(y)
        outs.append((y * np.asarray(jax.nn.silu(z[t]))) @ p["out_proj"])
    return np.stack(outs), np.stack(ys), past[len(u):], h


@pytest.mark.parametrize("which", list(GEOMETRIES))
def test_prefill_chunks_then_decode_follow_the_recurrence(which):
    """Two chunks of 16 (the second 11 real tokens of 16), then three
    decode steps, against 30 tokens one at a time: outputs, ``y``, the
    convolution's inputs and the state; the snapshot is the prompt's
    end, and a restore starts the next pass from it."""
    geo = GEOMETRIES[which]
    p = _layer(geo)
    rng = np.random.default_rng(9)
    u = jnp.asarray(rng.normal(size=(30, HIDDEN)), jnp.float32)
    host = jax.tree_util.tree_map(np.asarray, p)
    out, ys, conv, h = _token_by_token(geo, host, np.asarray(u[:27]))
    st = mamba1.init_state(geo, 3, jnp.float32)
    assert (mamba1.scan_refusal(16, st) is None) == (which == "kernel")
    slot = jnp.int32(1)
    o1, y1, st = mamba1.mamba_prefill(geo, p, u[:16], st, slot, jnp.int32(0),
                                      jnp.int32(16))
    padded = jnp.concatenate([u[16:27], jnp.zeros((5, HIDDEN))])
    o2, y2, st = mamba1.mamba_prefill(geo, p, padded, st, slot,
                                      jnp.int32(16), jnp.int32(11))
    assert np.allclose(np.concatenate([o1, o2[:11]]), out, atol=2e-4)
    assert np.allclose(np.concatenate([y1, y2[:11]]), ys, atol=2e-4)
    assert np.allclose(st["ssm"][1], h, atol=2e-5)
    assert np.allclose(st["conv"][1], conv, atol=1e-6)
    for name in ("ssm", "conv"):
        assert np.array_equal(st[name], st[name + "_snap"])
        assert not np.asarray(st[name][0]).any()         # another stream's
    # three decode steps of all three streams; stream 1 goes on
    full, ys_full, _conv, h_full = _token_by_token(geo, host, np.asarray(u))
    snap = st["ssm_snap"]
    for t in range(27, 30):
        o, y, st = mamba1.mamba_decode(geo, p, jnp.tile(u[t][None], (3, 1)),
                                       st)
        assert np.allclose(o[1], full[t], atol=2e-4)
        assert np.allclose(y[1], ys_full[t], atol=2e-4)
    assert np.allclose(st["ssm"][1], h_full, atol=2e-5)
    assert np.array_equal(st["ssm_snap"], snap)          # only read
    # the next pass: stream 1 restores, the others go on
    [back] = mamba2.restored([st], jnp.asarray([False, True, False]))
    assert np.array_equal(back["ssm"][1], snap[1])
    assert np.array_equal(back["conv"][1], st["conv_snap"][1])
    assert np.array_equal(back["ssm"][0], st["ssm"][0])
    o, _y, _st = mamba1.mamba_decode(geo, p, jnp.tile(u[27][None], (3, 1)),
                                     back)
    assert np.allclose(o[1], full[27], atol=2e-4)


def test_a_chunk_from_zero_ignores_what_the_slot_held():
    geo = GEOMETRIES["kernel"]
    p = _layer(geo)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(16, HIDDEN)),
                    jnp.float32)
    clean = mamba1.init_state(geo, 2, jnp.float32)
    dirty = jax.tree_util.tree_map(lambda a: a + 1.0, clean)
    args = (jnp.int32(0), jnp.int32(0), jnp.int32(16))
    o1, _y, s1 = mamba1.mamba_prefill(geo, p, u, clean, *args)
    o2, _y, s2 = mamba1.mamba_prefill(geo, p, u, dirty, *args)
    assert np.array_equal(o1, o2)
    assert np.array_equal(s1["ssm"][0], s2["ssm"][0])


def test_state_row_bytes_and_shapes():
    geo = mamba1.Geometry(d_inner=5120, state_size=16, conv_kernel=4,
                          dt_rank=160)
    st = jax.eval_shape(lambda: mamba1.init_state(geo, 32, jnp.bfloat16))
    assert st["ssm"].shape == (32, 16, 5120) and st["ssm"].dtype == jnp.float32
    assert st["conv"].shape == (32, 3, 5120)
    assert mamba1.state_row_bytes(st) == 16 * 5120 * 4 + 3 * 5120 * 2
    sizes = mamba1.param_shapes(geo, 2560)
    assert round(sum(int(np.prod(s)) for s, _ in sizes.values()) / 1e6, 1) \
        == 41.2
