"""The Mamba-1 mixer of a token model (``phi4_flash.py``): the input
projection and its split, the causal depthwise convolution, the
projection that makes every token's step size and its ``B`` and ``C``,
the selective recurrence over a prefill chunk and as one step of a
decode, the gate and the output projection.

``[s | z] = u W_in``; ``c_t = silu(conv(s)_t + b_c)`` (kernel
``conv_kernel``, causal, depthwise); ``[delta' | B_t | C_t] = c_t W_x``
(``dt_rank | state | state``); ``delta_t = softplus(delta' W_delta +
b_delta)`` per CHANNEL; ``A = -exp(A_log)``; ``h_t = exp(delta_t A)
h_{t-1} + (delta_t c_t) (x) B_t`` per (channel, state); ``y_t = h_t C_t
+ D c_t``; ``(y_t * silu(z_t)) W_out``.

What differs from ``mamba2.py`` is the decay: per (channel, state), not
a scalar a head, so a chunk has no matrix form and a step no rows of one
decay: a prefill chunk is ``ops/kernels.py`` ``selective_scan`` (a block
of channels' state kept in registers over the chunk) where its refusal
has nothing to say, a ``lax.scan`` a token where it has; a decode step
is XLA's own fusion over ``[streams, state, channels]`` (the state is
the least of that model's step).  Both mixers' calls return ``y`` beside
the mixer's output: a later layer of the model may read the scan's
output of the SAME token (a gated memory unit).

**A state that no position addresses**, as ``mamba2.py`` keeps it: a
layer's ``{"conv", "conv_snap", "ssm", "ssm_snap"}``, ``ssm [streams,
state, channels]`` float32 (the state axis before the channels: the
channels fill the lanes) and ``conv [streams, conv_kernel - 1,
channels]``, live and as the snapshot at the stream's prompt end.
:func:`mamba_prefill` takes ``count`` (a padded token gets ``delta =
0``: decay 1 and no input, exact) and leaves live state and snapshot
alike; :func:`mamba_decode` steps the live state, which the caller has
run through ``mamba2.restored`` (the names are the same, so the loop
that copies the restoring streams' snapshots serves both mixers;
``models/streams.py`` has the book that says which streams restore).

Scopes, under the caller's: ``in_proj``, ``conv``, ``x_proj``, ``scan``
(prefill) or ``step`` (decode), ``gate``, ``out_proj``.
"""

from __future__ import annotations

import dataclasses
import math

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover
    jax = jnp = lax = None

from ..ops import kernels
from ..utils import profile as _profile
from . import moe
from . import streams as stream


@dataclasses.dataclass(frozen=True)
class Geometry:
    """One model's Mamba-1 mixer."""

    d_inner: int
    state_size: int
    conv_kernel: int
    dt_rank: int


def _in_proj(geo: Geometry, p, u):
    """``s`` and ``z`` in the stream's type."""
    with jax.named_scope("in_proj"):
        sz = moe.mm(u, p["in_proj"]).astype(u.dtype)
        return sz[:, :geo.d_inner], sz[:, geo.d_inner:]


def _steps(geo: Geometry, p, act, dtype):
    """``(delta [N, channels], B [N, state], C [N, state])`` float32 of
    the convolution's output ``act [N, channels]`` (float32; rounded to
    the stream's type for the products)."""
    with jax.named_scope("x_proj"):
        dbc = moe.mm(act.astype(dtype), p["x_proj"])
        r, n = geo.dt_rank, geo.state_size
        delta = jax.nn.softplus(
            moe.mm(dbc[:, :r].astype(dtype), p["dt_proj"]) + p["dt_bias"])
        return delta, dbc[:, r:r + n], dbc[:, r + n:]


def _gate_out(p, y, z, dtype):
    with jax.named_scope("gate"):
        g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    with jax.named_scope("out_proj"):
        return moe.mm(g, p["out_proj"]).astype(dtype)


def scan_refusal(tokens: int, st: dict):
    """Why a chunk of ``tokens`` on this layer state is not
    ``ops/kernels.py`` ``selective_scan``, or None."""
    return kernels.selective_scan_refusal(tokens, st["ssm"].shape[1:],
                                          {st["ssm"].dtype})


def mamba_prefill(geo: Geometry, p, u, st, slot, start, count):
    """A chunk ``u [C, hidden]`` of stream ``slot`` whose first token is
    at ``start`` and whose first ``count`` tokens are real.  Starts from
    zeros where ``start`` is 0, else from the slot's live state; leaves
    live state and snapshot at ``start + count`` tokens.  Returns ``(the
    mixer's output [C, hidden], y [C, channels] float32, the state)``.
    The set-up span this is traced under says which scan the chunk took
    (``utils/profile.py`` ``note``)."""
    size = u.shape[0]
    s, z = _in_proj(geo, p, u)
    fresh = start == 0
    conv0 = jnp.where(fresh, 0, st["conv"][slot])
    ssm0 = jnp.where(fresh, 0.0, st["ssm"][slot])
    with jax.named_scope("conv"):
        ext = jnp.concatenate([conv0, s])             # [K - 1 + C, channels]
        act = jax.nn.silu(p["conv_b"] + sum(
            ext[k:k + size].astype(jnp.float32) * p["conv_w"][k]
            for k in range(geo.conv_kernel)))
        # the last K - 1 REAL inputs: rows count - (K - 1) .. count - 1
        conv = lax.dynamic_slice_in_dim(ext, count, geo.conv_kernel - 1)
    delta, b, c = _steps(geo, p, act, u.dtype)
    refusal = scan_refusal(size, st)
    shapes = f"mamba1 scan of {size} tokens on {tuple(st['ssm'].shape[1:])}"
    _profile.note(f"{shapes}: a lax.scan a token ({refusal})" if refusal
                  else f"{shapes}: the kernel")
    with jax.named_scope("scan"):
        delta = jnp.where((jnp.arange(size) < count)[:, None], delta, 0.0)
        scan = kernels.selective_scan_reference if refusal \
            else kernels.selective_scan
        y, ssm = scan(delta, delta * act, b, c, -jnp.exp(p["A_log"]), ssm0)
        y = y + act * p["D"]
        st = dict(st, conv=st["conv"].at[slot].set(conv),
                  conv_snap=st["conv_snap"].at[slot].set(conv),
                  ssm=st["ssm"].at[slot].set(ssm),
                  ssm_snap=st["ssm_snap"].at[slot].set(ssm))
    return _gate_out(p, y, z, u.dtype), y, st


def mamba_decode(geo: Geometry, p, u, st):
    """One token of every stream, ``u [B, hidden]``, from the LIVE state
    (the caller has restored); the live state is overwritten, the
    snapshot is kept.  XLA's own fusion: it reads ``ssm`` once and
    writes it once.  Returns ``(the mixer's output, y [B, channels]
    float32, the state)``."""
    s, z = _in_proj(geo, p, u)
    with jax.named_scope("conv"):
        window = jnp.concatenate([st["conv"], s[:, None]], axis=1)
        act = jax.nn.silu(p["conv_b"] + jnp.sum(
            window.astype(jnp.float32) * p["conv_w"], axis=1))
    delta, b, c = _steps(geo, p, act, u.dtype)
    with jax.named_scope("step"):
        ssm = jnp.exp(delta[:, None, :] * -jnp.exp(p["A_log"])) * st["ssm"] \
            + b[:, :, None] * (delta * act)[:, None, :]
        y = jnp.sum(ssm * c[:, :, None], axis=1) + act * p["D"]
    st = dict(st, conv=window[:, 1:], ssm=ssm)
    return _gate_out(p, y, z, u.dtype), y, st


def init_state(geo: Geometry, streams: int, dtype) -> dict:
    """One layer's state: the recurrent state (float32, the state axis
    before the channels) and the convolution's last inputs (``dtype``,
    the inputs' axis before the channels), live and as the snapshot at
    the stream's prompt end.  One buffer a leaf: the state is donated
    leaf by leaf."""
    ssm = (streams, geo.state_size, geo.d_inner)
    conv = (streams, geo.conv_kernel - 1, geo.d_inner)
    return {"conv": jnp.zeros(conv, dtype),
            "conv_snap": jnp.zeros(conv, dtype),
            "ssm": jnp.zeros(ssm, jnp.float32),
            "ssm_snap": jnp.zeros(ssm, jnp.float32)}


def state_row_bytes(st: dict) -> int:
    """Bytes of ONE stream's ``ssm`` and ``conv`` of a layer state: what
    a step reads and writes once each."""
    return sum(math.prod(st[k].shape[1:]) * st[k].dtype.itemsize
               for k in ("ssm", "conv"))


def param_shapes(geo: Geometry, hidden: int) -> dict:
    """A layer's ``(shape, role)`` leaves (``models/streams.py``
    ``seeded_params``), without the norm before it.  ``A_log`` lies as
    the state does, ``[state, channels]``."""
    d, n, r = geo.d_inner, geo.state_size, geo.dt_rank
    return {"in_proj": ((hidden, 2 * d), "in_proj"),
            "conv_w": ((geo.conv_kernel, d), "conv_w"),
            "conv_b": ((d,), "conv_b"),
            "x_proj": ((d, r + 2 * n), "x_proj"),
            "dt_proj": ((r, d), "dt_proj"),
            "dt_bias": ((d,), "dt_bias"),
            "A_log": ((n, d), "A_log"), "D": ((d,), "D"),
            "out_proj": ((d, hidden), "out_proj")}


def seeded_laws() -> dict:
    """The ``special`` laws of ``streams.seeded_params`` for a layer's
    small leaves: ``delta`` at rest log-uniform in 0.001-0.1,
    ``exp(A_log)`` in 1-2 (a channel remembers tens to a thousand
    tokens), the convolution's taps N(0, 1/kernel), a small bias."""
    def dt_bias(k, shape):
        rest = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return rest + jnp.log(-jnp.expm1(-rest))            # softplus^-1

    return {"dt_bias": dt_bias,
            "A_log": lambda k, shape: jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 2.0)),
            "conv_w": lambda k, shape: jax.random.normal(
                k, shape, jnp.float32) * shape[0] ** -0.5,
            "conv_b": stream.normal_vector(0.1)}
