"""The Mamba-2 mixer the token models share (``nemotron_h.py``, where a
layer IS the mixer; ``falcon_h1.py``, where it runs beside an attention
on one normed input): the input projection and its split, the causal
depthwise convolution, the recurrence chunked for a prefill and as one
step for a decode, the gated grouped norm and the output projection.

``[z | xBC | dt] = u W_in``; ``xBC`` through the convolution (kernel
``conv_kernel``, with bias) and SiLU, split into ``x [heads,
head_dim]``, ``B`` and ``C`` ``[groups, state]`` (head ``h`` reads
group ``h // (heads / groups)``); ``delta = softplus(dt + dt_bias)``,
``a = exp(-delta exp(A_log))``; per head ``S_t = a S_{t-1} + delta x_t
(x) B_t``, ``y_t = S_t C_t + D x_t``; ``y * silu(z)`` through an
RMSNorm over groups of ``d_inner / groups`` values, then ``W_out``.

What differs between the models is the :class:`Geometry` a model hands
every call: the sizes, and ``column_scale``, a multiplier for each of
the projection's five segments ``z | x | B | C | dt`` (Falcon-H1's
``ssm_multipliers``; None where the model has none, and then no
operation).  A scalar on the mixer's INPUT is the caller's: it scales
``u`` before it calls.  A layer's parameters are ``in_proj``,
``conv_w``, ``conv_b``, ``dt_bias``, ``A_log``, ``D``, ``gate_norm``
and ``out_proj``; its state ``{"conv", "conv_snap", "ssm",
"ssm_snap"}`` (:func:`init_state`).

**A state that no position addresses.**  A layer keeps ONE array a
stream (``ssm [streams, groups, state, heads a group x head_dim]``
float32: the state axis before a group's values, so that the decode
step's kernel finds a head's decay and ``delta x`` as rows and ``y`` as
a sum over sublanes) and the last ``conv_kernel - 1`` inputs of its
convolution (``conv [streams, conv_kernel - 1, conv_dim]``), both
overwritten by every token, and beside each its SNAPSHOT at the
stream's prompt end.  :func:`mamba_prefill` takes ``count`` (a padded
token gets ``delta = 0``, which is ``a = 1`` and no input: exact; the
convolution's state is taken at ``count``) and leaves live state and
snapshot alike; :func:`mamba_decode` starts the streams of ``restore``
from their snapshots: where the state's shape allows
(:func:`step_refusal`) ONE kernel a layer (``ops/kernels.py``
``ssm_decode_step``) that reads a stream's state once, from its
snapshot or live, updates it and reduces it to ``y`` in fast memory and
writes it once over the live state; for every other shape the ``jnp``
step from the live state behind :func:`restored`, the loop that copies
the restoring streams' snapshots first (``models/streams.py`` has the
book that says which streams restore).

Scopes, under the caller's: ``in_proj``, ``conv``, ``scan`` (prefill)
or ``step`` (decode), ``gate_norm``, ``out_proj``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

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
    """One model's Mamba-2 mixer: the published sizes, and the
    multipliers of the projection's columns where the model has them."""

    heads: int
    head_dim: int
    groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    eps: float
    column_scale: Optional[Tuple[float, float, float, float, float]] = None

    @property
    def d_inner(self) -> int:
        """Heads x head size, NOT ``expand`` x hidden."""
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """What the convolution runs over: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.groups * self.state_size

    @property
    def proj_width(self) -> int:
        """The input projection's columns: ``z | x | B | C | dt``."""
        return self.d_inner + self.conv_dim + self.heads

    def column_scales(self):
        """``column_scale`` a column of the projection, float32."""
        gn = self.groups * self.state_size
        return np.repeat(np.asarray(self.column_scale, np.float32),
                         (self.d_inner, self.d_inner, gn, gn, self.heads))


def _in_proj(geo: Geometry, p, u):
    """``z`` and ``xBC`` in the stream's type, ``dt`` in float32."""
    with jax.named_scope("in_proj"):
        zxbcdt = moe.mm(u, p["in_proj"])
        if geo.column_scale is not None:
            zxbcdt = zxbcdt * geo.column_scales()
        d, c = geo.d_inner, geo.conv_dim
        return (zxbcdt[:, :d].astype(u.dtype),
                zxbcdt[:, d:d + c].astype(u.dtype), zxbcdt[:, d + c:])


def _ssm_inputs(geo: Geometry, act):
    """The convolution's output ``[..., conv_dim]`` (float32) as ``x
    [..., groups, heads a group, head_dim]``, ``B`` and ``C`` ``[...,
    groups, state]``."""
    d, gn = geo.d_inner, geo.groups * geo.state_size
    lead = act.shape[:-1]
    x = act[..., :d].reshape(*lead, geo.groups, geo.heads // geo.groups,
                             geo.head_dim)
    b = act[..., d:d + gn].reshape(*lead, geo.groups, geo.state_size)
    c = act[..., d + gn:].reshape(*lead, geo.groups, geo.state_size)
    return x, b, c


def _by_group(geo: Geometry, per_head):
    """``[..., heads] -> [..., groups, heads a group]``."""
    return per_head.reshape(*per_head.shape[:-1], geo.groups,
                            geo.heads // geo.groups)


def _gate_norm_out(geo: Geometry, p, y, z, dtype):
    """``rms(y * silu(z))`` over groups of ``d_inner / groups`` values,
    then ``W_out``."""
    with jax.named_scope("gate_norm"):
        n = y.shape[0]
        g = (y.reshape(n, geo.d_inner)
             * jax.nn.silu(z.astype(jnp.float32))).reshape(n, geo.groups, -1)
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + geo.eps)
        g = (g.reshape(n, geo.d_inner) * p["gate_norm"]).astype(dtype)
    with jax.named_scope("out_proj"):
        return moe.mm(g, p["out_proj"]).astype(dtype)


def ssd_scan(geo: Geometry, x, b, c, delta, a_log, state):
    """The recurrence over ``T`` tokens of one stream, chunked: ``x [T,
    groups, heads a group, head_dim]``, ``b`` and ``c`` ``[T, groups,
    state]``, ``delta [T, heads]`` (0 for a token that is padding),
    ``state [heads, head_dim, state]``, all float32.  Returns ``(y [T,
    ...as x], the state after the last token)``.  Inside a chunk of
    ``chunk_size`` tokens ``y`` is the quadratic form ``(C B^T * decay)
    (delta x)``; between chunks the state is carried."""
    hp = lax.Precision.HIGHEST
    t, size = x.shape[0], geo.chunk_size
    pad = -t % size
    if pad:
        x, b, c, delta = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                          for v in (x, b, c, delta))
    n = (t + pad) // size
    g, r = geo.groups, geo.heads // geo.groups
    log_a = _by_group(geo, -delta * jnp.exp(a_log))        # [T, g, r], <= 0
    xd = (x * _by_group(geo, delta)[..., None]).reshape(
        n, size, g, r, geo.head_dim)
    b, c = (v.reshape(n, size, g, geo.state_size) for v in (b, c))
    # cs[.., l]: the log of the decay from the chunk's start through l
    cs = jnp.cumsum(log_a.reshape(n, size, g, r), axis=1).transpose(0, 2, 3, 1)
    seen = jnp.arange(size)[:, None] >= jnp.arange(size)[None, :]
    decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))                   # [n, g, r, l, s]
    cb = jnp.einsum("clgn,csgn->cgls", c, b, precision=hp)
    y = jnp.einsum("cgrls,csgrp->clgrp", cb[:, :, None] * decay, xd,
                   precision=hp)
    # what a chunk adds to the state, and what it leaves of the old one
    to_end = jnp.exp(cs[..., -1:] - cs).transpose(0, 3, 1, 2)   # [n, s, g, r]
    gained = jnp.einsum("csgrp,csgn->cgrpn", xd * to_end[..., None], b,
                        precision=hp)
    kept = jnp.exp(cs[..., -1])                                 # [n, g, r]

    def carry(s, step):
        keep, gain = step
        return keep[..., None, None] * s + gain, s

    s0 = state.reshape(g, r, geo.head_dim, geo.state_size)
    last, starts = lax.scan(carry, s0, (kept, gained))
    y = y + jnp.einsum("clgn,cgrpn->clgrp", c, starts, precision=hp) \
        * jnp.exp(cs).transpose(0, 3, 1, 2)[..., None]
    return y.reshape((t + pad,) + x.shape[1:])[:t], last.reshape(state.shape)


def mamba_prefill(geo: Geometry, p, u, st, slot, start, count):
    """A chunk ``u [C, hidden]`` of stream ``slot`` whose first token is
    at ``start`` and whose first ``count`` tokens are real.  Starts from
    zeros where ``start`` is 0, else from the slot's live state; leaves
    live state and snapshot at ``start + count`` tokens."""
    size = u.shape[0]
    z, xbc, dt = _in_proj(geo, p, u)
    fresh = start == 0
    conv0 = jnp.where(fresh, 0, st["conv"][slot])
    g, r = geo.groups, geo.heads // geo.groups
    # the filter keeps [groups, state, heads a group x head_dim]; the scan
    # carries [heads, head_dim, state]: one transposition at each end
    ssm0 = jnp.where(fresh, 0.0, st["ssm"][slot]).reshape(
        g, geo.state_size, r, geo.head_dim).transpose(0, 2, 3, 1) \
        .reshape(geo.heads, geo.head_dim, geo.state_size)
    with jax.named_scope("conv"):
        ext = jnp.concatenate([conv0, xbc])           # [K - 1 + C, conv_dim]
        act = p["conv_b"] + sum(
            ext[k:k + size].astype(jnp.float32) * p["conv_w"][k]
            for k in range(geo.conv_kernel))
        x, b, c = _ssm_inputs(geo, jax.nn.silu(act))
        # the last K - 1 REAL inputs: rows count - (K - 1) .. count - 1
        conv = lax.dynamic_slice_in_dim(ext, count, geo.conv_kernel - 1)
    with jax.named_scope("scan"):
        real = jnp.arange(size) < count
        delta = jnp.where(real[:, None],
                          jax.nn.softplus(dt + p["dt_bias"]), 0.0)
        y, ssm = ssd_scan(geo, x, b, c, delta, p["A_log"], ssm0)
        y = y + x * _by_group(geo, p["D"])[..., None]
        ssm = ssm.reshape(g, r, geo.head_dim, geo.state_size) \
            .transpose(0, 3, 1, 2).reshape(st["ssm"].shape[1:])
        st = dict(st, conv=st["conv"].at[slot].set(conv),
                  conv_snap=st["conv_snap"].at[slot].set(conv),
                  ssm=st["ssm"].at[slot].set(ssm),
                  ssm_snap=st["ssm_snap"].at[slot].set(ssm))
    return _gate_norm_out(geo, p, y, z, u.dtype), st


def restored(mamba: list, restore) -> list:
    """The layers' states with the live state of every stream of
    ``restore [B]`` overwritten by its snapshot, a stream at a time in
    place: a step in which no stream restores reads no snapshot, and one
    in which some do reads theirs alone.  The path of the shapes
    :func:`step_refusal` names: where the step is the kernel, the kernel
    chooses a stream's source and this loop is not in the program."""
    first = jnp.argsort(~restore)             # the restoring streams first

    def one(i, live):
        b = first[i]
        return [{name: lax.dynamic_update_slice_in_dim(
            now[name], lax.dynamic_slice_in_dim(st[name + "_snap"], b, 1),
            b, 0) for name in ("conv", "ssm")}
            for now, st in zip(live, mamba)]

    live = lax.fori_loop(0, jnp.sum(restore), one,
                         [{"conv": st["conv"], "ssm": st["ssm"]}
                          for st in mamba])
    return [dict(st, **now) for st, now in zip(mamba, live)]


def step_refusal(st: dict):
    """Why one layer's decode step is not ``ops/kernels.py``
    ``ssm_decode_step`` for a layer state of these shapes, or None."""
    return kernels.ssm_decode_step_refusal(
        st["ssm"].shape, {st["ssm"].dtype, st["ssm_snap"].dtype})


def mamba_decode(geo: Geometry, p, u, st, restore):
    """One token of every stream, ``u [B, hidden]``; the live state is
    overwritten, the snapshot is kept.  One algorithm, two programs,
    chosen from the state's shape (:func:`step_refusal`): the kernel,
    which starts each stream of ``restore [B]`` from its snapshot and
    every other from its live state; or, for a shape it refuses, the
    ``jnp`` step from the live state, which the caller has run through
    :func:`restored` first.  The set-up span this is traced under says
    which (``utils/profile.py`` ``note``)."""
    refusal = step_refusal(st)
    shapes = f"mamba_decode {tuple(st['ssm'].shape)} " \
             f"{st['ssm'].dtype.name}"
    _profile.note(f"{shapes}: the jnp step behind the restore loop "
                  f"({refusal})" if refusal else f"{shapes}: the kernel")
    z, xbc, dt = _in_proj(geo, p, u)
    with jax.named_scope("conv"):
        held = st["conv"] if refusal else jnp.where(
            restore[:, None, None], st["conv_snap"], st["conv"])
        window = jnp.concatenate([held, xbc[:, None]], axis=1)
        act = p["conv_b"] + jnp.sum(
            window.astype(jnp.float32) * p["conv_w"], axis=1)
        x, b, c = _ssm_inputs(geo, jax.nn.silu(act))
    with jax.named_scope("step"):
        delta = _by_group(geo, jax.nn.softplus(dt + p["dt_bias"]))
        a = jnp.exp(-delta * _by_group(geo, jnp.exp(p["A_log"])))
        # a head's decay over its lanes, beside its delta x
        lanes = (u.shape[0], geo.groups, -1)
        a = jnp.broadcast_to(a[..., None], x.shape).reshape(lanes)
        dx = (delta[..., None] * x).reshape(lanes)
        if refusal:
            ssm, y = kernels.ssm_decode_step_reference(st["ssm"], a, dx, b, c)
        else:
            ssm, y = kernels.ssm_decode_step(st["ssm"], st["ssm_snap"],
                                             restore, a, dx, b, c)
        y = y.reshape(x.shape) + x * _by_group(geo, p["D"])[..., None]
    st = dict(st, conv=window[:, 1:], ssm=ssm)
    return _gate_norm_out(geo, p, y, z, u.dtype), st


def init_state(geo: Geometry, streams: int, dtype) -> dict:
    """One layer's state: the recurrent state (float32) and the
    convolution's last inputs (``dtype``), live and as the snapshot at
    the stream's prompt end.  One buffer a leaf: the state is donated
    leaf by leaf."""
    # the state axis before a group's heads x head_dim: whole lanes of
    # [state sublanes], so that a step's decay and delta x are rows and y
    # is a sum over sublanes (ops/kernels.py ssm_decode_step)
    ssm = (streams, geo.groups, geo.state_size,
           geo.heads // geo.groups * geo.head_dim)
    # the inputs' axis before the channels': K - 1 rows of whole lanes,
    # where [.., conv_dim, K - 1] would pad every channel's values to a tile
    conv = (streams, geo.conv_kernel - 1, geo.conv_dim)
    return {"conv": jnp.zeros(conv, dtype),
            "conv_snap": jnp.zeros(conv, dtype),
            "ssm": jnp.zeros(ssm, jnp.float32),
            "ssm_snap": jnp.zeros(ssm, jnp.float32)}


def state_row_bytes(st: dict) -> int:
    """Bytes of ONE stream's ``ssm`` and ``conv`` of a layer state: what
    a step reads and writes once each."""
    return sum(st[k][0].size * st[k].dtype.itemsize for k in ("ssm", "conv"))


def param_shapes(geo: Geometry, hidden: int) -> dict:
    """A layer's ``(shape, role)`` leaves (``models/streams.py``
    ``seeded_params``), without the norm before it."""
    d, heads = geo.d_inner, geo.heads
    return {"in_proj": ((hidden, geo.proj_width), "in_proj"),
            "conv_w": ((geo.conv_kernel, geo.conv_dim), "conv_w"),
            "conv_b": ((geo.conv_dim,), "conv_b"),
            "dt_bias": ((heads,), "dt_bias"),
            "A_log": ((heads,), "A_log"), "D": ((heads,), "D"),
            "gate_norm": ((d,), "norm"),
            "out_proj": ((d, hidden), "out_proj")}


def seeded_laws() -> dict:
    """The ``special`` laws of ``streams.seeded_params`` for a layer's
    small vectors: ``delta`` at rest log-uniform in 0.001-0.1 and
    ``exp(A_log)`` in 1-2 (a head remembers tens to a thousand tokens),
    the convolution's taps N(0, 1/kernel), a small bias."""
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
