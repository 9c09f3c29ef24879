"""Grouped-query attention parts the token models share
(``smallthinker.py``, ``nemotron_h.py``, ``exaone_moe.py``,
``falcon_h1.py``): the rotation
of q and k, a layer's K and V cache, a prefill chunk's attention over a
stream's FULL cache, and the decode step on a full cache or a ring
(``ops/kernels.py`` ``gqa_decode_attention`` where it takes the shapes,
its ``jnp`` reference where it refuses them) with the rows it fetches.
How a model makes its q, k and v (norms, rotation, which layers) stays
with the model.
"""

from __future__ import annotations

import math

import numpy as np

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover
    jax = jnp = lax = None

from ..ops import kernels
from . import moe

NEG = -1e30


def rope_angles(theta: float, head_dim: int, positions):
    """``(cos, sin)`` of ``positions`` for pairs ``(i, i + head_dim/2)``,
    no scaling: ``[..., head_dim / 2]`` float32."""
    half = head_dim // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    angle = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angle), jnp.sin(angle)


def rope(x, cos, sin):
    """Rotate pairs ``(i, i + d/2)`` of the last axis; ``cos`` and
    ``sin`` broadcast against ``x[..., :d/2]``."""
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def kv_cache(streams: int, kv_heads: int, total: int, head_dim: int,
             dtype) -> dict:
    """One layer's K and V, ``[streams, kv heads, total, head_dim]``
    zeros (positions second to last: a product over them reads whole
    rows and XLA adds no transposed copy).  One buffer a leaf: the
    state is donated leaf by leaf."""
    shape = (streams, kv_heads, int(total), head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def heads_out(p, o, dtype):
    """Heads side by side through ``W_o``."""
    return moe.mm(o.astype(dtype).reshape(o.shape[0], -1), p["o"]) \
        .astype(dtype)


def decode_step(q, k, v, cache, positions, window: int, scale: float):
    """One token of every stream: ``q [B, kv heads, heads a group, d]``,
    ``k`` and ``v`` ``[B, kv heads, d]``, stream ``b`` at
    ``positions[b]``.  Writes each stream's K and V row in slot
    ``position % T`` of its ``T`` (a ring where ``T`` is less than the
    stream's length; on a cache of every position the remainder names
    the position's own row), then attends over the slots that hold a
    position of ``max(0, p - window + 1) .. p``: ``window`` is ``T`` for
    a layer that sees every position.  Returns ``(o [B, kv heads, heads
    a group, d] float32, cache)``."""
    b, groups = q.shape[:2]
    total = cache["k"].shape[2]
    with jax.named_scope("cache_write"):
        where = (jnp.arange(b)[:, None], jnp.arange(groups)[None, :],
                 (positions % total)[:, None])
        cache = {"k": cache["k"].at[where].set(k.astype(cache["k"].dtype)),
                 "v": cache["v"].at[where].set(v.astype(cache["v"].dtype))}
    if kernels.gqa_decode_attention_refusal(
            q.shape, cache["k"].shape, cache["v"].shape, window) is None:
        # the call names its own scope, `.../gqa_decode_attention`
        attend = kernels.gqa_decode_attention
    else:
        attend = kernels.gqa_decode_attention_reference
    return attend(q, cache["k"], cache["v"], positions, window, scale), cache


def decode_rows_fetched(caches, per_group: int, positions, window=None):
    """Rows of ONE of ``caches`` (the caches of one kind of layer, all
    of one shape; a token's K and V count as one row) that
    :func:`decode_step` reads in for streams at ``positions``
    (``ops/kernels.py`` ``gqa_decode_rows_fetched``: the kernel's live
    cells, or the whole cache where it refuses the shape); ``window``
    None is a layer that sees every position; 0 where the model has no
    layer of the kind."""
    if not caches:
        return 0
    b, groups, total, d = shape = caches[0]["k"].shape
    return kernels.gqa_decode_rows_fetched(
        (b, groups, per_group, d), shape, positions, window or total)


def full_prefill(qkv, size: int, cache, slot, start, hp,
                 key_block: int = 1024):
    """A chunk of ``size`` tokens of stream ``slot`` whose first token
    is at ``start``, on a cache that holds EVERY position (``{"k",
    "v"}`` of ``[streams, kv heads, positions, d]``).  ``qkv(positions)``
    is the model's own ``(q [C, kv heads, heads a group, d], k [C, kv
    heads, d], v)`` of the chunk.  Writes the chunk's K and V rows, then
    attends to the stream's cache ``key_block`` rows at a time with a
    running softmax, position ``p`` seeing ``0 .. p``.  Returns ``(o [C,
    kv heads, heads a group, d] float32, cache)``.  A padded token's row
    lies beyond the prompt and is overwritten by the answer before any
    step reads it."""
    total = cache["k"].shape[2]
    positions = start + jnp.arange(size, dtype=jnp.int32)
    q, k, v = qkv(positions)
    groups, per, d = q.shape[1:]
    with jax.named_scope("cache_write"):
        cache = {
            "k": cache["k"].at[slot, :, positions].set(
                k.astype(cache["k"].dtype)),
            "v": cache["v"].at[slot, :, positions].set(
                v.astype(cache["v"].dtype))}
    kb = math.gcd(int(key_block), total)
    scale = d ** -0.5

    def body(j, carry):
        m, l, acc = carry
        kj, vj = (lax.dynamic_slice(
            cache[name], (slot, 0, j * kb, 0),
            (1, groups, kb, d))[0].astype(q.dtype)
            for name in ("k", "v"))
        s = jnp.einsum("cgqd,gkd->gqck", q, kj,
                       preferred_element_type=jnp.float32, precision=hp)
        keys = j * kb + jnp.arange(kb, dtype=jnp.int32)
        seen = keys[None, :] <= positions[:, None]
        s = jnp.where(seen[None, None], s * scale, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(s - m_new[..., None])
        l = l * alpha + prob.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "gqck,gkd->gqcd", prob.astype(q.dtype), vj,
            preferred_element_type=jnp.float32, precision=hp)
        return m_new, l, acc

    # the first block holds position 0, which every query sees: a later
    # block whose keys are all masked for a query adds nothing to it
    blocks = jnp.minimum(total // kb, (start + size - 1 + kb) // kb)
    m0 = jnp.full((groups, per, size), NEG, jnp.float32)
    _, l, acc = lax.fori_loop(
        0, blocks, body,
        (m0, jnp.zeros_like(m0), jnp.zeros(m0.shape + (d,), jnp.float32)))
    return (acc / l[..., None]).transpose(2, 0, 1, 3), cache   # [C, g, q, d]
