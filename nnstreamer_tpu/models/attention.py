"""Grouped-query attention parts the token models share
(``smallthinker.py``, ``nemotron_h.py``, ``exaone_moe.py``,
``falcon_h1.py``, ``phi4_flash.py``): the rotation
of q and k, a layer's K and V cache, a prefill chunk's attention and
the decode step, each on a full cache or a ring (``ops/kernels.py``
``gqa_prefill_attention`` and ``gqa_decode_attention`` where they take
the shapes, their ``jnp`` references where they refuse them) with the
rows a step fetches.
Writing a layer's rows and attending to a cache are two calls each
(:func:`write_step` / :func:`attend_step`, :func:`write_chunk` /
:func:`attend_chunk`), because a cache may have ONE writer and several
readers (``phi4_flash.py``: seven layers attend to another layer's
rows and write none); :func:`decode_step` and :func:`prefill` are their
compositions, what a layer that owns its cache calls.
How a model makes its q, k and v (norms, rotation, which layers, heads
paired into one row) stays with the model.
"""

from __future__ import annotations

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


def write_step(k, v, cache, positions):
    """One token of every stream WRITTEN: ``k`` and ``v`` ``[B, kv
    heads, d]`` into slot ``position % T`` of each stream's ``T`` (a
    ring where ``T`` is less than the stream's length; on a cache of
    every position the remainder names the position's own row).
    Returns the cache; nothing is read."""
    b, groups = k.shape[:2]
    total = cache["k"].shape[2]
    with jax.named_scope("cache_write"):
        where = (jnp.arange(b)[:, None], jnp.arange(groups)[None, :],
                 (positions % total)[:, None])
        return {"k": cache["k"].at[where].set(k.astype(cache["k"].dtype)),
                "v": cache["v"].at[where].set(v.astype(cache["v"].dtype))}


def attend_step(q, cache, positions, window: int, scale: float):
    """One token of every stream ATTENDING to a cache that holds its
    rows already, whoever wrote them (this layer's :func:`write_step`,
    or another layer's: a layer that owns no state reads its writer's):
    ``q [B, kv heads, heads a group, d]``, stream ``b`` at
    ``positions[b]``, over the slots that hold a position of ``max(0, p
    - window + 1) .. p``; ``window`` is ``T`` for a layer that sees
    every position.  Returns ``o [B, kv heads, heads a group, d]``
    float32: the kernel where it takes the shapes, its ``jnp``
    mathematics where it refuses them."""
    if kernels.gqa_decode_attention_refusal(
            q.shape, cache["k"].shape, cache["v"].shape, window) is None:
        # the call names its own scope, `.../gqa_decode_attention`
        attend = kernels.gqa_decode_attention
    else:
        attend = kernels.gqa_decode_attention_reference
    return attend(q, cache["k"], cache["v"], positions, window, scale)


def decode_step(q, k, v, cache, positions, window: int, scale: float):
    """One token of every stream: ``q [B, kv heads, heads a group, d]``,
    ``k`` and ``v`` ``[B, kv heads, d]``, stream ``b`` at
    ``positions[b]``: :func:`write_step`, then :func:`attend_step` on
    what it wrote.  Returns ``(o [B, kv heads, heads a group, d]
    float32, cache)``."""
    cache = write_step(k, v, cache, positions)
    return attend_step(q, cache, positions, window, scale), cache


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


def _write_rows(cache, slot, at, rows):
    """``rows [C, kv heads, d]`` into the slots ``at`` of stream
    ``slot`` of ``cache [streams, kv heads, T, d]``: the stream's own
    ``[kv heads, T, d]`` taken out, written and put back where it lay.
    (A scatter into the whole cache made XLA copy the cache into
    another layout and back, twice a tensor a chunk, and a kernel wants
    it in the layout it has: ``PERF.md`` section 6, PR 49.)"""
    one = lax.dynamic_index_in_dim(cache, slot, 0, keepdims=False)
    one = one.at[:, at].set(rows.transpose(1, 0, 2).astype(cache.dtype))
    return lax.dynamic_update_index_in_dim(cache, one, slot, 0)


def write_chunk(k, v, cache, slot, positions, window: int):
    """A chunk's rows WRITTEN: ``k`` and ``v`` ``[C, kv heads, d]`` of
    stream ``slot`` at ``positions [C]`` into slots ``position % T`` of
    a ring, the position itself on a cache of every position (``window
    == T``), where a row beyond the cache (a padded chunk's end) is
    dropped, not wrapped onto the stream's first rows.  Returns the
    cache; nothing is read."""
    total = cache["k"].shape[2]
    with jax.named_scope("cache_write"):
        at = positions % total if window < total else positions
        return {"k": _write_rows(cache["k"], slot, at, k),
                "v": _write_rows(cache["v"], slot, at, v)}


def attend_chunk(q, cache, slot, start, window: int, hp, scale=None):
    """A chunk ``q [C, kv heads, heads a group, d]`` of stream ``slot``
    whose first token is at ``start`` ATTENDING to a cache that holds
    the chunk's rows already, whoever wrote them: position ``p`` sees
    ``max(0, p - window + 1) .. p``, a block of keys at a time with a
    running softmax; ``scale`` is ``d^-1/2`` where none is given.
    Returns ``o [C, kv heads, heads a group, d]``.

    One algorithm, two programs, chosen from the shapes: the kernel
    (``ops/kernels.py`` ``gqa_prefill_attention``: a query block's
    scores never leave fast memory) wherever its refusal has nothing to
    say, its reference (XLA's own ``while`` over the key blocks)
    everywhere else.  The set-up span this is traced under says which
    (``utils/profile.py`` ``note``)."""
    refusal = kernels.gqa_prefill_attention_refusal(
        q.shape, cache["k"].shape, cache["v"].shape, window,
        {q.dtype, cache["k"].dtype, cache["v"].dtype})
    shapes = f"prefill {q.shape[0]} x {q.shape[1]} x {q.shape[2]} heads, " \
             f"a window of {window} on {tuple(cache['k'].shape)} " \
             f"{cache['k'].dtype.name}"
    _profile.note(f"{shapes}: the jnp loop ({refusal})" if refusal
                  else f"{shapes}: the kernel")
    if scale is None:
        scale = q.shape[3] ** -0.5
    if refusal:
        return kernels.gqa_prefill_attention_reference(
            q, cache["k"], cache["v"], slot, start, window, scale,
            precision=hp)
    # the call names its own scope, `.../gqa_prefill_attention`
    return kernels.gqa_prefill_attention(q, cache["k"], cache["v"], slot,
                                         start, window, scale)


def prefill(qkv, size: int, cache, slot, start, window: int, hp,
            scale=None):
    """A chunk of ``size`` tokens of stream ``slot`` whose first token
    is at ``start``, on one layer's cache (``{"k", "v"}`` of ``[streams,
    kv heads, T, d]``): a ring where ``T`` is less than the stream's
    length (``T >= window + size - 1``, so every position a query of the
    chunk sees is still there once the chunk is written), a cache of
    every position where it is not; ``window`` is ``T`` for a layer that
    sees every position, as :func:`decode_step` has it.
    ``qkv(positions)`` is the model's own ``(q [C, kv heads, heads a
    group, d], k [C, kv heads, d], v)`` of the chunk.
    :func:`write_chunk`, then :func:`attend_chunk` on what it wrote.
    Returns ``(o [C, kv heads, heads a group, d], cache)``;
    :func:`heads_out` rounds ``o`` to the model's type first thing.  A
    padded token's row lies beyond the prompt and is overwritten by the
    answer before any step reads it."""
    positions = start + jnp.arange(size, dtype=jnp.int32)
    q, k, v = qkv(positions)
    cache = write_chunk(k, v, cache, slot, positions, window)
    return attend_chunk(q, cache, slot, start, window, hp, scale), cache
