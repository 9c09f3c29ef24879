"""A prefill chunk's grouped-query attention as a kernel
(``ops/kernels.py`` ``gqa_prefill_attention``) against its reference,
XLA's own loop over the key blocks, and against the plain softmax over
the positions a query sees, interpreted on the CPU: on a cache of every
position and on a ring, at chunk starts before, at and past the ring's
wrap, aligned to the key blocks and not, with a window shorter than,
equal to and longer than what is written so far; for 5, 7, 8 and 16
heads a group; what lies in a block no query sees is not read; which
shapes the kernel refuses, that ``models/attention.py`` ``prefill``
takes the loop for those, and that it says which it took.  No number
here is a rate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import attention
from nnstreamer_tpu.ops import kernels
from nnstreamer_tpu.utils import profile

D = 128
SCALE = D ** -0.5
HIGHEST = jax.lax.Precision.HIGHEST


def _history(c, start, total, window, groups=2, per=3, dtype=jnp.float32,
             dead=np.nan, kb=None):
    """A chunk's queries and the caches of 3 streams once the chunk is
    written: stream 1's slot ``s`` holds the newest position up to the
    chunk's last that falls on it; a key block none of whose slots holds
    a position a query of the chunk sees, and every other stream, hold
    ``dead``.  Also the history's keys and values by position."""
    keys = jax.random.split(jax.random.PRNGKey(c + start + total + per), 3)
    last = start + c - 1
    q = jax.random.normal(keys[0], (c, groups, per, D))
    k_at = jax.random.normal(keys[1], (last + 1, groups, D))
    v_at = jax.random.normal(keys[2], (last + 1, groups, D))
    slots = np.arange(total)
    held = last - (last - slots) % total
    live = (held >= max(0, start - window + 1))
    if kb:                               # a block with a live slot is read
        live = np.repeat(live.reshape(-1, kb).any(axis=1), kb)
    caches = []
    for rows in (k_at, v_at):
        one = jnp.where(held[None, :, None] >= 0,
                        rows[np.maximum(held, 0)].transpose(1, 0, 2), 0.5)
        one = jnp.where(live[None, :, None], one, dead)
        caches.append(jnp.stack([jnp.full_like(one, dead), one,
                                 jnp.full_like(one, dead)]).astype(dtype))
    return (q.astype(dtype), *caches, jnp.int32(1), jnp.int32(start)), \
        (k_at, v_at)


def _plain(q, k_at, v_at, start, window):
    """Each query's softmax over the positions it sees, in float64."""
    q, k_at, v_at = (np.asarray(a, np.float64) for a in (q, k_at, v_at))
    out = np.zeros(q.shape)
    for r in range(q.shape[0]):
        p = start + r
        seen = slice(max(0, p - window + 1), p + 1)
        s = np.einsum("gqd,kgd->gqk", q[r], k_at[seen]) * SCALE
        prob = np.exp(s - s.max(axis=-1, keepdims=True))
        prob /= prob.sum(axis=-1, keepdims=True)
        out[r] = np.einsum("gqk,kgd->gqd", prob, v_at[seen])
    return out


def _close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


#: start, positions of the cache, window, key block, query rows, heads a
#: grid step: a chunk of 256
CASES = {
    # a cache of every position: window == T
    "full, start": (0, 1024, 1024, 256, 64, 3),
    "full, later chunk, diagonal inside a key block": (512, 1024, 1024,
                                                       512, 64, 1),
    "full, one query block": (512, 1024, 1024, 256, 256, 3),
    "full, a start off the blocks": (100, 1024, 1024, 256, 64, 3),
    "full, the chunk ends the cache": (768, 1024, 1024, 128, 128, 3),
    # a ring of 768 behind a window of 512
    "ring, start: window longer than what is written": (0, 768, 512, 128,
                                                        64, 3),
    "ring, window equal to what is written": (256, 768, 512, 128, 64, 3),
    "ring, the chunk ends at the ring's end": (512, 768, 512, 128, 64, 1),
    "ring, the chunk starts at the wrap": (768, 768, 512, 128, 64, 3),
    "ring, past the wrap": (1024, 768, 512, 256, 64, 3),
    "ring, the wrap inside the chunk": (700, 768, 512, 128, 64, 3),
    "ring, before the wrap, off the blocks": (333, 768, 512, 128, 128, 3),
    "ring, laps later, off the blocks": (1999, 768, 512, 256, 128, 3),
    "ring, one key block": (1999, 768, 512, 768, 64, 3),
    # both edges inside one (query block, key block) pair
    "a window shorter than a key block": (1500, 1024, 100, 128, 64, 3),
    "a ring far longer than its window": (1500, 2048, 100, 128, 64, 1),
    "a ring far longer, wrapped, off the blocks": (3001, 2048, 300, 256,
                                                  128, 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_loop(case):
    """The kernel's ``[C, kv heads, heads a group, d]`` is the
    reference's to float32 rounding and both are the plain softmax: the
    cases hold key blocks whose later query blocks see them whole, ones
    the diagonal or the window's edge pass through (or both), ones that
    lie wholly after a query block or wholly before its window (which
    the kernel skips), and the block inside which the ring's newest
    position falls; every block no query sees holds NaN, as does every
    other stream."""
    start, total, window, kb, tq, heads = CASES[case]
    operands, (k_at, v_at) = _history(256, start, total, window, kb=kb)
    # the loop reads every block up to the chunk's end: no NaN for it
    want = kernels.gqa_prefill_attention_reference(
        *_history(256, start, total, window, kb=kb, dead=0.5)[0], window,
        SCALE, key_block=kb, precision=HIGHEST)
    got = kernels._gqa_prefill(*operands, window, SCALE, heads, tq, kb)
    assert got.shape == (256, 2, 3, D) and got.dtype == jnp.float32
    _close(got, want, 5e-6)
    _close(want, _plain(operands[0], k_at, v_at, start, window), 5e-6)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("per", [5, 7, 8, 16])
@pytest.mark.parametrize("total,window,start", [
    (1024, 1024, 512), (768, 512, 1024)], ids=["full", "ring"])
def test_the_kernel_as_a_caller_calls_it(total, window, start, per, dtype,
                                         tol):
    """:func:`gqa_prefill_attention` chooses its own tiling (the key
    block from the cache, the heads a step from the group: 5, 7, 8 and,
    of 16, 8), in bf16 as the cells run it and in float32."""
    operands, _ = _history(256, start, total, window, per=per, dtype=dtype,
                           dead=1e4)
    assert kernels.gqa_prefill_attention_refusal(
        operands[0].shape, operands[1].shape, operands[2].shape, window,
        {jnp.dtype(dtype)}) is None
    assert kernels.gqa_prefill_tiles(256, per, D, dtype) \
        == (min(per, 8), 256)
    got = kernels.gqa_prefill_attention(*operands, window, SCALE)
    assert got.shape == (256, 2, per, D) and got.dtype == dtype
    hp = HIGHEST if dtype == jnp.float32 else None
    _close(got, kernels.gqa_prefill_attention_reference(
        *operands, window, SCALE, precision=hp), tol)


def test_a_padded_chunk_that_overhangs_a_cache_of_every_position():
    """A last chunk whose padded end lies beyond a cache of every
    position: those rows were dropped, not wrapped, so the cache holds
    ``0 .. T - 1`` and the real queries see what they would have."""
    total, start, c = 384, 256, 256
    operands, (k_at, v_at) = _history(total - start, start, total, total)
    q = jnp.concatenate([operands[0], operands[0][:start + c - total]])
    want = kernels.gqa_prefill_attention_reference(
        q, *operands[1:], total, SCALE, precision=HIGHEST)
    got = kernels.gqa_prefill_attention(q, *operands[1:], total, SCALE)
    _close(got, want, 5e-6)
    _close(want[:total - start],
           _plain(operands[0], k_at, v_at, start, total), 5e-6)


def test_the_tiling_follows_the_shapes_and_the_budget(monkeypatch):
    """A key/value head's whole group a grid step at three cells'
    shapes (7, 5 and 8 heads of a chunk of 2,048, bf16) and half of it
    at the fourth's 16, 512 query rows a pass; float32 operands take
    twice the blocks and fewer query rows where they must; under a
    smaller budget the query rows give way
    first, then the heads."""
    for per, heads in ((7, 7), (5, 5), (8, 8), (16, 8)):
        assert kernels.gqa_prefill_tiles(2048, per, D, jnp.bfloat16) \
            == (heads, 512)
    assert kernels._gqa_prefill_vmem(2048, 8, 512, 1024, D,
                                     jnp.bfloat16) == 51 << 20
    assert kernels.gqa_prefill_tiles(2048, 8, D, jnp.float32) == (8, 256)
    assert kernels.gqa_prefill_tiles(2048, 7, D, jnp.float32) == (7, 512)
    monkeypatch.setattr(kernels, "_PREFILL_VMEM_BUDGET", 48 << 20)
    assert kernels.gqa_prefill_tiles(2048, 8, D, jnp.bfloat16) == (8, 256)
    monkeypatch.setattr(kernels, "_PREFILL_VMEM_BUDGET", 32 << 20)
    assert kernels.gqa_prefill_tiles(2048, 8, D, jnp.bfloat16) == (4, 512)
    monkeypatch.setattr(kernels, "_PREFILL_VMEM_BUDGET", 4 << 20)
    assert kernels.gqa_prefill_tiles(2048, 8, D, jnp.bfloat16) == (0, 0)
    assert "no step of a chunk of 2048 fits 4 MiB" \
        in kernels.gqa_prefill_attention_refusal(
            (2048, 4, 8, D), (32, 4, 4096, D), (32, 4, 4096, D), 4096,
            {"bfloat16"})


BF16 = {"bfloat16"}
REFUSED = {
    "mixed types": (
        ((256, 2, 3, D), (3, 2, 1024, D), (3, 2, 1024, D), 1024,
         {"bfloat16", "float32"}),
        "operands of bfloat16, float32: all bfloat16 or all float32"),
    "a type it is not written for": (
        ((256, 2, 3, D), (3, 2, 1024, D), (3, 2, 1024, D), 1024,
         {"float16"}), "operands of float16: all bfloat16 or all float32"),
    "ranks": (
        ((256, 6, D), (3, 2, 1024, D), (3, 2, 1024, D), 1024, BF16),
        "are not [C, kv heads, heads a group, d] and twice [streams, kv "
        "heads, T, d]"),
    "other key/value heads": (
        ((256, 4, 3, D), (3, 2, 1024, D), (3, 2, 1024, D), 1024, BF16),
        "q (256, 4, 3, 128), k (3, 2, 1024, 128) and v (3, 2, 1024, 128)"),
    "caches of two shapes": (
        ((256, 2, 3, D), (3, 2, 1024, D), (3, 2, 512, D), 1024, BF16),
        "twice [streams, kv heads, T, d]"),
    "a toy head size": (
        ((8, 2, 2, 16), (2, 2, 40, 16), (2, 2, 40, 16), 40, BF16),
        "head size 16 is not whole lanes of 128"),
    "a cache the key block does not divide": (
        ((256, 2, 3, D), (3, 2, 1000, D), (3, 2, 1000, D), 1000, BF16),
        "a key block of 8 positions (a cache of 1000) is not whole lanes "
        "of 128"),
    "no window": (
        ((256, 2, 3, D), (3, 2, 1024, D), (3, 2, 1024, D), 0, BF16),
        "a window of 0 positions on a cache of 1024"),
    "a window longer than the cache": (
        ((256, 2, 3, D), (3, 2, 1024, D), (3, 2, 1024, D), 2048, BF16),
        "a window of 2048 positions on a cache of 1024"),
    "a ring too short for its window and the chunk": (
        ((256, 2, 3, D), (3, 2, 640, D), (3, 2, 640, D), 512, BF16),
        "a ring of 640 positions does not hold a window of 512 behind "
        "every query of a chunk of 256"),
    "a cache shorter than the chunk": (
        ((512, 2, 3, D), (3, 2, 256, D), (3, 2, 256, D), 256, BF16),
        "a cache of 256 positions does not hold a chunk of 512"),
    "a toy chunk": (
        ((12, 2, 3, D), (3, 2, 1024, D), (3, 2, 1024, D), 1024, BF16),
        "no step of a chunk of 12 fits 64 MiB in whole tiles of 16 rows"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_every_refusal_says_why(case):
    shapes, said = REFUSED[case]
    refusal = kernels.gqa_prefill_attention_refusal(*shapes)
    assert refusal is not None and said in refusal, refusal


def test_a_refused_shape_is_an_error_of_the_kernel_not_a_second_path():
    operands, (k_at, v_at) = _history(12, 20, 1024, 1024, dead=0.5)
    with pytest.raises(ValueError, match="gqa_prefill_attention: no step "
                                         "of a chunk of 12 fits"):
        kernels.gqa_prefill_attention(*operands, 1024, SCALE)
    # the loop takes it
    _close(kernels.gqa_prefill_attention_reference(
        *operands, 1024, SCALE, precision=HIGHEST),
        _plain(operands[0], k_at, v_at, 20, 1024), 5e-6)


# -- models/attention.py prefill ----------------------------------------------


def _prefilled(total, window, chunk, chunks, fill=np.nan):
    """``chunks`` chunks of stream 1 prefilled in turn into a cache of
    three streams that held ``fill``: every chunk's output and the
    cache."""
    cache = {name: jnp.full((3, 2, total, D), fill, jnp.float32)
             for name in ("k", "v")}
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (chunks * chunk, 2, 3, D))
    k, v = (jax.random.normal(key, (chunks * chunk, 2, D))
            for key in keys[1:])
    outs = []
    for i in range(chunks):
        def qkv(positions, at=slice(i * chunk, (i + 1) * chunk)):
            assert positions.shape == (chunk,)
            return q[at], k[at], v[at]

        out, cache = attention.prefill(qkv, chunk, cache, jnp.int32(1),
                                       jnp.int32(i * chunk), window, HIGHEST)
        outs.append(out)
    return jnp.concatenate(outs), cache


@pytest.mark.parametrize("total,window,chunks", [(1280, 1280, 3),
                                                 (768, 512, 5)],
                         ids=["full", "ring"])
def test_prefill_takes_the_kernel_and_leaves_the_loops_cache(
        monkeypatch, total, window, chunks):
    """Chunks of 256 through ``attention.prefill`` with the kernel and,
    the refusal forced, with the loop: the same output to float32
    rounding and the SAME cache bit for bit (rows never written, NaN
    here, are neither read into a result nor touched; the ring wraps
    after its third chunk)."""
    taken = []
    kernel = kernels.gqa_prefill_attention
    monkeypatch.setattr(kernels, "gqa_prefill_attention",
                        lambda *a: taken.append(a[0].shape) or kernel(*a))
    got, cache = _prefilled(total, window, 256, chunks)
    assert taken == [(256, 2, 3, D)] * chunks
    monkeypatch.setattr(kernels, "gqa_prefill_attention_refusal",
                        lambda *a: "the test asks for the loop")
    want, cache_loop = _prefilled(total, window, 256, chunks)
    assert len(taken) == chunks
    _close(got, want, 1e-5)
    written = min(total, chunks * 256)
    for name in ("k", "v"):
        rows = np.asarray(cache[name])
        assert np.array_equal(rows, np.asarray(cache_loop[name]),
                              equal_nan=True)
        assert np.isfinite(rows[1, :, :written]).all() \
            and np.isnan(rows[1, :, written:]).all()
        assert np.isnan(rows[0]).all() and np.isnan(rows[2]).all()


def test_the_span_it_is_traced_under_says_which_path():
    """``prefill`` chooses at trace time, so the choice is a note of the
    set-up span open around the trace (the filter's ``trace_lower``),
    once for each distinct call with its count."""
    def trace(chunk, total, window, d=D):
        cache = attention.kv_cache(3, 2, total, d, jnp.float32)
        jax.make_jaxpr(lambda c: attention.prefill(
            lambda positions: (jnp.zeros((chunk, 2, 3, d)),
                               jnp.zeros((chunk, 2, d)),
                               jnp.zeros((chunk, 2, d))),
            chunk, c, jnp.int32(1), jnp.int32(0), window, HIGHEST))(cache)

    profile.clear()
    with profile.span("pf_net", "trace_lower", setup=True):
        trace(256, 1024, 1024)
        trace(256, 1024, 1024)
        trace(256, 768, 512)
        trace(8, 40, 40, d=16)
    trace(256, 1024, 1024)                    # no span open: says nothing
    note = [s.note for s in profile.spans()
            if s.name == "pf_net/trace_lower"][-1]
    assert "prefill 256 x 2 x 3 heads, a window of 1024 on (3, 2, 1024, " \
           "128) float32: the kernel (x2)" in note
    assert "prefill 256 x 2 x 3 heads, a window of 512 on (3, 2, 768, " \
           "128) float32: the kernel" in note
    assert "prefill 8 x 2 x 3 heads, a window of 40 on (3, 2, 40, 16) " \
           "float32: the jnp loop (head size 16 is not whole lanes of " \
           "128)" in note


@pytest.mark.parametrize("per,streams,total,window", [
    (7, 32, 6144, 4096), (7, 32, 16384, 16384), (16, 32, 16384, 16384),
    (5, 128, 4096, 4096), (8, 128, 4096, 4096)],
    ids=["smallthinker.decode16k ring", "smallthinker.decode16k full",
         "kexaone.decode16k", "falconh1.decode4k", "nemotron3.decode4k"])
def test_the_note_says_the_kernel_at_the_cells_shapes(per, streams, total,
                                                      window):
    """A chunk of 2,048 at the four grouped-query cells' shapes (bf16, 4
    key/value heads of 7, 16, 5 or 8 query heads of 128), traced with
    abstract arguments: nothing is refused, and the span says so."""
    cache = {name: jax.ShapeDtypeStruct((streams, 4, total, D), jnp.bfloat16)
             for name in ("k", "v")}

    def chunk(cache):
        return attention.prefill(
            lambda positions: (jnp.zeros((2048, 4, per, D), jnp.bfloat16),
                               jnp.zeros((2048, 4, D), jnp.bfloat16),
                               jnp.zeros((2048, 4, D), jnp.bfloat16)),
            2048, cache, jnp.int32(1), jnp.int32(2048), window, None)

    profile.clear()
    with profile.span("pf_net", "trace_lower", setup=True):
        o, _ = jax.eval_shape(chunk, cache)
    assert o.shape == (2048, 4, per, D) and o.dtype == jnp.bfloat16
    note = [s.note for s in profile.spans()
            if s.name == "pf_net/trace_lower"][-1]
    assert note == f"prefill 2048 x 4 x {per} heads, a window of {window} " \
                   f"on ({streams}, 4, {total}, 128) bfloat16: the kernel"
