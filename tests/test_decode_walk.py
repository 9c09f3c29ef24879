"""The walk a decode attention kernel copies a stream's live rows by
(``nnstreamer_tpu/ops/kernels.py``: ``decode_walk_plan``, ``walk_cells``,
``walk_items``, ``walk_item``, ``walk_piece``, ``decode_rows_fetched``) and
``gqa_decode_attention`` on it, interpreted on the CPU: the kernel
against its ``jnp`` mathematics over rings, dense caches, groups, heads
and the positions where the walk changes shape; the rows the plan names
against a brute-force count; and the three models' counters of them.
``latent_decode_attention`` walks the same way over a latent cache whose
rows pack two positions at the published sizes (rank 512, rope 64) and
hold one, padded to whole lanes, at others: both forms against the
kernel's mathematics here, more of the padded form's shapes in
``tests/test_deepseek_v2.py``.  No number here is a rate."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models import deepseek_v2 as dsv2  # noqa: E402
from nnstreamer_tpu.models import nemotron_h as nh  # noqa: E402
from nnstreamer_tpu.models import smallthinker as st  # noqa: E402
from nnstreamer_tpu.ops import kernels  # noqa: E402
from nnstreamer_tpu.ops.kernels import WalkPlan  # noqa: E402

LAT = 128                                   # the lattice: a lane tile

# -- the kernel against its mathematics ----------------------------------------

#: a ring of 768 wrapped three times, one stream in every cell of it, at
#: a cell's first row, its last and between
WRAP = [3 * 768 + c * LAT + r for c, r in enumerate((0, 127, 1, 64, 126, 5))]

CASES = {
    # the cell's ring layer (two of its four groups, five of its seven
    # heads, float32), by the plan the call derives: chunks of 1,024
    "ring-6144-window-4096": (
        2, 5, 6144, 4096, None, "float32",
        [0, 4095, 4096, 6143, 6144, 10000, 3 * 6144 - 1]),
    "small-twin-ring-768-window-512": (
        2, 5, 768, 512, WalkPlan(256, 3), "float32",
        [0, 511, 512, 767, 768, 1000]),
    "every-cell-of-the-ring-at-its-wrap": (
        2, 5, 768, 512, WalkPlan(256, 4), "float32", WRAP),
    "young-beside-old": (
        2, 5, 768, 512, WalkPlan(384, 3), "float32", [100, 5000]),
    "window-within-a-cell-of-the-ring": (
        2, 5, 384, 300, WalkPlan(128, 3), "float32",
        [299, 300, 383, 384, 500, 1151]),
    "dense-sixteen-heads-bf16": (
        2, 16, 1024, 1024, WalkPlan(512, 3), "bfloat16",
        [0, 127, 128, 600, 1023]),
    "dense-four-groups-five-heads": (
        4, 5, 512, 512, WalkPlan(256, 3), "float32",
        [0, 255, 256, 511]),
    "dense-window-beyond-the-cache": (
        2, 5, 512, 1 << 20, WalkPlan(256, 2), "float32",
        [3, 300, 511]),
    "chunk-of-one-cell": (
        2, 7, 768, 512, WalkPlan(128, 5), "float32",
        [511, 512, 900]),
    "whole-cache-a-chunk": (
        2, 7, 384, 256, WalkPlan(384, 2), "float32",
        [5, 300, 1151]),
    "five-streams-by-the-derived-plan": (
        2, 5, 256, 256, None, "float32", [0, 17, 128, 200, 255]),
    "six-streams-four-groups-bf16": (
        4, 16, 512, 256, WalkPlan(256, 3), "bfloat16",
        [0, 255, 256, 511, 512, 2000]),
    # `falconh1.decode4k`'s layer: four groups of FIVE heads (a tile of
    # 16 holds them), bf16, a dense cache of 4,096, the derived plan
    "dense-4096-four-groups-five-heads-bf16": (
        4, 5, 4096, 4096, None, "bfloat16",
        [0, 2047, 2048, 3071, 3840, 4095]),
    # `phi4flash.decode16k`'s two calls: TEN K/V pairs of 128 with FOUR
    # query rows a pair (`[q1 | 0]`, `[0 | q2]` of heads of 64), bf16,
    # by the plans the calls derive from rows of 5,120 B: a ring of
    # 1,536 read through a window of 512, and the one shared cache of
    # 16,384, both in chunks of 512 rows (the least a chunk has where
    # the bytes allow: 2 MiB alone would hold 256 of such rows)
    "ring-1536-window-512-ten-pairs-four-rows-bf16": (
        10, 4, 1536, 512, None, "bfloat16",
        [0, 511, 512, 1535, 1536, 9000, 16383]),
    "dense-16384-ten-pairs-four-rows-bf16": (
        10, 4, 16384, 16384, None, "bfloat16", [8191, 16383]),
    "a-queue-longer-than-a-stream": (
        2, 5, 768, 512, WalkPlan(256, 8), "float32",
        [0, 130, 5000, 2, 767]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_walk_is_the_reference(case):
    groups, per, total, window, plan, dtype, at = CASES[case]
    rng = np.random.default_rng(3)
    b = len(at)
    q = jnp.asarray(rng.normal(size=(b, groups, per, 128)), dtype)
    k = jnp.asarray(rng.normal(size=(b, groups, total, 128)), dtype)
    v = jnp.asarray(rng.normal(size=(b, groups, total, 128)), dtype)
    at = jnp.asarray(at, jnp.int32)
    if plan is None:
        got = kernels.gqa_decode_attention(q, k, v, at, window, 0.09)
    else:
        got = kernels._gqa_decode_walk(q, k, v, at, window, 0.09, plan)
    want = kernels.gqa_decode_attention_reference(q, k, v, at, window, 0.09)
    assert got.shape == (b, groups, per, 128) and got.dtype == jnp.float32
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert np.allclose(np.asarray(got), np.asarray(want), atol=tol)


# -- the plan a call derives ---------------------------------------------------------


@pytest.mark.parametrize("total,row_bytes,plan", [
    (6144, 2048, (1024, 4)), (16384, 2048, (1024, 4)),
    (4096, 1024, (2048, 4)), (256, 2048, (128, 8)),
    (384, 1024, (128, 8)), (768, 64 << 10, (128, 3)),
    (16640, 1280, (1280, 5)), (4096, 1280, (1024, 6)),
    (16640, 1152, (1664, 4)), (4096, 1152, (1024, 7)),
    (16384, 5120, (512, 3)), (1536, 5120, (512, 3)),
], ids=["smallthinker-ring", "smallthinker-full", "nemotron3", "two-cells",
        "three-cells", "wide-rows", "rows-of-640-in-16640",
        "rows-of-640-in-4096", "dsv2-latent", "longcat-latent",
        "phi4flash-shared", "phi4flash-ring"])
def test_the_plan_follows_what_the_call_sees(total, row_bytes, plan):
    got = kernels.decode_walk_plan(total, row_bytes)
    assert tuple(got) == plan
    assert total % got.chunk == 0 and got.chunk % LAT == 0
    assert got.slots >= 3 and got.pieces == tuple(
        1 << k for k in reversed(range((got.cells - 1).bit_length())))


def test_the_latent_cells_plan_is_chunks_of_thirteen_cells_in_four_buffers():
    """`dsv2.decode16k`'s caches, `[32, 8320, 1152]` bf16, two positions
    a row: 16,640 positions of 1,152 bytes as stored, 130 cells, of whose
    divisors thirteen is the most that 2 MiB hold (1.9 MB a chunk of 832
    rows), so a last item comes in pieces of 8, 4, 2 and 1 cells, the
    last of them 64 rows."""
    per, width = kernels.latent_cache_row(512, 64)
    assert (per, width) == (2, 1152)
    plan = kernels.decode_walk_plan(16640, width // per * 2)
    assert plan == WalkPlan(1664, 4) and plan.cells == 13
    assert plan.pieces == (8, 4, 2, 1)
    for count in range(1, plan.cells):
        got = [(int(kernels.walk_piece(count, size)[1]), size)
               for size in plan.pieces if kernels.walk_piece(count, size)[0]]
        assert sum(size for _, size in got) == count
        assert [at for at, _ in got] == [
            sum(size for _, size in got[:i]) for i in range(len(got))]


@pytest.mark.parametrize("rank,rope,row", [
    (512, 64, (2, 1152)), (128, 32, (4, 640)), (256, 16, (8, 2176)),
    (16, 8, (1, 128)), (24, 8, (1, 128)), (512, 128, (1, 640)),
    (512, 96, (1, 640)), (128, 8, (1, 256)), (500, 64, (1, 640)),
], ids=["published", "four-a-row", "eight-a-row", "toy-dsv2", "toy-longcat",
        "rope-a-whole-tile", "rope-not-dividing-a-lane",
        "cells-under-a-tile-of-rows", "rank-not-whole-lanes"])
def test_a_latent_row_packs_where_every_slice_is_whole_tiles(rank, rope, row):
    """The layout's rule, by sizes alone: positions a row and the row's
    width.  Packed rows store ``rank + rope`` values a position; a
    padded row's width is whole lanes."""
    per, width = kernels.latent_cache_row(rank, rope)
    assert (per, width) == row and width % LAT == 0
    assert width == per * (rank + rope) if per > 1 else width >= rank + rope
    rows = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 16, rank + rope)), jnp.float32)
    packed = kernels.latent_pack(rows, rank)
    assert packed.shape == (2, 16 // per, width)
    assert np.array_equal(np.asarray(
        kernels.latent_unpack(packed, rank, rope)), np.asarray(rows))
    # position p of a stream: row p // per, its latent part at lanes
    # (p % per) * rank, its rotary key at per * rank + (p % per) * rope
    for p in (0, 1, 5):
        r, h = divmod(p, per)
        assert np.array_equal(
            np.asarray(packed[1, r, h * rank:(h + 1) * rank]),
            np.asarray(rows[1, p, :rank]))
        at = per * rank + h * rope
        assert np.array_equal(np.asarray(packed[1, r, at:at + rope]),
                              np.asarray(rows[1, p, rank:]))
    # a token placed at a position leaves the row's other positions
    old = packed[:, 1]
    new = jnp.ones((2, rank + rope), jnp.float32)
    at = np.array([per, 2 * per - 1], np.int32)
    placed = kernels.latent_unpack(
        kernels.latent_place(old, new, at, rank)[:, None], rank, rope)
    want = np.asarray(rows[:, per:2 * per]).copy()
    want[0, 0], want[1, per - 1] = 1.0, 1.0
    assert np.array_equal(np.asarray(placed), want)


#: ``heads, rank, queries' width (rank + rope), positions of the cache,
#: dtype, plan, streams at``; without a plan the call derives its own.
LATENT_WALKS = {
    # `dsv2.decode16k`'s call, four of its streams: position 0, the last
    # row of the plan's chunk of 1,664, the first of the next, the
    # cache's last
    "published-32-heads-16640-by-its-plan": (
        32, 512, 576, 16640, "bfloat16", None, [0, 1663, 1664, 16639]),
    # `longcat.decode4k`'s (chunks of 1,024 positions in seven buffers:
    # the plan follows the cache's positions and a position's bytes,
    # not the streams): two whole chunks, the first row of a third, three
    # whole, a fourth of 4 + 1 cells, the cache's last row
    "published-64-heads-4096-by-its-plan": (
        64, 512, 576, 4096, "bfloat16", None,
        [2047, 2048, 3071, 3072 + 513, 4095]),
    # position 0, the first and the second position of a packed row, a
    # lattice cell's last row and the next one's first, a chunk's last
    # and first, the cache's last two
    "published-32-heads-a-rows-both-positions": (
        32, 512, 576, 1024, "float32", WalkPlan(256, 3),
        [0, 1, 2, 3, 127, 128, 255, 256, 1022, 1023]),
    # chunks of ten cells: a last item of 8 + 1 cells, of 4 + 2 + 1, of
    # one cell (64 rows) after a whole chunk, and two whole chunks, at
    # odd and even positions
    "published-64-heads-pieces-8-4-2-1": (
        64, 512, 576, 2560, "bfloat16", WalkPlan(1280, 3),
        [1040, 1041, 868, 1285, 2559]),
    "published-one-item-before-many-heads-padded": (
        5, 512, 576, 1024, "float32", WalkPlan(256, 4), [3, 1000, 130, 700]),
    "published-whole-cache-a-chunk": (
        16, 512, 576, 384, "bfloat16", WalkPlan(384, 2), [4, 131, 383]),
    "four-positions-a-row": (
        8, 128, 160, 1024, "float32", WalkPlan(512, 3),
        [0, 1, 2, 3, 4, 127, 128, 511, 512, 701, 1023]),
    "eight-positions-a-row": (
        8, 128, 144, 512, "float32", None, [0, 7, 8, 300, 511]),
    # a row a position, padded to whole lanes: the toys' (values out of
    # the whole row), and a rank of whole tiles beside a rotary part that
    # does not divide a lane
    "padded-toy-f32": (2, 16, 24, 256, "float32", None, [0, 1, 127, 128, 255]),
    "padded-toy-bf16": (3, 16, 24, 384, "bfloat16", WalkPlan(128, 3),
                        [5, 172, 383]),
    "padded-queries-as-wide-as-the-row": (
        3, 24, 128, 256, "float32", None, [5, 172, 255]),
    "padded-rank-of-whole-tiles": (
        8, 128, 256, 768, "float32", WalkPlan(384, 2), [0, 383, 384, 767]),
}


@pytest.mark.parametrize("case", list(LATENT_WALKS))
def test_the_latent_walk_is_the_reference(case):
    """The kernel (interpreted) against its mathematics on the rows as
    the cache holds them, and that against the same mathematics on a
    plain ``[streams, positions, values]`` array the packing never
    touched."""
    heads, rank, wide, total, dtype, plan, at = LATENT_WALKS[case]
    per, width = kernels.latent_cache_row(rank, wide - rank)
    rng = np.random.default_rng(4)
    b = len(at)
    q = jnp.asarray(rng.normal(size=(b, heads, wide)), dtype)
    plain = jnp.asarray(rng.normal(size=(b, total, wide)), dtype)
    cache = kernels.latent_pack(plain, rank)
    assert cache.shape == (b, total // per, width)
    assert kernels.latent_decode_attention_refusal(
        q.shape, cache.shape, rank) is None
    at = jnp.asarray(at, jnp.int32)
    scale = 192 ** -0.5
    if plan is None:
        got = kernels.latent_decode_attention(q, cache, at, rank, scale)
    else:
        got = kernels._latent_decode_walk(q, cache, at, rank, scale, plan)
    want = kernels.latent_decode_attention_reference(q, cache, at, rank,
                                                     scale)
    assert got.shape == (b, heads, rank) and got.dtype == jnp.float32
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert np.allclose(np.asarray(got), np.asarray(want), atol=tol)
    # the same mathematics on rows nothing packed: zeros to whole lanes
    pad = ((0, 0), (0, 0), (0, -wide % LAT))
    unpacked = kernels.latent_decode_attention_reference(
        jnp.pad(q, pad), jnp.pad(plain, pad), at, rank, scale)
    assert np.allclose(np.asarray(want), np.asarray(unpacked), atol=tol / 10)
    # what the walk fetches for them: every live cell whole
    assert int(kernels.decode_rows_fetched(at, total, total)) \
        == sum((int(p) // 128 + 1) * 128 for p in at)


def test_the_latent_kernel_at_64_heads_on_caches_of_4096_positions():
    """`longcat.decode4k`'s call, `[128, 64, 576]` on `[128, 2048, 1152]`
    bf16: the refusal function takes the cell's shapes, the plan is
    chunks of 1,024 positions (512 rows) in seven buffers with pieces of
    4, 2 and 1 cells, and the same call on the rows of 640 values a
    position that the cache had before is an error that names the row
    it wants."""
    assert kernels.latent_decode_attention_refusal(
        (128, 64, 576), (128, 2048, 1152), 512) is None
    plan = kernels.decode_walk_plan(4096, 1152)
    assert plan == WalkPlan(1024, 7) and plan.pieces == (4, 2, 1)
    said = kernels.latent_decode_attention_refusal(
        (128, 64, 576), (128, 4096, 640), 512)
    assert "rows of 1152 for 2 positions" in said and "not 640" in said
    # queries as wide as a padded row still meet a padded row
    assert kernels.latent_decode_attention_refusal(
        (128, 64, 640), (128, 4096, 640), 512) is None


@pytest.mark.parametrize("kernel", ["gqa", "latent"])
def test_a_plan_that_does_not_divide_the_cache_is_an_error(kernel):
    q = jnp.zeros((1, 1, 8, 128))
    k = jnp.zeros((1, 1, 384, 128))
    at = jnp.zeros((1,), jnp.int32)
    for plan in (WalkPlan(256, 3), WalkPlan(192, 3),
                 WalkPlan(128, 1)):
        with pytest.raises(ValueError, match="does not divide"):
            if kernel == "gqa":
                kernels._gqa_decode_walk(q, k, k, at, 384, 1.0, plan)
            else:
                kernels._latent_decode_walk(q[0], k[0], at, 128, 1.0, plan)
                kernels._latent_decode_walk(
                    jnp.zeros((1, 8, 576)), jnp.zeros((1, 192, 1152)), at,
                    512, 1.0, plan)


# -- the rows the plan names ------------------------------------------------------------


def _named(pos: int, total: int, window: int, plan: WalkPlan):
    """The cache rows the kernel's plan names for one stream, one entry
    a copy: ``(first row, rows)``.  The pieces a partial item is copied
    and computed on by lie end to end from its first cell."""
    first, cells = (int(x) for x in kernels.walk_cells(pos, total, window))
    lat, ring = LAT, total // LAT
    copies = []
    for item in range(int(kernels.walk_items(cells, plan))):
        start, count, over = kernels.walk_item(first, cells, item, total,
                                               plan)
        start, count, over = int(start), int(count), bool(over)
        assert 0 <= start < ring and 0 < count <= plan.cells
        assert over == (start + count > ring)
        pieces = [(int(offset), size) for size in plan.pieces
                  for has, offset in [kernels.walk_piece(count, size)]
                  if has]
        if count == plan.cells:
            pieces = [(0, count)]
        # a partial item: at most one piece of a size, largest first,
        # end to end from the item's first cell
        assert [o for o, _ in pieces] \
            == [sum(n for _, n in pieces[:i]) for i in range(len(pieces))]
        assert sum(n for _, n in pieces) == count
        if over:                        # cell by cell round the ring's end
            copies += [((start + c) % ring * lat, lat) for c in range(count)]
        else:
            copies += [((start + o) * lat, n * lat) for o, n in pieces]
    return copies


@pytest.mark.parametrize("total,window,chunk,ends", [
    (768, 512, 256, 2), (768, 512, 128, 2), (768, 512, 768, 2),
    (768, 512, 384, 2), (384, 300, 128, 2), (512, 512, 256, 1),
    (512, 1 << 20, 512, 1),
], ids=["ring", "ring-chunk-of-a-cell", "ring-one-chunk",
        "ring-chunk-of-three-cells", "window-within-a-cell-of-the-ring",
        "dense", "dense-one-chunk"])
def test_the_rows_fetched_are_the_rows_the_plan_names(total, window, chunk,
                                                      ends):
    """For every position of a small cache (a ring: three turns of it):
    the ``jnp`` count equals the rows of the copies the chunk plan
    names, which lie inside the cache on the lattice, name no row
    twice, hold every row in use, and exceed the rows in use by less
    than a cell at each end the window has inside the cache."""
    plan = WalkPlan(chunk, 3)
    last = 3 * total if window < total else total
    positions = np.arange(last, dtype=np.int32)
    # one count a stream: the function sums over the streams it is given
    counted = np.asarray(jax.vmap(
        lambda p: kernels.decode_rows_fetched(p[None], total, window))(
            jnp.asarray(positions)))
    assert int(kernels.decode_rows_fetched(positions, total, window)) \
        == counted.sum()
    for pos in positions:
        copies = _named(int(pos), total, window, plan)
        rows = [r for at, n in copies for r in range(at, at + n)]
        assert all(at % LAT == 0 and at + n <= total for at, n in copies)
        assert len(rows) == len(set(rows)) == counted[pos]
        in_use = {p % total for p in range(max(0, pos - window + 1), pos + 1)}
        assert in_use <= set(rows)
        assert len(rows) - len(in_use) <= ends * (LAT - 1)
        assert len(rows) <= total


def test_the_cells_shapes_fetch_within_the_stated_shares():
    """At the cells' shapes and positions the walk fetches, over the
    rows in use: within 7 % on a ring of 6,144 read through a window of
    4,096, 2 % on a dense cache of 16,384 at 8-16 k, 5 % on one of
    4,096 at 2-4 k (the schedule by blocks of 1,024 it replaced: 25 %,
    4 % and 17 %)."""
    rng = np.random.default_rng(0)
    for total, window, lo, hi, share in ((6144, 4096, 8192, 16384, 1.07),
                                         (16384, 16384, 8192, 16384, 1.02),
                                         (4096, 4096, 2048, 4096, 1.05)):
        positions = rng.integers(lo, hi, 4096).astype(np.int32)
        used = np.minimum(positions + 1, window).sum()
        fetched = int(kernels.decode_rows_fetched(positions, total, window))
        assert used <= fetched <= share * used


def test_a_dense_cache_fetches_every_live_cell_whole():
    """``window = total`` (the latent cache's call): never an item over
    the cache's end, the cells ``0 .. pos // 128`` whole, so within one
    cell of the rows in use a stream; at `dsv2.decode16k`'s positions
    that is under 1 % over them."""
    total = 1024
    plan = WalkPlan(256, 3)
    for pos in range(total):
        first, cells = (int(x) for x in kernels.walk_cells(pos, total, total))
        assert (first, cells) == (0, pos // LAT + 1)
        assert not any(bool(kernels.walk_item(first, cells, item, total,
                                              plan)[2])
                       for item in range(int(kernels.walk_items(cells,
                                                                plan))))
        fetched = int(kernels.decode_rows_fetched(
            np.array([pos], np.int32), total, total))
        assert fetched == cells * LAT and 0 <= fetched - (pos + 1) < LAT
    positions = np.random.default_rng(1).integers(
        8192, 16640, 4096).astype(np.int32)
    fetched = int(kernels.decode_rows_fetched(positions, 16640, 16640))
    used = int((positions + 1).sum())
    assert used <= fetched <= used + len(positions) * (LAT - 1)
    assert fetched <= 1.01 * used


# -- the models' counters ------------------------------------------------------------------


def _counted(state) -> dict:
    return {k: int(v) for k, v in state["counters"].items()}


def test_smallthinker_counts_the_walk_once_a_layer_of_a_kind():
    """Three layers at the published head size (a dense cache of 512
    and two rings of 256 read through a window of 128) decode through
    the kernel: one step adds the walk's rows of ONE ring to
    ``window_rows_fetched`` and of ONE dense cache to
    ``full_rows_fetched``, and the units count the layers."""
    cfg = st.SmallThinkerConfig.from_dict({
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 4,
        "moe_num_active_primary_experts": 2, "sliding_window_size": 128,
        "rope_theta": 1500000, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 512, "vocab_size": 32,
        "num_hidden_layers": 3, "sliding_window_layout": [0, 1, 1],
        "rope_layout": [0, 1, 1]})
    params = st.init_params(cfg, 1, jnp.float32)
    state = st.init_state(cfg, params, 2, 512, 128)
    positions = np.array([130, 300], np.int32)
    before = _counted(state)
    state, _ = st.decode(cfg, params, state, np.array([3, 5], np.int32),
                         positions)
    after = _counted(state)
    gained = {k: after[k] - before[k] for k in after}
    assert gained["window_rows_read"] == 2 * 128
    assert gained["full_rows_read"] == 131 + 301
    # 3..130 and 173..300 of a ring of 256: two cells each; 0..130 and
    # 0..300 of the dense cache: two cells and three
    assert gained["window_rows_fetched"] == (2 + 2) * LAT \
        == int(kernels.decode_rows_fetched(positions, 256, 128))
    assert gained["full_rows_fetched"] == (2 + 3) * LAT \
        == int(kernels.decode_rows_fetched(positions, 512, 512))
    row = 2 * 2 * 128 * 4
    units = st.counter_units(cfg, state)
    assert units["window_bytes_fetched"] == ("window_rows_fetched", row * 2)
    assert units["full_bytes_fetched"] == ("full_rows_fetched", row * 1)
    assert units["cache_bytes_fetched"] == [units["window_bytes_fetched"],
                                            units["full_bytes_fetched"]]


@pytest.mark.parametrize("sizes", [{}, {"kv_lora_rank": 512,
                                       "qk_rope_head_dim": 64}],
                         ids=["toy", "published-latent-sizes"])
def test_deepseek_v2_counts_the_walk_once_a_step(sizes):
    """The toy's three layers over latent caches of 384 positions
    decode through the kernel: one step adds the walk's rows of ONE
    layer to ``cache_rows_fetched``, and the units count the layers at
    the values a position takes as the cache HOLDS it: the toy's 24
    padded to a lane tile, beside the rows in use at ``latent`` values;
    at the published rank and rotary size the 576 themselves, two
    positions a row of 1,152, so fetched over used is the walk's
    partial cells and nothing else."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_dsv2.json")) as f:
        cfg = dsv2.DeepSeekV2Config.from_dict(dict(json.load(f), **sizes))
    params = dsv2.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    state = dsv2.init_state(cfg, params, 3, 384)
    positions = np.array([0, 127, 300], np.int32)
    before = _counted(state)
    state, _ = dsv2.decode(cfg, params, state,
                           np.full(3, cfg.vocab0, np.int32), positions)
    after = _counted(state)
    assert after["steps"] - before["steps"] == 1
    assert after["cache_rows_read"] - before["cache_rows_read"] \
        == 1 + 128 + 301
    assert after["cache_rows_fetched"] - before["cache_rows_fetched"] \
        == (1 + 1 + 3) * LAT \
        == int(kernels.decode_rows_fetched(positions, 384, 384))
    units = dsv2.counter_units(cfg, state)
    assert cfg.layers == 3
    if sizes:
        assert cfg.row == cfg.latent == 576
        assert [c.shape for c in state["cache"]] == [(3, 192, 1152)] * 3
    else:
        assert cfg.row == LAT > cfg.latent == 24
        assert [c.shape for c in state["cache"]] == [(3, 384, 128)] * 3
    # a cache's bytes: streams x positions x the values a position takes
    assert state["cache"][0].nbytes == 3 * 384 * cfg.row * 4
    assert units["cache_bytes_read"] == (
        "cache_rows_read", cfg.latent * 4 * cfg.layers)
    assert units["cache_bytes_fetched"] == (
        "cache_rows_fetched", cfg.row * 4 * cfg.layers)


def test_nemotron_h_counts_the_walk_once_an_attention_layer():
    """The toy's seven layers with the published head size: its one
    attention layer decodes through the kernel over a dense cache of
    256, and a step adds the walk's rows of ONE layer to
    ``kv_rows_fetched``."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_nemotron3.json")) as f:
        toy = json.load(f)
    cfg = nh.NemotronHConfig.from_dict(
        dict(toy, head_dim=128, max_position_embeddings=256))
    params = nh.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    state = nh.init_state(cfg, params, 3, 256)
    positions = np.array([0, 127, 200], np.int32)
    # as after prompts that ended one position earlier
    state = dict(state, last=jnp.asarray(positions - 1),
                 prompt_end=jnp.asarray(positions))
    before = _counted(state)
    state, _ = nh.decode(cfg, params, state,
                         np.full(3, cfg.vocab0, np.int32), positions)
    after = _counted(state)
    assert after["kv_rows_read"] - before["kv_rows_read"] == 1 + 128 + 201
    assert after["kv_rows_fetched"] - before["kv_rows_fetched"] \
        == (1 + 1 + 2) * LAT \
        == int(kernels.decode_rows_fetched(positions, 256, 256))
    assert after["position_faults"] == 0
    units = nh.counter_units(cfg, state)
    assert units["kv_bytes_fetched"] == units["cache_bytes_fetched"] \
        == ("kv_rows_fetched", 2 * 2 * 128 * 4 * 1)


def test_a_refused_shape_fetches_the_cache_whole():
    """Heads of 16 take the ``jnp`` mathematics, which reads every row
    of every stream: that is what the counter says then."""
    assert kernels.gqa_decode_rows_fetched(
        (3, 2, 4, 16), (3, 2, 40, 16), np.array([1, 2, 3]), 8) == 3 * 40
    assert int(kernels.gqa_decode_rows_fetched(
        (3, 2, 4, 128), (3, 2, 256, 128), np.array([1, 2, 200]), 256)) \
        == 4 * LAT


def test_phi4_flash_counts_the_shared_cache_once_and_its_readers_in_the_unit():
    """The toy's eight layers at heads of 64 (paired rows of 128): two
    rings and ONE cache through the kernel.  A step adds the walk's rows
    of one ring and of the cache ONCE; the units multiply the ring's by
    the rings and the cache's by its readers (its writer and the one
    cross layer of the toy)."""
    from nnstreamer_tpu.models import phi4_flash as pf

    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_phi4flash.json")) as f:
        cfg = pf.Phi4FlashConfig.from_dict(json.load(f))
    params = pf.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    state = pf.init_state(cfg, params, 3, 256, 96)
    assert state["rings"][0]["k"].shape == (3, 2, 128, 128)
    assert state["shared"]["k"].shape == (3, 2, 256, 128)
    positions = np.array([0, 127, 200], np.int32)
    state = dict(state, last=jnp.asarray(positions - 1),
                 prompt_end=jnp.asarray(positions))
    before = _counted(state)
    state, _ = pf.decode(cfg, params, state, np.zeros(3, np.int32), positions)
    after = _counted(state)
    assert after["shared_rows_read"] - before["shared_rows_read"] \
        == 1 + 128 + 201
    assert after["ring_rows_read"] - before["ring_rows_read"] == 1 + 32 + 32
    assert after["shared_rows_fetched"] - before["shared_rows_fetched"] \
        == (1 + 1 + 2) * LAT \
        == int(kernels.decode_rows_fetched(positions, 256, 256))
    # a window of 32 at 127 and at 200 lies in one cell of the ring
    assert after["ring_rows_fetched"] - before["ring_rows_fetched"] \
        == int(kernels.decode_rows_fetched(positions, 128, 32)) == 3 * LAT
    assert after["position_faults"] == 0
    units = pf.counter_units(cfg, state)
    row = 2 * 2 * 128 * 4
    assert units["shared_kv_bytes_fetched"] == ("shared_rows_fetched",
                                                row * 2)
    assert units["ring_kv_bytes_fetched"] == ("ring_rows_fetched", row * 2)
    assert units["cache_bytes_fetched"] == units["kv_bytes_fetched"] \
        == [units["shared_kv_bytes_fetched"], units["ring_kv_bytes_fetched"]]
