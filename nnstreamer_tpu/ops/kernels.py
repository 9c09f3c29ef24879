"""Pallas TPU kernels: fused normalize/typecast, flash attention, short
attention, latent decode attention, latent prefill attention,
grouped-query decode attention, grouped-query prefill attention, the
routed experts' grouped product and the sum of its rows into their
tokens, the Mamba-2 decode step and the Mamba-1 selective scan of a
prefill chunk.

Parity/role:
- ``scale_bias_cast`` is the tensor_transform arithmetic prologue
  (``typecast:float32,add:B,mul/div:S``) as ONE VPU kernel — the TPU
  form of the reference's Orc-accelerated transform loops
  (gsttensor_transform.c:473-483).  It matters on the standalone
  transform path (transform feeding a host sink); when a jax-xla filter
  follows, the fusion pass already inlines the chain into the filter's
  XLA program.
- ``flash_attention`` is the blockwise-attention block kernel (online
  softmax, never materializing the (S, S) score matrix) — the
  single-chip engine under long-context sequence parallelism
  (parallel/collectives.ring_attention rotates K/V blocks between chips
  with the same math).
- ``short_attention`` is whole-sequence attention for sequences short
  enough that the whole key range is one block (a ViT's few hundred
  patches): a frame with all its heads a grid step, read from the qkv
  projection's ``(B, S, 3·D)`` and written as the ``(B, S, D)`` the
  output projection reads, scores in VMEM only.  ``S`` need not tile:
  it pads and masks inside.
- ``latent_decode_attention`` is absorbed latent attention of one token
  a stream over a dense latent cache (``models/mla.py``'s decode step,
  for ``deepseek_v2.py`` and ``longcat_flash.py``): one pass over a
  stream's live rows, a chunk read once for both products.  A row of
  the cache packs two positions where the sizes allow
  (``latent_cache_row``), and is scored as it lies.
- ``latent_prefill_attention`` is expanded latent attention of one
  chunk of one stream over the same cache (``models/mla.py``'s prefill
  chunk): the grid walks (head group, key block), a step rebuilds the
  block's keys and values from the latent rows once and scores the
  chunk's queries against them a block of rows at a time, so the scores
  exist in fast memory only.
- ``gqa_decode_attention`` is grouped-query attention of one token a
  stream over separate K and V caches (``models/smallthinker.py``'s
  decode step, ``models/nemotron_h.py``'s and
  ``models/exaone_moe.py``'s), the cache a ring a stream wraps around
  or a dense array it grows into; a chunk of K and of V is read once
  for all the query heads of its group.
  Both decode kernels WALK: the caches stay in HBM and the kernel
  copies a stream's live rows itself, whole chunks counted from the
  first live cell and pieces of 2^k cells of 128 rows at the end,
  through one queue of buffers (``_walk_stream``) that runs on into
  the next stream, so no stream starts cold.
- ``gqa_prefill_attention`` is grouped-query attention of one chunk of
  one stream over the same caches (``models/attention.py``'s
  ``prefill``, for ``smallthinker.py``, ``nemotron_h.py``,
  ``falcon_h1.py`` and ``exaone_moe.py``): the grid walks (a key/value
  head's group of query heads, key block) from the block with the
  oldest position a query sees round a ring to the one with the chunk's
  end, a block's K and V rows read once for all the heads of the step,
  the scores in fast memory only.
- ``grouped_gated_product`` is the gated MLPs of the experts a decode
  step's tokens were routed to (``models/moe.py`` ``grouped_experts``):
  one call whose grid walks the plan's blocks with the block's expert
  prefetched, so the next expert's matrices stream in while this one's
  are multiplied.
- ``weighted_row_sum`` is what follows it (``models/moe.py``
  ``combine``): the rows the product computed, each scaled by its
  pair's weight and added into its token's float32 row.  The rows stay
  in HBM; the kernel copies an expert's real rows by the plan's counts,
  sixteen a copy and a few copies in flight, and holds a column tile of
  the result for every token in fast memory, so its work follows the
  pairs held HERE and no ``[tokens, picks, hidden]`` value exists.
- ``ssm_decode_step`` is one token of the Mamba-2 recurrence for every
  stream (``models/nemotron_h.py``'s decode step): a stream's float32
  state is copied in once (from its snapshot or live, as a prefetched
  flag says), updated and reduced to ``y`` in fast memory and copied
  out once over the live state, a few streams a grid step, the copies
  in and the copies out in separate phases.
- ``selective_scan`` is the Mamba-1 recurrence over a prefill chunk of
  one stream (``models/mamba1.py``, for ``phi4_flash.py``): the decay
  is per (channel, state), so no chunked matrix form exists; a block of
  channels' ``[state, channels]`` stays in vector registers over the
  chunk's tokens and only ``delta``, ``delta x`` and ``y`` pass through
  fast memory.

All compile natively on TPU (Mosaic) and run under the Pallas
interpreter on CPU backends (tests).  ``scale_bias_cast`` and
``flash_attention`` take their jnp reference for a shape that does not
meet the tiling constraints — lane dim a multiple of 128, sublane dim a
multiple of the dtype's tile height (8 rows of 4-byte, 16 of 2-byte, 32
of 1-byte elements); ``short_attention``, ``latent_decode_attention``,
``latent_prefill_attention``, ``gqa_decode_attention``,
``gqa_prefill_attention``, ``grouped_gated_product``,
``weighted_row_sum``, ``ssm_decode_step`` and ``selective_scan`` refuse
a shape they cannot take (``*_refusal`` says why: axes that do not
fill tiles, a type the kernel is not written for, blocks over a
fast-memory budget) and leave the choice to the caller.
Either way the ``*_available`` / ``*_refusal`` predicates are the whole
eligibility rule, so the fallback is a decision made here, never an
exception caught somewhere.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

_LANE = 128


def _sublane(dtype) -> int:
    """Rows of one (sublane x 128) VMEM tile of ``dtype``: a tile is
    8 x 128 32-bit words, so narrower elements pack more rows."""
    return 32 // max(min(np.dtype(dtype).itemsize, 4), 1)


def _pl():
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return jax, pl, pltpu


def on_tpu() -> bool:
    """Whether programs built now compile for a TPU.  The ONE place the
    package asks: the kernels below (Mosaic vs the Pallas interpreter)
    and ``models/ssd.py`` (approximate vs exact top-k) both read it,
    and ``chip_smoke.py`` asserts what it returns on the chip."""
    import jax

    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


# -- fused scale/bias/cast ---------------------------------------------------


def scale_bias_cast_available(shape, in_dtype,
                              out_dtype=np.float32) -> bool:
    """Kernel eligibility: the element count must tile into
    (rows, 128) blocks whose row count suits BOTH the input's and the
    output's tile height (a uint8 frame tiles at 32 rows, an f32 result
    at 8), and the input must not be float64 (the kernel computes in
    f32; f64 inputs take the precision-preserving jnp fallback)."""
    if np.dtype(in_dtype) == np.dtype(np.float64):
        return False
    n = int(np.prod(shape))
    rows = max(_sublane(in_dtype), _sublane(out_dtype))
    return n > 0 and n % (_LANE * rows) == 0


def scale_bias_cast(x, scale: float, bias: float, out_dtype=np.float32,
                    block_rows: int = 256):
    """``((x + bias) * scale).astype(out_dtype)`` as one tiled VPU kernel.

    Accepts any shape :func:`scale_bias_cast_available` admits;
    otherwise computes the jnp reference.
    """
    import jax.numpy as jnp

    out_dtype = jnp.dtype(out_dtype)
    n = int(np.prod(x.shape))
    if not scale_bias_cast_available(x.shape, x.dtype, out_dtype):
        # fallback computes at the input's precision when it is wider
        ct = jnp.promote_types(x.dtype, jnp.float32)
        return ((x.astype(ct) + bias) * scale).astype(out_dtype)
    jax, pl, pltpu = _pl()
    rows = n // _LANE
    # the largest block <= block_rows that divides the rows AND is a
    # whole number of tiles of both dtypes (eligibility guarantees
    # ``step`` itself divides the rows, so the search terminates)
    step = max(_sublane(x.dtype), _sublane(out_dtype))
    block = max(min(block_rows, rows) // step * step, step)
    while rows % block:
        block -= step

    def kernel(in_ref, out_ref):
        v = in_ref[:]
        if v.dtype in (jnp.uint8, jnp.int8, jnp.uint16, jnp.int16):
            # Mosaic has no direct small-int→float cast: widen first
            v = v.astype(jnp.int32)
        v = v.astype(jnp.float32)
        out_ref[:] = ((v + bias) * scale).astype(out_dtype)

    flat = x.reshape(rows, _LANE)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, _LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, _LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), out_dtype),
        interpret=_interpret(),
    )(flat)
    return out.reshape(x.shape)


# -- flash attention ---------------------------------------------------------


def flash_attention_reference(q, k, v, scale: Optional[float] = None):
    """jnp reference: softmax(q kᵀ · scale) v, f32 accumulation."""
    import jax.numpy as jnp

    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("...qk,...kd->...qd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def flash_attention_available(q_shape, k_shape, dtype,
                              block_q: int = 128,
                              block_k: int = 128) -> bool:
    """Kernel eligibility for (..., S, D) queries against (..., Sk, D)
    keys: D and the K block whole multiples of the 128 lanes (the
    running max/normalizer are kept lane-replicated and widened by
    whole-lane repeats), S and Sk whole numbers of blocks, and the Q
    block a whole number of ``dtype`` tiles."""
    S, D = q_shape[-2], q_shape[-1]
    Sk = k_shape[-2]
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    return not (D % _LANE or block_k % _LANE
                or S % block_q or Sk % block_k
                or block_q % _sublane(dtype))


def flash_attention(q, k, v, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    """Blockwise attention, never materializing the (S, S) scores.

    q/k/v: (..., S, D) in a shape :func:`flash_attention_available`
    admits — otherwise the jnp reference runs.  Leading dims are
    flattened into the grid's outer axis; the kernel keeps a running
    max/normalizer/accumulator in VMEM scratch across K blocks (online
    softmax), so VMEM holds only (block_q + 2·block_k) × D floats.
    """
    import jax.numpy as jnp

    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if not flash_attention_available(q.shape, k.shape, q.dtype,
                                     block_q, block_k):
        return flash_attention_reference(q, k, v, scale)
    S, D = q.shape[-2], q.shape[-1]
    Sk = k.shape[-2]
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    jax, pl, pltpu = _pl()
    lead = q.shape[:-2]
    B = int(np.prod(lead)) if lead else 1
    qf = q.reshape(B, S, D)
    kf = k.reshape(B, Sk, D)
    vf = v.reshape(B, Sk, D)
    nq, nk = S // block_q, Sk // block_k

    def kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        ik = pl.program_id(2)

        @pl.when(ik == 0)
        def _init():
            m_ref[:] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

        qb = q_ref[0].astype(jnp.float32)           # (bq, D)
        kb = k_ref[0].astype(jnp.float32)           # (bk, D)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        # the per-row running max / normalizer live replicated across
        # one full lane width, (bq, 128): every load, store and
        # elementwise op stays on whole (8,128) tiles, and widening to
        # the (bq, bk) / (bq, D) operands is a whole-lane repeat — no
        # single-lane extract into a 1-D vector anywhere
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - pltpu.repeat(m_new, block_k // _LANE, 1))
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * pltpu.repeat(corr, D // _LANE, 1) \
            + jax.lax.dot_general(
                p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_ref[:] = m_new

        @pl.when(ik == nk - 1)
        def _finish():
            o_ref[0] = (acc_ref[:] / pltpu.repeat(l_ref[:], D // _LANE, 1)
                        ).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # normalizer
            pltpu.VMEM((block_q, D), jnp.float32),       # accumulator
        ],
        interpret=_interpret(),
    )(qf, kf, vf)
    return out.reshape(*lead, S, D) if lead else out[0]


# -- short attention ----------------------------------------------------------

# what one grid step of short_attention may hold in VMEM: its blocks,
# double buffered, and the float32 scores the unrolled heads keep alive
# (counted at six a step: a head's scores, probabilities and their cast,
# and the next head's already in flight).  The chip's scoped default is
# 16 MiB and the rest is the compiler's; every shape this admits of a
# grid of widths, heads and lengths compiled for a v5e, and the first
# one past it (768 wide, 512 positions) ran out of VMEM.
_SHORT_VMEM_BUDGET = 10 << 20


def _short_rows(positions: int, dtype) -> tuple:
    """(query rows, key rows) of a step's blocks: queries whole tiles of
    ``dtype``, keys whole lanes (they are the scores' lane axis)."""
    tile = _sublane(dtype)
    return -(-positions // tile) * tile, -(-positions // _LANE) * _LANE


def short_attention_available(qkv_shape, heads: int, dtype) -> bool:
    """Kernel eligibility for a ``(B, S, 3·D)`` qkv projection of
    ``heads`` heads: bfloat16 or float32; head size 64 (two heads fill
    one 128-lane block) or whole lanes; ``D`` whole lanes; and ``S``
    short enough that a frame's q, k, v and o blocks (double buffered)
    and the float32 scores in flight fit ``_SHORT_VMEM_BUDGET`` (up to
    384 positions at ViT-B/16's width).  ``S`` itself may be ragged: the
    kernel pads and masks."""
    if len(qkv_shape) != 3 or heads <= 0 or qkv_shape[2] % 3:
        return False
    if np.dtype(dtype) not in (np.dtype("bfloat16"), np.dtype(np.float32)):
        return False
    S, D = qkv_shape[1], qkv_shape[2] // 3
    if S <= 0 or D % heads or D % _LANE:
        return False
    dh = D // heads
    if dh % _LANE and not (dh == _LANE // 2 and heads % 2 == 0):
        return False
    rows_q, rows_k = _short_rows(S, dtype)
    blocks = 2 * (2 * rows_q + 2 * rows_k) * D * np.dtype(dtype).itemsize
    scores = 6 * rows_q * rows_k * 4
    return blocks + scores <= _SHORT_VMEM_BUDGET


def short_attention_reference(qkv, heads: int,
                              scale: Optional[float] = None):
    """jnp reference on the same layout: split the projection, heads to
    the front, :func:`flash_attention_reference`, heads back."""
    import jax.numpy as jnp

    B, S, D = qkv.shape[0], qkv.shape[1], qkv.shape[2] // 3

    def split(t):
        return t.reshape(B, S, heads, D // heads).transpose(0, 2, 1, 3)

    o = flash_attention_reference(
        *map(split, jnp.split(qkv, 3, axis=-1)), scale)
    return o.transpose(0, 2, 1, 3).reshape(B, S, D)


def short_attention(qkv, heads: int, scale: Optional[float] = None):
    """Whole-sequence attention for short sequences, read where the qkv
    projection wrote it: ``qkv (B, S, 3·D)`` (q, k, v side by side,
    each ``heads`` contiguous slices of ``D/heads`` lanes) to ``o (B, S,
    D)``, the layout the output projection reads — no head split, no
    transpose either side.

    One grid step takes a frame with all its heads.  The whole key range
    is one block, so the softmax is the plain one in float32 (row
    maximum, ``exp``, row sum; no running statistics), and scores and
    probabilities never leave VMEM.  Products take the operands as they
    are (bf16 on the MXU for bf16 activations) and accumulate in
    float32; the probabilities are cast to the activations' dtype for
    ``p v`` and the row sum divides the product.  Heads of 64 are worked
    in pairs on their shared 128-lane block: a head's scores contract
    over the whole block against keys whose other half is zeroed, and
    its ``p v`` keeps its own half of the lanes — the matrix unit is as
    busy as with 64-lane slices and nothing is shuffled across lanes.

    ``S`` need not tile: the blocks overhang the array (queries to whole
    tiles, keys to whole lanes); key columns beyond ``S`` are masked to
    -inf before the maximum, value rows beyond ``S`` are zeroed (what
    lies there is not defined, and 0 x NaN is NaN), and query rows
    beyond ``S`` are never written.  A shape
    :func:`short_attention_available` refuses is an error: the caller
    chooses."""
    import jax.numpy as jnp

    if not short_attention_available(qkv.shape, heads, qkv.dtype):
        raise ValueError(
            f"short_attention: {tuple(qkv.shape)} {qkv.dtype} with {heads} "
            "heads is not a shape short_attention_available admits")
    jax, pl, pltpu = _pl()
    B, S, D = qkv.shape[0], qkv.shape[1], qkv.shape[2] // 3
    dh = D // heads
    if scale is None:
        scale = dh ** -0.5
    width = max(dh, _LANE)            # lanes worked at a time
    rows_q, rows_k = _short_rows(S, qkv.dtype)
    nt = (((1,), (1,)), ((), ()))     # q k^T without a transpose

    def kernel(q_ref, k_ref, v_ref, o_ref):
        key_ok = jax.lax.broadcasted_iota(
            jnp.int32, (rows_q, rows_k), 1) < S
        row_ok = jax.lax.broadcasted_iota(
            jnp.int32, (rows_k, width), 0) < S
        lane_k = jax.lax.broadcasted_iota(jnp.int32, (rows_k, width), 1)
        lane_q = jax.lax.broadcasted_iota(jnp.int32, (rows_q, width), 1)
        for g in range(D // width):
            lanes = slice(g * width, (g + 1) * width)
            q, k, v = q_ref[0, :, lanes], k_ref[0, :, lanes], \
                v_ref[0, :, lanes]
            if rows_k != S:
                v = jnp.where(row_ok, v, jnp.zeros_like(v))
            o = None
            for j in range(width // dh):
                kj = k if width == dh else jnp.where(
                    (lane_k >= j * dh) & (lane_k < (j + 1) * dh),
                    k, jnp.zeros_like(k))
                s = jax.lax.dot_general(
                    q, kj, nt, preferred_element_type=jnp.float32) * scale
                if rows_k != S:
                    s = jnp.where(key_ok, s, -jnp.inf)
                p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                norm = jnp.sum(p, axis=-1, keepdims=True)
                oj = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) / norm
                o = oj if o is None else jnp.where(
                    lane_q < j * dh, o, oj)
            o_ref[0, :, lanes] = o.astype(o_ref.dtype)

    # no ``name=``: it would open a scope of its own, and the caller's
    # scope is the stage this call's device time is booked to
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, rows_q, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, rows_k, D), lambda b: (b, 0, 1)),
            pl.BlockSpec((1, rows_k, D), lambda b: (b, 0, 2)),
        ],
        out_specs=pl.BlockSpec((1, rows_q, D), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(qkv, qkv, qkv)


# -- a decode kernel's walk over a stream's live rows --------------------------
#
# A decode attention kernel that leaves its cache in HBM and copies a
# stream's live rows itself fetches by one unit and computes by another.
# Rows are named on a LATTICE of whole lane tiles that divides the cache
# (so every copy starts aligned and a ring's end falls between two
# cells).  A stream's live cells, in the order of their positions, are
# cut into ITEMS of a chunk's cells counted from the FIRST live cell:
# every item is a whole chunk but the last, which is copied and computed
# on in pieces of 2^k cells (at most one of each size, none of a row
# not in use beyond its cell), and an item that runs over a ring's end
# is copied cell by cell.  The items go through a queue of buffers whose
# copies run on from one stream into the next.  What follows is the
# walk's arithmetic (``jnp`` on scalars, traced or not) and, on it, the
# queue as a kernel runs it for one stream (:func:`_walk_stream`).  Both
# decode kernels walk: :func:`gqa_decode_attention` (K and V caches, a
# ring or dense) and :func:`latent_decode_attention` (one dense latent
# cache) hand the queue their caches, their buffers and the update of
# their running sums, and :func:`decode_rows_fetched` counts by the
# same cells.

#: rows of one lattice cell: a lane tile of scores
_WALK_LATTICE = _LANE
#: bytes of one chunk's copies (a row: K and V of every group) the plan
#: aims at, and bytes of the queue's buffers together; ``PERF.md``
#: section 6 (PR 39) has what other sizes read on the chip
_WALK_CHUNK_BYTES = 2 << 20
_WALK_QUEUE_BYTES = 8 << 20
#: rows a chunk has at least, where twice ``_WALK_CHUNK_BYTES`` hold
#: them: an update of the running sums costs as much at 128 rows as at
#: 512, so a WIDE row (ten K/V heads: 5,120 B) must not shrink the chunk
#: to 256 rows.  On the chip (``PERF.md`` section 6, PR 50; 32 streams,
#: 10 groups of 4 rows, a cache of 16,384 at 8-16 k): chunks of 256 rows
#: 4.63 ms a call, of 512, 1,024 and 2,048 rows 2.77-2.79
_WALK_CHUNK_ROWS = 512


class WalkPlan(NamedTuple):
    """How a decode kernel walks caches of ``total`` rows: ``chunk``
    rows a buffer (whole cells; divides ``total``), ``slots`` buffers
    (``slots - 1`` items in flight while one is computed on)."""
    chunk: int
    slots: int

    @property
    def cells(self) -> int:
        """Cells of a chunk."""
        return self.chunk // _WALK_LATTICE

    @property
    def pieces(self) -> tuple:
        """The sizes, in cells, a partial item comes in, largest
        first: the powers of two below a chunk's cells."""
        return tuple(1 << k for k in reversed(range(
            (self.cells - 1).bit_length())))


def decode_walk_plan(total: int, row_bytes: int) -> WalkPlan:
    """The plan for caches of ``total`` rows of ``row_bytes``, from what
    a call can see: the largest chunk of whole cells that divides
    ``total`` within ``_WALK_CHUNK_BYTES``, or within ``_WALK_CHUNK_ROWS``
    rows where that is more and twice those bytes hold them (and within
    half the cache, so that a short cache still has a chunk to compute
    on while the next one lands), and as many buffers as
    ``_WALK_QUEUE_BYTES`` hold (three to eight)."""
    lat = _WALK_LATTICE
    rows = _WALK_CHUNK_BYTES // row_bytes
    if rows < _WALK_CHUNK_ROWS \
            and _WALK_CHUNK_ROWS * row_bytes <= 2 * _WALK_CHUNK_BYTES:
        rows = _WALK_CHUNK_ROWS
    most = max(min(rows, total // 2), lat)
    chunk = next(c for c in range(most // lat * lat, 0, -lat)
                 if total % c == 0)
    slots = min(max(_WALK_QUEUE_BYTES // (chunk * row_bytes), 3), 8)
    return WalkPlan(chunk, slots)


def walk_cells(pos, total: int, window: int):
    """The lattice cells that hold a live row of a stream at ``pos``
    (positions ``max(0, pos - window + 1) .. pos`` of a cache of
    ``total`` slots, position ``p`` in slot ``p % total``): the first
    one, counted by position before the ring folds it, and how many.
    Never more than the cache has: a window within a cell of the
    ring's length meets its own tail in one cell, which is copied
    once."""
    import jax.numpy as jnp

    first = jnp.maximum(pos - window + 1, 0) // _WALK_LATTICE
    return first, jnp.minimum(pos // _WALK_LATTICE - first + 1,
                              total // _WALK_LATTICE)


def walk_items(cells, plan: WalkPlan):
    """Items (buffers' worth) that ``cells`` live cells come in: whole
    chunks and, of what is left, one more."""
    return (cells + plan.cells - 1) // plan.cells


def walk_item(first, cells, item, total: int, plan: WalkPlan):
    """Item ``item`` of the live cells ``first .. first + cells - 1``:
    the cell of the cache it starts in (folded), how many cells it
    holds, and whether it runs over the cache's end (a ring's: it is
    copied cell by cell then)."""
    import jax.numpy as jnp

    ring = total // _WALK_LATTICE
    start = (first + item * plan.cells) % ring
    count = jnp.minimum(cells - item * plan.cells, plan.cells)
    return start, count, start + count > ring


def walk_piece(count, size: int):
    """Of an item of ``count`` cells (fewer than a chunk's): whether it
    has a piece of ``size`` cells (a power of two), and the cell of the
    item the piece starts at (the larger pieces come first)."""
    return (count & size) != 0, count - count % (2 * size)


def decode_rows_fetched(positions, total: int, window: int):
    """Rows of ONE cache (a token's K and V count as one row) that the
    walk copies for streams at ``positions [B]``: every live cell
    whole, so at most a cell less one row beyond the rows in use at
    each end a window has inside the cache (two on a ring past its
    window, one on a dense cache).  The kernel issues its copies by the
    same cells; a scalar of ``positions``' type."""
    import jax.numpy as jnp

    _, cells = walk_cells(jnp.asarray(positions), total, window)
    return jnp.sum(cells) * _WALK_LATTICE


def _walk_plan_check(kernel: str, plan: WalkPlan, total: int):
    """An explicit plan has to divide the cache into whole cells and
    leave a buffer to copy into beside the one computed on."""
    if total % plan.chunk or plan.chunk % _WALK_LATTICE or plan.slots < 2:
        raise ValueError(f"{kernel}: {plan} does not divide caches of "
                         f"{total} rows")


def _walk_stream(pos_ref, pairs, arrived, base_ref, sums, o_ref, update, *,
                 total: int, window: int, plan: WalkPlan, ring: bool,
                 cell_rows: int = _WALK_LATTICE):
    """One grid step of a decode kernel on the walk (the grid runs over
    streams, in order): stream ``i``'s items through the queue, the
    running sums over them, and its output.

    ``pairs`` are the ``(cache, buffers)`` an item is copied between (a
    cache ``[streams, .., positions, width]`` left in HBM, its buffers
    ``[slots, .., chunk, width]``; one copy a pair an item, signalled on
    ``arrived[pair, slot]``; a cell of the lattice is ``cell_rows`` rows
    of either, fewer than its positions where a row packs several),
    ``base_ref`` (SMEM) carries the buffer of
    a stream's first item from one step to the next, ``sums`` are the
    running max, normaliser and accumulator, and ``update(pos, slot,
    start, offset, size)`` adds to them rows ``offset .. offset + size``
    of buffer ``slot``, which holds the cache's rows from cell ``start``
    on.  ``ring`` says whether an item can run over the cache's end: a
    dense cache has no cell-by-cell copies in its program.  ``total``,
    ``window``, the plan's chunk and ``update``'s rows count positions."""
    import jax.numpy as jnp

    jax, pl, pltpu = _pl()
    lat, (rows, slots) = _WALK_LATTICE, plan
    ahead = slots - 1                    # items in flight beside the one
    #                                      being computed on
    streams = pos_ref.shape[0]
    m_ref, l_ref, acc_ref = sums

    def walk(pos):
        """A stream's first live cell, its cells and its items."""
        first, cells = walk_cells(pos, total, window)
        return first, cells, walk_items(cells, plan)

    i = pl.program_id(0)                # the stream
    pos = pos_ref[i]

    def copies(stream, start, count, over, slot, wait=False):
        """Start (or wait for) the copies of an item of ``stream``
        (:func:`walk_item`) into buffer ``slot``."""

        def copy(cell, offset, size):
            """``size`` cells from ``cell`` of the cache to ``offset``
            cells into the buffer."""
            for n, (ref, buf) in enumerate(pairs):
                between = (slice(None),) * (len(ref.shape) - 3)
                dma = pltpu.make_async_copy(
                    ref.at[(stream, *between, pl.ds(pl.multiple_of(
                        cell * cell_rows, cell_rows), size * cell_rows),
                        slice(None))],
                    buf.at[(slot, *between, pl.ds(pl.multiple_of(
                        offset * cell_rows, cell_rows), size * cell_rows),
                        slice(None))],
                    arrived.at[n, slot])
                dma.wait() if wait else dma.start()

        def pieces():
            for size in plan.pieces:
                has, offset = walk_piece(count, size)
                pl.when(has)(functools.partial(
                    copy, start + offset, offset, size))

        def cells():
            def cell(c, _):
                copy((start + c) % (total // lat), c, 1)
                return 0

            jax.lax.fori_loop(0, count, cell, 0)

        whole = count == plan.cells
        if ring:
            whole = whole & jnp.logical_not(over)
        pl.when(whole)(lambda: copy(start, 0, plan.cells))
        pl.when(jnp.logical_not(whole | over if ring else whole))(pieces)
        if ring:
            pl.when(over)(cells)

    first, cells, items = walk(pos)
    # the queue runs on into the next stream's first items
    after = jnp.minimum(i + 1, streams - 1)
    first_n, cells_n, items_n = walk(pos_ref[after])
    items_n = jnp.where(i + 1 < streams, jnp.minimum(items_n, ahead), 0)

    @pl.when(i == 0)
    def _first():
        base_ref[0] = 0

    base = base_ref[0]          # the buffer of this stream's first item

    def issue(t):
        """Start item ``t`` of the queue, counted from this stream's
        first: its own, then the next stream's."""
        own = t < items
        start, count, over = walk_item(
            jnp.where(own, first, first_n),
            jnp.where(own, cells, cells_n),
            jnp.where(own, t, t - items), total, plan)
        pl.when(own | (t - items < items_n))(
            lambda: copies(jnp.where(own, i, after), start, count, over,
                           (base + t) % slots))

    def top_up(t, _):
        # the stream before started this one's first items; nobody
        # started the first stream's, nor what a stream of few items
        # leaves of the queue
        pl.when((i == 0) | (t >= items))(lambda: issue(t))
        return 0

    jax.lax.fori_loop(0, ahead, top_up, 0)
    m_ref[:] = jnp.full(m_ref.shape, -1e30, jnp.float32)
    l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    def consume(j, _):
        slot = (base + j) % slots
        start, count, over = walk_item(first, cells, j, total, plan)
        copies(i, start, count, over, slot, wait=True)
        issue(j + ahead)
        pl.when(count == plan.cells)(
            lambda: update(pos, slot, start, 0, rows))

        @pl.when(count < plan.cells)
        def _pieces():
            for size in plan.pieces:
                has, offset = walk_piece(count, size)
                pl.when(has)(functools.partial(
                    update, pos, slot, start,
                    pl.multiple_of(offset * lat, lat), size * lat))
        return 0

    jax.lax.fori_loop(0, items, consume, 0)
    o_ref[0] = acc_ref[:] / l_ref[..., :1]
    base_ref[0] = (base + items) % slots


# -- latent decode attention --------------------------------------------------
#
# A latent cache keeps of a token its ``rank`` latent values ``c_kv`` and
# the ``rope`` values of its rotary key ``k_r``.  Where ``rank`` is whole
# lane tiles and ``rope`` divides one (512 and 64 in both published
# models), a cache row PACKS the ``per = 128 / rope`` positions ``per * r
# .. per * r + per - 1``: their latent parts side by side, then one lane
# tile of their rotary keys, ``[c_kv 0 | .. | c_kv per-1 | k_r 0 .. k_r
# per-1]``, so a row stores what its positions hold and nothing else and
# every slice of it is whole tiles (1,152 values for two positions; a row
# a position padded to whole lanes took 640 for 576).  Any other sizes
# keep a row a position, ``[c_kv | k_r | 0..]`` padded to whole lanes.
# :func:`latent_cache_row` is the rule, and the kernel, its reference and
# ``models/mla.py`` (which writes the rows) all read the form off it.


def latent_cache_row(rank: int, rope: int) -> tuple:
    """``(per, width)``: the positions a row of a latent cache holds and
    the row's width, for tokens of ``rank`` latent and ``rope`` rotary
    values.  Packed where that stores fewer values than a padded row and
    a lattice cell's ``128 / per`` rows are whole tiles of a 2-byte type
    (``per`` at most 8)."""
    if rank % _LANE == 0 and 16 <= rope < _LANE and _LANE % rope == 0:
        per = _LANE // rope
        return per, per * rank + _LANE
    return 1, -(-(rank + rope) // _LANE) * _LANE


def latent_pack(rows, rank: int):
    """Tokens' ``[.., n, rank + rope]`` values ``(c_kv, k_r)`` as the
    ``[.., n / per, width]`` cache rows that hold them, the first token
    at a row's first position (``n`` whole rows)."""
    import jax.numpy as jnp

    *lead, n, latent = rows.shape
    per, width = latent_cache_row(rank, latent - rank)
    if per == 1:
        return jnp.pad(rows, [(0, 0)] * (len(lead) + 1)
                       + [(0, width - latent)])
    return jnp.concatenate(
        [rows[..., :rank].reshape(*lead, n // per, per * rank),
         rows[..., rank:].reshape(*lead, n // per, _LANE)], axis=-1)


def latent_unpack(rows, rank: int, rope: int):
    """The ``[.., n * per, rank + rope]`` values ``(c_kv, k_r)`` of the
    positions that the cache rows ``[.., n, width]`` hold, in order: the
    inverse of :func:`latent_pack` on a block of rows (never on a whole
    cache: it is a copy)."""
    import jax.numpy as jnp

    *lead, n, _ = rows.shape
    per, _ = latent_cache_row(rank, rope)
    if per == 1:
        return rows[..., :rank + rope]
    return jnp.concatenate(
        [rows[..., :per * rank].reshape(*lead, n * per, rank),
         rows[..., per * rank:].reshape(*lead, n * per, rope)], axis=-1)


def latent_place(old, rows, positions, rank: int):
    """The cache rows ``old [B, width]`` with the tokens ``rows [B, rank
    + rope]`` at ``positions [B]`` put in their places: a packed row's
    other positions stay as they were."""
    import jax.numpy as jnp

    rope = rows.shape[-1] - rank
    per, _ = latent_cache_row(rank, rope)
    if per == 1:
        return latent_pack(rows, rank).astype(old.dtype)
    mine = np.concatenate([np.arange(per * rank) // rank,
                           np.arange(_LANE) // rope])
    new = jnp.concatenate([jnp.tile(rows[:, :rank], (1, per)),
                           jnp.tile(rows[:, rank:], (1, per))], axis=-1)
    return jnp.where(mine[None] == (positions % per)[:, None],
                     new.astype(old.dtype), old)


def latent_decode_attention_refusal(q_shape, cache_shape,
                                    rank: int) -> Optional[str]:
    """Why :func:`latent_decode_attention` cannot take these shapes, or
    None: the cache's rows as :func:`latent_cache_row` lays them out for
    the queries' ``rank`` and rotary values, and a cache of whole
    lattice cells (lane tiles of positions), at least one."""
    if len(q_shape) != 3 or len(cache_shape) != 3 \
            or q_shape[0] != cache_shape[0]:
        return f"q {tuple(q_shape)} and cache {tuple(cache_shape)} are " \
               "not [B, heads, width] and [B, rows, width]"
    if not 0 < rank <= q_shape[2]:
        return f"queries of {q_shape[2]} values do not hold the {rank} " \
               "latent ones"
    per, width = latent_cache_row(rank, q_shape[2] - rank)
    if width != cache_shape[2]:
        return f"queries of {q_shape[2]} values ({rank} latent) want " \
               f"cache rows of {width} for {per} positions, whole lanes " \
               f"of {_LANE}, not {cache_shape[2]}"
    positions = cache_shape[1] * per
    if positions < _WALK_LATTICE or positions % _WALK_LATTICE:
        return f"{positions} cache positions are not whole lattice " \
               f"cells of {_WALK_LATTICE}"
    return None


def latent_decode_attention_reference(q, cache, positions, rank: int,
                                      scale: float):
    """The kernel's mathematics in jnp: every head's scores against
    every cached position up to the stream's, softmax in float32,
    values the positions' first ``rank`` entries."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    cache = latent_unpack(cache, rank, q.shape[2] - rank).astype(q.dtype)
    s = jnp.einsum("bhl,btl->bht", q, cache,
                   preferred_element_type=jnp.float32, precision=hp)
    t = jnp.arange(cache.shape[1], dtype=jnp.int32)
    s = jnp.where(t[None, None, :] <= positions[:, None, None],
                  s * scale, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btl->bhl", prob.astype(q.dtype), cache,
                      preferred_element_type=jnp.float32,
                      precision=hp)[..., :rank]


def latent_decode_attention(q, cache, positions, rank: int, scale: float):
    """Absorbed latent attention of one token a stream over a latent
    cache: ``q [B, heads, rank + rope]`` (each head's absorbed query and
    its rotary part side by side), ``cache [B, positions / per, width]``
    (dense: position ``p`` in row ``p // per``, laid out as
    :func:`latent_cache_row` says: two positions a row of 1,152 values
    at ``rank`` 512 and ``rope`` 64, a position a row padded to whole
    lanes where ``rank`` is not whole lanes), ``positions [B]`` int32.
    Returns
    ``[B, heads, rank]`` float32: softmax(q k^T * scale) over positions
    ``0..positions[b]``, times the positions' latent values.

    One pass, and the kernel copies a stream's live rows itself: the
    cache stays in HBM, the grid runs over streams, and a stream's rows
    come in by the walk above (:func:`decode_walk_plan` on the bytes a
    position takes as stored: chunks of 1,664 positions in four buffers
    for caches of 16,640, of 1,024 in seven for caches of 4,096),
    counted from the first live cell, the last item in pieces of 8, 4, 2
    and 1 cells, through the queue :func:`gqa_decode_attention` runs
    too (:func:`_walk_stream`): its copies run on into the next stream's
    first items, so no stream starts cold.  An item is read once from
    its buffer and serves both products in one update of the running
    max, normaliser and accumulator.  A softmax does not care in which
    order it meets its positions, so a packed row is never unpacked:
    the positions ``per * r + h`` of an item's rows ``r`` are scored as
    one part-block a ``h`` (the absorbed query against the ``h``-th
    latent part, the rotary query, shifted to the ``h``-th key's lanes,
    against the keys' tile), masked by their own positions, and the
    values product takes each latent part as it lies.  Only an item that
    reaches the stream's position masks.  What the walk fetches beyond
    the positions in use is less than a cell a stream
    (:func:`decode_rows_fetched` with ``window = positions``).  (XLA's
    own two products read every position of the cache twice, and one
    whose rows are not whole lanes it copies whole first.)

    On the chip (the kernel alone at ``dsv2.decode16k``'s shapes, 32
    streams of 32 heads at 8-16 k, device ms a call; ``PERF.md`` section
    6, PR 41, rows of 640 values a position): blocks of 640 rows through
    a ``BlockSpec`` pipeline 0.845 -> the walk 0.703; its copies alone
    0.700 (730 GB/s of rows as the cache held them, 640 values for 576
    in use), its arithmetic alone 0.414: the copies bind, and 3 to 8
    buffers and chunks of 640 rows read the same to 0.4 %.  Inside the
    decode step a call read 0.80 -> 0.678 (754 GB/s).  Rows of 640
    values a position -> two positions a row of 1,152 (PR 44, the same
    streams, both trees in one call): 0.686 -> 0.618, copies alone 0.685
    -> 0.616 (755 GB/s either way: time follows the bytes, 576/640),
    arithmetic alone 0.397 -> 0.366; at ``longcat.decode4k``'s shapes
    (128 streams of 64 heads at 2-4 k) 0.720 -> 0.669, copies alone
    0.718 -> 0.667, arithmetic alone 0.546 -> 0.558: the copies bind at
    64 heads too, and there the queries read and the output written, 29
    MB a call, are 6 % of the bytes and do not shrink.  Inside the
    decode steps a call reads 0.678 -> 0.609 and 0.688 -> 0.655.
    Heads are padded to whole tiles here; a shape
    :func:`latent_decode_attention_refusal` names is an error, there is
    no second path."""
    refusal = latent_decode_attention_refusal(q.shape, cache.shape, rank)
    if refusal:
        raise ValueError(f"latent_decode_attention: {refusal}")
    per, width = latent_cache_row(rank, q.shape[2] - rank)
    plan = decode_walk_plan(cache.shape[1] * per,
                            width // per * np.dtype(q.dtype).itemsize)
    return _latent_decode_walk(q, cache, positions, rank, scale, plan)


def _latent_decode_walk(q, cache, positions, rank: int, scale: float,
                        plan: WalkPlan):
    """:func:`latent_decode_attention` by an explicit ``plan`` (the
    tests and the chip's sweeps choose theirs)."""
    import jax
    import jax.numpy as jnp

    b, held, _ = q.shape
    rope = q.shape[2] - rank
    per, width = latent_cache_row(rank, rope)
    # whole tiles of heads: padded heads score zero everywhere and are
    # cut off again
    heads = -(-held // _sublane(q.dtype)) * _sublane(q.dtype)
    q = jnp.pad(q, ((0, 0), (0, heads - held), (0, 0)))
    # beside the absorbed query: zeros to a padded row's width, or the
    # rotary query once a position of a packed row, on that key's lanes
    # of a tile of its own
    lanes = [(0, width - q.shape[2])] if per == 1 else [
        (h * rope, _LANE - (h + 1) * rope) for h in range(per)]
    q = jnp.concatenate([q[..., :rank]] + [
        jnp.pad(q[..., rank:], ((0, 0), (0, 0), part)) for part in lanes],
        axis=-1)
    call = _latent_decode_walk_call(b, heads, rank, per, width,
                                    cache.shape[1] * per, float(scale),
                                    np.dtype(q.dtype).name, plan,
                                    _interpret())
    # the stage a trace books the kernel's time to (a jit is no scope)
    with jax.named_scope("latent_decode_attention"):
        out = call(positions.astype(jnp.int32), q, cache.astype(q.dtype))
    return out[:, :held, :rank]


@functools.lru_cache(maxsize=16)
def _latent_decode_walk_call(b: int, heads: int, rank: int, per: int,
                             width: int, total: int, scale: float,
                             dtype: str, plan: WalkPlan, interpret: bool):
    """The jitted call of :func:`latent_decode_attention` for one shape,
    built once: a model's layers share the function, so a program that
    attends in five layers traces and lowers the kernel once (as
    :func:`_gqa_decode_walk_call` does).  ``total`` positions in rows of
    ``width`` for ``per`` of them each."""
    import jax.numpy as jnp

    jax, pl, pltpu = _pl()
    lat, (rows, slots) = _WALK_LATTICE, plan
    _walk_plan_check("latent_decode_attention", plan, total)
    packed = per > 1
    # a padded row's values that are not whole lanes come out of the
    # whole row
    values = rank if rank % _LANE == 0 else width
    q_width = rank + per * _LANE if packed else width

    def kernel(pos_ref, q_ref, cache_ref, o_ref, buf, arrived, base_ref,
               m_ref, l_ref, acc_ref):

        def update(pos, slot, start, offset, size):
            """The online softmax over ``size`` positions of buffer
            ``slot`` from position ``offset``: both products off the
            same rows, a part-block a position of a row."""
            n, row = size // per, offset // per
            if not isinstance(offset, int):   # a piece: whole cells in
                row = pl.multiple_of(row, lat // per)
            at = pl.ds(row, n)

            def keys(h: int):
                """Part ``h``'s scores ``(heads, n)`` and its values."""
                if not packed:
                    kb = buf[slot, at, :]                    # (n, width)
                    return jax.lax.dot_general(
                        q_ref[0], kb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32), kb[:, :values]
                kb = buf[slot, at, pl.ds(h * rank, rank)]    # (n, rank)
                s = jax.lax.dot_general(
                    q_ref[0, :, pl.ds(0, rank)], kb,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return s + jax.lax.dot_general(
                    q_ref[0, :, pl.ds(rank + h * _LANE, _LANE)],
                    buf[slot, at, pl.ds(per * rank, _LANE)],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32), kb

            def add(masked: bool):
                parts = [keys(h) for h in range(per)]
                scores = [s * scale for s, _ in parts]
                if masked:                                # (heads, n)
                    first = start * lat + offset \
                        + per * jax.lax.broadcasted_iota(
                            jnp.int32, (heads, n), 1)
                    scores = [jnp.where(first + h <= pos, s, -1e30)
                              for h, s in enumerate(scores)]
                # running max / normaliser replicated across a lane
                # width, as in flash_attention above
                m_prev = m_new = m_ref[:]
                for s in scores:
                    m_new = jnp.maximum(m_new,
                                        jnp.max(s, axis=-1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                l_new, acc = l_ref[:] * corr, acc_ref[:] * corr[:, :1]
                for s, (_, vb) in zip(scores, parts):
                    p = jnp.exp(s - m_new[:, :1])
                    l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
                    acc = acc + jax.lax.dot_general(
                        p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                l_ref[:], acc_ref[:], m_ref[:] = l_new, acc, m_new

            if size < rows:       # a piece of the last item: it may
                add(True)         # hold the position
                return
            # a whole chunk masks only where it reaches the position
            reaches = start * lat + rows > pos + 1
            pl.when(reaches)(lambda: add(True))
            pl.when(jnp.logical_not(reaches))(lambda: add(False))

        _walk_stream(pos_ref, ((cache_ref, buf),), arrived, base_ref,
                     (m_ref, l_ref, acc_ref), o_ref, update,
                     total=total, window=total, plan=plan, ring=False,
                     cell_rows=lat // per)

    buffer = (slots, rows // per, width)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, q_width), lambda i, pos: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, values), lambda i, pos: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(buffer, dtype),
            pltpu.SemaphoreType.DMA((1, slots)),
            pltpu.SMEM((1,), jnp.int32),                 # first buffer
            pltpu.VMEM((heads, _LANE), jnp.float32),     # running max
            pltpu.VMEM((heads, _LANE), jnp.float32),     # normaliser
            pltpu.VMEM((heads, values), jnp.float32),    # accumulator
        ])
    call = pl.pallas_call(
        kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((b, heads, values), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(np.prod(buffer))
            * np.dtype(dtype).itemsize + (4 << 20)),
        name="latent_decode_attention",
        interpret=interpret)

    def latent_decode_attention(*operands):
        return call(*operands)

    return jax.jit(latent_decode_attention)


# -- latent prefill attention -------------------------------------------------

#: positions a key block of :func:`latent_prefill_attention` holds (a
#: chunk shorter than that is one block)
_PREFILL_KEY_BLOCK = 1024
#: fast memory a grid step of :func:`latent_prefill_attention` may take:
#: a head group's queries, output and running softmax whole, a key
#: block's rows and what is rebuilt from them, a query block's scores
#: (the call states it through ``vmem_limit_bytes``)
_PREFILL_VMEM_BUDGET = 64 << 20
#: heads a grid step takes and query rows a pass of the inner loop
#: scores, where the budget allows (``PERF.md`` section 6, PR 48: the
#: sweep on the chip)
_PREFILL_HEADS_A_STEP = 2
_PREFILL_QUERY_ROWS = 512


def _prefill_key_block(c: int, key_block: int = _PREFILL_KEY_BLOCK) -> int:
    """Positions a key block holds where whole blocks must tile ``c``
    positions: a chunk of :func:`latent_prefill_attention`, so that the
    blocks end with it; a cache of :func:`gqa_prefill_attention`."""
    return int(np.gcd(int(key_block), int(c)))


def _prefill_rope_lanes(rank: int, rope: int) -> int:
    """Lanes the rotary keys of a cache row take: a packed row's one
    tile, a padded row's whole tiles after the latent part."""
    per, width = latent_cache_row(rank, rope)
    return _LANE if per > 1 else width - rank


def _prefill_vmem(c: int, group: int, tq: int, kb: int, rank: int,
                  rope: int, nope: int, v: int, dtype) -> int:
    """Bytes a grid step of :func:`latent_prefill_attention` holds."""
    size = np.dtype(dtype).itemsize
    per, width = latent_cache_row(rank, rope)
    wide = nope + _prefill_rope_lanes(rank, rope)
    blocks = 2 * size * (c * group * (per * wide + v)       # q and o
                         + rank * group * (nope + v) + kb // per * width)
    stats = 4 * group * c * (2 * _LANE + v)
    rebuilt = size * kb * (wide + v)
    # a pass's scores, probabilities and their cast in flight; the
    # mask's offsets
    scores = 4 * 4 * tq * kb + 4 * tq * kb // per
    return blocks + stats + rebuilt + scores


def _prefill_tiles(c: int, heads: int, most: int, dtype, vmem) -> tuple:
    """``(heads a grid step, query rows a pass)`` of a prefill kernel
    for a chunk of ``c`` tokens: the largest divisors of ``heads`` and
    ``c`` up to ``most`` and ``_PREFILL_QUERY_ROWS`` (whole tiles of
    rows) whose step, ``vmem(heads a step, query rows)`` bytes, fits
    ``_PREFILL_VMEM_BUDGET``; ``(0, 0)`` where none does."""
    tile = _sublane(dtype)
    for group in range(min(most, heads), 0, -1):
        if heads % group:
            continue
        for tq in range(min(_PREFILL_QUERY_ROWS, c) // tile * tile, 0, -tile):
            if c % tq == 0 and vmem(group, tq) <= _PREFILL_VMEM_BUDGET:
                return group, tq
    return 0, 0


def latent_prefill_tiles(c: int, heads: int, rank: int, rope: int,
                         nope: int, v: int, dtype) -> tuple:
    """``(heads a grid step, query rows a pass)`` of
    :func:`latent_prefill_attention` for a chunk of ``c`` tokens
    (:func:`_prefill_tiles`, up to ``_PREFILL_HEADS_A_STEP`` heads)."""
    kb = _prefill_key_block(c)
    return _prefill_tiles(
        c, heads, _PREFILL_HEADS_A_STEP, dtype,
        lambda group, tq: _prefill_vmem(c, group, tq, kb, rank, rope, nope,
                                        v, dtype))


def latent_prefill_attention_refusal(q_nope_shape, q_rope_shape, cache_shape,
                                     w_shape, dtypes) -> Optional[str]:
    """Why :func:`latent_prefill_attention` cannot take these shapes, or
    None: ``q_nope [C, heads, nope]`` and ``q_rope [C, heads, rope]``
    beside ``w_kvb [rank, heads, nope + v]`` and a cache ``[streams,
    rows, width]`` laid out as :func:`latent_cache_row` says, all of ONE
    type (``dtypes``: the set of theirs), bf16 or float32; ``rank``,
    ``nope`` and ``v`` whole lanes; a key block (1,024 positions, or the
    chunk where it is shorter) whose every part (the positions ``per * r
    + h`` of its rows) is whole lanes of scores; a cache that holds the
    chunk; and a tiling that fits."""
    names = sorted(np.dtype(d).name for d in dtypes)
    if names not in (["bfloat16"], ["float32"]):
        return f"operands of {', '.join(names)}: all bfloat16 or all float32"
    if len(q_nope_shape) != 3 or len(q_rope_shape) != 3 \
            or len(w_shape) != 3 or len(cache_shape) != 3 \
            or tuple(q_nope_shape[:2]) != tuple(q_rope_shape[:2]) \
            or w_shape[1] != q_nope_shape[1] \
            or w_shape[2] <= q_nope_shape[2]:
        return f"q_nope {tuple(q_nope_shape)}, q_rope " \
               f"{tuple(q_rope_shape)}, w_kvb {tuple(w_shape)} and cache " \
               f"{tuple(cache_shape)} are not [C, heads, nope], [C, heads, " \
               "rope], [rank, heads, nope + v] and [streams, rows, width]"
    (c, heads, nope), rope, rank = q_nope_shape, q_rope_shape[2], w_shape[0]
    v = w_shape[2] - nope
    if rank % _LANE or nope % _LANE or v % _LANE:
        return f"widths {rank} (latent), {nope} (nope) and {v} (values) " \
               f"are not whole lanes of {_LANE}"
    per, width = latent_cache_row(rank, rope)
    if width != cache_shape[2]:
        return f"tokens of {rank} latent and {rope} rotary values want " \
               f"cache rows of {width} for {per} positions, not " \
               f"{cache_shape[2]}"
    kb = _prefill_key_block(c)
    if kb % (per * _LANE):
        return f"a key block of {kb} positions (a chunk of {c}) is not " \
               f"whole lanes of {_LANE} for each of a row's {per}"
    if cache_shape[1] * per < c:
        return f"a cache of {cache_shape[1] * per} positions does not " \
               f"hold a chunk of {c}"
    if not latent_prefill_tiles(c, heads, rank, rope, nope, v, names[0])[0]:
        return f"no step of a chunk of {c} fits " \
               f"{_PREFILL_VMEM_BUDGET >> 20} MiB"
    return None


def latent_prefill_attention_reference(q_nope, q_rope, cache, slot, start,
                                       w_kvb, scale: float,
                                       key_block: int = _PREFILL_KEY_BLOCK):
    """The kernel's mathematics in jnp, and the path ``models/mla.py``
    takes for a shape the kernel refuses: XLA's own ``while`` over the
    key blocks up to ``start + C``, each block's keys and values rebuilt
    from its rows (unpacked) and rounded to the queries' type, the
    scores of the whole chunk against the block in float32, a running
    softmax.  (On the chip each iteration writes and reads those scores
    through HBM several times: ``PERF.md`` section 6, PR 48.)"""
    import jax
    import jax.numpy as jnp

    c, heads, nope = q_nope.shape
    rank, rope = w_kvb.shape[0], q_rope.shape[2]
    per, _ = latent_cache_row(rank, rope)
    hp = jax.lax.Precision.HIGHEST if w_kvb.dtype == jnp.float32 else None
    # a chunk starts at a multiple of its own length (the caller's
    # contract), so it starts a cache row, and whole key blocks never
    # reach beyond start + C
    kb = _prefill_key_block(c, key_block)
    if kb % per:
        raise ValueError(f"latent prefill: key blocks of {kb} positions (a "
                         f"chunk of {c}) are not whole cache rows of {per}")
    positions = start + jnp.arange(c, dtype=jnp.int32)

    def body(j, carry):
        m, l, acc = carry
        blk = latent_unpack(jax.lax.dynamic_slice(
            cache, (slot, j * (kb // per), 0),
            (1, kb // per, cache.shape[2]))[0], rank, rope
        ).astype(q_nope.dtype)
        blk_r = blk[:, rank:]
        blk = blk[:, :rank]
        kv = jnp.einsum("kr,rhd->khd", blk, w_kvb,
                        preferred_element_type=jnp.float32,
                        precision=hp).astype(q_nope.dtype)
        k_nope, v = jnp.split(kv, [nope], axis=-1)
        s = jnp.einsum("chd,khd->hck", q_nope, k_nope,
                       preferred_element_type=jnp.float32, precision=hp) \
            + jnp.einsum("chd,kd->hck", q_rope, blk_r,
                         preferred_element_type=jnp.float32, precision=hp)
        key_pos = j * kb + jnp.arange(kb, dtype=jnp.int32)
        s = jnp.where(key_pos[None, None, :] <= positions[None, :, None],
                      s * scale, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(s - m_new[..., None])
        l = l * alpha + prob.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hck,khd->hcd", prob.astype(q_nope.dtype), v,
            preferred_element_type=jnp.float32, precision=hp)
        return m_new, l, acc

    blocks = (start + c + kb - 1) // kb
    m0 = jnp.full((heads, c), -1e30, jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, blocks, body,
        (m0, jnp.zeros_like(m0),
         jnp.zeros((heads, c, w_kvb.shape[2] - nope), jnp.float32)))
    return (acc / l[..., None]).astype(q_nope.dtype).transpose(1, 0, 2)


def latent_prefill_attention(q_nope, q_rope, cache, slot, start, w_kvb,
                             scale: float):
    """Expanded latent attention of one chunk of one stream over its
    latent cache: ``q_nope [C, heads, nope]`` and ``q_rope [C, heads,
    rope]`` (rotated), ``cache [streams, positions / per, width]`` as
    :func:`latent_cache_row` lays it out, with the chunk's own rows
    already written, ``slot`` and ``start`` (int32 scalars: the stream,
    and the chunk's first position, a multiple of ``C``), ``w_kvb
    [rank, heads, nope + v]``.  Returns ``[C, heads, v]`` in the
    queries' type: each query's softmax over positions ``0..`` its own,
    keys and values rebuilt from the latent rows by ``w_kvb``.

    The mathematics is :func:`latent_prefill_attention_reference`'s: a
    key block's keys and values are rounded to the queries' type, the
    scores (nope and rotary part in one float32 accumulation) are scaled
    and masked by ``key position <= query position``, the running
    maximum, normaliser and accumulator are float32, the probabilities
    are rounded to the queries' type for the value product and the
    normaliser divides once at the end.  What differs is where the
    scores live: a query block's ``[rows, key block]`` scores and
    probabilities exist only in fast memory, where XLA's loop writes
    ``[heads, C, key block]`` of them to HBM and reads them back several
    times an iteration.

    The grid walks (head group, key block).  A step holds its heads'
    queries, output and running softmax for the WHOLE chunk (they stay
    where they are while the key blocks pass), takes the block's rows
    from the cache through the pipeline (``slot`` and ``start`` are
    prefetched, so the index map picks the stream), rebuilds each
    head's keys and values from them ONCE, and then scores the chunk's
    queries against them a block of rows at a time.  The grid's extent
    is every whole block of the cache; a chunk at ``start`` needs
    ``(start + C) / 1024`` of them, and steps beyond compute nothing and
    name the last live block's rows, so nothing is copied for them (as
    :func:`grouped_gated_product` does for blocks not in use).  A query
    block that lies wholly before a key block is skipped (every score
    of it is masked: it adds exact zeros), one the diagonal passes
    through is masked, every other is not.

    A packed row is never unpacked (a softmax does not care in which
    order it meets its positions): the positions ``per * r + h`` of the
    block's rows ``r`` are part ``h``, rebuilt from the ``h``-th latent
    part, and scored against the queries' ``[q_nope | q_rope on the
    h-th key's lanes of the rotary tile]``, laid out once a chunk before
    the call: the nope and the rotary product are ONE contraction on
    the matrix unit.  A shape
    :func:`latent_prefill_attention_refusal` names is an error: the
    caller chooses."""
    refusal = latent_prefill_attention_refusal(
        q_nope.shape, q_rope.shape, cache.shape, w_kvb.shape,
        {q_nope.dtype, q_rope.dtype, cache.dtype, w_kvb.dtype})
    if refusal:
        raise ValueError(f"latent_prefill_attention: {refusal}")
    c, heads, nope = q_nope.shape
    group, tq = latent_prefill_tiles(
        c, heads, w_kvb.shape[0], q_rope.shape[2], nope,
        w_kvb.shape[2] - nope, q_nope.dtype)
    return _latent_prefill(q_nope, q_rope, cache, slot, start, w_kvb, scale,
                           group, tq, _prefill_key_block(c))


def _latent_prefill(q_nope, q_rope, cache, slot, start, w_kvb, scale: float,
                    group: int, tq: int, kb: int):
    """:func:`latent_prefill_attention` by an explicit tiling (the tests
    and the chip's sweeps choose theirs): ``group`` heads a grid step,
    ``tq`` query rows a pass, key blocks of ``kb`` positions."""
    import jax
    import jax.numpy as jnp

    c, heads, nope = q_nope.shape
    rank, rope = w_kvb.shape[0], q_rope.shape[2]
    v = w_kvb.shape[2] - nope
    per, _ = latent_cache_row(rank, rope)
    lanes = _prefill_rope_lanes(rank, rope)
    call = _latent_prefill_call(
        c, heads, nope, rope, v, rank, cache.shape[1], float(scale),
        np.dtype(q_nope.dtype).name, group, tq, kb, _interpret())
    # the stage a trace books the kernel's time to (a jit is no scope),
    # and with it the layout of its queries
    with jax.named_scope("latent_prefill_attention"):
        # a head's queries once a part of a row: beside the nope query
        # the rotary one on that part's lanes of the rotary tile, zeros
        # elsewhere
        q = jnp.concatenate([
            jnp.concatenate([q_nope, jnp.pad(q_rope, (
                (0, 0), (0, 0), (h * rope, lanes - (h + 1) * rope)))],
                axis=-1) for h in range(per)], axis=-1)
        out = call(jnp.reshape(slot, (1,)).astype(jnp.int32),
                   jnp.reshape(start, (1,)).astype(jnp.int32),
                   q.reshape(c, -1), w_kvb.reshape(rank, -1), cache)
    return out.reshape(c, heads, v)


@functools.lru_cache(maxsize=16)
def _latent_prefill_call(c: int, heads: int, nope: int, rope: int, v: int,
                         rank: int, rows: int, scale: float, dtype: str,
                         group: int, tq: int, kb: int, interpret: bool):
    """The jitted call of :func:`latent_prefill_attention` for one
    shape, built once: a model's layers share the function, so a
    program that attends in eight sub-blocks traces and lowers the
    kernel once (as :func:`_latent_decode_walk_call` does).  A cache of
    ``rows`` rows."""
    import jax.numpy as jnp

    jax, pl, pltpu = _pl()
    per, width = latent_cache_row(rank, rope)
    wide = nope + _prefill_rope_lanes(rank, rope)
    n = kb // per                        # rows, and a part's positions
    blocks = rows // n                   # whole key blocks of the cache
    hp = jax.lax.Precision.HIGHEST if dtype == "float32" else None
    nt = (((1,), (1,)), ((), ()))        # q k^T without a transpose

    def live(start_ref):
        """Key blocks a chunk at ``start`` attends to."""
        return jnp.clip((start_ref[0] + c) // kb, 1, blocks)

    def kernel(slot_ref, start_ref, q_ref, w_ref, rows_ref, o_ref,
               keys, values, ahead_ref, m_ref, l_ref, acc_ref):
        j = pl.program_id(1)
        start = start_ref[0]

        @pl.when(j == 0)
        def _first():
            m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
            # how far a part's key lies ahead of a block's query, less
            # what the two blocks' own places add: a masked pair then
            # costs a comparison and a choice an element (built there,
            # the two counters and their difference doubled its time)
            ahead_ref[...] = per * jax.lax.broadcasted_iota(
                jnp.int32, (tq, n), 1) - jax.lax.broadcasted_iota(
                    jnp.int32, (tq, n), 0)

        def attend(g: int, i, masked: bool):
            """The online softmax of head ``g``'s query block ``i``
            against the key block rebuilt in ``keys`` and ``values``."""
            at = pl.ds(pl.multiple_of(i * tq, tq), tq)
            scores = []
            for h in range(per):
                s = jax.lax.dot_general(
                    q_ref[at, pl.ds((g * per + h) * wide, wide)], keys[h],
                    nt, preferred_element_type=jnp.float32,
                    precision=hp) * scale                      # (tq, n)
                if masked:
                    s = jnp.where(
                        ahead_ref[...] <= start + i * tq - j * kb - h,
                        s, -1e30)
                scores.append(s)
            # running max / normaliser replicated across a lane width,
            # as in flash_attention above
            m_prev = m_new = m_ref[g, at, :]
            for s in scores:
                m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            l_new, acc = l_ref[g, at, :] * alpha, \
                acc_ref[g, at, :] * alpha[:, :1]
            for h, s in enumerate(scores):
                p = jnp.exp(s - m_new[:, :1])
                l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc + jax.lax.dot_general(
                    p.astype(values.dtype), values[h],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=hp)
            m_ref[g, at, :], l_ref[g, at, :], acc_ref[g, at, :] = \
                m_new, l_new, acc

        @pl.when(j < live(start_ref))
        def _block():
            for h in range(per):         # the rotary keys: every head's
                keys[h, :, nope:] = rows_ref[:, per * rank:]
            for g in range(group):
                for h in range(per):
                    kv = jax.lax.dot_general(
                        rows_ref[:, h * rank:(h + 1) * rank],
                        w_ref[:, g * (nope + v):(g + 1) * (nope + v)],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=hp).astype(keys.dtype)
                    keys[h, :, :nope] = kv[:, :nope]
                    values[h] = kv[:, nope:]

                def query_block(i, carry, g=g):
                    # the block's first key against the query block's
                    # last query, its last key against the first
                    first = start + i * tq
                    seen = j * kb <= first + tq - 1
                    cut = j * kb + kb - 1 > first
                    pl.when(seen & cut)(lambda: attend(g, i, True))
                    pl.when(seen & jnp.logical_not(cut))(
                        lambda: attend(g, i, False))
                    return carry

                jax.lax.fori_loop(0, c // tq, query_block, 0)

        @pl.when(j == live(start_ref) - 1)
        def _write():
            for g in range(group):
                o_ref[:, g * v:(g + 1) * v] = (
                    acc_ref[g] / l_ref[g][:, :1]).astype(o_ref.dtype)

    def heads_block(hg, j, slot, start):
        return 0, hg

    def key_block(hg, j, slot, start):
        # beyond the live blocks the last one named: not copied again
        return slot[0], jnp.minimum(j, live(start) - 1), 0

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(heads // group, blocks),
        in_specs=[
            pl.BlockSpec((c, group * per * wide), heads_block),
            pl.BlockSpec((rank, group * (nope + v)), heads_block),
            pl.BlockSpec((None, n, width), key_block),
        ],
        out_specs=pl.BlockSpec((c, group * v), heads_block),
        scratch_shapes=[
            pltpu.VMEM((per, n, wide), dtype),               # keys
            pltpu.VMEM((per, n, v), dtype),                  # values
            pltpu.VMEM((tq, n), jnp.int32),                  # key - query
            pltpu.VMEM((group, c, _LANE), jnp.float32),      # running max
            pltpu.VMEM((group, c, _LANE), jnp.float32),      # normaliser
            pltpu.VMEM((group, c, v), jnp.float32),          # accumulator
        ])
    call = pl.pallas_call(
        kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((c, heads * v), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_prefill_vmem(c, group, tq, kb, rank, rope,
                                           nope, v, dtype) + (8 << 20)),
        interpret=interpret)

    # no ``name=``: it would open a scope of its own below the caller's
    # (``.../attn/latent_prefill_attention``), the stage this call's
    # device time is booked to.  An inner jit is no scope, and names the
    # instruction all the same
    def latent_prefill_attention(*operands):
        return call(*operands)

    return jax.jit(latent_prefill_attention)


# -- grouped-query decode attention -------------------------------------------


def gqa_decode_attention_refusal(q_shape, k_shape, v_shape,
                                 window: int) -> Optional[str]:
    """Why :func:`gqa_decode_attention` cannot take these shapes, or
    None: ``q [B, groups, heads a group, d]`` beside ``k`` and ``v``
    ``[B, groups, positions, d]``, ``d`` whole lanes, and caches of
    whole lattice cells (lane tiles of positions)."""
    if len(q_shape) != 4 or len(k_shape) != 4 \
            or tuple(k_shape) != tuple(v_shape) \
            or tuple(q_shape[:2]) != tuple(k_shape[:2]) \
            or q_shape[3] != k_shape[3]:
        return f"q {tuple(q_shape)}, k {tuple(k_shape)} and v " \
               f"{tuple(v_shape)} are not [B, groups, heads, d] and " \
               "twice [B, groups, positions, d]"
    if q_shape[3] % _LANE:
        return f"head size {q_shape[3]} is not whole lanes of {_LANE}"
    if k_shape[2] < _WALK_LATTICE or k_shape[2] % _WALK_LATTICE:
        return f"{k_shape[2]} cache positions are not whole lanes " \
               f"of {_LANE}"
    if window < 1:
        return f"a window of {window} positions"
    return None


def gqa_decode_attention_reference(q, k, v, positions, window: int,
                                   scale: float):
    """The kernel's mathematics in jnp, and the path a model takes for
    a shape the kernel refuses.  Slot ``s`` of a cache of ``T``
    positions holds, for a stream at ``p``, position ``p - (p - s) mod
    T`` (the newest one that falls on it); it counts where that lies in
    ``[max(0, p - window + 1), p]``.  A dense cache is the case ``T``
    larger than every position, where the rule reads ``s <= p``."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    t = k.shape[2]
    s = jnp.einsum("bgqd,bgtd->bgqt", q, k.astype(q.dtype),
                   preferred_element_type=jnp.float32, precision=hp)
    at = positions[:, None] - (positions[:, None]
                               - jnp.arange(t, dtype=jnp.int32)[None]) % t
    valid = at >= jnp.maximum(positions[:, None] - window + 1, 0)
    s = jnp.where(valid[:, None, None, :], s * scale, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgqt,bgtd->bgqd", prob.astype(q.dtype),
                      v.astype(q.dtype),
                      preferred_element_type=jnp.float32, precision=hp)


def gqa_decode_rows_fetched(q_shape, k_shape, positions, window: int):
    """Rows of ONE cache (a token's K and V count as one row) that a
    model's decode attention reads in for streams at ``positions``: the
    walk's live cells (:func:`decode_rows_fetched`) where
    :func:`gqa_decode_attention` takes the shapes, every row of every
    stream where it refuses them and the ``jnp`` mathematics reads the
    caches whole."""
    if gqa_decode_attention_refusal(q_shape, k_shape, k_shape,
                                    window) is None:
        return decode_rows_fetched(positions, k_shape[2], window)
    return k_shape[0] * k_shape[2]


def gqa_decode_attention(q, k, v, positions, window: int, scale: float):
    """Grouped-query attention of one token a stream: ``q [B, groups,
    heads a group, d]``, ``k`` and ``v`` ``[B, groups, T, d]``,
    ``positions [B]`` int32.  Position ``p`` of a stream lives in slot
    ``p % T``: a ring where ``T`` is less than the stream's length (it
    must be at least ``window``), a dense cache where it never wraps.
    Returns ``[B, groups, heads a group, d]`` float32: softmax(q k^T *
    scale) over the positions ``max(0, p - window + 1) .. p``, times v.

    One pass, and the kernel copies a stream's live rows itself: K and
    V stay in HBM, the grid runs over streams, and a stream's rows come
    in by the walk above (:func:`decode_walk_plan`): the live cells of
    128 rows, in the order of their positions (a ring is walked from its
    oldest cell round to its newest), a chunk at a time counted from the
    FIRST live cell, so that every item is a whole chunk but the last,
    which comes in (and is computed on) in at most one piece each of 4,
    2 and 1 cells; an item that runs over a ring's end is copied cell by
    cell.  The items pass through a queue of buffers whose copies run
    ahead of the arithmetic and on into the next stream's first items,
    so that no stream starts cold and none takes a step it has no rows
    for.  Each item is read once for every query head of a group, with
    a running max, normaliser and accumulator in float32; a row outside
    the window is masked by its slot's position and adds exactly zero.
    What the walk fetches beyond the rows in use is less than a cell at
    each end (:func:`decode_rows_fetched`).

    On the chip (the kernel alone, device ms a call, blocks of 1,024
    through a ``BlockSpec`` before -> the walk; ``PERF.md`` section 6,
    PR 39): 32 streams, 4 groups, a window of 4,096 in a ring of 6,144
    0.461 -> 0.384 (fetched over used 1.25 -> 1.03); a dense cache of
    16,384 at 8-16 k 1.184 -> 1.063; 128 streams, 2 groups, a dense
    cache of 4,096 at 2-4 k 0.745 -> 0.580.  An update of the running
    sums costs about as much at 128 rows as at 512, so the arithmetic
    wants whole chunks: the same walk with chunks on the CACHE's lattice
    and both ends of a window computed on cell by cell read 0.395, 1.062
    and 0.675 (0.808 at chunks of 2,048); chunks of 512 read 0.419,
    1.106 and 0.809; 3 to 8 buffers, 1 to 8 streams a grid step and
    chunks of 1,024 or 2,048 read the same to 1 %.
    Heads are padded to whole tiles here; a shape
    :func:`gqa_decode_attention_refusal` names is an error."""
    refusal = gqa_decode_attention_refusal(q.shape, k.shape, v.shape, window)
    if refusal:
        raise ValueError(f"gqa_decode_attention: {refusal}")
    plan = decode_walk_plan(
        k.shape[2], 2 * q.shape[1] * q.shape[3] * np.dtype(q.dtype).itemsize)
    return _gqa_decode_walk(q, k, v, positions, window, scale, plan)


def _gqa_decode_walk(q, k, v, positions, window: int, scale: float,
                     plan: WalkPlan):
    """:func:`gqa_decode_attention` by an explicit ``plan`` (the tests
    and the chip's sweeps choose theirs)."""
    import jax
    import jax.numpy as jnp

    b, groups, held, d = q.shape
    per = -(-held // _sublane(q.dtype)) * _sublane(q.dtype)
    if per != held:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, per - held), (0, 0)))
    call = _gqa_decode_walk_call(b, groups, per, d, k.shape[2], window,
                                 float(scale), np.dtype(q.dtype).name, plan,
                                 _interpret())
    # the stage a trace books the kernel's time to, as the call's own
    # name made it before the call sat in a jit (which is no scope)
    with jax.named_scope("gqa_decode_attention"):
        out = call(positions.astype(jnp.int32), q, k.astype(q.dtype),
                   v.astype(q.dtype))
    return out[:, :, :held]


@functools.lru_cache(maxsize=16)
def _gqa_decode_walk_call(b: int, groups: int, per: int, d: int, total: int,
                          window: int, scale: float, dtype: str,
                          plan: WalkPlan, interpret: bool):
    """The jitted call of :func:`gqa_decode_attention` for one shape,
    built once: a model's layers share the function, so a program that
    attends in six layers traces and lowers the kernel once
    (as :func:`_ssm_decode_step_call` does)."""
    import jax.numpy as jnp

    jax, pl, pltpu = _pl()
    lat, (rows, slots) = _WALK_LATTICE, plan
    _walk_plan_check("gqa_decode_attention", plan, total)

    def kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, kbuf, vbuf, arrived,
               base_ref, m_ref, l_ref, acc_ref):

        def update(pos, slot, start, offset, size):
            """The online softmax over ``size`` rows of buffer ``slot``
            from row ``offset``; the buffer holds the cache's rows from
            cell ``start`` on, round the ring's end."""
            # a slot holds the newest position that falls on it: this
            # turn of the ring up to the stream's slot, the turn before
            # beyond it
            where = start * lat + offset + jax.lax.broadcasted_iota(
                jnp.int32, (per, size), 1)
            where = jnp.where(where >= total, where - total, where)
            position = where + pos // total * total \
                - jnp.where(where > pos % total, total, 0)
            valid = (position >= 0) & (position > pos - window)
            for g in range(groups):
                kb = kbuf[slot, g, pl.ds(offset, size), :]   # (size, d)
                vb = vbuf[slot, g, pl.ds(offset, size), :]
                s = jax.lax.dot_general(
                    q_ref[0, g], kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)    # (per, size)
                s = jnp.where(valid, s * scale, -1e30)
                m_prev = m_ref[g]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new[:, :1])
                l_ref[g] = l_ref[g] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
                acc_ref[g] = acc_ref[g] * corr[:, :1] + jax.lax.dot_general(
                    p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[g] = m_new

        _walk_stream(pos_ref, ((k_ref, kbuf), (v_ref, vbuf)), arrived,
                     base_ref, (m_ref, l_ref, acc_ref), o_ref, update,
                     total=total, window=window, plan=plan, ring=True)

    buffer = (slots, groups, rows, d)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[
            pl.BlockSpec((1, groups, per, d), lambda i, pos: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, groups, per, d),
                               lambda i, pos: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(buffer, dtype), pltpu.VMEM(buffer, dtype),
            pltpu.SemaphoreType.DMA((2, slots)),
            pltpu.SMEM((1,), jnp.int32),                     # first buffer
            pltpu.VMEM((groups, per, _LANE), jnp.float32),   # running max
            pltpu.VMEM((groups, per, _LANE), jnp.float32),   # normaliser
            pltpu.VMEM((groups, per, d), jnp.float32),       # accumulator
        ])
    call = pl.pallas_call(
        kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((b, groups, per, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * int(np.prod(buffer))
            * np.dtype(dtype).itemsize + (4 << 20)),
        name="gqa_decode_attention",
        interpret=interpret)

    def gqa_decode_attention(*operands):
        return call(*operands)

    return jax.jit(gqa_decode_attention)


# -- grouped-query prefill attention ------------------------------------------

#: query heads a grid step of :func:`gqa_prefill_attention` takes (a
#: part of a key/value head's group, or all of it) where
#: ``_PREFILL_VMEM_BUDGET`` allows (``PERF.md`` section 6, PR 49: the
#: sweep on the chip)
_GQA_PREFILL_HEADS_A_STEP = 8


def _gqa_prefill_vmem(c: int, heads: int, tq: int, kb: int, d: int,
                      dtype) -> int:
    """Bytes a grid step of :func:`gqa_prefill_attention` holds."""
    size = np.dtype(dtype).itemsize
    blocks = 2 * size * (2 * heads * c * d + 2 * kb * d)   # q, o; k, v
    stats = 4 * heads * c * (2 * _LANE + d)
    # a pass's scores, probabilities and their cast in flight; the
    # mask's offsets
    scores = 4 * 4 * tq * kb + 4 * tq * kb
    return blocks + stats + scores


def gqa_prefill_tiles(c: int, per_group: int, d: int, dtype) -> tuple:
    """``(query heads a grid step, query rows a pass)`` of
    :func:`gqa_prefill_attention` for a chunk of ``c`` tokens and
    ``per_group`` query heads a key/value head, against the largest key
    block (:func:`_prefill_tiles`, up to ``_GQA_PREFILL_HEADS_A_STEP``
    heads of the group)."""
    return _prefill_tiles(
        c, per_group, _GQA_PREFILL_HEADS_A_STEP, dtype,
        lambda heads, tq: _gqa_prefill_vmem(c, heads, tq, _PREFILL_KEY_BLOCK,
                                            d, dtype))


def gqa_prefill_attention_refusal(q_shape, k_shape, v_shape, window: int,
                                  dtypes) -> Optional[str]:
    """Why :func:`gqa_prefill_attention` cannot take these shapes, or
    None: ``q [C, kv heads, heads a group, d]`` beside ``k`` and ``v``
    caches ``[streams, kv heads, T, d]``, all of ONE type (``dtypes``:
    the set of theirs), bf16 or float32; ``d`` whole lanes; key blocks
    (1,024 positions, or the largest part of that which divides ``T``)
    of whole lanes of scores; ``window == T`` (a layer that sees every
    position of a cache that holds them all) or a ring that holds a
    window behind every query of the chunk (``T >= window + C - 1``);
    and a tiling that fits."""
    names = sorted(np.dtype(d).name for d in dtypes)
    if names not in (["bfloat16"], ["float32"]):
        return f"operands of {', '.join(names)}: all bfloat16 or all float32"
    if len(q_shape) != 4 or len(k_shape) != 4 \
            or tuple(k_shape) != tuple(v_shape) \
            or q_shape[1] != k_shape[1] or q_shape[3] != k_shape[3]:
        return f"q {tuple(q_shape)}, k {tuple(k_shape)} and v " \
               f"{tuple(v_shape)} are not [C, kv heads, heads a group, d] " \
               "and twice [streams, kv heads, T, d]"
    c, _, per, d = q_shape
    total = k_shape[2]
    if d % _LANE:
        return f"head size {d} is not whole lanes of {_LANE}"
    kb = _prefill_key_block(total)
    if kb % _LANE:
        return f"a key block of {kb} positions (a cache of {total}) is " \
               f"not whole lanes of {_LANE}"
    if window < 1 or window > total:
        return f"a window of {window} positions on a cache of {total}"
    if window < total and total < window + c - 1:
        return f"a ring of {total} positions does not hold a window of " \
               f"{window} behind every query of a chunk of {c}"
    if total < c:
        return f"a cache of {total} positions does not hold a chunk of {c}"
    if not gqa_prefill_tiles(c, per, d, names[0])[0]:
        return f"no step of a chunk of {c} fits " \
               f"{_PREFILL_VMEM_BUDGET >> 20} MiB in whole tiles of " \
               f"{_sublane(names[0])} rows"
    return None


def _gqa_prefill_last(start, c: int, total: int, window: int):
    """The last position a chunk of ``c`` tokens at ``start`` has
    written: its own last, on a ring; on a cache of every position
    (``window == total``) no further than the cache's last, what lies
    beyond (a padded chunk's end) having been dropped."""
    import jax.numpy as jnp

    last = start + c - 1
    return last if window < total else jnp.minimum(last, total - 1)


def gqa_prefill_attention_reference(q, k_cache, v_cache, slot, start,
                                    window: int, scale: float,
                                    key_block: int = _PREFILL_KEY_BLOCK,
                                    precision=None):
    """The kernel's mathematics in jnp, and the path
    ``models/attention.py`` ``prefill`` takes for a shape the kernel
    refuses: XLA's own ``while`` over the key blocks of stream
    ``slot``'s cache up to the chunk's end, the scores of the whole
    chunk against a block in float32, a running softmax.  A slot ``s``
    of a cache of ``T`` positions holds, once the chunk is written, the
    newest position up to the chunk's last that falls on it (``last -
    (last - s) mod T``: a ring where a stream is longer than ``T``, the
    position ``s`` itself where it is not, :func:`_gqa_prefill_last`); a
    query at ``p`` sees the
    slots that hold a position of ``max(0, p - window + 1) .. p``.
    Returns ``[C, kv heads, heads a group, d]`` float32.  (On the chip
    each iteration writes and reads its scores through HBM several
    times: ``PERF.md`` section 6, PRs 48 and 49.)"""
    import jax
    import jax.numpy as jnp

    c, groups, per, d = q.shape
    total = k_cache.shape[2]
    kb = _prefill_key_block(total, key_block)
    positions = start + jnp.arange(c, dtype=jnp.int32)
    last = _gqa_prefill_last(start, c, total, window)

    def body(j, carry):
        m, l, acc = carry
        kj, vj = (jax.lax.dynamic_slice(
            cache, (slot, 0, j * kb, 0),
            (1, groups, kb, d))[0].astype(q.dtype)
            for cache in (k_cache, v_cache))
        s = jnp.einsum("cgqd,gkd->gqck", q, kj,
                       preferred_element_type=jnp.float32,
                       precision=precision)
        # the newest position up to the chunk's end that falls on a slot
        slots = j * kb + jnp.arange(kb, dtype=jnp.int32)
        held = last - (last - slots) % total
        seen = (held[None, :] >= 0) & (held[None, :] <= positions[:, None])
        if window < total:
            seen &= held[None, :] > positions[:, None] - window
        s = jnp.where(seen[None, None], s * scale, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(s - m_new[..., None])
        l = l * alpha + prob.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "gqck,gkd->gqcd", prob.astype(q.dtype), vj,
            preferred_element_type=jnp.float32, precision=precision)
        return m_new, l, acc

    # a block the cache has not reached holds nothing a query sees, and
    # one whose every key is masked for a query adds to that query's sums
    # only until a later block with a key it sees scales them to nothing
    blocks = jnp.minimum(total // kb, (last + kb) // kb)
    m0 = jnp.full((groups, per, c), -1e30, jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, blocks, body,
        (m0, jnp.zeros_like(m0), jnp.zeros(m0.shape + (d,), jnp.float32)))
    return (acc / l[..., None]).transpose(2, 0, 1, 3)         # [C, g, q, d]


def gqa_prefill_attention(q, k_cache, v_cache, slot, start, window: int,
                          scale: float):
    """Grouped-query attention of one chunk of one stream over its K
    and V caches: ``q [C, kv heads, heads a group, d]`` (rotated where
    the model rotates), caches ``[streams, kv heads, T, d]`` with the
    chunk's own rows ALREADY written at ``position % T``, ``slot`` and
    ``start`` (int32 scalars: the stream, and the chunk's first
    position, ANY position), ``window`` static (``T`` for a layer that
    sees every position).  Returns ``[C, kv heads, heads a group, d]``
    in the queries' type: each query's softmax over the positions
    ``max(0, p - window + 1) .. p``.

    The mathematics is :func:`gqa_prefill_attention_reference`'s: keys,
    values and queries in their own type, a (query block, key block)
    pair's scores in one float32 accumulation, scaled, masked at -1e30;
    the running maximum, normaliser and accumulator float32; the
    probabilities rounded to the queries' type for the value product;
    one division at the end (rounded to the queries' type, as
    ``attention.heads_out`` rounds the loop's float32 first thing).
    What differs is where the scores live: a query block's ``[rows, key
    block]`` scores and probabilities exist only in fast memory, where
    XLA's loop writes ``[kv heads, heads a group, C, key block]`` of
    them to HBM and reads them back several times an iteration.

    The grid walks (a key/value head's group, or a part of it where the
    budget wants that; key block).  A step holds its heads' queries,
    output and running softmax for the WHOLE chunk, takes the block's K
    and V rows through the pipeline once for all of those heads
    (``slot`` and ``start`` are prefetched, so the index map picks the
    stream and the block), and scores a block of one head's query rows
    at a time.  The blocks are walked in the order of their positions,
    from the one that holds the oldest position a query of the chunk
    sees (slot ``max(0, start - window + 1) % T``) round the ring to
    the one with the chunk's end; the grid's extent is every block of
    the cache, and steps beyond the live ones compute nothing and name
    the last live block, so nothing is copied for them.

    A slot ``s`` holds position ``last - (last - s) % T``.  Up to the
    slot of ``last`` that is ``s`` plus one scalar, after it ``s`` plus
    that scalar less ``T``: a block that lies on one side holds a run
    of positions, and its pair with a query block is skipped where it
    lies wholly after the diagonal or wholly before every query's
    window, masked by ``key - query <= scalar`` where the diagonal
    passes through it, by ``> scalar`` where the window's edge does,
    and not at all where neither does.  The one block the ring's newest
    position falls INSIDE holds two runs (only once the ring has
    wrapped, and only for a chunk that does not end with a block: never
    at the cells' starts, multiples of ``C``), and takes a general mask
    with the slot's index in it, as does a pair both edges pass
    through.  A shape :func:`gqa_prefill_attention_refusal` names is an
    error: the caller chooses."""
    refusal = gqa_prefill_attention_refusal(
        q.shape, k_cache.shape, v_cache.shape, window,
        {q.dtype, k_cache.dtype, v_cache.dtype})
    if refusal:
        raise ValueError(f"gqa_prefill_attention: {refusal}")
    c, _, per, d = q.shape
    heads, tq = gqa_prefill_tiles(c, per, d, q.dtype)
    return _gqa_prefill(q, k_cache, v_cache, slot, start, window, scale,
                        heads, tq, _prefill_key_block(k_cache.shape[2]))


def _gqa_prefill(q, k_cache, v_cache, slot, start, window: int, scale: float,
                 heads: int, tq: int, kb: int):
    """:func:`gqa_prefill_attention` by an explicit tiling (the tests
    and the chip's sweeps choose theirs): ``heads`` query heads a grid
    step, ``tq`` query rows a pass, key blocks of ``kb`` positions."""
    import jax
    import jax.numpy as jnp

    c, groups, per, d = q.shape
    call = _gqa_prefill_call(
        c, groups, per, d, k_cache.shape[2], int(window), float(scale),
        np.dtype(q.dtype).name, heads, tq, kb, _interpret())
    # the stage a trace books the kernel's time to (a jit is no scope),
    # and with it the layout of its queries: a head's rows together
    with jax.named_scope("gqa_prefill_attention"):
        out = call(jnp.reshape(slot, (1,)).astype(jnp.int32),
                   jnp.reshape(start, (1,)).astype(jnp.int32),
                   q.transpose(1, 2, 0, 3).reshape(groups * per, c, d),
                   k_cache, v_cache)
        return out.reshape(groups, per, c, d).transpose(2, 0, 1, 3)


@functools.lru_cache(maxsize=16)
def _gqa_prefill_call(c: int, groups: int, per: int, d: int, total: int,
                      window: int, scale: float, dtype: str, heads: int,
                      tq: int, kb: int, interpret: bool):
    """The jitted call of :func:`gqa_prefill_attention` for one shape,
    built once: a model's layers share the function, so a program whose
    eight layers attend on rings and full caches traces and lowers two
    kernels (as :func:`_latent_prefill_call` does)."""
    import jax.numpy as jnp

    jax, pl, pltpu = _pl()
    blocks = total // kb                 # key blocks of the cache
    parts = per // heads                 # grid steps a key/value head takes
    nq = c // tq
    hp = jax.lax.Precision.HIGHEST if dtype == "float32" else None
    nt = (((1,), (1,)), ((), ()))        # q k^T without a transpose

    # the walk's arithmetic on scalars that are never negative, as lax
    # primitives: every jnp operator is a jitted function of its own,
    # and this is traced once an index map and once in the kernel
    lax = jax.lax
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)

    def last_of(start):
        last = start + i32(c - 1)
        return last if window < total else lax.min(last, i32(total - 1))

    def walk(start):
        """``(first, live)``: the block that holds the oldest position a
        query of a chunk at ``start`` sees, and how many blocks lie from
        it round to the one with the chunk's end."""
        oldest = lax.max(start - i32(window - 1), i32(0))
        at = lax.rem(oldest, i32(total))
        first = lax.div(at, i32(kb))
        return first, lax.min(i32(blocks), lax.div(
            at + last_of(start) - oldest, i32(kb)) - first + i32(1))

    def block_at(j, first, live):
        # beyond the live blocks the last one named: not copied again
        return lax.rem(first + lax.min(j, live - i32(1)), i32(blocks))

    def kernel(slot_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
               ahead_ref, m_ref, l_ref, acc_ref):
        j = pl.program_id(1)
        start = start_ref[0]
        first, live = walk(start)
        slot0 = block_at(j, first, live) * kb    # the block's first slot
        last = last_of(start)
        newest = lax.rem(last, i32(total))       # the slot ``last`` lies in
        lap = last - newest                      # positions before this lap
        # the ring's newest position inside the block and not at its
        # end, behind it the positions of the lap before: two runs
        split = newest - slot0
        mixed = (lap >= total) & (split >= 0) & (split < kb - 1)
        # the position the block's first slot holds
        key0 = slot0 + lap - jnp.where(slot0 > newest, total, 0)

        @pl.when(j == 0)
        def _first():
            m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
            # how far a key lies ahead of a query, less what the two
            # blocks' own places add: a masked pair then costs a
            # comparison and a choice an element
            ahead_ref[...] = jax.lax.broadcasted_iota(
                jnp.int32, (tq, kb), 1) - jax.lax.broadcasted_iota(
                    jnp.int32, (tq, kb), 0)

        def attend(g, i, mask):
            """The online softmax of head ``g``'s query block ``i``
            against the key block, under ``mask``."""
            at = pl.ds(pl.multiple_of(i * tq, tq), tq)
            s = jax.lax.dot_general(
                q_ref[g, at, :], k_ref[...], nt,
                preferred_element_type=jnp.float32,
                precision=hp) * scale                          # (tq, kb)
            # the block's first key lies ``gap`` behind the first query
            gap = start + i * tq - key0
            if mask == "diagonal":
                s = jnp.where(ahead_ref[...] <= gap, s, -1e30)
            elif mask == "window":
                s = jnp.where(ahead_ref[...] > gap - window, s, -1e30)
            elif mask == "general":
                # the slots after ``newest`` are a lap behind
                slots = jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 1)
                gaps = gap + jnp.where(slots > split,
                                       jnp.where(mixed, total, 0), 0)
                s = jnp.where((ahead_ref[...] <= gaps)
                              & (ahead_ref[...] > gaps - window), s, -1e30)
            # running max / normaliser replicated across a lane width,
            # as in flash_attention above
            m_prev = m_ref[g, at, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[g, at, :] = l_ref[g, at, :] * alpha \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g, at, :] = acc_ref[g, at, :] * alpha[:, :1] \
                + jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[...],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=hp)
            m_ref[g, at, :] = m_new

        @pl.when(j < live)
        def _block():
            def pair(r, carry):
                g, i = r // nq, r % nq
                # the block's first key against the query block's last
                # query, its last key against the first query (and the
                # first position of that query's window)
                q0 = start + i * tq
                seen = key0 <= q0 + tq - 1
                diagonal = key0 + kb - 1 > q0
                if window < total:
                    # (a cache of every position has no window's edge
                    # and no second run: its kernel holds neither mask)
                    seen = seen & (key0 + kb - 1 > q0 - window) \
                        & jnp.logical_not(mixed)
                    edge = key0 <= q0 + tq - 1 - window
                    pl.when(mixed | (seen & diagonal & edge))(
                        lambda: attend(g, i, "general"))
                    pl.when(seen & edge & jnp.logical_not(diagonal))(
                        lambda: attend(g, i, "window"))
                    seen = seen & jnp.logical_not(edge)
                pl.when(seen & diagonal)(lambda: attend(g, i, "diagonal"))
                pl.when(seen & jnp.logical_not(diagonal))(
                    lambda: attend(g, i, None))
                return carry

            jax.lax.fori_loop(0, heads * nq, pair, 0)

        @pl.when(j == live - 1)
        def _write():
            def head(g, carry):
                o_ref[g] = (acc_ref[g] / l_ref[g][:, :1]).astype(o_ref.dtype)
                return carry

            jax.lax.fori_loop(0, heads, head, 0)

    def heads_block(hg, j, slot, start):
        return hg, 0, 0

    def key_block(hg, j, slot, start):
        return slot[0], hg // parts, block_at(j, *walk(start[0])), 0

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(groups * parts, blocks),
        in_specs=[
            pl.BlockSpec((heads, c, d), heads_block),
            pl.BlockSpec((None, None, kb, d), key_block),
            pl.BlockSpec((None, None, kb, d), key_block),
        ],
        out_specs=pl.BlockSpec((heads, c, d), heads_block),
        scratch_shapes=[
            pltpu.VMEM((tq, kb), jnp.int32),                 # key - query
            pltpu.VMEM((heads, c, _LANE), jnp.float32),      # running max
            pltpu.VMEM((heads, c, _LANE), jnp.float32),      # normaliser
            pltpu.VMEM((heads, c, d), jnp.float32),          # accumulator
        ])
    call = pl.pallas_call(
        kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((groups * per, c, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_gqa_prefill_vmem(c, heads, tq, kb, d, dtype)
            + (8 << 20)),
        interpret=interpret)

    # no ``name=``: it would open a scope of its own below the caller's
    # (``.../attn/gqa_prefill_attention``), the stage this call's device
    # time is booked to.  An inner jit is no scope, and names the
    # instruction all the same
    def gqa_prefill_attention(*operands):
        return call(*operands)

    return jax.jit(gqa_prefill_attention)


# -- the routed experts' grouped product --------------------------------------

#: fast memory both buffers of a grid step's weight tiles (three for a
#: gated expert, two for an ungated one) may take;
#: the call states it (and its rows' part) through ``vmem_limit_bytes``.
#: Alone the kernel reads a little faster the larger its tiles; inside a
#: decode step, 24 MiB read faster than 32 and 48 (``PERF.md`` section
#: 5: what the kernel leaves, the compiler prefetches weights into)
_GROUPED_VMEM_BUDGET = 24 << 20


def grouped_tile(hidden: int, inter: int, dtype,
                 budget: int = _GROUPED_VMEM_BUDGET, matrices: int = 3) -> int:
    """Columns of the intermediate width a grid step of
    :func:`grouped_gated_product` takes: the largest divisor of
    ``inter`` of whole lanes whose ``matrices`` tiles (``[hidden,
    tile]`` for ``up`` and, of a gated expert's three, ``gate``;
    ``[tile, hidden]``), twice for the pipeline's two buffers, fit
    ``budget``; 0 where none does."""
    size = np.dtype(dtype).itemsize
    for tile in range(inter // _LANE * _LANE, 0, -_LANE):
        if inter % tile == 0 \
                and 2 * matrices * hidden * tile * size <= budget:
            return tile
    return 0


def grouped_gated_product_refusal(x_shape, gate_shape, down_shape, dtypes,
                                  blk: int, matrices: int = 3
                                  ) -> Optional[str]:
    """Why :func:`grouped_gated_product` cannot take these shapes, or
    None: ``x [tokens, hidden]`` beside ``up`` (and, of a gated
    expert's three ``matrices``, ``gate``: ``gate_shape`` is the shape
    of either) ``[experts, hidden, inter]`` and ``down [experts, inter,
    hidden]``, all of ONE type (``dtypes``: the set of theirs), bf16 or
    float32, both widths whole lanes, a block of whole sublanes, and a
    tile of the intermediate width that fits."""
    names = sorted(np.dtype(d).name for d in dtypes)
    if names not in (["bfloat16"], ["float32"]):
        return f"operands of {', '.join(names)}: all bfloat16 or all float32"
    dtype = names[0]
    if len(x_shape) != 2 or len(gate_shape) != 3 \
            or tuple(down_shape) != (gate_shape[0], gate_shape[2],
                                     gate_shape[1]) \
            or x_shape[1] != gate_shape[1]:
        return f"x {tuple(x_shape)}, gate {tuple(gate_shape)} and down " \
               f"{tuple(down_shape)} are not [tokens, hidden], [experts, " \
               "hidden, inter] and [experts, inter, hidden]"
    hidden, inter = gate_shape[1], gate_shape[2]
    if hidden % _LANE or inter % _LANE:
        return f"widths {hidden} and {inter} are not whole lanes of {_LANE}"
    if blk < 1 or blk % _sublane(dtype):
        return f"a block of {blk} rows is not whole tiles of " \
               f"{_sublane(dtype)}"
    if not grouped_tile(hidden, inter, dtype, matrices=matrices):
        return f"no tile of [{hidden}, {inter}] fits " \
               f"{_GROUPED_VMEM_BUDGET >> 20} MiB twice"
    return None


def grouped_gated_product(x, gate, up, down, row_token, block_expert,
                          blocks, blk: int, act, tile: Optional[int] = None):
    """The MLPs of the experts a plan of ``models/moe.py``
    ``dispatch`` lays rows out for, as one call: ``x [tokens, hidden]``,
    ``gate`` and ``up`` ``[experts, hidden, inter]``, ``down [experts,
    inter, hidden]`` all of one type, ``row_token [rows]`` (the token a
    row holds, ``tokens`` = none), ``block_expert [rows / blk]``,
    ``blocks`` (how many of them are in use), ``act`` the
    nonlinearity.  Returns ``[rows + 1, hidden]``: row ``r`` of a block
    in use is ``(act(x_t gate_e) * (x_t up_e)) down_e`` of its token and
    its block's expert, or, where ``gate`` is None (an UNGATED expert:
    two matrices, two tiles a step), ``act(x_t up_e) down_e``; products
    accumulated in float32, the hidden activation rounded to ``x``'s
    type before ``down``, the row rounded once; the last row is zero;
    rows of blocks NOT in use are not written and hold anything.

    The grid walks (block, tile of the intermediate width) with
    ``block_expert`` and ``blocks`` prefetched, so the weight operands'
    index maps choose the expert and the pipeline fetches the next
    step's weight tiles while this step multiplies: a block of 32 rows
    is nothing to compute, the call streams the touched experts'
    matrices once.  Steps beyond the blocks in use name the tiles the
    last block in use named (a repeated block is not copied again) and
    compute nothing; one step more writes the zero row.  A shape
    :func:`grouped_gated_product_refusal` names is an error: the caller
    chooses."""
    import jax.numpy as jnp

    ins = [up] if gate is None else [gate, up]
    refusal = grouped_gated_product_refusal(
        x.shape, up.shape, down.shape,
        {x.dtype, down.dtype} | {w.dtype for w in ins}, blk,
        len(ins) + 1)
    if refusal:
        raise ValueError(f"grouped_gated_product: {refusal}")
    jax, pl, pltpu = _pl()
    hidden, inter = up.shape[1], up.shape[2]
    tile = tile or grouped_tile(hidden, inter, x.dtype,
                                matrices=len(ins) + 1)
    tiles, grid_blocks = inter // tile, row_token.shape[0] // blk
    hp = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    tokens = x.shape[0]
    # up to a block of tokens (``dispatch`` caps a block at 256 rows)
    # the kernel holds them whole and picks a block's rows out of them
    # itself, by a product with ones where a row holds a token (exact:
    # one term a row; a row of no token is zero); more tokens are laid
    # out as the plan's rows before the call
    inside = tokens <= blk
    if inside:
        row_operands = [row_token.reshape(-1, 1), x]
        row_specs = [(blk, 1), (tokens, hidden)]
    else:
        row_operands = [jnp.concatenate(
            [x, jnp.zeros((1, hidden), x.dtype)])[row_token]]
        row_specs = [(blk, hidden)]

    def mm(a, w):
        return jax.lax.dot_general(a, w, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=hp)

    def kernel(expert_ref, blocks_ref, *refs):
        row_refs, in_refs = refs[:-len(ins) - 3], refs[-len(ins) - 3:-3]
        down_ref, o_ref, acc_ref = refs[-3:]
        b, t = pl.program_id(0), pl.program_id(1)

        @pl.when(b < blocks_ref[0])
        def _product():
            if inside:
                token_ref, x_ref = row_refs
                holds = jax.lax.broadcasted_iota(
                    jnp.int32, (blk, tokens), 1) == token_ref[...]
                xb = mm(holds.astype(x.dtype), x_ref[...]).astype(x.dtype)
            else:
                xb = row_refs[0][...]
            h = act(mm(xb, in_refs[0][...]))
            if gate is not None:
                h = h * mm(xb, in_refs[1][...])
            part = mm(h.astype(xb.dtype), down_ref[...])
            if tiles == 1:
                o_ref[...] = part.astype(o_ref.dtype)
                return

            @pl.when(t == 0)
            def _first():
                acc_ref[...] = part

            @pl.when(t > 0)
            def _next():
                acc_ref[...] += part

            @pl.when(t == tiles - 1)
            def _write():
                o_ref[...] = acc_ref[...].astype(o_ref.dtype)

        @pl.when(b == grid_blocks)
        def _zero_row():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def at(b, t, count):
        """The (block, tile) whose operands step ``(b, t)`` names: its
        own, or beyond the blocks in use the last ones named."""
        live = b < count[0]
        return jnp.where(live, b, jnp.maximum(count[0] - 1, 0)), \
            jnp.where(live, t, tiles - 1)

    def row_block(b, t, expert, count):
        return at(b, t, count)[0], 0

    def out_block(b, t, expert, count):
        return jnp.where(b == grid_blocks, b, at(b, t, count)[0]), 0

    def in_tile(b, t, expert, count):
        block, column = at(b, t, count)
        return expert[block], 0, column

    def out_tile(b, t, expert, count):
        block, column = at(b, t, count)
        return expert[block], column, 0

    size = np.dtype(x.dtype).itemsize
    # the weights' tiles twice; the rows in and out twice, their float32
    # sum and a step's part of it; the hidden activation's float32 parts
    vmem = 2 * (len(ins) + 1) * hidden * tile * size \
        + blk * hidden * (4 * size + 8) + 4 * blk * tile * 4 + (4 << 20)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(grid_blocks + 1, tiles),
        in_specs=[
            pl.BlockSpec(row_specs[0], row_block),
            *[pl.BlockSpec(whole, lambda b, t, expert, count: (0, 0))
              for whole in row_specs[1:]],
            *[pl.BlockSpec((None, hidden, tile), in_tile) for _ in ins],
            pl.BlockSpec((None, tile, hidden), out_tile),
        ],
        out_specs=pl.BlockSpec((blk, hidden), out_block),
        scratch_shapes=[pltpu.VMEM((blk, hidden), jnp.float32)])
    # no ``name=``: the caller's scope (``.../moe/experts``) is the stage
    # this call's device time is booked to, as with ``short_attention``
    return pl.pallas_call(
        kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((row_token.shape[0] + 1, hidden),
                                       x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=_interpret(),
    )(block_expert.astype(jnp.int32),
      jnp.reshape(blocks, (1,)).astype(jnp.int32), *row_operands, *ins,
      down)


# -- the routed experts' rows summed into their tokens ------------------------

#: rows of one copy of :func:`weighted_row_sum`: a whole tile of either
#: type it takes, so that an expert's rows are copied in pieces no
#: larger than its count rounded up to this
_ROW_SUM_UNIT = 16
#: bytes one buffer of the result's column tile may take (the pipeline
#: keeps two: a tile is written back while the next is summed), and the
#: copies in flight beside the one being summed
_ROW_SUM_TILE_BYTES = 24 << 20
_ROW_SUM_SLOTS = 4
#: 32-bit words of scalar memory the plan's two per-row arrays may take
_ROW_SUM_SMEM_WORDS = 1 << 16


def row_sum_tile(tokens: int, hidden: int) -> int:
    """Columns of the result a grid step of :func:`weighted_row_sum`
    holds for every token: the largest divisor of ``hidden`` of whole
    lanes whose ``[tokens, tile]`` float32 fits
    ``_ROW_SUM_TILE_BYTES``; 0 where none does."""
    for tile in range(hidden // _LANE * _LANE, 0, -_LANE):
        if hidden % tile == 0 and tokens * tile * 4 <= _ROW_SUM_TILE_BYTES:
            return tile
    return 0


def weighted_row_sum_refusal(out_shape, dtype, tokens: int, blk: int
                             ) -> Optional[str]:
    """Why :func:`weighted_row_sum` cannot take these shapes, or None:
    ``out [rows + 1, hidden]`` of bf16 or float32, ``hidden`` whole
    lanes, blocks of whole copies, a column tile of the result that
    fits, and a plan whose per-row arrays fit scalar memory."""
    name = np.dtype(dtype).name
    if name not in ("bfloat16", "float32"):
        return f"rows of {name}: bfloat16 or float32"
    if len(out_shape) != 2 or out_shape[1] % _LANE:
        return f"rows of {tuple(out_shape)}: not [rows + 1, whole lanes " \
               f"of {_LANE}]"
    if blk < 1 or blk % _ROW_SUM_UNIT or (out_shape[0] - 1) % blk:
        return f"blocks of {blk} rows are not whole copies of " \
               f"{_ROW_SUM_UNIT}"
    if not row_sum_tile(tokens, out_shape[1]):
        return f"no column tile of [{tokens}, {out_shape[1]}] float32 " \
               f"fits {_ROW_SUM_TILE_BYTES >> 20} MiB"
    if 2 * (out_shape[0] - 1) > _ROW_SUM_SMEM_WORDS:
        return f"{out_shape[0] - 1} rows: their tokens and weights do " \
               f"not fit {_ROW_SUM_SMEM_WORDS >> 8} KiB of scalar memory"
    return None


def weighted_row_sum(out, row_token, row_weight, counts, blk: int,
                     tokens: int):
    """``[tokens, hidden]`` float32: row ``r`` of ``out [rows + 1,
    hidden]`` times ``row_weight[r]`` (float32) added into token
    ``row_token[r]``, over the REAL rows of a plan of ``models/moe.py``
    ``dispatch`` alone: expert ``e``'s are the first ``counts[e]`` rows
    from where its blocks (of ``blk`` rows) start.  A token no real row
    names gets zeros; a token's rows are added in the order they lie
    in, expert by expert.  No other row enters a sum: a block's padding
    travels with the last copy of its expert's rows (at most
    ``_ROW_SUM_UNIT - 1`` rows) and is dropped there; blocks not in use
    are never copied.

    ``out`` stays in HBM.  The grid walks column tiles of the result
    (:func:`row_sum_tile`), which a step holds for every token; within
    a step the kernel lists the copies the counts ask for, keeps
    ``_ROW_SUM_SLOTS - 1`` of them in flight, widens one to float32
    and adds its real rows, each scaled by its weight, into its
    token's row.  The work follows ``sum(counts)``.  A shape
    :func:`weighted_row_sum_refusal` names is an error: the caller
    chooses."""
    import jax.numpy as jnp

    refusal = weighted_row_sum_refusal(out.shape, out.dtype, tokens, blk)
    if refusal:
        raise ValueError(f"weighted_row_sum: {refusal}")
    call = _row_sum_call(out.shape[0] - 1, out.shape[1], tokens,
                         counts.shape[0], blk, np.dtype(out.dtype).name,
                         _interpret())
    return call(counts.astype(jnp.int32), row_token.astype(jnp.int32),
                row_weight.astype(jnp.float32), out)


@functools.lru_cache(maxsize=16)
def _row_sum_call(rows: int, hidden: int, tokens: int, held: int, blk: int,
                  dtype: str, interpret: bool):
    """The jitted call of :func:`weighted_row_sum` for one shape, built
    once: a model's sparse layers share the function, so a program
    traces and lowers one kernel (as :func:`_gqa_prefill_call`
    does)."""
    import jax.numpy as jnp

    jax, pl, pltpu = _pl()
    unit, slots = _ROW_SUM_UNIT, _ROW_SUM_SLOTS
    tile = row_sum_tile(tokens, hidden)
    ahead = slots - 1

    def kernel(counts_ref, token_ref, weight_ref, out_ref, o_ref,
               first_ref, real_ref, buf, wide, arrived):
        col = pl.multiple_of(pl.program_id(0) * tile, _LANE)

        # the copies the counts ask for, in the order the rows lie in:
        # where each starts and how many of its rows are real
        def expert(e, carry):
            start, n = carry

            def piece(u, n):
                first_ref[n] = start + u * unit
                real_ref[n] = jnp.minimum(counts_ref[e] - u * unit, unit)
                return n + 1

            n = jax.lax.fori_loop(
                0, (counts_ref[e] + unit - 1) // unit, piece, n)
            return start + (counts_ref[e] + blk - 1) // blk * blk, n

        _, pieces = jax.lax.fori_loop(
            0, held, expert, (jnp.int32(0), jnp.int32(0)))

        def copy(n):
            return pltpu.make_async_copy(
                out_ref.at[pl.ds(pl.multiple_of(first_ref[n], unit), unit),
                           pl.ds(col, tile)],
                buf.at[n % slots], arrived.at[n % slots])

        for n in range(ahead):
            pl.when(n < pieces)(lambda n=n: copy(n).start())
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        def summed(n, _):
            copy(n).wait()
            pl.when(n + ahead < pieces)(lambda: copy(n + ahead).start())
            wide[...] = buf[n % slots].astype(jnp.float32)
            first = first_ref[n]

            def add(r, _):
                token = pl.ds(token_ref[first + r], 1)
                o_ref[token, :] = o_ref[token, :] \
                    + wide[pl.ds(r, 1), :] * weight_ref[first + r]
                return 0

            jax.lax.fori_loop(0, real_ref[n], add, 0)
            return 0

        jax.lax.fori_loop(0, pieces, summed, 0)

    size = np.dtype(dtype).itemsize
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(hidden // tile,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tokens, tile), lambda c, *_: (0, c)),
        scratch_shapes=[
            pltpu.SMEM((rows // unit,), jnp.int32),       # a copy's first row
            pltpu.SMEM((rows // unit,), jnp.int32),       # its real rows
            pltpu.VMEM((slots, unit, tile), jnp.dtype(dtype)),
            pltpu.VMEM((unit, tile), jnp.float32),
            pltpu.SemaphoreType.DMA((slots,)),
        ])
    call = pl.pallas_call(
        kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * tokens * tile * 4
            + unit * tile * (slots * size + 4) + (8 << 20)),
        interpret=interpret)

    # no ``name=``: the caller's scope (``.../moe/combine``) is the stage
    # this call's device time is booked to
    def weighted_row_sum(*operands):
        return call(*operands)

    return jax.jit(weighted_row_sum)


# -- the Mamba-2 decode step --------------------------------------------------

#: fast memory the two sets of state buffers of :func:`ssm_decode_step`
#: may take (a set: the streams of one grid step, 4 x 2 MiB at 64 heads
#: of 64 over a state of 128); the call states it through
#: ``vmem_limit_bytes``.  Alone the kernel reads 0.812 / 0.793 / 0.789 ms
#: a layer at 2 / 4 / 8 streams a step; inside the cell's decode step
#: the step reads 16.32 / 16.06 / 16.33 ms (``PERF.md`` section 6: what
#: a kernel leaves, the compiler prefetches weights into), so four
_SSM_VMEM_BUDGET = 32 << 20
_SSM_STREAMS_A_STEP = 4


def ssm_step_streams(streams: int, stream_bytes: int) -> int:
    """Streams a grid step of :func:`ssm_decode_step` takes: the largest
    divisor of ``streams`` up to ``_SSM_STREAMS_A_STEP`` whose two sets
    of buffers fit ``_SSM_VMEM_BUDGET``; 0 where one stream's do not."""
    for k in range(min(_SSM_STREAMS_A_STEP, streams), 0, -1):
        if streams % k == 0 and 2 * k * stream_bytes <= _SSM_VMEM_BUDGET:
            return k
    return 0


def ssm_decode_step_refusal(state_shape, dtypes) -> Optional[str]:
    """Why :func:`ssm_decode_step` cannot take a recurrent state of
    ``state_shape`` (``[streams, groups, state, heads a group x
    head_dim]``) whose operands have ``dtypes`` (the set of theirs), or
    None: float32 throughout, the last axis whole lanes, the state axis
    whole lanes too (it is transposed on the matrix unit), and two
    buffers of a stream's state within ``_SSM_VMEM_BUDGET``."""
    names = sorted(np.dtype(d).name for d in dtypes)
    if names != ["float32"]:
        return f"operands of {', '.join(names)}: the recurrence is float32"
    if len(state_shape) != 4:
        return f"a state {tuple(state_shape)} is not [streams, groups, " \
               "state, heads a group x head_dim]"
    streams, groups, state, lanes = state_shape
    if lanes % _LANE or state % _LANE:
        return f"a group's [{state}, {lanes}] of state is not whole lanes " \
               f"of {_LANE} both ways"
    if not ssm_step_streams(streams, groups * state * lanes * 4):
        return f"a stream's state twice, {groups * state * lanes >> 17} " \
               f"MiB, is over {_SSM_VMEM_BUDGET >> 20} MiB"
    return None


def ssm_decode_step_reference(ssm, a, dx, b, c):
    """The kernel's mathematics in jnp on the LIVE state (no snapshot:
    the caller has restored), and the path a model takes for a shape the
    kernel refuses: ``ssm [B, groups, state, lanes]``, ``a`` and ``dx``
    ``[B, groups, lanes]``, ``b`` and ``c`` ``[B, groups, state]``, all
    float32.  Returns ``(a S + b (x) dx, its sum over the state axis
    weighted by c)``."""
    import jax.numpy as jnp

    new = a[:, :, None, :] * ssm + b[..., None] * dx[:, :, None, :]
    return new, jnp.sum(new * c[..., None], axis=2)


def ssm_decode_step(ssm, snap, restore, a, dx, b, c):
    """One token of the Mamba-2 recurrence for every stream, the state
    read once and written once: ``ssm`` and ``snap`` ``[B, groups,
    state, lanes]`` float32 (``lanes``: a group's heads x head_dim; the
    live state and its snapshot), ``restore [B]`` (which streams start
    from their snapshot), ``a`` and ``dx`` ``[B, groups, lanes]`` (the
    decay and ``delta x``, a head's value repeated over its lanes),
    ``b`` and ``c`` ``[B, groups, state]``.  Returns ``(S', y)``: ``S' =
    a S + b (x) dx`` written over ``ssm`` (aliased: donate it), ``y [B,
    groups, lanes] = sum_n S'[n] c[n]``; ``snap`` is only read.

    Both states stay in HBM and the kernel copies them itself.  A grid
    step takes a few streams (:func:`ssm_step_streams`): it starts the
    copies of the NEXT step's streams, each from the stream's snapshot
    or its live state as ``restore`` (prefetched) says and from no
    other, updates this step's streams where they lie in fast memory
    while those copies run, waits for them, and only then writes this
    step's streams back and waits again: reads and writes of the state
    never share the memory's time.  (Mixed, as a pipeline of blocked
    operands or XLA's own fusion mixes them, the chip moves 655 GB/s;
    alone it reads 739 and writes 656: ``PERF.md`` section 5.)  The
    arithmetic is a sixth of the step and hides behind the reads.

    With the state axis in the sublanes ``a`` and ``dx`` are rows, ``y``
    is a sum of vregs and one sublane reduction a group, and only ``b``
    and ``c`` are columns: their 16 rows a stream are transposed once by
    a product with the identity (``HIGHEST``: exact) and a column is
    spread over the lanes once for a group's lane tiles.  Float32 on the
    vector unit throughout.  A shape :func:`ssm_decode_step_refusal`
    names is an error: the caller chooses."""
    import jax.numpy as jnp

    refusal = ssm_decode_step_refusal(
        ssm.shape, {v.dtype for v in (ssm, snap, a, dx, b, c)})
    if refusal:
        raise ValueError(f"ssm_decode_step: {refusal}")
    streams, groups, state, lanes = ssm.shape
    k = ssm_step_streams(streams, groups * state * lanes * 4)
    call = _ssm_decode_step_call(streams, groups, state, lanes, k,
                                 _interpret())
    return call(restore.astype(jnp.int32), a, dx,
                jnp.concatenate([b, c], axis=1), ssm, snap)


@functools.lru_cache(maxsize=8)
def _ssm_decode_step_call(streams: int, groups: int, state: int, lanes: int,
                          k: int, interpret: bool):
    """The jitted call of :func:`ssm_decode_step` for one shape, built
    once: a model's layers share the function, so a program that steps
    nine layers traces and lowers the kernel once."""
    import jax.numpy as jnp

    jax, pl, pltpu = _pl()
    steps = streams // k

    def kernel(restore_ref, a_ref, dx_ref, bc_ref, live_ref, snap_ref,
               out_ref, y_ref, held, arrived, left):
        step = pl.program_id(0)
        here = step % 2                  # the set this step's streams are in

        def fetch(of, j, into, wait=False):
            """Start (or wait for) the copy of stream ``j`` of step
            ``of`` into set ``into``, from the one source it starts
            from."""
            at = of * k + j
            live = pltpu.make_async_copy(live_ref.at[at], held.at[into, j],
                                         arrived.at[into, j])
            if wait:                     # either source: the same bytes
                live.wait()
                return
            anew = restore_ref[at] != 0
            pl.when(anew)(pltpu.make_async_copy(
                snap_ref.at[at], held.at[into, j], arrived.at[into, j]).start)
            pl.when(jnp.logical_not(anew))(live.start)

        @pl.when(step == 0)
        def _first():
            for j in range(k):
                fetch(0, j, 0)
            for j in range(k):
                fetch(0, j, 0, wait=True)

        @pl.when(step + 1 < steps)
        def _next():
            for j in range(k):
                fetch(step + 1, j, 1 - here)

        eye = (jax.lax.broadcasted_iota(jnp.int32, (state, state), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (state, state), 1)
               ).astype(jnp.float32)
        for j in range(k):
            columns = jax.lax.dot_general(               # [state, 2 groups]
                eye, bc_ref[j], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            for g in range(groups):
                # a group whole, [state, lanes]: in rows of 8 to 128 a
                # pass the kernel reads the same, the copies bind it
                new = a_ref[j, g:g + 1, :] * held[here, j, g] \
                    + columns[:, g:g + 1] * dx_ref[j, g:g + 1, :]
                held[here, j, g] = new
                y_ref[j, g:g + 1, :] = jnp.sum(
                    new * columns[:, groups + g:groups + g + 1], axis=0,
                    keepdims=True)

        @pl.when(step + 1 < steps)
        def _arrived():
            for j in range(k):
                fetch(step + 1, j, 1 - here, wait=True)

        back = [pltpu.make_async_copy(held.at[here, j],
                                      out_ref.at[step * k + j], left.at[j])
                for j in range(k)]
        for copy in back:
            copy.start()
        for copy in back:
            copy.wait()

    def rows_of(height, width):
        return pl.BlockSpec((k, height, width), lambda i, r: (i, 0, 0))

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(steps,),
        in_specs=[rows_of(groups, lanes), rows_of(groups, lanes),
                  rows_of(2 * groups, state),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   rows_of(groups, lanes)],
        scratch_shapes=[pltpu.VMEM((2, k, groups, state, lanes), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, k)),
                        pltpu.SemaphoreType.DMA((k,))])
    call = pl.pallas_call(
        kernel, grid_spec=grid,
        out_shape=[
            jax.ShapeDtypeStruct((streams, groups, state, lanes), jnp.float32),
            jax.ShapeDtypeStruct((streams, groups, lanes), jnp.float32)],
        # operand 4 (after the one prefetched) is the live state
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * k * groups * state * lanes * 4 + (12 << 20)),
        interpret=interpret)

    # no ``name=``: it would open a scope of its own below the caller's
    # (``.../mamba/step``), the stage this call's device time is booked
    # to.  An inner jit is no scope, and names the instruction all the
    # same: a trace's device operations read ``ssm_decode_step.N``
    def ssm_decode_step(*operands):
        return call(*operands)

    return jax.jit(ssm_decode_step)


# -- the Mamba-1 selective scan of a prefill chunk ----------------------------

#: channels a grid step of :func:`selective_scan` keeps (the widest of
#: these that divides the channels: ``[16, 512]`` float32 is 8 vregs,
#: carried through the loop over the tokens) and tokens a block
_SCAN_CHANNELS = (512, 256, 128)
_SCAN_TOKENS = 256


def _scan_tiles(tokens: int, channels: int) -> tuple:
    """``(tokens a block, channels a grid step)`` of
    :func:`selective_scan`, 0 where none divides."""
    cb = next((c for c in _SCAN_CHANNELS if channels % c == 0), 0)
    tb = next((t for t in (_SCAN_TOKENS, 128, 64, 32, 16, 8)
               if tokens % t == 0), 0)
    return tb, cb


def selective_scan_refusal(tokens: int, state_shape, dtypes) -> Optional[str]:
    """Why :func:`selective_scan` cannot take a chunk of ``tokens`` on a
    state ``[state, channels]`` whose operands have ``dtypes`` (the set
    of theirs), or None: float32 throughout, whole tiles of 8 tokens,
    the state axis whole sublanes and the channels whole lanes."""
    names = sorted(np.dtype(d).name for d in dtypes)
    if names != ["float32"]:
        return f"operands of {', '.join(names)}: the recurrence is float32"
    if len(state_shape) != 2:
        return f"a state {tuple(state_shape)} is not [state, channels]"
    state, channels = state_shape
    tb, cb = _scan_tiles(tokens, channels)
    if state % 8 or not cb:
        return f"a state of [{state}, {channels}] is not whole tiles of " \
               f"8 x {_LANE}"
    if not tb:
        return f"{tokens} tokens are not whole tiles of 8"
    return None


def selective_scan_reference(delta, dx, b, c, a, h0):
    """The kernel's mathematics in jnp, a ``lax.scan`` a token, and the
    path ``models/mamba1.py`` takes for a shape the kernel refuses:
    ``delta`` and ``dx`` ``[T, channels]`` (``delta`` 0 for a token that
    is padding, ``dx = delta x``), ``b`` and ``c`` ``[T, state]``, ``a``
    and ``h0`` ``[state, channels]`` (``a = -exp(A_log)``), all float32.
    ``h_t = exp(delta_t a) h_{t-1} + b_t (x) dx_t``, ``y_t = sum_n
    h_t[n] c_t[n]``.  Returns ``(y [T, channels], h_T)``."""
    import jax
    import jax.numpy as jnp

    def token(h, t):
        d, x, bt, ct = t
        h = jnp.exp(d[None, :] * a) * h + bt[:, None] * x[None, :]
        return h, jnp.sum(h * ct[:, None], axis=0)

    h, y = jax.lax.scan(token, h0, (delta, dx, b, c))
    return y, h


def selective_scan(delta, dx, b, c, a, h0):
    """The Mamba-1 recurrence over a chunk of one stream, operands as
    :func:`selective_scan_reference` has them.  The decay ``exp(delta_t
    a)`` differs by channel AND state, so the chunked matrix form of
    Mamba-2 (a scalar decay a head) does not exist; what is left is to
    keep the state where the arithmetic is.  The grid walks (a block of
    channels, a block of tokens): a step carries its channels' ``[state,
    channels]`` through its tokens in vector registers (state in the
    sublanes, channels in the lanes: ``delta_t`` and ``dx_t`` are rows,
    ``y_t`` a sum over sublanes), 8 tokens a loop iteration, whose 8
    rows of ``b`` and ``c`` are turned into columns by a product with
    the identity (``HIGHEST``: exact), and only ``delta``, ``dx`` and
    ``y`` pass through fast memory, once.  Between the token blocks of a
    channel block the state waits in scratch.  A padded token (``delta``
    and ``dx`` 0) leaves the state as it was: ``exp(0) h + 0``.  A shape
    :func:`selective_scan_refusal` names is an error: the caller
    chooses."""
    refusal = selective_scan_refusal(
        delta.shape[0], h0.shape,
        {v.dtype for v in (delta, dx, b, c, a, h0)})
    if refusal:
        raise ValueError(f"selective_scan: {refusal}")
    import jax.numpy as jnp

    tokens, channels = delta.shape
    tb, cb = _scan_tiles(tokens, channels)
    call = _selective_scan_call(tokens, channels, h0.shape[0], tb, cb,
                                _interpret())
    return call(delta, dx, jnp.concatenate([b, c], axis=1), a, h0)


@functools.lru_cache(maxsize=8)
def _selective_scan_call(tokens: int, channels: int, state: int, tb: int,
                         cb: int, interpret: bool):
    """The jitted call of :func:`selective_scan` for one shape, built
    once: a model's layers share the function."""
    import jax.numpy as jnp

    jax, pl, pltpu = _pl()
    blocks = tokens // tb

    def kernel(delta_ref, dx_ref, bc_ref, a_ref, h0_ref, y_ref, h_ref, held):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _start():
            held[...] = h0_ref[...]

        a = a_ref[...]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (2 * state, 2 * state), 0)
               == jax.lax.broadcasted_iota(jnp.int32,
                                           (2 * state, 2 * state), 1)
               ).astype(jnp.float32)

        def eight(i, h):
            rows = pl.ds(pl.multiple_of(i * 8, 8), 8)
            d8, x8 = delta_ref[rows, :], dx_ref[rows, :]
            columns = jax.lax.dot_general(               # [2 state, 8]
                eye, bc_ref[rows, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            ys = []
            for s in range(8):
                h = jnp.exp(d8[s:s + 1, :] * a) * h \
                    + columns[:state, s:s + 1] * x8[s:s + 1, :]
                ys.append(jnp.sum(h * columns[state:, s:s + 1], axis=0,
                                  keepdims=True))
            y_ref[rows, :] = jnp.concatenate(ys, axis=0)
            return h

        h = jax.lax.fori_loop(0, tb // 8, eight, held[...])
        held[...] = h

        @pl.when(t == blocks - 1)
        def _end():
            h_ref[...] = h

    by_token = pl.BlockSpec((tb, cb), lambda ch, t: (t, ch))
    by_channel = pl.BlockSpec((state, cb), lambda ch, t: (0, ch))
    call = pl.pallas_call(
        kernel, grid=(channels // cb, blocks),
        in_specs=[by_token, by_token,
                  pl.BlockSpec((tb, 2 * state), lambda ch, t: (t, 0)),
                  by_channel, by_channel],
        out_specs=[by_token, by_channel],
        out_shape=[jax.ShapeDtypeStruct((tokens, channels), jnp.float32),
                   jax.ShapeDtypeStruct((state, channels), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((state, cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret)

    # no ``name=``: the call's device time is booked to the caller's
    # stage (``.../mamba/scan``); the inner jit names the instruction
    def selective_scan(*operands):
        return call(*operands)

    return jax.jit(selective_scan)
