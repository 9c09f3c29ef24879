"""Routed experts as the token models run them (``deepseek_v2.py``,
``smallthinker.py``, ``nemotron_h.py``, ``exaone_moe.py``,
``longcat_flash.py``): the plan that lays (token, expert) pairs out by
expert (counts and ranks from a one-hot of the pairs' experts and its
running sums: no sort), the product over blocks of one expert's rows,
and the weighted sum back to tokens.  No token is dropped and there is
no capacity factor; only the blocks in use are computed and only the
rows computed are summed, so the work of both follows the tokens routed
to the experts HELD here (``[expert0, expert0 + experts)`` of the
router's width), not how many are held nor how many picks a token has.

What differs between the models is a parameter of the call: which
experts are held, and the form of one (``silu`` or ``relu``: gated,
``act(x W_gate) * (x W_up)`` through ``W_down``, three matrices;
``relu2``: ungated, ``relu(x W_up)^2`` through ``W_down``, two).  How a
model routes (groups, scaling, normalisation, what the router reads)
stays with the model; the sigmoid router two of them share is
:func:`route_sigmoid`, and the softmax router over real and
zero-compute experts alike is :func:`route_softmax` (a pick on a
zero-compute expert is a pair :func:`dispatch` drops like any expert
held elsewhere, and :func:`zero_weight` of the token's own input, which
every chip adds alike).

Also the small parts the models are made of: RMSNorm with float32
statistics, and a product in the weights' type accumulated in float32.
"""

from __future__ import annotations

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover
    jax = jnp = lax = None

from ..ops import kernels
from ..utils import profile as _profile


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def activation(name: str):
    """``(act, gated)`` of an expert's form: gated, ``act(x W_gate) * (x
    W_up)``, or not, ``act(x W_up)``."""
    try:
        return {"silu": (jax.nn.silu, True), "relu": (jax.nn.relu, True),
                "relu2": (_relu2, False)}[name]
    except KeyError:
        raise ValueError(f"expert activation {name!r}: only silu and relu "
                         "(gated) and relu2 (ungated) are written") from None


def rms(x, gain, eps):
    x32 = x.astype(jnp.float32)
    out = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (out * gain).astype(x.dtype)


def precision(w):
    return lax.Precision.HIGHEST if w.dtype == jnp.float32 else None


def mm(x, w):
    """``x @ w`` in the weights' type with float32 accumulation."""
    return jnp.matmul(x, w, preferred_element_type=jnp.float32,
                      precision=precision(w))


def route_sigmoid(u, router, bias, top_k: int, scaling: float):
    """Sigmoid scores over ALL the published experts in float32; the
    ``top_k`` largest of ``score + bias`` are chosen, and weighted by
    their scores alone, normalised to 1 and scaled by ``scaling``:
    ``(idx [N, k] int32, weight [N, k] float32)``.  The router of
    ``nemotron_h.py`` and ``exaone_moe.py`` (no group limit)."""
    score = jax.nn.sigmoid(jnp.matmul(
        u.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(score + bias, top_k)
    kept = jnp.take_along_axis(score, idx, axis=-1)
    weight = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), weight * scaling


def route_softmax(u, router, bias, top_k: int, scaling: float):
    """Softmax scores in float32 over ALL the router's outputs (the
    published experts and, after them, the zero-compute ones); the
    ``top_k`` largest of ``score + bias`` are chosen, and weighted by
    their scores alone times ``scaling``, NOT normalised to 1: ``(idx
    [N, k] int32, weight [N, k] float32)``.  The router of
    ``longcat_flash.py``."""
    score = jax.nn.softmax(jnp.matmul(
        u.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    _, idx = lax.top_k(score + bias, top_k)
    weight = jnp.take_along_axis(score, idx, axis=-1)
    return idx.astype(jnp.int32), weight * scaling


def zero_weight(idx, weight, n_real: int):
    """``[N]`` float32: the summed weight of each token's picks on
    zero-compute experts (``idx >= n_real``).  An identity expert
    returns its input, so the picks add this times the token's input
    and cost no product; every chip of a layer computes it alike."""
    return jnp.sum(jnp.where(idx >= n_real, weight, 0.0), axis=-1)


def block_rows(n_tokens: int) -> int:
    """Rows of one block of the grouped product: a block holds rows of
    ONE expert, so a larger block reads that expert's weights for more
    rows, and a smaller one pads less."""
    return int(min(256, -(-n_tokens // 8) * 8))


def plan_rows(n_tokens: int, k: int, held: int) -> int:
    """Rows a plan of :func:`dispatch` lays out: the pairs in whole
    blocks, and a block more an expert held (each expert's rows end in
    a block of their own)."""
    blk = block_rows(n_tokens)
    return -(-n_tokens * k // blk) * blk + held * blk


#: The most ``rows x pairs`` cells :func:`dispatch` inverts ``dest`` over
#: by a compare and a minimum (a decode step's plans: 0.2-3.9 M); a
#: larger plan (a prefill chunk's: 300-650 M) takes the one scatter.
ROW_TOKEN_COMPARE_CELLS = 1 << 23


def _by_row(dest, value, none, rows: int):
    """``value [pairs]`` laid out by the row ``dest [pairs]`` sends each
    pair to (``rows`` = none), ``none`` (above every value) in a row no
    pair is sent to: ``dest`` inverted, over at most
    ``ROW_TOKEN_COMPARE_CELLS`` cells of ``rows x pairs`` by a compare
    and a minimum, over more by one scatter."""
    if rows * dest.shape[0] <= ROW_TOKEN_COMPARE_CELLS:
        return jnp.min(jnp.where(
            dest[None, :] == jnp.arange(rows, dtype=jnp.int32)[:, None],
            value[None, :], none), axis=1)
    return jnp.full((rows,), none, value.dtype).at[dest].set(
        value, mode="drop")


def dispatch(idx, n_tokens: int, expert0: int, held: int, routed: int):
    """Lay the (token, expert) pairs of ``idx [n_tokens, k]`` that fall
    on a HELD expert (``held`` of the ``routed`` a pick can name) out by
    expert, each expert's rows in pair order and in whole blocks.
    Returns the plan of the grouped product: per padded row the token it
    holds (``n_tokens`` = none), per pair the row its result lands in
    (the last row = none: an expert held elsewhere), per block its
    expert, the number of blocks in use, the tokens each held expert
    got, and the share of picks that name an expert held ``elsewhere``
    (static: :func:`combine` chooses its program by it).

    Nothing is sorted: a one-hot ``[held, pairs]`` of the pairs' experts
    gives the counts as its row sums and a pair's rank among its
    expert's pairs as the running sum along its row, so ``dest`` is
    arithmetic a pair.  ``row_token`` is ``dest`` inverted: over at most
    ``ROW_TOKEN_COMPARE_CELLS`` cells of ``rows x pairs`` by a compare
    and a minimum, over more by the plan's one scatter; the static
    shapes alone choose."""
    k = idx.shape[1]
    blk = block_rows(n_tokens)
    pairs = n_tokens * k
    rows = plan_rows(n_tokens, k, held)
    local = idx.reshape(-1) - expert0
    hot = (local[None, :] == jnp.arange(held, dtype=jnp.int32)[:, None]
           ).astype(jnp.int32)
    counts = jnp.sum(hot, axis=1)
    padded = (counts + blk - 1) // blk * blk
    pad_end = jnp.cumsum(padded)
    # a held pair's row: where its expert's rows start, and the pairs of
    # that expert before it
    row = (pad_end - padded)[:, None] + jnp.cumsum(hot, axis=1) - hot
    dest = jnp.where((local >= 0) & (local < held),
                     jnp.sum(hot * row, axis=0), rows)
    row_token = _by_row(dest, jnp.arange(pairs, dtype=jnp.int32) // k,
                        n_tokens, rows)
    block_expert = jnp.minimum(jnp.searchsorted(
        pad_end, jnp.arange(rows // blk, dtype=jnp.int32) * blk,
        side="right", method="compare_all"), held - 1).astype(jnp.int32)
    return {"row_token": row_token, "dest": dest.reshape(n_tokens, k),
            "block_expert": block_expert, "blocks": pad_end[-1] // blk,
            "counts": counts, "blk": blk, "rows": rows,
            "elsewhere": max(routed - held, 0) / routed}


def one_group_plan(n_rows: int):
    """The plan of ``n_rows`` rows that all belong to expert 0, as ONE
    block: how a model runs a dense MLP through :func:`grouped_experts`
    (its matrices given a leading axis of 1), so that the kernel's
    pipeline streams them while it multiplies.  Row ``i`` of the result
    is token ``i``; there is nothing to combine."""
    return {"row_token": jnp.arange(n_rows, dtype=jnp.int32),
            "block_expert": jnp.zeros((1,), jnp.int32),
            "blocks": jnp.int32(1), "blk": n_rows, "rows": n_rows}


def grouped_experts(p, x, plan, act: str = "silu"):
    """The held experts' MLPs (``p``: ``up`` and ``down`` and, for a
    gated ``act``, ``gate``) on the rows ``plan`` lays out, a block (of
    one expert's rows) at a time: ``[rows + 1, hidden]``, the last row
    zero.  Only the blocks in use are computed, so the work follows the
    tokens routed here, not the held experts; a row no pair's ``dest``
    points at holds anything, which is why :func:`combine` reads an
    expert's first ``counts[e]`` rows and no other.

    One algorithm, two programs, chosen from the shapes: the pipelined
    kernel (``ops/kernels.py`` ``grouped_gated_product``: the next
    block's matrices stream in while this one's are multiplied) wherever
    its refusal has nothing to say, :func:`grouped_experts_loop`
    everywhere else.  The set-up span this is traced under says which
    (``utils/profile.py`` ``note``)."""
    fn, gated = activation(act)
    w, gate = p["up"], p["gate"] if gated else None
    matrices = 3 if gated else 2
    refusal = kernels.grouped_gated_product_refusal(
        x.shape, w.shape, p["down"].shape,
        {x.dtype} | {m.dtype for m in p.values()}, plan["blk"], matrices)
    shapes = f"grouped_experts {plan['blk']} rows x {plan['rows'] // plan['blk']} " \
             f"blocks, {tuple(w.shape)} {w.dtype.name}"
    if refusal:
        _profile.note(f"{shapes}: the loop ({refusal})")
        return grouped_experts_loop(p, x, plan, act)
    tile = kernels.grouped_tile(w.shape[1], w.shape[2], w.dtype,
                                matrices=matrices)
    _profile.note(f"{shapes}: the kernel, tiles of {tile}")
    return kernels.grouped_gated_product(
        x, gate, w, p["down"], plan["row_token"], plan["block_expert"],
        plan["blocks"], plan["blk"], fn)


def grouped_experts_loop(p, x, plan, act: str = "silu"):
    """:func:`grouped_experts` as XLA's own ``while``: an iteration a
    block in use, each ending before the next begins.  The program of
    every shape the kernel refuses, and what the kernel is tested
    against; every row of a block not in use is zero here."""
    blk, rows = plan["blk"], plan["rows"]
    fn, gated = activation(act)
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])

    def body(b, out):
        e = plan["block_expert"][b]
        tok = lax.dynamic_slice(plan["row_token"], (b * blk,), (blk,))
        xb = x_pad[tok]
        if gated:
            h = fn(mm(xb, p["gate"][e])) * mm(xb, p["up"][e])
        else:
            h = fn(mm(xb, p["up"][e]))
        ob = mm(h.astype(x.dtype), p["down"][e]).astype(x.dtype)
        return lax.dynamic_update_slice(out, ob, (b * blk, 0))

    return lax.fori_loop(0, plan["blocks"], body,
                         jnp.zeros((rows + 1, x.shape[1]), x.dtype))


#: elements of rows (pairs of experts held elsewhere x hidden) the
#: gather must be spared before the walk's fixed cost is worth paying:
#: a launch, its lists and the first copies' latency read 9-12 us alone
#: on the chip, what the gather takes over 4 M elements (``PERF.md``
#: section 6, PR 54)
WALK_WORTH_ELEMENTS = 1 << 22


def combine(out, plan, weight):
    """Each token's weighted sum of its pairs' rows: ``[tokens, hidden]``
    float32 from ``out`` (:func:`grouped_experts`'), the plan and
    ``weight [tokens, k]`` float32.

    Only rows a pair's ``dest`` points at are read, each in the type it
    is stored in; it is widened to float32, scaled by its pair's weight
    and added in float32.  Neither a block's padding rows nor a block
    past ``plan["blocks"]`` enters a sum, whatever they hold.  A token
    none of whose picks is held gets zeros; a NaN, an infinity or a
    ``-0.0`` in a held row reaches its own token's sum and no other.
    No value of ``[tokens, k, hidden]`` exists in either program.

    One sum, two programs, chosen from the shapes.  The rows this chip
    computed are WALKED (``ops/kernels.py`` ``weighted_row_sum``):
    expert ``e``'s first ``plan["counts"][e]`` rows from where its
    blocks start, each read once, a token's rows added in the order
    they lie in (expert by expert: the pick order's sum up to the
    float32 rounding of at most ``k`` terms), so the work follows
    ``sum(counts)`` and not the pairs.  Where that spares little (the
    pairs ``plan["elsewhere"]`` expects on other chips are fewer than
    ``WALK_WORTH_ELEMENTS`` of rows: a decode step of a few hundred
    pairs, or every expert held here) or the kernel refuses the shapes,
    the pairs are GATHERED (:func:`combine_gather`), ``k`` rows a token
    summed in pick order, the zero row for a pair held elsewhere.  The
    set-up span this is traced under says which (``utils/profile.py``
    ``note``)."""
    tokens, k = weight.shape
    rows, hidden = plan["rows"], out.shape[1]
    shapes = f"combine {tokens} tokens x {k} picks of {rows} rows, " \
             f"{tuple(out.shape)} {out.dtype.name}"
    spared = int(tokens * k * plan["elsewhere"])
    refusal = f"the walk would spare it {spared} rows of {tokens * k}" \
        if spared * hidden <= WALK_WORTH_ELEMENTS else \
        kernels.weighted_row_sum_refusal(out.shape, out.dtype, tokens,
                                         plan["blk"])
    if refusal:
        _profile.note(f"{shapes}: the gather ({refusal})")
        return combine_gather(out, plan, weight)
    _profile.note(f"{shapes}: the row walk, tiles of "
                  f"{kernels.row_sum_tile(tokens, hidden)} columns")
    # a row's weight, as :func:`dispatch` finds its token; the walk
    # reads neither of a row no pair is sent to
    row_weight = _by_row(plan["dest"].reshape(-1), weight.reshape(-1),
                         jnp.inf, rows)
    return kernels.weighted_row_sum(out, plan["row_token"], row_weight,
                                    plan["counts"], plan["blk"], tokens)


def combine_gather(out, plan, weight):
    """:func:`combine` a pair at a time: ``k`` gathers of one row a
    token, each widened, scaled and added to the float32 sum in pick
    order.  A pair held elsewhere reads the zero row: the work follows
    the pairs.  The program of every plan the walk would spare little
    and of every shape the kernel refuses, and what the kernel is
    tested against."""
    total = jnp.zeros((weight.shape[0], out.shape[1]), jnp.float32)
    for pick in range(weight.shape[1]):
        total = total + out[plan["dest"][:, pick]].astype(jnp.float32) \
            * weight[:, pick, None]
    return total
