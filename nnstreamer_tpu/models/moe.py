"""Routed experts as the token models run them (``deepseek_v2.py``,
``smallthinker.py``, ``nemotron_h.py``, ``exaone_moe.py``,
``longcat_flash.py``): the plan that lays (token, expert) pairs out by
expert (counts and ranks from a one-hot of the pairs' experts and its
running sums: no sort), the product over blocks of one expert's rows,
and the weighted sum back to tokens.  No token is dropped and there is
no capacity factor; only the blocks in use are computed, so the work
follows the tokens routed to the experts HELD here (``[expert0,
expert0 + experts)`` of the router's width), not how many are held.

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


#: The most ``rows x pairs`` cells :func:`dispatch` inverts ``dest`` over
#: by a compare and a minimum (a decode step's plans: 0.2-3.9 M); a
#: larger plan (a prefill chunk's: 300-650 M) takes the one scatter.
ROW_TOKEN_COMPARE_CELLS = 1 << 23


def dispatch(idx, n_tokens: int, expert0: int, held: int):
    """Lay the (token, expert) pairs of ``idx [n_tokens, k]`` that fall
    on a HELD expert out by expert, each expert's rows in pair order and
    in whole blocks.  Returns the plan of the grouped product: per padded
    row the token it holds (``n_tokens`` = none), per pair the row its
    result lands in (the last row = none: an expert held elsewhere), per
    block its expert, the number of blocks in use and the tokens each
    held expert got.

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
    rows = -(-pairs // blk) * blk + held * blk
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
    token = jnp.arange(pairs, dtype=jnp.int32) // k
    if rows * pairs <= ROW_TOKEN_COMPARE_CELLS:
        row_token = jnp.min(jnp.where(
            dest[None, :] == jnp.arange(rows, dtype=jnp.int32)[:, None],
            token[None, :], n_tokens), axis=1)
    else:
        row_token = jnp.full((rows,), n_tokens, jnp.int32).at[dest].set(
            token, mode="drop")
    block_expert = jnp.minimum(jnp.searchsorted(
        pad_end, jnp.arange(rows // blk, dtype=jnp.int32) * blk,
        side="right", method="compare_all"), held - 1).astype(jnp.int32)
    return {"row_token": row_token, "dest": dest.reshape(n_tokens, k),
            "block_expert": block_expert, "blocks": pad_end[-1] // blk,
            "counts": counts, "blk": blk, "rows": rows}


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
    points at holds anything.

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


def combine(out, plan, weight):
    """Each token's weighted sum of its pairs' rows, float32."""
    return jnp.sum(out[plan["dest"]].astype(jnp.float32)
                   * weight[..., None], axis=1)
