"""``models/moe.py`` ``combine``: each token's weighted sum of the rows
this chip computed, by the row walk (``ops/kernels.py``
``weighted_row_sum``, under the Pallas interpreter here;
``tests/test_tpu_compile.py`` compiles it for the chip) or by the gather
a pick at a time, against the formula it was until PR 54, kept here as
its plain reference: a gather of one row for EVERY pair, widened,
weighted and summed.  Also what the compiled programs of a toy
``longcat`` share no longer hold, and the note that says which program
a shape took."""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import longcat_flash as lc
from nnstreamer_tpu.models import moe
from nnstreamer_tpu.ops import kernels
from nnstreamer_tpu.utils import profile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUTED, K, HIDDEN = 64, 4, 128
#: tokens of a decode step (one block of 32 rows an expert) and of a
#: prefill chunk (blocks of 256 rows: every expert's last one is partial)
SHAPES = {"decode": 32, "chunk": 264}
SHARES = {"1-in-64": 1, "1-in-8": 8, "1-in-4": 16, "all": 64}
EXPERT0 = {1: 37, 8: 8, 16: 48, 64: 0}


def _note(span):
    """What the newest set-up span of that name was told."""
    return [s.note for s in profile.spans() if s.name == span][-1]


def combine_every_pair(out, plan, weight):
    """``combine`` as it was: the pair of an expert held elsewhere reads
    the zero row, and a ``[tokens, k, hidden]`` float32 is summed."""
    return jnp.sum(out[plan["dest"]].astype(jnp.float32)
                   * weight[..., None], axis=1)


def _picks(case, tokens, expert0, held):
    """``idx [tokens, K]``: K distinct experts a token, seeded, bent to
    what the case's name says."""
    rng = np.random.default_rng(54)
    idx = np.argsort(rng.random((tokens, ROUTED)), axis=1)[:, :K]
    if case == "a-token-of-no-held-pick" and held < ROUTED:
        elsewhere = [e for e in range(ROUTED)
                     if not expert0 <= e < expert0 + held]
        idx[3] = elsewhere[:K]
        idx[tokens - 1] = elsewhere[-K:]
    elif case == "every-pair-on-one-expert":
        # but the last token's: whole blocks and a partial one
        idx[:] = expert0 + held // 2
        idx[tokens - 1] = (expert0 + held // 2 + 1) % ROUTED
    return jnp.asarray(idx, jnp.int32)


def _rows(plan, case):
    """``out [rows + 1, HIDDEN]`` bfloat16 as the grouped product leaves
    it: seeded values in the rows a pair points at, the last row zero,
    and GARBAGE everywhere else (a block's padding, blocks past
    ``plan["blocks"]``): "a row no pair's dest points at holds
    anything"."""
    rows = plan["rows"]
    dest = np.asarray(plan["dest"]).reshape(-1)
    out = np.random.default_rng(5).standard_normal(
        (rows + 1, HIDDEN)).astype(np.float32)
    real = np.zeros(rows + 1, bool)
    real[dest] = True
    real[rows] = False
    garbage = np.where(np.arange(HIDDEN) % 2, np.nan, 3e38)
    out[~real] = garbage
    out[rows] = 0.0
    if case == "specials-in-a-held-row" and real.any():
        first, second = np.flatnonzero(real)[[0, -1]]
        out[first, 0], out[first, 1], out[first, 2] = np.nan, np.inf, -np.inf
        out[second, :] = -0.0
    return jnp.asarray(out, jnp.bfloat16), real[:rows]


CASES = ("seeded", "a-token-of-no-held-pick", "every-pair-on-one-expert",
         "specials-in-a-held-row")
#: what ``WALK_WORTH_ELEMENTS`` is set to for a program to be taken at
#: the tests' small shapes, and what the note then says
PROGRAMS = {"the-walk": (-1, "the row walk, tiles of 128 columns"),
            "the-gather": (1 << 62, "the gather (the walk would spare it ")}


@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("share", list(SHARES))
def test_combine_is_the_sum_over_every_pair(share, shape, case, program,
                                            monkeypatch):
    """Held 1 in 64, 1 in 8, 1 in 4 and all, a decode step and a chunk,
    both programs: the sum equals the old formula's to the float32
    rounding of a reordered sum of K terms; a token of no held pick
    gets zeros; garbage in padding rows and in blocks not in use
    reaches nothing; a NaN, an infinity and a ``-0.0`` reach their own
    token as they did."""
    tokens, held = SHAPES[shape], SHARES[share]
    worth, says = PROGRAMS[program]
    monkeypatch.setattr(moe, "WALK_WORTH_ELEMENTS", worth)
    expert0 = EXPERT0[held]
    idx = _picks(case, tokens, expert0, held)
    weight = jnp.asarray(np.random.default_rng(6).uniform(
        -1.0, 2.0, idx.shape).astype(np.float32))
    plan = moe.dispatch(idx, tokens, expert0, held, ROUTED)
    assert plan["elsewhere"] == 1 - held / ROUTED
    out, real = _rows(plan, case)
    assert int(real.sum()) == int(plan["counts"].sum())
    profile.clear()
    with profile.span("el_net", "trace_lower", setup=True):
        got = np.asarray(jax.jit(
            lambda out, weight: moe.combine(out, plan, weight))(out, weight))
    note = _note("el_net/trace_lower")
    assert says in note, note
    want = np.asarray(combine_every_pair(out, plan, weight))
    assert got.dtype == np.float32 and got.shape == (tokens, HIDDEN)
    # nothing but the order of at most K float32 additions differs
    terms = np.abs(np.nan_to_num(np.asarray(
        out, np.float32)[np.asarray(plan["dest"])]
        * np.asarray(weight)[..., None], posinf=0.0, neginf=0.0))
    slack = 4 * np.finfo(np.float32).eps * terms.sum(axis=1)
    assert np.array_equal(np.isnan(got), np.isnan(want))
    assert np.array_equal(np.isposinf(got), np.isposinf(want))
    assert np.array_equal(np.isneginf(got), np.isneginf(want))
    finite = np.isfinite(want)
    with np.errstate(invalid="ignore"):
        assert np.all(np.abs(got - want)[finite] <= slack[finite])
    here = (np.asarray(idx) >= expert0) & (np.asarray(idx) < expert0 + held)
    none = ~here.any(axis=1)
    assert not got[none].any()
    if case == "a-token-of-no-held-pick" and held < ROUTED:
        assert none[3] and none[tokens - 1]
    if case == "specials-in-a-held-row":
        assert np.isnan(got).sum() == 1 and np.isinf(got).sum() == 2
    if case == "every-pair-on-one-expert":
        counts = np.asarray(plan["counts"])
        assert counts.max() == (tokens - 1) * K
        assert counts.max() % plan["blk"]       # a partial block after
        assert counts.max() > plan["blk"]       # whole ones


@pytest.mark.parametrize("what,out_shape,dtype,tokens,blk", [
    ("bfloat16 or float32", (65, 128), jnp.float16, 8, 16),
    ("whole lanes of 128", (65, 192), jnp.bfloat16, 8, 16),
    ("whole copies of 16", (49, 128), jnp.bfloat16, 8, 24),
    ("whole copies of 16", (65, 128), jnp.float32, 8, 8),
    ("no column tile", (16385, 128), jnp.bfloat16, 1 << 16, 256),
    ("scalar memory", ((1 << 17) + 1, 128), jnp.bfloat16, 2048, 256),
])
def test_the_walk_refuses_what_it_cannot_take(what, out_shape, dtype, tokens,
                                              blk):
    refusal = kernels.weighted_row_sum_refusal(out_shape, dtype, tokens, blk)
    assert refusal and what in refusal, refusal
    with pytest.raises(ValueError, match="weighted_row_sum"):
        kernels.weighted_row_sum(
            jnp.zeros(out_shape, dtype), jnp.zeros((out_shape[0] - 1,)),
            jnp.zeros((out_shape[0] - 1,)), jnp.zeros((4,)), blk, tokens)


def test_a_refused_shape_gathers_and_the_note_says_why(monkeypatch):
    """Hidden 64 is half a lane: the pairs are gathered a pick at a
    time, and the span says so."""
    monkeypatch.setattr(moe, "WALK_WORTH_ELEMENTS", 0)
    idx = _picks("seeded", 32, 8, 8)
    plan = moe.dispatch(idx, 32, 8, 8, ROUTED)
    out = jnp.ones((plan["rows"] + 1, 64), jnp.bfloat16).at[-1].set(0)
    weight = jnp.ones(idx.shape, jnp.float32)
    profile.clear()
    with profile.span("el_net", "trace_lower", setup=True):
        got = moe.combine(out, plan, weight)
    moe.combine(out, plan, weight)           # no span open: says nothing
    note = _note("el_net/trace_lower")
    assert "combine 32 tokens x 4 picks of 384 rows, (385, 64) bfloat16: " \
           "the gather (rows of (385, 64): not [rows + 1, whole lanes of " \
           "128])" in note, note
    held = ((np.asarray(idx) >= 8) & (np.asarray(idx) < 16)).sum(axis=1)
    assert np.array_equal(np.asarray(got), np.tile(held[:, None], (1, 64)))


#: the five routed cells: tokens of a step, picks, held, the router's
#: width, hidden
CELLS = {"longcat.decode4k": (128, 12, 8, 768, 6144),
         "dsv2.decode16k": (32, 6, 40, 160, 5120),
         "kexaone.decode16k": (32, 8, 16, 128, 6144),
         "nemotron3.decode4k": (128, 6, 16, 128, 2688),
         "smallthinker.decode16k": (32, 6, 64, 64, 2560)}


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("entry", ["chunk", "decode"])
def test_the_static_shapes_alone_choose_the_program(cell, entry):
    """What each cell's two programs take, from the shapes alone
    (nothing runs): a chunk of 2,048 tokens is walked wherever an
    expert is held elsewhere; a decode step is walked where the walk
    spares the gather more than its own fixed cost (``longcat``'s 1,536
    pairs of which 1 in 96 is held), and gathered where a step has a
    few hundred pairs; ``smallthinker`` holds every expert, so the walk
    would spare it nothing."""
    tokens, k, held, routed, hidden = CELLS[cell]
    if entry == "chunk":
        tokens = 2048
    rows = moe.plan_rows(tokens, k, held)
    profile.clear()
    with profile.span("el_net", "trace_lower", setup=True):
        got = jax.eval_shape(
            lambda idx, out, weight: moe.combine(
                out, moe.dispatch(idx, tokens, 0, held, routed), weight),
            jax.ShapeDtypeStruct((tokens, k), jnp.int32),
            jax.ShapeDtypeStruct((rows + 1, hidden), jnp.bfloat16),
            jax.ShapeDtypeStruct((tokens, k), jnp.float32))
    assert got.shape == (tokens, hidden) and got.dtype == jnp.float32
    walked = "the row walk" in _note("el_net/trace_lower")
    assert walked == (cell != "smallthinker.decode16k" and (
        entry == "chunk" or cell == "longcat.decode4k"))


def test_the_tile_and_the_cells_shapes():
    """The result's column tile at the five cells' chunks (2,048 tokens)
    and decode steps: the widest divisor of the hidden width, of whole
    lanes, within 24 MiB of float32."""
    assert kernels.row_sum_tile(2048, 6144) == 3072     # longcat, kexaone
    assert kernels.row_sum_tile(2048, 5120) == 2560     # dsv2
    assert kernels.row_sum_tile(2048, 2688) == 2688     # nemotron3
    assert kernels.row_sum_tile(128, 6144) == 6144
    assert kernels.row_sum_tile(32, 5120) == 5120
    for tokens, k, held, hidden in ((2048, 12, 8, 6144), (2048, 6, 40, 5120),
                                    (2048, 6, 64, 2560), (32, 8, 16, 6144)):
        assert kernels.weighted_row_sum_refusal(
            (moe.plan_rows(tokens, k, held) + 1, hidden), jnp.bfloat16,
            tokens, moe.block_rows(tokens)) is None


# -- the programs of a toy longcat share --------------------------------------------


STREAMS, CHUNK, POSITIONS, PICKS = 16, 32, 128, 3


def _toy_programs():
    """The optimised text of the prefill and decode programs of the toy
    twin of ``longcat_flash_omni_share64`` at a hidden width of one lane
    (so that the walk takes its shapes) and 3 picks a token (a shape
    nothing else in the program has)."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_longcat.json")) as f:
        raw = json.load(f)
    raw.update(hidden_size=HIDDEN, moe_topk=PICKS)
    raw["published"].update(hidden_size=HIDDEN, moe_topk=PICKS)
    cfg = lc.LongCatFlashConfig.from_dict(raw)
    params = jax.eval_shape(lambda: lc.init_params(cfg, 0))
    state = jax.eval_shape(
        lambda: lc.init_state(cfg, params, STREAMS, POSITIONS))
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    texts = {}
    for name, fn, inputs in (
            ("prefill", lc.prefill,
             (jax.ShapeDtypeStruct((CHUNK,), jnp.int32), one, one)),
            ("decode", lc.decode,
             (jax.ShapeDtypeStruct((STREAMS,), jnp.int32),) * 2)):
        texts[name] = jax.jit(
            lambda p, s, *x, fn=fn: fn(cfg, p, s, *x)).lower(
                params, state, *inputs).compile().as_text()
    return texts


def _pair_values(text, tokens):
    """Values of ``[tokens, picks, hidden]`` in a program's text."""
    return re.findall(rf"\b(?:f32|bf16)\[{tokens},{PICKS},{HIDDEN}\]", text)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_a_toy_longcat_shares_programs_hold_no_value_a_pair(program,
                                                            monkeypatch):
    """Neither the prefill chunk's program nor the decode step's holds
    a ``[tokens, k, hidden]`` value, float32 or not, nor a gather of
    ``tokens x k`` rows, whichever of its two programs ``combine``
    takes (the cell's shapes take the walk in both; the toy's take it
    here because the test says so); the span names the program."""
    worth, says = PROGRAMS[program]
    monkeypatch.setattr(moe, "WALK_WORTH_ELEMENTS", worth)
    profile.clear()
    with profile.span("pf_net", "trace_lower", setup=True):
        texts = _toy_programs()
    note = _note("pf_net/trace_lower")
    for tokens in (CHUNK, STREAMS):
        assert f"combine {tokens} tokens x {PICKS} picks" in note, note
    assert says in note and "(x2)" in note, note
    for name, tokens in (("prefill", CHUNK), ("decode", STREAMS)):
        assert not _pair_values(texts[name], tokens), name
        assert not re.search(
            rf"\[{tokens * PICKS},{HIDDEN}\]\S* gather\(", texts[name]), name


def test_the_old_formula_would_show_in_the_text(monkeypatch):
    """With the old formula in ``combine``'s place both programs hold
    the value a pair: the text can show it."""
    monkeypatch.setattr(moe, "combine", combine_every_pair)
    texts = _toy_programs()
    assert _pair_values(texts["prefill"], CHUNK)
    assert _pair_values(texts["decode"], STREAMS)
