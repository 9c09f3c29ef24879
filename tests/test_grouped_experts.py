"""The routed experts' grouped product as one kernel
(``ops/kernels.py`` ``grouped_gated_product``) against the loop it
stands in for (``models/moe.py`` ``grouped_experts_loop``), which path
``grouped_experts`` takes for a shape, and the stage the call's device
time is booked to.  The kernel runs under the Pallas interpreter here;
``tests/test_tpu_compile.py`` compiles it for the chip.  Also the plan
both run on (``moe.dispatch``) against the sort and the scatters it was
made of until PR 45, kept here as its oracle."""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import deepseek_v2 as dsv2
from nnstreamer_tpu.models import moe
from nnstreamer_tpu.models import nemotron_h as nh
from nnstreamer_tpu.models import smallthinker as st
from nnstreamer_tpu.ops import kernels
from nnstreamer_tpu.utils import profile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD, ROUTED, K, HIDDEN, INTER = 4, 8, 2, 128, 256


def _experts(dtype, seed=0, act="silu", inter=INTER):
    """An expert's matrices: three of a gated form, two of an ungated."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    names = ("gate", "up", "down") if moe.activation(act)[1] \
        else ("", "up", "down")
    return {name: (jax.random.normal(key, shape) * shape[1] ** -0.5)
            .astype(dtype)
            for key, name, shape in zip(
                keys, names,
                [(HELD, HIDDEN, inter)] * 2 + [(HELD, inter, HIDDEN)])
            if name}


def _routes(case, n):
    """``idx [n, K]`` over ROUTED experts of which the first HELD are
    held here."""
    idx = jax.random.randint(jax.random.PRNGKey(7), (n, K), 0, ROUTED)
    if case == "all-on-one":
        return jnp.full_like(idx, 1).at[:, 1:].set(HELD + 1)
    if case == "two-blocks-of-one":    # both of a token's pairs on it
        return jnp.full_like(idx, 2)
    if case == "none-held":
        return HELD + idx % (ROUTED - HELD)
    if case == "few-blocks":           # one expert in use of four held
        return jnp.where(idx < HELD, 3, idx)
    return idx


def _both(dtype, act, case, tile):
    """The kernel's and the loop's ``[rows + 1, hidden]`` and the plan."""
    n = {"one-token": 1, "a-prefill-chunk": 264}.get(case, 32)
    p = _experts(dtype, act=act)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, HIDDEN)).astype(dtype)
    plan = moe.dispatch(_routes(case, n), n, 0, HELD, ROUTED)
    got = kernels.grouped_gated_product(
        x, p.get("gate"), p["up"], p["down"], plan["row_token"],
        plan["block_expert"], plan["blocks"], plan["blk"],
        moe.activation(act)[0], tile=tile)
    return got, moe.grouped_experts_loop(p, x, plan, act), plan


CASES = [(dtype, act, case, tile)
         for dtype in ("bfloat16", "float32")
         for act in ("silu", "relu", "relu2")
         for case in ("seeded", "all-on-one", "two-blocks-of-one",
                      "none-held", "few-blocks", "one-token",
                      # more tokens than a block (256 rows): the rows are
                      # laid out before the call, not picked in it
                      "a-prefill-chunk")
         for tile in (None, 128)
         # a block of one token is 8 rows: half a tile of bf16, which the
         # kernel refuses (the refusal test below has it)
         if not (case == "one-token" and dtype == "bfloat16")]


@pytest.mark.parametrize("dtype,act,case,tile", CASES)
def test_the_kernel_is_the_loop(dtype, act, case, tile):
    """Every row of a block in use and every token's weighted sum: bit
    for bit where the intermediate width is one tile (the same
    products, accumulated and rounded at the same points), within
    float32 accumulation's tolerance where it is two (the down
    projection summed tile by tile).  The row ``rows`` reads zero."""
    got, want, plan = _both(jnp.dtype(dtype), act, case, tile)
    blocks, grid = int(plan["blocks"]), plan["rows"] // plan["blk"]
    assert got.shape == want.shape == (plan["rows"] + 1, HIDDEN)
    assert got.dtype == want.dtype
    assert blocks < grid                       # steps with nothing to do
    assert blocks == {"all-on-one": 1, "two-blocks-of-one": 2,
                      "none-held": 0, "few-blocks": 1}.get(case, blocks)
    used = blocks * plan["blk"]
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    assert not g[plan["rows"]].any()
    weight = jax.random.uniform(jax.random.PRNGKey(3), plan["dest"].shape)
    sums = [np.asarray(moe.combine(out, plan, weight)) for out in (got, want)]
    if tile is None:
        assert kernels.grouped_tile(HIDDEN, INTER, dtype) == INTER
        assert np.array_equal(g[:used], w[:used])
        assert np.array_equal(*sums)
    else:
        tol = 2e-5 if dtype == "float32" else 2 ** -7
        assert np.allclose(g[:used], w[:used], rtol=tol, atol=tol)
        assert np.allclose(*sums, rtol=tol, atol=tol)
    if case == "none-held":
        assert not sums[0].any()


@pytest.mark.parametrize("x,gate,dtypes,blk,says", [
    ((32, 64), (4, 64, 256), {"float32"}, 32, "whole lanes"),
    ((32, 128), (4, 128, 96), {"float32"}, 32, "whole lanes"),
    ((8, 128), (4, 128, 256), {"bfloat16"}, 8, "whole tiles of 16"),
    ((32, 128), (4, 128, 256), {"bfloat16", "float32"}, 32, "all bfloat16"),
    ((32, 128), (4, 128, 256), {"float16"}, 32, "all bfloat16"),
    ((32, 128), (4, 256, 256), {"float32"}, 32, "not [tokens, hidden]"),
    ((32, 1 << 20), (4, 1 << 20, 128), {"float32"}, 32, "no tile"),
])
def test_a_refused_shape_keeps_the_loop(x, gate, dtypes, blk, says):
    """What the kernel cannot take it says, the kernel itself raises
    with it, and ``grouped_experts`` then IS the loop."""
    down = (gate[0], gate[2], gate[1])
    refusal = kernels.grouped_gated_product_refusal(x, gate, down, dtypes,
                                                    blk)
    assert says in refusal
    if x[1] > 4096 or x[1] != gate[1] or len(dtypes) > 1 \
            or "float16" in dtypes:
        return                   # no operands the loop could take either
    dtype = jnp.dtype(next(iter(dtypes)))
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    p = {"gate": jax.random.normal(keys[0], gate, dtype),
         "up": jax.random.normal(keys[1], gate, dtype),
         "down": jax.random.normal(keys[2], down, dtype)}
    xs = jax.random.normal(keys[3], x, dtype)
    plan = moe.dispatch(_routes("seeded", x[0]) % gate[0], x[0], 0, gate[0],
                        gate[0])
    assert plan["blk"] == blk
    with pytest.raises(ValueError, match="grouped_gated_product"):
        kernels.grouped_gated_product(
            xs, p["gate"], p["up"], p["down"], plan["row_token"],
            plan["block_expert"], plan["blocks"], blk, jax.nn.silu)
    text = str(jax.make_jaxpr(
        lambda p, xs: moe.grouped_experts(p, xs, plan))(p, xs))
    assert "pallas_call" not in text
    assert np.array_equal(np.asarray(moe.grouped_experts(p, xs, plan)),
                          np.asarray(moe.grouped_experts_loop(p, xs, plan)))


@pytest.mark.parametrize("inter", [256, 192])
@pytest.mark.parametrize("act", ["silu", "relu", "relu2"])
def test_kernel_and_loop_are_the_per_token_product(act, inter):
    """Gated and ungated alike, ``grouped_experts`` is each (token,
    expert) pair's own product, written out pair by pair: through the
    kernel at a width of whole lanes, through the loop at one that is
    not (192 = a lane and a half, which the kernel refuses)."""
    p = _experts(jnp.float32, act=act, inter=inter)
    assert set(p) == ({"gate", "up", "down"} if act != "relu2"
                      else {"up", "down"})
    x = jax.random.normal(jax.random.PRNGKey(1), (24, HIDDEN))
    idx = _routes("seeded", 24)
    plan = moe.dispatch(idx, 24, 0, HELD, ROUTED)
    weight = jax.random.uniform(jax.random.PRNGKey(3), idx.shape)
    text = str(jax.make_jaxpr(
        lambda p, x: moe.grouped_experts(p, x, plan, act))(p, x))
    assert ("pallas_call" in text) == (inter == 256)
    got = [np.asarray(moe.combine(fn(p, x, plan, act), plan, weight))
           for fn in (moe.grouped_experts, moe.grouped_experts_loop)]
    want = np.zeros((24, HIDDEN))
    xs, ws = np.asarray(x, np.float64), np.asarray(weight, np.float64)
    q = {k: np.asarray(v, np.float64) for k, v in p.items()}
    for t in range(24):
        for j, e in enumerate(np.asarray(idx)[t]):
            if e >= HELD:
                continue                    # an expert held elsewhere
            up = xs[t] @ q["up"][e]
            if act == "relu2":
                h = np.maximum(up, 0) ** 2
            else:
                g = xs[t] @ q["gate"][e]
                h = (g / (1 + np.exp(-g)) if act == "silu"
                     else np.maximum(g, 0)) * up
            want[t] += ws[t, j] * (h @ q["down"][e])
    for out in got:
        assert np.allclose(out, want, rtol=2e-5, atol=2e-5)


def test_an_ungated_expert_streams_two_tiles_a_step():
    """Two weight operands and not three with a dummy: the call's
    operands are the plan's two scalars, the rows' two, ``up`` and
    ``down``."""
    p, x = _experts(jnp.float32, act="relu2"), jnp.zeros((32, HIDDEN))
    plan = moe.dispatch(_routes("seeded", 32), 32, 0, HELD, ROUTED)
    calls = {act: _calls(jax.make_jaxpr(
        lambda p, x: moe.grouped_experts(p, x, plan, act))(q, x).jaxpr, [])
        for act, q in (("relu2", p), ("relu", _experts(jnp.float32)))}
    assert [len(c[0].invars) for c in calls.values()] == [6, 7]
    with pytest.raises(ValueError, match="relu2"):
        moe.activation("gelu")


def test_the_tile_follows_the_shapes():
    """The largest divisor of the intermediate width, of whole lanes,
    whose tiles (three of a gated expert, two of an ungated one) fit
    the budget twice: the three cells' experts."""
    # Nemotron-3-Nano's 1,856 columns stored as 1,920 = 15 lanes: tiles
    # of 640; the published width has no tile of whole lanes at all
    assert kernels.grouped_tile(2688, 1920, jnp.bfloat16, matrices=2) == 640
    assert kernels.grouped_tile(2688, 1856, jnp.bfloat16, matrices=2) == 0
    assert "whole lanes" in kernels.grouped_gated_product_refusal(
        (128, 2688), (16, 2688, 1856), (16, 1856, 2688), {"bfloat16"}, 128,
        matrices=2)
    assert kernels.grouped_gated_product_refusal(
        (128, 2688), (16, 2688, 1920), (16, 1920, 2688), {"bfloat16"}, 128,
        matrices=2) is None
    assert kernels.grouped_tile(2560, 768, jnp.bfloat16) == 768
    assert kernels.grouped_tile(5120, 1536, jnp.bfloat16) == 384
    assert kernels.grouped_tile(5120, 1536, jnp.float32) == 128
    assert kernels.grouped_tile(5120, 1536, jnp.bfloat16, 8 << 20) == 128
    assert kernels.grouped_tile(5120, 1536, jnp.bfloat16, 1 << 20) == 0


def test_the_span_it_is_traced_under_says_which_path():
    """``grouped_experts`` chooses at trace time, so the choice is a
    note of the set-up span open around the trace (the filter's
    ``trace_lower``), once for each distinct call with its count."""
    p, x = _experts(jnp.float32), jnp.zeros((32, HIDDEN))
    small = {k: v[:, :64, :64] for k, v in p.items()}
    plan = moe.dispatch(_routes("seeded", 32), 32, 0, HELD, ROUTED)
    profile.clear()
    with profile.span("el_net", "trace_lower", setup=True):
        for _ in range(2):
            jax.make_jaxpr(lambda p, x: moe.grouped_experts(p, x, plan))(p, x)
        jax.make_jaxpr(lambda p, x: moe.grouped_experts(p, x, plan))(
            small, x[:, :64])
    moe.grouped_experts(p, x, plan)          # no span open: says nothing
    note = [s.note for s in profile.spans()
            if s.name == "el_net/trace_lower"][-1]
    assert "grouped_experts 32 rows x 6 blocks, (4, 128, 256) float32: " \
           "the kernel, tiles of 256 (x2)" in note
    assert "(4, 64, 64) float32: the loop (widths 64 and 64 are not whole " \
           "lanes of 128)" in note


def _toy(name, **changed):
    with open(os.path.join(REPO, "tests", "benchmark", "data", name)) as f:
        return {**json.load(f), **changed}


def _decode_programs():
    """The three models' decode steps at toy sizes with experts of whole
    lanes, 16 streams in bf16: ``(name, jaxpr, expert layers)``."""
    cfg = dsv2.DeepSeekV2Config.from_dict(_toy(
        "toy_dsv2.json", hidden_size=128, moe_intermediate_size=128))
    params = jax.eval_shape(lambda: dsv2.init_params(cfg, 0))
    state = jax.eval_shape(lambda: dsv2.init_state(cfg, params, 16, 128))
    ids = jax.ShapeDtypeStruct((16,), jnp.int32)
    yield "dsv2", jax.make_jaxpr(
        lambda p, s, i, at: dsv2.decode(cfg, p, s, i, at))(
            params, state, ids, ids), [1, 2]
    cfg = st.SmallThinkerConfig.from_dict(_toy(
        "toy_smallthinker.json", hidden_size=128, moe_ffn_hidden_size=128))
    params = jax.eval_shape(lambda: st.init_params(cfg, 0))
    state = jax.eval_shape(lambda: st.init_state(cfg, params, 16, 32, 8))
    yield "smallthinker", jax.make_jaxpr(
        lambda p, s, i, at: st.decode(cfg, p, s, i, at))(
            params, state, ids, ids), list(range(cfg.layers))
    cfg = nh.NemotronHConfig.from_dict(_toy(
        "toy_nemotron3.json", hidden_size=128, moe_intermediate_size=128))
    params = jax.eval_shape(lambda: nh.init_params(cfg, 0))
    state = jax.eval_shape(lambda: nh.init_state(cfg, params, 16, 32))
    yield "nemotron_h", jax.make_jaxpr(
        lambda p, s, i, at: nh.decode(cfg, p, s, i, at))(
            params, state, ids, ids), [1, 3, 6]


def _calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls(sub, found)
    return found


@pytest.mark.parametrize("reader", ["program", "benchmark"])
def test_the_kernels_time_is_booked_to_the_experts_stage(reader):
    """The call sits straight in the ``experts`` scope of its layer in
    three models' decode programs, so the stage reader books its device
    time to ``nns.model/layerNN/moe/experts``, which the cells' stage
    metrics (``experts_ms_per_window``, ``relu_experts_ms_per_window``,
    ``relu2_experts_ms_per_window``) sum."""
    if reader == "program":
        stage_of = profile.stage_of
    else:
        from benchmark.stages import stage_of
    for name, program, layers in _decode_programs():
        stages = [stage_of(f"jit(f)/nns.model/{eqn.source_info.name_stack}/"
                           f"{eqn.primitive.name}")
                  for eqn in _calls(program.jaxpr, [])]
        experts = [s for s in stages if s.endswith("/moe/experts")]
        assert experts == [f"nns.model/layer{i:02d}/moe/experts"
                           for i in layers], (name, stages)
        # the attention kernels keep a stage of their own, and nothing
        # else is a kernel
        assert len(stages) - len(experts) == len(
            [s for s in stages if s.endswith("decode_attention")])


def dispatch_by_sort(idx, n_tokens: int, expert0: int, held: int):
    """``moe.dispatch`` as it was until PR 45, the oracle of the plan: a
    stable sort of the pairs by expert, a scatter-add of the counts, and
    two scatters of one element a pair (``row_token``, and the sort's
    inverse for ``dest``)."""
    k = idx.shape[1]
    blk = moe.block_rows(n_tokens)
    pairs = n_tokens * k
    rows = -(-pairs // blk) * blk + held * blk
    local = idx.reshape(-1) - expert0
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)
    sorted_e = local[order]
    counts = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)
    padded = (counts[:held] + blk - 1) // blk * blk
    pad_end = jnp.cumsum(padded)
    first = jnp.cumsum(counts) - counts           # of each expert, sorted
    rank = jnp.arange(pairs, dtype=jnp.int32) - first[sorted_e]
    here = sorted_e < held
    dest_sorted = jnp.where(
        here, (pad_end - padded)[jnp.minimum(sorted_e, held - 1)] + rank,
        rows)
    row_token = jnp.full((rows,), n_tokens, jnp.int32).at[dest_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    dest = jnp.zeros((pairs,), jnp.int32).at[order].set(dest_sorted)
    block_expert = jnp.minimum(jnp.searchsorted(
        pad_end, jnp.arange(rows // blk, dtype=jnp.int32) * blk,
        side="right"), held - 1).astype(jnp.int32)
    return {"row_token": row_token, "dest": dest.reshape(n_tokens, k),
            "block_expert": block_expert, "blocks": pad_end[-1] // blk,
            "counts": counts[:held], "blk": blk, "rows": rows}


#: ``(tokens, k, expert0, held, router width)`` of a decode step's plan in
#: the five token cells (``benchmark/configs``: the experts a chip holds
#: of those the router scores).
DECODE_PLANS = {
    "dsv2.decode16k": (32, 6, 0, 40, 160),
    "smallthinker.decode16k": (32, 6, 0, 64, 64),
    "nemotron3.decode4k": (128, 6, 0, 16, 128),
    "kexaone.decode16k": (32, 8, 0, 16, 128),
    "longcat.decode4k": (128, 12, 0, 8, 768),
}
PLANS = dict(
    DECODE_PLANS,
    **{"a-prefill-chunk": (2048, 12, 0, 8, 768),
       "a-prefill-chunk-of-40-held": (2048, 6, 0, 40, 160),
       "one-token": (1, 6, 0, 40, 160),
       # experts [40, 80) of 160: pairs below the first held one too
       "expert0-above-0": (32, 6, 40, 40, 160),
       "the-last-experts-held": (128, 6, 112, 16, 128),
       # every pick is on an expert the router has and no chip holds
       "picks-beyond-the-real-experts": (128, 12, 0, 8, 768),
       "every-pair-on-one-expert": (128, 6, 0, 16, 128),
       "every-token-twice-on-one-expert": (32, 6, 8, 16, 128),
       "no-pair-held": (32, 8, 0, 16, 128),
       "one-expert-held": (32, 6, 3, 1, 64)})


def _picks(case):
    """``idx [tokens, k]`` of a plan in ``PLANS``: seeded picks over the
    router's width, bent to what the case's name says."""
    n, k, expert0, held, width = PLANS[case]
    idx = np.random.default_rng(45).integers(0, width, (n, k))
    if case == "picks-beyond-the-real-experts":
        idx = 512 + idx % 256
        idx[1, :3] = [0, 7, 8]
    elif case == "every-pair-on-one-expert":
        idx[:] = expert0 + 5
    elif case == "every-token-twice-on-one-expert":
        idx[:, :2] = expert0 + 2
    elif case == "no-pair-held":
        idx = expert0 + held + idx % (width - held)
    return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("case,form", [
    (case, form) for case, (n, k, *_) in PLANS.items()
    for form in ("by-the-shapes", "compare", "scatter")
    # rows x pairs of a prefill chunk (650 M cells) is the scatter's
    # shape, and no test's
    if not (form == "compare" and n * k > 4096)])
def test_the_plan_is_the_sorted_plan_key_for_key(case, form, monkeypatch):
    """Counts from a one-hot and ranks from its running sums give the
    plan the stable sort gave, value for value, in either form of
    ``row_token`` (the shapes choose one; both are held here wherever
    the compare's ``rows x pairs`` cells fit a test)."""
    n, k, expert0, held, width = PLANS[case]
    if form == "compare":
        monkeypatch.setattr(moe, "ROW_TOKEN_COMPARE_CELLS", 1 << 62)
    elif form == "scatter":
        monkeypatch.setattr(moe, "ROW_TOKEN_COMPARE_CELLS", 0)
    idx = _picks(case)
    want = dispatch_by_sort(idx, n, expert0, held)
    got = moe.dispatch(idx, n, expert0, held, width)
    # the share of picks held elsewhere is the shapes': the sort knew
    # no width
    assert got.pop("elsewhere") == (width - held) / width
    assert set(got) == set(want)
    for key in ("blk", "rows"):
        assert got[key] == want[key], key
    for key in ("row_token", "dest", "block_expert", "blocks", "counts"):
        assert got[key].dtype == want[key].dtype, key
        assert np.array_equal(np.asarray(got[key]), np.asarray(want[key])), key
    if case == "no-pair-held":
        assert int(got["blocks"]) == 0
        assert (np.asarray(got["row_token"]) == n).all()
    if case == "every-pair-on-one-expert":
        assert int(got["counts"][5]) == n * k


def _plan_operations(case, dispatch=moe.dispatch):
    """``{operation: count}`` of the sorts, scatters and gathers in the
    lowered plan of ``case``: operations, not the words (a scatter's own
    attributes say ``indices_are_sorted``)."""
    n, k, expert0, held, width = PLANS[case]
    sizes = (n, expert0, held) + ((width,) if dispatch is moe.dispatch else ())
    text = jax.jit(dispatch, static_argnums=tuple(range(1, 1 + len(sizes)))
                   ).lower(_picks(case), *sizes).as_text()
    return {op: len(re.findall(rf'= "?stablehlo\.{op}"?[ (]', text))
            for op in ("sort", "scatter", "gather")}


@pytest.mark.parametrize("case", list(DECODE_PLANS))
def test_a_decode_steps_plan_holds_no_sort_and_no_chain_of_scatters(case):
    ops = _plan_operations(case)
    assert ops["sort"] == 0 and ops["scatter"] <= 1, ops


def test_the_static_shapes_alone_choose_how_row_token_is_made():
    """A decode step's ``rows x pairs`` is compared and reduced; a
    prefill chunk's (650 M cells) takes the plan's one scatter."""
    for case in DECODE_PLANS:
        assert _plan_operations(case)["scatter"] == 0, case
    for case in ("a-prefill-chunk", "a-prefill-chunk-of-40-held"):
        assert _plan_operations(case) == {"sort": 0, "scatter": 1,
                                          "gather": 0}, case


def test_the_oracle_of_the_plan_is_counted_as_the_chain_it_is():
    """The count reads operations where they are (a count of 0 above is
    not a pattern that matches nothing): the sorted plan's one sort,
    three scatters and the gathers through the sorted order."""
    ops = _plan_operations("longcat.decode4k", dispatch_by_sort)
    assert ops["sort"] == 1 and ops["scatter"] == 3 and ops["gather"] >= 3
