"""LongCat-Flash as a stateful model (``nnstreamer_tpu/models/longcat_flash.py``)
at toy sizes on the CPU: prefill and decode through both latent caches of
every layer against the benchmark's plain full forward (logits, float32
and bfloat16), the shares of the expert branch adding up to the uncut
layer with what every chip computes alike counted once, the softmax
router over real and zero-compute experts against a plain top-k, the
zero-compute picks' term, the picks ``dispatch`` drops, and the faults
the comparison has to see.  No number here is a rate."""

import dataclasses
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

from benchmark.run import Loader  # noqa: E402
from nnstreamer_tpu.models import longcat_flash as lc  # noqa: E402
from nnstreamer_tpu.models import mla, moe  # noqa: E402
from nnstreamer_tpu.ops import kernels  # noqa: E402

SEED = 11
CHUNK, POSITIONS = 8, 48
LENGTHS = (13, 24, 9)          # a padded last chunk, whole chunks, one real id
STEPS = 6
#: float32 program against float32 reference: rounding of sums taken in
#: another order (the absorbed form, the blocked softmax, the sorted
#: expert product), nothing else; bfloat16 reads a hundred times this
F32_TOL = 3e-5
#: bfloat16 program against the float32 reference at hidden 64 (the toy
#: cell reads 0.002-0.007 a frame)
BF16_TOL = 0.03


@pytest.fixture(scope="module")
def toy():
    """The toy twin of the benchmark's configuration: hidden 64, two
    layers of two sub-blocks, 4 heads of 16 + 8 over a latent row of 24
    stored 128 wide, a router 24 wide (16 real experts, 8 zero-compute,
    4 picks), experts 4-7 held at width 32, vocabulary rows 64-127."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_longcat.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def files():
    loader = Loader(REPO)
    return {kind: loader.module(kind, "longcat_flash_omni_share64")
            for kind in ("weights", "reference", "costs")}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _model(raw, params):
    cfg = lc.LongCatFlashConfig.from_dict(raw)
    return {"cfg": cfg, "params": params,
            "prefill": jax.jit(lambda p, s, *x: lc.prefill(cfg, p, s, *x)),
            "decode": jax.jit(lambda p, s, *x: lc.decode(cfg, p, s, *x))}


def _ids(cfg, shape, seed):
    return np.random.default_rng(seed).integers(
        cfg.vocab0, cfg.vocab0 + cfg.vocab, shape).astype(np.int32)


def _prefilled(model, ids, lengths=LENGTHS):
    cfg = model["cfg"]
    state = lc.init_state(cfg, model["params"], len(lengths), POSITIONS)
    for row, n in enumerate(lengths):
        for at in range(0, n, CHUNK):
            part = np.full(CHUNK, cfg.vocab0, np.int32)
            real = min(CHUNK, n - at)
            part[:real] = ids[row, at:at + real]
            state, _ = model["prefill"](
                model["params"], state, part, np.array([row], np.int32),
                np.array([at], np.int32))
    return state


def _answer(model, state, ids, lengths=LENGTHS, steps=STEPS):
    """``steps`` decode steps on top of the prompts: logits ``[steps,
    streams, vocab]``."""
    out = []
    for j in range(steps):
        at = [n + j for n in lengths]
        state, (logits, greedy) = model["decode"](
            model["params"], state,
            np.array([ids[r, p] for r, p in enumerate(at)]),
            np.array(at, np.int32))
        assert np.array_equal(np.asarray(greedy), np.asarray(logits)
                              .argmax(-1) + model["cfg"].vocab0)
        out.append(np.asarray(logits, np.float32))
    return state, np.stack(out)


def _reference(files, raw, ids, faults=(), lengths=LENGTHS, steps=STEPS):
    """The plain full forward over every (step, stream)'s whole history:
    ``[steps, streams, vocab]``."""
    histories = [ids[r, :n + j + 1] for j in range(steps)
                 for r, n in enumerate(lengths)]
    out = files["reference"].forward_last(raw, SEED, histories,
                                          faults=faults)
    return out.reshape(steps, len(lengths), -1)


def _rel(got, ref):
    return float(np.abs(got - ref).max() / max(1.0, np.abs(ref).max()))


@pytest.fixture(scope="module")
def sound(toy, files):
    """Three streams prefilled in chunks of 8, then two passes of six
    decode steps with a rewind to each prompt's end between them, in
    float32, beside the reference."""
    model = _model(toy, _f32(files["weights"].make(toy, SEED)))
    ids = _ids(model["cfg"], (3, max(LENGTHS) + STEPS + 1), 5)
    state = _prefilled(model, ids)
    prefilled = jax.tree_util.tree_map(np.asarray, state["cache"])
    state, first = _answer(model, state, ids)
    once = jax.device_get(state["counters"])
    state, second = _answer(model, state, ids)
    return {"model": model, "ids": ids, "first": first, "second": second,
            "prefilled": prefilled, "once": once,
            "twice": jax.device_get(state["counters"]),
            "ref": _reference(files, toy, ids)}


# -- the program against the reference ------------------------------------------------


def test_prefill_then_decode_agrees_with_the_full_forward(sound):
    assert _rel(sound["first"], sound["ref"]) < F32_TOL
    # a pass of the ring rewinds to the prompts' ends: the same answer
    assert _rel(sound["second"], sound["ref"]) < F32_TOL


def test_both_caches_of_every_layer_are_written_and_differ(sound, toy):
    cfg = sound["model"]["cfg"]
    caches = sound["prefilled"]
    assert len(caches) == cfg.layers == 2
    assert all(len(pair) == lc.SUBS for pair in caches)
    for pair in caches:
        for cache in pair:
            assert cache.shape == (3, 128, cfg.row) and cfg.row == 128
            for row, n in enumerate(LENGTHS):
                assert np.abs(cache[row, :n, :cfg.latent]).min(-1).max() > 0
                assert not cache[row, :, cfg.latent:].any()
        assert not np.allclose(pair[0][0, :9], pair[1][0, :9])


#: the published ``kv_lora_rank`` and ``qk_rope_head_dim`` on the toy's
#: few heads and layers: a cache row packs two positions there
PUBLISHED_LATENT = {"kv_lora_rank": 512, "qk_rope_head_dim": 64}


@pytest.fixture(scope="module")
def packed(toy, files):
    """The same three streams at the published latent sizes: prefilled
    in chunks of 8 to 13, 24 and 9 tokens, then six decode steps from an
    odd position across a chunk's start (13 .. 18), from an even one
    that starts a chunk (24 .. 29) and from 9, in float32."""
    raw = dict(toy, **PUBLISHED_LATENT)
    model = _model(raw, _f32(files["weights"].make(raw, SEED)))
    ids = _ids(model["cfg"], (3, max(LENGTHS) + STEPS + 1), 5)
    state = _prefilled(model, ids)
    prefilled = jax.tree_util.tree_map(np.asarray, state["cache"])
    state, answer = _answer(model, state, ids)
    return {"model": model, "ids": ids, "answer": answer,
            "prefilled": prefilled, "state": jax.device_get(state),
            "ref": _reference(files, raw, ids)}


def test_prefill_then_decode_agrees_at_the_published_latent_sizes(packed):
    assert _rel(packed["answer"], packed["ref"]) < F32_TOL


def test_a_packed_cache_holds_two_positions_a_row_and_nothing_else(packed):
    cfg = packed["model"]["cfg"]
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    assert (cfg.latent, cfg.row) == (576, 576)
    for before, after in zip(packed["prefilled"], packed["state"]["cache"]):
        for was, cache in zip(before, after):
            # streams x positions x 576 values: a lattice cell of 128
            # positions is 64 rows
            assert cache.shape == (3, 64, 1152)
            assert cache.nbytes == 3 * 128 * 576 * 4
            rows = np.asarray(kernels.latent_unpack(cache, rank, rope))
            for row, n in enumerate(LENGTHS):
                assert np.abs(rows[row, :n + STEPS]).min(-1).max() > 0
                # a decode step's token went into its own half of a row:
                # what the chunks wrote is what it was
                assert np.array_equal(
                    np.asarray(kernels.latent_unpack(
                        was, rank, rope))[row, :n], rows[row, :n])
    # a chunk padded beyond its prompt wrote to its end, no further
    rows = np.asarray(kernels.latent_unpack(
        packed["state"]["cache"][0][0], rank, rope))
    assert not rows[:, 32:].any()
    counters = packed["state"]["counters"]
    assert counters["cache_rows_read"] == sum(
        n + j + 1 for n in LENGTHS for j in range(STEPS))
    assert counters["cache_rows_fetched"] == STEPS * 3 * 128
    units = lc.counter_units(cfg, packed["state"])
    caches = cfg.layers * lc.SUBS
    assert units["cache_bytes_read"] == ("cache_rows_read",
                                         cfg.latent * 4 * caches)
    assert units["cache_bytes_fetched"] == ("cache_rows_fetched",
                                            cfg.latent * 4 * caches)


def test_bfloat16_in_place_of_float32_fails_the_float32_comparison(
        sound, toy, files):
    model = _model(toy, files["weights"].make(toy, SEED))
    assert model["params"]["layers"][0]["mlp"][0]["gate"].dtype \
        == jnp.bfloat16
    state = _prefilled(model, sound["ids"])
    assert state["cache"][0][0].dtype == jnp.bfloat16
    _, got = _answer(model, state, sound["ids"])
    assert _rel(got, sound["ref"]) > 30 * F32_TOL
    each = np.linalg.norm(got - sound["ref"], axis=-1) \
        / np.linalg.norm(sound["ref"], axis=-1)
    assert each.max() < BF16_TOL


def test_the_counters_say_how_a_steps_picks_divide(sound):
    cfg = sound["model"]["cfg"]
    once, twice = sound["once"], sound["twice"]
    assert set(once) == set(lc.COUNTERS)
    assert once["steps"] == STEPS and twice["steps"] == 2 * STEPS
    assert once["cache_rows_read"] == sum(
        n + j + 1 for n in LENGTHS for j in range(STEPS))
    assert once["cache_rows_fetched"] == STEPS * 3 * 128
    # a step's picks are a constant: tokens x picks a token x layers
    picks = STEPS * 3 * cfg.moe_topk * cfg.layers
    assert 0 < once["zero_picks"] < picks
    assert 0 < once["expert_hits"] <= picks - once["zero_picks"]
    assert 0 < once["experts_touched"] <= STEPS * cfg.layers * cfg.experts
    for name in lc.COUNTERS:
        assert twice[name] == 2 * once[name], name
    units = lc.counter_units(cfg, {"cache": [[np.zeros((1, 1, 1),
                                                       np.float32)]]})
    assert units["cache_bytes_read"] == (
        "cache_rows_read", cfg.latent * 4 * cfg.layers * lc.SUBS)
    assert units["cache_bytes_fetched"] == (
        "cache_rows_fetched", cfg.row * 4 * cfg.layers * lc.SUBS)


# -- the faults the comparison has to see ---------------------------------------------


def test_swapping_a_layers_two_caches_fails(sound):
    model = sound["model"]
    state = _prefilled(model, sound["ids"])
    pair = state["cache"][1]
    state["cache"][1] = [pair[1], pair[0]]
    _, got = _answer(model, state, sound["ids"], steps=2)
    assert _rel(got, sound["ref"][:2]) > 100 * F32_TOL


def test_leaving_out_the_kv_scale_fails(sound, toy):
    raw = dict(toy, mla_scale_kv_lora=False)
    model = _model(raw, sound["model"]["params"])
    assert model["cfg"].kv_lora_scale == 1.0
    assert sound["model"]["cfg"].kv_lora_scale == pytest.approx(2.0)
    assert sound["model"]["cfg"].q_lora_scale == pytest.approx(2 ** 0.5)
    _, got = _answer(model, _prefilled(model, sound["ids"]), sound["ids"],
                     steps=2)
    assert _rel(got, sound["ref"][:2]) > 100 * F32_TOL


@pytest.mark.parametrize("fault", ["early_rejoin", "one_cache",
                                   "no_zero_term", "no_held_experts",
                                   "no_kv_scale"])
def test_a_reference_with_a_part_done_wrongly_is_told_apart(sound, toy,
                                                            files, fault):
    """The branch added after the first sub-block instead of at the
    layer's end (and the other faults the reference can be given): the
    program's logits then lie outside the tolerance."""
    wrong = _reference(files, toy, sound["ids"], faults=(fault,), steps=2)
    assert _rel(sound["first"][:2], wrong) > 100 * F32_TOL


@pytest.mark.parametrize("fault,low,high", [
    ("sound", 0.0, 0.05), ("dropped_in_decode", 0.5, 1.5),
    ("summed_twice", 0.5, 1.5)])
def test_the_held_experts_part_is_read_along_its_own_direction(
        sound, toy, files, monkeypatch, fault, low, high):
    """``held_experts_part_off`` of the benchmark's comparison, on the
    bfloat16 program: near 0 where the program is sound, near 1 where
    the DECODE program alone drops the held experts' part (prefill still
    sound: the histories' part is there, the frames' own is not) and
    where every program adds it twice.
    The cell's check rests on this number at a size where no distance
    sees the part (``PERF.md`` section 2)."""
    reference = files["reference"]
    ref0 = _reference(files, toy, sound["ids"], faults=("no_held_experts",))
    model = _model(toy, files["weights"].make(toy, SEED))
    state = _prefilled(model, sound["ids"])
    real = moe.combine
    if fault == "dropped_in_decode":
        monkeypatch.setattr(moe, "combine", lambda out, plan, weight:
                            0.0 * real(out, plan, weight))
    elif fault == "summed_twice":
        monkeypatch.setattr(moe, "combine", lambda out, plan, weight:
                            2.0 * real(out, plan, weight))
    _, got = _answer(model, state, sound["ids"])
    vocab = got.shape[-1]
    numbers = reference.compare_numbers(
        toy, sound["ref"].reshape(-1, vocab),
        {"logits": got.reshape(-1, vocab)}, ref0.reshape(-1, vocab))
    assert low <= numbers["held_experts_part_off"] <= high, numbers
    if fault == "sound":
        # the same served logits against a reference given without the
        # second forward: the number is left out, the others unchanged
        plain = reference.compare_numbers(
            toy, sound["ref"].reshape(-1, vocab),
            {"logits": got.reshape(-1, vocab)})
        assert "held_experts_part_off" not in plain
        assert plain["logits_rel_l2_worst"] == numbers["logits_rel_l2_worst"]


def test_the_two_paths_of_a_dense_mlp_agree(toy, files):
    """``dense_mlp`` forks by the row count: a decode step's rows through
    the grouped product as one group of one expert, a prefill chunk's
    through three plain products.  The same rows both ways, float32: a
    rounding apart, so the fork cannot drift."""
    params = _f32(files["weights"].make(toy, SEED))["layers"][0]["mlp"][0]
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (264, toy["hidden_size"])), jnp.float32)
    assert lc.dense_mlp_grouped(256) and not lc.dense_mlp_grouped(264)
    whole = np.asarray(lc.dense_mlp(params, x))             # plain products
    parts = np.concatenate([np.asarray(lc.dense_mlp(params, x[:256])),
                            np.asarray(lc.dense_mlp(params, x[256:]))])
    assert _rel(parts, whole) < F32_TOL
    plan = moe.one_group_plan(8)
    assert (plan["blk"], plan["rows"], int(plan["blocks"])) == (8, 8, 1)
    assert np.array_equal(plan["row_token"], np.arange(8))
    assert np.array_equal(plan["block_expert"], [0])


# -- the shares add up ----------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(toy, files):
    """One layer at toy size: the held experts' parts of ALL four shares
    of four experts, with both attentions, both MLPs and the
    zero-compute picks' term counted ONCE, equal what the reference
    gives for the layer with all 16 experts held."""
    uncut = dict(toy, n_routed_experts=16, share={"expert0": 0,
                                                  "vocab0": 64})
    p = _f32(files["weights"].make_part(uncut, SEED, "layer01"))
    rows = 256                                   # a whole query block
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, 64), jnp.float32)
    reference = files["reference"]
    keep = {k: v for k, v in uncut.items()
            if isinstance(v, (int, float, bool, dict)) and k != "limits"}
    fns = reference._built(json.dumps(keep, sort_keys=True), False, True)
    want = np.asarray(reference._layer(fns, uncut, p, x, rows, False,
                                       frozenset()))

    whole = lc.LongCatFlashConfig.from_dict(uncut)
    eps = whole.rms_norm_eps

    def attend(sub, h):
        cache = mla.init_cache(whole, 1, rows, jnp.float32)
        out, _ = mla.attn_prefill(whole, p["attn"][sub], moe.rms(
            h, p["attn_norm"][sub], eps), cache, jnp.int32(0), jnp.int32(0))
        return h + out

    a0 = attend(0, x)
    u = moe.rms(a0, p["mlp_norm"][0], eps)
    branch, hits, zeros = 0.0, 0, set()
    for e0 in range(0, 16, 4):
        share = dataclasses.replace(whole, experts=4, expert0=e0)
        part = dict(p["moe"], experts={
            k: w[e0:e0 + 4] for k, w in p["moe"]["experts"].items()})
        routed, zero, counts, zero_picks = lc.moe_parts(share, part, u)
        branch = branch + routed                 # every share's own part
        hits += int(counts.sum())
        zeros.add(int(zero_picks))
    branch = branch + zero                       # what all compute alike: once
    assert len(zeros) == 1
    assert hits + zeros.pop() == rows * whole.moe_topk
    b0 = a0 + lc.dense_mlp(p["mlp"][0], u)
    a1 = attend(1, b0)
    v = moe.rms(a1, p["mlp_norm"][1], eps)
    got = a1 + lc.dense_mlp(p["mlp"][1], v) + branch
    assert np.abs(np.asarray(got) - want).max() \
        < F32_TOL * max(1.0, np.abs(want).max())
    # and one share alone is NOT the layer
    assert np.abs(np.asarray(got - branch + routed + zero) - want).max() \
        > 100 * F32_TOL


# -- the router -----------------------------------------------------------------------


def _route(logits, bias, top_k=4, scaling=6.0):
    """``route_softmax`` on hand-made logits: one-hot inputs pick the
    router's rows out."""
    n, width = logits.shape
    return moe.route_softmax(jnp.eye(n, dtype=jnp.float32),
                             jnp.asarray(logits, jnp.float32),
                             jnp.asarray(bias, jnp.float32), top_k, scaling)


def _plain_route(logits, bias, top_k=4, scaling=6.0):
    logits = np.asarray(logits, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    idx = np.argsort(-(p + bias), axis=-1, kind="stable")[:, :top_k]
    return idx, scaling * np.take_along_axis(p, idx, axis=-1)


def test_route_against_a_plain_top_k():
    rng = np.random.default_rng(0)
    logits = rng.normal(0, 2, (6, 24))
    logits[1, [3, 9, 17, 20, 22]] = 5.0         # five tied for four places
    logits[2, :] = 0.0                          # all tied: the first four
    bias = np.zeros(24)
    idx, weight = _route(logits, bias)
    want_idx, want_weight = _plain_route(logits, bias)
    assert np.array_equal(np.asarray(idx), want_idx)
    assert np.allclose(np.asarray(weight), want_weight, rtol=1e-5)
    assert np.asarray(idx)[1].tolist() == [3, 9, 17, 20]
    assert np.asarray(idx)[2].tolist() == [0, 1, 2, 3]
    # no renormalisation: the weights are 6 p, whatever they add up to
    sums = np.asarray(weight).sum(-1)
    assert not np.allclose(sums, 6.0) and not np.allclose(sums, 1.0)
    assert np.asarray(weight)[2] == pytest.approx(np.full(4, 6 / 24))
    # a bias changes the choice and not the weight
    bias = np.zeros(24)
    loser = int(np.argsort(-logits[0])[7])
    bias[loser] = 1.0
    b_idx, b_weight = _route(logits, bias)
    want_idx, want_weight = _plain_route(logits, bias)
    assert np.array_equal(np.asarray(b_idx), want_idx)
    assert loser in np.asarray(b_idx)[0] and loser not in np.asarray(idx)[0]
    at = np.asarray(b_idx)[0].tolist().index(loser)
    p = np.exp(logits[0] - logits[0].max())
    assert np.asarray(b_weight)[0, at] == pytest.approx(
        6 * p[loser] / p.sum(), rel=1e-5)


def test_a_token_on_zero_compute_experts_alone_touches_no_expert(toy, files):
    """Every pick of the token on a zero-compute expert: the branch adds
    ``6 (sum of their p) u`` and no held expert gets a row."""
    raw = dict(toy, share={"expert0": 0, "vocab0": 64},
               n_routed_experts=16)
    cfg = lc.LongCatFlashConfig.from_dict(raw)
    p = _f32(files["weights"].make_part(raw, SEED, "layer00"))["moe"]
    n = cfg.hidden_size
    logits = np.full((n, cfg.router_width), -4.0, np.float32)
    logits[:, [17, 19, 20, 23]] = 3.0            # rows pick zero experts
    logits[1, [2, 5, 17, 20]] = 4.0              # but row 1: two real ones
    u = jnp.eye(n, dtype=jnp.float32)
    part = dict(p, router=jnp.asarray(logits))
    routed, zero, counts, zero_picks = lc.moe_parts(cfg, part, u)
    prob = np.exp(logits[0]) / np.exp(logits[0]).sum()
    assert np.asarray(zero)[0] == pytest.approx(
        6 * 4 * prob[17] * np.asarray(u)[0], rel=1e-5)
    assert not np.asarray(routed)[0].any()
    assert np.asarray(routed)[1].any()
    assert int(counts.sum()) == 2 and int(counts[2]) == int(counts[5]) == 1
    assert int(zero_picks) == 4 * n - 2


def test_dispatch_drops_every_pick_beyond_the_real_experts():
    """At the published router: picks on zero-compute experts (512-767)
    and on real experts held elsewhere fall out of the grouped product;
    only those on experts 0-7 get a row."""
    rng = np.random.default_rng(1)
    idx = np.stack([rng.permutation(768)[:12] for _ in range(128)])
    idx[0] = np.arange(512, 524)                 # a token of zero picks
    idx[1, :3] = [0, 7, 8]
    plan = moe.dispatch(jnp.asarray(idx, jnp.int32), 128, 0, 8, 768)
    here = idx < 8
    assert int(plan["counts"].sum()) == here.sum()
    assert np.array_equal(np.asarray(plan["counts"]),
                          [(idx == e).sum() for e in range(8)])
    dest = np.asarray(plan["dest"])
    assert (dest[~here] == plan["rows"]).all()   # the zero row: dropped
    assert (dest[here] < plan["rows"]).all()
    assert len(set(dest[here].tolist())) == here.sum()
    tokens = np.asarray(plan["row_token"])
    assert sorted(tokens[dest[here]].tolist()) \
        == sorted(np.nonzero(here)[0].tolist())
    assert (dest[0] == plan["rows"]).all()
    w = moe.zero_weight(jnp.asarray(idx), jnp.ones(idx.shape), 512)
    assert np.array_equal(np.asarray(w), (idx >= 512).sum(-1))
