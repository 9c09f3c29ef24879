"""EXAONE-MoE as a stateful model (``nnstreamer_tpu/models/exaone_moe.py``)
at toy sizes on the CPU: prefill and decode through rings shorter than a
chunk, full caches and the prediction module's cache against the
benchmark's plain full forward for BOTH logits tensors, padded last
chunks shorter and longer than the ring, the rewind a ring takes and
the one it cannot, the module's cache row at the prompt's end, the
shares of the expert layer, the kernels where they take the shapes, and
two launch lines on one state.  No number here is a rate."""

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
from nnstreamer_tpu.filters.api import SHARED_MODELS  # noqa: E402
from nnstreamer_tpu.filters.jax_xla import unregister_model  # noqa: E402
from nnstreamer_tpu.models import exaone_moe as ex  # noqa: E402
from nnstreamer_tpu.models import moe  # noqa: E402
from nnstreamer_tpu.models import nemotron_h as nh  # noqa: E402
from nnstreamer_tpu.ops import kernels  # noqa: E402
from nnstreamer_tpu.runtime import parse_launch  # noqa: E402
from nnstreamer_tpu.utils.stats import STATE_STATS  # noqa: E402

SEED = 11
CHUNK, POSITIONS, REWIND = 8, 48, 6
# a padded last chunk longer than the ring, whole chunks, a prompt
# shorter than the ring (12) whose last chunk holds one real id
LENGTHS = (13, 24, 9)
STEPS = 6


@pytest.fixture(scope="module")
def toy():
    """The toy twin of the benchmark's configuration: hidden 64, the
    five leading layers (window, window, window, full, window; layer 0
    dense) and the prediction module, a window of 4, 4 query heads over
    2 key/value heads of 16, experts 4-7 of 16 (3 a token) at width 32,
    vocabulary rows 32-63."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_kexaone.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def files():
    loader = Loader(REPO)
    return {kind: loader.module(kind, "kexaone_236b_share8")
            for kind in ("weights", "reference", "costs", "inputs")}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _model(toy, files):
    cfg = ex.ExaoneMoeConfig.from_dict(toy)
    return {"cfg": cfg, "params": _f32(files["weights"].make(toy, SEED)),
            "prefill": jax.jit(lambda p, s, *x: ex.prefill(cfg, p, s, *x)),
            "decode": jax.jit(lambda p, s, *x: ex.decode(cfg, p, s, *x))}


@pytest.fixture(scope="module")
def model(toy, files):
    return _model(toy, files)


def _prefill(model, state, row, ids, n, chunk=CHUNK):
    """The first ``n`` of ``ids`` of stream ``row`` from position 0 in
    chunks of ``chunk``, the last padded with the first held id and told
    its count; ``ids[n]`` is the id that follows the prompt."""
    out = None
    for at in range(0, n, chunk):
        real = min(chunk, n - at)
        part, follows = (np.full(chunk, model["cfg"].vocab0, np.int32)
                         for _ in range(2))
        part[:real] = ids[at:at + real]
        follows[:real] = ids[at + 1:at + 1 + real]
        state, out = model["prefill"](
            model["params"], state, part, follows, np.array([row], np.int32),
            np.array([at], np.int32), np.array([real], np.int32))
    return state, out


def _ids(cfg, shape, seed):
    return np.random.default_rng(seed).integers(
        cfg.vocab0, cfg.vocab0 + cfg.vocab, shape).astype(np.int32)


def _answer(model, state, ids, lengths=LENGTHS, steps=STEPS):
    """``steps`` decode steps on top of the prompts: ``(main, mtp)``,
    each ``[steps, streams, vocab]``."""
    out = []
    for j in range(steps):
        at = [n + j for n in lengths]
        state, (lg, lg_mtp, greedy, greedy_mtp) = model["decode"](
            model["params"], state,
            np.array([ids[r, p] for r, p in enumerate(at)]),
            np.array([ids[r, p + 1] for r, p in enumerate(at)]),
            np.array(at, np.int32))
        for served, logits in ((greedy, lg), (greedy_mtp, lg_mtp)):
            assert np.array_equal(np.asarray(served), np.asarray(logits)
                                  .argmax(-1) + model["cfg"].vocab0)
        out.append((np.asarray(lg), np.asarray(lg_mtp)))
    return state, tuple(np.stack(part) for part in zip(*out))


@pytest.fixture(scope="module")
def served(model):
    """Three streams: prompts of 13, 24 and 9 tokens prefilled in chunks
    of 8, then two passes of 6 decode steps with a rewind to each
    prompt's end between them, in float32."""
    cfg = model["cfg"]
    ids = _ids(cfg, (3, max(LENGTHS) + STEPS + 1), 5)
    state = ex.init_state(cfg, model["params"], 3, POSITIONS, REWIND)
    at_end = []
    for r, n in enumerate(LENGTHS):
        state, out = _prefill(model, state, r, ids[r], n)
        at_end.append([np.asarray(o) for o in out])
    prefilled = jax.tree_util.tree_map(np.asarray, state)
    state, first = _answer(model, state, ids)
    once = jax.device_get(state["counters"])
    state, second = _answer(model, state, ids)
    return {"first": first, "second": second, "ids": ids, "at_end": at_end,
            "prefilled": prefilled, "once": once,
            "twice": jax.device_get(state["counters"])}


def _close(got, ref, tol=3e-5):
    return np.abs(got - ref).max() <= tol * max(1.0, np.abs(ref).max())


def _reference(toy, files, ids, ends, **kw):
    """Both logits tensors after position ``ends[r] - 1`` of stream
    ``r``: the plain full forward over ``ids[r, :ends[r]]``."""
    return files["reference"].forward_last(
        toy, SEED, [ids[r, :n] for r, n in enumerate(ends)],
        [ids[r, 1:n + 1] for r, n in enumerate(ends)], **kw)


# -- prefill and decode against the plain forward ------------------------------------


def test_the_rings_are_shorter_than_a_chunk_and_than_most_prompts(model):
    cfg = model["cfg"]
    assert cfg.ring(REWIND) == 12 and cfg.window == 4
    state = ex.init_state(cfg, model["params"], 3, POSITIONS, REWIND)
    assert [c["k"].shape[2] for c in state["cache"]] == [12, 12, 12, 48, 12]
    assert state["mtp"]["k"].shape == (3, 2, 48, 16)
    # the cell's: a window of 128 and a rewind of 256 in cells of 128
    real = ex.ExaoneMoeConfig.from_dict(
        Loader(REPO).config("kexaone_236b_share8"))
    assert real.ring(256) == 384 <= 512 and real.ring(0) == 128
    assert real.ring(300) == 512
    with pytest.raises(ValueError, match="whole windows"):
        ex.entries(cfg, 3, POSITIONS, 6, REWIND)


def test_prefill_serves_the_reference_after_the_last_real_token(
        toy, files, served):
    ref = _reference(toy, files, served["ids"], LENGTHS)
    for r in range(3):
        logits, logits_mtp = served["at_end"][r][:2]
        assert _close(logits[0], ref[0][r]) and _close(logits_mtp[0],
                                                       ref[1][r])


@pytest.mark.parametrize("step", range(STEPS))
def test_prefill_then_decode_is_the_reference_at_every_position(
        toy, files, served, step):
    ref = _reference(toy, files, served["ids"],
                     [n + step + 1 for n in LENGTHS])
    for which in (0, 1):
        assert _close(served["first"][which][step], ref[which]), which
    # and the two tensors are not one: the module predicts another token
    assert not _close(served["first"][0][step], ref[1], tol=1e-2)


def test_a_rewind_the_ring_takes_answers_alike(toy, files, model, served):
    """A second pass from each prompt's end equals the first bit for
    bit: the rows of the answer before lie on slots outside the window.
    A module cache row lost shows in the module's logits alone."""
    for which in (0, 1):
        assert np.array_equal(served["first"][which],
                              served["second"][which])
    assert served["twice"]["position_faults"] == 0
    state = jax.tree_util.tree_map(jnp.asarray, served["prefilled"])
    state["mtp"] = {name: jnp.zeros_like(a)
                    for name, a in state["mtp"].items()}
    _, lost = _answer(model, state, served["ids"], steps=1)
    assert np.array_equal(lost[0], served["first"][0][:1])
    ref = _reference(toy, files, served["ids"], [n + 1 for n in LENGTHS])[1]
    rel = np.linalg.norm(lost[1][0] - ref, axis=-1) \
        / np.linalg.norm(ref, axis=-1)
    assert rel.min() > 0.05, rel


def test_a_rewind_the_ring_cannot_take_is_counted(model, served):
    """A ring of 12 positions with a window of 4 takes a rewind of
    eight (it was asked for six and holds whole windows); nine is a
    fault, and so is a position that is neither the one after the last
    nor the prompt's end."""
    ids = served["ids"]
    fed = [ids[:, 0], ids[:, 1]]

    def step(state, offsets):
        return model["decode"](
            model["params"], state, *fed,
            np.array([n + o for n, o in zip(LENGTHS, offsets)], np.int32))[0]

    def faults(state):
        return int(state["counters"]["position_faults"])

    state = jax.tree_util.tree_map(jnp.asarray, served["prefilled"])
    for j in range(9):
        state = step(state, [j] * 3)
    assert [int(v) for v in state["newest"]] == [n + 8 for n in LENGTHS]
    state = step(state, [0, 0, 0])                   # eight back: taken
    assert faults(state) == 0
    # stream 0 goes on, stream 1 skips a position, stream 2 rewinds anew
    state = step(state, [1, 2, 0])
    assert faults(state) == 1
    # stream 1 is lost until it is back at its prompt's end
    state = step(state, [2, 4, 1])
    assert faults(state) == 2
    state = step(state, [3, 0, 2])
    assert faults(state) == 2
    state = jax.tree_util.tree_map(jnp.asarray, served["prefilled"])
    for j in range(10):
        state = step(state, [j] * 3)
    assert faults(state) == 0
    state = step(state, [0, 0, 0])                   # nine back: not
    assert faults(state) == 3
    # and the rings stay spoilt however the stream moves on: the newest
    # row they were ever given is what counts
    state = step(state, [1, 1, 1])
    state = step(state, [0, 0, 0])
    assert faults(state) == 6


def test_the_modules_row_at_the_prompts_end_uses_the_first_answer_id(
        toy, files, model, served):
    """The module's cache row of the prompt's LAST position is made
    from the id that follows the prompt.  Prefilled with another id
    there, the first step's module logits move and the main ones do
    not."""
    cfg, ids = model["cfg"], served["ids"]
    other = ids.copy()
    for r, n in enumerate(LENGTHS):
        other[r, n] = cfg.vocab0 + (ids[r, n] - cfg.vocab0 + 1) % cfg.vocab
    state = ex.init_state(cfg, model["params"], 3, POSITIONS, REWIND)
    for r, n in enumerate(LENGTHS):
        state, _ = _prefill(model, state, r, other[r], n)
    _, got = _answer(model, state, ids, steps=1)
    assert np.array_equal(got[0], served["first"][0][:1])
    rel = np.linalg.norm(got[1] - served["first"][1][:1], axis=-1) \
        / np.linalg.norm(served["first"][1][:1], axis=-1)
    assert rel.min() > 1e-3, rel


@pytest.mark.parametrize("window,rewind,length,chunk", [
    (128, 256, 300, 128),       # the cell's ring: 384, three cells
    (128, 256, 470, 256),       # a chunk of two windows, padded
    (128, 0, 200, 128)])        # a ring of exactly the window
def test_a_rewind_of_256_onto_a_ring_of_384(toy, files, window, rewind,
                                            length, chunk):
    """The cell's window and ring at toy widths: a prompt past the ring,
    an answer of ``rewind`` tokens, a rewind to the prompt's end and the
    first steps again, all against the plain forward.  With no room for
    a rewind the ring is the window and the rewind is a fault."""
    wide = dict(toy, sliding_window=window, max_position_embeddings=1024)
    model = _model(wide, files)
    cfg = model["cfg"]
    assert cfg.ring(rewind) == window + rewind
    answer = max(rewind, 3)
    ids = _ids(cfg, (1, length + answer + 1), 9)
    state = ex.init_state(cfg, model["params"], 1, length + answer, rewind)
    state, out = _prefill(model, state, 0, ids[0], length, chunk)
    ref = _reference(wide, files, ids, [length])
    assert _close(np.asarray(out[0])[0], ref[0][0])
    assert _close(np.asarray(out[1])[0], ref[1][0])
    state, first = _answer(model, state, ids, (length,), answer)
    for step in (0, answer - 1):
        ref = _reference(wide, files, ids, [length + step + 1])
        assert _close(first[0][step], ref[0]) and _close(first[1][step],
                                                         ref[1])
    state, again = _answer(model, state, ids, (length,), 2)
    faults = int(state["counters"]["position_faults"])
    if rewind:
        assert faults == 0
        assert np.array_equal(again[0], first[0][:2])
        assert np.array_equal(again[1], first[1][:2])
    else:
        assert faults == 1
        assert not _close(again[0][0], first[0][0], tol=1e-3)


def test_full_forward_is_the_reference_with_a_window_of_its_own(toy, files):
    """What the check can see: the reference with a window one position
    short is another function."""
    ids = _ids(ex.ExaoneMoeConfig.from_dict(toy), (2, 21), 3)
    sound = _reference(toy, files, ids, [20, 17])
    short = _reference(dict(toy, sliding_window=3), files, ids, [20, 17])
    for which in (0, 1):
        rel = np.linalg.norm(short[which] - sound[which], axis=-1) \
            / np.linalg.norm(sound[which], axis=-1)
        assert rel.min() > 0.01, rel


def test_the_steps_count_what_they_read(model, served):
    cfg, once = model["cfg"], served["once"]
    rows = sum(n + j + 1 for n in LENGTHS for j in range(STEPS))
    assert once["steps"] == STEPS
    assert once["window_rows_read"] == STEPS * 3 * cfg.window
    assert once["full_rows_read"] == once["mtp_rows_read"] == rows
    # heads of 16 take the jnp mathematics, which reads the caches whole
    assert once["window_rows_fetched"] == STEPS * 3 * 12
    assert once["full_rows_fetched"] == STEPS * 3 * POSITIONS
    # four sparse layers and the module, three experts a token, a
    # quarter of 16 held
    assert 0 < once["expert_hits"] < STEPS * 3 * 5 * 3
    assert 0 < once["mtp_experts_touched"] < once["experts_touched"] \
        <= min(once["expert_hits"], STEPS * 5 * cfg.experts)
    assert once["position_faults"] == 0
    state = ex.init_state(cfg, model["params"], 3, POSITIONS, REWIND)
    units = ex.counter_units(cfg, state)
    row = 2 * 2 * 16 * 4
    assert units["window_bytes_read"] == ("window_rows_read", row * 4)
    assert units["full_bytes_read"] == ("full_rows_read", row * 1)
    assert units["mtp_bytes_read"] == ("mtp_rows_read", row)
    assert units["cache_bytes_read"] == [
        units["window_bytes_read"], units["full_bytes_read"],
        units["mtp_bytes_read"]]
    # the module's cache is fetched as a full layer's
    assert units["cache_bytes_fetched"] == [
        ("window_rows_fetched", row * 4), ("full_rows_fetched", row),
        ("full_rows_fetched", row)]


# -- the expert layer ----------------------------------------------------------------


def test_one_router_for_two_models(model):
    """``moe.route_sigmoid`` is what ``nemotron_h.route`` was, and what
    both models' expert layers call: the bias moves the choice and not
    the weights; the kept weights add up to the scaling factor."""
    cfg = model["cfg"]
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    u = jax.random.normal(keys[0], (5, cfg.hidden_size))
    router = jax.random.normal(keys[1], (cfg.hidden_size, 16)) * 0.2
    bias = 0.05 * jax.random.normal(keys[2], (16,))
    score = 1 / (1 + np.exp(-np.asarray(u, np.float64)
                            @ np.asarray(router, np.float64)))
    idx, weight = moe.route_sigmoid(u, router, bias, cfg.top_k,
                                    cfg.routed_scaling_factor)
    want = np.argsort(-(score + np.asarray(bias)), axis=-1)[:, :cfg.top_k]
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(want))
    kept = np.take_along_axis(score, np.asarray(idx), axis=-1)
    assert np.allclose(np.asarray(weight),
                       2.5 * kept / kept.sum(-1, keepdims=True), atol=1e-6)

    # neither model keeps a router of its own beside it
    assert not hasattr(nh, "route") and not hasattr(ex, "route")


def test_the_shares_add_up_to_the_uncut_layer(toy, files):
    """Four chips' routed parts (experts 0-3, 4-7, 8-11, 12-15 of 16)
    with the shared expert, the dense layer and attention counted ONCE
    are the uncut reference's layer: the plain forward with all 16
    experts held."""
    whole_toy = dict(toy, num_experts=16, share={"expert0": 0, "vocab0": 32})
    whole = ex.ExaoneMoeConfig.from_dict(whole_toy)
    layer = _f32(files["weights"].make_part(whole_toy, SEED, "layer01"))
    assert layer["moe"]["experts"]["up"].shape == (16, 64, 32)
    x = jax.random.normal(jax.random.PRNGKey(2), (12, 64))
    m = moe.rms(x, layer["ffn_norm"], whole.eps)
    routed, shared, counts = ex.moe_parts(whole, layer["moe"], m)
    assert int(counts.sum()) == 12 * whole.top_k
    parts = []
    for first in range(0, 16, 4):
        cfg = ex.ExaoneMoeConfig.from_dict(dict(
            toy, share={"expert0": first, "vocab0": 32}))
        held = dict(layer["moe"], experts={
            name: w[first:first + 4]
            for name, w in layer["moe"]["experts"].items()})
        part, again, got = ex.moe_parts(cfg, held, m)
        assert np.array_equal(np.asarray(again), np.asarray(shared))
        assert np.array_equal(np.asarray(got),
                              np.asarray(counts[first:first + 4]))
        parts.append(np.asarray(part))
    assert np.allclose(sum(parts), np.asarray(routed), atol=1e-5)
    assert not np.allclose(parts[0], np.asarray(routed), atol=1e-3)
    # the uncut reference's layer on the same rows: x + shared + all 16
    fns = files["reference"]._build(whole_toy, False)
    ref = files["reference"]._sparse(fns, whole_toy, layer["moe"],
                                     layer["ffn_norm"], x)
    mine = np.asarray(x) + np.asarray(shared) + sum(parts)
    assert np.allclose(mine, np.asarray(ref), atol=1e-5)
    # and the whole uncut model, attention and the dense layer in it: the
    # program holding all 16 is the reference holding all 16
    ids = _ids(whole, (1, 14), 6)
    model = _model(whole_toy, files)
    state = ex.init_state(whole, model["params"], 1, 16, REWIND)
    _, out = _prefill(model, state, 0, ids[0], 13)
    ref = _reference(whole_toy, files, ids, [13])
    assert _close(np.asarray(out[0])[0], ref[0][0])
    assert _close(np.asarray(out[1])[0], ref[1][0])


# -- the configuration ---------------------------------------------------------------


def test_the_configuration_is_read_as_published(toy):
    cfg = ex.ExaoneMoeConfig.from_dict(toy)
    assert cfg.layers == 5 and cfg.mtp
    assert cfg.window_layers == (True, True, True, False, True)
    assert cfg.dense_layers == (True, False, False, False, False)
    assert (cfg.n_routed_experts, cfg.experts, cfg.expert0) == (16, 4, 4)
    assert (cfg.vocab, cfg.vocab0, cfg.shared_width) == (32, 32, 32)
    assert (cfg.window, cfg.rope_theta, cfg.per_group) == (4, 1e6, 2)
    shapes = ex.param_shapes(cfg)
    assert ["mlp" in layer for layer in shapes["layers"]] \
        == list(cfg.dense_layers)
    assert shapes["layers"][0]["mlp"]["gate"][0] == (64, 96)
    assert shapes["layers"][1]["moe"]["experts"]["gate"][0] == (4, 64, 32)
    assert shapes["layers"][1]["moe"]["router"][0] == (64, 16)
    assert shapes["layers"][1]["attn"]["q_norm"][0] == (16,)
    assert shapes["mtp"]["eh_proj"][0] == (128, 64)
    assert "moe" in shapes["mtp"]["layer"]
    # the benchmark's weights have the model's own tree
    assert jax.tree_util.tree_structure(
        jax.eval_shape(lambda: ex.init_params(cfg, 0))) \
        == jax.tree_util.tree_structure(jax.eval_shape(
            lambda: Loader(REPO).module("weights", "kexaone_236b_share8")
            .make(toy, 1)))
    plain = ex.ExaoneMoeConfig.from_dict(dict(toy,
                                              num_nextn_predict_layers=0))
    assert not plain.mtp and "mtp" not in ex.param_shapes(plain)
    for change, says in [
            ({"layer_types": ["linear_attention"] * 8}, "layer_types"),
            ({"num_hidden_layers": 9}, "layer_types"),
            ({"n_group": 2}, "group-limited"),
            ({"hidden_act": "relu"}, "silu-gated"),
            ({"scoring_func": "softmax"}, "sigmoid"),
            ({"norm_topk_prob": False}, "renormalised"),
            ({"rope_parameters": {"rope_type": "yarn"}}, "rope scaling"),
            ({"num_nextn_predict_layers": 2}, "one prediction module"),
            ({"mtp_layer_types": ["sliding_attention"]}, "full attention"),
            ({"share": {"expert0": 14}}, "experts [14, 18) of 16")]:
        with pytest.raises(ValueError, match=says.replace("[", r"\[")
                           .replace(")", r"\)")):
            ex.ExaoneMoeConfig.from_dict(dict(toy, **change))
    with pytest.raises(ValueError, match="positions"):
        ex.init_state(cfg, {"embed": jnp.zeros((1, 1))}, 2, 65, REWIND)


def test_a_model_without_the_module_serves_one_logits_tensor(toy, files):
    plain_toy = dict(toy, num_nextn_predict_layers=0)
    cfg = ex.ExaoneMoeConfig.from_dict(plain_toy)
    params = _f32(files["weights"].make(plain_toy, SEED))
    assert "mtp" not in params
    state = ex.init_state(cfg, params, 2, 16, REWIND)
    assert "mtp" not in state
    ids = _ids(cfg, (2,), 1)
    state, out = ex.decode(cfg, params, state, ids, ids,
                           np.zeros(2, np.int32))
    assert len(out) == 2 and out[0].shape == (2, 32)
    ref = files["reference"].forward_last(
        plain_toy, SEED, [ids[:1], ids[1:]], [ids[:1], ids[1:]])
    assert ref[1] is None and _close(np.asarray(out[0]), ref[0])


def test_stage_scopes_are_in_the_program_text(model):
    cfg = model["cfg"]
    state = ex.init_state(cfg, model["params"], 3, POSITIONS, REWIND)
    i32 = np.zeros(3, np.int32)

    def scoped(fn, *x):
        def step(p, s, *x):
            with jax.named_scope("nns.model"):
                return fn(cfg, p, s, *x)
        return jax.jit(step).lower(model["params"], state, *x) \
            .as_text(debug_info=True)

    decode = scoped(ex.decode, i32, i32, i32)
    one, chunk = np.zeros(1, np.int32), np.zeros(CHUNK, np.int32)
    prefill = scoped(ex.prefill, chunk, chunk, one, one, one)
    for text in (decode, prefill):
        for scope in ("embed", "head", "state", "layer00/attn_window",
                      "layer00/mlp", "layer02/attn_window/cache_write",
                      "layer03/attn_full/cache_write", "layer01/moe/router",
                      "layer02/moe/dispatch", "layer04/moe/experts",
                      "layer04/moe/combine", "layer03/moe/shared",
                      "mtp/merge", "mtp/attn_full/cache_write",
                      "mtp/moe/router", "mtp/moe/experts", "mtp/moe/shared",
                      "mtp/head"):
            assert f"nns.model/{scope}" in text, scope
        assert "layer00/moe" not in text and "layer01/mlp" not in text
        assert "layer03/attn_window" not in text


def test_the_experts_go_through_moe_and_nothing_else(model, monkeypatch):
    cfg = model["cfg"]

    def gone(*a, **k):
        raise RuntimeError("moe.grouped_experts")

    monkeypatch.setattr(moe, "grouped_experts", gone)
    state = ex.init_state(cfg, model["params"], 3, POSITIONS, REWIND)
    ids = np.full(3, 32, np.int32)
    with pytest.raises(RuntimeError, match="moe.grouped_experts"):
        ex.decode(cfg, model["params"], state, ids, ids,
                  np.zeros(3, np.int32))


def test_the_model_takes_the_kernels_where_it_can(toy, files, monkeypatch):
    """At a head size of whole lanes and rings of whole cells the decode
    step attends through ``gqa_decode_attention`` (interpreted here) in
    all six caches and agrees with the plain forward; what it fetches is
    the walk's cells: two of a ring for a window that straddles them."""
    wide = dict(toy, head_dim=128, sliding_window=128,
                max_position_embeddings=1024)
    model = _model(wide, files)
    cfg = model["cfg"]
    calls = []
    real = kernels.gqa_decode_attention
    monkeypatch.setattr(kernels, "gqa_decode_attention",
                        lambda *a: calls.append(a[4]) or real(*a))
    length = 300
    ids = _ids(cfg, (1, length + 3), 2)
    state = ex.init_state(cfg, model["params"], 1, 384, 256)
    state, _ = _prefill(model, state, 0, ids[0], length, 128)
    state, got = _answer(model, state, ids, (length,), 2)
    assert calls == [128, 128, 128, 384, 128, 384] * 1
    for step in (0, 1):
        ref = _reference(wide, files, ids, [length + step + 1])
        assert _close(got[0][step], ref[0], tol=1e-4)
        assert _close(got[1][step], ref[1], tol=1e-4)
    counters = jax.device_get(state["counters"])
    # positions 300 and 301: rows 173..300 lie in cells 1 and 2
    assert counters["window_rows_read"] == 2 * 128
    assert counters["window_rows_fetched"] == 2 * 256
    assert counters["full_rows_fetched"] == 2 * 384


# -- on the element stream -----------------------------------------------------------


def _pull(sink, n, timeout=60.0):
    out = []
    while len(out) < n:
        buf = sink.pull(timeout=timeout)
        assert buf is not None, "the line served nothing"
        out.append(buf)
    return out


def test_two_launch_lines_prefill_and_decode_on_one_state(toy, files, model):
    """``tensor_filter framework=jax-xla model=<name>`` twice on one
    ``shared-tensor-filter-key``: the schema (five tensors or three)
    picks prefill or decode, both work on one state of rings, full
    caches and the module's cache, a decode buffer serves four tensors,
    and the counters reach ``STATE_STATS``."""
    cfg = model["cfg"]
    SHARED_MODELS.clear()
    STATE_STATS.reset()
    name = "exaone_toy_stream"
    ex.register(name, cfg, model["params"], streams=3, positions=POSITIONS,
                chunk=CHUNK, rewind=REWIND)
    ids = _ids(cfg, (3, 24), 8)
    line = ("device_src name={p}src num_buffers={n} ! tensor_filter "
            "name={p}net framework=jax-xla model=" + name
            + " shared-tensor-filter-key=ex stat-sample-interval-ms=0 "
            "! appsink name={p}sink")
    # prompts of 13 tokens: a whole chunk, then five real ids of eight
    chunks = []
    for r in range(3):
        for at, count in ((0, 8), (8, 5)):
            part, follows = (np.full(CHUNK, cfg.vocab0, np.int32)
                             for _ in range(2))
            part[:count] = ids[r, at:at + count]
            follows[:count] = ids[r, at + 1:at + 1 + count]
            chunks.append((part, follows, np.array([r], np.int32),
                           np.array([at], np.int32),
                           np.array([count], np.int32)))
    try:
        pre = parse_launch(line.format(p="pf_", n=len(chunks)))
        pre["pf_src"].frames, pre["pf_src"].pool_size = chunks, len(chunks)
        pre.start()
        assert len(_pull(pre["pf_sink"], len(chunks))[0].tensors) == 4
        # four steps, a rewind to the prompts' end, the same four again
        steps = [(ids[:, 13 + j], ids[:, 14 + j],
                  np.full(3, 13 + j, np.int32)) for j in range(4)] * 2
        run = parse_launch(line.format(p="el_", n=len(steps)))
        run["el_src"].frames, run["el_src"].pool_size = steps, len(steps)
        run.start()
        served = _pull(run["el_sink"], len(steps))
        cell = run["el_net"].subplugin._cell
        assert cell is pre["pf_net"].subplugin._cell and cell.refs == 2
        ring, full = 3 * 2 * 12 * 16 * 4, 3 * 2 * 48 * 16 * 4
        assert cell.state_bytes == 2 * (4 * ring + 2 * full) \
            + 3 * 3 * 4 + len(ex.COUNTERS) * 4
        for j, buf in enumerate(served):
            ref = _reference(toy, files, ids, [13 + j % 4 + 1] * 3)
            assert len(buf.tensors) == 4
            for which in (0, 1):
                assert _close(buf.tensors[which].np(), ref[which])
                assert np.array_equal(buf.tensors[2 + which].np(),
                                      ref[which].argmax(-1) + cfg.vocab0)
        stats = STATE_STATS.snapshot()
        assert stats["steps"] == 8 and stats["position_faults"] == 0
        row = 2 * 2 * 16 * 4
        rows = 2 * sum(3 * (13 + j + 1) for j in range(4))
        assert stats["window_bytes_read"] == 8 * 3 * 4 * 4 * row
        assert stats["full_bytes_read"] == stats["mtp_bytes_read"] \
            == rows * row
        assert stats["cache_bytes_read"] == stats["window_bytes_read"] \
            + 2 * rows * row
        assert stats["cache_bytes_fetched"] == 8 * 3 * row * (
            4 * 12 + 2 * POSITIONS)
        assert stats["state_bytes"] == cell.state_bytes
        pre.stop()
        run.stop()
        assert STATE_STATS.snapshot()["state_bytes"] == 0
    finally:
        unregister_model(name)
        SHARED_MODELS.clear()
