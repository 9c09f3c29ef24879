"""Nemotron-H as a stateful model (``nnstreamer_tpu/models/nemotron_h.py``)
at toy sizes on the CPU: the chunked scan against the token-by-token
recurrence, prefill and decode through the filter's state against the
benchmark's plain reference, the snapshot at the prompt's end and who
starts from it, the router, the shares of the expert layer, and two
launch lines on one state.  No number here is a rate."""

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
from nnstreamer_tpu.models import mamba2, moe  # noqa: E402
from nnstreamer_tpu.models import nemotron_h as nh  # noqa: E402
from nnstreamer_tpu.runtime import parse_launch  # noqa: E402
from nnstreamer_tpu.utils.stats import STATE_STATS  # noqa: E402

SEED = 11
CHUNK, POSITIONS = 8, 48
LENGTHS = (13, 24, 9)       # a padded last chunk, whole chunks, two chunks
STEPS = 6


@pytest.fixture(scope="module")
def toy():
    """The toy twin of the benchmark's configuration: hidden 64, the
    seven leading layers MEMEM*E, Mamba-2 of 8 heads of 8 over 2 groups
    with a state of 16 (scan chunks of 4), experts 4-7 of 16 (3 a
    token) at width 24 stored as 32, 4 query heads over 2 key/value
    heads of 16, vocabulary rows 32-63."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_nemotron3.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def files():
    loader = Loader(REPO)
    return {kind: loader.module(kind, "nemotron3_nano_share8")
            for kind in ("weights", "reference", "costs", "inputs")}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def model(toy, files):
    cfg = nh.NemotronHConfig.from_dict(toy)
    return {"cfg": cfg, "params": _f32(files["weights"].make(toy, SEED)),
            "prefill": jax.jit(lambda p, s, *x: nh.prefill(cfg, p, s, *x)),
            "decode": jax.jit(lambda p, s, *x: nh.decode(cfg, p, s, *x))}


def _prefill(model, state, row, ids, chunk=CHUNK):
    """``ids`` of stream ``row`` from position 0 in chunks of ``chunk``,
    the last padded with the first held id and told its count."""
    logits = None
    for at in range(0, len(ids), chunk):
        part = np.full(chunk, model["cfg"].vocab0, np.int32)
        real = ids[at:at + chunk]
        part[:len(real)] = real
        state, (logits, _) = model["prefill"](
            model["params"], state, part, np.array([row], np.int32),
            np.array([at], np.int32), np.array([len(real)], np.int32))
    return state, logits


def _ids(cfg, shape, seed):
    return np.random.default_rng(seed).integers(
        cfg.vocab0, cfg.vocab0 + cfg.vocab, shape).astype(np.int32)


def _answer(model, state, ids, steps=STEPS):
    """``steps`` decode steps on top of the prompts: ``[steps, 3, vocab]``."""
    out = []
    for j in range(steps):
        state, (lg, greedy) = model["decode"](
            model["params"], state,
            np.array([ids[r, n + j] for r, n in enumerate(LENGTHS)]),
            np.array([n + j for n in LENGTHS], np.int32))
        assert np.array_equal(np.asarray(greedy),
                              np.asarray(lg).argmax(-1) + model["cfg"].vocab0)
        out.append(np.asarray(lg))
    return state, np.stack(out)


@pytest.fixture(scope="module")
def served(model):
    """Three streams: prompts of 13, 24 and 9 tokens prefilled in chunks
    of 8, then two passes of 6 decode steps with a rewind to each
    prompt's end between them, in float32."""
    cfg = model["cfg"]
    ids = _ids(cfg, (3, max(LENGTHS) + STEPS), 5)
    state = nh.init_state(cfg, model["params"], 3, POSITIONS)
    for r, n in enumerate(LENGTHS):
        state, _ = _prefill(model, state, r, ids[r, :n])
    prefilled = jax.tree_util.tree_map(np.asarray, state)
    state, first = _answer(model, state, ids)
    once = jax.device_get(state["counters"])
    state, second = _answer(model, state, ids)
    return {"first": first, "second": second, "ids": ids,
            "prefilled": prefilled, "once": once,
            "twice": jax.device_get(state["counters"])}


def _close(got, ref, tol=3e-5):
    return np.abs(got - ref).max() <= tol * max(1.0, np.abs(ref).max())


def _reference(toy, files, served, step):
    return files["reference"].forward_last(
        toy, SEED, [served["ids"][r, :n + step + 1]
                    for r, n in enumerate(LENGTHS)])


# -- the recurrence ------------------------------------------------------------------


@pytest.mark.parametrize("tokens,size", [(8, 4), (13, 4), (3, 4), (16, 16),
                                         (12, 5)])
def test_the_chunked_scan_is_the_recurrence(model, tokens, size):
    """``ssd_scan`` against one token at a time, from a state that is
    not zero, across the scan's chunk boundaries and with a last chunk
    that is not whole."""
    import dataclasses

    cfg = dataclasses.replace(model["cfg"], chunk_size=size)
    g, r = cfg.groups, cfg.mamba_heads // cfg.groups
    keys = jax.random.split(jax.random.PRNGKey(tokens * 31 + size), 6)
    x = jax.random.normal(keys[0], (tokens, g, r, cfg.mamba_head_dim))
    b = jax.random.normal(keys[1], (tokens, g, cfg.state_size))
    c = jax.random.normal(keys[2], (tokens, g, cfg.state_size))
    delta = jax.random.uniform(keys[3], (tokens, cfg.mamba_heads), jnp.float32,
                               0.01, 0.5)
    a_log = jnp.log(jax.random.uniform(keys[4], (cfg.mamba_heads,),
                                       jnp.float32, 1.0, 2.0))
    s0 = jax.random.normal(keys[5], (cfg.mamba_heads, cfg.mamba_head_dim,
                                     cfg.state_size))
    y, last = mamba2.ssd_scan(cfg.mamba, x, b, c, delta, a_log, s0)
    s = np.asarray(s0, np.float64).reshape(g, r, cfg.mamba_head_dim, -1)
    for t in range(tokens):
        a = np.exp(-np.asarray(delta[t], np.float64)
                   * np.exp(np.asarray(a_log, np.float64))).reshape(g, r)
        dx = np.asarray(delta[t]).reshape(g, r)[..., None] * np.asarray(x[t])
        s = a[..., None, None] * s \
            + dx[..., None] * np.asarray(b[t])[:, None, None, :]
        want = np.sum(s * np.asarray(c[t])[:, None, None, :], axis=-1)
        assert np.allclose(np.asarray(y[t]), want, atol=2e-5), t
    assert np.allclose(np.asarray(last).reshape(s.shape), s, atol=2e-5)


@pytest.mark.parametrize("row", range(3))
def test_prefill_leaves_what_token_by_token_decode_leaves(model, served, row):
    """The state after a prompt prefilled in padded chunks is the state
    after the same tokens fed one a step from nothing (a stream at
    position 0 with no prompt starts from its zero snapshot), and live
    state and snapshot are alike."""
    cfg, n = model["cfg"], LENGTHS[row]
    state = nh.init_state(cfg, model["params"], 3, POSITIONS)
    for t in range(n):
        state, _ = model["decode"](
            model["params"], state,
            np.array([served["ids"][r, min(t, LENGTHS[r] - 1)]
                      for r in range(3)]), np.full(3, t, np.int32))
    for layer, got in zip(state["mamba"], served["prefilled"]["mamba"]):
        for name in ("ssm", "conv"):
            assert _close(got[name][row], np.asarray(layer[name][row])), name
            assert np.array_equal(got[name][row], got[name + "_snap"][row])
    assert served["prefilled"]["prompt_end"].tolist() == list(LENGTHS)
    assert served["prefilled"]["last"].tolist() == [n - 1 for n in LENGTHS]


@pytest.mark.parametrize("step", range(STEPS))
def test_prefill_then_decode_is_the_reference_at_every_position(
        toy, files, served, step):
    """Logits, not ids: the state's path (chunked prefill with a padded
    last chunk, then one token a step) against the reference's scan over
    the whole history from nothing."""
    assert _close(served["first"][step], _reference(toy, files, served, step))


def test_a_rewind_to_the_prompts_end_answers_alike(toy, files, model, served):
    """A second pass from each prompt's end equals the first bit for
    bit and the reference; with the snapshots zeroed it does not: the
    check can see the recurrent state."""
    assert np.array_equal(served["first"], served["second"])
    assert served["once"]["restores"] == 3
    assert served["twice"]["restores"] == 6
    assert served["twice"]["position_faults"] == 0
    state = jax.tree_util.tree_map(jnp.asarray, served["prefilled"])
    state["mamba"] = [dict(layer, ssm_snap=jnp.zeros_like(layer["ssm"]),
                           conv_snap=jnp.zeros_like(layer["conv"]))
                      for layer in state["mamba"]]
    _, lost = _answer(model, state, served["ids"], steps=1)
    ref = _reference(toy, files, served, 0)
    rel = np.linalg.norm(lost[0] - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    assert rel.min() > 0.05, rel


def test_the_steps_count_what_they_touch(model, served):
    cfg, once = model["cfg"], served["once"]
    assert once["steps"] == STEPS and once["ssm_rows"] == STEPS * 3
    assert once["kv_rows_read"] == sum(n + j + 1 for n in LENGTHS
                                       for j in range(STEPS))
    # heads of 16 take the jnp mathematics, which reads the cache whole
    assert once["kv_rows_fetched"] == STEPS * len(LENGTHS) * POSITIONS
    # three expert layers, three experts a token, a quarter of 16 held
    assert 0 < once["expert_hits"] < STEPS * 3 * 3 * 3
    assert once["experts_touched"] <= min(once["expert_hits"],
                                          STEPS * 3 * cfg.experts)
    state = nh.init_state(cfg, model["params"], 3, POSITIONS)
    units = nh.counter_units(cfg, state)
    row = cfg.mamba_heads * cfg.mamba_head_dim * cfg.state_size * 4 \
        + (cfg.conv_kernel - 1) * cfg.mamba.conv_dim * 4
    assert units["ssm_bytes"] == ("ssm_rows", 2 * row * 3)
    assert units["kv_bytes_read"] == units["cache_bytes_read"] \
        == ("kv_rows_read", 2 * 2 * 16 * 4 * 1)
    assert units["kv_bytes_fetched"] == units["cache_bytes_fetched"] \
        == ("kv_rows_fetched", 2 * 2 * 16 * 4 * 1)


def test_a_position_the_state_cannot_serve_is_counted(model, served):
    """Neither the one after the last nor the prompt's end: stream 1
    skips a position, stream 2 goes back one; stream 0 goes on."""
    state = jax.tree_util.tree_map(jnp.asarray, served["prefilled"])
    ids = served["ids"]
    state, _ = _answer(model, state, ids, steps=2)
    at = np.array([LENGTHS[0] + 2, LENGTHS[1] + 3, LENGTHS[2]], np.int32)
    state, _ = model["decode"](model["params"], state, ids[:, 0], at)
    got = jax.device_get(state["counters"])
    assert got["position_faults"] == 1 and got["restores"] == 3 + 1
    state, _ = model["decode"](model["params"], state, ids[:, 0], at)
    got = jax.device_get(state["counters"])
    assert got["position_faults"] == 1 + 2 and got["restores"] == 3 + 2


# -- the expert layer ----------------------------------------------------------------


def test_router_weights(model):
    """Sigmoid scores; the bias moves the choice and not the weights;
    the kept weights add up to the scaling factor."""
    cfg = model["cfg"]
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    u = jax.random.normal(keys[0], (5, cfg.hidden_size))
    router = jax.random.normal(keys[1], (cfg.hidden_size, 16)) * 0.2
    score = 1 / (1 + np.exp(-np.asarray(u, np.float64)
                            @ np.asarray(router, np.float64)))

    def route(u, bias):
        return moe.route_sigmoid(u, router, bias, cfg.top_k,
                                 cfg.routed_scaling_factor)

    idx, weight = route(u, jnp.zeros(16))
    want = np.argsort(-score, axis=-1)[:, :cfg.top_k]
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(want))
    kept = np.take_along_axis(score, np.asarray(idx), axis=-1)
    assert np.allclose(np.asarray(weight),
                       2.5 * kept / kept.sum(-1, keepdims=True), atol=1e-6)
    assert np.allclose(np.asarray(weight).sum(-1), 2.5, atol=1e-6)
    # a bias that lifts the three lowest scores into the choice: the
    # weights are still their own (low) scores, normalised
    low = np.argsort(score, axis=-1)[:, :cfg.top_k]
    for token in range(5):
        bias = jnp.zeros(16).at[low[token]].set(10.0)
        idx, weight = route(u[token:token + 1], bias)
        assert set(np.asarray(idx)[0]) == set(low[token])
        kept = score[token][np.asarray(idx)[0]]
        assert np.allclose(np.asarray(weight)[0], 2.5 * kept / kept.sum(),
                           atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(toy, files):
    """Four chips' expert parts (experts 0-3, 4-7, 8-11, 12-15 of 16)
    plus the shared expert ONCE are the layer with all 16 experts held;
    and a stored width of 32 columns computes what 24 do."""
    whole = nh.NemotronHConfig.from_dict(dict(
        toy, n_routed_experts=16, share={"expert0": 0, "vocab0": 0}))
    wide = dict(toy, n_routed_experts=16, expert_columns_stored=0)
    layer = _f32(files["weights"].make_part(wide, SEED, "layer01"))
    assert layer["experts"]["up"].shape == (16, 64, 24)
    u = jax.random.normal(jax.random.PRNGKey(2), (12, 64))
    routed, shared, counts = nh.moe_parts(whole, layer, u)
    assert int(counts.sum()) == 12 * whole.top_k
    parts = []
    for first in range(0, 16, 4):
        cfg = nh.NemotronHConfig.from_dict(dict(
            toy, share={"expert0": first, "vocab0": 0}))
        held = dict(layer, experts={
            name: jnp.pad(w[first:first + 4],
                          [(0, 0)] + [(0, 8 if n == 24 else 0)
                                      for n in w.shape[1:]])
            for name, w in layer["experts"].items()})
        assert held["experts"]["down"].shape == (4, 32, 64)
        part, again, got = nh.moe_parts(cfg, held, u)
        assert np.array_equal(np.asarray(again), np.asarray(shared))
        assert np.array_equal(np.asarray(got),
                              np.asarray(counts[first:first + 4]))
        parts.append(np.asarray(part))
    assert np.allclose(sum(parts), np.asarray(routed), atol=1e-5)
    assert not np.allclose(parts[0], np.asarray(routed), atol=1e-3)


# -- the configuration ---------------------------------------------------------------


def test_the_configuration_is_read_as_published(toy):
    cfg = nh.NemotronHConfig.from_dict(toy)
    assert cfg.pattern == "MEMEM*E" and cfg.layers == 7
    assert (cfg.count("M"), cfg.count("E"), cfg.count("*")) == (3, 3, 1)
    assert cfg.mamba.d_inner == 64 and cfg.mamba.conv_dim == 64 + 2 * 2 * 16
    assert (cfg.n_routed_experts, cfg.experts, cfg.expert0) == (16, 4, 4)
    assert (cfg.vocab, cfg.vocab0, cfg.shared_width) == (32, 32, 48)
    shapes = nh.param_shapes(cfg)
    assert [sorted(layer)[0] for layer in shapes["layers"]] == [
        "A_log", "experts", "A_log", "experts", "A_log", "k", "experts"]
    assert shapes["layers"][0]["in_proj"][0] == (64, 64 + 128 + 8)
    assert shapes["layers"][1]["experts"]["up"][0] == (4, 64, 24)
    assert "gate" not in shapes["layers"][1]["experts"]
    for change, says in [
            ({"hybrid_override_pattern": "MEX"}, "pattern"),
            ({"num_hidden_layers": 9}, "pattern"),
            ({"n_group": 2}, "group-limited"),
            ({"mlp_hidden_act": "silu"}, "relu2"),
            ({"norm_topk_prob": False}, "renormalised"),
            ({"attention_bias": True}, "bias"),
            ({"use_conv_bias": False}, "bias"),
            ({"share": {"expert0": 14}}, "experts [14, 18) of 16")]:
        with pytest.raises(ValueError, match=says.replace("[", r"\[")
                           .replace(")", r"\)")):
            nh.NemotronHConfig.from_dict(dict(toy, **change))
    with pytest.raises(ValueError, match="positions"):
        nh.init_state(cfg, {"embed": jnp.zeros((1, 1))}, 2, 65)


def test_stage_scopes_are_in_the_program_text(model):
    cfg = model["cfg"]
    state = nh.init_state(cfg, model["params"], 3, POSITIONS)
    i32 = np.zeros(3, np.int32)

    def scoped(fn, *x):
        def step(p, s, *x):
            with jax.named_scope("nns.model"):
                return fn(cfg, p, s, *x)
        return jax.jit(step).lower(model["params"], state, *x) \
            .as_text(debug_info=True)

    decode = scoped(nh.decode, i32, i32)
    one = np.zeros(1, np.int32)
    prefill = scoped(nh.prefill, np.zeros(CHUNK, np.int32), one, one, one)
    for text, inner in ((decode, "step"), (prefill, "scan")):
        for scope in ("embed", "head", "state", "layer00/mamba/in_proj",
                      "layer00/mamba/conv", f"layer04/mamba/{inner}",
                      "layer02/mamba/gate_norm", "layer02/mamba/out_proj",
                      "layer05/attn/cache_write", "layer01/moe/router",
                      "layer03/moe/dispatch", "layer06/moe/experts",
                      "layer06/moe/combine", "layer06/moe/shared"):
            assert f"nns.model/{scope}" in text, scope
    # the toy's state (32 lanes over a state of 16) is a shape the step's
    # kernel refuses: its decode takes the jnp step behind the restore
    # loop (tests/test_ssm_step.py has the kernel's program without it)
    assert "whole lanes" in mamba2.step_refusal(state["mamba"][0])
    assert "nns.model/ssm_restore" in decode
    assert "nns.model/ssm_restore" not in prefill


def test_the_experts_go_through_moe_and_nothing_else(model, monkeypatch):
    """The model reaches its routed experts only through
    ``models/moe.py``: with the grouped product knocked out no expert
    layer can run."""
    cfg = model["cfg"]

    def gone(*a, **k):
        raise RuntimeError("moe.grouped_experts")

    monkeypatch.setattr(moe, "grouped_experts", gone)
    state = nh.init_state(cfg, model["params"], 3, POSITIONS)
    with pytest.raises(RuntimeError, match="moe.grouped_experts"):
        nh.decode(cfg, model["params"], state, np.full(3, 32, np.int32),
                  np.zeros(3, np.int32))


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
    ``shared-tensor-filter-key``: the schema (four tensors or two) picks
    prefill or decode, both work on one state of recurrent states,
    snapshots and a cache, and the counters reach ``STATE_STATS``."""
    cfg = model["cfg"]
    SHARED_MODELS.clear()
    STATE_STATS.reset()
    name = "nemotron_toy_stream"
    nh.register(name, cfg, model["params"], streams=3, positions=POSITIONS,
                chunk=CHUNK)
    ids = _ids(cfg, (3, 24), 8)
    line = ("device_src name={p}src num_buffers={n} ! tensor_filter "
            "name={p}net framework=jax-xla model=" + name
            + " shared-tensor-filter-key=nh stat-sample-interval-ms=0 "
            "! appsink name={p}sink")
    # prompts of 13 tokens: a whole chunk, then five real ids of eight
    chunks = []
    for r in range(3):
        for at, count in ((0, 8), (8, 5)):
            part = np.full(CHUNK, cfg.vocab0, np.int32)
            part[:count] = ids[r, at:at + count]
            chunks.append((part, np.array([r], np.int32),
                           np.array([at], np.int32),
                           np.array([count], np.int32)))
    try:
        pre = parse_launch(line.format(p="pf_", n=len(chunks)))
        pre["pf_src"].frames, pre["pf_src"].pool_size = chunks, len(chunks)
        pre.start()
        _pull(pre["pf_sink"], len(chunks))
        # four steps, a rewind to the prompts' end, the same four again
        steps = [(ids[:, 13 + j], np.full(3, 13 + j, np.int32))
                 for j in range(4)] * 2
        run = parse_launch(line.format(p="el_", n=len(steps)))
        run["el_src"].frames, run["el_src"].pool_size = steps, len(steps)
        run.start()
        served = _pull(run["el_sink"], len(steps))
        cell = run["el_net"].subplugin._cell
        assert cell is pre["pf_net"].subplugin._cell and cell.refs == 2
        ssm, conv, kv = 3 * 8 * 8 * 16 * 4, 3 * 3 * 128 * 4, 3 * 2 * 48 * 16 * 4
        assert cell.state_bytes == 3 * 2 * (ssm + conv) + 2 * kv \
            + 2 * 3 * 4 + len(nh.COUNTERS) * 4
        for j, buf in enumerate(served):
            ref = files["reference"].forward_last(
                toy, SEED, [ids[r, :13 + j % 4 + 1] for r in range(3)])
            assert _close(buf.tensors[0].np(), ref)
            assert np.array_equal(buf.tensors[1].np(),
                                  ref.argmax(-1) + cfg.vocab0)
        stats = STATE_STATS.snapshot()
        assert stats["steps"] == 8 and stats["restores"] == 6
        assert stats["position_faults"] == 0
        assert stats["ssm_bytes"] == 8 * 3 * 2 * (ssm + conv)
        assert stats["kv_bytes_read"] == stats["cache_bytes_read"] \
            == 2 * sum(3 * (13 + j + 1) for j in range(4)) * 2 * 2 * 16 * 4
        assert stats["kv_bytes_fetched"] == stats["cache_bytes_fetched"] \
            == 8 * 3 * POSITIONS * 2 * 2 * 16 * 4
        assert stats["state_bytes"] == cell.state_bytes
        pre.stop()
        run.stop()
        assert STATE_STATS.snapshot()["state_bytes"] == 0
    finally:
        unregister_model(name)
        SHARED_MODELS.clear()
