"""SmallThinker (``nnstreamer_tpu/models/smallthinker.py``) at a small
size on the CPU, against the benchmark's plain float32 reference
(``benchmark/reference/smallthinker_21b_stage8.py``, which imports
nothing of the program): prefill and decode through the two kinds of
cache (a ring that wraps, a dense cache that grows), chunks at the
ring's wrap and at the window's edge, the early router, the decode
kernel against its ``jnp`` mathematics, and the model on the element
stream through ``parse_launch``.
"""

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
from nnstreamer_tpu.models import smallthinker as st  # noqa: E402
from nnstreamer_tpu.ops import kernels  # noqa: E402
from nnstreamer_tpu.runtime import parse_launch  # noqa: E402
from nnstreamer_tpu.utils.stats import STATE_STATS  # noqa: E402

SEED = 11
WINDOW, CHUNK, POSITIONS = 8, 4, 40
LENGTHS = (13, 22, 9)            # prompts: the last chunk of each is padded
STEPS = 12                       # contexts of 20-34 by the last step


@pytest.fixture(scope="module")
def toy():
    """The toy twin of the benchmark's configuration: hidden 64, 6 query
    heads over 2 key/value heads of 16, 8 experts of width 32 (3 a
    token), a window of 8, six layers (one and a half periods: full
    attention at 0 and 4), 64 ids."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_smallthinker.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def files():
    loader = Loader(REPO)
    return {kind: loader.module(kind, "smallthinker_21b_stage8")
            for kind in ("weights", "reference", "costs")}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def model(toy, files):
    cfg = st.SmallThinkerConfig.from_dict(toy)
    return {"cfg": cfg, "params": _f32(files["weights"].make(toy, SEED)),
            "prefill": jax.jit(lambda p, s, *x: st.prefill(cfg, p, s, *x)),
            "decode": jax.jit(lambda p, s, *x: st.decode(cfg, p, s, *x))}


def _prefill(model, state, row, ids, start, chunk=CHUNK):
    """``ids`` of stream ``row`` from position ``start`` in chunks of
    ``chunk``, the last padded with zeros."""
    logits = None
    for at in range(0, len(ids), chunk):
        part = np.zeros(chunk, np.int32)
        part[:len(ids[at:at + chunk])] = ids[at:at + chunk]
        state, (logits, _) = model["prefill"](
            model["params"], state, part, np.array([row], np.int32),
            np.array([start + at], np.int32))
    return state, logits


@pytest.fixture(scope="module")
def served(model):
    """Three streams: prompts of 13, 22 and 9 tokens prefilled in chunks
    of 4 (each last chunk padded, the rings of 12 wrapped by the second
    prompt), then 12 decode steps, in float32."""
    cfg = model["cfg"]
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg.vocab, (3, max(LENGTHS) + STEPS)) \
        .astype(np.int32)
    state = st.init_state(cfg, model["params"], 3, POSITIONS, CHUNK)
    assert [leaf["k"].shape[2] for leaf in state["cache"]] \
        == [POSITIONS, 12, 12, 12, POSITIONS, 12]
    for r, n in enumerate(LENGTHS):
        state, _ = _prefill(model, state, r, ids[r, :n], 0)
    logits = []
    for j in range(STEPS):
        state, (lg, greedy) = model["decode"](
            model["params"], state,
            np.array([ids[r, n + j] for r, n in enumerate(LENGTHS)]),
            np.array([n + j for n in LENGTHS], np.int32))
        assert np.array_equal(np.asarray(greedy), np.asarray(lg).argmax(-1))
        logits.append(np.asarray(lg))
    return {"logits": logits, "ids": ids, "state": state}


def _close(got, ref, tol=3e-5):
    return np.abs(got - ref).max() <= tol * max(1.0, np.abs(ref).max())


@pytest.mark.parametrize("step", range(STEPS))
def test_prefill_then_decode_is_the_reference_at_every_position(
        toy, files, served, step):
    """Prefill in padded chunks, then decode steps through rings that
    wrap and full caches that grow, against the reference's full forward
    (a mask, no cache) over the same history: logits, not ids."""
    histories = [served["ids"][r, :n + step + 1]
                 for r, n in enumerate(LENGTHS)]
    ref = files["reference"].forward_last(toy, SEED, histories)
    assert _close(served["logits"][step], ref)


def test_the_router_reads_the_attention_normed_input(toy, files, served):
    """The reference with its router on the post-attention stream, where
    most models have it, is another model: the program agrees with the
    early router and not with that."""
    histories = [served["ids"][r, :n + 1] for r, n in enumerate(LENGTHS)]
    late = files["reference"].forward_last(toy, SEED, histories,
                                           router_reads="expert_input")
    early = files["reference"].forward_last(toy, SEED, histories)
    assert _close(served["logits"][0], early)
    assert np.abs(late - early).max() > 0.1
    with pytest.raises(ValueError):
        files["reference"].forward_last(toy, SEED, histories,
                                        router_reads="nothing")


def test_the_steps_count_what_they_read(model, served):
    cfg = model["cfg"]
    counters = {k: int(v) for k, v in served["state"]["counters"].items()}
    assert counters["steps"] == STEPS
    # a ring's rows IN USE: the window once a stream is past it
    assert counters["window_rows_read"] == sum(
        min(n + j + 1, WINDOW) for n in LENGTHS for j in range(STEPS))
    assert counters["full_rows_read"] == sum(
        n + j + 1 for n in LENGTHS for j in range(STEPS))
    assert counters["expert_hits"] == STEPS * 3 * cfg.layers * cfg.top_k
    assert 0 < counters["experts_touched"] <= STEPS * cfg.layers * cfg.experts
    # heads of 16 take the jnp mathematics, which reads a cache whole:
    # the ring of 12 and all POSITIONS of a dense cache, every stream
    assert counters["window_rows_fetched"] == STEPS * len(LENGTHS) * 12
    assert counters["full_rows_fetched"] == STEPS * len(LENGTHS) * POSITIONS
    row = 2 * 2 * 16 * 4                     # K and V, float32 here
    units = {}
    for did in ("read", "fetched"):
        window = (f"window_rows_{did}", row * 4)
        full = (f"full_rows_{did}", row * 2)
        units.update({f"window_bytes_{did}": window,
                      f"full_bytes_{did}": full,
                      f"cache_bytes_{did}": [window, full]})
    assert st.counter_units(cfg, served["state"]) == units


@pytest.mark.parametrize("first,chunk", [
    (10, 4),      # slots 10, 11, 0, 1 of the ring of 12: over its end
    (6, 4),       # positions 6..9: the window of 8 runs out inside it
    (0, 12),      # longer than the window: its own first rows leave it
    (9, 12),      # all of it at once: longer, over the end, past the edge
], ids=["straddles-the-wrap", "window-runs-out-inside", "longer-than-window",
        "all-three"])
def test_a_prefill_chunk_at_the_rings_edges(toy, files, model, first, chunk):
    """A chunk whose rows fall over the ring's end, one inside which the
    window runs out, and one longer than the window: every row's output
    is the reference's, and so is the token decoded after it."""
    cfg = model["cfg"]
    rng = np.random.default_rng(first * 31 + chunk)
    ids = rng.integers(0, cfg.vocab, first + chunk + 1).astype(np.int32)
    state = st.init_state(cfg, model["params"], 2, POSITIONS, chunk)
    ring = WINDOW + chunk
    assert state["cache"][1]["k"].shape[2] == ring
    # the history before the chunk, a token at a time
    for p in range(first):
        state, _ = model["decode"](
            model["params"], state, np.array([0, ids[p]], np.int32),
            np.array([0, p], np.int32))
    run = jax.jit(lambda p, s, *x: st.prefill(cfg, p, s, *x))
    state, (logits, _) = run(model["params"], state, ids[first:first + chunk],
                             np.array([1], np.int32),
                             np.array([first], np.int32))
    ref = files["reference"].forward_last(toy, SEED, [ids[:first + chunk]])
    assert _close(np.asarray(logits), ref)
    state, (logits, _) = model["decode"](
        model["params"], state, np.array([0, ids[-1]], np.int32),
        np.array([0, first + chunk], np.int32))
    ref = files["reference"].forward_last(toy, SEED, [ids])
    assert _close(np.asarray(logits)[1:], ref)


@pytest.mark.parametrize("length", [WINDOW - 1, WINDOW, WINDOW + 1],
                         ids=["one-short", "exactly-at", "one-past"])
def test_a_stream_at_the_windows_edge(toy, files, model, length):
    """A stream whose history is one short of the window, exactly the
    window and one past it: the last is the first to lose a position."""
    cfg = model["cfg"]
    ids = np.random.default_rng(length).integers(
        0, cfg.vocab, length).astype(np.int32)
    state = st.init_state(cfg, model["params"], 1, POSITIONS, CHUNK)
    state, _ = _prefill(model, state, 0, ids[:-1], 0)
    state, (logits, _) = model["decode"](
        model["params"], state, ids[-1:], np.array([length - 1], np.int32))
    ref = files["reference"].forward_last(toy, SEED, [ids])
    assert _close(np.asarray(logits), ref)
    wide = dict(toy, sliding_window_size=64)     # no position ever lost
    full = files["reference"].forward_last(wide, SEED, [ids])
    assert (np.abs(full - ref).max() > 1e-3) == (length > WINDOW)


@pytest.mark.parametrize("dtype,tol", [("float32", 3e-5), ("bfloat16", 0.04)])
def test_full_forward_is_the_reference(toy, files, dtype, tol):
    """One chunk over a whole prompt of 32: the logits after its last
    token, for five prompts.  In bfloat16 an expert chosen on a near tie
    may differ from float32's choice and move ONE prompt's logits far,
    so the median is held tight and the worst loosely."""
    cfg = st.SmallThinkerConfig.from_dict(toy)
    params = files["weights"].make(toy, SEED + 1)
    if dtype == "float32":
        params = _f32(params)
    prompts = np.random.default_rng(9).integers(
        0, cfg.vocab, (5, 32)).astype(np.int32)
    run = jax.jit(lambda p, s, *x: st.prefill(cfg, p, s, *x))
    got = []
    for ids in prompts:
        state = st.init_state(cfg, params, 2, 32, 32)
        _, (logits, _greedy) = run(params, state, ids,
                                   np.array([1], np.int32),
                                   np.array([0], np.int32))
        got.append(np.asarray(logits)[0])
    ref = files["reference"].forward_last(toy, SEED + 1, list(prompts))
    err = np.linalg.norm(np.stack(got) - ref, axis=-1) \
        / np.linalg.norm(ref, axis=-1)
    assert np.median(err) <= tol and err.max() <= 10 * tol, err


def test_the_configuration_is_read_as_published(toy):
    cfg = st.SmallThinkerConfig.from_dict(toy)
    assert (cfg.layers, cfg.per_group, cfg.row_values) == (6, 3, 64)
    assert cfg.window_layers == (False, True, True, True, False, True)
    assert cfg.rope_layers == cfg.window_layers
    assert cfg.ring(4) == 12
    for key, value in (("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True),
                       ("norm_topk_prob", False),
                       ("moe_primary_router_apply_softmax", False),
                       ("num_key_value_heads", 4),
                       ("num_hidden_layers", 9)):
        with pytest.raises(ValueError, match="smallthinker"):
            st.SmallThinkerConfig.from_dict(dict(toy, **{key: value}))
    with pytest.raises(ValueError, match="positions"):
        st.init_state(cfg, {"embed": jnp.zeros((1,))}, 1, 65, 4)
    shapes = st.param_shapes(cfg)
    assert shapes["layers"][0]["moe"]["experts"]["gate"][0] == (8, 64, 32)
    params = st.init_params(cfg, 3)
    assert params["head"].shape == (64, 64) \
        and params["head"].dtype == jnp.bfloat16


# -- the decode kernel --------------------------------------------------------------


@pytest.mark.parametrize("groups,per,total,window,chunk,dtype,at", [
    (2, 7, 384, 256, 128, "float32", [5, 300, 383 + 384 * 2]),
    (2, 3, 384, 200, 128, "float32", [199, 200, 201]),
    (4, 7, 512, 512, 128, "bfloat16", [0, 127, 128]),
    (1, 7, 256, 1 << 20, 256, "float32", [3, 255, 128]),
    (2, 4, 256, 128, 256, "float32", [130, 300, 255]),
], ids=["ring-wrapped-twice", "window-off-the-blocks", "bf16-four-groups",
        "dense-cache", "ring-of-one-block"])
def test_gqa_kernel_is_its_reference(groups, per, total, window, chunk,
                                     dtype, at):
    """The Pallas kernel (interpreted on the CPU) against its jnp
    mathematics: a stream inside the first chunk, one at the window's
    edge, one that has wrapped the ring twice; a window that starts
    inside a chunk; a dense cache read up to the position.  By the plan
    the call derives and by one of ``chunk`` rows a buffer
    (``tests/test_decode_walk.py`` has the walk's own cases)."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(3, groups, per, 128)), dtype)
    k = jnp.asarray(rng.normal(size=(3, groups, total, 128)), dtype)
    v = jnp.asarray(rng.normal(size=(3, groups, total, 128)), dtype)
    at = jnp.asarray(at, jnp.int32)
    assert kernels.gqa_decode_attention_refusal(
        q.shape, k.shape, v.shape, window) is None
    got = kernels.gqa_decode_attention(q, k, v, at, window, 0.09)
    want = kernels.gqa_decode_attention_reference(q, k, v, at, window, 0.09)
    assert got.shape == (3, groups, per, 128) and got.dtype == jnp.float32
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert np.allclose(np.asarray(got), np.asarray(want), atol=tol)
    plan = kernels.WalkPlan(chunk, 3)
    by_plan = kernels._gqa_decode_walk(q, k, v, at, window, 0.09, plan)
    assert np.allclose(np.asarray(by_plan), np.asarray(want), atol=tol)
    # a slot beyond the window moves nothing: the stream at 300 of the
    # first case sees 45..300, so slot 44 may hold anything
    if window == 256:
        k2 = k.at[1, :, 44].set(1e4)
        again = kernels.gqa_decode_attention(q, k2, v, at, window, 0.09)
        assert np.array_equal(np.asarray(again[1]), np.asarray(got[1]))


@pytest.mark.parametrize("q_shape,k_shape,v_shape,window,says", [
    ((2, 4, 7, 16), (2, 4, 128, 16), (2, 4, 128, 16), 8, "whole lanes"),
    ((2, 4, 7, 128), (2, 4, 12, 128), (2, 4, 12, 128), 8, "12 cache"),
    ((2, 4, 7, 128), (2, 4, 128, 128), (2, 4, 256, 128), 8, "are not"),
    ((2, 28, 128), (2, 4, 128, 128), (2, 4, 128, 128), 8, "are not"),
    ((2, 4, 7, 128), (2, 4, 128, 128), (2, 4, 128, 128), 0, "window"),
], ids=["toy-heads", "toy-ring", "k-is-not-v", "no-groups", "no-window"])
def test_gqa_kernel_refuses_with_an_error(q_shape, k_shape, v_shape, window,
                                          says):
    """What it cannot take it names, and the model asks first: the toy's
    heads of 16 and its ring of 12 take the jnp mathematics."""
    assert says in kernels.gqa_decode_attention_refusal(
        q_shape, k_shape, v_shape, window)
    with pytest.raises(ValueError, match="gqa_decode_attention") as e:
        kernels.gqa_decode_attention(
            jnp.zeros(q_shape), jnp.zeros(k_shape), jnp.zeros(v_shape),
            jnp.zeros((2,), jnp.int32), window, 1.0)
    assert says in str(e.value)


def test_the_model_takes_the_kernel_where_it_can(model):
    """A layer at the published head size decodes through the kernel
    (interpreted here) and gives what its jnp mathematics gives."""
    cfg = st.SmallThinkerConfig.from_dict({
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 4,
        "moe_num_active_primary_experts": 2, "sliding_window_size": 128,
        "rope_theta": 1500000, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 512, "vocab_size": 32,
        "num_hidden_layers": 2, "sliding_window_layout": [0, 1],
        "rope_layout": [0, 1]})
    params = st.init_params(cfg, 1, jnp.float32)
    ids = np.array([3, 5], np.int32)
    positions = np.array([130, 300], np.int32)

    def step():
        state = st.init_state(cfg, params, 2, 512, 128)
        key = jax.random.PRNGKey(0)
        state["cache"] = [
            {name: jax.random.normal(jax.random.fold_in(key, 2 * i + n),
                                     leaf[name].shape)
             for n, name in enumerate(("k", "v"))}
            for i, leaf in enumerate(state["cache"])]
        return jax.make_jaxpr(lambda s: st.decode(cfg, params, s, ids,
                                                  positions))(state), \
            st.decode(cfg, params, state, ids, positions)[1][0]

    text, with_kernel = step()
    assert "gqa_decode_attention" in str(text)
    refusal = kernels.gqa_decode_attention_refusal
    try:
        kernels.gqa_decode_attention_refusal = lambda *a, **k: "off"
        text, without = step()
    finally:
        kernels.gqa_decode_attention_refusal = refusal
    assert "gqa_decode_attention" not in str(text)
    assert np.allclose(np.asarray(with_kernel), np.asarray(without),
                       atol=2e-5)


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
    ``shared-tensor-filter-key``: the schema picks prefill or decode,
    both work on one state of rings and full caches, and the counters
    tell the two kinds of cache apart."""
    cfg = model["cfg"]
    SHARED_MODELS.clear()
    STATE_STATS.reset()
    name = "smallthinker_toy_stream"
    st.register(name, cfg, model["params"], streams=3, positions=POSITIONS,
                chunk=CHUNK)
    rng = np.random.default_rng(8)
    ids = rng.integers(0, cfg.vocab, (3, 20)).astype(np.int32)
    line = ("device_src name={p}src num_buffers={n} ! tensor_filter "
            "name={p}net framework=jax-xla model=" + name
            + " shared-tensor-filter-key=st stat-sample-interval-ms=0 "
            "! appsink name={p}sink")
    chunks = [(ids[r, at:at + CHUNK], np.array([r], np.int32),
               np.array([at], np.int32))
              for r in range(3) for at in range(0, 16, CHUNK)]
    try:
        pre = parse_launch(line.format(p="pf_", n=len(chunks)))
        pre["pf_src"].frames, pre["pf_src"].pool_size = chunks, len(chunks)
        pre.start()
        _pull(pre["pf_sink"], len(chunks))
        steps = [(ids[:, 16 + j], np.full(3, 16 + j, np.int32))
                 for j in range(4)]
        run = parse_launch(line.format(p="el_", n=len(steps)))
        run["el_src"].frames, run["el_src"].pool_size = steps, len(steps)
        run.start()
        served = _pull(run["el_sink"], len(steps))
        cell = run["el_net"].subplugin._cell
        assert cell is pre["pf_net"].subplugin._cell and cell.refs == 2
        # leaves of two shapes in one state, counted together
        shapes = {leaf.shape for leaf in jax.tree_util.tree_leaves(
            cell.state["cache"])}
        assert shapes == {(3, 2, POSITIONS, 16), (3, 2, 12, 16)}
        assert cell.state_bytes == len(st.COUNTERS) * 4 \
            + 2 * 3 * 2 * 16 * 4 * (2 * POSITIONS + 4 * 12)
        for j, buf in enumerate(served):
            ref = files["reference"].forward_last(
                toy, SEED, [ids[r, :16 + j + 1] for r in range(3)])
            assert _close(buf.tensors[0].np(), ref)
            assert np.array_equal(buf.tensors[1].np(), ref.argmax(-1))
        stats = STATE_STATS.snapshot()
        row = 2 * 2 * 16 * 4
        assert stats["steps"] == 4
        assert stats["window_bytes_read"] == 4 * 3 * WINDOW * row * 4
        assert stats["full_bytes_read"] == sum(
            3 * (16 + j + 1) for j in range(4)) * row * 2
        assert stats["cache_bytes_read"] == stats["window_bytes_read"] \
            + stats["full_bytes_read"]
        assert stats["window_bytes_fetched"] == 4 * 3 * 12 * row * 4
        assert stats["full_bytes_fetched"] == 4 * 3 * POSITIONS * row * 2
        assert stats["cache_bytes_fetched"] == stats["window_bytes_fetched"] \
            + stats["full_bytes_fetched"]
        assert stats["state_bytes"] == cell.state_bytes
        pre.stop()
        run.stop()
        assert STATE_STATS.snapshot()["state_bytes"] == 0
    finally:
        unregister_model(name)
        SHARED_MODELS.clear()
