"""Falcon-H1 as a stateful model (``nnstreamer_tpu/models/falcon_h1.py``)
at toy widths of whole lanes on the CPU (both decode kernels
interpreted): prefill in padded chunks and decode through BOTH states of
every layer against the benchmark's plain reference, the snapshot at the
prompt's end and who starts from it, every multiplier where the source
applies it, and two launch lines on one state.  No number here is a
rate."""

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
from jax import lax  # noqa: E402

from benchmark.run import Loader  # noqa: E402
from nnstreamer_tpu.filters.api import SHARED_MODELS  # noqa: E402
from nnstreamer_tpu.filters.jax_xla import unregister_model  # noqa: E402
from nnstreamer_tpu.models import falcon_h1 as fh  # noqa: E402
from nnstreamer_tpu.models import mamba2, moe  # noqa: E402
from nnstreamer_tpu.runtime import parse_launch  # noqa: E402
from nnstreamer_tpu.utils.stats import STATE_STATS  # noqa: E402

SEED = 11
CHUNK, POSITIONS = 8, 128
LENGTHS = (13, 24, 9)       # a padded last chunk, whole chunks, two chunks
STEPS = 4
FILES = "falcon_h1_34b_stage4_vocab8"
# float32 program against the float32 reference, the largest difference
# over the reference's largest logit: rounding in another order, nothing
# else (it reads 6e-7 to 9e-7 here).  The bfloat16 program reads 6e-3,
# so bf16 in float32's place fails this 300 times over.
F32_TOL = 2e-5
# the bfloat16 program against the float32 reference, relative L2 a
# row: rounding to 8 bits of mantissa through two layers reads 0.005 to
# 0.008; the mildest fault below (mu_B on C's columns) reads 0.056
BF16_TOL = 0.02


@pytest.fixture(scope="module")
def toy():
    """The toy twin of the benchmark's configuration: hidden 128, two
    layers, Mamba-2 of 4 heads of 64 over 2 groups with a state of 128
    (scan chunks of 16), 10 query heads over 2 key/value heads of 128,
    an MLP of 256, vocabulary rows 64-127, every multiplier as
    published."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_falconh1.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def files():
    loader = Loader(REPO)
    return {kind: loader.module(kind, FILES)
            for kind in ("weights", "reference", "costs")}


def _cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, tree)


def _model(cfg, params):
    return {"cfg": cfg, "params": params,
            "prefill": jax.jit(lambda p, s, *x: fh.prefill(cfg, p, s, *x)),
            "decode": jax.jit(lambda p, s, *x: fh.decode(cfg, p, s, *x))}


@pytest.fixture(scope="module")
def model(toy, files):
    return _model(fh.FalconH1Config.from_dict(toy),
                  _cast(files["weights"].make(toy, SEED), jnp.float32))


def _prefill(model, state, row, ids, chunk=CHUNK):
    """``ids`` of stream ``row`` from position 0 in chunks of ``chunk``,
    the last padded with the first held id and told its count."""
    for at in range(0, len(ids), chunk):
        part = np.full(chunk, model["cfg"].vocab0, np.int32)
        real = ids[at:at + chunk]
        part[:len(real)] = real
        state, _ = model["prefill"](
            model["params"], state, part, np.array([row], np.int32),
            np.array([at], np.int32), np.array([len(real)], np.int32))
    return state


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


def _serve(model, passes=2):
    """Three streams: prompts of 13, 24 and 9 tokens prefilled in chunks
    of 8, then ``passes`` passes of 4 decode steps with a rewind to each
    prompt's end between them."""
    cfg = model["cfg"]
    ids = _ids(cfg, (3, max(LENGTHS) + STEPS), 5)
    state = fh.init_state(cfg, model["params"], 3, POSITIONS)
    for r, n in enumerate(LENGTHS):
        state = _prefill(model, state, r, ids[r, :n])
    out = {"ids": ids, "prefilled": jax.tree_util.tree_map(np.asarray, state),
           "passes": [], "counters": []}
    for _ in range(passes):
        state, got = _answer(model, state, ids)
        out["passes"].append(got)
        out["counters"].append(jax.device_get(state["counters"]))
    return out


@pytest.fixture(scope="module")
def served(model):
    return _serve(model)


def _reference(toy, files, ids, step, **kw):
    return files["reference"].forward_last(
        toy, SEED, [ids[r, :n + step + 1] for r, n in enumerate(LENGTHS)],
        **kw)


def _off(got, ref):
    """The largest difference, relative to the reference's largest."""
    return np.abs(got - ref).max() / max(1.0, np.abs(ref).max())


def _rel(got, ref):
    return np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


# -- both states, against the reference ----------------------------------------------


@pytest.mark.parametrize("step", range(STEPS))
def test_prefill_then_decode_is_the_reference_at_every_position(
        toy, files, served, step):
    """Logits, not ids: chunked prefill of both kinds of state (a padded
    last chunk), then one token a step through both decode kernels,
    against the reference's full forward over the whole history."""
    ref = _reference(toy, files, served["ids"], step)
    assert _off(served["passes"][0][step], ref) <= F32_TOL
    # the branches are all there to see: none vanished under its multiplier
    assert np.abs(ref).max() > 1.0


def test_a_rewind_restores_from_the_snapshot(toy, files, model, served):
    """A second pass from each prompt's end equals the first bit for bit;
    with the snapshots zeroed it does not: the check sees the recurrent
    state of every layer."""
    first, second = served["passes"]
    assert np.array_equal(first, second)
    assert [c["restores"] for c in served["counters"]] == [3, 6]
    assert served["counters"][1]["position_faults"] == 0
    for layer in served["prefilled"]["layers"]:
        for name in ("ssm", "conv"):
            assert np.array_equal(layer[name], layer[name + "_snap"])
            assert np.abs(layer[name]).max() > 0
    assert served["prefilled"]["prompt_end"].tolist() == list(LENGTHS)
    state = jax.tree_util.tree_map(jnp.asarray, served["prefilled"])
    state["layers"] = [dict(layer, ssm_snap=jnp.zeros_like(layer["ssm"]),
                            conv_snap=jnp.zeros_like(layer["conv"]))
                       for layer in state["layers"]]
    _, lost = _answer(model, state, served["ids"], steps=1)
    ref = _reference(toy, files, served["ids"], 0)
    assert _rel(lost[0], ref).min() > 0.05


def test_bf16_is_close_and_fails_the_float32_comparison(toy, files):
    """The program in the configuration's own type: within ``BF16_TOL``
    of the float32 reference a row, and far outside ``F32_TOL``."""
    bf16 = _model(fh.FalconH1Config.from_dict(toy),
                  files["weights"].make(toy, SEED))
    assert bf16["params"]["layers"][0]["mlp"]["up"].dtype == jnp.bfloat16
    got = _serve(bf16, passes=1)
    for layer in got["prefilled"]["layers"]:
        assert layer["ssm"].dtype == np.float32
        assert layer["conv"].dtype == layer["k"].dtype == jnp.bfloat16
    for step in (0, STEPS - 1):
        ref = _reference(toy, files, got["ids"], step)
        rel = _rel(got["passes"][0][step], ref)
        assert rel.max() < BF16_TOL, rel
        assert _off(got["passes"][0][step], ref) > 10 * F32_TOL


# -- each fault fails the comparison -----------------------------------------------


def _swapped_b_c(cfg):
    z, x, b, c, dt = cfg.ssm_multipliers
    return dataclasses.replace(cfg, ssm_multipliers=(z, x, c, b, dt))


PROGRAM_FAULTS = {
    "no_m_k": lambda cfg: dataclasses.replace(cfg, key_multiplier=1.0),
    "no_m_so": lambda cfg: dataclasses.replace(cfg, ssm_out_multiplier=1.0),
    "no_m_ao": lambda cfg: dataclasses.replace(
        cfg, attention_out_multiplier=1.0),
    "no_m_g": lambda cfg: dataclasses.replace(
        cfg, mlp_multipliers=(1.0, cfg.mlp_multipliers[1])),
    "mu_B_on_C": _swapped_b_c}


@pytest.mark.parametrize("fault", sorted(PROGRAM_FAULTS))
def test_a_multiplier_dropped_or_misplaced_fails(toy, files, model, served,
                                                 fault):
    """The program with one multiplier left out (``m_k``, ``m_so``,
    ``m_ao``, ``m_g``) or ``mu_B`` on ``C``'s columns, against the sound
    reference: far outside the tolerance at the first decode step."""
    wrong = _model(PROGRAM_FAULTS[fault](model["cfg"]), model["params"])
    got = _serve(wrong, passes=1)["passes"][0][0]
    ref = _reference(toy, files, served["ids"], 0)
    assert _off(got, ref) > 50 * F32_TOL, fault
    assert _rel(got, ref).min() > 0.02, fault


@pytest.mark.parametrize("fault", ["attn_unnormed", "gate_after_norm"])
def test_a_misplaced_norm_fails(toy, files, served, fault):
    """Attention fed ``x`` instead of ``rms(x)``, and the gate after the
    norm: the reference computed so against what the sound program
    served."""
    wrong = _reference(toy, files, served["ids"], 0, faults=(fault,))
    got = served["passes"][0][0]
    assert _off(got, wrong) > 50 * F32_TOL, fault
    assert _rel(got, wrong).min() > 0.02, fault


def test_a_stream_not_restored_at_its_prompts_end_fails(toy, files, model,
                                                        served):
    """The second pass with a book that does not know where the prompts
    end: every stream goes on from its live state (and is counted as a
    fault); the cache cannot tell, the recurrent state can."""
    state = jax.tree_util.tree_map(jnp.asarray, served["prefilled"])
    state, _ = _answer(model, state, served["ids"])
    state["prompt_end"] = jnp.full((3,), -7, jnp.int32)
    state, again = _answer(model, state, served["ids"], steps=1)
    assert jax.device_get(state["counters"])["position_faults"] == 3
    ref = _reference(toy, files, served["ids"], 0)
    assert _off(served["passes"][0][0], ref) <= F32_TOL
    assert _off(again[0], ref) > 50 * F32_TOL
    assert _rel(again[0], ref).min() > 0.02


def test_the_convolutions_state_taken_at_the_chunks_end_fails(
        toy, files, model, served, monkeypatch):
    """A padded last chunk whose convolution state is its last three
    rows (padding) and not the three before ``count``: streams 0 and 2
    (padded) leave the reference, stream 1 (whole chunks) does not."""

    class ChunkEnd:
        def __getattr__(self, name):
            return getattr(lax, name)

        @staticmethod
        def dynamic_slice_in_dim(x, _start, size):
            return lax.dynamic_slice_in_dim(x, x.shape[0] - size, size)

    monkeypatch.setattr(mamba2, "lax", ChunkEnd())
    wrong = _model(model["cfg"], model["params"])
    got = _serve(wrong, passes=1)["passes"][0][0]
    ref = _reference(toy, files, served["ids"], 0)
    rel = _rel(got, ref)
    assert rel[0] > 0.02 and rel[2] > 0.02, rel
    assert np.abs(got[1] - ref[1]).max() <= F32_TOL * max(
        1.0, np.abs(ref).max())


# -- counters, configuration, scopes -------------------------------------------------


def test_the_steps_count_what_they_touch(model, served):
    cfg, once = model["cfg"], served["counters"][0]
    assert once["steps"] == STEPS and once["ssm_rows"] == STEPS * 3
    assert once["kv_rows_read"] == sum(n + j + 1 for n in LENGTHS
                                       for j in range(STEPS))
    # heads of 128 on one lattice cell of 128 rows: the kernel fetches it
    assert once["kv_rows_fetched"] == STEPS * 3 * POSITIONS
    state = fh.init_state(cfg, model["params"], 3, POSITIONS)
    units = fh.counter_units(cfg, state)
    row = 4 * 64 * 128 * 4 + 3 * (256 + 2 * 2 * 128) * 4
    # EVERY layer counts in both kinds: two layers here
    assert units["ssm_bytes"] == ("ssm_rows", 2 * row * 2)
    assert units["kv_bytes_read"] == units["cache_bytes_read"] \
        == ("kv_rows_read", 2 * 2 * 128 * 4 * 2)
    assert units["kv_bytes_fetched"] == units["cache_bytes_fetched"] \
        == ("kv_rows_fetched", 2 * 2 * 128 * 4 * 2)
    assert set(once) == set(fh.COUNTERS)
    assert not {"experts_touched", "expert_hits"} & set(fh.COUNTERS)


def test_a_position_the_state_cannot_serve_is_counted(model, served):
    state = jax.tree_util.tree_map(jnp.asarray, served["prefilled"])
    ids = served["ids"]
    state, _ = _answer(model, state, ids, steps=2)
    at = np.array([LENGTHS[0] + 2, LENGTHS[1] + 3, LENGTHS[2]], np.int32)
    state, _ = model["decode"](model["params"], state, ids[:, 0], at)
    got = jax.device_get(state["counters"])
    assert got["position_faults"] == 1 and got["restores"] == 3 + 1


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")


def test_the_configuration_is_read_as_published(toy):
    real = Loader(REPO).config(FILES)
    cfg = fh.FalconH1Config.from_dict(real)
    assert (cfg.layers, cfg.vocab, cfg.vocab0) == (4, 32640, 0)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.heads, cfg.kv_heads,
            cfg.head_dim, cfg.per_group) == (5120, 21504, 20, 4, 128, 5)
    geo = cfg.mamba
    assert (geo.heads, geo.head_dim, geo.groups, geo.state_size,
            geo.conv_kernel, geo.chunk_size) == (32, 128, 2, 256, 4, 128)
    assert (geo.d_inner, geo.conv_dim, geo.proj_width) == (4096, 5120, 9248)
    assert geo.column_scale == cfg.ssm_multipliers
    scales = geo.column_scales()
    assert scales.shape == (9248,) and scales.dtype == np.float32
    assert [float(scales[i]) for i in (0, 4096, 8192, 8704, 9216)] \
        == [np.float32(m) for m in cfg.ssm_multipliers]
    row = _catalog_row()
    if row is not None:
        whole = fh.FalconH1Config.from_dict(row["config"])
        assert (whole.layers, whole.vocab) == (72, 261120)
        assert dataclasses.replace(whole, layers=4, vocab=32640) == cfg
    # what is not written is refused, not guessed at
    for key, value in (("mamba_norm_before_gate", True),
                       ("attn_layer_indices", [0, 2]),
                       ("rope_scaling", {"type": "yarn"}),
                       ("mlp_bias", True), ("mamba_d_ssm", 8192),
                       ("ssm_multipliers", [1.0, 1.0])):
        with pytest.raises(ValueError):
            fh.FalconH1Config.from_dict(dict(toy, **{key: value}))


def test_the_seeded_law_absorbs_the_multipliers(toy, files):
    """A matrix times the multiplier that follows it has the variance
    ``init.gain`` states; the input projection by segment."""
    layer = files["weights"].make_part(toy, SEED, "layer00")
    gain = toy["init"]["gain"]

    def var(w, m):
        w = np.asarray(w, np.float32)
        return float(np.mean(np.square(w * m)) * w.shape[0])

    assert var(layer["attn"]["k"], toy["key_multiplier"]) \
        == pytest.approx(gain["k"], rel=0.1)
    assert var(layer["attn"]["o"], toy["attention_out_multiplier"]) \
        == pytest.approx(gain["o"], rel=0.1)
    assert var(layer["mamba"]["out_proj"], toy["ssm_out_multiplier"]) \
        == pytest.approx(gain["out_proj"], rel=0.1)
    assert var(layer["mlp"]["gate"], toy["mlp_multipliers"][0]) \
        == pytest.approx(gain["gate"], rel=0.1)
    assert var(layer["mlp"]["down"], toy["mlp_multipliers"][1]) \
        == pytest.approx(gain["down"], rel=0.1)
    w = np.asarray(layer["mamba"]["in_proj"], np.float32)
    edges = np.cumsum([0, 256, 256, 256, 256, 4])
    for i, (g, mu) in enumerate(zip(gain["in_proj"], toy["ssm_multipliers"])):
        part = w[:, edges[i]:edges[i + 1]] * mu * toy["ssm_in_multiplier"]
        assert float(np.mean(np.square(part)) * w.shape[0]) \
            == pytest.approx(g, rel=0.25), i
    # centred: out_proj's columns add up to nothing over its inputs
    out = np.asarray(layer["mamba"]["out_proj"], np.float32)
    assert np.abs(out.mean(axis=0)).max() < 0.02 * np.abs(out).max()


def test_stage_scopes_are_in_the_program_text(model):
    cfg = model["cfg"]
    state = fh.init_state(cfg, model["params"], 3, POSITIONS)
    i32 = np.zeros(3, np.int32)

    def scoped(fn, *x):
        def step(p, s, *x):
            with jax.named_scope("nns.model"):
                return fn(cfg, p, s, *x)
        return jax.jit(step).lower(model["params"], state, *x) \
            .as_text(debug_info=True)

    decode = scoped(fh.decode, i32, i32)
    one = np.zeros(1, np.int32)
    prefill = scoped(fh.prefill, np.zeros(CHUNK, np.int32), one, one, one)
    for text, inner in ((decode, "step"), (prefill, "scan")):
        for scope in ("embed", "head", "state", "layer00/norm",
                      "layer00/mamba/in_proj", "layer01/mamba/conv",
                      f"layer01/mamba/{inner}", "layer00/mamba/gate_norm",
                      "layer01/mamba/out_proj", "layer00/attn/qkv",
                      "layer01/attn/cache_write", "layer01/attn/o",
                      "layer00/mix", "layer01/mlp"):
            assert f"nns.model/{scope}" in text, scope
    assert "layer01/attn/gqa_decode_attention" in decode
    # whole lanes both ways: the step is the kernel, which picks each
    # stream's source itself, so the restore loop is not in the program
    assert mamba2.step_refusal(state["layers"][0]) is None
    assert "nns.model/ssm_restore" not in decode
    assert "nns.model/ssm_restore" not in prefill


def test_the_dense_mlp_gates_under_its_multiplier(model):
    """``silu(m_g W_gate x) * W_up x`` through ``W_down``, without
    ``m_d``; with ``m_g`` left out it is another function."""
    cfg = model["cfg"]
    p = model["params"]["layers"][0]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(3), (24, cfg.hidden_size),
                          jnp.float32)
    m_g = cfg.mlp_multipliers[0]
    by_hand = moe.mm(jax.nn.silu(m_g * moe.mm(x, p["gate"]))
                     * moe.mm(x, p["up"]), p["down"])
    got = np.asarray(fh.dense_mlp(cfg, p, x))
    assert _off(got, np.asarray(by_hand)) <= 1e-5
    no_m_g = dataclasses.replace(cfg, mlp_multipliers=(1.0, 1.0))
    assert _off(np.asarray(fh.dense_mlp(no_m_g, p, x)), got) > 0.1


# -- through the filter ---------------------------------------------------------------


def _pull(sink, n, timeout=120.0):
    out = []
    for _ in range(n):
        buf = sink.pull(timeout=timeout)
        assert buf is not None
        out.append(buf)
    return out


def test_two_launch_lines_prefill_and_decode_on_one_state(toy, files, model):
    """``tensor_filter framework=jax-xla model=<name>`` twice on one
    ``shared-tensor-filter-key``: the schema (four tensors or two) picks
    prefill or decode, both work on one state in which every layer holds
    a recurrent state, its snapshot and a cache, and the counters reach
    ``STATE_STATS``."""
    cfg = model["cfg"]
    SHARED_MODELS.clear()
    STATE_STATS.reset()
    name = "falcon_toy_stream"
    fh.register(name, cfg, model["params"], streams=3, positions=POSITIONS,
                chunk=CHUNK)
    ids = _ids(cfg, (3, 24), 8)
    line = ("device_src name={p}src num_buffers={n} ! tensor_filter "
            "name={p}net framework=jax-xla model=" + name
            + " shared-tensor-filter-key=fh stat-sample-interval-ms=0 "
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
        # three steps, a rewind to the prompts' end, the same three again
        steps = [(ids[:, 13 + j], np.full(3, 13 + j, np.int32))
                 for j in range(3)] * 2
        run = parse_launch(line.format(p="el_", n=len(steps)))
        run["el_src"].frames, run["el_src"].pool_size = steps, len(steps)
        run.start()
        got = _pull(run["el_sink"], len(steps))
        cell = run["el_net"].subplugin._cell
        assert cell is pre["pf_net"].subplugin._cell and cell.refs == 2
        ssm, conv = 3 * 4 * 64 * 128 * 4, 3 * 3 * 768 * 4
        kv = 3 * 2 * POSITIONS * 128 * 4
        assert cell.state_bytes == 2 * (2 * (ssm + conv) + 2 * kv) \
            + 2 * 3 * 4 + len(fh.COUNTERS) * 4
        for j, buf in enumerate(got):
            ref = files["reference"].forward_last(
                toy, SEED, [ids[r, :13 + j % 3 + 1] for r in range(3)])
            assert _off(buf.tensors[0].np(), ref) <= F32_TOL
            assert np.array_equal(buf.tensors[1].np(),
                                  ref.argmax(-1) + cfg.vocab0)
        stats = STATE_STATS.snapshot()
        assert stats["steps"] == 6 and stats["restores"] == 6
        assert stats["position_faults"] == 0
        assert stats["ssm_bytes"] == 6 * 2 * 2 * (ssm + conv)
        assert stats["kv_bytes_read"] == stats["cache_bytes_read"] \
            == 2 * sum(3 * (13 + j + 1) for j in range(3)) \
            * 2 * 2 * 128 * 4 * 2
        pre.stop()
        run.stop()
        assert STATE_STATS.snapshot()["state_bytes"] == 0
    finally:
        unregister_model(name)
        SHARED_MODELS.clear()
