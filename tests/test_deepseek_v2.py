"""DeepSeek-V2 as a chip's share (``nnstreamer_tpu/models/deepseek_v2.py``)
at a small size on the CPU, against the benchmark's plain float32
reference (``benchmark/reference/deepseek_v2_share4.py``, which imports
nothing of the program): the two paths through the cache, the absorbed
against the expanded form of latent attention, the shares adding up to
the uncut layer, routing at its extremes, and YaRN against hand values.
"""

import copy
import json
import math
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
from nnstreamer_tpu.models import deepseek_v2 as dsv2  # noqa: E402
from nnstreamer_tpu.models import mla  # noqa: E402
from nnstreamer_tpu.ops import kernels  # noqa: E402

SEED = 11
STREAMS, POSITIONS, CHUNK, STEPS = 3, 32, 8, 8


@pytest.fixture(scope="module")
def toy():
    """The toy twin of the benchmark's configuration: hidden 64, 2 of 4
    heads, 8 of 16 experts in 4 groups, 2 shared, 1 dense + 2 expert
    layers, 64 of 256 vocabulary rows."""
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_dsv2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def files():
    loader = Loader(REPO)
    return {kind: loader.module(kind, "deepseek_v2_share4")
            for kind in ("weights", "reference", "inputs", "costs")}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


#: the published ``kv_lora_rank`` and ``qk_rope_head_dim`` on the toy's
#: few heads and layers: a cache row packs two positions there
PUBLISHED_LATENT = {"kv_lora_rank": 512, "qk_rope_head_dim": 64}


@pytest.fixture(scope="module", params=["toy", "published-latent-sizes"])
def served(request, toy, files):
    """Every stream's 20 tokens: 12 prefilled in two chunks (the second
    padded), then 8 decode steps through the cache, in float32 (from
    position 12 on, even and odd, across the third chunk's start at 16).
    ``[step][stream]`` logits, and the token ids fed."""
    if request.param != "toy":
        toy = dict(toy, **PUBLISHED_LATENT)
    cfg = dsv2.DeepSeekV2Config.from_dict(toy)
    params = _f32(files["weights"].make(toy, SEED))
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg.vocab, (STREAMS, 12 + STEPS)).astype(np.int32)
    state = dsv2.init_state(cfg, params, STREAMS, POSITIONS, jnp.float32)
    prefill = jax.jit(lambda p, s, *x: dsv2.prefill(cfg, p, s, *x))
    decode = jax.jit(lambda p, s, *x: dsv2.decode(cfg, p, s, *x))
    for r in range(STREAMS):
        for start in (0, CHUNK):
            chunk = np.zeros(CHUNK, np.int32)
            part = ids[r, start:min(start + CHUNK, 12)]
            chunk[:len(part)] = part
            state, _ = prefill(params, state, chunk,
                               np.array([r], np.int32),
                               np.array([start], np.int32))
    logits = []
    for j in range(STEPS):
        state, (lg, greedy) = decode(
            params, state, ids[:, 12 + j],
            np.full(STREAMS, 12 + j, np.int32))
        assert np.array_equal(np.asarray(greedy),
                              np.asarray(lg).argmax(-1) + cfg.vocab0)
        logits.append(np.asarray(lg))
    return {"logits": logits, "ids": ids, "state": state, "cfg": cfg,
            "raw": toy}


@pytest.mark.parametrize("step", range(STEPS))
def test_prefill_then_decode_is_the_reference_at_every_position(
        files, served, step):
    """Prefill in two chunks, then decode steps through the cache,
    against the reference's full forward (expanded form, no cache) over
    the same history, at every decoded position."""
    histories = [served["ids"][r, :12 + step + 1] for r in range(STREAMS)]
    ref = files["reference"].forward_last(served["raw"], SEED, histories)
    got = served["logits"][step]
    assert np.abs(got - ref).max() <= 2e-5 * max(1.0, np.abs(ref).max())


def test_the_steps_count_what_they_read(served):
    counters = {k: int(v) for k, v in served["state"]["counters"].items()}
    assert counters["steps"] == STEPS
    # rows 0..position of every stream at every step
    assert counters["cache_rows_read"] == STREAMS * sum(
        12 + j + 1 for j in range(STEPS))
    cfg = served["cfg"]
    moe = cfg.layers - cfg.first_k_dense_replace
    assert 0 < counters["expert_hits"] <= STEPS * STREAMS * moe \
        * cfg.num_experts_per_tok
    assert 0 < counters["experts_touched"] <= min(
        counters["expert_hits"], STEPS * moe * cfg.experts)
    # what the decode kernel copies for them: a cache of 128 positions
    # is one lattice cell, whole, a stream a step
    assert counters["cache_rows_fetched"] == STEPS * STREAMS * 128
    units = dsv2.counter_units(cfg, served["state"])
    assert units == {
        "cache_bytes_read": ("cache_rows_read", cfg.latent * 4 * cfg.layers),
        "cache_bytes_fetched": ("cache_rows_fetched",
                                cfg.row * 4 * cfg.layers)}
    # a position as the cache holds it: the toy's 24 values in a lane
    # tile, the published 576 in half a row of 1,152
    packed = cfg.kv_lora_rank == 512
    assert (cfg.latent, cfg.row) == ((576, 576) if packed else (24, 128))
    for cache in served["state"]["cache"]:
        assert cache.shape == ((STREAMS, 64, 1152) if packed
                               else (STREAMS, 128, 128))
        assert cache.nbytes == STREAMS * 128 * cfg.row * 4


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.03)])
def test_full_forward_is_the_reference(toy, files, dtype, tol):
    """One chunk over a whole prompt: the logits after its last token,
    for five prompts.  In bfloat16 an expert chosen on a near tie may
    differ from float32's choice and move ONE prompt's logits far, so
    the median is held tight and the worst loosely (the statistic the
    benchmark's check uses)."""
    cfg = dsv2.DeepSeekV2Config.from_dict(toy)
    params = files["weights"].make(toy, SEED + 1)
    if dtype == "float32":
        params = _f32(params)
    prompts = np.random.default_rng(9).integers(
        0, cfg.vocab, (5, 16)).astype(np.int32)
    run = jax.jit(lambda p, s, *x: dsv2.prefill(cfg, p, s, *x))
    got = []
    for ids in prompts:
        state = dsv2.init_state(cfg, params, 2, 16)
        _, (logits, _greedy) = run(params, state, ids,
                                   np.array([1], np.int32),
                                   np.array([0], np.int32))
        got.append(np.asarray(logits)[0])
    ref = files["reference"].forward_last(toy, SEED + 1, list(prompts))
    err = np.linalg.norm(np.stack(got) - ref, axis=-1) \
        / np.linalg.norm(ref, axis=-1)
    assert np.median(err) <= tol and err.max() <= 10 * tol, err


@pytest.mark.parametrize("sizes", [{}, PUBLISHED_LATENT],
                         ids=["toy", "published-latent-sizes"])
def test_absorbed_is_expanded(toy, files, sizes):
    """Both forms of latent attention are one function of the same
    weights: a token decoded through the absorbed form gives what the
    expanded form gives for it as the last row of a chunk, and both
    leave the same cache: a decode step's token in the second half of
    a packed row beside the prefilled first, the other rows as they
    were."""
    toy = dict(toy, **sizes)
    cfg = dsv2.DeepSeekV2Config.from_dict(toy)
    p = _f32(files["weights"].make(toy, SEED))["layers"][1]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (8, cfg.hidden_size))
    cache = mla.init_cache(cfg, 2, 128, jnp.float32)
    one = jnp.int32(1)
    expanded, cache_a = mla.attn_prefill(cfg, p, x, cache, one,
                                          jnp.int32(0))
    # the first seven rows cached, the eighth decoded in stream 1
    _, cache_b = mla.attn_prefill(cfg, p, x.at[7].set(0.0), cache, one,
                                   jnp.int32(0))
    both = jnp.stack([jnp.zeros_like(x[7]), x[7]])
    absorbed, cache_b = mla.attn_decode(cfg, p, both, cache_b,
                                         jnp.array([0, 7], jnp.int32))
    assert np.allclose(np.asarray(absorbed[1]), np.asarray(expanded[7]),
                       atol=2e-5)
    assert np.allclose(np.asarray(cache_b[1]), np.asarray(cache_a[1]),
                       atol=1e-6)
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    rows = np.asarray(kernels.latent_unpack(cache_a[1], rank, rope))
    assert rows.shape == (128, cfg.latent)
    assert np.abs(rows[:8]).min(-1).max() > 0 and not rows[8:].any()
    if sizes:
        # two positions a row and nothing else in it
        assert cache_a.shape == (2, 64, 2 * cfg.latent) and cfg.row == 576
        assert np.array_equal(np.asarray(cache_a[1, 3, :rank]), rows[6, :rank])
        assert np.array_equal(np.asarray(cache_a[1, 3, rank:2 * rank]),
                              rows[7, :rank])
        assert np.array_equal(np.asarray(cache_a[1, 3, 2 * rank:]),
                              np.concatenate([rows[6, rank:], rows[7, rank:]]))
    else:
        # a cache row is (c_kv, k_r) and zeros to whole lanes
        assert cfg.row == 128
        assert not np.asarray(cache_a[1, :8, cfg.latent:]).any()


#: ``heads, width, rank, positions of the cache, dtype, plan, streams at``;
#: without a plan the call derives its own.  The explicit plans put the
#: positions where the walk changes shape, as ``tests/test_decode_walk.py``
#: does for the sibling kernel.
LATENT_CASES = {
    "whole-tiles": (8, 256, 128, 512, "float32", None, [5, 300, 511]),
    # the toy: heads padded to a tile, values out of the whole row
    "toy-f32": (2, 128, 16, 128, "float32", None, [5, 108, 127]),
    "toy-bf16": (3, 128, 24, 256, "bfloat16", None, [5, 172, 255]),
    # in the first cell, on a chunk's last row, on a chunk's first row,
    # at the cache's last row
    "walk-a-chunks-ends": (8, 128, 128, 1024, "float32",
                           kernels.WalkPlan(256, 3), [5, 255, 256, 1023]),
    # chunks of ten cells, as the cell's: a last item of 8 + 1 cells, of
    # 4 + 2 + 1, of one cell after a whole chunk, and two whole chunks
    "walk-pieces-8-4-2-1": (8, 128, 128, 2560, "float32",
                            kernels.WalkPlan(1280, 3),
                            [1041, 868, 1285, 2559]),
    # the queue runs on from a stream of one item into one of four (and
    # from that into one of two); more buffers than a stream has items
    "walk-one-item-before-many": (8, 128, 128, 1024, "float32",
                                  kernels.WalkPlan(256, 4),
                                  [3, 1000, 130, 700]),
    "walk-one-stream": (8, 128, 128, 1024, "float32",
                        kernels.WalkPlan(256, 3), [600]),
    "walk-heads-padded-bf16": (5, 256, 128, 768, "bfloat16",
                               kernels.WalkPlan(384, 2), [0, 383, 384, 767]),
}


@pytest.mark.parametrize("case", list(LATENT_CASES))
def test_latent_kernel_is_its_reference(case):
    """The Pallas kernel (interpreted on the CPU) against its jnp
    mathematics, with streams at different positions."""
    heads, width, rank, positions, dtype, plan, at = LATENT_CASES[case]
    rng = np.random.default_rng(2)
    b = len(at)
    q = jnp.asarray(rng.normal(size=(b, heads, width)), dtype)
    cache = jnp.asarray(rng.normal(size=(b, positions, width)), dtype)
    at = jnp.asarray(at, jnp.int32)
    assert kernels.latent_decode_attention_refusal(
        q.shape, cache.shape, rank) is None
    if plan is None:
        got = kernels.latent_decode_attention(q, cache, at, rank, 0.05)
    else:
        got = kernels._latent_decode_walk(q, cache, at, rank, 0.05, plan)
    want = kernels.latent_decode_attention_reference(q, cache, at, rank, 0.05)
    assert got.shape == (b, heads, rank) and got.dtype == jnp.float32
    assert np.allclose(np.asarray(got), np.asarray(want),
                       atol=2e-5 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("q_shape,cache_shape,rank,says", [
    ((3, 8, 192), (3, 512, 192), 128,
     "want cache rows of 384 for 2 positions, whole lanes of 128, not 192"),
    ((3, 8, 200), (3, 512, 200), 128, "whole lanes"),
    ((3, 8, 256), (3, 512, 128), 128, "whole lanes"),
    ((3, 8, 256), (3, 100, 256), 128,
     "100 cache positions are not whole lattice cells of 128"),
    ((3, 8, 256), (3, 0, 256), 128, "0 cache positions"),
    ((3, 8, 256), (3, 512, 256), 384, "do not hold the 384 latent ones"),
    ((3, 8, 256), (4, 512, 256), 128, "are not [B, heads, width]"),
    ((3, 8, 576), (3, 512, 640), 512,
     "want cache rows of 1152 for 2 positions"),
    ((3, 8, 576), (3, 100, 1152), 512,
     "200 cache positions are not whole lattice cells of 128"),
], ids=["packed-queries-on-padded-rows", "row-not-lanes", "widths-differ",
        "positions-not-lanes", "no-positions", "rank-over-width",
        "streams-differ", "published-queries-on-rows-of-640",
        "packed-positions-not-lanes"])
def test_latent_kernel_refuses_with_an_error(q_shape, cache_shape, rank,
                                             says):
    """No second path: a shape the kernel cannot take is an error that
    says which rule it breaks."""
    with pytest.raises(ValueError, match="latent_decode_attention") as e:
        kernels.latent_decode_attention(
            jnp.zeros(q_shape), jnp.zeros(cache_shape),
            jnp.zeros((q_shape[0],), jnp.int32), rank, 1.0)
    assert says in str(e.value)


# -- the shares add up ------------------------------------------------------------


def _uncut(toy):
    cfg = copy.deepcopy(toy)
    for key, value in toy["published"].items():
        if key != "num_hidden_layers":
            cfg[key] = value
    cfg["published"] = dict(toy["published"])
    cfg["share"] = {"head0": 0, "expert0": 0, "vocab0": 0}
    return cfg


def _share_of(params, cfg_all, cfg_one):
    """The slices of the uncut weights that share ``cfg_one`` holds."""
    h0, nh = cfg_one.head0, cfg_one.heads
    e0, ne = cfg_one.expert0, cfg_one.experts
    v0, nv = cfg_one.vocab0, cfg_one.vocab
    qd, kvd = cfg_all.q_head_dim, cfg_all.qk_nope_head_dim + cfg_all.v_head_dim

    def heads(w, per):          # columns of whole heads
        return w.reshape(w.shape[0], cfg_all.heads, per)[:, h0:h0 + nh] \
            .reshape(w.shape[0], nh * per)

    layers = []
    for layer in params["layers"]:
        a = layer["attn"]
        out = dict(layer, attn=dict(
            a, q_b=heads(a["q_b"], qd), kv_b=heads(a["kv_b"], kvd),
            o=a["o"].reshape(cfg_all.heads, cfg_all.v_head_dim, -1)
            [h0:h0 + nh].reshape(nh * cfg_all.v_head_dim, -1)))
        if "moe" in layer:
            out["moe"] = dict(layer["moe"], experts={
                k: w[e0:e0 + ne] for k, w in layer["moe"]["experts"].items()})
        layers.append(out)
    return dict(params, layers=layers, embed=params["embed"][v0:v0 + nv],
                head=params["head"][:, v0:v0 + nv])


@pytest.fixture(scope="module")
def shares(toy):
    uncut = _uncut(toy)
    cfg_all = dsv2.DeepSeekV2Config.from_dict(uncut)
    params = dsv2.init_params(cfg_all, 4, jnp.float32)
    parts = []
    for k in range(4):
        one = copy.deepcopy(toy)
        one["share"] = {"head0": k, "expert0": k * 4, "vocab0": k * 64}
        one.update(num_attention_heads=1, num_key_value_heads=1,
                   n_routed_experts=4)
        cfg_one = dsv2.DeepSeekV2Config.from_dict(one)
        parts.append((cfg_one, _share_of(params, cfg_all, cfg_one)))
    return uncut, cfg_all, params, parts


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["dense", "moe1", "moe2"])
def test_the_shares_add_up_to_the_uncut_layer(files, shares, layer):
    """Four shares of a layer (a head and a group of four experts each):
    their partial sums, with the residual, the dense MLP and the shared
    experts counted once, are the uncut REFERENCE's layer output."""
    uncut, cfg_all, params, parts = shares
    ref = files["reference"]
    fns = ref._build(uncut, False)
    p_all = params["layers"][layer]
    x = jax.random.normal(jax.random.PRNGKey(layer), (8, cfg_all.hidden_size))
    k_nope, k_r, v = fns["keys_values"](p_all["attn"], p_all["attn_norm"], x)
    want_attn = fns["attend"](p_all["attn"], p_all["attn_norm"], x, k_nope,
                              k_r, v, 0, rows=8)
    if "mlp" in p_all:
        want = fns["dense_mlp"](p_all["mlp"], p_all["mlp_norm"], want_attn)
    else:
        want = ref._moe(fns, uncut, p_all["moe"], p_all["mlp_norm"],
                        want_attn, 8)

    attn = x
    for cfg_one, p_one in parts:
        p = p_one["layers"][layer]
        cache = jnp.zeros((1, 8, cfg_one.row), jnp.float32)
        part, _ = mla.attn_prefill(
            cfg_one, p["attn"], dsv2._rms(x, p["attn_norm"], 1e-6), cache,
            jnp.int32(0), jnp.int32(0))
        attn = attn + part
    assert np.allclose(np.asarray(attn), np.asarray(want_attn), atol=3e-5)

    h = dsv2._rms(attn, p_all["mlp_norm"], 1e-6)
    if "mlp" in p_all:
        got = attn + dsv2._mlp(parts[0][1]["layers"][layer]["mlp"], h)
    else:
        got, hits = attn, 0
        for cfg_one, p_one in parts:
            routed, shared, counts = dsv2.moe_parts(
                cfg_one, p_one["layers"][layer]["moe"], h)
            got = got + routed
            hits += int(counts.sum())
        got = got + shared              # what every chip computes alike
        # every (token, expert) pair landed on exactly one share
        assert hits == 8 * cfg_all.num_experts_per_tok
    assert np.allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_the_vocabulary_slices_concatenate(shares):
    _uncut_cfg, cfg_all, params, parts = shares
    x = jax.random.normal(jax.random.PRNGKey(8), (5, cfg_all.hidden_size))
    whole, greedy = dsv2._head(cfg_all, params, x)
    sliced = [dsv2._head(cfg_one, p_one, x) for cfg_one, p_one in parts]
    got = np.concatenate([np.asarray(lg) for lg, _ in sliced], axis=-1)
    assert np.allclose(got, np.asarray(whole), atol=1e-5)
    # each slice's greedy id is a global id inside the slice
    for k, (_lg, ids) in enumerate(sliced):
        assert ((np.asarray(ids) >= 64 * k)
                & (np.asarray(ids) < 64 * (k + 1))).all()
    # and a slice embeds global ids
    cfg_3, p_3 = parts[3]
    ids = jnp.array([192, 255], jnp.int32)
    assert np.array_equal(np.asarray(dsv2._embed(cfg_3, p_3, ids)),
                          np.asarray(params["embed"][ids]))


# -- routing at its extremes --------------------------------------------------------


def _moe_by_hand(cfg, p, x, idx, weight):
    """Every (token, expert) pair's MLP, one at a time, in numpy."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        for e, w in zip(np.asarray(idx[n]), np.asarray(weight[n])):
            local = int(e) - cfg.expert0
            if not 0 <= local < cfg.experts:
                continue
            g = x[n] @ np.asarray(p["gate"][local], np.float64)
            u = x[n] @ np.asarray(p["up"][local], np.float64)
            out[n] += float(w) * ((g / (1 + np.exp(-g)) * u)
                                  @ np.asarray(p["down"][local], np.float64))
    return out


@pytest.mark.parametrize("case", ["seeded", "all-on-one", "none-held",
                                  "one-token"])
def test_sorted_expert_product_drops_nothing(toy, files, case):
    """The grouped product against a pair-by-pair count by hand, for the
    router's own choice, for every token on ONE held expert (more rows
    than a block), and for no token on a held expert."""
    cfg = dsv2.DeepSeekV2Config.from_dict(toy)
    p = _f32(files["weights"].make(toy, SEED))["layers"][1]["moe"]
    n = 1 if case == "one-token" else 40
    x = jax.random.normal(jax.random.PRNGKey(1), (n, cfg.hidden_size))
    idx, weight = dsv2.route(cfg, x, p["router"])
    if case == "all-on-one":
        idx = jnp.full_like(idx, 5).at[:, 1:].set(
            jnp.arange(8, 8 + idx.shape[1] - 1))       # the rest elsewhere
    elif case == "none-held":
        idx = 8 + idx % 8
    plan = dsv2.dispatch(cfg, idx, n)
    out = dsv2.grouped_experts(p["experts"], x, plan)
    got = jnp.sum(out[plan["dest"]] * weight[..., None], axis=1)
    want = _moe_by_hand(cfg, p["experts"], x, idx, weight)
    assert np.allclose(np.asarray(got), want, atol=2e-5)
    held = (np.asarray(idx) < cfg.experts).sum()
    assert int(plan["counts"].sum()) == held
    if case == "all-on-one":
        assert int(plan["counts"][5]) == n and int(plan["blocks"]) == 1
    if case == "none-held":
        assert int(plan["blocks"]) == 0 and not np.asarray(got).any()


def _dispatch_before_the_move(cfg, idx, n_tokens):
    """``deepseek_v2.dispatch`` as it stood before the expert code moved
    to ``models/moe.py``, kept here word for word."""
    k, held = cfg.num_experts_per_tok, cfg.experts
    blk = int(min(256, -(-n_tokens // 8) * 8))
    pairs = n_tokens * k
    rows = -(-pairs // blk) * blk + held * blk
    local = idx.reshape(-1) - cfg.expert0
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)
    sorted_e = local[order]
    counts = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)
    padded = (counts[:held] + blk - 1) // blk * blk
    pad_end = jnp.cumsum(padded)
    first = jnp.cumsum(counts) - counts
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


def _experts_before_the_move(p, x, plan):
    """``deepseek_v2.grouped_experts`` as it stood before the move."""
    from jax import lax

    blk, rows = plan["blk"], plan["rows"]
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])

    def mm(a, w):
        return jnp.matmul(a, w, preferred_element_type=jnp.float32,
                          precision=lax.Precision.HIGHEST
                          if w.dtype == jnp.float32 else None)

    def body(b, out):
        e = plan["block_expert"][b]
        tok = lax.dynamic_slice(plan["row_token"], (b * blk,), (blk,))
        xb = x_pad[tok]
        h = jax.nn.silu(mm(xb, p["gate"][e])) * mm(xb, p["up"][e])
        ob = mm(h.astype(x.dtype), p["down"][e]).astype(x.dtype)
        return lax.dynamic_update_slice(out, ob, (b * blk, 0))

    return lax.fori_loop(0, plan["blocks"], body,
                         jnp.zeros((rows + 1, x.shape[1]), x.dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_shared_expert_module_gives_the_same_bits(toy, files, dtype):
    """The sort-by-expert plan, the block loop and the combine moved to
    ``models/moe.py`` (which ``smallthinker.py`` runs with ReLU and all
    its experts held): through it ``moe_parts`` gives what the code gave
    where it stood, and ReLU is another function."""
    from nnstreamer_tpu.models import moe

    cfg = dsv2.DeepSeekV2Config.from_dict(toy)
    p = files["weights"].make(toy, SEED)["layers"][2]["moe"]
    if dtype == "float32":
        p = _f32(p)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.hidden_size),
                          p["shared"]["gate"].dtype)
    routed, _shared, counts = jax.jit(
        lambda p, x: dsv2.moe_parts(cfg, p, x))(p, x)

    def before(p, x):
        idx, weight = dsv2.route(cfg, x, p["router"])
        plan = _dispatch_before_the_move(cfg, idx, x.shape[0])
        out = _experts_before_the_move(p["experts"], x, plan)
        return jnp.sum(out[plan["dest"]].astype(jnp.float32)
                       * weight[..., None], axis=1), plan

    want, plan = jax.jit(before)(p, x)
    # the plan and the product bit for bit; the combine (since PR 54 a
    # pick at a time, or the rows walked) to the float32 rounding of
    # six terms summed in another order
    np.testing.assert_allclose(
        np.asarray(routed), np.asarray(want), rtol=0,
        atol=6 * np.finfo(np.float32).eps * float(np.abs(want).max()))
    assert np.array_equal(np.asarray(counts), np.asarray(plan["counts"]))
    mine = dsv2.dispatch(cfg, dsv2.route(cfg, x, p["router"])[0], 40)
    for key in ("row_token", "dest", "block_expert", "blocks", "counts"):
        assert np.array_equal(np.asarray(mine[key]), np.asarray(plan[key]))
    relu = moe.grouped_experts(p["experts"], x, mine, "relu")
    assert not np.array_equal(
        np.asarray(relu), np.asarray(dsv2.grouped_experts(p["experts"], x,
                                                          mine)))
    with pytest.raises(ValueError, match="gelu"):
        moe.grouped_experts(p["experts"], x, mine, "gelu")


def test_router_is_group_limited_and_unnormalised(toy, files):
    cfg = dsv2.DeepSeekV2Config.from_dict(toy)
    router = _f32(files["weights"].make(toy, SEED))["layers"][1]["moe"]["router"]
    x = jax.random.normal(jax.random.PRNGKey(2), (32, cfg.hidden_size))
    idx, weight = dsv2.route(cfg, x, router)
    p = np.asarray(jax.nn.softmax(x @ router, axis=-1), np.float64)
    per = cfg.n_routed_experts // cfg.n_group
    for n in range(32):
        groups = p[n].reshape(cfg.n_group, per).max(-1)
        kept = set(np.argsort(-groups)[:cfg.topk_group])
        assert {int(e) // per for e in idx[n]} <= kept
        allowed = [e for e in range(cfg.n_routed_experts) if e // per in kept]
        best = sorted(allowed, key=lambda e: -p[n, e])[:cfg.num_experts_per_tok]
        assert sorted(int(e) for e in idx[n]) == sorted(best)
        assert np.allclose(np.asarray(weight[n]),
                           p[n, np.asarray(idx[n])] * cfg.routed_scaling_factor,
                           rtol=1e-5)


# -- YaRN ---------------------------------------------------------------------------


def test_yarn_against_hand_values():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "deepseek_v2_share4.json")) as f:
        cfg = dsv2.DeepSeekV2Config.from_dict(json.load(f))
    # correction dimensions of beta_fast 32 and beta_slow 1 at theta 1e4,
    # 64 rope dimensions, original length 4,096: 10.47 -> 10, 22.51 -> 23
    assert dsv2.yarn_correction_range(cfg) == (10, 23)
    inv = dsv2.yarn_inv_freq(cfg)
    plain = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,)
    assert np.allclose(inv[:11], plain[:11], rtol=1e-6)          # kept
    assert np.allclose(inv[23:], plain[23:] / 40, rtol=1e-6)     # / factor
    mid = 16                       # ramp (16 - 10) / 13
    ramp = 6 / 13
    assert math.isclose(inv[mid], plain[mid] / 40 * ramp
                        + plain[mid] * (1 - ramp), rel_tol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert math.isclose(dsv2.yarn_mscale(40, 0.707), m)
    assert math.isclose(m, 1.26080, rel_tol=1e-5)
    assert dsv2.rope_scale(cfg) == 1.0
    assert math.isclose(dsv2.attn_scale(cfg), 192 ** -0.5 * m * m)
    assert math.isclose(dsv2.attn_scale(cfg), 0.114722, rel_tol=1e-5)


def test_the_share_is_whole_groups(toy):
    bad = copy.deepcopy(toy)
    bad["n_routed_experts"] = 6            # groups are of 4
    with pytest.raises(ValueError, match="whole groups"):
        dsv2.DeepSeekV2Config.from_dict(bad)
    cfg = dsv2.DeepSeekV2Config.from_dict(toy)
    assert (cfg.n_routed_experts, cfg.experts, cfg.heads, cfg.vocab) \
        == (16, 8, 2, 64)
    assert cfg.latent == 24 and cfg.row == 128
