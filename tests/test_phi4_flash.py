"""``models/phi4_flash.py`` on the CPU at toy sizes, seeded weights, the
program in float32 against a plain forward of the issue's equations
(differential attention head by head at the head's own size, the
Mamba-1 recurrence a token at a time, no cache, no chunk, no skip):
prefill in chunks then decode through the rings and the one shared
cache; a prefill that stops at layer ``F`` against one that runs every
layer on every token; the rewind to a prompt's end; and named faults,
each of which has to move the logits by more than the toy limit."""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models import attention, phi4_flash  # noqa: E402
from nnstreamer_tpu.models import streams as stream  # noqa: E402

RAW = dict(hidden_size=128, intermediate_size=192, num_attention_heads=8,
           num_key_value_heads=4, num_hidden_layers=8, sliding_window=16,
           layer_norm_eps=1e-5, vocab_size=96, max_position_embeddings=512,
           mb_per_layer=2, tie_word_embeddings=True, hidden_act="silu",
           mlp_bias=False, lm_head_bias=False, model_type="phi4flash")
CHUNK, POSITIONS = 16, 256
#: prompts inside the window (shorter than 16), past it, and past the
#: ring's 128 slots (so that the ring has wrapped), the last two ragged
PROMPTS = (10, 40, 150)
ANSWER = 6
SOUND, LIMIT = 2e-5, 1e-2


@pytest.fixture(scope="module")
def cfg():
    return phi4_flash.Phi4FlashConfig.from_dict(RAW)


@pytest.fixture(scope="module")
def params(cfg):
    return phi4_flash.init_params(cfg, 7, jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(11)
    return [rng.integers(0, RAW["vocab_size"], n + ANSWER).astype(np.int32)
            for n in PROMPTS]


# -- the plain forward ----------------------------------------------------------------


def _ln(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + RAW["layer_norm_eps"]) * p["g"] + p["b"]


def _plain_mamba(cfg, p, u):
    """``(Mixer(u), y)`` over a whole history, a token at a time."""
    d, n, r, k = (cfg.mamba.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv)
    sz = u @ p["in_proj"]
    s, z = sz[:, :d], sz[:, d:]
    past = jnp.concatenate([jnp.zeros((k - 1, d)), s])
    c = jax.nn.silu(p["conv_b"] + sum(past[i:i + len(u)] * p["conv_w"][i]
                                      for i in range(k)))
    dbc = c @ p["x_proj"]
    delta = jax.nn.softplus(dbc[:, :r] @ p["dt_proj"] + p["dt_bias"])
    b, cc = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(p["A_log"])                                  # [n, d]

    def token(h, t):
        dl, ct, bt, c_t = t
        h = jnp.exp(dl[None] * a) * h + bt[:, None] * (dl * ct)[None]
        return h, (h * c_t[:, None]).sum(0)

    _, y = jax.lax.scan(token, jnp.zeros((n, d)), (delta, c, b, cc))
    y = y + p["D"] * c
    return (y * jax.nn.silu(z)) @ p["out_proj"], y


def _plain_attention(cfg, p, layer, u, kv, window):
    """Differential attention head by head: query pair ``i`` reads K/V
    pair ``i // 2``; ``kv = (k [T, pairs, 2, d], v [T, pairs, 2 d])``."""
    t, hd = len(u), cfg.head_dim
    q = (u @ p["q"] + p["q_b"]).reshape(t, cfg.heads // 2, 2, hd)
    k, v = kv
    at = np.arange(t)
    seen = (at[None] <= at[:, None]) & (at[None] > at[:, None] - window)
    lam = jnp.exp(p["lq1"] @ p["lk1"]) - jnp.exp(p["lq2"] @ p["lk2"]) \
        + cfg.lambda_init(layer)
    outs = []
    for i in range(cfg.heads // 2):
        j = i // 2
        a = []
        for w in range(2):
            s = q[:, i, w] @ k[:, j, w].T / np.sqrt(hd)
            a.append(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
                     @ v[:, j])
        d = a[0] - lam * a[1]
        d = d / jnp.sqrt((d * d).mean(-1, keepdims=True)
                         + RAW["layer_norm_eps"]) * p["subln"]
        outs.append((1 - cfg.lambda_init(layer)) * d)
    return jnp.concatenate(outs, -1) @ p["o"] + p["o_b"]


def _plain_kv(cfg, p, u):
    kv = (u @ p["kv"] + p["kv_b"]).reshape(len(u), 2, cfg.kv_pairs,
                                           cfg.pair_dim)
    return (kv[:, 0].reshape(len(u), cfg.kv_pairs, 2, cfg.head_dim),
            kv[:, 1])


@jax.jit
def _plain(params, ids):
    """Logits ``[T, vocab]`` of one stream's whole history."""
    cfg = phi4_flash.Phi4FlashConfig.from_dict(RAW)
    x = params["embed"][ids]
    memory = shared = None
    for i, layer in enumerate(params["layers"]):
        u, p, kind = _ln(x, layer["norm"]), layer["mixer"], cfg.kind(i)
        if kind == "mamba":
            out, memory = _plain_mamba(cfg, p, u)
        elif kind == "gmu":
            out = (memory * jax.nn.silu(u @ p["in"])) @ p["out"]
        elif kind == "attn_cross":
            out = _plain_attention(cfg, p, i, u, shared, len(ids))
        else:
            kv = _plain_kv(cfg, p, u)
            if kind == "attn_full":
                shared = kv
            out = _plain_attention(
                cfg, p, i, u, kv,
                cfg.window if kind == "attn_window" else len(ids))
        x = x + out
        gu = _ln(x, layer["mlp_norm"]) @ layer["mlp"]["gate_up"]
        f = cfg.intermediate_size
        x = x + (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ layer["mlp"]["down"]
    return _ln(x, params["final_norm"]) @ params["embed"].T


# -- the program through its entry points ---------------------------------------------


def _i32(v):
    return jnp.asarray([v], jnp.int32)


@pytest.fixture(scope="module")
def programs(cfg):
    return {"prefill": jax.jit(lambda p, s, *a: phi4_flash.prefill(
                cfg, p, s, *a)),
            "prefill_all": jax.jit(lambda p, s, *a: phi4_flash.prefill(
                cfg, p, s, *a, skip=False)),
            "decode": jax.jit(lambda p, s, *a: phi4_flash.decode(
                cfg, p, s, *a))}


def _prefilled(cfg, params, tokens, program):
    """The state after every stream's prompt, and the logits each
    prompt's last chunk served."""
    state = phi4_flash.init_state(cfg, params, len(PROMPTS), POSITIONS, CHUNK)
    served = []
    for slot, (n, ids) in enumerate(zip(PROMPTS, tokens)):
        for start in range(0, n, CHUNK):
            part = ids[start:min(start + CHUNK, n)]
            chunk = np.zeros(CHUNK, np.int32)
            chunk[:len(part)] = part
            state, (logits, greedy) = program(
                params, state, jnp.asarray(chunk), _i32(slot), _i32(start),
                _i32(len(part)))
        served.append((np.asarray(logits[0]), int(greedy[0])))
    return state, served


@pytest.fixture(scope="module")
def prefilled(cfg, params, tokens, programs):
    return _prefilled(cfg, params, tokens, programs["prefill"])


def _answers(params, state, tokens, programs, steps=ANSWER):
    """``steps`` decode steps of every stream from its prompt's end:
    ``(state, logits [steps, streams, vocab])``."""
    out = []
    for t in range(steps):
        ids = jnp.asarray([tok[n + t] for n, tok in zip(PROMPTS, tokens)])
        at = jnp.asarray([n + t for n in PROMPTS], jnp.int32)
        state, (logits, greedy) = programs["decode"](params, state, ids, at)
        assert np.array_equal(greedy, np.argmax(logits, -1))
        out.append(np.asarray(logits))
    return state, np.stack(out)


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def whole(params, tokens):
    return [np.asarray(_plain(params, jnp.asarray(ids))) for ids in tokens]


@pytest.mark.parametrize("slot", range(len(PROMPTS)),
                         ids=[f"prompt{n}" for n in PROMPTS])
def test_prefill_in_chunks_serves_the_full_forwards_logits(prefilled, whole,
                                                           slot):
    _state, served = prefilled
    logits, greedy = served[slot]
    want = whole[slot][PROMPTS[slot] - 1]
    assert _rel(logits, want) < SOUND
    assert greedy == int(np.argmax(logits))


@pytest.mark.parametrize("slot", range(len(PROMPTS)),
                         ids=[f"prompt{n}" for n in PROMPTS])
def test_decode_through_the_caches_equals_the_full_forward(
        params, tokens, programs, prefilled, whole, slot):
    """Inside the window, past it and past the ring's wrap: every decoded
    position's logits are the plain forward's."""
    state, _served = prefilled
    _state, got = _answers(params, state, tokens, programs)
    n = PROMPTS[slot]
    for t in range(ANSWER):
        assert _rel(got[t, slot], whole[slot][n + t]) < SOUND, t


def test_the_skip_is_exact(cfg, params, tokens, programs, prefilled):
    """A prefill that runs layers above ``F`` on one token a chunk leaves
    every state a prefill of all 32 layers on every token leaves, and
    serves the same logits; only the count of cross-decoder tokens
    differs."""
    state, served = prefilled
    full, served_all = _prefilled(cfg, params, tokens,
                                  programs["prefill_all"])
    for (a, ga), (b, gb) in zip(served, served_all):
        assert _rel(a, b) < SOUND and ga == gb
    counters, counters_all = state.pop("counters"), full.pop("counters")
    same = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), state, full)
    assert all(jax.tree_util.tree_leaves(same)), same
    state["counters"] = counters
    chunks = sum(-(-n // CHUNK) for n in PROMPTS)
    assert int(counters["prefill_tokens"]) == sum(PROMPTS) \
        == int(counters_all["prefill_tokens"]) \
        == int(counters_all["cross_tokens"])
    assert int(counters["cross_tokens"]) == chunks


def test_a_rewind_restores_all_the_states_and_the_rings_room(
        cfg, params, tokens, programs, prefilled):
    """The pass after a rewind to the prompts' ends serves the first
    pass's logits: the recurrent states start from their snapshots, the
    rings' stale rows lie within their room, the shared cache is masked
    by position.  A rewind from beyond the rings' room is a fault."""
    state, _served = prefilled
    state, first = _answers(params, state, tokens, programs)
    live = [np.asarray(st["ssm"]) for st in state["mamba"]]
    state, again = _answers(params, state, tokens, programs)
    assert np.array_equal(first, again)
    for st, was in zip(state["mamba"], live):
        assert np.array_equal(st["ssm"], was)
        assert not np.array_equal(st["ssm"], st["ssm_snap"])
    counters = state["counters"]
    assert len(state["mamba"]) == cfg.count("mamba") == 3
    assert int(counters["restores"]) == 2 * len(PROMPTS)
    assert int(counters["position_faults"]) == 0
    assert int(counters["steps"]) == 2 * ANSWER
    # the rings hold window + chunk rows in cells of 128: a room of 112
    room = state["rings"][0]["k"].shape[2] - cfg.window
    assert room == 112
    spoilt = dict(state, newest=state["newest"] + room)
    _state, _ = _answers(params, spoilt, tokens, programs, steps=1)
    assert int(_state["counters"]["position_faults"]) == len(PROMPTS)
    # a position that is neither the prompt's end nor the one after the last
    ids = jnp.zeros(len(PROMPTS), jnp.int32)
    skipped, _ = programs["decode"](
        params, state, ids, jnp.asarray([n + ANSWER + 1 for n in PROMPTS]))
    assert int(skipped["counters"]["position_faults"]) == len(PROMPTS)


def test_counters_count_every_reader_of_the_shared_cache(cfg, params, tokens,
                                                         programs, prefilled):
    state, _served = prefilled
    state, _ = _answers(params, state, tokens, programs, steps=1)
    units = phi4_flash.counter_units(cfg, state)
    row = 2 * cfg.kv_pairs * cfg.pair_dim * 4
    # layer F and the one cross layer of the toy's eight
    assert units["shared_kv_bytes_read"] == ("shared_rows_read", row * 2)
    assert units["ring_kv_bytes_read"] == ("ring_rows_read", row * 2)
    assert units["cache_bytes_read"] == [units["shared_kv_bytes_read"],
                                         units["ring_kv_bytes_read"]]
    per = 4 * cfg.d_state * cfg.mamba.d_inner + 4 * 3 * cfg.mamba.d_inner
    assert units["ssm_bytes"] == ("ssm_rows", 2 * per * 3)
    counters = state["counters"]
    assert int(counters["shared_rows_read"]) == sum(n + 1 for n in PROMPTS)
    assert int(counters["ring_rows_read"]) == sum(min(n + 1, 16)
                                                  for n in PROMPTS)
    # toy rows are not whole lanes: the jnp path reads the caches whole
    assert int(counters["shared_rows_fetched"]) == len(PROMPTS) * POSITIONS
    assert int(counters["ring_rows_fetched"]) == len(PROMPTS) * 128
    assert set(counters) == set(phi4_flash.COUNTERS)


# -- the pair layout ------------------------------------------------------------------


def test_the_pair_layout_is_differential_attention_head_by_head(cfg, params):
    """``[q1 | 0]`` and ``[0 | q2]`` against rows ``[k1 | k2]`` through
    ``attention.decode_step`` give ``a1`` and ``a2`` of the heads taken
    one at a time at their own size."""
    rng = np.random.default_rng(5)
    p = params["layers"][1]["mixer"]
    u = jnp.asarray(rng.normal(size=(2, cfg.hidden_size)), jnp.float32)
    q, k, v = phi4_flash._qkv(cfg, p, u)
    assert q.shape == (2, cfg.kv_pairs, cfg.rows, cfg.pair_dim) \
        == (2, 2, 4, 32)
    hd = cfg.head_dim
    # the zero lanes
    assert not np.asarray(q[:, :, 0::2, hd:]).any()
    assert not np.asarray(q[:, :, 1::2, :hd]).any()
    cache = attention.kv_cache(2, cfg.kv_pairs, 128, cfg.pair_dim,
                               jnp.float32)
    cache = {name: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
             for name, a in cache.items()}
    at = jnp.asarray([5, 40], jnp.int32)
    o, cache = attention.decode_step(q, k, v, cache, at, 128, hd ** -0.5)
    raw = (u @ p["q"] + p["q_b"]).reshape(2, cfg.heads // 2, 2, hd)
    for b in range(2):
        n = int(at[b]) + 1
        for i in range(cfg.heads // 2):
            j = i // 2
            for w in range(2):
                keys = cache["k"][b, j, :n, w * hd:(w + 1) * hd]
                prob = jax.nn.softmax(keys @ raw[b, i, w] / np.sqrt(hd))
                want = prob @ cache["v"][b, j, :n]
                got = o[b, j, 2 * (i % 2) + w]
                assert np.allclose(got, want, atol=1e-5), (b, i, w)


def test_the_configuration_is_the_published_one_and_refuses_the_rest():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "phi4_mini_flash_reasoning.json")
    import json
    with open(path) as f:
        cfg = phi4_flash.Phi4FlashConfig.from_dict(json.load(f))
    assert (cfg.layers, cfg.full_layer, cfg.head_dim, cfg.pair_dim,
            cfg.kv_pairs, cfg.rows, cfg.window) == (32, 17, 64, 128, 10, 4,
                                                    512)
    kinds = [cfg.kind(i) for i in range(32)]
    assert kinds[:18] == ["mamba", "attn_window"] * 8 + ["mamba", "attn_full"]
    assert kinds[18:] == ["gmu", "attn_cross"] * 7
    assert (cfg.mamba.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) \
        == (5120, 16, 4, 160)
    assert round(cfg.lambda_init(17), 4) == 0.7963
    assert cfg.ring(1024) == 1536
    sizes = jax.tree_util.tree_leaves(
        phi4_flash.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
    assert round(sum(int(np.prod(s)) for s, _ in sizes) / 1e9, 2) == 3.85
    for key, value in (("tie_word_embeddings", False), ("mlp_bias", True),
                       ("hidden_act", "gelu"), ("mb_per_layer", 4),
                       ("num_hidden_layers", 30),
                       ("num_key_value_heads", 5)):
        with pytest.raises(ValueError, match="phi4flash"):
            phi4_flash.Phi4FlashConfig.from_dict(dict(RAW, **{key: value}))


# -- named faults ---------------------------------------------------------------------


def _stale_memory(monkeypatch):
    """The gated memory units read the memory of the token BEFORE."""
    real = phi4_flash._gmu
    monkeypatch.setattr(phi4_flash, "_gmu", lambda p, u, m: real(
        p, u, jnp.concatenate([jnp.zeros_like(m[:1]), m[:-1]])))


def _cross_reads_a_ring(monkeypatch):
    """The cross layers see the window's 16 positions of the cache, as
    if they read a ring: layer ``F`` still sees every position."""
    real, calls = attention.attend_chunk, []

    def attend_chunk(q, cache, slot, start, window, hp, scale=None):
        if window == cache["k"].shape[2]:
            calls.append(None)
            if len(calls) > 1:
                window = RAW["sliding_window"]
        return real(q, cache, slot, start, window, hp, scale)

    monkeypatch.setattr(attention, "attend_chunk", attend_chunk)


def _no_lambda(monkeypatch):
    monkeypatch.setattr(phi4_flash, "_lambda", lambda cfg, p, layer: 0.0)


def _late_row(monkeypatch):
    """Layer ``F`` writes its K/V rows a position late."""
    real = attention.write_chunk

    def write_chunk(k, v, cache, slot, positions, window):
        late = window == cache["k"].shape[2]
        return real(k, v, cache, slot, positions + late, window)

    monkeypatch.setattr(attention, "write_chunk", write_chunk)


@pytest.mark.parametrize("fault", [None, _stale_memory, _cross_reads_a_ring,
                                   _no_lambda, _late_row],
                         ids=lambda f: f.__name__.strip("_") if f else "sound")
def test_named_faults_fail_the_toy_limits(cfg, params, monkeypatch, fault):
    """One chunk of 64 tokens through every layer (``skip=False``)
    against the plain forward: sound it agrees, and each named fault
    moves the last token's logits by more than the limit."""
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, RAW["vocab_size"], 64), jnp.int32)
    if fault is not None:
        fault(monkeypatch)
    state = phi4_flash.init_state(cfg, params, 1, 128, 64)
    _state, (logits, _greedy) = phi4_flash.prefill(
        cfg, params, state, ids, _i32(0), _i32(0), _i32(64), skip=False)
    monkeypatch.undo()
    got = _rel(np.asarray(logits[0]), np.asarray(_plain(params, ids))[-1])
    assert (got < SOUND) if fault is None else (got > LIMIT), got


def test_entries_name_the_two_schemas(cfg):
    table = phi4_flash.entries(cfg, streams=4, positions=128, chunk=16)
    assert table["entries"]["decode"][1] == [(4,), (4,)]
    assert table["entries"]["prefill"][1] == [(16,), (1,), (1,), (1,)]
    assert table["setup_entries"] == ("prefill",)
    assert table is phi4_flash.entries(cfg, streams=4, positions=128,
                                       chunk=16)
    stream.entries.cache_clear()
