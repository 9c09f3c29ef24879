"""``models/streams.py`` and the grouped-query step of
``models/attention.py``: what the five token models share of a stream's
state, held to what each model's own copy did before the move.  The
expected sums of ``test_seeded_params_are_the_parents`` were written at
the parent commit, from each model's own ``init_params``."""

import importlib
import json
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models import attention  # noqa: E402
from nnstreamer_tpu.models import streams as stream  # noqa: E402
from nnstreamer_tpu.ops import kernels  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the book -----------------------------------------------------------------


def _book(newest=None, prompt_end=(10, 10, 10, 10), last=(9, 14, 14, 14)):
    state = {"prompt_end": jnp.asarray(prompt_end, jnp.int32),
             "last": jnp.asarray(last, jnp.int32)}
    if newest is not None:
        state["newest"] = jnp.asarray(newest, jnp.int32)
    return state


def test_a_fresh_book_and_a_prefill_chunks_write():
    fresh = stream.book(3)
    assert set(fresh) == {"prompt_end", "last"}
    assert fresh["prompt_end"].tolist() == [0, 0, 0]
    assert fresh["last"].tolist() == [-1, -1, -1]
    with_newest = stream.book(3, newest=True)
    assert with_newest["newest"].tolist() == [-1, -1, -1]
    after = stream.book_prefilled(with_newest, 1, jnp.int32(13))
    assert after["prompt_end"].tolist() == [0, 13, 0]
    assert after["last"].tolist() == after["newest"].tolist() == [-1, 12, -1]
    assert set(stream.book_prefilled(fresh, 1, jnp.int32(13))) == set(fresh)


@pytest.mark.parametrize("positions, restore, fault", [
    # at the prompt's end, first after a prefill (last + 1 too) and on a
    # rewind; one after the last; anywhere else
    ((10, 10, 15, 12), (1, 1, 0, 0), (0, 0, 0, 1)),
    ((10, 15, 15, 15), (1, 0, 0, 0), (0, 0, 0, 0)),
    ((11, 9, 0, 16), (0, 0, 0, 0), (1, 1, 1, 1)),
])
def test_a_step_restores_at_the_prompts_end_and_counts_a_stray(
        positions, restore, fault):
    state = _book()
    got_restore, got_fault, new = stream.book_step(
        state, jnp.asarray(positions, jnp.int32))
    assert got_restore.tolist() == [bool(v) for v in restore]
    assert got_fault.tolist() == [bool(v) for v in fault]
    assert set(new) == {"prompt_end", "last"}
    assert new["last"].tolist() == list(positions)
    assert new["prompt_end"].tolist() == state["prompt_end"].tolist()


def test_with_a_newest_a_rewind_further_than_the_room_is_a_fault():
    # the rings have room for rows 6 ahead: the newest row written lies
    # 4, 6, 7 and 30 ahead of the prompt's end
    state = _book(newest=(14, 16, 17, 40), last=(14, 16, 17, 40))
    positions = jnp.full((4,), 10, jnp.int32)
    restore, fault, new = stream.book_step(state, positions, room=6)
    assert restore.tolist() == [True, True, False, False]
    assert fault.tolist() == [False, False, True, True]
    assert new["newest"].tolist() == [14, 16, 17, 40]    # never goes back
    # one after the last is served however far the newest row lies
    _, fault, new = stream.book_step(
        state, jnp.asarray((15, 17, 18, 41), jnp.int32), room=6)
    assert not fault.any() and new["newest"].tolist() == [15, 17, 18, 41]


# -- counters -----------------------------------------------------------------


def test_a_bump_wraps_in_uint32_and_keeps_the_gaineds_order():
    names = ("steps", "rows", "hits")
    old = stream.zeros(names)
    assert all(v.dtype == jnp.uint32 and v.shape == () for v in old.values())
    old = dict(old, rows=jnp.uint32(2 ** 32 - 3))
    gained = {"steps": 1, "rows": jnp.sum(jnp.arange(4, dtype=jnp.int32)),
              "hits": jnp.int32(0)}
    new = jax.jit(stream.bump)(old, gained)
    assert {k: int(v) for k, v in new.items()} == {"steps": 1, "rows": 3,
                                                   "hits": 0}
    assert list(stream.bump(old, gained)) == list(names)
    assert all(v.dtype == jnp.uint32 for v in new.values())
    assert stream.counters({"counters": new, "cache": []}) is new


# -- seeded weights -----------------------------------------------------------

#: module -> (toy configuration, leaves, sum, sum of magnitudes) of
#: ``init_params(cfg, 7)`` in float64, written at the parent commit
PARENTS = {
    "deepseek_v2": ("toy_dsv2", 47, 487.29691257327795, 21457.671995081007),
    "smallthinker": ("toy_smallthinker", 63, 806.0360267795622,
                     42509.1439233087),
    "nemotron_h": ("toy_nemotron3", 56, 514.7548224651982,
                   15067.209543494551),
    "exaone_moe": ("toy_kexaone", 98, 1240.7985352366231,
                   27624.617653930094),
    "longcat_flash": ("toy_longcat", 61, 741.6702170874923,
                      23744.738771485165),
}


def _toy(module, toy):
    mod = importlib.import_module(f"nnstreamer_tpu.models.{module}")
    made = [v for v in vars(mod).values() if isinstance(v, type)
            and v.__module__ == mod.__name__ and hasattr(v, "from_dict")]
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           toy + ".json")) as f:
        return mod, made[0].from_dict(json.load(f))


@pytest.mark.parametrize("module", list(PARENTS))
def test_seeded_params_are_the_parents(module):
    toy, count, total, magnitude = PARENTS[module]
    mod, cfg = _toy(module, toy)
    leaves = jax.tree_util.tree_leaves(mod.init_params(cfg, 7))
    values = [np.asarray(leaf.astype(jnp.float32), np.float64)
              for leaf in leaves]
    assert len(leaves) == count
    # float64 sums of float32 values: one changed bit of one leaf shows
    assert sum(v.sum() for v in values) == pytest.approx(total, rel=1e-12)
    assert sum(np.abs(v).sum() for v in values) == pytest.approx(
        magnitude, rel=1e-12)
    assert {str(leaf.dtype) for leaf in leaves} == {"bfloat16", "float32"}


def test_seeded_params_roles():
    shapes = {"a": ((4, 8), "q"), "b": ((8, 4), "o"), "g": ((4,), "norm"),
              "e": ((16, 4), "embed"), "s": ((5,), "bias")}
    got = stream.seeded_params(shapes, 3, jnp.float32, special={
        "bias": stream.normal_vector(0.1)})
    key = jax.random.PRNGKey(3)
    # leaves are numbered in the pytree's order: a, b, e, g, s
    assert np.array_equal(got["a"], jax.random.normal(
        jax.random.fold_in(key, 0), (4, 8)) * (1.0 / 4) ** 0.5)
    assert np.array_equal(got["b"], jax.random.normal(
        jax.random.fold_in(key, 1), (8, 4)) * (0.5 / 8) ** 0.5)
    assert np.array_equal(got["e"], jax.random.normal(
        jax.random.fold_in(key, 2), (16, 4)))
    assert np.array_equal(got["g"], np.ones(4, np.float32))
    assert np.array_equal(got["s"], 0.1 * jax.random.normal(
        jax.random.fold_in(key, 4), (5,), jnp.float32))
    assert stream.seeded_params(shapes, key, special={
        "bias": stream.normal_vector(0.1)})["a"].dtype == jnp.bfloat16


# -- the grouped-query decode step --------------------------------------------

#: (streams, kv heads, heads a group, head size, cache positions, window
#: or None for every position, the streams' positions)
STEPS = {
    # whole lanes: the kernel (interpreted); a ring walked past its end
    "ring": (3, 2, 2, 128, 256, 128, (5, 255, 700)),
    "full": (3, 2, 2, 128, 256, None, (0, 130, 255)),
    # shapes the kernel refuses: the jnp mathematics
    "toy_ring": (2, 2, 3, 16, 12, 4, (3, 29)),
    "toy_full": (2, 2, 3, 16, 12, None, (0, 11)),
}


@pytest.mark.parametrize("case", list(STEPS))
def test_the_shared_step_writes_the_row_and_is_the_reference(case):
    b, g, per, d, total, window, positions = STEPS[case]
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(keys[0], (b, g, per, d), jnp.float32)
    k, v = (jax.random.normal(key, (b, g, d), jnp.float32)
            for key in keys[1:3])
    cache = {name: jax.random.normal(key, (b, g, total, d), jnp.float32)
             for name, key in zip(("k", "v"), keys[3:])}
    at = jnp.asarray(positions, jnp.int32)
    refused = kernels.gqa_decode_attention_refusal(
        q.shape, cache["k"].shape, cache["v"].shape, window or total)
    assert (refused is not None) == case.startswith("toy")
    o, new = jax.jit(lambda *a: attention.decode_step(
        *a, window or total, d ** -0.5))(q, k, v, cache, at)
    want = {name: np.array(cache[name]) for name in cache}
    for s, p in enumerate(positions):
        want["k"][s, :, p % total], want["v"][s, :, p % total] = k[s], v[s]
    assert np.array_equal(new["k"], want["k"])
    assert np.array_equal(new["v"], want["v"])
    ref = kernels.gqa_decode_attention_reference(
        q, jnp.asarray(want["k"]), jnp.asarray(want["v"]), at,
        window or total, d ** -0.5)
    assert o.shape == (b, g, per, d) and o.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(o - ref))) < 3e-5
    fetched = attention.decode_rows_fetched([new], per, at, window)
    assert int(fetched) == int(kernels.gqa_decode_rows_fetched(
        q.shape, cache["k"].shape, at, window or total))
    if refused:
        assert fetched == b * total


def test_small_parts_of_the_step():
    assert attention.decode_rows_fetched([], 4, jnp.zeros((2,), jnp.int32),
                                         128) == 0
    cache = attention.kv_cache(3, 2, 24, 16, jnp.bfloat16)
    assert set(cache) == {"k", "v"} and cache["k"] is not cache["v"]
    assert cache["v"].shape == (3, 2, 24, 16) and not cache["k"].any()
    assert cache["k"].dtype == jnp.bfloat16
    o = jnp.arange(2 * 2 * 3 * 4, dtype=jnp.float32).reshape(2, 2, 3, 4)
    w = jnp.eye(24, 5, dtype=jnp.float32)
    out = attention.heads_out({"o": w}, o, jnp.float32)
    assert np.array_equal(out, o.reshape(2, 24)[:, :5])


# -- entries ------------------------------------------------------------------


def test_entries_are_cached_by_their_arguments_and_register():
    from nnstreamer_tpu.filters import jax_xla
    from nnstreamer_tpu.models import smallthinker as st

    mod, cfg = _toy("smallthinker", "toy_smallthinker")
    assert mod is st
    one = st.entries(cfg, 4, 36, 8)
    two = st.entries(cfg, streams=4, positions=36, chunk=8)
    # the filter keys a program by the entry's function: two sets of
    # weights of one configuration must be handed the same one
    assert one["entries"]["decode"][0] is two["entries"]["decode"][0]
    assert one["entries"]["prefill"][0] is two["entries"]["prefill"][0]
    assert st.entries(cfg, 5, 36, 8)["entries"]["decode"][0] \
        is not one["entries"]["decode"][0]
    assert one["entries"]["decode"][1:] == ([(4,), (4,)], np.int32)
    assert one["entries"]["prefill"][1] == [(8,), (1,), (1,)]
    assert one["setup_entries"] == ("prefill",)
    assert one["counters"] is stream.counters
    params = st.init_params(cfg, 1, jnp.float32)
    state = one["init_state"](params)
    assert len(state["cache"]) == cfg.layers
    assert set(one["counter_units"](state)) >= {"cache_bytes_read",
                                                "cache_bytes_fetched"}
    name = "streams_test_smallthinker"
    try:
        assert st.register(name, cfg, params, streams=4, positions=36,
                           chunk=8) == name
        model = jax_xla._models[name]
        assert model.entries["decode"][0] is one["entries"]["decode"][0]
        assert model.params is params
    finally:
        jax_xla.unregister_model(name)
