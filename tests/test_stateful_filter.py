"""The stateful-model contract of the ``jax-xla`` sub-plugin
(``Documentation/stateful-models.md``): weights and state are arguments
of the executable, the state is kept between invokes, donated to each
call and freed at close; two ``tensor_filter`` elements of one
``shared-tensor-filter-key`` work on one state, each through the entry
point its input schema picks; RELOAD and hot swap refuse; the stateless
path is untouched.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.filters.api import (FilterError, FilterProps,
                                        SHARED_MODELS)
from nnstreamer_tpu.filters.jax_xla import (JaxXlaFilter,
                                            _stateful_programs,
                                            register_model,
                                            register_stateful_model,
                                            unregister_model)
from nnstreamer_tpu.runtime import parse_launch
from nnstreamer_tpu.runtime.events import Event
from nnstreamer_tpu.utils import profile
from nnstreamer_tpu.utils.stats import COMPILE_STATS, STATE_STATS

WIDTH = 2048          # a weight vector of 8 KB: large enough to be seen


def _init_state(params):
    return {"sum": jnp.zeros((4,), jnp.float32),
            "steps": jnp.zeros((), jnp.uint32)}


def _add(params, state, x):
    """The stream's entry: adds the frame (scaled by the weights' mean)
    to the running sum and serves it."""
    total = state["sum"] + x * jnp.mean(params["w"])
    return {"sum": total, "steps": state["steps"] + jnp.uint32(1)}, total


def _load(params, state, x, slot):
    """The set-up entry: overwrites one slot of the sum."""
    total = state["sum"].at[slot[0]].set(x[0])
    return {"sum": total, "steps": state["steps"]}, (total,)


def _register(name, scale=1.0):
    return register_stateful_model(
        name, params={"w": jnp.full((WIDTH,), scale, jnp.float32)},
        init_state=_init_state,
        entries={"add": (_add, [(4,)], np.float32),
                 "load": (_load, [(1,), (1,)], [np.float32, np.int32])},
        setup_entries=("load",),
        counters=lambda state: {"steps": state["steps"]},
        counter_units=lambda state: {"sum_bytes": ("steps", 16)})


@pytest.fixture
def model():
    SHARED_MODELS.clear()
    STATE_STATS.reset()
    _stateful_programs.clear()      # each test loads its own programs
    names = []

    def make(name="stateful_toy", scale=1.0):
        names.append(name)
        return _register(name, scale)

    yield make
    for name in names:
        unregister_model(name)
    SHARED_MODELS.clear()


def _open(name, key=None, spec=None):
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model=name,
                             shared_key=key, input_spec=spec))
    return sp


ONES = np.ones(4, np.float32)


def test_state_persists_and_is_donated(model):
    sp = _open(model())
    before = sp._cell.state["sum"]
    assert np.allclose(sp.invoke([ONES])[0], 1.0)
    assert np.allclose(sp.invoke([ONES])[0], 2.0)
    assert np.allclose(sp.invoke([2 * ONES])[0], 4.0)
    # the old handle went into the call donated: it is dead
    assert before.is_deleted()
    assert sp._cell.state_bytes == 16 + 4
    assert STATE_STATS.snapshot()["state_bytes"] == 20
    sp.close()
    assert STATE_STATS.snapshot()["state_bytes"] == 0


def test_close_frees_and_a_new_open_begins_from_init_state(model):
    name = model()
    sp = _open(name)
    sp.invoke([ONES])
    cell = sp._cell
    live = cell.state["sum"]
    sp.close()
    assert live.is_deleted() and cell.state is None
    again = _open(name)
    assert np.allclose(again.invoke([ONES])[0], 1.0)
    again.close()


def test_the_schema_picks_the_entry_point(model):
    name = model()
    sp = _open(name)
    in_spec, out_spec = sp.get_model_info()
    assert [t.shape for t in in_spec.tensors] == [(4,)]     # the first entry
    assert sp._model.entry_for(sp._model.entries["load"][1]) == "load"
    sp.set_input_info(sp._model.entries["load"][1])
    out = sp.invoke([np.array([7.0], np.float32), np.array([2], np.int32)])
    assert np.allclose(out[0], [0, 0, 7, 0])
    from nnstreamer_tpu.core import TensorsSpec
    with pytest.raises(FilterError, match="no entry point"):
        sp.set_input_info(TensorsSpec.from_shapes([(5,)], np.float32))
    sp.close()


def test_two_filters_of_one_key_share_one_state(model):
    name = model()
    load = _open(name, key="k")
    load.set_input_info(load._model.entries["load"][1])
    add = _open(name, key="k")
    assert add._cell is load._cell and add._cell.refs == 2
    load.invoke([np.array([5.0], np.float32), np.array([1], np.int32)])
    assert np.allclose(add.invoke([ONES])[0], [1, 6, 1, 1])
    # another key, another state
    other = _open(name, key="k2")
    assert np.allclose(other.invoke([ONES])[0], 1.0)
    other.close()
    # the state lives as long as one sharer holds it
    load.close()
    assert np.allclose(add.invoke([ONES])[0], [2, 7, 2, 2])
    live = add._cell.state["sum"]
    add.close()
    assert live.is_deleted()
    assert SHARED_MODELS.get("jax-xla:k:" + str(
        JaxXlaFilter._placement_key(add.props or FilterProps()))) is None


@pytest.mark.parametrize("units,want", [
    ({"ring_bytes": ("ring_rows", 8)}, {"ring_bytes": 24}),
    ({"ring_bytes": ("ring_rows", 8), "dense_bytes": ("dense_rows", 16),
      "cache_bytes": [("ring_rows", 8), ("dense_rows", 16)]},
     {"ring_bytes": 24, "dense_bytes": 112, "cache_bytes": 136}),
], ids=["one-counter", "two-kinds-and-their-sum"])
def test_a_state_of_several_kinds_of_leaf_counts_each_kind(units, want):
    """A state whose leaves differ in shape from layer to layer (a ring
    beside a dense cache) is one state: donated, kept and freed whole;
    a published counter may be one raw counter by its unit or several
    that add up."""
    SHARED_MODELS.clear()
    STATE_STATS.reset()
    _stateful_programs.clear()

    def init_state(params):
        return {"cache": [{"k": jnp.zeros((2, 3, 4)), "v": jnp.zeros((2, 3, 4))},
                          {"k": jnp.zeros((2, 7, 4)), "v": jnp.zeros((2, 7, 4))}],
                "n": {"ring_rows": jnp.zeros((), jnp.uint32),
                      "dense_rows": jnp.zeros((), jnp.uint32)}}

    def step(params, state, x):
        cache = [{"k": c["k"] + x[0], "v": c["v"]} for c in state["cache"]]
        n = {"ring_rows": state["n"]["ring_rows"] + jnp.uint32(3),
             "dense_rows": state["n"]["dense_rows"] + jnp.uint32(7)}
        return {"cache": cache, "n": n}, cache[1]["k"][0, :, 0]

    name = "two_kinds_toy"
    register_stateful_model(
        name, params={"w": jnp.ones((4,))}, init_state=init_state,
        entries={"step": (step, [(1,)], np.float32)},
        counters=lambda state: state["n"],
        counter_units=lambda state: units)
    try:
        sp = _open(name)
        old = sp._cell.state["cache"][0]["k"]
        assert np.allclose(sp.invoke([np.ones(1, np.float32)])[0], 1.0)
        assert old.is_deleted()
        assert sp._cell.state_bytes == 2 * 4 * (24 + 56) + 8
        sp.fetch_counters()
        stats = STATE_STATS.snapshot()
        assert stats["ring_rows"] == 3 and stats["dense_rows"] == 7
        assert {k: stats[k] for k in want} == want
        sp.close()
        assert STATE_STATS.snapshot()["state_bytes"] == 0
    finally:
        unregister_model(name)
        SHARED_MODELS.clear()


def test_reload_and_hot_swap_refuse(model):
    sp = _open(model())
    with pytest.raises(FilterError, match="stateful"):
        sp.prepare_swap({"w": np.zeros(WIDTH, np.float32)})
    with pytest.raises(FilterError, match="stateful"):
        sp.handle_event(Event.reload_model("stateful_toy"))
    with pytest.raises(FilterError, match="micro-batched"):
        sp.invoke_batched([[ONES]], 1)
    assert np.allclose(sp.invoke([ONES])[0], 1.0)      # and still serves
    sp.close()


def test_no_weight_literal_and_two_sets_of_weights_compile_once(model):
    """Weights and state are arguments: the optimised program holds no
    constant of more than a few KB, and a second set of weights of the
    same shapes runs the first one's program."""
    COMPILE_STATS.reset()
    one = _open(model("stateful_seed_1", scale=1.0))
    assert np.allclose(one.invoke([ONES])[0], 1.0)
    compiles = COMPILE_STATS.total_compiles
    two = _open(model("stateful_seed_2", scale=3.0))
    assert np.allclose(two.invoke([ONES])[0], 3.0)
    assert COMPILE_STATS.total_compiles == compiles
    assert one._compiled.jitted is not two._compiled.jitted    # own cells
    text = one.executable_text()
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
             for dims in re.findall(r"= \w+\[([\d,]*)\]\S* constant\(", text)]
    assert max(sizes, default=0) * 4 < 4096, sizes
    assert f"f32[{WIDTH}]" in text and "parameter(" in text
    one.close()
    two.close()


def test_a_stateless_model_takes_its_weights_as_arguments_too():
    """A stateless model has joined the contract (PR 51): its program
    takes the weights as arguments and holds no constant of their size;
    what it computes from them alone (here their sum) is the weights
    prologue's, computed once at open."""
    w = jnp.arange(WIDTH, dtype=jnp.float32)

    def fn(params, x):
        return x * jnp.sum(params["w"])

    register_model("stateless_toy", fn, params={"w": w},
                   in_shapes=[(4,)], in_dtypes=np.float32)
    try:
        sp = _open("stateless_toy")
        assert sp._cell is None
        sp.fetch_counters()                      # nothing, and no error
        made = sp._compiled.program.weights
        assert [(a.shape, float(a)) for a in made] == [((), float(w.sum()))]
        text = sp.executable_text()
        assert "parameter(1)" in text            # the sum and the input
        assert "reduce(" not in text and f"f32[{WIDTH}]" not in text
        assert np.allclose(sp.invoke([ONES])[0], float(w.sum()))
        sp.close()
    finally:
        unregister_model("stateless_toy")


# -- through parse_launch ------------------------------------------------------------


def _pull(sink, n, timeout=30.0):
    out = []
    while len(out) < n:
        buf = sink.pull(timeout=timeout)
        assert buf is not None, "the line served nothing"
        out.append(buf)
    return out


def test_two_launch_lines_work_on_one_state_and_stop_frees_it(model):
    name = model()
    profile.clear()
    line = ("device_src name={p}src num_buffers={n} ! tensor_filter "
            "name={p}net framework=jax-xla model=" + name
            + " shared-tensor-filter-key=shared stat-sample-interval-ms=0 "
            "! appsink name={p}sink")
    pre = parse_launch(line.format(p="pf_", n=2))
    pre["pf_src"].frames = [(np.array([3.0], np.float32),
                             np.array([0], np.int32)),
                            (np.array([4.0], np.float32),
                             np.array([3], np.int32))]
    pre["pf_src"].pool_size = 2
    pre.start()
    loaded = _pull(pre["pf_sink"], 2)
    assert np.allclose(loaded[1].tensors[0].np(), [3, 0, 0, 4])
    run = parse_launch(line.format(p="el_", n=3))
    run["el_src"].frames = [ONES]
    run["el_src"].pool_size = 1
    run.start()
    served = _pull(run["el_sink"], 3)
    # the stream's entry worked on what the set-up entry had loaded
    assert np.allclose(served[2].tensors[0].np(), [6, 3, 3, 7])
    cell = run["el_net"].subplugin._cell
    assert cell is pre["pf_net"].subplugin._cell and cell.refs == 2
    # counters were read at the stats sample, never more often
    stats = STATE_STATS.snapshot()
    assert stats["steps"] == 3 and stats["sum_bytes"] == 48
    assert stats["state_bytes"] == 20
    pre.stop()
    assert cell.state is not None           # the stream still holds it
    run.stop()
    assert cell.state is None and STATE_STATS.snapshot()["state_bytes"] == 0
    names = {s.name for s in profile.spans() if s.kind == "setup"}
    # the filter that opened first made the state and built both
    # programs (its default entry's at configure), so the set-up spans
    # carry its name; the second joined
    assert {"pf_net/state_init", "pf_net/load", "pf_net/trace_lower",
            "pf_net/first_call"} <= names
    # (it is opened and activated, and builds nothing)
    assert {n for n in names if n.startswith("el_net/")} \
        == {"el_net/open", "el_net/activate"}
    # a restart begins from init_state
    again = parse_launch(line.format(p="el_", n=1))
    again["el_src"].frames = [ONES]
    again["el_src"].pool_size = 1
    again.start()
    assert np.allclose(_pull(again["el_sink"], 1)[0].tensors[0].np(), 1.0)
    again.stop()


def test_reload_event_on_a_stateful_element_is_an_error(model):
    name = model()
    pipe = parse_launch(
        "device_src name=s num_buffers=-1 ! tensor_filter name=net "
        f"framework=jax-xla model={name} is-updatable=true ! "
        "appsink name=sink max_buffers=2")
    pipe["s"].frames = [ONES]
    pipe["s"].pool_size = 1
    pipe.start()
    try:
        _pull(pipe["sink"], 1)
        with pytest.raises(FilterError, match="stateful"):
            pipe["net"].subplugin.handle_event(Event.reload_model(name))
    finally:
        pipe.stop()
