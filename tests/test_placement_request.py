"""The placement request (ISSUE 25): a mesh ``tensor_filter`` asks at
start, through elements that hand buffers on untouched, for the layout
its executable reads; ``device_src`` stages its pool in it, so no window
is placed again.  What sends a request, what passes it, what drops it,
who honours it and when today's staging stays."""

import numpy as np
import pytest

from nnstreamer_tpu.elements.basic import Tee
from nnstreamer_tpu.elements.devicesrc import DeviceSrc
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.filters.jax_xla import register_model, unregister_model
from nnstreamer_tpu.obs.transfer import LEDGER
from nnstreamer_tpu.runtime import MODEL_POOL, Pipeline, parse_launch
from nnstreamer_tpu.runtime.events import Event, EventKind
from nnstreamer_tpu.utils import profile

jax = pytest.importorskip("jax")

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="the request needs the 8-device (virtual) inventory")

SHAPE = (8, 4, 3)       # a window of 8 frames: divides data:4 and data:2
ODD = (6, 4, 3)         # 6 does not divide data:4
W = np.asarray(np.random.RandomState(3).randn(3, 5), np.float32)
N = 6

CAST = "tensor_transform name=norm mode=typecast option=float32"
LINE = ("device_src name=src num_buffers={n} ! {between} ! "
        "tensor_filter name=net framework=jax-xla model={model} {props} ! "
        "tensor_sink name=out")


@pytest.fixture(scope="module", autouse=True)
def _models():
    register_model("_t_req", lambda x: x @ W + 1.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    register_model("_t_req_odd", lambda x: x @ W + 1.0,
                   in_shapes=[ODD], in_dtypes=np.float32)
    register_model("_t_req_two", lambda x, y: (x @ W + y[0, 0, 0],),
                   in_shapes=[SHAPE, ODD], in_dtypes=np.float32)
    yield
    for name in ("_t_req", "_t_req_odd", "_t_req_two"):
        unregister_model(name)


@pytest.fixture(autouse=True)
def _clean():
    LEDGER.clear()
    profile.clear()
    yield
    profile._active.clear()
    profile.clear()
    MODEL_POOL.clear()


@pytest.fixture
def capture(monkeypatch):
    """A capture that is active without a profiler behind it, so that
    every per-window span is kept."""

    class Annotation:
        def __init__(self, name):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    profile._active.set()


def _frames(shape=SHAPE, n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, shape, dtype=np.uint8) for _ in range(n)]


def _line(props="mesh=data:4", between=CAST, model="_t_req", n=N,
          frames=None, fuse=True):
    p = parse_launch(LINE.format(n=n, between=between, model=model,
                                 props=props))
    p.fuse = fuse
    frames = _frames() if frames is None else frames
    p["src"].frames, p["src"].pool_size = frames, len(frames)
    return p


def _run(p):
    """Start, stream to EOS, and return (outputs on the host, each pool
    slot's first array, the filter's input layouts) read while live."""
    got = []
    p["out"].connect(
        lambda b: got.append([np.asarray(t.jax()) for t in b.tensors]))
    with p:
        assert p.wait_eos(timeout=60)
        pool = [slot[0] for slot in p["src"]._pool]
        layouts = p["net"].subplugin._compiled.in_shardings
    return got, pool, layouts


def _input_rows():
    return [r for r in LEDGER.snapshot() if r["reason"] == "input"]


def _on_default_device(pool):
    dev = jax.devices()[0]
    return all(a.devices() == {dev} for a in pool)


def _stage_note():
    return [s.note for s in profile.spans() if s.name == "src/stage"][-1]


# -- the whole line ------------------------------------------------------------


def test_pool_is_staged_in_the_mesh_filters_input_layout():
    p = _line()
    got, pool, layouts = _run(p)
    assert p["norm"]._fused and len(got) == N and len(pool) == 3
    for a in pool:
        assert layouts[0].is_equivalent_to(a.sharding, a.ndim)
        assert len({s.device for s in a.addressable_shards}) == 4
        assert {s.data.shape for s in a.addressable_shards} == {(2, 4, 3)}
    assert _stage_note().startswith("as asked: NamedSharding")


def test_no_window_is_placed_again(capture):
    got, _pool, _layouts = _run(_line())
    assert len(got) == N
    assert _input_rows() == []
    names = [s.name for s in profile.spans()]
    assert names.count("net/dispatch") == N and "net/place" not in names


def test_suppressed_request_places_every_window_and_outputs_agree(
        capture, monkeypatch):
    """Today's path, by taking the request away: every window is put
    onto the mesh by the filter, and the outputs are the same bits."""
    asked, _, _ = _run(_line())
    LEDGER.clear()
    profile.clear()
    monkeypatch.setattr(TensorFilter, "_request_placement",
                        lambda self: None)
    plain, pool, _ = _run(_line())
    assert _on_default_device(pool)
    rows = _input_rows()
    assert [(r["direction"], r["count"]) for r in rows] == [("d2d", N)]
    assert rows[0]["bytes"] == N * int(np.prod(SHAPE))
    assert [s.name for s in profile.spans()].count("net/place") == N
    assert len(asked) == len(plain) == N
    for a, b in zip(asked, plain):
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert _stage_note() == "default device"


def test_no_mesh_stages_on_one_device_as_before():
    got, pool, layouts = _run(_line(props=""))
    assert layouts is None and len(got) == N
    assert _on_default_device(pool)
    assert all(isinstance(a.sharding, jax.sharding.SingleDeviceSharding)
               for a in pool)
    assert _input_rows() == []
    assert _stage_note() == "default device"


def test_generated_pattern_honours_the_request():
    p = parse_launch(LINE.format(
        n=4, between=CAST, model="_t_req", props="mesh=data:4"))
    p["src"].spec = "3:4:8"
    p["src"].pattern = "gradient"
    got, pool, layouts = _run(p)
    assert len(got) == 4 and len(pool) == p["src"].pool_size
    assert all(layouts[0].is_equivalent_to(a.sharding, a.ndim)
               for a in pool)
    assert _input_rows() == []
    flat = np.arange(int(np.prod(SHAPE))).reshape(SHAPE)
    assert np.array_equal(np.asarray(pool[1]), (flat + 1) % 256)


# -- who passes the request on, who drops it -----------------------------------


@pytest.mark.parametrize("between", [
    CAST,
    "queue name=q ! " + CAST,
    "identity name=i ! " + CAST,
    "capsfilter name=c ! " + CAST,
    "tee name=t ! " + CAST,
])
def test_elements_that_hand_buffers_on_pass_the_request(between):
    got, pool, layouts = _run(_line(between=between))
    assert len(got) == N
    assert all(layouts[0].is_equivalent_to(a.sharding, a.ndim)
               for a in pool)
    assert _input_rows() == []


def test_unfused_computing_element_drops_the_request():
    """The same transform with fusion off computes on every buffer: it
    must not be handed an array laid out for the filter behind it."""
    p = _line(fuse=False)
    got, pool, _ = _run(p)
    assert not p["norm"]._fused and len(got) == N
    assert _on_default_device(pool)
    assert [(r["direction"], r["count"]) for r in _input_rows()] == \
        [("d2d", N)]


@pytest.mark.parametrize("factory", [
    "tensor_filter", "tensor_converter", "tensor_mux", "tensor_merge",
    "tensor_rate", "tensor_aggregator", "tensor_decoder"])
def test_base_class_drops_the_request(factory):
    """Only an element that opted in forwards it: QOS still travels."""
    from nnstreamer_tpu.runtime.registry import element_factory

    cls = element_factory(factory)
    assert cls.PASSES_BUFFERS is False
    src = DeviceSrc(name="src", frames=_frames())
    got = []
    src.handle_upstream_event = lambda pad, ev: got.append(ev.kind)
    kw = {"framerate": "10/1"} if factory == "tensor_rate" else {}
    el = cls(name="el", **kw)
    if not el.sinkpads:
        el.request_pad("sink_%u")
    src.srcpad.link(el.sinkpads[0])
    layout = jax.sharding.SingleDeviceSharding(jax.devices()[1])
    for ev in (Event.placement([layout]), Event.qos_throttle(5)):
        type(el).handle_upstream_event(el, el.sinkpads[0], ev)
    assert got == [EventKind.QOS_THROTTLE]


# -- who sends it --------------------------------------------------------------


def test_leading_dim_that_does_not_divide_the_data_axis_sends_none():
    """A replicated input is not asked for: honouring it would hold the
    pool once per chip."""
    got, pool, layouts = _run(_line(model="_t_req_odd",
                                    frames=_frames(ODD)))
    assert layouts[0].is_fully_replicated and len(got) == N
    assert _on_default_device(pool)
    assert _stage_note() == "default device"


def test_only_the_sharded_tensor_of_two_is_asked_for():
    frames = [[a, b] for a, b in zip(_frames(), _frames(ODD, seed=1))]
    p = _line(model="_t_req_two", frames=frames)
    got, _, layouts = _run(p)
    assert len(got) == N
    assert layouts[0].is_equivalent_to(p["src"]._pool[0][0].sharding, 3)
    assert p["src"]._pool[0][1].devices() == {jax.devices()[0]}
    # the replicated one is still placed by the filter, as before
    assert [(r["direction"], r["count"], r["bytes"])
            for r in _input_rows()] == [("d2d", N, N * int(np.prod(ODD)))]


@pytest.mark.parametrize("props", [
    "mesh=data:4 batch=2 batch-buckets=2 batch-timeout-ms=5",
    "mesh=data:4 share-model=true",
    "mesh=data:4 share-model=true batch=2 batch-buckets=2",
    "mesh=data:4 custom=donate",
    "mesh=data:1",
])
def test_other_invoke_paths_send_no_request(props):
    got, pool, _ = _run(_line(props=props))
    assert len(got) == N
    assert _on_default_device(pool)


def test_input_combination_sends_no_request():
    """The filter reads a subset of the buffer: the rest is someone
    else's, and the request speaks of tensors by position."""
    frames = [[a.astype(np.float32), b]
              for a, b in zip(_frames(), _frames(ODD, seed=1))]
    p = _line(between="identity name=i", frames=frames)
    p["net"].input_combination = "0"
    got, pool, _ = _run(p)
    assert len(got) == N
    assert _on_default_device(pool)


# -- a tee ---------------------------------------------------------------------


def _tee_line(props_a, props_b):
    p = parse_launch(
        f"device_src name=src num_buffers={N} ! tee name=t "
        f"t. ! {CAST} ! tensor_filter name=net framework=jax-xla "
        f"model=_t_req {props_a} ! tensor_sink name=out "
        f"t. ! tensor_transform name=norm2 mode=typecast option=float32 ! "
        f"tensor_filter name=net2 framework=jax-xla model=_t_req "
        f"{props_b} ! tensor_sink name=out2")
    p["src"].frames, p["src"].pool_size = _frames(), 3
    return p


def test_tee_to_two_filters_that_agree_is_honoured():
    p = _tee_line("mesh=data:4", "mesh=data:4")
    second = []
    p["out2"].connect(second.append)
    got, pool, layouts = _run(p)
    assert len(got) == len(second) == N
    assert all(layouts[0].is_equivalent_to(a.sharding, a.ndim)
               for a in pool)
    assert _input_rows() == []


@pytest.mark.parametrize("props_b", ["mesh=data:2", "", "mesh=data:4 "
                                     "devices=4-7"])
def test_tee_to_two_filters_that_differ_falls_back(props_b):
    p = _tee_line("mesh=data:4", props_b)
    second = []
    p["out2"].connect(
        lambda b: second.append(np.asarray(b.tensors[0].jax())))
    got, pool, _ = _run(p)
    assert _on_default_device(pool)
    assert len(got) == len(second) == N
    # two programs (two partitionings): the same values, not the same bits
    assert all(np.allclose(a[0], b, atol=1e-3) for a, b in zip(got, second))


def test_tee_merges_per_tensor_and_forgets_at_stop():
    tee = Tee(name="t")
    src = DeviceSrc(name="src", frames=_frames())
    src.srcpad.link(tee.sinkpad)
    sinks = [TensorFilter(name=f"f{i}") for i in range(2)]
    for f in sinks:
        tee.get_pad("src_%u").link(f.sinkpad)
    a, b = (jax.sharding.SingleDeviceSharding(d) for d in jax.devices()[:2])
    tee.handle_upstream_event(tee.srcpads[0], Event.placement([a, b]))
    assert src._layouts == ()          # the other branch has not asked
    tee.handle_upstream_event(tee.srcpads[1], Event.placement([a, a]))
    assert src._layouts == (a, None)
    tee.stop()
    tee.handle_upstream_event(tee.srcpads[1], Event.placement([a, a]))
    assert src._layouts == ()


# -- restart -------------------------------------------------------------------


def test_stop_start_restages_from_the_new_starts_request():
    p = _line(n=-1)
    seen = []
    p["out"].connect(seen.append)
    p.start()
    first = [slot[0] for slot in p["src"]._pool]
    assert all(len(a.devices()) == 4 for a in first)
    p.stop()
    assert p["src"]._layouts == ()
    p.start()
    again = [slot[0] for slot in p["src"]._pool]
    assert all(len(a.devices()) == 4 for a in again)
    assert all(x is not y for x, y in zip(first, again))
    p.stop()
    # the same line without its mesh: nobody asks, today's staging
    p["net"].mesh = ""
    p.start()
    assert _on_default_device([slot[0] for slot in p["src"]._pool])
    p.stop()
