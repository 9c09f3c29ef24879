"""A stateless model's weights are ARGUMENTS of its window program
(``filters/jax_xla.py`` ``ModelDef.placed`` / ``_Program``,
``filters/weightsplit.py``): the program's text holds nothing of their
values and serves every set of weights of the same shapes; what the
model computes from weights alone runs once, in the weights prologue;
a weights-only swap of the same shapes builds nothing.  On one device:
over a mesh the weights are still literals of the program.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.filters import weightsplit
from nnstreamer_tpu.filters.api import FilterError, FilterProps
from nnstreamer_tpu.filters.jax_xla import (JaxXlaFilter, get_model,
                                            register_model,
                                            unregister_model)
from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.utils.stats import COMPILE_STATS

WIDTH = 96          # 96 x 96 float32 = 36,864 bytes a matrix


def _toy_params(seed: int, width: int = WIDTH) -> dict:
    """A float32 checkpoint of a model that computes in bfloat16: a
    matrix, a folded batch-norm and a bias."""
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((width, width)).astype(np.float32),
            "scale": rng.uniform(0.5, 1.5, width).astype(np.float32),
            "var": rng.uniform(0.5, 1.5, width).astype(np.float32),
            "mean": rng.standard_normal(width).astype(np.float32),
            "b": rng.standard_normal(width).astype(np.float32),
            "taps": 3}                       # configuration, not a weight


def _toy(params, x):
    """cast + batch-norm fold + bias broadcast from the weights alone; a
    mask from neither weights nor the input; the product from both."""
    dtype = jnp.bfloat16
    inv = (params["scale"] * jax.lax.rsqrt(params["var"] + 1e-3)
           ).astype(dtype)
    off = (params["b"] - params["mean"] * params["scale"]
           * jax.lax.rsqrt(params["var"] + 1e-3)).astype(dtype)
    keep = (jnp.arange(x.shape[-1]) % int(params["taps"]) != 0)
    y = x.astype(dtype) @ params["w"].astype(dtype)
    y = y * inv[None, :] + off[None, :]
    return jnp.tanh(jnp.where(keep, y, 0)).astype(jnp.float32)


X = np.random.default_rng(5).standard_normal((8, WIDTH)).astype(np.float32)


@pytest.fixture
def toys():
    names = []

    def make(seed, width=WIDTH, fn=_toy):
        name = f"_t_wa_{seed}_{width}_{len(names)}"
        register_model(name, fn, params=_toy_params(seed, width),
                       in_shapes=[(8, width)], in_dtypes=np.float32)
        names.append(name)
        return name

    yield make
    for name in names:
        unregister_model(name)


def _open(name, **props):
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model=name,
                             accelerator="cpu", **props))
    return sp


def _texts(sp, path):
    """The lowered text of one window path's program, after running it."""
    c = sp._compiled
    if path == "single":
        sp.invoke([X])
        return c.jitted.lower().as_text()
    if path == "batched":
        # device-resident frames keep the flat path on a mesh too
        frames = [[jnp.asarray(X)], [jnp.asarray(X)]]
        sp.invoke_batched(frames, 2)
        return sp._batch_exec[(c.in_spec, 2)].lower().as_text()
    sp.invoke_batched([[X], [X]], 2)         # host frames on a mesh
    return sp._batch_exec[(c.in_spec, 2, "stacked")].lower().as_text()


def _kinds():
    return {(r["kind"], r["bucket"]): r["count"]
            for r in COMPILE_STATS.snapshot()}


# -- (a) the program's text ----------------------------------------------------


@pytest.mark.parametrize("path", ["single", "batched"])
def test_the_lowered_text_holds_no_weight_and_serves_every_seed(toys, path):
    texts = []
    for seed in (1, 2):
        sp = _open(toys(seed))
        texts.append(_texts(sp, path))
        sp.close()
    assert texts[0] == texts[1]              # to the byte
    # one matrix alone is 36,864 bytes, 73,728 characters as a literal
    assert len(texts[0]) < 20_000, len(texts[0])
    literals = re.findall(r'dense<"0x([0-9A-Fa-f]*)"', texts[0])
    assert max((len(h) // 2 for h in literals), default=0) < 256
    assert f"tensor<{WIDTH}x{WIDTH}xbf16>" in texts[0]     # an argument


@pytest.mark.parametrize("path", ["single", "batched", "stacked"])
def test_over_a_mesh_the_weights_are_still_literals_of_the_program(
        toys, path):
    """A meshed filter keeps the parent's program for now (PR 51 measured
    `ssd300.replay.mesh4` 8 % slower with the weights as arguments:
    `PERF.md` sections 6 and 7): the weights, laid over the mesh by the
    rules, are closed over; the text changes with the seed; a
    weights-only swap builds."""
    texts = []
    for seed in (1, 2):
        sp = _open(toys(seed), mesh="data:2")
        assert sp._compiled.program.weights == []
        texts.append(_texts(sp, path))
        kinds = _kinds()
        shadow = sp.prepare_swap(_toy_params(seed + 2))
        assert _kinds().get(("reload", "0"), 0) \
            - kinds.get(("reload", "0"), 0) == 1
        assert shadow._compiled.exe is not sp._compiled.exe
        sp.close()
    assert texts[0] != texts[1] and len(texts[0]) > 73_728


def test_a_mesh_places_the_weights_by_its_rules():
    rng = np.random.default_rng(3)
    w = rng.standard_normal((16, 8)).astype(np.float32)
    register_model("_t_wa_mesh", lambda p, x: jnp.dot(x, p["w"]) + p["b"],
                   params={"w": w, "b": np.ones(8, np.float32), "taps": 3},
                   in_shapes=[(8, 16)])
    try:
        sp = _open("_t_wa_mesh", mesh="data:2,model:2", sharding="tp")
        placed = sp._model._mesh_params[(sp._mesh, sp._rules)]
        assert tuple(placed["w"].sharding.spec) == (None, "model")
        assert placed["taps"] == 3               # no array: left as it is
        x = rng.standard_normal((8, 16)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(sp.invoke([x])[0]),
                                   x @ w + 1.0, rtol=1e-4, atol=1e-4)
        sp.close()
    finally:
        unregister_model("_t_wa_mesh")


# -- (b) the same numbers ------------------------------------------------------


def _mobilenet():
    from nnstreamer_tpu.models.mobilenet import (mobilenet_v2_apply,
                                                 mobilenet_v2_init)

    params = mobilenet_v2_init(4, num_classes=7, width=0.25)
    return mobilenet_v2_apply, params, (2, 32, 32, 3), np.float32


def _ssd():
    from nnstreamer_tpu.models import ssd

    size = 96
    anchors = ssd.ssd_anchors(size, tuple(
        int(np.ceil(size / s)) for s in (16, 32, 64, 128, 256, 512)))
    params = ssd.ssd_mobilenet_v2_init(4, num_classes=5)

    def detect(p, x):
        return ssd.ssd_detect_apply(p, x, anchors, max_out=10)

    return detect, params, (2, size, size, 3), np.float32


def _vit():
    from nnstreamer_tpu.models.vit import vit_apply, vit_init

    params = vit_init(4, image_size=32, patch=16, dim=64, depth=2, heads=2,
                      mlp_dim=128, num_classes=10)
    return (lambda p, x: vit_apply(p, x, heads=2)), params, \
        (2, 32, 32, 3), np.uint8


@pytest.mark.parametrize("make", [_mobilenet, _ssd, _vit])
def test_outputs_are_those_of_the_plain_jitted_function_to_the_bit(make):
    fn, params, shape, dtype = make()
    name = f"_t_wa_{make.__name__}"
    register_model(name, fn, params=params, in_shapes=[shape],
                   in_dtypes=dtype)
    try:
        rng = np.random.default_rng(11)
        x = (rng.integers(0, 255, shape).astype(dtype) if dtype == np.uint8
             else rng.standard_normal(shape).astype(dtype))
        # the plain function: every array leaf an argument, the rest
        # closed over, as ``ModelDef.placed`` splits them
        plain, weights = get_model(name).placed(jax.devices("cpu")[0])
        want = jax.tree_util.tree_leaves(jax.jit(plain)(weights, x))
        sp = _open(name)
        assert sp._compiled.program.weights       # and a prologue ran
        for got in (sp.invoke([x]), sp.invoke_batched([[x], [x]], 2)[1]):
            assert len(got) == len(want)
            for a, b in zip(got, want):
                assert a.dtype == b.dtype and a.shape == b.shape
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        sp.close()
    finally:
        unregister_model(name)


# -- (c) the split -------------------------------------------------------------


def _primitives(closed):
    return [e.primitive.name for e in closed.jaxpr.eqns]


def test_every_weights_only_equation_is_in_the_prologue():
    params = _toy_params(1)
    names = sorted(k for k in params if k != "taps")
    weights = [jnp.asarray(params[k]) for k in names]

    def whole(ws, x):
        return _toy({**dict(zip(names, ws)), "taps": 3}, x)

    closed = jax.make_jaxpr(whole)(weights, X)
    parts = weightsplit.split(closed, len(weights))
    pro, win = parts.prologue_jaxpr, parts.window_jaxpr
    # the cast of the matrix and of both folds and the batch-norm fold:
    # weights alone
    assert _primitives(pro).count("convert_element_type") == 3
    assert _primitives(pro).count("rsqrt") == 2
    assert not {"dot_general", "tanh", "iota"} & set(_primitives(pro))
    # nothing of it is left in the window program: every equation there
    # reads the input, or neither the input nor a weight — but for the
    # folds' [None, :], which hands each over as the row it was computed
    # as (a [1, n] argument would cost a relayout every window)
    n_res = len(parts.direct) + len(pro.jaxpr.outvars)
    residuals = set(win.jaxpr.invars[:n_res])
    varying = set(win.jaxpr.invars[n_res:])
    for eqn in win.jaxpr.eqns:
        ins = [v for v in eqn.invars if hasattr(v, "count")]
        if any(v in varying for v in ins):
            varying.update(eqn.outvars)
        elif any(v in residuals for v in ins):
            assert eqn.primitive.name == "broadcast_in_dim", eqn
            assert eqn.outvars[0].aval.shape == (1, WIDTH)
            varying.update(eqn.outvars)
    assert [v.aval.shape for v in pro.jaxpr.outvars] == [
        (WIDTH,), (WIDTH,), (WIDTH, WIDTH)]
    assert "rsqrt" not in _primitives(win)
    assert _primitives(win).count("convert_element_type") \
        == _primitives(closed).count("convert_element_type") - 3
    # the mask depends on neither: a constant of the window program
    assert "iota" in _primitives(win)
    # three results (matrix, inv, off) for five weights, none passed on
    # as it is, and the same numbers to the bit
    assert parts.direct == [] and len(pro.jaxpr.outvars) == 3
    got = jax.jit(parts.window)(parts.make(weights), X)
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(jax.jit(whole)(weights, X)))


def test_an_equation_that_grows_stays_in_the_window_program():
    """A bias broadcast to the whole activation is no weights-only
    buffer: XLA fuses it into its consumer where it is."""
    def whole(ws, x):
        return x + jnp.broadcast_to(ws[0].astype(x.dtype) * 2.0, x.shape)

    b = jnp.ones((WIDTH,), jnp.bfloat16)
    parts = weightsplit.split(jax.make_jaxpr(whole)([b], X), 1)
    assert _primitives(parts.prologue_jaxpr) == ["convert_element_type",
                                                 "mul"]
    assert parts.prologue_jaxpr.jaxpr.outvars[0].aval.shape == (WIDTH,)
    assert "broadcast_in_dim" in _primitives(parts.window_jaxpr)


def test_a_reshape_in_the_middle_of_weights_only_work_stays_with_it():
    """Only the shape-only equations at the prologue's END move to the
    window program; one whose result feeds more weights-only work is
    part of that work."""
    def whole(ws, x):
        table = ws[0].reshape(4, WIDTH // 4) * ws[1][:, None]
        return x * table.reshape(1, WIDTH)

    ws = [jnp.ones((WIDTH,)), jnp.ones((4,))]
    parts = weightsplit.split(jax.make_jaxpr(whole)(ws, X), 2)
    pro = _primitives(parts.prologue_jaxpr)
    assert pro.count("reshape") == 1 and pro[-1] == "mul"
    (made,) = parts.prologue_jaxpr.jaxpr.outvars
    assert made.aval.shape == (4, WIDTH // 4)      # before the last reshape
    assert _primitives(parts.window_jaxpr) == ["reshape", "mul"]


def test_a_model_that_derives_nothing_has_no_prologue():
    parts = weightsplit.split(
        jax.make_jaxpr(lambda ws, x: x @ ws[0])([X.T], X), 1)
    assert parts.prologue_jaxpr is None and parts.direct == [0]
    kept = jnp.asarray(X.T)
    assert parts.make([kept])[0] is kept


# -- (d) a weights-only swap ---------------------------------------------------


class _Builds:
    """XLA builds (or loads) counted by ``jax.monitoring`` while on."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, _seconds, **_kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.fixture(scope="module")
def builds():
    return _Builds()


def test_a_weights_only_swap_reuses_the_programs_and_builds_nothing(
        toys, builds):
    sp = _open(toys(1))
    frames = [[X], [X]]
    before_out = np.asarray(sp.invoke([X])[0])
    sp.invoke_batched(frames, 2)
    served, batch_served = sp._compiled.exe, dict(sp._batch_exec)
    new = _toy_params(2)
    plain, weights = get_model(toys(2)).placed(jax.devices("cpu")[0])
    want = np.asarray(jax.jit(plain)(weights, X))
    assert not np.array_equal(want, before_out)
    kinds = _kinds()
    builds.n, builds.on = 0, True
    try:
        shadow = sp.prepare_swap(new)
        sp.commit_swap(shadow)
        got = np.asarray(sp.invoke([X])[0])
        got_b = np.asarray(sp.invoke_batched(frames, 2)[1][0])
    finally:
        builds.on = False
    assert builds.n == 0                    # no program built or loaded
    after = _kinds()
    assert after.get(("reuse", "0"), 0) - kinds.get(("reuse", "0"), 0) == 1
    assert {k: v for k, v in after.items() if k[0] != "reuse"} \
        == {k: v for k, v in kinds.items() if k[0] != "reuse"}
    assert sp._compiled.exe is served        # the executables that served
    assert sp._batch_exec == batch_served
    np.testing.assert_array_equal(got, want)     # over the new weights
    np.testing.assert_array_equal(got_b, want)
    text = REGISTRY.exposition()
    assert re.search(r'nns_compiles_total\{[^}]*kind="reuse"[^}]*\} [1-9]',
                     text)
    sp.close()


def test_a_swap_that_changes_a_shape_or_a_setting_builds_a_reload(toys):
    sp = _open(toys(1))
    sp.invoke([X])
    for new in ({**_toy_params(2), "w": np.ones((WIDTH, WIDTH),
                                                np.float16)},
                {**_toy_params(2), "taps": 2}):
        kinds = _kinds()
        shadow = sp.prepare_swap(new)
        after = _kinds()
        assert after.get(("reload", "0"), 0) \
            - kinds.get(("reload", "0"), 0) == 1
        assert after.get(("reuse", "0"), 0) == kinds.get(("reuse", "0"), 0)
        assert shadow._compiled.exe is not sp._compiled.exe
    sp.commit_swap(shadow)                   # taps=2 serves
    plain, weights = get_model(toys(2)).placed(jax.devices("cpu")[0])
    assert not np.array_equal(np.asarray(sp.invoke([X])[0]),
                              np.asarray(jax.jit(plain)(weights, X)))
    sp.close()


# -- (e) leaves that are no arrays; weights read by value -----------------------


def test_a_leaf_that_is_no_array_stays_what_it_was(toys):
    name = toys(1)
    sp = _open(name)                 # int(params["taps"]) traced: static
    fn, weights = get_model(name).placed(sp._device)
    assert weights["taps"] is None and isinstance(weights["w"], jax.Array)
    assert get_model(name)._dev_params[sp._device]["taps"] == 3
    assert len(sp._compiled.program.weights) == 3    # matrix, inv, off
    sp.close()


def test_a_model_that_reads_a_weights_value_is_refused_at_open():
    def fn(params, x):
        return x[: int(params["head"]["rows"])] * params["gain"]

    register_model("_t_wa_reads", fn,
                   params={"head": {"rows": np.int32(2)},
                           "gain": np.float32(2.0)},
                   in_shapes=[(4,)], in_dtypes=np.float32)
    try:
        with pytest.raises(FilterError) as err:
            _open("_t_wa_reads")
        assert "_t_wa_reads reads the VALUE of its weight " \
            "['head']['rows'] while" in str(err.value)
    finally:
        unregister_model("_t_wa_reads")
    # the same constant as a Python number is configuration: it opens
    register_model("_t_wa_reads", fn,
                   params={"head": {"rows": 2}, "gain": np.float32(2.0)},
                   in_shapes=[(4,)], in_dtypes=np.float32)
    try:
        sp = _open("_t_wa_reads")
        out = sp.invoke([np.arange(4, dtype=np.float32)])[0]
        np.testing.assert_array_equal(np.asarray(out), [0.0, 2.0])
        sp.close()
    finally:
        unregister_model("_t_wa_reads")


# -- a frame of another shape ---------------------------------------------------


def test_a_frame_of_another_batch_is_served_as_jit_serves_it(toys):
    """``jax.jit`` traces a function anew for inputs of another shape,
    and a filter opened for one batch has always served another so
    (``FilterSingle`` on a saved model); the window program is traced
    and split again for it and takes the weights already made."""
    name = toys(1)
    sp = _open(name)
    made = list(sp._compiled.program.weights)
    plain, weights = get_model(name).placed(sp._device)
    for rows in (8, 3):
        x = np.random.default_rng(rows).standard_normal(
            (rows, WIDTH)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(sp.invoke([x])[0]),
            np.asarray(jax.jit(plain)(weights, x)))
    assert all(a is b for a, b in zip(sp._compiled.program.weights, made))
    sp.close()


def test_another_shape_that_changes_the_prologue_is_refused():
    def fn(ws, x):      # what it makes of the weight depends on the input
        return x * (ws[0] if x.shape[0] == 2 else ws[0] * 2.0)

    w = jnp.ones((), jnp.float32)
    parts = weightsplit.trace(fn, [w], jax.ShapeDtypeStruct((2,), np.float32))
    made = parts.make([w])
    assert float(jax.jit(parts.window)(made, np.ones(2, np.float32))[0]) == 1
    with pytest.raises(ValueError, match="other things of the weights"):
        jax.jit(parts.window)(made, np.ones(3, np.float32))
