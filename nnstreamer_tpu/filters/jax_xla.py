"""``jax-xla`` — the flagship filter sub-plugin.

The TPU-native answer to the reference's accelerator sub-plugins (tensorrt,
edgetpu, tflite — /root/reference/ext/nnstreamer/tensor_filter/): a model is
an XLA computation resident on the device.  Where TensorRT builds a CUDA
engine and keeps outputs in ``cudaMallocManaged`` memory
(tensor_filter_tensorrt.cc:292-358,396), jax-xla compiles a jitted function
once per input schema and keeps params *and* activations in TPU HBM;
``invoke`` is an async XLA dispatch, so the pipeline thread runs ahead of the
device (the framework's allocate-in-invoke is structural, not opt-in).

Model sources:
- in-process registration: ``register_model("name", fn, params=...)`` then
  ``model="name"`` (the TPU analog of the reference's in-process custom-easy
  registration, generalized to any jittable callable)
- ``.jaxexp`` file: a serialized ``jax.export.Exported`` computation (the
  StableHLO interchange format — parity with loading a compiled .tflite/.uff)
- a raw Python callable passed as ``model=``

Hot reload (``is-updatable``): RELOAD_MODEL events compile the replacement
*before* atomically swapping it in — parity with the tflite sub-plugin's
double-interpreter reload (tensor_filter_tensorflow_lite.cc:269-274).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import DType, TensorSpec, TensorsSpec
from ..obs import meshstat as _meshstat
from ..obs import transfer as _xfer
from ..obs import xlacost as _xlacost
from ..runtime.events import Event, EventKind
from ..utils import profile as _profile
from ..utils.stats import COMPILE_STATS, DISPATCH_STATS, STATE_STATS
from . import weightsplit as _weightsplit
from .api import FilterError, FilterProps, FilterSubplugin, SHARED_MODELS
from .registry import register_filter


def _jax():
    import jax

    return jax


def _timed_first_call(fn: Callable, stats_key, owner: str,
                      lower: Callable) -> Callable:
    """Attribute the executable's FIRST invocation to its compile-stats
    row: ``jax.jit`` compiles lazily, so the first call is where XLA
    actually builds the program — timing only the trace/lower at the
    compile site would miss almost all of the cold-start cost.  The
    same call is the ``<owner>/first_call`` set-up span.  After the
    first call the wrapper is one bool check per dispatch.  ``lower``
    (traces and lowers the program again, for
    ``JaxXlaFilter.executable_text``) rides on the wrapper: a thunk, so
    that no IR stays pinned for the executable's lifetime."""
    done = [False]

    def wrapped(*args):
        if done[0]:
            return fn(*args)
        t0 = time.perf_counter()
        with _profile.span(owner, "first_call", setup=True):
            out = fn(*args)
        if not done[0]:
            done[0] = True
            COMPILE_STATS.add_seconds(stats_key,
                                      time.perf_counter() - t0)
        return out

    wrapped.lower = lower
    return wrapped


def _aot_call(lowered, jitted: Callable, pkey: Optional[str] = None,
              bucket: int = 0,
              devices: Optional[Sequence[Any]] = None,
              owner: str = "jax-xla") -> Callable:
    """Serve dispatches from an already-traced ``Lowered``: AOT-compile
    it on first use so the whole path costs one trace.  A program the
    backend refuses to BUILD raises here, once, with the backend's own
    error — ``jitted`` would hand XLA the same program, so retrying
    through it could only pay a second compile and lose the first
    message.  Only the AOT *call* signature (exact avals, no weak-type
    promotion) is stricter than jit dispatch: a rejected call cannot
    have consumed donated buffers, so that one case retries through
    ``jitted`` — logged, and counted as an ``aot_fallback`` compile so
    the second build is never silent.

    ``pkey`` (``runtime/compilecache.py``) arms the persistent AOT
    cache for this executable: the first use tries to DESERIALIZE the
    program from ``NNS_TPU_COMPILE_CACHE_DIR`` (counted as a
    ``persist_hit`` compile) onto ``devices`` — the executable's own
    device list — before paying the XLA build, and a fresh build is
    serialized back for the next process.  The weights are among the
    call's arguments, so an entry holds none and serves every set of
    the key's schema."""
    # the Lowered (traced jaxpr + IR) lives in state, not the closure's
    # free variables, so it can be dropped the moment the executable is
    # resolved — a long-running serving process must not pin megabytes
    # of IR per (model, bucket)
    state: Dict[str, Any] = {"lowered": lowered}
    del lowered

    def call(*args):
        fb = state.get("fb")
        if fb is not None:
            return fb(*args)
        compiled = state.get("c")
        if compiled is None:
            from ..runtime import compilecache as _pcache

            compiled = state["c"] = _pcache.load_or_compile(
                pkey, state["lowered"], bucket=bucket, devices=devices,
                owner=owner)
            state.pop("lowered", None)
        try:
            return compiled(*args)
        except (TypeError, ValueError) as e:
            # signature mismatch: permanently fall back before any
            # execution happened
            from ..utils.log import logw

            logw("jax-xla: AOT executable (bucket %d) rejected its "
                 "arguments, recompiling through jit: %s: %s",
                 bucket, type(e).__name__, e)
            COMPILE_STATS.record("aot_fallback", bucket=bucket)
            state["fb"] = jitted
            return jitted(*args)

    return call


def _avals_nbytes(avals) -> int:
    """Total payload bytes of a flat list of ShapeDtypeStructs."""
    return sum(int(np.prod(a.shape, dtype=np.int64))
               * np.dtype(a.dtype).itemsize for a in avals)


def _chain_sha(chain: str) -> str:
    """Short stable hash of a fused-chain digest string for the
    persistent-cache key (keeps filenames bounded; the full ordered
    digest string is what gets hashed, so order matters)."""
    import hashlib

    return hashlib.sha256(chain.encode()).hexdigest()[:16]


# -- in-process model registry ----------------------------------------------

_models: Dict[str, "ModelDef"] = {}
_models_lock = threading.Lock()


class ModelDef:
    """A jittable model: ``fn(params, *inputs) -> output(s)`` (or
    ``fn(*inputs)`` when params is None) plus its input schema."""

    def __init__(self, fn: Callable, params: Any = None,
                 in_spec: Optional[TensorsSpec] = None,
                 name: str = "<anonymous>"):
        self.fn = fn
        self.params = params
        self.in_spec = in_spec
        self.name = name
        self._dev_params: Dict[Any, Any] = {}  # device → placed pytree
        self._mesh_params: Dict[Any, Any] = {}  # (mesh, rules) → pytree

    def placed(self, device=None, mesh=None, rules=None
               ) -> Tuple[Callable, Any]:
        """``(fn, weights)``: the model as ``fn(weights, *inputs)`` and
        the weights it takes, on ``device`` — or, given a mesh, laid
        over it per the named ``rules`` (parallel.shard_params) — cached
        per device / per (mesh, rules) so shared and hot-reloaded
        instances don't transfer twice.  ``weights`` is the params
        pytree's ARRAY leaves under their own paths; a leaf that is no
        array (a Python int, float or string) is configuration: it stays
        the value ``fn`` closes over, and the program is built for it.
        The weights are arguments of whatever the caller compiles, never
        closed over: host leaves would be baked into the HLO as
        literals, and so would device arrays.  Committed to their place,
        they also pin the computation there (the accelerator=
        property).  A model without params takes ``None`` for them."""
        if self.params is None:
            return (lambda _weights, *inputs: self.fn(*inputs)), None
        jax = _jax()
        tree = jax.tree_util
        cache, key = (self._dev_params, device) if mesh is None \
            else (self._mesh_params, (mesh, rules))
        if key not in cache:
            t0 = time.perf_counter()
            if mesh is None:
                leaves, treedef = tree.tree_flatten(self.params)
                put = iter(jax.device_put(
                    [x for x in leaves if _is_weight(x)], device))
                cache[key] = tree.tree_unflatten(treedef, [
                    next(put) if _is_weight(x) else x for x in leaves])
            else:
                from ..parallel import shard_params

                cache[key] = tree.tree_map(
                    lambda was, put: put if _is_weight(was) else was,
                    self.params, shard_params(mesh, self.params, rules))
            nbytes = _xfer.params_nbytes(self.params)
            _xfer.record("h2d", "weights", nbytes,
                         time.perf_counter() - t0, source=self.name)
            _profile.note(f"{nbytes} B of weights put on {device}"
                          if mesh is None else
                          f"{nbytes} B of weights laid over "
                          f"{dict(mesh.shape)}")
        leaves, treedef = tree.tree_flatten(cache[key])
        at = [i for i, x in enumerate(leaves) if _is_weight(x)]
        static = [None if _is_weight(x) else x for x in leaves]
        inner = self.fn

        def fn(weights, *inputs):
            full = list(static)
            for i, w in zip(at, tree.tree_leaves(weights)):
                full[i] = w
            return inner(tree.tree_unflatten(treedef, full), *inputs)

        return fn, tree.tree_map(
            lambda x: x if _is_weight(x) else None, cache[key])


def _is_weight(leaf) -> bool:
    """An array leaf of a params pytree is a weight; anything else (a
    Python number, a string) is configuration."""
    return hasattr(leaf, "shape") and hasattr(leaf, "dtype")


def register_model(name: str, fn: Callable, params: Any = None,
                   in_spec: Optional[TensorsSpec] = None,
                   in_shapes: Optional[Sequence] = None,
                   in_dtypes: Any = None) -> str:
    """Register a jittable callable as a named model for ``model=name``."""
    with _profile.span(name, "register", setup=True):
        if in_spec is None and in_shapes is not None:
            in_spec = TensorsSpec.from_shapes(
                in_shapes,
                in_dtypes if in_dtypes is not None else np.float32)
        with _models_lock:
            _models[name] = ModelDef(fn, params, in_spec, name)
    return name


def unregister_model(name: str) -> None:
    with _models_lock:
        _models.pop(name, None)


def get_model(name: str) -> Optional[ModelDef]:
    with _models_lock:
        return _models.get(name)


# -- stateful models ---------------------------------------------------------


class StatefulModelDef(ModelDef):
    """A model that owns device state between invokes
    (``Documentation/stateful-models.md``): ``init_state(params) ->
    state`` and one or more entry points ``fn(params, state, *inputs) ->
    (state, outputs)``, each with the input schema it serves.  A filter
    runs the entry point whose schema its negotiated input matches.
    ``params`` and ``state`` are ARGUMENTS of the executable, never
    closed over: the compiled program holds no weight literal and serves
    every set of weights of the same shapes.  ``setup_entries`` names
    the entry points that build state ahead of a stream (a prefill):
    their calls are fenced inside a set-up span of their own name.
    ``counters(state)`` picks the small unsigned counters the steps keep
    in the state; ``counter_units(state)`` maps a published counter to
    ``(raw counter, what one count stands for)``, or to a list of such
    pairs that add up (a state of several kinds of cache publishes each
    kind's bytes and their sum).  The state is any pytree of arrays:
    its leaves may differ in shape from layer to layer."""

    def __init__(self, entries: Dict[str, Tuple[Callable, TensorsSpec]],
                 params: Any, init_state: Callable, name: str,
                 setup_entries: Sequence[str] = (),
                 counters: Optional[Callable] = None,
                 counter_units: Optional[Callable] = None):
        if not entries:
            raise ValueError("a stateful model needs an entry point")
        self.entries = dict(entries)
        super().__init__(None, params, next(iter(self.entries.values()))[1],
                         name)
        self.init_state = init_state
        self.setup_entries = tuple(setup_entries)
        self.counters = counters
        self.counter_units = counter_units

    def entry_for(self, in_spec: TensorsSpec) -> str:
        """The entry point that serves ``in_spec``."""
        for name, (_fn, spec) in self.entries.items():
            if in_spec.is_compatible(spec):
                return name
        raise FilterError(
            f"jax-xla: stateful model {self.name} has no entry point for "
            f"input {in_spec}; it serves "
            + "; ".join(f"{n}: {s}" for n, (_f, s) in self.entries.items()))


def register_stateful_model(name: str, entries: Dict[str, Tuple],
                            params: Any, init_state: Callable,
                            setup_entries: Sequence[str] = (),
                            counters: Optional[Callable] = None,
                            counter_units: Optional[Callable] = None) -> str:
    """Register a stateful model for ``model=name``.  ``entries`` maps
    an entry point's name to ``(fn, in_shapes, in_dtypes)`` (or ``(fn,
    TensorsSpec)``); the first is what an element negotiates by
    default."""
    with _profile.span(name, "register", setup=True):
        table = {}
        for ename, entry in entries.items():
            fn, spec = entry[0], entry[1]
            if not isinstance(spec, TensorsSpec):
                spec = TensorsSpec.from_shapes(
                    spec, entry[2] if len(entry) > 2 else np.float32)
            table[ename] = (fn, spec)
        with _models_lock:
            _models[name] = StatefulModelDef(
                table, params, init_state, name,
                setup_entries=setup_entries, counters=counters,
                counter_units=counter_units)
    return name


#: one jitted step per (entry function, device, argument shapes): two
#: sets of weights of one model share their programs
_stateful_programs: Dict[Tuple, Dict[str, Any]] = {}
_stateful_lock = threading.Lock()


def _tree_avals(tree) -> Any:
    jax = _jax()
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


class _StateCell:
    """The weights and the state of one stateful model on the device,
    shared by every filter opened on it (one filter, or all the filters
    of one ``shared-tensor-filter-key``): the state goes into each call
    donated and comes back, under ``lock``; the last sharer to close
    frees it."""

    def __init__(self, model: StatefulModelDef, device, owner: str,
                 table_key: Optional[str]):
        jax = _jax()
        self.model, self.device, self.table_key = model, device, table_key
        self.owner = owner
        self.lock = threading.Lock()
        self.refs = 0
        self.compiled: Dict[str, _Compiled] = {}
        self._last: Dict[str, int] = {}
        self._counters_built = False
        with _profile.span(owner, "state_init", setup=True) as made:
            t0 = time.perf_counter()
            self.params = jax.device_put(model.params, device)
            nbytes = _xfer.params_nbytes(model.params)
            _xfer.record("h2d", "weights", nbytes,
                         time.perf_counter() - t0, source=model.name)
            self.state = jax.device_put(model.init_state(self.params),
                                        device)
            jax.block_until_ready(self.state)
            self.state_bytes = sum(
                int(a.nbytes)
                for a in jax.tree_util.tree_leaves(self.state))
            made.note = (f"{nbytes} B of weights put on {device}, "
                         f"{self.state_bytes} B of state made")
        STATE_STATS.add("state_bytes", self.state_bytes)

    def step(self, program: Callable, inputs: Sequence[Any]):
        """One call of ``program(params, state, *inputs)``: the state is
        donated to it and the returned one kept."""
        with self.lock:
            state, self.state = self.state, None
            if state is None:
                raise FilterError(
                    f"jax-xla: the state of {self.model.name} was lost "
                    "(freed, or consumed by a call that failed)")
            self.state, out = program(self.params, state, *inputs)
        return out

    def fetch_counters(self) -> None:
        """Read the counters the steps keep in the state (a fence: call
        it at the stats-sample cadence, never per step) and add what
        they gained since the last read to ``STATE_STATS``."""
        model = self.model
        if model.counters is None:
            return
        if not self._counters_built:
            # the first read builds the little programs that pick the
            # counters out of the state (0.8 s of the first window where
            # there are many): set-up, under a span of its own
            self._counters_built = True
            with _profile.span(self.owner, "counters_init", setup=True):
                self._fetch_counters(model)
            return
        self._fetch_counters(model)

    def _fetch_counters(self, model: StatefulModelDef) -> None:
        with self.lock:
            if self.state is None:
                return
            raw = _jax().device_get(model.counters(self.state))
            units = model.counter_units(self.state) \
                if model.counter_units is not None else {}
        gained = {}
        for name, value in raw.items():
            value = int(value)
            gained[name] = (value - self._last.get(name, 0)) % (1 << 32)
            self._last[name] = value
        published = {
            name: sum(gained.get(raw_name, 0) * unit for raw_name, unit
                      in ([terms] if isinstance(terms, tuple) else terms))
            for name, terms in units.items()}
        gained.update(published)
        for name, n in gained.items():
            STATE_STATS.add(name, n)

    def release(self) -> None:
        with self.lock:
            self.refs -= 1
            if self.refs > 0:
                return
            state, self.state = self.state, None
            self.compiled.clear()
        if self.table_key is not None:
            SHARED_MODELS.remove(self.table_key)
        if state is not None:
            for leaf in _jax().tree_util.tree_leaves(state):
                leaf.delete()
            STATE_STATS.add("state_bytes", -self.state_bytes)


# -- the sub-plugin ----------------------------------------------------------


class _Program:
    """The per-frame computation of one (model, input schema) as every
    window path compiles it: ``fn(weights, *inputs)``, outputs
    normalized to a tuple, and the ``weights`` it takes now — what the
    model's weights prologue (``filters/weightsplit.py``) made of the
    placed params, on the device; empty for a model without params,
    and over a mesh, where the weights are still closed over.
    The executables built from ``fn`` hold no weight:
    :meth:`rebound` gives the same program over another set of weights
    of the same shapes, which is all a weights-only swap is."""

    __slots__ = ("fn", "weights", "with_pre", "with_post", "_serves",
                 "_make")

    def __init__(self, fn, weights, with_pre, with_post, serves=None,
                 make=None):
        self.fn = fn
        self.weights = weights
        self.with_pre = with_pre
        self.with_post = with_post
        self._serves = serves   # (model fn, _params_schema)
        self._make = make       # placed weights -> the program's weights

    def avals(self) -> List[Any]:
        """What an executable is lowered for in the weights' place:
        their shapes, dtypes and shardings, and nothing of their
        values."""
        jax = _jax()
        return [jax.ShapeDtypeStruct(w.shape, w.dtype, sharding=w.sharding)
                for w in self.weights]

    def over_frames(self, nt: int) -> Callable:
        """``fn`` over a leading axis of its ``nt`` inputs: the weights
        are every frame's."""
        return _jax().vmap(self.fn, in_axes=(None,) + (0,) * nt)

    def rebound(self, model: ModelDef, weights) -> Optional["_Program"]:
        """This program over ``weights`` (``model``'s placed array
        leaves): the prologue run on them, no trace and no build.  None
        where they are another function's or differ in a shape, a dtype,
        a sharding or a non-array leaf's value."""
        if self._make is None or self._serves != (
                model.fn, _params_schema(model.params, weights)):
            return None
        return _Program(self.fn, self._make(weights), self.with_pre,
                        self.with_post, self._serves, self._make)


def _params_schema(params, weights) -> Tuple:
    """What a program is built for of its model's params: the tree, the
    value of every leaf that is no array (the function closes over it)
    and every placed array leaf's shape, dtype and sharding."""
    tree = _jax().tree_util
    leaves, treedef = tree.tree_flatten(params)
    return (treedef, tuple(x for x in leaves if not _is_weight(x)),
            tuple((w.shape, w.dtype, w.sharding)
                  for w in tree.tree_leaves(weights)))


class _Compiled:
    """One compiled schema-specialized executable + its I/O specs.
    ``jitted(*inputs)`` runs ``exe(program.weights, *inputs)``: the
    executable takes the weights as its leading argument and
    ``program`` holds the ones this instance serves (``exe`` and
    ``program`` are None for a stateful model, whose cell threads its
    own).  ``with_pre`` records whether a fused transform prologue was
    baked in, so negotiation can detect a stale executable after the
    fusion pass re-derives (e.g. the element was re-used unfused).
    ``in_shardings`` (mesh path only) holds the per-input NamedSharding
    the executable was specialized to, so ``invoke`` can place incoming
    host/foreign arrays without a resharding surprise.  ``with_post``
    mirrors ``with_pre`` for a fused downstream epilogue (decoder
    overlay fusion)."""

    __slots__ = ("jitted", "in_spec", "out_spec", "with_pre", "with_post",
                 "in_shardings", "exe", "program")

    def __init__(self, jitted, in_spec: TensorsSpec, out_spec: TensorsSpec,
                 with_pre: bool = False, with_post: bool = False,
                 in_shardings=None, exe=None, program=None):
        self.jitted = jitted
        self.in_spec = in_spec
        self.out_spec = out_spec
        self.with_pre = with_pre
        self.with_post = with_post
        self.in_shardings = in_shardings
        self.exe = exe
        self.program = program

    @classmethod
    def bound(cls, exe, program: _Program, in_spec, out_spec,
              in_shardings=None) -> "_Compiled":
        """``exe`` (weights first) serving ``program``'s weights."""
        def jitted(*inputs):
            return exe(program.weights, *inputs)

        jitted.lower = exe.lower
        return cls(jitted, in_spec, out_spec, program.with_pre,
                   program.with_post, in_shardings, exe, program)


@register_filter
class JaxXlaFilter(FilterSubplugin):
    NAME = "jax-xla"
    ACCELERATORS = ("tpu", "cpu")
    ALLOCATE_IN_INVOKE = True
    #: micro-batching capability: TensorFilter batch>1 routes coalesced
    #: windows through invoke_batched (one dispatch per micro-batch)
    SUPPORTS_BATCH = True

    #: shared-instance table backing ``open_shared``/``close_shared``
    #: (the serving pool's framework-level dedup): key -> [instance,
    #: refcount].  One entry means ONE params copy in HBM and ONE
    #: per-bucket executable cache no matter how many filters share it.
    _shared_lock = threading.Lock()
    _shared_instances: Dict[Tuple, list] = {}

    def __init__(self):
        super().__init__()
        self._model: Optional[ModelDef] = None
        self._compiled: Optional[_Compiled] = None
        self._swap_lock = threading.Lock()
        self._shared_refs = 0  # >0 when this instance came from open_shared
        # micro-batch executables, keyed by (in_spec, bucket): the set of
        # compiled shapes is bounded by the bucket list, not by how many
        # distinct window sizes the traffic produces
        self._batch_exec: Dict[Tuple[TensorsSpec, int], Any] = {}
        self._batch_lock = threading.Lock()
        self.batch_cache_hits = 0
        self.batch_cache_misses = 0
        # per-bucket split of the hit/miss counters, for the registry's
        # nns_executable_cache_{hits,misses}_total{...,bucket} export
        # (guarded by _batch_lock like the aggregates)
        self._cache_by_bucket: Dict[int, List[int]] = {}  # b -> [hit, miss]
        self._device = None
        self._dev_kind: Optional[str] = None
        self._donate = False
        self._pre_chains: list = []  # fused transform op chains, in order
        self._post_fns: list = []    # fused downstream epilogue (≤1)
        # the ONE placement object (parallel/placement.py): resolved
        # from mesh=/sharding=/devices= at configure; _mesh/_rules/
        # _data_axis below are views of it kept for introspection
        # (element props, tests) — every compile/dispatch seam reads
        # self._placement
        self._placement = None       # parallel.ResolvedPlacement
        self._mesh = None            # jax.sharding.Mesh (mesh= property)
        self._rules = None           # param-layout rules (sharding= property)
        self._data_axis: Optional[str] = None
        # compile-stats attribution override: a swap SHADOW's configure
        # compile is a "reload", not a "cold" start (set by
        # prepare_swap before configure)
        self._compile_kind: Optional[str] = None
        # what serves on the instance a swap SHADOW stands in for (set
        # by prepare_swap before configure): new weights of its shapes
        # take its executables instead of building their own
        self._donor: Optional[_Compiled] = None
        # who the spans of this instance's work are named after
        # (utils/profile.py): the owning element sets its own name, a
        # serving pool its label
        self.trace_owner = self.NAME
        # the weights and state of a stateful model (None for every
        # other model): what this instance's calls thread through
        self._cell: Optional[_StateCell] = None

    def set_fused_pre(self, chains: list) -> None:
        """Install upstream transform op chains (runtime/fusion.py) to be
        compiled into this filter's program.  They apply at the NEXT
        (re)compile — the fusion pass runs before negotiation, and
        negotiation always recompiles via set_input_info when chains are
        present.  The list is kept BY REFERENCE: a transform that unfuses
        during negotiation (flexible stream) removes its chain in place
        and the change must be visible here."""
        self._pre_chains = chains

    def set_fused_post(self, posts: list) -> None:
        """Install a downstream epilogue (runtime/fusion.py decoder
        fusion): a jit-inlinable fn mapping the model's output tuple to
        the fused output tuple (e.g. the bounding-box device overlay —
        one dispatch for transform+model+NMS+overlay).  Same by-
        reference contract as :meth:`set_fused_pre`."""
        self._post_fns = posts

    # -- lifecycle -----------------------------------------------------------

    def configure(self, props: FilterProps) -> None:
        super().configure(props)
        self._parse_accelerator(props.accelerator)
        self._donate = "donate" in (props.custom or "")
        if getattr(props, "sharding", "") and not getattr(props, "mesh", ""):
            raise FilterError(
                f"jax-xla: sharding={props.sharding!r} requires mesh=")
        if getattr(props, "devices", "") and not getattr(props, "mesh", ""):
            raise FilterError(
                f"jax-xla: devices={props.devices!r} requires mesh=")
        if getattr(props, "mesh", ""):
            self._build_mesh(props)
        shared = None
        # the table key carries the CANONICAL placement key: instances
        # that share a model name but differ in placement must not
        # collide, while equivalent spellings (data:-1 vs data:8 on an
        # 8-device host) must — parallel/placement.py is the one
        # definition of "same placement".  Reuse the key of the
        # placement just resolved instead of resolving again.
        table_key = f"jax-xla:{props.shared_key}:" \
            f"{self._placement.key if self._placement is not None else self._placement_key(props)}"
        if props.shared_key:
            shared = SHARED_MODELS.get(table_key)
        if isinstance(shared, _StateCell):
            self._open_stateful(shared.model, props, table_key, shared)
            return
        if shared is not None:
            self._model, self._compiled = shared
            return
        self._model = self._resolve_model(props.model)
        if isinstance(self._model, StatefulModelDef):
            self._open_stateful(self._model, props,
                                table_key if props.shared_key else None)
            return
        in_spec = props.input_spec or self._model.in_spec
        if in_spec is None:
            raise FilterError(
                f"jax-xla: model {self._model.name} has no input spec; pass "
                "input_spec or register with in_shapes")
        self._compiled = self._compile(self._model, in_spec)
        if props.shared_key:
            self._model, self._compiled = SHARED_MODELS.insert(
                table_key, (self._model, self._compiled))

    def close(self) -> None:
        cell, self._cell = self._cell, None
        if cell is not None:
            cell.release()      # the last sharer frees the state
        self._compiled = None
        self._model = None
        with self._batch_lock:
            self._batch_exec.clear()

    def cache_snapshot(self) -> dict:
        """One consistent read of the per-bucket executable-cache
        hit/miss counters — the pull API the metrics registry scrapes
        (``nns_executable_cache_{hits,misses}_total``)."""
        with self._batch_lock:
            return {
                "hits": self.batch_cache_hits,
                "misses": self.batch_cache_misses,
                "by_bucket": {str(b): {"hits": hm[0], "misses": hm[1]}
                              for b, hm in
                              sorted(self._cache_by_bucket.items())},
            }

    def executable_text(self, bucket: int = 0) -> str:
        """The optimised HLO of one of this instance's programs as XLA
        compiled it (``bucket`` 0: the single-frame, or whole-window
        replay, program; else the micro-batch program of that bucket):
        every instruction, fusions included, carries the ``nns.pre`` /
        ``nns.model/<stage>`` / ``nns.post`` scope it came from in its
        ``op_name`` metadata, which is how
        ``utils/profile.stage_seconds`` names a device operation where
        the trace itself does not.  Traces, lowers and compiles the
        program again (a persistent compile cache makes that a load),
        so it is for an operator's hand, not a hot path."""
        c = self._compiled
        if c is None:
            raise FilterError("jax-xla: not configured")
        fn = c.jitted
        if bucket:
            with self._batch_lock:
                fn = self._batch_exec.get((c.in_spec, bucket)) or \
                    self._batch_exec.get((c.in_spec, bucket, "stacked"))
            if fn is None:
                raise FilterError(
                    f"jax-xla: no program of bucket {bucket} compiled yet")
        return fn.lower().compile().as_text()

    def model_name(self) -> str:
        """Name of the model this instance serves ("" before
        configure) — the join key the obs layer maps dispatch sources
        (element names, pool labels) to executable cost rows with."""
        return self._model.name if self._model is not None else ""

    def _placement_label(self) -> str:
        """Where this instance's executables run: ``mesh(<axes>)`` on a
        mesh, else the selected device platform — the ``placement``
        label on the ``nns_executable_*`` gauges."""
        if self._placement is not None:
            return self._placement.describe()
        return self._dev_kind or (self._device.platform
                                  if self._device is not None else "")

    def _platform(self) -> str:
        if self._placement is not None:
            return self._placement.platform
        return self._device.platform if self._device is not None else ""

    def weight_bytes(self) -> Optional[dict]:
        """Weight-footprint pull API for the metrics registry
        (``nns_model_weight_bytes{pool,placement}``): total param bytes
        and where they currently live — ``host`` before placement,
        ``device`` once committed via device_put, ``mesh`` when laid
        out over a mesh.  None for param-less models."""
        model = self._model
        if model is None or model.params is None:
            return None
        placement = "mesh" if model._mesh_params else (
            "device" if model._dev_params or self._cell is not None
            else "host")
        return {"bytes": _xfer.params_nbytes(model.params),
                "placement": placement}

    # -- shared instances (ModelPool / open_shared) --------------------------

    @staticmethod
    def _placement_key(props: FilterProps) -> Tuple:
        """Canonical placement key of a props set — the one identity
        ``parallel.Placement`` resolves every equivalent spelling to."""
        from ..parallel import Placement

        return Placement.from_props(props).key()

    @classmethod
    def _share_key(cls, props: FilterProps) -> Tuple:
        model = props.model
        mkey = model if isinstance(model, str) else f"obj:{id(model)}"
        return (mkey, cls._placement_key(props),
                str(props.custom or ""),
                str(props.input_spec or ""), str(props.output_spec or ""),
                str(props.shared_key or ""))

    @classmethod
    def open_shared(cls, props: FilterProps) -> "JaxXlaFilter":
        """Ref-counted shared open: ONE instance per (model, placement,
        custom, forced-spec) config — N sharers see one params copy and
        one lock-protected executable cache.  Pair every call with
        :meth:`close_shared`."""
        key = cls._share_key(props)
        with cls._shared_lock:
            ent = cls._shared_instances.get(key)
            if ent is None:
                sp = cls()
                sp.configure(props)
                ent = cls._shared_instances[key] = [sp, 0]
            ent[1] += 1
            ent[0]._shared_refs = ent[1]
            return ent[0]

    @classmethod
    def close_shared(cls, sp: "JaxXlaFilter") -> None:
        """Drop one reference; the instance closes only when the last
        sharer releases it.  An instance not found in the table (a plain
        ``configure`` open handed in by mistake) closes immediately."""
        last = False
        with cls._shared_lock:
            for key, ent in list(cls._shared_instances.items()):
                if ent[0] is sp:
                    ent[1] -= 1
                    sp._shared_refs = max(ent[1], 0)
                    if ent[1] <= 0:
                        del cls._shared_instances[key]
                        last = True
                    break
            else:
                last = True
        if last:
            sp.close()

    def _parse_accelerator(self, accl: str) -> None:
        """Parity: parse_accl_hw_fill (tensor_filter_common.c). Grammar:
        "true:tpu", "tpu", "cpu", "" (auto = first platform device).
        The kind parse is the SHARED one (parallel.parse_accel_kind)
        so the canonical placement key and the device selection can
        never disagree."""
        from ..parallel import parse_accel_kind

        jax = _jax()
        kind = parse_accel_kind(accl)
        try:
            devs = jax.devices(kind) if kind else jax.devices()
        except RuntimeError as e:
            raise FilterError(f"jax-xla: no {kind} devices: {e}") from None
        self._dev_kind = kind
        self._device = devs[0]

    def _build_mesh(self, props: FilterProps) -> None:
        """Resolve the ``mesh=`` / ``sharding=`` / ``devices=`` properties
        through the ONE placement layer (parallel/placement.py) into a
        device mesh + param-layout rules.  The mesh is laid over the
        devices the ``accelerator=`` property selected (so tests run the
        same code path on the 8-virtual-CPU mesh that production runs over
        a TPU slice); ``devices=`` restricts it to an index subset, the
        SUBMESH placement that lets two pipeline stages occupy disjoint
        chips with device-to-device handoff between their invokes; and
        ``dcn.``-prefixed axes span the processes of a jax.distributed
        group (the multi-host placement — one logical model served by a
        fleet of processes).  SURVEY.md §7.6: this is the pjit redesign
        of the reference's remote tensor_filter
        (tensor_query_client.c:673-741) — the "query servers" are chips
        on the mesh and the transport is ICI/DCN."""
        from ..parallel import Placement

        try:
            self._placement = Placement.from_props(props).resolve(
                self._dev_kind)
        except (ValueError, TypeError) as e:
            raise FilterError(f"jax-xla: mesh {props.mesh!r}: {e}") from e
        rp = self._placement
        self._mesh = rp.mesh
        self._rules = rp.rules
        self._data_axis = rp.data_axis

    def _resolve_model(self, model) -> ModelDef:
        if isinstance(model, ModelDef):
            return model
        if callable(model):
            return ModelDef(model)
        if isinstance(model, str):
            m = get_model(model)
            if m is not None:
                return m
            if os.path.isfile(model):
                return self._load_file(model)
            raise FilterError(
                f"jax-xla: model {model!r} is neither a registered name nor "
                "a file")
        raise FilterError(f"jax-xla: unsupported model object {type(model)}")

    def _load_file(self, path: str) -> ModelDef:
        ext = os.path.splitext(path)[1].lower()
        if ext in (".npz", ".safetensors"):
            return self._load_weights_file(path, ext)
        if ext in (".jaxexp", ".stablehlo", ".mlir"):
            jax = _jax()
            with open(path, "rb") as f:
                exported = jax.export.deserialize(bytearray(f.read()))
            in_spec = TensorsSpec.from_shapes(
                [a.shape for a in exported.in_avals],
                [np.dtype(a.dtype) for a in exported.in_avals])
            return ModelDef(exported.call, None, in_spec, name=path)
        if ext in (".pkl", ".msgpack"):
            return self._load_pickled(path, ext)
        raise FilterError(f"jax-xla: unsupported model file type {ext!r}")

    def _load_weights_file(self, path: str, ext: str) -> ModelDef:
        """Checkpoint-interop model files (models/params_io.py): a
        weight pytree plus an ``apply`` "module:callable" import path in
        the metadata — so npz/safetensors checkpoints are directly
        loadable via ``model=weights.safetensors`` (parity: the
        reference's framework-native checkpoint loading,
        tensor_filter_tensorflow_lite.cc:242-280)."""
        import json
        import struct as _struct

        from ..models.params_io import load_npz, load_safetensors

        try:
            params, meta = (load_npz(path) if ext == ".npz"
                            else load_safetensors(path))
            in_shapes = meta.get("in_shapes")
            if isinstance(in_shapes, str):
                in_shapes = json.loads(in_shapes)
        except (ValueError, KeyError, OSError, _struct.error,
                json.JSONDecodeError) as e:
            raise FilterError(f"jax-xla: {path}: {e}") from e
        apply = meta.get("apply")
        if not apply:
            raise FilterError(
                f"jax-xla: {path} carries no 'apply' metadata (write it "
                "with models.params_io.save_npz/save_safetensors)")
        fn = self._resolve_apply(apply, path)
        in_spec = None
        if in_shapes:
            in_spec = TensorsSpec.from_shapes(
                in_shapes, np.dtype(meta.get("in_dtypes") or "float32"))
        return ModelDef(fn, params, in_spec, name=path)

    def _resolve_apply(self, target, path: str) -> Callable:
        import importlib

        if callable(target):
            return target
        if isinstance(target, str):
            mod, _, attr = target.partition(":")
            try:
                return getattr(importlib.import_module(mod), attr)
            except (ImportError, AttributeError) as e:
                raise FilterError(
                    f"jax-xla: cannot resolve apply {target!r} "
                    f"({path}): {e}") from e
        raise FilterError(f"jax-xla: bad apply entry {type(target)}")

    def _load_pickled(self, path: str, ext: str) -> ModelDef:
        """Params-file format: a dict with ``apply`` = "module:callable"
        import path, ``params`` = weight pytree, optional ``in_shapes`` /
        ``in_dtypes`` — the framework's analog of a checkpoint file consumed
        by a named architecture (cf. caffe2's two-file init/predict model,
        tensor_filter_caffe2.cc)."""
        if ext == ".pkl":
            import pickle

            with open(path, "rb") as f:
                blob = pickle.load(f)
        else:
            try:
                from flax import serialization
            except ImportError as e:
                raise FilterError(
                    f"jax-xla: .msgpack needs flax: {e}") from None
            with open(path, "rb") as f:
                blob = serialization.msgpack_restore(f.read())
        if not isinstance(blob, dict) or "apply" not in blob:
            raise FilterError(
                f"jax-xla: {path} must hold a dict with an 'apply' "
                "\"module:callable\" entry")
        fn = self._resolve_apply(blob["apply"], path)
        in_spec = None
        if blob.get("in_shapes") is not None:
            in_spec = TensorsSpec.from_shapes(
                blob["in_shapes"], blob.get("in_dtypes", np.float32))
        return ModelDef(fn, blob.get("params"), in_spec, name=path)

    # -- compile -------------------------------------------------------------

    def _chain_digest(self) -> Optional[str]:
        """Ordered identity of every fused stage baked into this
        instance's executables (transform prologues + decoder
        epilogue), or None when ANY fused stage is un-digestable —
        the caller must then keep the program out of the persistent
        cache, because a wrong hit is the one failure mode a compile
        cache must never have.  Empty string: nothing is fused."""
        parts: List[str] = []
        for c in self._pre_chains:
            if not hasattr(c, "digest"):
                return None
            parts.append("pre:" + c.digest())
        for p in self._post_fns:
            dig = getattr(p, "chain_digest", None)
            if dig is None:
                return None
            parts.append("post:" + dig)
        return ";".join(parts)

    def _persist_key(self, model: ModelDef, in_spec: Any,
                     bucket: int) -> Optional[str]:
        """Persistent-cache key for one executable of this instance
        (``runtime/compilecache.py``), or None when the cache is
        disarmed — or when a fused stage carries no digest.  Fused
        whole-graph programs key on the model digest PLUS the ordered
        chain digest (transform op chains, decoder epilogue config), so
        they get warm-process cold starts like plain models do while a
        changed stage config misses instead of wrongly hitting."""
        from ..runtime import compilecache as _pcache

        if not _pcache.enabled():
            return None
        chain = self._chain_digest()
        if chain is None:
            return None
        model_dig = _pcache.model_digest(model)
        if chain:
            model_dig = f"{model_dig}+chain:{_chain_sha(chain)}"
        placement = self._placement.key if self._placement is not None \
            else ("dev", self._dev_kind or "")
        return _pcache.make_key(model_dig, in_spec,
                                bucket, placement,
                                donate=self._donate)

    def _exec_devices(self) -> List[Any]:
        """The devices this instance's executables run on, in
        assignment order — what a persisted executable must be loaded
        back onto (``runtime/compilecache.load``)."""
        if self._mesh is not None:
            return list(self._mesh.devices.flat)
        return [self._device]

    def _capture_cost(self, model: ModelDef, lowered, bucket: int,
                      avals) -> None:
        """Executable cost capture (obs/xlacost.py) off a jit lowering:
        static flops/bytes, keyed (model, bucket), with where — and on
        what kind of device — the executable runs.  What the capture
        costs every program build is the set-up span
        ``<filter>/cost_capture``."""
        with _profile.span(self.trace_owner, "cost_capture", setup=True):
            _xlacost.capture(
                model.name, lowered, bucket=bucket,
                placement=self._placement_label(),
                platform=self._platform(),
                device_kind=self._exec_devices()[0].device_kind,
                in_bytes=_avals_nbytes(avals),
                out_bytes=_avals_nbytes(
                    _jax().tree_util.tree_leaves(lowered.out_info)))

    def _normalized_fn(self, model: ModelDef, in_spec: TensorsSpec
                       ) -> _Program:
        """The per-frame computation as one traceable callable, weights
        first: fused transform prologue + model + fused decoder
        epilogue, outputs normalized to a tuple.  Built once per
        ``_compile`` and shared by the single-frame executable and the
        per-bucket micro-batch ones (which vmap it over the frames).

        A model that carries params (on one device: see below for a
        mesh) is traced here once with its weights abstract and split (``filters/weightsplit.py``): what it
        computes from weights alone is the weights prologue, a small
        program of its own that runs inside ``<filter>/state_init``;
        the rest, with the prologue's results as its leading argument,
        is what the window paths compile."""
        jax = _jax()
        # a stateless model's share of <filter>/state_init: its weights
        # go onto the device (or over the mesh) the first time only
        with _profile.span(self.trace_owner, "state_init", setup=True):
            fn, placed = model.placed(self._device, self._mesh, self._rules)
        pre = self._pre_fns(in_spec) if self._pre_chains else None
        post = self._post_fns[0] if self._post_fns else None

        scope = jax.named_scope

        def normalized(weights, *inputs):
            # the stage vocabulary of the device trace
            # (Documentation/observability.md): scopes are HLO metadata
            # only and cost nothing at run time
            if pre is not None:
                with scope("nns.pre"):
                    inputs = [g(x) for g, x in zip(pre, inputs)]
            with scope("nns.model"):
                out = fn(weights, *inputs)
            out = tuple(out) if isinstance(out, (list, tuple)) else (out,)
            if post is not None:
                # fused downstream epilogue (decoder device overlay):
                # still ONE XLA program, one dispatch
                with scope("nns.post"):
                    out = tuple(post(*out))
            return out

        if placed is None or self._mesh is not None:
            # Over a mesh the weights stay literals of the program, as
            # they were: with the SSD's weights as arguments XLA fuses
            # a chip's 64 frames of the backbone differently and
            # ``ssd300.replay.mesh4`` lost 8 % of its rate (PERF.md
            # section 6, PR 51; section 7 has what to try).  So a meshed
            # filter's program still changes with its weights, and a
            # swap there builds.
            return _Program(
                lambda _none, *inputs: normalized(placed, *inputs), [],
                pre is not None, post is not None)
        try:
            with _profile.span(self.trace_owner, "trace_lower", setup=True):
                parts = _weightsplit.trace(normalized, placed, *[
                    jax.ShapeDtypeStruct(t.shape, t.dtype.np_dtype)
                    for t in in_spec.tensors])
        except jax.errors.ConcretizationTypeError as e:
            import re

            read = sorted(set(re.findall(r"weights((?:\[[^\]]+\])+)",
                                         str(e))))
            raise FilterError(
                f"jax-xla: model {model.name} reads the VALUE of "
                + (f"its weight{'s' if len(read) > 1 else ''} "
                   f"{', '.join(read)}" if read else "an argument")
                + " while it is traced (a shape, an index or a branch "
                "taken from an array's contents).  Its weights are "
                "arguments of the program, which is built for their "
                "shapes alone: keep such a constant as a Python number "
                f"in the params, or in the function's closure.  {e}"
            ) from e
        except Exception as e:
            raise FilterError(
                f"jax-xla: model {model.name} rejects input {in_spec}: {e}"
            ) from e
        with _profile.span(self.trace_owner, "state_init", setup=True):
            weights = parts.make(placed)
        return _Program(
            parts.window, weights, pre is not None, post is not None,
            serves=(model.fn, _params_schema(model.params, placed)),
            make=parts.make)

    def _reuse(self, model: ModelDef, in_spec: TensorsSpec
               ) -> Optional[_Compiled]:
        """The serving executable over ``model``'s weights, where a
        swap shadow's model differs from the one that serves in its
        weights' values alone (same function, shapes, dtypes, shardings
        and fused stages): a placement and a run of the prologue, no
        trace, no lowering and no build — counted as a ``reuse``."""
        cur = self._donor
        if cur is None or cur.program is None or cur.in_spec != in_spec \
                or cur.with_pre != bool(self._pre_chains) \
                or cur.with_post != bool(self._post_fns):
            return None
        t0 = time.perf_counter()
        with _profile.span(self.trace_owner, "state_init", setup=True):
            _fn, placed = model.placed(self._device, self._mesh, self._rules)
            program = cur.program.rebound(model, placed)
        if program is None:
            return None
        COMPILE_STATS.record("reuse", time.perf_counter() - t0)
        return _Compiled.bound(cur.exe, program, cur.in_spec, cur.out_spec,
                               cur.in_shardings)

    def _compile(self, model: ModelDef, in_spec: TensorsSpec,
                 kind: str = "cold") -> _Compiled:
        jax = _jax()
        reused = self._reuse(model, in_spec)
        if reused is not None:
            return reused
        if self._compile_kind is not None:
            kind = self._compile_kind
        mesh = self._mesh
        owner = self.trace_owner
        t_compile0 = time.perf_counter()
        program = self._normalized_fn(model, in_spec)
        nt = in_spec.num_tensors
        kw = {}
        if self._donate:
            # the inputs alone: the weights serve every call
            kw["donate_argnums"] = tuple(range(1, 1 + nt))
        in_shardings = None
        if mesh is not None:
            in_shardings = tuple(
                self._input_sharding(t) for t in in_spec.tensors)
            kw["in_shardings"] = (
                [w.sharding for w in program.weights], *in_shardings)
        jitted = jax.jit(program.fn, **kw)
        # Infer output schema without running the device: the jit
        # LOWERING yields the out avals AND the executable's static
        # cost (HLO cost analysis — no XLA build, measured ~1 ms) in
        # one trace.
        w_avals = program.avals()
        avals = [jax.ShapeDtypeStruct(t.shape, t.dtype.np_dtype)
                 for t in in_spec.tensors]
        try:
            with _profile.span(owner, "trace_lower", setup=True):
                lowered = jitted.lower(w_avals, *avals)
        except Exception as e:
            raise FilterError(
                f"jax-xla: model {model.name} rejects input {in_spec}: {e}"
            ) from e
        out_avals = jax.tree_util.tree_leaves(lowered.out_info)
        # compile telemetry: one count per _compile call (`kind` names
        # the path — cold/reshape/reload), seconds = trace+abstract-eval
        # here plus the executable's first invocation (the lazy XLA
        # compile) attributed via the wrapper
        skey = COMPILE_STATS.record(
            kind, time.perf_counter() - t_compile0)
        out_spec = TensorsSpec.from_shapes(
            [o.shape for o in out_avals],
            [np.dtype(o.dtype) for o in out_avals])
        # bucket 0 is the single-frame executable; a reshape/reload
        # overwrites the row so the gauges describe what currently serves
        self._capture_cost(model, lowered, 0, avals)
        fn = jitted
        pkey = self._persist_key(model, in_spec, 0)
        if pkey is not None:
            # persistent cache armed: serve the single-frame path AOT
            # off this same lowering too, so a warm-cache process skips
            # the XLA build here exactly like on the bucket path
            fn = _aot_call(lowered, jitted, pkey=pkey, bucket=0,
                           devices=self._exec_devices(), owner=owner)
        return _Compiled.bound(
            _timed_first_call(fn, skey, owner,
                              lambda: jitted.lower(w_avals, *avals)),
            program, in_spec, out_spec, in_shardings)

    def _input_sharding(self, tspec: TensorSpec):
        """Batch-shard an input over the placement's data axes when its
        leading dim divides the data parallelism; replicate otherwise
        (small/odd inputs — e.g. a batch=1 frame on an 8-chip mesh —
        must still run)."""
        return self._placement.input_sharding(tspec.shape)

    def _pre_fns(self, in_spec: TensorsSpec):
        """Per-input composition of the fused transform chains: traces
        each chain's op fn for the schema flowing into it, so the whole
        prologue + model compiles as one XLA program."""
        specs = list(in_spec.tensors)
        stages = []  # list of per-tensor fn lists, chain-major
        for chain in self._pre_chains:
            stages.append([chain.fn_for(sp) for sp in specs])
            specs = [chain.out_spec_of(sp) for sp in specs]

        # one inner scope per fused transform, by element name
        names = [getattr(chain, "scope", None) or "transform"
                 for chain in self._pre_chains]
        scope = _jax().named_scope

        def compose(i):
            fns = [st[i] for st in stages]

            def g(x):
                for name, f in zip(names, fns):
                    with scope(name):
                        x = f(x)
                return x

            return g

        return [compose(i) for i in range(len(in_spec.tensors))]

    # -- stateful models -----------------------------------------------------

    def _open_stateful(self, model: StatefulModelDef, props: FilterProps,
                       table_key: Optional[str],
                       cell: Optional[_StateCell] = None) -> None:
        """Open on a stateful model: join the cell ``table_key`` names
        in the shared-model table (one set of weights and one state for
        every filter of one ``shared-tensor-filter-key``) or make one of
        this instance's own, and pick the entry point the input schema
        asks for."""
        if self._mesh is not None:
            raise FilterError(
                f"jax-xla: stateful model {model.name} cannot be laid "
                "over a mesh yet (mesh= shards the batch of a stateless "
                "program; a sharded state has no placement rule)")
        if cell is None:
            cell = _StateCell(model, self._device, self.trace_owner,
                              table_key)
            if table_key is not None:
                kept = SHARED_MODELS.insert(table_key, cell)
                if kept is not cell:      # lost a race: use the winner's
                    cell.refs = 1
                    cell.table_key = None
                    cell.release()
                    cell = kept
        with cell.lock:
            cell.refs += 1
        self._model, self._cell = model, cell
        self._compiled = self._compile_stateful(
            props.input_spec or model.in_spec)

    def _compile_stateful(self, in_spec: TensorsSpec) -> _Compiled:
        """The executable of the entry point that serves ``in_spec``:
        ``step(params, state, *inputs) -> (state, outputs)`` with the
        state donated.  Weights and state are arguments, so the program
        is keyed by shapes alone and is shared by every cell (every set
        of weights) of those shapes."""
        jax = _jax()
        cell, model, owner = self._cell, self._model, self.trace_owner
        if self._pre_chains or self._post_fns:
            raise FilterError(
                f"jax-xla: stateful model {model.name} cannot take a "
                "fused transform or decoder stage")
        entry = model.entry_for(in_spec)
        done = cell.compiled.get(entry)
        if done is not None:
            return done
        fn, spec = model.entries[entry]
        avals = [jax.ShapeDtypeStruct(t.shape, t.dtype.np_dtype)
                 for t in spec.tensors]
        p_avals, s_avals = _tree_avals(cell.params), _tree_avals(cell.state)
        key = (fn, cell.device, str(p_avals), str(s_avals), spec)
        with _stateful_lock:
            prog = _stateful_programs.get(key)
        if prog is None:
            t0 = time.perf_counter()

            def step(params, state, *inputs):
                with jax.named_scope("nns.model"):
                    state, out = fn(params, state, *inputs)
                return state, (tuple(out) if isinstance(out, (list, tuple))
                               else (out,))

            jitted = jax.jit(step, donate_argnums=(1,))
            try:
                with _profile.span(owner, "trace_lower", setup=True):
                    lowered = jitted.lower(p_avals, s_avals, *avals)
            except Exception as e:
                raise FilterError(
                    f"jax-xla: stateful model {model.name} entry {entry} "
                    f"rejects input {spec}: {e}") from e
            out_avals = jax.tree_util.tree_leaves(lowered.out_info[1])
            skey = COMPILE_STATS.record(
                self._compile_kind or "cold", time.perf_counter() - t0)
            self._capture_cost(model, lowered, 0, avals)
            prog = {"call": _timed_first_call(
                jitted, skey, owner,
                lambda: jitted.lower(p_avals, s_avals, *avals)),
                "out_spec": TensorsSpec.from_shapes(
                    [o.shape for o in out_avals],
                    [np.dtype(o.dtype) for o in out_avals])}
            with _stateful_lock:
                prog = _stateful_programs.setdefault(key, prog)
        call = prog["call"]
        if entry in model.setup_entries:
            # an entry that builds state ahead of the stream: its span
            # covers the device's work, so the call is fenced

            def run(*inputs):
                with _profile.span(owner, entry, setup=True):
                    out = cell.step(call, inputs)
                    jax.block_until_ready(out)
                return out
        else:
            def run(*inputs):
                return cell.step(call, inputs)

        run.lower = call.lower
        done = cell.compiled[entry] = _Compiled(run, spec, prog["out_spec"])
        return done

    def _refuse_swap_of_state(self) -> None:
        if self._cell is not None:
            raise FilterError(
                f"jax-xla: model {self._model.name} is stateful: a hot "
                "swap or RELOAD would have to say what becomes of the "
                "state its steps built (kept under new weights, or "
                "rebuilt), and nothing does yet; stop the pipeline and "
                "start it on the new model")

    def fetch_counters(self) -> None:
        """Add what the state's counters gained to ``STATE_STATS``.  A
        fence: the owning element calls it at its stats-sample cadence,
        never per step.  Nothing for a stateless model."""
        cell = self._cell
        if cell is not None:
            cell.fetch_counters()

    # -- model info ----------------------------------------------------------

    def get_model_info(self) -> Tuple[TensorsSpec, TensorsSpec]:
        c = self._compiled
        if c is None:
            raise FilterError("jax-xla: not configured")
        return c.in_spec, c.out_spec

    def set_input_info(self, in_spec: TensorsSpec
                       ) -> Tuple[TensorsSpec, TensorsSpec]:
        """Reshape by recompiling for the new schema (XLA retraces; static
        shapes per schema — SURVEY.md §7 'Dynamic shapes vs XLA').

        Shared instances (``open_shared``): re-negotiating a schema the
        executable already serves is idempotent (every sharer negotiates
        the same caps — only the first pays the compile), while an
        actual reshape is rejected when other sharers still depend on
        the current schema (one pipeline must not recompile the model
        under another's feet)."""
        if self._cell is not None:
            # a stateful model is not reshaped: the schema picks one of
            # its entry points
            c = self._compile_stateful(in_spec)
            with self._swap_lock:
                self._compiled = c
            return c.in_spec, c.out_spec
        if self._shared_refs > 0:
            with self._swap_lock:
                c = self._compiled
            if c is not None and not self._pre_chains and not self._post_fns \
                    and in_spec.is_compatible(c.in_spec):
                return c.in_spec, c.out_spec
            if self._shared_refs > 1:
                raise FilterError(
                    f"jax-xla: model {self._model.name if self._model else '?'} "
                    f"is shared by {self._shared_refs} filters; a sharer "
                    f"cannot reshape it to {in_spec} — sharers must "
                    f"negotiate identical input schemas")
        c = self._compile(self._model, in_spec, kind="reshape")
        with self._swap_lock:
            self._compiled = c
        with self._batch_lock:
            # bucket executables are keyed by in_spec, so entries for the
            # old schema are dead weight; drop them all
            self._batch_exec.clear()
        return c.in_spec, c.out_spec

    def input_layouts(self) -> Optional[tuple]:
        """The mesh executable's batch-sharded ``in_shardings``, which
        :meth:`invoke` passes through untouched when an input already
        has them.  None for an input it replicates (a producer staging
        THAT would hold its buffers once per chip) and for one this
        process cannot address alone; no wish at all without a mesh, or
        when the executable donates its inputs (today it consumes the
        placed copy, never the producer's own array)."""
        c = self._compiled
        if c is None or c.in_shardings is None or self._donate:
            return None
        return tuple(
            None if s.is_fully_replicated or not s.is_fully_addressable
            else s for s in c.in_shardings)

    # -- hot path ------------------------------------------------------------

    def invoke(self, inputs: Sequence[Any]) -> List[Any]:
        c = self._compiled
        if c is None:
            raise FilterError("jax-xla: not configured")
        if c.in_shardings is not None:
            # Mesh path: place each frame per the executable's sharding
            # (scatter over the data axis rides ICI; already-matching
            # device arrays pass through untouched).
            jax = _jax()
            inputs = [
                x if hasattr(x, "sharding")
                and s.is_equivalent_to(x.sharding, getattr(x, "ndim", 0))
                else self._put_input(jax, x, s)
                for x, s in zip(inputs, c.in_shardings)]
        else:
            dev = self._device
            if dev is not None:
                # Honor accelerator=: route inputs to the selected device
                # unless already resident there (committed params also pin
                # the compute, but fn-only models have no params to pin).
                inputs = [
                    x if hasattr(x, "devices") and dev in x.devices()
                    else self._put_input(_jax(), x, dev)
                    for x in inputs]
        with _profile.span(self.trace_owner, "dispatch"):
            out = c.jitted(*inputs)
        DISPATCH_STATS.count("filter")
        if self._placement is not None:
            # per-shard attribution (obs/meshstat.py): the leading dim
            # batch-shards over the data axes when divisible, else the
            # input was replicated onto every chip
            b = 1
            if c.in_spec.tensors and c.in_spec.tensors[0].shape:
                b = int(c.in_spec.tensors[0].shape[0] or 1)
            self._record_mesh(
                slots=b, frames=b,
                sharded=b % self._placement.data_axis_size == 0)
        return list(out)

    def _put_input(self, jax, x, where):
        """``device_put`` one input to a device/sharding (the
        ``<owner>/place`` span), counting the move into the transfer
        ledger byte-exact: ``h2d`` for a host array, ``d2d`` for an
        array that is already device-resident (a reshard onto this
        executable's layout travels chip to chip, never through the
        host)."""
        with _profile.span(self.trace_owner, "place"):
            if not _xfer.ACTIVE:
                return jax.device_put(x, where)
            t0 = time.perf_counter()
            y = jax.device_put(x, where)
            _xfer.record("d2d" if isinstance(x, jax.Array) else "h2d",
                         "input", int(getattr(x, "nbytes", 0)),
                         time.perf_counter() - t0)
            return y

    def _record_mesh(self, slots: int, frames: int,
                     sharded: bool, local: bool = False) -> None:
        """Feed one mesh dispatch into the per-shard attribution store
        (keyed by model name, like the executable cost rows).  The
        placement's full data-axes tuple goes along, so a multi-tier
        window (``dcn.data`` x ``data``) attributes over every shard
        it actually spread across.  ``local=True`` (the stacked-window
        path) restricts a MULTI-PROCESS placement to its local (ICI)
        data axes: this process only sees its own ``slots``/``frames``
        slice of the global window, so splitting them over the global
        shard product would zero every count — multi-process mesh
        attribution is per-process-local by design
        (Documentation/serving.md)."""
        rp = self._placement
        axes = rp.data_axes if rp is not None else self._data_axis
        if local and rp is not None and rp.num_processes > 1:
            from ..parallel.placement import DCN_PREFIX

            axes = tuple(a for a in rp.data_axes
                         if not a.startswith(DCN_PREFIX)) or axes
        _meshstat.record_dispatch(
            self._model.name if self._model is not None else "?",
            self._mesh, axes, slots, frames, sharded)

    # -- micro-batched hot path ----------------------------------------------

    def _compile_batched(self, model: ModelDef, c: _Compiled,
                         bucket: int):
        """One executable per (in_spec, bucket) of ``c``'s program:
        takes the weights, then ``bucket`` frames' tensors as flat args
        (frame-major), stacks each input along a new leading micro-batch
        axis INSIDE the program, vmaps the per-frame computation over
        the frames (the weights are every frame's), and returns
        per-frame output tensors — so a whole window is exactly one XLA
        dispatch, stack/unstack included.

        Multi-chip: the micro-batch axis is sharded over the mesh's data
        axis (the same ``_data_axis`` the single-frame path batch-shards
        over) via a sharding constraint on the stacked arrays, so a
        ``mesh="data:-1"`` filter spreads the window across chips instead
        of padding one frame onto all of them."""
        jax = _jax()
        import jax.numpy as jnp

        t_compile0 = time.perf_counter()
        in_spec, program = c.in_spec, c.program
        nt = in_spec.num_tensors
        over_frames = program.over_frames(nt)
        constraint = None
        if self._placement is not None:
            # the placement layer owns the divisibility rule: shard the
            # stacked micro-batch axis over the data axes when the
            # window splits evenly, else leave it replicated
            constraint = self._placement.window_sharding(bucket)

        def batched(weights, *flat):
            # nns.window: the stack and unstack around the per-frame
            # stages, so that every operation of a window program has a
            # stage in the device trace
            with jax.named_scope("nns.window"):
                stacked = [
                    jnp.stack([flat[i * nt + j] for i in range(bucket)])
                    for j in range(nt)]
                if constraint is not None:
                    stacked = [
                        jax.lax.with_sharding_constraint(s, constraint)
                        for s in stacked]
            outs = over_frames(weights, *stacked)
            per_frame = []
            with jax.named_scope("nns.window"):
                for i in range(bucket):
                    per_frame.extend(o[i] for o in outs)
            return tuple(per_frame)

        kw = {}
        if self._donate:
            kw["donate_argnums"] = tuple(range(1, 1 + bucket * nt))
        jitted = jax.jit(batched, **kw)
        # executable cost capture for this bucket's window program: ONE
        # trace — the capture's Lowered is also what serves dispatches
        # (AOT-compiled on the first call, so the XLA build stays lazy
        # and first-call-attributed; jit's own call path would re-trace
        # since lower() doesn't seed its cache)
        w_avals = program.avals()
        avals = [jax.ShapeDtypeStruct(t.shape, t.dtype.np_dtype)
                 for _ in range(bucket) for t in in_spec.tensors]
        owner = self.trace_owner
        with _profile.span(owner, "trace_lower", setup=True):
            lowered = jitted.lower(w_avals, *avals)
        self._capture_cost(model, lowered, bucket, avals)
        skey = COMPILE_STATS.record(
            "bucket", time.perf_counter() - t_compile0, bucket=bucket)
        fn = _aot_call(lowered, jitted,
                       pkey=self._persist_key(model, in_spec, bucket),
                       bucket=bucket, devices=self._exec_devices(),
                       owner=owner)
        return _timed_first_call(fn, skey, owner,
                                 lambda: jitted.lower(w_avals, *avals))

    def _compile_batched_stacked(self, model: ModelDef, c: _Compiled,
                                 bucket: int):
        """The mesh-placement window executable: takes the weights, then ONE
        ``(global_bucket, ...)`` stacked array per input tensor with
        the micro-batch axis sharded over the placement's data axes
        via ``in_shardings`` — each shard's bytes travel straight to
        its own device instead of landing replicated and resharding
        inside the program — vmaps the per-frame computation, and
        returns the stacked outputs under the same batch sharding (the
        caller demuxes per-frame results).  On a multi-process
        placement ``global_bucket = num_processes * bucket``: every
        process stacks its OWN window and the dispatch spans the fleet
        (per-process window formation, globally sharded dispatch)."""
        jax = _jax()
        rp = self._placement
        t_compile0 = time.perf_counter()
        in_spec, program = c.in_spec, c.program
        nt = in_spec.num_tensors
        gbucket = bucket * rp.num_processes
        sharding = rp.batch_sharding()
        over_frames = program.over_frames(nt)

        def batched(weights, *stacked):
            return tuple(over_frames(weights, *stacked))

        # out_shardings pinned to the batch sharding: the demux relies
        # on each process's rows being addressable locally
        kw = {"in_shardings": ([w.sharding for w in program.weights],
                               *(sharding,) * nt),
              "out_shardings": sharding}
        if self._donate:
            kw["donate_argnums"] = tuple(range(1, 1 + nt))
        jitted = jax.jit(batched, **kw)
        w_avals = program.avals()
        avals = [jax.ShapeDtypeStruct((gbucket,) + tuple(t.shape),
                                      t.dtype.np_dtype)
                 for t in in_spec.tensors]
        owner = self.trace_owner
        with _profile.span(owner, "trace_lower", setup=True):
            lowered = jitted.lower(w_avals, *avals)
        self._capture_cost(model, lowered, gbucket, avals)
        skey = COMPILE_STATS.record(
            "bucket", time.perf_counter() - t_compile0, bucket=gbucket)
        # the stacked window program takes ONE (gbucket, ...) array per
        # tensor where the flat program takes bucket*nt flat args — the
        # "stacked" tag keys them apart in the persistent cache
        fn = _aot_call(lowered, jitted,
                       pkey=self._persist_key(
                           model, ("stacked", in_spec), gbucket),
                       bucket=gbucket, devices=self._exec_devices(),
                       owner=owner)
        return _timed_first_call(fn, skey, owner,
                                 lambda: jitted.lower(w_avals, *avals))

    def _invoke_batched_stacked(self, frames: Sequence[Sequence[Any]],
                                bucket: int, c: _Compiled,
                                model: ModelDef, execs: Dict
                                ) -> List[List[Any]]:
        """Mesh-placement window dispatch: stack the window ONCE on the
        host (pad slots replay the last frame; ``np.stack`` copies, so
        donation can never consume a caller's buffer twice), place each
        stacked tensor with the batch axis sharded over the data axes,
        and run one XLA dispatch.  Replaces the flat per-frame feed —
        which landed every frame replicated on the mesh and resharded
        inside the program — with bytes that go straight to their own
        shard's device."""
        rp = self._placement
        n = len(frames)
        key = (c.in_spec, bucket, "stacked")
        with self._batch_lock:
            jitted = execs.get(key)
            if jitted is not None:
                self.batch_cache_hits += 1
                self._cache_by_bucket.setdefault(bucket, [0, 0])[0] += 1
        if jitted is None:
            jitted = self._compile_batched_stacked(model, c, bucket)
            with self._batch_lock:
                self.batch_cache_misses += 1
                self._cache_by_bucket.setdefault(bucket, [0, 0])[1] += 1
                if self._compiled is c:
                    self._batch_exec[key] = jitted
        pad_rows = bucket - n
        stacked: List[np.ndarray] = []
        for j in range(c.in_spec.num_tensors):
            rows = [np.asarray(f[j]) for f in frames]
            if pad_rows:
                # pad slots replay the last frame (discarded on demux);
                # they still burn device time — counted below and by
                # the mesh attribution store
                rows.extend(rows[-1:] * pad_rows)
            stacked.append(np.stack(rows))
        with _profile.span(self.trace_owner, "place"):
            t0 = time.perf_counter()
            arrs = rp.feed_window(stacked)
            if _xfer.ACTIVE:
                per_frame = sum(int(a.nbytes) // bucket for a in stacked)
                _xfer.record("h2d", "input", per_frame * n,
                             time.perf_counter() - t0)
                if pad_rows:
                    _xfer.record("h2d", "pad", per_frame * pad_rows)
        with _profile.span(self.trace_owner, "dispatch"):
            out = jitted(c.program.weights, *arrs)
        DISPATCH_STATS.count("filter")
        self._record_mesh(slots=bucket, frames=n, sharded=True,
                          local=True)
        if rp.num_processes > 1:
            # globally sharded output: this process demuxes only ITS
            # rows (the window it formed), via the addressable shards
            out = [rp.local_rows(o) for o in out]
        return [[o[i] for o in out] for i in range(n)]

    def invoke_batched(self, frames: Sequence[Sequence[Any]],
                       bucket: int) -> List[List[Any]]:
        """Run ``frames`` (n per-frame input lists, n <= bucket) as ONE
        XLA dispatch padded up to ``bucket``; returns n per-frame output
        lists.  Pad slots replay the last frame (copies when donation is
        on — a buffer must not be donated twice) and their outputs are
        discarded."""
        with self._swap_lock:
            # consistent (model, compiled, bucket executables) snapshot:
            # a concurrent reload swaps all three together under this
            # lock, and an executable takes the weights of its own
            # program
            c = self._compiled
            model = self._model
            execs = self._batch_exec
        if c is None:
            raise FilterError("jax-xla: not configured")
        if self._cell is not None:
            raise FilterError(
                f"jax-xla: stateful model {model.name} cannot be "
                "micro-batched (batch>1 stacks frames of a stateless "
                "program; its entry points take whole windows)")
        n = len(frames)
        if n == 0:
            return []
        if n > bucket:
            raise FilterError(
                f"jax-xla: {n} frames exceed bucket {bucket}")
        rp = self._placement
        if rp is not None and rp.window_sharding(bucket) is not None \
                and (rp.num_processes > 1
                     or all(isinstance(x, np.ndarray)
                            for f in frames for x in f)):
            # mesh placement + host frames (or a multi-process
            # placement, where the global dispatch REQUIRES explicit
            # global-array formation): the stack-once sharded window.
            # Device-resident single-process frames keep the flat path
            # below — stacking them on the host would force a d2h
            # round-trip the program-side stack avoids.
            return self._invoke_batched_stacked(frames, bucket, c, model,
                                                execs)
        key = (c.in_spec, bucket)
        with self._batch_lock:
            jitted = execs.get(key)
            if jitted is not None:
                self.batch_cache_hits += 1
                self._cache_by_bucket.setdefault(bucket, [0, 0])[0] += 1
        if jitted is None:
            jitted = self._compile_batched(model, c, bucket)
            with self._batch_lock:
                self.batch_cache_misses += 1
                self._cache_by_bucket.setdefault(bucket, [0, 0])[1] += 1
                if self._compiled is c:
                    self._batch_exec[key] = jitted
                # else: a reload/reshape swapped the model mid-compile
                # and cleared the cache — this window still runs the
                # executable it started with, but caching it would pin
                # the OLD model for every future window of this bucket
        jax = _jax()
        # Explicit placement only when accelerator= picked a NON-default
        # device: for the default device the executable's own arg
        # handling places host arrays on a faster path than a per-frame
        # device_put, and device arrays are already where they belong.
        dev = self._device if self._mesh is None \
            and self._dev_kind is not None else None
        flat: List[Any] = []
        for f in frames:
            for x in f:
                if dev is not None and not (
                        hasattr(x, "devices") and dev in x.devices()):
                    x = self._put_input(jax, x, dev)
                elif _xfer.ACTIVE and isinstance(x, np.ndarray):
                    # batched-window feed: the executable's own arg
                    # handling transfers host arrays — counted at the
                    # feed site (byte-exact; the transfer itself is
                    # not separately timeable, hence duration 0)
                    _xfer.record("h2d", "input", int(x.nbytes))
                flat.append(x)
        if n < bucket:
            last = flat[-len(frames[-1]):]
            for _ in range(bucket - n):
                if self._donate:
                    # a buffer must not be donated twice: each pad slot
                    # gets its own copy of the replayed frame
                    import jax.numpy as jnp

                    for x in last:
                        if _xfer.ACTIVE and isinstance(x, np.ndarray):
                            # copying a HOST replay uploads it: a pad
                            # crossing (device-resident replays copy
                            # on-device and never cross)
                            t0 = time.perf_counter()
                            y = jnp.copy(x)
                            _xfer.record("h2d", "pad", int(x.nbytes),
                                         time.perf_counter() - t0)
                        else:
                            y = jnp.copy(x)
                        flat.append(y)
                else:
                    if _xfer.ACTIVE:
                        for x in last:
                            if isinstance(x, np.ndarray):
                                # host replays re-fed to the executable
                                # transfer again, once per pad slot
                                _xfer.record("h2d", "pad",
                                             int(x.nbytes))
                    flat.extend(last)
        with _profile.span(self.trace_owner, "dispatch"):
            out = jitted(c.program.weights, *flat)
        DISPATCH_STATS.count("filter")
        if self._mesh is not None:
            # window attribution: bucket slots over the data axis (pads
            # included — they burn device time, which is the point of
            # the nns_mesh_pad_slots counter and nns-lint NNS509)
            axis = int(self._mesh.shape[self._data_axis])
            self._record_mesh(slots=bucket, frames=n,
                              sharded=bucket % axis == 0)
        nt_out = len(out) // bucket
        return [list(out[i * nt_out:(i + 1) * nt_out]) for i in range(n)]

    # -- double-buffered hot swap (runtime/lifecycle.py drives this) ---------

    def hot_buckets(self) -> Tuple[int, ...]:
        """Bucket sizes with a live window executable right now — the
        set a replacement model must have warm BEFORE the flip, so the
        first post-swap window dispatches instead of compiling."""
        with self._batch_lock:
            return tuple(sorted({int(k[1]) for k in self._batch_exec}))

    def prepare_swap(self, model: Any, buckets: Sequence[int] = (),
                     warm: bool = True) -> "JaxXlaFilter":
        """Load + compile a replacement model OFF the dispatch path:
        returns a fully-configured SHADOW instance (same placement /
        accelerator / custom / fused-chain config as this one, new
        model) whose executables are built — and, with ``warm=True``,
        have paid their lazy first-call XLA build on zero inputs — while
        this instance keeps serving untouched.  :meth:`commit_swap`
        flips the shadow's state in atomically; the lifecycle layer
        (``runtime/lifecycle.py``) also dispatches canary windows
        through the shadow directly.

        ``model`` may be anything ``model=`` accepts, or a bare params
        pytree (dict) — the weights-only swap: the architecture (this
        instance's ``fn``) is kept and only the weights change, which is
        how ``trainers/checkpoint.py`` orbax checkpoints hot-load into
        a serving pool.  Weights of the serving ones' shapes, dtypes and
        shardings take the executables that serve (:meth:`_reuse`): the
        shadow places them and runs the weights prologue, and nothing
        is traced, lowered or built (a ``reuse`` in ``COMPILE_STATS``);
        any other replacement builds its own programs (a ``reload``)."""
        if self.props is None:
            raise FilterError("jax-xla: not configured (nothing to swap)")
        self._refuse_swap_of_state()
        import dataclasses as _dc

        cur = self._compiled
        if isinstance(model, dict) and "apply" not in model:
            # weights-only swap: same architecture, new params
            if self._model is None or self._model.params is None:
                raise FilterError(
                    "jax-xla: weights-only swap needs a params-carrying "
                    "model to swap into")
            model = ModelDef(self._model.fn, model,
                             self._model.in_spec,
                             name=f"{self._model.name}@weights")
        shadow = type(self)()
        # the shadow compiles the SAME program shape: fused chains ride
        # along (by reference, like set_fused_pre documents), and the
        # negotiated input schema is forced so the executables the flip
        # installs serve the caps already flowing
        shadow._pre_chains = self._pre_chains
        shadow._post_fns = self._post_fns
        shadow._compile_kind = "reload"
        shadow._donor = cur
        props = _dc.replace(
            self.props, model=model,
            input_spec=cur.in_spec if cur is not None
            else self.props.input_spec,
            # the shadow must not collide with SHARED_MODELS: it is a
            # private staging instance until commit
            shared_key=None)
        shadow.configure(props)
        if cur is not None \
                and shadow._compiled.out_spec != cur.out_spec:
            raise FilterError(
                f"jax-xla: replacement model {shadow.model_name()!r} "
                f"changes the output schema "
                f"({cur.out_spec} -> {shadow._compiled.out_spec}) — a "
                f"hot swap must preserve negotiated caps; restart the "
                f"pipeline to change schemas")
        if cur is not None and shadow._compiled.exe is cur.exe:
            # the same programs over new weights: the bucket
            # executables that serve take them too
            with self._batch_lock:
                shadow._batch_exec = dict(self._batch_exec)
        want = tuple(sorted(set(int(b) for b in buckets)
                            or self.hot_buckets()))
        if warm:
            self._warm_shadow(shadow, want)
        return shadow

    def _warm_shadow(self, shadow: "JaxXlaFilter",
                     buckets: Tuple[int, ...]) -> None:
        """Run the shadow's executables once on zeros: jit builds
        lazily, so without this the first post-flip dispatch would pay
        the XLA build ON the dispatch path — the exact stall
        double-buffering exists to remove.  With the persistent cache
        armed the build is usually a deserialize anyway; warming also
        covers the backends where it is not."""
        from ..runtime.serving import block_all

        c = shadow._compiled
        zeros = [np.zeros(t.shape, t.dtype.np_dtype)
                 for t in c.in_spec.tensors]
        block_all(shadow.invoke(list(zeros)))
        for b in buckets:
            frames = [list(zeros) for _ in range(int(b))]
            outs = shadow.invoke_batched(frames, int(b))
            block_all([o for out in outs for o in out])

    def commit_swap(self, shadow: "JaxXlaFilter") -> None:
        """Atomically adopt a prepared shadow's (model, executable,
        bucket cache): the double-buffer flip.  Serving threads snapshot
        (model, compiled) under ``_swap_lock``, so no dispatch ever
        sees a torn pair; the lifecycle layer additionally flips at a
        window boundary so not even a window straddles the swap."""
        with self._swap_lock, self._batch_lock:
            self._model = shadow._model
            self._compiled = shadow._compiled
            self._batch_exec = dict(shadow._batch_exec)

    # -- events --------------------------------------------------------------

    def handle_event(self, event: Event) -> None:
        if event.kind != EventKind.RELOAD_MODEL:
            return
        self._refuse_swap_of_state()
        if self.props is None or not self.props.is_updatable:
            raise FilterError("jax-xla: model is not updatable")
        # double-buffered reload: the replacement (single-frame AND the
        # currently-hot bucket/window executables — meshed filters
        # included) loads, compiles and warms OFF the dispatch path;
        # the old executables serve until the atomic flip.  The old
        # path cleared _batch_exec instead, which made the first
        # post-reload window recompile INLINE on the dispatch path —
        # on a meshed filter that stall was the whole stacked build.
        shadow = self.prepare_swap(event.data["model"])
        self.commit_swap(shadow)


def export_model(fn: Callable, example_inputs: Sequence[Any], path: str,
                 params: Any = None) -> str:
    """Serialize a jitted computation to a ``.jaxexp`` file loadable via
    ``model=path`` (the framework's on-disk model format)."""
    jax = _jax()
    if params is not None:
        inner = fn

        def fn(*xs):
            return inner(params, *xs)

    exported = jax.export.export(jax.jit(fn))(
        *[jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
          if not hasattr(x, "shape") else
          jax.ShapeDtypeStruct(x.shape, x.dtype) for x in example_inputs])
    data = exported.serialize()
    with open(path, "wb") as f:
        f.write(bytes(data))
    return path


def save_params_model(path: str, apply: str, params: Any,
                      in_shapes: Optional[Sequence] = None,
                      in_dtypes: Any = None) -> str:
    """Write a ``.pkl`` params-file loadable via ``model=path``:
    ``apply`` is a "module:callable" import path, params the weight pytree
    (host copies are stored)."""
    import pickle

    jax = _jax()
    host = jax.tree_util.tree_map(np.asarray, params)
    with open(path, "wb") as f:
        pickle.dump({"apply": apply, "params": host,
                     "in_shapes": in_shapes, "in_dtypes": in_dtypes}, f)
    return path
