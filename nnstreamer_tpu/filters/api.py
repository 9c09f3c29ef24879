"""Filter sub-plugin ABI.

Parity target: the v1 filter framework ABI
(/root/reference/gst/nnstreamer/include/nnstreamer_plugin_api_filter.h:247-469)
and the C++ base class
(include/nnstreamer_cppplugin_api_filter.hh:165-193): open/close lifecycle,
``invoke``, model-info queries incl. SET_INPUT_INFO reshape, event handling
(model RELOAD), allocate-in-invoke, and the shared-model table
(nnstreamer_plugin_api_filter.h:551-590).

TPU-native redesign: ``invoke`` consumes and produces *device-resident*
``jax.Array``s — the "allocate_in_invoke" pattern of the TensorRT sub-plugin
(tensor_filter_tensorrt.cc:253,396) is the default here, because XLA owns
output allocation and buffers stay in HBM end-to-end.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..core import TensorsSpec
from ..runtime.events import Event


@dataclasses.dataclass
class FilterProps:
    """Parsed ``tensor_filter`` properties handed to sub-plugin ``open``
    (parity: GstTensorFilterProperties, tensor_filter_common.h:84-109)."""

    framework: str = ""
    model: Any = None          # path(s) or in-process object
    accelerator: str = ""      # e.g. "true:tpu", "cpu"
    custom: str = ""           # free-form custom_properties
    input_spec: Optional[TensorsSpec] = None   # user-forced input info
    output_spec: Optional[TensorsSpec] = None
    shared_key: Optional[str] = None  # shared compiled-model table key
    is_updatable: bool = False        # hot reload allowed
    latency_report: bool = False
    #: mesh spec string ("data:-1", "data:4,model:2"): compile the model
    #: SPMD over a device mesh instead of one chip.  The TPU-native form of
    #: the reference's *remote* tensor_filter (offload to query servers,
    #: tensor_query_client.c:673-741): one invoke spans every chip, XLA
    #: inserts the ICI collectives.
    mesh: str = ""
    #: named param-layout rules (parallel.PARAM_RULES) for the mesh path
    sharding: str = ""
    #: device-index subset for the mesh ("0-3", "4,5,6,7", "0-1,6"):
    #: lays the mesh over a SUBMESH of the platform's devices, so two
    #: filter stages in one pipeline can occupy disjoint device subsets
    #: (stage A on chips 0-3, stage B on 4-7) with device-to-device
    #: handoff — the distributed-pipeline form of SURVEY §7.6.
    devices: str = ""


class FilterError(Exception):
    pass


class FilterSubplugin:
    """Abstract base for filter frameworks (jax-xla, custom-easy, python3…).

    Lifecycle: ``configure(props)`` → ``get_model_info()`` (and optionally
    ``set_input_info``) during negotiation → ``invoke`` per frame → ``close``.
    """

    #: registry name, e.g. "jax-xla"
    NAME: str = ""
    #: hardware the framework can run on (parity: getFrameworkInfo hw list)
    ACCELERATORS: Tuple[str, ...] = ("cpu",)
    #: outputs are freshly allocated by invoke (always true for XLA)
    ALLOCATE_IN_INVOKE: bool = True
    #: sub-plugin implements ``invoke_batched(frames, bucket)`` — run a
    #: micro-batched window of frames as ONE dispatch (see
    #: runtime/batching.py).  Frameworks without it still work under
    #: ``tensor_filter batch>1``: the element falls back to per-frame
    #: ``invoke`` inside the coalesced window (ordering/flush semantics
    #: preserved, no dispatch reduction).
    SUPPORTS_BATCH: bool = False

    def __init__(self):
        self.props: Optional[FilterProps] = None

    # -- lifecycle -----------------------------------------------------------

    def configure(self, props: FilterProps) -> None:
        """Parity: open() / configure_instance()."""
        self.props = props

    def close(self) -> None:
        pass

    # -- shared open (serving pool, runtime/serving.py) ----------------------

    @classmethod
    def open_shared(cls, props: FilterProps) -> "FilterSubplugin":
        """Open an instance for shared use across filter elements (the
        ModelPool path).  Default: a fresh configured instance — the
        pool itself deduplicates per key, so this is enough for
        lightweight frameworks.  Frameworks with heavyweight device
        state (jax-xla: params in HBM, executable caches) override this
        with their own ref-counted table so even pool-external callers
        share ONE instance per model config."""
        sp = cls()
        sp.configure(props)
        return sp

    @classmethod
    def close_shared(cls, sp: "FilterSubplugin") -> None:
        """Release an instance obtained from :meth:`open_shared`
        (default: close it — pairs with the default open)."""
        sp.close()

    # -- model info ----------------------------------------------------------

    def get_model_info(self) -> Tuple[TensorsSpec, TensorsSpec]:
        """Return (input_spec, output_spec)."""
        raise NotImplementedError

    def set_input_info(self, in_spec: TensorsSpec
                       ) -> Tuple[TensorsSpec, TensorsSpec]:
        """Reshape the model for a new input schema; return updated
        (in, out).  Parity: GET/SET_INPUT_INFO
        (nnstreamer_plugin_api_filter.h:418-441).  Default: reject."""
        raise FilterError(
            f"{self.NAME}: model cannot be reshaped to {in_spec}")

    def input_layouts(self) -> Optional[tuple]:
        """Where :meth:`invoke` reads its inputs, for a producer that
        can stage them there (``Event.placement``): one
        ``jax.sharding.Sharding`` or None ("no wish") per input tensor.
        Default: no wish at all."""
        return None

    # -- hot path ------------------------------------------------------------

    def invoke(self, inputs: Sequence[Any]) -> List[Any]:
        """Run the model on one frame's tensors (device arrays in, device
        arrays out).  Must be thread-safe w.r.t. ``handle_event``."""
        raise NotImplementedError

    def fetch_counters(self) -> None:
        """Called by the owning element where its blocking stats sample
        has fenced the stream anyway: a framework whose model keeps
        counters on the device reads them here (jax-xla's stateful
        models), never per invoke.  Default: nothing to read."""

    # -- events --------------------------------------------------------------

    def handle_event(self, event: Event) -> None:
        """RELOAD_MODEL etc. (parity: eventHandler,
        nnstreamer_plugin_api_filter.h:351-357)."""


class SharedModelTable:
    """key → opened representation shared across filter instances
    (parity: nnstreamer_filter_shared_model_get/insert/remove/replace,
    nnstreamer_plugin_api_filter.h:551-590)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._table: Dict[str, Any] = {}

    def get(self, key: str) -> Optional[Any]:
        with self._lock:
            return self._table.get(key)

    def insert(self, key: str, value: Any) -> Any:
        with self._lock:
            return self._table.setdefault(key, value)

    def replace(self, key: str, value: Any) -> None:
        with self._lock:
            self._table[key] = value

    def remove(self, key: str) -> None:
        with self._lock:
            self._table.pop(key, None)

    def clear(self) -> None:
        with self._lock:
            self._table.clear()


SHARED_MODELS = SharedModelTable()
