"""``device_src`` — a source whose frames are staged in device HBM.

The reference's converter guarantees zero-copy media ingestion on host
(video/x-raw → tensor without memcpy unless width%4≠0 —
/root/reference/gst/nnstreamer/elements/gsttensor_converter.md
"Performance Characteristics").  The TPU-native equivalent of "zero-copy"
is *device residence*: frames are staged into HBM once (a bounded pool,
double-buffer style) and the streaming loop never touches the host again —
each created Buffer references a pool slot.  This is the right source for
benchmarks and for any pipeline whose ingest can be prefetched (datarepo
replay, synthetic load, camera DMA staging).

Where the pool is staged is the consumer's to say (``Event.placement``,
sent upstream at start; the class docstring says by whom and when not).

Patterns (parity: videotestsrc patterns feeding tensor_converter in the
reference's SSAT pipelines): ``noise`` (PRNG uint8), ``gradient``,
``frames`` (a user-supplied ndarray pool, uploaded at start).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import itertools

import numpy as np

from ..core import Buffer, Tensor, TensorsSpec
from ..runtime.element import NegotiationError, Pad, SourceElement
from ..runtime.events import Event, EventKind
from ..runtime.registry import register_element
from ..utils import profile as _profile


_stage_seed = itertools.count(1)


@register_element("device_src")
class DeviceSrc(SourceElement):
    """``device_src`` — a source whose frames are staged in device HBM
    once, at start, in the layout its consumer asks for: a mesh
    ``tensor_filter`` (``mesh=``, plain per-buffer invoke) reached
    through elements that hand buffers on untouched (a fused
    ``tensor_transform``, ``queue``, ``capsfilter``, ``identity``, a
    ``tee`` whose branches all ask for the same layout) gets the pool
    sharded over its chips, so no window is placed again.  With no such
    request (no mesh, a micro-batch or ``share-model`` filter, an input
    the executable replicates, a computing element in between) every
    slot goes to jax's default device, and a filter elsewhere places
    each window itself."""

    FACTORY = "device_src"

    def __init__(self, name=None, spec: Optional[TensorsSpec] = None,
                 pattern: str = "noise", frames: Optional[Sequence] = None,
                 pool_size: int = 4, num_buffers: int = -1,
                 fps: Optional[float] = None, **props):
        self.spec = spec
        self.pattern = pattern
        self.frames = frames
        self.pool_size = pool_size
        self.num_buffers = num_buffers
        self.fps = fps
        super().__init__(name, **props)
        self._pool: List[List[object]] = []  # pool[i] = per-tensor jax arrays
        self._i = 0
        # per tensor, the layout the consumer asked for since the last
        # stop (None / missing = jax's default device)
        self._layouts: tuple = ()

    def output_spec(self):
        if isinstance(self.spec, str):
            # pipeline-string form: `spec=3:224:224:64` or
            # `spec=3:224:224:1/float32,1000:1/float32` — dims[/type] per
            # tensor, type defaulting to the pattern dtype (uint8)
            dims, types = [], []
            for part in self.spec.split(","):
                d, _, t = part.partition("/")
                dims.append(d.strip())
                types.append(t.strip() or "uint8")
            self.spec = TensorsSpec.parse(",".join(dims), ",".join(types))
        if self.spec is None and self.frames is not None:
            first = self.frames[0]
            arrays = first if isinstance(first, (list, tuple)) else [first]
            self.spec = TensorsSpec.from_shapes(
                [a.shape for a in arrays], [np.dtype(a.dtype) for a in arrays])
        return self.spec

    def handle_upstream_event(self, pad: Pad, event: Event) -> None:
        if event.kind == EventKind.PLACEMENT:
            # the newest request stands: a tee sends what its branches
            # agree on again after each of them has asked
            self._layouts = event.data["layouts"]
        super().handle_upstream_event(pad, event)

    def start(self) -> None:
        with _profile.span(self.name, "stage", setup=True) as staging:
            self._stage_pool()
            asked = [s for s in self._layouts if s is not None]
            staging.note = "default device" if not asked \
                else "as asked: " + ", ".join(map(str, asked))
        super().start()

    def stop(self) -> None:
        super().stop()
        self._layouts = ()  # the next start's consumers ask anew

    def _stage(self, host: np.ndarray, i: int):
        """Tensor ``i`` of one slot onto the device(s), resident before
        streaming starts: in the layout asked for, else on jax's default
        device."""
        import jax

        want = self._layouts[i] if i < len(self._layouts) else None
        d = jax.device_put(host, want)  # None: the bare device_put
        d.block_until_ready()
        return d

    def _stage_pool(self) -> None:
        spec = self.output_spec()
        if spec is None:
            raise NegotiationError(f"{self.name}: no spec/frames given")
        self._pool = []
        if self.frames is not None:
            for f in self.frames[:min(self.pool_size, len(self.frames))]:
                arrays = f if isinstance(f, (list, tuple)) else [f]
                self._pool.append([self._stage(np.asarray(a), i)
                                   for i, a in enumerate(arrays)])
            return
        # a fresh seed per staging: no two pipeline instantiations stage
        # byte-identical pools
        rng = np.random.default_rng(next(_stage_seed))
        for k in range(self.pool_size):
            staged = []
            for i, t in enumerate(spec.tensors):
                if self.pattern == "gradient":
                    flat = np.arange(t.num_elements, dtype=np.int64)
                    host = ((flat + k) % 256).astype(
                        t.dtype.np_dtype).reshape(t.shape)
                else:  # noise
                    if t.dtype.np_dtype == np.uint8:
                        host = rng.integers(
                            0, 256, t.shape, dtype=np.uint8)
                    else:
                        host = rng.standard_normal(t.shape).astype(
                            t.dtype.np_dtype)
                staged.append(self._stage(host, i))
            self._pool.append(staged)

    def create(self) -> Optional[Buffer]:
        if 0 <= self.num_buffers <= self._i:
            return None
        slot = self._pool[self._i % len(self._pool)]
        pts = None
        if self.fps:
            pts = int(self._i * 1_000_000_000 / self.fps)
        buf = Buffer(tensors=[Tensor(a) for a in slot], pts=pts,
                     offset=self._i)
        self._i += 1
        return buf
