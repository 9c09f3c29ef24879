"""Basic plumbing elements: appsrc, appsink, tensor_sink, queue, tee,
identity, fakesink.

Parity targets: GStreamer appsrc/appsink semantics as used throughout the
reference tests (programmatic pipelines,
/root/reference/tests/common/unittest_common.cc) and the tensor_sink
``new-data`` callback element
(/root/reference/gst/nnstreamer/elements/gsttensor_sink.c).
The ``queue`` element is the runtime's thread boundary, standing in for
GStreamer queue threads (SURVEY.md §1 "Key structural fact").
"""

from __future__ import annotations

import collections
import queue as _q
import threading
from typing import Callable, List, Optional

from ..core import Buffer, Caps, TensorsSpec
from ..obs import hooks as _hooks
from ..runtime.element import (
    Element,
    Pad,
    SinkElement,
    SourceElement,
)
from ..runtime.events import Event, EventKind, Message, MessageKind
from ..runtime.registry import register_element
from ..utils import profile as _profile


@register_element("appsrc")
class AppSrc(SourceElement):
    """Application-driven source: the app pushes Buffers via :meth:`push_buffer`
    and ends the stream with :meth:`end_of_stream`.  ``spec`` (a TensorsSpec or
    a caps-string pair) must be set before the pipeline starts."""

    FACTORY = "appsrc"

    def __init__(self, name=None, spec: Optional[TensorsSpec] = None,
                 caps=None, max_buffers: int = 64, **props):
        self.spec = spec
        self.caps = caps
        self.max_buffers = max_buffers
        super().__init__(name, **props)
        if isinstance(self.caps, str):
            from ..runtime.parser import parse_caps_string

            self.caps = parse_caps_string(self.caps)
        self._q: "_q.Queue" = _q.Queue(maxsize=int(self.max_buffers))

    def output_caps(self) -> Caps:
        if self.caps is not None:
            return self.caps
        # super() raises the structured "source has no output spec"
        # NegotiationError when neither caps nor spec is set yet
        return super().output_caps()

    def output_spec(self):
        return self.spec

    def push_buffer(self, buf: Buffer, timeout: Optional[float] = None) -> None:
        self._q.put(buf, timeout=timeout)

    def end_of_stream(self) -> None:
        self._q.put(None)

    def create(self) -> Optional[Buffer]:
        while self._running.is_set():
            try:
                return self._q.get(timeout=0.05)
            except _q.Empty:
                continue
        return None


@register_element("appsink")
class AppSink(SinkElement):
    """Pull-style sink: the app calls :meth:`pull` to take buffers out."""

    FACTORY = "appsink"

    def __init__(self, name=None, max_buffers: int = 64, drop: bool = False,
                 **props):
        self.max_buffers = max_buffers
        self.drop = drop
        super().__init__(name, **props)
        self._q: "_q.Queue" = _q.Queue(maxsize=int(self.max_buffers))

    def render(self, buf: Buffer) -> None:
        with _profile.span(self.name, "render"):
            try:
                self._q.put_nowait(buf)
                return
            except _q.Full:
                pass
            if self.drop:
                try:
                    self._q.get_nowait()
                except _q.Empty:
                    pass
                self._q.put_nowait(buf)
            else:
                # back-pressure: the consumer has not pulled yet
                with _profile.span(self.name, "render_wait"):
                    self._q.put(buf)

    def pull(self, timeout: Optional[float] = None) -> Optional[Buffer]:
        try:
            return self._q.get(timeout=timeout)
        except _q.Empty:
            return None


@register_element("tensor_sink")
class TensorSink(SinkElement):
    """Callback sink (parity: gsttensor_sink.c ``new-data`` signal +
    emit-signal/signal-rate properties)."""

    FACTORY = "tensor_sink"

    def __init__(self, name=None, callback: Optional[Callable] = None,
                 emit_signal: bool = True, sync: bool = False, **props):
        self.callback = callback
        self.emit_signal = emit_signal
        self.sync = sync
        super().__init__(name, **props)
        self.buffers_rendered = 0
        self.last_buffer: Optional[Buffer] = None
        self._cbs: List[Callable] = []

    def connect(self, cb: Callable) -> None:
        """connect('new-data'-style) a callback(buffer)."""
        self._cbs.append(cb)

    def render(self, buf: Buffer) -> None:
        self.buffers_rendered += 1
        self.last_buffer = buf
        if self.emit_signal:
            if self.callback is not None:
                self.callback(buf)
            for cb in self._cbs:
                cb(buf)


@register_element("fakesink")
class FakeSink(SinkElement):
    FACTORY = "fakesink"

    def render(self, buf: Buffer) -> None:
        pass


@register_element("queue")
class Queue(Element):
    """Thread boundary with a bounded buffer (parity: GStreamer queue).
    ``leaky``: '' (block), 'upstream' (drop new), 'downstream' (drop old).

    ``prefetch_host=True`` starts an async device→host copy for every
    device-resident tensor as it ENTERS the queue (i.e. at XLA dispatch
    time, while the computation may still be running).  A host-side
    consumer on the other side of the thread boundary then finds the
    payload already on host instead of paying a blocking device
    round-trip per buffer — the TPU-native output-drain pattern for
    decoder/sink stages."""

    FACTORY = "queue"
    PASSES_BUFFERS = True

    def __init__(self, name=None, max_size_buffers: int = 16,
                 leaky: str = "", prefetch_host: bool = False, **props):
        self.max_size_buffers = max_size_buffers
        self.leaky = leaky
        self.prefetch_host = prefetch_host
        super().__init__(name, **props)
        self.add_sink_pad()
        self.add_src_pad()
        self._dq: collections.deque = collections.deque()
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._eos = False

    def chain(self, pad: Pad, buf: Buffer) -> None:
        cap = int(self.max_size_buffers)
        with self._cv:
            if self.leaky == "upstream" and len(self._dq) >= cap:
                return  # drop the incoming buffer (before any prefetch)
            if self.leaky == "downstream":
                while len(self._dq) >= cap:
                    self._dq.popleft()
            else:
                while self._running and len(self._dq) >= cap:
                    self._cv.wait(0.05)
                if not self._running:
                    return
            if self.prefetch_host:  # only for buffers actually enqueued
                for t in buf.tensors:
                    t.prefetch_host()
            tracer = _hooks.tracer
            if tracer is not None:
                tracer.queue_enqueued(self, buf)
            self._dq.append(buf)
            self._cv.notify_all()

    def handle_event(self, pad: Pad, event: Event) -> None:
        if event.kind == EventKind.EOS:
            with self._cv:
                self._eos = True
                self._cv.notify_all()
        else:
            self.forward_event(event)

    def start(self) -> None:
        self._running = True
        self._eos = False
        # deterministic name (nns:<pipeline>:<element>) + thread-
        # registry coverage for profiler attribution (obs/prof.py)
        from ..obs import prof as _prof

        self._thread = _prof.element_thread(self, self._loop, "queue")
        self._thread.start()

    def stop(self) -> None:
        with self._cv:
            self._running = False
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _loop(self) -> None:
        import time

        from ..obs import prof as _prof

        # exact run/wait accounting (obs/prof.py): the cv-wait/pop is
        # the wait side, push() — the whole downstream chain runs in
        # this thread — is the run side.  None under NNS_TPU_OBS_DISABLE
        # → the loop skips every clock read.
        pipe = getattr(self, "pipeline", None)
        acct = _prof.element_account(
            getattr(pipe, "name", "") or "-", self.name)
        t0 = c0 = 0.0
        while True:
            if acct is not None:
                t0 = time.monotonic()
                c0 = time.thread_time()
            with self._cv:
                while self._running and not self._dq and not self._eos:
                    self._cv.wait(0.05)
                if not self._running:
                    return
                if self._dq:
                    buf = self._dq.popleft()
                    self._cv.notify_all()
                elif self._eos:
                    break
                else:
                    continue
            tracer = _hooks.tracer
            if tracer is not None:
                tracer.queue_dequeued(self, buf)
            if acct is None:
                self.push(buf)
            else:
                t1 = time.monotonic()
                self.push(buf)
                acct.add(t1 - t0, time.monotonic() - t1,
                         time.thread_time() - c0)
        self.forward_event(Event.eos())

    @property
    def current_level_buffers(self) -> int:
        with self._cv:
            return len(self._dq)


@register_element("tee")
class Tee(Element):
    """1→N fan-out; each downstream branch receives every buffer."""

    FACTORY = "tee"
    PASSES_BUFFERS = True

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.add_sink_pad()
        self._next = 0
        # src pad name -> the layouts that branch asked for at start
        self._wishes: dict = {}

    def request_pad(self, name: str) -> Optional[Pad]:
        if name in ("src_%u", "src"):
            name = f"src_{self._next}"
        if not name.startswith("src_"):
            return None
        self._next += 1
        return self.add_src_pad(name)

    def propose_src_caps(self, pad: Pad) -> Caps:
        if self.sinkpad.caps is not None:
            return self.sinkpad.caps
        return Caps.any_tensors()

    def chain(self, pad: Pad, buf: Buffer) -> None:
        for sp in self.srcpads:
            self.push(buf, sp)

    def handle_upstream_event(self, pad: Pad, event: Event) -> None:
        if event.kind == EventKind.PLACEMENT:
            # every branch reads the SAME buffer: what goes upstream is
            # what they all asked for, as it stands after each request
            self._wishes[pad.name] = event.data["layouts"]
            event = Event.placement(self._common_wish())
        super().handle_upstream_event(pad, event)

    def _common_wish(self) -> tuple:
        """Per tensor, the one layout every linked branch asked for, or
        None where they differ; nothing while a branch has not asked."""
        asked = [self._wishes.get(sp.name) for sp in self.srcpads
                 if sp.peer is not None]
        if any(w is None for w in asked):
            return ()
        return tuple(ws[0] if all(w == ws[0] for w in ws[1:]) else None
                     for ws in zip(*asked))

    def stop(self) -> None:
        self._wishes.clear()


@register_element("identity")
class Identity(Element):
    FACTORY = "identity"
    PASSES_BUFFERS = True

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.add_sink_pad()
        self.add_src_pad()

    def chain(self, pad: Pad, buf: Buffer) -> None:
        self.push(buf)


@register_element("filesrc")
class FileSrc(SourceElement):
    """Read a file and push its bytes as application/octet-stream buffers
    (parity: GStreamer filesrc, the head of every SSAT golden pipeline).
    ``blocksize=0`` pushes the whole file as one buffer."""

    FACTORY = "filesrc"

    def __init__(self, name=None, location: str = "", blocksize: int = 0,
                 **props):
        self.location = location
        self.blocksize = blocksize
        super().__init__(name, **props)
        self._fh = None
        self._done = False

    def output_caps(self) -> Caps:
        from ..core import CapsStruct

        return Caps.new(CapsStruct.make("application/octet-stream"))

    def output_spec(self):
        return None

    def start(self) -> None:
        self._fh = open(self.location, "rb")
        self._done = False
        super().start()

    def stop(self) -> None:
        super().stop()
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def create(self) -> Optional[Buffer]:
        import numpy as np

        if self._done or self._fh is None:
            return None
        size = int(self.blocksize)
        data = self._fh.read(size) if size > 0 else self._fh.read()
        if not data or size <= 0:
            self._done = True
        if not data:
            return None
        from ..core import Tensor, TensorSpec

        arr = np.frombuffer(data, np.uint8)
        return Buffer(tensors=[Tensor(
            arr, TensorSpec.from_shape(arr.shape, np.uint8))])


@register_element("filesink")
class FileSink(SinkElement):
    """Append every incoming buffer's payload bytes to a file (parity:
    GStreamer filesink — the tail of every SSAT golden comparison)."""

    FACTORY = "filesink"

    def __init__(self, name=None, location: str = "", **props):
        self.location = location
        super().__init__(name, **props)
        self._fh = None

    def start(self) -> None:
        self._fh = open(self.location, "wb")

    def render(self, buf: Buffer) -> None:
        for t in buf.tensors:
            self._fh.write(t.tobytes())

    def stop(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


@register_element("tensor_debug")
class TensorDebug(Element):
    """Stream introspection (parity:
    /root/reference/gst/nnstreamer/elements/gsttensor_debug.c): posts an
    ELEMENT bus message describing each buffer, passes data through."""

    FACTORY = "tensor_debug"

    def __init__(self, name=None, output_mode: str = "console", **props):
        self.output_mode = output_mode
        super().__init__(name, **props)
        self.add_sink_pad()
        self.add_src_pad()

    def chain(self, pad: Pad, buf: Buffer) -> None:
        desc = {
            "num_tensors": buf.num_tensors,
            "dims": [t.spec.dim_string() for t in buf.tensors],
            "types": [str(t.dtype) for t in buf.tensors],
            "format": str(buf.format),
            "pts": buf.pts,
        }
        if self.output_mode == "console":
            from ..utils.log import logi

            logi("buffer %s", desc, element=self.name)
        self.post_message(
            Message(MessageKind.ELEMENT, self.name, data=desc))
        self.push(buf)
