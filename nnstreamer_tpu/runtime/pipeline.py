"""Pipeline container: graph assembly, negotiation, state, bus.

Replaces GstPipeline/GstBus for this framework.  ``Pipeline.start()`` runs
the static negotiation pass (sources outward, parity with the PAUSED-state
caps negotiation described at
/root/reference/gst/nnstreamer/tensor_filter/tensor_filter.c:188-194), then
spawns source threads.  ``bus`` carries ERROR/EOS/LATENCY/ELEMENT messages.
"""

from __future__ import annotations

import queue as _q
import threading
import time
from typing import Dict, List, Optional, Union

from ..obs import metrics as _metrics
from ..utils import profile as _profile
from ..utils.log import logi
from .element import (Element, NegotiationError, Pad, SinkElement,
                      SourceElement)
from .events import Message, MessageKind


class Bus:
    """Message bus (parity: GstBus).  Watch handlers run synchronously in
    the posting thread, so handler registration is copy-on-write under a
    lock: ``post`` reads an immutable snapshot and never holds the lock
    while invoking handlers (a handler may itself add/remove watches)."""

    def __init__(self):
        self._q: "_q.Queue[Message]" = _q.Queue()
        self._handlers: tuple = ()
        self._handlers_lock = threading.Lock()

    def post(self, msg: Message) -> None:
        handlers = self._handlers  # immutable snapshot; no lock on post
        for h in handlers:
            h(msg)
        self._q.put(msg)

    def pop(self, timeout: Optional[float] = None) -> Optional[Message]:
        try:
            return self._q.get(timeout=timeout)
        except _q.Empty:
            return None

    def add_watch(self, handler) -> None:
        with self._handlers_lock:
            self._handlers = self._handlers + (handler,)

    def remove_watch(self, handler) -> bool:
        """Remove ONE registration of a previously added watch (parity:
        gst_bus_remove_watch — paired add/remove by independent callers
        stays balanced).  Returns whether it was registered.  A ``post``
        racing with the removal may still deliver one last message to the
        handler."""
        with self._handlers_lock:
            # equality, not identity: bound methods compare equal across
            # distinct access objects (bus.remove_watch(self._watch))
            for i, h in enumerate(self._handlers):
                if h == handler:
                    self._handlers = (self._handlers[:i]
                                      + self._handlers[i + 1:])
                    return True
            return False


#: ``Pipeline._first_window_from`` between the sinks being armed and
#: ``start()`` returning
_ARMED = 0


class Pipeline:
    def __init__(self, name: str = "pipeline", fuse: bool = True):
        self.name = name
        # transform↔filter fusion pass (SURVEY §7 stage 4); opt out with
        # fuse=False to run every element as its own computation
        self.fuse = fuse
        # captured single-dispatch segments (runtime/fusion.py
        # FusedSegment), rebuilt on every start()
        self.fused_segments: list = []
        self.elements: Dict[str, Element] = {}
        self.bus = Bus()
        self.playing = False
        self._eos_evt = threading.Event()
        self._err_evt = threading.Event()
        # single combined wake-up for wait_eos: set on EITHER terminal
        # condition so the waiter blocks on one event instead of
        # busy-polling two
        self._done_evt = threading.Event()
        self._first_error: Optional[Message] = None
        self._n_sinks = 0
        self._eos_sinks: set = set()
        # <pipeline>/first_window: None, then _ARMED while start() runs,
        # then when start() returned (time.perf_counter_ns), and None
        # again once a sink's first fence has returned
        self._first_window_from: Optional[int] = None
        self._first_window_lock = threading.Lock()
        self.bus.add_watch(self._watch)

    # -- assembly ------------------------------------------------------------

    def add(self, *elements: Element) -> "Pipeline":
        for e in elements:
            if e.name in self.elements:
                raise ValueError(f"duplicate element name {e.name!r}")
            self.elements[e.name] = e
            e.pipeline = self
        return self

    def __getitem__(self, name: str) -> Element:
        return self.elements[name]

    def link(self, *chain: Union[Element, str]) -> "Pipeline":
        """Link elements in sequence using their default src/sink pads."""
        els = [self.elements[c] if isinstance(c, str) else c for c in chain]
        for a, b in zip(els, els[1:]):
            self.link_pads(a, "src", b, "sink")
        return self

    def link_pads(self, a: Union[Element, str], apad: str,
                  b: Union[Element, str], bpad: str) -> "Pipeline":
        """Link ``a.apad`` → ``b.bpad``.  Re-linking an already-connected
        pad raises ``ValueError`` naming the existing peer — a link is
        never silently overwritten (unlink first to re-route)."""
        a = self.elements[a] if isinstance(a, str) else a
        b = self.elements[b] if isinstance(b, str) else b
        a.get_pad(apad).link(b.get_pad(bpad))
        return self

    # -- state ---------------------------------------------------------------

    def start(self) -> "Pipeline":
        if self.playing:
            return self
        # a pause is charged the collections that overlap it: watched
        # from the first start of a process to the last stop
        _profile.watch_collections(self)
        # set-up is one tree: everything start() does lies under this
        # span, and <pipeline>/first_window takes over where it ends
        with _profile.span(self.name, "start", setup=True):
            self._start()
        with self._first_window_lock:
            if self._first_window_from == _ARMED:   # nothing fenced yet
                self._first_window_from = time.perf_counter_ns()
        return self

    def _first_fenced(self) -> None:
        """A sink's first fence of this start has returned: the first
        window is computed.  Kept once a start, as the set-up span
        ``<pipeline>/first_window`` on the thread that fenced: it holds
        what the streaming thread builds lazily (``trace_lower``,
        ``load_or_compile``, ``first_call``) and the first execution.
        Nothing is kept for a stream that was through its first fence
        before ``start()`` returned."""
        with self._first_window_lock:
            since, self._first_window_from = self._first_window_from, None
            for e in self.elements.values():
                if isinstance(e, SinkElement):
                    e._first_fence_due = False
        if since:
            _profile.keep_setup(self.name + "/first_window", since,
                                time.perf_counter_ns())

    def _start(self) -> None:
        # fresh terminal state for this run: a restarted pipeline must
        # not report the previous run's EOS/error from wait_eos()
        self._eos_evt.clear()
        self._err_evt.clear()
        self._done_evt.clear()
        self._eos_sinks.clear()
        self._first_error = None
        sources = [e for e in self.elements.values()
                   if isinstance(e, SourceElement)]
        if not sources:
            raise NegotiationError("pipeline has no source element")
        try:
            self._check_links()
            from .fusion import fuse_pipeline

            # whole-graph capture: collapse every eligible linear
            # transform→filter→decoder segment into one XLA program and
            # record the FusedSegment descriptors (digests key the
            # persistent compile cache; names label dispatch counting)
            with _profile.span(self.name, "fuse", setup=True):
                fuse_pipeline(self, enable=self.fuse)
            # Negotiation: sources fix their caps and propagate downstream.
            with _profile.span(self.name, "negotiate", setup=True):
                for s in sources:
                    s.negotiate()
            self._check_negotiated()
            self._n_sinks = sum(
                1 for e in self.elements.values()
                if not e.srcpads and e.sinkpads)
            # Start sinks/others before sources so data finds everything
            # live.
            self._first_window_from = _ARMED
            for e in self.elements.values():
                if isinstance(e, SinkElement):
                    e._first_fence_due = True
                if not isinstance(e, SourceElement):
                    with _profile.span(e.name, "activate", setup=True):
                        e.start()
            for s in sources:
                with _profile.span(s.name, "activate", setup=True):
                    s.start()
        except Exception:
            # A failed transition must not leak what already opened:
            # filters acquired during negotiation hold process-global
            # resources (serving-pool refcounts pin params in HBM), and
            # some elements may have started threads.  Roll back to NULL
            # — stop() is safe on never-started elements — then re-raise
            # the original failure.
            self.stop()
            raise
        self.playing = True
        # observability: the pipeline becomes visible to the process
        # metrics registry (weakly referenced — scrape-time pull only,
        # the hot path pays nothing; Documentation/observability.md)
        _metrics.REGISTRY.register_pipeline(self)
        # chaos: NNS_TPU_CHAOS installs a process-wide fault plan on
        # first pipeline start (Documentation/robustness.md)
        from ..chaos import hooks as _chaos_hooks

        _chaos_hooks.maybe_install_from_env()
        # flight recorder: NNS_TPU_FLIGHTREC_DIR arms dump-to-disk on
        # first pipeline start (Documentation/observability.md)
        from ..obs import flightrec as _flightrec

        _flightrec.maybe_arm_from_env()
        # watchdog: NNS_TPU_WATCH starts the alerting sampler on first
        # pipeline start (Documentation/observability.md, "Alerting")
        from ..obs import watch as _watch

        _watch.maybe_start_from_env()
        # host profiler: NNS_TPU_PROF starts the sampling profiler,
        # NNS_TPU_PROF_DEEP_DIR arms alert-triggered deep captures
        # (Documentation/observability.md, "Host execution profiling")
        from ..obs import prof as _prof

        _prof.maybe_start_from_env()
        # controller: NNS_TPU_CTL closes the loop — alerts steer the
        # actuator API (Documentation/observability.md, "Closed-loop
        # control & MTTR")
        from ..obs import control as _control

        _control.maybe_start_from_env()

    def stop(self) -> "Pipeline":
        _metrics.REGISTRY.unregister_pipeline(self)
        for e in self.elements.values():
            if isinstance(e, SourceElement):
                e.stop()
        for e in self.elements.values():
            if not isinstance(e, SourceElement):
                e.stop()
        # Going to NULL clears negotiated caps (GStreamer semantics): an
        # element relinked into another pipeline — or this pipeline
        # restarted — renegotiates from scratch instead of tripping over
        # stale pad schemas.
        for e in self.elements.values():
            for p in e.sinkpads + e.srcpads:
                p.caps = None
                p.spec = None
            e._eos_seen.clear()
        self.playing = False
        _profile.unwatch_collections(self)
        # what held a window up for 50 ms or more, and what the process
        # did meanwhile
        _profile.report_slow(logi)
        return self

    def _check_links(self) -> None:
        for e in self.elements.values():
            for p in e.sinkpads:
                if p.peer is None:
                    raise NegotiationError(
                        f"{e.name}.{p.name}: sink pad not linked")

    def _check_negotiated(self) -> None:
        for e in self.elements.values():
            for p in e.sinkpads + e.srcpads:
                if p.peer is not None and p.caps is None:
                    raise NegotiationError(
                        f"{e.name}.{p.name}: caps not negotiated "
                        f"(negotiation did not reach this pad)")

    def to_dot(self) -> str:
        """Graphviz dot of the pipeline graph with negotiated caps on the
        edges (parity: GST_DEBUG_DUMP_DOT_DIR pipeline dumps,
        /root/reference/tools/debugging/README.md)."""
        lines = [f'digraph "{self.name}" {{', "  rankdir=LR;",
                 "  node [shape=box, fontsize=10];"]
        for e in self.elements.values():
            label = f"{e.name}\\n({e.FACTORY if hasattr(e, 'FACTORY') else type(e).__name__})"
            lines.append(f'  "{e.name}" [label="{label}"];')
        seen = set()
        for e in self.elements.values():
            for sp in e.srcpads:
                if sp.peer is None:
                    continue
                key = (e.name, sp.name)
                if key in seen:
                    continue
                seen.add(key)
                caps = str(sp.caps) if sp.caps is not None else "?"
                caps = caps.replace('"', "'")
                lines.append(
                    f'  "{e.name}" -> "{sp.peer.element.name}" '
                    f'[label="{caps}", fontsize=8];')
        lines.append("}")
        return "\n".join(lines)

    # -- bus convenience ------------------------------------------------------

    def post(self, msg: Message) -> None:
        self.bus.post(msg)

    def _watch(self, msg: Message) -> None:
        if msg.kind == MessageKind.ERROR:
            if self._first_error is None:
                self._first_error = msg
            self._err_evt.set()
            self._done_evt.set()
        elif msg.kind == MessageKind.EOS:
            self._eos_sinks.add(msg.source)
            if len(self._eos_sinks) >= max(self._n_sinks, 1):
                self._eos_evt.set()
                self._done_evt.set()

    def wait_eos(self, timeout: Optional[float] = None,
                 raise_on_error: bool = True) -> bool:
        """Block until every sink reported EOS (or an error).  Waits on
        ONE combined event — an idle pipeline burns no CPU re-waking a
        poll loop (with no timeout the wait is a plain blocking wait)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if self._err_evt.is_set():
                if raise_on_error:
                    raise RuntimeError(
                        f"pipeline error: {self._first_error}")
                return False
            if self._eos_evt.is_set():
                return True
            remain = None if deadline is None else deadline - time.monotonic()
            if remain is not None and remain <= 0:
                return False
            self._done_evt.wait(remain)

    @property
    def error(self) -> Optional[Message]:
        return self._first_error

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False
