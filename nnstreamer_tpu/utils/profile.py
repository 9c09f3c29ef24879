"""The program's trace layer: phase spans on the host, stage scopes on the
device, one capture that holds both.

Parity target: the reference defers profiling to GStreamer ecosystem
tooling — gst-instruments/gst-top, NNShark (/root/reference/tools/
profiling/README.md) — plus its in-tree per-filter latency/throughput
props.  The TPU-native substitute is the JAX profiler (SURVEY.md §7.7):
``pipeline_trace`` captures a TensorBoard-loadable trace of everything
the pipeline dispatches, and the runtime brackets its own work in
:class:`span` s named ``<element>/<phase>`` (``el_net/dispatch``,
``el_sink/fence``, ...; the vocabulary is in
Documentation/observability.md), so a gap on the device timeline can be
put down to what the host was doing in it.

A span goes to two sinks:

- the profiler's clock: a ``jax.profiler.TraceAnnotation`` of the same
  name while a ``pipeline_trace`` capture is active, so it lies in the
  same ``.xplane.pb`` as the device lines;
- memory (``time.perf_counter_ns``), read back with :func:`spans`: every
  set-up span always (with what jax reports of a program's build inside
  it: ``jax/trace``, ``jax/lower``, ``jax/compile_or_load``,
  ``jax/cache_load``), every per-window span while a capture is active,
  and outside a capture only a per-window span that lasted
  ``SLOW_NS`` or more (the slow list).  All three lists are bounded and
  keep their newest spans; :func:`spans_dropped` says how many each
  pushed out.

A per-window span of ``SLOW_NS`` or more also leaves a :class:`Pause`,
capture or not, in a bounded list of its own (:func:`pauses`): the CPU
time the thread and the whole process gained between a baseline read
before the span began and its end, the collections that overlap it, and
the one-word ``cause`` those name (:func:`cause_of`).  Nothing of it
reaches :func:`spans`: no span's name, kind or note says a cause.

Set-up is one tree a thread: ``<model>/register``, ``<pipeline>/parse``,
``<pipeline>/start`` (``fuse``, ``negotiate``, ``<element>/activate``
inside it) and ``<pipeline>/first_window`` (from ``start()`` returning
to the first fence at a sink, kept by hand through :func:`keep_setup`).

Inside the fused program the stages are ``jax.named_scope`` s
(``nns.pre``, ``nns.model/<stage>``, ``nns.post``); :func:`stage_seconds`
reduces a capture to device seconds per stage, and
``python -m nnstreamer_tpu.utils.profile <xplane.pb>`` prints it.

Usage::

    from nnstreamer_tpu.utils.profile import pipeline_trace, spans

    with pipeline_trace("/tmp/nns-trace"):
        with pipeline:
            ... stream ...
    # tensorboard --logdir /tmp/nns-trace, or spans() / stage_seconds()

What it costs: with no capture a per-window span is one small object,
two clock reads and two compares around the work (is it slow; is a
baseline due): 0.87 us a span on the
host of the one-chip machine (200,000 spans timed there, PR 24;
``tests/test_profile_spans.py`` prints the figure of the host it runs
on), some ten spans a window, so 9 us of an 18 ms window; PERF.md
section 6 has the rates measured on the chip with and without.  Nothing
is kept, no lock is taken and jax is not touched.  At most once in
``BASELINE_NS`` (100 ms) a thread, a span's exit reads the two CPU
clocks for the baseline of the next pause (two system calls, no file,
no lock).  During a capture each
span also enters a ``TraceAnnotation`` and appends one tuple under a
lock.  ``NNS_TPU_OBS_DISABLE`` switches spans off altogether (no clock
read).
"""

from __future__ import annotations

import collections
import contextlib
import gc
import re
import threading
import time
import weakref
from typing import Dict, List, NamedTuple, Optional

from ..obs import hooks as _hooks

_active = threading.Event()

#: a per-window span this long is kept even when no capture is active
SLOW_NS = 50_000_000
#: the note of a ``<sink>/fence`` span that lasted that long
#: (``SinkElement._who_was_late``)
HOST_LATE = "next window done: host late"
DEVICE_LATE = "next window running: device late"
SLOW_MAX = 1024
#: a thread's baseline (the reading that opens its next pause) is read
#: again from a span's exit once it is this old
BASELINE_NS = 100_000_000
#: baselines a thread keeps: a slow span's enclosing spans end after it
#: and began before it, so each needs an older one
BASELINES_KEPT = 8
#: the newest collections kept as intervals
COLLECTIONS_KEPT = 64
CAPTURE_MAX = 1 << 18
#: room for every set-up span of a process that builds state ahead of
#: its stream, many times over: the benchmark's cell with most keeps
#: 328 (256 prefill chunks and jax's parts of every build)
SETUP_MAX = 1 << 14


class Span(NamedTuple):
    """One kept span.  ``kind``: ``setup`` (always kept), ``window``
    (kept because a capture was active), ``slow`` (kept because it
    lasted ``SLOW_NS``) or ``trace`` (the capture itself:
    ``trace/start``, ``trace/capture``, ``trace/stop``).  ``window`` is
    the id every span of one window shares (``buf.pts`` in a replay
    line, the batcher's window number in a pool); :func:`spans` fills it
    in from the enclosing span where a site did not know it.  The span
    that caused this one is the one it nests in on its ``thread``."""

    name: str
    start_ns: int
    end_ns: int
    thread: int
    window: Optional[int]
    kind: str
    note: Optional[str]


class Pause(NamedTuple):
    """What the process did around one per-window span of ``SLOW_NS`` or
    more.  ``start_ns`` and ``end_ns`` are the span's own, on the spans'
    clock (``time.perf_counter_ns``, which a capture's
    ``TraceAnnotation`` of the same span shares with the device lines);
    ``note`` is the span's (a fence's says who was late) and ``window``
    its window id, filled in from the enclosing pause as :func:`spans`
    does.  ``deltas``: ``thread_cpu_ns`` (``time.thread_time_ns``: this
    thread computed) and ``process_cpu_ns`` (``time.process_time_ns``:
    every thread of the process, the runtime's and the application's
    too) gained between the thread's baseline and the span's end;
    ``gc_ns``, ``gc_collections``, ``gc_generation`` and
    ``gc_other_thread`` are the collections that overlap the span
    itself, on any thread (no ``gc_*`` key while no started pipeline has
    them watched).  ``baseline_age_ns`` is how long before the span
    began the baseline was read: the two CPU deltas cover that stretch
    of ordinary work too; negative where the only baseline is from
    inside the span, ``None`` (and no CPU delta) where the thread had
    none.  ``cause`` is :func:`cause_of` of them."""

    name: str
    thread: int
    window: Optional[int]
    start_ns: int
    end_ns: int
    note: Optional[str]
    baseline_age_ns: Optional[int]
    deltas: Dict[str, int]
    cause: str


#: a pause's cause where nothing read accounts for half of it: every
#: delta is in the record, so it still says what the pause was not
UNEXPLAINED = "unexplained"


def charges(deltas: Dict[str, int],
            baseline_age_ns: Optional[int] = 0) -> Dict[str, int]:
    """Nanoseconds of the span each cause can answer for: ``gc`` (the
    collections overlapping it, by interval) and ``on_cpu`` (what the
    thread SURELY computed inside it: its CPU time since the baseline
    less the baseline's age, all of which it may have computed through
    before the span began)."""
    out = {}
    if "gc_ns" in deltas:
        out["gc"] = deltas["gc_ns"]
    if "thread_cpu_ns" in deltas:
        out["on_cpu"] = max(
            0, deltas["thread_cpu_ns"] - max(baseline_age_ns or 0, 0))
    return out


def cause_of(length_ns: int, deltas: Dict[str, int],
             baseline_age_ns: Optional[int] = 0) -> str:
    """The rule, written once: of :func:`charges`, the largest, if it
    covers at least half of the span's length; else ``unexplained``: the
    thread neither computed nor stood behind a collection, so it waited
    or was not run, and ``process_cpu_ns`` beside it says whether the
    rest of the process stood still too.  ``on_cpu`` is for spans that
    are not waits: a wait computes nothing.  What the operating system
    and the machine count of a thread that is not run (run-queue delay,
    a control group's throttling, steal) decides nothing yet: no host
    the benchmark reaches shows those counters (PERF.md section 7)."""
    timed = charges(deltas, baseline_age_ns)
    if timed:
        cause = max(timed, key=timed.get)
        if 2 * timed[cause] >= length_ns:
            return cause
    return UNEXPLAINED


class _Recorder:
    """The three bounded lists: each keeps its newest spans, so a
    process that lives long still has its latest set-up, capture and
    slow windows.  Appended to under the lock only: a site that keeps
    nothing never gets here.  The pauses have a list of their own, which
    :func:`spans` never reads."""

    def __init__(self, limits=None):
        self.lock = threading.Lock()
        limits = dict(limits or {"setup": SETUP_MAX, "window": CAPTURE_MAX,
                                 "slow": SLOW_MAX})
        self.pauses: collections.deque = collections.deque(
            maxlen=limits.pop("pause", SLOW_MAX))
        self.pauses_dropped = 0
        self.lists: Dict[str, collections.deque] = {
            which: collections.deque(maxlen=n)
            for which, n in limits.items()}
        self.dropped = dict.fromkeys(self.lists, 0)
        self.slow_kept = 0          # ever, for report_slow
        self.slow_reported = 0

    def keep(self, name, t0, t1, window, kind, note=None) -> None:
        row = Span(name, t0, t1, threading.get_ident(), window, kind, note)
        which = "setup" if kind == "trace" else kind
        with self.lock:
            rows = self.lists[which]
            if len(rows) == rows.maxlen:
                self.dropped[which] += 1
            rows.append(row)
            if which == "slow":
                self.slow_kept += 1

    def keep_pause(self, row: Pause) -> None:
        with self.lock:
            if len(self.pauses) == self.pauses.maxlen:
                self.pauses_dropped += 1
            self.pauses.append(row)

    def clear(self) -> None:
        with self.lock:
            for rows in self.lists.values():
                rows.clear()
            self.pauses.clear()
            self.dropped = dict.fromkeys(self.lists, 0)
            self.slow_kept = self.slow_reported = self.pauses_dropped = 0


_REC = _Recorder()


class span:
    """``with span(owner, phase, window):`` around one phase of one
    element's work.  The name is ``owner/phase`` (``owner`` alone
    without a phase) and is only built when the span is kept.
    ``setup=True`` marks a span of pipeline set-up, kept always;
    ``note`` (settable inside the block) rides along, e.g. ``hit`` or
    ``miss`` on a compile-cache load.  ``if_slow`` (settable inside the
    block) is called for the note of a per-window span only when it
    lasted ``SLOW_NS`` or more: what is worth asking once a window was
    held up, and not on every window."""

    __slots__ = ("owner", "phase", "window", "setup", "note", "if_slow",
                 "_t0", "_ann", "_said")

    def __init__(self, owner: str, phase: Optional[str] = None,
                 window: Optional[int] = None, setup: bool = False):
        self.owner = owner
        self.phase = phase
        self.window = window
        self.setup = setup
        self.note = None
        self.if_slow = None
        self._t0 = None
        self._ann = None
        self._said = None

    @property
    def name(self) -> str:
        return self.owner if self.phase is None \
            else self.owner + "/" + self.phase

    def __enter__(self) -> "span":
        if _hooks.DISABLED:
            return self
        if self.setup:
            _watch_jax_compiles()
            if not hasattr(_tls, "setup"):
                _tls.setup = []       # the set-up spans open on this thread
            _tls.setup.append(self)
        if _active.is_set():
            import jax

            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t0 = self._t0
        if t0 is None:
            return False
        t1 = time.perf_counter_ns()
        ann = self._ann
        if ann is not None:
            ann.__exit__(*exc)
        if self.setup:
            _tls.setup.pop()
            if self._said:
                said = "; ".join(text if n == 1 else f"{text} (x{n})"
                                 for text, n in self._said.items())
                self.note = said if self.note is None \
                    else f"{self.note}; {said}"
            _REC.keep(self.name, t0, t1, self.window, "setup", self.note)
        elif t1 - t0 >= SLOW_NS:
            if self.if_slow is not None:
                self.note = self.if_slow()
            _REC.keep(self.name, t0, t1, self.window,
                      "slow" if ann is None else "window", self.note)
            _keep_pause(self.name, t0, t1, self.window, self.note)
        elif ann is not None:
            _REC.keep(self.name, t0, t1, self.window, "window", self.note)
            if t1 >= _gate_ns:
                _gate(t1)
        elif t1 >= _gate_ns:
            # the fast path's one compare more, with a module global: a
            # span that is not kept reads no clock of the process, and
            # looks no further, unless the gate stands open
            _gate(t1)
        return False


def keep_setup(name: str, start_ns: int, end_ns: int,
               note: Optional[str] = None) -> None:
    """Keeps a set-up span timed by hand (``time.perf_counter_ns``), on
    the calling thread: one whose two ends lie in different calls, such
    as ``<pipeline>/first_window``."""
    if not _hooks.DISABLED:
        _REC.keep(name, start_ns, end_ns, None, "setup", note)


def note(text: str) -> None:
    """Adds ``text`` to the note of the innermost set-up span open on
    this thread; a text said again is counted, not repeated.  For code
    that runs deep inside a span it cannot see: a model function says
    here, while ``<filter>/trace_lower`` traces it, which program it
    chose for a shape.  Outside a set-up span it does nothing."""
    open_here = getattr(_tls, "setup", None)
    if open_here:
        inner = open_here[-1]
        if inner._said is None:
            inner._said = {}
        inner._said[text] = inner._said.get(text, 0) + 1

# -- the pause ledger: what the process did around a slow span ---------------


#: a thread's own, each made when first needed: ``setup`` (its open
#: set-up spans) and ``baselines`` (its newest readings of the CPU
#: clocks, for the pauses)
_tls = threading.local()
#: the gate a span's exit compares its clock read with, and when the
#: slot of the opening that is on ends.  The gate opens once in
#: ``BASELINE_NS`` and stands open for ``GATE_SLOT_NS`` from the first
#: span through it, so that every thread that streams gets through; a
#: module global costs that compare 20 ns of a span's 800, a
#: thread-local 90
_gate_ns = 0
_slot_ends_ns = 0
GATE_SLOT_NS = 5_000_000


def _reading(t_ns: int) -> Dict[str, int]:
    """The calling thread's cumulative readings of one instant, stamped
    ``t_ns`` (the caller's clock read: a span's exit holds one)."""
    return {"t_ns": t_ns, "thread_cpu_ns": time.thread_time_ns(),
            "process_cpu_ns": time.process_time_ns()}


def _baselines() -> List[Dict[str, int]]:
    try:
        return _tls.baselines
    except AttributeError:
        _tls.baselines = []
        return _tls.baselines


def _gate(t1: int) -> None:
    """The gate stands open.  Reads this thread's baseline if its newest
    is ``BASELINE_NS`` old, and shuts the gate for ``BASELINE_NS`` once
    the slot is over: it never stays open for a thread that has ended
    or idles.  A thread that exits no span inside a slot keeps its older
    baseline (its age is in the record); no lock: two threads at once
    cost at most a reading too many."""
    global _gate_ns, _slot_ends_ns
    known = _baselines()
    if not known or t1 - known[-1]["t_ns"] >= BASELINE_NS:
        known.append(_reading(t1))
        del known[:-BASELINES_KEPT]
    if _slot_ends_ns <= _gate_ns:       # the first through this opening
        _slot_ends_ns = t1 + GATE_SLOT_NS
    elif t1 >= _slot_ends_ns:
        _gate_ns = t1 + BASELINE_NS


class _Collections:
    """ONE ``gc.callbacks`` entry, installed while anyone watches: it
    runs only when a collection does and keeps (start, end, generation,
    thread) of the newest ``COLLECTIONS_KEPT``, on the spans' clock.  A
    collection holds the interpreter, so it stops every thread that runs
    Python, whichever thread it was triggered on: a pause is charged the
    ones that overlap it by interval."""

    def __init__(self):
        self.recent: collections.deque = collections.deque(
            maxlen=COLLECTIONS_KEPT)
        self._began: Optional[tuple] = None
        self._owners: "weakref.WeakSet" = weakref.WeakSet()
        self._lock = threading.Lock()

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._began = (time.perf_counter_ns(), threading.get_ident())
        elif self._began is not None:
            (t0, thread), self._began = self._began, None
            self.recent.append((t0, time.perf_counter_ns(),
                                int(info.get("generation", -1)), thread))

    @property
    def installed(self) -> bool:
        return self._callback in gc.callbacks

    def watch(self, owner) -> None:
        """Installs the callback for as long as ``owner`` (held weakly)
        or any other watches."""
        with self._lock:
            self._owners.add(owner)
            if not self.installed:
                gc.callbacks.append(self._callback)

    def unwatch(self, owner) -> None:
        with self._lock:
            self._owners.discard(owner)
            if not self._owners and self.installed:
                gc.callbacks.remove(self._callback)
                self._began = None

    def overlapping(self, t0: int, t1: int) -> list:
        """[(start, end, generation, thread)] of the kept collections
        that overlap ``[t0, t1]``, each clipped to it; one still running
        counts up to ``t1``."""
        rows = list(self.recent)
        began = self._began
        if began is not None:
            rows.append((began[0], t1, -1, began[1]))
        return [(max(a, t0), min(b, t1), gen, thread)
                for a, b, gen, thread in rows if a < t1 and b > t0]


_GC = _Collections()


def _keep_pause(name: str, t0: int, t1: int, window: Optional[int],
                note: Optional[str]) -> None:
    """The reading that closes a slow span, against the newest baseline
    from before it began; that reading is the next pause's baseline."""
    me = threading.get_ident()
    closed = _reading(t1)
    known = _baselines()
    opened = next((b for b in reversed(known) if b["t_ns"] <= t0),
                  known[0] if known else None)
    # whatever was read inside this span serves no later one: the spans
    # around it began before it, the spans after it begin after ``closed``
    while known and known[-1]["t_ns"] > t0:
        known.pop()
    known.append(closed)
    del known[:-BASELINES_KEPT]
    deltas, age = {}, None
    if opened is not None:
        age = t0 - opened["t_ns"]
        deltas = {key: closed[key] - was for key, was in opened.items()
                  if key != "t_ns"}
    if _GC.installed:
        during = _GC.overlapping(t0, t1)
        deltas["gc_ns"] = sum(b - a for a, b, _g, _t in during)
        deltas["gc_collections"] = len(during)
        if during:
            deltas["gc_generation"] = max(g for _a, _b, g, _t in during)
            deltas["gc_other_thread"] = int(any(
                t != me for _a, _b, _g, t in during))
    _REC.keep_pause(Pause(name, me, window, t0, t1, note, age, deltas,
                          cause_of(t1 - t0, deltas, age)))


def watch_collections(owner) -> None:
    """``Pipeline.start`` calls this: while any started pipeline lives,
    ONE ``gc.callbacks`` entry keeps the newest collections' intervals,
    so a pause is charged the ones that overlap it.  Never installed
    under ``NNS_TPU_OBS_DISABLE``."""
    if not _hooks.DISABLED:
        _GC.watch(owner)


def unwatch_collections(owner) -> None:
    """``Pipeline.stop`` calls this; the last one removes the entry."""
    _GC.unwatch(owner)


# -- what jax itself did inside a set-up span ---------------------------------

_watching = threading.Event()
#: jax.monitoring's duration events of one program build, by the name of
#: the set-up span each is kept as
JAX_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    # a build, or the load of one from the persistent cache
    "/jax/core/compile/backend_compile_duration": "jax/compile_or_load",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax/cache_load",
}


#: jax traces every jnp function a model calls on its own: hundreds of
#: steps of microseconds a build, which say nothing
JAX_PART_MIN_S = 0.01


def _on_jax_duration(event: str, seconds: float, **kw) -> None:
    """Keeps a build step jax reports while a set-up span is open on
    this thread as a set-up span of its own (ending now, ``seconds``
    long), so that ``<filter>/trace_lower`` and ``<filter>/first_call``
    say what they spent their time on: tracing, lowering, compiling or
    loading; what is left of ``first_call`` is the first execution."""
    name = JAX_PARTS.get(event)
    if name is None or seconds < JAX_PART_MIN_S \
            or not getattr(_tls, "setup", None):
        return
    end = time.perf_counter_ns()
    keep_setup(name, end - int(seconds * 1e9), end, kw.get("fun_name"))


def _watch_jax_compiles() -> None:
    if _watching.is_set():
        return
    import jax

    with _REC.lock:             # once, whoever comes first
        if _watching.is_set():
            return
        _watching.set()
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)



@contextlib.contextmanager
def pipeline_trace(log_dir: str, profiler_options=None):
    """Capture a JAX profiler trace of everything run inside.
    ``profiler_options`` (a ``jax.profiler.ProfileOptions``) is handed
    to ``jax.profiler.start_trace`` only when given, so options a caller
    bound onto ``start_trace`` itself stay in force.  The profiler's own
    start and stop are kept as ``trace/start`` and ``trace/stop`` spans,
    and the capture between them as ``trace/capture``."""
    import jax

    kw = {} if profiler_options is None \
        else {"profiler_options": profiler_options}
    keep = not _hooks.DISABLED
    t0 = time.perf_counter_ns()
    jax.profiler.start_trace(log_dir, **kw)
    t1 = time.perf_counter_ns()
    _active.set()
    try:
        yield log_dir
    finally:
        _active.clear()
        t2 = time.perf_counter_ns()
        jax.profiler.stop_trace()
        t3 = time.perf_counter_ns()
        if keep:
            _REC.keep("trace/start", t0, t1, None, "trace", log_dir)
            _REC.keep("trace/capture", t1, t2, None, "trace", log_dir)
            _REC.keep("trace/stop", t2, t3, None, "trace", log_dir)


def trace_active() -> bool:
    return _active.is_set()


def frame_annotation(trace_ids) -> "contextlib.AbstractContextManager":
    """TraceAnnotation naming the obs trace ids riding a dispatch.

    The join key between the two trace worlds: the host-side latency
    tracer (obs/tracer.py, Chrome trace) stamps each sampled frame with
    a process-unique id, and wrapping the XLA dispatch in
    ``nns:frames:<ids>`` makes the same ids searchable on the
    device-side TensorBoard timeline — so a slow frame found in one
    trace can be located in the other.  No-op (and near-free) unless a
    ``pipeline_trace`` capture is active AND the dispatch carries at
    least one sampled frame."""
    if not _active.is_set() or not trace_ids:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(
        "nns:frames:" + ",".join(str(i) for i in trace_ids))


# -- reading the spans back ---------------------------------------------------


def spans() -> List[Span]:
    """Every kept span, by start time.  A span whose site did not know
    its window takes the id of the innermost span of the same thread
    that encloses it."""
    with _REC.lock:
        rows = [s for part in _REC.lists.values() for s in part]
    return _by_start_with_windows(rows)


def pauses() -> List[Pause]:
    """Every kept :class:`Pause`, by start time: one a per-window span
    of ``SLOW_NS`` or more, nested ones too (a slow fence, and the chain
    spans around it), whether a capture was on or not."""
    with _REC.lock:
        rows = list(_REC.pauses)
    return _by_start_with_windows(rows)


def _by_start_with_windows(rows: list) -> list:
    rows.sort(key=lambda s: (s.start_ns, -s.end_ns))
    open_by_thread: Dict[int, list] = {}
    out = []
    for s in rows:
        stack = open_by_thread.setdefault(s.thread, [])
        while stack and stack[-1].end_ns < s.end_ns:
            stack.pop()
        if s.window is None and stack and stack[-1].window is not None:
            s = s._replace(window=stack[-1].window)
        stack.append(s)
        out.append(s)
    return out


def spans_dropped() -> Dict[str, int]:
    """Spans pushed out of each full list (``setup``, ``window``,
    ``slow``) by newer ones: whoever adds spans up reads this beside
    them, since a sum over a list that lost rows is short."""
    with _REC.lock:
        return dict(_REC.dropped)


def pauses_dropped() -> int:
    """Pauses pushed out of their full list by newer ones."""
    with _REC.lock:
        return _REC.pauses_dropped


def clear() -> None:
    """Forget every kept span (tests, and a process that runs one
    measurement after another)."""
    _REC.clear()


def report_slow(log) -> int:
    """Log the per-window spans of ``SLOW_NS`` or more kept since the
    last report, once a name and cause, with the time each delta of
    their pauses gained in all (``Pipeline.stop`` calls this); returns
    how many there were."""
    with _REC.lock:
        fresh = _REC.slow_kept - _REC.slow_reported
        rows = list(_REC.lists["slow"])[-fresh:] if fresh else []
        _REC.slow_reported = _REC.slow_kept
        found = {(p.thread, p.start_ns, p.end_ns): p for p in _REC.pauses}
    groups: Dict[tuple, list] = {}
    for s in rows:
        pause = found.get((s.thread, s.start_ns, s.end_ns))
        groups.setdefault((s.name, pause.cause if pause else None),
                          []).append((s.end_ns - s.start_ns, pause))
    for (name, cause), kept in sorted(groups.items(),
                                      key=lambda kv: (kv[0][0], str(kv[0][1]))):
        durs = [d for d, _p in kept]
        said = ""
        if cause is not None:
            gained: Dict[str, int] = {}
            for _d, pause in kept:
                for key, ns in pause.deltas.items():
                    if key.endswith("_ns"):
                        gained[key[:-3]] = gained.get(key[:-3], 0) + ns
            said = "; cause %s (%s)" % (cause, ", ".join(
                f"{what} {ns * 1e-6:.1f} ms"
                for what, ns in sorted(gained.items())) or "no baseline")
        log("slow span %s: %d over %d ms, %.1f ms in all, longest %.1f ms%s",
            name, len(durs), SLOW_NS // 1_000_000, sum(durs) * 1e-6,
            max(durs) * 1e-6, said)
    return len(rows)


# -- device stages ------------------------------------------------------------

#: the root scopes of the fused program (filters/jax_xla._normalized_fn)
STAGE_ROOT = "nns."
NO_SCOPE = "(no nns scope)"
NO_METADATA = "(no metadata)"

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_SCOPE = re.compile(r"^[\w.\-]+$")
_EVENT_INSTR = re.compile(r"^%?([\w.\-]+)")


def _unwrapped(part: str) -> str:
    """``vmap(nms)`` → ``nms``: the scope a transformation wraps; an
    inner ``jit(...)`` stays as it is (it is no scope)."""
    while True:
        m = _WRAPPED.match(part)
        if m is None or m.group(1) in ("jit", "pjit"):
            return part
        part = m.group(2)


def stage_of(op_name: str) -> str:
    """``jit(f)/nns.model/vmap(nms)/jit(_where)/select_n`` →
    ``nns.model/nms``: the scopes from the ``nns.`` root down, without
    the primitive at the end, with transformations unwrapped (a bucket
    program's root is ``vmap(nns.model)``) and anything under an inner
    ``jit`` (or a name that is no scope's, such as an einsum's
    subscripts) left out."""
    parts = [_unwrapped(part) for part in op_name.split("/")]
    for i, part in enumerate(parts):
        if part.startswith(STAGE_ROOT):
            break
    else:
        return NO_SCOPE
    stage = [parts[i]]
    for part in parts[i + 1:-1]:
        if not _SCOPE.match(part):
            break          # an inner jit, or a name jax put there itself
        stage.append(part)
    return "/".join(stage)


def stage_map(executable_text: str) -> Dict[str, str]:
    """Instruction name → stage, from an optimised HLO text
    (``JaxXlaFilter.executable_text()``): the metadata every
    instruction that came from the traced function, fusions included,
    carries its scope in.  An instruction the compiler made itself (a
    reduction it split, a copy it wrapped) has none and takes the stage
    most instructions of the computations it calls have."""
    own: Dict[str, Optional[str]] = {}
    called: Dict[str, List[str]] = {}
    members: Dict[str, List[str]] = {}
    computation = None
    for line in executable_text.splitlines():
        m = _COMPUTATION.match(line)
        if m is not None:
            computation = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = stage_of(op.group(1)) if op else None
        called[name] = _CALLED.findall(line)
        members.setdefault(computation, []).append(name)

    def resolve(name: str, depth: int = 0) -> Optional[str]:
        if own.get(name) not in (None, NO_SCOPE) or depth > 8:
            return own.get(name)
        votes: Dict[str, int] = {}
        for comp in called.get(name, ()):
            for member in members.get(comp, ()):
                stage = resolve(member, depth + 1)
                if stage not in (None, NO_SCOPE):
                    votes[stage] = votes.get(stage, 0) + 1
        if votes:
            return max(sorted(votes), key=votes.get)
        return own.get(name)

    out = {}
    for name in own:
        stage = resolve(name)
        if stage is not None:
            out[name] = stage
    return out


def _self_ns(events: list) -> list:
    """[(event, ns not covered by events nested in it)] of one line."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][1] + stack[-1][0][2] <= ev[1]:
            out.append(stack.pop())
        if stack:
            stack[-1][1] -= ev[2]
        stack.append([ev, ev[2]])
    out.extend(stack)
    return [(ev, max(ns, 0.0)) for ev, ns in out]


def stage_seconds(xplane_path: str,
                  executable: Optional[str] = None) -> Dict[str, float]:
    """Device seconds per stage of the fused program in one capture,
    per chip (the mean over the device planes that ran anything).

    A device operation is an event of a ``/device:`` plane's ``XLA Ops``
    line, named by its whole instruction (the TPU), or any event with an
    ``hlo_op`` stat (the CPU backend's thunk lines).  Neither backend
    records the instruction's ``op_name`` with the event (checked on
    the v5e and on the CPU, jax 0.9.0), so the stage is looked up by
    instruction name in ``executable``, the program's optimised HLO
    text; an operation not found there (or every one, without
    ``executable``) is booked under ``(no metadata)``, one whose
    op_name has no ``nns.`` scope under ``(no nns scope)``.  Nested
    events count once (self time), so the stages sum to the union of
    the operation intervals."""
    from jax.profiler import ProfileData

    by_name = stage_map(executable) if executable else {}
    per_plane = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith("/device:")
        totals: Dict[str, float] = {}
        for line in plane.lines:
            # of a device plane only the operations' own line: its
            # "Async XLA Ops" line holds copies that overlap them
            if device and not line.name.startswith("XLA Ops"):
                continue
            events = []
            for ev in line.events:
                if device:
                    instr = _EVENT_INSTR.match(ev.name).group(1)
                else:
                    instr = {k: v for k, v in ev.stats}.get("hlo_op")
                    if not instr:
                        continue
                events.append((by_name.get(str(instr), NO_METADATA),
                               float(ev.start_ns), float(ev.duration_ns)))
            for (stage, _s, _d), ns in _self_ns(events):
                totals[stage] = totals.get(stage, 0.0) + ns * 1e-9
        if totals:
            per_plane.append(totals)
    if not per_plane:
        return {}
    stages = sorted({k for t in per_plane for k in t})
    return {k: sum(t.get(k, 0.0) for t in per_plane) / len(per_plane)
            for k in stages}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="device seconds per stage of the fused program in a "
                    "jax.profiler capture")
    ap.add_argument("xplane", help="the capture's .xplane.pb")
    ap.add_argument("--hlo", help="optimised HLO text of the program "
                    "(JaxXlaFilter.executable_text()), for backends whose "
                    "device events carry no op_name")
    args = ap.parse_args(argv)
    text = None
    if args.hlo:
        with open(args.hlo) as f:
            text = f.read()
    stages = stage_seconds(args.xplane, text)
    total = sum(stages.values())
    for stage, seconds in sorted(stages.items(), key=lambda kv: -kv[1]):
        print(f"{seconds * 1e3:12.3f} ms {100 * seconds / total:6.2f} %  "
              f"{stage}")
    print(f"{total * 1e3:12.3f} ms 100.00 %  all device operations, per chip")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
