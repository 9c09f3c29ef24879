"""Shared-model serving runtime: cross-pipeline batch coalescing.

PR 2's :class:`~nnstreamer_tpu.runtime.batching.MicroBatcher` coalesces
the in-flight buffers of ONE ``tensor_filter``.  At serving scale that
is the wrong granularity: 100 concurrent pipelines running the same
jax-xla model mean 100 params copies in HBM, 100 per-bucket executable
caches, and 100 independent batch windows that each dispatch
nearly-empty buckets.  Continuous-batching servers (Orca, OSDI '22) and
prediction-serving systems that share one model replica across request
streams (Clipper, NSDI '17) coalesce at the MODEL, not the element.

This module lifts the window machinery to per-model:

- :class:`ModelPool` — a process-wide table of opened sub-plugin
  instances, ref-counted and keyed by ``(framework, model,
  accelerator/mesh config)``.  N filters with ``share-model=true``
  referencing the same model share ONE instance: one params copy, one
  per-bucket executable cache (``filters/jax_xla.py`` ``open_shared`` /
  ``close_shared`` back this at the framework level).
- :class:`PoolEntry` — one pooled model plus its cross-stream batcher
  and :class:`~nnstreamer_tpu.utils.stats.InvokeStats` (dispatches,
  frames, and *distinct streams per dispatch*).
- :class:`SharedBatcher` — a MicroBatcher over ``(stream, buffer)``
  pairs from MANY pipelines.  Per-stream FIFO order is preserved (one
  FIFO window, serialized flushes); results are demuxed back to each
  owning filter's downstream pad on that filter's flush context (a
  broken downstream in pipeline A errors on A's bus without killing
  B's demux); per-stream EOS flushes only that stream's parked frames;
  and the **adaptive window** flushes early whenever the device is idle
  instead of always waiting out the deadline — coalescing happens
  exactly while a dispatch is in flight, so an idle device never sits
  out a ``batch-timeout-ms``.

Frameworks without ``SUPPORTS_BATCH`` still share the instance (one
params copy); their streams fall back to per-frame dispatch through the
element's normal chain path — no frames are parked, none are lost.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..chaos import hooks as _chaos
from ..obs import hooks as _obs_hooks
from ..obs import tenantstat as _tenantstat
from ..obs import transfer as _xfer
from ..obs.tracer import TRACE_META_KEY
from ..utils import lockdep as _lockdep
from ..utils import profile as _profile
from ..utils.log import logw
from ..utils.stats import InvokeStats
from .admission import (
    INGRESS_TS_META,
    AdmissionController,
    StreamPolicy,
    _controller_armed,
    _controller_disarmed,
    parse_priority,
    priority_name,
)
from .batching import MicroBatcher, parse_buckets, pick_bucket
from .events import Message, MessageKind

#: sampling cadence of pool-level dispatch stats (same policy as
#: TensorFilter.STAT_SAMPLE_INTERVAL: at most one blocking sample per
#: interval, so stats never throttle the shared hot path)
POOL_STAT_SAMPLE_INTERVAL = 1.0


def block_all(outs) -> None:
    """Block until every array in ``outs`` finished executing on the
    device (arrays without ``block_until_ready`` pass through)."""
    for o in outs:
        if hasattr(o, "block_until_ready"):
            o.block_until_ready()


class PoolConflictError(ValueError):
    """Sharers of one pool entry disagree on pool-level settings
    (``batch`` / ``batch-timeout-ms`` / ``batch-buckets`` are properties
    of the SHARED window, not of one element)."""


class SharedBatcher(MicroBatcher):
    """Deadline + max-batch coalescer over ``(stream, item, deadline,
    enqueue-ts)`` tuples.

    Inherits the MicroBatcher contract — serialized FIFO flushes,
    full/deadline/forced window closes — and adds per-stream draining:
    :meth:`flush_stream` dispatches windows from the head of the FIFO
    until none of one stream's frames are parked, leaving frames other
    streams parked *after* that point untouched.  Runs with the adaptive
    window on by default (idle device ⇒ flush now; busy device ⇒ keep
    coalescing until full/deadline).

    With :attr:`edf` armed (the pool's admission controller is on),
    window formation turns earliest-deadline-first: the dispatched
    window carries the frames whose deadlines expire soonest rather
    than the oldest arrivals, so a latency-critical stream never waits
    behind a bulk stream's backlog.  The selection sort is stable and
    per-stream deadlines are monotonic, so per-stream FIFO order is
    preserved.
    """

    def __init__(self, max_batch: int, timeout_s: float,
                 flush_fn: Callable[[List[Any]], None],
                 error_fn: Optional[Callable[[BaseException], None]] = None,
                 adaptive: bool = True, name: str = ""):
        super().__init__(max_batch, timeout_s, flush_fn, error_fn,
                         adaptive=adaptive, name=name)
        self.edf = False  # armed by PoolEntry when admission is on

    def submit_from(self, stream: Any, item: Any,
                    deadline_s: float = 0.0,
                    enq: Optional[float] = None) -> None:
        """Enqueue one frame of ``stream``; dispatches inline when the
        cross-stream window fills.  ``deadline_s`` (relative, 0 = none)
        drives EDF formation when armed; ``enq`` (the admission entry
        time — BEFORE any backpressure wait) anchors the latency signal
        and the deadline."""
        if enq is None:
            enq = time.monotonic()
        dl = enq + deadline_s if deadline_s > 0 else float("inf")
        self.submit((stream, item, dl, enq))

    def pending_of(self, stream: Any) -> int:
        with self._cv:
            return sum(1 for it in self._pending if it[0] is stream)

    def wait_below(self, stream: Any, limit: int,
                   timeout_s: float) -> bool:
        """Block (backpressure) until ``stream`` parks fewer than
        ``limit`` frames.  False when the window never drained within
        ``timeout_s`` — a wedged device must not wedge the producer
        forever; the caller sheds visibly instead."""
        if limit <= 0:
            return True
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while sum(1 for it in self._pending
                      if it[0] is stream) >= limit:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    return False
                self._cv.wait(min(remain, 0.05))
        return True

    def _take_batch_locked(self) -> List[Any]:
        if not self.edf or len(self._pending) <= self.max_batch:
            return super()._take_batch_locked()
        # earliest-deadline-first: pick (and order) the window by
        # (deadline, arrival index) — stable, so per-stream FIFO holds;
        # the un-picked remainder keeps its arrival order
        sel = sorted(range(len(self._pending)),
                     key=lambda i: (self._pending[i][2], i)
                     )[:self.max_batch]
        batch = [self._pending[i] for i in sel]
        chosen = set(sel)
        self._pending = [it for i, it in enumerate(self._pending)
                         if i not in chosen]
        return batch

    def flush_stream(self, stream: Any) -> None:
        """Drain windows (FIFO from the head) until no frame of
        ``stream`` is parked — the per-stream EOS/stop path.  Frames of
        other streams that arrived before this stream's last frame ride
        along (order is preserved); frames parked after it stay for
        their own window.  Returns only after any in-flight window that
        may carry this stream's frames completed."""
        while True:
            with self._cv:
                mine = any(it[0] is stream for it in self._pending)
            if not mine:
                break
            if self._drain() == 0:
                break
            self.flushes_forced += 1
        with self._flush_serial_lock:
            pass  # barrier: flushes are FIFO-serialized, so once this
            # lock is free every window taken before now has demuxed


class PoolEntry:
    """One pooled model: the shared sub-plugin instance, the attached
    streams, the cross-stream batcher, and pool-level stats."""

    def __init__(self, pool: "ModelPool", key: Tuple,
                 subplugin: Any, close_fn: Callable[[Any], None]):
        self.pool = pool
        self.key = key
        self.subplugin = subplugin
        self._close_fn = close_fn
        self.refcount = 0  # managed by ModelPool under the pool lock
        self.stats = InvokeStats()
        self._lock = threading.Lock()
        self._streams: Dict[int, Any] = {}  # id(owner) -> owner element
        # the name the pool's spans carry (utils/profile.py), also on
        # the shared instance's own (place, dispatch, compile)
        self.trace_owner = self.label()
        if hasattr(subplugin, "trace_owner"):
            subplugin.trace_owner = self.trace_owner
        self.batcher: Optional[SharedBatcher] = None
        self.buckets: Tuple[int, ...] = (1,)
        self._batch_cfg: Optional[Tuple] = None
        # SLO-aware admission (runtime/admission.py): armed when any
        # sharer sets slo-ms > 0 (pool-level, conflict-checked like the
        # batch settings); per-stream policies keyed like _streams
        self.admission: Optional[AdmissionController] = None
        self._policies: Dict[int, StreamPolicy] = {}
        # id(owner) -> tenant, read lock-free on the dispatch path
        # (same discipline as the unlocked self.admission read there:
        # plain dict lookups, rebuilt only under self._lock)
        self._tenants: Dict[int, str] = {}
        self._shed_warn_ts: Dict[int, float] = {}
        # dispatch sampling state (serialized by the batcher flush lock)
        self._seq = 0
        self._last_sample_ts = 0.0
        self._last_out: Any = None
        # sampling cadence: the pool default, tightened by any attached
        # filter's stat-sample-interval-ms (the pool keeps the minimum
        # so the most latency-curious sharer wins)
        self.sample_interval = POOL_STAT_SAMPLE_INTERVAL
        # actuator set (runtime/actuators.py), built lazily and kept
        # for the entry's lifetime: cooldown state must survive
        # rebuilds, and the closures read batcher/admission through
        # self so a torn-down window fails the actuation cleanly
        # instead of steering a dead object
        self._actuators: Dict[str, Any] = {}
        # model lifecycle (runtime/lifecycle.py): version registry +
        # hot-swap/canary state machine, built on first use (a pool
        # that never swaps pays nothing on the dispatch path)
        self._lifecycle = None

    # -- streams -------------------------------------------------------------

    @property
    def attached_streams(self) -> int:
        with self._lock:
            return len(self._streams)

    @property
    def placement(self):
        """The resolved placement (``parallel.ResolvedPlacement``) the
        pooled sub-plugin compiled over; None on a single-device pool.
        THE join point between the serving pool and the mesh: the
        window divisibility rule, the shard count the obs layer
        attributes against, and the multi-process fan-out all read
        from here."""
        return getattr(self.subplugin, "_placement", None)

    def label(self) -> str:
        """Stable short pool label (``framework:model-tail``) — the
        ``pool=`` value on every metric this entry exports."""
        from ..obs.metrics import pool_label

        return pool_label(self)

    # -- model lifecycle (runtime/lifecycle.py) -------------------------------

    @property
    def lifecycle(self):
        """The entry's version registry / hot-swap state machine,
        built on first use — a pool that never swaps or canaries pays
        nothing for it on the dispatch path."""
        with self._lock:
            if self._lifecycle is None:
                from .lifecycle import VersionManager

                self._lifecycle = VersionManager(self)
            return self._lifecycle

    def subplugin_for(self, owner: Any) -> Any:
        """The instance serving ``owner``'s per-frame dispatches: the
        canary shadow for canary-routed streams, the shared instance
        otherwise (the batched path partitions whole windows instead —
        see ``_dispatch_inner``)."""
        lc = self._lifecycle
        if lc is not None and lc.canary_active:
            return lc.subplugin_for(owner)
        return self.subplugin

    def reload_model(self, model: Any, version: str = "") -> dict:
        """RELOAD_MODEL for a share-model pool: stage the replacement
        OFF the dispatch path (load + compile + warm while the old
        executable serves), then either start the declared canary
        split (pool-level ``canary=``) or hot-swap at the next window
        boundary.  This is what lifts PR 3's share-model refusal of
        ``is-updatable``: the reload steers the POOL, never one
        sharer's private instance."""
        lc = self.lifecycle
        ver = lc.stage(model, version=version)
        tag, n = lc.default_canary
        # the declared tag GATES the split: `canary=next:1/N` canaries
        # whatever gets staged; a concrete tag (`canary=v7:1/N`)
        # canaries only that version — anything else cuts over
        # directly, as an undeclared version would
        if n >= 2 and (tag in ("", "next") or ver.tag == tag):
            return lc.start_canary(n, ver)
        return lc.swap(ver)

    def _serve_hist(self):
        """The registry's per-pool serve-latency histogram the admission
        controller feeds AND reads its p99 from — the exported signal
        and the shed signal are one and the same."""
        from ..obs.metrics import admission_latency_hist

        return admission_latency_hist(self.label())

    def attach(self, owner: Any, batch: int, timeout_ms: float,
               buckets_spec: str, slo_ms: float = 0.0,
               priority: Any = "normal", deadline_ms: float = 0.0,
               queue_limit: int = 0, canary: str = "",
               tenant: str = "") -> bool:
        """Register ``owner`` as a live stream of this entry.  The first
        attach fixes the pool-level window settings (``batch*``,
        ``slo-ms`` and the ``canary=`` routing declaration); later
        attaches with different settings raise
        :class:`PoolConflictError`.  ``priority`` / ``deadline-ms`` /
        ``queue-limit`` / ``tenant`` are PER-STREAM
        (runtime/admission.py; the tenant names who this stream's
        frames are attributed to — obs/tenantstat.py).  Returns
        True when the owner must submit through the shared batcher,
        False for shared-instance/per-frame dispatch (``batch<=1`` or a
        framework without ``SUPPORTS_BATCH``)."""
        from .lifecycle import parse_canary

        batch = int(batch or 1)
        batched = batch > 1 and bool(
            getattr(self.subplugin, "SUPPORTS_BATCH", False))
        slo_ms = float(slo_ms or 0.0)
        canary = str(canary or "").strip()
        canary_cfg = parse_canary(canary)  # validates the grammar
        cfg = (batch, float(timeout_ms), str(buckets_spec or "").strip(),
               slo_ms, canary)
        prio = parse_priority(priority)
        policy = StreamPolicy(
            tenant=str(tenant or "").strip() or _tenantstat.DEFAULT_TENANT,
            priority=prio,
            # EDF deadline: explicit per-stream deadline, else the pool
            # SLO (a frame older than the SLO is the one to save first)
            deadline_s=(float(deadline_ms) if float(deadline_ms or 0.0) > 0
                        else slo_ms) / 1e3,
            # bounded per-stream queue: explicit, else 16 windows'
            # worth — deep enough that overload backlog lives INSIDE
            # the window (where the latency signal sees it), still a
            # hard bound backpressure enforces
            queue_limit=int(queue_limit) if int(queue_limit or 0) > 0
            else (16 * batch if slo_ms > 0 else 0))
        owner_ms = getattr(owner, "stat_sample_interval_ms", None)
        mn = getattr(self.subplugin, "model_name", None)
        if callable(mn):
            # obs join key: the pool's nns_invoke_device_seconds series
            # measures executables of this model (obs/xlacost.py)
            from ..obs import xlacost as _xlacost

            _xlacost.map_source(self.label(), mn())
        start = None
        with self._lock:
            if owner_ms is not None:
                self.sample_interval = min(self.sample_interval,
                                           float(owner_ms) / 1e3)
            if self._streams and self._batch_cfg is not None \
                    and cfg != self._batch_cfg:
                raise PoolConflictError(
                    f"{getattr(owner, 'name', owner)}: batch settings "
                    f"{cfg} conflict with the pool's {self._batch_cfg} — "
                    f"batch/batch-timeout-ms/batch-buckets/slo-ms are "
                    f"pool-level for share-model filters and must agree "
                    f"across all {len(self._streams)} sharer(s)")
            self._streams[id(owner)] = owner
            self._policies[id(owner)] = policy
            self._tenants[id(owner)] = policy.tenant
            self._batch_cfg = cfg
            if slo_ms > 0 and self.admission is None:
                self.admission = AdmissionController(
                    slo_ms / 1e3, hist=self._serve_hist())
                _controller_armed()  # sources start stamping ingress
            if batched and self.batcher is None:
                self.buckets = parse_buckets(cfg[2], batch)
                self.batcher = SharedBatcher(
                    max_batch=batch, timeout_s=cfg[1] / 1e3,
                    flush_fn=self._dispatch, error_fn=self._error_all,
                    name=f"pool:{self.key[0]}")
                self.batcher.edf = slo_ms > 0
                start = self.batcher
            n = len(self._streams)
        self.stats.attached_streams = n
        if canary_cfg[1] >= 2:
            # the pool declares canary routing: reloads stage + canary
            # at this split instead of cutting the whole pool over
            self.lifecycle.default_canary = canary_cfg
        lc = self._lifecycle
        if lc is not None:
            lc.on_attach(owner)
        if start is not None:
            start.start()
        return batched

    def detach(self, owner: Any) -> None:
        """Unregister one stream: flush ITS parked frames first (no
        frame loss on a mid-stream stop), then — if it was the last
        stream out — drain and tear the batcher down so a later
        attach can bring new window settings."""
        with self._lock:
            present = self._streams.pop(id(owner), None) is not None
            self._policies.pop(id(owner), None)
            self._tenants.pop(id(owner), None)
            self._shed_warn_ts.pop(id(owner), None)
            batcher = self.batcher
            n = len(self._streams)
            last = not self._streams
            if last:
                self.batcher = None
                self._batch_cfg = None
                if self.admission is not None:
                    self.admission = None
                    _controller_disarmed()
        self.stats.attached_streams = n
        lc = self._lifecycle
        if lc is not None:
            lc.on_detach(owner)
        if batcher is None:
            return
        if present and not last:
            batcher.flush_stream(owner)
        elif last:
            batcher.flush()  # nothing can be parked but a survivor's
            # tail; drain everything before the timer dies
            batcher.stop()

    def flush_stream(self, owner: Any) -> None:
        """Per-stream EOS: dispatch this stream's parked frames (other
        streams' windows are untouched past that point)."""
        with self._lock:
            batcher = self.batcher
        if batcher is not None:
            batcher.flush_stream(owner)

    def submit(self, owner: Any, buf: Any) -> None:
        with self._lock:
            batcher = self.batcher
            adm = self.admission
            pol = self._policies.get(id(owner))
        if batcher is None:
            raise RuntimeError(
                f"{getattr(owner, 'name', owner)}: stream is not "
                f"attached to a shared batcher (start() not run?)")
        # deadline/latency anchor: the buffer's pipeline-INGRESS stamp
        # when present (a full window dispatches inline on the producer
        # thread, so overload backlog queues UPSTREAM of this call —
        # only the ingress anchor lets the controller see that wait),
        # else now (covers un-stamped buffers, e.g. pushed before the
        # controller armed)
        enq = time.monotonic()
        if adm is not None and pol is not None:
            t_in = buf.meta.get(INGRESS_TS_META)
            if t_in is not None:
                enq = t_in
            if not adm.admit(pol.priority):
                # p99 over SLO and this stream is sheddable: dropped at
                # the cheapest point — before any queueing — and LOUDLY
                # (counter + rate-limited bus warning)
                _tenantstat.record_shed(self.label(), pol.tenant, "slo")
                self._warn_shed(owner, pol, adm, reason="slo")
                return
            if pol.queue_limit > 0 and not batcher.wait_below(
                    owner, pol.queue_limit,
                    timeout_s=max(1.0, 8 * batcher.timeout_s)):
                # bounded queue never drained (wedged device): shed
                # rather than wedge the producer thread forever
                adm.count_queue_full(pol.priority)
                _tenantstat.record_shed(self.label(), pol.tenant,
                                        "queue-full")
                self._warn_shed(owner, pol, adm, reason="queue-full")
                return
        batcher.submit_from(owner, buf,
                            deadline_s=pol.deadline_s if pol else 0.0,
                            enq=enq)

    def _warn_shed(self, owner: Any, pol: StreamPolicy,
                   adm: AdmissionController, reason: str) -> None:
        """Every shed is counted; the bus warning is rate-limited to
        one per stream per second (it carries the cumulative count, so
        nothing is lost — the bus just isn't flooded under overload)."""
        now = time.monotonic()
        with self._lock:
            last = self._shed_warn_ts.get(id(owner), 0.0)
            if now - last < 1.0:
                return
            self._shed_warn_ts[id(owner)] = now
        total = adm.total_shed
        owner.post_message(Message(
            MessageKind.WARNING, getattr(owner, "name", str(owner)),
            data={"shed": True, "reason": reason,
                  "priority": priority_name(pol.priority),
                  "pool": f"{self.key[0]}", "total_shed": total}))
        logw("%s: load-shedding %s-priority frames (%s; %d shed so far "
             "on this pool)", getattr(owner, "name", owner),
             priority_name(pol.priority), reason, total)
        # black box: every (rate-limited) shed episode is recorded; the
        # shed ramp saturating at 1.0 is the HARD-shed threshold that
        # triggers a flight-recorder dump (obs/flightrec.py)
        from ..obs.flightrec import FLIGHT

        FLIGHT.shed(self.label(), priority_name(pol.priority), reason,
                    total, hard=adm.shed_probability >= 1.0)

    # -- the actuator API (runtime/actuators.py) ------------------------------

    def _live_batcher(self) -> SharedBatcher:
        from .actuators import ActuationError

        b = self.batcher
        if b is None:
            raise ActuationError(
                f"{self.label()}: no live cross-stream window "
                f"(no batched stream attached, or the pool is "
                f"tearing down)")
        return b

    def _live_admission(self) -> Any:
        from .actuators import ActuationError

        adm = self.admission
        if adm is None:
            raise ActuationError(
                f"{self.label()}: no admission controller armed "
                f"(no sharer set slo-ms)")
        return adm

    def actuators(self) -> Dict[str, Any]:
        """The pool's named, bounded, reversible knobs: window
        deadline, window size, coalescing pause, admission shed ramp,
        per-stream queue limits.  Built once per entry (cooldown and
        revert state persist); every knob reads its target through the
        entry, so an actuation racing ``Pipeline.stop()`` raises a
        clean ``ActuationError`` instead of steering a dead window."""
        with self._lock:
            acts = self._actuators
        if acts:
            # the window bound follows the live bucket set (a pool
            # re-attached with new settings keeps its knobs' cooldown/
            # revert state but must clamp against the NEW ceiling)
            acts["max-batch"].hi = float(self.buckets[-1])
            return acts
        from .actuators import Actuator

        label = self.label()

        def _set_window_ms(v: float) -> None:
            b = self._live_batcher()
            b.timeout_s = v / 1e3
            b.settle_s = min(b.settle_s, b.timeout_s)

        def _window_cfg():
            # snapshot BOTH knobs the setter touches: settle_s only
            # ever shrinks under _set_window_ms, so a scalar prior
            # could not restore it and "revert restores the exact
            # prior config" would silently lie
            b = self._live_batcher()
            return (b.timeout_s, b.settle_s)

        def _restore_window(prior) -> None:
            b = self._live_batcher()
            b.timeout_s, b.settle_s = prior

        def _set_max_batch(v: float) -> None:
            self._live_batcher().max_batch = int(round(v))

        def _set_coalescing(v: float) -> None:
            b = self._live_batcher()
            if v >= 0.5:
                b.resume()
            else:
                b.pause()

        def _queue_limits() -> Dict[int, int]:
            with self._lock:
                return {sid: pol.queue_limit
                        for sid, pol in self._policies.items()}

        def _set_queue_limit(v: float) -> None:
            self._live_admission()  # queue limits are an admission knob
            with self._lock:
                for pol in self._policies.values():
                    pol.queue_limit = int(round(v))

        def _restore_queue_limits(prior: Dict[int, int]) -> None:
            # exact per-stream restore; streams that detached since the
            # snapshot are simply gone (their policy died with them)
            with self._lock:
                for sid, pol in self._policies.items():
                    if sid in prior:
                        pol.queue_limit = prior[sid]

        # max-batch upper bound: the LARGEST configured bucket — every
        # window size up to it pads onto an already-compiled
        # executable; growing past it would demand a recompile the
        # guard exists to forbid
        built = {
            "window-ms": Actuator(
                "window-ms", "pool", label,
                get_fn=lambda: self._live_batcher().timeout_s * 1e3,
                set_fn=_set_window_ms, lo=0.1, hi=1000.0, unit="ms",
                snapshot_fn=_window_cfg, restore_fn=_restore_window),
            "max-batch": Actuator(
                "max-batch", "pool", label,
                get_fn=lambda: float(self._live_batcher().max_batch),
                set_fn=_set_max_batch, lo=1.0,
                hi=float(self.buckets[-1]), unit="frames"),
            "coalescing": Actuator(
                "coalescing", "pool", label,
                get_fn=lambda: 0.0 if self._live_batcher().paused
                else 1.0,
                set_fn=_set_coalescing, lo=0.0, hi=1.0, unit="on"),
            "ramp-start": Actuator(
                "ramp-start", "pool", label,
                get_fn=lambda: self._live_admission().ramp_start,
                set_fn=lambda v: self._live_admission()
                .set_ramp_start(v),
                lo=0.3, hi=0.99, unit="xSLO"),
            "queue-limit": Actuator(
                "queue-limit", "pool", label,
                get_fn=lambda: float(max(
                    _queue_limits().values(), default=0)),
                set_fn=_set_queue_limit, lo=1.0, hi=65536.0,
                unit="frames", snapshot_fn=_queue_limits,
                restore_fn=_restore_queue_limits),
        }
        with self._lock:
            # two concurrent first builds must converge on ONE set —
            # split sets would split the cooldown/revert state the
            # module promises to persist
            if not self._actuators:
                self._actuators = built
            return self._actuators

    # -- the cross-stream dispatch -------------------------------------------

    def _dispatch(self, items: List[Tuple[Any, Any, float, float]]
                  ) -> None:
        """Window flush: ONE invoke for frames from every attached
        stream, then demux each result back to its owner's downstream
        pad.  Serialized by the batcher (never concurrent); items are
        ``(owner, buf, deadline, enqueue-ts)`` in window order (arrival
        order, or EDF order under admission control)."""
        # lockdep fence: a window flush is a device-dispatch point — a
        # thread that reaches it holding any witnessed lock stalls
        # every pooled stream for the invoke (utils/lockdep.py)
        if _lockdep.ENABLED:
            _lockdep.check_dispatch(f"pool:{self.label()}")
        # transfer-label context: the pool dispatch runs on whichever
        # producer/timer thread closed the window — its crossings
        # (batched feeds, pads, drains) belong to the POOL, not to the
        # thread's own element
        xctx = None
        pushed = _xfer.ACTIVE
        if pushed:
            traces = tuple(
                tr for tr in (buf.meta.get(TRACE_META_KEY)
                              for _o, buf, _dl, _enq in items)
                if tr is not None) or None
            xctx = _xfer.push_context("", self.label(), traces)
        try:
            self._dispatch_inner(items)
        finally:
            if pushed:
                _xfer.pop_context(xctx)

    def _dispatch_inner(self, items: List[Tuple[Any, Any, float, float]]
                        ) -> None:
        if _obs_hooks.DISABLED:
            # NNS_TPU_OBS_DISABLE: fully async pool dispatch — no
            # seq/interval bookkeeping, no backlog drain, no _last_out
            # retention (mirrors TensorFilter._sample_gate)
            sample = False
        else:
            self._seq += 1
            now = time.monotonic()
            sample = (self._seq == 1 or
                      now - self._last_sample_ts >= self.sample_interval)
            if sample and self._last_out is not None:
                # drain the async backlog first, so t0→done times ONE
                # window
                with _profile.span(self.trace_owner, "sample_fence"):
                    block_all([self._last_out])
        lc = self._lifecycle
        if lc is not None and lc.canary_active:
            # canary split: the window partitions by the owners'
            # version assignment (every stream maps to exactly ONE
            # version, so per-stream FIFO survives the split) and each
            # part dispatches through its version's own executable —
            # a failing canary errors only its own streams' buses and
            # only its version's error counter
            for ver, sp, part in lc.partition(items):
                self._dispatch_group(part, sp, ver, sample)
            return
        self._dispatch_group(items, self.subplugin, None, sample)

    def _dispatch_group(self, items: List[Tuple[Any, Any, float, float]],
                        sp: Any, version: Any, sample: bool) -> None:
        """Dispatch one version-homogeneous group of window items
        through ``sp`` (the shared instance, or a canary shadow) —
        invoke, per-owner demux, stats, cost attribution.  ``version``
        (a ``lifecycle.ModelVersion``) collects per-version stats and
        errors when the window was split."""
        owners: Dict[int, List[Any]] = {}
        for owner, _buf, _dl, _enq in items:
            owners.setdefault(id(owner), [owner, 0])[1] += 1
        t0 = time.monotonic()
        bucket = len(items)
        # the pool's spans, where LatencyTracer marks park / dispatch /
        # demux: <pool>/window (frame prep, stack and pad: its self
        # time) around the shared instance's own <pool>/dispatch, then
        # <pool>/demux
        name = self.trace_owner
        window = getattr(self.batcher, "window_seq", None)
        try:
            ch = _chaos.plan
            if ch is not None:
                # model-path fault seam: slow-invoke sleeps here (the
                # whole window pays, like a real device stall);
                # fail-invoke raises into the guard below, exercising
                # the every-owner error fan-out
                from ..chaos.plan import apply_invoke_fault

                apply_invoke_fault(ch, f"pool:{self.key[0]}:{self.key[1]}")
            # frame prep inside the guard: items already left the
            # pending queue, so ANY failure from here on loses the
            # window and must surface on every owner's bus
            with _profile.span(name, "window", window):
                frames = [owner._pool_frame_inputs(buf)
                          for owner, buf, _dl, _enq in items]
                t1 = time.monotonic()  # host-prep done, device phase
                if getattr(sp, "SUPPORTS_BATCH", False):
                    bucket = pick_bucket(len(frames), self.buckets)
                    outs = sp.invoke_batched(frames, bucket)
                else:
                    # shared instance without a batched entry point:
                    # the window still coalesces (ordering, EOS
                    # semantics) but each frame dispatches separately
                    outs = [sp.invoke(list(f)) for f in frames]
        except Exception as e:  # noqa: BLE001 - a failed shared window
            # affects EVERY stream that parked a frame in it: the error
            # must land on each owner's bus, not only on whichever
            # producer happened to trigger the flush.  A split window
            # scopes that blast radius to THIS version's streams.
            if version is not None:
                # direct attribute read: version non-None implies the
                # manager exists, and the lifecycle PROPERTY takes the
                # entry lock — needless contention on the hot path
                self._lifecycle.record_error(version)
            for owner, _n in owners.values():
                owner.post_error(e)
            return
        if getattr(sp, "SUPPORTS_BATCH", False) and \
                getattr(sp, "_donate", False):
            # donation bookkeeping, mirroring the element paths
            # (elements/filter.py): the batched executable consumed the
            # device-resident inputs it was fed — mark exactly the
            # input-combination subset each owner dispatched, so a
            # retained reference raises DonatedTensorError instead of
            # reading reused HBM
            for owner, buf, _dl, _enq in items:
                ts = buf.tensors
                combi = getattr(owner, "_in_combi", None)
                if combi is not None:
                    ts = [ts[i] for i in combi]
                for t in ts:
                    t.mark_donated()
        flat = [o for out in outs for o in out]
        if sample:
            with _profile.span(name, "sample_fence", window):
                block_all(flat)
            t2 = time.monotonic()
            self.stats.record(t2 - t0, frames=len(items),
                              streams=len(owners))
            self._last_sample_ts = t2
        else:
            t2 = time.monotonic()
            self.stats.count(frames=len(items), streams=len(owners))
        if version is not None:
            # per-version serving stats: the canary-vs-baseline
            # comparator series (nns_model_canary/baseline_latency_us);
            # attribute read, not the lock-taking property (hot path)
            self._lifecycle.record(
                version, (t2 - t0) if sample else None,
                frames=len(items), streams=len(owners))
        self._last_out = (flat[-1] if flat else None) \
            if not _obs_hooks.DISABLED else None
        for owner, n in owners.values():
            owner.invoke_stats.count(frames=n)
        if sample:
            tracer = _obs_hooks.tracer
            if tracer is not None:
                # marks BEFORE the demux (sinks reached inline finalize
                # the trace records); each buffer's demux mark closes
                # its own drain span
                tracer.invoke_split(
                    [(getattr(owner, "name", str(owner)), buf)
                     for owner, buf, _dl, _enq in items], t0, t1, t2)
        adm = self.admission
        done = time.monotonic()
        tstats = _tenantstat.ACTIVE
        label = self.label() if (tstats or sample) else ""
        tenants = self._tenants
        with _profile.span(name, "demux", window):
            for (owner, buf, _dl, enq), out in zip(items, outs):
                if adm is not None:
                    # the admission controller's latency signal: window
                    # park → results demuxed (sampled windows blocked on
                    # the device above, so they include execution time;
                    # under overload the queueing term dominates either
                    # way — that's the term admission must react to)
                    lat = done - enq
                    adm.observe(lat)
                    if tstats:
                        # per-tenant SLO attainment, graded on the SAME
                        # per-frame latency the shed decision reads
                        _tenantstat.record_latency(
                            label, tenants.get(id(owner), "default"),
                            lat, adm.slo_s)
                try:
                    # the owner's flush context: push through ITS pads, so
                    # a broken downstream errors on ITS bus only
                    owner._pool_emit(buf, out)
                except Exception as e:  # noqa: BLE001 - keep demuxing the
                    # other streams' frames of this window
                    owner.post_error(e)
        if sample:
            # cost attribution: host-prep (t0→t1) / device (t1→t2) /
            # host-drain (t2→now: unbatch + per-owner demux) into the
            # pool stats and the registry's nns_invoke_* histograms
            from ..obs.metrics import observe_invoke_phases

            t3 = time.monotonic()
            self.stats.record_phases(t1 - t0, t2 - t1, t3 - t2)
            observe_invoke_phases("pool", label, bucket,
                                  t1 - t0, t2 - t1, t3 - t2)
        if tstats:
            # tenant attribution: split this window's device phase by
            # useful-frame occupancy, from the SAME t1/t2 clock reads
            # the histogram above observed — unsampled dispatches
            # count frames only (they take no honest device timing,
            # exactly like the histogram)
            tenant_frames: Dict[str, int] = {}
            for owner, n in owners.values():
                t = tenants.get(id(owner), "default")
                tenant_frames[t] = tenant_frames.get(t, 0) + n
            _tenantstat.record_window(
                label, tenant_frames,
                round((t2 - t1) * 1e9) if sample else None)

    def _error_all(self, err: BaseException) -> None:
        with self._lock:
            owners = list(self._streams.values())
        for o in owners:  # post outside the lock: bus handlers reenter
            o.post_error(err)

    # -- teardown (pool-internal) --------------------------------------------

    def _close(self) -> None:
        batcher, self.batcher = self.batcher, None
        if self.admission is not None:
            # pool torn down without a last detach (e.g. test clear())
            self.admission = None
            _controller_disarmed()
        if batcher is not None:
            batcher.flush()
            batcher.stop()
        self._close_fn(self.subplugin)


class ModelPool:
    """Process-wide ref-counted table of opened sub-plugin instances.

    ``acquire`` returns the existing entry for a key (refcount+1) or
    opens a new one via ``open_fn``; ``release`` closes the instance
    when the last reference drops.  Keys must carry everything that
    makes two opens non-interchangeable — the helper :func:`pool_key`
    builds them from FilterProps.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple, PoolEntry] = {}

    def acquire(self, key: Tuple, open_fn: Callable[[], Any],
                close_fn: Callable[[Any], None]) -> PoolEntry:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                # same model, DIFFERENT placement: opening a second
                # pool would silently defeat the sharing the filter
                # asked for (two params copies, two windows) — surface
                # it as the pool-level conflict it is.  Equivalent
                # placement spellings never get here: they resolve to
                # one canonical key and join the existing entry.
                base = _key_base(key)
                for other in self._entries.values():
                    pk, ok = _key_placement(key), \
                        _key_placement(other.key)
                    kinds = {pk[0] if isinstance(pk, tuple) and pk
                             else "?",
                             ok[0] if isinstance(ok, tuple) and ok
                             else "?"}
                    if "raw" in kinds or "mesh" not in kinds:
                        # the conflict is about MESH placements: two
                        # resolved meshes of one model, or a meshed
                        # and an unmeshed sharer, cannot share one
                        # pool's story.  A "raw" key is an
                        # unresolvable spec whose own configure error
                        # must surface, and two "device"
                        # (null-placement) keys differ legitimately —
                        # accelerator auto vs explicit simply opens
                        # separate single-device pools, as it always
                        # did.
                        continue
                    if len(other.key) == len(key) \
                            and _key_base(other.key) == base \
                            and ok != pk:
                        if _disjoint_mesh_subsets(pk, ok):
                            # pipeline-split serving: the SAME model
                            # deliberately staged more than once over
                            # DISJOINT device subsets (``devices=0-3``
                            # and ``devices=4-7``) is not a sharing
                            # mistake — each stage gets its own pool,
                            # window and params copy on its own chips,
                            # and frames move between them over the
                            # device channel.  Only overlapping or
                            # whole-inventory re-placements stay a
                            # conflict.
                            continue
                        raise PoolConflictError(
                            f"share-model filters disagree on placement "
                            f"for {key[0]}:{key[1]}: this open resolves "
                            f"to {_key_placement(key)!r} but a live pool "
                            f"of the same model runs "
                            f"{_key_placement(other.key)!r} — placement "
                            f"(mesh/sharding/devices/accelerator) is "
                            f"pool-level for sharing filters; align the "
                            f"properties, or stop the other sharers "
                            f"before re-placing the model")
                entry = PoolEntry(self, key, open_fn(), close_fn)
                self._entries[key] = entry
            entry.refcount += 1
            return entry

    def release(self, entry: PoolEntry) -> None:
        close = False
        with self._lock:
            entry.refcount -= 1
            if entry.refcount <= 0:
                self._entries.pop(entry.key, None)
                close = True
        if close:
            entry._close()

    def get(self, key: Tuple) -> Optional[PoolEntry]:
        with self._lock:
            return self._entries.get(key)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        """Drop every entry regardless of refcount (test teardown)."""
        with self._lock:
            entries = list(self._entries.values())
            self._entries.clear()
        for e in entries:
            e._close()


def pool_key(framework: str, props: Any) -> Tuple:
    """Build the ModelPool key from a framework name + FilterProps:
    everything that makes two opens non-interchangeable (model identity,
    placement, custom options, forced I/O specs).  Non-string models
    (callables, ModelDef, lists) key by object identity — two filters
    share only when handed the very same object.

    The placement component is the CANONICAL resolved key from
    ``parallel.Placement`` — equivalent spellings (``mesh=data:-1`` vs
    ``mesh=data:8`` on an 8-device host, ``sharding=dp`` vs
    ``sharding=replicated``, ``accelerator=cpu`` vs ``true:cpu``) join
    ONE pool instead of silently opening two and defeating sharing."""
    from ..parallel import Placement

    model = props.model
    if isinstance(model, (list, tuple)):
        mkey = tuple(m if isinstance(m, str) else f"obj:{id(m)}"
                     for m in model)
    elif isinstance(model, str):
        mkey = model
    else:
        mkey = f"obj:{id(model)}"
    return (str(framework), mkey,
            Placement.from_props(props).key(),
            str(props.custom or ""),
            str(props.input_spec or ""), str(props.output_spec or ""),
            str(props.shared_key or ""))


def _disjoint_mesh_subsets(a, b) -> bool:
    """Two canonical mesh keys (``("mesh", platform, axes, device-ids,
    rules)``) name non-overlapping device subsets — the legitimate
    coexistence case pipeline-split serving runs on.  False for any
    shared chip (or malformed keys), which keeps the conflict error."""
    try:
        ida, idb = set(a[3]), set(b[3])
    except Exception:  # noqa: BLE001 - malformed/foreign key: conflict
        return False
    return bool(ida) and bool(idb) and not (ida & idb)


def _key_placement(key: Tuple):
    """The placement component of a :func:`pool_key` tuple."""
    return key[2] if len(key) > 2 else None


def _key_base(key: Tuple) -> Tuple:
    """A :func:`pool_key` tuple with the placement removed — the model
    identity two conflicting placements collide on."""
    return key[:2] + key[3:]


#: the process-wide pool `tensor_filter share-model=true` attaches to
MODEL_POOL = ModelPool()
