"""Host↔device transfer ledger — byte-exact data-movement accounting.

PR 7 attributed dispatch *time* (host-prep / device / host-drain) but
not *movement*: nothing could say where bytes cross the host/device
boundary or how many crossings a frame pays.  This module is the
measurement the device-resident dataflow is judged against.

Every host→device and device→host crossing at the jax seams records
into the process-wide :data:`LEDGER`:

- ``Tensor.jax()`` uploads and ``Tensor.np()`` drains (core/buffer.py)
  — the residency conversions the pipeline hot path actually performs;
- explicit ``device_put`` placement of inputs (filters/jax_xla.py
  ``invoke``/``invoke_batched``) and of weights (``ModelDef.placed``,
  for a device and for a mesh alike: once per device or per (mesh,
  rules), whoever opens first);
- micro-batch window feeds: host arrays handed to the batched
  executable (transferred by XLA's own arg handling — counted at the
  feed site with zero duration) and the pad-slot replays.

Rows are keyed ``(pipeline, source, direction, reason)`` with
``direction`` ``h2d``/``d2h``/``d2d`` and ``reason`` one of
``input``/``weights``/``drain``/``pad``/``handoff``.  ``d2d`` rows are
device→device moves (the cross-stage HBM handoff of a pipeline split
over disjoint device subsets): they never touch the host, so the
crossings-per-frame accounting (which counts host↔device residency
flips) stays at 0.0 while the handoff bytes remain byte-exact on the
ledger.  The *labels* come from a
thread-local context the runtime pushes around each element chain
(``runtime/element.py``), micro-batch flush and pool dispatch — the
recording site itself only knows the bytes.  Counts and bytes are
EXACT (``nbytes`` of the crossing array, every crossing counted, no
sampling); durations feed a per-row histogram.

Exported by the metrics registry at scrape time like every other
collected stat: ``nns_transfer_bytes_total`` /
``nns_transfer_count_total`` counters and ``nns_transfer_seconds``
histograms, the snapshot's ``transfers`` table (v4), XFER B/s and
X/FRAME columns in ``nns-top``, and — for sampled buffers — Chrome
trace ``xfer`` sub-spans via the trace dicts the context carries.

The whole subsystem obeys the global observability kill switch
(``NNS_TPU_OBS_DISABLE``, :func:`nnstreamer_tpu.obs.hooks.obs_disabled`)
and can be toggled programmatically with :func:`set_enabled`.
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import Any, Dict, List, Optional, Tuple

from . import hooks as _hooks

#: crossing directions and reasons (the label vocabulary); ``d2d`` is
#: the cross-stage HBM handoff (never a host crossing), ``handoff``
#: its reason tag
DIRECTIONS = ("h2d", "d2h", "d2d")
REASONS = ("input", "weights", "drain", "pad", "handoff")

#: transfer duration histogram bounds (seconds): sub-µs CPU-backend
#: no-op conversions up to multi-second weight placements
TRANSFER_SECONDS_BUCKETS = (1e-6, 2.5e-6, 5e-6, 1e-5, 2.5e-5, 5e-5,
                            1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
                            .01, .025, .05, .1, .25, 1.0, float("inf"))

#: fast-path flag every recording site reads first (one attribute load
#: + branch, same cost class as the tracer hook); honors the global
#: obs kill switch at process start
ACTIVE = not _hooks.DISABLED


def set_enabled(flag: bool) -> None:
    """Programmatic on/off (bench A/B, tests).  The env kill switch
    (``NNS_TPU_OBS_DISABLE``) wins: it cannot be re-enabled at
    runtime — the hot paths were told at startup the whole obs layer
    is off."""
    global ACTIVE
    ACTIVE = bool(flag) and not _hooks.DISABLED


class _Row:
    """One (pipeline, source, direction, reason) series: exact count
    and bytes plus a duration histogram (guarded by the ledger lock)."""

    __slots__ = ("count", "bytes", "seconds", "buckets")

    def __init__(self):
        self.count = 0
        self.bytes = 0
        self.seconds = 0.0
        self.buckets = [0] * len(TRANSFER_SECONDS_BUCKETS)


class TransferLedger:
    """Process-wide, thread-safe table of host↔device crossings."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rows: Dict[Tuple[str, str, str, str], _Row] = {}

    def record(self, direction: str, reason: str, nbytes: int,
               seconds: float = 0.0, source: Optional[str] = None,
               pipeline: Optional[str] = None) -> None:
        """Count one crossing.  ``source``/``pipeline`` default to the
        thread-local context the runtime pushed (empty outside any
        element).  ``seconds=0`` marks a transfer performed inside the
        executable's own arg handling (counted, not separately
        timed)."""
        ctx = getattr(_TLS, "ctx", None)
        if pipeline is None:
            pipeline = ctx[0] if ctx is not None else ""
        if source is None:
            source = ctx[1] if ctx is not None else ""
        key = (pipeline, source, direction, reason)
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                row = self._rows[key] = _Row()
            row.count += 1
            row.bytes += nbytes
            row.seconds += seconds
            row.buckets[bisect_left(TRANSFER_SECONDS_BUCKETS,
                                    seconds)] += 1
        if ctx is not None and ctx[2]:
            # sampled buffers in flight: the crossing renders as a
            # Chrome-trace `xfer` sub-span inside the owning element's
            # residency span (obs/tracer.py chrome_trace)
            t_end = time.monotonic()
            span = (t_end - float(seconds), float(seconds), str(source),
                    direction, reason, int(nbytes))
            for tr in ctx[2]:
                tr.setdefault("xfers", []).append(span)

    # -- pull side -----------------------------------------------------------

    def snapshot(self) -> List[dict]:
        """Rows for the registry's ``transfers`` table (v4), sorted."""
        with self._lock:
            return [{"pipeline": pl, "source": src, "direction": d,
                     "reason": r, "count": row.count,
                     "bytes": row.bytes, "seconds": row.seconds,
                     "buckets": list(row.buckets)}
                    for (pl, src, d, r), row
                    in sorted(self._rows.items())]

    def totals(self, pipeline: Optional[str] = None,
               direction: Optional[str] = None,
               reason: Optional[str] = None) -> Tuple[int, int]:
        """(count, bytes) summed over rows matching the given labels —
        the accounting helper of the tests and ``chip_smoke.py``."""
        count = nbytes = 0
        with self._lock:
            for (pl, _src, d, r), row in self._rows.items():
                if pipeline is not None and pl != pipeline:
                    continue
                if direction is not None and d != direction:
                    continue
                if reason is not None and r != reason:
                    continue
                count += row.count
                nbytes += row.bytes
        return count, nbytes

    def clear(self) -> None:
        """Tests/bench only: drop every row."""
        with self._lock:
            self._rows.clear()


#: the process-wide ledger every recording seam feeds
LEDGER = TransferLedger()

_TLS = threading.local()


def push_context(pipeline: str, source: str,
                 traces: Optional[tuple] = None):
    """Install the transfer-label context for the current thread
    (returns the previous context for :func:`pop_context`).  ``traces``
    optionally carries the trace dicts of sampled buffers in flight so
    crossings render as Chrome-trace sub-spans."""
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (pipeline, source, traces)
    return prev


def pop_context(prev) -> None:
    _TLS.ctx = prev


def record(direction: str, reason: str, nbytes: int,
           seconds: float = 0.0, source: Optional[str] = None,
           pipeline: Optional[str] = None) -> None:
    """Module-level recording shim: no-op unless :data:`ACTIVE`."""
    if not ACTIVE:
        return
    LEDGER.record(direction, reason, nbytes, seconds,
                  source=source, pipeline=pipeline)


def params_nbytes(params: Any) -> int:
    """Total payload bytes of a weight pytree (host or device leaves)."""
    try:
        from jax.tree_util import tree_leaves
    except ImportError:  # pragma: no cover - jax always present here
        return 0
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in tree_leaves(params))
