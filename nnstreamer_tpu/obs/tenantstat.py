"""Per-tenant attribution of the shared serving path.

The pool layer (PR 3/12) coalesces many pipelines' frames into one
cross-stream window, and PR 7's cost attribution times each sampled
dispatch's host/device phases — but a window mixes *tenants* (the
``tenant=`` stream property on ``tensor_filter``), and nothing said
who consumed the device-seconds.  This module is the process-wide
store behind ``nns_tenant_*``: every pool dispatch splits its
phase-split device time across the tenants that parked useful frames
in the window, proportionally to their frame counts.

The headline invariant is EXACT, not approximate: the split happens
on the SAME ``t1``/``t2`` clock reads the pool's
``nns_invoke_device_seconds`` histogram observes, converted once to
integer nanoseconds and partitioned with the residual assigned to the
window's largest tenant — so the sum over tenants of attributed
device time equals the pool's total with zero drift, dispatch after
dispatch (``exactness()`` exposes both integer accumulators;
``tests/test_tenant.py`` pins their equality).  Dollars are
derived at scrape time — device-seconds × the
:func:`~nnstreamer_tpu.obs.hwspec.chip_hour_price` figure
(``NNS_TPU_CHIP_HOUR_USD`` overridable) — never stored, so a price
change never has to rewrite history.

SLO attainment rides the same demux loop the admission controller's
latency signal comes from: each demuxed frame's ingress→demux latency
is graded against the pool SLO per tenant, so
``nns_tenant_slo_attainment`` answers "whose frames made it" with the
exact latencies the shedder acted on.  Sheds are counted per tenant
and reason at the same seam ``nns_admission_shed_total`` counts them.

Pulled by the metrics registry at scrape time like every collected
stat: the snapshot's ``tenants`` table (v9), the
``nns_tenant_{device_seconds,frames,dollars,shed}_total`` /
``nns_tenant_slo_attainment`` families, and nns-top's TENANT section.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from . import hooks as _hooks

#: fast-path flag (same contract as obs/transfer.py / obs/stagestat.py)
ACTIVE = not _hooks.DISABLED

#: the tenant every stream belongs to unless its filter says otherwise
DEFAULT_TENANT = "default"


class _TenantRow:
    __slots__ = ("frames", "device_ns", "lat_total", "lat_within",
                 "shed")

    def __init__(self):
        self.frames = 0
        self.device_ns = 0
        self.lat_total = 0       # demuxed frames graded against the SLO
        self.lat_within = 0      # ... of which landed within it
        self.shed: Dict[str, int] = {}


class TenantStats:
    """Process-wide, thread-safe per-(pool, tenant) attribution store."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rows: Dict[Tuple[str, str], _TenantRow] = {}
        # per-pool total device time, the OTHER side of the exactness
        # invariant: accumulated from the very same integer-ns values
        # the per-tenant shares partition
        self._pool_ns: Dict[str, int] = {}

    def _row(self, pool: str, tenant: str) -> _TenantRow:
        key = (str(pool), str(tenant) or DEFAULT_TENANT)
        row = self._rows.get(key)
        if row is None:
            row = self._rows[key] = _TenantRow()
        return row

    def record_window(self, pool: str, tenant_frames: Dict[str, int],
                      device_ns: Optional[int] = None) -> None:
        """Attribute one pool dispatch: ``tenant_frames`` maps tenant →
        useful frames it parked in the window.  ``device_ns`` (the
        sampled dispatch's device phase, integer nanoseconds from the
        same two clock reads ``nns_invoke_device_seconds`` observes) is
        split proportionally by frame count with the integer residual
        going to the largest tenant — so the per-tenant shares sum to
        ``device_ns`` EXACTLY.  None on unsampled dispatches (no
        ``block_until_ready`` fence → no honest device time): frames
        still count, device time doesn't — mirroring the histogram,
        which also only sees sampled windows."""
        items = [(str(t) or DEFAULT_TENANT, int(n))
                 for t, n in tenant_frames.items() if int(n) > 0]
        if not items:
            return
        total = sum(n for _t, n in items)
        with self._lock:
            for tenant, n in items:
                self._row(pool, tenant).frames += n
            if device_ns is None:
                return
            device_ns = int(device_ns)
            self._pool_ns[str(pool)] = \
                self._pool_ns.get(str(pool), 0) + device_ns
            shares = [(tenant, n, device_ns * n // total)
                      for tenant, n in items]
            residual = device_ns - sum(s for _t, _n, s in shares)
            # deterministic residual home: the largest tenant (first
            # such in dict order on ties) — it moves the relative
            # attribution least
            big = max(range(len(shares)), key=lambda i: shares[i][1])
            for i, (tenant, _n, share) in enumerate(shares):
                self._row(pool, tenant).device_ns += \
                    share + (residual if i == big else 0)

    def record_latency(self, pool: str, tenant: str, lat_s: float,
                       slo_s: float) -> None:
        """Grade one demuxed frame's ingress→demux latency against the
        pool SLO — the same per-frame signal the admission controller
        observes, attributed to the frame's tenant."""
        with self._lock:
            row = self._row(pool, tenant)
            row.lat_total += 1
            if lat_s <= slo_s:
                row.lat_within += 1

    def record_shed(self, pool: str, tenant: str, reason: str,
                    frames: int = 1) -> None:
        """Count frames shed at admission, per tenant and reason
        (``slo`` / ``queue-full`` — the same reasons
        ``nns_admission_shed_total`` partitions by)."""
        with self._lock:
            shed = self._row(pool, tenant).shed
            shed[str(reason)] = shed.get(str(reason), 0) + int(frames)

    # -- pull side -----------------------------------------------------------

    def exactness(self, pool: str) -> Tuple[int, int]:
        """``(sum over tenants of attributed device-ns, pool total
        device-ns)`` — equal by construction; ``tests/test_tenant.py``
        asserts it stays that way."""
        with self._lock:
            tenant_ns = sum(r.device_ns for (p, _t), r
                            in self._rows.items() if p == str(pool))
            return tenant_ns, self._pool_ns.get(str(pool), 0)

    def snapshot(self) -> List[dict]:
        """Rows for the registry's ``tenants`` table (v9), sorted by
        (pool, tenant).  Dollars derive from the CURRENT chip-hour
        price (``obs/hwspec.py``, env-overridable) — attribution stores
        time, never money."""
        with self._lock:
            rows = [(pool, tenant, r.frames, r.device_ns, r.lat_total,
                     r.lat_within, dict(r.shed))
                    for (pool, tenant), r in sorted(self._rows.items())]
        if not rows:
            return []
        from .hwspec import chip_hour_price, default_device_kind

        usd_per_s = chip_hour_price(default_device_kind()) / 3600.0
        out: List[dict] = []
        for pool, tenant, frames, ns, lt, lw, shed in rows:
            dev_s = ns / 1e9
            out.append({
                "pool": pool, "tenant": tenant,
                "frames": frames,
                "device_seconds": dev_s,
                "dollars": dev_s * usd_per_s,
                "slo_attainment": (lw / lt) if lt else None,
                "slo_frames": lt,
                "shed": shed,
            })
        return out

    def reset(self) -> None:
        """Tests/bench only: drop every row."""
        with self._lock:
            self._rows.clear()
            self._pool_ns.clear()


#: the process-wide store the pool dispatch / admission seams feed
TENANT_STATS = TenantStats()


def record_window(pool: str, tenant_frames: Dict[str, int],
                  device_ns: Optional[int] = None) -> None:
    """Module-level shim (inert under the global obs kill switch;
    never raises into the hot path)."""
    if not ACTIVE:
        return
    try:
        TENANT_STATS.record_window(pool, tenant_frames, device_ns)
    except Exception:  # noqa: BLE001 - telemetry must not kill a dispatch
        pass


def record_latency(pool: str, tenant: str, lat_s: float,
                   slo_s: float) -> None:
    if not ACTIVE:
        return
    try:
        TENANT_STATS.record_latency(pool, tenant, lat_s, slo_s)
    except Exception:  # noqa: BLE001 - telemetry must not kill a dispatch
        pass


def record_shed(pool: str, tenant: str, reason: str,
                frames: int = 1) -> None:
    if not ACTIVE:
        return
    try:
        TENANT_STATS.record_shed(pool, tenant, reason, frames)
    except Exception:  # noqa: BLE001 - telemetry must not kill a dispatch
        pass
