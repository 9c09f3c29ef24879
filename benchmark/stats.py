"""The arithmetic of the yardstick: percentiles, quartile spread."""

from __future__ import annotations

import gc
import math
import statistics
import time


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics; raises on an empty sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def quartile_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, the way the driver reads a metric's spread."""
    q1, _q2, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / statistics.median(values)


class GcWatch:
    """Counts the interpreter's garbage collections and their seconds
    between :meth:`start` and :meth:`stop`: a full collection stops every
    thread of the process, pipeline threads included."""

    def __init__(self):
        self.collections = 0
        self.seconds = 0.0
        self.longest = 0.0
        self._t0 = None

    def _callback(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            took = time.perf_counter() - self._t0
            self.collections += 1
            self.seconds += took
            self.longest = max(self.longest, took)
            self._t0 = None

    def start(self) -> None:
        gc.callbacks.append(self._callback)

    def stop(self) -> str:
        if self._callback in gc.callbacks:
            gc.callbacks.remove(self._callback)
        return (f"{self.collections} collections, {self.seconds * 1e3:.1f} "
                f"ms in all, longest {self.longest * 1e3:.1f} ms")


def settle_heap() -> None:
    """Collect once and move everything that set-up allocated out of the
    collector's reach, as a long-running application does after start-up:
    later collections then scan only what the stream allocates."""
    gc.collect()
    gc.freeze()
