"""Per-filter latency/throughput instrumentation.

Parity target: /root/reference/gst/nnstreamer/tensor_filter/tensor_filter.c:366-468
— rolling window of recent invoke latencies (GST_TF_STAT_MAX_RECENT = 10),
overflow-safe accumulators, throughput as 1000×FPS integer, and LATENCY
reporting with 5% headroom / 25% update threshold (tensor_filter.c:109-120).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Optional

STAT_MAX_RECENT = 10
LATENCY_REPORT_HEADROOM = 1.05   # 5% headroom on reported latency
LATENCY_REPORT_THRESHOLD = 0.25  # re-report when moving beyond ±25%


class InvokeStats:
    """Thread-safe rolling invoke statistics.

    With micro-batching (``runtime/batching.py``) one *invoke* (XLA
    dispatch) can carry several *frames*; ``record``/``count`` take the
    per-invoke frame count so the stats report both frames/s
    (:attr:`throughput_milli_fps`) and dispatches/s
    (:attr:`dispatch_milli_fps`), plus the realized batch occupancy.
    Unbatched callers (frames=1) see the exact pre-batching numbers.

    With the shared-model serving pool (``runtime/serving.py``) one
    dispatch can additionally carry frames from several *pipelines*:
    ``streams`` is the number of distinct streams contributing to the
    dispatch, accumulated into :attr:`avg_stream_occupancy` (the
    cross-stream coalescing measure), and :attr:`attached_streams` is a
    gauge of how many streams are currently attached to the pool entry.
    """

    def __init__(self, window: int = STAT_MAX_RECENT):
        self._lock = threading.Lock()
        self._recent = collections.deque(maxlen=window)
        self.total_invoke_num = 0   # dispatches
        self.total_frame_num = 0    # frames carried by those dispatches
        self.total_stream_num = 0   # distinct streams, summed per dispatch
        self.attached_streams = 0   # gauge: streams on the pool entry
        self.total_invoke_latency_us = 0  # accumulated, overflow-free (py int)
        self._first_ts: Optional[float] = None
        self._first_frames = 0  # frames carried by the first dispatch
        self._last_ts: Optional[float] = None
        self._last_reported_us: Optional[float] = None
        # dispatch cost attribution (sampled dispatches only): rolling
        # window of (host-prep, device, host-drain) seconds plus
        # cumulative totals — the boundaries are block_until_ready
        # fences, so prep + device equals the recorded invoke latency
        # and prep + device + drain partitions the whole dispatch
        self._phase_recent = collections.deque(maxlen=window)
        self.phase_samples = 0
        self.total_host_prep_s = 0.0
        self.total_device_s = 0.0
        self.total_host_drain_s = 0.0

    def _tick(self, frames: int, streams: int) -> None:
        """Bump invoke count + first/last timestamps (callers hold _lock)."""
        now = time.monotonic()
        self.total_invoke_num += 1
        self.total_frame_num += max(int(frames), 1)
        self.total_stream_num += max(int(streams), 1)
        if self._first_ts is None:
            self._first_ts = now
            self._first_frames = max(int(frames), 1)
        self._last_ts = now

    def record(self, latency_s: float, frames: int = 1,
               streams: int = 1) -> None:
        us = latency_s * 1e6
        with self._lock:
            self._recent.append(us)
            self.total_invoke_latency_us += int(us)
            self._tick(frames, streams)

    def count(self, frames: int = 1, streams: int = 1) -> None:
        """Count an invoke without a latency sample (async dispatch whose
        execution time is unknown) so throughput stays accurate while
        latency reflects only sampled, device-synchronized invokes."""
        with self._lock:
            self._tick(frames, streams)

    def record_phases(self, prep_s: float, device_s: float,
                      drain_s: float) -> None:
        """Record one sampled dispatch's host/device phase split:
        host-prep (input gather/convert/place), device (dispatch →
        ``block_until_ready``) and host-drain (output wrap/demux).
        Phases come from consecutive clock reads around one dispatch,
        so their sum IS the dispatch's wall time by construction."""
        with self._lock:
            self._phase_recent.append((prep_s, device_s, drain_s))
            self.phase_samples += 1
            self.total_host_prep_s += prep_s
            self.total_device_s += device_s
            self.total_host_drain_s += drain_s

    # -- unlocked readers (callers hold _lock) -------------------------------

    def _latency_us_locked(self) -> int:
        if not self._recent:
            return -1
        return int(sum(self._recent) / len(self._recent))

    def _throughput_milli_fps_locked(self) -> int:
        if (self.total_invoke_num < 2 or self._first_ts is None
                or self._last_ts is None or self._last_ts <= self._first_ts):
            return -1
        fps = (self.total_frame_num - self._first_frames) \
            / (self._last_ts - self._first_ts)
        return int(fps * 1000)

    def _dispatch_milli_fps_locked(self) -> int:
        if (self.total_invoke_num < 2 or self._first_ts is None
                or self._last_ts is None or self._last_ts <= self._first_ts):
            return -1
        dps = (self.total_invoke_num - 1) / (self._last_ts - self._first_ts)
        return int(dps * 1000)

    def _avg_batch_occupancy_locked(self) -> float:
        if self.total_invoke_num == 0:
            return 0.0
        return self.total_frame_num / self.total_invoke_num

    def _avg_stream_occupancy_locked(self) -> float:
        if self.total_invoke_num == 0:
            return 0.0
        return self.total_stream_num / self.total_invoke_num

    def _phase_means_us_locked(self):
        """Rolling-window mean of each phase in µs, or (-1,-1,-1) before
        the first sampled dispatch (same "no data yet" sentinel as
        :attr:`latency_us`)."""
        if not self._phase_recent:
            return -1, -1, -1
        n = len(self._phase_recent)
        prep = sum(p for p, _, _ in self._phase_recent) / n
        dev = sum(d for _, d, _ in self._phase_recent) / n
        drain = sum(d for _, _, d in self._phase_recent) / n
        return int(prep * 1e6), int(dev * 1e6), int(drain * 1e6)

    # -- public readers ------------------------------------------------------

    @property
    def latency_us(self) -> int:
        """Average invoke latency over the recent window, µs (parity:
        'latency' property, tensor_filter_common.c:982-988)."""
        with self._lock:
            return self._latency_us_locked()

    @property
    def throughput_milli_fps(self) -> int:
        """1000×FPS over the whole run, in FRAMES (parity: 'throughput'
        property, tensor_filter_common.c:989-996; identical to the
        dispatch rate when every invoke carries one frame).  The first
        dispatch's frames are excluded, mirroring the unbatched (N-1)
        events over (N-1) intervals accounting — else a 2-dispatch
        batched run would report nearly double its true rate."""
        with self._lock:
            return self._throughput_milli_fps_locked()

    @property
    def dispatch_milli_fps(self) -> int:
        """1000×dispatches/s — with micro-batching, the XLA invoke rate
        (< frame rate when coalescing is happening)."""
        with self._lock:
            return self._dispatch_milli_fps_locked()

    @property
    def avg_batch_occupancy(self) -> float:
        """Mean frames per dispatch (1.0 unbatched)."""
        with self._lock:
            return self._avg_batch_occupancy_locked()

    @property
    def avg_stream_occupancy(self) -> float:
        """Mean distinct streams contributing to one dispatch (1.0 for a
        single-pipeline filter; >1 exactly when the serving pool is
        coalescing across pipelines)."""
        with self._lock:
            return self._avg_stream_occupancy_locked()

    def snapshot(self) -> dict:
        """Every derived statistic as ONE consistent dict, read under a
        single lock acquisition — the poller API (`nns-top`, the obs
        metrics registry).  Reading the individual properties instead
        takes the lock once per field, so a dispatch landing between
        reads yields e.g. a frame total from one dispatch and a latency
        from the next."""
        with self._lock:
            prep_us, dev_us, drain_us = self._phase_means_us_locked()
            return {
                "invokes": self.total_invoke_num,
                "frames": self.total_frame_num,
                "latency_us": self._latency_us_locked(),
                "throughput_milli_fps": self._throughput_milli_fps_locked(),
                "dispatch_milli_fps": self._dispatch_milli_fps_locked(),
                "avg_batch_occupancy": self._avg_batch_occupancy_locked(),
                "avg_stream_occupancy": self._avg_stream_occupancy_locked(),
                "attached_streams": self.attached_streams,
                "host_prep_us": prep_us,
                "device_us": dev_us,
                "host_drain_us": drain_us,
                "phase": {
                    "samples": self.phase_samples,
                    "host_prep_s": self.total_host_prep_s,
                    "device_s": self.total_device_s,
                    "host_drain_s": self.total_host_drain_s,
                },
            }

    def latency_to_report(self) -> Optional[int]:
        """µs to report on the bus if it moved past the threshold, else None
        (parity: track_latency, tensor_filter.c:480-506).  The window
        mean is computed inside the same lock acquisition as the
        last-reported compare-and-swap — re-entering through the
        ``latency_us`` property would read one window and threshold
        against another when a concurrent ``record`` lands between."""
        with self._lock:
            cur = self._latency_us_locked()
            if cur < 0:
                return None
            last = self._last_reported_us
            if last is None or abs(cur - last) > last * LATENCY_REPORT_THRESHOLD:
                self._last_reported_us = cur
                return int(cur * LATENCY_REPORT_HEADROOM)
        return None


class CompileStats:
    """Process-wide XLA compile telemetry: one row per (framework,
    kind, bucket), where ``kind`` names the compile path — ``cold``
    (first configure), ``reshape`` (SET_INPUT_INFO recompile),
    ``reload`` (hot model swap), ``bucket`` (a micro-batch bucket
    executable), ``persist_hit`` (an executable loaded from the
    persistent AOT cache) and ``reuse`` (a weights-only swap of the
    serving shapes: the executables that serve take the new weights, no
    program is traced or built; its seconds are the placement and the
    weights prologue's run).  ``seconds`` accumulates the trace/lower time spent at
    the compile site PLUS the executable's first invocation (jit
    compiles lazily — the first call is where XLA actually builds the
    program; on a non-trivial model that dwarfs the first execution).

    Pulled into the metrics registry at scrape time like every other
    collected stat (``nns_compiles_total`` / ``nns_compile_seconds_
    total``) and rendered as the COMPILE section of ``nns-top`` — the
    measurement substrate a persistent AOT compile cache will be
    judged against (ROADMAP item 4)."""

    def __init__(self):
        self._lock = threading.Lock()
        # (framework, kind, bucket) -> [count, seconds]
        self._rows: dict = {}

    def record(self, kind: str, seconds: float = 0.0, bucket: int = 0,
               framework: str = "jax-xla"):
        """Count one compile; returns the row key so the caller can
        attribute the executable's first-call time to the same row via
        :meth:`add_seconds`."""
        key = (str(framework), str(kind), str(int(bucket or 0)))
        with self._lock:
            row = self._rows.setdefault(key, [0, 0.0])
            row[0] += 1
            row[1] += float(seconds)
        return key

    def add_seconds(self, key, seconds: float) -> None:
        with self._lock:
            row = self._rows.get(key)
            if row is not None:
                row[1] += float(seconds)

    @property
    def total_compiles(self) -> int:
        with self._lock:
            return sum(r[0] for r in self._rows.values())

    @property
    def total_seconds(self) -> float:
        with self._lock:
            return sum(r[1] for r in self._rows.values())

    def snapshot(self) -> list:
        """Rows for the registry / nns-top: sorted, one dict per
        (framework, kind, bucket)."""
        with self._lock:
            return [{"framework": fw, "kind": kind, "bucket": bucket,
                     "count": row[0], "seconds": row[1]}
                    for (fw, kind, bucket), row
                    in sorted(self._rows.items())]

    def reset(self) -> None:
        """Tests/bench only: drop every row."""
        with self._lock:
            self._rows.clear()


#: the process-wide compile telemetry every framework sub-plugin feeds
COMPILE_STATS = CompileStats()


class DispatchStats:
    """Process-wide count of XLA program launches, by launch site
    (``filter`` / ``transform`` / ``decoder`` / ``decoder_pack``).

    This is the denominator-side witness of the fusion work
    (runtime/fusion.py): a fused transform→filter→decoder window is
    exactly ONE ``filter`` launch, while the unfused pipeline pays one
    launch per stage.  ``benchmark/readers/dispatches_per_window.py``
    and ``tests/test_fusion.py::test_fused_window_is_one_dispatch`` read
    a delta of the sites' sum over a counted number of windows — which
    only works if every site that hands a program to XLA bumps the
    counter, so keep the call sites in sync with the ``site`` names
    above.  One short lock per dispatch; a
    dispatch costs orders of magnitude more than the bump."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sites: dict = {}

    def count(self, site: str, n: int = 1) -> None:
        with self._lock:
            self._sites[site] = self._sites.get(site, 0) + int(n)

    @property
    def total(self) -> int:
        with self._lock:
            return sum(self._sites.values())

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._sites)

    def reset(self) -> None:
        """Tests only."""
        with self._lock:
            self._sites.clear()


#: process-wide dispatch accounting (the benchmark's dispatches_per_window)
DISPATCH_STATS = DispatchStats()


class StateStats:
    """Process-wide totals of what stateful models count about their own
    state (``filters/jax_xla.py`` ``_StateCell``): ``state_bytes`` (a
    level: bytes of device state alive now) and the counters a model's
    steps keep in its state, added up whenever the owning element's
    stats sample reads them (``steps``, and for a model with a cache
    and routed experts ``cache_bytes_read``, ``experts_touched``,
    ``expert_hits``; a model with two kinds of cache tells them apart,
    ``window_bytes_read`` of its rings and ``full_bytes_read`` of its
    dense caches, and ``cache_bytes_read`` is their sum; beside each
    ``*_bytes_read`` of a grouped-query model stands ``*_bytes_fetched``
    (``window_``, ``full_``, ``kv_`` and ``cache_bytes_fetched``): the
    rows its decode attention reads in for the rows in use, so fetched
    over read is what the kernel's schedule costs in bytes, while the
    benchmark's rooflines count the rows in use; a model with a
    recurrent state adds ``ssm_bytes`` read and written, ``restores``
    from its snapshots and ``position_faults`` (a model whose EVERY
    layer owns both kinds, ``falcon_h1.py``, counts every layer in
    ``ssm_bytes`` and in ``kv_bytes_*`` alike, and keeps no expert
    counter: it routes nothing); a model in which ONE cache has one
    writer and several readers, ``phi4_flash.py``, publishes
    ``shared_kv_bytes_*`` (a row times its READERS) beside
    ``ring_kv_bytes_*``, and from its prefill entry ``prefill_tokens``
    and ``cross_tokens``; a model whose router
    also scores zero-compute experts adds ``zero_picks`` (the picks
    that cost no product; a step's picks are a constant, tokens x picks
    a token x layers, so ``zero_picks`` and ``expert_hits`` a frame over
    it say how a step's picks divide);
    ``Documentation/observability.md``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals: dict = {}

    def add(self, name: str, n: int) -> None:
        with self._lock:
            self._totals[name] = self._totals.get(name, 0) + int(n)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._totals)

    def reset(self) -> None:
        """Tests only."""
        with self._lock:
            self._totals.clear()


#: process-wide accounting of stateful models' state
STATE_STATS = StateStats()
