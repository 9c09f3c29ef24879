"""Open-loop camera streams into one shared model: ``streams`` pipelines
built from the cell's launch line, each fed host uint8 frames on a fixed
periodic schedule by ONE generator thread, each drained by a consumer
thread that pulls every buffer and fences its outputs.

Mix parameters (``benchmark/traffic/<mix>.json``): ``streams``, ``fps``
of each stream, ``jitter_ms`` (half-width of the per-frame jitter),
``frame_ring`` distinct host frames, ``batch`` and ``batch_timeout_ms``
of the pool, ``warmup_s`` of scheduled traffic before the window opens,
``drain_s`` the longest wait for frames due in the window after it
closes, ``trace_seconds``, ``tracer_sample_every``.

The schedule is the same set of phases and jitters for every seed, dealt
to the streams and frames in a seeded order: stream ``i`` has phase
``perm[i] / streams`` of a frame interval, and frame ``k`` is due at
``phase + k / fps + jitter`` with the jitters a seeded shuffle of an even
grid over ``[-jitter_ms, +jitter_ms]``.  A frame's latency runs from the
instant it was DUE to the instant its outputs are fenced at the sink; how
late the generator pushed is reported beside it (``gen_lag``).  A frame
that is refused at push, comes out of order, or is not delivered by the
end of the drain is failed and has no latency.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from benchmark import BenchmarkError
from benchmark.inputs import sampled, tensors
from benchmark.stats import GcWatch, settle_heap
from benchmark.trace import reduce_run, trace_seconds, trace_steady_window

PULL_TIMEOUT_S = 600.0


def schedule(seed: int, streams: int, fps: float, jitter_ms: float,
             frames_per_stream: int) -> np.ndarray:
    """Due times (streams, frames) in seconds from the origin."""
    rng = np.random.default_rng([int(seed), 3])
    interval = 1.0 / fps
    phase = rng.permutation(streams) / streams * interval
    n = streams * frames_per_stream
    grid = (np.arange(n) + 0.5) / n * 2.0 - 1.0           # even in (-1, 1)
    jitter = rng.permutation(grid).reshape(streams, frames_per_stream) \
        * jitter_ms * 1e-3
    k = np.arange(frames_per_stream)
    return phase[:, None] + k[None, :] * interval + jitter


def run(run) -> dict:
    from nnstreamer_tpu.core import Buffer, TensorsSpec
    from nnstreamer_tpu.runtime import Pipeline, parse_launch

    mix, cfg = run.mix, run.cfg
    n, fps = int(mix["streams"]), float(mix["fps"])
    warmup_s, drain_s = float(mix["warmup_s"]), float(mix["drain_s"])
    t0 = time.perf_counter()
    params = run.make_weights()
    model = f"bench_{cfg['name']}_frame_s{run.seed}"
    run.model.register(cfg, params, 1, model)
    ring = run.make_ring(int(mix["frame_ring"]), 1)
    run.log(f"weights and {len(ring)} host frames made in "
            f"{time.perf_counter() - t0:.1f} s")

    total_s = warmup_s + run.seconds
    per_stream = int(np.ceil(total_s * fps)) + 1
    due = schedule(run.seed, n, fps, float(mix["jitter_ms"]), per_stream)
    order = np.argsort(due, axis=None, kind="stable")
    first = tensors(ring[0])
    spec = TensorsSpec.from_shapes([a.shape for a in first],
                                   [a.dtype for a in first])
    launch = run.launch(model=model)
    pipes = []
    for i in range(n):
        p = parse_launch(launch, pipeline=Pipeline(name=f"cam{i}"))
        p["el_src"].spec = spec
        pipes.append(p)

    lock = threading.Lock()
    done_t = np.full((n, per_stream), np.nan)     # fence time per frame
    kept: dict = {}                                # (stream, k) -> outputs
    errors = {"order": 0, "consumer": None}
    quit_ = threading.Event()
    # frames whose outputs are kept for the check, drawn from the seed
    rng = np.random.default_rng([run.seed, 11])
    k_lo = int(np.ceil(warmup_s * fps)) + 1
    k_hi = max(int(total_s * fps) - 1, k_lo + 1)
    want = int(cfg.get("check_frames", 64))
    keep = {(int(rng.integers(n)), int(rng.integers(k_lo, k_hi)))
            for _ in range(want)}

    def consume(i, sink):
        expect = 0
        try:
            while not quit_.is_set():
                buf = sink.pull(timeout=0.2)
                if buf is None:
                    continue
                run.model.fence(buf)
                now = time.perf_counter()
                k = buf.pts
                if k is not None and k < 0:      # a warm-up frame
                    continue
                # a gap is a frame refused or lost (failed, below); a
                # step back is a frame out of order or delivered twice
                if k is None or k < expect:
                    with lock:
                        errors["order"] += 1
                    continue
                expect = k + 1
                if k < per_stream:
                    done_t[i, k] = now
                    if (i, k) in keep:
                        kept[(i, k)] = run.model.outputs(buf)
                        errors["out_bytes"] = run.model.served_nbytes(buf)
        except Exception as e:  # noqa: BLE001 - reported by the main thread
            errors["consumer"] = e

    consumers = [threading.Thread(target=consume, args=(i, p["el_sink"]),
                                  name=f"bench-consumer-{i}", daemon=True)
                 for i, p in enumerate(pipes)]
    trace_obs, tracer = None, None
    pushed_t = np.full((n, per_stream), np.nan)
    refused = np.zeros((n, per_stream), bool)
    try:
        for p in pipes:
            p.start()
        for c in consumers:
            c.start()
        pool = pipes[0]["el_net"].pool
        run.log(f"{n} pipelines started "
                f"{time.perf_counter() - run.t_start:.1f} s after process "
                "start")
        _warm_buckets(run, pipes, pool, ring, Buffer)
        done_t[:] = np.nan
        settle_heap()
        gc_watch = GcWatch()
        gc_watch.start()
        # scheduled traffic: origin, then the window [open, close)
        origin = time.perf_counter() + 0.05
        t_open, t_close = origin + warmup_s, origin + total_s
        # a traced run captures the END of the window: the profiler costs
        # the host what a pool has least of, so the counters and clocks of
        # the layers are read over the part before it starts
        t_quiet = t_close - trace_seconds(run) if run.trace else t_close
        marks = {}
        if run.trace:
            from nnstreamer_tpu.obs.tracer import LatencyTracer

            tracer = LatencyTracer(
                sample_every=int(mix.get("tracer_sample_every", 16)),
                max_records=1 << 16)
            tracer_thread = threading.Thread(
                target=_trace_part, args=(run, t_quiet, marks),
                name="bench-trace", daemon=True)
        for flat in order:
            i, k = divmod(int(flat), per_stream)
            t_due = origin + due[i, k]
            if t_due >= t_close:
                break
            if "open" not in marks and t_due >= t_open:
                marks["open"] = (run.counters.snapshot(), _pool_stats(pool))
                run.log("setup_s %.3f (window open)" % (t_open - run.t_start))
                if run.trace:
                    tracer.install()
                    tracer_thread.start()
            if run.trace and "quiet" not in marks and t_due >= t_quiet:
                marks["quiet"] = (run.counters.snapshot(),
                                  _pool_stats(pool))
                tracer.uninstall()
            wait = t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            frame = ring[(i * 7 + k) % len(ring)]
            try:
                pipes[i]["el_src"].push_buffer(
                    Buffer.of(*tensors(frame), pts=k), timeout=0.001)
                pushed_t[i, k] = time.perf_counter()
            except queue.Full:                   # refused at push
                refused[i, k] = True
        marks["close"] = (run.counters.snapshot(), _pool_stats(pool))
        marks.setdefault("quiet", marks["close"])
        run.log("gc from warm-up to close: " + gc_watch.stop())
        # bounded drain of the frames due in the window
        in_window = (origin + due >= t_open) & (origin + due < t_close)
        deadline = time.perf_counter() + drain_s
        while time.perf_counter() < deadline:
            if not np.isnan(done_t[in_window & ~refused]).any():
                break
            time.sleep(0.01)
        if run.trace:
            tracer_thread.join(timeout=PULL_TIMEOUT_S)
            if isinstance(marks.get("trace"), Exception):
                raise marks["trace"]
    finally:
        # the consumers keep draining until every pipeline has stopped: a
        # pool flush that blocks on a full sink holds the pool's flush
        # lock, and a stop() behind it would wait for ever
        for p in pipes:
            p.stop()
        quit_.set()
        for c in consumers:
            c.join(timeout=30)
    if errors["consumer"] is not None:
        raise errors["consumer"]
    if "open" not in marks:
        raise BenchmarkError("the window never opened")

    # a traced run reports the part of the window before the capture:
    # what the profiler's stop costs the streams is not the program's
    quiet = origin + due < t_quiet          # all of the window, untraced
    if run.trace:
        lost = int((in_window & ~quiet & np.isnan(done_t)).sum())
        run.log(f"{lost} of {int((in_window & ~quiet).sum())} frames due "
                "during the capture were not delivered (not reported)")
    in_window = in_window & quiet
    delivered = in_window & ~np.isnan(done_t)
    attempted = int(in_window.sum())
    failed = int((in_window & np.isnan(done_t)).sum()) + errors["order"]
    lat_ms = (done_t - (origin + due))[delivered & quiet] * 1e3
    lag_ms = (pushed_t - (origin + due))[in_window & quiet
                                         & ~np.isnan(pushed_t)] * 1e3
    counted = int((delivered & quiet & (done_t < t_quiet)).sum())
    # a growing backlog shows as latency rising through the window
    third = (origin + due - t_open) // (run.seconds / 3.0)
    thirds = [float(np.median((done_t - (origin + due))[delivered
                                                         & (third == j)]))
              * 1e3 if (delivered & (third == j)).any() else None
              for j in range(3)]
    run.log(f"median latency of the window's thirds, ms: {thirds}")
    (snap_a, pool_a), (snap_b, pool_b) = marks["open"], marks["quiet"]
    window = run.counters.delta(snap_a, snap_b)
    if run.trace and marks.get("trace") is True:
        trace_obs = reduce_run(run)
        trace_obs["tracer_records"] = [
            {"marks": [(t, name, phase) for t, name, phase in r["marks"]]}
            for r in tracer.records()]
    # a sampled frame that was not delivered is a failed frame, counted
    # above; the check compares the sampled frames that were
    picks = sorted(kept)
    if len(picks) < max(len(keep) // 2, 1):
        raise BenchmarkError(f"only {len(picks)} of {len(keep)} sampled "
                             "frames were delivered")
    sample = {
        "frames": sampled(ring, [((i * 7 + k) % len(ring), 0)
                                 for i, k in picks]),
        "served": {name: np.stack([np.asarray(kept[p][name])[0]
                                   for p in picks])
                   for name in kept[picks[0]]},
    }
    obs = {
        "setup_s": t_open - run.t_start,
        "window_s": t_quiet - t_open,
        "frames": counted,
        "attempted": attempted,
        "failed": failed,
        "latencies_ms": lat_ms.tolist(),
        "gen_lag_ms": lag_ms.tolist(),
        "latency_thirds_ms": thirds,
        "streams": n,
        "out_bytes_per_frame": float(errors.get("out_bytes", 0)),
        "window": window,
        "pool": {k: pool_b[k] - pool_a[k] for k in pool_a},
        "trace": trace_obs,
        "sample": sample,
        "stream_checks": [
            {"name": "order_errors", "value": float(errors["order"]),
             "limit": 0.0}],
    }
    del kept, pipes, params
    run.model.unregister(model)
    return obs


def _pool_stats(pool) -> dict:
    s = pool.stats
    return {"dispatches": s.total_invoke_num, "frames": s.total_frame_num,
            "stream_slots": s.total_stream_num}


def _warm_buckets(run, pipes, pool, ring, Buffer) -> None:
    """Run every bucket program the streams can fill once: bursts of
    ``b`` frames from ``b`` streams until the pool's cache holds every
    bucket up to the number of streams (a burst may split over two
    windows, so it is repeated with a bound)."""
    n = len(pipes)
    # the buckets the streams can fill, and the one a window of all
    # ``n`` streams is padded to
    buckets, covered = [], False
    for b in sorted(int(b) for b in pool.buckets):
        if b <= n or not covered:
            buckets.append(b)
        covered = covered or b >= n
    sent = [0] * n
    deadline = time.perf_counter() + PULL_TIMEOUT_S

    def compiled():
        by = pool.subplugin.cache_snapshot()["by_bucket"]
        return {int(b) for b in by}

    for attempt in range(40):
        missing = [b for b in buckets if b not in compiled()]
        if not missing:
            break
        for b in missing:
            for i in range(min(b, n)):
                pipes[i]["el_src"].push_buffer(
                    Buffer.of(*tensors(ring[i % len(ring)]),
                              pts=-1 - sent[i]))
                sent[i] += 1
            # wait for the burst to drain before the next
            while time.perf_counter() < deadline:
                s = pool.stats
                if s.total_frame_num >= sum(sent):
                    break
                time.sleep(0.002)
    missing = [b for b in buckets if b not in compiled()]
    if missing:
        raise BenchmarkError(f"buckets {missing} never formed in warm-up")
    # let the last outputs reach the sinks
    time.sleep(0.2)
    run.log(f"buckets warmed: {sorted(compiled())} of {buckets} with "
            f"{sum(sent)} frames")


def _trace_part(run, t_start, marks) -> None:
    """The capture, on a thread of its own: the generator thread keeps
    its schedule."""
    try:
        trace_steady_window(run, t_start)
        marks["trace"] = True
    except Exception as e:  # noqa: BLE001 - re-raised by the main thread
        marks["trace"] = e
