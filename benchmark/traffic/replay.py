"""Closed-loop replay: ``device_src`` replays a ring of seeded buffers
staged in HBM through the cell's launch line as fast as the sink is
drained; the benchmark's consumer thread pulls every buffer and fences
its outputs.

Mix parameters (``benchmark/traffic/<mix>.json``): ``batch`` frames a
buffer, ``ring_buffers`` distinct buffers staged, ``sink_depth`` buffers
the appsink may hold, ``warmup_windows`` buffers fenced before the heap is
settled, ``trace_seconds`` traced in a ``--trace 1`` run, ``check_windows``
buffers of the window whose outputs are kept for the output check.

The window opens at the instant a buffer is fenced, ``sink_depth + 2``
buffers after the heap was settled: settling stops every thread, the
device drains what was in flight, and a window opened there would start
with a refill whose length differs from run to run.  A frame counts when
its buffer's outputs are fenced inside the window, on this module's
clock.  Buffers in flight when the window closes do not count.

The launch line gives ``device_src`` ``fps=1000000000``, which only
stamps buffer ``i`` with ``pts = i`` (it does not pace the source); every
pulled buffer must carry the next ``pts`` (none missing, none extra,
order kept), and its ring slot is ``pts % ring_buffers``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import BenchmarkError
from benchmark.inputs import sampled, tensors
from benchmark.stages import program_text
from benchmark.stats import GcWatch, settle_heap
from benchmark.trace import reduce_run, trace_steady_window

PULL_TIMEOUT_S = 600.0


def run(run) -> dict:
    """Set-up (weights, model, ring, launch line), then :func:`stream`.
    A kind with more set-up, such as state built before the window,
    does its own and calls :func:`stream` likewise."""
    from nnstreamer_tpu.runtime import parse_launch

    mix, cfg = run.mix, run.cfg
    batch, slots = int(mix["batch"]), int(mix["ring_buffers"])

    t0 = time.perf_counter()
    params = run.make_weights()
    model = f"bench_{cfg['name']}_b{batch}_s{run.seed}"
    run.model.register(cfg, params, batch, model)
    ring = run.make_ring(slots, batch)
    nbytes = sum(a.nbytes for slot in ring for a in tensors(slot))
    run.log(f"weights and a ring of {slots} x {batch} frames "
            f"({nbytes / 1e9:.2f} GB) made in "
            f"{time.perf_counter() - t0:.1f} s")

    t1 = time.perf_counter()
    pipe = parse_launch(run.launch(model=model))
    obs = stream(run, pipe, ring, t1)
    # free the program's state before the reference runs
    del ring, pipe, params
    run.model.unregister(model)
    return obs


def stream(run, pipe, ring, t1: float) -> dict:
    """The second half of a replay run: stage ``ring`` through
    ``pipe``'s ``device_src``, start, warm up, settle, open the window at
    a fence, consume and count for ``run.seconds``, trace where asked,
    stop, and sample what was served.  ``t1`` is when the launch line
    was parsed (the log's clock).  Returns ``obs``; the caller frees
    what it made (ring, weights, model) before the reference runs."""
    mix = run.mix
    batch, slots = int(mix["batch"]), len(ring)
    warm = int(mix.get("warmup_windows", 3))
    open_at = warm + int(mix["sink_depth"]) + 2
    prefix = run.workload.get("element_prefix", "el_")
    src = pipe[prefix + "src"]
    src.frames, src.pool_size = ring, len(ring)
    sink = pipe[prefix + "sink"]

    state = {"open": None, "close": None, "windows": 0, "kept": [],
             "order_errors": 0, "pulled": 0, "snap_open": None, "fenced": [],
             "snap_close": None, "error": None}
    done = threading.Event()
    quit_ = threading.Event()
    # the windows whose outputs are kept for the check: drawn from the
    # seed among the first pass over the ring after the window opens
    keep = set(np.random.default_rng([run.seed, 11]).choice(
        slots, size=min(int(mix.get("check_windows", 4)), slots),
        replace=False).tolist())

    gc_watch = GcWatch()

    def consume():
        expect = 0
        try:
            while not quit_.is_set():
                buf = sink.pull(timeout=0.5)
                if buf is None:
                    continue
                run.model.fence(buf)
                now = time.perf_counter()
                if buf.pts != expect:
                    state["order_errors"] += 1
                expect = (buf.pts if buf.pts is not None
                          else expect) + 1
                state["pulled"] += 1
                if state["pulled"] == 1:
                    state["out_bytes"] = run.model.served_nbytes(buf)
                    run.log(f"first window fenced {now - t1:.1f} s after "
                            "the launch line was parsed")
                if state["open"] is None:
                    if state["pulled"] == warm:
                        settle_heap()
                        gc_watch.start()
                    elif state["pulled"] >= open_at:
                        state["open"] = now
                        state["fenced"].append(now)
                        state["snap_open"] = run.counters.snapshot()
                    continue
                if state["close"] is not None:
                    continue
                if now > state["open"] + run.seconds:
                    # its window does not count, its interval does: a
                    # stall that outlasts the window must show
                    state["fenced"].append(now)
                    state["snap_close"] = run.counters.snapshot()
                    state["close"] = now
                    done.set()
                    continue
                state["fenced"].append(now)
                if state["windows"] in keep:
                    state["kept"].append((buf.pts,
                                          run.model.outputs(buf)))
                state["windows"] += 1
        except Exception as e:  # noqa: BLE001 - reported by the main thread
            state["error"] = e
            done.set()

    consumer = threading.Thread(target=consume, name="bench-consumer",
                                daemon=True)
    text = None
    pipe.start()
    run.log(f"ring staged and pipeline started in "
            f"{time.perf_counter() - t1:.1f} s")
    consumer.start()
    try:
        deadline = time.perf_counter() + PULL_TIMEOUT_S
        while state["open"] is None and state["error"] is None:
            if time.perf_counter() > deadline:
                raise BenchmarkError("warm-up did not finish")
            time.sleep(0.005)
        run.log("setup_s %.3f (window open at the fence of buffer %d)"
                % (state["open"] - run.t_start, open_at))
        if run.trace and state["error"] is None:
            trace_steady_window(
                run, state["open"] + min(1.0, run.seconds / 4))
        done.wait(timeout=run.seconds + PULL_TIMEOUT_S)
        if run.trace and state["close"] is not None:
            # the window has closed and the stream runs on: the filter
            # builds its program's text again, outside every reading
            text = program_text(run, pipe)
    finally:
        # the consumer keeps draining until the pipeline has stopped
        pipe.stop()
        quit_.set()
        consumer.join(timeout=30)
    if state["error"] is not None:
        raise state["error"]
    if state["close"] is None:
        raise BenchmarkError("the window did not close")
    trace_obs = reduce_run(run, text) if run.trace else None

    window = run.counters.delta(state["snap_open"], state["snap_close"])
    gaps_ms = np.diff(np.asarray(state["fenced"])) * 1e3
    if len(gaps_ms):
        p50 = float(np.percentile(gaps_ms, 50))
        stalls = gaps_ms[gaps_ms > 2 * p50]
        slow = gaps_ms[gaps_ms > 1.25 * p50]
        run.log("window intervals ms: p50 %.2f p95 %.2f max %.2f; %d over "
                "twice the median, %.1f ms in all; %d over 1.25 times, "
                "%.1f ms beyond the median in all; gc: %s; ledger in the "
                "window %s" % (
                    p50, np.percentile(gaps_ms, 95), gaps_ms.max(),
                    len(stalls), stalls.sum(), len(slow),
                    (slow - p50).sum(), gc_watch.stop(),
                    {k: v for k, v in window["ledger"].items() if v}))
    kept = state["kept"]
    sample = _sample(run, ring, kept, batch, slots)
    obs = {
        "setup_s": state["open"] - run.t_start,
        "window_s": run.seconds,
        "frames": state["windows"] * batch,
        "windows": state["windows"],
        "attempted": state["windows"] * batch,
        "failed": 0,
        "batch": batch,
        "frames_per_window": float(batch),
        "window_gaps_ms": gaps_ms.tolist(),
        "out_bytes_per_frame": state["out_bytes"] / batch,
        "window": window,
        "trace": trace_obs,
        "sample": sample,
        "stream_checks": [{"name": "order_errors",
                           "value": float(state["order_errors"]),
                           "limit": 0.0}],
    }
    return obs


def _sample(run, ring, kept, batch, slots) -> dict:
    """``check_frames`` frames drawn from the seed over the kept windows:
    the served outputs and the host frames that went in."""
    rng = np.random.default_rng([run.seed, 7])
    want = int(run.cfg.get("check_frames", 64))
    if not kept:
        raise BenchmarkError("no window completed inside the window")
    host = [{k: np.asarray(v) for k, v in outs.items()}
            for _off, outs in kept]
    picks = [(int(rng.integers(len(kept))), int(rng.integers(batch)))
             for _ in range(want)]
    frames = sampled(ring, [(kept[w][0] % slots, r) for w, r in picks])
    served = {k: np.stack([host[w][k][r] for w, r in picks])
              for k in host[0]}
    return {"frames": frames, "served": served}
