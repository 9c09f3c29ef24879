"""Closed-loop replay over state built in set-up: every stream's prompt
is prefilled into the filter's cache, then ``device_src`` replays a ring
of decode steps on top of it (``replay.py``'s window, consumer, trace,
drain and sample).

The cell's file gives two launch lines on ONE model: ``prefill_launch``
(``<pf>src ! tensor_filter ! <pf>sink``, fed the configuration's
``prefill_chunks``) and ``launch`` (the ring).  Both filters name one
``shared-tensor-filter-key``, so they work on one set of weights and
one state; which entry point each runs follows from its input schema.
The prefill line is started first and stays up, idle, until the window
has closed: the state lives as long as a filter holds it.  Prefill is
set-up, so ``setup_s`` guards that path.

The program's own counters of its state (``STATE_STATS``: steps, cache
bytes read, experts touched and hit, fetched by the filter at its
stats-sample cadence) ride in the window's counter snapshots under
``state``; a program without them gives none.
"""

from __future__ import annotations

import time

from benchmark import BenchmarkError
from benchmark.inputs import tensors
from benchmark.run import launch_line
from benchmark.traffic import replay

PULL_TIMEOUT_S = 600.0


class _WithState:
    """The run's counters, and with them the program's state counters."""

    def __init__(self, inner):
        self.inner = inner

    def snapshot(self) -> dict:
        snap = self.inner.snapshot()
        try:
            from nnstreamer_tpu.utils.stats import STATE_STATS
        except ImportError:
            return snap
        snap["state"] = STATE_STATS.snapshot()
        return snap

    def delta(self, a: dict, b: dict) -> dict:
        out = self.inner.delta(a, b)
        if "state" in a and "state" in b:
            out["state"] = {k: b["state"].get(k, 0) - a["state"].get(k, 0)
                            for k in set(a["state"]) | set(b["state"])}
        return out


def run(run) -> dict:
    from nnstreamer_tpu.runtime import parse_launch

    mix, cfg = run.mix, run.cfg
    batch, slots = int(mix["batch"]), int(mix["ring_buffers"])

    preflight = getattr(run.model, "preflight", None)
    if preflight is not None:
        preflight(cfg)          # a program without the model fails here
    t0 = time.perf_counter()
    params = run.make_weights()
    model = f"bench_{cfg['name']}_b{batch}_s{run.seed}"
    run.model.register(cfg, params, batch, model)
    ring = run.make_ring(slots, batch)
    chunks = run.inputs.prefill_chunks(cfg, run.seed)
    nbytes = sum(a.nbytes for slot in ring for a in tensors(slot))
    run.log(f"weights, a ring of {slots} x {batch} frames "
            f"({nbytes / 1e6:.2f} MB) and {len(chunks)} prefill chunks "
            f"made in {time.perf_counter() - t0:.1f} s")

    t1 = time.perf_counter()
    prefix = run.workload.get("prefill_prefix", "pf_")
    pre = parse_launch(launch_line(
        {"launch": run.workload["prefill_launch"],
         "name": run.workload.get("name")}, cfg, mix, model=model))
    src, sink = pre[prefix + "src"], pre[prefix + "sink"]
    src.frames, src.pool_size = chunks, len(chunks)
    src.num_buffers = len(chunks)
    pre.start()
    try:
        deadline = time.perf_counter() + PULL_TIMEOUT_S
        done = 0
        while done < len(chunks):
            buf = sink.pull(timeout=0.5)
            if buf is None:
                if pre.error is not None:
                    raise BenchmarkError(f"prefill line: {pre.error}")
                if time.perf_counter() > deadline:
                    raise BenchmarkError(
                        f"prefill stopped after {done} of {len(chunks)} "
                        "chunks")
                continue
            run.model.fence(buf)
            done += 1
        tokens = sum(len(c[0]) for c in chunks)
        run.log(f"{len(chunks)} chunks ({tokens} tokens) prefilled in "
                f"{time.perf_counter() - t1:.1f} s")
        run.counters = _WithState(run.counters)
        pipe = parse_launch(run.launch(model=model))
        obs = replay.stream(run, pipe, ring, time.perf_counter())
    finally:
        pre.stop()
    # free the program's weights and state before the reference runs
    del ring, chunks, pipe, pre, params, src, sink
    run.model.unregister(model)
    return obs
