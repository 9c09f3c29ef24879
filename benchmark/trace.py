"""From a profiler trace to numbers: device busy time, idle share, the
device operations that took most time, and the longest idle gaps by what
the host was doing.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load_xplane` reads it
with ``jax.profiler.ProfileData`` into plain Python (planes of lines of
``(name, start_ns, duration_ns)`` events) and :func:`reduce_trace` does
the arithmetic on that plain form, so a test checks it on a synthetic
trace.  A device plane is one whose name starts with ``/device:TPU:``;
its operations are the events of its ``XLA Ops`` line.  Host element
spans are the ``TraceAnnotation`` events that ``runtime/element.py``
writes under each element's name while a trace is active; the cell's
launch line names its elements with a common prefix so that they are
found.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import os
import re
import time

from benchmark.stages import stage_seconds

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
NO_ELEMENT = "no element running"


@contextlib.contextmanager
def quiet_pipeline_trace(log_dir: str):
    """The program's ``pipeline_trace`` (it starts ``jax.profiler`` and
    switches the per-element ``TraceAnnotation`` spans on) with the
    Python tracer off, so that the trace of a hundred threads stays
    small.  ``pipeline_trace`` takes no profiler options, so they are
    bound onto ``jax.profiler.start_trace`` for the length of the call."""
    import jax

    from nnstreamer_tpu.utils.profile import pipeline_trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    plain = jax.profiler.start_trace
    jax.profiler.start_trace = functools.partial(
        plain, profiler_options=options)
    try:
        with pipeline_trace(log_dir) as where:
            jax.profiler.start_trace = plain
            yield where
    finally:
        jax.profiler.start_trace = plain


def trace_seconds(run) -> float:
    return min(float(run.mix.get("trace_seconds", 3.0)),
               max(run.seconds - 1.5, 0.5))


def trace_steady_window(run, t_start: float) -> None:
    """Trace ``trace_seconds`` of the steady window from ``t_start``
    (``time.perf_counter``) on.  Only the capture happens here: the
    reduction (:func:`reduce_run`) waits until the pipelines have stopped,
    so that its Python does not compete with the stream for the
    interpreter."""
    wait = t_start - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    with quiet_pipeline_trace(run.out_dir):
        time.sleep(trace_seconds(run))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str, element_prefix: str = "",
                device_plane: str = DEVICE_PLANE,
                ops_line: str = OPS_LINE) -> list:
    """[{name, lines: [{name, events: [(name, start_ns, dur_ns)]}]}].
    Kept are the operation lines of device planes (every event) and, of
    every other line, the events whose name starts with
    ``element_prefix``: a host trace of a hundred threads is large and
    nothing else of it is read."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(device_plane)
        if not device and not plane.name.startswith(HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            ops = device and (line.name.startswith(ops_line)
                              or line.name == MODULES_LINE)
            events = []
            for ev in line.events:
                name = ev.name
                if not ops and not (element_prefix
                                    and name.startswith(element_prefix)):
                    continue
                events.append((name, float(ev.start_ns),
                               float(ev.duration_ns)))
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


_OP = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def short_op_name(name: str) -> str:
    """An HLO instruction's name and result shape, without its operands:
    the profiler names a device operation by its whole instruction."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:80]


def union(intervals: list) -> list:
    """Merged, sorted (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _attribute(gap, spans) -> str:
    """The element span that covers most of ``gap``; of equals, the
    innermost (shortest)."""
    a, b = gap
    best, best_key = NO_ELEMENT, (0.0, 0.0)
    for name, start, dur in spans:
        overlap = min(b, start + dur) - max(a, start)
        if overlap <= 0:
            continue
        key = (overlap, -dur)
        if key > best_key:
            best, best_key = name, key
    return best


def _clipped(merged: list, span) -> float:
    """Nanoseconds of the merged intervals that lie inside ``span``."""
    a, b = span
    return sum(max(0.0, min(b, end) - max(a, start)) for start, end in merged)


def reduce_trace(planes: list, chips: int, element_prefix: str = "",
                 device_plane: str = DEVICE_PLANE,
                 ops_line: str = OPS_LINE,
                 program_event: str | None = None) -> dict:
    """Device busy seconds (union of operation intervals, mean over the
    ``chips`` device planes that ran anything), the traced window (first
    operation start to last operation end over all chips), the ten
    operations with most summed time, and the ten longest idle gaps of
    the first chip by element span.

    The filter program is the one with most device time on the first
    chip's ``XLA Modules`` line (every shape of it).  ``windows`` is the
    number of its executions the trace holds and ``program_busy_s`` the
    device-busy seconds between the start of the first and the end of
    the last of them, each taken per chip from that chip's own lines and
    averaged: time and count come from one span of one source."""
    planes = sorted(planes, key=lambda p: p["name"])
    per_chip, programs, t_min, t_max = [], [], None, None
    op_seconds: dict = {}
    spans = []
    for plane in planes:
        device = plane["name"].startswith(device_plane)
        intervals, runs = [], {}
        for line in plane["lines"]:
            if device and line["name"].startswith(ops_line):
                for name, start, dur in line["events"]:
                    intervals.append((start, start + dur))
                    if name == program_event:
                        runs.setdefault(name, []).append((start, start + dur))
                    op = short_op_name(name)
                    op_seconds[op] = op_seconds.get(op, 0.0) + dur * 1e-9
            elif device and line["name"] == MODULES_LINE:
                for name, start, dur in line["events"]:
                    runs.setdefault(name.split("(")[0], []).append(
                        (start, start + dur))
            elif element_prefix:
                spans.extend(ev for ev in line["events"]
                             if ev[0].startswith(element_prefix))
        if not intervals:
            continue
        merged = union(intervals)
        per_chip.append(merged)
        programs.append(runs)
        t_min = merged[0][0] if t_min is None else min(t_min, merged[0][0])
        t_max = merged[-1][1] if t_max is None else max(t_max, merged[-1][1])
    if not per_chip:
        raise ValueError("the trace holds no device operation")
    if len(per_chip) < chips:
        raise ValueError(f"the trace holds operations of {len(per_chip)} "
                         f"chip(s), the cell uses {chips}")
    per_chip, programs = per_chip[:chips], programs[:chips]
    busy = [sum(b - a for a, b in merged) * 1e-9 for merged in per_chip]
    first = per_chip[0]
    gaps = sorted(((b[0] - a[1], (a[1], b[0]))
                   for a, b in zip(first, first[1:])), reverse=True)[:10]
    by_what: dict = {}
    for length, gap in gaps:
        what = _attribute(gap, spans)
        by_what[what] = by_what.get(what, 0.0) + length * 1e-9
    n = len(per_chip)
    ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
    main = max(programs[0], default=None, key=lambda name: sum(
        b - a for a, b in programs[0][name]))
    counts, program_busy = [], []
    for merged, runs in zip(per_chip, programs):
        runs = runs.get(main)
        if not runs:         # a chip whose lines do not name the program
            continue
        span = (min(a for a, _ in runs), max(b for _, b in runs))
        counts.append(len(runs))
        program_busy.append(_clipped(merged, span) * 1e-9)
    return {
        "programs": {name: len(runs) for name, runs in programs[0].items()},
        "main_program": main,
        "windows": sum(counts) / len(counts) if counts else None,
        "program_busy_s": (sum(program_busy) / len(program_busy)
                           if counts else None),
        "busy_s": sum(busy) / n,
        "busy_s_per_chip": busy,
        "window_s": (t_max - t_min) * 1e-9,
        "device_ops": [[name, seconds / n] for name, seconds in ops],
        "idle_gaps": sorted(([what, seconds]
                             for what, seconds in by_what.items()),
                            key=lambda kv: -kv[1]),
        "longest_gap_s": gaps[0][0] * 1e-9 if gaps else 0.0,
    }


def reduce_run(run, program_text: str | None = None) -> dict:
    """The reduction of the trace a run has just written under
    ``run.out_dir``.  On the chip the device planes are the TPU's; the
    CPU rehearsal (``rehearsal=True``) reads XLA's host thread-pool
    lines in their place so that the whole path can be driven.

    With ``program_text`` (the filter program's optimised HLO,
    ``benchmark/stages.py`` ``program_text``) the result also holds
    ``stage_s``: device seconds per ``nns.*`` stage of the fused program
    over the whole capture, per chip, which sum to ``busy_s``."""
    prefix = run.workload.get("element_prefix", "el_")
    if run.on_chip:
        where = {"device_plane": DEVICE_PLANE, "ops_line": OPS_LINE}
        chips = run.chips
    else:
        where = {"device_plane": "/host:CPU", "ops_line": "tf_XLA",
                 "program_event": "ThunkExecutor::Execute"}
        chips = 1
    planes = load_xplane(find_xplane(run.out_dir), prefix,
                         where["device_plane"], where["ops_line"])
    out = reduce_trace(planes, chips, prefix, **where)
    out["stage_s"] = None if program_text is None else stage_seconds(
        planes, program_text, chips, where["device_plane"],
        where["ops_line"])
    return out


def describe(path: str, top: int = 8) -> None:
    """Print the planes, lines and commonest event names of a trace:
    what to look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            counts: dict = {}
            total = 0.0
            for ev in line.events:
                counts[ev.name] = counts.get(ev.name, 0) + 1
                total += ev.duration_ns
            names = sorted(counts.items(), key=lambda kv: -kv[1])[:top]
            print(f"   LINE {line.name!r} events {sum(counts.values())} "
                  f"sum {total * 1e-9:.4f} s {names}")
