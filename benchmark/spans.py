"""From the program's phase spans to numbers: which spans belong to the
traced run, a span's self time, and the sums the span readers report.

The program keeps its spans in memory (``nnstreamer_tpu/utils/profile.py``
``spans()``: name ``<element>/<phase>``, start and end in nanoseconds of
one clock, thread, window id, kind) and the readers under ``readers/``
ask for them after the run; one process runs one cell.  A program that
has no ``spans`` (a commit before the trace layer) gives ``None`` here
and the readers leave their metrics out.  The arithmetic is plain
tuples in, numbers out, so the tests check it on made-up spans.
"""

from __future__ import annotations

#: phases in which the host waits for the device or for the consumer
WAIT_PHASES = ("fence", "sample_fence", "render_wait")
#: the span that bounds one window: one executable call
WINDOW_PHASE = "dispatch"
#: parts of a program's load, each a set-up span of the filter
LOAD_PHASES = ("trace_lower", "load_or_compile", "first_call")
SLOW_NS = 50_000_000


def program_spans():
    """Every span the program kept, or None where it keeps none."""
    try:
        from nnstreamer_tpu.utils import profile
    except ImportError:
        return None
    read = getattr(profile, "spans", None)
    return read() if read is not None else None


def phase(span) -> str:
    return span.name.rsplit("/", 1)[-1] if "/" in span.name else ""


def is_wait(span) -> bool:
    return phase(span) in WAIT_PHASES


def last_capture(spans):
    """(begin, end, end of the capture before it or 0) of the newest
    ``trace/capture`` span, or None where no capture was made."""
    captures = [s for s in spans if s.name == "trace/capture"]
    if not captures:
        return None
    stops = [s.end_ns for s in spans if s.name == "trace/stop"
             and s.end_ns <= captures[-1].start_ns]
    return captures[-1].start_ns, captures[-1].end_ns, max(stops, default=0)


def captured(spans):
    """The per-window spans that began inside the newest capture."""
    found = last_capture(spans)
    if found is None:
        return None
    begin, end, _ = found
    return [s for s in spans
            if s.kind == "window" and begin <= s.start_ns <= end]


def self_ns(spans) -> list:
    """[(span, nanoseconds of it that no span nested in it on its own
    thread covers)]."""
    out = []
    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    for rows in by_thread.values():
        rows.sort(key=lambda s: (s.start_ns, -s.end_ns))
        stack = []
        for s in rows:
            while stack and stack[-1][0].end_ns <= s.start_ns:
                out.append(tuple(stack.pop()))
            if stack:
                stack[-1][1] -= s.end_ns - s.start_ns
            stack.append([s, s.end_ns - s.start_ns])
        out.extend(tuple(row) for row in stack)
    return out


def windows(spans) -> int:
    return sum(1 for s in spans if phase(s) == WINDOW_PHASE)


def innermost_slow(spans, since_ns: int) -> list:
    """The spans of ``SLOW_NS`` or more that began at or after
    ``since_ns`` and hold no other such span of their thread."""
    slow = [s for s in spans if s.kind in ("window", "slow")
            and s.start_ns >= since_ns and s.end_ns - s.start_ns >= SLOW_NS]
    return [s for s in slow if not any(
        o is not s and o.thread == s.thread
        and s.start_ns <= o.start_ns and o.end_ns <= s.end_ns
        for o in slow)]


def union_ns(spans) -> int:
    total, reach = 0, None
    for s in sorted(spans, key=lambda s: s.start_ns):
        if reach is None or s.start_ns > reach:
            total += s.end_ns - s.start_ns
            reach = s.end_ns
        elif s.end_ns > reach:
            total += s.end_ns - reach
            reach = s.end_ns
    return total
