"""Milliseconds a window that the program's phase spans of the traced
capture account for, by what ``what`` asks:

- ``host``: the self time of every span that is not a wait (what the
  streaming threads themselves did: element glue, input prep, placement,
  the executable call, demux, render);
- ``phases``: the summed duration of the spans whose phase is one of
  ``phases`` (``fence`` + ``sample_fence``: the host waiting for the
  device; ``place``: inputs put onto the program's devices or mesh).

Both divide by the number of ``<filter>/dispatch`` spans in the same
capture, so time and count are taken at one boundary.  ``None`` where
the program keeps no spans, no capture was made or no window ran in it;
0.0 where windows ran and the phase never did."""

from benchmark import spans as _spans


def read(obs: dict, what: str, phases=()):
    kept = _spans.program_spans()
    rows = _spans.captured(kept) if kept is not None else None
    if not rows:
        return None
    n = _spans.windows(rows)
    if not n:
        return None
    if what == "host":
        ns = sum(t for s, t in _spans.self_ns(rows) if not _spans.is_wait(s))
    else:
        ns = sum(s.end_ns - s.start_ns for s in rows
                 if _spans.phase(s) in phases)
    return ns * 1e-6 / n
