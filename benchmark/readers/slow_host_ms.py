"""Element runtime: milliseconds in host phases that took 50 ms or more
from the start of the traced capture to the end of the run.  The program
keeps such a span even when no capture is on; counted are the innermost
of them (a slow ``el_net/dispatch`` once, not again as ``el_net`` and
``el_norm`` around it), waits on the device or the consumer left out.
Set-up and warm-up lie before the capture and are out.  ``None`` where
the program keeps no spans or no capture was made."""

from benchmark import spans as _spans


def read(obs: dict):
    kept = _spans.program_spans()
    found = _spans.last_capture(kept) if kept is not None else None
    if found is None:
        return None
    slow = _spans.innermost_slow(kept, found[0])
    for s in slow:
        print(f"[bench] slow span {s.name}: "
              f"{(s.end_ns - s.start_ns) * 1e-6:.1f} ms, window {s.window}"
              f"{', a wait' if _spans.is_wait(s) else ''}", flush=True)
    return sum(s.end_ns - s.start_ns for s in slow
               if not _spans.is_wait(s)) * 1e-6
