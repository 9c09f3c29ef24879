"""Sink fence: milliseconds of the ``<sink>/fence`` spans of 50 ms or
more, from the start of the traced capture to the end of the run (the
window ``slow_host_ms`` reads), whose note says the host was late: when
the fence on window N-1 returned, window N was already computed, so the
chip had run dry while the host was away.  A slow fence noted the other
way (the next window still running: the device took that long) is logged
and not counted.  ``None`` where the program keeps no spans, no capture
was made, or its fences say nothing of who was late (a commit before
the note)."""

from benchmark import spans as _spans


def read(obs: dict):
    kept = _spans.program_spans()
    found = _spans.last_capture(kept) if kept is not None else None
    if found is None:
        return None
    from nnstreamer_tpu.utils import profile

    host_late = getattr(profile, "HOST_LATE", None)
    if host_late is None:
        return None
    slow = [s for s in _spans.innermost_slow(kept, found[0])
            if _spans.phase(s) == "fence"]
    for s in slow:
        print(f"[bench] slow fence {s.name}: "
              f"{(s.end_ns - s.start_ns) * 1e-6:.1f} ms, window {s.window}, "
              f"{s.note}", flush=True)
    return sum(s.end_ns - s.start_ns for s in slow
               if s.note == host_late) * 1e-6
