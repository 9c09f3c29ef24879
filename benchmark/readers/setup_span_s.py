"""Parts of ``setup_s``: seconds the program's set-up spans of the given
``phases`` cover between the capture before (or process start) and the
traced capture.  ``program_load_s`` reads ``trace_lower``,
``load_or_compile`` and ``first_call`` (one inside another counts once:
the union of their intervals); ``staging_s`` reads ``stage``.  ``None``
where the program keeps no spans, no capture was made or no such span
was kept."""

from benchmark import spans as _spans


def read(obs: dict, phases):
    kept = _spans.program_spans()
    found = _spans.last_capture(kept) if kept is not None else None
    if found is None:
        return None
    begin, _end, since = found
    rows = [s for s in kept if s.kind == "setup"
            and _spans.phase(s) in phases and since <= s.start_ns <= begin]
    if not rows:
        return None
    return _spans.union_ns(rows) * 1e-9
