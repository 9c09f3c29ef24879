"""Element runtime: milliseconds of the timed window that the program's
``Pause`` records of the given ``causes`` cover.  The program keeps one
``Pause`` a per-window span of 50 ms or more, capture or not
(``nnstreamer_tpu/utils/profile.py`` ``pauses()``): the span's two ends,
the CPU time the thread and the process gained meanwhile, the
collections that overlap it, and the one-word cause those name
(``gc``, ``on_cpu``, else ``unexplained``).  Counted are the
innermost of a thread (a slow fence once, not again as the chain spans
around it), clipped to the window ``[T_PROCESS_START + setup_s,
+ window_s]``; a pause that overlaps the profiler's own ``trace/start``
or ``trace/stop`` is the profiler's doing, logged and left out, so that
a traced run's reading means the stream's own pauses.  Every pause is
logged once a process with its cause and every delta, and so is how
many pauses the program's bounded list pushed out.  ``None`` where the
program keeps no pauses (a commit before the ledger) or no origin is
found."""

from benchmark import spans as _spans
from benchmark.readers import setup_gap_s as _gap

_SAID: set = set()


def innermost(pauses) -> list:
    """[(pause, names of the pauses of its thread that hold it)] of the
    pauses that hold no other of their thread."""
    out = []
    for p in pauses:
        same = [o for o in pauses if o is not p and o.thread == p.thread]
        if any(p.start_ns <= o.start_ns and o.end_ns <= p.end_ns
               for o in same):
            continue
        out.append((p, [o.name for o in same if o.start_ns <= p.start_ns
                        and p.end_ns <= o.end_ns]))
    return out


def _key(p) -> tuple:
    return p.name, p.thread, p.start_ns, p.end_ns


def _overlaps(p, spans) -> bool:
    return any(s.start_ns < p.end_ns and p.start_ns < s.end_ns
               for s in spans)


def _delta_text(deltas: dict) -> str:
    return ", ".join(
        f"{key[:-3]} {value * 1e-6:.1f} ms" if key.endswith("_ns")
        else f"{key} {value}" for key, value in sorted(deltas.items()))


def _say(profile, begin: int, end: int, rows: list, profilers: set) -> None:
    if "dropped" not in _SAID:
        _SAID.add("dropped")
        print("[bench] pauses the program's list pushed out: "
              f"{getattr(profile, 'pauses_dropped', int)()}", flush=True)
    for p, around in rows:
        key = _key(p)
        if key in _SAID:
            continue
        _SAID.add(key)
        where = "the profiler's own, left out" if key in profilers \
            else "in the window" if p.end_ns > begin and p.start_ns < end \
            else "outside the window"
        age = "no baseline" if p.baseline_age_ns is None \
            else f"baseline {p.baseline_age_ns * 1e-6:.1f} ms before"
        print(f"[bench] pause {p.name}: "
              f"{(p.end_ns - p.start_ns) * 1e-6:.1f} ms at "
              f"{(p.start_ns - begin) * 1e-9:+.3f} s of the window "
              f"({where}), window {p.window}, note {p.note}; cause "
              f"{p.cause}; {age}; {_delta_text(p.deltas)}; inside "
              f"{around or 'no other slow span'}", flush=True)


def read(obs: dict, causes):
    try:
        from nnstreamer_tpu.utils import profile
    except ImportError:
        return None
    kept = getattr(profile, "pauses", None)
    origin = _gap.origin_s()
    if kept is None or origin is None:
        return None
    begin = int((origin + obs["setup_s"]) * 1e9)
    end = begin + int(obs["window_s"] * 1e9)
    own = [s for s in _spans.program_spans() or ()
           if s.name in ("trace/start", "trace/stop")]
    rows = innermost(kept())
    profilers = {_key(p) for p, _around in rows if _overlaps(p, own)}
    _say(profile, begin, end, rows, profilers)
    return sum(max(0, min(p.end_ns, end) - max(p.start_ns, begin))
               for p, _around in rows
               if p.cause in causes and _key(p) not in profilers) * 1e-6
