"""What of ``setup_s`` no set-up span of the program names.  The run
from process start (the harness's ``T_PROCESS_START``) to window open
(``obs["setup_s"]`` later) is cut by the union of every set-up span the
program kept, all threads, each clipped to the run:

- ``part="before_program"`` (``setup_before_program_s``): process start
  to the start of the first set-up span, which is the application's
  first call into the program (``<model>/register``): the interpreter,
  jax, the TPU client, the seeded weights.  The program shortens none
  of it.
- ``part="unnamed"`` (``setup_unnamed_s``): from there to window open,
  the seconds under no set-up span: the application's own work between
  its calls into the program (the ring, the prefill chunks, the heap
  settled and the buffers fenced before the window opens) plus whatever
  the program still fails to name.

The two and the union of the spans add up to ``setup_s``
(:func:`partition`).  ``unnamed`` logs every stretch of ``LOG_S`` or
more with the spans before and after it, every stretch of
``INSIDE_LOG_S`` or more of a ``<pipeline>/start`` or
``<pipeline>/first_window`` span that no other span names, and how many
set-up spans the program's bounded list pushed out (a sum over a list
that lost rows is short).

The origin is read from the harness module that is already loaded: under
``python3 -m benchmark.run`` that is ``__main__``, and importing
``benchmark.run`` anew would execute it again and give a later origin.
``None`` where no origin is found, the program keeps no spans, or it
kept no set-up span inside the run."""

import sys

from benchmark import spans as _spans

LOG_S = 0.5
INSIDE_LOG_S = 0.25
#: the program's spans that hold a whole phase of set-up: a stretch of
#: one that nothing else names is as good as unnamed
ROOT_PHASES = ("start", "first_window")


def origin_s():
    for name in ("__main__", "benchmark.run"):
        t = getattr(sys.modules.get(name), "T_PROCESS_START", None)
        if t is not None:
            return float(t)
    return None


def gaps(rows, begin_ns: int, end_ns: int) -> list:
    """[(start, end, name before, name after)] of the stretches of
    ``[begin_ns, end_ns]`` that no span of ``rows`` covers; the first
    has no name before it, the last none after."""
    out, reach, last = [], begin_ns, None
    for s in sorted(rows, key=lambda s: s.start_ns):
        if s.end_ns <= reach or s.start_ns >= end_ns:
            continue
        if s.start_ns > reach:
            out.append((reach, s.start_ns, last, s.name))
        reach, last = s.end_ns, s.name
    if reach < end_ns:
        out.append((reach, end_ns, last, None))
    return out


def partition(rows, begin_ns: int, end_ns: int):
    """(before, unnamed, named, the unnamed stretches) in nanoseconds;
    ``None`` where no span lies inside the run."""
    found = gaps(rows, begin_ns, end_ns)
    if found and found[0][2] is None and found[0][3] is None:
        return None                         # one gap, end to end
    before = 0
    if found and found[0][2] is None:
        before = found[0][1] - found[0][0]
        found = found[1:]
    unnamed = sum(b - a for a, b, _p, _n in found)
    named = end_ns - begin_ns - before - unnamed
    return before, unnamed, named, found


def inside_roots(rows, begin_ns: int, end_ns: int) -> list:
    """[(root, start, end, name before, name after)]: the stretches of
    each root span that no span other than a root covers."""
    others = [s for s in rows if _spans.phase(s) not in ROOT_PHASES]
    out = []
    for root in rows:
        if _spans.phase(root) not in ROOT_PHASES:
            continue
        a, b = max(root.start_ns, begin_ns), min(root.end_ns, end_ns)
        if a < b:
            out += [(root.name,) + g for g in gaps(others, a, b)]
    return out


def read(obs: dict, part: str):
    kept = _spans.program_spans()
    origin = origin_s()
    if kept is None or origin is None:
        return None
    begin = int(origin * 1e9)
    end = begin + int(obs["setup_s"] * 1e9)
    rows = [s for s in kept if s.kind == "setup" and s.end_ns > begin
            and s.start_ns < end]
    cut = partition(rows, begin, end)
    if cut is None:
        return None
    before, unnamed, named, stretches = cut
    if part == "before_program":
        return before * 1e-9
    first = min(rows, key=lambda s: s.start_ns).name
    print(f"[bench] setup_s {obs['setup_s']:.3f} = {before * 1e-9:.3f} "
          f"before the program's first span ({first}) + {named * 1e-9:.3f} "
          f"under {len(rows)} set-up spans + {unnamed * 1e-9:.3f} unnamed",
          flush=True)
    for a, b, prev, nxt in stretches:
        if b - a >= LOG_S * 1e9:
            print(f"[bench] unnamed stretch: {(b - a) * 1e-9:.3f} s from "
                  f"{(a - begin) * 1e-9:.3f} s, after {prev}, before "
                  f"{nxt or 'window open'}", flush=True)
    for root, a, b, prev, nxt in inside_roots(rows, begin, end):
        if b - a >= INSIDE_LOG_S * 1e9:
            print(f"[bench] inside {root}: {(b - a) * 1e-9:.3f} s from "
                  f"{(a - begin) * 1e-9:.3f} s that no span names, after "
                  f"{prev or 'its start'}, before {nxt or 'its end'}",
                  flush=True)
    from nnstreamer_tpu.utils import profile

    dropped = getattr(profile, "spans_dropped", None)
    if dropped is not None:
        print(f"[bench] spans the program's lists pushed out: {dropped()}",
              flush=True)
    return unnamed * 1e-9
