"""Device milliseconds a window spent in the stages of the fused filter
program whose name starts with one of ``starts`` and, where ``ends`` is
given, ends with one of ``ends``: ``trace["stage_s"]`` (device seconds
per ``nns.*`` stage over the traced capture, per chip;
``benchmark/stages.py``) summed over the matching stages, divided by the
filter program's executions in the same capture (``trace["windows"]``).

``layer_metrics/<metric>.json`` gives the arguments: ``starts``
``["nns.model/backbone"]`` is the backbone, ``starts`` ``["nns.model/layer"]``
with ``ends`` ``["/attn"]`` every layer's attention.  ``None`` without a
trace, without the program's text, or where no stage matches: a cell
whose program has no such stage reports nothing, never 0."""


def read(obs: dict, starts, ends=None):
    trace = obs.get("trace") or {}
    stages = trace.get("stage_s")
    if not stages or not trace.get("windows"):
        return None
    picked = [seconds for name, seconds in stages.items()
              if name.startswith(tuple(starts))
              and (not ends or name.endswith(tuple(ends)))]
    if not picked:
        return None
    return sum(picked) / trace["windows"] * 1e3
