"""Filter program: device-busy time (union of device-operation
intervals) between the start of the first and the end of the last
execution of the filter program in the trace, per execution, each taken
per chip and averaged over the cell's chips.  The filter program is the
one with most device time on the trace's ``XLA Modules`` line, every
bucket shape of it; time and count are read from the same span of the
same trace (``benchmark/trace.py``)."""


def read(obs: dict):
    trace = obs.get("trace")
    if not trace or not trace.get("windows"):
        return None
    return trace["program_busy_s"] / trace["windows"] * 1e3
