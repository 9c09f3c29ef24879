"""Kernels: the least time the chip could take for the traced windows
(the larger of operations / peak FLOP/s and compulsory bytes / peak B/s,
per chip) as a share of the device-busy time measured for them.

The frames are the filter program's executions that the trace holds
times the frames one execution carries (the replay's batch; a traffic
kind whose windows vary reports no ``frames_per_window`` and gets no
share), and the busy time is that of the span those executions cover:
both from ``benchmark/trace.py``'s one reading of the trace.
Operations and input/weight bytes come from ``benchmark/costs/<config>``
by the configuration's shapes; output bytes are what the sink was served
per frame.  Compulsory bytes are inputs + weights (once a window) +
outputs, so the share cannot pass 100 % by construction of the count.
"""


def bound(obs: dict):
    """(least seconds, 'compute' | 'memory') for the traced span."""
    trace, cost, peaks = obs["trace"], obs["cost"], obs["peaks"]
    windows = trace["windows"]
    frames = windows * obs["frames_per_window"]
    flops = frames * cost["flops_per_frame"]
    nbytes = (frames * (cost["in_bytes_per_frame"]
                        + obs.get("out_bytes_per_frame", 0.0))
              + windows * cost["weight_bytes"])
    chips = obs["chips"]
    t_compute = flops / (chips * peaks["peak_flops_bf16"])
    t_memory = nbytes / (chips * peaks["peak_hbm_bytes_per_s"])
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"


def read(obs: dict):
    trace = obs.get("trace")
    if not trace or not obs.get("peaks") or not trace.get("windows") \
            or not obs.get("frames_per_window"):
        return None
    least, which = bound(obs)
    print(f"[bench] roofline bound: {which}; least {least:.6f} s of "
          f"{trace['program_busy_s']:.6f} s busy", flush=True)
    return 100.0 * least / trace["program_busy_s"]
