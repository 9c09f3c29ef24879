"""Kernels: the least time the chip could take for the decode steps the
trace holds (the larger of operations / peak FLOP/s and compulsory bytes
/ peak B/s) as a share of the device-busy time measured for them, for a
model that routes NOTHING: ``decode_step_roofline``'s floor without its
expert terms, so the same share of the whole step (that reader indexes
the ``experts_touched`` and ``expert_hits`` counters and the
``expert_bytes`` cost, which a dense model has no business keeping at
zero to please it).

Compulsory bytes of a step are a floor no correct program can beat
(``costs/<config>``): every weight once, the K/V rows up to each
stream's position in every layer (``cache_bytes_read``), a token's
inputs with the row it writes and its stream's recurrent state read and
written (``in_bytes_per_frame``), and the served outputs.  Operations
likewise, with attention by the cached row.  The steps are the filter
program's executions in the trace; the per-step means come from the
window's counters, which the filter samples at one instant each
(``state_counter_ratio``).  ``None`` without a trace, peaks or counters
(a program that lacks the model keeps none).
"""


def read(obs: dict):
    trace = obs.get("trace")
    state = (obs.get("window") or {}).get("state") or {}
    steps = state.get("steps", 0)
    if not trace or not trace.get("windows") or not obs.get("peaks") \
            or steps <= 0 or "cache_bytes_read" not in state:
        return None
    cost, peaks, batch = obs["cost"], obs["peaks"], obs["batch"]
    rows = state["cache_bytes_read"] / steps / cost["cache_row_bytes"]
    nbytes = (cost["weight_bytes"] + state["cache_bytes_read"] / steps
              + batch * (cost["in_bytes_per_frame"]
                         + cost["out_bytes_per_frame"]))
    flops = batch * cost["flops_per_frame"] \
        + rows * cost["flops_per_cache_row"]
    t_memory = nbytes / peaks["peak_hbm_bytes_per_s"]
    t_compute = flops / peaks["peak_flops_bf16"]
    least = max(t_memory, t_compute) * trace["windows"]
    which = "memory" if t_memory >= t_compute else "compute"
    print(f"[bench] dense decode step floor: {which}; {nbytes / 1e9:.3f} GB "
          f"and {flops / 1e9:.1f} GFLOP a step, least {least:.6f} s of "
          f"{trace['program_busy_s']:.6f} s busy", flush=True)
    return 100.0 * least / trace["program_busy_s"]
