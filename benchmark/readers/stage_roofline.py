"""Kernels: the least time the chip could take for the compulsory bytes
of some stages of the fused filter program (bytes / peak B/s; the
stages named here are bound by what they read and write) as a share of
the device time measured for them.

The stages are picked as ``stage_ms_per_window`` picks them (``starts``,
``ends``) and their device seconds are ``trace["stage_s"]`` summed over
them.  Compulsory bytes of a step are added up from what
``layer_metrics/<metric>.json`` names:

``cost_bytes``   keys of ``costs/<config>`` read once a step whatever it
                 is fed (a layer kind's weights);
``counters``     pairs ``[counter, cost key or null]``: a counter of the
                 program's state over the window by the step
                 (``state_counter_ratio``), times the cost key's bytes
                 where one is given (``experts_touched`` x
                 ``expert_bytes``), else bytes already (``ssm_bytes``).

``None`` without a trace, the program's text, peaks, one of the
counters, or such a stage (a program that has no such layer)."""


def read(obs: dict, starts, ends=None, cost_bytes=(), counters=()):
    trace = obs.get("trace") or {}
    state = (obs.get("window") or {}).get("state") or {}
    stages, steps = trace.get("stage_s"), state.get("steps", 0)
    if not stages or not trace.get("windows") or not obs.get("peaks") \
            or steps <= 0 or any(name not in state for name, _ in counters):
        return None
    seconds = sum(s for name, s in stages.items()
                  if name.startswith(tuple(starts))
                  and (not ends or name.endswith(tuple(ends))))
    if seconds <= 0:
        return None
    cost = obs["cost"]
    nbytes = sum(cost[key] for key in cost_bytes) + sum(
        state[name] / steps * (cost[key] if key else 1.0)
        for name, key in counters)
    least = nbytes / obs["peaks"]["peak_hbm_bytes_per_s"] * trace["windows"]
    print(f"[bench] stages {'|'.join(ends or starts)}: {nbytes / 1e9:.3f} GB "
          f"a step, least {least:.6f} s of {seconds:.6f} s in them",
          flush=True)
    return 100.0 * least / seconds
