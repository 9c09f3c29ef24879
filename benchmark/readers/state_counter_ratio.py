"""A counter a stateful model keeps about its own state
(``nnstreamer_tpu/utils/stats.py`` ``STATE_STATS``), over the window, by
the step or by the frame: ``cache_bytes_per_frame`` (``cache_bytes_read``
a frame), ``expert_hits_per_frame`` (a frame and an expert layer),
``experts_touched_share`` (``experts_touched`` a step, as a share of
the expert slots held): ``of_cost`` names the key of ``costs/<config>``
the ratio is divided by.

Counter and step count are read by the filter at one instant (its
stats-sample cadence), so their ratio is exact over the steps between
the first and the last sample of the window.  ``None`` where the program
keeps no such counters or no step was sampled in the window."""


def read(obs: dict, counter: str, per: str, of_cost=None):
    state = (obs.get("window") or {}).get("state") or {}
    steps = state.get("steps", 0)
    if steps <= 0 or counter not in state:
        return None
    value = state[counter] / steps
    if per == "frame":
        value /= obs["batch"]
    if of_cost is not None:
        value /= obs["cost"][of_cost]
    return value
