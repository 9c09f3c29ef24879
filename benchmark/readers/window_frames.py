"""Window formation: frames per pool dispatch in the window, from the
pool entry's ``InvokeStats``."""


def read(obs: dict):
    pool = obs.get("pool")
    if not pool or not pool["dispatches"]:
        return None
    return pool["frames"] / pool["dispatches"]
