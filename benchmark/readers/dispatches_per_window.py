"""Fusion pass: XLA program launches (``DISPATCH_STATS``, every site)
in the window per window completed: 1.0 when transform, filter and
decoder run as one program."""


def read(obs: dict):
    if not obs.get("windows"):
        return None
    return sum(obs["window"]["dispatch"].values()) / obs["windows"]
