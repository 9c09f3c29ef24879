"""Frames fenced at the sink inside the window, per second, per chip."""


def read(obs: dict):
    return obs["frames"] / obs["window_s"] / obs["chips"]
