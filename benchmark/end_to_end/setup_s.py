"""Process start to window open: weights, frames, staging, pipeline
start, compilation (or the fetch from the compile cache) and warm-up."""


def read(obs: dict):
    return obs["setup_s"]
