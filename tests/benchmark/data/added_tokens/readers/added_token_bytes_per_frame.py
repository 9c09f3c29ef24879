"""Bytes a frame that went in: a reader of the added cell alone."""


def read(obs: dict):
    return obs["cost"]["in_bytes_per_frame"]
