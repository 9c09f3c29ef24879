"""uint8 camera frames, ``image_size`` a side: what a configuration that
names no ``inputs`` file is fed.  One array a slot, made by
``benchmark/frames.py`` from the seed, to the byte what the replay and
open-loop kinds made themselves before inputs were found by name."""

from benchmark.frames import make_ring as _make_ring


def make_ring(cfg: dict, mix: dict, seed: int, slots: int, batch: int) -> list:
    """``slots`` arrays (batch, size, size, 3) uint8 from ``seed``."""
    return _make_ring(seed, slots, batch, int(cfg["image_size"]))
