"""What a cell's frames are, found by name: ``benchmark/inputs/<name>.py``
has ``make_ring(cfg, mix, seed, slots, batch) -> list`` of ``slots``
seeded ring slots, each one array or a tuple of arrays (one a tensor of
the frame) whose first axis is the ``batch`` frames of a window.  A
configuration names its file under ``inputs``; one that names none gets
``image_frames``.  The traffic kind says how many slots and frames a
slot (its mix's ring and batch); the file says what a frame is, from the
configuration and, where it needs them, further parameters of the mix
(lengths, a vocabulary slice).  The same seed gives the same bytes."""

import numpy as np


def tensors(slot) -> tuple:
    """The arrays of one ring slot (or of one sampled set of frames)."""
    return tuple(slot) if isinstance(slot, (tuple, list)) else (slot,)


def sampled(ring: list, picks):
    """Row ``r`` of slot ``k`` for every ``(k, r)`` of ``picks``, stacked
    in a slot's own structure: one array where a slot is one, else a
    tuple of one array a tensor.  What ``reference.check`` is handed as
    the frames that went in."""
    rows = [tuple(a[r] for a in tensors(ring[k])) for k, r in picks]
    columns = tuple(np.stack(column) for column in zip(*rows))
    return columns if isinstance(ring[0], (tuple, list)) else columns[0]
