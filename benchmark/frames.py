"""Seeded input frames, made cheaply on the host.

``device_src`` stages host arrays only, and a long staged ring is what
fills HBM in a replay deployment, so a ring of gigabytes has to be made
in seconds: one buffer of 7-bit noise is drawn once, and every ring slot
adds its own seeded 7-bit coarse pattern (``block`` x ``block`` pixel
cells, one value per cell, frame and channel) in a single pass.  Frames
are distinct, full-range uint8, and carry low-frequency structure, so a
model's output depends on which frame it was given.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def coarse_block(size: int, want: int = 16) -> int:
    """The divisor of ``size`` nearest ``want``."""
    divisors = [d for d in range(1, size + 1) if size % d == 0]
    return min(divisors, key=lambda d: (abs(d - want), d))


def make_ring(seed: int, slots: int, batch: int, size: int) -> list:
    """``slots`` arrays (batch, size, size, 3) uint8 from ``seed``."""
    rng = np.random.default_rng([int(seed), slots, batch, size])
    blk = coarse_block(size)
    cells = size // blk
    noise = rng.integers(0, 128, (batch, cells, blk, cells, blk, 3),
                         dtype=np.uint8)
    coarse = rng.integers(0, 128, (slots, batch, cells, 1, cells, 1, 3),
                          dtype=np.uint8)

    def slot(k):
        out = np.empty(noise.shape, np.uint8)
        np.add(noise, coarse[k], out=out)
        return out.reshape(batch, size, size, 3)

    # numpy releases the interpreter lock inside the add
    with ThreadPoolExecutor(max_workers=8) as pool:
        return list(pool.map(slot, range(slots)))


def seed_key(seed: int):
    """A JAX key from any whole number up to well past 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)),
                              seed // (2 ** 31))
