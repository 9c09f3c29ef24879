"""Token windows: a slot is (token ids, positions), two int32 arrays of
``batch`` rows, the ids drawn from the chip's slice of the vocabulary."""

import numpy as np


def make_ring(cfg: dict, mix: dict, seed: int, slots: int, batch: int) -> list:
    rng = np.random.default_rng([int(seed), slots, batch])
    tokens = rng.integers(0, int(cfg["vocab_size"]), (slots, batch, 1),
                          dtype=np.int32)
    positions = rng.integers(0, int(cfg["max_position_embeddings"]),
                             (slots, batch, 1), dtype=np.int32)
    return [(tokens[k], positions[k]) for k in range(slots)]
