"""Token frames of the cached-decode cells: what a stream's resident
prompt is, and what the ring replays on top of it.

The configuration's ``serving`` object says how many streams the cache
holds (``streams``), how long a prompt may be (``prompt_tokens``: lowest
and highest; uniform from the seed, a draw from each of ``streams`` equal
strata so that every seed fills the cache alike), how many tokens one answer
has (``answer_tokens``: the ring's slots) and in what chunks a prompt is
prefilled (``prefill_chunk``).  Ids are drawn from the chip's slice of
the vocabulary, ``[vocab0, vocab0 + vocab_size)``.

Ring slot ``j`` is ``(ids[streams], positions[streams])``, both int32,
with ``positions[r] = prompt_len[r] + j``: one pass of the ring is one
answer of ``answer_tokens`` tokens to every resident prompt, and the
next pass is the next question on the same prompts.  Ids are forced from
the ring and not sampled.  The output check is handed a sampled frame's
``(id, position)`` and the served row only, so :func:`locate` finds the
``(slot, row)`` they came from: the ring is made so that no two frames
share both.
"""

from __future__ import annotations

import numpy as np


def _serving(cfg: dict) -> tuple:
    s = cfg["serving"]
    low, high = (int(v) for v in s["prompt_tokens"])
    return int(s["streams"]), low, high, int(s["answer_tokens"])


def _vocab(cfg: dict) -> tuple:
    return int(cfg.get("share", {}).get("vocab0", 0)), int(cfg["vocab_size"])


def prompts(cfg: dict, seed: int) -> list:
    """One int32 array of ids a stream: its resident prompt."""
    streams, low, high, _answer = _serving(cfg)
    v0, vocab = _vocab(cfg)
    rng = np.random.default_rng([int(seed), 29, streams])
    # uniform over [low, high], one draw from each of ``streams`` equal
    # strata, dealt to the streams in a seeded order: every seed's
    # prompts then add up to the same tokens within 0.2 %, where plain
    # draws differed by 15 % between seeds and took the step's cache
    # bytes (1.6 % of the rate) and the prefill's length with them
    edges = np.linspace(low, high + 1, streams + 1)
    lengths = rng.permutation(np.floor(
        edges[:-1] + rng.random(streams) * np.diff(edges)).astype(np.int64))
    return [rng.integers(v0, v0 + vocab, int(n), dtype=np.int32)
            for n in lengths]


def _ring_arrays(cfg: dict, seed: int, slots: int, batch: int):
    streams, _low, _high, answer = _serving(cfg)
    if (slots, batch) != (answer, streams):
        raise ValueError(
            f"the mix asks for a ring of {slots} x {batch}, the "
            f"configuration's serving object for {answer} x {streams}")
    v0, vocab = _vocab(cfg)
    lengths = np.array([len(p) for p in prompts(cfg, seed)], np.int64)
    rng = np.random.default_rng([int(seed), 31, slots, batch])
    ids = rng.integers(0, vocab, (slots, batch), dtype=np.int64)
    positions = lengths[None, :] + np.arange(slots, dtype=np.int64)[:, None]
    # no two frames with one (position, id): bump an id until it is free
    key = positions * vocab + ids
    while True:
        flat = key.reshape(-1)
        _vals, first, counts = np.unique(flat, return_index=True,
                                         return_counts=True)
        if (counts == 1).all():
            break
        dup = np.ones(flat.size, bool)
        dup[first] = False
        bumped = ids.reshape(-1)
        bumped[dup] = (bumped[dup] + 1) % vocab
        ids = bumped.reshape(slots, batch)
        key = positions * vocab + ids
    return (ids + v0).astype(np.int32), positions.astype(np.int32)


def make_ring(cfg: dict, mix: dict, seed: int, slots: int, batch: int) -> list:
    ids, positions = _ring_arrays(cfg, seed, slots, batch)
    return [(ids[j], positions[j]) for j in range(slots)]


def locate(cfg: dict, seed: int, frame_ids, frame_positions) -> list:
    """``[(slot, row)]`` of the sampled frames, and with them the
    history each was decoded on: ``prompt[row] + ring[0..slot][row]``."""
    streams, _low, _high, answer = _serving(cfg)
    ids, positions = _ring_arrays(cfg, seed, answer, streams)
    found = []
    for tok, pos in zip(np.asarray(frame_ids).reshape(-1),
                        np.asarray(frame_positions).reshape(-1)):
        where = np.argwhere((ids == tok) & (positions == pos))
        if len(where) != 1:
            raise ValueError(f"frame (id {tok}, position {pos}) is in the "
                             f"ring {len(where)} times")
        found.append((int(where[0][0]), int(where[0][1])))
    return found


def history(cfg: dict, seed: int, slot: int, row: int) -> np.ndarray:
    """Every id stream ``row`` has been fed up to ring slot ``slot``."""
    streams, _low, _high, answer = _serving(cfg)
    ids, _positions = _ring_arrays(cfg, seed, answer, streams)
    return np.concatenate([prompts(cfg, seed)[row], ids[:slot + 1, row]])


def prefill_chunks(cfg: dict, seed: int) -> list:
    """Every stream's prompt as the frames of the prefill line:
    ``(ids[chunk], slot[1], start[1])``, int32, a stream's chunks in
    order.  The last chunk of a prompt is padded with id ``vocab0``: the
    rows it writes beyond the prompt are overwritten by the answer's
    tokens before any step reads them."""
    chunk = int(cfg["serving"]["prefill_chunk"])
    v0 = _vocab(cfg)[0]
    frames = []
    for slot, prompt in enumerate(prompts(cfg, seed)):
        for start in range(0, len(prompt), chunk):
            ids = np.full(chunk, v0, np.int32)
            part = prompt[start:start + chunk]
            ids[:len(part)] = part
            frames.append((ids, np.array([slot], np.int32),
                           np.array([start], np.int32)))
    return frames


def cache_positions(cfg: dict) -> int:
    """Positions a stream's cache holds: the longest prompt in whole
    chunks (a padded last chunk writes that far) and one answer."""
    _streams, _low, high, answer = _serving(cfg)
    chunk = int(cfg["serving"]["prefill_chunk"])
    return max(-(-high // chunk) * chunk, high + answer)
