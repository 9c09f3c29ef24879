"""Token frames of the K-EXAONE share's cached-decode cell: the resident
prompts, the ring's ids and positions and the sampled frames' histories
are those of ``deepseek_v2_share4.py`` (one file, found beside this
one); what differs is that every frame also carries the id that FOLLOWS
it, which the model's multi-token-prediction module reads.

Ring slot ``j`` is ``(ids[streams], next_ids[streams],
positions[streams])``: slot ``j``'s ``next_ids`` are slot ``j + 1``'s
``ids``, and the pass's last slot gets seeded ids of its own (the next
pass rewinds every stream to its prompt's end, so nothing follows it).
A prefill frame is ``(ids[chunk], next_ids[chunk], slot[1], start[1],
count[1])``: a window layer's ring is shorter than a chunk, so a padded
chunk has to say where it ends (the first ``count`` ids are real), and
the id after the prompt's last is the ring's first for that stream.
Ids are forced, not sampled and not fed back.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _tokens():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "deepseek_v2_share4.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_inputs_deepseek_v2_share4_for_kexaone", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_T = _tokens()
prompts, locate, history = _T.prompts, _T.locate, _T.history
cache_positions = _T.cache_positions


def _vocab(cfg: dict) -> tuple:
    return int(cfg.get("share", {}).get("vocab0", 0)), int(cfg["vocab_size"])


def _after_the_pass(cfg: dict, seed: int, streams: int) -> np.ndarray:
    """The ids that follow the ring's last slot, one a stream."""
    v0, vocab = _vocab(cfg)
    rng = np.random.default_rng([int(seed), 37, streams])
    return rng.integers(v0, v0 + vocab, streams, dtype=np.int32)


def make_ring(cfg: dict, mix: dict, seed: int, slots: int, batch: int) -> list:
    ring = _T.make_ring(cfg, mix, seed, slots, batch)
    ids = [slot[0] for slot in ring] + [_after_the_pass(cfg, seed, batch)]
    return [(ids[j], ids[j + 1], ring[j][1]) for j in range(slots)]


def next_history(cfg: dict, seed: int, slot: int, row: int) -> np.ndarray:
    """The id that follows each id of :func:`history` ``(slot, row)``:
    the history shifted by one, and after its last the ring's
    ``next_ids`` at that slot."""
    s = cfg["serving"]
    streams, answer = int(s["streams"]), int(s["answer_tokens"])
    fed = history(cfg, seed, slot, row)
    if slot + 1 < answer:
        after = _T.make_ring(cfg, {}, seed, answer, streams)[slot + 1][0][row]
    else:
        after = _after_the_pass(cfg, seed, streams)[row]
    return np.concatenate([fed[1:], [after]]).astype(np.int32)


def prefill_chunks(cfg: dict, seed: int) -> list:
    """Every stream's prompt as the frames of the prefill line, a
    stream's chunks in order.  The last chunk of a prompt is padded with
    id ``vocab0`` and says how many of its ids are real."""
    s = cfg["serving"]
    chunk, v0 = int(s["prefill_chunk"]), _vocab(cfg)[0]
    first = _T.make_ring(cfg, {}, seed, int(s["answer_tokens"]),
                         int(s["streams"]))[0][0]
    frames = []
    for slot, prompt in enumerate(prompts(cfg, seed)):
        follows = np.concatenate([prompt[1:], first[slot:slot + 1]])
        for start in range(0, len(prompt), chunk):
            part = prompt[start:start + chunk]
            ids, next_ids = (np.full(chunk, v0, np.int32) for _ in range(2))
            ids[:len(part)] = part
            next_ids[:len(part)] = follows[start:start + chunk]
            frames.append((ids, next_ids, np.array([slot], np.int32),
                           np.array([start], np.int32),
                           np.array([len(part)], np.int32)))
    return frames
