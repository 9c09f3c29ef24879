"""Token frames of the Nemotron-3-Nano share's cached-decode cell: the
resident prompts, the ring and the sampled frames' histories are those
of ``deepseek_v2_share4.py`` (one file, found beside this one); what
differs is the prefill line's frame.

A state-space layer's state is overwritten by every token it is fed and
no position addresses it, so a padded chunk has to say where it ends:
:func:`prefill_chunks` gives ``(ids[chunk], slot[1], start[1],
count[1])``, the first ``count`` ids real.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _tokens():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "deepseek_v2_share4.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_inputs_deepseek_v2_share4_for_nemotron3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_T = _tokens()
prompts, make_ring, locate, history = (
    _T.prompts, _T.make_ring, _T.locate, _T.history)
cache_positions = _T.cache_positions


def prefill_chunks(cfg: dict, seed: int) -> list:
    """Every stream's prompt as the frames of the prefill line, a
    stream's chunks in order.  The last chunk of a prompt is padded with
    id ``vocab0`` and says how many of its ids are real."""
    chunk = int(cfg["serving"]["prefill_chunk"])
    v0 = int(cfg.get("share", {}).get("vocab0", 0))
    frames = []
    for slot, prompt in enumerate(prompts(cfg, seed)):
        for start in range(0, len(prompt), chunk):
            part = prompt[start:start + chunk]
            ids = np.full(chunk, v0, np.int32)
            ids[:len(part)] = part
            frames.append((ids, np.array([slot], np.int32),
                           np.array([start], np.int32),
                           np.array([len(part)], np.int32)))
    return frames
