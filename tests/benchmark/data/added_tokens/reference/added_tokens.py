"""Plain reference of the added token configuration: numpy float32 on
tables of its own from the seed.  ``frames`` is what went in, in a
slot's structure: (token ids, positions)."""

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _tables(cfg: dict, seed: int) -> dict:
    path = os.path.join(os.path.dirname(_HERE), "weights", "added_tokens.py")
    spec = importlib.util.spec_from_file_location("added_tokens_weights",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return {k: np.asarray(v, np.float32) for k, v in
            mod.make(cfg, seed).items()}


def _numbers(cfg, want, served) -> list:
    err = float(np.abs(np.asarray(served["sum"], np.float32) - want).max())
    return [{"name": "sum_abs_err", "value": err,
             "limit": float(cfg["limits"]["sum_abs_err"])}]


def _forward(cfg, seed, frames, tables=None) -> np.ndarray:
    tokens, positions = frames
    assert tokens.dtype == positions.dtype == np.int32
    t = tables or _tables(cfg, seed)
    x = t["tokens"][tokens[:, 0]] + t["positions"][positions[:, 0]]
    for w in t["mix"]:
        x = x + np.tanh(x @ w)
    return x.sum(axis=-1, keepdims=True)


def check(cfg: dict, seed: int, frames, served: dict) -> list:
    return _numbers(cfg, _forward(cfg, seed, frames), served)


def control(cfg: dict, seed: int, frames) -> list:
    """The tables rounded to float8_e4m3fn, put in the program's place."""
    import jax.numpy as jnp

    low = {k: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn), np.float32)
           for k, v in _tables(cfg, seed).items()}
    return _numbers(cfg, _forward(cfg, seed, frames),
                    {"sum": _forward(cfg, seed, frames, low)})
