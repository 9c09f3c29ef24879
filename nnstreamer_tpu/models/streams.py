"""What a stream is to a token model, written once (``deepseek_v2.py``,
``smallthinker.py``, ``nemotron_h.py``, ``exaone_moe.py``,
``longcat_flash.py``, ``falcon_h1.py``, ``phi4_flash.py``): the seeded weights tests run on, the counters a
step adds to, the book of where each stream stands, and the table a
model registers with the ``jax-xla`` filter.  A model file keeps its
configuration, its layers, ``param_shapes`` with their roles,
``counter_units`` (what a row is IS the model) and its two entry
points; the grouped-query decode step and its caches are in
``attention.py``, latent attention in ``mla.py``, the Mamba-2 mixer
with its recurrent state and snapshots in ``mamba2.py``, the Mamba-1
mixer in ``mamba1.py``, the experts in
``moe.py`` (``Documentation/stateful-models.md``, "Adding a token
model").  The models import this module as ``stream``: ``streams`` is
their word for how many streams a state holds.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Mapping, Optional, Sequence

import numpy as np

try:
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover
    jax = jnp = None


# -- seeded weights -----------------------------------------------------------


def normal_vector(std: float) -> Callable:
    """A special role's law: ``N(0, std^2)`` in float32."""
    return lambda key, shape: std * jax.random.normal(key, shape, jnp.float32)


def seeded_params(shapes, key, dtype=None, ones: Sequence[str] = ("norm",),
                  halved: Sequence[str] = ("o", "down", "expert_down"),
                  special: Optional[Mapping[str, Callable]] = None):
    """Weights for a pytree of ``(shape, role)`` (a model's
    ``param_shapes``): leaf ``n`` is drawn from ``fold_in(key, n)``.  A
    role of ``ones`` is a gain of 1 (float32), one of ``special`` is
    ``special[role](key, shape)``, every other a matrix ``N(0, gain /
    fan_in)`` in ``dtype`` (bfloat16): ``fan_in`` the last axis but one
    (1 for ``embed``), ``gain`` a half for the ``halved`` roles, the
    residual branches' last matrices.  For tests and examples; a
    deployment loads its own."""
    dtype, special = dtype or jnp.bfloat16, special or {}
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[1], str))
    out = []
    for n, (shape, role) in enumerate(leaves):
        k = jax.random.fold_in(key, n)
        if role in ones:
            out.append(jnp.ones(shape, jnp.float32))
        elif role in special:
            out.append(special[role](k, shape))
        else:
            fan_in = 1 if role == "embed" else shape[-2]
            gain = 0.5 if role in halved else 1.0
            out.append((jax.random.normal(k, shape)
                        * (gain / fan_in) ** 0.5).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# -- counters -----------------------------------------------------------------


def zeros(names: Sequence[str]) -> dict:
    """The counters a state starts with: ``uint32`` scalars, one buffer
    each (the reader takes differences, so a wrap costs nothing)."""
    return {name: jnp.zeros((), jnp.uint32) for name in names}


def counters(state: dict) -> dict:
    return state["counters"]


def bump(old: dict, gained: dict) -> dict:
    """``old`` with what one step ``gained`` added, counter by counter
    in ``gained``'s order (a model's ``COUNTERS``)."""
    return {name: old[name] + jnp.asarray(gain).astype(jnp.uint32)
            for name, gain in gained.items()}


# -- the book: where each stream stands ---------------------------------------
#
# A state that no position addresses (a recurrent state, a ring shorter
# than a rewind) can serve a stream only where it stands.  Such a model
# keeps, beside its state, ``prompt_end [streams]`` (where the resident
# prompt ends: a step AT it answers anew), ``last [streams]`` (the
# position last fed, -1: nothing yet) and, where rows newer than the
# served position can do harm, ``newest [streams]`` (the newest position
# ever written).  A model whose rows are all addressed by position
# (``deepseek_v2.py``, ``smallthinker.py``, ``longcat_flash.py``) keeps
# no book and trusts the traffic.


def book(streams: int, newest: bool = False) -> dict:
    """The book's entries of a fresh state."""
    out = {"prompt_end": jnp.zeros((streams,), jnp.int32),
           "last": jnp.full((streams,), -1, jnp.int32)}
    if newest:
        out["newest"] = jnp.full((streams,), -1, jnp.int32)
    return out


def book_prefilled(state: dict, slot, end, newest=None) -> dict:
    """The book's entries after a prefill chunk that leaves stream
    ``slot`` at ``end`` tokens (chunks arrive in order, so the last one
    leaves ``prompt_end`` at the prompt's end); ``newest`` where the
    chunk wrote rows beyond its last real token (a padded chunk on a
    ring that takes its padding)."""
    out = {"prompt_end": state["prompt_end"].at[slot].set(end),
           "last": state["last"].at[slot].set(end - 1)}
    if "newest" in state:
        out["newest"] = state["newest"].at[slot].set(
            end - 1 if newest is None else newest)
    return out


def book_step(state: dict, positions, room=None):
    """A decode step at ``positions [B]``: ``(restore, fault, the
    book's new entries)``.  A stream at its ``prompt_end`` restores (it
    starts again from what the prompt left) and, where the book has a
    ``newest``, only while the newest row lies at most ``room`` ahead of
    it; any other must be at ``last + 1``, or it is a fault: counted,
    not guessed at."""
    restore = positions == state["prompt_end"]
    out = {"prompt_end": state["prompt_end"], "last": positions}
    if "newest" in state:
        restore = restore & (state["newest"] - positions <= room)
        out["newest"] = jnp.maximum(state["newest"], positions)
    fault = ~restore & (positions != state["last"] + 1)
    return restore, fault, out


# -- entries and registration -------------------------------------------------


@functools.lru_cache(maxsize=64)
def entries(cfg, decode: Callable, decode_shapes: tuple, prefill: Callable,
            prefill_shapes: tuple, init_state: Callable,
            counter_units: Callable, **sizes: int) -> Dict[str, Any]:
    """What ``register_stateful_model`` takes beside a name and the
    weights: the two entry points (``fn(cfg, params, state, *inputs)``)
    with their int32 input shapes, ``init_state(cfg, params, **sizes)``
    and the counters' reading.  Cached by its arguments, so that two
    sets of weights of one configuration and size share their
    programs (the filter keys a program by the entry's function)."""
    return {
        "entries": {
            "decode": (functools.partial(decode, cfg), list(decode_shapes),
                       np.int32),
            "prefill": (functools.partial(prefill, cfg), list(prefill_shapes),
                        np.int32)},
        "setup_entries": ("prefill",),
        "init_state": functools.partial(init_state, cfg, **sizes),
        "counters": counters,
        "counter_units": functools.partial(counter_units, cfg)}


def register(name: str, params, table: Dict[str, Any]) -> str:
    """Register ``params`` with a model's ``entries(...)`` as the
    stateful model ``name`` for ``tensor_filter framework=jax-xla
    model=<name>``: a filter whose negotiated input is the prefill
    entry's prefills, one whose input is the decode entry's decodes; two
    filters with one ``shared-tensor-filter-key`` work on one state."""
    from ..filters.jax_xla import register_stateful_model

    return register_stateful_model(name, params=params, **table)
