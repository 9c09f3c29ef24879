"""Seeded, well-conditioned weights for the SmallThinker stage, laid out
as the pytree ``nnstreamer_tpu/models/smallthinker.py`` consumes and as
``benchmark/reference/smallthinker_21b_stage8.py`` reads: matrices in
bf16 (the type they are served in), norm gains in float32.

A part (the embedding, one layer, the head) is made by itself from the
seed and its own index, leaf by leaf, one jitted call a distinct leaf
shape: the program asks for all of them (:func:`make`, 7.9 GB on the
device at the cell's size), the reference for one layer at a time
(:func:`make_part`), and both get the same values because both run the
same calls.  The generator is ``rbg`` (XLA's ``RngBitGenerator``): a
leaf of 126 M values is drawn in place, where threefry would hold
gigabytes of bits beside it.

Gains are in the configuration file under ``init``: every matrix is
N(0, gain / fan_in).
"""

from __future__ import annotations

import functools

import numpy as np


def shapes(cfg: dict) -> dict:
    """``{part: pytree of (shape, role)}`` from the configuration's own
    keys: ``embed``, ``layer00`` .., ``head``."""
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    e, f = int(cfg["moe_num_primary_experts"]), int(cfg["moe_ffn_hidden_size"])
    vocab = int(cfg["vocab_size"])
    parts = {"embed": {"embed": ((vocab, h), "embed")}}
    for i in range(int(cfg["num_hidden_layers"])):
        parts[f"layer{i:02d}"] = {
            "attn_norm": ((h,), "norm"), "ffn_norm": ((h,), "norm"),
            "attn": {"q": ((h, nh * d), "q"), "k": ((h, nkv * d), "k"),
                     "v": ((h, nkv * d), "v"), "o": ((nh * d, h), "o")},
            "moe": {"router": ((h, e), "router"),
                    "experts": {"gate": ((e, h, f), "gate"),
                                "up": ((e, h, f), "up"),
                                "down": ((e, f, h), "expert_down")}}}
    parts["head"] = {"final_norm": ((h,), "norm"),
                     "head": ((h, vocab), "head")}
    return parts


@functools.lru_cache(maxsize=64)
def _leaf_maker(shape: tuple, std: float, vector: bool):
    import jax
    import jax.numpy as jnp

    if vector:
        return jax.jit(lambda key: 1.0 + 0.1 * jax.random.uniform(
            key, shape, jnp.float32, -1.0, 1.0))
    return jax.jit(lambda key: (jax.random.normal(key, shape, jnp.float32)
                                * std).astype(jnp.bfloat16))


def _key(seed: int, part: int):
    import jax

    seed = int(seed)
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed // (2 ** 31)),
                              part)


def make_part(cfg: dict, seed: int, part: str) -> dict:
    """One part's pytree for ``seed``, on the default device."""
    import jax

    all_parts = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        all_parts[part], is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[1], str))
    base = _key(seed, list(all_parts).index(part))
    gain = cfg["init"]["gain"]
    out = []
    for n, (shape, role) in enumerate(leaves):
        key = jax.random.fold_in(base, n)
        if role == "norm":
            out.append(_leaf_maker(tuple(shape), 0.0, True)(key))
        else:
            # the last axis but one is what a product sums over
            fan_in = 1 if role == "embed" else shape[-2]
            std = float(np.sqrt(float(gain[role]) / fan_in))
            out.append(_leaf_maker(tuple(shape), std, False)(key))
    return jax.tree_util.tree_unflatten(treedef, out)


def make(cfg: dict, seed: int) -> dict:
    """The whole params pytree of the program's model for ``seed``."""
    parts = {name: make_part(cfg, seed, name) for name in shapes(cfg)}
    head = parts.pop("head")
    return {"embed": parts.pop("embed")["embed"],
            "layers": [parts[name] for name in sorted(parts)],
            "final_norm": head["final_norm"], "head": head["head"]}
