"""Seeded, well-conditioned weights for the K-EXAONE share, laid out as
the pytree ``nnstreamer_tpu/models/exaone_moe.py`` consumes and as
``benchmark/reference/kexaone_236b_share8.py`` reads: matrices in bf16
(the type they are served in), vectors in float32.

A part (the embedding, one layer, the head, the multi-token-prediction
module) is made by itself from the seed and its own index, leaf by leaf,
one jitted call a distinct leaf: the program asks for all of them
(:func:`make`, 9.1 GB on the device at the cell's size), the reference
for one part at a time (:func:`make_part`), and both get the same values
because both run the same calls.  The generator is ``rbg`` (XLA's
``RngBitGenerator``): a leaf of 201 M values is drawn in place, where
threefry would hold gigabytes of bits beside it.

Matrices are N(0, gain / fan_in) with the gains of the configuration's
``init``; norm gains lie within 10 % of 1, the per-head gains of q and k
within 10 % of ``init.qk_norm`` (with q and k normalised, these and not
the projections set the scale of the scores); the router's correction
bias is N(0, ``init.router_bias_std``^2).
"""

from __future__ import annotations

import functools

import numpy as np


def _layer(cfg: dict, dense: bool) -> dict:
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    f, held = int(cfg["moe_intermediate_size"]), int(cfg["num_experts"])
    router = int(cfg.get("published", {}).get("num_experts", held))

    def mlp(width):
        return {"gate": ((h, width), "gate"), "up": ((h, width), "up"),
                "down": ((width, h), "down")}

    out = {"attn_norm": ((h,), "norm"), "ffn_norm": ((h,), "norm"),
           "attn": {"q": ((h, nh * d), "q"), "k": ((h, nkv * d), "k"),
                    "v": ((h, nkv * d), "v"), "o": ((nh * d, h), "o"),
                    "q_norm": ((d,), "qk_norm"),
                    "k_norm": ((d,), "qk_norm")}}
    if dense:
        out["mlp"] = mlp(int(cfg["intermediate_size"]))
    else:
        out["moe"] = {
            "router": ((h, router), "router"),
            "router_bias": ((router,), "router_bias"),
            "experts": {"gate": ((held, h, f), "gate"),
                        "up": ((held, h, f), "up"),
                        "down": ((held, f, h), "expert_down")},
            "shared": mlp(f * int(cfg["num_shared_experts"]))}
    return out


def shapes(cfg: dict) -> dict:
    """``{part: pytree of (shape, role)}`` from the configuration's own
    keys: ``embed``, ``layer00`` .., ``head``, ``mtp``."""
    h, vocab = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    parts = {"embed": {"embed": ((vocab, h), "embed")}}
    for i in range(int(cfg["num_hidden_layers"])):
        parts[f"layer{i:02d}"] = _layer(
            cfg, cfg["mlp_layer_types"][i] == "dense")
    parts["head"] = {"final_norm": ((h,), "norm"),
                     "head": ((h, vocab), "head")}
    if int(cfg.get("num_nextn_predict_layers", 0)):
        parts["mtp"] = {"embed_norm": ((h,), "norm"),
                        "hidden_norm": ((h,), "norm"),
                        "eh_proj": ((2 * h, h), "eh_proj"),
                        "layer": _layer(cfg, False),
                        "final_norm": ((h,), "norm")}
    return parts


@functools.lru_cache(maxsize=64)
def _leaf_maker(shape: tuple, kind: str, scale: float):
    """One jitted maker a distinct leaf and law: ``near`` (within 10 %
    of ``scale``), ``vector`` (N(0, scale^2), float32) or ``matrix``
    (N(0, scale^2), bf16)."""
    import jax
    import jax.numpy as jnp

    def make(key):
        if kind == "near":
            return scale * (1.0 + 0.1 * jax.random.uniform(
                key, shape, jnp.float32, -1.0, 1.0))
        out = jax.random.normal(key, shape, jnp.float32) * scale
        return out if kind == "vector" else out.astype(jnp.bfloat16)

    return jax.jit(make)


def _key(seed: int, part: int):
    import jax

    seed = int(seed)
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed // (2 ** 31)),
                              part)


def _law(cfg: dict, shape: tuple, role: str) -> tuple:
    init = cfg["init"]
    if role == "norm":
        return "near", 1.0
    if role == "qk_norm":
        return "near", float(init["qk_norm"])
    if role == "router_bias":
        return "vector", float(init["router_bias_std"])
    # the last axis but one is what a product sums over
    fan_in = 1 if role == "embed" else shape[-2]
    return "matrix", float(np.sqrt(float(init["gain"][role]) / fan_in))


def make_part(cfg: dict, seed: int, part: str) -> dict:
    """One part's pytree for ``seed``, on the default device."""
    import jax

    all_parts = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        all_parts[part], is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[1], str))
    base = _key(seed, list(all_parts).index(part))
    out = [_leaf_maker(tuple(shape), *_law(cfg, tuple(shape), role))(
        jax.random.fold_in(base, n)) for n, (shape, role) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def make(cfg: dict, seed: int) -> dict:
    """The whole params pytree of the program's model for ``seed``."""
    parts = {name: make_part(cfg, seed, name) for name in shapes(cfg)}
    head = parts.pop("head")
    out = {"embed": parts.pop("embed")["embed"],
           "final_norm": head["final_norm"], "head": head["head"]}
    if "mtp" in parts:
        out["mtp"] = parts.pop("mtp")
    out["layers"] = [parts[name] for name in sorted(parts)]
    return out
