"""Seeded, well-conditioned weights for the DeepSeek-V2 share, laid out
as the pytree ``nnstreamer_tpu/models/deepseek_v2.py`` consumes and as
``benchmark/reference/deepseek_v2_share4.py`` reads: matrices in bf16
(the type they are served in), norm gains in float32.

A part (the embedding, one layer, the head) is made by itself from the
seed and its own index, leaf by leaf, one jitted call a distinct leaf
shape: the program asks for all of them (:func:`make`, 9.3 GB on the
device at the cell's size), the reference for one layer at a time
(:func:`make_part`), and both get the same values because both run the
same calls.  The generator is ``rbg`` (XLA's ``RngBitGenerator``): a
leaf of 315 M values is drawn in place, where threefry would hold
gigabytes of bits beside it.

Gains are in the configuration file under ``init``: every matrix is
N(0, gain / fan_in); the residual branches (``o``, ``down``) are damped
so that the stream stays O(1) through the depth, and the router's gain
spreads its probabilities over about one order of magnitude.
"""

from __future__ import annotations

import functools

import numpy as np


def shapes(cfg: dict) -> dict:
    """``{part: pytree of (shape, role)}`` from the configuration's own
    keys: ``embed``, ``layer00`` .., ``head``."""
    h, qr, kr = (int(cfg[k]) for k in ("hidden_size", "q_lora_rank",
                                       "kv_lora_rank"))
    nope, rope, vd = (int(cfg[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    nh, vocab = int(cfg["num_attention_heads"]), int(cfg["vocab_size"])
    held, f = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
    router = int(cfg.get("published", {}).get("n_routed_experts", held))

    def mlp(width, down="down"):
        return {"gate": ((h, width), "gate"), "up": ((h, width), "up"),
                "down": ((width, h), down)}

    parts = {"embed": {"embed": ((vocab, h), "embed")}}
    for i in range(int(cfg["num_hidden_layers"])):
        layer = {
            "attn_norm": ((h,), "norm"), "mlp_norm": ((h,), "norm"),
            "attn": {"q_a": ((h, qr), "q_a"), "q_a_norm": ((qr,), "norm"),
                     "q_b": ((qr, nh * (nope + rope)), "q_b"),
                     "kv_a": ((h, kr + rope), "kv_a"),
                     "kv_a_norm": ((kr,), "norm"),
                     "kv_b": ((kr, nh * (nope + vd)), "kv_b"),
                     "o": ((nh * vd, h), "o")}}
        if i < int(cfg["first_k_dense_replace"]):
            layer["mlp"] = mlp(int(cfg["intermediate_size"]))
        else:
            layer["moe"] = {
                "router": ((h, router), "router"),
                "experts": {"gate": ((held, h, f), "gate"),
                            "up": ((held, h, f), "up"),
                            "down": ((held, f, h), "expert_down")},
                "shared": mlp(f * int(cfg["n_shared_experts"]))}
        parts[f"layer{i:02d}"] = layer
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
    tree = all_parts[part]
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple)
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
