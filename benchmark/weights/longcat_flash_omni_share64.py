"""Seeded, well-conditioned weights for the LongCat-Flash share, laid out
as the pytree ``nnstreamer_tpu/models/longcat_flash.py`` consumes and as
``benchmark/reference/longcat_flash_omni_share64.py`` reads: matrices in
bf16 (the type they are served in), vectors in float32.

A part (the embedding, one layer, the head) is made by itself from the
seed and its own index, leaf by leaf, one jitted call a distinct leaf
shape and law: the program asks for all of them (:func:`make`, 7.9 GB on
the device at the cell's size), the reference for one layer at a time
(:func:`make_part`), and both get the same values because both run the
same calls.  The generator is ``rbg`` (XLA's ``RngBitGenerator``): a
leaf of 101 M values is drawn in place, where threefry would hold
gigabytes of bits beside it.

A layer holds two of everything but the expert branch: ``attn``,
``attn_norm``, ``mlp`` and ``mlp_norm`` are pairs, sub-block 0's first.
Matrices are N(0, gain / fan_in) with the gains of the configuration's
``init``; norm gains lie within 10 % of 1; the router's correction bias
is zero (``assumed``: a trained checkpoint's is not in the repository).
"""

from __future__ import annotations

import functools

import numpy as np

SUBS = 2


def _layer(cfg: dict) -> dict:
    h, qr, kr = (int(cfg[k]) for k in ("hidden_size", "q_lora_rank",
                                       "kv_lora_rank"))
    nope, rope, vd = (int(cfg[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    nh, width = int(cfg["num_attention_heads"]), int(cfg["ffn_hidden_size"])
    held, f = int(cfg["n_routed_experts"]), int(cfg["expert_ffn_hidden_size"])
    router = int(cfg.get("published", {}).get("n_routed_experts", held)) \
        + int(cfg["zero_expert_num"])
    attn = {"q_a": ((h, qr), "q_a"), "q_a_norm": ((qr,), "norm"),
            "q_b": ((qr, nh * (nope + rope)), "q_b"),
            "kv_a": ((h, kr + rope), "kv_a"), "kv_a_norm": ((kr,), "norm"),
            "kv_b": ((kr, nh * (nope + vd)), "kv_b"),
            "o": ((nh * vd, h), "o")}
    mlp = {"gate": ((h, width), "gate"), "up": ((h, width), "up"),
           "down": ((width, h), "down")}
    return {"attn_norm": [((h,), "norm")] * SUBS,
            "attn": [dict(attn) for _ in range(SUBS)],
            "mlp_norm": [((h,), "norm")] * SUBS,
            "mlp": [dict(mlp) for _ in range(SUBS)],
            "moe": {"router": ((h, router), "router"),
                    "router_bias": ((router,), "router_bias"),
                    "experts": {"gate": ((held, h, f), "expert_gate"),
                                "up": ((held, h, f), "expert_up"),
                                "down": ((held, f, h), "expert_down")}}}


def shapes(cfg: dict) -> dict:
    """``{part: pytree of (shape, role)}`` from the configuration's own
    keys: ``embed``, ``layer00`` .., ``head``."""
    h, vocab = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    parts = {"embed": {"embed": ((vocab, h), "embed")}}
    for i in range(int(cfg["num_layers"])):
        parts[f"layer{i:02d}"] = _layer(cfg)
    parts["head"] = {"final_norm": ((h,), "norm"),
                     "head": ((h, vocab), "head")}
    return parts


@functools.lru_cache(maxsize=64)
def _leaf_maker(shape: tuple, kind: str, scale: float):
    """One jitted maker a distinct leaf and law: ``near`` (within 10 %
    of ``scale``), ``zeros`` (float32) or ``matrix`` (N(0, scale^2),
    bf16)."""
    import jax
    import jax.numpy as jnp

    def make(key):
        if kind == "near":
            return scale * (1.0 + 0.1 * jax.random.uniform(
                key, shape, jnp.float32, -1.0, 1.0))
        if kind == "zeros":
            return jnp.zeros(shape, jnp.float32)
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    return jax.jit(make)


def _key(seed: int, part: int):
    import jax

    seed = int(seed)
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed // (2 ** 31)),
                              part)


def _law(cfg: dict, shape: tuple, role: str) -> tuple:
    if role == "norm":
        return "near", 1.0
    if role == "router_bias":
        return "zeros", 0.0
    # the last axis but one is what a product sums over
    fan_in = 1 if role == "embed" else shape[-2]
    return "matrix", float(np.sqrt(float(cfg["init"]["gain"][role])
                                   / fan_in))


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
    return {"embed": parts.pop("embed")["embed"],
            "layers": [parts[name] for name in sorted(parts)],
            "final_norm": head["final_norm"], "head": head["head"]}
