"""Seeded, well-conditioned weights for the Nemotron-3-Nano share, laid
out as the pytree ``nnstreamer_tpu/models/nemotron_h.py`` consumes and as
``benchmark/reference/nemotron3_nano_share8.py`` reads: matrices in bf16
(the type they are served in), vectors in float32.

A part (the embedding, one layer, the head) is made by itself from the
seed and its own index, leaf by leaf, one jitted call a distinct leaf:
the program asks for all of them (:func:`make`, 4.0 GB on the device at
the cell's size), the reference for one layer at a time
(:func:`make_part`), and both get the same values because both run the
same calls.  The generator is ``rbg`` (XLA's ``RngBitGenerator``).

Matrices are N(0, gain / fan_in) with the gains of the configuration's
``init``.  A Mamba-2 layer's small vectors are seeded as its source
seeds them: ``delta`` at rest log-uniform in ``time_step_min`` ..
``time_step_max`` (``dt_bias`` is its inverse softplus), ``exp(A_log)``
uniform in ``init.A``, ``D`` = ``init.D``; the convolution's taps are
N(0, 1 / kernel).  Where the configuration names
``expert_columns_stored``, an expert's ``up`` gets that many columns and
its ``down`` that many rows, those beyond ``moe_intermediate_size``
ZERO: ``relu(0)^2`` times a zero row adds nothing, so the stored
experts compute exactly what the published width does.  A matrix whose
role ``init.centred`` lists is drawn with every output's weights adding
up to zero over the inputs: a hidden activation with a positive mean
(``relu^2``, a gated ``silu``) then adds no vector common to all tokens
to the residual stream, which would give every expert a standing offset
in the router's scores and starve some of them (``PERF.md`` section 6,
PR 33).
"""

from __future__ import annotations

import functools

import numpy as np


def _sizes(cfg: dict) -> dict:
    heads, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    d = heads * p
    return {
        "h": int(cfg["hidden_size"]), "d": d, "heads": heads,
        "conv": d + 2 * int(cfg["n_groups"]) * int(cfg["ssm_state_size"]),
        "kernel": int(cfg["conv_kernel"]),
        "f": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["moe_shared_expert_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        "held": int(cfg["n_routed_experts"]),
        "router": int(cfg.get("published", {}).get(
            "n_routed_experts", cfg["n_routed_experts"])),
        "q": int(cfg["num_attention_heads"]) * int(cfg["head_dim"]),
        "kv": int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
        "vocab": int(cfg["vocab_size"])}


def shapes(cfg: dict) -> dict:
    """``{part: pytree of (shape, role)}`` from the configuration's own
    keys: ``embed``, ``layer00`` .., ``head``; an expert's matrices at
    their PUBLISHED width."""
    s = _sizes(cfg)
    h = s["h"]
    kinds = {
        "M": {"norm": ((h,), "norm"),
              "in_proj": ((h, s["d"] + s["conv"] + s["heads"]), "in_proj"),
              "conv_w": ((s["kernel"], s["conv"]), "conv_w"),
              "conv_b": ((s["conv"],), "conv_b"),
              "dt_bias": ((s["heads"],), "dt_bias"),
              "A_log": ((s["heads"],), "A_log"), "D": ((s["heads"],), "D"),
              "gate_norm": ((s["d"],), "norm"),
              "out_proj": ((s["d"], h), "out_proj")},
        "E": {"norm": ((h,), "norm"),
              "router": ((h, s["router"]), "router"),
              "router_bias": ((s["router"],), "router_bias"),
              "experts": {"up": ((s["held"], h, s["f"]), "up"),
                          "down": ((s["held"], s["f"], h), "expert_down")},
              "shared": {"up": ((h, s["shared"]), "up"),
                         "down": ((s["shared"], h), "down")}},
        "*": {"norm": ((h,), "norm"), "q": ((h, s["q"]), "q"),
              "k": ((h, s["kv"]), "k"), "v": ((h, s["kv"]), "v"),
              "o": ((s["q"], h), "o")}}
    parts = {"embed": {"embed": ((s["vocab"], h), "embed")}}
    pattern = cfg["hybrid_override_pattern"][:int(cfg["num_hidden_layers"])]
    for i, kind in enumerate(pattern):
        parts[f"layer{i:02d}"] = kinds[kind]
    parts["head"] = {"final_norm": ((h,), "norm"),
                     "head": ((h, s["vocab"]), "head")}
    return parts


@functools.lru_cache(maxsize=128)
def _leaf_maker(shape: tuple, law: tuple):
    """One jitted maker a distinct leaf and law (:func:`_law`)."""
    import jax
    import jax.numpy as jnp

    kind, *args = law

    def make(key):
        if kind == "near_one":
            return 1.0 + 0.1 * jax.random.uniform(key, shape, jnp.float32,
                                                  -1.0, 1.0)
        if kind == "constant":
            return jnp.full(shape, args[0], jnp.float32)
        if kind == "dt_bias":              # delta at rest, through softplus^-1
            low, high, floor = args
            rest = jnp.maximum(floor, jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(low), np.log(high))))
            return rest + jnp.log(-jnp.expm1(-rest))
        if kind == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              *args))
        out = jax.random.normal(key, shape, jnp.float32) * args[0]
        if kind == "vector":
            return out
        if args[2]:        # centred: what the product sums over adds to 0
            out = out - jnp.mean(out, axis=-2, keepdims=True)
        return jnp.pad(out.astype(jnp.bfloat16),
                       [(0, n) for n in args[1]])

    return jax.jit(make)


def _key(seed: int, part: int):
    import jax

    seed = int(seed)
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed // (2 ** 31)),
                              part)


def _law(cfg: dict, shape: tuple, role: str) -> tuple:
    """How one leaf is drawn: its kind and that kind's parameters."""
    init = cfg["init"]
    if role == "norm":
        return ("near_one",)
    if role == "D":
        return "constant", float(init["D"])
    if role == "dt_bias":
        return ("dt_bias", float(cfg["time_step_min"]),
                float(cfg["time_step_max"]), float(cfg["time_step_floor"]))
    if role == "A_log":
        return "A_log", float(init["A"][0]), float(init["A"][1])
    if role == "conv_w":
        return "vector", float(shape[0]) ** -0.5
    if role in ("conv_b", "router_bias"):
        return "vector", float(init[role + "_std"])
    # the last axis but one is what a product sums over
    fan_in = 1 if role == "embed" else shape[-2]
    std = float(np.sqrt(float(init["gain"][role]) / fan_in))
    stored = int(cfg.get("expert_columns_stored", 0))
    f = int(cfg["moe_intermediate_size"])
    routed = stored and len(shape) == 3            # an expert's up or down
    return ("matrix", std, tuple(stored - n if routed and n == f else 0
                                 for n in shape),
            role in init.get("centred", ()))


def make_part(cfg: dict, seed: int, part: str) -> dict:
    """One part's pytree for ``seed``, on the default device."""
    import jax

    all_parts = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        all_parts[part], is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[1], str))
    base = _key(seed, list(all_parts).index(part))
    out = [_leaf_maker(tuple(shape), _law(cfg, tuple(shape), role))(
        jax.random.fold_in(base, n)) for n, (shape, role) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def make(cfg: dict, seed: int) -> dict:
    """The whole params pytree of the program's model for ``seed``."""
    parts = {name: make_part(cfg, seed, name) for name in shapes(cfg)}
    head = parts.pop("head")
    return {"embed": parts.pop("embed")["embed"],
            "layers": [parts[name] for name in sorted(parts)],
            "final_norm": head["final_norm"], "head": head["head"]}
