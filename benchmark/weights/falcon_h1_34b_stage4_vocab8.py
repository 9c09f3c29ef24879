"""Seeded, well-conditioned weights for the Falcon-H1-34B stage, laid out
as the pytree ``nnstreamer_tpu/models/falcon_h1.py`` consumes and as
``benchmark/reference/falcon_h1_34b_stage4_vocab8.py`` reads: matrices in
bf16 (the type they are served in), vectors in float32.

A part (the embedding, one layer, the head) is made by itself from the
seed and its own index, leaf by leaf, one jitted call a distinct leaf:
the program asks for all of them (:func:`make`, 4.1 GB on the device at
the cell's size), the reference for one layer at a time
(:func:`make_part`), and both get the same values because both run the
same calls.  The generator is ``rbg`` (XLA's ``RngBitGenerator``).

**The law absorbs the multipliers.**  A trained muP model's matrices are
large where its multipliers are small; drawn at N(0, 1/fan_in) this
model's three branches would vanish under 0.0375, 0.088 and 0.011 and
the output check would be blind to every state.  So a matrix is drawn
N(0, gain / fan_in) DIVIDED by the multiplier that follows it
(:func:`absorbed`), where ``gain`` (the configuration's ``init.gain``)
is the variance it should leave AFTER that multiplier on a unit-RMS
input.  The multipliers stay operations of the program and of the
reference; nothing is folded.  The input projection's five column
segments ``z | x | B | C | dt`` have a gain and a multiplier each.

A Mamba-2 mixer's small vectors: ``delta`` at rest log-uniform in
``init.dt`` (``dt_bias`` is its inverse softplus), ``exp(A_log)``
uniform in ``init.A``, ``D`` = ``init.D``; the convolution's taps are
N(0, 1 / kernel).  A matrix whose role ``init.centred`` lists is drawn
with every output's weights adding up to zero over the inputs (``y *
silu(z)`` has a positive mean, which would otherwise add one vector to
every token's stream in every layer).
"""

from __future__ import annotations

import functools

import numpy as np


def _sizes(cfg: dict) -> dict:
    heads, p = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    gn = int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    return {
        "h": int(cfg["hidden_size"]), "f": int(cfg["intermediate_size"]),
        "d": heads * p, "gn": gn, "heads": heads,
        "conv": heads * p + 2 * gn, "kernel": int(cfg["mamba_d_conv"]),
        "q": int(cfg["num_attention_heads"]) * int(cfg["head_dim"]),
        "kv": int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
        "vocab": int(cfg["vocab_size"])}


def shapes(cfg: dict) -> dict:
    """``{part: pytree of (shape, role)}`` from the configuration's own
    keys: ``embed``, ``layer00`` .., ``head``."""
    s = _sizes(cfg)
    h = s["h"]
    layer = {
        "norm": ((h,), "norm"),
        "mamba": {"in_proj": ((h, s["d"] + s["conv"] + s["heads"]), "in_proj"),
                  "conv_w": ((s["kernel"], s["conv"]), "conv_w"),
                  "conv_b": ((s["conv"],), "conv_b"),
                  "dt_bias": ((s["heads"],), "dt_bias"),
                  "A_log": ((s["heads"],), "A_log"),
                  "D": ((s["heads"],), "D"),
                  "gate_norm": ((s["d"],), "norm"),
                  "out_proj": ((s["d"], h), "out_proj")},
        "attn": {"q": ((h, s["q"]), "q"), "k": ((h, s["kv"]), "k"),
                 "v": ((h, s["kv"]), "v"), "o": ((s["q"], h), "o")},
        "mlp_norm": ((h,), "norm"),
        "mlp": {"gate": ((h, s["f"]), "gate"), "up": ((h, s["f"]), "up"),
                "down": ((s["f"], h), "down")}}
    parts = {"embed": {"embed": ((s["vocab"], h), "embed")}}
    for i in range(int(cfg["num_hidden_layers"])):
        parts[f"layer{i:02d}"] = layer
    parts["head"] = {"final_norm": ((h,), "norm"),
                     "head": ((h, s["vocab"]), "head")}
    return parts


def absorbed(cfg: dict, role: str) -> tuple:
    """The multiplier(s) the program applies after (or, on its input,
    before) the product with a matrix of ``role``: one number, or one a
    column segment of the input projection.  A role no multiplier
    follows absorbs 1."""
    if role == "in_proj":
        return tuple(float(cfg["ssm_in_multiplier"]) * float(m)
                     for m in cfg["ssm_multipliers"])
    if role in ("q", "k", "v"):         # their input is under m_ai
        m = float(cfg["attention_in_multiplier"])
        return (m * float(cfg["key_multiplier"]) if role == "k" else m,)
    key = {"embed": "embedding_multiplier", "head": "lm_head_multiplier",
           "o": "attention_out_multiplier",
           "out_proj": "ssm_out_multiplier"}.get(role)
    if key is not None:
        return (float(cfg[key]),)
    if role in ("gate", "down"):
        return (float(cfg["mlp_multipliers"][role == "down"]),)
    return (1.0,)


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
            low, high = args
            rest = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(low), np.log(high)))
            return rest + jnp.log(-jnp.expm1(-rest))
        if kind == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              *args))
        if kind == "vector":
            return jax.random.normal(key, shape, jnp.float32) * args[0]
        stds, widths, centred = args       # a matrix, by column segment
        out = jax.random.normal(key, shape, jnp.float32)
        if centred:        # what the product sums over adds up to 0
            out = out - jnp.mean(out, axis=-2, keepdims=True)
        return (out * np.repeat(np.asarray(stds, np.float32), widths)
                ).astype(jnp.bfloat16)

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
        return "dt_bias", float(init["dt"][0]), float(init["dt"][1])
    if role == "A_log":
        return "A_log", float(init["A"][0]), float(init["A"][1])
    if role == "conv_w":
        return "vector", float(shape[0]) ** -0.5
    if role == "conv_b":
        return "vector", float(init["conv_b_std"])
    # the last axis but one is what a product sums over
    fan_in = 1 if role == "embed" else shape[-2]
    gains = init["gain"][role]
    gains = tuple(gains) if isinstance(gains, list) else (gains,)
    stds = tuple(float(np.sqrt(float(g) / fan_in) / m)
                 for g, m in zip(gains, absorbed(cfg, role)))
    widths = (shape[-1],)
    if role == "in_proj":
        s = _sizes(cfg)
        widths = (s["d"], s["d"], s["gn"], s["gn"], s["heads"])
    return ("matrix", stds, widths, role in init.get("centred", ()))


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
