"""Seeded, well-conditioned weights for Phi-4-mini-flash-reasoning, laid
out as the pytree ``nnstreamer_tpu/models/phi4_flash.py`` consumes and
as ``benchmark/reference/phi4_mini_flash_reasoning.py`` reads: matrices
in bf16 (the type they are served in), vectors in float32.

A part (the embedding, one layer, the final norm) is made by itself from
the seed and its own index, leaf by leaf, one jitted call a distinct
leaf: the program asks for all of them (:func:`make`, 7.7 GB on the
device), the reference for one layer at a time (:func:`make_part`), and
both get the same values because both run the same calls.  The jitted
leaf makers and the ``rbg`` key are ``falcon_h1_34b_stage4_vocab8.py``'s
(found beside this file); the shapes, roles and laws are this model's.

The layout of a layer follows its kind (``assumed`` in the configuration
says which permutation of a checkpoint's columns each is):

``mamba``        ``in_proj [h, s | z]``, ``conv_w [4, d]``, ``conv_b``,
                 ``x_proj [d, delta' | B | C]``, ``dt_proj [160, d]``,
                 ``dt_bias``, ``A_log [16, d]``, ``D``, ``out_proj``
``attn_window``  ``q [h, pair j x query pair a x (q1 | q2) x 64]``,
``attn_full``    ``kv [h, (k | v) x pair j x 128]`` with a K row ``[k1_j
                 | k2_j]`` and a V row ``[v_2j | v_2j+1]``, ``o``, the
                 three biases, four ``lambda`` vectors, ``subln``
``attn_cross``   the same without ``kv`` and its bias
``gmu``          ``in [h, d]``, ``out [d, h]``

Matrices are N(0, gain / fan_in) with ``gain`` from the configuration's
``init.gain`` (one number, or one a column segment).  ``o`` ABSORBS the
constant ``1 - lambda0(l)`` that the differential combination leaves on
its input (a trained ``W_o`` is large where that constant is small;
drawn without it the attention branch of the last layers would carry a
twenty-fifth of the others' variance and a cross layer that read the
wrong cache would hide under bf16 rounding).  A role ``init.centred``
lists is drawn with every output's weights adding up to zero over the
inputs (``y silu(z)`` and ``m silu(.)`` have a positive mean).
"""

from __future__ import annotations

import importlib.util
import math
import os

import numpy as np


def sizes(cfg: dict) -> dict:
    h = int(cfg["hidden_size"])
    heads, kv = (int(cfg[k]) for k in ("num_attention_heads",
                                       "num_key_value_heads"))
    rank = cfg.get("mamba_dt_rank", "auto")
    return {"h": h, "f": int(cfg["intermediate_size"]),
            "d": int(cfg.get("mamba_expand", 2)) * h,
            "n": int(cfg.get("mamba_d_state", 16)),
            "kernel": int(cfg.get("mamba_d_conv", 4)),
            "rank": -(-h // 16) if rank == "auto" else int(rank),
            "hd": h // heads, "pairs": heads // 2, "kv_pairs": kv // 2,
            "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"])}


def kind(cfg: dict, layer: int) -> str:
    """Which mixer layer ``layer`` holds (``F = layers / 2 + 1``)."""
    full = int(cfg["num_hidden_layers"]) // 2 + 1
    if layer == full:
        return "attn_full"
    if layer < full:
        return "attn_window" if layer % 2 else "mamba"
    return "attn_cross" if layer % 2 else "gmu"


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _mixer(cfg: dict, layer: int) -> dict:
    s = sizes(cfg)
    h, d, n, r = s["h"], s["d"], s["n"], s["rank"]
    what = kind(cfg, layer)
    if what == "mamba":
        return {"in_proj": ((h, 2 * d), "in_proj"),
                "conv_w": ((s["kernel"], d), "conv_w"),
                "conv_b": ((d,), "conv_b"),
                "x_proj": ((d, r + 2 * n), "x_proj"),
                "dt_proj": ((r, d), "dt_proj"),
                "dt_bias": ((d,), "dt_bias"),
                "A_log": ((n, d), "A_log"), "D": ((d,), "D"),
                "out_proj": ((d, h), "out_proj")}
    if what == "gmu":
        return {"in": ((h, d), "gmu_in"), "out": ((d, h), "gmu_out")}
    out = {"q": ((h, h), "q"), "q_b": ((h,), "bias"),
           "o": ((h, h), f"o{layer}"), "o_b": ((h,), "bias"),
           "subln": ((2 * s["hd"],), "norm")}
    for name in ("lq1", "lk1", "lq2", "lk2"):
        out[name] = ((s["hd"],), "lambda")
    if what != "attn_cross":
        kv = 2 * s["kv_pairs"] * 2 * s["hd"]
        out.update(kv=((h, kv), "kv"), kv_b=((kv,), "bias"))
    return out


def shapes(cfg: dict) -> dict:
    """``{part: pytree of (shape, role)}`` from the configuration's own
    keys: ``embed``, ``layer00`` .., ``tail``."""
    s = sizes(cfg)
    h, f = s["h"], s["f"]

    def norm():
        return {"g": ((h,), "norm"), "b": ((h,), "norm_b")}

    parts = {"embed": {"embed": ((s["vocab"], h), "embed")}}
    for i in range(s["layers"]):
        parts[f"layer{i:02d}"] = {
            "norm": norm(), "mixer": _mixer(cfg, i), "mlp_norm": norm(),
            "mlp": {"gate_up": ((h, 2 * f), "gate_up"),
                    "down": ((f, h), "down")}}
    parts["tail"] = {"final_norm": norm()}
    return parts


def _falcon():
    """The leaf makers and the key of ``falcon_h1_34b_stage4_vocab8.py``
    (found beside this file): the kinds of law are the same, ``near_one``,
    ``constant``, ``dt_bias``, ``A_log``, ``vector`` and a matrix by
    column segment, centred or not."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "falcon_h1_34b_stage4_vocab8.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_weights_falcon_h1_for_phi4flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_F = _falcon()
_leaf_maker, _key = _F._leaf_maker, _F._key


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
    if role in ("conv_b", "norm_b", "bias", "lambda"):
        return "vector", float(init["std"][role])
    absorbed = 1.0
    if role.startswith("o") and role[1:].isdigit():   # o of layer l
        absorbed, role = 1.0 - lambda_init(int(role[1:])), "o"
    # the last axis but one is what a product sums over
    fan_in = 1 if role == "embed" else shape[-2]
    gains = init["gain"][role]
    gains = tuple(gains) if isinstance(gains, list) else (gains,)
    stds = tuple(float(np.sqrt(float(g) / fan_in) / absorbed) for g in gains)
    widths = (shape[-1],)
    s = sizes(cfg)
    if role == "in_proj":
        widths = (s["d"], s["d"])
    elif role == "x_proj":
        widths = (s["rank"], s["n"], s["n"])
    elif role == "kv":
        widths = (shape[-1] // 2, shape[-1] // 2)
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
    tail = parts.pop("tail")
    return {"embed": parts.pop("embed")["embed"],
            "layers": [parts[name] for name in sorted(parts)],
            "final_norm": tail["final_norm"]}
