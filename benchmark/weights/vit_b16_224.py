"""Seeded, well-conditioned weights for the ViT configuration, laid out
as the pytree ``models/vit.py`` consumes: one jitted call on the device
from ``--seed``, matrices in bf16 (the type they are served in), vectors
in float32.  Gains are in the configuration file under ``init``: every
matrix is N(0, gain / fan_in), the two residual branches are damped so
that the stream stays O(1) through the depth.  The reference makes the
same pytree by calling :func:`make` itself.
"""

from __future__ import annotations

import functools
import json

import numpy as np

from benchmark.frames import seed_key


@functools.lru_cache(maxsize=4)
def _maker(cfg_key: str):
    import jax
    import jax.numpy as jnp

    cfg = json.loads(cfg_key)
    patch, dim = int(cfg["patch_size"]), int(cfg["hidden_size"])
    depth, mlp = int(cfg["num_hidden_layers"]), int(cfg["intermediate_size"])
    classes = int(cfg["num_classes"])
    n_pos = (int(cfg["image_size"]) // patch) ** 2
    gain = cfg["init"]["gain"]

    def make(key):
        # two draws for the whole network, sliced per leaf: one random op
        # compiles in a moment where one per leaf takes many seconds
        kw, kv = jax.random.split(key)
        shapes = [("embed", (patch, patch, 3, dim), patch * patch * 3),
                  ("head", (dim, classes), dim)]
        for _ in range(depth):
            shapes += [("qkv", (dim, 3 * dim), dim), ("proj", (dim, dim), dim),
                       ("mlp1", (dim, mlp), dim), ("mlp2", (mlp, dim), mlp)]
        n_w = sum(int(np.prod(s)) for _r, s, _f in shapes) + n_pos * dim
        n_v = (dim + classes + 2 * dim
               + depth * (4 * dim + 3 * dim + dim + mlp + dim))
        flat_w = jax.random.normal(kw, (n_w,), jnp.float32)
        flat_v = jax.random.uniform(kv, (n_v,), jnp.float32, -1.0, 1.0)
        at = {"w": 0, "v": 0}

        def matrix(role, shape, fan_in):
            size = int(np.prod(shape))
            w = flat_w[at["w"]:at["w"] + size].reshape(shape)
            at["w"] += size
            return (w * (float(gain[role]) / fan_in) ** 0.5
                    ).astype(jnp.bfloat16)

        def vector(n, centre, spread):
            v = flat_v[at["v"]:at["v"] + n]
            at["v"] += n
            return centre + spread * v

        def dense(role, din, dout):
            return {"w": matrix(role, (din, dout), din),
                    "b": vector(dout, 0.0, 0.05)}

        def norm(n):
            return {"g": vector(n, 1.0, 0.1), "b": vector(n, 0.0, 0.2)}

        params = {
            "embed": {"w": matrix("embed", (patch, patch, 3, dim),
                                  patch * patch * 3),
                      "b": vector(dim, 0.0, 0.05)},
            "head": dense("head", dim, classes),
            "ln_f": norm(dim),
            "blocks": [],
        }
        for _ in range(depth):
            params["blocks"].append({
                "ln1": norm(dim), "qkv": dense("qkv", dim, 3 * dim),
                "proj": dense("proj", dim, dim), "ln2": norm(dim),
                "mlp1": dense("mlp1", dim, mlp),
                "mlp2": dense("mlp2", mlp, dim)})
        size = n_pos * dim
        params["pos"] = (float(cfg["init"]["pos_std"])
                         * flat_w[at["w"]:at["w"] + size].reshape(n_pos, dim)
                         ).astype(jnp.bfloat16)
        return params

    return jax.jit(make)


def make(cfg: dict, seed: int) -> dict:
    """The params pytree of ``vit_apply`` for ``seed``, on the default
    device."""
    keep = {k: cfg[k] for k in ("patch_size", "hidden_size",
                                "num_hidden_layers", "intermediate_size",
                                "num_classes", "image_size", "init")}
    return _maker(json.dumps(keep, sort_keys=True))(seed_key(seed))
