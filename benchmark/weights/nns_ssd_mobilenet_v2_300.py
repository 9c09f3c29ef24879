"""Seeded, well-conditioned weights for the SSD-MobileNetV2 configuration.

The benchmark owns the weights: one jitted call makes every leaf on the
device from ``--seed``, in the type it is served in (bf16 kernels, f32
batch-norm vectors), laid out as the pytree ``models/ssd.py`` consumes.
The reference makes the same pytree by calling :func:`make` itself; it
takes nothing from the program.

Conditioning (``PERF.md`` section 6, PR 21: He-initialised heads saturate
every score at 1.0): each kernel is N(0, gain / fan_in) with the gain
chosen by what feeds the layer, so pre-activations stay O(1) through
the depth, and the class head gets a small gain and a negative bias so
that sigmoid scores spread below 1.  The gains are in the configuration
file under ``init``.
"""

from __future__ import annotations

import functools

from benchmark.frames import seed_key


def conv_table(cfg: dict) -> list:
    """Every convolution of the network in the program's order:
    ``(path, k, cin, cout, groups, role)`` with ``path`` the keys into
    the params pytree and ``role`` naming the gain in ``cfg['init']``."""
    rows = []
    stem = int(cfg["stem_channels"])
    rows.append((("backbone", "stem"), 3, 3, stem, 1, "stem"))
    cin, idx = stem, 0
    for t, c, n, _s in cfg["backbone_blocks"]:
        for r in range(n):
            mid = cin * t
            base = ("backbone", "blocks", idx)
            if t != 1:
                rows.append((base + ("expand",), 1, cin, mid, 1, "expand"))
            rows.append((base + ("dw",), 3, mid, mid, mid, "depthwise"))
            # a block that adds its input back gets the residual gain
            residual = r > 0
            rows.append((base + ("project",), 1, mid, c, 1,
                         "project_residual" if residual else "project"))
            cin = c
            idx += 1
    prev = cin
    for i, c in enumerate(cfg["extra_channels"]):
        rows.append((("extras", i), 3, prev, c, 1,
                     "extra_first" if i == 0 else "extra"))
        prev = c
    a = int(cfg["anchors_per_cell"])
    for i, c in enumerate(head_channels(cfg)):
        role = "head_linear_in" if i < 2 else "head_relu_in"
        rows.append((("heads", i, "loc"), 3, c, a * 4, 1, role + "_loc"))
        rows.append((("heads", i, "cls"), 3, c, a * int(cfg["num_classes"]),
                     1, role + "_cls"))
    return rows


def block_channels(cfg: dict) -> list:
    out = []
    for _t, c, n, _s in cfg["backbone_blocks"]:
        out.extend([c] * n)
    return out


def head_channels(cfg: dict) -> list:
    ch = block_channels(cfg)
    return [ch[int(cfg["tap_block"])], ch[-1], *cfg["extra_channels"]]


def _set(tree, path, leaf):
    node = tree
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = leaf


def _skeleton(cfg: dict) -> dict:
    n_blocks = len(block_channels(cfg))
    return {"backbone": {"stem": None,
                         "blocks": [dict() for _ in range(n_blocks)]},
            "extras": [None] * len(cfg["extra_channels"]),
            "heads": [dict() for _ in range(len(head_channels(cfg)))]}


@functools.lru_cache(maxsize=4)
def _maker(cfg_key: str):
    import json

    import jax
    import jax.numpy as jnp

    cfg = json.loads(cfg_key)
    table = conv_table(cfg)
    init = cfg["init"]

    def make(key):
        # two draws for the whole network, sliced per layer: one random
        # op compiles in a moment where one per leaf takes many seconds
        kw, kv = jax.random.split(key)
        n_w = sum(k * k * (cin // g) * cout
                  for _p, k, cin, cout, g, _r in table)
        n_v = sum(cout for _p, _k, _cin, cout, _g, _r in table)
        flat_w = jax.random.normal(kw, (n_w,), jnp.float32)
        flat_v = jax.random.uniform(kv, (4, n_v), jnp.float32, -1.0, 1.0)
        tree = _skeleton(cfg)
        ow = ov = 0
        for path, k, cin, cout, groups, role in table:
            shape = (k, k, cin // groups, cout)
            size = k * k * (cin // groups) * cout
            fan_in = k * k * cin // groups
            gain = float(init["gain"][role])
            w = flat_w[ow:ow + size].reshape(shape) * (gain / fan_in) ** 0.5
            v = flat_v[:, ov:ov + cout]
            ow, ov = ow + size, ov + cout
            bias0 = float(init["cls_bias"]) if role.endswith("_cls") else 0.0
            _set(tree, path, {
                "w": w.astype(jnp.bfloat16),
                "scale": 1.0 + 0.1 * v[0],
                "bias": bias0 + 0.2 * v[1],
                "mean": 0.2 * v[2],
                "var": 1.0 + 0.2 * v[3],
            })
        return tree

    return jax.jit(make)


def make(cfg: dict, seed: int) -> dict:
    """The params pytree of ``ssd_mobilenet_v2_apply`` for ``seed``, on
    the default device.  ``num_classes`` rides along as the Python int
    the program's apply reshapes with."""
    import json

    keep = {k: cfg[k] for k in ("stem_channels", "backbone_blocks",
                                "extra_channels", "anchors_per_cell",
                                "num_classes", "tap_block", "init")}
    params = _maker(json.dumps(keep, sort_keys=True))(seed_key(seed))
    params["num_classes"] = int(cfg["num_classes"])
    return params
