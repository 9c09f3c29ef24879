"""Vision Transformer for the model zoo.

The reference ships CNN-era test models only; this family extends the
zoo with the attention-based architecture class and is the in-tree user
of the Pallas attention kernels.  ``_attention`` picks the core from the
shape alone: a patch sequence short enough for one key block (ViT-B/16's
196 positions with heads of 64, and every other size the zoo registers)
takes ``ops.short_attention`` on the qkv projection's own layout; a
longer one is split into heads for the blockwise ``ops.flash_attention``
— the single-chip engine under the ring-attention sequence-parallel path
(parallel/collectives.py) — which keeps its own jnp fallback.

Functional pytree style matching models/mobilenet.py: ``vit_init`` →
params dict, ``vit_apply(params, x)`` jittable, bf16 compute with f32
accumulation, ``register_vit`` exposes it to ``tensor_filter``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

try:
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover
    jax = jnp = None

Params = dict


def _dense_init(key, din, dout):
    k1, _ = jax.random.split(key)
    scale = np.sqrt(2.0 / din)
    return {"w": jax.random.normal(k1, (din, dout)) * scale,
            "b": jnp.zeros((dout,))}


def _dense(p, x, dtype):
    return x @ p["w"].astype(dtype) + p["b"].astype(dtype)


def _ln(p, x):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + 1e-6)
    return (out * p["g"] + p["b"]).astype(x.dtype)


def vit_init(key, image_size: int = 224, patch: int = 16, dim: int = 256,
             depth: int = 6, heads: int = 2, mlp_dim: int = 512,
             num_classes: int = 1000) -> Params:
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    n_patches = (image_size // patch) ** 2
    keys = jax.random.split(key, depth * 4 + 3)
    # NOTE: no python scalars in the pytree are needed: patch derives
    # from embed.w's shape and heads is a call arg (a Python number
    # would stay configuration: the filter places and traces only the
    # array leaves)
    params: Params = {
        "embed": {"w": jax.random.normal(
            keys[0], (patch, patch, 3, dim)) * np.sqrt(2.0 / (patch ** 2 * 3)),
            "b": jnp.zeros((dim,))},
        "pos": jax.random.normal(keys[1], (n_patches, dim)) * 0.02,
        "blocks": [],
        "head": _dense_init(keys[2], dim, num_classes),
        "ln_f": {"g": jnp.ones((dim,)), "b": jnp.zeros((dim,))},
    }
    for i in range(depth):
        k = keys[3 + i * 4:3 + (i + 1) * 4]
        params["blocks"].append({
            "ln1": {"g": jnp.ones((dim,)), "b": jnp.zeros((dim,))},
            "qkv": _dense_init(k[0], dim, dim * 3),
            "proj": _dense_init(k[1], dim, dim),
            "ln2": {"g": jnp.ones((dim,)), "b": jnp.zeros((dim,))},
            "mlp1": _dense_init(k[2], dim, mlp_dim),
            "mlp2": _dense_init(k[3], mlp_dim, dim),
        })
    return params


def _attention(block, x, heads: int, dtype):
    """qkv projection, attention core, output projection.  The core is
    chosen from the shape alone: a sequence short enough for one key
    block takes ``short_attention`` on the projection's own layout;
    anything else is split into heads for ``flash_attention`` (which
    keeps its own rule and its jnp fallback)."""
    from ..ops import (flash_attention, short_attention,
                       short_attention_available)

    B, S, D = x.shape
    qkv = _dense(block["qkv"], x, dtype)                  # (B,S,3D)
    if short_attention_available(qkv.shape, heads, qkv.dtype):
        # straight in the caller's scope: the call's device time is
        # booked to the layer's ``attn`` stage
        o = short_attention(qkv, heads)                   # (B,S,D)
    else:
        q, k, v = jnp.split(qkv, 3, axis=-1)
        dh = D // heads

        def split(t):  # (B,S,D) → (B,H,S,dh)
            return t.reshape(B, S, heads, dh).transpose(0, 2, 1, 3)

        o = flash_attention(split(q), split(k), split(v))
        o = o.transpose(0, 2, 1, 3).reshape(B, S, D)
    return _dense(block["proj"], o, dtype)


def vit_apply(params: Params, x, heads: int = 2, dtype=None):
    """(B, H, W, 3) image → (B, num_classes) logits."""
    if dtype is None:
        dtype = jnp.bfloat16
    patch = params["embed"]["w"].shape[0]
    # stage scopes of the device trace (Documentation/observability.md)
    scope = jax.named_scope
    with scope("embed"):
        x = x.astype(dtype)
        x = jax.lax.conv_general_dilated(
            x, params["embed"]["w"].astype(dtype),
            window_strides=(patch, patch), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        B, ph, pw, D = x.shape
        x = x.reshape(B, ph * pw, D) + params["embed"]["b"].astype(dtype)
        x = x + params["pos"].astype(dtype)
    for i, block in enumerate(params["blocks"]):
        layer = f"layer{i:02d}"
        with scope(layer + "/ln1"):
            h = _ln(block["ln1"], x)
        with scope(layer + "/attn"):
            x = x + _attention(block, h, heads, dtype)
        with scope(layer + "/ln2"):
            h = _ln(block["ln2"], x)
        with scope(layer + "/mlp"):
            h = _dense(block["mlp1"], h, dtype)
            x = x + _dense(block["mlp2"], jax.nn.gelu(h), dtype)
    with scope("head"):
        x = _ln(params["ln_f"], x).mean(axis=1)           # global pool
        return _dense(params["head"], x,
                      jnp.float32).astype(jnp.float32)


def register_vit(name: str = "vit_s16", batch: int = 1,
                 image_size: int = 224, num_classes: int = 1000,
                 heads: int = 2, seed: int = 0, **kw) -> str:
    """Register a ViT in the filter model registry.

    Attention runs in a Pallas kernel where the head size dim/heads is
    64 (an even number of heads) or a multiple of 128 and ``dim`` is a
    multiple of 128; the patch sequence ((image_size/patch)²) need not
    tile (``ops.short_attention_available`` is the rule; 224/16 → 196
    patches with heads of 64 is ViT-B/16's own shape).  Default
    ``heads=2`` keeps the head dim of the default ``dim`` at 128.  Any
    other head size takes the jnp reference.
    """
    from ..filters.jax_xla import register_model

    params = vit_init(jax.random.PRNGKey(seed), image_size=image_size,
                      num_classes=num_classes, heads=heads, **kw)
    return register_model(
        name, lambda p, x: vit_apply(p, x, heads=heads), params=params,
        in_shapes=[(batch, image_size, image_size, 3)],
        in_dtypes=np.float32)
