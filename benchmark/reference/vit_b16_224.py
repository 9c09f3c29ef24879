"""Plain float32 reference of the ViT-B/16 configuration, the comparison
that decides ``correct`` for its cells, and the control.

Written from ``benchmark/configs/vit_b16_224.json`` (Dosovitskiy et al.,
Table 1, ViT-Base, with the listed departures: global average pooling,
no class token, tanh GELU, LayerNorm epsilon 1e-6): straightforward
``jax.numpy`` at ``precision=HIGHEST``, attention as an explicit
softmax(q k^T / sqrt(d)) v, no kernels, no fusion.  It imports nothing
of the program and makes its own weights from the seed.

What is compared is what the timed path hands the application: the
logits of sampled frames.

``logits_rel_l2``  ||served - reference|| / ||reference|| over the sample

The control is the same forward pass with every matrix multiplication's
inputs and weights rounded to float8_e4m3fn, the nearest precision below
the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _weights_module():
    path = os.path.join(os.path.dirname(_HERE), "weights", "vit_b16_224.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_weights_vit_b16_224", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _forward_fn(cfg: dict, lower: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax

    heads = int(cfg["num_attention_heads"])
    patch = int(cfg["patch_size"])
    eps = float(cfg["layer_norm_eps"])
    hi = lax.Precision.HIGHEST

    def q(a):
        a = a.astype(jnp.float32)
        if not lower:
            return a
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def dense(p, x):
        return jnp.matmul(q(x), q(p["w"]), precision=hi) + p["b"]

    def layer_norm(p, x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]

    def gelu_tanh(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))

    def attention(blk, x):
        b, s, d = x.shape
        dh = d // heads
        qkv = dense(blk["qkv"], x)
        qq, kk, vv = (t.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)
                      for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q(qq), q(kk),
                            precision=hi) / np.sqrt(dh)
        prob = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", q(prob), q(vv), precision=hi)
        return dense(blk["proj"], out.transpose(0, 2, 1, 3).reshape(b, s, d))

    def forward(params, frames_u8):
        x = (frames_u8.astype(jnp.float32) - 127.5) / 127.5
        x = lax.conv_general_dilated(
            q(x), q(params["embed"]["w"]), (patch, patch), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
        b, ph, pw, d = x.shape
        x = x.reshape(b, ph * pw, d) + params["embed"]["b"]
        x = x + params["pos"].astype(jnp.float32)
        for blk in params["blocks"]:
            x = x + attention(blk, layer_norm(blk["ln1"], x))
            h = gelu_tanh(dense(blk["mlp1"], layer_norm(blk["ln2"], x)))
            x = x + dense(blk["mlp2"], h)
        pooled = layer_norm(params["ln_f"], x).mean(axis=1)
        return dense(params["head"], pooled)

    return jax.jit(forward)


@functools.lru_cache(maxsize=4)
def _forward_cached(cfg_key: str, lower: bool):
    return _forward_fn(json.loads(cfg_key), lower)


def raw_outputs(cfg: dict, seed: int, frames_u8, lower: bool = False,
                block: int = 16) -> np.ndarray:
    """Reference logits (n, classes) as numpy float32, in blocks."""
    import jax

    params = _weights_module().make(cfg, seed)
    key = json.dumps({k: cfg[k] for k in (
        "num_attention_heads", "patch_size", "layer_norm_eps")},
        sort_keys=True)
    fwd = _forward_cached(key, bool(lower))
    frames_u8 = np.asarray(frames_u8)
    out = []
    for i in range(0, len(frames_u8), block):
        part = frames_u8[i:i + block]
        pad = block - len(part)
        if pad:
            part = np.concatenate([part, np.repeat(part[-1:], pad, 0)])
        out.append(np.asarray(fwd(params, jax.device_put(part)))[:block - pad])
    return np.concatenate(out)


def compare_numbers(cfg: dict, ref_logits, served: dict) -> dict:
    got = np.asarray(served["logits"], np.float32)
    ref = np.asarray(ref_logits, np.float32)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return {"logits_rel_l2": float("inf")}
    return {"logits_rel_l2":
            float(np.linalg.norm(got - ref) / np.linalg.norm(ref))}


def check(cfg: dict, seed: int, frames_u8, served: dict) -> list:
    numbers = compare_numbers(cfg, raw_outputs(cfg, seed, frames_u8), served)
    return [{"name": k, "value": v, "limit": float(cfg["limits"][k])}
            for k, v in numbers.items()]


def control(cfg: dict, seed: int, frames_u8) -> list:
    ref = raw_outputs(cfg, seed, frames_u8)
    low = raw_outputs(cfg, seed, frames_u8, lower=True)
    numbers = compare_numbers(cfg, ref, {"logits": low})
    return [{"name": k, "value": v, "limit": float(cfg["limits"][k])}
            for k, v in numbers.items()]
