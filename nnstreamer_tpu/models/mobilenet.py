"""MobileNetV1 / MobileNetV2 in pure JAX, TPU-first.

Capability parity: the classification model files the reference feeds its
filter sub-plugins (``mobilenet_v1_1.0_224_quant.tflite``,
``mobilenet_v2_1.0_224_quant.tflite`` — /root/reference/tests/
nnstreamer_filter_tensorflow2_lite/runTest.sh), here as jittable functions.

TPU design notes:
- NHWC layout end-to-end; convs lower to MXU via
  ``lax.conv_general_dilated`` with ``('NHWC','HWIO','NHWC')``.
- Compute dtype defaults to bfloat16 (MXU-native); params stay float32 and
  cast at apply time so one param pytree serves train and serve paths.
- Inference applies *folded* batch-norm (scale/bias precomputed into the
  conv epilogue) so the whole block fuses into one XLA computation; train
  mode uses batch statistics.
- No Python control flow on data — a fixed block list unrolls at trace time.

Params are nested dicts (pytrees): serialization-friendly and directly
shardable with jax.sharding NamedSharding annotations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, Any]

_DN = ("NHWC", "HWIO", "NHWC")
_BN_EPS = 1e-3


def _rng_of(key) -> np.random.Generator:
    """Host-side init RNG.  Accepts an int seed or a jax PRNGKey (its raw
    data seeds numpy).  Init runs on host with zero XLA compiles — params
    only move to device when first used under jit."""
    if isinstance(key, np.random.Generator):
        return key
    if hasattr(key, "dtype"):  # PRNGKey (old-style uint32 pair or new-style)
        try:
            import jax

            key = int(np.asarray(jax.random.key_data(key)).ravel()[-1])
        except Exception:  # noqa: BLE001 - any key layout
            key = int(np.asarray(key).ravel()[-1])
    return np.random.default_rng(int(key))


# -- primitive layers --------------------------------------------------------


def _conv_init(rng: np.random.Generator, kh, kw, cin, cout,
               groups: int = 1) -> Params:
    fan_in = kh * kw * cin // groups
    w = np.clip(rng.standard_normal(
        (kh, kw, cin // groups, cout), dtype=np.float32), -2, 2)
    w = w * np.sqrt(2.0 / max(fan_in, 1), dtype=np.float32)
    return {
        "w": w,
        # batch-norm params (fused at inference)
        "scale": np.ones((cout,), np.float32),
        "bias": np.zeros((cout,), np.float32),
        "mean": np.zeros((cout,), np.float32),
        "var": np.ones((cout,), np.float32),
    }


def _conv_bn(p: Params, x, stride: int, groups: int = 1, relu6: bool = True,
             train: bool = False, dtype=jnp.bfloat16):
    w = p["w"].astype(dtype)
    y = lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=_DN, feature_group_count=groups)
    if train:
        mean = jnp.mean(y.astype(jnp.float32), axis=(0, 1, 2))
        var = jnp.var(y.astype(jnp.float32), axis=(0, 1, 2))
    else:
        mean, var = p["mean"], p["var"]
    inv = (p["scale"] * lax.rsqrt(var + _BN_EPS)).astype(dtype)
    off = (p["bias"] - mean * p["scale"] * lax.rsqrt(var + _BN_EPS)).astype(dtype)
    y = y * inv + off
    if relu6:
        y = jnp.clip(y, 0.0, 6.0)
    return y


def _dense_init(rng: np.random.Generator, cin, cout) -> Params:
    w = np.clip(rng.standard_normal((cin, cout), dtype=np.float32), -2, 2)
    return {"w": w * np.sqrt(1.0 / cin, dtype=np.float32),
            "b": np.zeros((cout,), np.float32)}


def _dense(p: Params, x, dtype=jnp.bfloat16):
    return x @ p["w"].astype(dtype) + p["b"].astype(dtype)


# -- MobileNetV1 -------------------------------------------------------------

# (stride, out_channels) per depthwise-separable block.
_V1_BLOCKS: List[Tuple[int, int]] = [
    (1, 64), (2, 128), (1, 128), (2, 256), (1, 256), (2, 512),
    (1, 512), (1, 512), (1, 512), (1, 512), (1, 512),
    (2, 1024), (1, 1024),
]


def mobilenet_v1_init(key, num_classes: int = 1001,
                      width: float = 1.0) -> Params:
    def ch(c):
        return max(8, int(c * width))

    rng = _rng_of(key)
    params: Params = {"stem": _conv_init(rng, 3, 3, 3, ch(32))}
    cin = ch(32)
    blocks = []
    for stride, cout in _V1_BLOCKS:
        cout = ch(cout)
        blocks.append({
            "dw": _conv_init(rng, 3, 3, cin, cin, groups=cin),
            "pw": _conv_init(rng, 1, 1, cin, cout),
        })
        cin = cout
    params["blocks"] = blocks
    params["head"] = _dense_init(rng, cin, num_classes)
    return params


def mobilenet_v1_apply(params: Params, x, train: bool = False,
                       dtype=jnp.bfloat16):
    """``x``: NHWC float in [0,1] or normalized; returns (N, num_classes)
    logits in float32."""
    x = x.astype(dtype)
    x = _conv_bn(params["stem"], x, stride=2, train=train, dtype=dtype)
    for i, (stride, _cout) in enumerate(_V1_BLOCKS):
        b = params["blocks"][i]
        cin = b["dw"]["w"].shape[3]
        x = _conv_bn(b["dw"], x, stride=stride, groups=cin, train=train,
                     dtype=dtype)
        x = _conv_bn(b["pw"], x, stride=1, train=train, dtype=dtype)
    x = jnp.mean(x, axis=(1, 2))  # global average pool
    return _dense(params["head"], x, dtype=dtype).astype(jnp.float32)


# -- MobileNetV2 -------------------------------------------------------------

# (expansion, out_channels, num_repeats, first_stride)
_V2_BLOCKS: List[Tuple[int, int, int, int]] = [
    (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
    (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1),
]


def _inverted_residual_init(rng: np.random.Generator, cin, cout,
                            expansion) -> Params:
    mid = cin * expansion
    p: Params = {}
    if expansion != 1:
        p["expand"] = _conv_init(rng, 1, 1, cin, mid)
    p["dw"] = _conv_init(rng, 3, 3, mid, mid, groups=mid)
    p["project"] = _conv_init(rng, 1, 1, mid, cout)
    return p


def _inverted_residual(p: Params, x, stride: int, train: bool, dtype):
    h = x
    if "expand" in p:
        h = _conv_bn(p["expand"], h, stride=1, train=train, dtype=dtype)
    mid = h.shape[-1]
    h = _conv_bn(p["dw"], h, stride=stride, groups=mid, train=train,
                 dtype=dtype)
    h = _conv_bn(p["project"], h, stride=1, relu6=False, train=train,
                 dtype=dtype)
    if stride == 1 and x.shape[-1] == h.shape[-1]:
        h = h + x  # residual
    return h


def mobilenet_v2_init(key, num_classes: int = 1001,
                      width: float = 1.0) -> Params:
    def ch(c):
        return max(8, int(c * width))

    rng = _rng_of(key)
    params: Params = {"stem": _conv_init(rng, 3, 3, 3, ch(32))}
    cin = ch(32)
    blocks = []
    for t, c, n, s in _V2_BLOCKS:
        for _ in range(n):
            blocks.append(_inverted_residual_init(rng, cin, ch(c), t))
            cin = ch(c)
    params["blocks"] = blocks
    last = max(1280, int(1280 * width))
    params["last"] = _conv_init(rng, 1, 1, cin, last)
    params["head"] = _dense_init(rng, last, num_classes)
    return params


def _v2_strides() -> List[int]:
    out = []
    for _t, _c, n, s in _V2_BLOCKS:
        out.extend([s] + [1] * (n - 1))
    return out


def mobilenet_v2_backbone(params: Params, x, train: bool = False,
                          dtype=jnp.bfloat16,
                          taps: Sequence[int] = ()) -> Tuple[Any, List[Any]]:
    """Run stem+blocks; returns (final feature map, [tapped feature maps]).

    ``taps`` are block indices whose *outputs* are collected — SSD heads
    attach at intermediate strides the way the reference's detection
    pipelines consume `ssd_mobilenet_v2` feature maps.
    """
    # stage scopes of the device trace (Documentation/observability.md)
    with jax.named_scope("backbone/stem"):
        x = x.astype(dtype)
        x = _conv_bn(params["stem"], x, stride=2, train=train, dtype=dtype)
    tapped = []
    for i, stride in enumerate(_v2_strides()):
        with jax.named_scope(f"backbone/block{i:02d}"):
            x = _inverted_residual(params["blocks"][i], x, stride, train,
                                   dtype)
        if i in taps:
            tapped.append(x)
    return x, tapped


def mobilenet_v2_apply(params: Params, x, train: bool = False,
                       dtype=jnp.bfloat16):
    x, _ = mobilenet_v2_backbone(params, x, train=train, dtype=dtype)
    x = _conv_bn(params["last"], x, stride=1, train=train, dtype=dtype)
    x = jnp.mean(x, axis=(1, 2))
    return _dense(params["head"], x, dtype=dtype).astype(jnp.float32)


# -- registration helpers ----------------------------------------------------


@functools.lru_cache(maxsize=None)
def _cached_params(family: str, num_classes: int, width: float, seed: int):
    key = jax.random.PRNGKey(seed)
    if family == "v1":
        return mobilenet_v1_init(key, num_classes, width)
    return mobilenet_v2_init(key, num_classes, width)


def register_mobilenet(name: str = "mobilenet_v1", family: str = "v1",
                       num_classes: int = 1001, width: float = 1.0,
                       batch: int = 1, size: int = 224, seed: int = 0) -> str:
    """Register a randomly-initialized MobileNet with the jax-xla filter
    (deterministic per seed — the framework's analog of the reference's tiny
    deterministic test models, usable at real benchmark scale)."""
    from ..filters.jax_xla import register_model

    params = _cached_params(family, num_classes, width, seed)
    apply = mobilenet_v1_apply if family == "v1" else mobilenet_v2_apply
    return register_model(
        name, apply, params=params,
        in_shapes=[(batch, size, size, 3)], in_dtypes=np.float32)
