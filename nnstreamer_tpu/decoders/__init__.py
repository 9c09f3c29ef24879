"""Decoder sub-plugins (L3): tensor streams → media/semantic streams.

Parity target: the decoder sub-plugin ABI
(/root/reference/gst/nnstreamer/include/nnstreamer_plugin_api_decoder.h:38-99):
``init/exit``, ``setOption``, ``getOutCaps``, ``decode``, registered under a
mode string; sub-plugin inventory per
/root/reference/ext/nnstreamer/tensor_decoder/ (SURVEY.md §2.4).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Type

import numpy as np

from ..core import Buffer, Caps, Tensor, TensorsSpec

_lock = threading.Lock()
_decoders: Dict[str, Type["Decoder"]] = {}


# -- single-packed-drain helper ----------------------------------------------
#
# Host decoders read N tensors of one frame (boxes/classes/scores/num,
# heatmaps+offsets, ...).  Draining them one .np() at a time costs N
# device→host crossings per frame, each a blocking fetch that pays the
# full round trip.  ``drain_once`` packs every
# device-resident tensor into ONE uint8 array on the device (a jitted
# bitcast+concat — no math, pure layout) and drains that single array,
# then seeds each source Tensor's host cache from the split so later
# ``.np()`` calls are free.  The ledger sees exactly one d2h row per
# frame with the byte-exact sum of all tensor payloads.

class JitFnCache:
    """Locked, bounded get-or-compile cache for the decoders' jitted
    helper programs (packed drains, pre-reductions), keyed by input
    schema.  Bounded because a genuinely dynamic flexible stream would
    otherwise accumulate one XLA executable per distinct shape without
    limit; at the cap the cache clears wholesale and starts over.  One
    shared implementation — the three decoder caches (pack, yolo
    top-k, pose keypoints) must not each re-grow their own unlocked
    copy of this pattern."""

    def __init__(self, max_entries: int = 64):
        self._lock = threading.Lock()
        self._fns: Dict[tuple, object] = {}
        self._max = max_entries

    def get_or_build(self, key: tuple, build):
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        fn = build()  # compile outside the lock (can take seconds)
        with self._lock:
            if len(self._fns) >= self._max:
                self._fns.clear()
            return self._fns.setdefault(key, fn)

    def __len__(self) -> int:
        with self._lock:
            return len(self._fns)


_PACK_CACHE = JitFnCache()


def _pack_fn(key: tuple):
    def build():
        import jax
        import jax.numpy as jnp

        def pack(*xs):
            parts = []
            for x in xs:
                b = x if x.dtype == jnp.uint8 \
                    else jax.lax.bitcast_convert_type(x, jnp.uint8)
                parts.append(b.reshape(-1))
            return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

        return jax.jit(pack)

    return _PACK_CACHE.get_or_build(key, build)


def drain_once(tensors: List[Tensor]) -> List[np.ndarray]:
    """Drain N device-resident tensors with ONE device→host crossing;
    returns their host arrays (and seeds each tensor's host cache, so
    subsequent ``.np()`` reads are free).  Tensors already host-resident
    pass through untouched; with one (or zero) device tensors the plain
    ``.np()`` path is already optimal."""
    dev = [t for t in tensors if t.is_device]
    if len(dev) <= 1:
        return [t.np() for t in tensors]
    key = tuple((t.spec.shape, t.spec.dtype.np_dtype.str) for t in dev)
    packed = Tensor(_pack_fn(key)(*[t.jax() for t in dev]))
    from ..utils.stats import DISPATCH_STATS

    DISPATCH_STATS.count("decoder_pack")
    flat = packed.np()  # the one counted d2h drain
    off = 0
    for t in dev:
        n = t.spec.nbytes
        t.seed_host(flat[off:off + n].view(t.spec.dtype.np_dtype))
        off += n
    return [t.np() for t in tensors]


class Decoder:
    """One decode mode (e.g. image_labeling, bounding_boxes)."""

    MODE = ""

    def __init__(self):
        self.options: List[str] = [""] * 9

    def set_option(self, index: int, value: str) -> None:
        """Parity: option1..option9 properties of tensor_decoder."""
        while len(self.options) <= index:
            self.options.append("")
        self.options[index] = value
        self.options_updated()

    def options_updated(self) -> None:
        pass

    def out_caps(self, in_spec: TensorsSpec) -> Caps:
        raise NotImplementedError

    def wants_host_input(self) -> bool:
        """Whether decode() reads the input tensors on host.  True for
        every reference decoder (they are CPU rasterizers); a decoder
        that renders on-device returns False so tensor_decoder skips the
        device→host prefetch entirely."""
        return True

    def prereduce_active(self, buf: Buffer) -> bool:
        """Whether decode() will pre-reduce THIS buffer on device (an
        argmax/top-k/packed drain, so only a small final result — or
        one packed array — crosses to host).  When true,
        tensor_decoder skips the per-tensor host prefetch: prefetching
        payloads the device reduction makes redundant would pay the
        full transfer for data nobody reads."""
        return False

    def decode(self, buf: Buffer, in_spec: Optional[TensorsSpec]) -> Buffer:
        raise NotImplementedError


def register_decoder(cls: Type[Decoder]) -> Type[Decoder]:
    if not cls.MODE:
        raise ValueError(f"{cls.__name__} has empty MODE")
    with _lock:
        _decoders[cls.MODE] = cls
    return cls


def find_decoder(mode: str) -> Type[Decoder]:
    _ensure_builtin()
    with _lock:
        try:
            return _decoders[mode]
        except KeyError:
            known = ", ".join(sorted(_decoders))
            raise KeyError(
                f"no decoder mode {mode!r}; known: {known}") from None


def list_decoders():
    _ensure_builtin()
    with _lock:
        return sorted(_decoders)


_builtin_done = False
_builtin_lock = threading.Lock()


def _ensure_builtin() -> None:
    global _builtin_done
    if _builtin_done:
        return
    with _builtin_lock:
        if _builtin_done:
            return
        from . import directvideo, imagelabel  # noqa: F401
        for mod in ("boundingbox", "imagesegment", "pose", "tensorregion",
                    "octetstream", "flexbuf", "wirefmt", "python3"):
            __import__(f"{__name__}.{mod}")
        _builtin_done = True
