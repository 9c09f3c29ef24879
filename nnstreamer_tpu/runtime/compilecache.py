"""Persistent AOT compilation cache: skip the XLA trace+build across
process restarts.

PR 7's CompileStats made cold-start cost a *measured* number — every
fresh serving process pays the full trace + XLA build for every
(model, schema, bucket) executable it serves, and nothing removed that
cost.  This module does: the ``Lowered``/``Compiled`` objects PR 9's
``_aot_call`` already holds are serialized (``jax.experimental.
serialize_executable``) under ``NNS_TPU_COMPILE_CACHE_DIR``, keyed by
everything that makes two compiles interchangeable::

    (model digest, input schema, bucket, placement canonical key,
     jax version, jaxlib version, backend platform)

A fresh process/host with a warm cache *deserializes* the executable
instead of tracing and building it, and every load is counted into
CompileStats under the ``persist_hit`` kind, so the cold-start win is
an exportable number (``nns_compiles_total{kind="persist_hit"}``) that
``tests/test_lifecycle.py::test_persistent_cache_hits_and_counts``
holds to the executables actually loaded.

Failure policy: the cache can only ever make things faster, never
wronger or broken.  A corrupt/truncated/version-skewed entry fails the
deserialize and falls back to a normal compile (the bad file is
removed best-effort); an unwritable cache dir disables stores but
leaves serving untouched (and ``nns-lint`` NNS513 warns about the
misconfiguration up front).  Entries carry the jax/jaxlib versions and
backend platform in their *key*, so a version bump or a CPU↔TPU move
simply misses instead of loading an incompatible program.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
from typing import Any, Optional, Sequence

from ..utils.log import logw

#: the one switch: set to a directory to arm the persistent cache
CACHE_ENV = "NNS_TPU_COMPILE_CACHE_DIR"

#: on-disk entry suffix (pickled ``serialize_executable`` 3-tuple)
CACHE_SUFFIX = ".aotx"

_lock = threading.Lock()
#: cache dirs we already warned about (unwritable/missing) — once each
_warned_dirs: set = set()


class CacheStats:
    """Process-wide persistent-cache accounting, pulled like every
    other collected stat (``tests/test_lifecycle.py`` asserts
    ``hits == executables loaded``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.errors = 0  # corrupt/unreadable entries, failed stores

    def _bump(self, field: str) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + 1)

    def snapshot(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "stores": self.stores, "errors": self.errors}

    def reset(self) -> None:
        with self._lock:
            self.hits = self.misses = self.stores = self.errors = 0


#: the process-wide persistent-cache stats
CACHE_STATS = CacheStats()


def cache_dir() -> Optional[str]:
    """The armed cache directory, or None when the env is unset.  A
    set-but-missing/unwritable directory returns None too (with one
    warning per directory): a misconfigured cache must degrade to
    "no cache", never to a serving failure."""
    path = os.environ.get(CACHE_ENV, "").strip()
    if not path:
        return None
    if not os.path.isdir(path) or not os.access(path, os.W_OK):
        with _lock:
            if path not in _warned_dirs:
                _warned_dirs.add(path)
                logw("compilecache: %s=%r is not a writable directory "
                     "— persistent AOT cache disabled (nns-lint "
                     "NNS513 flags this)", CACHE_ENV, path)
        return None
    return path


def enabled() -> bool:
    return cache_dir() is not None


def _versions() -> tuple:
    import jax

    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "?")
    except ImportError:  # pragma: no cover - jaxlib rides with jax
        jl = "?"
    return (getattr(jax, "__version__", "?"), jl)


def _platform() -> str:
    """Backend platform baked into the key: a serialized CPU executable
    must never be offered to a TPU process (it would fail the
    deserialize — but missing outright is cheaper and quieter)."""
    try:
        import jax

        return jax.default_backend()
    except Exception:  # noqa: BLE001 - key derivation must not raise
        return "?"


def file_digest(path: str) -> str:
    """Content digest of a model file (streamed sha256) — the model
    component of the cache key for file-backed models: editing the
    file in place misses instead of serving stale weights."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def model_digest(model_def: Any) -> str:
    """Digest of a ModelDef-ish object.  File-backed models (``name``
    is an existing file) digest by CONTENT; in-process models digest by
    name + function source (best effort) + the params tree's schema:
    every array leaf's shape and dtype, and the VALUE of every leaf that
    is no array (configuration the function closes over).  The weights'
    values are no part of it: they are arguments of the executable
    (``filters/jax_xla.py``), so one entry serves every set of weights
    of the schema — on one device; a meshed filter's executable still
    holds its weights, and content skew under an unchanged name and
    source is the caller's contract there (Documentation/lifecycle.md)."""
    name = str(getattr(model_def, "name", "") or "")
    if name and os.path.isfile(name):
        try:
            return "file:" + file_digest(name)
        except OSError:
            pass
    h = hashlib.sha256()
    h.update(name.encode())
    fn = getattr(model_def, "fn", None)
    if fn is not None:
        try:
            import inspect

            h.update(inspect.getsource(fn).encode())
        except (OSError, TypeError):
            h.update(repr(fn).encode())
    params = getattr(model_def, "params", None)
    if params is not None:
        try:
            import jax

            for leaf in jax.tree_util.tree_leaves(params):
                if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                    h.update(f"{leaf.shape}{leaf.dtype}".encode())
                else:
                    h.update(repr(leaf).encode())
        except Exception:  # noqa: BLE001 - schema digest is best effort
            pass
    return "obj:" + h.hexdigest()


def make_key(model_dig: str, in_spec: Any, bucket: int,
             placement_key: Any, donate: bool = False) -> str:
    """The persistent key: everything that makes two compiles
    interchangeable, hashed to a filename-safe id."""
    h = hashlib.sha256()
    for part in (model_dig, str(in_spec), str(int(bucket)),
                 repr(placement_key), "donate" if donate else "",
                 *_versions(), _platform()):
        h.update(str(part).encode())
        h.update(b"\x00")
    return h.hexdigest()


def _entry_path(dirpath: str, key: str) -> str:
    return os.path.join(dirpath, key + CACHE_SUFFIX)


def load(key: str, devices: Optional[Sequence[Any]] = None
         ) -> Optional[Any]:
    """Deserialize one cached executable; None on miss OR any failure
    (corrupt pickle, truncated payload, version-skewed program — the
    bad entry is removed best-effort and counted as an error).

    ``devices`` is the device list the executable was compiled for, in
    assignment order (one device, or the mesh's flat device list).
    ``deserialize_and_load`` otherwise loads onto EVERY device of the
    backend, and a one-device program then dies at its first call on
    any host with more than one device."""
    dirpath = cache_dir()
    if dirpath is None:
        return None
    path = _entry_path(dirpath, key)
    if not os.path.exists(path):
        CACHE_STATS._bump("misses")
        return None
    try:
        from jax.experimental import serialize_executable as _se

        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        compiled = _se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=list(devices) if devices else None)
    except Exception as e:  # noqa: BLE001 - ANY load failure means
        # "treat as miss and recompile"; a cache can corrupt in every
        # way a filesystem can, and none of them may break serving
        CACHE_STATS._bump("errors")
        CACHE_STATS._bump("misses")
        logw("compilecache: dropping unreadable entry %s (%s: %s)",
             os.path.basename(path), type(e).__name__, e)
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    CACHE_STATS._bump("hits")
    return compiled


def store(key: str, compiled: Any) -> bool:
    """Serialize one executable under ``key`` (atomic tmp+rename so a
    concurrent reader never sees a torn entry).  False (counted) when
    the backend cannot serialize this program or the write fails."""
    dirpath = cache_dir()
    if dirpath is None:
        return False
    try:
        from jax.experimental import serialize_executable as _se

        blob = pickle.dumps(_se.serialize(compiled))
    except Exception as e:  # noqa: BLE001 - backend-dependent API:
        # an unserializable program just stays uncached
        CACHE_STATS._bump("errors")
        logw("compilecache: cannot serialize executable for %s... "
             "(%s: %s)", key[:12], type(e).__name__, e)
        return False
    path = _entry_path(dirpath, key)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError as e:
        CACHE_STATS._bump("errors")
        logw("compilecache: cannot write %s: %s", path, e)
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False
    CACHE_STATS._bump("stores")
    return True


def load_or_compile(key: Optional[str], lowered: Any,
                    stats_kind: str = "persist_hit",
                    bucket: int = 0,
                    devices: Optional[Sequence[Any]] = None,
                    owner: str = "compilecache") -> Any:
    """The one seam ``filters/jax_xla._aot_call`` drives: try the
    persistent cache, fall back to ``lowered.compile()``, store the
    fresh build for the next process.  A cache hit is recorded into
    CompileStats under ``persist_hit`` with the DESERIALIZE time as its
    seconds — the number the cold-start gate compares against the
    trace+build cost it replaced.  ``devices``: see :func:`load`.
    The whole of it is the ``<owner>/load_or_compile`` set-up span,
    noted ``hit`` or ``miss``."""
    from ..utils import profile as _profile
    from ..utils.stats import COMPILE_STATS

    with _profile.span(owner, "load_or_compile", setup=True) as sp:
        sp.note = "miss"
        if key is not None:
            t0 = time.perf_counter()
            cached = load(key, devices)
            if cached is not None:
                sp.note = "hit"
                COMPILE_STATS.record(stats_kind,
                                     time.perf_counter() - t0,
                                     bucket=bucket)
                return cached
        compiled = lowered.compile()
        if key is not None:
            store(key, compiled)
        return compiled
