"""What the model adaptors under ``benchmark/models/`` share: the calls
an application makes around any registered model."""

from __future__ import annotations


def unregister(name: str) -> None:
    from nnstreamer_tpu.filters.jax_xla import unregister_model

    unregister_model(name)


def _arrays(buf):
    det = buf.meta.get("detections_device") or {}
    return [t.jax() for t in buf.tensors] + list(det.values())


def fence(buf) -> None:
    """Wait until everything the buffer carries is computed."""
    for a in _arrays(buf):
        a.block_until_ready()


def served_nbytes(buf) -> int:
    """Bytes of everything the buffer serves the application."""
    return int(sum(a.nbytes for a in _arrays(buf)))
