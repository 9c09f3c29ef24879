"""Multi-host distributed runtime: process init + hybrid ICI/DCN meshes.

Parity target: the reference's cross-host communication backend — the
external nnstreamer-edge library plus MQTT/gRPC bridges (SURVEY.md §5.8)
— whose TPU-native form is the XLA runtime itself: every host runs the
same program, `jax.distributed` forms the process group, and collectives
ride ICI within a slice and DCN across slices.  Pipelines then scale
multi-host with NO element changes: the jax-xla filter's computation is
jitted over a global mesh and XLA inserts the cross-host collectives
(the "pick a mesh → annotate shardings → let XLA place collectives"
recipe).

- :func:`initialize` wraps ``jax.distributed.initialize`` with
  environment autodetection (TPU pods populate coordinator/process info
  themselves; explicit args serve CPU/GPU clusters and tests).
- :func:`hybrid_mesh` builds a Mesh whose outer axes span hosts over DCN
  and inner axes span the ICI-connected devices of each slice — the
  layout that keeps bandwidth-hungry collectives (tensor/sequence
  parallel) on ICI and only data-parallel gradient reductions on DCN.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join (or form) the multi-host process group.

    On TPU pods all arguments are autodetected from the runtime
    environment; pass them explicitly for CPU/GPU clusters.  Safe to call
    once per process, before any other jax API touches the backend.
    """
    import jax

    kw = {}
    if coordinator_address is not None:
        kw["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    jax.distributed.initialize(**kw)


def process_info() -> Tuple[int, int]:
    """(process_index, process_count) of this host."""
    import jax

    return jax.process_index(), jax.process_count()


def hybrid_mesh(ici_axes: Sequence[Tuple[str, int]],
                dcn_axes: Optional[Sequence[Tuple[str, int]]] = None,
                devices=None):
    """Mesh with DCN-spanning outer axes and ICI-spanning inner axes.

    ``ici_axes``: (name, size) per intra-slice axis, e.g.
    ``[("model", 4), ("data", 2)]``.  ``dcn_axes``: (name, size) per
    cross-host axis, e.g. ``[("replica", num_slices)]``; defaults to a
    size-1 ``replica`` axis so single-slice runs use the same call.
    """
    import jax
    from jax.experimental import mesh_utils

    dcn_axes = list(dcn_axes or [("replica", 1)])
    ici_axes = list(ici_axes)
    names = tuple(n for n, _ in dcn_axes) + tuple(n for n, _ in ici_axes)
    ici_shape = tuple(s for _, s in ici_axes)
    dcn_shape = tuple(s for _, s in dcn_axes)
    if all(s == 1 for s in dcn_shape):
        # single-slice: a plain device mesh with leading unit axes keeps
        # the axis names (and therefore the sharding annotations) stable
        devs = devices if devices is not None else jax.devices()
        import numpy as np

        n = int(np.prod(ici_shape))
        if len(devs) < n:
            raise ValueError(
                f"hybrid_mesh: need {n} devices for {ici_axes}, have "
                f"{len(devs)}")
        arr = np.array(devs[:n]).reshape(dcn_shape + ici_shape)
        return jax.sharding.Mesh(arr, names)
    # create_hybrid_device_mesh multiplies mesh and dcn shapes
    # ELEMENTWISE (np.block), so both must be padded to the combined
    # rank: DCN axes lead with unit ICI extents and vice versa.
    mesh_shape = (1,) * len(dcn_shape) + ici_shape
    dcn_mesh_shape = dcn_shape + (1,) * len(ici_shape)
    try:
        arr = mesh_utils.create_hybrid_device_mesh(
            mesh_shape, dcn_mesh_shape, devices=devices)
    except (ValueError, AttributeError):
        # non-TPU process groups (CPU/GPU clusters) carry no
        # slice_index — mesh_utils either sees one big slice
        # (ValueError) or trips on the missing attribute entirely
        # (AttributeError, backend-dependent): group by process_index
        # instead — DCN axes span processes, ICI axes span each
        # process's local devices
        arr = _mesh_by_process(jax, devices, dcn_shape, ici_shape)
    return jax.sharding.Mesh(arr, names)


def _mesh_by_process(jax, devices, dcn_shape, ici_shape):
    import numpy as np

    devs = list(devices) if devices is not None else jax.devices()
    groups: dict = {}
    for d in devs:
        groups.setdefault(d.process_index, []).append(d)
    ndcn = int(np.prod(dcn_shape))
    nici = int(np.prod(ici_shape))
    if len(groups) != ndcn:
        raise ValueError(
            f"hybrid_mesh: dcn axes {tuple(dcn_shape)} want {ndcn} "
            f"processes, group has {len(groups)}")
    ordered = []
    for pi in sorted(groups):
        local = sorted(groups[pi], key=lambda d: d.id)
        if len(local) < nici:
            raise ValueError(
                f"hybrid_mesh: ici axes {tuple(ici_shape)} want {nici} "
                f"devices per process, process {pi} has {len(local)}")
        if len(local) > nici:
            from ..utils.log import logw

            logw("hybrid_mesh: ici axes %s use %d of process %d's %d "
                 "local devices; devices %s stay idle",
                 tuple(ici_shape), nici, pi, len(local),
                 [d.id for d in local[nici:]])
        ordered.extend(local[:nici])
    return np.array(ordered).reshape(tuple(dcn_shape) + tuple(ici_shape))
