"""Hardware detection / capability probing.

Parity target: /root/reference/gst/nnstreamer/hw_accel.c (NEON/SIMD
probing via ``getauxval(AT_HWCAP)``) and the accelerator strings the
filter layer parses (``parse_accl_hw_fill``, tensor_filter_common.c).

On this stack the accelerator inventory comes from the XLA backends:
``probe()`` reports every visible platform with device kind, counts,
and per-device memory stats when the runtime exposes them.  The jax-xla
filter's ``accelerator=`` property selects among these
(filters/jax_xla.py ``_parse_accelerator``).
"""

from __future__ import annotations

from typing import Any, Dict, List


def probe() -> Dict[str, List[dict]]:
    """Platform → list of device capability dicts."""
    import jax

    out: Dict[str, List[dict]] = {}
    for platform in ("tpu", "gpu", "cpu"):
        try:
            devs = jax.devices(platform)
        except RuntimeError:
            continue
        entries = []
        for d in devs:
            e = {
                "id": d.id,
                "kind": getattr(d, "device_kind", platform),
                "platform": d.platform,
                "process_index": getattr(d, "process_index", 0),
            }
            try:
                stats = d.memory_stats()
                if stats:
                    e["bytes_limit"] = stats.get("bytes_limit")
                    e["bytes_in_use"] = stats.get("bytes_in_use")
            except Exception:  # noqa: BLE001 — stats are best-effort
                pass
            entries.append(e)
        if entries:
            out[platform] = entries
    return out


def accelerator_available(kind: str) -> bool:
    """True when ``accelerator=<kind>`` would resolve to a device."""
    import jax

    try:
        return bool(jax.devices(kind))
    except RuntimeError:
        return False


def require_devices(need: int, who: str, cpu_devices: int = 8) -> List[Any]:
    """The default backend's devices, at least ``need`` of them, or a
    RuntimeError naming what JAX found.  The CPU client is sized to
    ``cpu_devices`` first, so a run that SAYS ``JAX_PLATFORMS=cpu``
    (CI, tests) gets its virtual mesh; an accelerator backend with too
    few chips is an error, never traded for CPU devices behind the
    caller's back."""
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", cpu_devices)
    except RuntimeError:
        pass  # backend already initialized: its device count stands
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"{who}: needs {need} devices; JAX found {len(devs)} "
            f"({devs[0].platform}, {devs[0].device_kind})")
    return devs
