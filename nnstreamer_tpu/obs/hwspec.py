"""Hardware peak table — the denominator of every utilization figure.

MFU and HBM-bandwidth utilization are ratios against *hardware* peaks.
This module is the program's one table: the scrape-time MFU join
(:mod:`.xlacost`) resolves the spec of the device an executable was
compiled for through :func:`spec_for_device_kind`, and ``chip_smoke.py``
refuses a device that is not in it.

The table is keyed by ``jax.Device.device_kind`` — the string the
runtime reports for the silicon — not by the platform tag: every TPU
generation says ``platform == "tpu"``, and quoting one generation's
peaks for another would be a made-up number.  A kind that is not in
the table (the CPU the tests run on, or a TPU generation nobody has
entered) resolves to ``None``: cost capture still exports the
program's flops / bytes / arithmetic intensity — those are
computation-intrinsic — but no utilization gauge and no price is
derived.  :func:`set_override` lets a test pin the spec explicitly.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class HwSpec:
    """Public peak figures of one accelerator generation."""

    name: str
    peak_flops: float        #: dense bf16 peak, FLOP/s per chip
    hbm_bw: float            #: HBM bandwidth, bytes/s per chip
    ici_bw: float = 0.0      #: aggregate ICI, bytes/s per chip
    chip_hour_usd: float = 0.0  #: on-demand list price, $/chip-hour

    @property
    def ridge(self) -> float:
        """Roofline ridge point (flops/byte): programs above it are
        compute-bound, below it bandwidth-bound."""
        return self.peak_flops / self.hbm_bw if self.hbm_bw else 0.0


#: TPU v5e, per chip.  Source: Google Cloud documentation, "TPU v5e"
#: system architecture page — 197 TFLOP/s bf16, 819 GB/s HBM2e,
#: 1,600 Gbit/s (= 200 GB/s) inter-chip interconnect.  $1.20 is the
#: on-demand list price per chip-hour (Cloud TPU pricing page); a
#: deployment's own price goes in NNS_TPU_CHIP_HOUR_USD.
V5E = HwSpec(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
             ici_bw=200e9, chip_hour_usd=1.20)

#: ``jax.Device.device_kind`` -> spec.  A v5e chip reports
#: "TPU v5 lite".  Anything absent — CPU included — is unknown
#: hardware: intensity-only reporting, no utilization, no price.
DEVICE_KIND_SPECS: Dict[str, HwSpec] = {
    "TPU v5 lite": V5E,
}

_lock = threading.Lock()
_override: Optional[HwSpec] = None


def set_override(spec: Optional[HwSpec]) -> Optional[HwSpec]:
    """Pin the spec every utilization derivation uses (None clears it).
    Returns the previous override so tests can restore it."""
    global _override
    with _lock:
        prev = _override
        _override = spec
    return prev


def spec_for_device_kind(device_kind: Optional[str]) -> Optional[HwSpec]:
    """The peak table entry for a ``jax.Device.device_kind``, or None
    when the hardware is unknown (utilization must not be derived)."""
    with _lock:
        if _override is not None:
            return _override
    return DEVICE_KIND_SPECS.get(str(device_kind or ""))


def default_device_kind() -> Optional[str]:
    """``device_kind`` of the process's first device; None while jax is
    not even imported (a scrape must not be what initializes — and on
    an accelerator host, claims — the backend)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.devices()[0].device_kind


def chip_hour_price(device_kind: Optional[str]) -> float:
    """The $/chip-hour figure the tenant cost export multiplies
    device-seconds by (``nns_tenant_dollars_total``).  Resolution
    order: ``NNS_TPU_CHIP_HOUR_USD`` (deployment override — negotiated
    pricing differs from list), then the active spec override, then the
    device-kind table.  0.0 when the hardware (and hence a price) is
    unknown — a dollars figure from a made-up price would be worse
    than none; the tenant table still carries device-seconds."""
    env = os.environ.get("NNS_TPU_CHIP_HOUR_USD", "").strip()
    if env:
        try:
            return max(float(env), 0.0)
        except ValueError:
            pass  # a malformed override must not break a scrape
    spec = spec_for_device_kind(device_kind)
    return spec.chip_hour_usd if spec is not None else 0.0
