"""``nnstreamer_tpu.obs`` — unified observability layer.

The runtime introspection the reference ecosystem delegates to external
tooling (gst-top / gst-instruments wall-time attribution, NNShark's
GstTracer-fed per-element view, GstTracer latency tracers), built in as
one subsystem (Documentation/observability.md):

- :mod:`.metrics` — process-wide registry of labeled counters / gauges /
  histograms that absorbs the runtime's existing stats at *scrape* time
  (``Element.count_stat`` flow counters, ``InvokeStats.snapshot()``,
  MicroBatcher/SharedBatcher window state, ``queue`` depth, the serving
  ``ModelPool``), with Prometheus text exposition, a JSON snapshot API
  and an optional stdlib-http endpoint (``serve_metrics`` /
  ``NNS_TPU_METRICS_PORT``).
- :mod:`.tracer` — GstTracer-style per-buffer latency tracer fed by
  hook points in the runtime core (pre/post chain, queue in/out,
  batching park → dispatch → demux), sampled 1-in-N, exporting
  per-element residency breakdowns and Chrome trace-event JSON
  (Perfetto-loadable) for the host-side time a JAX device trace can't
  see.
- :mod:`.hooks` — the one-global-read dispatch point the runtime hot
  path checks; strictly a no-op while no tracer is attached.
- :mod:`.tracectx` — cross-device trace propagation: the wire contexts
  that carry a sampled trace over a tensor_query/edge/MQTT/gRPC hop and
  the clock math that places remote spans on the local timeline.
- :mod:`.top` — ``nns-top``: the gst-top/NNShark parity tool, a
  live/``--once`` terminal table of per-element frames/s, queue depth,
  invoke latency, host/device cost attribution (DEV/HOST columns),
  batch/stream occupancy per pipeline and per pool — plus LINK rows
  for the edge links and a COMPILE section (XLA compile telemetry),
  aggregated across a fleet of ``--connect`` endpoints.
- :mod:`.transfer` — the byte-exact host↔device transfer ledger:
  every crossing at the jax seams counted with exact ``nbytes``,
  labeled ``{pipeline, source, direction, reason}``, exported as
  ``nns_transfer_*`` + ``nns-top`` XFER columns and, for sampled
  buffers, Chrome-trace ``xfer`` sub-spans (the crossings-per-frame
  measurement substrate for the device-resident-dataflow rework).
- :mod:`.devicemem` — scrape-time device-memory accounting
  (``nns_device_memory_bytes{device,kind}``; graceful empty table on
  the CPU backend) plus per-pool model weight footprints.
- :mod:`.flightrec` — the always-on flight recorder: a bounded ring
  of control-plane events dumped (Perfetto trace + registry snapshot)
  on admission hard-shed, breaker open, element error, ``/dump`` or
  SIGUSR2.
- :mod:`.scrape` — the shared fleet scrape client (one
  snapshot-over-HTTP fetch/parse + failure-tolerance implementation
  behind both ``nns-top --connect`` and the watchdog's fleet mode).
- :mod:`.watch` — ``nns-watch``: the alerting watchdog; a background
  sampler folding registry snapshots into bounded per-series rings
  (rate / level / windowed quantiles) and evaluating declarative
  threshold / SLO-burn / drift-anomaly rules, with bus-WARNING +
  flight-recorder + ``nns_alert_state`` export actions
  ("Alerting & watchdog" in the docs).
- :mod:`.control` — ``nns-ctl``: the closed-loop controller; watch
  alert state mapped through declarative playbooks onto the bounded,
  cooldown-guarded, reversible actuator API
  (``runtime/actuators.py``) on serving pools, admission and link
  breakers — every decision audited (ring + ``nns_control_*`` export,
  snapshot-v6 ``control`` table, ``nns-top`` CONTROL section,
  ``/healthz`` summary) and the fault → alert → actuation →
  knob-restored loop pinned by ``tests/test_control.py``.
"""

from __future__ import annotations

from . import hooks
from .metrics import REGISTRY, LinkMetrics, MetricsRegistry, serve_metrics
from .tracer import TRACE_META_KEY, LatencyTracer

__all__ = [
    "REGISTRY",
    "LinkMetrics",
    "MetricsRegistry",
    "serve_metrics",
    "LatencyTracer",
    "TRACE_META_KEY",
    "hooks",
]
