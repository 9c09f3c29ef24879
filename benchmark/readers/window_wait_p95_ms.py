"""Window formation: 95th percentile of the time sampled frames waited
parked in a coalescing window (``LatencyTracer`` marks ``park`` ->
``dispatch``), on the host clock."""

from benchmark.stats import percentile


def read(obs: dict):
    records = (obs.get("trace") or {}).get("tracer_records")
    if not records:
        return None
    waits = []
    for rec in records:
        parked = None
        for t, _name, phase in rec["marks"]:
            if phase == "park":
                parked = t
            elif phase == "dispatch" and parked is not None:
                waits.append((t - parked) * 1e3)
                parked = None
    return percentile(waits, 95) if waits else None
