"""95th percentile latency, due time to sink fence, of every frame due
in the window that was delivered."""

from benchmark.stats import percentile


def read(obs: dict):
    lat = obs.get("latencies_ms")
    return percentile(lat, 95) if lat else None
