"""Filter program, seen from the sink: the median interval between two
windows fenced in the window, on the benchmark's clock.  A steadier
statistic than the rate, which a single stall moves."""

from benchmark.stats import percentile


def read(obs: dict):
    gaps = obs.get("window_gaps_ms")
    return percentile(gaps, 50) if gaps else None
