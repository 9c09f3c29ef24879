"""Device: milliseconds of the window that the intervals between fenced
windows took beyond the median interval, summed over the intervals longer
than 1.25 times the median (jitter stays below that).  It is what the
rate lost against batch / median interval: stalls of many windows and
spells of slow windows alike, which the median interval does not show."""

from benchmark.stats import percentile

SLOW = 1.25


def excess_ms(gaps) -> float:
    median = percentile(gaps, 50)
    return float(sum(g - median for g in gaps if g > SLOW * median))


def read(obs: dict):
    gaps = obs.get("window_gaps_ms")
    return excess_ms(gaps) if gaps else None
