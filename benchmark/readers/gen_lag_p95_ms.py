"""Load generator: 95th percentile of (actual push - due time) over the
frames due in the window, on the benchmark's clock.  A starved generator
shows here, and is not read as a fast pool."""

from benchmark.stats import percentile


def read(obs: dict):
    lag = obs.get("gen_lag_ms")
    return percentile(lag, 95) if lag else None
