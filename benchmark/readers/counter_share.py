"""One counter a stateful model keeps about its own state as a share of
another (``nnstreamer_tpu/utils/stats.py`` ``STATE_STATS``), over the
whole run and not over the window: what set-up counted is there too.
``phi4flash_cross_tokens_share`` is ``cross_tokens`` (tokens the layers
above the shared cache's writer ran on in prefill) over
``prefill_tokens``: about one a prefill chunk where the prefill stops at
that layer, 1.0 where it does not.

The filter adds a state's counters up at its stats-sample cadence, so by
the window's end the prefill's are all in.  ``None`` where the program
keeps no such counters (another model, a program without the model) or
the denominator is 0."""


def read(obs: dict, counter: str, of: str):
    try:
        from nnstreamer_tpu.utils.stats import STATE_STATS
    except ImportError:
        return None
    seen = STATE_STATS.snapshot()
    if counter not in seen or seen.get(of, 0) <= 0:
        return None
    return seen[counter] / seen[of]
