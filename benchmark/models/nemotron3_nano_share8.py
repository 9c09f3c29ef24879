"""Application glue for the Nemotron-3-Nano share: registers the
program's stateful model (``nnstreamer_tpu/models/nemotron_h.py``: a
prefill and a decode entry point on one set of weights and one state of
recurrent states, their snapshots and caches) under a model name and
says what a pulled decode buffer serves.

A recurrent state cannot serve a position that is neither the one after
the last nor the stream's prompt end; the program counts such a step
(``position_faults``) and :func:`fence` fails the run on the first it
sees in the program's published counters, rather than count a wrong
token as served."""

from __future__ import annotations

import importlib.util
import os

from benchmark import BenchmarkError, appglue
from benchmark.appglue import served_nbytes  # noqa: F401


def _inputs(cfg: dict):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "inputs", cfg["inputs"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_inputs_for_models_" + cfg["inputs"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def preflight(cfg: dict) -> None:
    """Raise ``ImportError`` at once where the program has no such
    model or no stateful filter, before gigabytes of weights are made."""
    from nnstreamer_tpu.filters.jax_xla import register_stateful_model  # noqa: F401
    from nnstreamer_tpu.models import nemotron_h  # noqa: F401


def register(cfg: dict, params, batch: int, name: str) -> None:
    from nnstreamer_tpu.models import nemotron_h

    serving = cfg["serving"]
    if int(batch) != int(serving["streams"]):
        raise BenchmarkError(
            f"the mix's batch is {batch}, the configuration's state holds "
            f"{serving['streams']} streams")
    nemotron_h.register(
        name, nemotron_h.NemotronHConfig.from_dict(cfg), params,
        streams=int(batch), positions=_inputs(cfg).cache_positions(cfg),
        chunk=int(serving["prefill_chunk"]))


def _state_counters() -> dict:
    from nnstreamer_tpu.utils.stats import STATE_STATS

    return STATE_STATS.snapshot()


def fence(buf) -> None:
    """Wait until everything the buffer carries is computed, and fail
    on a position the state could not serve (the filter publishes its
    counters at its stats-sample cadence, so within a sample of it)."""
    appglue.fence(buf)
    faults = _state_counters().get("position_faults", 0)
    if faults:
        raise BenchmarkError(
            f"{faults} decode position(s) were neither the one after the "
            "stream's last nor its prompt end: a recurrent state cannot "
            "serve them")


def unregister(name: str) -> None:
    seen = _state_counters()
    print("[bench] state counters at the end: restores "
          f"{seen.get('restores', 0)}, position_faults "
          f"{seen.get('position_faults', 0)}", flush=True)
    appglue.unregister(name)


def outputs(buf) -> dict:
    return {"logits": buf.tensors[0].jax(), "greedy": buf.tensors[1].jax()}
