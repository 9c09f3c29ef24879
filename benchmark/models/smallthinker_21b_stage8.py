"""Application glue for the SmallThinker stage: registers the program's
stateful model (``nnstreamer_tpu/models/smallthinker.py``: a prefill and
a decode entry point on one set of weights and one state of rings and
full caches) under a model name and says what a pulled decode buffer
serves."""

from __future__ import annotations

import importlib.util
import os

from benchmark import BenchmarkError
from benchmark.appglue import fence, served_nbytes, unregister  # noqa: F401


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
    from nnstreamer_tpu.models import smallthinker  # noqa: F401


def register(cfg: dict, params, batch: int, name: str) -> None:
    from nnstreamer_tpu.models import smallthinker

    serving = cfg["serving"]
    if int(batch) != int(serving["streams"]):
        raise BenchmarkError(
            f"the mix's batch is {batch}, the configuration's caches hold "
            f"{serving['streams']} streams")
    smallthinker.register(
        name, smallthinker.SmallThinkerConfig.from_dict(cfg), params,
        streams=int(batch), positions=_inputs(cfg).cache_positions(cfg),
        chunk=int(serving["prefill_chunk"]))


def outputs(buf) -> dict:
    return {"logits": buf.tensors[0].jax(), "greedy": buf.tensors[1].jax()}
