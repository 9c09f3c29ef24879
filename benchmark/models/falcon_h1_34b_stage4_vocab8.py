"""Application glue for the Falcon-H1-34B stage: registers the program's
stateful model (``nnstreamer_tpu/models/falcon_h1.py``: a prefill and a
decode entry point on one set of weights and one state in which every
layer keeps a recurrent state, its snapshot and a K/V cache) under a
model name.  What a pulled decode buffer serves, the fence that fails a
run on the first ``position_fault`` and the counters printed at the end
are those of the other model with a recurrent state
(``nemotron3_nano_share8.py``, found beside this file): a recurrent
state cannot serve a position that is neither the one after the last
nor the stream's prompt end, whichever model keeps it."""

from __future__ import annotations

import importlib.util
import os

from benchmark import BenchmarkError


def _hybrid():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "nemotron3_nano_share8.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_models_nemotron3_nano_share8_for_falcon_h1", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_H = _hybrid()
fence, unregister, outputs, served_nbytes = (
    _H.fence, _H.unregister, _H.outputs, _H.served_nbytes)


def preflight(cfg: dict) -> None:
    """Raise ``ImportError`` at once where the program has no such
    model or no stateful filter, before gigabytes of weights are made;
    and on the chip fail the run if the state update's kernel refuses
    the cell's shape, rather than time the ``jnp`` step behind the
    restore loop the model would fall back to.  A rehearsal on the CPU
    times nothing and runs toy shapes; the decode attention kernel
    raises by itself."""
    from nnstreamer_tpu.filters.jax_xla import register_stateful_model  # noqa: F401
    import jax.numpy as jnp

    from nnstreamer_tpu.models import falcon_h1
    from nnstreamer_tpu.ops import kernels

    if not kernels.on_tpu():
        return
    geo = falcon_h1.FalconH1Config.from_dict(cfg).mamba
    refusal = kernels.ssm_decode_step_refusal(
        (int(cfg["serving"]["streams"]), geo.groups, geo.state_size,
         geo.d_inner // geo.groups), {jnp.dtype(jnp.float32)})
    if refusal:
        raise BenchmarkError(
            f"{cfg['name']}: ssm_decode_step refuses the cell's state: "
            f"{refusal}")


def register(cfg: dict, params, batch: int, name: str) -> None:
    from nnstreamer_tpu.models import falcon_h1

    serving = cfg["serving"]
    if int(batch) != int(serving["streams"]):
        raise BenchmarkError(
            f"the mix's batch is {batch}, the configuration's state holds "
            f"{serving['streams']} streams")
    falcon_h1.register(
        name, falcon_h1.FalconH1Config.from_dict(cfg), params,
        streams=int(batch), positions=_H._inputs(cfg).cache_positions(cfg),
        chunk=int(serving["prefill_chunk"]))
