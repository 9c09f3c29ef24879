"""Application glue for Phi-4-mini-flash-reasoning: registers the
program's stateful model (``nnstreamer_tpu/models/phi4_flash.py``: a
prefill and a decode entry point on one set of weights and one state of
nine recurrent states with their snapshots, eight rings and ONE K/V
cache that one layer writes and eight read) under a model name.  What a
pulled decode buffer serves, the fence that fails a run on the first
``position_fault`` and the counters printed at the end are those of the
other models with a recurrent state (``nemotron3_nano_share8.py``, found
beside this file)."""

from __future__ import annotations

import importlib.util
import os

from benchmark import BenchmarkError


def _hybrid():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "nemotron3_nano_share8.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_models_nemotron3_nano_share8_for_phi4flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_H = _hybrid()
fence, unregister, outputs, served_nbytes = (
    _H.fence, _H.unregister, _H.outputs, _H.served_nbytes)


def preflight(cfg: dict) -> None:
    """Raise ``ImportError`` at once where the program has no such
    model or no stateful filter, before gigabytes of weights are made;
    and on the chip fail the run if a kernel refuses the cell's shapes
    (the paired heads' 128-wide rows on the shared cache and on a ring,
    the prefill chunk on a ring, the Mamba-1 scan of a chunk), rather
    than time the ``jnp`` path the model would fall back to.  A
    rehearsal on the CPU times nothing and runs toy shapes."""
    from nnstreamer_tpu.filters.jax_xla import register_stateful_model  # noqa: F401
    import jax.numpy as jnp

    from nnstreamer_tpu.models import phi4_flash
    from nnstreamer_tpu.ops import kernels

    if not kernels.on_tpu():
        return
    model = phi4_flash.Phi4FlashConfig.from_dict(cfg)
    serving = cfg["serving"]
    streams, chunk = int(serving["streams"]), int(serving["prefill_chunk"])
    bf16 = {jnp.dtype(jnp.bfloat16)}
    q = (streams, model.kv_pairs, model.rows, model.pair_dim)
    refusals = []
    for total, window in ((_H._inputs(cfg).cache_positions(cfg),) * 2,
                          (model.ring(chunk), model.window)):
        kv = (streams, model.kv_pairs, total, model.pair_dim)
        refusals.append(kernels.gqa_decode_attention_refusal(q, kv, kv,
                                                             window))
    ring = (streams, model.kv_pairs, model.ring(chunk), model.pair_dim)
    refusals.append(kernels.gqa_prefill_attention_refusal(
        (chunk,) + q[1:], ring, ring, model.window, bf16))
    refusals.append(kernels.selective_scan_refusal(
        chunk, (model.d_state, model.mamba.d_inner),
        {jnp.dtype(jnp.float32)}))
    if any(refusals):
        raise BenchmarkError(
            f"{cfg['name']}: a kernel refuses the cell's shapes: "
            + "; ".join(r for r in refusals if r))


def register(cfg: dict, params, batch: int, name: str) -> None:
    from nnstreamer_tpu.models import phi4_flash

    serving = cfg["serving"]
    if int(batch) != int(serving["streams"]):
        raise BenchmarkError(
            f"the mix's batch is {batch}, the configuration's state holds "
            f"{serving['streams']} streams")
    phi4_flash.register(
        name, phi4_flash.Phi4FlashConfig.from_dict(cfg), params,
        streams=int(batch), positions=_H._inputs(cfg).cache_positions(cfg),
        chunk=int(serving["prefill_chunk"]))
