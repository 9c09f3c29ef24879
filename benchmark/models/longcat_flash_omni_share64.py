"""Application glue for the LongCat-Flash share: registers the program's
stateful model (``nnstreamer_tpu/models/longcat_flash.py``: a prefill and
a decode entry point on one set of weights and one state of two latent
caches a layer) under a model name and says what a pulled decode buffer
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


def _sizes(cfg: dict) -> dict:
    serving = cfg["serving"]
    return {"streams": int(serving["streams"]),
            "positions": _inputs(cfg).cache_positions(cfg),
            "chunk": int(serving["prefill_chunk"])}


def preflight(cfg: dict) -> None:
    """Raise ``ImportError`` at once where the program has no such model
    or no stateful filter, before gigabytes of weights are made; and on
    the chip fail the run if the grouped product's kernel refuses one of
    the cell's shapes, rather than time the loop the model would fall
    back to: the held experts' at a decode step's and a prefill chunk's
    rows, and a dense MLP's as one group of one expert at a decode
    step's (a chunk's dense MLPs are XLA's products).  A rehearsal on
    the CPU times nothing and runs toy shapes; the decode attention
    kernel has no second path and raises by itself."""
    from nnstreamer_tpu.filters.jax_xla import register_stateful_model  # noqa: F401
    import jax.numpy as jnp

    from nnstreamer_tpu.models import longcat_flash, moe
    from nnstreamer_tpu.ops import kernels

    if not kernels.on_tpu():
        return
    model = longcat_flash.LongCatFlashConfig.from_dict(cfg)
    sizes = _sizes(cfg)
    held, h, f = model.experts, model.hidden_size, \
        model.expert_ffn_hidden_size
    streams, width = sizes["streams"], model.ffn_hidden_size
    dtypes = {jnp.dtype(cfg["precision"])}
    calls = [((tokens, h), (held, h, f), (held, f, h),
              moe.block_rows(tokens))
             for tokens in (streams, sizes["chunk"])]
    if longcat_flash.dense_mlp_grouped(streams):
        calls.append(((streams, h), (1, h, width), (1, width, h), streams))
    refused = [why for why in (
        kernels.grouped_gated_product_refusal(x, up, down, dtypes, blk)
        for x, up, down, blk in calls) if why]
    if refused:
        raise BenchmarkError(
            f"{cfg['name']}: grouped_gated_product refuses the cell's "
            "shapes: " + "; ".join(refused))


def register(cfg: dict, params, batch: int, name: str) -> None:
    from nnstreamer_tpu.models import longcat_flash

    sizes = _sizes(cfg)
    if int(batch) != sizes["streams"]:
        raise BenchmarkError(
            f"the mix's batch is {batch}, the configuration's caches hold "
            f"{sizes['streams']} streams")
    longcat_flash.register(
        name, longcat_flash.LongCatFlashConfig.from_dict(cfg), params,
        **sizes)


def outputs(buf) -> dict:
    return {"logits": buf.tensors[0].jax(), "greedy": buf.tensors[1].jax()}
