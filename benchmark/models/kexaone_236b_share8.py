"""Application glue for the K-EXAONE share: registers the program's
stateful model (``nnstreamer_tpu/models/exaone_moe.py``: a prefill and a
decode entry point on one set of weights and one state of rings, full
caches and the multi-token-prediction module's cache) under a model name
and says what a pulled decode buffer serves: two logits tensors and two
greedy ids.

A ring of window + rewind positions cannot serve a position that is
neither the one after the stream's last nor its prompt's end; the
program counts such a step (``position_faults``) and :func:`fence`
fails the run on the first it sees in the program's published counters,
rather than count a wrong token as served."""

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


def _sizes(cfg: dict) -> dict:
    serving = cfg["serving"]
    return {"streams": int(serving["streams"]),
            "positions": _inputs(cfg).cache_positions(cfg),
            "chunk": int(serving["prefill_chunk"]),
            "rewind": int(serving["answer_tokens"])}


def preflight(cfg: dict) -> None:
    """Raise ``ImportError`` at once where the program has no such model
    or no stateful filter, before gigabytes of weights are made; and
    on the chip fail the run if a kernel refuses the cell's shapes,
    rather than time the ``jnp`` path the model would fall back to (a
    rehearsal on the CPU times nothing and runs toy shapes)."""
    from nnstreamer_tpu.filters.jax_xla import register_stateful_model  # noqa: F401
    import jax.numpy as jnp

    from nnstreamer_tpu.models import exaone_moe, moe
    from nnstreamer_tpu.ops import kernels

    if not kernels.on_tpu():
        return
    model = exaone_moe.ExaoneMoeConfig.from_dict(cfg)
    sizes = _sizes(cfg)
    b, dtype = sizes["streams"], jnp.dtype(cfg["precision"])
    q = (b, model.kv_heads, model.per_group, model.head_dim)
    refused = []
    for total, window in ((model.ring(sizes["rewind"]), model.window),
                          (sizes["positions"], sizes["positions"])):
        kv = (b, model.kv_heads, total, model.head_dim)
        why = kernels.gqa_decode_attention_refusal(q, kv, kv, window)
        if why:
            refused.append(f"gqa_decode_attention: {why}")
    held, h, f = model.experts, model.hidden_size, model.expert_width
    for tokens in (b, sizes["chunk"]):
        why = kernels.grouped_gated_product_refusal(
            (tokens, h), (held, h, f), (held, f, h), {dtype},
            moe.block_rows(tokens))
        if why:
            refused.append(f"grouped_gated_product: {why}")
    if refused:
        raise BenchmarkError(
            f"{cfg['name']}: a kernel refuses the cell's shapes: "
            + "; ".join(refused))


def register(cfg: dict, params, batch: int, name: str) -> None:
    from nnstreamer_tpu.models import exaone_moe

    sizes = _sizes(cfg)
    if int(batch) != sizes["streams"]:
        raise BenchmarkError(
            f"the mix's batch is {batch}, the configuration's caches hold "
            f"{sizes['streams']} streams")
    exaone_moe.register(name, exaone_moe.ExaoneMoeConfig.from_dict(cfg),
                        params, **sizes)


def _state_counters() -> dict:
    from nnstreamer_tpu.utils.stats import STATE_STATS

    return STATE_STATS.snapshot()


def fence(buf) -> None:
    """Wait until everything the buffer carries is computed, and fail
    on a position the rings could not serve (the filter publishes its
    counters at its stats-sample cadence, so within a sample of it)."""
    appglue.fence(buf)
    faults = _state_counters().get("position_faults", 0)
    if faults:
        raise BenchmarkError(
            f"{faults} decode position(s) were neither the one after the "
            "stream's last nor a prompt's end its rings could rewind to")


def unregister(name: str) -> None:
    print("[bench] state counters at the end: position_faults "
          f"{_state_counters().get('position_faults', 0)}", flush=True)
    appglue.unregister(name)


def outputs(buf) -> dict:
    logits, logits_mtp, greedy, greedy_mtp = (t.jax() for t in buf.tensors)
    return {"logits": logits, "logits_mtp": logits_mtp, "greedy": greedy,
            "greedy_mtp": greedy_mtp}
