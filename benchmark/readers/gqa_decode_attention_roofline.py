"""Kernels: the least time the chip could take for the grouped-query
decode attention kernel's calls in the trace (its compulsory bytes /
peak B/s; the kernel is bound by the caches it reads) as a share of the
device time measured for them.

The kernel (``nnstreamer_tpu/ops/kernels.py`` ``gqa_decode_attention``)
is one instruction a layer, under the stage
``nns.model/layerNN/attn_window/gqa_decode_attention`` or
``.../attn_full/gqa_decode_attention``, so its device seconds are
``trace["stage_s"]`` summed over those stages.  Compulsory bytes of a
step: the K and V rows IN USE in every layer (the program's
``cache_bytes_read`` counter: a ring's rows within the window, a full
cache's rows up to the stream's position; the block a window starts or
ends inside is the kernel's own cost), each head's query (bf16) and its
output (float32).  ``None`` without a trace, the program's text, peaks,
counters or such a stage (a program that has no such kernel)."""

STAGE = "/gqa_decode_attention"


def read(obs: dict):
    trace = obs.get("trace") or {}
    state = (obs.get("window") or {}).get("state") or {}
    stages, steps = trace.get("stage_s"), state.get("steps", 0)
    if not stages or not trace.get("windows") or not obs.get("peaks") \
            or steps <= 0 or "cache_bytes_read" not in state:
        return None
    seconds = sum(s for name, s in stages.items() if name.endswith(STAGE))
    if seconds <= 0:
        return None
    cost = obs["cost"]
    nbytes = state["cache_bytes_read"] / steps \
        + obs["batch"] * cost["attn_io_bytes_per_frame"]
    least = nbytes / obs["peaks"]["peak_hbm_bytes_per_s"] * trace["windows"]
    print(f"[bench] gqa decode attention: {nbytes / 1e9:.3f} GB a step, "
          f"least {least:.6f} s of {seconds:.6f} s in the kernel",
          flush=True)
    return 100.0 * least / seconds
