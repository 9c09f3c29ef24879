"""Operations and compulsory bytes of one decode step of the
Nemotron-3-Nano share, counted from its shapes: a floor no correct
program can beat.

``frame_cost(cfg)`` gives what depends on the configuration alone; what
depends on the traffic (cache rows in use, experts hit) the readers take
from the window's counters (``readers/decode_step_roofline.py``, whose
keys these are, ``readers/gqa_decode_attention_roofline.py`` and
``readers/stage_roofline.py``):

``weight_bytes``        every weight a step must read whatever it is
                        fed: the Mamba-2 layers' projections and
                        vectors, attention's four matrices, the router
                        and the shared expert of every ``E`` layer, the
                        head, the norms (bf16 matrices, f32 vectors).
                        The embedding is gathered, a row a token, and is
                        under ``in_bytes_per_frame``.
``mamba_weight_bytes``  the Mamba-2 layers' part of it.
``expert_bytes``        one routed expert's two matrices at their
                        PUBLISHED width (the stored ones are wider by
                        zero columns, which a step has no need to read):
                        read once a step by every expert slot a token of
                        that step is routed to, and not otherwise.
``cache_row_bytes``     a token's K and V of one ``*`` layer, what the
                        counters count a row in use by.
``ssm_row_bytes``       a stream's recurrent state and convolution
                        inputs of one ``M`` layer, read and written.
``in_bytes_per_frame``  a token's id and position, its embedding row,
                        the K and V row it writes in every ``*`` layer,
                        and its stream's state of every ``M`` layer read
                        and written: a step's cost a frame whatever the
                        stream's length.
``out_bytes_per_frame`` a row of float32 logits and the greedy id.
``flops_per_frame``     2 per multiply-add of every product a token's
                        step needs apart from its routed experts and its
                        cache length, and the recurrence's own
                        (``S = a S + dx (x) B`` and ``y = S C``: 5 a
                        state value); ``flops_per_expert_hit`` and
                        ``flops_per_cache_row`` give the rest by the
                        unit.
``expert_slots``        ``E`` layers x experts held: what
                        ``experts_touched`` is a share of.
``expert_layers``       the layers that route.
``attn_io_bytes_per_frame``  what the decode attention kernel reads and
                        writes for a token beside the caches: every
                        head's query (bf16) and output (float32), every
                        ``*`` layer.
``mamba_layers``, ``attn_layers``  how many layers keep a recurrent
                        state, and how many a cache.
"""

from __future__ import annotations


def frame_cost(cfg: dict) -> dict:
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    mh, mp = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    groups, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    kernel = int(cfg["conv_kernel"])
    held, f = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
    router = int(cfg.get("published", {}).get("n_routed_experts", held))
    shared = int(cfg["moe_shared_expert_intermediate_size"]) \
        * int(cfg["n_shared_experts"])
    vocab = int(cfg["vocab_size"])
    pattern = cfg["hybrid_override_pattern"][:int(cfg["num_hidden_layers"])]
    m, e, a = (pattern.count(kind) for kind in "ME*")
    d_inner = mh * mp
    conv = d_inner + 2 * groups * n
    mamba = h * (d_inner + conv + mh) + d_inner * h
    mamba_vectors = h + kernel * conv + conv + 3 * mh + d_inner
    attn = h * nh * d + 2 * h * nkv * d + nh * d * h
    moe = h * router + 2 * h * shared
    matrices = m * mamba + a * attn + e * moe + h * vocab
    vectors = m * mamba_vectors + a * h + e * (h + router) + h
    expert = 2 * h * f
    row = 2 * nkv * d
    state = mh * mp * n
    ssm_row = 2 * (state * 4 + (kernel - 1) * conv * 2)
    return {"flops_per_frame": float(2 * matrices + m * 5 * state),
            "flops_per_expert_hit": float(2 * expert),
            "flops_per_cache_row": float(2 * nh * 2 * d),
            "weight_bytes": float(matrices * 2 + vectors * 4),
            "mamba_weight_bytes": float(m * (mamba * 2 + mamba_vectors * 4)),
            "expert_bytes": float(expert * 2),
            "cache_row_bytes": float(row * 2),
            "ssm_row_bytes": float(ssm_row),
            "in_bytes_per_frame": float(8 + h * 2 + a * row * 2
                                        + m * ssm_row),
            "out_bytes_per_frame": float(vocab * 4 + 4),
            "expert_slots": float(e * held),
            "expert_layers": float(e),
            "attn_io_bytes_per_frame": float(a * nh * (d * 2 + d * 4)),
            "mamba_layers": float(m),
            "attn_layers": float(a),
            "layers": float(len(pattern))}
