"""Operations and compulsory bytes of one decode step of the K-EXAONE
share, counted from its shapes at the published widths: a floor no
correct program can beat.

``frame_cost(cfg)`` gives what depends on the configuration alone; what
depends on the traffic (cache rows in use, experts hit) the readers take
from the window's counters (``readers/decode_step_roofline.py``, whose
keys these are, ``readers/gqa_decode_attention_roofline.py`` and
``readers/stage_roofline.py``).  The multi-token-prediction module is
one more sparse full-attention layer: its matrices are in the step's
fixed part, its routed experts go by the slot touched like the layers'
(the program's ``experts_touched`` counts both), and the head is read
twice, once for each logits tensor.

``weight_bytes``        every weight a step must read whatever it is
                        fed: attention's four matrices and two per-head
                        gains in every layer and in the module, the
                        dense layer's MLP, the router and the shared
                        expert of every sparse layer and of the module,
                        the module's merge matrix, the head TWICE, the
                        norms (bf16 matrices, f32 vectors).  The
                        embedding is gathered, two rows a token, and is
                        under ``in_bytes_per_frame``.
``mtp_weight_bytes``    the module's part of it, with the head's second
                        reading.
``expert_bytes``        one routed expert's three matrices: read once a
                        step by every expert slot a token of that step
                        is routed to, and not otherwise.
``cache_row_bytes``     a token's K and V of one cache, what the
                        counters count a row in use by: a ring's rows in
                        use are at most the window, whatever it holds.
``in_bytes_per_frame``  a token's two ids and position, their two
                        embedding rows, and the K and V row it writes in
                        every layer's cache and the module's.
``out_bytes_per_frame`` two rows of float32 logits and two greedy ids.
``flops_per_frame``     2 per multiply-add of every product a token's
                        step needs apart from its routed experts and its
                        cache length: ``flops_per_expert_hit`` and
                        ``flops_per_cache_row`` give those by the unit
                        (every query head's score and value products on
                        one cached row of one cache).
``expert_slots``        (sparse layers + the module) x experts held:
                        what ``experts_touched`` is a share of.
``expert_layers``       the layers that route, the module among them.
``attn_io_bytes_per_frame``  what the decode attention kernel reads and
                        writes for a token beside the caches: every
                        head's query (bf16) and output (float32), every
                        cache.
``window_layers``, ``full_layers``, ``mtp_layers``  how many caches are
                        rings, how many hold every position, and
                        whether the module keeps one.
"""

from __future__ import annotations


def frame_cost(cfg: dict) -> dict:
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    held, f = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
    router = int(cfg.get("published", {}).get("num_experts", held))
    shared = f * int(cfg["num_shared_experts"])
    vocab, depth = int(cfg["vocab_size"]), int(cfg["num_hidden_layers"])
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    rings = sum(1 for kind in cfg["layer_types"][:depth]
                if kind == "sliding_attention")
    dense = sum(1 for kind in cfg["mlp_layer_types"][:depth]
                if kind == "dense")
    sparse = depth - dense
    attn = h * nh * d + 2 * h * nkv * d + nh * d * h
    moe = h * router + 3 * h * shared
    module = attn + moe + 2 * h * h + h * vocab      # and the head again
    matrices = depth * attn + dense * 3 * h * int(cfg["intermediate_size"]) \
        + sparse * moe + h * vocab + mtp * module
    # two norms and two per-head gains a layer, a sparse layer's bias
    layer_vectors = 2 * h + 2 * d
    module_vectors = layer_vectors + router + 3 * h
    vectors = depth * layer_vectors + sparse * router + h \
        + mtp * module_vectors
    expert = 3 * h * f
    row = 2 * nkv * d
    caches = depth + mtp
    return {"flops_per_frame": float(2 * matrices),
            "flops_per_expert_hit": float(2 * expert),
            "flops_per_cache_row": float(2 * nh * 2 * d),
            "weight_bytes": float(matrices * 2 + vectors * 4),
            "mtp_weight_bytes": float(mtp * (module * 2
                                             + module_vectors * 4)),
            "expert_bytes": float(expert * 2),
            "cache_row_bytes": float(row * 2),
            "in_bytes_per_frame": float(12 + (1 + mtp) * h * 2
                                        + caches * row * 2),
            "out_bytes_per_frame": float((1 + mtp) * (vocab * 4 + 4)),
            "expert_slots": float((sparse + mtp) * held),
            "expert_layers": float(sparse + mtp),
            "attn_io_bytes_per_frame": float(caches * nh * (d * 2 + d * 4)),
            "window_layers": float(rings),
            "full_layers": float(depth - rings),
            "mtp_layers": float(mtp),
            "layers": float(depth)}
