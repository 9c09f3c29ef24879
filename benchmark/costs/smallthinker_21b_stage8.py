"""Operations and compulsory bytes of one decode step of the
SmallThinker stage, counted from its shapes: a floor no correct program
can beat.

``frame_cost(cfg)`` gives what depends on the configuration alone; what
depends on the traffic (cache rows in use, experts hit) the readers take
from the window's counters (``readers/decode_step_roofline.py``, whose
keys these are, and ``readers/gqa_decode_attention_roofline.py``):

``weight_bytes``        every weight a step must read whatever it is
                        fed: attention's four matrices and the router of
                        every layer, the head, the norms (bf16 matrices,
                        f32 vectors).  The embedding is gathered, a row
                        a token, and is under ``in_bytes_per_frame``.
``expert_bytes``        one expert's three matrices: read once a step by
                        every expert slot a token of that step is routed
                        to, and not otherwise.
``cache_row_bytes``     a token's K and V of one layer, what the
                        counters count a row in use by: a ring's rows in
                        use are at most the window, whatever it holds.
``in_bytes_per_frame``  a token's id and position, its embedding row,
                        and the K and V row it writes in every layer.
``out_bytes_per_frame`` a row of float32 logits and the greedy id.
``flops_per_frame``     2 per multiply-add of every product a token's
                        step needs apart from its experts and its cache
                        length: ``flops_per_expert_hit`` and
                        ``flops_per_cache_row`` give those by the unit
                        (every query head's score and value products on
                        one cached row of one layer).
``expert_slots``        layers x experts: what ``experts_touched`` is a
                        share of.
``expert_layers``       the layers that route (all of them).
``attn_io_bytes_per_frame``  what the decode attention kernel reads and
                        writes for a token beside the caches: every
                        head's query (bf16) and output (float32), every
                        layer.
``window_layers``, ``full_layers``  how many layers keep a ring, and
                        how many every position.
"""

from __future__ import annotations


def frame_cost(cfg: dict) -> dict:
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    e, f = int(cfg["moe_num_primary_experts"]), int(cfg["moe_ffn_hidden_size"])
    vocab, depth = int(cfg["vocab_size"]), int(cfg["num_hidden_layers"])
    rings = sum(1 for v in cfg["sliding_window_layout"][:depth] if v)
    attn = h * nh * d + 2 * h * nkv * d + nh * d * h
    matrices = depth * (attn + h * e) + h * vocab
    vectors = depth * 2 * h + h
    expert = 3 * h * f
    row = 2 * nkv * d
    return {"flops_per_frame": float(2 * matrices),
            "flops_per_expert_hit": float(2 * expert),
            "flops_per_cache_row": float(2 * nh * 2 * d),
            "weight_bytes": float(matrices * 2 + vectors * 4),
            "expert_bytes": float(expert * 2),
            "cache_row_bytes": float(row * 2),
            "in_bytes_per_frame": float(8 + h * 2 + depth * row * 2),
            "out_bytes_per_frame": float(vocab * 4 + 4),
            "expert_slots": float(depth * e),
            "expert_layers": float(depth),
            "attn_io_bytes_per_frame": float(depth * nh * (d * 2 + d * 4)),
            "window_layers": float(rings),
            "full_layers": float(depth - rings),
            "layers": float(depth)}
