"""Operations and compulsory bytes of one decode step of the DeepSeek-V2
share, counted from its shapes: a floor no correct program can beat.

``frame_cost(cfg)`` gives what depends on the configuration alone; what
depends on the traffic (latent rows read, experts hit) the readers take
from the window's counters (``readers/decode_step_roofline.py``):

``weight_bytes``        every weight a step must read whatever it is
                        fed: attention's five matrices of every layer,
                        the dense MLP, each expert layer's router and
                        shared experts, the head, the norms (bf16
                        matrices, f32 vectors).  The embedding is
                        gathered, a row a token, and is under
                        ``in_bytes_per_frame``.
``expert_bytes``        one routed expert's three matrices: read once a
                        step by every expert slot a token of that step
                        is routed to, and not otherwise.
``cache_row_bytes``     one latent row of one layer (what
                        ``cache_bytes_read`` counts a position by).
``in_bytes_per_frame``  a token's id and position, its embedding row,
                        and the latent row it writes in every layer.
``out_bytes_per_frame`` a row of float32 logits and the greedy id.
``flops_per_frame``     2 per multiply-add of every product a token's
                        step needs apart from its routed experts and its
                        cache length: ``flops_per_expert_hit`` and
                        ``flops_per_cache_row`` give those by the unit.
``expert_slots``        expert layers x experts held: what
                        ``experts_touched`` is a share of.
``attn_io_bytes_per_frame``  what the decode attention kernel reads
                        and writes for a token beside the cache: every
                        head's query over the latent row (bf16) and its
                        output over ``c_kv`` (float32), every layer.
``expert_layers``       the layers that route: ``expert_hits_per_frame``
                        is a token's hits in ONE of them.
"""

from __future__ import annotations


def frame_cost(cfg: dict) -> dict:
    h, qr, kr = (int(cfg[k]) for k in ("hidden_size", "q_lora_rank",
                                       "kv_lora_rank"))
    nope, rope, vd = (int(cfg[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    nh, vocab = int(cfg["num_attention_heads"]), int(cfg["vocab_size"])
    depth = int(cfg["num_hidden_layers"])
    dense = min(int(cfg["first_k_dense_replace"]), depth)
    moe = depth - dense
    held, f = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
    router = int(cfg.get("published", {}).get("n_routed_experts", held))
    shared = f * int(cfg["n_shared_experts"])
    attn = (h * qr + qr * nh * (nope + rope) + h * (kr + rope)
            + kr * nh * (nope + vd) + nh * vd * h)
    matrices = (depth * attn + dense * 3 * h * int(cfg["intermediate_size"])
                + moe * (h * router + 3 * h * shared) + h * vocab)
    vectors = depth * (2 * h + qr + kr) + h
    expert = 3 * h * f
    latent = kr + rope
    # absorbed attention by the cached row: scores over the latent row,
    # values over c_kv
    per_row = 2 * nh * (latent + kr)
    per_token = 2 * (matrices - moe * h * router) \
        + 2 * moe * h * router            # the router once more: float32
    return {"flops_per_frame": float(per_token),
            "flops_per_expert_hit": float(2 * expert),
            "flops_per_cache_row": float(per_row),
            "weight_bytes": float(matrices * 2 + vectors * 4),
            "expert_bytes": float(expert * 2),
            "cache_row_bytes": float(latent * 2),
            "in_bytes_per_frame": float(8 + h * 2 + depth * latent * 2),
            "out_bytes_per_frame": float(vocab * 4 + 4),
            "expert_slots": float(moe * held),
            "expert_layers": float(moe),
            "attn_io_bytes_per_frame": float(
                depth * nh * (latent * 2 + kr * 4)),
            "layers": float(depth)}
