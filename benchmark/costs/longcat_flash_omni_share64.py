"""Operations and compulsory bytes of one decode step of the
LongCat-Flash share, counted from its shapes at the PUBLISHED sizes (a
latent row is its 576 values, not the 640 it is stored in): a floor no
correct program can beat, whatever implements the step.

``frame_cost(cfg)`` gives what depends on the configuration alone; what
depends on the traffic (latent rows in use, experts hit) the readers
take from the window's counters (``readers/decode_step_roofline.py``,
whose keys these are, ``readers/latent_decode_attention_roofline.py`` and
``readers/stage_roofline.py``).

``weight_bytes``        every weight a step must read whatever it is
                        fed: the five matrices of BOTH latent-attention
                        sub-blocks of every layer, both dense MLPs, the
                        router (768 wide) and its correction bias, the
                        head, the norms (bf16 matrices, f32 vectors).
                        The embedding is gathered, a row a token, and is
                        under ``in_bytes_per_frame``.
``dense_mlp_bytes``     the dense MLPs' part of it: two a layer, three
                        matrices each.
``expert_bytes``        one routed expert's three matrices: read once a
                        step by every expert slot a token of that step
                        is routed to, and not otherwise.  A pick on a
                        zero-compute expert reads nothing.
``cache_row_bytes``     one latent row of one cache (what
                        ``cache_bytes_read`` counts a position by).
``in_bytes_per_frame``  a token's id and position, its embedding row,
                        and the latent row it writes in both caches of
                        every layer.
``out_bytes_per_frame`` a row of float32 logits and the greedy id.
``flops_per_frame``     2 per multiply-add of every product a token's
                        step needs apart from its routed experts and its
                        cache length, the zero-compute picks' ``weight *
                        u`` among them (``hidden`` multiply-adds a
                        layer): ``flops_per_expert_hit`` and
                        ``flops_per_cache_row`` give the rest by the
                        unit (absorbed attention on one cached row of
                        one cache: every head's scores over the latent
                        row, its values over ``c_kv``).
``expert_slots``        layers x experts held: what ``experts_touched``
                        is a share of.
``expert_layers``       the layers that route: every one.
``picks_per_frame``     the router's picks a token makes in a step,
                        ``moe_topk`` a layer, whatever they fall on:
                        what ``zero_picks`` and ``expert_hits`` a frame
                        are shares of.
``attn_io_bytes_per_frame``  what the decode attention kernel reads and
                        writes for a token beside the caches: every
                        head's query over the latent row (bf16) and its
                        output over ``c_kv`` (float32), every cache.
``caches``              latent caches a stream keeps: two a layer.
"""

from __future__ import annotations


def frame_cost(cfg: dict) -> dict:
    h, qr, kr = (int(cfg[k]) for k in ("hidden_size", "q_lora_rank",
                                       "kv_lora_rank"))
    nope, rope, vd = (int(cfg[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    nh, vocab = int(cfg["num_attention_heads"]), int(cfg["vocab_size"])
    depth, width = int(cfg["num_layers"]), int(cfg["ffn_hidden_size"])
    held, f = int(cfg["n_routed_experts"]), int(cfg["expert_ffn_hidden_size"])
    router = int(cfg.get("published", {}).get("n_routed_experts", held)) \
        + int(cfg["zero_expert_num"])
    subs = 2
    attn = (h * qr + qr * nh * (nope + rope) + h * (kr + rope)
            + kr * nh * (nope + vd) + nh * vd * h)
    mlp = 3 * h * width
    matrices = depth * (subs * (attn + mlp) + h * router) + h * vocab
    # four norms a layer, the low-rank streams' two a sub-block, the
    # correction bias; the final norm
    vectors = depth * (2 * subs * h + subs * (qr + kr) + router) + h
    expert = 3 * h * f
    latent, caches = kr + rope, depth * subs
    return {"flops_per_frame": float(2 * matrices + 2 * depth * h),
            "flops_per_expert_hit": float(2 * expert),
            "flops_per_cache_row": float(2 * nh * (latent + kr)),
            "weight_bytes": float(matrices * 2 + vectors * 4),
            "dense_mlp_bytes": float(depth * subs * mlp * 2),
            "expert_bytes": float(expert * 2),
            "cache_row_bytes": float(latent * 2),
            "in_bytes_per_frame": float(8 + h * 2 + caches * latent * 2),
            "out_bytes_per_frame": float(vocab * 4 + 4),
            "expert_slots": float(depth * held),
            "expert_layers": float(depth),
            "picks_per_frame": float(depth * int(cfg["moe_topk"])),
            "attn_io_bytes_per_frame": float(
                caches * nh * (latent * 2 + kr * 4)),
            "caches": float(caches),
            "layers": float(depth)}
