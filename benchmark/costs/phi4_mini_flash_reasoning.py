"""Operations and compulsory bytes of one decode step of
Phi-4-mini-flash-reasoning, counted from its shapes at the PUBLISHED
sizes (heads of 64, not the 128-wide rows with zero lanes the kernels
are handed; 4 query rows a K/V pair, not a tile of 16): a floor no
correct program can beat, whatever implements the step.

``frame_cost(cfg)`` gives what depends on the configuration alone; what
depends on the traffic (the cache rows in use, BY READER: a row of the
one shared cache is read by layer 17 and by each of the seven cross
layers) the readers take from the window's counters
(``readers/dense_decode_step_roofline.py``, whose keys these are,
``readers/gqa_decode_attention_roofline.py`` and
``readers/stage_roofline.py``).

``weight_bytes``        every weight a step must read whatever it is
                        fed, ONCE: the 32 MLPs, the 9 Mamba-1 mixers,
                        the 9 attentions with K/V of their own, the 7
                        cross attentions (``W_q``, ``W_o``), the 7 gated
                        memory units, the norms, and the embedding's
                        200,064 rows once as the tied head (bf16
                        matrices, f32 vectors).  The embedding row a
                        token gathers is under ``in_bytes_per_frame``.
``dense_mlp_bytes``     the MLPs' part of it: two matrices a layer.
``head_bytes``          the tied head's part of it.
``mamba_weight_bytes``  the Mamba-1 mixers' part of it.
``cache_row_bytes``     a token's K and V of one layer, what the
                        counters count one READ of a row by.
``ssm_row_bytes``       a stream's recurrent state and convolution
                        inputs of one layer, read and written.
``in_bytes_per_frame``  a token's id and position, its embedding row,
                        the K and V row it writes in each of the nine
                        layers that own a cache, and its stream's nine
                        recurrent states read and written.
``out_bytes_per_frame`` a row of float32 logits over the whole
                        vocabulary and the greedy id.
``flops_per_frame``     2 per multiply-add of every product a token's
                        step needs apart from its cache length, and the
                        recurrence's own (``h = exp(delta A) h + dx (x)
                        B`` and ``y = h C``: 6 a state value);
                        ``flops_per_cache_row`` gives the rest by the
                        row READ in one layer: 40 heads' scores at 64
                        and their products with a 128-wide value row.
``attn_io_bytes_per_frame``  what the decode attention reads and writes
                        for a token beside the caches: each of the 40
                        heads' query (bf16, 64) and output (float32,
                        128), in each of the 16 attention layers.
"""

from __future__ import annotations


def frame_cost(cfg: dict) -> dict:
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    heads, kv = (int(cfg[k]) for k in ("num_attention_heads",
                                       "num_key_value_heads"))
    depth, vocab = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    hd = h // heads
    d = int(cfg.get("mamba_expand", 2)) * h
    n = int(cfg.get("mamba_d_state", 16))
    kernel = int(cfg.get("mamba_d_conv", 4))
    rank = cfg.get("mamba_dt_rank", "auto")
    rank = -(-h // 16) if rank == "auto" else int(rank)
    full = depth // 2 + 1
    mambas = (full + 1) // 2                 # the even layers below F
    owners = full - mambas + 1               # rings, and the one cache
    gmus = (depth - full) // 2
    crosses = depth - full - 1 - gmus
    mamba = h * 2 * d + d * (rank + 2 * n) + rank * d + d * h
    mamba_vectors = kernel * d + 3 * d + n * d
    q_o, kv_w = 2 * h * h, h * kv * hd * 2
    # q, o and K/V biases, four lambda vectors, the sub-norm's gain
    attn_vectors = 2 * h + 4 * hd + 2 * hd
    mlp = 3 * h * f
    matrices = depth * mlp + mambas * mamba + owners * (q_o + kv_w) \
        + crosses * q_o + gmus * 2 * h * d + vocab * h
    vectors = mambas * mamba_vectors \
        + (owners + crosses) * attn_vectors + owners * 2 * kv * hd \
        + depth * 4 * h + 2 * h
    row = 2 * kv * hd                        # a token's K and V, values
    ssm_row = 2 * (n * d * 4 + (kernel - 1) * d * 2)
    return {
        "flops_per_frame": float(2 * matrices + mambas * 6 * n * d),
        "flops_per_cache_row": float(2 * heads * (hd + 2 * hd)),
        "weight_bytes": float(matrices * 2 + vectors * 4),
        "dense_mlp_bytes": float(depth * mlp * 2),
        "head_bytes": float(vocab * h * 2),
        "mamba_weight_bytes": float(mambas * (mamba * 2 + mamba_vectors * 4)),
        "cache_row_bytes": float(row * 2),
        "ssm_row_bytes": float(ssm_row),
        "in_bytes_per_frame": float(8 + h * 2 + owners * row * 2
                                    + mambas * ssm_row),
        "out_bytes_per_frame": float(vocab * 4 + 4),
        "attn_io_bytes_per_frame": float((owners + crosses) * heads
                                         * (hd * 2 + 2 * hd * 4)),
        "layers": float(depth)}
