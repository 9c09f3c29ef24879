"""Operations and compulsory bytes of one decode step of the
Falcon-H1-34B stage, counted from its shapes at the PUBLISHED sizes (5
query heads a group, not a tile of 16; the recurrent state read once and
written once): a floor no correct program can beat, whatever implements
the step.

``frame_cost(cfg)`` gives what depends on the configuration alone; what
depends on the traffic (the K/V rows in use) the readers take from the
window's counters (``readers/dense_decode_step_roofline.py``, whose keys
these are, ``readers/gqa_decode_attention_roofline.py`` and
``readers/stage_roofline.py``).  Every layer counts in both kinds of
state.

``weight_bytes``        every weight a step must read whatever it is
                        fed: each layer's Mamba-2 projections and
                        vectors, attention's four matrices, the MLP's
                        three, the head, the norms (bf16 matrices, f32
                        vectors).  The embedding is gathered, a row a
                        token, and is under ``in_bytes_per_frame``.
``mamba_weight_bytes``  the Mamba-2 mixers' part of it.
``dense_mlp_bytes``     the MLPs' part of it: three matrices a layer.
``cache_row_bytes``     a token's K and V of one layer, what the
                        counters count a row in use by.
``ssm_row_bytes``       a stream's recurrent state and convolution
                        inputs of one layer, read and written.
``in_bytes_per_frame``  a token's id and position, its embedding row,
                        the K and V row it writes in every layer, and
                        its stream's recurrent state of every layer read
                        and written: a step's cost a frame whatever the
                        stream's length.
``out_bytes_per_frame`` a row of float32 logits and the greedy id.
``flops_per_frame``     2 per multiply-add of every product a token's
                        step needs apart from its cache length, and the
                        recurrence's own (``S = a S + dx (x) B`` and ``y
                        = S C``: 5 a state value);
                        ``flops_per_cache_row`` gives the rest by the
                        row in use of one layer.
``attn_io_bytes_per_frame``  what the decode attention kernel reads and
                        writes for a token beside the caches: every
                        head's query (bf16) and output (float32), every
                        layer.
``layers``              the held layers: each keeps a recurrent state
                        AND a cache.
"""

from __future__ import annotations


def frame_cost(cfg: dict) -> dict:
    h, f, d = (int(cfg[k]) for k in ("hidden_size", "intermediate_size",
                                     "head_dim"))
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    mh, mp = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    groups, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    kernel, vocab = int(cfg["mamba_d_conv"]), int(cfg["vocab_size"])
    depth = int(cfg["num_hidden_layers"])
    d_ssm = mh * mp
    conv = d_ssm + 2 * groups * n
    mamba = h * (d_ssm + conv + mh) + d_ssm * h
    mamba_vectors = kernel * conv + conv + 3 * mh + d_ssm
    attn = h * nh * d + 2 * h * nkv * d + nh * d * h
    mlp = 3 * h * f
    matrices = depth * (mamba + attn + mlp) + h * vocab
    # two norms a layer beside the mixer's own vectors; the final norm
    vectors = depth * (mamba_vectors + 2 * h) + h
    row = 2 * nkv * d
    state = mh * mp * n
    ssm_row = 2 * (state * 4 + (kernel - 1) * conv * 2)
    return {"flops_per_frame": float(2 * matrices + depth * 5 * state),
            "flops_per_cache_row": float(2 * nh * 2 * d),
            "weight_bytes": float(matrices * 2 + vectors * 4),
            "mamba_weight_bytes": float(depth * (mamba * 2
                                                 + mamba_vectors * 4)),
            "dense_mlp_bytes": float(depth * mlp * 2),
            "cache_row_bytes": float(row * 2),
            "ssm_row_bytes": float(ssm_row),
            "in_bytes_per_frame": float(8 + h * 2 + depth * row * 2
                                        + depth * ssm_row),
            "out_bytes_per_frame": float(vocab * 4 + 4),
            "attn_io_bytes_per_frame": float(depth * nh * (d * 2 + d * 4)),
            "layers": float(depth)}
