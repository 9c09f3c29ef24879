"""DeepSeek-V2 as one chip's share of a layer divided over several chips.

Written from the model's public ``config.json`` (``model_type``
``deepseek_v2``): pre-norm residual layers, RMSNorm, no biases,
multi-head latent attention (MLA) with a YaRN-scaled rotary part, a
dense MLP in the leading layer(s) and, after them, ``n_routed_experts``
routed experts chosen by ``group_limited_greedy`` beside shared experts.

**The share.**  The model is told what it holds
(:class:`DeepSeekV2Config`): heads ``[head0, head0 + heads)``, routed
experts ``[expert0, expert0 + experts)`` (whole groups of the router, so
that a chip is a device of the paper's device-limited routing) and
vocabulary rows ``[vocab0, vocab0 + vocab)``.  It routes over ALL the
published experts, computes the held experts' part for the tokens routed to them (no token is
dropped and there is no capacity factor: tokens are laid out by expert and
the product runs over blocks of one expert's rows each; ``models/moe.py``,
which ``smallthinker.py`` runs too), adds what every
chip computes alike (the shared experts, the dense MLP) and passes that
partial sum on; attention's output is the held heads' partial sum through
their rows of ``W_o``; logits are over the slice.  Nothing stands in for
the absent chips or their exchange.

**Two paths through one set of weights.**  The state is the latent cache
alone: per layer a token's ``(c_kv, k_r)`` after norm and rotation, 576
values at the published sizes, two positions a row: ``[streams,
positions / 2, 1152]`` (``models/mla.py``, which ``longcat_flash.py``
runs too, has the attention and the row's form, which follows from
``kv_lora_rank`` and ``qk_rope_head_dim`` alone: a rank that is not
whole lanes keeps a row a position, padded to whole lanes; what this
model gives it is the YaRN rotation and the scores' scale).  :func:`prefill` runs a chunk of ONE stream through the
expanded form of MLA and writes the chunk's rows; :func:`decode` runs
one token of EVERY stream through the absorbed form (``ops/kernels.py``
``latent_decode_attention``).  Positions come with the frame; rows
beyond a stream's position are masked, so a stale or padded row is
never read.

Stage scopes (``Documentation/observability.md``): ``embed``,
``layerNN/attn`` (``.../attn/cache_write`` inside it), ``layerNN/mlp``
(dense layers), ``layerNN/moe/router|dispatch|experts|combine|shared``,
``head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import numpy as np

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover
    jax = jnp = lax = None

from ..ops import kernels
from . import mla, moe
from . import streams as stream

Params = dict


@dataclasses.dataclass(frozen=True)
class DeepSeekV2Config:
    """The published sizes, and beside them what is HELD here.  Every
    width is the source's; ``layers``, ``heads``, ``experts`` and
    ``vocab`` (with their offsets) are the share."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int          # the router's width (published)
    n_shared_experts: int
    n_group: int
    topk_group: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    first_k_dense_replace: int
    rms_norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_original_max: int
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    layers: int                    # held: the leading layers of the model
    heads: int
    head0: int
    experts: int
    expert0: int
    vocab: int
    vocab0: int

    @classmethod
    def from_dict(cls, cfg: dict) -> "DeepSeekV2Config":
        """From a ``config.json`` as published, or from a chip's share
        of one: then ``num_hidden_layers``, ``num_attention_heads``,
        ``n_routed_experts`` and ``vocab_size`` count what is held,
        ``published`` gives the source's values (the router's width is
        ``published.n_routed_experts``) and ``share`` the offsets
        ``head0`` / ``expert0`` / ``vocab0`` (0 where absent)."""
        published = cfg.get("published", {})
        share = cfg.get("share", {})
        rope = cfg["rope_scaling"]
        if cfg.get("topk_method", "group_limited_greedy") \
                != "group_limited_greedy" \
                or cfg.get("scoring_func", "softmax") != "softmax":
            raise ValueError("deepseek_v2: only group_limited_greedy "
                             "routing over softmax scores is written")
        out = cls(
            hidden_size=int(cfg["hidden_size"]),
            intermediate_size=int(cfg["intermediate_size"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            q_lora_rank=int(cfg["q_lora_rank"]),
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]),
            n_routed_experts=int(published.get("n_routed_experts",
                                               cfg["n_routed_experts"])),
            n_shared_experts=int(cfg["n_shared_experts"]),
            n_group=int(cfg["n_group"]),
            topk_group=int(cfg["topk_group"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", False)),
            first_k_dense_replace=int(cfg["first_k_dense_replace"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            rope_factor=float(rope["factor"]),
            rope_original_max=int(rope["original_max_position_embeddings"]),
            rope_beta_fast=float(rope["beta_fast"]),
            rope_beta_slow=float(rope["beta_slow"]),
            rope_mscale=float(rope["mscale"]),
            rope_mscale_all_dim=float(rope["mscale_all_dim"]),
            layers=int(cfg["num_hidden_layers"]),
            heads=int(cfg["num_attention_heads"]),
            head0=int(share.get("head0", 0)),
            experts=int(cfg["n_routed_experts"]),
            expert0=int(share.get("expert0", 0)),
            vocab=int(cfg["vocab_size"]),
            vocab0=int(share.get("vocab0", 0)))
        if out.norm_topk_prob:
            raise ValueError("deepseek_v2: norm_topk_prob is not written")
        per_group = out.n_routed_experts // out.n_group
        if out.n_routed_experts % out.n_group or out.experts % per_group \
                or out.expert0 % per_group:
            raise ValueError(
                "deepseek_v2: the held experts must be whole groups of "
                f"{per_group} (expert0 {out.expert0}, held {out.experts})")
        return out

    @property
    def latent(self) -> int:
        """Values a token keeps in a layer's cache: ``c_kv`` and ``k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row(self) -> int:
        """Values a position takes in a cache as stored: ``latent``
        where rows are packed, else padded to whole lanes."""
        return mla.row_values(self)

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    # what ``models/mla.py`` reads beside the sizes: the YaRN rotation,
    # the scores' scale, and no constant on the low-rank streams
    q_lora_scale = kv_lora_scale = 1.0

    @property
    def score_scale(self) -> float:
        return attn_scale(self)

    def cos_sin(self, positions):
        angle = positions.astype(jnp.float32)[..., None] \
            * jnp.asarray(yarn_inv_freq(self))
        scale = rope_scale(self)
        return jnp.cos(angle) * scale, jnp.sin(angle) * scale


# -- YaRN rotary embedding ----------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(cfg: DeepSeekV2Config) -> Tuple[int, int]:
    """The rope dimensions between which the frequencies are blended."""
    dim = cfg.qk_rope_head_dim

    def correction(rotations: float) -> float:
        return dim * math.log(cfg.rope_original_max
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), dim - 1)
    return low, high


def yarn_inv_freq(cfg: DeepSeekV2Config) -> np.ndarray:
    """``[rope/2]`` inverse frequencies: ``1/theta^(2i/d)`` where the
    wavelength is short, that divided by ``factor`` where it is long,
    blended linearly between the two correction dimensions."""
    dim = cfg.qk_rope_head_dim
    extra = 1.0 / cfg.rope_theta ** (np.arange(0, dim, 2,
                                               dtype=np.float64) / dim)
    inter = extra / cfg.rope_factor
    low, high = yarn_correction_range(cfg)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: not interpolated
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def attn_scale(cfg: DeepSeekV2Config) -> float:
    """``s`` of the scores: ``q_head_dim^-1/2 * mscale(factor,
    mscale_all_dim)^2``."""
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cfg.q_head_dim ** -0.5 * m * m


def rope_scale(cfg: DeepSeekV2Config) -> float:
    """What cos and sin are scaled by: ``mscale / mscale_all_dim`` form."""
    return yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)


# -- small parts --------------------------------------------------------------


_rms, _mm = moe.rms, moe.mm


def _mlp(p, x):
    h = jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"])
    return _mm(h.astype(x.dtype), p["down"]).astype(x.dtype)


# -- routing ------------------------------------------------------------------


def route(cfg: DeepSeekV2Config, x, router):
    """``group_limited_greedy`` over ALL the published experts, in
    float32: ``(idx [N, k] int32, weight [N, k] float32)``."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    n = p.shape[0]
    group = p.reshape(n, cfg.n_group, -1).max(axis=-1)
    _, best = lax.top_k(group, cfg.topk_group)
    kept = jnp.zeros((n, cfg.n_group), bool).at[
        jnp.arange(n)[:, None], best].set(True)
    masked = jnp.where(jnp.repeat(kept, cfg.n_routed_experts // cfg.n_group,
                                  axis=1), p, 0.0)
    weight, idx = lax.top_k(masked, cfg.num_experts_per_tok)
    return idx.astype(jnp.int32), weight * cfg.routed_scaling_factor


def dispatch(cfg: DeepSeekV2Config, idx, n_tokens: int):
    """The plan of the grouped product over the experts held here
    (``models/moe.py`` ``dispatch``)."""
    return moe.dispatch(idx, n_tokens, cfg.expert0, cfg.experts,
                        cfg.n_routed_experts)


grouped_experts = moe.grouped_experts          # gated by SiLU, its default


def moe_parts(cfg: DeepSeekV2Config, p, x):
    """``(routed, shared, counts)``: the held experts' weighted part for
    the tokens routed to them (float32), what every chip computes alike,
    and how many tokens each held expert got."""
    n = x.shape[0]
    with jax.named_scope("router"):
        idx, weight = route(cfg, x, p["router"])
    with jax.named_scope("dispatch"):
        plan = dispatch(cfg, idx, n)
    with jax.named_scope("experts"):
        out = grouped_experts(p["experts"], x, plan)
    with jax.named_scope("combine"):
        routed = moe.combine(out, plan, weight)
    with jax.named_scope("shared"):
        shared = _mlp(p["shared"], x)
    return routed, shared, plan["counts"]


# -- the model ----------------------------------------------------------------


def _layers(cfg: DeepSeekV2Config, params, x, caches, attend):
    """Every held layer on ``x [N, hidden]``; ``attend(layer params,
    normed x, cache) -> (partial output, cache)``.  Returns the stream,
    the caches and the tokens each held expert of each expert layer
    got (``[expert layers, held]``)."""
    caches, counts = list(caches), []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{i:02d}"):
            # a branch's scope holds its norm and its residual add, so
            # that the fusions XLA roots there are booked to the branch
            with jax.named_scope("attn"):
                a, caches[i] = attend(
                    layer["attn"], _rms(x, layer["attn_norm"],
                                        cfg.rms_norm_eps), caches[i])
                x = x + a
            if cfg.is_dense(i):
                with jax.named_scope("mlp"):
                    x = x + _mlp(layer["mlp"], _rms(
                        x, layer["mlp_norm"], cfg.rms_norm_eps))
            else:
                with jax.named_scope("moe"):
                    routed, shared, got = moe_parts(
                        cfg, layer["moe"],
                        _rms(x, layer["mlp_norm"], cfg.rms_norm_eps))
                    x = x + (routed + shared.astype(jnp.float32)
                             ).astype(x.dtype)
                counts.append(got)
    return x, caches, jnp.stack(counts) if counts else \
        jnp.zeros((0, cfg.experts), jnp.int32)


def _embed(cfg: DeepSeekV2Config, params, ids):
    with jax.named_scope("embed"):
        return params["embed"][ids - cfg.vocab0]


def _head(cfg: DeepSeekV2Config, params, x):
    """Logits over the held slice of the vocabulary, float32, and the
    greedy id (global) beside them."""
    with jax.named_scope("head"):
        logits = _mm(_rms(x, params["final_norm"], cfg.rms_norm_eps),
                     params["head"])
        return logits, (jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        + cfg.vocab0)


COUNTERS = ("steps", "cache_rows_read", "cache_rows_fetched",
            "experts_touched", "expert_hits")


def init_state(cfg: DeepSeekV2Config, params, streams: int, positions: int,
               dtype=None) -> dict:
    """The state a filter owns between invokes: the latent cache of the
    held layers, and the counters the steps add to (``uint32``: the
    reader takes differences, so a wrap costs nothing)."""
    dtype = dtype or params["embed"].dtype
    # one buffer a leaf: the state is donated leaf by leaf
    return {"cache": [mla.init_cache(cfg, streams, positions, dtype)
                      for _ in range(cfg.layers)],
            "counters": stream.zeros(COUNTERS)}


def counter_units(cfg: DeepSeekV2Config, state: dict) -> dict:
    """What one count of each counter stands for.  ``cache_rows_read``
    counts the latent rows IN USE of ONE layer (``0 .. position``), a
    row its ``latent`` values; ``cache_rows_fetched`` the rows the
    decode kernel copies for them (``ops/kernels.py``
    ``decode_rows_fetched``: every live cell whole), a row as the cache
    holds it (``row``: the ``latent`` values themselves where rows are
    packed, else padded to whole lanes).  A row is read in every
    layer."""
    size = state["cache"][0].dtype.itemsize * cfg.layers
    return {"cache_bytes_read": ("cache_rows_read", cfg.latent * size),
            "cache_bytes_fetched": ("cache_rows_fetched", cfg.row * size)}


def prefill(cfg: DeepSeekV2Config, params, state, ids, slot, start):
    """A chunk of ONE stream: ``ids [C]``, ``slot [1]``, ``start [1]``
    (all int32).  Writes rows ``[start, start + C)`` of the stream's
    cache; serves the logits and greedy id after the chunk's last
    token.  A chunk padded beyond its prompt writes rows that every
    later step masks or overwrites."""
    slot, start = slot[0], start[0]
    x = _embed(cfg, params, ids)
    x, caches, _ = _layers(
        cfg, params, x, state["cache"],
        lambda p, h, cache: mla.attn_prefill(cfg, p, h, cache, slot, start))
    logits, greedy = _head(cfg, params, x[-1:])
    return {"cache": caches, "counters": state["counters"]}, \
        (logits, greedy)


def decode(cfg: DeepSeekV2Config, params, state, ids, positions):
    """One token of EVERY stream: ``ids [B]``, ``positions [B]`` int32.
    Serves ``logits [B, vocab held]`` float32 and the greedy ids."""
    x = _embed(cfg, params, ids)
    x, caches, got = _layers(
        cfg, params, x, state["cache"],
        lambda p, h, cache: mla.attn_decode(cfg, p, h, cache, positions))
    logits, greedy = _head(cfg, params, x)
    total = mla.cache_positions(cfg, caches[0])
    gained = {"steps": 1,
              "cache_rows_read": jnp.sum(positions + 1),
              "cache_rows_fetched": kernels.decode_rows_fetched(
                  positions, total, total),
              "experts_touched": jnp.sum(got > 0),
              "expert_hits": jnp.sum(got)}
    return {"cache": caches,
            "counters": stream.bump(state["counters"], gained)}, \
        (logits, greedy)


# -- weights of the right shapes, and registration ----------------------------


def param_shapes(cfg: DeepSeekV2Config) -> dict:
    """The pytree of ``(shape, role)`` a weights maker fills: matrices
    carry the role their init gain is looked up by, vectors ``norm``."""
    h, qr, kr = cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank
    nh, f = cfg.heads, cfg.moe_intermediate_size
    attn = {"q_a": ((h, qr), "q_a"), "q_a_norm": ((qr,), "norm"),
            "q_b": ((qr, nh * cfg.q_head_dim), "q_b"),
            "kv_a": ((h, cfg.latent), "kv_a"),
            "kv_a_norm": ((kr,), "norm"),
            "kv_b": ((kr, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                     "kv_b"),
            "o": ((nh * cfg.v_head_dim, h), "o")}

    def mlp(width, down="down"):
        return {"gate": ((h, width), "gate"), "up": ((h, width), "up"),
                "down": ((width, h), down)}

    layers = []
    for i in range(cfg.layers):
        layer = {"attn_norm": ((h,), "norm"), "attn": dict(attn),
                 "mlp_norm": ((h,), "norm")}
        if cfg.is_dense(i):
            layer["mlp"] = mlp(cfg.intermediate_size)
        else:
            e = cfg.experts
            layer["moe"] = {
                "router": ((h, cfg.n_routed_experts), "router"),
                "experts": {"gate": ((e, h, f), "gate"),
                            "up": ((e, h, f), "up"),
                            "down": ((e, f, h), "expert_down")},
                "shared": mlp(f * cfg.n_shared_experts)}
        layers.append(layer)
    return {"embed": ((cfg.vocab, h), "embed"), "layers": layers,
            "final_norm": ((h,), "norm"), "head": ((h, cfg.vocab), "head")}


def init_params(cfg: DeepSeekV2Config, key, dtype=None) -> Params:
    """Seeded weights of the right shapes (``models/streams.py``
    ``seeded_params``): matrices N(0, 1/fan_in) (residual branches
    halved), norm gains 1."""
    return stream.seeded_params(param_shapes(cfg), key, dtype)


def entries(cfg: DeepSeekV2Config, streams: int, positions: int,
            chunk: int) -> Dict[str, Any]:
    """What :func:`register` hands ``register_stateful_model``
    (``models/streams.py`` ``entries``, cached by these arguments): the
    two entry points with their input schemas, and ``init_state``."""
    return stream.entries(
        cfg, decode, ((streams,), (streams,)),
        prefill, ((chunk,), (1,), (1,)), init_state, counter_units,
        streams=streams, positions=positions)


def register(name: str, cfg: DeepSeekV2Config, params: Params, streams: int,
             positions: int, chunk: int) -> str:
    """Register ``params`` as the stateful model ``name`` for
    ``tensor_filter framework=jax-xla model=<name>``: a filter whose
    negotiated input is ``(ids[chunk], slot[1], start[1])`` prefills,
    one whose input is ``(ids[streams], positions[streams])`` decodes;
    two filters with one ``shared-tensor-filter-key`` work on one
    cache."""
    return stream.register(name, params,
                           entries(cfg, streams, positions, chunk))
