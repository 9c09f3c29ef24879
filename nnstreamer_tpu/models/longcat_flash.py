"""LongCat-Flash's language model as one chip's share of a layer divided
over several chips (the family of ``LongCat-Flash-Chat`` and of
``LongCat-Flash-Omni``, whose public ``config.json`` is the language
model's alone: no audio or vision encoder and no codec decoder is
here).

Written from the config's keys, the LongCat-Flash technical report
(arXiv:2509.01322) and the ``longcat_flash`` model of the
``transformers`` library.  RMSNorm, no biases.  A layer is not "one
mixer, one MLP" but two latent-attention sub-blocks, two dense MLPs and
one expert branch that leaves the stream after the FIRST sub-block's
attention and rejoins it at the layer's END (the shortcut-connected
layer)::

    a0 = x  + MLA[l,0]( rms(x;  g_in0) )
    u  =      rms(a0; g_post0)
    m  =      MoE[l](u)                 # the shortcut branch
    b0 = a0 + MLP[l,0](u)
    a1 = b0 + MLA[l,1]( rms(b0; g_in1) )
    v  =      rms(a1; g_post1)
    y  = a1 + MLP[l,1](v) + m

``MLA`` is ``models/mla.py`` (which ``deepseek_v2.py`` runs too) with
every head held, a plain rotation (``rope_theta``, no scaling) and two
constants on the normed low-rank streams, ``sqrt(hidden / q_lora_rank)``
and ``sqrt(hidden / kv_lora_rank)`` (``mla_scale_q_lora``,
``mla_scale_kv_lora``; ``k_r`` is not scaled).  ``MLP`` is SwiGLU at
``ffn_hidden_size``.  ``MoE`` routes by softmax in float32 over ALL
``n_routed_experts + zero_expert_num`` logits, chooses the ``moe_topk``
largest of ``p + b`` (``b`` the correction bias) and weights them by
``routed_scaling_factor * p``, not normalised; a pick on a real expert
adds that expert's SwiGLU MLP of ``u``, a pick on a zero-compute
(identity) expert adds ``weight * u`` and costs no product, so the work
a token costs varies with the token.

**The share.**  :class:`LongCatFlashConfig` holds the published sizes
and, beside them, what is held: ``layers``, routed experts ``[expert0,
expert0 + experts)`` and vocabulary rows ``[vocab0, vocab0 + vocab)``;
heads, the dense MLPs and the router whole.  The model routes over all
the router's outputs, computes the held experts' part for the tokens
routed to them (``models/moe.py``: nothing dropped, no capacity; a pick
on an expert held elsewhere or on a zero-compute expert falls out of
the plan), adds what every chip computes alike (both attentions, both
MLPs, the zero-compute picks' term) and passes that partial sum on.
Nothing stands in for the absent chips or their exchange.

**State.**  Two latent caches a layer, ``state["cache"][layer][sub]``,
each a token's 576 values two positions a row, ``[streams, positions /
2, 1152]``, at the published sizes (``models/mla.py`` has the row's
form: a rank that is not whole lanes keeps a row a position, padded to
whole lanes); :func:`prefill` writes and reads both through the
expanded form, :func:`decode` through the absorbed form.  Counters beside them: ``steps``, ``cache_rows_read``,
``cache_rows_fetched``, ``experts_touched``, ``expert_hits`` and
``zero_picks``, the picks that fell on zero-compute experts (a step
makes tokens x ``moe_topk`` x layers picks, a constant: no counter).

Stage scopes (``Documentation/observability.md``): ``embed``,
``layerNN/s0/attn`` and ``layerNN/s1/attn`` (``.../attn/cache_write``
and ``.../attn/latent_decode_attention`` inside), ``layerNN/s0/mlp``,
``layerNN/s1/mlp``, ``layerNN/moe/router|dispatch|experts|zero|combine``,
``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

try:
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover
    jax = jnp = None

from ..ops import kernels
from . import mla, moe
from . import streams as stream
from .attention import rope_angles

Params = dict
SUBS = 2                       # latent-attention sub-blocks a layer

_rms, _mm = moe.rms, moe.mm


@dataclasses.dataclass(frozen=True)
class LongCatFlashConfig:
    """The published sizes, and beside them what is HELD here.  Every
    width is the source's; ``layers``, ``experts`` and ``vocab`` (with
    their offsets) are the share."""

    hidden_size: int
    ffn_hidden_size: int
    expert_ffn_hidden_size: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    heads: int                     # every head: attention is whole
    n_routed_experts: int          # the real experts (published)
    zero_expert_num: int
    moe_topk: int
    routed_scaling_factor: float
    mla_scale_q_lora: bool
    mla_scale_kv_lora: bool
    rms_norm_eps: float
    rope_theta: float
    layers: int                    # held: the leading layers of the model
    experts: int
    expert0: int
    vocab: int
    vocab0: int

    @classmethod
    def from_dict(cls, cfg: dict) -> "LongCatFlashConfig":
        """From a ``config.json`` as published, or from a chip's share
        of one: then ``num_layers``, ``n_routed_experts`` and
        ``vocab_size`` count what is held, ``published`` gives the
        source's values (the router is ``published.n_routed_experts +
        zero_expert_num`` wide) and ``share`` the offsets ``expert0`` /
        ``vocab0`` (0 where absent)."""
        published = cfg.get("published", {})
        share = cfg.get("share", {})
        if cfg.get("zero_expert_type", "identity") != "identity":
            raise ValueError("longcat_flash: only identity zero-compute "
                             "experts are written")
        out = cls(
            hidden_size=int(cfg["hidden_size"]),
            ffn_hidden_size=int(cfg["ffn_hidden_size"]),
            expert_ffn_hidden_size=int(cfg["expert_ffn_hidden_size"]),
            q_lora_rank=int(cfg["q_lora_rank"]),
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]),
            heads=int(cfg["num_attention_heads"]),
            n_routed_experts=int(published.get("n_routed_experts",
                                               cfg["n_routed_experts"])),
            zero_expert_num=int(cfg.get("zero_expert_num", 0)),
            moe_topk=int(cfg["moe_topk"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            mla_scale_q_lora=bool(cfg.get("mla_scale_q_lora", False)),
            mla_scale_kv_lora=bool(cfg.get("mla_scale_kv_lora", False)),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            layers=int(cfg["num_layers"]),
            experts=int(cfg["n_routed_experts"]),
            expert0=int(share.get("expert0", 0)),
            vocab=int(cfg["vocab_size"]),
            vocab0=int(share.get("vocab0", 0)))
        if out.expert0 < 0 \
                or out.expert0 + out.experts > out.n_routed_experts:
            raise ValueError(
                f"longcat_flash: held experts [{out.expert0}, "
                f"{out.expert0 + out.experts}) are not among the "
                f"{out.n_routed_experts} real ones")
        return out

    @property
    def router_width(self) -> int:
        """The router's outputs: real experts, then zero-compute ones."""
        return self.n_routed_experts + self.zero_expert_num

    @property
    def latent(self) -> int:
        """Values a token keeps in one cache: ``c_kv`` and ``k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row(self) -> int:
        """Values a position takes in a cache as stored: ``latent``
        where rows are packed, else padded to whole lanes."""
        return mla.row_values(self)

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # what ``models/mla.py`` reads beside the sizes
    @property
    def score_scale(self) -> float:
        return self.q_head_dim ** -0.5

    @property
    def q_lora_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_lora_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    def cos_sin(self, positions):
        return rope_angles(self.rope_theta, self.qk_rope_head_dim, positions)


# -- the parts of a layer -----------------------------------------------------


def dense_mlp_grouped(n_rows: int) -> bool:
    """Whether :func:`dense_mlp` sends ``n_rows`` rows through the
    grouped product: as many as ONE of its blocks holds."""
    return n_rows <= moe.block_rows(n_rows)


def dense_mlp(p, x):
    """``W_down(silu(W_gate x) * W_up x)`` at ``ffn_hidden_size``.

    A decode step's rows (:func:`dense_mlp_grouped`) go through
    ``models/moe.py``'s grouped product as one group of one expert:
    where ``ops/kernels.py`` ``grouped_gated_product`` takes the shapes,
    its pipeline streams the three matrices' tiles while it multiplies,
    and the benchmark's step reads 17.11 ms so against 18.18 with XLA's
    three products (``PERF.md`` section 6, PR 43); where it refuses
    them, the loop computes the same.  A prefill chunk has more rows
    than a block and keeps XLA's products (not measured the other way:
    ``PERF.md`` section 7)."""
    n = x.shape[0]
    if not dense_mlp_grouped(n):
        h = jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"])
        return _mm(h.astype(x.dtype), p["down"]).astype(x.dtype)
    return moe.grouped_experts({k: w[None] for k, w in p.items()},
                               x, moe.one_group_plan(n))[:n]


def route(cfg: LongCatFlashConfig, u, p):
    """Softmax in float32 over real and zero-compute experts alike, the
    ``moe_topk`` largest of ``p + b``, weighted by ``routed_scaling_factor
    * p`` without normalising: ``(idx [N, k] int32, weight [N, k]
    float32)``; ``idx >= n_routed_experts`` is a zero-compute expert."""
    return moe.route_softmax(u, p["router"], p["router_bias"], cfg.moe_topk,
                             cfg.routed_scaling_factor)


def moe_parts(cfg: LongCatFlashConfig, p, u):
    """``(routed, zero, counts, zero_picks)``: the held experts' weighted
    part for the tokens routed to them (float32), the zero-compute
    picks' ``(sum of their weights) * u`` that every chip computes alike
    (float32), how many tokens each held expert got, and how many picks
    fell on zero-compute experts."""
    n = u.shape[0]
    with jax.named_scope("router"):
        idx, weight = route(cfg, u, p)
    with jax.named_scope("dispatch"):
        # a pick on a zero-compute expert lies beyond every held expert
        # and falls out of the plan like one held elsewhere
        plan = moe.dispatch(idx, n, cfg.expert0, cfg.experts,
                            cfg.router_width)
    with jax.named_scope("experts"):
        out = moe.grouped_experts(p["experts"], u, plan)
    with jax.named_scope("zero"):
        zero = moe.zero_weight(idx, weight, cfg.n_routed_experts)[:, None] \
            * u.astype(jnp.float32)
    with jax.named_scope("combine"):
        routed = moe.combine(out, plan, weight)
    return routed, zero, plan["counts"], \
        jnp.sum(idx >= cfg.n_routed_experts)


def _layers(cfg: LongCatFlashConfig, params, x, caches, attend):
    """Every held layer on ``x [N, hidden]``; ``attend(sub-block's
    params, normed x, cache) -> (output, cache)``.  Returns the stream,
    the caches, the tokens each held expert of each layer got
    (``[layers, held]``) and the picks on zero-compute experts."""
    caches = [list(pair) for pair in caches]
    counts, zero_picks = [], jnp.int32(0)
    eps = cfg.rms_norm_eps
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{i:02d}"):
            # a branch's scope holds its norm and its residual add, so
            # that the fusions XLA roots there are booked to the branch
            with jax.named_scope("s0"):
                with jax.named_scope("attn"):
                    a, caches[i][0] = attend(
                        layer["attn"][0],
                        _rms(x, layer["attn_norm"][0], eps), caches[i][0])
                    x = x + a
                with jax.named_scope("mlp"):
                    u = _rms(x, layer["mlp_norm"][0], eps)
                    b0 = x + dense_mlp(layer["mlp"][0], u)
            with jax.named_scope("moe"):
                routed, zero, got, picked = moe_parts(cfg, layer["moe"], u)
                branch = routed + zero
            with jax.named_scope("s1"):
                with jax.named_scope("attn"):
                    a, caches[i][1] = attend(
                        layer["attn"][1],
                        _rms(b0, layer["attn_norm"][1], eps), caches[i][1])
                    x = b0 + a
                with jax.named_scope("mlp"):
                    v = _rms(x, layer["mlp_norm"][1], eps)
                    # the shortcut branch rejoins here, at the layer's end
                    x = x + (dense_mlp(layer["mlp"][1], v).astype(
                        jnp.float32) + branch).astype(x.dtype)
            counts.append(got)
            zero_picks = zero_picks + picked
    return x, caches, jnp.stack(counts), zero_picks


def _embed(cfg: LongCatFlashConfig, params, ids):
    with jax.named_scope("embed"):
        return params["embed"][ids - cfg.vocab0]


def _head(cfg: LongCatFlashConfig, params, x):
    """Logits over the held slice of the vocabulary, float32, and the
    greedy id (global) beside them."""
    with jax.named_scope("head"):
        logits = _mm(_rms(x, params["final_norm"], cfg.rms_norm_eps),
                     params["head"])
        return logits, (jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        + cfg.vocab0)


# -- the state and the two entry points ---------------------------------------

COUNTERS = ("steps", "cache_rows_read", "cache_rows_fetched",
            "experts_touched", "expert_hits", "zero_picks")


def init_state(cfg: LongCatFlashConfig, params, streams: int,
               positions: int, dtype=None) -> dict:
    """The state a filter owns between invokes: two latent caches a held
    layer, addressed ``[layer][sub-block]``, and the counters the steps
    add to (``uint32``: the reader takes differences, so a wrap costs
    nothing)."""
    dtype = dtype or params["embed"].dtype
    # one buffer a leaf: the state is donated leaf by leaf
    return {"cache": [[mla.init_cache(cfg, streams, positions, dtype)
                       for _ in range(SUBS)] for _ in range(cfg.layers)],
            "counters": stream.zeros(COUNTERS)}


def counter_units(cfg: LongCatFlashConfig, state: dict) -> dict:
    """What one count of each counter stands for.  ``cache_rows_read``
    counts the latent rows IN USE of ONE cache (``0 .. position``), a
    row its ``latent`` values; ``cache_rows_fetched`` the rows the
    decode kernel copies for them (every live cell whole), a row as the
    cache holds it (``row``: the ``latent`` values themselves where rows
    are packed, else padded to whole lanes).  A row is read in both caches
    of every layer."""
    size = state["cache"][0][0].dtype.itemsize * cfg.layers * SUBS
    return {"cache_bytes_read": ("cache_rows_read", cfg.latent * size),
            "cache_bytes_fetched": ("cache_rows_fetched", cfg.row * size)}


def prefill(cfg: LongCatFlashConfig, params, state, ids, slot, start):
    """A chunk of ONE stream: ``ids [C]``, ``slot [1]``, ``start [1]``
    (all int32).  Writes rows ``[start, start + C)`` of the stream's
    caches; serves the logits and greedy id after the chunk's last
    token.  A chunk padded beyond its prompt writes rows that every
    later step masks or overwrites."""
    slot, start = slot[0], start[0]
    x = _embed(cfg, params, ids)
    x, caches, _, _ = _layers(
        cfg, params, x, state["cache"],
        lambda p, h, cache: mla.attn_prefill(cfg, p, h, cache, slot, start))
    logits, greedy = _head(cfg, params, x[-1:])
    return {"cache": caches, "counters": state["counters"]}, \
        (logits, greedy)


def decode(cfg: LongCatFlashConfig, params, state, ids, positions):
    """One token of EVERY stream: ``ids [B]``, ``positions [B]`` int32.
    Serves ``logits [B, vocab held]`` float32 and the greedy ids."""
    x = _embed(cfg, params, ids)
    x, caches, got, zero_picks = _layers(
        cfg, params, x, state["cache"],
        lambda p, h, cache: mla.attn_decode(cfg, p, h, cache, positions))
    logits, greedy = _head(cfg, params, x)
    total = mla.cache_positions(cfg, caches[0][0])
    gained = {
        "steps": 1,
        "cache_rows_read": jnp.sum(positions + 1),
        "cache_rows_fetched": kernels.decode_rows_fetched(
            positions, total, total),
        "experts_touched": jnp.sum(got > 0),
        "expert_hits": jnp.sum(got),
        "zero_picks": zero_picks}
    return {"cache": caches,
            "counters": stream.bump(state["counters"], gained)}, \
        (logits, greedy)


# -- weights of the right shapes, and registration ----------------------------


def param_shapes(cfg: LongCatFlashConfig) -> dict:
    """The pytree of ``(shape, role)`` a weights maker fills: matrices
    carry the role their init gain is looked up by, vectors ``norm``
    (the router's correction bias ``router_bias``)."""
    h, qr, kr = cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank
    nh, f, e = cfg.heads, cfg.expert_ffn_hidden_size, cfg.experts
    attn = {"q_a": ((h, qr), "q_a"), "q_a_norm": ((qr,), "norm"),
            "q_b": ((qr, nh * cfg.q_head_dim), "q_b"),
            "kv_a": ((h, cfg.latent), "kv_a"),
            "kv_a_norm": ((kr,), "norm"),
            "kv_b": ((kr, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                     "kv_b"),
            "o": ((nh * cfg.v_head_dim, h), "o")}
    width = cfg.ffn_hidden_size
    mlp = {"gate": ((h, width), "gate"), "up": ((h, width), "up"),
           "down": ((width, h), "down")}
    layer = {
        "attn_norm": [((h,), "norm")] * SUBS,
        "attn": [dict(attn) for _ in range(SUBS)],
        "mlp_norm": [((h,), "norm")] * SUBS,
        "mlp": [dict(mlp) for _ in range(SUBS)],
        "moe": {"router": ((h, cfg.router_width), "router"),
                "router_bias": ((cfg.router_width,), "router_bias"),
                "experts": {"gate": ((e, h, f), "expert_gate"),
                            "up": ((e, h, f), "expert_up"),
                            "down": ((e, f, h), "expert_down")}}}
    return {"embed": ((cfg.vocab, h), "embed"),
            "layers": [layer for _ in range(cfg.layers)],
            "final_norm": ((h,), "norm"), "head": ((h, cfg.vocab), "head")}


def init_params(cfg: LongCatFlashConfig, key, dtype=None) -> Params:
    """Seeded weights of the right shapes (``models/streams.py``
    ``seeded_params``): matrices N(0, 1/fan_in) (residual branches
    halved), norm gains 1, the correction bias 0."""
    return stream.seeded_params(
        param_shapes(cfg), key, dtype, special={
            "router_bias": lambda _k, shape: jnp.zeros(shape, jnp.float32)})


def entries(cfg: LongCatFlashConfig, streams: int, positions: int,
            chunk: int) -> Dict[str, Any]:
    """What :func:`register` hands ``register_stateful_model``
    (``models/streams.py`` ``entries``, cached by these arguments): the
    two entry points with their input schemas, and ``init_state``."""
    return stream.entries(
        cfg, decode, ((streams,), (streams,)),
        prefill, ((chunk,), (1,), (1,)), init_state, counter_units,
        streams=streams, positions=positions)


def register(name: str, cfg: LongCatFlashConfig, params: Params,
             streams: int, positions: int, chunk: int) -> str:
    """Register ``params`` as the stateful model ``name`` for
    ``tensor_filter framework=jax-xla model=<name>``: a filter whose
    negotiated input is ``(ids[chunk], slot[1], start[1])`` prefills,
    one whose input is ``(ids[streams], positions[streams])`` decodes;
    two filters with one ``shared-tensor-filter-key`` work on one
    state."""
    return stream.register(name, params,
                           entries(cfg, streams, positions, chunk))
