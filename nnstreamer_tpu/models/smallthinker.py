"""SmallThinker (``model_name`` ``smallthinker_21b_instruct``) as a
stateful model of the element stream.

Written from the model's public ``config.json``: pre-norm residual
layers, RMSNorm, no biases, grouped-query attention (``num_attention_heads``
query heads over ``num_key_value_heads`` key/value heads of ``head_dim``),
and in every layer ``moe_num_primary_experts`` routed experts with a
ReLU gate, ``moe_num_active_primary_experts`` a token, no shared expert.
Two things set it apart from ``deepseek_v2.py``:

**Two kinds of layer, two kinds of cache.**  Where
``sliding_window_layout[l]`` is 1 the layer rotates q and k
(``rope_theta``, no scaling, pairs ``(i, i + head_dim/2)``) and position
``p`` sees positions ``p - window + 1 .. p``; where it is 0 there is no
positional signal at all and ``p`` sees ``0 .. p``.  The state keeps,
per layer, a K and a V array ``[streams, kv heads, T, head_dim]``
(positions second to last: a product over them reads whole rows and
XLA adds no transposed copy).  In a window layer ``T`` is a RING of
``window + chunk`` positions, position ``p`` in slot ``p % T``; in a
full layer ``T`` is every position the stream may reach and never
wraps.  The ring is wider than the window by one prefill chunk because
rows NEWER than the position being decoded may lie in it, each on the
slot of a position ``T`` older: the last chunk of a prompt arrives
padded to a whole chunk and nothing tells the model where the prompt
ends (up to ``chunk - 1`` rows), and a stream may be rewound to answer
again from its prompt's end (the rows of the answer before).  A row at
``g > p`` is harmless while ``g - p <= T - window``: a ring of ``window
+ chunk`` takes a padded chunk and a rewind of up to ``chunk``
positions, a ring of exactly ``window`` neither.  With that room a
chunk's rows can also be written first and attended to afterwards (``T
>= window + chunk - 1``), wherever the chunk straddles the ring's end.
Which slots count
is one rule for both kinds (``ops/kernels.py``
``gqa_decode_attention_reference``): a slot holds the newest position
that falls on it, and counts where that lies in the window.

**The router runs before attention.**  A layer's routing decision is
taken from the input of ATTENTION (the attention-normed stream), so the
router and the dispatch plan (a sort) are issued ahead of attention
and need nothing from it; the experts then run on the post-attention
stream with those weights.  Top-k of the logits, weights the softmax
over the kept k (the same numbers as softmax over all, top-k,
renormalised).  The expert product itself is ``models/moe.py``, shared
with ``deepseek_v2.py``.

:func:`prefill` runs a chunk of ONE stream (blocked over its cache with
a running softmax, the exact window mask), :func:`decode` one token of
EVERY stream (``ops/kernels.py`` ``gqa_prefill_attention`` and
``gqa_decode_attention`` where they take the shapes, their ``jnp``
references where they refuse them: a head size that is not whole
lanes, a toy ring).

Stage scopes (``Documentation/observability.md``): ``embed``,
``layerNN/attn_window`` or ``layerNN/attn_full`` (``.../cache_write``
and ``.../gqa_prefill_attention`` or ``.../gqa_decode_attention``
inside), ``layerNN/moe/router|dispatch|experts|combine``, ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover
    jax = jnp = lax = None

from . import attention, moe
from . import streams as stream

Params = dict


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """The published sizes; ``layers`` counts the leading layers held
    (with their entries of the two layouts)."""

    hidden_size: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_width: int
    experts: int
    top_k: int
    window: int
    rope_theta: float
    rms_norm_eps: float
    max_positions: int
    vocab: int
    layers: int
    window_layers: Tuple[bool, ...]      # per held layer: a ring
    rope_layers: Tuple[bool, ...]        # per held layer: rotary q and k

    @classmethod
    def from_dict(cls, cfg: dict) -> "SmallThinkerConfig":
        """From a ``config.json`` as published, or a cut of its depth:
        the layouts keep their published length and the leading
        ``num_hidden_layers`` entries are used."""
        depth = int(cfg["num_hidden_layers"])
        layouts = [cfg["sliding_window_layout"], cfg["rope_layout"]]
        if any(len(layout) < depth for layout in layouts):
            raise ValueError("smallthinker: a layout is shorter than the "
                             f"{depth} layers held")
        if cfg.get("rope_scaling") is not None \
                or cfg.get("tie_word_embeddings", False):
            raise ValueError("smallthinker: rope scaling and tied "
                             "embeddings are not written")
        if not cfg.get("moe_primary_router_apply_softmax", True) \
                or not cfg.get("norm_topk_prob", True):
            raise ValueError("smallthinker: only a softmax router whose "
                             "kept weights are renormalised is written")
        out = cls(
            hidden_size=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            expert_width=int(cfg["moe_ffn_hidden_size"]),
            experts=int(cfg["moe_num_primary_experts"]),
            top_k=int(cfg["moe_num_active_primary_experts"]),
            window=int(cfg["sliding_window_size"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            max_positions=int(cfg["max_position_embeddings"]),
            vocab=int(cfg["vocab_size"]),
            layers=depth,
            window_layers=tuple(bool(v) for v in layouts[0][:depth]),
            rope_layers=tuple(bool(v) for v in layouts[1][:depth]))
        if out.heads % out.kv_heads or out.head_dim % 2:
            raise ValueError(
                f"smallthinker: {out.heads} query heads over "
                f"{out.kv_heads} key/value heads of {out.head_dim}")
        return out

    @property
    def per_group(self) -> int:
        """Query heads that read one key/value head."""
        return self.heads // self.kv_heads

    @property
    def row_values(self) -> int:
        """Values a token keeps in a layer's cache: its K and its V."""
        return 2 * self.kv_heads * self.head_dim

    def ring(self, chunk: int) -> int:
        """Positions of a window layer's ring (module docstring)."""
        return self.window + int(chunk)


# -- small parts --------------------------------------------------------------


def route(cfg: SmallThinkerConfig, h, router):
    """The ``top_k`` largest logits of ``h W_r`` and the softmax over
    them, in float32: ``(idx [N, k] int32, weight [N, k] float32)``."""
    logits = jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    kept, idx = lax.top_k(logits, cfg.top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(kept, axis=-1)


def _qkv(cfg: SmallThinkerConfig, p, h, positions, rotary: bool):
    """``(q [N, kv heads, heads a group, d], k [N, kv heads, d], v)``:
    query head ``i`` reads key/value head ``i // per_group``."""
    n, dt = h.shape[0], h.dtype
    q = moe.mm(h, p["q"]).astype(dt).reshape(
        n, cfg.kv_heads, cfg.per_group, cfg.head_dim)
    k = moe.mm(h, p["k"]).astype(dt).reshape(n, cfg.kv_heads, cfg.head_dim)
    v = moe.mm(h, p["v"]).astype(dt).reshape(n, cfg.kv_heads, cfg.head_dim)
    if rotary:
        cos, sin = attention.rope_angles(cfg.rope_theta, cfg.head_dim,
                                         positions)
        q = attention.rope(q, cos[:, None, None], sin[:, None, None])
        k = attention.rope(k, cos[:, None], sin[:, None])
    return q, k, v


# -- attention ----------------------------------------------------------------


def attn_prefill(cfg: SmallThinkerConfig, layer: int, p, h, cache, slot,
                 start):
    """A chunk ``h [C, hidden]`` of stream ``slot`` whose first token is
    at ``start``: ``models/attention.py`` ``prefill`` on this model's q,
    k and v under the layer's mask.  ``T >= window + C - 1`` in a window
    layer, so every position a query of the chunk sees is still in the
    ring once the chunk is written."""
    window = cfg.window if cfg.window_layers[layer] \
        else cache["k"].shape[2]
    o, cache = attention.prefill(
        lambda positions: _qkv(cfg, p, h, positions, cfg.rope_layers[layer]),
        h.shape[0], cache, slot, start, window, moe.precision(p["q"]))
    return attention.heads_out(p, o, h.dtype), cache


def attn_decode(cfg: SmallThinkerConfig, layer: int, p, h, cache, positions):
    """One token of every stream: ``h [B, hidden]``, stream ``b`` at
    ``positions[b]``.  Writes each stream's K and V row, then attends
    over the slots that hold a position of its window
    (``models/attention.py`` ``decode_step``)."""
    window = cfg.window if cfg.window_layers[layer] \
        else cache["k"].shape[2]
    q, k, v = _qkv(cfg, p, h, positions, cfg.rope_layers[layer])
    o, cache = attention.decode_step(q, k, v, cache, positions, window,
                                     cfg.head_dim ** -0.5)
    return attention.heads_out(p, o, h.dtype), cache


# -- the model ----------------------------------------------------------------


def _layers(cfg: SmallThinkerConfig, params, x, caches, attend):
    """Every held layer on ``x [N, hidden]``; ``attend(layer, layer
    params, normed x, cache) -> (output, cache)``.  Returns the stream,
    the caches and the tokens each expert of each layer got (``[layers,
    experts]``)."""
    caches, counts, n = list(caches), [], x.shape[0]
    for i, layer in enumerate(params["layers"]):
        attn = "attn_window" if cfg.window_layers[i] else "attn_full"
        with jax.named_scope(f"layer{i:02d}"):
            # a branch's scope holds its norm and its residual add, so
            # that the fusions XLA roots there are booked to the branch
            with jax.named_scope(attn):
                h = moe.rms(x, layer["attn_norm"], cfg.rms_norm_eps)
            # the early router: chosen from attention's input, planned
            # before attention runs
            with jax.named_scope("moe"):
                with jax.named_scope("router"):
                    idx, weight = route(cfg, h, layer["moe"]["router"])
                with jax.named_scope("dispatch"):
                    plan = moe.dispatch(idx, n, 0, cfg.experts,
                                        cfg.experts)
            with jax.named_scope(attn):
                a, caches[i] = attend(i, layer["attn"], h, caches[i])
                x = x + a
            with jax.named_scope("moe"):
                u = moe.rms(x, layer["ffn_norm"], cfg.rms_norm_eps)
                with jax.named_scope("experts"):
                    out = moe.grouped_experts(layer["moe"]["experts"], u,
                                              plan, "relu")
                with jax.named_scope("combine"):
                    y = moe.combine(out, plan, weight)
                x = x + y.astype(x.dtype)
            counts.append(plan["counts"])
    return x, caches, jnp.stack(counts)


def _embed(params, ids):
    with jax.named_scope("embed"):
        return params["embed"][ids]


def _head(cfg: SmallThinkerConfig, params, x):
    """Logits over the vocabulary, float32, and the greedy id."""
    with jax.named_scope("head"):
        logits = moe.mm(moe.rms(x, params["final_norm"], cfg.rms_norm_eps),
                        params["head"])
        return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32)


COUNTERS = ("steps", "window_rows_read", "full_rows_read",
            "window_rows_fetched", "full_rows_fetched",
            "experts_touched", "expert_hits")


def init_state(cfg: SmallThinkerConfig, params, streams: int, positions: int,
               chunk: int, dtype=None) -> dict:
    """The state a filter owns between invokes: per layer a K and a V
    array, a ring of ``cfg.ring(chunk)`` positions in a window layer
    and ``positions`` in a full one, and the counters the steps add to
    (``uint32``: the reader takes differences, so a wrap costs
    nothing)."""
    dtype = dtype or params["embed"].dtype
    if positions > cfg.max_positions:
        raise ValueError(f"smallthinker: {positions} positions, the model "
                         f"has {cfg.max_positions}")

    return {"cache": [attention.kv_cache(
                streams, cfg.kv_heads, cfg.ring(chunk) if ring else positions,
                cfg.head_dim, dtype) for ring in cfg.window_layers],
            "counters": stream.zeros(COUNTERS)}


def counter_units(cfg: SmallThinkerConfig, state: dict) -> dict:
    """What the raw counters stand for in bytes.  ``window_rows_read``
    and ``full_rows_read`` count the rows IN USE of ONE layer of their
    kind (a stream past the window uses ``window`` rows of a ring,
    whatever the ring holds), ``*_rows_fetched`` the rows the decode
    attention reads in for them (``models/attention.py``
    ``decode_rows_fetched``); a row is a token's K and V."""
    row = cfg.row_values * state["cache"][0]["k"].dtype.itemsize
    rings = sum(cfg.window_layers)
    out = {}
    for did in ("read", "fetched"):
        window = (f"window_rows_{did}", row * rings)
        full = (f"full_rows_{did}", row * (cfg.layers - rings))
        out.update({f"window_bytes_{did}": window, f"full_bytes_{did}": full,
                    f"cache_bytes_{did}": [window, full]})
    return out


def prefill(cfg: SmallThinkerConfig, params, state, ids, slot, start):
    """A chunk of ONE stream: ``ids [C]``, ``slot [1]``, ``start [1]``
    (all int32).  Writes the rows of positions ``[start, start + C)``;
    serves the logits and greedy id after the chunk's last token.  A
    chunk padded beyond its prompt writes rows that no later step sees
    before it overwrites them."""
    slot, start = slot[0], start[0]
    x = _embed(params, ids)
    x, caches, _ = _layers(
        cfg, params, x, state["cache"],
        lambda i, p, h, cache: attn_prefill(cfg, i, p, h, cache, slot, start))
    logits, greedy = _head(cfg, params, x[-1:])
    return {"cache": caches, "counters": state["counters"]}, \
        (logits, greedy)


def decode(cfg: SmallThinkerConfig, params, state, ids, positions):
    """One token of EVERY stream: ``ids [B]``, ``positions [B]`` int32.
    Serves ``logits [B, vocab]`` float32 and the greedy ids."""
    x = _embed(params, ids)
    x, caches, got = _layers(
        cfg, params, x, state["cache"],
        lambda i, p, h, cache: attn_decode(cfg, i, p, h, cache, positions))
    logits, greedy = _head(cfg, params, x)
    rows = positions + 1
    rings = [c for c, ring in zip(caches, cfg.window_layers) if ring]
    fulls = [c for c, ring in zip(caches, cfg.window_layers) if not ring]
    gained = {"steps": 1,
              "window_rows_read": jnp.sum(jnp.minimum(rows, cfg.window)),
              "full_rows_read": jnp.sum(rows),
              "window_rows_fetched": attention.decode_rows_fetched(
                  rings, cfg.per_group, positions, cfg.window),
              "full_rows_fetched": attention.decode_rows_fetched(
                  fulls, cfg.per_group, positions),
              "experts_touched": jnp.sum(got > 0),
              "expert_hits": jnp.sum(got)}
    return {"cache": caches,
            "counters": stream.bump(state["counters"], gained)}, \
        (logits, greedy)


# -- weights of the right shapes, and registration ----------------------------


def param_shapes(cfg: SmallThinkerConfig) -> dict:
    """The pytree of ``(shape, role)`` a weights maker fills: matrices
    carry the role their init gain is looked up by, vectors ``norm``."""
    h, d, f, e = cfg.hidden_size, cfg.head_dim, cfg.expert_width, cfg.experts
    layer = {
        "attn_norm": ((h,), "norm"), "ffn_norm": ((h,), "norm"),
        "attn": {"q": ((h, cfg.heads * d), "q"),
                 "k": ((h, cfg.kv_heads * d), "k"),
                 "v": ((h, cfg.kv_heads * d), "v"),
                 "o": ((cfg.heads * d, h), "o")},
        "moe": {"router": ((h, e), "router"),
                "experts": {"gate": ((e, h, f), "gate"),
                            "up": ((e, h, f), "up"),
                            "down": ((e, f, h), "expert_down")}}}
    return {"embed": ((cfg.vocab, h), "embed"),
            "layers": [layer for _ in range(cfg.layers)],
            "final_norm": ((h,), "norm"), "head": ((h, cfg.vocab), "head")}


def init_params(cfg: SmallThinkerConfig, key, dtype=None) -> Params:
    """Seeded weights of the right shapes (``models/streams.py``
    ``seeded_params``): matrices N(0, 1/fan_in) (residual branches
    halved), norm gains 1."""
    return stream.seeded_params(param_shapes(cfg), key, dtype)


def entries(cfg: SmallThinkerConfig, streams: int, positions: int,
            chunk: int) -> Dict[str, Any]:
    """What :func:`register` hands ``register_stateful_model``
    (``models/streams.py`` ``entries``, cached by these arguments): the
    two entry points with their input schemas, and ``init_state``."""
    return stream.entries(
        cfg, decode, ((streams,), (streams,)),
        prefill, ((chunk,), (1,), (1,)), init_state, counter_units,
        streams=streams, positions=positions, chunk=chunk)


def register(name: str, cfg: SmallThinkerConfig, params: Params, streams: int,
             positions: int, chunk: int) -> str:
    """Register ``params`` as the stateful model ``name`` for
    ``tensor_filter framework=jax-xla model=<name>``: a filter whose
    negotiated input is ``(ids[chunk], slot[1], start[1])`` prefills,
    one whose input is ``(ids[streams], positions[streams])`` decodes;
    two filters with one ``shared-tensor-filter-key`` work on one state
    (the rings and the full caches)."""
    return stream.register(name, params,
                           entries(cfg, streams, positions, chunk))
