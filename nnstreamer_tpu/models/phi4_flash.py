"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``) as a stateful
model of the element stream: a decoder-hybrid-decoder (SambaY,
arXiv:2507.06607, with differential attention) whose last layers own no
state.

Written from the model's public ``config.json`` and the paper's
equations.  LayerNorm (gain and bias, float32 statistics), no rotary and
no other positional term anywhere (the state-space layers carry
position), a tied head::

    x0 = E[id]
    u  = LN_l(x);   x = x + Mixer_l(u)
    w  = LN'_l(x);  x = x + W_down( silu(g) * p ),  [g | p] = W_gate_up w
    logits = LN_f(x) E^T                                   (float32)

With ``F = num_hidden_layers / 2 + 1`` (17 of 32) the mixers are:

=====================  =======================================  ==========
layer                  mixer                                    state
=====================  =======================================  ==========
even, below ``F``      Mamba-1 (``models/mamba1.py``)           recurrent
odd, below ``F``       differential attention, a window         a ring
``F``                  differential attention, every position   THE cache
even, above ``F``      gated memory unit on layer ``F - 1``'s   none
                       scan output ``m_t`` of the SAME token
odd, above ``F``       differential CROSS-attention to layer    none
                       ``F``'s K and V, queries of its own
=====================  =======================================  ==========

**State with one writer and several readers.**  The layers above ``F``
own nothing: ``state`` is ``{"mamba": [...], "rings": [...], "shared":
{"k", "v"}}`` and not a list with one entry a layer.  Layer ``F`` writes
a token's row of ``shared`` (``attention.write_step`` /
``write_chunk``) and attends to it; every cross layer calls
``attention.attend_step`` / ``attend_chunk`` on the same arrays with
its own queries.  ``m_t`` is a value of the step, not state: layer ``F -
1`` computes it and the gated memory units read it.

**Differential attention in the kernels' layout.**  Heads of 64 are
paired: a K row is ``[k1_j | k2_j]`` and a V row ``[v_2j | v_2j+1]``,
128 wide, and query pair ``i`` (reading K/V pair ``i // 2``) is two
rows ``[q1_i | 0]`` and ``[0 | q2_i]``.  The zero lanes make the row's
product with a K row exactly ``q1 . k1`` (or ``q2 . k2``) and both rows
read the 128-wide V, so ``models/attention.py`` and both GQA kernels
take the model as ``kv heads`` 10, 4 query rows a group, ``d`` 128, a
scale of ``64^-1/2``, and return ``a1`` and ``a2``: ``o_i = (1 -
lambda0) RMSNorm_128(a1 - lambda a2)``, ``lambda = exp(lq1 . lk1) -
exp(lq2 . lk2) + lambda0``, ``lambda0 = 0.8 - 0.6 exp(-0.3 l)``.  The
projections' columns are laid out in that order; a checkpoint's are a
permutation of them.

**Two entry points of different depth.**  The cross-decoder at position
``t`` depends on the self-decoder's outputs at ``t``, on ``shared`` up
to ``t`` and on nothing the cross-decoder computed elsewhere.  So
:func:`prefill` runs layers ``0 .. F - 1`` and layer ``F``'s K/V
projection over all the chunk's tokens and everything after on ONE
token, the chunk's last real one, whose logits it serves (``skip=False``
runs every layer on every token: what the tests hold the skip to).  The
counters ``prefill_tokens`` and ``cross_tokens`` say how many tokens
each half ran on.

:func:`prefill` takes ``(ids, slot, start, count)``: the recurrent
states obey ``count``, the rings and the cache obey positions (a ring of
``window + chunk`` absorbs a padded chunk's rows and a rewind of up to a
chunk).  :func:`decode` takes ``(ids, positions)`` and obeys the book
(``models/streams.py`` ``book_step`` with a ``newest``: a stream at its
``prompt_end`` restores the nine recurrent states from their snapshots
while the rings' newest row lies within their room).

Stage scopes (``Documentation/observability.md``): ``embed``, ``state``,
``ssm_restore``, ``layerNN/mamba/in_proj|conv|x_proj|scan|step|gate|
out_proj``, ``layerNN/attn_window|attn_full|attn_cross/qkv|cache_write|
gqa_decode_attention|gqa_prefill_attention|diff|o``, ``layerNN/gmu``,
``layerNN/mlp``, ``head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover
    jax = jnp = lax = None

from ..ops import kernels
from ..utils import profile as _profile
from . import attention, mamba1, mamba2, moe
from . import streams as stream

Params = dict


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The published sizes, and the Mamba-1 sizes the configuration
    class defaults to where the file does not carry them."""

    hidden_size: int
    intermediate_size: int
    heads: int
    kv_heads: int
    layers: int
    window: int
    eps: float
    vocab: int
    max_positions: int
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int

    @classmethod
    def from_dict(cls, cfg: dict) -> "Phi4FlashConfig":
        """From a ``config.json`` as published.  ``mamba_d_state``,
        ``mamba_d_conv``, ``mamba_expand`` and ``mamba_dt_rank`` default
        to Mamba-1's (16, 4, 2, ``ceil(hidden / 16)``)."""
        if cfg.get("hidden_act", "silu") != "silu" \
                or not cfg.get("tie_word_embeddings", True) \
                or cfg.get("mlp_bias", False) \
                or cfg.get("lm_head_bias", False) \
                or int(cfg.get("mb_per_layer", 2)) != 2:
            raise ValueError("phi4flash: only a silu MLP without bias, a "
                             "tied head without bias and a Mamba mixer in "
                             "every second layer are written")
        hidden = int(cfg["hidden_size"])
        rank = cfg.get("mamba_dt_rank", "auto")
        out = cls(
            hidden_size=hidden,
            intermediate_size=int(cfg["intermediate_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            layers=int(cfg["num_hidden_layers"]),
            window=int(cfg["sliding_window"]),
            eps=float(cfg["layer_norm_eps"]),
            vocab=int(cfg["vocab_size"]),
            max_positions=int(cfg["max_position_embeddings"]),
            d_state=int(cfg.get("mamba_d_state", 16)),
            d_conv=int(cfg.get("mamba_d_conv", 4)),
            expand=int(cfg.get("mamba_expand", 2)),
            dt_rank=-(-hidden // 16) if rank == "auto" else int(rank))
        if out.layers % 4 or out.layers < 8 or hidden % out.heads \
                or out.heads % 2 or out.kv_heads % 2 \
                or out.heads % out.kv_heads:
            raise ValueError(
                f"phi4flash: {out.layers} layers (whole periods of 4, two "
                f"at least), {out.heads} query heads over {out.kv_heads} "
                f"key/value heads (pairs of each) of a hidden size of "
                f"{hidden}")
        return out

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.heads

    @property
    def pair_dim(self) -> int:
        """A pair of heads side by side: the width of a K or V row."""
        return 2 * self.head_dim

    @property
    def kv_pairs(self) -> int:
        """K/V pairs: what ``models/attention.py`` calls kv heads."""
        return self.kv_heads // 2

    @property
    def rows(self) -> int:
        """Query rows that read one K/V pair: two a query pair."""
        return self.heads // self.kv_pairs

    @property
    def full_layer(self) -> int:
        """``F``: the one layer that attends to every position and owns
        the cache the cross-decoder reads."""
        return self.layers // 2 + 1

    def kind(self, layer: int) -> str:
        if layer == self.full_layer:
            return "attn_full"
        if layer < self.full_layer:
            return "attn_window" if layer % 2 else "mamba"
        return "attn_cross" if layer % 2 else "gmu"

    def count(self, kind: str) -> int:
        return sum(self.kind(i) == kind for i in range(self.layers))

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    @property
    def mamba(self) -> mamba1.Geometry:
        return mamba1.Geometry(d_inner=self.expand * self.hidden_size,
                               state_size=self.d_state,
                               conv_kernel=self.d_conv, dt_rank=self.dt_rank)

    def ring(self, chunk: int) -> int:
        """Positions a window layer's ring holds: the window and a
        prefill chunk (a padded chunk's rows and a rewind of up to a
        chunk are absorbed, and ``attention.prefill`` finds every row a
        query of the chunk sees), in whole cells of the decode kernel's
        walk."""
        return -(-(self.window + chunk) // 128) * 128


# -- the parts of a layer -----------------------------------------------------


def _ln(x, p, eps: float):
    """LayerNorm with gain and bias, statistics in float32."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * lax.rsqrt(var + eps) * p["g"] + p["b"]) \
        .astype(x.dtype)


def _queries(cfg: Phi4FlashConfig, p, u):
    """``[N, kv pairs, rows, pair_dim]``: query pair ``(q1, q2)`` as the
    rows ``[q1 | 0]`` and ``[0 | q2]``."""
    n, dt = u.shape[0], u.dtype
    q = (moe.mm(u, p["q"]) + p["q_b"]).astype(dt).reshape(
        n, cfg.kv_pairs, cfg.rows // 2, 2, cfg.head_dim)
    zero = jnp.zeros_like(q[..., 0, :])
    return jnp.stack([jnp.concatenate([q[..., 0, :], zero], axis=-1),
                      jnp.concatenate([zero, q[..., 1, :]], axis=-1)],
                     axis=3).reshape(n, cfg.kv_pairs, cfg.rows, cfg.pair_dim)


def _keys_values(cfg: Phi4FlashConfig, p, u):
    """``k [N, kv pairs, pair_dim] = [k1 | k2]`` and ``v = [v_2j |
    v_2j+1]``."""
    n, dt = u.shape[0], u.dtype
    kv = (moe.mm(u, p["kv"]) + p["kv_b"]).astype(dt).reshape(
        n, 2, cfg.kv_pairs, cfg.pair_dim)
    return kv[:, 0], kv[:, 1]


def _lambda(cfg: Phi4FlashConfig, p, layer: int):
    return jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
        - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + cfg.lambda_init(layer)


def _differ(cfg: Phi4FlashConfig, p, layer: int, o, dtype):
    """``W_o [o_0 | ... ]`` of the kernels' ``o [N, kv pairs, rows,
    pair_dim]`` (rows ``a1, a2`` a query pair): ``o_i = (1 - lambda0)
    RMSNorm(a1 - lambda a2)``."""
    with jax.named_scope("diff"):
        n = o.shape[0]
        a = o.astype(jnp.float32).reshape(n, cfg.heads // 2, 2, cfg.pair_dim)
        d = a[:, :, 0] - _lambda(cfg, p, layer) * a[:, :, 1]
        d = d * lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + cfg.eps)
        d = (d * p["subln"] * (1.0 - cfg.lambda_init(layer))).astype(dtype)
    with jax.named_scope("o"):
        return (moe.mm(d.reshape(n, -1), p["o"]) + p["o_b"]).astype(dtype)


def _qkv(cfg: Phi4FlashConfig, p, u):
    with jax.named_scope("qkv"):
        return (_queries(cfg, p, u),) + _keys_values(cfg, p, u)


def _gmu(p, u, memory):
    """``W_out(m * silu(W_in u))`` on the memory of the SAME tokens."""
    g = (memory * jax.nn.silu(moe.mm(u, p["in"]))).astype(u.dtype)
    return moe.mm(g, p["out"]).astype(u.dtype)


def _mlp(cfg: Phi4FlashConfig, layer, x):
    """The layer's second half: its norm, the gated MLP and the add, in
    ONE scope, so that the fusions XLA roots there are booked to it."""
    with jax.named_scope("mlp"):
        w = _ln(x, layer["mlp_norm"], cfg.eps)
        gu = moe.mm(w, layer["mlp"]["gate_up"])
        f = cfg.intermediate_size
        h = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(x.dtype)
        return x + moe.mm(h, layer["mlp"]["down"]).astype(x.dtype)


def _self_decoder(cfg: Phi4FlashConfig, params, x, state, mamba, attend):
    """Layers ``0 .. F - 1`` on ``x [N, hidden]``: ``mamba(params, input,
    a layer's state) -> (output, y, state)``, ``attend(params, layer,
    input, a ring) -> (o, ring)``.  Returns the stream, the new recurrent
    states, the new rings and layer ``F - 1``'s ``y``: the memory the
    gated memory units read."""
    states, rings, memory = [], [], None
    for i in range(cfg.full_layer):
        layer = params["layers"][i]
        with jax.named_scope(f"layer{i:02d}"):
            kind = cfg.kind(i)
            with jax.named_scope(kind):
                u = _ln(x, layer["norm"], cfg.eps)
                if kind == "mamba":
                    out, memory, st = mamba(layer["mixer"], u,
                                            state["mamba"][len(states)])
                    states.append(st)
                else:
                    o, ring = attend(layer["mixer"], u,
                                     state["rings"][len(rings)])
                    out = _differ(cfg, layer["mixer"], i, o, x.dtype)
                    rings.append(ring)
                x = x + out
            x = _mlp(cfg, layer, x)
    return x, states, rings, memory


def _cross_decoder(cfg: Phi4FlashConfig, params, x, memory, attend):
    """Layers ``F + 1 ..`` on ``x [N, hidden]`` with the SAME tokens'
    ``memory [N, channels]``; ``attend(q) -> o`` reads layer ``F``'s
    cache.  No layer here owns or writes state."""
    for i in range(cfg.full_layer + 1, cfg.layers):
        layer = params["layers"][i]
        with jax.named_scope(f"layer{i:02d}"):
            kind = cfg.kind(i)
            with jax.named_scope(kind):
                u = _ln(x, layer["norm"], cfg.eps)
                if kind == "gmu":
                    out = _gmu(layer["mixer"], u, memory)
                else:
                    with jax.named_scope("qkv"):
                        q = _queries(cfg, layer["mixer"], u)
                    out = _differ(cfg, layer["mixer"], i, attend(q), x.dtype)
                x = x + out
            x = _mlp(cfg, layer, x)
    return x


def _embed(params, ids):
    with jax.named_scope("embed"):
        return params["embed"][ids]


def _head(cfg: Phi4FlashConfig, params, x):
    """Logits over the whole vocabulary through the embedding's own rows
    (tied), float32, and the greedy id beside them."""
    with jax.named_scope("head"):
        logits = lax.dot_general(
            _ln(x, params["final_norm"], cfg.eps), params["embed"],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=moe.precision(params["embed"]))
        return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32)


# -- the state and the two entry points ---------------------------------------

COUNTERS = ("steps", "ssm_rows", "shared_rows_read", "ring_rows_read",
            "shared_rows_fetched", "ring_rows_fetched", "restores",
            "position_faults", "prefill_tokens", "cross_tokens")


def init_state(cfg: Phi4FlashConfig, params, streams: int, positions: int,
               chunk: int, dtype=None) -> dict:
    """The state a filter owns between invokes.  NOT one entry a layer:
    the recurrent states of the Mamba layers (live and snapshot), the
    rings of the window layers (``cfg.ring(chunk)`` positions), and ONE
    cache of ``positions`` rows a stream that layer ``F`` writes and
    every cross layer reads; the book (with ``newest``, for the rings'
    room) and the counters (``uint32``, read as differences)."""
    dtype = dtype or params["embed"].dtype
    if positions > cfg.max_positions:
        raise ValueError(f"phi4flash: {positions} positions, the model "
                         f"has {cfg.max_positions}")
    return {
        "mamba": [mamba1.init_state(cfg.mamba, streams, dtype)
                  for _ in range(cfg.count("mamba"))],
        "rings": [attention.kv_cache(streams, cfg.kv_pairs, cfg.ring(chunk),
                                     cfg.pair_dim, dtype)
                  for _ in range(cfg.count("attn_window"))],
        "shared": attention.kv_cache(streams, cfg.kv_pairs, positions,
                                     cfg.pair_dim, dtype),
        **stream.book(streams, newest=True),
        "counters": stream.zeros(COUNTERS)}


def counter_units(cfg: Phi4FlashConfig, state: dict) -> dict:
    """What the raw counters stand for in bytes.  ``shared_rows_*``
    count rows of THE cache once; a byte of it is read by layer ``F``
    and by every cross layer, so the unit is a row times its READERS.
    ``ring_rows_*`` count the rows of ONE ring (a stream past the window
    uses ``window`` of them), times the rings.  ``ssm_rows`` counts the
    streams stepped in ONE Mamba layer: a row is a stream's ``ssm`` and
    ``conv``, read and written.  ``cache_bytes_*`` are the names the
    attention kernel's and the step's roofline readers know the sum
    by."""
    row = 2 * cfg.kv_pairs * cfg.pair_dim * state["shared"]["k"].dtype.itemsize
    readers = 1 + cfg.count("attn_cross")
    out = {"ssm_bytes": ("ssm_rows", 2 * mamba1.state_row_bytes(
        state["mamba"][0]) * len(state["mamba"]))}
    for did in ("read", "fetched"):
        shared = (f"shared_rows_{did}", row * readers)
        ring = (f"ring_rows_{did}", row * len(state["rings"]))
        out.update({f"shared_kv_bytes_{did}": shared,
                    f"ring_kv_bytes_{did}": ring,
                    f"kv_bytes_{did}": [shared, ring],
                    f"cache_bytes_{did}": [shared, ring]})
    return out


def _bumped(counters: dict, gained: dict) -> dict:
    """All the counters, those of ``gained`` advanced: an entry point
    bumps its own (a prefill chunk its two, a decode step the rest)."""
    return dict(counters, **stream.bump(counters, gained))


def _room(cfg: Phi4FlashConfig, state: dict) -> int:
    """How far ahead of a served position a ring's newest row may lie."""
    return state["rings"][0]["k"].shape[2] - cfg.window


def prefill(cfg: Phi4FlashConfig, params, state, ids, slot, start, count,
            skip: bool = True):
    """A chunk of ONE stream: ``ids [C]``, ``slot [1]``, ``start [1]``,
    ``count [1]`` (all int32); the first ``count`` ids are real.  Layers
    ``0 .. F - 1`` and layer ``F``'s K/V rows for every token of the
    chunk (rows ``[start, start + C)`` of the rings and the cache; the
    recurrent states, live and snapshot, left at ``start + count``
    tokens); layer ``F``'s attention and every layer above it for the
    chunk's LAST REAL token only, whose logits and greedy id are served:
    a cross layer at ``t`` needs the self-decoder at ``t`` and the cache
    up to ``t``, nothing of other positions.  ``skip=False`` runs every
    layer on every token and serves the same logits (tests)."""
    slot, start, count = slot[0], start[0], count[0]
    size, scale = ids.shape[0], cfg.head_dim ** -0.5
    hp = moe.precision(params["embed"])
    total = state["shared"]["k"].shape[2]
    upper = f"its attention and layers {cfg.full_layer + 1}-{cfg.layers - 1}"
    if skip:
        rows = (1, cfg.kv_pairs, total, cfg.pair_dim)
        refusal = kernels.gqa_decode_attention_refusal(
            (1, cfg.kv_pairs, cfg.rows, cfg.pair_dim), rows, rows, total)
        upper += " on 1 (attend_step on the stream's own rows: " + (
            f"the jnp mathematics ({refusal}))" if refusal else "the kernel)")
    else:
        upper += f" on {size}"
    _profile.note(f"phi4flash prefill: layers 0-{cfg.full_layer - 1} and "
                  f"layer {cfg.full_layer}'s K/V on {size} tokens, {upper}")

    def attend_ring(p, u, ring):
        q, k, v = _qkv(cfg, p, u)
        return attention.prefill(lambda positions: (q, k, v), size, ring,
                                 slot, start, cfg.window, hp, scale)

    x, mamba, rings, memory = _self_decoder(
        cfg, params, _embed(params, ids), state,
        lambda p, u, st: mamba1.mamba_prefill(cfg.mamba, p, u, st, slot,
                                              start, count),
        attend_ring)
    last = count - 1
    i = cfg.full_layer
    layer = params["layers"][i]
    with jax.named_scope(f"layer{i:02d}"):
        with jax.named_scope("attn_full"):
            u = _ln(x, layer["norm"], cfg.eps)
            with jax.named_scope("qkv"):
                k, v = _keys_values(cfg, layer["mixer"], u)
            shared = attention.write_chunk(
                k, v, state["shared"], slot,
                start + jnp.arange(size, dtype=jnp.int32), total)
            if skip:
                x, u, memory = (lax.dynamic_slice_in_dim(a, last, 1)
                                for a in (x, u, memory))
                # the stream's own rows, once for the eight readers
                one = {name: lax.dynamic_slice_in_dim(cache, slot, 1)
                       for name, cache in shared.items()}
                at = jnp.reshape(start + last, (1,))

                def attend(q):
                    return attention.attend_step(q, one, at, total, scale)
            else:
                def attend(q):
                    return attention.attend_chunk(q, shared, slot, start,
                                                  total, hp, scale)
            with jax.named_scope("qkv"):
                q = _queries(cfg, layer["mixer"], u)
            x = x + _differ(cfg, layer["mixer"], i, attend(q), x.dtype)
        x = _mlp(cfg, layer, x)
    x = _cross_decoder(cfg, params, x, memory, attend)
    if not skip:
        x = lax.dynamic_slice_in_dim(x, last, 1)
    logits, greedy = _head(cfg, params, x)
    with jax.named_scope("state"):
        # a padded chunk's rows lie on the rings too
        book = stream.book_prefilled(state, slot, start + count,
                                     newest=start + size - 1)
        new = dict(mamba=mamba, rings=rings, shared=shared, **book,
                   counters=_bumped(state["counters"], {
                       "prefill_tokens": count,
                       "cross_tokens": count if not skip else 1}))
    return new, (logits, greedy)


def decode(cfg: Phi4FlashConfig, params, state, ids, positions):
    """One token of EVERY stream: ``ids [B]``, ``positions [B]`` int32.
    Serves ``logits [B, vocab]`` float32 and the greedy ids.  A stream at
    its ``prompt_end`` starts its recurrent states from their snapshots
    (while the rings' newest row lies within their room); any other must
    be at ``last + 1``, or the step counts a position fault.  Layer
    ``F`` writes each stream's row of the shared cache; it and the cross
    layers read it."""
    scale = cfg.head_dim ** -0.5
    with jax.named_scope("state"):
        restore, fault, book = stream.book_step(state, positions,
                                                _room(cfg, state))
    with jax.named_scope("ssm_restore"):
        # mamba1.py keeps mamba2.py's names: the loop that copies the
        # restoring streams' snapshots over their live state serves both
        mamba = mamba2.restored(state["mamba"], restore)
    total = state["shared"]["k"].shape[2]

    def attend_ring(p, u, ring):
        q, k, v = _qkv(cfg, p, u)
        return attention.decode_step(q, k, v, ring, positions, cfg.window,
                                     scale)

    x, mamba, rings, memory = _self_decoder(
        cfg, params, _embed(params, ids), dict(state, mamba=mamba),
        lambda p, u, st: mamba1.mamba_decode(cfg.mamba, p, u, st),
        attend_ring)
    i = cfg.full_layer
    layer = params["layers"][i]
    with jax.named_scope(f"layer{i:02d}"):
        with jax.named_scope("attn_full"):
            u = _ln(x, layer["norm"], cfg.eps)
            q, k, v = _qkv(cfg, layer["mixer"], u)
            shared = attention.write_step(k, v, state["shared"], positions)

            def attend(q):
                return attention.attend_step(q, shared, positions, total,
                                             scale)

            x = x + _differ(cfg, layer["mixer"], i, attend(q), x.dtype)
        x = _mlp(cfg, layer, x)
    x = _cross_decoder(cfg, params, x, memory, attend)
    logits, greedy = _head(cfg, params, x)
    with jax.named_scope("state"):
        gained = {
            "steps": 1, "ssm_rows": ids.shape[0],
            "shared_rows_read": jnp.sum(positions + 1),
            "ring_rows_read": jnp.sum(jnp.minimum(positions + 1,
                                                  cfg.window)),
            "shared_rows_fetched": attention.decode_rows_fetched(
                [shared], cfg.rows, positions),
            "ring_rows_fetched": attention.decode_rows_fetched(
                rings, cfg.rows, positions, cfg.window),
            "restores": jnp.sum(restore),
            "position_faults": jnp.sum(fault)}
        new = dict(mamba=mamba, rings=rings, shared=shared, **book,
                   counters=_bumped(state["counters"], gained))
    return new, (logits, greedy)


# -- weights of the right shapes, and registration ----------------------------


def param_shapes(cfg: Phi4FlashConfig) -> dict:
    """The pytree of ``(shape, role)`` a weights maker fills.  A layer
    is ``{"norm", "mixer", "mlp_norm", "mlp"}``; what ``mixer`` holds
    follows the layer's kind.  ``q`` ``[hidden, kv pairs x query pairs a
    group x (q1 | q2) x head_dim]``, ``kv`` ``[hidden, (k | v) x kv
    pairs x pair_dim]``: the kernels' order."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    d = cfg.mamba.d_inner
    kv = 2 * cfg.kv_pairs * cfg.pair_dim

    def norm():
        return {"g": ((h,), "norm"), "b": ((h,), "norm_b")}

    def attn(own_kv: bool):
        out = {"q": ((h, h), "q"), "q_b": ((h,), "bias"),
               "o": ((h, h), "o"), "o_b": ((h,), "bias"),
               "subln": ((cfg.pair_dim,), "norm")}
        for name in ("lq1", "lk1", "lq2", "lk2"):
            out[name] = ((cfg.head_dim,), "lambda")
        if own_kv:
            out.update(kv=((h, kv), "kv"), kv_b=((kv,), "bias"))
        return out

    mixers = {"mamba": lambda: mamba1.param_shapes(cfg.mamba, h),
              "attn_window": lambda: attn(True),
              "attn_full": lambda: attn(True),
              "attn_cross": lambda: attn(False),
              "gmu": lambda: {"in": ((h, d), "gmu_in"),
                              "out": ((d, h), "gmu_out")}}
    return {"embed": ((cfg.vocab, h), "embed"),
            "layers": [{"norm": norm(), "mixer": mixers[cfg.kind(i)](),
                        "mlp_norm": norm(),
                        "mlp": {"gate_up": ((h, 2 * f), "gate_up"),
                                "down": ((f, h), "down")}}
                       for i in range(cfg.layers)],
            "final_norm": norm()}


def init_params(cfg: Phi4FlashConfig, key, dtype=None) -> Params:
    """Seeded weights of the right shapes (``models/streams.py``
    ``seeded_params``, ``models/mamba1.py`` ``seeded_laws``): matrices
    N(0, 1/fan_in) (residual branches halved), norm gains and ``D`` 1,
    biases and the four ``lambda`` vectors N(0, 0.1^2)."""
    special = dict(mamba1.seeded_laws(), **{
        role: stream.normal_vector(0.1)
        for role in ("norm_b", "bias", "lambda")})
    return stream.seeded_params(
        param_shapes(cfg), key, dtype, ones=("norm", "D"),
        halved=("o", "out_proj", "gmu_out", "down"), special=special)


def entries(cfg: Phi4FlashConfig, streams: int, positions: int,
            chunk: int) -> Dict[str, Any]:
    """What :func:`register` hands ``register_stateful_model``
    (``models/streams.py`` ``entries``, cached by these arguments): the
    two entry points with their input schemas, and ``init_state`` (the
    rings are sized by the prefill chunk)."""
    return stream.entries(
        cfg, decode, ((streams,), (streams,)),
        prefill, ((chunk,), (1,), (1,), (1,)), init_state, counter_units,
        streams=streams, positions=positions, chunk=chunk)


def register(name: str, cfg: Phi4FlashConfig, params: Params, streams: int,
             positions: int, chunk: int) -> str:
    """Register ``params`` as the stateful model ``name`` for
    ``tensor_filter framework=jax-xla model=<name>``: a filter whose
    negotiated input is ``(ids[chunk], slot[1], start[1], count[1])``
    prefills, one whose input is ``(ids[streams], positions[streams])``
    decodes; two filters with one ``shared-tensor-filter-key`` work on
    one state."""
    return stream.register(name, params,
                           entries(cfg, streams, positions, chunk))
