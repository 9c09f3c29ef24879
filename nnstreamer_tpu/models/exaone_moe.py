"""EXAONE-MoE (``model_type`` ``exaone_moe``: K-EXAONE-236B-A23B) as a
stateful model of the element stream.

Written from the model's public ``config.json``: pre-norm residual
layers, RMSNorm, no biases; grouped-query attention whose q and k are
RMS-normalised per head (gains ``q_norm``, ``k_norm``); ``layer_types``
says which layers rotate q and k (``rope_theta``, no scaling, pairs
``(i, i + head_dim/2)``) and let position ``p`` see ``p - window + 1 ..
p`` (``sliding_attention``) and which carry no positional signal and
see ``0 .. p`` (``full_attention``); ``mlp_layer_types`` says which
layers have one SwiGLU MLP (``dense``) and which a sigmoid router over
ALL the published experts with a correction bias in the choice only,
SwiGLU experts (``models/moe.py``, shared with the other token models)
and one shared expert added unweighted (``sparse``).  Which experts and
which rows of the vocabulary are HELD here is the share.  Three things
set it apart from ``smallthinker.py`` and ``nemotron_h.py``:

**A ring shorter than a prefill chunk.**  A window layer keeps K and V
``[streams, kv heads, T, head_dim]`` with ``T = window + rewind`` (whole
cells of the decode kernel's walk), position ``p`` in slot ``p % T``:
the window and the longest REWIND a stream may make (to its prompt's
end, to answer again), not a prefill chunk.  So :func:`prefill` cannot
write a chunk into the ring and attend to it afterwards.  It attends to
the chunk's own K and V and the ``window`` rows before the chunk, read
from the ring first (in blocks of ``window`` queries on ``2 window``
keys), and THEN writes the chunk's last ``min(count, T)`` real rows: it
is told how many ids of a padded chunk are real (``count``), and
padding writes nothing.  A row newer than the decoded position ``p`` is
harmless while it is at most ``rewind`` positions ahead (it lies on the
slot of a position outside the window); so :func:`decode` serves a
position that is the stream's ``last + 1``, or its prompt's end if the
newest row the ring was ever given lies at most ``rewind`` ahead of it,
and counts any other (``position_faults``).  A full layer's cache holds
every position, never wraps and takes any rewind.

**A step that serves two logits tensors.**  The model carries one
multi-token-prediction module (``num_nextn_predict_layers`` 1,
DeepSeek-V3's form): for the token at position ``i`` it merges the main
model's last-layer stream ``x_L,i`` (before the final norm) with the
embedding of the NEXT token, ``u = W_eh [rms(Emb(t_{i+1})) ;
rms(x_L,i)]``, runs one more sparse full-attention layer on ``u`` with
a cache of its own, and predicts ``t_{i+2}`` through the shared head
behind a norm of its own.  So both entry points take the shifted ids
beside the ids and serve ``(logits, logits_mtp, greedy, greedy_mtp)``.
The ids are fed, not sampled: a verify step that advances a stream by
what was accepted is not written.

**A third group of state**, the module's cache, written from another
input (``u``) than the layers' caches and at the same positions.

:func:`decode` attends through ``ops/kernels.py``
``gqa_decode_attention`` where it takes the shapes (its ``jnp``
reference where it refuses them: a toy head size, a toy ring).

Stage scopes (``Documentation/observability.md``): ``embed``, ``state``,
``layerNN/attn_window`` or ``layerNN/attn_full`` (``.../cache_write``
and ``.../gqa_decode_attention`` inside), ``layerNN/mlp`` or
``layerNN/moe/router|dispatch|experts|combine|shared``, ``head``, and
``mtp/merge``, ``mtp/attn_full/...``, ``mtp/moe/...``, ``mtp/head``.
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
#: rows of one cell of the decode kernel's walk (``ops/kernels.py``
#: refuses a cache that is not whole cells)
CELL = 128


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """The published sizes, and beside them what is HELD here: the
    leading ``layers`` (with their entries of the two layouts),
    ``experts`` and ``vocab`` with their offsets.  Every width is the
    source's."""

    hidden_size: int
    heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    expert_width: int
    shared_width: int
    n_routed_experts: int          # the router's width (published)
    top_k: int
    routed_scaling_factor: float
    window: int
    rope_theta: float
    eps: float
    max_positions: int
    experts: int
    expert0: int
    vocab: int
    vocab0: int
    window_layers: Tuple[bool, ...]      # per held layer: a ring, rotary
    dense_layers: Tuple[bool, ...]       # per held layer: one MLP, no router
    mtp: bool                            # the prediction module is held

    @classmethod
    def from_dict(cls, cfg: dict) -> "ExaoneMoeConfig":
        """From a ``config.json`` as published, or from a chip's share
        of one: then ``num_hidden_layers`` (the layouts keep their
        published length; the leading entries are used), ``num_experts``
        and ``vocab_size`` count what is held, ``published`` gives the
        source's values (the router's width is
        ``published.num_experts``) and ``share`` the offsets
        ``expert0`` / ``vocab0`` (0 where absent)."""
        published, share = cfg.get("published", {}), cfg.get("share", {})
        depth = int(cfg["num_hidden_layers"])
        kinds, mlps = cfg["layer_types"], cfg["mlp_layer_types"]
        if len(kinds) < depth or len(mlps) < depth \
                or set(kinds) - {"sliding_attention", "full_attention"} \
                or set(mlps) - {"dense", "sparse"}:
            raise ValueError("exaone_moe: layer_types and mlp_layer_types "
                             f"do not give {depth} layers of sliding or "
                             "full attention and dense or sparse MLPs")
        if int(cfg.get("n_group", 1)) != 1 \
                or int(cfg.get("topk_group", 1)) != 1:
            raise ValueError("exaone_moe: a group-limited router is not "
                             "written")
        if cfg.get("hidden_act", "silu") != "silu" \
                or cfg.get("scoring_func", "sigmoid") != "sigmoid" \
                or not cfg.get("norm_topk_prob", True) \
                or cfg.get("tie_word_embeddings", False):
            raise ValueError("exaone_moe: only silu-gated experts behind a "
                             "sigmoid router whose kept weights are "
                             "renormalised, and an untied head, are written")
        rope = cfg.get("rope_parameters", {})
        if rope.get("rope_type", "default") != "default":
            raise ValueError("exaone_moe: rope scaling is not written")
        nextn = int(cfg.get("num_nextn_predict_layers", 0))
        if nextn > 1 or (nextn and list(cfg.get("mtp_layer_types", []))
                         != ["full_attention"]):
            raise ValueError("exaone_moe: only one prediction module, of "
                             "full attention, is written")
        out = cls(
            hidden_size=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            dense_width=int(cfg["intermediate_size"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            shared_width=int(cfg["moe_intermediate_size"])
            * int(cfg.get("num_shared_experts", 1)),
            n_routed_experts=int(published.get("num_experts",
                                               cfg["num_experts"])),
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            window=int(cfg["sliding_window"]),
            rope_theta=float(rope.get("rope_theta", 10000.0)),
            eps=float(cfg["rms_norm_eps"]),
            max_positions=int(cfg["max_position_embeddings"]),
            experts=int(cfg["num_experts"]),
            expert0=int(share.get("expert0", 0)),
            vocab=int(cfg["vocab_size"]),
            vocab0=int(share.get("vocab0", 0)),
            window_layers=tuple(k == "sliding_attention"
                                for k in kinds[:depth]),
            dense_layers=tuple(m == "dense" for m in mlps[:depth]),
            mtp=bool(nextn))
        if out.heads % out.kv_heads or out.head_dim % 2 \
                or out.expert0 + out.experts > out.n_routed_experts:
            raise ValueError(
                f"exaone_moe: {out.heads} query heads over {out.kv_heads} "
                f"key/value heads of {out.head_dim}, experts "
                f"[{out.expert0}, {out.expert0 + out.experts}) of "
                f"{out.n_routed_experts}")
        return out

    @property
    def layers(self) -> int:
        return len(self.window_layers)

    @property
    def per_group(self) -> int:
        """Query heads that read one key/value head."""
        return self.heads // self.kv_heads

    @property
    def row_values(self) -> int:
        """Values a token keeps in a layer's cache: its K and its V."""
        return 2 * self.kv_heads * self.head_dim

    def ring(self, rewind: int) -> int:
        """Positions of a window layer's ring that takes rewinds of up
        to ``rewind`` positions: window + rewind in whole cells (whole
        windows where a toy's window is shorter than a cell)."""
        cell = min(CELL, self.window)
        return -(-(self.window + int(rewind)) // cell) * cell


# -- small parts --------------------------------------------------------------


def _swiglu(p, x):
    """One gated MLP, dense: ``(silu(x W_gate) * (x W_up)) W_down``."""
    h = jax.nn.silu(moe.mm(x, p["gate"])) * moe.mm(x, p["up"])
    return moe.mm(h.astype(x.dtype), p["down"]).astype(x.dtype)


def moe_parts(cfg: ExaoneMoeConfig, p, u):
    """``(routed, shared, counts)``: the held experts' weighted part for
    the tokens routed to them (float32), the shared expert (what every
    chip computes alike), and how many tokens each held expert got."""
    n = u.shape[0]
    with jax.named_scope("router"):
        idx, weight = moe.route_sigmoid(u, p["router"], p["router_bias"],
                                        cfg.top_k, cfg.routed_scaling_factor)
    with jax.named_scope("dispatch"):
        plan = moe.dispatch(idx, n, cfg.expert0, cfg.experts,
                            cfg.n_routed_experts)
    with jax.named_scope("experts"):
        out = moe.grouped_experts(p["experts"], u, plan, "silu")
    with jax.named_scope("combine"):
        routed = moe.combine(out, plan, weight)
    with jax.named_scope("shared"):
        shared = _swiglu(p["shared"], u)
    return routed, shared, plan["counts"]


def _qkv(cfg: ExaoneMoeConfig, p, h, positions, rotary: bool):
    """``(q [N, kv heads, heads a group, d], k [N, kv heads, d], v)``:
    query head ``i`` reads key/value head ``i // per_group``; q and k
    normalised per head, and rotated where ``rotary``."""
    n, dt = h.shape[0], h.dtype
    q = moe.rms(moe.mm(h, p["q"]).astype(dt).reshape(
        n, cfg.kv_heads, cfg.per_group, cfg.head_dim), p["q_norm"], cfg.eps)
    k = moe.rms(moe.mm(h, p["k"]).astype(dt).reshape(
        n, cfg.kv_heads, cfg.head_dim), p["k_norm"], cfg.eps)
    v = moe.mm(h, p["v"]).astype(dt).reshape(n, cfg.kv_heads, cfg.head_dim)
    if rotary:
        cos, sin = attention.rope_angles(cfg.rope_theta, cfg.head_dim,
                                         positions)
        q = attention.rope(q, cos[:, None, None], sin[:, None, None])
        k = attention.rope(k, cos[:, None], sin[:, None])
    return q, k, v


# -- attention ----------------------------------------------------------------


def window_prefill(cfg: ExaoneMoeConfig, p, h, cache, slot, start, count):
    """A chunk ``h [C, hidden]`` (whole windows) of stream ``slot``
    whose first token is at ``start`` and whose first ``count`` tokens
    are real, on a RING that may be shorter than the chunk.  Query block
    ``j`` (``window`` positions) sees the ``2 window`` keys that end
    with its own: the chunk's own K and V behind the ``window`` rows
    before the chunk, which are read from the ring before anything is
    written.  Then the chunk's last ``min(count, T)`` real rows are
    written, a padded row never."""
    c, w = h.shape[0], cfg.window
    total, dt = cache["k"].shape[2], h.dtype
    positions = start + jnp.arange(c, dtype=jnp.int32)
    q, k, v = _qkv(cfg, p, h, positions, True)
    before = (start - w + jnp.arange(w, dtype=jnp.int32)) % total
    hp = moe.precision(p["q"])

    def blocks(name, new):
        """``[C / w, 2 w, kv heads, d]``: block ``j``'s keys."""
        ext = jnp.concatenate([
            cache[name][slot][:, before].transpose(1, 0, 2).astype(dt), new])
        ext = ext.reshape(c // w + 1, w, cfg.kv_heads, cfg.head_dim)
        return jnp.concatenate([ext[:-1], ext[1:]], axis=1)

    s = jnp.einsum("jrgqd,jtgd->jgqrt",
                   q.reshape(c // w, w, cfg.kv_heads, cfg.per_group,
                             cfg.head_dim), blocks("k", k),
                   preferred_element_type=jnp.float32, precision=hp)
    # key t of a block lies w - t positions before the block's first query
    r = jnp.arange(w, dtype=jnp.int32)[:, None]
    t = jnp.arange(2 * w, dtype=jnp.int32)[None, :]
    first = (positions[::w] - w)[:, None, None]              # of block j
    seen = (t > r) & (t <= r + w) & (first + t >= 0)
    prob = jax.nn.softmax(
        jnp.where(seen[:, None, None], s * cfg.head_dim ** -0.5,
                  attention.NEG), axis=-1)
    o = jnp.einsum("jgqrt,jtgd->jrgqd", prob.astype(dt), blocks("v", v),
                   preferred_element_type=jnp.float32, precision=hp)
    with jax.named_scope("cache_write"):
        row = jnp.arange(c, dtype=jnp.int32)
        at = jnp.where((row < count) & (row >= count - total),
                       positions % total, total)          # total: dropped
        cache = {"k": cache["k"].at[slot, :, at].set(
                     k.astype(cache["k"].dtype), mode="drop"),
                 "v": cache["v"].at[slot, :, at].set(
                     v.astype(cache["v"].dtype), mode="drop")}
    return attention.heads_out(
        p, o.reshape(c, cfg.kv_heads, cfg.per_group, cfg.head_dim), dt), cache


def full_prefill(cfg: ExaoneMoeConfig, p, h, cache, slot, start):
    """A chunk of stream ``slot`` on a cache of every position
    (``models/attention.py`` ``prefill``, every position seen): nothing
    is rotated."""
    o, cache = attention.prefill(
        lambda positions: _qkv(cfg, p, h, positions, False), h.shape[0],
        cache, slot, start, cache["k"].shape[2], moe.precision(p["q"]))
    return attention.heads_out(p, o, h.dtype), cache


def attn_decode(cfg: ExaoneMoeConfig, ring: bool, p, h, cache, positions):
    """One token of every stream: ``h [B, hidden]``, stream ``b`` at
    ``positions[b]``.  Writes each stream's K and V row (slot ``position
    % T``), then attends over the slots that hold a position it sees
    (``models/attention.py`` ``decode_step``)."""
    q, k, v = _qkv(cfg, p, h, positions, ring)
    o, cache = attention.decode_step(
        q, k, v, cache, positions,
        cfg.window if ring else cache["k"].shape[2], cfg.head_dim ** -0.5)
    return attention.heads_out(p, o, h.dtype), cache


# -- the model ----------------------------------------------------------------


def _block(cfg: ExaoneMoeConfig, layer, x, cache, ring: bool, attend):
    """One layer on ``x [N, hidden]``: attention behind its norm, then
    the layer's MLP (``mlp``) or experts (``moe``) behind theirs.
    ``attend(ring, attention params, normed x, cache) -> (output,
    cache)``.  Returns the stream, the cache and the tokens each held
    expert got (None for a dense layer)."""
    # a branch's scope holds its norm and its residual add, so that the
    # fusions XLA roots there are booked to the branch
    with jax.named_scope("attn_window" if ring else "attn_full"):
        a, cache = attend(ring, layer["attn"],
                          moe.rms(x, layer["attn_norm"], cfg.eps), cache)
        x = x + a
    if "mlp" in layer:
        with jax.named_scope("mlp"):
            return x + _swiglu(layer["mlp"], moe.rms(
                x, layer["ffn_norm"], cfg.eps)), cache, None
    with jax.named_scope("moe"):
        routed, shared, got = moe_parts(
            cfg, layer["moe"], moe.rms(x, layer["ffn_norm"], cfg.eps))
        return x + (routed + shared.astype(jnp.float32)).astype(x.dtype), \
            cache, got


def _forward(cfg: ExaoneMoeConfig, params, state, ids, next_ids, attend,
             served):
    """Both entry points' forward: every held layer on the embedded
    ``ids``, the head on the rows ``served`` picks, and the prediction
    module on the last-layer stream and the embedded ``next_ids``.
    Returns the new caches, the four served tensors and the tokens each
    held expert got (``[sparse layers, experts]`` of the main stack,
    ``[experts]`` of the module)."""
    with jax.named_scope("embed"):
        x = params["embed"][ids - cfg.vocab0]
    caches, counts = list(state["cache"]), []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{i:02d}"):
            x, caches[i], got = _block(cfg, layer, x, caches[i],
                                       cfg.window_layers[i], attend)
        if got is not None:
            counts.append(got)
    new = {"cache": caches}

    def head(x, norm):
        logits = moe.mm(moe.rms(served(x), norm, cfg.eps), params["head"])
        return logits, (jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        + cfg.vocab0)

    with jax.named_scope("head"):
        logits, greedy = head(x, params["final_norm"])
    out, got_mtp = (logits, greedy), jnp.zeros((cfg.experts,), jnp.int32)
    if cfg.mtp:
        m = params["mtp"]
        with jax.named_scope("mtp"):
            with jax.named_scope("merge"):
                e = moe.rms(params["embed"][next_ids - cfg.vocab0],
                            m["embed_norm"], cfg.eps)
                u = moe.mm(jnp.concatenate(
                    [e, moe.rms(x, m["hidden_norm"], cfg.eps)], axis=-1),
                    m["eh_proj"]).astype(x.dtype)
            u, new["mtp"], got_mtp = _block(cfg, m["layer"], u, state["mtp"],
                                            False, attend)
            with jax.named_scope("head"):
                logits_mtp, greedy_mtp = head(u, m["final_norm"])
        out = (logits, logits_mtp, greedy, greedy_mtp)
    counts = jnp.stack(counts) if counts \
        else jnp.zeros((0, cfg.experts), jnp.int32)
    return new, out, (counts, got_mtp)


COUNTERS = ("steps", "window_rows_read", "window_rows_fetched",
            "full_rows_read", "full_rows_fetched", "mtp_rows_read",
            "experts_touched", "expert_hits", "mtp_experts_touched",
            "position_faults")


def init_state(cfg: ExaoneMoeConfig, params, streams: int, positions: int,
               rewind: int, dtype=None) -> dict:
    """The state a filter owns between invokes.  Per layer a K and a V
    array: a ring of ``cfg.ring(rewind)`` positions in a window layer,
    ``positions`` in a full one; the prediction module's cache
    (``positions``); once, where each stream's prompt ends, the position
    it was last fed (-1: nothing yet) and the newest one its rings were
    ever given; and the counters the steps add to (``uint32``: the
    reader takes differences, so a wrap costs nothing).  One buffer a
    leaf: the state is donated leaf by leaf."""
    dtype = dtype or params["embed"].dtype
    if positions > cfg.max_positions:
        raise ValueError(f"exaone_moe: {positions} positions, the model "
                         f"has {cfg.max_positions}")

    def kv(total):
        return attention.kv_cache(streams, cfg.kv_heads, total, cfg.head_dim,
                                  dtype)

    state = {"cache": [kv(cfg.ring(rewind) if ring else positions)
                       for ring in cfg.window_layers],
             **stream.book(streams, newest=True),
             "counters": stream.zeros(COUNTERS)}
    if cfg.mtp:
        state["mtp"] = kv(positions)
    return state


def counter_units(cfg: ExaoneMoeConfig, state: dict) -> dict:
    """What the raw counters stand for in bytes.  ``window_rows_read``,
    ``full_rows_read`` and ``mtp_rows_read`` count the rows IN USE of
    ONE cache of their kind (a stream past the window uses ``window``
    rows of a ring, whatever the ring holds), ``*_rows_fetched`` the
    rows the decode attention reads in for them (``models/attention.py``
    ``decode_rows_fetched``; the module's cache is fetched as a full
    layer's); a row is a token's K and V.  ``cache_bytes_*`` is the sum
    of the three kinds."""
    row = cfg.row_values * state["cache"][0]["k"].dtype.itemsize
    rings = sum(cfg.window_layers)
    out = {}
    for did in ("read", "fetched"):
        window = (f"window_rows_{did}", row * rings)
        full = (f"full_rows_{did}", row * (cfg.layers - rings))
        mtp = ("mtp_rows_read" if did == "read" else "full_rows_fetched",
               row * int(cfg.mtp))
        out.update({f"window_bytes_{did}": window, f"full_bytes_{did}": full,
                    f"mtp_bytes_{did}": mtp,
                    f"cache_bytes_{did}": [window, full, mtp]})
    return out


def prefill(cfg: ExaoneMoeConfig, params, state, ids, next_ids, slot, start,
            count):
    """A chunk of ONE stream: ``ids [C]``, ``next_ids [C]`` (the id that
    follows each; after the prompt's last, the answer's first), ``slot
    [1]``, ``start [1]``, ``count [1]`` (all int32); the first ``count``
    ids are real.  Serves both logits tensors and greedy ids after the
    chunk's last real token.  Chunks of a stream arrive in order."""
    slot, start, count = slot[0], start[0], count[0]

    def attend(ring, p, h, cache):
        if ring:
            return window_prefill(cfg, p, h, cache, slot, start, count)
        return full_prefill(cfg, p, h, cache, slot, start)

    new, out, _ = _forward(
        cfg, params, state, ids, next_ids, attend,
        lambda x: lax.dynamic_slice_in_dim(x, count - 1, 1))
    with jax.named_scope("state"):
        new.update(stream.book_prefilled(state, slot, start + count),
                   counters=state["counters"])
    return new, out


def decode(cfg: ExaoneMoeConfig, params, state, ids, next_ids, positions):
    """One token of EVERY stream: ``ids [B]``, ``next_ids [B]`` (the id
    that follows each), ``positions [B]`` int32.  Serves ``logits [B,
    vocab]`` and ``logits_mtp [B, vocab]`` float32 (the token after, and
    the one after that) and their greedy ids.  A stream is served at
    ``last + 1``, or at its prompt's end while its rings' newest row
    lies no further ahead than they were sized for; any other position
    is counted as a fault."""
    with jax.named_scope("state"):
        rings = [c["k"].shape[2] for c, ring
                 in zip(state["cache"], cfg.window_layers) if ring]
        room = min(rings) - cfg.window if rings else cfg.max_positions
        _, fault, book = stream.book_step(state, positions, room)
    new, out, (got, got_mtp) = _forward(
        cfg, params, state, ids, next_ids,
        lambda ring, p, h, cache: attn_decode(cfg, ring, p, h, cache,
                                              positions),
        lambda x: x)
    with jax.named_scope("state"):
        rows = positions + 1
        kinds = list(zip(new["cache"], cfg.window_layers))
        rings = [c for c, ring in kinds if ring]
        # the module's cache is a full layer's
        fulls = [c for c, ring in kinds if not ring] \
            + ([new["mtp"]] if cfg.mtp else [])
        gained = {"steps": 1,
                  "window_rows_read": jnp.sum(jnp.minimum(rows, cfg.window)),
                  "window_rows_fetched": attention.decode_rows_fetched(
                      rings, cfg.per_group, positions, cfg.window),
                  "full_rows_read": jnp.sum(rows),
                  "full_rows_fetched": attention.decode_rows_fetched(
                      fulls, cfg.per_group, positions),
                  "mtp_rows_read": jnp.sum(rows),
                  "experts_touched": jnp.sum(got > 0) + jnp.sum(got_mtp > 0),
                  "expert_hits": jnp.sum(got) + jnp.sum(got_mtp),
                  "mtp_experts_touched": jnp.sum(got_mtp > 0),
                  "position_faults": jnp.sum(fault)}
        new.update(book, counters=stream.bump(state["counters"], gained))
    return new, out


# -- weights of the right shapes, and registration ----------------------------


def param_shapes(cfg: ExaoneMoeConfig) -> dict:
    """The pytree of ``(shape, role)`` a weights maker fills: matrices
    carry the role their init gain is looked up by, norm gains ``norm``
    and the per-head gains of q and k ``qk_norm``."""
    h, d, f, e = cfg.hidden_size, cfg.head_dim, cfg.expert_width, cfg.experts

    def mlp(width, down="down"):
        return {"gate": ((h, width), "gate"), "up": ((h, width), "up"),
                "down": ((width, h), down)}

    def layer(dense: bool):
        out = {"attn_norm": ((h,), "norm"), "ffn_norm": ((h,), "norm"),
               "attn": {"q": ((h, cfg.heads * d), "q"),
                        "k": ((h, cfg.kv_heads * d), "k"),
                        "v": ((h, cfg.kv_heads * d), "v"),
                        "o": ((cfg.heads * d, h), "o"),
                        "q_norm": ((d,), "qk_norm"),
                        "k_norm": ((d,), "qk_norm")}}
        if dense:
            out["mlp"] = mlp(cfg.dense_width)
        else:
            out["moe"] = {
                "router": ((h, cfg.n_routed_experts), "router"),
                "router_bias": ((cfg.n_routed_experts,), "router_bias"),
                "experts": {"gate": ((e, h, f), "gate"),
                            "up": ((e, h, f), "up"),
                            "down": ((e, f, h), "expert_down")},
                "shared": mlp(cfg.shared_width)}
        return out

    shapes = {"embed": ((cfg.vocab, h), "embed"),
              "layers": [layer(dense) for dense in cfg.dense_layers],
              "final_norm": ((h,), "norm"), "head": ((h, cfg.vocab), "head")}
    if cfg.mtp:
        shapes["mtp"] = {"embed_norm": ((h,), "norm"),
                         "hidden_norm": ((h,), "norm"),
                         "eh_proj": ((2 * h, h), "eh_proj"),
                         "layer": layer(False),
                         "final_norm": ((h,), "norm")}
    return shapes


def init_params(cfg: ExaoneMoeConfig, key, dtype=None) -> Params:
    """Seeded weights of the right shapes (``models/streams.py``
    ``seeded_params``): matrices N(0, 1/fan_in) (residual branches
    halved), norm gains 1, a small router bias."""
    return stream.seeded_params(
        param_shapes(cfg), key, dtype, ones=("norm", "qk_norm"),
        special={"router_bias": stream.normal_vector(0.1)})


def entries(cfg: ExaoneMoeConfig, streams: int, positions: int, chunk: int,
            rewind: int) -> Dict[str, Any]:
    """What :func:`register` hands ``register_stateful_model``
    (``models/streams.py`` ``entries``, cached by these arguments): the
    two entry points with their input schemas, and ``init_state``."""
    if any(cfg.window_layers) and chunk % cfg.window:
        raise ValueError(f"exaone_moe: a prefill chunk of {chunk} tokens "
                         f"is not whole windows of {cfg.window}")
    return stream.entries(
        cfg, decode, ((streams,),) * 3,
        prefill, ((chunk,), (chunk,), (1,), (1,), (1,)), init_state,
        counter_units, streams=streams, positions=positions, rewind=rewind)


def register(name: str, cfg: ExaoneMoeConfig, params: Params, streams: int,
             positions: int, chunk: int, rewind: int) -> str:
    """Register ``params`` as the stateful model ``name`` for
    ``tensor_filter framework=jax-xla model=<name>``: a filter whose
    negotiated input is ``(ids[chunk], next_ids[chunk], slot[1],
    start[1], count[1])`` prefills, one whose input is ``(ids[streams],
    next_ids[streams], positions[streams])`` decodes; two filters with
    one ``shared-tensor-filter-key`` work on one state (the rings, the
    full caches and the prediction module's cache).  ``rewind`` is the
    longest way back to its prompt's end a stream may be sent."""
    return stream.register(
        name, params, entries(cfg, streams, positions, chunk, rewind))
