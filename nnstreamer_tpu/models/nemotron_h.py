"""Nemotron-H (``model_type`` ``nemotron_h``: NVIDIA-Nemotron-3-Nano) as
a stateful model of the element stream.

Written from the model's public ``config.json``: one stack whose layers
are chosen by the letters of ``hybrid_override_pattern``, each layer ONE
mixer behind a pre-norm and a residual add and no separate MLP:

``M``  Mamba-2 (``models/mamba2.py``, which ``falcon_h1.py`` runs too,
       has the equations): the projection ``[z | xBC | dt] = u W_in``,
       the causal depthwise convolution, the recurrence over
       ``mamba_num_heads`` heads in ``n_groups`` groups, the gated
       grouped norm, ``W_out``; no multiplier on its columns.
``E``  Mixture of experts: a sigmoid router over ALL the published
       experts, the choice taken from the scores plus a correction
       bias, the weights from the scores alone, normalised and scaled;
       UNGATED experts ``relu(u W_up)^2 W_down`` (``models/moe.py``,
       shared with ``deepseek_v2.py`` and ``smallthinker.py``) and one
       shared expert of the same form, added unweighted.  Which experts
       are HELD here is the share (``[expert0, expert0 + experts)``).
``*``  Grouped-query attention over every position, no rotary and no
       other positional signal: the state-space layers carry order.

**A state that no position addresses.**  An attention layer keeps a K
and a V row a position (``[streams, kv heads, positions, head_dim]``):
a row written too far is overwritten before it is read, so a padded
prefill chunk and a rewind to the prompt's end cost nothing there.  A
Mamba-2 layer keeps ONE array a stream (``ssm``) and the last
``conv_kernel - 1`` inputs of its convolution (``conv``), both
overwritten by every token (``models/mamba2.py`` has their shapes and
why).  So (``Documentation/stateful-models.md``):

* :func:`prefill` takes a fourth tensor, ``count``: the first ``count``
  tokens of the chunk are real.  A padded token gets ``delta = 0``,
  which is ``a = 1`` and no input (exact), and the convolution's state
  is taken at ``count``.
* beside each live state the filter keeps a SNAPSHOT of it at the
  stream's prompt end (``ssm_snap``, ``conv_snap``; ``prompt_end
  [streams]``): every prefill chunk leaves live state and snapshot alike
  at ``start + count`` tokens, and chunks arrive in order.
* :func:`decode` starts a stream whose position IS its ``prompt_end``
  from the snapshot (it answers anew from the resident prompt) and any
  other from its live state, which must be at ``last + 1``: a position
  that is neither cannot be served by a recurrent state and is counted
  (``position_faults``), not guessed at.  A step reads the snapshots of
  the streams that restore in it and of no other.

:func:`prefill` runs the recurrence chunked (``mamba2.mamba_prefill``),
:func:`decode` one step of it (``mamba2.mamba_decode``: where the
state's shape allows, ONE kernel a layer, ``ops/kernels.py``
``ssm_decode_step``; for every other shape the ``jnp`` step from the
live state behind ``mamba2.restored``, the loop that copies the
restoring streams' snapshots first).

Stage scopes (``Documentation/observability.md``): ``embed``, ``state``,
``ssm_restore`` (the loop that copies snapshots: empty where the step
is the kernel), ``layerNN/mamba``
(``.../in_proj``, ``.../conv``, ``.../scan`` or
``.../step``, ``.../gate_norm``, ``.../out_proj``), ``layerNN/attn``
(``.../cache_write``, ``.../gqa_decode_attention``),
``layerNN/moe/router|dispatch|experts|combine|shared``, ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover
    jax = jnp = lax = None

from . import attention, mamba2, moe
from . import streams as stream

Params = dict
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published sizes, and beside them what is HELD here:
    ``pattern`` (the leading layers' letters), ``experts`` and ``vocab``
    with their offsets.  Every width is the source's."""

    hidden_size: int
    pattern: str
    mamba_heads: int
    mamba_head_dim: int
    groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_width: int
    shared_width: int
    n_routed_experts: int          # the router's width (published)
    top_k: int
    routed_scaling_factor: float
    eps: float
    max_positions: int
    experts: int
    expert0: int
    vocab: int
    vocab0: int

    @classmethod
    def from_dict(cls, cfg: dict) -> "NemotronHConfig":
        """From a ``config.json`` as published, or from a chip's share
        of one: then ``num_hidden_layers`` (the pattern's leading
        letters), ``n_routed_experts`` and ``vocab_size`` count what is
        held, ``published`` gives the source's values (the router's
        width is ``published.n_routed_experts``) and ``share`` the
        offsets ``expert0`` / ``vocab0`` (0 where absent)."""
        published, share = cfg.get("published", {}), cfg.get("share", {})
        depth = int(cfg["num_hidden_layers"])
        pattern = str(cfg["hybrid_override_pattern"])
        if len(pattern) < depth or set(pattern) - set(KINDS):
            raise ValueError(f"nemotron_h: the pattern {pattern!r} does not "
                             f"give {depth} layers of M, E and *")
        if int(cfg.get("n_group", 1)) != 1 \
                or int(cfg.get("topk_group", 1)) != 1:
            raise ValueError("nemotron_h: a group-limited router is not "
                             "written")
        if cfg.get("mlp_hidden_act", "relu2") != "relu2" \
                or cfg.get("mamba_hidden_act", "silu") != "silu" \
                or not cfg.get("norm_topk_prob", True) \
                or cfg.get("tie_word_embeddings", False):
            raise ValueError("nemotron_h: only relu2 experts, a silu "
                             "Mamba-2, renormalised kept weights and an "
                             "untied head are written")
        if any(cfg.get(k, False) for k in ("attention_bias", "mlp_bias",
                                           "mamba_proj_bias", "use_bias")) \
                or not cfg.get("use_conv_bias", True):
            raise ValueError("nemotron_h: only the convolution has a bias")
        out = cls(
            hidden_size=int(cfg["hidden_size"]),
            pattern=pattern[:depth],
            mamba_heads=int(cfg["mamba_num_heads"]),
            mamba_head_dim=int(cfg["mamba_head_dim"]),
            groups=int(cfg["n_groups"]),
            state_size=int(cfg["ssm_state_size"]),
            conv_kernel=int(cfg["conv_kernel"]),
            chunk_size=int(cfg["chunk_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            shared_width=int(cfg["moe_shared_expert_intermediate_size"])
            * int(cfg.get("n_shared_experts", 1)),
            n_routed_experts=int(published.get("n_routed_experts",
                                               cfg["n_routed_experts"])),
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            eps=float(cfg["layer_norm_epsilon"]),
            max_positions=int(cfg["max_position_embeddings"]),
            experts=int(cfg["n_routed_experts"]),
            expert0=int(share.get("expert0", 0)),
            vocab=int(cfg["vocab_size"]),
            vocab0=int(share.get("vocab0", 0)))
        if out.heads % out.kv_heads or out.mamba_heads % out.groups \
                or out.expert0 + out.experts > out.n_routed_experts:
            raise ValueError(
                f"nemotron_h: {out.heads} query heads over {out.kv_heads} "
                f"key/value heads, {out.mamba_heads} Mamba-2 heads over "
                f"{out.groups} groups, experts [{out.expert0}, "
                f"{out.expert0 + out.experts}) of {out.n_routed_experts}")
        return out

    @property
    def layers(self) -> int:
        return len(self.pattern)

    @property
    def mamba(self) -> mamba2.Geometry:
        """What ``models/mamba2.py`` reads: an ``M`` layer's sizes, and
        no multiplier on the projection's columns."""
        return mamba2.Geometry(
            heads=self.mamba_heads, head_dim=self.mamba_head_dim,
            groups=self.groups, state_size=self.state_size,
            conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
            eps=self.eps)

    @property
    def per_group(self) -> int:
        """Query heads that read one key/value head."""
        return self.heads // self.kv_heads

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)


def _shared_expert(p, x):
    """One expert of the routed ones' form, dense: every token."""
    act, _gated = moe.activation("relu2")
    h = act(moe.mm(x, p["up"]))
    return moe.mm(h.astype(x.dtype), p["down"]).astype(x.dtype)


def moe_parts(cfg: NemotronHConfig, p, u):
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
        out = moe.grouped_experts(p["experts"], u, plan, "relu2")
    with jax.named_scope("combine"):
        routed = moe.combine(out, plan, weight)
    with jax.named_scope("shared"):
        shared = _shared_expert(p["shared"], u)
    return routed, shared, plan["counts"]


# -- attention ----------------------------------------------------------------


def _qkv(cfg: NemotronHConfig, p, u):
    """``(q [N, kv heads, heads a group, d], k [N, kv heads, d], v)``:
    query head ``i`` reads key/value head ``i // per_group``; nothing is
    rotated."""
    n, dt = u.shape[0], u.dtype
    q = moe.mm(u, p["q"]).astype(dt).reshape(
        n, cfg.kv_heads, cfg.per_group, cfg.head_dim)
    k = moe.mm(u, p["k"]).astype(dt).reshape(n, cfg.kv_heads, cfg.head_dim)
    v = moe.mm(u, p["v"]).astype(dt).reshape(n, cfg.kv_heads, cfg.head_dim)
    return q, k, v


def attn_prefill(cfg: NemotronHConfig, p, u, cache, slot, start):
    """A chunk ``u [C, hidden]`` of stream ``slot`` whose first token is
    at ``start``: ``models/attention.py`` ``prefill`` on this model's q,
    k and v (nothing is rotated), every position seen."""
    o, cache = attention.prefill(
        lambda _positions: _qkv(cfg, p, u), u.shape[0], cache, slot, start,
        cache["k"].shape[2], moe.precision(p["q"]))
    return attention.heads_out(p, o, u.dtype), cache


def attn_decode(cfg: NemotronHConfig, p, u, cache, positions):
    """One token of every stream: writes each stream's K and V row at
    its position, then attends over ``0 .. position``
    (``models/attention.py`` ``decode_step`` with the whole cache as its
    window)."""
    q, k, v = _qkv(cfg, p, u)
    o, cache = attention.decode_step(q, k, v, cache, positions,
                                     cache["k"].shape[2], cfg.head_dim ** -0.5)
    return attention.heads_out(p, o, u.dtype), cache


# -- the model ----------------------------------------------------------------


def _layers(cfg: NemotronHConfig, params, x, state, mamba, attend):
    """Every held layer on ``x [N, hidden]``: ``mamba(layer params,
    normed x, layer state) -> (output, layer state)`` and ``attend``
    likewise on a layer's cache.  Returns the stream, the layers' new
    states and the tokens each held expert of each ``E`` layer got."""
    old = {"mamba": iter(state["mamba"]), "cache": iter(state["cache"])}
    states = {"mamba": [], "cache": []}
    counts = []
    for i, (kind, layer) in enumerate(zip(cfg.pattern, params["layers"])):
        # a mixer's scope holds its norm and its residual add, so that the
        # fusions XLA roots there are booked to the mixer
        with jax.named_scope(f"layer{i:02d}"), \
                jax.named_scope(KINDS[kind]):
            u = moe.rms(x, layer["norm"], cfg.eps)
            if kind == "E":
                routed, shared, got = moe_parts(cfg, layer, u)
                counts.append(got)
                y = (routed + shared.astype(jnp.float32)).astype(x.dtype)
            else:
                run, which = (mamba, "mamba") if kind == "M" \
                    else (attend, "cache")
                y, new = run(layer, u, next(old[which]))
                states[which].append(new)
            x = x + y
    return x, states, jnp.stack(counts) if counts \
        else jnp.zeros((0, cfg.experts), jnp.int32)


def _embed(cfg: NemotronHConfig, params, ids):
    with jax.named_scope("embed"):
        return params["embed"][ids - cfg.vocab0]


def _head(cfg: NemotronHConfig, params, x):
    """Logits over the held rows of the vocabulary, float32, and the
    greedy id (global) beside them."""
    with jax.named_scope("head"):
        logits = moe.mm(moe.rms(x, params["final_norm"], cfg.eps),
                        params["head"])
        return logits, (jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        + cfg.vocab0)


COUNTERS = ("steps", "ssm_rows", "kv_rows_read", "kv_rows_fetched",
            "experts_touched", "expert_hits", "restores", "position_faults")


def init_state(cfg: NemotronHConfig, params, streams: int, positions: int,
               dtype=None) -> dict:
    """The state a filter owns between invokes.  Per ``M`` layer the
    recurrent state and the convolution's last inputs, live and as the
    snapshot at the stream's prompt end; per ``*`` layer a K and a V
    array of ``positions`` rows a stream; once, where each stream's
    prompt ends and the position it was last fed (-1: nothing yet); and
    the counters the steps add to (``uint32``: the reader takes
    differences, so a wrap costs nothing).  One buffer a leaf: the
    state is donated leaf by leaf."""
    dtype = dtype or params["embed"].dtype
    if positions > cfg.max_positions:
        raise ValueError(f"nemotron_h: {positions} positions, the model "
                         f"has {cfg.max_positions}")
    return {
        "mamba": [mamba2.init_state(cfg.mamba, streams, dtype)
                  for _ in range(cfg.count("M"))],
        "cache": [attention.kv_cache(streams, cfg.kv_heads, positions,
                                     cfg.head_dim, dtype)
                  for _ in range(cfg.count("*"))],
        **stream.book(streams), "counters": stream.zeros(COUNTERS)}


def counter_units(cfg: NemotronHConfig, state: dict) -> dict:
    """What the raw counters stand for in bytes.  ``ssm_rows`` counts
    the streams stepped in ONE ``M`` layer: a row is a stream's ``ssm``
    and ``conv``, read and written.  ``kv_rows_read`` counts the rows in
    use (``0 .. position``) of ONE ``*`` layer, ``kv_rows_fetched`` the
    rows the decode attention copies for them (``models/attention.py``
    ``decode_rows_fetched``): a row is a token's K and V."""
    out = {}
    if state["mamba"]:
        row = mamba2.state_row_bytes(state["mamba"][0])
        out["ssm_bytes"] = ("ssm_rows", 2 * row * len(state["mamba"]))
    if state["cache"]:
        k = state["cache"][0]["k"]
        row = 2 * cfg.kv_heads * cfg.head_dim * k.dtype.itemsize
        for did in ("read", "fetched"):
            kv = (f"kv_rows_{did}", row * len(state["cache"]))
            out.update({f"kv_bytes_{did}": kv, f"cache_bytes_{did}": kv})
    return out


def prefill(cfg: NemotronHConfig, params, state, ids, slot, start, count):
    """A chunk of ONE stream: ``ids [C]``, ``slot [1]``, ``start [1]``,
    ``count [1]`` (all int32); the first ``count`` ids are real.  Serves
    the logits and greedy id after the chunk's last real token.  Chunks
    of a stream arrive in order, so the last one leaves the snapshot at
    the prompt's end."""
    slot, start, count = slot[0], start[0], count[0]
    x = _embed(cfg, params, ids)
    x, states, _ = _layers(
        cfg, params, x, state,
        lambda p, u, st: mamba2.mamba_prefill(cfg.mamba, p, u, st, slot, start,
                                              count),
        lambda p, u, cache: attn_prefill(cfg, p, u, cache, slot, start))
    logits, greedy = _head(cfg, params,
                           lax.dynamic_slice_in_dim(x, count - 1, 1))
    with jax.named_scope("state"):
        new = dict(states, **stream.book_prefilled(state, slot, start + count),
                   counters=state["counters"])
    return new, (logits, greedy)


def decode(cfg: NemotronHConfig, params, state, ids, positions):
    """One token of EVERY stream: ``ids [B]``, ``positions [B]`` int32.
    Serves ``logits [B, vocab]`` float32 and the greedy ids.  A stream
    at its ``prompt_end`` starts from its snapshot; any other must be at
    ``last + 1``, or the step counts a position fault."""
    with jax.named_scope("state"):
        restore, fault, book = stream.book_step(state, positions)
    with jax.named_scope("ssm_restore"):
        # empty where the step is the kernel, which picks each stream's
        # source itself
        if any(mamba2.step_refusal(st) for st in state["mamba"]):
            state = dict(state,
                         mamba=mamba2.restored(state["mamba"], restore))
    x = _embed(cfg, params, ids)
    x, states, got = _layers(
        cfg, params, x, state,
        lambda p, u, st: mamba2.mamba_decode(cfg.mamba, p, u, st, restore),
        lambda p, u, cache: attn_decode(cfg, p, u, cache, positions))
    logits, greedy = _head(cfg, params, x)
    with jax.named_scope("state"):
        gained = {"steps": 1, "ssm_rows": ids.shape[0],
                  "kv_rows_read": jnp.sum(positions + 1),
                  "kv_rows_fetched": attention.decode_rows_fetched(
                      states["cache"], cfg.per_group, positions),
                  "experts_touched": jnp.sum(got > 0),
                  "expert_hits": jnp.sum(got),
                  "restores": jnp.sum(restore),
                  "position_faults": jnp.sum(fault)}
        new = dict(states, **book,
                   counters=stream.bump(state["counters"], gained))
    return new, (logits, greedy)


# -- weights of the right shapes, and registration ----------------------------


def param_shapes(cfg: NemotronHConfig) -> dict:
    """The pytree of ``(shape, role)`` a weights maker fills: matrices
    carry the role their init gain is looked up by, norm gains ``norm``,
    and the Mamba-2 layer's small vectors their own names."""
    h, f, e = cfg.hidden_size, cfg.expert_width, cfg.experts
    kinds = {
        "M": {"norm": ((h,), "norm"), **mamba2.param_shapes(cfg.mamba, h)},
        "E": {"norm": ((h,), "norm"),
              "router": ((h, cfg.n_routed_experts), "router"),
              "router_bias": ((cfg.n_routed_experts,), "router_bias"),
              "experts": {"up": ((e, h, f), "up"),
                          "down": ((e, f, h), "expert_down")},
              "shared": {"up": ((h, cfg.shared_width), "up"),
                         "down": ((cfg.shared_width, h), "down")}},
        "*": {"norm": ((h,), "norm"),
              "q": ((h, cfg.heads * cfg.head_dim), "q"),
              "k": ((h, cfg.kv_heads * cfg.head_dim), "k"),
              "v": ((h, cfg.kv_heads * cfg.head_dim), "v"),
              "o": ((cfg.heads * cfg.head_dim, h), "o")}}
    return {"embed": ((cfg.vocab, h), "embed"),
            "layers": [kinds[kind] for kind in cfg.pattern],
            "final_norm": ((h,), "norm"), "head": ((h, cfg.vocab), "head")}


def init_params(cfg: NemotronHConfig, key, dtype=None) -> Params:
    """Seeded weights of the right shapes (``models/streams.py``
    ``seeded_params``): matrices N(0, 1/fan_in) (residual branches
    halved), norm gains and ``D`` 1, ``delta`` at rest log-uniform in
    0.001-0.1 and ``exp(A_log)`` in 1-2 (a head remembers tens to a
    thousand tokens), a small router bias."""
    return stream.seeded_params(
        param_shapes(cfg), key, dtype, ones=("norm", "D"),
        halved=("o", "out_proj", "down", "expert_down"),
        special=dict(mamba2.seeded_laws(),
                     router_bias=stream.normal_vector(0.1)))


def entries(cfg: NemotronHConfig, streams: int, positions: int,
            chunk: int) -> Dict[str, Any]:
    """What :func:`register` hands ``register_stateful_model``
    (``models/streams.py`` ``entries``, cached by these arguments): the
    two entry points with their input schemas, and ``init_state``."""
    return stream.entries(
        cfg, decode, ((streams,), (streams,)),
        prefill, ((chunk,), (1,), (1,), (1,)), init_state, counter_units,
        streams=streams, positions=positions)


def register(name: str, cfg: NemotronHConfig, params: Params, streams: int,
             positions: int, chunk: int) -> str:
    """Register ``params`` as the stateful model ``name`` for
    ``tensor_filter framework=jax-xla model=<name>``: a filter whose
    negotiated input is ``(ids[chunk], slot[1], start[1], count[1])``
    prefills, one whose input is ``(ids[streams], positions[streams])``
    decodes; two filters with one ``shared-tensor-filter-key`` work on
    one state (recurrent states, their snapshots and the caches)."""
    return stream.register(name, params,
                           entries(cfg, streams, positions, chunk))
