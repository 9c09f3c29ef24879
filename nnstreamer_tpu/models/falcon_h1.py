"""Falcon-H1 (``model_type`` ``falcon_h1``: Falcon-H1-34B-Instruct) as a
stateful model of the element stream: a hybrid in which EVERY layer runs
a Mamba-2 mixer and a grouped-query attention side by side on one normed
input, then a dense SwiGLU MLP, under muP multipliers on every branch.

Written from the model's public ``config.json``, the Falcon-H1 technical
report (arXiv:2507.22448) and the ``falcon_h1`` model of the
``transformers`` library.  RMSNorm with float32 statistics, no biases
but the convolution's::

    x0 = m_emb * Embed[id]
    u  = rms(x; g_in)
    x  = x + m_so * Mamba2(m_si * u) + m_ao * Attn(m_ai * u)
    w  = rms(x; g_ff)
    x  = x + m_d * W_down( silu(m_g * W_gate w) * W_up w )
    logits = m_head * W_head rms(x_last; g_f)            (float32)

``Mamba2`` is ``models/mamba2.py`` (which ``nemotron_h.py`` runs too) at
this model's geometry (``mamba_d_ssm`` given outright: heads x head
size) with ``ssm_multipliers`` on the input projection's columns ``z | x
| B | C | dt``.  ``Attn``: ``q = rope(W_q z)``, ``k = rope(m_k W_k z)``,
``v = W_v z``; query head ``h`` reads key/value head ``h // (heads / kv
heads)``; rotary over the whole head (pairs ``(i, i + head_dim/2)``,
``rope_theta``, no scaling); scores scaled by ``head_dim^-1/2``, causal
over every position, softmax in float32; ``W_o`` on the heads side by
side.  Every multiplier is an operation where the source applies it;
none is folded into a matrix (``attention_in_multiplier`` 1 is skipped
as exact).

**The cut.**  :class:`FalconH1Config` holds the published sizes and,
beside them, what is held: ``layers`` (the model's leading ones) and
vocabulary rows ``[vocab0, vocab0 + vocab)``.  No layer is divided:
every head, group, width and multiplier is the source's, so a stage
passes the stream on and no partial sum.

**A layer that owns both kinds of state**
(``Documentation/stateful-models.md``).  ``state["layers"][l]`` is
``{"conv", "conv_snap", "ssm", "ssm_snap", "k", "v"}``: the recurrent
state and the convolution's last inputs, which no position addresses
(each live and as the snapshot at the stream's prompt end), and a K and
a V row a position.  One prefill chunk writes both and one decode step
reads both:

* :func:`prefill` takes ``(ids, slot, start, count)``.  The recurrent
  state obeys ``count`` (a padded token is ``delta = 0``; the
  convolution's state is taken at ``count``); the cache obeys positions
  (a padded token's row lies beyond the prompt and is overwritten by the
  answer before any step reads it).
* :func:`decode` takes ``(ids, positions)``.  The cache is masked by
  ``positions``; the recurrent state obeys the book
  (``models/streams.py`` ``book_step``): a stream at its ``prompt_end``
  starts from the snapshot, any other from its live state at ``last +
  1``, else the step counts a ``position_fault``.

Stage scopes (``Documentation/observability.md``): ``embed``, ``state``,
``ssm_restore`` (empty where the step is the kernel), ``layerNN/norm``,
``layerNN/mamba/in_proj|conv|scan|step|gate_norm|out_proj``,
``layerNN/attn/qkv|cache_write|gqa_decode_attention|o``, ``layerNN/mix``
(the two multipliers and the add), ``layerNN/mlp``, ``head``.
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

from . import attention, mamba2, moe
from . import streams as stream

Params = dict


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published sizes and multipliers, and beside them what is HELD
    here: ``layers`` and ``vocab`` (with its offset).  Every width is
    the source's."""

    hidden_size: int
    intermediate_size: int
    heads: int
    kv_heads: int
    head_dim: int
    mamba_heads: int
    mamba_head_dim: int
    groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    eps: float
    rope_theta: float
    max_positions: int
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: Tuple[float, float, float, float, float]
    mlp_multipliers: Tuple[float, float]           # (gate, down)
    layers: int
    vocab: int
    vocab0: int

    @classmethod
    def from_dict(cls, cfg: dict) -> "FalconH1Config":
        """From a ``config.json`` as published, or from a stage's cut of
        one: then ``num_hidden_layers`` and ``vocab_size`` count what is
        held and ``share`` gives the offset ``vocab0`` (0 where
        absent)."""
        share = cfg.get("share", {})
        if cfg.get("hidden_act", "silu") != "silu" \
                or cfg.get("mamba_norm_before_gate", False) \
                or not cfg.get("mamba_rms_norm", True) \
                or cfg.get("rope_scaling") is not None \
                or cfg.get("attn_layer_indices") is not None \
                or cfg.get("tie_word_embeddings", False):
            raise ValueError("falcon_h1: only a silu MLP, a gate before a "
                             "grouped RMSNorm, an unscaled rotation, "
                             "attention in every layer and an untied head "
                             "are written")
        if any(cfg.get(k, False) for k in ("attention_bias", "mlp_bias",
                                           "mamba_proj_bias",
                                           "projectors_bias")) \
                or not cfg.get("mamba_conv_bias", True):
            raise ValueError("falcon_h1: only the convolution has a bias")
        out = cls(
            hidden_size=int(cfg["hidden_size"]),
            intermediate_size=int(cfg["intermediate_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            mamba_heads=int(cfg["mamba_n_heads"]),
            mamba_head_dim=int(cfg["mamba_d_head"]),
            groups=int(cfg["mamba_n_groups"]),
            state_size=int(cfg["mamba_d_state"]),
            conv_kernel=int(cfg["mamba_d_conv"]),
            chunk_size=int(cfg["mamba_chunk_size"]),
            eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            max_positions=int(cfg["max_position_embeddings"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            lm_head_multiplier=float(cfg["lm_head_multiplier"]),
            attention_in_multiplier=float(cfg["attention_in_multiplier"]),
            attention_out_multiplier=float(cfg["attention_out_multiplier"]),
            key_multiplier=float(cfg["key_multiplier"]),
            ssm_in_multiplier=float(cfg["ssm_in_multiplier"]),
            ssm_out_multiplier=float(cfg["ssm_out_multiplier"]),
            ssm_multipliers=tuple(float(v) for v in cfg["ssm_multipliers"]),
            mlp_multipliers=tuple(float(v) for v in cfg["mlp_multipliers"]),
            layers=int(cfg["num_hidden_layers"]),
            vocab=int(cfg["vocab_size"]),
            vocab0=int(share.get("vocab0", 0)))
        if out.heads % out.kv_heads or out.head_dim % 2 \
                or out.mamba_heads % out.groups \
                or int(cfg.get("mamba_d_ssm", out.mamba.d_inner)) \
                != out.mamba.d_inner \
                or len(out.ssm_multipliers) != 5 \
                or len(out.mlp_multipliers) != 2:
            raise ValueError(
                f"falcon_h1: {out.heads} query heads over {out.kv_heads} "
                f"key/value heads of {out.head_dim}, {out.mamba_heads} "
                f"Mamba-2 heads of {out.mamba_head_dim} over {out.groups} "
                f"groups against mamba_d_ssm {cfg.get('mamba_d_ssm')}, "
                f"{len(out.ssm_multipliers)} ssm and "
                f"{len(out.mlp_multipliers)} mlp multipliers")
        return out

    @property
    def mamba(self) -> mamba2.Geometry:
        """What ``models/mamba2.py`` reads: the mixer's sizes, and
        ``ssm_multipliers`` on the projection's columns."""
        return mamba2.Geometry(
            heads=self.mamba_heads, head_dim=self.mamba_head_dim,
            groups=self.groups, state_size=self.state_size,
            conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
            eps=self.eps, column_scale=self.ssm_multipliers)

    @property
    def per_group(self) -> int:
        """Query heads that read one key/value head."""
        return self.heads // self.kv_heads


# -- the parts of a layer -----------------------------------------------------


def _scaled(x, m: float):
    """``m * x`` in float32, rounded to ``x``'s type; ``x`` itself where
    ``m`` is 1 (exact)."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def _qkv(cfg: FalconH1Config, p, z, positions):
    """``(q [N, kv heads, heads a group, d], k [N, kv heads, d], v)`` of
    ``z [N, hidden]`` at ``positions [N]``: q and k rotated, k under
    ``key_multiplier`` before its rotation."""
    with jax.named_scope("qkv"):
        n, dt = z.shape[0], z.dtype
        q = moe.mm(z, p["q"]).astype(dt).reshape(
            n, cfg.kv_heads, cfg.per_group, cfg.head_dim)
        k = (moe.mm(z, p["k"]) * cfg.key_multiplier).astype(dt).reshape(
            n, cfg.kv_heads, cfg.head_dim)
        v = moe.mm(z, p["v"]).astype(dt).reshape(
            n, cfg.kv_heads, cfg.head_dim)
        cos, sin = attention.rope_angles(cfg.rope_theta, cfg.head_dim,
                                         positions)
        return (attention.rope(q, cos[:, None, None], sin[:, None, None]),
                attention.rope(k, cos[:, None], sin[:, None]), v)


def _out(p, o, dtype):
    with jax.named_scope("o"):
        return attention.heads_out(p, o, dtype)


def attn_prefill(cfg: FalconH1Config, p, z, cache, slot, start):
    """A chunk ``z [C, hidden]`` of stream ``slot`` whose first token is
    at ``start``: ``models/attention.py`` ``prefill`` on this model's q,
    k and v, every position seen."""
    o, cache = attention.prefill(
        lambda positions: _qkv(cfg, p, z, positions), z.shape[0], cache,
        slot, start, cache["k"].shape[2], moe.precision(p["q"]))
    return _out(p, o, z.dtype), cache


def attn_decode(cfg: FalconH1Config, p, z, cache, positions):
    """One token of every stream: writes each stream's K and V row at
    its position, then attends over ``0 .. position``
    (``models/attention.py`` ``decode_step`` with the whole cache as its
    window)."""
    q, k, v = _qkv(cfg, p, z, positions)
    o, cache = attention.decode_step(q, k, v, cache, positions,
                                     cache["k"].shape[2], cfg.head_dim ** -0.5)
    return _out(p, o, z.dtype), cache


def dense_mlp(cfg: FalconH1Config, p, x):
    """``W_down(silu(m_g W_gate x) * W_up x)`` at ``intermediate_size``,
    without ``m_d`` (the caller's, on the branch): XLA's three products
    at every row count.  At a decode step's 128 rows they read 3.54 ms
    a step for the four layers' 2.64 GB (91 % of the bytes' floor)
    against 3.73 through ``models/moe.py``'s grouped product as one
    group of one expert in tiles of 384 columns, the step 16.35 against
    16.54 (``PERF.md`` section 6, PR 47): the other way round from
    ``longcat_flash.py``'s narrower MLPs, which keep that path."""
    m_g = cfg.mlp_multipliers[0]
    h = jax.nn.silu(moe.mm(x, p["gate"]) * m_g) * moe.mm(x, p["up"])
    return moe.mm(h.astype(x.dtype), p["down"]).astype(x.dtype)


def _layers(cfg: FalconH1Config, params, x, states, mamba, attend):
    """Every held layer on ``x [N, hidden]``: ``mamba(mixer's params,
    its input, the layer's state) -> (output, the state with its
    recurrent leaves renewed)`` and ``attend(attention's params, its
    input, the layer's state) -> (output, the new K and V)``.  Returns
    the stream and the layers' new states."""
    new = []
    m_d = cfg.mlp_multipliers[1]
    for i, (layer, st) in enumerate(zip(params["layers"], states)):
        with jax.named_scope(f"layer{i:02d}"):
            with jax.named_scope("norm"):
                u = moe.rms(x, layer["norm"], cfg.eps)
            with jax.named_scope("mamba"):
                m, st = mamba(layer["mamba"],
                              _scaled(u, cfg.ssm_in_multiplier), st)
            with jax.named_scope("attn"):
                a, cache = attend(layer["attn"],
                                  _scaled(u, cfg.attention_in_multiplier), st)
            with jax.named_scope("mix"):
                x = x + (m.astype(jnp.float32) * cfg.ssm_out_multiplier
                         + a.astype(jnp.float32)
                         * cfg.attention_out_multiplier).astype(x.dtype)
            # the MLP's scope holds its norm and its residual add, so that
            # the fusions XLA roots there are booked to it
            with jax.named_scope("mlp"):
                w = moe.rms(x, layer["mlp_norm"], cfg.eps)
                x = x + _scaled(dense_mlp(cfg, layer["mlp"], w), m_d)
            new.append({**st, **cache})
    return x, new


def _embed(cfg: FalconH1Config, params, ids):
    with jax.named_scope("embed"):
        return _scaled(params["embed"][ids - cfg.vocab0],
                       cfg.embedding_multiplier)


def _head(cfg: FalconH1Config, params, x):
    """Logits over the held rows of the vocabulary, float32, under
    ``lm_head_multiplier``, and the greedy id (global) beside them."""
    with jax.named_scope("head"):
        logits = moe.mm(moe.rms(x, params["final_norm"], cfg.eps),
                        params["head"]) * cfg.lm_head_multiplier
        return logits, (jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        + cfg.vocab0)


# -- the state and the two entry points ---------------------------------------

COUNTERS = ("steps", "ssm_rows", "kv_rows_read", "kv_rows_fetched",
            "restores", "position_faults")


def init_state(cfg: FalconH1Config, params, streams: int, positions: int,
               dtype=None) -> dict:
    """The state a filter owns between invokes.  Per layer the recurrent
    state and the convolution's last inputs, live and as the snapshot at
    the stream's prompt end, AND a K and a V array of ``positions`` rows
    a stream; once, where each stream's prompt ends and the position it
    was last fed (-1: nothing yet); and the counters the steps add to
    (``uint32``: the reader takes differences, so a wrap costs nothing).
    One buffer a leaf: the state is donated leaf by leaf."""
    dtype = dtype or params["embed"].dtype
    if positions > cfg.max_positions:
        raise ValueError(f"falcon_h1: {positions} positions, the model "
                         f"has {cfg.max_positions}")
    return {
        "layers": [{**mamba2.init_state(cfg.mamba, streams, dtype),
                    **attention.kv_cache(streams, cfg.kv_heads, positions,
                                         cfg.head_dim, dtype)}
                   for _ in range(cfg.layers)],
        **stream.book(streams), "counters": stream.zeros(COUNTERS)}


def counter_units(cfg: FalconH1Config, state: dict) -> dict:
    """What the raw counters stand for in bytes; EVERY layer counts in
    both kinds.  ``ssm_rows`` counts the streams stepped in ONE layer: a
    row is a stream's ``ssm`` and ``conv``, read and written.
    ``kv_rows_read`` counts the rows in use (``0 .. position``) of ONE
    layer, ``kv_rows_fetched`` the rows the decode attention copies for
    them (``models/attention.py`` ``decode_rows_fetched``): a row is a
    token's K and V.  ``cache_bytes_*`` are the names the attention
    kernel's roofline reader knows the same bytes by."""
    first, depth = state["layers"][0], len(state["layers"])
    out = {"ssm_bytes": ("ssm_rows",
                         2 * mamba2.state_row_bytes(first) * depth)}
    row = 2 * cfg.kv_heads * cfg.head_dim * first["k"].dtype.itemsize
    for did in ("read", "fetched"):
        kv = (f"kv_rows_{did}", row * depth)
        out.update({f"kv_bytes_{did}": kv, f"cache_bytes_{did}": kv})
    return out


def prefill(cfg: FalconH1Config, params, state, ids, slot, start, count):
    """A chunk of ONE stream: ``ids [C]``, ``slot [1]``, ``start [1]``,
    ``count [1]`` (all int32); the first ``count`` ids are real.  Writes
    rows ``[start, start + C)`` of the stream's caches and leaves its
    recurrent states, live and snapshot, at ``start + count`` tokens;
    serves the logits and greedy id after the chunk's last real token.
    Chunks of a stream arrive in order, so the last one leaves the
    snapshot at the prompt's end."""
    slot, start, count = slot[0], start[0], count[0]
    x = _embed(cfg, params, ids)
    x, layers = _layers(
        cfg, params, x, state["layers"],
        lambda p, u, st: mamba2.mamba_prefill(cfg.mamba, p, u, st, slot,
                                              start, count),
        lambda p, z, cache: attn_prefill(cfg, p, z, cache, slot, start))
    logits, greedy = _head(cfg, params,
                           lax.dynamic_slice_in_dim(x, count - 1, 1))
    with jax.named_scope("state"):
        new = dict(layers=layers, counters=state["counters"],
                   **stream.book_prefilled(state, slot, start + count))
    return new, (logits, greedy)


def decode(cfg: FalconH1Config, params, state, ids, positions):
    """One token of EVERY stream: ``ids [B]``, ``positions [B]`` int32.
    Serves ``logits [B, vocab held]`` float32 and the greedy ids.  A
    stream at its ``prompt_end`` starts from its snapshot; any other
    must be at ``last + 1``, or the step counts a position fault."""
    with jax.named_scope("state"):
        restore, fault, book = stream.book_step(state, positions)
    layers = state["layers"]
    with jax.named_scope("ssm_restore"):
        # empty where the step is the kernel, which picks each stream's
        # source itself
        if any(mamba2.step_refusal(st) for st in layers):
            layers = mamba2.restored(layers, restore)
    x = _embed(cfg, params, ids)
    x, layers = _layers(
        cfg, params, x, layers,
        lambda p, u, st: mamba2.mamba_decode(cfg.mamba, p, u, st, restore),
        lambda p, z, cache: attn_decode(cfg, p, z, cache, positions))
    logits, greedy = _head(cfg, params, x)
    with jax.named_scope("state"):
        gained = {"steps": 1, "ssm_rows": ids.shape[0],
                  "kv_rows_read": jnp.sum(positions + 1),
                  "kv_rows_fetched": attention.decode_rows_fetched(
                      layers, cfg.per_group, positions),
                  "restores": jnp.sum(restore),
                  "position_faults": jnp.sum(fault)}
        new = dict(layers=layers, **book,
                   counters=stream.bump(state["counters"], gained))
    return new, (logits, greedy)


# -- weights of the right shapes, and registration ----------------------------


def param_shapes(cfg: FalconH1Config) -> dict:
    """The pytree of ``(shape, role)`` a weights maker fills: matrices
    carry the role their init gain is looked up by, norm gains ``norm``,
    and the Mamba-2 mixer's small vectors their own names."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    layer = {"norm": ((h,), "norm"),
             "mamba": mamba2.param_shapes(cfg.mamba, h),
             "attn": {"q": ((h, q), "q"), "k": ((h, kv), "k"),
                      "v": ((h, kv), "v"), "o": ((q, h), "o")},
             "mlp_norm": ((h,), "norm"),
             "mlp": {"gate": ((h, f), "gate"), "up": ((h, f), "up"),
                     "down": ((f, h), "down")}}
    return {"embed": ((cfg.vocab, h), "embed"),
            "layers": [layer for _ in range(cfg.layers)],
            "final_norm": ((h,), "norm"), "head": ((h, cfg.vocab), "head")}


def init_params(cfg: FalconH1Config, key, dtype=None) -> Params:
    """Seeded weights of the right shapes (``models/streams.py``
    ``seeded_params``, ``models/mamba2.py`` ``seeded_laws``): matrices
    N(0, 1/fan_in) (residual branches halved), norm gains and ``D`` 1.
    The law knows nothing of the multipliers: at the published ones the
    three branches all but vanish, so a test that must see them scales
    its own (``benchmark/weights`` draws a law that absorbs them)."""
    return stream.seeded_params(
        param_shapes(cfg), key, dtype, ones=("norm", "D"),
        halved=("o", "out_proj", "down"), special=mamba2.seeded_laws())


def entries(cfg: FalconH1Config, streams: int, positions: int,
            chunk: int) -> Dict[str, Any]:
    """What :func:`register` hands ``register_stateful_model``
    (``models/streams.py`` ``entries``, cached by these arguments): the
    two entry points with their input schemas, and ``init_state``."""
    return stream.entries(
        cfg, decode, ((streams,), (streams,)),
        prefill, ((chunk,), (1,), (1,), (1,)), init_state, counter_units,
        streams=streams, positions=positions)


def register(name: str, cfg: FalconH1Config, params: Params, streams: int,
             positions: int, chunk: int) -> str:
    """Register ``params`` as the stateful model ``name`` for
    ``tensor_filter framework=jax-xla model=<name>``: a filter whose
    negotiated input is ``(ids[chunk], slot[1], start[1], count[1])``
    prefills, one whose input is ``(ids[streams], positions[streams])``
    decodes; two filters with one ``shared-tensor-filter-key`` work on
    one state (every layer's recurrent states, their snapshots and its K
    and V)."""
    return stream.register(name, params,
                           entries(cfg, streams, positions, chunk))
