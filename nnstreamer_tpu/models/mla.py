"""Multi-head latent attention (MLA) as the token models run it
(``deepseek_v2.py``, ``longcat_flash.py``): a token's queries through
the low-rank ``q`` stream, what the cache keeps of it, and the two paths
through one set of weights, the expanded form for a prefill chunk
(``ops/kernels.py`` ``latent_prefill_attention``, or its reference for a
shape it refuses) and the absorbed form for a decode step
(``latent_decode_attention``).

A cache keeps of a token its ``(c_kv, k_r)`` after norm and rotation,
``latent`` values, in the rows ``ops/kernels.py`` ``latent_cache_row``
lays out for the configuration's sizes.  At the published ones
(``kv_lora_rank`` 512, whole lane tiles, and ``qk_rope_head_dim`` 64,
half of one) a cache is ``[streams, positions / 2, 1152]``: a row PACKS
two neighbouring positions, ``[c_kv a | c_kv b | k_r a, k_r b]``, so it
stores the 576 values of each and nothing else and every slice of it is
whole tiles.  (The TPU's compiler lays a ``[.., positions, 576]`` array
out with positions minor, and every product over it then copied the
whole cache; a row a position padded to whole lanes took 640 values for
576, a tenth of every fetch.)  A chunk's rows are packed before they
are written (the prefill kernel scores them as they lie; its reference
unpacks a key block after it is sliced); a decode
step reads the one row a stream its token falls in, places the token
and writes the row back; the decode kernel works on the packed rows as
they lie.  Nothing reshapes a cache: that is a copy of it.  Other sizes
(a rank that is not whole lanes: the tests' toy configurations) keep a
row a position, ``[c_kv | k_r | 0..]`` padded to whole lanes, through
the same functions.  Positions beyond a stream's are masked, so a stale
or padded one is never read.

What differs between the models is read off the configuration handed
in, which names the sizes as the published configs do
(``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``rms_norm_eps``; ``heads`` those HELD, ``q_head_dim``,
``latent`` and ``row``, the values a position takes as stored, derived:
:func:`row_values`) and says:

``cos_sin(positions)``  the rotation's ``(cos, sin) [.., rope / 2]``,
                        with whatever scaling of the frequencies the
                        model has (YaRN, or none);
``score_scale``         what the scores are multiplied by;
``q_lora_scale``, ``kv_lora_scale``  a constant on the normed low-rank
                        streams ``c_q`` and ``c_kv`` (1 where the model
                        has none; ``k_r`` is never scaled).  It rides on
                        the norm's gain, so the stream is rounded once.
"""

from __future__ import annotations

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover
    jax = jnp = lax = None

from ..ops import kernels
from ..utils import profile as _profile
from . import moe
from .attention import rope

_rms, _mm, _precision = moe.rms, moe.mm, moe.precision


def _gain(gain, scale: float):
    return gain if scale == 1.0 else gain * jnp.float32(scale)


def queries(cfg, p, x, cos, sin):
    """``(q_nope [N, heads, nope], q_rope [N, heads, rope])`` rotated."""
    c_q = _rms(_mm(x, p["q_a"]).astype(x.dtype),
               _gain(p["q_a_norm"], cfg.q_lora_scale), cfg.rms_norm_eps)
    q = _mm(c_q, p["q_b"]).astype(x.dtype).reshape(
        x.shape[0], cfg.heads, cfg.q_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, rope(q_rope, cos[:, None], sin[:, None])


def _row(cfg) -> tuple:
    """``(positions a cache row holds, the row's width)``."""
    return kernels.latent_cache_row(cfg.kv_lora_rank, cfg.qk_rope_head_dim)


def row_values(cfg) -> int:
    """Values a position takes in a cache as stored (a configuration's
    ``row``): its ``latent`` ones where rows are packed, else those
    padded to whole lanes."""
    per, width = _row(cfg)
    return width // per


def latent_rows(cfg, p, x, cos, sin, dtype):
    """What the cache keeps of each token: ``[c_kv | k_r]``, normed and
    rotated, ``cfg.latent`` wide."""
    kv = _mm(x, p["kv_a"]).astype(x.dtype)
    c_kv = _rms(kv[:, :cfg.kv_lora_rank],
                _gain(p["kv_a_norm"], cfg.kv_lora_scale), cfg.rms_norm_eps)
    k_r = rope(kv[:, cfg.kv_lora_rank:], cos, sin)
    return jnp.concatenate([c_kv, k_r], axis=-1).astype(dtype)


def kv_b(cfg, p):
    """``W_kvb`` as ``[latent rank, heads, nope + v]``."""
    return p["kv_b"].reshape(cfg.kv_lora_rank, cfg.heads,
                             cfg.qk_nope_head_dim + cfg.v_head_dim)


def attn_prefill(cfg, p, x, cache, slot, start):
    """Expanded MLA over a chunk ``x [C, hidden]`` of stream ``slot``
    whose first token is at ``start`` (a multiple of ``C``): writes the
    chunk's latent rows, then attends to rows ``[0, start + C)`` block
    by block (keys and values rebuilt from the latent rows, a running
    softmax, so no ``[heads, chunk, positions]`` score tensor exists).
    Returns the held heads' partial output and the cache.

    One algorithm, two programs, chosen from the shapes: the kernel
    (``ops/kernels.py`` ``latent_prefill_attention``: a query block's
    scores never leave fast memory) wherever its refusal has nothing to
    say, its reference (XLA's own ``while`` over the key blocks)
    everywhere else.  The set-up span this is traced under says which
    (``utils/profile.py`` ``note``)."""
    c = x.shape[0]
    rank, per = cfg.kv_lora_rank, _row(cfg)[0]
    positions = start + jnp.arange(c, dtype=jnp.int32)
    cos, sin = cfg.cos_sin(positions)
    q_nope, q_rope = queries(cfg, p, x, cos, sin)
    with jax.named_scope("cache_write"):
        rows = kernels.latent_pack(
            latent_rows(cfg, p, x, cos, sin, cache.dtype), rank)
        cache = lax.dynamic_update_slice(cache, rows[None],
                                         (slot, start // per, 0))
    w_kvb = kv_b(cfg, p)
    refusal = kernels.latent_prefill_attention_refusal(
        q_nope.shape, q_rope.shape, cache.shape, w_kvb.shape,
        {x.dtype, cache.dtype, w_kvb.dtype})
    shapes = f"attn_prefill {c} x {cfg.heads} heads on {tuple(cache.shape)} " \
             f"{cache.dtype.name}"
    _profile.note(f"{shapes}: the jnp loop ({refusal})" if refusal
                  else f"{shapes}: the kernel")
    attend = kernels.latent_prefill_attention_reference if refusal \
        else kernels.latent_prefill_attention
    o = attend(q_nope, q_rope, cache, slot, start, w_kvb, cfg.score_scale)
    return _mm(o.reshape(c, cfg.heads * cfg.v_head_dim),
               p["o"]).astype(x.dtype), cache


def attn_decode(cfg, p, x, cache, positions):
    """Absorbed MLA for one token of every stream: ``x [B, hidden]``,
    stream ``b`` at ``positions[b]``.  Writes each stream's token into
    its row (``b`` rows read, the token placed, ``b`` rows written: a
    packed row's other position stays), then scores and values straight
    on the latent rows up to its position (``q~ = q_nope W_kvb[k]^T``;
    one pass over a stream's live rows, which the kernel copies
    itself)."""
    b = x.shape[0]
    cos, sin = cfg.cos_sin(positions)
    q_nope, q_rope = queries(cfg, p, x, cos, sin)
    with jax.named_scope("cache_write"):
        rows = latent_rows(cfg, p, x, cos, sin, cache.dtype)
        at = (jnp.arange(b), positions // _row(cfg)[0])
        cache = cache.at[at].set(kernels.latent_place(
            cache[at], rows, positions, cfg.kv_lora_rank))
    w_kvb = kv_b(cfg, p)
    hp = _precision(p["kv_b"])
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope,
                       w_kvb[..., :cfg.qk_nope_head_dim],
                       preferred_element_type=jnp.float32,
                       precision=hp).astype(x.dtype)
    q_cat = jnp.concatenate([q_abs, q_rope], axis=-1)
    o_lat = kernels.latent_decode_attention(
        q_cat, cache, positions, cfg.kv_lora_rank, cfg.score_scale)
    o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(x.dtype),
                   w_kvb[..., cfg.qk_nope_head_dim:],
                   preferred_element_type=jnp.float32,
                   precision=hp).astype(x.dtype)
    return _mm(o.reshape(b, -1), p["o"]).astype(x.dtype), cache


def cache_positions(cfg, cache) -> int:
    """Positions a cache holds."""
    return cache.shape[1] * _row(cfg)[0]


def init_cache(cfg, streams: int, positions: int, dtype):
    """One latent cache: whole lattice cells of positions (the decode
    kernel copies a stream's live rows by cells of 128), ``cfg.row``
    values each; positions never written are masked."""
    positions = -(-int(positions) // 128) * 128
    per, width = _row(cfg)
    return jnp.zeros((streams, positions // per, width), dtype)
