"""Plain float32 reference of Phi-4-mini-flash-reasoning, the comparison
that decides ``correct`` for its cell, and the control.

Written from ``benchmark/configs/phi4_mini_flash_reasoning.json`` (the
model's public ``config.json``, uncut) and the equations of the issue
that added it: straightforward ``jax.numpy`` at ``precision=HIGHEST``.
``LN(x; g, b) = (x - mean x) / sqrt(var x + eps) g + b``; for ``l =
0..31`` (``F = 17``)::

    x0 = E[id]
    u  = LN_l(x);   x = x + Mixer_l(u)
    w  = LN'_l(x);  x = x + W_down( silu(g) * p ),  [g | p] = W_gate_up w
    logits = LN_f(x_last) E^T

``Mixer_l``: Mamba-1 in the even layers below ``F`` (``[s | z] = u
W_in``; ``c_t = silu(conv4(s)_t + b_c)``, the convolution written as the
sum of 4 shifted inputs; ``[delta' | B | C] = c W_x``; ``delta =
softplus(delta' W_delta + b_delta)``; token by token ``h = exp(delta (x)
A) h + (delta c) (x) B``, ``y = h C + D c``; ``(y silu(z)) W_out``;
layer 16 also keeps ``m = y``); differential attention over the last
512 positions in the odd layers below ``F`` and over every position in
layer ``F``; a gated memory unit ``(m silu(u W_in')) W_out'`` on layer
16's ``m`` of the same token in the even layers above ``F``;
differential attention of the layer's own queries to layer ``F``'s keys
and values in the odd ones.

Differential attention is computed HEAD BY HEAD at the head's own size
of 64: query pair ``i`` is ``(q1_i, q2_i)``, key pair ``j = i // 2`` is
``(k1_j, k2_j)`` with the value row ``V_j`` of 128; ``a_w = softmax(q_w
. k_w / 8) V_j`` (an explicit masked softmax in blocks of queries);
``o_i = (1 - lambda0) RMSNorm_128(a_1 - lambda a_2)``; ``lambda =
exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0``, ``lambda0 = 0.8 - 0.6
exp(-0.3 l)``.  No zero lane, no 128-wide key row, no kernel.

No cache, no chunk, no snapshot and NO PREFILL SKIP: all 32 layers run
over the WHOLE history of a sampled frame (the program's prefill runs
the layers above ``F`` on one token a chunk), the recurrence is a
``lax.scan`` over the history's tokens from a zero state, and only the
head is applied to the last row alone.  It imports nothing of the
program and makes its own weights from the seed a layer at a time
(``benchmark/weights``, bf16 values upcast).  Departures from the
published model are the configuration's ``assumed``.

A sampled frame is one token of one stream at one ring slot.  Its
history follows from the seed (``benchmark/inputs``: the stream's prompt
and the ring's ids up to that slot), and a frame is right only if the
chunked prefill of the nine recurrent states, the eight rings and the
one shared cache, the skip, the snapshots, the restore at each pass's
first step and every step since left what the plain forward computes.
Every history is padded to the configuration's cache length, so that
one set of programs serves every frame of every seed; causality keeps
the padding out of the result.

What is compared is what the timed path served, the statistics of the
other token cells (``reference/smallthinker_21b_stage8.py``):

``logits_rel_l2_lower_median``  the largest of the better half of the
                          sampled frames' ||served - reference|| /
                          ||reference|| (the 4th smallest of 8)
``logits_rel_l2_worst``   the largest of them
``greedy_mismatch``       frames whose served greedy id is not the
                          argmax of their served logits

The control is the same forward with every matrix product's inputs and
weights rounded to float8_e4m3fn, the nearest precision below the
configuration's bfloat16.

``faults`` (tests only: the comparison must tell each from the sound
forward): ``stale_memory`` hands the gated memory units the memory of
the token before; ``cross_reads_window`` lets the cross layers see the
last 512 positions only; ``no_lambda`` drops ``lambda``.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
QUERY_BLOCK = 256


def _sibling(kind: str, name: str):
    path = os.path.join(os.path.dirname(_HERE), kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}_for_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(cfg: dict, lower: bool, faults: tuple):
    """The forward's pieces, jitted: one per kind of work."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["layer_norm_eps"])
    h = int(cfg["hidden_size"])
    heads, kvh = (int(cfg[k]) for k in ("num_attention_heads",
                                        "num_key_value_heads"))
    hd, pairs, kv_pairs = h // heads, heads // 2, kvh // 2
    window, f = int(cfg["sliding_window"]), int(cfg["intermediate_size"])
    d = int(cfg.get("mamba_expand", 2)) * h
    n, kernel = (int(cfg.get(k, v)) for k, v in (("mamba_d_state", 16),
                                                 ("mamba_d_conv", 4)))
    rank = cfg.get("mamba_dt_rank", "auto")
    rank = -(-h // 16) if rank == "auto" else int(rank)

    def q8(a):
        a = a.astype(jnp.float32)
        if not lower:
            return a
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def mm(x, w):
        return jnp.matmul(q8(x), q8(w), precision=hi)

    def ln(x, p):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
        return (x - mean) * lax.rsqrt(var + eps) * p["g"] + p["b"]

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(jnp.float32)

    @jax.jit
    def normed(p, x):
        return ln(x, p)

    @jax.jit
    def mamba(p, u):
        """``(Mixer(u), y)`` over a whole history ``u [T, hidden]`` from
        a zero state, the recurrence token by token."""
        steps = u.shape[0]
        sz = mm(u, p["in_proj"])
        s, z = sz[:, :d], sz[:, d:]
        past = jnp.concatenate([jnp.zeros((kernel - 1, d)), s])
        c = jax.nn.silu(p["conv_b"] + sum(
            past[k:k + steps] * p["conv_w"][k] for k in range(kernel)))
        dbc = mm(c, p["x_proj"])
        delta = jax.nn.softplus(mm(dbc[:, :rank], p["dt_proj"])
                                + p["dt_bias"])
        a = -jnp.exp(p["A_log"])                              # [n, d]

        def token(state, t):
            d_t, c_t, b_t, cc_t = t
            state = jnp.exp(d_t[None, :] * a) * state \
                + b_t[:, None] * (d_t * c_t)[None, :]
            return state, jnp.sum(state * cc_t[:, None], axis=0)

        _, y = lax.scan(token, jnp.zeros((n, d), jnp.float32),
                        (delta, c, dbc[:, rank:rank + n], dbc[:, rank + n:]))
        y = y + p["D"] * c
        return mm(y * jax.nn.silu(z), p["out_proj"]), y

    @jax.jit
    def keys_values(p, u):
        """``k [T, kv pairs, 2, 64]`` (``k1``, ``k2``) and ``v [T, kv
        pairs, 128]``."""
        kv = (mm(u, p["kv"]) + p["kv_b"]).reshape(-1, 2, kv_pairs, 2 * hd)
        return kv[:, 0].reshape(-1, kv_pairs, 2, hd), kv[:, 1]

    @functools.partial(jax.jit, static_argnames=("seen",))
    def attend(p, u, k, v, first, lam0, seen):
        """Rows ``[first, first + QUERY_BLOCK)`` of a layer's
        differential attention, ``lam0`` its ``lambda0(l)``; ``seen``
        positions back from each query (the history's length: every
        position)."""
        rows = QUERY_BLOCK
        uq = lax.dynamic_slice_in_dim(u, first, rows)
        at = first + jnp.arange(rows)
        q = (mm(uq, p["q"]) + p["q_b"]).reshape(rows, kv_pairs,
                                                pairs // kv_pairs, 2, hd)
        keys = jnp.arange(k.shape[0])
        if seen < k.shape[0]:
            # the keys a block of queries can see, not the whole history
            k, v = (lax.dynamic_slice_in_dim(
                jnp.pad(t, ((seen, 0),) + ((0, 0),) * (t.ndim - 1)), first,
                seen + rows) for t in (k, v))
            keys = first - seen + jnp.arange(seen + rows)
        # [pair j, a, w, query, key]: head (j, a, w) at its own 64
        s = jnp.einsum("qjawd,kjwd->jawqk", q8(q), q8(k), precision=hi) \
            * hd ** -0.5
        mask = (keys[None, :] <= at[:, None]) & (keys[None, :] >= 0) \
            & (keys[None, :] > at[:, None] - seen)
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        a = jnp.einsum("jawqk,kjd->qjawd", q8(prob), q8(v), precision=hi)
        lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
            - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0
        if "no_lambda" in faults:
            lam = 0.0
        diff = a[:, :, :, 0] - lam * a[:, :, :, 1]             # [q, j, a, 128]
        diff = diff * lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True)
                                + eps) * p["subln"] * (1.0 - lam0)
        return mm(diff.reshape(rows, h), p["o"]) + p["o_b"]

    @jax.jit
    def gmu(p, u, memory):
        return mm(memory * jax.nn.silu(mm(u, p["in"])), p["out"])

    @jax.jit
    def add_mlp(p, x, out):
        x = x + out
        gu = mm(ln(x, p["mlp_norm"]), p["mlp"]["gate_up"])
        return x + mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], p["mlp"]["down"])

    @jax.jit
    def head(p, table, x):
        return mm(ln(x, p), table.T)

    return dict(embed=embed, normed=normed, mamba=mamba,
                keys_values=keys_values, attend=attend, gmu=gmu,
                add_mlp=add_mlp, head=head, window=window)


@functools.lru_cache(maxsize=8)
def _built(cfg_key: str, lower: bool, faults: tuple):
    return _build(json.loads(cfg_key), lower, faults)


def forward_last(cfg: dict, seed: int, histories: list,
                 lower: bool = False, faults: tuple = ()) -> np.ndarray:
    """Logits ``[n, vocab]`` after the last token of each history (an
    int array of ids), float32.  A history at a time through all the
    layers, every layer over every token, a layer's weights made as it
    is reached; only the head reads the last row alone."""
    import jax
    import jax.numpy as jnp

    weights = _sibling("weights", cfg["weights"])
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str))
            and k not in ("limits_why",)}
    fns = _built(json.dumps(keep, sort_keys=True), bool(lower),
                 tuple(sorted(faults)))
    depth = int(cfg["num_hidden_layers"])
    longest = max(len(hist) for hist in histories)
    t_pad = max(longest,
                _sibling("inputs", cfg["inputs"]).cache_positions(cfg))
    t_pad = -(-t_pad // QUERY_BLOCK) * QUERY_BLOCK
    window = fns["window"]

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    table = weights.make_part(cfg, seed, "embed")["embed"]
    tail = weights.make_part(cfg, seed, "tail")["final_norm"]
    out = []
    for hist in histories:
        ids = np.zeros(t_pad, np.int32)
        ids[:len(hist)] = np.asarray(hist)
        x = fns["embed"](table, jnp.asarray(ids))
        memory = shared = None
        for i in range(depth):
            p = f32(weights.make_part(cfg, seed, f"layer{i:02d}"))
            what, mixer = weights.kind(cfg, i), p["mixer"]
            u = fns["normed"](p["norm"], x)
            if what == "mamba":
                mixed, memory = fns["mamba"](mixer, u)
            elif what == "gmu":
                m = memory
                if "stale_memory" in faults:
                    m = jnp.concatenate([jnp.zeros_like(m[:1]), m[:-1]])
                mixed = fns["gmu"](mixer, u, m)
            else:
                if what == "attn_cross":
                    k, v = shared
                    seen = window if "cross_reads_window" in faults else t_pad
                else:
                    k, v = fns["keys_values"](mixer, u)
                    seen = window if what == "attn_window" else t_pad
                    if what == "attn_full":
                        shared = (k, v)
                lam0 = np.float32(0.8 - 0.6 * np.exp(-0.3 * i))
                mixed = jnp.concatenate([
                    fns["attend"](mixer, u, k, v, first, lam0, seen=seen)
                    for first in range(0, t_pad, QUERY_BLOCK)])
            x = fns["add_mlp"](p, x, mixed)
            del p
        out.append(np.asarray(fns["head"](
            tail, table, x[len(hist) - 1:len(hist)])))
    return np.concatenate(out)


#: the newest float32 result, so that ``control`` after ``check`` on
#: the same frames (``benchmark/control.py``) runs the forward once
_newest: dict = {}


def raw_outputs(cfg: dict, seed: int, frames, lower: bool = False):
    """Reference logits of the sampled frames ``(ids, positions)``."""
    key = (json.dumps(cfg, sort_keys=True), int(seed), bool(lower),
           np.asarray(frames[0]).tobytes(), np.asarray(frames[1]).tobytes())
    if _newest.get("key") == key:
        return _newest["logits"]
    inputs = _sibling("inputs", cfg["inputs"])
    where = inputs.locate(cfg, seed, frames[0], frames[1])
    logits = forward_last(cfg, seed, [inputs.history(cfg, seed, j, r)
                                      for j, r in where], lower)
    if not lower:
        _newest.update(key=key, logits=logits)
    return logits


#: the statistics and their rows beside the limits are the other dense
#: token cell's, to the letter (its file imports nothing of the program
#: either; the model's vocabulary starts at 0, its default)
_STATS = _sibling("reference", "falcon_h1_34b_stage4_vocab8")
compare_numbers, _rows = _STATS.compare_numbers, _STATS._rows


def check(cfg: dict, seed: int, frames, served: dict) -> list:
    t0 = time.perf_counter()
    ref = raw_outputs(cfg, seed, frames)
    print(f"[bench] reference forward of {len(ref)} histories took "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    return _rows(cfg, compare_numbers(cfg, ref, served))


def control(cfg: dict, seed: int, frames) -> list:
    ref = raw_outputs(cfg, seed, frames)
    low = raw_outputs(cfg, seed, frames, lower=True)
    return _rows(cfg, compare_numbers(cfg, ref, {"logits": low}))
