"""Plain float32 reference of the K-EXAONE share, the comparison that
decides ``correct`` for its cell, and the control.

Written from ``benchmark/configs/kexaone_236b_share8.json`` (the model's
public ``config.json`` with the stated cut, and what the config leaves
open under ``assumed``) and the equations of the issue that added it:
straightforward ``jax.numpy`` at ``precision=HIGHEST``.  With ``rms(x;
g) = g x / sqrt(mean(x^2) + 1e-5)``, a layer on its input ``x``:

    a       = rms(x; g1)
    q, k, v = a W_q, a W_k, a W_v   (64 / 8 / 8 heads of 128; query head
                                     i reads key/value head i // 8)
    q, k    = rms_128(q; g_q), rms_128(k; g_k)          per head
    sliding_attention: q and k rotated (theta 1e6, pairs (i, i + 64)),
        position p sees p - 127 .. p
    full_attention: no rotation, p sees 0 .. p
    x1      = x + concat(heads of softmax(q k^T / sqrt(128)) v) W_o
    m       = rms(x1; g2)
    dense:  x2 = x1 + (silu(m W_g) * (m W_u)) W_d
    sparse: s = sigmoid(m W_r) over all 128 experts; chosen = the 8
            largest of s + b; w_e = 2.5 s_e / sum of the chosen s;
            x2 = x1 + sum over chosen AND held e of w_e SwiGLU_e(m)
                    + SwiGLU_shared(m)

    logits     = rms(x_L; g_f) W_head
    u          = W_eh [rms(Emb(t_{i+1}); g_e) ; rms(x_L,i; g_h)]
    logits_mtp = rms(layer_mtp(u); g_m) W_head    (a sparse full layer
                                                   over every u_0..u_i)

No cache, no ring, no kernel, no sorted expert product: the window is a
mask over an explicit causal softmax, and an expert runs on the rows
routed to it, picked out on the host.  It imports nothing of the
program and makes its own weights from the seed a part at a time
(``benchmark/weights``, bf16 values upcast; two copies of the weights do
not fit a chip).  The chip's share is the program's: experts ``expert0
.. expert0 + num_experts`` of the router's published 128 and rows
``vocab0 ..`` of the vocabulary; what absent experts would add is left
out.

A sampled frame is one token of one stream at one ring slot.  Its
history follows from the seed (``benchmark/inputs``: the stream's prompt
and the ring's ids up to that slot, and the id that follows each), and
the reference runs a full causal forward over that history and reads
both logits after its last token; the module's are computed from the
same forward's last-layer streams.  Every history is padded to the
traffic's longest (its longest prompt and a whole answer), whatever the
sample drew, so that one set of programs serves every history of every
seed and a machine's compile cache serves every later process;
causality keeps the padding out of the result.

What is compared is what the timed path served, for the main logits and
(``mtp_`` before the name) for the module's:

``logits_rel_l2_lower_median``  the largest of the better half of the
                          sampled frames' ||served - reference|| /
                          ||reference|| (the 4th smallest of 8): at most
                          half the sample may lie over the limit.
                          Routing is discontinuous: an expert chosen on
                          a near tie in bfloat16 may differ from
                          float32's choice, and that frame then sits far
                          from the rest.  A lower precision moves EVERY
                          frame, so this order statistic tells the two
                          apart where the mean would fail a sound run
                          with one such frame.
``logits_rel_l2_worst``   the largest of them.  With this
                          configuration's damped routed experts a frame
                          with an expert chosen otherwise than float32
                          chose reads a third of what the control's best
                          frame reads, so this limit too lies between
                          the sound runs' largest and the control's
                          smallest, and the control fails it as well.
``greedy_mismatch``       frames whose served greedy id, of either
                          tensor, is not the argmax of its served logits

The control is the same forward with every matrix product's inputs and
weights rounded to float8_e4m3fn, the nearest precision below the
configuration's bfloat16.
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
#: an expert's rows are padded to a multiple of this: twice what a held
#: expert of the cell gets from a padded history (16,384 / 16), so that
#: nearly every expert of every history runs the one program
ROW_BUCKET = 2048


def _sibling(kind: str, name: str):
    path = os.path.join(os.path.dirname(_HERE), kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}_for_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(cfg: dict, lower: bool):
    """The forward's pieces, jitted: one per kind of work."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["rms_norm_eps"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    top_k = int(cfg["num_experts_per_tok"])
    scaling = float(cfg["routed_scaling_factor"])
    span = int(cfg["sliding_window"])
    inv_freq = (1.0 / float(cfg["rope_parameters"]["rope_theta"]) ** (
        np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32)

    def q8(a):
        a = a.astype(jnp.float32)
        if not lower:
            return a
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def mm(x, w):
        return jnp.matmul(q8(x), q8(w), precision=hi)

    def rms(x, gain):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain

    def rotate(x, positions):
        # x [rows, heads, d]; pairs (i, i + d/2)
        angle = positions.astype(jnp.float32)[:, None] * inv_freq
        cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def swiglu(p, m):
        return mm(jax.nn.silu(mm(m, p["gate"])) * mm(m, p["up"]), p["down"])

    @jax.jit
    def embed(table, ids):
        return table.astype(jnp.float32)[ids]

    @functools.partial(jax.jit, static_argnames=("windowed",))
    def keys_values(p, gain, x, windowed):
        """Every row's keys and values ``[rows, 8, 128]``."""
        h = rms(x, gain)
        k = rms(mm(h, p["k"]).reshape(-1, nkv, d), p["k_norm"])
        v = mm(h, p["v"]).reshape(-1, nkv, d)
        if windowed:
            k = rotate(k, jnp.arange(x.shape[0]))
        return k, v

    @functools.partial(jax.jit, static_argnames=("rows", "windowed"))
    def attend(p, gain, x, k, v, first, rows, windowed):
        """Rows ``[first, first + rows)`` of ``x + attention(x)``."""
        xq = lax.dynamic_slice_in_dim(x, first, rows)
        positions = first + jnp.arange(rows)
        q = rms(mm(rms(xq, gain), p["q"]).reshape(rows, nh, d), p["q_norm"])
        if windowed:
            q = rotate(q, positions)
        q = q.reshape(rows, nkv, nh // nkv, d)    # head i reads kv i // 8
        s = jnp.einsum("qgjd,kgd->gjqk", q8(q), q8(k), precision=hi) \
            * d ** -0.5
        keys = jnp.arange(x.shape[0])[None, :]
        seen = keys <= positions[:, None]
        if windowed:
            seen &= keys > positions[:, None] - span
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf),
                              axis=-1)
        o = jnp.einsum("gjqk,kgd->qgjd", q8(prob), q8(v), precision=hi)
        return xq + mm(o.reshape(rows, nh * d), p["o"])

    @jax.jit
    def mlp(p, gain, x):
        """A dense SwiGLU MLP on the normed rows (no residual add)."""
        return swiglu(p, rms(x, gain))

    @jax.jit
    def routing(router, bias, gain, x):
        """The 8 largest of sigmoid score + bias, weighted by their
        scores alone, normalised and scaled."""
        score = jax.nn.sigmoid(jnp.matmul(
            rms(x, gain), router.astype(jnp.float32), precision=hi))
        _, idx = lax.top_k(score + bias, top_k)
        kept = jnp.take_along_axis(score, idx, axis=-1)
        return idx, scaling * kept / jnp.sum(kept, -1, keepdims=True)

    @jax.jit
    def expert_rows(p, e, gain, x, rows, weight, y):
        """``y`` plus expert ``e``'s weighted output on ``rows`` of ``x``
        (a row index past the end adds nothing)."""
        m = rms(x, gain).at[rows].get(mode="fill", fill_value=0.0)
        out = swiglu({name: w[e] for name, w in p.items()}, m)
        return y.at[rows].add(weight[:, None] * out, mode="drop")

    @jax.jit
    def merge(m, table, x, next_ids):
        """The module's input: ``W_eh [rms(Emb(next)) ; rms(x_L)]``."""
        e = rms(table.astype(jnp.float32)[next_ids], m["embed_norm"])
        return mm(jnp.concatenate([e, rms(x, m["hidden_norm"])], -1),
                  m["eh_proj"])

    @jax.jit
    def head(gain, w, x):
        return mm(rms(x, gain), w)

    return dict(embed=embed, keys_values=keys_values, attend=attend,
                mlp=mlp, routing=routing, expert_rows=expert_rows,
                merge=merge, head=head)


@functools.lru_cache(maxsize=4)
def _built(cfg_key: str, lower: bool):
    return _build(json.loads(cfg_key), lower)


def _sparse(fns, cfg: dict, p, gain, x1, experts=None):
    """``x1 + the held (or the given) experts' weighted part + the
    shared expert``; each expert runs on the rows routed to it."""
    import jax.numpy as jnp

    idx, weight = fns["routing"](p["router"], p["router_bias"], gain, x1)
    idx, weight = np.asarray(idx), np.asarray(weight)
    e0 = int(cfg.get("share", {}).get("expert0", 0))
    y = x1 + fns["mlp"](p["shared"], gain, x1)
    for e in range(int(cfg["num_experts"])):
        rows, col = np.nonzero(idx == e0 + e)
        if not len(rows):
            continue
        pad = -len(rows) % ROW_BUCKET
        rows_p = np.concatenate([rows, np.full(pad, x1.shape[0])])
        w_p = np.concatenate([weight[rows, col], np.zeros(pad, np.float32)])
        y = fns["expert_rows"](p["experts"], e, gain, x1,
                               jnp.asarray(rows_p, jnp.int32),
                               jnp.asarray(w_p, jnp.float32), y)
    return y


def _layer(fns, cfg: dict, p, x, windowed: bool, length: int, last: bool):
    """One layer on a padded history ``x``: every row, or where ``last``
    only the row of the history's last token (``[1, hidden]``)."""
    import jax.numpy as jnp

    k, v = fns["keys_values"](p["attn"], p["attn_norm"], x, windowed=windowed)
    if last:
        x1 = fns["attend"](p["attn"], p["attn_norm"], x, k, v, length - 1,
                           rows=1, windowed=windowed)
    else:
        x1 = jnp.concatenate([
            fns["attend"](p["attn"], p["attn_norm"], x, k, v, first,
                          rows=QUERY_BLOCK, windowed=windowed)
            for first in range(0, x.shape[0], QUERY_BLOCK)])
    del k, v
    if "mlp" in p:
        return x1 + fns["mlp"](p["mlp"], p["ffn_norm"], x1)
    return _sparse(fns, cfg, p["moe"], p["ffn_norm"], x1)


def _padded_length(cfg: dict, lengths: list) -> int:
    """The traffic's longest history in whole query blocks (or the
    longest given, where the configuration states no traffic)."""
    longest = max(lengths)
    serving = cfg.get("serving")
    if serving:
        longest = max(longest, int(serving["prompt_tokens"][-1])
                      + int(serving["answer_tokens"]))
    return -(-longest // QUERY_BLOCK) * QUERY_BLOCK


def forward_last(cfg: dict, seed: int, histories: list, follows: list,
                 lower: bool = False) -> tuple:
    """``(logits [n, vocab], logits_mtp [n, vocab])`` after the last
    token of each history (an int array of ids; ``follows`` the id after
    each of its ids), float32.  Layer by layer over all the histories,
    so that each part's weights are made once."""
    import jax
    import jax.numpy as jnp

    weights = _sibling("weights", cfg["weights"])
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, dict)) and k != "limits"}
    fns = _built(json.dumps(keep, sort_keys=True), bool(lower))
    depth = int(cfg["num_hidden_layers"])
    v0 = int(cfg.get("share", {}).get("vocab0", 0))
    mtp = bool(int(cfg.get("num_nextn_predict_layers", 0)))
    lengths = [len(h) for h in histories]
    t_pad = _padded_length(cfg, lengths)

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def padded(ids):
        out = np.zeros(t_pad, np.int32)
        out[:len(ids)] = np.asarray(ids) - v0
        return jnp.asarray(out)

    table = weights.make_part(cfg, seed, "embed")["embed"]
    xs = [np.asarray(fns["embed"](table, padded(h))) for h in histories]
    for i in range(depth):
        p = f32(weights.make_part(cfg, seed, f"layer{i:02d}"))
        windowed = cfg["layer_types"][i] == "sliding_attention"
        # the module reads every position's last-layer stream
        last = i == depth - 1 and not mtp
        xs = [np.asarray(_layer(fns, cfg, p, jnp.asarray(x), windowed,
                                length, last))
              for x, length in zip(xs, lengths)]
        del p
    tail = weights.make_part(cfg, seed, "head")
    rows = [x if x.shape[0] == 1 else x[n - 1:n]
            for x, n in zip(xs, lengths)]
    logits = np.concatenate([np.asarray(fns["head"](
        tail["final_norm"], tail["head"], jnp.asarray(x))) for x in rows])
    if not mtp:
        return logits, None
    m = f32(weights.make_part(cfg, seed, "mtp"))
    us = [fns["merge"](m, table, jnp.asarray(x), padded(f))
          for x, f in zip(xs, follows)]
    rows = [np.asarray(_layer(fns, cfg, m["layer"], u, False, length, True))
            for u, length in zip(us, lengths)]
    return logits, np.concatenate([np.asarray(fns["head"](
        m["final_norm"], tail["head"], jnp.asarray(x))) for x in rows])


#: the newest float32 result, so that ``control`` after ``check`` on
#: the same frames (``benchmark/control.py``) runs the forward once
_newest: dict = {}


def raw_outputs(cfg: dict, seed: int, frames, lower: bool = False):
    """Reference logits ``(main, mtp)`` of the sampled frames ``(ids,
    next_ids, positions)``."""
    ids, next_ids, positions = (np.asarray(a) for a in frames)
    key = (json.dumps(cfg, sort_keys=True), int(seed), bool(lower),
           ids.tobytes(), positions.tobytes())
    if _newest.get("key") == key:
        return _newest["logits"]
    inputs = _sibling("inputs", cfg["inputs"])
    where = inputs.locate(cfg, seed, ids, positions)
    follows = [inputs.next_history(cfg, seed, j, r) for j, r in where]
    if [int(f[-1]) for f in follows] != next_ids.reshape(-1).tolist():
        raise ValueError("the sampled frames' next ids are not the ring's")
    logits = forward_last(cfg, seed, [inputs.history(cfg, seed, j, r)
                                      for j, r in where], follows, lower)
    if not lower:
        _newest.update(key=key, logits=logits)
    return logits


def _distances(name: str, got, ref) -> dict:
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    names = (name + "_rel_l2_lower_median", name + "_rel_l2_worst")
    if got.shape != ref.shape or not np.isfinite(got).all():
        return dict.fromkeys(names, float("inf"))
    each = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    print(f"[bench] {name}_rel_l2 by frame: "
          + " ".join(f"{v:.4g}" for v in each), flush=True)
    return {names[0]: float(np.sort(each)[(len(each) - 1) // 2]),
            names[1]: float(each.max())}


def compare_numbers(cfg: dict, ref, served: dict) -> dict:
    out = _distances("logits", served["logits"], ref[0])
    out.update(_distances("mtp_logits", served["logits_mtp"], ref[1]))
    if "greedy" in served:
        v0 = int(cfg.get("share", {}).get("vocab0", 0))
        out["greedy_mismatch"] = float(sum(
            np.sum(np.asarray(served[ids]).reshape(-1)
                   != np.asarray(served[logits]).argmax(-1) + v0)
            for ids, logits in (("greedy", "logits"),
                                ("greedy_mtp", "logits_mtp"))))
    return out


def _rows(cfg: dict, numbers: dict) -> list:
    return [{"name": k, "value": v, "limit": float(cfg["limits"][k])}
            for k, v in numbers.items()]


def check(cfg: dict, seed: int, frames, served: dict) -> list:
    t0 = time.perf_counter()
    ref = raw_outputs(cfg, seed, frames)
    print(f"[bench] reference forward of {len(ref[0])} histories took "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    return _rows(cfg, compare_numbers(cfg, ref, served))


def control(cfg: dict, seed: int, frames) -> list:
    ref = raw_outputs(cfg, seed, frames)
    low = raw_outputs(cfg, seed, frames, lower=True)
    return _rows(cfg, compare_numbers(cfg, ref, {"logits": low[0],
                                                 "logits_mtp": low[1]}))
