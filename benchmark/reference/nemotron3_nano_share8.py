"""Plain float32 reference of the Nemotron-3-Nano share, the comparison
that decides ``correct`` for its cell, and the control.

Written from ``benchmark/configs/nemotron3_nano_share8.json`` (the
model's public ``config.json`` with the stated cut) and the equations of
the issue that added it: straightforward ``jax.numpy`` at
``precision=HIGHEST``.  Layer ``l`` of kind ``hybrid_override_pattern[l]``
on its input ``x`` is ``x + mixer(rms(x, g_l))``:

``M``  ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv1d(xBC))``
       (depthwise, causal, kernel 4, with bias); ``x [64, 64]``, ``B``
       and ``C`` ``[8, 128]``, head ``h`` reads group ``h // 8``;
       ``delta = softplus(dt + dt_bias)``, ``a = exp(-delta
       exp(A_log))``; token by token ``S = a S + delta x (x) B``, ``y =
       S C + D x``; ``rms over groups of 512 of (y silu(z))`` times its
       gain, through ``W_out``.
``E``  ``s = sigmoid(u W_r)``; the 6 largest of ``s + bias`` are
       chosen, weighted ``2.5 s / sum of the chosen s``; expert ``e``
       is ``relu(u W_up[e])^2 W_down[e]``; the shared expert likewise,
       unweighted.  Of the routed experts only those HELD
       (``[expert0, expert0 + n_routed_experts)``) are added: the share
       the program computes.
``*``  32 query heads over 2 key/value heads of 128, no rotation,
       position ``p`` sees ``0 .. p``, scale ``128^-1/2``.

No cache, no chunk, no snapshot, no kernel, no sorted expert product:
the recurrence is a ``lax.scan`` over the tokens of a frame's WHOLE
history from a zero state, attention an explicit causal softmax, and an
expert runs on the rows routed to it, picked out on the host.  It
imports nothing of the program and makes its own weights from the seed
a layer at a time (``benchmark/weights``, bf16 values upcast).
Departures from the published model are the configuration's
``assumed``.

A sampled frame is one token of one stream at one ring slot.  Its
history follows from the seed (``benchmark/inputs``: the stream's prompt
and the ring's ids up to that slot), and the reference runs a full
forward over that history and reads the logits after its last token.
Every history is padded to one length, so that one set of programs
serves all of them; causality keeps the padding out of the result.

What is compared is what the timed path served, the statistics of the
other two token cells (``reference/smallthinker_21b_stage8.py``):

``logits_rel_l2_lower_median``  the largest of the better half of the
                          sampled frames' ||served - reference|| /
                          ||reference|| (the 4th smallest of 8)
``logits_rel_l2_worst``   the largest of them: a cap under what a frame
                          of zeros (1.0) or another stream's logits
                          (1.4) read
``greedy_mismatch``       frames whose served greedy id is not the
                          argmax of their served logits

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
QUERY_BLOCK = 512
ROW_BUCKET = 256


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
    eps = float(cfg["layer_norm_epsilon"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    mh, mp = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    groups, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    kernel = int(cfg["conv_kernel"])
    top_k = int(cfg["num_experts_per_tok"])
    scaling = float(cfg["routed_scaling_factor"])
    d_inner, gn = mh * mp, groups * n

    def q8(a):
        a = a.astype(jnp.float32)
        if not lower:
            return a
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def mm(x, w):
        return jnp.matmul(q8(x), q8(w), precision=hi)

    def rms(x, gain):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain

    @jax.jit
    def embed(table, ids):
        return table.astype(jnp.float32)[ids]

    @jax.jit
    def mamba(p, x):
        """``x + Mamba-2(rms(x))`` over a whole history ``[T, hidden]``
        from a zero state, the recurrence token by token."""
        steps = x.shape[0]
        zxbcdt = mm(rms(x, p["norm"]), p["in_proj"])
        z = zxbcdt[:, :d_inner]
        xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * gn]
        dt = zxbcdt[:, 2 * d_inner + 2 * gn:]
        past = jnp.concatenate([jnp.zeros((kernel - 1, xbc.shape[1])), xbc])
        xbc = jax.nn.silu(p["conv_b"] + sum(
            past[k:k + steps] * p["conv_w"][k] for k in range(kernel)))
        xs = xbc[:, :d_inner].reshape(steps, mh, mp)
        # head h reads group h // (heads / groups)
        b = jnp.repeat(xbc[:, d_inner:d_inner + gn].reshape(steps, groups, n),
                       mh // groups, axis=1)
        c = jnp.repeat(xbc[:, d_inner + gn:].reshape(steps, groups, n),
                       mh // groups, axis=1)
        delta = jax.nn.softplus(dt + p["dt_bias"])               # [T, heads]
        a = jnp.exp(-delta * jnp.exp(p["A_log"]))

        def token(s, t):
            a_t, dx_t, b_t, c_t = t
            s = a_t[:, None, None] * s + dx_t[:, :, None] * b_t[:, None, :]
            return s, jnp.sum(s * c_t[:, None, :], axis=-1)

        _, y = lax.scan(token, jnp.zeros((mh, mp, n), jnp.float32),
                        (a, delta[:, :, None] * xs, b, c))
        y = (y + p["D"][:, None] * xs).reshape(steps, d_inner) \
            * jax.nn.silu(z)
        g = y.reshape(steps, groups, -1)
        g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
        return x + mm(g.reshape(steps, d_inner) * p["gate_norm"],
                      p["out_proj"])

    @jax.jit
    def keys_values(p, x):
        u = rms(x, p["norm"])
        return (mm(u, p["k"]).reshape(-1, nkv, d),
                mm(u, p["v"]).reshape(-1, nkv, d))

    @functools.partial(jax.jit, static_argnames=("rows",))
    def attend(p, x, k, v, first, rows):
        """Rows ``[first, first + rows)`` of ``x + attention(rms(x))``."""
        xq = lax.dynamic_slice_in_dim(x, first, rows)
        positions = first + jnp.arange(rows)
        q = mm(rms(xq, p["norm"]), p["q"]).reshape(rows, nkv, nh // nkv, d)
        s = jnp.einsum("qgjd,kgd->gjqk", q8(q), q8(k), precision=hi) \
            * d ** -0.5
        seen = jnp.arange(x.shape[0])[None, :] <= positions[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf),
                              axis=-1)
        o = jnp.einsum("gjqk,kgd->qgjd", q8(prob), q8(v), precision=hi)
        return xq + mm(o.reshape(rows, nh * d), p["o"])

    @jax.jit
    def routing(p, x):
        """The 6 largest of sigmoid score + bias; weights from the
        scores alone, normalised and scaled."""
        score = jax.nn.sigmoid(jnp.matmul(
            rms(x, p["norm"]), p["router"].astype(jnp.float32),
            precision=hi))
        _, idx = lax.top_k(score + p["router_bias"], top_k)
        kept = jnp.take_along_axis(score, idx, axis=-1)
        return idx, scaling * kept / jnp.sum(kept, -1, keepdims=True)

    def relu2_mlp(up, down, u):
        return mm(jnp.square(jax.nn.relu(mm(u, up))), down)

    @jax.jit
    def shared(p, x):
        return x + relu2_mlp(p["shared"]["up"], p["shared"]["down"],
                             rms(x, p["norm"]))

    @jax.jit
    def expert_rows(p, e, x, rows, weight, y):
        """``y`` plus held expert ``e``'s weighted output on ``rows`` of
        ``x`` (a row index past the end adds nothing)."""
        u = rms(x, p["norm"]).at[rows].get(mode="fill", fill_value=0.0)
        out = relu2_mlp(p["experts"]["up"][e], p["experts"]["down"][e], u)
        return y.at[rows].add(weight[:, None] * out, mode="drop")

    @jax.jit
    def head(gain, w, x):
        return mm(rms(x, gain), w)

    return dict(embed=embed, mamba=mamba, keys_values=keys_values,
                attend=attend, routing=routing, shared=shared,
                expert_rows=expert_rows, head=head)


@functools.lru_cache(maxsize=4)
def _built(cfg_key: str, lower: bool):
    return _build(json.loads(cfg_key), lower)


def _moe(fns, cfg: dict, p, x, rows: slice):
    """``x + shared(x) + the HELD experts' weighted outputs`` for the
    rows of ``rows``; each expert runs on the rows routed to it."""
    import jax.numpy as jnp

    x = x[rows]
    idx, weight = (np.asarray(v) for v in fns["routing"](p, x))
    y = fns["shared"](p, x)
    first = int(cfg.get("share", {}).get("expert0", 0))
    for e in range(int(cfg["n_routed_experts"])):
        at, col = np.nonzero(idx == first + e)
        if not len(at):
            continue
        pad = -len(at) % ROW_BUCKET
        at_p = np.concatenate([at, np.full(pad, x.shape[0])])
        w_p = np.concatenate([weight[at, col], np.zeros(pad, np.float32)])
        y = fns["expert_rows"](p, e, x, jnp.asarray(at_p, jnp.int32),
                               jnp.asarray(w_p, jnp.float32), y)
    return y


def forward_last(cfg: dict, seed: int, histories: list,
                 lower: bool = False) -> np.ndarray:
    """Logits ``[n, vocab]`` after the last token of each history (an
    int array of ids), float32.  Layer by layer over all the histories,
    so that each layer's weights are made once.  In the last layer, where
    it keeps no recurrent state, only the last row is computed."""
    import jax
    import jax.numpy as jnp

    weights = _sibling("weights", cfg["weights"])
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str)) and k != "limits_why"}
    fns = _built(json.dumps(keep, sort_keys=True), bool(lower))
    depth = int(cfg["num_hidden_layers"])
    pattern = cfg["hybrid_override_pattern"][:depth]
    v0 = int(cfg.get("share", {}).get("vocab0", 0))
    lengths = [len(h) for h in histories]
    t_pad = -(-max(lengths) // QUERY_BLOCK) * QUERY_BLOCK

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    table = weights.make_part(cfg, seed, "embed")["embed"]
    xs = []
    for h in histories:
        ids = np.zeros(t_pad, np.int32)
        ids[:len(h)] = np.asarray(h) - v0
        xs.append(np.asarray(fns["embed"](table, jnp.asarray(ids))))
    del table
    for i, kind in enumerate(pattern):
        p = f32(weights.make_part(cfg, seed, f"layer{i:02d}"))
        last = i == depth - 1
        for j, (x_host, length) in enumerate(zip(xs, lengths)):
            x = jnp.asarray(x_host)
            final = slice(length - 1, length)
            if kind == "M":
                out = fns["mamba"](p, x)
                out = out[final] if last else out
            elif kind == "E":
                out = _moe(fns, cfg, p, x, final if last else slice(None))
            else:
                k, v = fns["keys_values"](p, x)
                if last:
                    out = fns["attend"](p, x, k, v, length - 1, rows=1)
                else:
                    out = jnp.concatenate([
                        fns["attend"](p, x, k, v, first, rows=QUERY_BLOCK)
                        for first in range(0, t_pad, QUERY_BLOCK)])
            xs[j] = np.asarray(out)
        del p
    tail = weights.make_part(cfg, seed, "head")
    # the last layer left each history's last row alone
    return np.concatenate([
        np.asarray(fns["head"](tail["final_norm"], tail["head"],
                               jnp.asarray(x))) for x in xs])


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


def compare_numbers(cfg: dict, ref_logits, served: dict) -> dict:
    got = np.asarray(served["logits"], np.float32)
    ref = np.asarray(ref_logits, np.float32)
    names = ("logits_rel_l2_lower_median", "logits_rel_l2_worst")
    if got.shape != ref.shape or not np.isfinite(got).all():
        return dict.fromkeys(names, float("inf"))
    each = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    print("[bench] logits_rel_l2 by frame: "
          + " ".join(f"{v:.4g}" for v in each), flush=True)
    out = {names[0]: float(np.sort(each)[(len(each) - 1) // 2]),
           names[1]: float(each.max())}
    if "greedy" in served:
        v0 = int(cfg.get("share", {}).get("vocab0", 0))
        out["greedy_mismatch"] = float(np.sum(
            np.asarray(served["greedy"]).reshape(-1) != got.argmax(-1) + v0))
    return out


def _rows(cfg: dict, numbers: dict) -> list:
    return [{"name": k, "value": v, "limit": float(cfg["limits"][k])}
            for k, v in numbers.items()]


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
