"""Plain float32 reference of the Falcon-H1-34B stage, the comparison
that decides ``correct`` for its cell, and the control.

Written from ``benchmark/configs/falcon_h1_34b_stage4_vocab8.json`` (the
model's public ``config.json`` with the stated cut) and the equations of
the issue that added it: straightforward ``jax.numpy`` at
``precision=HIGHEST``.  With ``rms(z; g) = z / sqrt(mean z^2 + eps) g``::

    x0 = m_emb Embed[id]
    u  = rms(x; g_in)
    x  = x + m_so Mamba2(m_si u) + m_ao Attn(m_ai u)
    w  = rms(x; g_ff)
    x  = x + m_d W_down( silu(m_g W_gate w) * W_up w )
    logits = m_head W_head rms(x_last; g_f)

``Mamba2(u)``: ``[z | x | B | C | dt] = (u W_in) * mu``, widths ``d_ssm
| d_ssm | groups x state | groups x state | heads``, ``mu`` the five
``ssm_multipliers`` each over its segment; ``[x | B | C]`` through a
causal depthwise convolution written as the sum of ``mamba_d_conv``
shifted inputs (with bias) and SiLU; head ``h`` reads group ``h //
(heads / groups)``; ``delta = softplus(dt + dt_bias)``, ``a =
exp(-delta exp(A_log))``; token by token ``S = a S + delta x (x) B``,
``y = S C + D x``; ``rms over each group of (y silu(z))`` times its gain
(the gate BEFORE the norm), through ``W_out``.

``Attn(z)``: ``q = rope(z W_q)``, ``k = rope(m_k z W_k)``, ``v = z
W_v``; query head ``h`` reads key/value head ``h // (heads / kv
heads)``; rotation of pairs ``(i, i + head_dim / 2)`` by ``position
theta^(-2i / head_dim)``; position ``p`` sees ``0 .. p``, scale
``head_dim^-1/2``, an explicit masked softmax in blocks of queries.

No cache, no chunk, no snapshot, no kernel: the recurrence is a
``lax.scan`` over the tokens of a frame's WHOLE history from a zero
state (not the chunked form the program prefills with).  It imports
nothing of the program and makes its own weights from the seed a layer
at a time (``benchmark/weights``, bf16 values upcast), over the same
slice of the vocabulary.  Departures from the published model are the
configuration's ``assumed``.

A sampled frame is one token of one stream at one ring slot.  Its
history follows from the seed (``benchmark/inputs``: the stream's prompt
and the ring's ids up to that slot), and the reference runs a full
forward over that history and reads the logits after its last token: a
frame is right only if the chunked prefill of both kinds of state, the
snapshot, the restore at each pass's first step and every kernel step
since left what the plain forward computes.  Every history is padded to
one length, so that one set of programs serves all of them; causality
keeps the padding out of the result.

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
forward): ``attn_unnormed`` feeds attention ``x`` instead of
``rms(x)``; ``gate_after_norm`` norms ``y`` before it gates it.
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
    eps = float(cfg["rms_norm_eps"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, theta = int(cfg["head_dim"]), float(cfg["rope_theta"])
    mh, mp = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    groups, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    kernel = int(cfg["mamba_d_conv"])
    d_ssm, gn = mh * mp, groups * n
    m_emb, m_head = (float(cfg[k]) for k in ("embedding_multiplier",
                                             "lm_head_multiplier"))
    m_ai, m_ao, m_k = (float(cfg[k]) for k in (
        "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier"))
    m_si, m_so = (float(cfg[k]) for k in ("ssm_in_multiplier",
                                          "ssm_out_multiplier"))
    m_g, m_d = (float(v) for v in cfg["mlp_multipliers"])
    mu = np.repeat(np.asarray(cfg["ssm_multipliers"], np.float32),
                   (d_ssm, d_ssm, gn, gn, mh))

    def q8(a):
        a = a.astype(jnp.float32)
        if not lower:
            return a
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def mm(x, w):
        return jnp.matmul(q8(x), q8(w), precision=hi)

    def rms(x, gain):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain

    def rope(x, positions):
        """Pairs ``(i, i + d/2)`` of the last axis, turned by ``position
        x theta^(-2i/d)``; ``x [T, heads, d]``."""
        half = d // 2
        freq = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64)
                                     / half), jnp.float32)
        angle = positions.astype(jnp.float32)[:, None, None] * freq
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                b * jnp.cos(angle) + a * jnp.sin(angle)], -1)

    @jax.jit
    def embed(table, ids):
        return m_emb * table.astype(jnp.float32)[ids]

    @jax.jit
    def normed(gain, x):
        return rms(x, gain)

    @jax.jit
    def mamba(p, u):
        """``Mamba2(m_si u)`` over a whole history ``u [T, hidden]`` from
        a zero state, the recurrence token by token."""
        steps = u.shape[0]
        zxbcdt = mm(m_si * u, p["in_proj"]) * mu
        z = zxbcdt[:, :d_ssm]
        xbc = zxbcdt[:, d_ssm:2 * d_ssm + 2 * gn]
        dt = zxbcdt[:, 2 * d_ssm + 2 * gn:]
        past = jnp.concatenate([jnp.zeros((kernel - 1, xbc.shape[1])), xbc])
        xbc = jax.nn.silu(p["conv_b"] + sum(
            past[k:k + steps] * p["conv_w"][k] for k in range(kernel)))
        xs = xbc[:, :d_ssm].reshape(steps, mh, mp)
        # head h reads group h // (heads / groups)
        b = jnp.repeat(xbc[:, d_ssm:d_ssm + gn].reshape(steps, groups, n),
                       mh // groups, axis=1)
        c = jnp.repeat(xbc[:, d_ssm + gn:].reshape(steps, groups, n),
                       mh // groups, axis=1)
        delta = jax.nn.softplus(dt + p["dt_bias"])               # [T, heads]
        a = jnp.exp(-delta * jnp.exp(p["A_log"]))

        def token(s, t):
            a_t, dx_t, b_t, c_t = t
            s = a_t[:, None, None] * s + dx_t[:, :, None] * b_t[:, None, :]
            return s, jnp.sum(s * c_t[:, None, :], axis=-1)

        _, y = lax.scan(token, jnp.zeros((mh, mp, n), jnp.float32),
                        (a, delta[:, :, None] * xs, b, c))
        y = (y + p["D"][:, None] * xs).reshape(steps, d_ssm)

        def grouped_norm(v):
            g = v.reshape(steps, groups, -1)
            g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
            return g.reshape(steps, d_ssm) * p["gate_norm"]

        if "gate_after_norm" in faults:
            g = grouped_norm(y) * jax.nn.silu(z)
        else:
            g = grouped_norm(y * jax.nn.silu(z))
        return mm(g, p["out_proj"])

    @jax.jit
    def keys_values(p, z):
        positions = jnp.arange(z.shape[0])
        k = (m_k * mm(m_ai * z, p["k"])).reshape(-1, nkv, d)
        return rope(k, positions), mm(m_ai * z, p["v"]).reshape(-1, nkv, d)

    @functools.partial(jax.jit, static_argnames=("rows",))
    def attend(p, z, k, v, first, rows):
        """Rows ``[first, first + rows)`` of ``Attn(m_ai z)``."""
        zq = lax.dynamic_slice_in_dim(z, first, rows)
        positions = first + jnp.arange(rows)
        q = rope(mm(m_ai * zq, p["q"]).reshape(rows, nh, d), positions) \
            .reshape(rows, nkv, nh // nkv, d)
        s = jnp.einsum("qgjd,kgd->gjqk", q8(q), q8(k), precision=hi) \
            * d ** -0.5
        seen = jnp.arange(z.shape[0])[None, :] <= positions[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf),
                              axis=-1)
        o = jnp.einsum("gjqk,kgd->qgjd", q8(prob), q8(v), precision=hi)
        return mm(o.reshape(rows, nh * d), p["o"])

    @jax.jit
    def mix_mlp(p, x, m, a):
        """The layer's rest for some rows: the two branches under their
        multipliers, then the MLP."""
        x = x + m_so * m + m_ao * a
        w = rms(x, p["mlp_norm"])
        h = jax.nn.silu(m_g * mm(w, p["mlp"]["gate"])) * mm(w, p["mlp"]["up"])
        return x + m_d * mm(h, p["mlp"]["down"])

    @jax.jit
    def head(gain, w, x):
        return m_head * mm(rms(x, gain), w)

    return dict(embed=embed, normed=normed, mamba=mamba,
                keys_values=keys_values, attend=attend, mix_mlp=mix_mlp,
                head=head)


@functools.lru_cache(maxsize=8)
def _built(cfg_key: str, lower: bool, faults: tuple):
    return _build(json.loads(cfg_key), lower, faults)


def forward_last(cfg: dict, seed: int, histories: list,
                 lower: bool = False, faults: tuple = ()) -> np.ndarray:
    """Logits ``[n, vocab]`` after the last token of each history (an
    int array of ids), float32.  Layer by layer over all the histories,
    so that each layer's weights are made once.  In the last layer the
    recurrence still runs over the whole history; attention and the MLP
    compute the last row only."""
    import jax
    import jax.numpy as jnp

    weights = _sibling("weights", cfg["weights"])
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str, list))
            and k not in ("limits_why", "assumed")}
    fns = _built(json.dumps(keep, sort_keys=True), bool(lower),
                 tuple(sorted(faults)))
    depth = int(cfg["num_hidden_layers"])
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
    for i in range(depth):
        p = f32(weights.make_part(cfg, seed, f"layer{i:02d}"))
        last = i == depth - 1
        for j, (x_host, length) in enumerate(zip(xs, lengths)):
            x = jnp.asarray(x_host)
            u = fns["normed"](p["norm"], x)
            m = fns["mamba"](p["mamba"], u)
            z = x if "attn_unnormed" in faults else u
            k, v = fns["keys_values"](p["attn"], z)
            if last:
                rows = slice(length - 1, length)
                a = fns["attend"](p["attn"], z, k, v, length - 1, rows=1)
            else:
                rows = slice(None)
                a = jnp.concatenate([
                    fns["attend"](p["attn"], z, k, v, first, rows=QUERY_BLOCK)
                    for first in range(0, t_pad, QUERY_BLOCK)])
            xs[j] = np.asarray(fns["mix_mlp"](p, x[rows], m[rows], a))
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
