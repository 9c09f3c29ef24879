"""Plain float32 reference of the DeepSeek-V2 share, the comparison that
decides ``correct`` for its cells, and the control.

Written from ``benchmark/configs/deepseek_v2_share4.json`` (the model's
public ``config.json`` with the stated cut): straightforward
``jax.numpy`` at ``precision=HIGHEST``, the EXPANDED form of latent
attention only (every head's keys and values rebuilt from the latent
rows, an explicit causal softmax), no cache, no kernels, no absorbed
products, no sorted expert product: an expert runs on the rows routed to
it, picked out on the host.  It imports nothing of the program, makes
its own weights from the seed a layer at a time (``benchmark/weights``,
bf16 values upcast; two copies of the weights do not fit a chip), and is
given the same share: heads, experts and vocabulary rows held here, the
router over all the published experts, the partial sums passed on.

A sampled frame is one token of one stream at one ring slot.  Its
history follows from the seed (``benchmark/inputs``: the stream's prompt
and the ring's ids up to that slot), and the reference runs a full
causal forward over that history and reads the logits after its last
token.  Every history is padded to one length, so that one set of
programs serves all of them; causality keeps the padding out of the
result.  In the last layer only the last row is computed.

What is compared is what the timed path served:

``logits_rel_l2_lower_median``  the largest of the better half of the
                          sampled frames' ||served - reference|| /
                          ||reference|| (the 4th smallest of 8): at most
                          half the sample may lie over the limit.
                          Routing is discontinuous: an expert (or a
                          whole group) chosen on a near tie in bfloat16
                          may differ from float32's choice, and that
                          frame then sits ten to forty times further off
                          than the rest (about one frame in fourteen on
                          the chip, up to two of a run's eight).  A
                          lower precision moves EVERY frame, so this
                          order statistic tells the two apart where the
                          mean would fail a sound run with one such
                          frame, and a count of "at most three" one run
                          in eight hundred.
``logits_rel_l2_worst``   the largest of them: a cap between the largest
                          flipped frame seen (0.40) and what a frame of
                          zeros (1.0) or another stream's or step's
                          logits (1.4) read.  The control is not meant
                          to fail it.
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
import math
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


def yarn(cfg: dict) -> tuple:
    """``(inverse frequencies [rope/2], cos/sin scale, score scale)``."""
    r = cfg["rope_scaling"]
    dim, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    factor = float(r["factor"])
    orig = int(r["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(float(r["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(r["beta_slow"]))), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = extra / factor * ramp + extra * (1 - ramp)

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    q_head = int(cfg["qk_nope_head_dim"]) + dim
    return (inv_freq.astype(np.float32),
            mscale(float(r["mscale"])) / mscale(float(r["mscale_all_dim"])),
            q_head ** -0.5 * mscale(float(r["mscale_all_dim"])) ** 2)


def _build(cfg: dict, lower: bool):
    """The forward's pieces, jitted: one per kind of work."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["rms_norm_eps"])
    nh = int(cfg["num_attention_heads"])
    nope, rope, vd = (int(cfg[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rank = int(cfg["kv_lora_rank"])
    n_all = int(cfg.get("published", {}).get("n_routed_experts",
                                             cfg["n_routed_experts"]))
    groups, keep = int(cfg["n_group"]), int(cfg["topk_group"])
    top_k = int(cfg["num_experts_per_tok"])
    scaling = float(cfg["routed_scaling_factor"])
    inv_freq, rope_scale, score_scale = yarn(cfg)

    def q(a):
        a = a.astype(jnp.float32)
        if not lower:
            return a
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def mm(x, w):
        return jnp.matmul(q(x), q(w), precision=hi)

    def rms(x, gain):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain

    def rotate(x, positions):
        # pairs (i, i + rope/2): the layout the published code permutes
        # into before it rotates
        angle = positions.astype(jnp.float32)[:, None] * inv_freq
        cos, sin = jnp.cos(angle) * rope_scale, jnp.sin(angle) * rope_scale
        if x.ndim == 3:
            cos, sin = cos[:, None], sin[:, None]
        a, b = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def mlp(p, x):
        return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])

    @jax.jit
    def embed(table, ids):
        return table.astype(jnp.float32)[ids]

    @jax.jit
    def keys_values(p, gain, x):
        """Every row's keys and values: (k_nope, k_rope, v)."""
        xn = rms(x, gain)
        kv = mm(xn, p["kv_a"])
        c_kv = rms(kv[:, :rank], p["kv_a_norm"])
        k_r = rotate(kv[:, rank:], jnp.arange(x.shape[0]))
        up = mm(c_kv, p["kv_b"]).reshape(-1, nh, nope + vd)
        return up[..., :nope], k_r, up[..., nope:]

    @functools.partial(jax.jit, static_argnames=("rows",))
    def attend(p, gain, x, k_nope, k_r, v, first, rows):
        """Rows ``[first, first + rows)`` of ``x + attention(x)``."""
        xq = lax.dynamic_slice_in_dim(x, first, rows)
        positions = first + jnp.arange(rows)
        c_q = rms(mm(rms(xq, gain), p["q_a"]), p["q_a_norm"])
        qq = mm(c_q, p["q_b"]).reshape(rows, nh, nope + rope)
        q_nope, q_rope = qq[..., :nope], rotate(qq[..., nope:], positions)
        s = (jnp.einsum("qhd,khd->hqk", q(q_nope), q(k_nope), precision=hi)
             + jnp.einsum("qhd,kd->hqk", q(q_rope), q(k_r), precision=hi))
        causal = jnp.arange(x.shape[0])[None, :] <= positions[:, None]
        prob = jax.nn.softmax(jnp.where(causal[None], s * score_scale,
                                        -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q(prob), q(v), precision=hi)
        return xq + mm(o.reshape(rows, nh * vd), p["o"])

    @jax.jit
    def dense_mlp(p, gain, x):
        return x + mlp(p, rms(x, gain))

    @jax.jit
    def routing(router, gain, x):
        """group_limited_greedy over all the published experts."""
        prob = jax.nn.softmax(jnp.matmul(rms(x, gain),
                                         router.astype(jnp.float32),
                                         precision=hi), axis=-1)
        best = lax.top_k(prob.reshape(-1, groups, n_all // groups).max(-1),
                         keep)[1]
        kept = jnp.zeros((x.shape[0], groups), bool).at[
            jnp.arange(x.shape[0])[:, None], best].set(True)
        masked = jnp.where(jnp.repeat(kept, n_all // groups, axis=1),
                           prob, 0.0)
        weight, idx = lax.top_k(masked, top_k)
        return idx, weight * scaling

    @jax.jit
    def shared_part(p, gain, x):
        return x + mlp(p, rms(x, gain))

    @jax.jit
    def expert_rows(p, e, gain, x, rows, weight, y):
        """``y`` plus expert ``e``'s weighted output on ``rows`` of ``x``
        (a row index past the end adds nothing)."""
        one = {k: p[k][e] for k in ("gate", "up", "down")}
        xe = rms(x, gain).at[rows].get(mode="fill", fill_value=0.0)
        return y.at[rows].add(weight[:, None] * mlp(one, xe), mode="drop")

    @jax.jit
    def head(gain, w, x):
        return mm(rms(x, gain), w)

    return dict(embed=embed, keys_values=keys_values, attend=attend,
                dense_mlp=dense_mlp, routing=routing,
                shared_part=shared_part, expert_rows=expert_rows, head=head)


@functools.lru_cache(maxsize=4)
def _built(cfg_key: str, lower: bool):
    return _build(json.loads(cfg_key), lower)


def _moe(fns, cfg: dict, p, gain, x, n_valid: int):
    """``x + shared(x) + sum over the held experts`` for rows below
    ``n_valid``; each expert runs on the rows routed to it."""
    import jax.numpy as jnp

    idx, weight = fns["routing"](p["router"], gain, x)
    idx, weight = np.asarray(idx)[:n_valid], np.asarray(weight)[:n_valid]
    e0 = int(cfg.get("share", {}).get("expert0", 0))
    y = fns["shared_part"](p["shared"], gain, x)
    for e in range(int(cfg["n_routed_experts"])):
        rows, col = np.nonzero(idx == e0 + e)
        if not len(rows):
            continue
        pad = -len(rows) % ROW_BUCKET
        rows_p = np.concatenate([rows, np.full(pad, x.shape[0])])
        w_p = np.concatenate([weight[rows, col], np.zeros(pad, np.float32)])
        y = fns["expert_rows"](p["experts"], e, gain, x,
                               jnp.asarray(rows_p, jnp.int32),
                               jnp.asarray(w_p, jnp.float32), y)
    return y


def forward_last(cfg: dict, seed: int, histories: list,
                 lower: bool = False) -> np.ndarray:
    """Logits ``[n, vocab held]`` after the last token of each history
    (an int array of global ids), float32.  Layer by layer over all the
    histories, so that each layer's weights are made once."""
    import jax
    import jax.numpy as jnp

    weights = _sibling("weights", cfg["weights"])
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, dict)) and k != "limits"}
    fns = _built(json.dumps(keep, sort_keys=True), bool(lower))
    v0 = int(cfg.get("share", {}).get("vocab0", 0))
    depth = int(cfg["num_hidden_layers"])
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
        for n, (x_host, length) in enumerate(zip(xs, lengths)):
            x = jnp.asarray(x_host)
            k_nope, k_r, v = fns["keys_values"](p["attn"], p["attn_norm"], x)
            if last:
                x = fns["attend"](p["attn"], p["attn_norm"], x, k_nope, k_r,
                                  v, length - 1, rows=1)
                n_valid = 1
            else:
                x = jnp.concatenate([
                    fns["attend"](p["attn"], p["attn_norm"], x, k_nope, k_r,
                                  v, first, rows=QUERY_BLOCK)
                    for first in range(0, t_pad, QUERY_BLOCK)])
                n_valid = length
            del k_nope, k_r, v
            if "mlp" in p:
                x = fns["dense_mlp"](p["mlp"], p["mlp_norm"], x)
            else:
                x = _moe(fns, cfg, p["moe"], p["mlp_norm"], x, n_valid)
            xs[n] = np.asarray(x)
        del p
    tail = weights.make_part(cfg, seed, "head")
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
            np.asarray(served["greedy"]).reshape(-1) - v0
            != got.argmax(-1)))
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
