"""Plain float32 reference of the LongCat-Flash share, the comparison that
decides ``correct`` for its cell, and the control.

Written from ``benchmark/configs/longcat_flash_omni_share64.json`` (the
language model's public ``config.json`` with the stated cut, and what
the config leaves open under ``assumed``) and the equations of the issue
that added it: straightforward ``jax.numpy`` at ``precision=HIGHEST``.
With ``rms(z; g) = g z / sqrt(mean(z^2) + 1e-5)``, a layer on its input
``x``::

    a0 = x  + MLA_0( rms(x;  g_in0) )
    u  =      rms(a0; g_post0)
    m  =      MoE(u)                      # rejoins at the layer's end
    b0 = a0 + MLP_0(u)
    a1 = b0 + MLA_1( rms(b0; g_in1) )
    v  =      rms(a1; g_post1)
    y  = a1 + MLP_1(v) + m

    MLP(z) = W_down( silu(W_gate z) * W_up z )
    MLA(z): c_q = s_q rms(W_qa z; g_qa), s_q = sqrt(hidden / q_lora_rank)
            [q_nope_h | q_rope_h] = W_qb c_q, q_rope_h rotated
            [c | k_r] = W_kva z;  c~ = s_kv rms(c; g_kva),
            s_kv = sqrt(hidden / kv_lora_rank);  k_r rotated, not scaled
            [k_nope_h | v_h] = W_kvb c~
            scores (q_nope_h k_nope_h + q_rope_h k_r) / sqrt(192), causal,
            softmax; W_o on the heads' sum of p v
    MoE(u): p = softmax(W_r u) over real and zero-compute experts alike;
            chosen = the 12 largest of p + b;  w_e = 6 p_e, not
            normalised;  sum over chosen AND held real e of w_e
            SwiGLU_e(u), plus (sum over chosen zero-compute e of w_e) u
    logits = rms(y_L; g_f) W_head

The EXPANDED form of latent attention only (every head's keys and
values rebuilt from the latent rows, an explicit masked softmax), no
cache, no kernel, no absorbed products, no sorted expert product: an
expert runs on the rows routed to it, picked out on the host.  It
imports nothing of the program and makes its own weights from the seed a
layer at a time (``benchmark/weights``, bf16 values upcast; two copies
of the weights do not fit a chip).  The chip's share is the program's:
experts ``expert0 .. expert0 + n_routed_experts`` of the router's
published 512 + 256 and rows ``vocab0 ..`` of the vocabulary; what
absent experts would add is left out.

A sampled frame is one token of one stream at one ring slot.  Its
history follows from the seed (``benchmark/inputs``: the stream's prompt
and the ring's ids up to that slot), and the reference runs a full
causal forward over that history and reads the logits after its last
token.  Every history is padded to the traffic's longest (its longest
prompt and a whole answer), whatever the sample drew, so that one set of
programs serves every history of every seed and a machine's compile
cache serves every later process; causality keeps the padding out of
the result.

What is compared is what the timed path served:

``logits_rel_l2_lower_median``  the largest of the better half of the
                          sampled frames' ||served - reference|| /
                          ||reference|| (the 4th smallest of 8): at most
                          half the sample may lie over the limit.
                          Routing is discontinuous: an expert chosen on
                          a near tie in bfloat16 may differ from
                          float32's choice, and that frame then sits
                          further off than the rest.  A lower precision
                          moves EVERY frame, so this order statistic
                          tells the two apart.
``logits_rel_l2_worst``   the largest of them.
``held_experts_part_off`` how far the HELD experts' part of what was
                          served is from the reference's.  With
                          ``ref0`` the same forward without the held
                          experts (``no_held_experts``) and ``d = ref -
                          ref0`` what they add to a frame's logits, it
                          is ``|1 - sum <served - ref0, d> / sum <d,
                          d>|`` over the sampled frames.  The held
                          experts get 1/96 of the picks at the cell's
                          size and move a frame's logits by about what
                          bfloat16 does, so no distance can tell their
                          part dropped from rounding; rounding spread
                          over thousands of logits has next to no
                          component ALONG ``d``, though: a sound run
                          reads near 0, a program that drops the part
                          1, one that does half of it 0.5.
``greedy_mismatch``       frames whose served greedy id is not the
                          argmax of their served logits

The control is the same forward with every matrix product's inputs and
weights rounded to float8_e4m3fn, the nearest precision below the
configuration's bfloat16.

``faults`` (:func:`forward_last`) leave one part of the mathematics out
or do it wrongly, for the tests that show the comparison sees each part:
``no_held_experts``, ``no_zero_term``, ``one_cache`` (sub-block 1 reads
sub-block 0's keys and values), ``no_kv_scale``, ``early_rejoin`` (the
branch added after the first sub-block).  A run of the benchmark passes
one: ``check`` runs the forward a second time with ``no_held_experts``,
for ``held_experts_part_off``.
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
#: an expert's rows are padded to a multiple of this: about four times
#: what a held expert of the cell gets from a padded history (4,096 x 12
#: / 768), so that nearly every expert of every history runs the one
#: program
ROW_BUCKET = 256


def _sibling(kind: str, name: str):
    path = os.path.join(os.path.dirname(_HERE), kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}_for_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(cfg: dict, lower: bool, kv_scale: bool):
    """The forward's pieces, jitted: one per kind of work.  (The one
    fault that changes a piece is ``no_kv_scale``; the others leave a
    piece out, so a forward with one of them runs these same programs.)"""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["rms_norm_eps"])
    hidden, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope, vd = (int(cfg[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    q_rank, rank = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    top_k = int(cfg["moe_topk"])
    scaling = float(cfg["routed_scaling_factor"])
    s_q = (hidden / q_rank) ** 0.5 if cfg.get("mla_scale_q_lora") else 1.0
    s_kv = (hidden / rank) ** 0.5 if cfg.get("mla_scale_kv_lora") \
        and kv_scale else 1.0
    inv_freq = (1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, rope, 2, dtype=np.float64) / rope)).astype(np.float32)

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
        # pairs (i, i + rope/2): a relabelling of columns under seeded
        # weights (``assumed``)
        angle = positions.astype(jnp.float32)[:, None] * inv_freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        if x.ndim == 3:
            cos, sin = cos[:, None], sin[:, None]
        a, b = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def swiglu(p, z):
        return mm(jax.nn.silu(mm(z, p["gate"])) * mm(z, p["up"]), p["down"])

    @jax.jit
    def embed(table, ids):
        return table.astype(jnp.float32)[ids]

    @jax.jit
    def keys_values(p, gain, x):
        """Every row's keys and values: (k_nope, k_rope, v)."""
        kv = mm(rms(x, gain), p["kv_a"])
        c = s_kv * rms(kv[:, :rank], p["kv_a_norm"])
        k_r = rotate(kv[:, rank:], jnp.arange(x.shape[0]))
        up = mm(c, p["kv_b"]).reshape(-1, nh, nope + vd)
        return up[..., :nope], k_r, up[..., nope:]

    @functools.partial(jax.jit, static_argnames=("rows",))
    def attend(p, gain, x, k_nope, k_r, v, first, rows):
        """Rows ``[first, first + rows)`` of ``x + MLA(rms(x))``."""
        xq = lax.dynamic_slice_in_dim(x, first, rows)
        positions = first + jnp.arange(rows)
        c_q = s_q * rms(mm(rms(xq, gain), p["q_a"]), p["q_a_norm"])
        qq = mm(c_q, p["q_b"]).reshape(rows, nh, nope + rope)
        q_nope, q_rope = qq[..., :nope], rotate(qq[..., nope:], positions)
        s = (jnp.einsum("qhd,khd->hqk", q8(q_nope), q8(k_nope), precision=hi)
             + jnp.einsum("qhd,kd->hqk", q8(q_rope), q8(k_r), precision=hi)) \
            * (nope + rope) ** -0.5
        causal = jnp.arange(x.shape[0])[None, :] <= positions[:, None]
        prob = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q8(prob), q8(v), precision=hi)
        return xq + mm(o.reshape(rows, nh * vd), p["o"])

    @jax.jit
    def normed(gain, x):
        return rms(x, gain)

    @jax.jit
    def mlp(p, z):
        return swiglu(p, z)

    @jax.jit
    def routing(router, bias, u):
        """The 12 largest of softmax score + bias over real and
        zero-compute experts alike, weighted by their scores alone
        times the scaling factor, not normalised."""
        score = jax.nn.softmax(jnp.matmul(
            u, router.astype(jnp.float32), precision=hi), axis=-1)
        _, idx = lax.top_k(score + bias, top_k)
        return idx, scaling * jnp.take_along_axis(score, idx, axis=-1)

    @jax.jit
    def expert_rows(p, e, u, rows, weight, y):
        """``y`` plus expert ``e``'s weighted output on ``rows`` of ``u``
        (a row index past the end adds nothing)."""
        ue = u.at[rows].get(mode="fill", fill_value=0.0)
        out = swiglu({name: w[e] for name, w in p.items()}, ue)
        return y.at[rows].add(weight[:, None] * out, mode="drop")

    @jax.jit
    def head(gain, w, x):
        return mm(rms(x, gain), w)

    return dict(embed=embed, keys_values=keys_values, attend=attend,
                normed=normed, mlp=mlp, routing=routing,
                expert_rows=expert_rows, head=head)


@functools.lru_cache(maxsize=8)
def _built(cfg_key: str, lower: bool, kv_scale: bool):
    return _build(json.loads(cfg_key), lower, kv_scale)


def _branch(fns, cfg: dict, p, u, faults: frozenset):
    """``MoE(u)``: the held real experts' weighted part, each expert on
    the rows routed to it, and the zero-compute picks' part of ``u``."""
    import jax.numpy as jnp

    idx, weight = fns["routing"](p["router"], p["router_bias"], u)
    idx, weight = np.asarray(idx), np.asarray(weight)
    n_real = int(cfg.get("published", {}).get("n_routed_experts",
                                              cfg["n_routed_experts"]))
    e0 = int(cfg.get("share", {}).get("expert0", 0))
    zero = np.where(idx >= n_real, weight, 0.0).sum(-1)
    if "no_zero_term" in faults:
        zero = np.zeros_like(zero)
    y = jnp.asarray(zero, jnp.float32)[:, None] * u
    held = 0 if "no_held_experts" in faults else int(cfg["n_routed_experts"])
    for e in range(held):
        rows, col = np.nonzero(idx == e0 + e)
        if not len(rows):
            continue
        pad = -len(rows) % ROW_BUCKET
        rows_p = np.concatenate([rows, np.full(pad, u.shape[0])])
        w_p = np.concatenate([weight[rows, col], np.zeros(pad, np.float32)])
        y = fns["expert_rows"](p["experts"], e, u,
                               jnp.asarray(rows_p, jnp.int32),
                               jnp.asarray(w_p, jnp.float32), y)
    return y


def _attention(fns, p, gain, x, kv, length: int, last: bool):
    """``x + MLA(rms(x; gain))`` on keys and values ``kv``: every row,
    or where ``last`` only the row of the history's last token."""
    import jax.numpy as jnp

    if last:
        return fns["attend"](p, gain, x, *kv, length - 1, rows=1)
    return jnp.concatenate([
        fns["attend"](p, gain, x, *kv, first, rows=QUERY_BLOCK)
        for first in range(0, x.shape[0], QUERY_BLOCK)])


def _layer(fns, cfg: dict, p, x, length: int, last: bool,
           faults: frozenset):
    """One layer on a padded history ``x``: every row, or where ``last``
    only the row of the history's last token (``[1, hidden]``)."""
    kv0 = fns["keys_values"](p["attn"][0], p["attn_norm"][0], x)
    a0 = _attention(fns, p["attn"][0], p["attn_norm"][0], x, kv0, length,
                    False)
    u = fns["normed"](p["mlp_norm"][0], a0)
    m = _branch(fns, cfg, p["moe"], u, faults)
    b0 = a0 + fns["mlp"](p["mlp"][0], u)
    if "early_rejoin" in faults:
        b0, m = b0 + m, 0.0 * m
    kv1 = kv0 if "one_cache" in faults else fns["keys_values"](
        p["attn"][1], p["attn_norm"][1], b0)
    del kv0
    a1 = _attention(fns, p["attn"][1], p["attn_norm"][1], b0, kv1, length,
                    last)
    del kv1
    if last:
        m = m[length - 1:length]
    v = fns["normed"](p["mlp_norm"][1], a1)
    return a1 + fns["mlp"](p["mlp"][1], v) + m


def _padded_length(cfg: dict, lengths: list) -> int:
    """The traffic's longest history in whole query blocks (or the
    longest given, where the configuration states no traffic)."""
    longest = max(lengths)
    serving = cfg.get("serving")
    if serving:
        longest = max(longest, int(serving["prompt_tokens"][-1])
                      + int(serving["answer_tokens"]))
    return -(-longest // QUERY_BLOCK) * QUERY_BLOCK


def forward_last(cfg: dict, seed: int, histories: list,
                 lower: bool = False, faults=()) -> np.ndarray:
    """Logits ``[n, vocab held]`` after the last token of each history
    (an int array of global ids), float32.  Layer by layer over all the
    histories, so that each layer's weights are made once."""
    import jax
    import jax.numpy as jnp

    weights = _sibling("weights", cfg["weights"])
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, dict)) and k != "limits"}
    faults = frozenset(faults)
    fns = _built(json.dumps(keep, sort_keys=True), bool(lower),
                 "no_kv_scale" not in faults)
    v0 = int(cfg.get("share", {}).get("vocab0", 0))
    depth = int(cfg["num_layers"])
    lengths = [len(h) for h in histories]
    t_pad = _padded_length(cfg, lengths)

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
        xs = [np.asarray(_layer(fns, cfg, p, jnp.asarray(x), length,
                                i == depth - 1, faults))
              for x, length in zip(xs, lengths)]
        del p
    tail = weights.make_part(cfg, seed, "head")
    return np.concatenate([
        np.asarray(fns["head"](tail["final_norm"], tail["head"],
                               jnp.asarray(x))) for x in xs])


#: the newest frames' float32 results by fault, so that ``control``
#: after ``check`` on the same frames (``benchmark/control.py``) runs
#: neither float32 forward again
_newest: dict = {}

HELD_OUT = ("no_held_experts",)


def raw_outputs(cfg: dict, seed: int, frames, lower: bool = False,
                faults=()):
    """Reference logits of the sampled frames ``(ids, positions)``."""
    key = (json.dumps(cfg, sort_keys=True), int(seed),
           np.asarray(frames[0]).tobytes(), np.asarray(frames[1]).tobytes())
    if _newest.get("key") != key:
        _newest.clear()
        _newest.update(key=key, logits={})
    kept = _newest["logits"]
    if not lower and tuple(faults) in kept:
        return kept[tuple(faults)]
    inputs = _sibling("inputs", cfg["inputs"])
    where = inputs.locate(cfg, seed, frames[0], frames[1])
    logits = forward_last(cfg, seed, [inputs.history(cfg, seed, j, r)
                                      for j, r in where], lower, faults)
    if not lower:
        kept[tuple(faults)] = logits
    return logits


def compare_numbers(cfg: dict, ref_logits, served: dict,
                    without_held=None) -> dict:
    """The numbers compared; ``without_held`` is the reference's logits
    with the held experts' part left out, and with it comes
    ``held_experts_part_off``."""
    got = np.asarray(served["logits"], np.float32)
    ref = np.asarray(ref_logits, np.float32)
    names = ["logits_rel_l2_lower_median", "logits_rel_l2_worst"]
    if without_held is not None:
        names.append("held_experts_part_off")
    if got.shape != ref.shape or not np.isfinite(got).all():
        return dict.fromkeys(names, float("inf"))
    each = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    print("[bench] logits_rel_l2 by frame: "
          + " ".join(f"{v:.4g}" for v in each), flush=True)
    out = {names[0]: float(np.sort(each)[(len(each) - 1) // 2]),
           names[1]: float(each.max())}
    if without_held is not None:
        ref0 = np.asarray(without_held, np.float32)
        part = (ref - ref0).astype(np.float64)
        along = np.sum((got - ref0) * part, axis=-1)
        size = np.sum(part * part, axis=-1)
        print("[bench] held experts' part by frame, |ref - ref0| / |ref|: "
              + " ".join(f"{v:.4g}" for v in np.sqrt(size)
                         / np.linalg.norm(ref, axis=-1))
              + "; served along it: "
              + " ".join(f"{v:.4g}" for v in along / np.maximum(size, 1e-30)),
              flush=True)
        out[names[2]] = float(abs(1.0 - along.sum() / size.sum())) \
            if size.sum() > 0 else float("inf")
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
    ref0 = raw_outputs(cfg, seed, frames, faults=HELD_OUT)
    print(f"[bench] two reference forwards of {len(ref)} histories (the "
          f"second without the held experts) took "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    return _rows(cfg, compare_numbers(cfg, ref, served, ref0))


def control(cfg: dict, seed: int, frames) -> list:
    ref = raw_outputs(cfg, seed, frames)
    ref0 = raw_outputs(cfg, seed, frames, faults=HELD_OUT)
    low = raw_outputs(cfg, seed, frames, lower=True)
    return _rows(cfg, compare_numbers(cfg, ref, {"logits": low}, ref0))
