"""A prefill chunk's latent attention as a kernel (``ops/kernels.py``
``latent_prefill_attention``) against its reference, XLA's own loop over
the key blocks, interpreted on the CPU: at the start of a stream, at a
later chunk, with the diagonal inside a key block and with several query
blocks a key block, for two and four heads a grid step and both kinds of
cache row; what lies in the cache beyond the chunk is not read; which
shapes the kernel refuses, that ``models/mla.py`` ``attn_prefill`` takes
the loop for those, and that it says which it took.  No number here is a
rate."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import deepseek_v2 as dsv2
from nnstreamer_tpu.models import mla
from nnstreamer_tpu.ops import kernels
from nnstreamer_tpu.utils import profile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NOPE = VALUES = RANK = 128
SCALE = 0.11
#: rotary values a token -> positions a cache row holds: 64 pack two
#: (``[c_kv a | c_kv b | k_r a, k_r b]``, 384 values), 128 keep one a
#: row (``[c_kv | k_r]``, 256), 32 pack four (640)
ROWS = {"two a row": 64, "one a row": 128, "four a row": 32}


def _operands(rope, chunk, start, heads=4, total=1024, dtype=jnp.float32,
              beyond=np.nan):
    """A chunk's queries, ``W_kvb`` and a cache of 3 streams whose
    stream 1 holds tokens up to the chunk's end and ``beyond`` after it
    (the other streams hold ``beyond`` throughout)."""
    keys = jax.random.split(jax.random.PRNGKey(chunk + start + rope), 4)
    q_nope = jax.random.normal(keys[0], (chunk, heads, NOPE)).astype(dtype)
    q_rope = jax.random.normal(keys[1], (chunk, heads, rope)).astype(dtype)
    w_kvb = (jax.random.normal(keys[2], (RANK, heads, NOPE + VALUES))
             / np.sqrt(RANK)).astype(dtype)
    tokens = jax.random.normal(keys[3], (3, total, RANK + rope))
    live = (jnp.arange(total) < start + chunk)[None, :, None] \
        & (jnp.arange(3) == 1)[:, None, None]
    cache = kernels.latent_pack(
        jnp.where(live, tokens, beyond).astype(dtype), RANK)
    return q_nope, q_rope, cache, jnp.int32(1), jnp.int32(start), w_kvb


def _close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("group", [2, 4], ids=["2 heads", "4 heads"])
@pytest.mark.parametrize("row", ["two a row", "one a row"])
@pytest.mark.parametrize("start,kb,tq", [
    (0, 256, 64), (512, 256, 64), (512, 256, 256), (1024, 512, 128)],
    ids=["start", "later chunk, diagonal inside a key block",
         "one query block", "a key block a chunk"])
def test_the_kernel_is_the_loop(row, group, start, kb, tq):
    """The kernel's ``[C, heads, v]`` is the reference's to float32
    rounding: a chunk of 512 over key blocks of 256 has a key block
    whose later query blocks see it whole, one the diagonal passes
    through, and query blocks that lie wholly before a key block (which
    the kernel skips); the cache holds NaN beyond ``start + C``, in
    stream 1's later rows, in the dead blocks the grid still steps
    over, and in every other stream."""
    rope = ROWS[row]
    operands = _operands(rope, 512, start, total=2048)
    assert kernels.latent_cache_row(RANK, rope)[0] == {64: 2, 128: 1}[rope]
    want = kernels.latent_prefill_attention_reference(*operands, SCALE,
                                                      key_block=kb)
    got = kernels._latent_prefill(*operands, SCALE, group, tq, kb)
    assert got.shape == (512, 4, VALUES) and got.dtype == jnp.float32
    _close(got, want, 5e-6)
    # whatever the blocks, the same softmax
    _close(kernels.latent_prefill_attention_reference(*operands, SCALE),
           want, 5e-6)


@pytest.mark.parametrize("row,dtype,tol", [
    ("four a row", jnp.float32, 5e-6), ("two a row", jnp.bfloat16, 1e-2),
    ("one a row", jnp.bfloat16, 1e-2)])
def test_the_kernel_as_a_caller_calls_it(row, dtype, tol):
    """:func:`latent_prefill_attention` chooses its own tiling (here the
    chunk is one key block and its four heads two grid steps), in bf16
    as the cells run it and with four positions a row."""
    rope = ROWS[row]
    operands = _operands(rope, 512, 512, dtype=dtype, beyond=1e4)
    assert kernels.latent_prefill_attention_refusal(
        operands[0].shape, operands[1].shape, operands[2].shape,
        operands[5].shape, {jnp.dtype(dtype)}) is None
    assert kernels.latent_prefill_tiles(
        512, 4, RANK, rope, NOPE, VALUES, dtype) == (2, 512)
    got = kernels.latent_prefill_attention(*operands, SCALE)
    assert got.dtype == dtype
    _close(got, kernels.latent_prefill_attention_reference(*operands, SCALE),
           tol)


def test_the_tiling_follows_the_shapes_and_the_budget(monkeypatch):
    """Two heads a grid step and 512 query rows a pass at both cells'
    shapes (32 and 64 heads of a chunk of 2,048, bf16: 29 MB of fast
    memory); float32 operands take twice the blocks and still fit; an
    odd number of heads goes one a step; under a smaller budget the
    query rows give way first, then the heads."""
    cell = dict(c=2048, rank=512, rope=64, nope=128, v=128)
    for heads in (32, 64):
        assert kernels.latent_prefill_tiles(
            heads=heads, dtype=jnp.bfloat16, **cell) == (2, 512)
    assert kernels._prefill_vmem(2048, 2, 512, 1024, 512, 64, 128, 128,
                                 jnp.bfloat16) == 29 << 20
    assert kernels.latent_prefill_tiles(
        heads=32, dtype=jnp.float32, **cell) == (2, 512)
    assert kernels.latent_prefill_tiles(
        heads=5, dtype=jnp.bfloat16, **cell) == (1, 512)
    monkeypatch.setattr(kernels, "_PREFILL_VMEM_BUDGET", 26 << 20)
    assert kernels.latent_prefill_tiles(
        heads=32, dtype=jnp.bfloat16, **cell) == (2, 256)
    monkeypatch.setattr(kernels, "_PREFILL_VMEM_BUDGET", 20 << 20)
    assert kernels.latent_prefill_tiles(
        heads=32, dtype=jnp.bfloat16, **cell) == (1, 256)
    monkeypatch.setattr(kernels, "_PREFILL_VMEM_BUDGET", 8 << 20)
    assert kernels.latent_prefill_tiles(
        heads=32, dtype=jnp.bfloat16, **cell) == (0, 0)
    assert "no step of a chunk of 2048 fits 8 MiB" \
        in kernels.latent_prefill_attention_refusal(
            (2048, 32, 128), (2048, 32, 64), (32, 8320, 1152),
            (512, 32, 256), {"bfloat16"})


BF16 = {"bfloat16"}
REFUSED = {
    "mixed types": (
        ((512, 4, 128), (512, 4, 64), (3, 512, 384), (128, 4, 256),
         {"bfloat16", "float32"}),
        "operands of bfloat16, float32: all bfloat16 or all float32"),
    "a type it is not written for": (
        ((512, 4, 128), (512, 4, 64), (3, 512, 384), (128, 4, 256),
         {"float16"}), "operands of float16: all bfloat16 or all float32"),
    "ranks": (
        ((512, 4, 128), (512, 4, 64), (512, 384), (128, 4, 256), BF16),
        "are not [C, heads, nope], [C, heads, rope], [rank, heads, nope "
        "+ v] and [streams, rows, width]"),
    "other heads": (
        ((512, 4, 128), (512, 4, 64), (3, 512, 384), (128, 2, 256), BF16),
        "q_nope (512, 4, 128), q_rope (512, 4, 64), w_kvb (128, 2, 256)"),
    "no values": (
        ((512, 4, 128), (512, 4, 64), (3, 512, 384), (128, 4, 128), BF16),
        "[rank, heads, nope + v]"),
    "toy widths": (
        ((8, 2, 16), (8, 2, 8), (4, 128, 128), (16, 2, 32), BF16),
        "widths 16 (latent), 16 (nope) and 16 (values) are not whole "
        "lanes of 128"),
    "values of half a lane tile": (
        ((512, 4, 128), (512, 4, 64), (3, 512, 384), (128, 4, 192), BF16),
        "widths 128 (latent), 128 (nope) and 64 (values)"),
    "another row": (
        ((512, 4, 128), (512, 4, 64), (3, 1024, 256), (128, 4, 256), BF16),
        "tokens of 128 latent and 64 rotary values want cache rows of 384 "
        "for 2 positions, not 256"),
    "a short chunk of packed rows": (
        ((128, 4, 128), (128, 4, 64), (3, 512, 384), (128, 4, 256), BF16),
        "a key block of 128 positions (a chunk of 128) is not whole lanes "
        "of 128 for each of a row's 2"),
    "a chunk off the lanes": (
        ((96, 4, 128), (96, 4, 128), (3, 512, 256), (128, 4, 256), BF16),
        "a key block of 32 positions (a chunk of 96)"),
    "a cache shorter than the chunk": (
        ((512, 4, 128), (512, 4, 64), (3, 128, 384), (128, 4, 256), BF16),
        "a cache of 256 positions does not hold a chunk of 512"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_every_refusal_says_why(case):
    shapes, said = REFUSED[case]
    refusal = kernels.latent_prefill_attention_refusal(*shapes)
    assert refusal is not None and said in refusal, refusal


def test_a_refused_shape_is_an_error_of_the_kernel_not_a_second_path():
    operands = _operands(64, 128, 0)
    with pytest.raises(ValueError, match="latent_prefill_attention: a key "
                                         "block of 128 positions"):
        kernels.latent_prefill_attention(*operands, SCALE)
    # the loop takes it
    assert kernels.latent_prefill_attention_reference(
        *operands, SCALE).shape == (128, 4, VALUES)
    # ... but not key blocks that split a packed row
    with pytest.raises(ValueError, match="not whole cache rows of 2"):
        kernels.latent_prefill_attention_reference(
            *_operands(64, 3, 0), SCALE)


# -- models/mla.py attn_prefill -----------------------------------------------


@pytest.fixture(scope="module")
def layer():
    """The toy DeepSeek-V2 share with latent attention of whole lanes
    (rank, nope and values 128, rotary 64: two positions a cache row of
    384) and four heads held, one attention layer's float32 weights."""
    from benchmark.run import Loader

    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "toy_dsv2.json")) as f:
        toy = dict(json.load(f), kv_lora_rank=RANK, qk_nope_head_dim=NOPE,
                   qk_rope_head_dim=64, v_head_dim=VALUES,
                   num_attention_heads=4, num_key_value_heads=4)
    toy["published"] = dict(toy["published"], num_attention_heads=8,
                            num_key_value_heads=8)
    cfg = dsv2.DeepSeekV2Config.from_dict(toy)
    weights = Loader(REPO).module("weights", "deepseek_v2_share4")
    p = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights.make(toy, 11)["layers"][1]["attn"])
    return cfg, p


def _prefilled(cfg, p, chunk, chunks, fill=np.nan):
    """``chunks`` chunks of stream 1 prefilled in turn into a cache of
    three streams that held ``fill``: the last chunk's output and the
    cache."""
    cache = jnp.full_like(mla.init_cache(cfg, 3, 4 * chunk, jnp.float32),
                          fill)
    x = jax.random.normal(jax.random.PRNGKey(5),
                          (chunks * chunk, cfg.hidden_size))
    for i in range(chunks):
        out, cache = mla.attn_prefill(
            cfg, p, x[i * chunk:(i + 1) * chunk], cache, jnp.int32(1),
            jnp.int32(i * chunk))
    return out, cache


def test_attn_prefill_takes_the_kernel_and_leaves_the_loops_cache(
        layer, monkeypatch):
    """Three chunks of 256 through ``attn_prefill`` with the kernel and,
    the refusal forced, with the loop: the same partial output to
    float32 rounding and the SAME cache bit for bit (rows beyond the
    prefix, NaN here, are neither read into a result nor touched)."""
    cfg, p = layer
    assert cfg.heads == 4 and mla._row(cfg) == (2, 384)
    taken = []
    kernel = kernels.latent_prefill_attention
    monkeypatch.setattr(kernels, "latent_prefill_attention",
                        lambda *a: taken.append(a[0].shape) or kernel(*a))
    got, cache = _prefilled(cfg, p, 256, 3)
    assert taken == [(256, 4, NOPE)] * 3
    monkeypatch.setattr(kernels, "latent_prefill_attention_refusal",
                        lambda *a: "the test asks for the loop")
    want, cache_loop = _prefilled(cfg, p, 256, 3)
    assert len(taken) == 3
    _close(got, want, 1e-5)
    assert np.array_equal(np.asarray(cache), np.asarray(cache_loop),
                          equal_nan=True)
    rows = np.asarray(cache)
    assert np.isfinite(rows[1, :384]).all() and np.isnan(rows[1, 384:]).all()
    assert np.isnan(rows[0]).all() and np.isnan(rows[2]).all()


def test_the_span_it_is_traced_under_says_which_path(layer):
    """``attn_prefill`` chooses at trace time, so the choice is a note
    of the set-up span open around the trace (the filter's
    ``trace_lower``), once for each distinct call with its count."""
    cfg, p = layer
    cache = mla.init_cache(cfg, 3, 1024, jnp.float32)

    def trace(chunk):
        jax.make_jaxpr(lambda x, c: mla.attn_prefill(
            cfg, p, x, c, jnp.int32(1), jnp.int32(0)))(
                jnp.zeros((chunk, cfg.hidden_size)), cache)

    profile.clear()
    with profile.span("pf_net", "trace_lower", setup=True):
        trace(256)
        trace(256)
        trace(8)
    trace(256)                                # no span open: says nothing
    note = [s.note for s in profile.spans()
            if s.name == "pf_net/trace_lower"][-1]
    assert "attn_prefill 256 x 4 heads on (3, 512, 384) float32: the " \
           "kernel (x2)" in note
    assert "attn_prefill 8 x 4 heads on (3, 512, 384) float32: the jnp " \
           "loop (a key block of 8 positions (a chunk of 8) is not whole " \
           "lanes of 128 for each of a row's 2)" in note
