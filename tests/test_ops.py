"""Pallas kernels (ops/): fused scale/bias/cast, flash attention and
short attention.  On non-TPU backends the kernels run under the Pallas
interpreter."""

import numpy as np
import pytest

from nnstreamer_tpu.ops import (
    flash_attention,
    flash_attention_reference,
    scale_bias_cast,
    short_attention,
    short_attention_available,
    short_attention_reference,
)


class TestScaleBiasCast:
    def test_uint8_normalize_matches_numpy(self):
        x = np.random.default_rng(0).integers(
            0, 255, (2, 224, 224, 3), np.uint8)
        y = scale_bias_cast(x, 1 / 127.5, -127.5)
        np.testing.assert_allclose(
            np.asarray(y), (x.astype(np.float32) - 127.5) / 127.5,
            rtol=1e-6)

    def test_float_input(self):
        x = np.linspace(-1, 1, 8 * 128, dtype=np.float32).reshape(8, 128)
        y = scale_bias_cast(x, 2.0, 0.5)
        np.testing.assert_allclose(np.asarray(y), (x + 0.5) * 2.0,
                                   rtol=1e-6)

    def test_non_tiling_shape_falls_back(self):
        x = np.ones((3, 5), np.uint8)
        y = scale_bias_cast(x, 2.0, 1.0)
        np.testing.assert_allclose(np.asarray(y), np.full((3, 5), 4.0))

    def test_bfloat16_output(self):
        import jax.numpy as jnp

        x = np.full((8, 128), 4.0, np.float32)
        y = scale_bias_cast(x, 0.5, 0.0, out_dtype=jnp.bfloat16)
        assert y.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(y, np.float32), 2.0)


class TestFlashAttention:
    def test_matches_reference(self):
        rng = np.random.default_rng(1)
        shape = (2, 2, 256, 128)
        q = rng.standard_normal(shape).astype(np.float32)
        k = rng.standard_normal(shape).astype(np.float32)
        v = rng.standard_normal(shape).astype(np.float32)
        o = flash_attention(q, k, v)
        ref = flash_attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-2, atol=2e-3)

    def test_cross_attention_kv_longer(self):
        rng = np.random.default_rng(2)
        q = rng.standard_normal((1, 128, 128)).astype(np.float32)
        k = rng.standard_normal((1, 512, 128)).astype(np.float32)
        v = rng.standard_normal((1, 512, 128)).astype(np.float32)
        o = flash_attention(q, k, v)
        ref = flash_attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-2, atol=2e-3)

    def test_odd_shapes_fall_back(self):
        rng = np.random.default_rng(3)
        q = rng.standard_normal((1, 100, 64)).astype(np.float32)
        k = rng.standard_normal((1, 100, 64)).astype(np.float32)
        v = rng.standard_normal((1, 100, 64)).astype(np.float32)
        o = flash_attention(q, k, v)  # D=64 not 128-multiple: reference
        ref = flash_attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=1e-5)


class TestShortAttention:
    """``short_attention`` against its jnp reference (the split-heads
    path ``models/vit.py`` takes without the kernel).  The ragged cases
    carry NaN wherever a block overhangs the array (the interpreter
    fills what is not there with NaN, pinned below), so a key column
    left unmasked or a value row left unzeroed makes the output NaN."""

    @pytest.mark.parametrize("batch,positions,heads,size,dtype,tol", [
        # ViT-B/16's own: 196 positions, 12 heads of 64, bfloat16
        (2, 196, 12, 64, "bfloat16", 2e-2),
        # float32 toys: ragged with paired heads, ragged with whole-lane
        # heads, a single query tile, and one that tiles exactly
        (2, 50, 4, 64, "float32", 2e-5),
        (1, 16, 2, 128, "float32", 2e-5),
        (1, 8, 2, 64, "float32", 2e-5),
        (2, 128, 2, 64, "float32", 2e-5),
        (2, 256, 1, 128, "bfloat16", 2e-2),
    ])
    def test_matches_reference(self, batch, positions, heads, size, dtype,
                               tol):
        import jax.numpy as jnp

        rng = np.random.default_rng(positions)
        qkv = jnp.asarray(rng.standard_normal(
            (batch, positions, 3 * heads * size)), dtype)
        assert short_attention_available(qkv.shape, heads, qkv.dtype)
        o = short_attention(qkv, heads)
        assert o.shape == (batch, positions, heads * size)
        assert o.dtype == qkv.dtype
        got = np.asarray(o, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(
            got, np.asarray(short_attention_reference(qkv, heads), np.float32),
            rtol=tol, atol=tol)

    def test_what_overhangs_the_array_reads_nan_here(self):
        """The premise of the ragged cases above."""
        import jax
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        x = np.ones((5, 128), np.float32)
        out = pl.pallas_call(
            kernel, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), np.float32),
            interpret=True)(x)
        assert np.isnan(np.asarray(out)[5:]).all()
        assert (np.asarray(out)[:5] == 1).all()

    def test_masked_keys_would_change_the_answer(self):
        """Zeros where the NaN were are no better: 60 more key columns
        scoring 0 take their share of every row's probability."""
        import jax.numpy as jnp

        rng = np.random.default_rng(9)
        qkv = jnp.asarray(rng.standard_normal((1, 196, 3 * 128)), "float32")
        padded = jnp.pad(qkv, ((0, 0), (0, 60), (0, 0)))
        right = np.asarray(short_attention(qkv, 2))
        wrong = np.asarray(short_attention(padded, 2))[:, :196]
        assert np.abs(right - wrong).max() > 0.05
        np.testing.assert_allclose(
            right, np.asarray(short_attention_reference(qkv, 2)),
            rtol=2e-5, atol=2e-5)

    def test_scale_is_the_callers(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(10)
        qkv = jnp.asarray(rng.standard_normal((1, 24, 3 * 128)), "float32")
        with_scale = np.asarray(short_attention(qkv, 1, scale=0.3))
        np.testing.assert_allclose(
            with_scale, np.asarray(short_attention_reference(qkv, 1, 0.3)),
            rtol=2e-5, atol=2e-5)
        assert np.abs(with_scale
                      - np.asarray(short_attention(qkv, 1))).max() > 1e-2

    def test_a_refused_shape_is_an_error(self):
        with pytest.raises(ValueError, match="short_attention_available"):
            short_attention(np.zeros((1, 16, 3 * 96), np.float32), 1)


class TestKernelEligibility:
    """The ``*_available`` predicates are the whole decision between a
    Pallas kernel and its jnp reference: a shape Mosaic could refuse
    must be turned away HERE, not by an exception caught somewhere."""

    @staticmethod
    def pallas_calls(fn, *args):
        import jax

        return str(jax.make_jaxpr(fn)(*args)).count("pallas_call[")

    def test_scale_bias_cast_tiles_by_the_narrowest_dtype(self):
        from nnstreamer_tpu.ops import scale_bias_cast_available as ok

        # a tile is 8 rows of 4-byte, 16 of 2-byte, 32 of 1-byte elements
        assert ok((8, 128), np.float32)
        assert not ok((8, 128), np.uint8)        # a quarter of a u8 tile
        assert ok((32, 128), np.uint8)
        assert not ok((8, 128), np.int16)
        assert ok((16, 128), np.int16)
        # the OUTPUT tiles too: f32 -> bf16 needs 16 rows
        import jax.numpy as jnp

        assert not ok((8, 128), np.float32, jnp.bfloat16)
        assert ok((16, 128), np.float32, jnp.bfloat16)
        assert not ok((0, 128), np.float32)

    def test_scale_bias_cast_takes_the_path_the_predicate_names(self):
        for shape, kernel in (((32, 128), 1), ((8, 128), 0),
                              ((96, 128), 1)):   # 96 rows: block 96
            x = np.ones(shape, np.uint8)
            assert self.pallas_calls(
                lambda v: scale_bias_cast(v, 2.0, 1.0), x) == kernel
            np.testing.assert_allclose(
                np.asarray(scale_bias_cast(x, 2.0, 1.0)), 4.0)

    def test_flash_attention_eligibility(self):
        import jax.numpy as jnp

        from nnstreamer_tpu.ops import flash_attention_available as ok

        assert ok((4, 256, 128), (4, 256, 128), jnp.bfloat16)
        assert ok((1, 128, 128), (1, 512, 128), np.float32)
        assert not ok((1, 256, 64), (1, 256, 64), np.float32)    # D
        assert not ok((1, 64, 128), (1, 64, 128), np.float32)    # K block
        assert not ok((1, 200, 128), (1, 256, 128), np.float32)  # ragged S
        # the Q block is whole tiles of the dtype: 8 rows do for f32,
        # not for bf16
        assert ok((1, 8, 128), (1, 128, 128), np.float32)
        assert not ok((1, 8, 128), (1, 128, 128), jnp.bfloat16)

    @pytest.mark.parametrize("shape,heads,dtype,short,flash", [
        # ViT-B/16 as the benchmark runs it, and in float32
        ((64, 196, 2304), 12, "bfloat16", True, False),
        ((64, 196, 2304), 12, "float32", True, False),
        # whole-lane heads; chip_smoke.py's ViT (256 positions, heads of 128)
        # is short too although the blockwise kernel could take it
        ((2, 16, 768), 2, "bfloat16", True, False),
        ((64, 256, 1536), 4, "bfloat16", True, True),
        # the VMEM rule at ViT-B/16's width: 384 positions fit, 512
        # do not (Mosaic ran out of VMEM there when it was let through)
        ((2, 384, 2304), 12, "bfloat16", True, False),
        ((2, 512, 2304), 12, "bfloat16", False, False),
        # too long for one key block: the blockwise kernel's
        ((4, 1024, 2304), 6, "bfloat16", False, True),
        # ... or nobody's (heads of 64): jnp
        ((4, 1000, 2304), 12, "bfloat16", False, False),
        # heads that neither pair up on a lane block nor fill one
        ((2, 196, 2304), 24, "bfloat16", False, False),    # 32
        ((2, 196, 3 * 192), 3, "bfloat16", False, False),  # D 192
        ((2, 196, 3 * 96), 1, "float32", False, False),    # 96
        # dtypes the kernel was not written for
        ((2, 196, 2304), 12, "float16", False, False),
        ((2, 196, 2304), 12, "int8", False, False),
        # not a qkv projection
        ((2, 196, 2305), 12, "bfloat16", False, False),
        ((196, 2304), 12, "bfloat16", False, False),
    ])
    def test_short_attention_eligibility(self, shape, heads, dtype, short,
                                         flash):
        """What ``short_attention`` takes, what it leaves to
        ``flash_attention``, and what still falls to jnp."""
        from nnstreamer_tpu.ops import flash_attention_available

        assert short_attention_available(shape, heads, dtype) is short
        if len(shape) == 3 and shape[2] % (3 * heads) == 0:
            split = (shape[0], heads, shape[1], shape[2] // 3 // heads)
            assert flash_attention_available(split, split, dtype) is flash

    def test_short_attention_takes_the_path_the_predicate_names(self):
        rng = np.random.default_rng(5)
        qkv = rng.standard_normal((1, 20, 3 * 128)).astype(np.float32)
        assert self.pallas_calls(
            lambda x: short_attention(x, 2), qkv) == 1
        # one call whatever the heads: a step takes a frame, not a head
        wide = rng.standard_normal((2, 20, 3 * 512)).astype(np.float32)
        assert self.pallas_calls(
            lambda x: short_attention(x, 8), wide) == 1

    def test_flash_attention_takes_the_path_the_predicate_names(self):
        rng = np.random.default_rng(4)
        for sq, sk, kernel in ((128, 256, 1), (64, 64, 0)):
            q = rng.standard_normal((1, sq, 128)).astype(np.float32)
            k = rng.standard_normal((1, sk, 128)).astype(np.float32)
            assert self.pallas_calls(flash_attention, q, k, k) == kernel
            np.testing.assert_allclose(
                np.asarray(flash_attention(q, k, k)),
                np.asarray(flash_attention_reference(q, k, k)),
                rtol=2e-2, atol=2e-3)


class TestTransformAcceleration:
    """acceleration=true folds affine arithmetic chains into the kernel
    (the reference's Orc acceleration analog)."""

    def run_transform(self, accel, arr):
        from fractions import Fraction

        from nnstreamer_tpu.core import Buffer, TensorsSpec
        from nnstreamer_tpu.elements.basic import AppSink, AppSrc
        from nnstreamer_tpu.elements.transform import TensorTransform
        from nnstreamer_tpu.runtime import Pipeline

        p = Pipeline(fuse=False)
        src = AppSrc(name="src", spec=TensorsSpec.from_shapes(
            [arr.shape], arr.dtype, rate=Fraction(10)))
        t = TensorTransform(name="t", mode="arithmetic",
                            option="typecast:float32,add:-127.5,div:127.5",
                            acceleration=accel,
                            backend="pallas" if accel else "xla")
        sink = AppSink(name="out")
        p.add(src, t, sink).link(src, t, sink)
        with p:
            src.push_buffer(Buffer.of(arr))
            src.end_of_stream()
            assert p.wait_eos(timeout=60)
            return sink.pull(timeout=1).tensors[0].np()

    def test_accelerated_matches_plain(self):
        arr = np.random.default_rng(0).integers(
            0, 255, (2, 8, 128), np.uint8)
        fast = self.run_transform(True, arr)
        plain = self.run_transform(False, arr)
        np.testing.assert_allclose(fast, plain, rtol=1e-6)

    def test_fold_affine_guards(self):
        from nnstreamer_tpu.elements.transform import (
            _fold_affine,
            parse_arith_ops,
        )

        a, b, dt = _fold_affine(parse_arith_ops(
            "typecast:float32,add:-127.5,div:127.5"))
        assert a == pytest.approx(1 / 127.5)
        assert b == pytest.approx(-1.0)
        # non-affine chains refuse to fold
        assert _fold_affine(parse_arith_ops("pow:2.0")) is None
        assert _fold_affine(parse_arith_ops(
            "add:1.0,typecast:float32")) is None  # mid-chain cast
        assert _fold_affine(parse_arith_ops("mul:0.0")) is None
        # no leading typecast: f16/bf16/f64 inputs keep their dtype on
        # the plain path, so folding (always f32) must refuse
        import jax.numpy as jnp

        ops = parse_arith_ops("mul:2.0")
        assert _fold_affine(ops, np.dtype(np.float16)) is None
        assert _fold_affine(ops, np.dtype(np.float64)) is None
        assert _fold_affine(ops, jnp.bfloat16) is None
        assert _fold_affine(ops, np.dtype(np.uint8)) is not None
        assert _fold_affine(ops, np.dtype(np.float32)) is not None

    def test_f64_direct_call_keeps_precision(self):
        from nnstreamer_tpu.ops import scale_bias_cast_available

        x = np.full((8, 128), 1.0 + 1e-12, np.float64)
        assert not scale_bias_cast_available(x.shape, x.dtype)
        y = scale_bias_cast(x, 1.0, 0.0, out_dtype=np.float64)
        assert np.asarray(y).dtype == np.float64
