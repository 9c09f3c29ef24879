"""Pallas kernels (ops/): fused scale/bias/cast and flash attention.
On non-TPU backends the kernels run under the Pallas interpreter."""

import numpy as np
import pytest

from nnstreamer_tpu.ops import (
    flash_attention,
    flash_attention_reference,
    scale_bias_cast,
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
