"""ViT model family: functional correctness + filter integration +
which attention path a shape takes, and that the paths agree."""

import os
import sys

import numpy as np
import pytest

from nnstreamer_tpu.models.vit import register_vit, vit_apply, vit_init

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:   # the benchmark's stage vocabulary lives there
    sys.path.insert(0, REPO)


def pallas_calls(fn, *args):
    import jax

    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call[")


@pytest.fixture(scope="module")
def tiny():
    import jax

    params = vit_init(jax.random.PRNGKey(0), image_size=32, patch=8,
                      dim=256, depth=2, heads=2, mlp_dim=128,
                      num_classes=5)
    x = np.random.default_rng(0).standard_normal(
        (2, 32, 32, 3)).astype(np.float32)
    return params, x


class TestViT:
    def test_logits_shape_and_finite(self, tiny):
        import jax

        params, x = tiny
        y = jax.jit(lambda p, xx: vit_apply(p, xx, heads=2))(params, x)
        y = np.asarray(y)
        assert y.shape == (2, 5) and y.dtype == np.float32
        assert np.isfinite(y).all()

    def test_flash_and_reference_attention_agree(self, tiny):
        """16 positions with heads of 128 engage ``short_attention``;
        the same model with every kernel refused (the split-heads jnp
        path, as on a shape no predicate admits) gives the same logits."""
        import jax

        import nnstreamer_tpu.ops as ops

        params, x = tiny

        def logits():   # a new function each time: a new trace
            return np.asarray(jax.jit(
                lambda p, xx: vit_apply(p, xx, heads=2))(params, x))

        assert pallas_calls(lambda p, xx: vit_apply(p, xx, heads=2),
                            params, x) == 2
        y_kernel = logits()
        orig = ops.short_attention_available, ops.flash_attention
        try:
            ops.short_attention_available = lambda *a, **k: False
            ops.flash_attention = ops.flash_attention_reference
            assert pallas_calls(lambda p, xx: vit_apply(p, xx, heads=2),
                                params, x) == 0
            y_ref = logits()
        finally:
            ops.short_attention_available, ops.flash_attention = orig
        np.testing.assert_allclose(y_kernel, y_ref, rtol=5e-2, atol=5e-2)

    @pytest.mark.parametrize("image,patch,dim,heads,calls", [
        # ViT-B/16's attention shape: 196 positions, heads of 64
        (224, 16, 128, 2, "depth"),
        # 256 positions, heads of 128 (``register_vit``'s default heads)
        (64, 4, 256, 2, "depth"),
        # heads of 32: no kernel takes them, the jnp path as before
        (32, 8, 128, 4, 0),
        # too long for one key block, heads of 128: the blockwise kernel
        (512, 16, 768, 6, "depth"),
    ])
    def test_attention_path_follows_the_shape(self, image, patch, dim,
                                              heads, calls):
        """One ``pallas_call`` a layer wherever a predicate admits the
        shape, and the call sits straight in the layer's ``attn`` scope,
        so ``benchmark/stages.py`` books its device time to a stage that
        ends in ``/attn``."""
        import jax

        from benchmark.stages import stage_of

        depth = 2
        params = jax.eval_shape(lambda: vit_init(
            jax.random.PRNGKey(0), image_size=image, patch=patch, dim=dim,
            depth=depth, heads=heads, mlp_dim=64, num_classes=5))
        x = jax.ShapeDtypeStruct((2, image, image, 3), np.float32)

        def model(p, xx):
            with jax.named_scope("nns.model"):   # the filter's scope
                return vit_apply(p, xx, heads=heads)

        jaxpr = jax.make_jaxpr(model)(params, x)
        kernels = [eqn for eqn in jaxpr.eqns
                   if eqn.primitive.name == "pallas_call"]
        assert len(kernels) == (depth if calls == "depth" else calls)
        stages = [stage_of(f"jit(f)/{eqn.source_info.name_stack}/"
                           f"{eqn.primitive.name}") for eqn in kernels]
        assert stages == [f"nns.model/layer{i:02d}/attn"
                          for i in range(len(kernels))]

    def test_pipeline_through_filter(self, tiny):
        from fractions import Fraction

        from nnstreamer_tpu.core import Buffer, TensorsSpec
        from nnstreamer_tpu.runtime import parse_launch

        name = register_vit("vit_pipe_test", batch=1, image_size=32,
                            patch=8, dim=256, depth=1, heads=2,
                            mlp_dim=128, num_classes=5)
        p = parse_launch(
            "appsrc name=src ! tensor_transform mode=arithmetic "
            "option=typecast:float32,div:255.0 ! "
            f"tensor_filter framework=jax-xla model={name} ! "
            "appsink name=out")
        p["src"].spec = TensorsSpec.from_shapes([(1, 32, 32, 3)], np.uint8,
                                                rate=Fraction(10))
        x = np.random.default_rng(1).integers(0, 255, (1, 32, 32, 3),
                                              np.uint8)
        with p:
            p["src"].push_buffer(Buffer.of(x))
            p["src"].end_of_stream()
            assert p.wait_eos(timeout=120)
            got = p["out"].pull(timeout=1)
        assert got.tensors[0].np().shape == (1, 5)
