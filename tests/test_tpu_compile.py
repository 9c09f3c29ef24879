"""The decode kernel of the language cells compiled for the chip, without
the chip: the TPU's compiler is installed here and compiles for a v5e
that is described and not attached, so what Mosaic refuses at the cell's
real shapes (a slice off the tiling, more fast memory than a kernel may
use) fails here and costs no chip time.  Nothing runs: no result and no
time comes out of this file.

The topology is described inside a fixture (only the worker that runs
this file loads the TPU's library), and every compile is in the test's
own process; where no topology can be described the tests skip.
"""

import functools

import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.ops import kernels


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no compiler for it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("total,window", [(6144, 4096), (16384, 16384)],
                         ids=["ring", "full"])
def test_gqa_decode_attention_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, total, window):
    """`smallthinker.decode16k`'s two calls: 32 streams, 4 groups of 7
    heads of 128, a ring of 6,144 read through a window of 4,096 and a
    dense cache of 16,384, bf16, as a Mosaic kernel (not interpreted)."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn = jax.jit(functools.partial(kernels.gqa_decode_attention,
                                   window=window, scale=128 ** -0.5))
    compiled = fn.lower(shape((32, 4, 7, 128), jnp.bfloat16),
                        shape((32, 4, total, 128), jnp.bfloat16),
                        shape((32, 4, total, 128), jnp.bfloat16),
                        shape((32,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no copy of a cache beside the kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
