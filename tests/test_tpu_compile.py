"""The decode kernels of the language cells compiled for the chip, without
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


def test_latent_decode_attention_compiles_at_the_cells_shapes(
        one_chip, monkeypatch):
    """`dsv2.decode16k`'s call: 32 streams, 32 heads, caches of 16,640
    positions, two a row of 1,152 bf16 values, walked in chunks of 1,664
    positions (832 rows: six and a half lane tiles of scores a part)
    through four buffers (7.7 MB of fast memory, stated by the call), a
    last item's pieces down to 64 rows, as a Mosaic kernel; one custom
    call, no copy of the cache beside it."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert kernels.latent_cache_row(512, 64) == (2, 1152)
    assert kernels.decode_walk_plan(16640, 1152) == kernels.WalkPlan(1664, 4)
    fn = jax.jit(functools.partial(kernels.latent_decode_attention,
                                   rank=512, scale=0.1147))
    compiled = fn.lower(shape((32, 32, 576)), shape((32, 8320, 1152)),
                        shape((32,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("tokens,held,hidden,inter,grid", [
    (32, 40, 5120, 1536, 46), (32, 64, 2560, 768, 70),
    (2048, 40, 5120, 1536, 88), (2048, 64, 2560, 768, 112)],
    ids=["dsv2.decode16k", "smallthinker.decode16k",
         "dsv2.prefill", "smallthinker.prefill"])
def test_grouped_gated_product_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, tokens, held, hidden, inter, grid):
    """The routed experts' grouped product of both language cells, bf16,
    as a Mosaic kernel: a decode step's blocks of 32 rows (40 experts of
    5,120 x 1,536 on a grid of 46, 64 of 2,560 x 768 on a grid of 70)
    and a prefill chunk's blocks of 256 rows (grids of 88 and 112), in
    tiles of 384 and 768 columns (two buffers of three: 24 MB of fast
    memory either way, which the call asks for)."""
    from nnstreamer_tpu.models import moe

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    blk = moe.block_rows(tokens)
    assert blk == min(tokens, 256)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert kernels.grouped_gated_product_refusal(
        (tokens, hidden), (held, hidden, inter), (held, inter, hidden),
        {jnp.dtype(jnp.bfloat16)}, blk) is None
    assert kernels.grouped_tile(hidden, inter, jnp.bfloat16) \
        == 768 * 2560 // hidden
    fn = jax.jit(functools.partial(kernels.grouped_gated_product, blk=blk,
                                   act=jax.nn.silu))
    compiled = fn.lower(shape((tokens, hidden)),
                        shape((held, hidden, inter)),
                        shape((held, hidden, inter)),
                        shape((held, inter, hidden)),
                        shape((grid * blk,), jnp.int32),
                        shape((grid,), jnp.int32),
                        shape((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no copy of the experts beside the kernel: nothing at a decode
    # step, a prefill chunk's rows laid out for the plan
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (1 << 20) + (grid * blk * hidden * 2 if tokens > blk else 0)


@pytest.mark.parametrize("tokens,k,held,hidden,tile", [
    (2048, 12, 8, 6144, 3072), (128, 12, 8, 6144, 6144),
    (2048, 6, 40, 5120, 2560), (2048, 8, 16, 6144, 3072),
    (2048, 6, 16, 2688, 2688), (2048, 6, 64, 2560, 2560)],
    ids=["longcat.prefill", "longcat.decode4k", "dsv2.prefill",
         "kexaone.prefill", "nemotron3.prefill", "every-pair-held"])
def test_weighted_row_sum_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, tokens, k, held, hidden, tile):
    """The routed experts' rows summed into their tokens (PR 54), bf16
    rows into float32, as a Mosaic kernel at the shapes `combine` walks
    (four cells' chunks and `longcat.decode4k`'s step) and at a chunk
    whose every pair is held (which `combine` gathers): the plan's
    26,624-28,672 tokens and weights a row in scalar memory, the
    result's column tile (two buffers of up to 24 MiB) and four copies
    of 16 rows in fast memory, which the call asks for; one custom
    call and nothing beside it."""
    from nnstreamer_tpu.models import moe

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    blk, rows = moe.block_rows(tokens), moe.plan_rows(tokens, k, held)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert kernels.weighted_row_sum_refusal(
        (rows + 1, hidden), jnp.bfloat16, tokens, blk) is None
    assert kernels.row_sum_tile(tokens, hidden) == tile
    fn = jax.jit(functools.partial(kernels.weighted_row_sum, blk=blk,
                                   tokens=tokens))
    compiled = fn.lower(shape((rows + 1, hidden), jnp.bfloat16),
                        shape((rows,), jnp.int32),
                        shape((rows,), jnp.float32),
                        shape((held,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_the_hybrid_cells_kernels_compile_at_its_shapes(one_chip, monkeypatch):
    """`nemotron3.decode4k`'s calls, bf16, as Mosaic kernels: attention
    of 128 streams, 2 groups of 16 heads of 128 over a dense cache of
    4,096; the UNGATED grouped product of 16 experts of 2,688 x 1,920
    (the published 1,856 columns stored as 15 lanes) at a decode step's
    block of 128 rows and a prefill chunk's blocks of 256, two tiles of
    640 columns a step."""
    from nnstreamer_tpu.models import moe

    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn = jax.jit(functools.partial(kernels.gqa_decode_attention,
                                   window=4096, scale=128 ** -0.5))
    compiled = fn.lower(shape((128, 2, 16, 128)), shape((128, 2, 4096, 128)),
                        shape((128, 2, 4096, 128)),
                        shape((128,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    held, hidden, inter = 16, 2688, 1920
    assert kernels.grouped_tile(hidden, inter, jnp.bfloat16, matrices=2) == 640
    for tokens, grid in ((128, 6 + held), (2048, 48 + held)):
        blk = moe.block_rows(tokens)
        fn = jax.jit(functools.partial(
            kernels.grouped_gated_product, blk=blk,
            act=moe.activation("relu2")[0]), static_argnums=(1,))
        compiled = fn.lower(shape((tokens, hidden)), None,
                            shape((held, hidden, inter)),
                            shape((held, inter, hidden)),
                            shape((grid * blk,), jnp.int32),
                            shape((grid,), jnp.int32),
                            shape((), jnp.int32)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes \
            < (1 << 20) + (grid * blk * hidden * 2 if tokens > blk else 0)


def test_ssm_decode_step_compiles_at_the_cells_shapes(one_chip, monkeypatch):
    """`nemotron3.decode4k`'s recurrent state of one layer, 128 streams
    of 8 groups x a state of 128 x 512 lanes (8 heads of 64) float32, as
    a Mosaic kernel: four streams' 2 MiB each a grid step, copied in
    from the live state or the snapshot and back over the live state by
    the kernel itself (two sets of buffers, 16.8 MB of fast memory,
    which the call asks for)."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    state, row, column = (128, 8, 128, 512), (128, 8, 512), (128, 8, 128)
    assert kernels.ssm_decode_step_refusal(
        state, {jnp.dtype(jnp.float32)}) is None
    assert kernels.ssm_step_streams(128, 8 * 128 * 512 * 4) == 4
    compiled = jax.jit(kernels.ssm_decode_step, donate_argnums=(0,)).lower(
        shape(state), shape(state), shape((128,), jnp.bool_), shape(row),
        shape(row), shape(column), shape(column)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    # the new state is the live state's buffer; no copy of either beside it
    assert memory.alias_size_in_bytes == 128 * 8 * 128 * 512 * 4
    assert memory.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("entry,temp_mb", [("decode", 64), ("prefill", 576)])
def test_the_hybrid_cells_programs_copy_no_recurrent_state(
        one_chip, monkeypatch, entry, temp_mb, capsys):
    """`nemotron3.decode4k`'s two programs at the cell's sizes (20
    layers, 128 streams, 4,096 positions, chunks of 2,048; 4.0 GB of
    weights and 6.5 GB of state as arguments): the state is updated in
    the donated buffers.  One layer's recurrent state is 268 MB, so a
    copy of it (a `lax.cond` around the step made nine, 2.4 GB) shows
    in the temporaries.  A decode step's nine state updates are the
    kernel `ssm_decode_step`, each written over its layer's live
    state.  A prefill chunk attends through `gqa_prefill_attention` in
    its three caches (no loop over key blocks is left, and no copy of a
    268 MB cache around the chunk's rows: 565 MB of temporaries where
    the loops and the copies took 609)."""
    import json
    import os

    from nnstreamer_tpu.models import nemotron_h as nh

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "nemotron3_nano_share8.json")
    with open(path) as f:
        raw = json.load(f)
    cfg = nh.NemotronHConfig.from_dict(raw)
    stored = raw["expert_columns_stored"]

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(lambda: nh.init_params(cfg, 0))
    for layer in params["layers"]:
        if "experts" in layer:           # as the benchmark stores them
            up, down = layer["experts"]["up"], layer["experts"]["down"]
            layer["experts"] = {
                "up": jax.ShapeDtypeStruct(up.shape[:2] + (stored,), up.dtype),
                "down": jax.ShapeDtypeStruct(
                    (down.shape[0], stored, down.shape[2]), down.dtype)}
    state = jax.eval_shape(lambda: nh.init_state(cfg, params, 128, 4096))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(state))
    assert 6.5e9 < nbytes < 6.6e9

    def i32(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

    fn, inputs = {"decode": (nh.decode, [i32(128), i32(128)]),
                  "prefill": (nh.prefill, [i32(2048), i32(1), i32(1),
                                           i32(1)])}[entry]
    compiled = jax.jit(functools.partial(fn, cfg), donate_argnums=(1,)) \
        .lower(on_chip(params), on_chip(state), *inputs).compile()
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nnemotron3 {entry}: {memory}")
    assert memory.alias_size_in_bytes >= nbytes
    assert memory.temp_size_in_bytes < temp_mb << 20
    text = compiled.as_text()
    # the experts' grouped product and, of a decode step, three
    # attention calls and nine state updates
    assert text.count("tpu_custom_call") >= 8 + (
        3 + 9 if entry == "decode" else 3)
    # the kernel picks each stream's source: no restore loop before the
    # layers (the scope holds nothing at these shapes)
    assert "ssm_restore" not in text


def _kexaone(one_chip):
    """`kexaone.decode16k`'s configuration, and its weights and state as
    shapes on the described chip (9.1 GB and 4.5 GB)."""
    import json
    import os

    from nnstreamer_tpu.models import exaone_moe as ex

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "kexaone_236b_share8.json")
    with open(path) as f:
        cfg = ex.ExaoneMoeConfig.from_dict(json.load(f))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(lambda: ex.init_params(cfg, 0))
    state = jax.eval_shape(
        lambda: ex.init_state(cfg, params, 32, 16384, 256))
    return ex, cfg, on_chip(params), on_chip(state)


def test_the_window128_cells_kernels_compile_at_its_shapes(one_chip,
                                                            monkeypatch):
    """`kexaone.decode16k`'s calls, bf16, as Mosaic kernels: attention
    of 8 groups of 8 heads of 128 over a ring of 384 read through a
    window of 128 (chunks of one cell) and over a dense cache of 16,384,
    and the routed experts' gated product at hidden 6,144 x 2,048 in
    tiles of 256 columns, for a decode step's 32 tokens and a prefill
    chunk's 2,048."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert kernels.decode_walk_plan(384, 4096) == kernels.WalkPlan(128, 8)
    assert kernels.decode_walk_plan(16384, 4096) == kernels.WalkPlan(512, 4)
    for total, window in ((384, 128), (16384, 16384)):
        fn = jax.jit(functools.partial(kernels.gqa_decode_attention,
                                       window=window, scale=128 ** -0.5))
        compiled = fn.lower(shape((32, 8, 8, 128)),
                            shape((32, 8, total, 128)),
                            shape((32, 8, total, 128)),
                            shape((32,), jnp.int32)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert kernels.grouped_tile(6144, 2048, jnp.bfloat16) == 256
    from nnstreamer_tpu.models import moe

    for tokens in (32, 2048):
        blk = moe.block_rows(tokens)
        rows = -(-tokens * 8 // blk) * blk + 16 * blk

        def product(x, gate, up, down, row_token, block_expert, blocks):
            return kernels.grouped_gated_product(
                x, gate, up, down, row_token, block_expert, blocks, blk,
                jax.nn.silu)

        compiled = jax.jit(product).lower(
            shape((tokens, 6144)), shape((16, 6144, 2048)),
            shape((16, 6144, 2048)), shape((16, 2048, 6144)),
            shape((rows,), jnp.int32), shape((rows // blk,), jnp.int32),
            shape((), jnp.int32)).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("entry,temp_mb", [("decode", 64), ("prefill", 640)])
def test_the_window128_cells_programs_copy_no_cache(one_chip, monkeypatch,
                                                    entry, temp_mb, capsys):
    """`kexaone.decode16k`'s two programs at the cell's sizes (five
    layers and the prediction module, 32 streams, 16,384 positions,
    chunks of 2,048; 9.1 GB of weights and 4.5 GB of state as
    arguments): the caches are updated in the donated buffers.  A full
    cache is 1.07 GB a tensor, so a copy of one shows in the
    temporaries, and so would a prefill chunk's scores against a whole
    cache (`[64, 2048, 16384]` float32: 8.6 GB).  A decode step attends
    through the kernel in all six caches and runs the routed experts'
    grouped product in four layers and the module; a prefill chunk
    attends through `gqa_prefill_attention` in the layer and the module
    that see every position (620 MB of temporaries where the loop's
    scores and two copies of each full cache took 1,408)."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    ex, cfg, params, state = _kexaone(one_chip)
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(state))
    assert 4.49e9 < nbytes < 4.51e9
    rings = [c["k"].shape[2] for c in state["cache"]]
    assert rings == [384, 384, 384, 16384, 384] and max(
        r for r in rings if r < 16384) <= 512

    def i32(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

    fn, inputs = {"decode": (ex.decode, [i32(32)] * 3),
                  "prefill": (ex.prefill, [i32(2048), i32(2048), i32(1),
                                           i32(1), i32(1)])}[entry]
    compiled = jax.jit(functools.partial(fn, cfg), donate_argnums=(1,)) \
        .lower(params, state, *inputs).compile()
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nkexaone {entry}: {memory}")
    assert memory.alias_size_in_bytes >= nbytes
    assert memory.temp_size_in_bytes < temp_mb << 20
    # weights, state and temporaries together fit a chip's 16 GB
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.5e9
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5 + (
        6 if entry == "decode" else 2)
    assert entry == "decode" or " while(" not in text


def test_latent_decode_attention_compiles_at_64_heads(one_chip, monkeypatch):
    """`longcat.decode4k`'s call: 128 streams of 64 heads on caches of
    4,096 positions, two a row of 1,152 bf16 values, walked in chunks of
    1,024 positions (512 rows) through seven buffers (8.3 MB of fast
    memory, stated by the call), as a Mosaic kernel; one custom call, no
    copy of the cache beside it."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert kernels.decode_walk_plan(4096, 1152) == kernels.WalkPlan(1024, 7)
    fn = jax.jit(functools.partial(kernels.latent_decode_attention,
                                   rank=512, scale=192 ** -0.5))
    compiled = fn.lower(shape((128, 64, 576)), shape((128, 2048, 1152)),
                        shape((128,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("heads,streams,rows", [(32, 32, 8320),
                                                (64, 128, 2048)],
                         ids=["dsv2.decode16k", "longcat.decode4k"])
def test_latent_prefill_attention_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, heads, streams, rows):
    """A prefill chunk's attention of both latent-cache cells: 2,048
    queries of 32 or 64 heads against key blocks of 1,024 positions (512
    packed rows of 1,152 bf16 values) of a cache of 16,640 or 4,096, two
    heads a grid step and 512 query rows a pass (29 MB of fast memory,
    stated by the call), as a Mosaic kernel; one custom call, no copy of
    the cache beside it: the temporaries are the queries laid out once a
    part of a row."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    operands = [shape((2048, heads, 128)), shape((2048, heads, 64)),
                shape((streams, rows, 1152)), shape((), jnp.int32),
                shape((), jnp.int32), shape((512, heads, 256))]
    assert kernels.latent_prefill_attention_refusal(
        *(o.shape for o in operands[:3]), operands[5].shape,
        {jnp.dtype(jnp.bfloat16)}) is None
    assert kernels.latent_prefill_tiles(
        2048, heads, 512, 64, 128, 128, jnp.bfloat16) == (2, 512)
    fn = jax.jit(functools.partial(kernels.latent_prefill_attention,
                                   scale=0.1147))
    compiled = fn.lower(*operands).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # the queries twice a head (2 x 256 lanes), in and out of a relayout
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * 2048 * heads * 512 * 2 + (1 << 20)


@pytest.mark.parametrize("per,streams,total,window,heads", [
    (7, 32, 6144, 4096, 7), (7, 32, 16384, 16384, 7),
    (16, 32, 16384, 16384, 8), (5, 128, 4096, 4096, 5),
    (8, 128, 4096, 4096, 8)],
    ids=["smallthinker.decode16k ring", "smallthinker.decode16k full",
         "kexaone.decode16k", "falconh1.decode4k", "nemotron3.decode4k"])
def test_gqa_prefill_attention_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, per, streams, total, window, heads):
    """A prefill chunk's attention of the four grouped-query cells:
    2,048 queries of 28, 64, 20 or 32 heads (4 key/value heads of 7, 16,
    5 or 8) against key blocks of 1,024 positions of a ring of 6,144
    behind a window of 4,096 or of a cache of 4,096 or 16,384, a
    key/value head's group a grid step (half of it at 16) and 512 query
    rows a pass, as a Mosaic kernel; one custom call and no copy of a
    cache beside it: the temporaries are the queries and the output
    with a head's rows together."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    operands = [shape((2048, 4, per, 128)), shape((streams, 4, total, 128)),
                shape((streams, 4, total, 128)), shape((), jnp.int32),
                shape((), jnp.int32)]
    assert kernels.gqa_prefill_attention_refusal(
        *(o.shape for o in operands[:3]), window,
        {jnp.dtype(jnp.bfloat16)}) is None
    assert kernels.gqa_prefill_tiles(2048, per, 128, jnp.bfloat16) \
        == (heads, 512)
    fn = jax.jit(functools.partial(kernels.gqa_prefill_attention,
                                   window=window, scale=128 ** -0.5))
    compiled = fn.lower(*operands).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * 2048 * 4 * per * 128 * 2 + (1 << 20)


def test_the_ring_cells_prefill_program_copies_no_cache(one_chip,
                                                        monkeypatch, capsys):
    """`smallthinker.decode16k`'s prefill program at the cell's sizes
    (8 layers, 32 streams, 16,384 positions, chunks of 2,048; 7.9 GB of
    weights and 4.6 GB of rings and full caches as arguments): a chunk
    attends through `gqa_prefill_attention` in all eight layers (two
    kernels are built, one for the six rings and one for the two full
    caches) beside the eight grouped products, no loop over key blocks
    is left, and the chunk's rows are written into the donated caches
    where they lie (a scatter into a whole cache made two copies of each
    of the sixteen tensors, 201 and 537 MB apiece: 338 MB of temporaries
    where the loops' scores and those copies took 570)."""
    import json
    import os

    from nnstreamer_tpu.models import smallthinker as st

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "smallthinker_21b_stage8.json")
    with open(path) as f:
        cfg = st.SmallThinkerConfig.from_dict(json.load(f))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(lambda: st.init_params(cfg, 0))
    state = jax.eval_shape(
        lambda: st.init_state(cfg, params, 32, 16384, 2048))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(state))
    assert 4.5e9 < nbytes < 4.7e9
    assert sorted({c["k"].shape[2] for c in state["cache"]}) \
        == [6144, 16384]

    def i32(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

    kernels._gqa_prefill_call.cache_clear()
    compiled = jax.jit(functools.partial(st.prefill, cfg),
                       donate_argnums=(1,)) \
        .lower(on_chip(params), on_chip(state), i32(2048), i32(1),
               i32(1)).compile()
    assert kernels._gqa_prefill_call.cache_info().misses == 2
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nsmallthinker prefill: {memory}")
    assert memory.alias_size_in_bytes >= nbytes
    assert memory.temp_size_in_bytes < 384 << 20
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 16 and " while(" not in text


def _latent_cell(name: str):
    """A latent-cache cell's configuration, entry points and sizes:
    ``(cfg, params, state, {entry: (function, inputs' lengths)}, the
    grouped products' custom calls a chunk or a step, more a decode
    step, more a chunk)``."""
    import json
    import os

    from nnstreamer_tpu.models import deepseek_v2 as dsv2
    from nnstreamer_tpu.models import longcat_flash as lc

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            name + ".json")) as f:
        raw = json.load(f)
    if name == "deepseek_v2_share4":
        # five layers, four of them of routed experts: a decode step
        # attends through the kernel in five caches
        cfg = dsv2.DeepSeekV2Config.from_dict(raw)
        params = jax.eval_shape(lambda: dsv2.init_params(
            cfg, jax.random.PRNGKey(0), jnp.bfloat16))
        state = jax.eval_shape(lambda: dsv2.init_state(cfg, params, 32,
                                                       16640))
        return cfg, params, state, {
            "decode": (dsv2.decode, (32, 32)),
            "prefill": (dsv2.prefill, (2048, 1, 1))}, 4, 5, 5
    cfg = lc.LongCatFlashConfig.from_dict(raw)
    params = jax.eval_shape(lambda: lc.init_params(cfg, 0))
    state = jax.eval_shape(lambda: lc.init_state(cfg, params, 128, 4096))
    return cfg, params, state, {
        "decode": (lc.decode, (128, 128)),
        "prefill": (lc.prefill, (2048, 1, 1))}, 4, 8 + 8, 8


@pytest.mark.parametrize("entry,temp_mb", [("decode", 96), ("prefill", 1536)])
@pytest.mark.parametrize("cell", ["deepseek_v2_share4",
                                  "longcat_flash_omni_share64"])
def test_the_two_cache_cells_programs_fit_the_chip(one_chip, monkeypatch,
                                                   capsys, cell, entry,
                                                   temp_mb):
    """Both latent-cache cells' two programs at the cells' sizes.
    `dsv2.decode16k`: five layers, 32 streams, 16,640 positions, chunks
    of 2,048; 9.3 GB of weights and 3.07 GB of state.  `longcat.decode4k`:
    four layers of two latent-attention sub-blocks, 128 streams, 4,096
    positions, chunks of 2,048; 7.9 GB of weights and 4.83 GB of state.
    A cache is `[streams, positions / 2, 1152]`, 576 values a position
    (613 MB and 604 MB each), and every cache is updated in the donated
    buffers: neither a chunk's packed rows, nor the key blocks a chunk
    unpacks, nor a decode step's read, place and write of one row a
    stream copies or relays out a cache (one would show in the
    temporaries), weights, state and temporaries fit the chip, and a
    decode step attends through the kernel in every cache (five; eight)
    beside the grouped products (four; four and, as one group of one
    expert, the eight dense MLPs): 9 and 20 custom calls a decode step.
    A chunk attends through `latent_prefill_attention` in every cache
    too (since PR 48): 9 and 12 custom calls a chunk, none fallen back
    to the loop, whose `[heads, 2048, 1024]` float32 scores (268 and
    537 MB) were the prefill programs' largest temporaries."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    cfg, params, state, entries, product_calls, step_calls, chunk_calls = \
        _latent_cell(cell)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))

    caches = [c for c in jax.tree_util.tree_leaves(state["cache"])]
    if cell == "deepseek_v2_share4":
        assert 9.28e9 < nbytes(params) < 9.30e9
        assert [c.shape for c in caches] == [(32, 8320, 1152)] * 5
    else:
        assert 7.92e9 < nbytes(params) < 7.94e9
        assert [c.shape for c in caches] == [(128, 2048, 1152)] * 8
    # streams x positions x 576 values in bf16, and the counters
    streams, rows, _ = caches[0].shape
    assert nbytes(state) - 64 < len(caches) * streams * rows * 2 * 576 * 2 \
        <= nbytes(state)
    one_cache = nbytes(caches[0])

    fn, lengths = entries[entry]
    inputs = [jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
              for n in lengths]
    compiled = jax.jit(functools.partial(fn, cfg), donate_argnums=(1,)) \
        .lower(on_chip(params), on_chip(state), *inputs).compile()
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n{cell} {entry}: {memory}")
    assert memory.alias_size_in_bytes >= nbytes(state)
    assert memory.temp_size_in_bytes < temp_mb << 20
    if entry == "decode":
        # nothing the size of a cache beside the caches
        assert memory.temp_size_in_bytes < one_cache // 4
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.5e9
    text = compiled.as_text()
    # since PR 54 a sparse layer's `moe/combine` is a kernel too (the
    # row walk, `weighted_row_sum`: as many as grouped products) in
    # both chunks and in `longcat.decode4k`'s step of 1,536 pairs; a
    # step of `dsv2.decode16k`'s 192 pairs gathers them
    walks = entry == "prefill" or cell.startswith("longcat")
    assert text.count("tpu_custom_call") == (1 + walks) * product_calls + (
        step_calls if entry == "decode" else chunk_calls)
    # and no value a (token, pick) pair is left: `[tokens, k, hidden]`,
    # float32 or not, nor a gather of `tokens x k` rows
    tokens, (k, hidden) = lengths[0], (6, 5120) if cell.startswith(
        "deepseek") else (12, 6144)
    assert f"[{tokens},{k},{hidden}]" not in text
    assert not [line.strip()[:120] for line in text.splitlines()
                if f"[{tokens * k},{hidden}]" in line and " gather(" in line]
    # no copy, transposition or relayout of a cache's shape
    shape = "[" + ",".join(str(n) for n in caches[0].shape) + "]"
    moved = [line.strip()[:120] for line in text.splitlines()
             if "bf16" + shape in line.split(" = ", 1)[-1].split("(")[0]
             and (" copy(" in line or " transpose(" in line)]
    assert not moved, moved


def test_the_falcon_cells_kernels_compile_at_its_shapes(one_chip, monkeypatch):
    """`falconh1.decode4k`'s two kernels as Mosaic calls: the state
    update at `[128, 2, 256, 2048]` float32 (4 MiB a stream: four
    streams a grid step take both sets of buffers to exactly the 32 MiB
    budget; `B | C` is 4 rows of 256 a stream and the state axis two
    lane tiles), and attention of 4 groups of FIVE heads (a tile of 16
    holds them) over caches of 4,096.  (The dense MLPs are XLA's
    products: `PERF.md` section 6, PR 47.)"""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    state, row, column = (128, 2, 256, 2048), (128, 2, 2048), (128, 2, 256)
    assert kernels.ssm_decode_step_refusal(
        state, {jnp.dtype(jnp.float32)}) is None
    assert kernels.ssm_step_streams(128, 2 * 256 * 2048 * 4) == 4
    compiled = jax.jit(kernels.ssm_decode_step, donate_argnums=(0,)).lower(
        shape(state), shape(state), shape((128,), jnp.bool_), shape(row),
        shape(row), shape(column), shape(column)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 128 * 2 * 256 * 2048 * 4
    assert memory.temp_size_in_bytes < 1 << 20

    bf16 = functools.partial(shape, dtype=jnp.bfloat16)
    fn = jax.jit(functools.partial(kernels.gqa_decode_attention,
                                   window=4096, scale=128 ** -0.5))
    compiled = fn.lower(bf16((128, 4, 5, 128)), bf16((128, 4, 4096, 128)),
                        bf16((128, 4, 4096, 128)),
                        shape((128,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("entry,temp_mb", [("decode", 96), ("prefill", 352)])
def test_the_falcon_cells_programs_fit_the_chip(one_chip, monkeypatch, entry,
                                                temp_mb, capsys):
    """`falconh1.decode4k`'s two programs at the cell's sizes (4 layers,
    128 streams, 4,096 positions, chunks of 2,048; 4.1 GB of weights and
    8.6 GB of state as arguments): both kinds of state are updated in
    the donated buffers.  One layer's recurrent state is 537 MB and one
    layer's K or V as much, so a copy of either shows in the
    temporaries.  A decode step holds four state updates and four
    attention calls as kernels, a prefill chunk four attention calls
    (333 MB of temporaries where the loops' scores and two copies of
    each cache took 843)."""
    import json
    import os

    from nnstreamer_tpu.models import falcon_h1 as fh

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "falcon_h1_34b_stage4_vocab8.json")
    with open(path) as f:
        cfg = fh.FalconH1Config.from_dict(json.load(f))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(lambda: fh.init_params(cfg, 0))
    state = jax.eval_shape(lambda: fh.init_state(cfg, params, 128, 4096))
    held = [sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(tree))
            for tree in (params, state)]
    assert 4.10e9 < held[0] < 4.13e9 and 8.60e9 < held[1] < 8.65e9

    def i32(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

    fn, inputs = {"decode": (fh.decode, [i32(128), i32(128)]),
                  "prefill": (fh.prefill, [i32(2048), i32(1), i32(1),
                                           i32(1)])}[entry]
    compiled = jax.jit(functools.partial(fn, cfg), donate_argnums=(1,)) \
        .lower(on_chip(params), on_chip(state), *inputs).compile()
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nfalconh1 {entry}: {memory}")
    assert memory.alias_size_in_bytes >= held[1]
    assert memory.temp_size_in_bytes < temp_mb << 20
    # arguments and temporaries together leave the chip's 16 GB room
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 14.8e9
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (8 if entry == "decode" else 4)
    assert "ssm_restore" not in text


@pytest.mark.parametrize("streams", [32, 1], ids=["step", "one_token"])
@pytest.mark.parametrize("total,window", [(16384, 16384), (1536, 512)],
                         ids=["shared", "ring"])
def test_the_paired_heads_decode_attention_compiles(one_chip, monkeypatch,
                                                    streams, total, window):
    """`phi4flash.decode16k`'s decode attention: heads of 64 handed over
    as 10 K/V pairs of 128 with 4 query rows a pair (`[q1 | 0]`, `[0 |
    q2]`), on the one shared cache of 16,384 and on a ring of 1,536 read
    through a window of 512; 32 streams a step, and ONE stream (the
    prefill's last token, which layer 17 and the layers above it run
    on), as a Mosaic kernel with no copy of a cache beside it."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, kv = (streams, 10, 4, 128), (streams, 10, total, 128)
    assert kernels.gqa_decode_attention_refusal(q, kv, kv, window) is None
    fn = jax.jit(functools.partial(kernels.gqa_decode_attention,
                                   window=window, scale=64 ** -0.5))
    compiled = fn.lower(shape(q), shape(kv), shape(kv),
                        shape((streams,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_the_paired_heads_prefill_and_the_scan_compile(one_chip, monkeypatch):
    """`phi4flash.decode16k`'s two prefill kernels: a chunk of 1,024 on
    a ring of 1,536 (a window of 512 behind every query: 512 + 1,023
    rows) at 10 pairs x 4 rows x 128, and the Mamba-1 selective scan of
    the chunk on `[16, 5120]` float32 (10 blocks of 512 channels, 4 of
    256 tokens)."""
    monkeypatch.setattr(kernels, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, ring = (1024, 10, 4, 128), (32, 10, 1536, 128)
    assert kernels.gqa_prefill_attention_refusal(
        q, ring, ring, 512, {jnp.dtype(jnp.bfloat16)}) is None
    fn = jax.jit(functools.partial(kernels.gqa_prefill_attention,
                                   window=512, scale=64 ** -0.5))
    compiled = fn.lower(shape(q), shape(ring), shape(ring),
                        shape((), jnp.int32), shape((), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1

    f32 = functools.partial(shape, dtype=jnp.float32)
    assert kernels.selective_scan_refusal(
        1024, (16, 5120), {jnp.dtype(jnp.float32)}) is None
    assert kernels._scan_tiles(1024, 5120) == (256, 512)
    compiled = jax.jit(kernels.selective_scan).lower(
        f32((1024, 5120)), f32((1024, 5120)), f32((1024, 16)),
        f32((1024, 16)), f32((16, 5120)), f32((16, 5120))).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("entry,temp_mb", [("decode", 64), ("prefill", 256)])
def test_the_phi4flash_cells_programs_fit_the_chip(one_chip, monkeypatch,
                                                   entry, temp_mb, capsys):
    """`phi4flash.decode16k`'s two programs at the cell's sizes (the
    whole model: 32 layers, 200,064 rows; 32 streams, 16,384 positions,
    chunks of 1,024; 7.7 GB of weights and 4.9 GB of state as
    arguments).  The one shared cache is 2.68 GB in two leaves: a copy
    of either, or a transposed copy of the 1 GB embedding for the tied
    head, shows in the temporaries.  A decode step holds 16 attention
    calls (8 on rings, 8 on the ONE cache), a prefill chunk 8 prefill
    attentions on rings, 9 scans and 8 one-token attentions on the
    cache's rows of the chunk's stream."""
    import json
    import os

    from nnstreamer_tpu.models import phi4_flash as pf

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "phi4_mini_flash_reasoning.json")
    with open(path) as f:
        raw = json.load(f)
    cfg = pf.Phi4FlashConfig.from_dict(raw)
    chunk = raw["serving"]["prefill_chunk"]

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(lambda: pf.init_params(cfg, 0))
    state = jax.eval_shape(lambda: pf.init_state(cfg, params, 32, 16384,
                                                 chunk))
    held = [sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(tree))
            for tree in (params, state)]
    assert 7.70e9 < held[0] < 7.72e9 and 4.85e9 < held[1] < 4.95e9

    def i32(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

    fn, inputs = {"decode": (pf.decode, [i32(32), i32(32)]),
                  "prefill": (pf.prefill, [i32(chunk), i32(1), i32(1),
                                           i32(1)])}[entry]
    compiled = jax.jit(functools.partial(fn, cfg), donate_argnums=(1,)) \
        .lower(on_chip(params), on_chip(state), *inputs).compile()
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nphi4flash {entry}: {memory}")
    assert memory.alias_size_in_bytes >= held[1]
    assert memory.temp_size_in_bytes < temp_mb << 20
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 14.0e9
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (16 if entry == "decode"
                                             else 8 + 9 + 8)
