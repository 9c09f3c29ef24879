"""``tools/lowering_digest.py``: the digest that judges a move of a token
model's code says "the same program" for the same program, something
else for another, and makes no weight on the way.  Toy twins of the
token cells' configurations with heads, windows, caches and chunks of whole
lanes, so that the decode kernels are in the lowered text.  No case
compares with a digest kept in the tree."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "lowering_digest.py")

#: module -> (its toy configuration, the keys that make it whole lanes)
LANES = {"head_dim": 128, "max_position_embeddings": 256}
MODELS = {
    "deepseek_v2": ("toy_dsv2", {}),
    "smallthinker": ("toy_smallthinker", dict(LANES,
                                              sliding_window_size=128)),
    "nemotron_h": ("toy_nemotron3", LANES),
    "exaone_moe": ("toy_kexaone", dict(LANES, sliding_window=128)),
    "longcat_flash": ("toy_longcat", {}),
    "falcon_h1": ("toy_falconh1", {}),
    "phi4_flash": ("toy_phi4flash", {}),
}
SIZES = {"streams": 8, "positions": 256, "chunk": 128}


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("lowering_digest", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def configs(tmp_path_factory):
    """``{module: path}``: the toy twins, widened to whole lanes."""
    out = {}
    for module, (toy, lanes) in MODELS.items():
        with open(os.path.join(REPO, "tests", "benchmark", "data",
                               toy + ".json")) as f:
            cfg = dict(json.load(f), **lanes)
        out[module] = str(tmp_path_factory.mktemp("cfg") / f"{module}.json")
        with open(out[module], "w") as f:
            json.dump(cfg, f)
    return out


def _sizes(module, **changed):
    return dict(SIZES, **({"rewind": 128} if module == "exaone_moe" else {}),
                **changed)


@pytest.fixture(scope="module")
def lowered(tool, configs):
    """``{module: (two lowerings at one size, one at twice the
    streams)}``, each ``{entry: line}``; made once a module."""
    made = {}

    def get(module):
        if module not in made:
            made[module] = tuple(
                {line[1]: line for line in tool.digests(
                    module, configs[module], _sizes(module, **changed))}
                for changed in ({}, {}, {"streams": 16}))
        return made[module]

    return get


@pytest.mark.parametrize("entry", ["decode", "prefill"])
@pytest.mark.parametrize("module", list(MODELS))
def test_one_program_one_digest_and_another_size_another(lowered, module,
                                                         entry):
    first, again, wider = (run[entry] for run in lowered(module))
    assert first == again
    assert first[0] == module and len(first[2]) == len(first[4]) == 64
    assert first[3] > 1000                       # the text's length
    # twice the streams is another decode program; a prefill chunk is
    # one stream's, so only the state it passes through is wider
    assert wider[2] != first[2]


@pytest.mark.parametrize("module", list(MODELS))
def test_the_lowering_makes_no_weight_and_no_state(tool, configs, module):
    mod, cfg = tool.load(module, configs[module])
    args = tool.abstract_arguments(mod, cfg, _sizes(module))
    assert set(args) == {"decode", "prefill"}
    for _fn, params, state, inputs in args.values():
        leaves = jax.tree_util.tree_leaves((params, state, inputs))
        assert len(leaves) > 10
        assert all(type(leaf) is jax.ShapeDtypeStruct for leaf in leaves)


def test_the_scopes_name_the_stages_the_metrics_read(tool, configs):
    mod, cfg = tool.load("exaone_moe", configs["exaone_moe"])
    args = tool.abstract_arguments(mod, cfg, _sizes("exaone_moe"))
    text, scopes = tool.lowered_text(*args["decode"])
    assert "loc(" not in text                    # no file and no line
    paths = [line.rsplit(" ", 1)[0] for line in scopes.splitlines()]
    assert paths == sorted(set(paths))
    for stage in ("state", "embed", "head", "layer00/attn_window/cache_write",
                  "layer03/attn_full/gqa_decode_attention",
                  "layer01/moe/router", "mtp/merge"):
        assert any(f"/nns.model/{stage}/" in path for path in paths), stage


def test_state_that_owns_no_layer_lowers_under_the_readers_scopes(tool,
                                                                 configs):
    """``phi4_flash``: the layers above the shared cache's writer attend
    (a kernel under their own scope) and write nothing."""
    mod, cfg = tool.load("phi4_flash", configs["phi4_flash"])
    args = tool.abstract_arguments(mod, cfg, _sizes("phi4_flash"))
    _text, scopes = tool.lowered_text(*args["decode"])
    paths = [line.rsplit(" ", 1)[0] for line in scopes.splitlines()]
    for stage in ("layer00/mamba/step", "layer01/attn_window/cache_write",
                  "layer05/attn_full/cache_write",
                  "layer05/attn_full/gqa_decode_attention", "layer06/gmu",
                  "layer07/attn_cross/gqa_decode_attention",
                  "layer07/attn_cross/diff", "ssm_restore", "head"):
        assert any(f"/nns.model/{stage}" in path for path in paths), stage
    assert not any("attn_cross/cache_write" in path for path in paths)
    _text, scopes = tool.lowered_text(*args["prefill"])
    paths = [line.rsplit(" ", 1)[0] for line in scopes.splitlines()]
    for stage in ("layer00/mamba/scan", "layer01/attn_window/"
                  "gqa_prefill_attention", "layer05/attn_full/cache_write",
                  "layer05/attn_full/gqa_decode_attention",
                  "layer07/attn_cross/gqa_decode_attention"):
        assert any(f"/nns.model/{stage}" in path for path in paths), stage


def test_the_command_line_prints_one_line_an_entry(configs, tmp_path):
    done = subprocess.run(
        [sys.executable, TOOL, "deepseek_v2", configs["deepseek_v2"],
         "--streams", "8", "--positions", "256", "--chunk", "128",
         "--text", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [line.split() for line in done.stdout.splitlines()]
    assert [line[:2] for line in lines] == [["deepseek_v2", "decode"],
                                            ["deepseek_v2", "prefill"]]
    for _module, entry, digest, length, _scopes in lines:
        with open(tmp_path / f"deepseek_v2.{entry}.txt") as f:
            text = f.read()
        assert len(text) == int(length) and len(digest) == 64
        assert (tmp_path / f"deepseek_v2.{entry}.scopes").exists()


def test_the_tool_imports_nothing_of_the_benchmark():
    with open(TOOL) as f:
        source = f.read()
    assert "import benchmark" not in source
    assert "from benchmark" not in source
