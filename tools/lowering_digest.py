#!/usr/bin/env python
"""The digest of a token model's two programs, for judging a refactor.

    python tools/lowering_digest.py deepseek_v2 \\
        benchmark/configs/deepseek_v2_share4.json \\
        --streams 32 --positions 16640 --chunk 2048 [--text DIR]

Lowers every entry of ``nnstreamer_tpu.models.<module>.entries(...)`` as
the ``jax-xla`` filter does (``step(params, state, *inputs)`` under the
``nns.model`` scope, the state donated) and prints one line an entry:
module, entry, sha-256 of the lowered text, the text's length, and
sha-256 of the text's scopes (how many operations lie under each
``jax.named_scope`` path: the plain text carries no scope, and the
per-layer metrics are read by scope).  Every argument is ABSTRACT: the weights are ``jax.eval_shape`` of the model's
``init_params`` (the shapes of ``param_shapes``, the types its roles
get), the state ``jax.eval_shape`` of ``init_state``, so no weight is
made and a 236 B share lowers on any host.  ``--text DIR`` writes the
texts and the scopes, so that two trees can be diffed where a digest
differs.

A move of code that changes no program leaves every digest as it was:
run it on both trees with the same arguments and compare the lines
(``Documentation/stateful-models.md``, "Adding a token model", has the
cells' sizes).  The text is what THIS host's backend lowers to, so a
digest says "the same program" only beside one from the same host and
JAX.  Compare on the CPU, where the kernels are the Pallas
interpreter's loops: on a TPU a kernel is a Mosaic call whose
serialized body carries file names and lines, so there two checkouts at
two paths differ in every program that holds a kernel, and a call site
that moved shows as a difference.  No golden digest is kept in the
tree: every PR that means to change a program would have to edit it.
"""

from __future__ import annotations

import argparse
import collections
import hashlib
import importlib
import inspect
import json
import os
import re
import sys

try:
    import nnstreamer_tpu  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def load(module: str, config: str):
    """``(the model's module, its configuration)`` from a module name
    under ``nnstreamer_tpu.models`` and a JSON file its one
    ``<Config>.from_dict`` takes."""
    mod = importlib.import_module(f"nnstreamer_tpu.models.{module}")
    made = [v for v in vars(mod).values() if inspect.isclass(v)
            and v.__module__ == mod.__name__ and hasattr(v, "from_dict")]
    if len(made) != 1:
        raise ValueError(f"{mod.__name__} has {len(made)} classes with a "
                         "from_dict, not one")
    with open(config) as f:
        return mod, made[0].from_dict(json.load(f))


def abstract_arguments(mod, cfg, sizes: dict) -> dict:
    """``{entry: (fn, params, state, inputs)}`` with every leaf of the
    three a ``jax.ShapeDtypeStruct``.  ``sizes`` is what ``entries``
    takes after the configuration (``streams``, ``positions``,
    ``chunk``, and ``rewind`` where the model's state is sized by
    one)."""
    import jax

    table = mod.entries(cfg, **sizes)
    params = jax.eval_shape(lambda: mod.init_params(cfg, 0))
    state = jax.eval_shape(table["init_state"], params)
    return {name: (entry[0], params, state,
                   [jax.ShapeDtypeStruct(tuple(shape), entry[2])
                    for shape in entry[1]])
            for name, entry in table["entries"].items()}


def lowered_text(fn, params, state, inputs) -> tuple:
    """``(text, scopes)`` of ``fn`` lowered as ``filters/jax_xla.py``
    ``_compile_stateful`` lowers an entry: the text without locations
    (a file's lines move with every edit), and one line a scope path
    (``jit(step)/nns.model/layer00/attn/cache_write/scatter 2``) with
    the operations that carry it."""
    import jax

    def step(params, state, *inputs):
        with jax.named_scope("nns.model"):
            state, out = fn(params, state, *inputs)
        return state, tuple(out)

    lowered = jax.jit(step, donate_argnums=(1,)).lower(
        params, state, *inputs)
    located = lowered.as_text(debug_info=True)
    paths = dict(re.findall(r'^(#loc\d+) = loc\("(jit\([^"]*)"', located,
                            re.M))
    uses = collections.Counter(
        paths[use] for use in re.findall(r" loc\((#loc\d+)\)", located)
        if use in paths)
    return lowered.as_text(), "".join(
        f"{path} {n}\n" for path, n in sorted(uses.items()))


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def digests(module: str, config: str, sizes: dict, text_dir=None) -> list:
    """One ``(module, entry, sha-256 of the text, its length, sha-256
    of the scopes)`` an entry, in the entries' order."""
    mod, cfg = load(module, config)
    out = []
    for name, args in abstract_arguments(mod, cfg, sizes).items():
        text, scopes = lowered_text(*args)
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            for kind, body in (("txt", text), ("scopes", scopes)):
                with open(os.path.join(
                        text_dir, f"{module}.{name}.{kind}"), "w") as f:
                    f.write(body)
        out.append((module, name, _sha(text), len(text), _sha(scopes)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("module", help="a token model under nnstreamer_tpu."
                    "models, e.g. deepseek_v2")
    ap.add_argument("config", help="a JSON file the model's "
                    "<Config>.from_dict takes")
    ap.add_argument("--streams", type=int, required=True)
    ap.add_argument("--positions", type=int, required=True)
    ap.add_argument("--chunk", type=int, required=True)
    ap.add_argument("--rewind", type=int, help="where the model's "
                    "init_state takes one (exaone_moe)")
    ap.add_argument("--text", metavar="DIR", help="write each entry's "
                    "lowered text to DIR/<module>.<entry>.txt and its "
                    "scopes to DIR/<module>.<entry>.scopes")
    args = ap.parse_args(argv)
    sizes = {k: getattr(args, k) for k in ("streams", "positions", "chunk",
                                           "rewind")
             if getattr(args, k) is not None}
    for line in digests(args.module, args.config, sizes, args.text):
        print(*line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
