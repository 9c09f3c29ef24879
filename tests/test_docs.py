"""Documentation stays in lockstep with the code.

Parity model: the reference commits per-element .md files (e.g.
gst/nnstreamer/elements/gsttensor_transform.md); here the per-element
reference is GENERATED from the registry, and this test fails whenever
an element or property exists without an up-to-date committed page —
rerun ``python tools/gen_element_docs.py`` and commit.
"""

import fnmatch
import glob
import inspect
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC_DIR = os.path.join(ROOT, "Documentation", "elements")


def _load_generator():
    from nnstreamer_tpu.tools import gen_element_docs

    return gen_element_docs


def test_every_element_documented_and_current():
    gen = _load_generator()
    pages = gen.generate()
    stale, missing = [], []
    for fname, content in pages.items():
        path = os.path.join(DOC_DIR, fname)
        if not os.path.exists(path):
            missing.append(fname)
        elif open(path).read() != content:
            stale.append(fname)
    assert not missing, (
        f"undocumented elements: {missing} — run "
        "`python tools/gen_element_docs.py` and commit")
    assert not stale, (
        f"stale element docs: {stale} — run "
        "`python tools/gen_element_docs.py` and commit")


def test_doc_pages_cover_all_properties():
    """Belt and braces: each committed page lists every constructor
    property of its element (guards against a generator regression)."""
    from nnstreamer_tpu.runtime.registry import element_factory, list_elements

    for name in list_elements():
        page = open(os.path.join(DOC_DIR, f"{name}.md")).read()
        cls = element_factory(name)
        for p in inspect.signature(cls.__init__).parameters.values():
            if p.name in ("self", "name", "props") or \
                    p.kind == inspect.Parameter.VAR_KEYWORD:
                continue
            prop = p.name.rstrip("_").replace("_", "-")
            assert f"`{prop}`" in page, (
                f"{name}.md missing property {prop!r}")


def test_check_cli_names_resolve_to_docs():
    """Round-2 verdict done-criterion: every element name the check CLI
    prints resolves to a documented page."""
    from nnstreamer_tpu.runtime.registry import list_elements

    for name in list_elements():
        assert os.path.exists(os.path.join(DOC_DIR, f"{name}.md"))


def test_guides_exist_and_are_substantial():
    for fname, min_lines in [("writing-filter-subplugin.md", 60),
                             ("getting-started.md", 60)]:
        path = os.path.join(ROOT, "Documentation", fname)
        assert os.path.exists(path), f"missing guide {fname}"
        assert len(open(path).read().splitlines()) >= min_lines, (
            f"{fname} too thin")


# -- pages name only what exists -----------------------------------------------

#: what building, testing and running leave behind: not part of the tree
_UNTRACKED = {".git", "build", "chiprun_out", "__pycache__", ".jax_cache",
              ".pytest_cache", ".hypothesis"}
_TOP_DIRS = {d for d in os.listdir(ROOT)
             if os.path.isdir(os.path.join(ROOT, d))
             and d not in _UNTRACKED and not d.endswith(".egg-info")}
_PACKAGE_DIRS = {d for d in os.listdir(os.path.join(ROOT, "nnstreamer_tpu"))
                 if os.path.isdir(os.path.join(ROOT, "nnstreamer_tpu", d))}
_BASENAMES = set()
for _dir, _subdirs, _files in os.walk(ROOT):
    _subdirs[:] = [d for d in _subdirs if d not in _UNTRACKED
                   and not d.endswith(".egg-info")]
    _BASENAMES.update(_files)
_SCRIPT = re.compile(r"^(nns-[a-z]+(-[a-z]+)*|nnstreamer-tpu-[a-z-]+)$")
_MODULE = re.compile(r"python3? -m ([A-Za-z_][\w.]*)")
_BARE_FILE = re.compile(r"^[\w*-][\w.*-]*\.(py|json|jsonl|md)$")


def _console_scripts():
    # by hand: ``tomllib`` is not in the oldest Python the package takes
    text = open(os.path.join(ROOT, "pyproject.toml")).read()
    block = text.split("[project.scripts]", 1)[1].split("\n[", 1)[0]
    return {line.split("=", 1)[0].strip()
            for line in block.splitlines() if "=" in line}


def _tokens(text: str):
    """The backticked spans of a page, and each line of its fenced
    blocks (a command in a fence dangles like one in a span)."""
    parts = re.split(r"^```.*$", text, flags=re.M)
    for i, part in enumerate(parts):
        if i % 2:
            yield from (ln for ln in part.splitlines() if ln.strip())
        else:
            yield from (" ".join(t.split()) for t in
                        re.findall(r"`([^`]+)`", part.replace("``", "`")))


def _dangling(token: str, scripts):
    """Why ``token`` names something that is not there, or None.  A
    path is checked when it starts with a top-level directory of the
    repo, or is a ``.py`` file under a directory of the package
    (``runtime/fusion.py``); a bare file name (``chip_smoke.py``,
    ``BENCHMARK.json``) when it leads the token or follows ``python``."""
    for module in _MODULE.findall(token):
        path = os.path.join(ROOT, *module.split("."))
        if module.split(".")[0] in _TOP_DIRS and not (
                os.path.exists(path + ".py")
                or os.path.exists(os.path.join(path, "__main__.py"))):
            return f"no module {module}"
    words = [w.strip("()[],;\"'") for w in token.split()]
    for i, word in enumerate(words):
        if _SCRIPT.match(word):
            if word not in scripts:
                return f"no console script {word}"
            continue
        path, _, test_id = word.partition("::")
        path = re.sub(r":[\w,:-]+$", "", path).rstrip(".")
        if any(c in path for c in "<>{}$=") or "://" in path:
            continue            # a pattern of the page's own, not a name
        first = path.split("/", 1)[0]
        if "/" not in path:
            if not (_BARE_FILE.match(path)
                    and (i == 0 or words[i - 1] in ("python", "python3"))):
                continue
            found = fnmatch.filter(_BASENAMES, path)
        else:
            roots = ([ROOT] if first in _TOP_DIRS else []) + (
                [os.path.join(ROOT, "nnstreamer_tpu")]
                if first in _PACKAGE_DIRS and path.endswith(".py") else [])
            if not roots:
                continue
            found = [f for r in roots for f in glob.glob(
                os.path.join(r, path))]
        if not found:
            return f"no file {path}"
        name = test_id.rpartition("::")[2].split("[", 1)[0]
        if name and not re.search(rf"def {re.escape(name)}\(",
                                  open(found[0]).read()):
            return f"no test {name} in {path}"
    return None


_KEEP_HISTORY = {"PERF.md", "ROADMAP.md", "CHANGES.md", "VERDICT.md"}
_PAGES = [p for p in ["README.md", "PARITY.md", "BASELINE.md",
                      ".claude/skills/verify/SKILL.md"]
          + sorted("Documentation/" + f
                   for f in os.listdir(os.path.join(ROOT, "Documentation"))
                   if f.endswith(".md"))
          if os.path.exists(os.path.join(ROOT, p))
          and p not in _KEEP_HISTORY]


@pytest.mark.parametrize("page", _PAGES)
def test_pages_name_only_what_exists(page):
    """Every repo path, ``python -m`` module and console script a page
    names in backticks (or in a fenced block) is in the tree or in
    ``pyproject.toml``; a ``path::test`` names a test that is defined.
    The records that keep history (``PERF.md``, ``ROADMAP.md``,
    ``CHANGES.md``, ``VERDICT.md``) may name what is gone."""
    scripts = _console_scripts()
    text = open(os.path.join(ROOT, page)).read()
    gone = sorted({f"`{t}`: {why}" for t in _tokens(text)
                   for why in [_dangling(t, scripts)] if why})
    assert not gone, f"{page} names what is not there:\n" + "\n".join(gone)
