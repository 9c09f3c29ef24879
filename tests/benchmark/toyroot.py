"""Builds a throw-away benchmark root for the CPU tests: a copy of
``benchmark/`` with toy configurations, cells and traffic mixes ADDED as
files, and a ``BENCHMARK.json`` that names them.  Nothing of the copied
harness is edited, which is the add-by-files property itself."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")

# toy cell -> (configuration, mix, the cell whose launch line it takes)
TOY_CELLS = {
    "toy_ssd.replay": ("toy_ssd", "toy_replay", "ssd300.replay"),
    "toy_vit.replay": ("toy_vit", "toy_replay", "vitb16.replay"),
}


def build(root: str) -> str:
    """Make ``root`` a toy benchmark root and return it."""
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    like = {w["name"]: w for w in manifest["workloads"]}
    manifest["configs"], manifest["workloads"] = [], []
    for name in ("toy_ssd", "toy_vit"):
        shutil.copy(os.path.join(DATA, name + ".json"),
                    os.path.join(bench, "configs", name + ".json"))
        manifest["configs"].append({
            "name": name, "source": "tests/benchmark/data",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "toy"})
    for mix in ("toy_replay", "toy_pool"):
        shutil.copy(os.path.join(DATA, mix + ".json"),
                    os.path.join(bench, "traffic", mix + ".json"))
    renamed = {}
    for cell, (config, mix, model_cell) in TOY_CELLS.items():
        with open(os.path.join(bench, "workloads",
                               model_cell + ".json")) as f:
            work = json.load(f)
        work.update(name=cell, config=config, traffic=mix)
        with open(os.path.join(bench, "workloads", cell + ".json"),
                  "w") as f:
            json.dump(work, f)
        manifest["workloads"].append({
            "name": cell, "config": config, "traffic": mix,
            "chips": 1, "why": like[model_cell]["why"]})
        renamed[model_cell] = cell
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            if "workloads" in m:
                # a listed cell without a toy twin is dropped here
                m["workloads"] = [renamed[w] for w in m["workloads"]
                                  if w in renamed]
    # the open-loop camera pool: no cell of BENCHMARK.json uses that
    # traffic kind yet, so its cell, its latency metrics and its layers'
    # metrics are added here the way a later PR would add them
    shutil.copy(os.path.join(DATA, "toy_ssd.pool.json"),
                os.path.join(bench, "workloads", "toy_ssd.pool.json"))
    manifest["workloads"].append({
        "name": "toy_ssd.pool", "config": "toy_ssd", "traffic": "toy_pool",
        "chips": 1, "why": "toy"})
    with open(os.path.join(DATA, "toy_pool_entries.json")) as f:
        for section, rows in json.load(f).items():
            manifest[section] += rows
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return root
