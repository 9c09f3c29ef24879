"""Readings for the limits of the output check, and the control.

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3 \\
        --seconds 2 [--control 1]

For every seed, in ONE process (set-up is most of a run), it drives the
cell through ``benchmark.run.run_cell`` with a short window at the cell's
own load and records every number compared beside its limit; with
``--control 1`` it then reads the control on the same sampled frames: the
reference put in the program's place in the nearest precision below the
one the configuration states, which has to come out as not correct.  The
benchmark's own runs never call this; ``PERF.md`` section 2 gives the
readings each limit was set from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import BenchmarkError  # noqa: E402
from benchmark.run import Loader, run_cell  # noqa: E402


def read_seed(workload: str, seed: int, seconds: float, control: bool,
              root: str = ROOT) -> dict:
    details: dict = {}
    t0 = time.perf_counter()
    line = run_cell(workload, seed, seconds, False, root=root,
                    details=details)
    row = {"workload": workload, "seed": seed, "line": line,
           "numbers": {n["name"]: n["value"] for n in details["numbers"]},
           "seconds": time.perf_counter() - t0}
    if control:
        loader = Loader(root)
        cfg = details["cfg"]
        reference = loader.module("reference", cfg["reference"])
        numbers = reference.control(cfg, seed, details["frames"])
        row["control"] = {n["name"]: n["value"] for n in numbers}
        row["control_correct"] = all(n["value"] <= n["limit"]
                                     for n in numbers)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rows = []
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            row = read_seed(args.workload, seed, args.seconds,
                            bool(args.control))
            rows.append(row)
            print("[control] " + json.dumps(row), flush=True)
    except (BenchmarkError, ImportError, FileNotFoundError) as e:
        print(f"control: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    names = sorted(rows[0]["numbers"])
    for name in names:
        sound = max(r["numbers"][name] for r in rows)
        text = f"[control] {name}: sound max {sound:.6g}"
        if args.control and name in rows[0]["control"]:
            low = min(r["control"][name] for r in rows)
            text += f", control min {low:.6g}"
        print(text, flush=True)
    ok = all(r["line"]["correct"] for r in rows)
    bad = [r["seed"] for r in rows if r.get("control_correct")]
    print(f"[control] program correct on every seed: {ok}; control "
          f"passed (must be none): {bad}", flush=True)
    return 0 if ok and not bad else 3


if __name__ == "__main__":
    sys.exit(main())
