"""Spread of two sets of runs, the way the driver reads it.

    python3 -m benchmark.spread set_a.txt set_b.txt

Each file holds the result lines of one set of runs of one cell (other
lines are skipped).  For every metric it prints both sets' medians, their
quartile spreads (first to third quartile as a share of the median,
``statistics.quantiles(values, n=4)``), the wider spread, five times it
(the bound the contract asks for), and by how much the second median
differs from the first.
"""

from __future__ import annotations

import json
import statistics
import sys

from benchmark.stats import quartile_spread


def read_set(path: str) -> dict:
    values: dict = {}
    with open(path) as f:
        for text in f:
            text = text.strip()
            if not text.startswith("{"):
                continue
            line = json.loads(text)
            for name, m in line["metrics"].items():
                values.setdefault(name, []).append(m["value"])
            values.setdefault("_correct", []).append(line["correct"])
    return values


def main(argv=None) -> int:
    a, b = (read_set(p) for p in (argv or sys.argv[1:])[:2])
    for name in sorted(k for k in a if not k.startswith("_")):
        xa, xb = a[name], b.get(name, [])
        if len(xa) < 2 or len(xb) < 2:
            continue
        sa, sb = quartile_spread(xa), quartile_spread(xb)
        ma, mb = statistics.median(xa), statistics.median(xb)
        print(f"{name}: medians {ma:.6g} / {mb:.6g} (second differs by "
              f"{(mb - ma) / ma:+.2%}); spreads {sa:.2%} / {sb:.2%}; "
              f"wider {max(sa, sb):.2%}; x5 = {5 * max(sa, sb):.2%}; "
              f"values {[round(v, 3) for v in xa]} / "
              f"{[round(v, 3) for v in xb]}")
    print("correct:", a.get("_correct"), b.get("_correct"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
