#!/usr/bin/env python3
"""Medians and quartile spreads of the records ``runs.py`` wrote.

    python3 stencilbench/scripts/spread.py <out>/<cell>.<label>.jsonl ...

For each file and each end-to-end metric: the runs, the median, and the
spread, the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; then, for
each compared number, the largest reading and whether every run read
``correct``.  Traced runs and runs of the control are listed apart.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def summary(path: str) -> None:
    recs = [json.loads(line) for line in open(path)]
    print(f"== {path}: {len(recs)} runs")
    lines = []
    for r in recs:
        line = json.loads(r["last"]) if r["rc"] == 0 and r["last"] else None
        if line is None:
            print(f"   seed {r['seed']}: rc {r['rc']} {r['err_tail'][-300:]}")
            continue
        lines.append((r, line))
    metrics = sorted({m for _, line in lines for m in line["metrics"]})
    for m in metrics:
        vals = [line["metrics"][m]["value"] for r, line in lines
                if m in line["metrics"] and not r["control"]]
        if len(vals) >= 2:
            print(f"   {m}: n={len(vals)} median={statistics.median(vals)!r}"
                  f" spread={spread(vals) if len(vals) >= 2 else 0:.5f}"
                  f" min={min(vals)!r} max={max(vals)!r}")
        elif vals:
            print(f"   {m}: {vals[0]!r}")
    for control in (False, True):
        sel = [(r, line) for r, line in lines if r["control"] == control]
        if not sel:
            continue
        checks = sorted({c for _, line in sel for c in line["checks"]})
        for c in checks:
            vals = [line["checks"][c]["value"] for _, line in sel]
            print(f"   {'control ' if control else ''}{c}: max={max(vals)!r}"
                  f" min={min(vals)!r} limit={sel[0][1]['checks'][c]['limit']}")
        print(f"   {'control ' if control else ''}correct: "
              f"{sum(line['correct'] for _, line in sel)}/{len(sel)}")


if __name__ == "__main__":
    for p in sys.argv[1:]:
        summary(p)
