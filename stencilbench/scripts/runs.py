#!/usr/bin/env python3
"""Run one cell several times, each run in a process of its own, as a
benchmark check runs it, and keep one JSON record a run.

    python3 stencilbench/scripts/runs.py <cell> <seconds> <label> \\
        <seed>[:<trace>[:control]] ...

Each record (``<out>/<cell>.<label>.jsonl``, ``<out>`` from ``--out``,
``.stencilbench/runs`` by default) holds the seed, the flags, the exit code, the
wall time, the last line of stdout, the run's ``setup``/``window``/``check``
lines from stderr, and the end of stderr where the run failed.  This
process never imports JAX, so each child has the chip to itself.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RUN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "run.py")


def one(cell: str, seconds: float, spec: str) -> dict:
    seed, trace, control = (spec.split(":") + ["", ""])[:3]
    if control not in ("", "control"):
        raise SystemExit(f"{spec!r}: the third field is 'control' or none")
    control = control == "control"
    argv = [sys.executable, RUN, "--workload", cell, "--seed", seed,
            "--seconds", str(seconds), "--trace", trace or "0"]
    if control:
        argv.append("--control")
    t0 = time.perf_counter()
    p = subprocess.run(argv, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    out = p.stdout.strip().splitlines()
    notes = [line for line in p.stderr.splitlines()
             if line.startswith(('{"setup"', '{"window"', "check "))]
    return {"seed": int(seed), "trace": int(trace or 0),
            "control": control, "rc": p.returncode, "wall": wall,
            "last": out[-1] if out else None, "notes": notes,
            "err_tail": p.stderr[-2000:] if p.returncode else ""}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("seconds", type=float)
    ap.add_argument("label")
    ap.add_argument("specs", nargs="+")
    ap.add_argument("--out", default=os.path.join(".stencilbench", "runs"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.cell}.{args.label}.jsonl")
    for spec in args.specs:
        rec = one(args.cell, args.seconds, spec)
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"{args.cell} {spec} rc={rec['rc']} wall={rec['wall']:.1f} "
              f"{(rec['last'] or '')[:300]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
