#!/usr/bin/env python3
"""The served cell (left out of BENCHMARK.json) with the plan cache's bucket
backend pinned, so that each backend can be read on its own:

    python3 stencilbench/scripts/serve_witness.py <backend> <seed> <seconds>

It builds, under ``.stencilbench/serve_root``, a checkout whose
BENCHMARK.json also holds the cell as ``stencilbench/tests/test_serve.py``
defines it, and runs one untraced run of it there.
"""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from stencilbench import run as bench  # noqa: E402
from stencilbench.tests.test_serve import CELL, LAYERS, METRICS  # noqa: E402


def main() -> int:
    backend, seed, seconds = sys.argv[1:4]
    root = os.path.join(ROOT, ".stencilbench", "serve_root")
    os.makedirs(root, exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"].append(CELL)
    b["end_to_end"] += METRICS
    b["per_layer"] += LAYERS
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    for d in ("stencilbench", "src"):
        if not os.path.exists(os.path.join(root, d)):
            os.symlink(os.path.join(ROOT, d), os.path.join(root, d))
    bench.ROOT = root
    from repro.core.plan_cache import PlanCache
    PlanCache._bucket_backend = lambda self, *a, **k: backend
    return bench.main(["--workload", CELL["name"], "--seed", seed,
                       "--seconds", seconds, "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
