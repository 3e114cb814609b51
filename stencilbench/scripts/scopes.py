#!/usr/bin/env python3
"""One traced run seen through the program's own scopes and spans.

    python3 stencilbench/scripts/scopes.py <trace.xplane.pb>

Prints, for the ``bench.window`` span of the trace (``stencilbench/
scopes.py`` decodes it):

* device seconds per program scope (``repro.sweep``, ``repro.boundary``,
  ``repro.check``), with the ops in no scope listed by their short name;
* host milliseconds per ``repro.solve.*`` span, one row per solve id, and
  the device's idle time inside each solve's readback;
* the shared clock: sweep-kernel ops that lie outside every solve's
  stretch from the start of its ``repro.solve.dispatch`` to the end of its
  ``repro.solve.wait``, and ops that run inside a ``repro.solve.readback``
  span.  Both should be none where host and device share the clock.
"""
from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from stencilbench import scopes as sc, trace  # noqa: E402
from stencilbench.metrics.kernel_gpts_per_s import is_sweep_kernel  # noqa: E402

PHASES = ("dispatch", "wait", "readback")


def main(path: str) -> int:
    scopes = sc.load(path)
    if scopes is None:
        print(f"{path}: no TPU op or no bench.window span")
        return 1
    w0, w1 = scopes.window
    busy = trace.reduce(trace.load(path)).busy_s
    by_scope, unscoped = scopes.seconds()
    print(f"window {(w1 - w0) * 1e-9:.6f} s, device busy {busy:.6f} s")
    print("device seconds by program scope:")
    for scope, s in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        print(f"  {scope:16s} {s:12.6f} s {100 * s / busy:8.3f}% of busy")
        if scope == sc.UNSCOPED:
            for op, t in sorted(unscoped.items(), key=lambda kv: -kv[1]):
                print(f"      {t:12.6f} s  {op}")

    idle = dict(scopes.idle_in("repro.solve.readback"))
    solves = collections.defaultdict(dict)
    for name, solve, s, e in scopes.spans:
        if e > w0 and s < w1:
            solves[solve][name.rsplit(".", 1)[-1]] = (s, e)
    if not solves:
        print("no repro.solve.* span in the window")
        return 0
    print("host ms by span, per solve (idle: the device's, in the readback):")
    print("  solve " + " ".join(f"{p:>10s}" for p in PHASES) + "       idle")
    for solve in sorted(solves, key=lambda k: (k is None, k)):
        row = solves[solve]
        cells = [f"{(row[p][1] - row[p][0]) * 1e-6:10.3f}" if p in row
                 else f"{'-':>10s}" for p in PHASES]
        print(f"  {solve!s:>5s} " + " ".join(cells)
              + (f" {idle[solve] * 1e3:10.3f}" if solve in idle else ""))

    reach = [(row["dispatch"][0], row["wait"][1]) for row in solves.values()
             if "dispatch" in row and "wait" in row]
    readbacks = [row["readback"] for row in solves.values()
                 if "readback" in row]
    kernels, outside, inside = 0, [], []
    for ops in scopes.ops.values():
        for op, _, s, e in ops:
            if not (e > w0 and s < w1):
                continue
            if is_sweep_kernel(op):
                kernels += 1
                if not any(a <= s and e <= b for a, b in reach):
                    outside.append(min(max(a - s, e - b)
                                       for a, b in reach))
            # how long before the readback's end the op started in it
            early = max((b - max(s, a) for a, b in readbacks
                         if s < b and a < e), default=0.0)
            if early > 0:
                inside.append(early)
    print(f"clock: {len(outside)} of {kernels} sweep-kernel ops outside "
          f"every solve's dispatch..wait, by at most "
          f"{max(outside, default=0.0) * 1e-6:.3f} ms; {len(inside)} ops "
          f"inside a readback span, from at most "
          f"{max(inside, default=0.0) * 1e-6:.3f} ms before its end")
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    sys.exit(main(sys.argv[1]))
