"""Quickstart: the paper's 2D Jacobi benchmark through every encoding, all
dispatched through the unified ``stencil_apply`` / ``make_plan`` API, then
run to convergence through the ``solve`` engine.

  PYTHONPATH=src python examples/quickstart.py

Builds a 64x64 Laplace problem with Dirichlet BC = 1.0 (paper Table 1 shape),
lowers it through (a) the dense-layer encoding, (b) the convolution encoding
with the mask trick, (c) the direct Pallas stencil kernel, (d) the
temporally-blocked fused kernel, (e) whatever the auto cost model picks —
cross-validates that all agree with the reference oracle, reports the
paper's delivered-performance metric for each, and finally runs the actual
experiment: iterate until the relative residual converges, the whole time
loop as one compiled program (no manual Python iteration loop).
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax.numpy as jnp
import numpy as np

from repro.core import (
    BoundaryMode,
    DeliveredPerf,
    encoding_flops_per_point,
    laplace_jacobi,
    make_plan,
    solve,
)
from benchmarks.common import time_callable


def main():
    spec = laplace_jacobi(2)
    grid = (64, 64)
    iters = 20
    steps = 4
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.standard_normal((steps, *grid)), jnp.float32)

    print(f"== 2D Jacobi, grid {grid}, {iters} iterations, BC=1.0 ==")
    # the oracle, via the same solver engine (fixed-iteration mode) instead
    # of a manual per-instance Python loop
    ref = solve(spec, x0, backend="reference", bc=1.0,
                rtol=None, atol=None, max_iters=iters).x

    plans = {
        "dense-layer (Alg 1)": make_plan(
            spec, grid, backend="dense", bc=1.0, mode=BoundaryMode.MATRIX,
            iters=iters),
        "conv-layer (Alg 2, mask trick)": make_plan(
            spec, grid, backend="conv", bc=1.0, mode=BoundaryMode.MASK,
            iters=iters),
        "conv-layer (pad mode)": make_plan(
            spec, grid, backend="conv", bc=1.0, mode=BoundaryMode.PAD,
            iters=iters),
        "pallas direct": make_plan(
            spec, grid, backend="pallas", bc=1.0, iters=iters, fuse=1),
        "pallas fused T=4": make_plan(
            spec, grid, backend="pallas", bc=1.0, iters=iters, fuse=4),
    }
    auto = make_plan(spec, grid, backend="auto", bc=1.0, iters=iters)
    plans[f"auto -> {auto.backend}"] = auto

    n = grid[0] * grid[1]
    for name, plan in plans.items():
        if plan.backend == "dense":
            flops = encoding_flops_per_point(spec, "dense", n_total=n)
        elif plan.backend in ("conv", "conv3d_native"):
            flops = encoding_flops_per_point(spec, "conv")
        else:
            flops = encoding_flops_per_point(spec, "direct")
        out = plan(x0)
        err = float(jnp.abs(out - ref).max())
        sec = time_callable(plan, x0, warmup=1, iters=1)
        perf = DeliveredPerf(n * steps, flops, 7, iters, sec)
        print(f"{name:32s} max|err|={err:.2e}  "
              f"delivered={perf.delivered_gflops:8.3f} GFLOPS  "
              f"useful={perf.useful_gflops:7.3f}  waste x{perf.waste_ratio:.1f}")
    print("\nall encodings agree with the reference oracle ✓")

    # The paper's actual experiment is a *solve*: iterate until the residual
    # converges.  No manual loop — the solver runs the whole time loop
    # on-device, checking the relative L2 residual every 20 iterations.
    print("\n== run to convergence (solve) ==")
    res = solve(spec, jnp.zeros(grid, jnp.float32), bc=1.0,
                rtol=1e-6, check_every=20, max_iters=20_000)
    print(f"auto -> {res.backend}: converged={res.converged} in "
          f"{res.iterations} iterations  (residual {res.residual:.2e}, "
          f"{res.wall_seconds:.2f}s wall, "
          f"{res.wall_seconds / res.iterations * 1e6:.0f} us/iter)")
    print(f"residual trajectory (every {res.check_every * 10} iters): "
          + " ".join(f"{r:.1e}" for r in res.residual_history[::10]))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
