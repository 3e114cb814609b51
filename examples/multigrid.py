"""Geometric multigrid vs single-level Jacobi — the same Table-1 Laplace
solve the paper runs on the wafer, but with the V-cycle built out of the
repo's own stencil plans (smoothers, restriction/prolongation and red-black
sweeps all dispatch through ``make_plan``).

Also solves a heterogeneous-diffusion problem: a per-cell conductivity field
``kappa`` turned into a variable-coefficient stencil
(``heterogeneous_jacobi``) whose taps carry grid-shaped weight fields — the
same spec runs through the dense / conv-gather / Pallas encodings.

  PYTHONPATH=src python examples/multigrid.py
"""
import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.core import (
    heterogeneous_jacobi,
    laplace_jacobi,
    multigrid_solve,
    solve,
    stencil_apply,
)


def main():
    grid = (64, 64)
    bc_value = 1.0
    spec = laplace_jacobi(2)
    x0 = jnp.zeros(grid, jnp.float32)

    print(f"== Laplace on {grid}, walls at {bc_value} ==")
    jac = solve(spec, x0, bc=bc_value, rtol=1e-6, check_every=20,
                max_iters=20_000)
    print(f"jacobi:    {jac.iterations} iterations "
          f"(residual {jac.residual:.1e}, backend {jac.backend})")

    mg = multigrid_solve(spec, x0, bc=bc_value, rtol=1e-6)
    print(f"multigrid: {mg.cycles} V-cycles = {mg.work_units:.0f} fine-grid "
          f"work units (residual {mg.residual:.1e}, levels "
          f"{'->'.join(str(s[0]) for s in mg.level_shapes)}, smoother "
          f"red-black)")
    err = float(jnp.abs(mg.x - jac.x).max())
    ratio = jac.iterations / mg.work_units
    print(f"agreement |mg - jacobi|_max = {err:.1e}; multigrid did "
          f"{ratio:.0f}x less fine-grid work\n")

    # Variable-coefficient diffusion: a conductive inclusion in a slab.
    n = 65
    kappa = np.ones((n, n), np.float32)
    kappa[20:45, 20:45] = 10.0  # 10x more conductive block in the middle
    hspec = heterogeneous_jacobi(kappa)
    print(f"== heterogeneous diffusion on ({n}, {n}), kappa in "
          f"[{kappa.min():.0f}, {kappa.max():.0f}] ==")
    # The spec's taps are per-cell weight fields; every supported backend
    # computes the same operator (cross-validated in tests/conformance/).
    x = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    jnp.float32)
    ref = stencil_apply(hspec, x, backend="reference", bc=bc_value)
    for backend in ("dense", "conv", "pallas"):
        from repro.core import BoundaryMode, backend_support
        mode = (BoundaryMode.MATRIX if backend == "dense"
                else BoundaryMode.MASK)
        out = stencil_apply(hspec, x, backend=backend, mode=mode, bc=bc_value)
        print(f"{backend:8s} err={float(jnp.abs(out - ref).max()):.2e}")

    hres = multigrid_solve(hspec, jnp.zeros((n, n), jnp.float32),
                           bc=bc_value, rtol=1e-6)
    print(f"multigrid: converged={hres.converged} in {hres.cycles} V-cycles "
          f"({hres.work_units:.0f} work units, residual {hres.residual:.1e})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
