"""3D heat diffusion with non-zero Dirichlet boundary conditions — the paper's
Fig 6 scenario (X=64, Y=64, Z=10) through the channels-trick Conv2D encoding
and the native paths the CS-1 could not express, run to convergence through
the ``solve`` engine; optionally distributed over a device grid with halo
exchange (same ``solve()`` entry point, ``backend="halo"``).

  PYTHONPATH=src python examples/heat3d.py [--distributed]

(--distributed needs >1 jax device; run under
 XLA_FLAGS=--xla_force_host_platform_device_count=8 to try it on CPU.)
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.core import laplace_jacobi, solve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()

    spec = laplace_jacobi(3)
    bc_value = 100.0  # hot walls
    grid = (10, 64, 64)
    x0 = jnp.zeros(grid, jnp.float32)

    print(f"== 3D heat, grid (Z,X,Y)={grid}, walls at {bc_value} ==")
    # One spec, three encodings — all through the unified solver engine
    # (fixed-iteration mode), cross-validated against the oracle backend.
    ref = solve(spec, x0, backend="reference", bc=bc_value,
                rtol=None, atol=None, max_iters=args.iters).x
    for backend in ("conv", "conv3d_native", "pallas", "auto"):
        res = solve(spec, x0, backend=backend, bc=bc_value,
                    rtol=None, atol=None, max_iters=args.iters)
        tag = f"auto -> {res.backend}" if backend == "auto" else backend
        print(f"{tag:22s} err={float(jnp.abs(res.x - ref).max()):.2e}")

    # the actual experiment: iterate until the walls' heat fills the slab
    res = solve(spec, x0, backend="auto", bc=bc_value,
                rtol=1e-6, check_every=20, max_iters=20_000)
    centre = res.x[grid[0] // 2, grid[1] // 2, grid[2] // 2]
    print(f"solve: converged={res.converged} after {res.iterations} iters "
          f"(residual {res.residual:.1e}, backend {res.backend}); centre "
          f"temperature {float(centre):.3f} (walls {bc_value}) — heat "
          f"diffused inward ✓")

    if args.distributed:
        n = len(jax.devices())
        if n < 2:
            print("(--distributed skipped: single device)")
            return
        # distribute the 2D X-Y plane of the mid-Z slice problem over the
        # device mesh — the identical solve() call, backend="halo"
        mesh = jax.make_mesh((2, n // 2), ("data", "model"))
        spec2 = laplace_jacobi(2)
        x2 = jnp.zeros((2, 64, 64), jnp.float32)
        dist = solve(spec2, x2, backend="halo", mesh=mesh, bc=bc_value,
                     rtol=1e-6, check_every=20, max_iters=20_000)
        single = solve(spec2, x2, backend="reference", bc=bc_value,
                       rtol=1e-6, check_every=20, max_iters=20_000)
        err = float(jnp.abs(dist.x - single.x).max())
        print(f"distributed halo-exchange solve (mesh {dict(mesh.shape)}): "
              f"iters={list(map(int, dist.iterations))} vs single-device "
              f"{list(map(int, single.iterations))}, field err={err:.2e}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
