"""Multi-device solver tests: distributed ``solve()`` on a forced 8-device
CPU mesh must match the single-device solve per step and at convergence.

Each case runs in a subprocess (the ``run_with_devices`` fixture from
tests/conftest.py) so the main test process keeps its single-device view.
"""
import pytest

pytestmark = pytest.mark.slow


class TestDistributedSolve:
    def test_matches_single_device_per_step_and_at_convergence(
            self, run_with_devices):
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import laplace_jacobi, solve

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        spec = laplace_jacobi(2)
        rng = np.random.default_rng(0)
        x0 = jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32)

        # per-step: k fixed iterations through the sharded halo-exchange
        # chunk equal the single-device oracle's k steps
        for k in (1, 3, 10):
            d = solve(spec, x0, backend="halo", mesh=mesh, bc=1.0,
                      rtol=None, atol=None, max_iters=k)
            s = solve(spec, x0, backend="reference", bc=1.0,
                      rtol=None, atol=None, max_iters=k)
            err = float(jnp.abs(d.x - s.x).max())
            assert err < 1e-5, (k, err)

        # at convergence: same iteration counts, same field
        d = solve(spec, x0, backend="halo", mesh=mesh, bc=1.0,
                  rtol=1e-6, check_every=10, max_iters=2000)
        s = solve(spec, x0, backend="reference", bc=1.0,
                  rtol=1e-6, check_every=10, max_iters=2000)
        assert d.converged.all() and s.converged.all()
        assert np.array_equal(d.iterations, s.iterations), \
            (d.iterations, s.iterations)
        err = float(jnp.abs(d.x - s.x).max())
        assert err < 1e-5, err
        assert d.backend == "halo"
        print("dist-solve ok", err)
        """)
        assert "dist-solve ok" in out

    def test_nine_point_corners_ride_the_exchange(self, run_with_devices):
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import box, solve

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        spec = box(2)   # 9-point: corner halos must survive the two phases
        rng = np.random.default_rng(1)
        x0 = jnp.asarray(rng.standard_normal((1, 8, 16)), jnp.float32)
        d = solve(spec, x0, backend="halo", mesh=mesh, bc=0.5,
                  rtol=None, atol=None, max_iters=3)
        s = solve(spec, x0, backend="reference", bc=0.5,
                  rtol=None, atol=None, max_iters=3)
        err = float(jnp.abs(d.x - s.x).max())
        assert err < 1e-5, err
        print("box-solve ok", err)
        """)
        assert "box-solve ok" in out

    def test_fused_matches_single_device_both_parities(self,
                                                       run_with_devices):
        # Deep-halo fusion: fuse=k chunks must equal the single-device solve
        # per chunk and at convergence, on both local-tile parities (16x16
        # over (2,2) gives even 8x8 tiles; 18x18 gives odd 9x9 tiles, so
        # every trapezoid margin arithmetic path runs).
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import laplace_jacobi, solve

        mesh = jax.make_mesh((2, 2), ("data", "model"))
        spec = laplace_jacobi(2)
        rng = np.random.default_rng(2)
        for n in (16, 18):
            x0 = jnp.asarray(rng.standard_normal((2, n, n)), jnp.float32)
            # per chunk: one fixed 8-iteration chunk at each fuse depth
            s = solve(spec, x0, backend="reference", bc=1.0,
                      rtol=None, atol=None, max_iters=8)
            for fuse in (2, 4):
                d = solve(spec, x0, backend="halo", mesh=mesh, bc=1.0,
                          fuse=fuse, rtol=None, atol=None, max_iters=8)
                err = float(jnp.abs(d.x - s.x).max())
                assert d.fuse == fuse and err < 1e-5, (n, fuse, err)
            # at convergence: fuse divides check_every, counts must agree
            d = solve(spec, x0, backend="halo", mesh=mesh, bc=1.0, fuse=4,
                      rtol=1e-6, check_every=16, max_iters=2000)
            s = solve(spec, x0, backend="reference", bc=1.0,
                      rtol=1e-6, check_every=16, max_iters=2000)
            assert d.converged.all() and s.converged.all(), n
            assert np.array_equal(d.iterations, s.iterations), n
            err = float(jnp.abs(d.x - s.x).max())
            assert err < 1e-5, (n, err)
        print("fused-dist ok")
        """)
        assert "fused-dist ok" in out

    def test_fused_deep_halo_radius2_and_corners(self, run_with_devices):
        # radius-2 star at fuse=2 exchanges a 4-deep halo; box corners must
        # survive the deep two-phase exchange through every fused substep.
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import box, solve, star

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(3)
        x0 = jnp.asarray(rng.standard_normal((1, 16, 32)), jnp.float32)
        for spec in (star(2, [0.15, 0.05], center=0.2), box(2)):
            d = solve(spec, x0, backend="halo", mesh=mesh, bc=0.5, fuse=2,
                      rtol=None, atol=None, max_iters=6)
            s = solve(spec, x0, backend="reference", bc=0.5,
                      rtol=None, atol=None, max_iters=6)
            err = float(jnp.abs(d.x - s.x).max())
            assert err < 1e-5, (spec.name, err)
        print("deep-halo ok")
        """)
        assert "deep-halo ok" in out

    def test_variable_coefficients_shard_with_the_grid(self,
                                                       run_with_devices):
        # Per-cell weight fields shard P(None, row, col) and are exchanged
        # once per chunk; the fused distributed solve must match the oracle.
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import heterogeneous_jacobi, solve

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(4)
        kappa = 1.0 + 9.0 * rng.random((16, 16)).astype(np.float32)
        spec = heterogeneous_jacobi(kappa)
        x0 = jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32)
        for fuse in (1, 3):
            d = solve(spec, x0, backend="halo", mesh=mesh, bc=1.0, fuse=fuse,
                      rtol=None, atol=None, max_iters=6)
            s = solve(spec, x0, backend="reference", bc=1.0,
                      rtol=None, atol=None, max_iters=6)
            err = float(jnp.abs(d.x - s.x).max())
            assert err < 1e-5, (fuse, err)
        print("varcoef-dist ok")
        """)
        assert "varcoef-dist ok" in out

    def test_solver_auto_selects_legal_halo_fuse(self, run_with_devices):
        # the schedule must hand the solver a depth that divides check_every
        # and fits the local tile — and the result still matches.
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import laplace_jacobi, solve

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        spec = laplace_jacobi(2)
        rng = np.random.default_rng(5)
        x0 = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
        d = solve(spec, x0, backend="halo", mesh=mesh, bc=1.0,
                  rtol=1e-6, check_every=12, max_iters=1200, tuned=None)
        assert d.fuse >= 1 and 12 % d.fuse == 0, d.fuse
        assert d.fuse * spec.radius <= min(16 // 2, 16 // 4), d.fuse
        s = solve(spec, x0, backend="reference", bc=1.0,
                  rtol=1e-6, check_every=12, max_iters=1200)
        err = float(jnp.abs(d.x - s.x).max())
        assert err < 1e-5, err
        print("auto-fuse ok", d.fuse)
        """)
        assert "auto-fuse ok" in out

    def test_batched_distributed_convergence(self, run_with_devices):
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import laplace_jacobi, solve

        mesh = jax.make_mesh((2, 2), ("data", "model"))
        spec = laplace_jacobi(2)
        x0 = jnp.stack([jnp.zeros((16, 16)),
                        0.5 * jnp.ones((16, 16))]).astype(jnp.float32)
        d = solve(spec, x0, backend="halo", mesh=mesh, bc=1.0,
                  rtol=1e-6, check_every=10, max_iters=2000)
        s = solve(spec, x0, backend="reference", bc=1.0,
                  rtol=1e-6, check_every=10, max_iters=2000)
        assert d.converged.all()
        assert np.array_equal(d.iterations, s.iterations)
        err = float(jnp.abs(d.x - s.x).max())
        assert err < 1e-5, err
        print("batched-dist ok", list(map(int, d.iterations)))
        """)
        assert "batched-dist ok" in out
