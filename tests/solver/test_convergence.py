"""Solver-tier convergence tests: analytic solutions, residual behaviour,
chunking/fuse invariance, and batched-vs-loop equivalence.

Two analytic problems pin the solver down end to end:

  * Laplace on the unit square with ``u = sin(pi x)`` on the top wall and 0
    elsewhere — known series solution ``u = sinh(pi y) sin(pi x)/sinh(pi)``;
    Jacobi must converge to it within the O(h^2) discretization error.
  * Explicit heat stepping ``x <- x + c*Lap(x)`` with zero walls, started on
    the fundamental eigenmode — the field decays *exactly* by the known
    eigenvalue per step, so both the fixed-iteration trajectory and the
    iterations-to-convergence count are predictable in closed form.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BoundaryMode,
    DirichletBC,
    Solver,
    StencilSpec,
    laplace_jacobi,
    solve,
)

RNG = np.random.default_rng(20260802)


def heat_spec(c: float) -> StencilSpec:
    """Explicit 2D heat-equation step: out = x + c * (5-point Laplacian)."""
    taps = {(0, 0): 1.0 - 4 * c, (1, 0): c, (-1, 0): c, (0, 1): c, (0, -1): c}
    return StencilSpec(taps=taps, name="heat2d")


def heat_mode(n: int) -> np.ndarray:
    """Fundamental eigenmode of the zero-wall heat step on an n×n grid."""
    s = np.sin(np.pi * np.arange(n) / (n - 1))
    return np.outer(s, s).astype(np.float32)


class TestAnalyticLaplace:
    """Converge to the series solution of Laplace on a rectangle."""

    N = 24

    def _problem(self):
        n = self.N
        xs = np.linspace(0.0, 1.0, n)
        bc_grid = np.zeros((n, n), np.float32)
        bc_grid[-1, :] = np.sin(np.pi * xs)          # hot top wall
        ys = xs[:, None]
        analytic = (np.sinh(np.pi * ys) / np.sinh(np.pi)
                    * np.sin(np.pi * xs)[None, :]).astype(np.float32)
        return DirichletBC(jnp.asarray(bc_grid)), analytic

    @pytest.mark.parametrize("backend,mode", [
        ("reference", BoundaryMode.MASK),
        ("conv", BoundaryMode.MASK),
        ("dense", BoundaryMode.MATRIX),
    ])
    def test_converges_to_series_solution(self, backend, mode):
        bc, analytic = self._problem()
        res = solve(laplace_jacobi(2), jnp.zeros((self.N, self.N), jnp.float32),
                    backend=backend, bc=bc, mode=mode, rtol=0.0, atol=2e-5,
                    check_every=50, max_iters=6000)
        assert res.converged, res.residual
        assert res.backend == backend
        # iteration error (~atol/(1-rho)) + O(h^2) discretization error
        err = float(np.abs(np.asarray(res.x) - analytic).max())
        assert err < 0.02, err

    def test_backends_agree_at_convergence(self):
        bc, _ = self._problem()
        fields = [
            np.asarray(solve(laplace_jacobi(2),
                             jnp.zeros((self.N, self.N), jnp.float32),
                             backend=b, bc=bc, mode=m, rtol=0.0, atol=2e-5,
                             check_every=50, max_iters=6000).x)
            for b, m in (("reference", BoundaryMode.MASK),
                         ("conv", BoundaryMode.MASK),
                         ("dense", BoundaryMode.MATRIX))
        ]
        for f in fields[1:]:
            np.testing.assert_allclose(f, fields[0], atol=1e-3)


class TestAnalyticHeatDecay:
    """The eigenmode decays by exactly mu per step; both the trajectory and
    the iterations-to-convergence count follow in closed form."""

    N = 16
    C = 0.15

    @pytest.mark.parametrize("backend, fuse", [
        ("reference", None), ("conv", None), ("pallas", None),
        ("pallas", 4)], ids=["reference", "conv", "pallas", "pallas-fuse4"])
    def test_fixed_iteration_decay_rate(self, backend, fuse):
        v0 = heat_mode(self.N)
        mu = self._mu()
        k = 120
        res = solve(heat_spec(self.C), jnp.asarray(v0), backend=backend,
                    bc=0.0, rtol=None, atol=None, max_iters=k, fuse=fuse)
        assert res.iterations == k and not res.converged
        if fuse is not None:
            assert res.fuse == fuse
        np.testing.assert_allclose(np.asarray(res.x), mu**k * v0, atol=1e-3)

    @pytest.mark.parametrize("backend", ["reference", "conv", "pallas"])
    def test_iterations_to_convergence_match_theory(self, backend):
        v0 = heat_mode(self.N)
        mu = self._mu()
        atol, check = 1e-5, 50
        res = solve(heat_spec(self.C), jnp.asarray(v0), backend=backend,
                    bc=0.0, rtol=0.0, atol=atol, check_every=check,
                    max_iters=2000)
        assert res.converged
        # residual after chunk m: (1 - mu^C) * mu^{(m-1)C} * ||v0||_2
        norm0 = float(np.linalg.norm(v0))
        m = 1
        while (1 - mu**check) * mu**((m - 1) * check) * norm0 > atol:
            m += 1
        assert abs(res.iterations - m * check) <= check, \
            (res.iterations, m * check)
        assert float(np.abs(np.asarray(res.x)).max()) < 1e-2

    def _mu(self) -> float:
        # eigenvalue of the heat step on the fundamental mode:
        # 1 - 4c + 4c*cos(pi/(N-1))
        return 1.0 - 4 * self.C * (1.0 - np.cos(np.pi / (self.N - 1)))


class TestResidualBehaviour:
    def test_residual_history_is_monotone(self):
        x0 = jnp.asarray(RNG.standard_normal((16, 16)), jnp.float32)
        res = solve(laplace_jacobi(2), x0, backend="conv", bc=1.0, rtol=1e-6,
                    check_every=5, max_iters=3000)
        assert res.converged
        h = res.residual_history
        assert len(h) >= 3
        assert not np.isnan(h).any()
        assert np.all(h[1:] <= h[:-1] * (1 + 1e-6) + 1e-7), h

    def test_residual_matches_history_tail(self):
        res = solve(laplace_jacobi(2), jnp.zeros((12, 12), jnp.float32),
                    bc=1.0, rtol=1e-6, check_every=10, max_iters=2000)
        assert res.converged
        assert res.residual == pytest.approx(res.residual_history[-1])

    def test_max_iters_safety(self):
        res = solve(laplace_jacobi(2), jnp.zeros((16, 16), jnp.float32),
                    bc=1.0, rtol=1e-12, check_every=10, max_iters=40)
        assert not res.converged
        assert res.iterations == 40
        assert len(res.residual_history) == 4

    def test_unsatisfiable_criterion_rejected(self):
        # rtol=None alone is NOT fixed-iteration mode (atol still defaults
        # to 0.0 -> err <= 0 can never hold); fail loudly instead of
        # silently looping to max_iters
        with pytest.raises(ValueError, match="unsatisfiable"):
            solve(laplace_jacobi(2), jnp.zeros((8, 8), jnp.float32),
                  bc=1.0, rtol=None)
        with pytest.raises(ValueError, match="unsatisfiable"):
            solve(laplace_jacobi(2), jnp.zeros((8, 8), jnp.float32),
                  bc=1.0, rtol=0.0, atol=0.0)

    def test_linf_norm_criterion(self):
        res = solve(laplace_jacobi(2), jnp.zeros((16, 16), jnp.float32),
                    bc=1.0, rtol=0.0, atol=1e-6, norm="linf",
                    check_every=20, max_iters=5000)
        assert res.converged
        assert float(np.abs(np.asarray(res.x) - 1.0).max()) < 1e-3


class TestChunkingInvariance:
    """The converged answer must not depend on how the time loop is chunked
    (check_every) or temporally fused (fuse depth)."""

    def test_check_every_invariance(self):
        x0 = jnp.asarray(RNG.standard_normal((16, 16)), jnp.float32)
        fields = [
            np.asarray(solve(laplace_jacobi(2), x0, backend="conv", bc=1.0,
                             rtol=1e-6, check_every=c, max_iters=4000).x)
            for c in (10, 20, 40)
        ]
        for f in fields:
            np.testing.assert_allclose(f, np.ones_like(f), atol=2e-3)
        for f in fields[1:]:
            np.testing.assert_allclose(f, fields[0], atol=5e-3)

    def test_fuse_depth_invariance_fixed(self):
        x0 = jnp.asarray(RNG.standard_normal((16, 16)), jnp.float32)
        outs = [
            np.asarray(solve(laplace_jacobi(2), x0, backend="pallas", bc=1.0,
                             rtol=None, atol=None, max_iters=16, fuse=f).x)
            for f in (1, 4, 8)
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], atol=1e-5)

    def test_fuse_depth_invariance_converged(self):
        x0 = jnp.asarray(RNG.standard_normal((16, 16)), jnp.float32)
        a = solve(laplace_jacobi(2), x0, backend="pallas", bc=1.0,
                  rtol=1e-6, check_every=16, max_iters=2000, fuse=1)
        b = solve(laplace_jacobi(2), x0, backend="pallas", bc=1.0,
                  rtol=1e-6, check_every=16, max_iters=2000, fuse=8)
        assert a.iterations == b.iterations
        assert b.fuse == 8
        np.testing.assert_allclose(np.asarray(a.x), np.asarray(b.x), atol=1e-5)


class TestBatchedMode:
    def test_batched_matches_instance_by_instance(self):
        x0 = jnp.stack([
            jnp.zeros((16, 16)),
            0.5 * jnp.ones((16, 16)),
            jnp.asarray(RNG.standard_normal((16, 16))),
        ]).astype(jnp.float32)
        batched = solve(laplace_jacobi(2), x0, backend="conv", bc=1.0,
                        rtol=1e-6, check_every=10, max_iters=4000)
        assert batched.converged.all()
        singles = [solve(laplace_jacobi(2), x0[i], backend="conv", bc=1.0,
                         rtol=1e-6, check_every=10, max_iters=4000)
                   for i in range(3)]
        np.testing.assert_array_equal(
            batched.iterations, [s.iterations for s in singles])
        for i, s in enumerate(singles):
            np.testing.assert_allclose(np.asarray(batched.x[i]),
                                       np.asarray(s.x), atol=1e-6)
            assert batched.residual[i] == pytest.approx(s.residual, rel=1e-4)

    def test_frozen_instances_stop_recording_history(self):
        # instance 0 starts at the fixed point -> converges in one chunk
        x0 = jnp.stack([jnp.ones((16, 16)),
                        jnp.zeros((16, 16))]).astype(jnp.float32)
        res = solve(laplace_jacobi(2), x0, backend="conv", bc=1.0,
                    rtol=1e-6, check_every=10, max_iters=4000)
        assert res.converged.all()
        assert res.iterations[0] < res.iterations[1]
        h = res.residual_history
        # instance 0's rows go NaN once frozen; instance 1's stay recorded
        assert np.isnan(h[1:, 0]).all()
        assert not np.isnan(h[:, 1]).any()

    def test_solver_reuse_across_batch_shapes(self):
        s = Solver(laplace_jacobi(2), (12, 12), backend="conv", bc=1.0,
                   rtol=1e-6, check_every=10, max_iters=2000)
        r1 = s.solve(jnp.zeros((12, 12), jnp.float32))
        r2 = s.solve(jnp.zeros((2, 12, 12), jnp.float32))
        assert r1.converged and r2.converged.all()
        assert r1.iterations == r2.iterations[0]
