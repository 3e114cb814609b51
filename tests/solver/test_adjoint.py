"""Adjoint-solve tests: the differentiable fixed point (core/adjoint.py).

Three layers of pinning:

  * algebra — tap reflection is a true transpose (⟨Sx, u⟩ = ⟨x, S^T u⟩ for
    random fields) and an involution (transposing twice round-trips);
  * gradients — ``jax.grad`` through ``implicit_solve`` matches central
    finite differences for every differentiable operand (weight fields,
    source, boundary value) on every DIFF backend;
  * structure — batched gradients equal per-instance loop gradients, the
    x0 gradient is exactly zero, and a 5000-iteration fixed-length solve
    differentiates without unrolling (the O(1)-memory property: reverse
    through a ``lax.while_loop`` would fail outright).

FD checks run in float32, so epsilons are chosen where the central-
difference truncation error and the 1e-7 rounding noise cross (~1e-2 for
O(1) losses); tolerances are rtol 1e-3 with a small atol floor.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DIFF_BACKENDS,
    DirichletBC,
    apply_stencil,
    heterogeneous_jacobi,
    implicit_solve,
    jacobi_reference,
    laplace_jacobi,
    transpose_fields,
    transpose_spec,
    variable_coefficient,
)

RNG = np.random.default_rng(20260809)

GRID = (8, 9)


def _hetero_spec(grid=GRID):
    return heterogeneous_jacobi(1.0 + 9.0 * RNG.random(grid))


def _fd_grad(f, x, eps):
    """Central finite-difference gradient of scalar f at concrete x."""
    x = np.asarray(x, np.float64)
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    for _ in it:
        idx = it.multi_index
        xp, xm = x.copy(), x.copy()
        xp[idx] += eps
        xm[idx] -= eps
        g[idx] = (float(f(jnp.asarray(xp, jnp.float32)))
                  - float(f(jnp.asarray(xm, jnp.float32)))) / (2 * eps)
    return g


class TestTranspose:
    def test_pairing_identity_scalar_taps(self):
        spec = laplace_jacobi(2)
        x = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        u = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        lhs = jnp.vdot(apply_stencil(x, spec), u)
        rhs = jnp.vdot(x, apply_stencil(u, transpose_spec(spec)))
        np.testing.assert_allclose(lhs, rhs, rtol=1e-5)

    def test_pairing_identity_variable_taps(self):
        spec = _hetero_spec()
        x = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        u = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        lhs = jnp.vdot(apply_stencil(x, spec), u)
        rhs = jnp.vdot(x, apply_stencil(u, transpose_spec(spec)))
        np.testing.assert_allclose(lhs, rhs, rtol=1e-5)

    def test_double_transpose_round_trips(self):
        # Offsets round-trip exactly; fields round-trip up to the "dead"
        # border entries (weights whose reads fall outside the grid never
        # contribute, and transposition zero-fills exactly those) — so the
        # double transpose must equal the original *as an operator*.
        spec = _hetero_spec()
        back = transpose_spec(transpose_spec(spec))
        assert [o for o, _ in back.taps] == [o for o, _ in spec.taps]
        x = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        np.testing.assert_array_equal(np.asarray(apply_stencil(x, spec)),
                                      np.asarray(apply_stencil(x, back)))

    def test_transpose_fields_matches_transpose_spec(self):
        # The traced field-stack permutation must agree with the numpy
        # spec-level transposition tap for tap.
        spec = variable_coefficient(
            laplace_jacobi(2),
            {(0, 1): 0.2 + 0.1 * RNG.random(GRID),
             (1, 0): 0.2 + 0.1 * RNG.random(GRID)})
        stack = jnp.asarray(spec.field_stack())
        traced = transpose_fields(spec, stack)
        baked = transpose_spec(spec).field_stack()
        np.testing.assert_allclose(np.asarray(traced), np.asarray(baked),
                                   atol=0)

    def test_pairing_identity_asymmetric_offsets(self):
        # A one-sided (upwind-like) spec: transposition must handle taps
        # whose reflections are not themselves in the spec.
        spec = variable_coefficient(
            laplace_jacobi(2), {(1, 1): 0.1 + 0.05 * RNG.random(GRID)})
        x = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        u = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        lhs = jnp.vdot(apply_stencil(x, spec), u)
        rhs = jnp.vdot(x, apply_stencil(u, transpose_spec(spec)))
        np.testing.assert_allclose(lhs, rhs, rtol=1e-5)


class TestForwardAgreement:
    """implicit_solve's forward pass is the ordinary solve."""

    @pytest.mark.parametrize("backend", ["reference", "dense", "conv"])
    def test_matches_reference_fixed_point(self, backend):
        spec = _hetero_spec()
        src = jnp.asarray(0.1 * RNG.standard_normal(GRID), jnp.float32)
        out = implicit_solve(spec, jnp.zeros(GRID, jnp.float32),
                             fields=jnp.asarray(spec.field_stack()),
                             source=src, backend=backend, rtol=1e-7,
                             max_iters=4000)
        # Oracle: hand-iterate the masked update with the reference step.
        x = jnp.zeros(GRID, jnp.float32)
        m = jnp.zeros(GRID).at[1:-1, 1:-1].set(1.0)
        for _ in range(4000):
            x = m * (apply_stencil(x, spec) + src)
        np.testing.assert_allclose(out, x, atol=1e-5)

    def test_rejects_non_differentiable_backend(self):
        with pytest.raises(ValueError, match="differentiable"):
            implicit_solve(laplace_jacobi(2), jnp.zeros(GRID, jnp.float32),
                           backend="pallas")

    def test_auto_backend_is_differentiable(self):
        for nd, grid in ((1, (33,)), (2, GRID)):
            out = implicit_solve(laplace_jacobi(nd),
                                 jnp.zeros(grid, jnp.float32), bc_value=1.0,
                                 rtol=1e-6)
            assert out.shape == grid


class TestGradientsVsFiniteDifferences:
    """jax.grad through the adjoint == central FD, every operand x backend."""

    EPS = 1e-2
    # atol floors the check for near-zero entries, where the f32 loss
    # rounding (~loss * 1e-7 / 2eps) dominates the FD estimate.
    TOL = dict(rtol=1e-3, atol=2e-3)

    def _solve_kwargs(self, backend):
        return dict(backend=backend, rtol=1e-7, max_iters=4000)

    @pytest.mark.parametrize("backend", ["reference", "dense", "conv"])
    def test_weight_field_gradient(self, backend):
        spec = _hetero_spec()
        src = jnp.asarray(0.3 * RNG.standard_normal(GRID), jnp.float32)
        tgt = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        kw = self._solve_kwargs(backend)

        def loss(fields):
            x = implicit_solve(spec, jnp.zeros(GRID, jnp.float32),
                               fields=fields, source=src, **kw)
            return jnp.sum((x - tgt) ** 2)

        f0 = jnp.asarray(spec.field_stack())
        got = np.asarray(jax.grad(loss)(f0))
        want = _fd_grad(loss, f0, self.EPS)
        np.testing.assert_allclose(got, want, **self.TOL)

    @pytest.mark.parametrize("backend", ["reference", "dense", "conv"])
    def test_source_gradient(self, backend):
        spec = laplace_jacobi(2)
        tgt = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        kw = self._solve_kwargs(backend)

        def loss(src):
            x = implicit_solve(spec, jnp.zeros(GRID, jnp.float32),
                               source=src, bc_value=0.5, **kw)
            return jnp.sum((x - tgt) ** 2)

        s0 = jnp.asarray(0.3 * RNG.standard_normal(GRID), jnp.float32)
        got = np.asarray(jax.grad(loss)(s0))
        want = _fd_grad(loss, s0, self.EPS)
        np.testing.assert_allclose(got, want, **self.TOL)

    @pytest.mark.parametrize("backend", ["reference", "dense", "conv"])
    def test_scalar_bc_gradient(self, backend):
        spec = _hetero_spec()
        tgt = jnp.asarray(RNG.standard_normal(GRID), jnp.float32)
        kw = self._solve_kwargs(backend)
        fields = jnp.asarray(spec.field_stack())

        def loss(bc):
            x = implicit_solve(spec, jnp.zeros(GRID, jnp.float32),
                               fields=fields, bc_value=bc, **kw)
            return jnp.sum((x - tgt) ** 2)

        got = float(jax.grad(loss)(jnp.float32(0.7)))
        eps = self.EPS
        want = (float(loss(jnp.float32(0.7 + eps)))
                - float(loss(jnp.float32(0.7 - eps)))) / (2 * eps)
        np.testing.assert_allclose(got, want, rtol=1e-3)

    def test_gradients_through_1d_dense(self):
        spec = laplace_jacobi(1)
        n = 17
        tgt = jnp.asarray(RNG.standard_normal(n), jnp.float32)

        def loss(src):
            x = implicit_solve(spec, jnp.zeros(n, jnp.float32), source=src,
                               backend="dense", rtol=1e-7, max_iters=2000)
            return jnp.sum((x - tgt) ** 2)

        s0 = jnp.asarray(0.3 * RNG.standard_normal(n), jnp.float32)
        got = np.asarray(jax.grad(loss)(s0))
        want = _fd_grad(loss, s0, self.EPS)
        np.testing.assert_allclose(got, want, **self.TOL)


class TestStructure:
    def test_x0_gradient_is_exactly_zero(self):
        spec = laplace_jacobi(2)

        def loss(x0):
            return jnp.sum(implicit_solve(spec, x0, bc_value=1.0, rtol=1e-6,
                                          max_iters=2000) ** 2)

        g = jax.grad(loss)(jnp.asarray(RNG.standard_normal(GRID), jnp.float32))
        np.testing.assert_array_equal(np.asarray(g), 0.0)

    def test_batched_grad_equals_per_instance_loop(self):
        spec = _hetero_spec()
        f0 = jnp.asarray(spec.field_stack())
        srcs = jnp.asarray(0.3 * RNG.standard_normal((3, *GRID)), jnp.float32)
        tgts = jnp.asarray(RNG.standard_normal((3, *GRID)), jnp.float32)

        def batched(fields):
            x = implicit_solve(spec, jnp.zeros((3, *GRID), jnp.float32),
                               fields=fields, source=srcs, backend="conv",
                               rtol=1e-7, max_iters=3000)
            return jnp.sum((x - tgts) ** 2)

        def single(fields, i):
            x = implicit_solve(spec, jnp.zeros(GRID, jnp.float32),
                               fields=fields, source=srcs[i], backend="conv",
                               rtol=1e-7, max_iters=3000)
            return jnp.sum((x - tgts[i]) ** 2)

        g_batched = jax.grad(batched)(f0)
        g_loop = sum(jax.grad(lambda f, i=i: single(f, i))(f0)
                     for i in range(3))
        np.testing.assert_allclose(np.asarray(g_batched), np.asarray(g_loop),
                                   rtol=2e-4, atol=1e-5)

    def test_shared_source_grad_sums_over_batch(self):
        spec = laplace_jacobi(2)
        src = jnp.asarray(0.3 * RNG.standard_normal(GRID), jnp.float32)

        def shared(s):
            x = implicit_solve(spec, jnp.zeros((4, *GRID), jnp.float32),
                               source=s, rtol=1e-7, max_iters=2000)
            return jnp.sum(x ** 2)

        def batched(s):
            x = implicit_solve(spec, jnp.zeros((4, *GRID), jnp.float32),
                               source=jnp.broadcast_to(s, (4, *GRID)),
                               rtol=1e-7, max_iters=2000)
            return jnp.sum(x ** 2)

        g_shared = jax.grad(shared)(src)
        g_sum = jax.grad(batched)(src)
        np.testing.assert_allclose(np.asarray(g_shared), np.asarray(g_sum),
                                   rtol=1e-5, atol=1e-6)

    def test_five_thousand_iteration_fixed_solve_differentiates(self):
        # The O(1)-memory property: a fixed-length 5000-iteration solve
        # (rtol=None -> run exactly max_iters steps) reverse-differentiates
        # through one adjoint solve.  Unrolling would build a 5000-step
        # graph; reverse through lax.while_loop would raise outright.
        spec = laplace_jacobi(2)
        grid = (6, 6)

        def loss(src):
            x = implicit_solve(spec, jnp.zeros(grid, jnp.float32),
                               source=src, rtol=None, atol=None,
                               max_iters=5000, backend="conv")
            return jnp.sum(x ** 2)

        s0 = jnp.asarray(0.3 * RNG.standard_normal(grid), jnp.float32)
        g = jax.grad(loss)(s0)
        want = _fd_grad(loss, s0, 1e-2)
        np.testing.assert_allclose(np.asarray(g), want, rtol=1e-3, atol=2e-4)

    def test_jit_grad_composes(self):
        spec = _hetero_spec()
        f0 = jnp.asarray(spec.field_stack())

        @jax.jit
        def g(fields):
            def loss(f):
                x = implicit_solve(spec, jnp.zeros(GRID, jnp.float32),
                                   fields=f, bc_value=1.0, rtol=1e-6,
                                   max_iters=2000)
                return jnp.sum(x ** 2)
            return jax.grad(loss)(fields)

        eager = jax.grad(lambda f: jnp.sum(implicit_solve(
            spec, jnp.zeros(GRID, jnp.float32), fields=f, bc_value=1.0,
            rtol=1e-6, max_iters=2000) ** 2))(f0)
        np.testing.assert_allclose(np.asarray(g(f0)), np.asarray(eager),
                                   rtol=1e-5, atol=1e-7)
