"""The benchmark's own reference agrees with the program's oracle
(``core/reference.py``) and with its reference-backend solve, at small
sizes on the CPU; it shares no code with either."""
import jax
import numpy as np
import pytest

from repro.core import DirichletBC, laplace_jacobi, solve
from repro.core.reference import jacobi_reference
from stencilbench import reference


@pytest.mark.parametrize("grid", [(17, 23), (5, 9, 12)], ids=["2d", "3d"])
@pytest.mark.parametrize("bc", [1.0, 0.25])
def test_sweeps_match_the_program_oracle(grid, bc):
    x = np.asarray(jax.random.uniform(jax.random.key(3), (3, *grid)))
    spec = laplace_jacobi(len(grid))
    want = np.stack([jacobi_reference(g, spec, DirichletBC(bc), 7)
                     for g in x])
    got = np.asarray(reference.sweeps(x, len(grid), bc, 7))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_taps_are_the_programs_laplace_stencil():
    for ndim in (2, 3):
        assert dict(reference.laplace_taps(ndim)) == \
            dict(laplace_jacobi(ndim).taps)


@pytest.mark.parametrize("grid", [(16, 16), (14, 18)])
def test_converge_matches_the_program_solve(grid):
    x = np.asarray(jax.random.uniform(jax.random.key(5), (4, *grid)))
    res = solve(laplace_jacobi(2), x, backend="reference", bc=1.0,
                rtol=1e-4, check_every=8, max_iters=3000)
    got, iters, converged = reference.converge(
        x, 2, 1.0, rtol=1e-4, check_every=8, max_iters=3000)
    assert converged.all() and res.converged.all()
    np.testing.assert_array_equal(np.asarray(iters), res.iterations)
    np.testing.assert_allclose(np.asarray(got), np.asarray(res.x), rtol=0,
                               atol=1e-6)


def test_bfloat16_sweeps_drift_from_float32():
    x = np.asarray(jax.random.uniform(jax.random.key(7), (2, 16, 16)))
    lo = np.asarray(reference.sweeps(x, 2, 1.0, 20, dtype="bfloat16"),
                    np.float32)
    hi = np.asarray(reference.sweeps(x, 2, 1.0, 20))
    assert np.max(np.abs(lo - hi)) > 1e-3
