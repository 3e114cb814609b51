"""The Pallas sweep scans pair their kernel calls (``kernels/ops.py::
sweep_scan``) and still compute exactly what one call at a time computes.

Each case runs a scan of ``n`` kernel calls through the public entry point
and compares it, to the last bit, with a scan of one call per iteration
(``lax.scan`` without unrolling) of the same kernel: lengths 1 (one call,
no pairing), 2 (one pair) and 5 (two pairs and a call after the loop).
Interpret mode on the CPU, small shapes.

The variable-coefficient raw path (``stencil2d`` with weight fields) is
left out: on the CPU, XLA compiles the interpreted body of two adjacent
calls differently from one alone, a last-bit difference at some points that
says nothing of the chip, where each call is one opaque Mosaic kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DirichletBC, laplace_jacobi
from repro.core.plan import make_plan
from repro.kernels import (jacobi2d, jacobi2d_fused_step, jacobi3d, stencil3d,
                           sweep_scan)

SPEC2, SPEC3 = laplace_jacobi(2), laplace_jacobi(3)


def _pinned(x, bc):
    return jax.vmap(DirichletBC(bc).set_boundary)(x)


def _plan(spec, grid, n, **kw):
    return make_plan(spec, grid, bc=None, iters=n * kw.get("fuse", 1), **kw)


# name: (batch+grid shape, the scan of n calls, one call, Dirichlet value)
CASES = {
    "jacobi2d-fuse1": (
        (2, 16, 16),
        lambda n: lambda x: jacobi2d(x, SPEC2, bc_value=1.0, iterations=n,
                                     block_h=8),
        lambda x: jacobi2d_fused_step(x, SPEC2, bc_value=1.0, fuse=1,
                                      block_h=8),
        1.0),
    "jacobi2d-fuse4": (
        (2, 16, 16),
        lambda n: lambda x: jacobi2d(x, SPEC2, bc_value=1.0,
                                     iterations=4 * n, fuse=4, block_h=8),
        lambda x: jacobi2d_fused_step(x, SPEC2, bc_value=1.0, fuse=4,
                                      block_h=8),
        1.0),
    "jacobi3d": (
        (2, 4, 8, 8),
        lambda n: lambda x: jacobi3d(x, SPEC3, bc_value=1.0, iterations=n),
        lambda x: stencil3d(x, SPEC3, bc_value=1.0),
        1.0),
    "raw2d-fuse2": (
        (2, 16, 16),
        lambda n: _plan(SPEC2, (16, 16), n, backend="pallas", fuse=2),
        lambda x: jacobi2d_fused_step(x, SPEC2, fuse=2, rim="trapezoid"),
        None),
    "raw3d": (
        (2, 4, 8, 8),
        lambda n: _plan(SPEC3, (4, 8, 8), n, backend="pallas"),
        lambda x: stencil3d(x, SPEC3),
        None),
}


@pytest.mark.parametrize("n", [1, 2, 5])
@pytest.mark.parametrize("case", sorted(CASES))
def test_paired_scan_is_bit_identical(case, n):
    shape, scan, step, bc = CASES[case]
    x = jnp.asarray(np.random.default_rng(n).standard_normal(shape),
                    jnp.float32)

    @jax.jit
    def one_at_a_time(x):
        x = x if bc is None else _pinned(x, bc)
        return jax.lax.scan(lambda t, _: (step(t), None), x, None,
                            length=n)[0]

    want = one_at_a_time(x)
    got = scan(n)(x)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_sweep_scan_counts_calls():
    # the remainder call after the pairs is not lost or doubled
    x = jnp.zeros((3,), jnp.int32)
    for n in range(1, 8):
        y = jax.jit(lambda x, n=n: sweep_scan(lambda t: t + 1, x, n))(x)
        assert np.array_equal(np.asarray(y), np.full(3, n))
