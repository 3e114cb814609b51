"""Property tests for the multigrid transfer operators.

Restriction (full weighting) and prolongation (linear interpolation) are
plain ``StencilSpec``s applied through raw (zero-padded) plans, so their
algebraic structure is checkable exactly:

  * transpose pairing: ``<P e, x>_fine == 2^ndim * <e, R x>_coarse`` — the
    prolongation stencil is ``2^ndim`` times the restriction stencil, and
    zero-stuffing is the exact adjoint of even-index sampling under zero
    padding;
  * constant-field preservation on the interior (away from the zero-padded
    rim both operators have unit row sums);
  * shape round-tripping across odd/even and non-square grids.

Deterministic sweeps cover a fixed shape set; hypothesis-driven versions of
the same properties run when hypothesis is installed (they skip otherwise —
see tests/_hypothesis_stub.py).
"""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_stub import given, settings, st

from repro.core import (
    coarse_shape,
    make_plan,
    prolongation_spec,
    restriction_spec,
)

RNG = np.random.default_rng(20260802)

SHAPES_1D = [(9,), (12,), (33,)]
SHAPES_2D = [(9, 9), (12, 17), (16, 16), (33, 21)]
SHAPES_3D = [(6, 9, 12), (9, 9, 9)]
ALL_SHAPES = SHAPES_1D + SHAPES_2D + SHAPES_3D


def _restrict(x):
    nd = x.ndim
    plan = make_plan(restriction_spec(nd), x.shape, backend="reference",
                     bc=None, iters=1)
    return plan(jnp.asarray(x, jnp.float32))[(slice(None, None, 2),) * nd]


def _prolong(e, fine_shape):
    nd = len(fine_shape)
    stuff = (slice(None, None, 2),) * nd
    full = jnp.zeros(fine_shape, jnp.float32).at[stuff].set(
        jnp.asarray(e, jnp.float32))
    plan = make_plan(prolongation_spec(nd), fine_shape, backend="reference",
                     bc=None, iters=1)
    return plan(full)


def _check_transpose_pairing(fine_shape, rng):
    nd = len(fine_shape)
    cshape = coarse_shape(fine_shape)
    x = rng.standard_normal(fine_shape).astype(np.float32)
    e = rng.standard_normal(cshape).astype(np.float32)
    # The inner products are taken in float64: the property is about the
    # operators, and an fp32 sum of ~1000 O(1) products that cancel to O(1)
    # carries its own rounding of that order.
    f64 = np.float64
    lhs = float(np.sum(np.asarray(_prolong(e, fine_shape), f64) * x))
    rhs = (2.0 ** nd) * float(np.sum(e.astype(f64)
                                     * np.asarray(_restrict(x), f64)))
    scale = max(abs(lhs), abs(rhs), 1.0)
    assert abs(lhs - rhs) / scale < 1e-5, (fine_shape, lhs, rhs)


def _check_constant_preservation(fine_shape):
    nd = len(fine_shape)
    cshape = coarse_shape(fine_shape)

    r = np.asarray(_restrict(np.ones(fine_shape, np.float32)))
    # Coarse interior: coarse i maps to fine 2i with 2i +- 1 in-array.
    interior = tuple(slice(1, (s - 2) // 2 + 1) for s in fine_shape)
    if all(sl.start < sl.stop for sl in interior):
        np.testing.assert_allclose(r[interior], 1.0, atol=1e-6)

    p = np.asarray(_prolong(np.ones(cshape, np.float32), fine_shape))
    # Fine region where interpolation has full coarse support per dim:
    # indices 0 .. 2*(nc-1) - 1 plus the even endpoint 2*(nc-1).
    region = tuple(slice(0, 2 * (nc - 1) + 1) for nc in cshape)
    np.testing.assert_allclose(p[region], 1.0, atol=1e-6)


def _check_shapes(fine_shape, rng):
    cshape = coarse_shape(fine_shape)
    x = rng.standard_normal(fine_shape).astype(np.float32)
    r = _restrict(x)
    assert r.shape == cshape
    p = _prolong(np.asarray(r), fine_shape)
    assert p.shape == fine_shape


class TestTransfersDeterministic:
    @pytest.mark.parametrize("shape", ALL_SHAPES, ids=str)
    def test_transpose_pairing(self, shape):
        _check_transpose_pairing(shape, RNG)

    @pytest.mark.parametrize("shape", ALL_SHAPES, ids=str)
    def test_constant_preservation(self, shape):
        _check_constant_preservation(shape)

    @pytest.mark.parametrize("shape", ALL_SHAPES, ids=str)
    def test_shape_round_trip(self, shape):
        _check_shapes(shape, RNG)

    @pytest.mark.parametrize("ndim", [1, 2, 3])
    def test_prolongation_is_scaled_restriction(self, ndim):
        rk = restriction_spec(ndim).to_kernel()
        pk = prolongation_spec(ndim).to_kernel()
        np.testing.assert_allclose(pk, (2.0 ** ndim) * rk, atol=1e-12)
        # Full weighting has unit total mass.
        np.testing.assert_allclose(rk.sum(), 1.0, atol=1e-12)

    def test_prolongation_interpolates_linearly_1d(self):
        # Zero-stuff + stencil == linear interpolation between coarse points.
        e = np.asarray([0.0, 2.0, 4.0, 6.0], np.float32)
        p = np.asarray(_prolong(e, (7,)))
        np.testing.assert_allclose(p, [0, 1, 2, 3, 4, 5, 6], atol=1e-6)


class TestTransfersHypothesis:
    """Same invariants, hypothesis-driven (skips when not installed)."""

    @settings(max_examples=25, deadline=None)
    @given(h=st.integers(6, 40), w=st.integers(6, 40))
    def test_transpose_pairing_2d(self, h, w):
        _check_transpose_pairing((h, w), np.random.default_rng(h * 100 + w))

    @settings(max_examples=25, deadline=None)
    @given(h=st.integers(6, 40), w=st.integers(6, 40))
    def test_constant_preservation_2d(self, h, w):
        _check_constant_preservation((h, w))

    @settings(max_examples=25, deadline=None)
    @given(h=st.integers(6, 40), w=st.integers(6, 40))
    def test_shape_round_trip_2d(self, h, w):
        _check_shapes((h, w), np.random.default_rng(h * 100 + w))

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(6, 16))
    def test_transpose_pairing_3d(self, n):
        _check_transpose_pairing((n, n + 1, n + 2),
                                 np.random.default_rng(n))
