"""A grid wider than one row block runs the fused kernel's trapezoid.

``Solver`` plans the 2D Pallas sweep and records its row blocks
(``StencilPlan.block_rows``/``halo_rows``, from
``kernels/tiling.py::fused_block_geometry``).  A grid taller than one block
is cut into ``bh``-row blocks, each reading a ``fuse * r``-deep halo from
its two aligned neighbour blocks, clamped at the grid's edges, with the
rows outside the grid re-zeroed; these tests run that path, interpreted,
against the oracle and pin the geometry the chip would run.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DirichletBC, Solver, laplace_jacobi
from repro.core.reference import jacobi_reference

V5E = "TPU v5 lite"


@pytest.mark.parametrize("rows", [200, 72])
def test_multi_block_grid_matches_oracle(rows):
    # 64-row blocks with 4-row halos from 8-row neighbours: four blocks (the
    # last 8 rows) or two (the last 8 rows), both edges clamped.
    spec = laplace_jacobi(2)
    solver = Solver(spec, (rows, 4096), bc=1.0, rtol=None, atol=None,
                    max_iters=12, backend="pallas", device_kind=V5E)
    plan = solver.plan
    assert (plan.backend, plan.fuse, plan.rim) == ("pallas", 4, "trapezoid")
    assert (plan.block_rows, plan.halo_rows) == (64, 4)
    assert rows % plan.block_rows
    x0 = np.random.default_rng(rows).random((rows, 4096), np.float32)
    got = solver.solve(jnp.asarray(x0)).x
    want = jacobi_reference(jnp.asarray(x0), spec, DirichletBC(1.0), 12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


def test_one_block_tile_reads_no_halo():
    # A 64x64 tile is one block: its zero rim is rebuilt in VMEM.
    solver = Solver(laplace_jacobi(2), (64, 64), bc=1.0, rtol=None,
                    atol=None, max_iters=100, device_kind=V5E)
    assert (solver.plan.block_rows, solver.plan.halo_rows) == (64, 0)


def test_table1_grid_plan():
    # The table1-grid-fixed cell's program as planned for the chip: 16-row
    # blocks of 16384-wide rows, 4-row halos from 8-row neighbours.
    solver = Solver(laplace_jacobi(2), (16384, 16384), bc=1.0, rtol=None,
                    atol=None, max_iters=100, device_kind=V5E)
    plan = solver.plan
    assert (plan.backend, plan.fuse, plan.rim) == ("pallas", 4, "trapezoid")
    assert (plan.block_rows, plan.halo_rows) == (16, 4)


def test_no_row_blocks_off_the_2d_kernel():
    solver = Solver(laplace_jacobi(3), (10, 64, 64), bc=1.0, rtol=None,
                    atol=None, max_iters=4, backend="pallas")
    assert solver.plan.block_rows is None and solver.plan.halo_rows is None
