"""The paper's primary contribution: stencil computation expressed through
tensor-program primitives (dense layers, convolutions) plus the TPU-native
re-think (direct Pallas stencils, temporal blocking, halo-exchange
distribution).  See DESIGN.md §1-2.

The single entry point is the dispatcher in ``plan.py``:
``stencil_apply(spec, x, backend="auto", ...)`` routes one ``StencilSpec``
through any backend (reference oracle, dense, conv, the Pallas kernels
direct or temporally fused, sharded halo exchange); ``choose_schedule``
decides the backend and its fuse depth once, from a measured table or a
small cost model when ``backend="auto"``; ``make_plan`` prepares a reusable
executor and ``backend_support`` reports which backends are legal for a
given cell.  Every backend is cross-validated against the oracle in
tests/conformance/.

The time dimension lives in ``solver.py``: ``solve(spec, x0, ...)`` /
``Solver`` run the whole iteration loop to convergence as one compiled
program over any backend (batched per-instance convergence, distributed
halo-exchange stepping, roofline-selected temporal fusion); pinned down in
tests/solver/.

``multigrid.py`` composes those pieces into a geometric-multigrid V-cycle:
per-level smoothing plans, restriction/prolongation as ``StencilSpec``s, and
red-black Gauss-Seidel — ``multigrid_solve`` reaches the same fixed point as
``solve`` in a small constant number of fine-grid-equivalent sweeps.
Variable-coefficient operators (per-cell ``WeightField`` taps, e.g.
``heterogeneous_jacobi``) flow through the same spec/backend machinery.
"""
from repro.core.adjoint import (
    DIFF_BACKENDS,
    implicit_solve,
    transpose_fields,
    transpose_spec,
)
from repro.core.autotune import (
    TunedEntry,
    TunedTable,
    autotune_cell,
    default_tuned_table,
    set_default_tuned_table,
    shape_bucket,
    spec_family,
)
from repro.core.boundary import BoundaryMode, DirichletBC, runtime_bc_grids
from repro.core.conv1d import causal_conv1d, causal_conv1d_update
from repro.core.conv_encoding import (
    conv2d_kernel,
    conv3d_channels_kernel,
    conv3d_kernel,
    conv_jacobi_2d,
    conv_jacobi_3d_channels,
    conv_jacobi_3d_native,
    conv_var_jacobi,
    split_var_kernels,
)
from repro.core.dense_encoding import (
    build_dense_matrix,
    dense_jacobi,
    dense_jacobi_with_bc,
    dense_layer_bytes,
)
from repro.core.metrics import DeliveredPerf, encoding_flops_per_point
from repro.core.multigrid import (
    MGResult,
    Multigrid,
    coarse_shape,
    coarsen_spec,
    multigrid_solve,
    prolongation_spec,
    red_black_step,
    restriction_spec,
)
from repro.core.plan_cache import (
    CachedSolver,
    CacheStats,
    PlanCache,
    default_plan_cache,
    set_default_plan_cache,
)
from repro.core.plan import (
    BACKENDS,
    BackendSupport,
    Schedule,
    StencilPlan,
    backend_support,
    choose_schedule,
    make_plan,
    stencil_apply,
)
from repro.core.reference import apply_stencil, jacobi_reference, jacobi_step
from repro.core.solver import SolveResult, Solver, solve
from repro.core.stencil import (
    StencilSpec,
    WeightField,
    box,
    causal_conv1d_spec,
    heterogeneous_jacobi,
    laplace_jacobi,
    star,
    variable_coefficient,
)

__all__ = [
    "BACKENDS",
    "BackendSupport",
    "DIFF_BACKENDS",
    "BoundaryMode",
    "CacheStats",
    "CachedSolver",
    "DirichletBC",
    "MGResult",
    "Multigrid",
    "PlanCache",
    "Schedule",
    "SolveResult",
    "Solver",
    "StencilPlan",
    "StencilSpec",
    "TunedEntry",
    "TunedTable",
    "WeightField",
    "autotune_cell",
    "default_plan_cache",
    "default_tuned_table",
    "set_default_plan_cache",
    "set_default_tuned_table",
    "shape_bucket",
    "spec_family",
    "solve",
    "apply_stencil",
    "backend_support",
    "choose_schedule",
    "make_plan",
    "stencil_apply",
    "box",
    "build_dense_matrix",
    "causal_conv1d",
    "causal_conv1d_spec",
    "causal_conv1d_update",
    "coarse_shape",
    "coarsen_spec",
    "conv2d_kernel",
    "conv3d_channels_kernel",
    "conv3d_kernel",
    "conv_jacobi_2d",
    "conv_jacobi_3d_channels",
    "conv_jacobi_3d_native",
    "conv_var_jacobi",
    "dense_jacobi",
    "dense_jacobi_with_bc",
    "dense_layer_bytes",
    "DeliveredPerf",
    "encoding_flops_per_point",
    "heterogeneous_jacobi",
    "implicit_solve",
    "jacobi_reference",
    "jacobi_step",
    "laplace_jacobi",
    "multigrid_solve",
    "runtime_bc_grids",
    "transpose_fields",
    "transpose_spec",
    "prolongation_spec",
    "red_black_step",
    "restriction_spec",
    "split_var_kernels",
    "star",
    "variable_coefficient",
]
