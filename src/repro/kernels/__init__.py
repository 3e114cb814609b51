"""Pallas TPU kernels for the paper's compute hot-spot (stencil application).

Layout per kernel: <name>.py holds the pl.pallas_call + BlockSpec tiling,
ops.py the jit'd wrappers, ref.py the pure-jnp oracles.  All kernels are
validated in interpret mode on CPU (tests/test_kernels_*) and target TPU
Mosaic when run on hardware.
"""
from repro.kernels.ops import (
    dense_jacobi_kernel,
    dense_stencil_matmul,
    jacobi2d,
    jacobi2d_fused_step,
    jacobi2d_lanes_step,
    jacobi3d,
    stencil2d,
    stencil3d,
    sweep_scan,
)

__all__ = [
    "dense_jacobi_kernel",
    "dense_stencil_matmul",
    "jacobi2d",
    "jacobi2d_fused_step",
    "jacobi2d_lanes_step",
    "jacobi3d",
    "stencil2d",
    "stencil3d",
    "sweep_scan",
]
