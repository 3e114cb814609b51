"""Jit'd public wrappers around the Pallas kernels.

These are the entry points the rest of the framework (examples, benchmarks,
the stencil DSL drivers) calls.  Each wrapper:
  * sets the Dirichlet shell before iterating,
  * scans the kernel over iteration chunks (``fuse`` iterations per pass for
    the temporally-blocked 2D path) with ``sweep_scan``,
  * auto-selects interpret mode on CPU (TPU runs compiled Mosaic).

The shell pin and the kernel scan run under ``jax.named_scope``s
(``repro.boundary``, ``repro.sweep``), which name their ops in the HLO
metadata a device trace carries; the compiled program is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.boundary import DirichletBC
from repro.core.stencil import StencilSpec
from repro.kernels.dense_stencil import dense_stencil_matmul
from repro.kernels.jacobi_fused import jacobi2d_fused_step, jacobi2d_lanes_step
from repro.kernels.stencil2d import stencil2d
from repro.kernels.stencil3d import stencil3d


def sweep_scan(step, x: jnp.ndarray, n: int) -> jnp.ndarray:
    """``step`` applied ``n`` times to ``x``, as a scan of kernel calls.

    A ``while`` loop's carry and its body's result share one buffer, and a
    Pallas call cannot write the buffer it reads (its blocks read their
    neighbours' halos), so a scan of one call per iteration makes XLA copy
    the whole grid out of the carry before every call.  Two calls to an
    iteration ping-pong between the carry and a second buffer instead, and
    no copy is inserted; an odd count runs its last call after the loop.
    A length of 1 stays one call.  The calls and their order are unchanged.
    """
    with jax.named_scope("repro.sweep"):
        y, _ = jax.lax.scan(lambda t, _: (step(t), None), x, None,
                            length=n, unroll=2 if n > 1 else 1)
    return y


@functools.partial(
    jax.jit,
    static_argnames=("spec", "iterations", "fuse", "block_h", "bc_value",
                     "interpret", "rim", "lane_axis"),
)
def jacobi2d(
    x0: jnp.ndarray,
    spec: StencilSpec,
    *,
    bc_value: float,
    iterations: int,
    fuse: int = 1,
    block_h: int = 256,
    interpret: bool | None = None,
    rim: str = "trapezoid",
    fields: jnp.ndarray | None = None,
    lane_axis: str = "cols",
) -> jnp.ndarray:
    """``iterations`` Jacobi steps on (batch, H, W) via the Pallas kernels.

    fuse=1 streams one iteration per HBM round-trip (the paper-faithful
    pipeline); fuse=T applies temporal blocking (beyond-paper, §Perf) with
    ``rim`` selecting the fusion geometry (see jacobi_fused.py).
    ``iterations`` must be divisible by ``fuse``.  Variable-coefficient
    specs scan the direct ``stencil2d`` kernel at fuse=1 and the fused
    kernel (halo-replicated per-cell weight blocks) at fuse>1; ``fields``
    optionally overrides the spec's baked per-cell values with a runtime
    (V, H, W) stack (a traced operand — no recompile on value changes).

    ``lane_axis="batch"`` (what ``tiling.lane_axis`` gives a batch of
    narrow tiles; a plan passes its own choice) sweeps the batch with
    ``jacobi2d_lanes_step`` on its (H, W, batch) transpose, which XLA
    stores as it stores the (batch, H, W) argument, so neither transpose
    moves data; ``block_h`` and ``rim`` do not apply there (its tiles stay
    resident).  "cols" keeps each row on the lanes.
    """
    if iterations % fuse:
        raise ValueError(f"iterations={iterations} not divisible by fuse={fuse}")
    if lane_axis not in ("batch", "cols"):
        raise ValueError(f"lane_axis must be 'batch' or 'cols', got "
                         f"{lane_axis!r}")
    bc = DirichletBC(bc_value)
    lanes = lane_axis == "batch"
    with jax.named_scope("repro.boundary"):
        x = jax.vmap(bc.set_boundary)(x0)
        if lanes:
            # a bit cast that XLA fuses into the pin and names the fused op
            # after, so it shares the pin's scope
            x = jnp.transpose(x, (1, 2, 0))

    if lanes:
        def step(t):
            return jacobi2d_lanes_step(t, spec, fuse=fuse, bc_value=bc_value,
                                       interpret=interpret)
        return jnp.transpose(sweep_scan(step, x, iterations // fuse),
                             (2, 0, 1))

    if spec.is_variable and fuse == 1:
        def step(x):
            return stencil2d(x, spec, block_h=block_h, bc_value=bc_value,
                             interpret=interpret, fields=fields)
    else:
        def step(x):
            return jacobi2d_fused_step(
                x, spec, fuse=fuse, block_h=block_h, bc_value=bc_value,
                interpret=interpret, rim=rim, fields=fields,
            )

    return sweep_scan(step, x, iterations // fuse)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "iterations", "block_x", "bc_value", "interpret"),
)
def jacobi3d(
    x0: jnp.ndarray,
    spec: StencilSpec,
    *,
    bc_value: float,
    iterations: int,
    block_x: int = 64,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``iterations`` 3D Jacobi steps on (batch, Z, X, Y)."""
    bc = DirichletBC(bc_value)
    with jax.named_scope("repro.boundary"):
        x = jax.vmap(bc.set_boundary)(x0)

    def step(x):
        return stencil3d(x, spec, block_x=block_x, bc_value=bc_value,
                         interpret=interpret)

    return sweep_scan(step, x, iterations)


@functools.partial(
    jax.jit,
    static_argnames=("iterations", "bm", "bk", "bn", "interpret"),
)
def dense_jacobi_kernel(
    x0: jnp.ndarray,
    matrix: jnp.ndarray,
    *,
    iterations: int,
    bm: int = 128,
    bk: int = 512,
    bn: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The dense encoding via the Pallas blocked matmul.  x0: (batch, *grid).

    The BC lives inside ``matrix`` (identity rows); build it with
    ``core.build_dense_matrix`` and set the shell on x0 first.
    """
    batch = x0.shape[0]
    grid_shape = x0.shape[1:]

    def step(x):
        return dense_stencil_matmul(x, matrix, bm=bm, bk=bk, bn=bn,
                                    interpret=interpret)

    x = sweep_scan(step, x0.reshape(batch, -1), iterations)
    return x.reshape(batch, *grid_shape)


__all__ = [
    "dense_jacobi_kernel",
    "dense_stencil_matmul",
    "jacobi2d",
    "jacobi3d",
    "stencil2d",
    "stencil3d",
    "jacobi2d_fused_step",
    "jacobi2d_lanes_step",
    "sweep_scan",
]
