"""Direct 2D stencil Pallas kernel — the TPU-native re-think of the paper's
conv encoding (DESIGN §2).

On the WSE the grid lives in per-core SRAM and neighbour taps arrive over the
fabric.  The TPU analogue: row-tile the grid into VMEM blocks with a
radius-r halo, apply the taps as *shifted adds* on the VPU, and write back
the interior.  A 5-point stencil has no MXU-shaped reuse at C=1 — im2col
conv would waste 9/5 of its MACs and round-trip through a matmul — so the
direct form is the roofline-correct choice: arithmetic intensity ≈ 7 FLOP /
8 bytes streamed, i.e. memory-bound, and the kernel's job is to stream
HBM→VMEM exactly once per element.

One step is the T=1 case of the fused kernel in ``jacobi_fused.py``, which
owns the block geometry: whole rows per block (W rides the 128-wide lane
dimension at its full extent), row halos read from aligned neighbour
blocks, zero column halos built in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.stencil import StencilSpec
from repro.kernels.jacobi_fused import sweep2d
from repro.kernels.tiling import default_interpret


@functools.partial(
    jax.jit,
    static_argnames=("spec", "block_h", "bc_value", "interpret"),
)
def stencil2d(
    x: jnp.ndarray,
    spec: StencilSpec,
    *,
    block_h: int = 256,
    bc_value: float | None = None,
    interpret: bool | None = None,
    fields: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Apply one stencil step to x: (batch, H, W).

    bc_value=None → raw stencil with zero padding (matches stencil2d_ref);
    bc_value=v    → fused Jacobi step with scalar Dirichlet BC v
                    (matches one iteration of jacobi2d_ref).
    ``fields`` optionally overrides a variable spec's baked per-cell weight
    values with a runtime (V, H, W) stack — a traced operand, so value
    changes don't recompile and gradients flow through it.
    """
    if spec.ndim != 2:
        raise ValueError("stencil2d needs a 2D spec")
    return sweep2d(x, spec, T=1, bc_value=bc_value, pin_input=False,
                   block_h=block_h, rim="trapezoid",
                   interpret=default_interpret(interpret), fields=fields)
