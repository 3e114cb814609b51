"""Boundary-condition handling — the paper's mask trick and its alternatives.

The Cerebras TF stack lacked ``tf.pad`` and ``concatenate`` (paper §3), so
non-zero Dirichlet boundary conditions had to be applied as

    out = conv(x) * interior_mask + bc_values        (MASK mode)

where ``interior_mask`` is 1 in the interior and 0 on the boundary, and
``bc_values`` holds the Dirichlet values on the boundary and 0 inside.  This
costs 2N extra ops per iteration (one mul + one add per element).

JAX *does* have ``jnp.pad``; we therefore also implement:

  PAD    — 'valid' stencil application on an input padded with the BC values
           (the approach the paper says it *wanted*: pad + set boundary).
  MATRIX — BCs folded into the dense-encoding matrix (identity rows), the
           paper's dense-layer advantage: "the stencil matrix value can be
           set to 1 in order to maintain boundary conditions".

All modes compute identical results; MASK is the paper-faithful default for
the conv path and its overhead is quantified in EXPERIMENTS §Perf.
"""
from __future__ import annotations

import dataclasses
import enum

import jax
import jax.numpy as jnp


class BoundaryMode(enum.Enum):
    MASK = "mask"      # paper-faithful: conv('same') then mask-mult + bc-add
    PAD = "pad"        # jnp.pad with BC values, stencil applied 'valid'
    MATRIX = "matrix"  # dense encoding only: identity rows in the matrix


@dataclasses.dataclass(frozen=True)
class DirichletBC:
    """Fixed boundary values on the outermost shell of the grid.

    ``value`` may be a scalar or a full-grid array whose boundary shell holds
    the BC values (interior entries are ignored).
    """

    value: float | jnp.ndarray = 0.0

    def interior_mask(self, shape: tuple[int, ...], dtype=jnp.float32) -> jnp.ndarray:
        """1 in the interior, 0 on the boundary shell (paper §3 'mask')."""
        return interior_mask(shape, dtype)

    def bc_grid(self, shape: tuple[int, ...], dtype=jnp.float32) -> jnp.ndarray:
        """BC values on the boundary shell, 0 in the interior."""
        if isinstance(self.value, (int, float)):
            g = jnp.full(shape, float(self.value), dtype)
        else:
            g = jnp.asarray(self.value, dtype=dtype)
            if g.shape != shape:
                raise ValueError(f"bc grid shape {g.shape} != {shape}")
        mask = self.interior_mask(shape, dtype)
        return g * (1.0 - mask)

    def apply_mask_trick(self, out: jnp.ndarray) -> jnp.ndarray:
        """The paper's post-iteration fixup: zero the boundary, add BCs back."""
        mask = self.interior_mask(out.shape, out.dtype)
        bc = self.bc_grid(out.shape, out.dtype)
        return out * mask + bc

    def set_boundary(self, x: jnp.ndarray) -> jnp.ndarray:
        """Write the BC values onto the boundary shell of ``x``."""
        mask = self.interior_mask(x.shape, x.dtype)
        bc = self.bc_grid(x.shape, x.dtype)
        return x * mask + bc


def interior_mask(shape: tuple[int, ...], dtype=jnp.float32) -> jnp.ndarray:
    """1 in the interior, 0 on the boundary shell, computed from iotas.

    Under ``jit`` this is a few fused compares, not a grid-sized constant:
    a constant mask of a 16384^2 grid would put a gigabyte into the
    compiled program."""
    inside = jnp.ones(shape, bool)
    for d, n in enumerate(shape):
        i = jax.lax.broadcasted_iota(jnp.int32, shape, d)
        inside = inside & (i >= 1) & (i < n - 1)
    return inside.astype(dtype)


def runtime_bc_grids(shape: tuple[int, ...], bc_value,
                     dtype=jnp.float32) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(interior_mask, bc_grid) for a possibly-*traced* Dirichlet value.

    ``DirichletBC`` holds its value as static plan-build-time data; this is
    the runtime-operand counterpart: ``bc_value`` may be a Python scalar, a
    traced 0-d array, or a (possibly traced) full-grid array whose shell
    holds the values.  The returned ``bc_grid`` is a traced function of
    ``bc_value``, so gradients flow through it (the adjoint solve needs
    d(solution)/d(boundary value)).
    """
    mask = interior_mask(shape, dtype)
    v = jnp.asarray(bc_value, dtype)
    if v.ndim not in (0, len(shape)):
        raise ValueError(
            f"bc_value must be a scalar or a {len(shape)}D grid, got "
            f"shape {v.shape}")
    if v.ndim and v.shape != tuple(shape):
        raise ValueError(f"bc grid shape {v.shape} != {tuple(shape)}")
    return mask, v * (1.0 - mask)
