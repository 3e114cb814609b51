"""Direct 3D stencil Pallas kernel (7-point and general radius-r).

The paper could not express 3D natively (no Conv3D on the CS-1) and paid a
Z²-banded channel matrix instead (Figures 3-4).  On TPU we tile the X
dimension into VMEM blocks; Z and Y stay whole in the block (Z is small in
the paper's workloads — Z=10 — and Y rides the 128-lane dim at its full
extent, as the TPU compiler's block rule requires).  X halo rows come from
the two aligned neighbour blocks (none when one block holds all of X), the
Y halo is zeros built in VMEM, and Z-shifts are in-block with zero fill via
concatenation.  Small instances are batched several to a block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.stencil import StencilSpec, WeightField
from repro.kernels.tiling import (batch_block, default_interpret, pad_last2,
                                  round_up, row_block, sublanes)


def _shift3d(xb: jnp.ndarray, dz: int, dx: int, dy: int, r: int) -> jnp.ndarray:
    """result[..., z,i,j] = xb[..., z+dz, r+i+dx, r+j+dy], zero-filled in Z."""
    Z, h, w = xb.shape[-3:]
    lead = xb.shape[:-3]
    if dz > 0:
        xz = jnp.concatenate(
            [xb[..., dz:, :, :], jnp.zeros(lead + (dz, h, w), xb.dtype)],
            axis=-3)
    elif dz < 0:
        xz = jnp.concatenate(
            [jnp.zeros(lead + (-dz, h, w), xb.dtype), xb[..., :dz, :, :]],
            axis=-3)
    else:
        xz = xb
    return xz[..., r + dx:h - r + dx, r + dy:w - r + dy]


def _kernel(*refs, spec: StencilSpec, r: int, bx: int, hb: int | None,
            Z: int, X: int, Y: int, bc_value: float | None):
    refs = list(refs)
    o_ref = refs.pop()
    w_ref = refs.pop() if spec.is_variable else None
    xb = refs[0][...].astype(jnp.float32)          # (bb, Z, bx, Y)
    if hb is None:                                 # one block holds all of X
        x0 = -r
        xb = pad_last2(xb, r, r)
    else:
        x0 = pl.program_id(1) * bx - r
        top, bot = refs[1][...], refs[2][...]
        xb = jnp.concatenate([top[..., hb - r:, :].astype(jnp.float32), xb,
                              bot[..., :r, :].astype(jnp.float32)], axis=-2)
        xb = pad_last2(xb, 0, r)
    shape = xb.shape[-2:]
    xs = x0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    ys = -r + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    xb = jnp.where((xs >= 0) & (xs < X) & (ys >= 0) & (ys < Y), xb, 0.0)

    acc = None
    k = 0
    for off, wgt in spec.taps:
        term = _shift3d(xb, off[0], off[1], off[2], r)
        if isinstance(wgt, WeightField):
            term = term * w_ref[k].astype(jnp.float32)
            k += 1
        else:
            term = term * np.float32(wgt)
        acc = term if acc is None else acc + term

    if bc_value is not None:
        zs = jax.lax.broadcasted_iota(jnp.int32, acc.shape[-3:], 0)
        oxs = x0 + r + jax.lax.broadcasted_iota(jnp.int32, acc.shape[-3:], 1)
        oys = jax.lax.broadcasted_iota(jnp.int32, acc.shape[-3:], 2)
        interior = ((zs >= 1) & (zs < Z - 1) & (oxs >= 1) & (oxs < X - 1)
                    & (oys >= 1) & (oys < Y - 1))
        acc = jnp.where(interior, acc, np.float32(bc_value))
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "block_x", "bc_value", "interpret"),
)
def stencil3d(
    x: jnp.ndarray,
    spec: StencilSpec,
    *,
    block_x: int = 64,
    bc_value: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One 3D stencil step.  x: (batch, Z, X, Y).

    bc_value=None → raw zero-padded stencil (matches stencil3d_ref);
    bc_value=v    → fused Jacobi step with scalar Dirichlet BC.
    """
    if spec.ndim != 3:
        raise ValueError("stencil3d needs a 3D spec")
    interpret = default_interpret(interpret)
    B, Z, X, Y = x.shape
    r = spec.radius
    itemsize = x.dtype.itemsize
    # Z whole planes per instance (and per weight field) ride along in
    # every block.
    bx = row_block(X, Y, r, block_x, itemsize,
                   planes=Z * (1 + spec.num_variable_taps))
    bb = max(1, batch_block(B, bx, Y, itemsize) // Z)
    hb = None if bx == X else round_up(r, sublanes(itemsize))
    kern = functools.partial(_kernel, spec=spec, r=r, bx=bx, hb=hb, Z=Z, X=X,
                             Y=Y, bc_value=bc_value)
    in_specs = [pl.BlockSpec((bb, Z, bx, Y), lambda b, i: (b, 0, i, 0))]
    operands = [x]
    if hb is not None:
        step, last = bx // hb, pl.cdiv(X, hb) - 1
        in_specs += [
            pl.BlockSpec((bb, Z, hb, Y), lambda b, i: (
                b, 0, jnp.maximum(i * step - 1, 0), 0)),
            pl.BlockSpec((bb, Z, hb, Y), lambda b, i: (
                b, 0, jnp.minimum((i + 1) * step, last), 0)),
        ]
        operands += [x, x]
    if spec.is_variable:
        # Per-cell weight fields: output-aligned X blocks, batch-shared.
        wf = jnp.asarray(np.stack([w.array for _, w in spec.taps
                                   if isinstance(w, WeightField)]),
                         jnp.float32)
        in_specs.append(pl.BlockSpec((wf.shape[0], Z, bx, Y),
                                     lambda b, i: (0, 0, i, 0)))
        operands.append(wf)
    return pl.pallas_call(
        kern,
        grid=(pl.cdiv(B, bb), pl.cdiv(X, bx)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, Z, bx, Y), lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="stencil3d",
    )(*operands)
