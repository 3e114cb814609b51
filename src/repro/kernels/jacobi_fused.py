"""Temporally-blocked Jacobi Pallas kernel — T iterations per HBM round-trip.

The WSE's decisive advantage for stencils is that the whole grid stays in
on-chip SRAM across *all* iterations; a naive TPU conv pipeline streams the
grid HBM→VMEM→HBM every iteration, so at 7 FLOP per 8 streamed bytes it is
hopelessly memory-bound (arithmetic intensity ~0.9 vs the ~240 FLOP/byte
ridge of a v5e).  Temporal blocking is the TPU-native answer (DESIGN §2):
each VMEM tile carries a halo of depth T·r and applies the stencil T times
before writing back, multiplying arithmetic intensity by ~T at the cost of
O(T·r) redundant rim compute (the classic trapezoid/overlapped-tiling
scheme).

Block geometry (``tiling.fused_block_geometry``): blocks carry whole rows,
so the TPU compiler's (sublane, 128-lane) rule holds for any width and the
zero column halo is rebuilt in VMEM every iteration.  A grid that fits one
row block runs *resident*: its zero row rim is rebuilt the same way, so no
work is redundant and T is unbounded (the closest TPU analogue of the WSE's
grid-stays-in-SRAM execution).  Larger grids tile rows into ``bh``-row
blocks extended by T·r halo rows read from the two neighbouring aligned halo
blocks — the trapezoid: every iteration spoils r more rows at each end of
the extended block (they miss their outer neighbours), so after T the
``bh`` centre rows are exactly valid.  Out-of-array rows are re-zeroed and
the Dirichlet shell re-pinned every iteration (the fused mask trick).
Small instances are batched several to a block.

The iteration is a ``fori_loop`` over a fixed-shape block, so compile time
does not grow with T.  This one kernel also serves the single step of
``stencil2d`` (T=1, input shell left as given).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.stencil import StencilSpec, WeightField
from repro.kernels.tiling import (
    batch_block,
    default_interpret,
    fused_block_geometry,
    pad_last2,
    round_up,
    shift2d,
    sublanes,
)


def _kernel(*refs, spec: StencilSpec, r: int, T: int, bh: int,
            hb: int | None, halo: int, H: int, W: int,
            bc_value: float | None, pin_input: bool):
    """T sweeps of one block: ``refs`` are x's centre block (then, when
    ``hb`` is set, its two halo-neighbour blocks), the same for the weight
    fields of a variable spec, and the output block."""
    refs = list(refs)
    o_ref = refs.pop()
    n_x = 1 if hb is None else 3
    x_refs, w_refs = refs[:n_x], refs[n_x:]

    def extend(refs):
        """The centre block with its ``halo`` neighbour rows attached."""
        if hb is None:
            return refs[0][...].astype(jnp.float32)
        c, top, bot = (ref[...].astype(jnp.float32) for ref in refs)
        return jnp.concatenate(
            [top[..., hb - halo:, :], c, bot[..., :halo, :]], axis=-2)

    xb = extend(x_refs)
    wb = extend(w_refs) if w_refs else None
    row0 = pl.program_id(1) * bh - halo        # global row of xb[..., 0, :]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, xb.shape[-2:], 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, xb.shape[-2:], 1)
    in_array = (rows >= 0) & (rows < H)
    shell = in_array & ~((rows >= 1) & (rows < H - 1)
                         & (cols >= 1) & (cols < W - 1))
    bc = None if bc_value is None else np.float32(bc_value)

    def zones(y, pin):
        if hb is not None:
            y = jnp.where(in_array, y, 0.0)
        if pin:
            y = jnp.where(shell, bc, y)
        return y

    def sweep(_, y):
        acc = None
        k = 0
        yp = pad_last2(y, r, r)
        for off, wgt in spec.taps:
            term = shift2d(yp, off[0], off[1], r)
            if isinstance(wgt, WeightField):
                term = term * wb[k]
                k += 1
            else:
                term = term * np.float32(wgt)
            acc = term if acc is None else acc + term
        return zones(acc, bc is not None)

    xb = zones(xb, bc is not None and pin_input)
    xb = jax.lax.fori_loop(0, T, sweep, xb)
    o_ref[...] = xb[..., halo:halo + o_ref.shape[-2], :].astype(o_ref.dtype)


def sweep2d(x, spec: StencilSpec, *, T: int, bc_value: float | None,
            pin_input: bool, block_h: int, rim: str, interpret: bool,
            fields=None) -> jnp.ndarray:
    """``T`` stencil sweeps of x: (batch, H, W) in one ``pallas_call``.

    ``pin_input`` pins the input's Dirichlet shell to ``bc_value`` before
    the first sweep (the fused Jacobi step); without it the shell is read
    as given (``stencil2d``).  Per-cell weight fields (variable specs) are
    streamed as an extra operand tiled like ``x``; ``fields`` overrides the
    spec's baked values with a runtime (V, H, W) stack.
    """
    B, H, W = x.shape
    r = spec.radius
    itemsize = x.dtype.itemsize
    wf = None
    if spec.is_variable:
        if fields is None:
            fields = np.stack([w.array for _, w in spec.taps
                               if isinstance(w, WeightField)])
        wf = jnp.asarray(fields, jnp.float32)
    planes = 1 if wf is None else 1 + wf.shape[0]
    bh, halo = fused_block_geometry(H, W, T, r, block_h, rim, itemsize,
                                    planes=planes)
    if bh == H:
        hb, halo = None, 0
        bb = batch_block(B, H, W, itemsize)
    else:
        hb = round_up(halo, sublanes(itemsize))
        bb = batch_block(B, bh + 2 * hb, W, itemsize)
    kern = functools.partial(_kernel, spec=spec, r=r, T=T, bh=bh, hb=hb,
                             halo=halo, H=H, W=W, bc_value=bc_value,
                             pin_input=pin_input)

    def specs(lead, lead_idx):
        """Centre block and its two aligned halo neighbours (clamped at the
        grid edges, where the kernel masks them to zeros anyway)."""
        out = [pl.BlockSpec((lead, bh, W), lambda b, i: (lead_idx(b), i, 0))]
        if hb is not None:
            step, last = bh // hb, pl.cdiv(H, hb) - 1
            out += [
                pl.BlockSpec((lead, hb, W), lambda b, i: (
                    lead_idx(b), jnp.maximum(i * step - 1, 0), 0)),
                pl.BlockSpec((lead, hb, W), lambda b, i: (
                    lead_idx(b), jnp.minimum((i + 1) * step, last), 0)),
            ]
        return out

    in_specs = specs(bb, lambda b: b)
    if wf is not None:
        in_specs += specs(wf.shape[0], lambda b: 0)
    operands = [v for v in (x, wf) if v is not None
                for _ in range(1 if hb is None else 3)]
    return pl.pallas_call(
        kern,
        grid=(pl.cdiv(B, bb), pl.cdiv(H, bh)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bh, W), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "fuse", "block_h", "bc_value", "interpret",
                     "rim"),
)
def jacobi2d_fused_step(
    x: jnp.ndarray,
    spec: StencilSpec,
    *,
    fuse: int,
    block_h: int = 256,
    bc_value: float | None = None,
    interpret: bool | None = None,
    rim: str = "trapezoid",
    fields: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """``fuse`` Jacobi iterations in one kernel pass.  x: (batch, H, W).

    Pins the Dirichlet shell of x before the first iteration; with
    bc_value=None computes ``fuse`` raw zero-padded stencil steps.  ``rim``
    selects the fusion geometry (see module docstring); "resident" requires
    the grid to fit one VMEM block (the dispatcher checks this with
    ``tiling.fits_vmem``; the TPU compiler refuses a larger one).  ``fields``
    optionally overrides a
    variable spec's baked per-cell values with a runtime (V, H, W) stack.
    """
    if spec.ndim != 2:
        raise ValueError("jacobi2d_fused_step needs a 2D spec")
    return sweep2d(x, spec, T=fuse, bc_value=bc_value, pin_input=True,
                   block_h=block_h, rim=rim,
                   interpret=default_interpret(interpret), fields=fields)
