"""Shared block-geometry helpers for the Pallas kernels.

Every stencil kernel in this package tiles its operands the same way, in the
shape the TPU compiler accepts: a block's last two dimensions are either
multiples of the (sublane, 128-lane) tile or the array's full extent.  So
the lane (last) dimension is never blocked — each block carries whole rows —
and the row dimension is cut into ``bh``-row blocks.  A block reads its
radius-``halo`` row halo from the two neighbouring *aligned* halo blocks
(``hb`` rows each, ``hb`` a sublane multiple >= ``halo``) and builds the
zero column halo in VMEM, so nothing is padded or cropped in HBM.  A grid
small enough for one row block needs no neighbours at all: its zero rim is
re-built in VMEM every iteration (the "resident" scheme).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Bytes of one input block the kernels aim for (a few such blocks, double
# buffered, plus the in-kernel temporaries must fit the scoped VMEM the TPU
# compiler grants a kernel by default).
BLOCK_BYTES = 1 << 20

# How many copies of one halo-extended block a stencil kernel holds in VMEM
# at once: double-buffered inputs and output plus the in-kernel
# temporaries.  The budget they must fit is the device's
# (``DeviceProfile.scoped_vmem_bytes``).  Calibrated against the v5e
# compiler's 16 MiB: a resident 768x768 fp32 grid (2.25 MiB) compiles and
# 896x896 (3 MiB) does not; a depth-4 trapezoid on rows of 16384 fp32
# compiles and on rows of 32768 does not.  tests/test_tpu_compile.py keeps
# the largest admitted cases compiling.
VMEM_BLOCKS = 7


def default_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument: None means "interpret iff
    this process has no native Pallas lowering" (CPU hosts).

    Single source of truth for every Pallas kernel in this package — and for
    ``core/plan.py``, which records the resolved value on the plan so the
    dispatcher can tell an interpreted execution from a compiled one.
    """
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def round_up(v: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``v``."""
    return (v + m - 1) // m * m


def sublanes(itemsize: int) -> int:
    """Rows of one (sublane, lane) tile for an element of ``itemsize``
    bytes: 8 for 32-bit, 16 for 16-bit types."""
    return 8 * max(1, 4 // itemsize)


def tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """VMEM bytes of a (rows, cols) slab once padded to whole tiles."""
    return (round_up(rows, sublanes(itemsize)) * round_up(cols, 128)
            * itemsize)


def batch_block(B: int, rows: int, cols: int, itemsize: int = 4) -> int:
    """Instances per block: as many (rows, cols) slabs as fit
    ``BLOCK_BYTES`` (at least one, at most the batch)."""
    return max(1, min(B, BLOCK_BYTES // tile_bytes(rows, cols, itemsize)))


def shift2d(xb: jnp.ndarray, dr: int, dc: int, r: int) -> jnp.ndarray:
    """Slice the halo block so result[..., i, j] = interior[..., i+dr, j+dc].

    xb has r halo rows top/bottom and r halo cols left/right in its last two
    dims; the output is the interior window displaced by (dr, dc).
    """
    h, w = xb.shape[-2:]
    return xb[..., r + dr:h - r + dr, r + dc:w - r + dc]


def pad_last2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    """Zero-extend the last two dims by ``rows``/``cols`` on each side
    (built by concatenation, which the TPU compiler lowers at any offset)."""
    if cols:
        z = jnp.zeros(x.shape[:-1] + (cols,), x.dtype)
        x = jnp.concatenate([z, x, z], axis=-1)
    if rows:
        z = jnp.zeros(x.shape[:-2] + (rows, x.shape[-1]), x.dtype)
        x = jnp.concatenate([z, x, z], axis=-2)
    return x


def row_block(H: int, W: int, halo: int, block_h: int = 256,
              itemsize: int = 4, planes: int = 1) -> int:
    """Rows per block for a grid whose blocks read a ``halo``-deep row halo.

    Returns ``H`` when one block holds the whole grid (within ``block_h``
    rows and ``BLOCK_BYTES`` over ``planes`` stacked (rows, W) slabs);
    otherwise a multiple of the halo block ``round_up(halo, sublanes)``, so
    that the neighbours' halo rows are whole aligned blocks.
    """
    sub = sublanes(itemsize)
    hb = round_up(max(halo, 1), sub)
    cap = min(block_h,
              BLOCK_BYTES // (planes * tile_bytes(1, W, itemsize)) * sub)
    bh = max(hb, cap // hb * hb)
    return H if round_up(H, sub) <= cap or bh >= H else bh


def fused_block_geometry(H: int, W: int, fuse: int, r: int,
                         block_h: int = 256, rim: str = "trapezoid",
                         itemsize: int = 4,
                         planes: int = 1) -> tuple[int, int]:
    """Block geometry of the temporally-fused 2D Jacobi kernel.

    Returns ``(bh, halo)``: rows per block and the per-side halo depth.
    This is the single source of truth shared by ``jacobi_fused.py`` (which
    tiles with it) and the ``plan.py`` roofline model (which prices the rim
    recompute it implies).

    ``bh == H`` is the *resident* scheme: the whole grid is one block and a
    depth-``r`` zero rim is re-built between in-kernel iterations, so no work
    is redundant and the fuse depth is unbounded.  ``rim="resident"`` forces
    it (legal only where :func:`fits_vmem` admits the whole grid);
    ``rim="trapezoid"`` picks it
    whenever the grid fits one block and otherwise tiles rows into blocks
    that read a ``fuse * r``-deep halo from their neighbours and recompute
    it (the classic overlapped-tiling scheme).  ``planes`` counts the
    (rows, W) slabs a block carries (1 + the variable taps' weight fields).
    """
    if rim == "resident":
        return H, r
    if rim != "trapezoid":
        raise ValueError(f"unknown rim strategy {rim!r} "
                         f"(expected 'trapezoid' or 'resident')")
    halo = fuse * r
    bh = row_block(H, W, halo, block_h, itemsize, planes)
    return bh, (r if bh == H else halo)


def block_vmem_bytes(grid_shape: tuple[int, ...], fuse: int, r: int, *,
                     itemsize: int = 4, planes: int = 1,
                     block_h: int | None = None,
                     rim: str = "trapezoid") -> int:
    """VMEM bytes of one instance's halo-extended block: the 2D fused
    kernel at depth ``fuse`` (``planes`` = 1 + weight fields), or the 3D
    step kernel on a (Z, X, Y) grid (``fuse`` is ignored)."""
    sub = sublanes(itemsize)
    if len(grid_shape) == 3:
        Z, X, Y = grid_shape
        bx = row_block(X, Y, r, block_h or 64, itemsize, planes=Z * planes)
        rows = X if bx == X else bx + 2 * round_up(r, sub)
        return Z * planes * tile_bytes(rows, Y, itemsize)
    H, W = grid_shape
    bh, halo = fused_block_geometry(H, W, fuse, r, block_h or 256, rim,
                                    itemsize, planes)
    rows = H if bh == H else bh + 2 * round_up(halo, sub)
    return planes * tile_bytes(rows, W, itemsize)


def fits_vmem(grid_shape: tuple[int, ...], fuse: int, r: int, *,
              budget: int, **kw) -> bool:
    """Whether the kernel for this geometry fits ``budget`` bytes of scoped
    VMEM (``VMEM_BLOCKS`` copies of its block; ``kw`` as for
    :func:`block_vmem_bytes`)."""
    return VMEM_BLOCKS * block_vmem_bytes(grid_shape, fuse, r, **kw) <= budget


def fuse_redundancy(grid_shape: tuple[int, int], fuse: int, r: int,
                    block_h: int = 256, rim: str = "trapezoid") -> float:
    """Rim-recompute factor of the depth-``fuse`` schedule: elements each
    block touches divided by elements it owns.  1.0 means no redundant work;
    the cost model multiplies compute time by this when pricing a fuse depth.
    The resident scheme recomputes nothing (its rim is re-zeroed, not
    re-derived from a deeper halo).
    """
    H, W = grid_shape
    bh, halo = fused_block_geometry(H, W, fuse, r, block_h, rim)
    return 1.0 if bh == H else (bh + 2 * halo) / bh


def halo_fuse_redundancy(local_shape: tuple[int, int], fuse: int,
                         r: int) -> float:
    """Rim-recompute factor of a depth-``fuse`` deep-halo schedule on one
    (h_loc, w_loc) device tile: cells updated across the fused sweep divided
    by cells owned.  Substep ``s`` of the trapezoid computes the tile
    extended by margin ``(fuse-s)*r``, so the factor grows with depth — the
    distributed analogue of :func:`fuse_redundancy`, which the halo cost
    model multiplies compute time by when pricing a fuse depth.
    """
    h, w = local_shape
    if h <= 0 or w <= 0 or fuse <= 1:
        return 1.0
    total = sum((h + 2 * (fuse - s) * r) * (w + 2 * (fuse - s) * r)
                for s in range(1, fuse + 1))
    return total / (fuse * h * w)


def halo_exchange_bytes(local_shape: tuple[int, int], fuse: int, r: int,
                        itemsize: int = 4) -> int:
    """Bytes one device moves per deep-halo exchange: two ``r*fuse``-deep
    edge strips per mesh axis, the row phase widened by the already-attached
    column halos (the corner transit).  Perimeter-proportional — the
    communication term of the halo roofline."""
    h, w = local_shape
    R = r * fuse
    return int(2 * R * (h + w + 2 * R) * itemsize)
