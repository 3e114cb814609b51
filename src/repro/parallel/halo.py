"""Halo exchange over a device grid via shard_map + lax.ppermute.

The WSE's fabric places grid tiles on a 2D mesh of PEs with single-hop
neighbour links; a TPU pod's ICI torus is the same topology one level up.
This module exchanges radius-r halos (rows then columns — the second phase
carries the corners) with *non-wrapping* permutes: edge devices receive
zeros, matching the zero-padding semantics of the stencil oracle.

Deep halos are the communication-avoiding trick of the wafer-scale scaling
papers (Rocki et al., Jacquelin et al.): exchanging an ``r*k``-deep halo
once buys ``k`` local stencil iterations before the next exchange — the
valid region of the augmented tile shrinks by ``r`` per local step
(trapezoid-style), so ``ppermute`` rounds drop by ``k`` at the price of rim
recompute.  ``core/distributed.py`` builds that fused stepper on top of
:func:`exchange_halo_2d`; the depth is bounded by the local tile extent
(a device can only forward what it owns — a single exchange phase reaches
one neighbour deep).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def auto_axes(mesh):
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` returns ``Explicit`` axes, under which
    ``with_sharding_constraint`` refuses named specs and a jitted
    ``shard_map`` wants a mesh context.  The halo runner, the pipeline and
    the sharding rules place data with named specs inside ``jit``, so they
    work on this view of whatever mesh the caller built.  Anything that is
    not a ``Mesh`` (a bare shape, a test double) passes through.
    """
    if not isinstance(mesh, jax.sharding.Mesh):
        return mesh
    return jax.sharding.Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))


def _shift_perm(n: int, direction: int) -> list[tuple[int, int]]:
    """Permutation sending shard i -> i+direction (non-wrapping)."""
    if direction > 0:
        return [(i, i + 1) for i in range(n - 1)]
    return [(i + 1, i) for i in range(n - 1)]


def exchange_1d(xl: jnp.ndarray, axis_name: str, n: int, dim: int, r: int = 1):
    """Gather r-deep halos along ``dim`` from both neighbours on ``axis_name``.

    Returns (lo_halo, hi_halo): each has extent r along ``dim``; zeros at the
    global boundary (non-wrapping permute).  ``r`` may exceed the stencil
    radius (deep halos for temporal fusion) but never the local extent — a
    single exchange phase only reaches the adjacent shard.
    """
    size = xl.shape[dim]
    if r > size:
        raise ValueError(
            f"halo depth {r} exceeds the local extent {size} along dim {dim} "
            f"— one exchange phase can only fetch what the adjacent shard "
            f"owns (shrink the fuse depth or the device mesh)")
    hi_edge = jax.lax.slice_in_dim(xl, size - r, size, axis=dim)
    lo_edge = jax.lax.slice_in_dim(xl, 0, r, axis=dim)
    # neighbour i-1's high edge arrives as our low halo
    lo_halo = jax.lax.ppermute(hi_edge, axis_name, _shift_perm(n, +1))
    hi_halo = jax.lax.ppermute(lo_edge, axis_name, _shift_perm(n, -1))
    return lo_halo, hi_halo


def exchange_halo_2d(xl: jnp.ndarray, row_axis: str, col_axis: str,
                     n_row: int, n_col: int, r: int = 1) -> jnp.ndarray:
    """xl: (..., h, w) local tile -> (..., h+2r, w+2r) with halos filled.

    Phase 1 exchanges columns, phase 2 exchanges rows of the column-augmented
    tile so corner halos ride along — supports any radius-r box stencil (and
    any deep-halo depth ``r <= min(h, w)``).  Four ``ppermute`` rounds per
    call: two directions per axis.
    """
    wdim = xl.ndim - 1
    hdim = xl.ndim - 2
    left, right = exchange_1d(xl, col_axis, n_col, wdim, r)
    xw = jnp.concatenate([left, xl, right], axis=wdim)
    top, bot = exchange_1d(xw, row_axis, n_row, hdim, r)
    return jnp.concatenate([top, xw, bot], axis=hdim)
