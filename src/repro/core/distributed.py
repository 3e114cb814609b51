"""Distributed Jacobi: the paper's wafer-fabric decomposition on a TPU mesh.

The CS-1 compiler placed the grid across PEs with neighbour routing; here the
grid shards as P(row_axis, col_axis) over the device mesh and each exchange
gathers radius-``r*fuse`` halos (parallel/halo.py) before ``fuse`` *local*
stencil iterations — communication O(perimeter), compute O(area), the classic
HPC decomposition the WSE performs in hardware.

Two communication-avoiding tricks from the wafer-scale scaling papers
(Rocki et al. 2010.03660; Jacquelin et al. 2204.03775):

* **Deep-halo temporal fusion** (``fuse=k``): one ``r*k``-deep exchange buys
  ``k`` local iterations.  The valid region of the halo-augmented tile
  shrinks by ``r`` per local step (the trapezoid), so the chunk runs
  ``iterations/k`` exchanges — ``k``x fewer ``ppermute`` rounds — at the
  price of recomputing the shrinking rim (priced by
  ``kernels/tiling.py::halo_fuse_redundancy``).

* **Interior/rim split with overlap**: the step consuming the exchange
  computes the tile *interior* (no halo dependency) directly from the local
  tile, before the permutes' results are consumed; only the rim strips read
  the augmented tile.  Interior result and incoming halos land in separate
  buffers combined at the end (double-buffered), so the decomposition is
  explicit in the dependency graph and XLA's latency-hiding scheduler can
  overlap the collective with the interior compute instead of being left to
  find slack in a monolithic update.

The per-step batch dimension (the paper's "steps", problem = N x steps) is
embarrassingly parallel and rides the pod axis in the multi-pod mesh.

Variable-coefficient specs shard their per-cell ``WeightField`` taps with
the grid: the stacked fields are exchanged *once per chunk* (they are
iteration-invariant) at the depth the fused output margins need, then every
local step slices the cell-aligned weights out of the augmented field tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.boundary import DirichletBC
from repro.core.stencil import StencilSpec, WeightField
from repro.parallel.halo import auto_axes, exchange_halo_2d

# One exchange_halo_2d call = two directions x two mesh axes.
HALO_PHASES_PER_EXCHANGE = 4


def halo_comm_rounds(iterations: int, fuse: int = 1, *,
                     variable: bool = False) -> int:
    """``ppermute`` rounds a chunk of ``iterations`` executes at depth
    ``fuse`` — the quantity deep-halo fusion divides by ``fuse``.  Variable
    specs pay one extra exchange for the weight fields per chunk."""
    rounds = HALO_PHASES_PER_EXCHANGE * -(-iterations // fuse)
    if variable:
        rounds += HALO_PHASES_PER_EXCHANGE
    return rounds


def max_halo_fuse(radius: int, h_loc: int, w_loc: int) -> int:
    """Deepest legal fuse on a (h_loc, w_loc) tile: one exchange phase only
    reaches the adjacent shard, so the halo depth ``radius*fuse`` cannot
    exceed the local extent."""
    return max(1, min(h_loc, w_loc) // max(radius, 1))


def _stencil_acc(xb, spec: StencilSpec, r: int, fields):
    """Raw shifted-add stencil: (..., oh+2r, ow+2r) -> (..., oh, ow) in f32.

    ``fields`` is the output-aligned stack of per-cell weights for the
    spec's variable taps, (n_var, oh, ow), or None for all-scalar specs.
    """
    oh, ow = xb.shape[-2] - 2 * r, xb.shape[-1] - 2 * r
    acc = None
    ti = 0
    for off, wgt in spec.taps:
        sl = xb[..., r + off[0]: r + off[0] + oh,
                r + off[1]: r + off[1] + ow].astype(jnp.float32)
        if isinstance(wgt, WeightField):
            term = sl * fields[ti]
            ti += 1
        else:
            term = sl * np.float32(wgt)
        acc = term if acc is None else acc + term
    return acc


def _mask_zones(acc, bc_value, grows, gcols, H, W, dtype):
    """Dirichlet semantics over the (possibly domain-exceeding) region:
    interior cells keep the stencil result, the domain shell is pinned to
    ``bc_value``, cells outside the global grid are zero — exactly the
    oracle's zero-padding, so fused rim cells iterate to the same values a
    single-device solve produces."""
    interior = ((grows >= 1) & (grows < H - 1)
                & (gcols >= 1) & (gcols < W - 1))
    in_domain = (grows >= 0) & (grows < H) & (gcols >= 0) & (gcols < W)
    shell = jnp.where(in_domain, np.float32(bc_value), np.float32(0.0))
    return jnp.where(interior, acc, shell).astype(dtype)


def make_halo_runner(mesh, spec: StencilSpec, *, H: int, W: int,
                     bc_value: float, iterations: int,
                     row_axis: str = "data", col_axis: str = "model",
                     batch_axis: str | None = None, fuse: int = 1):
    """Builds an unjitted (batch, H, W) -> (batch, H, W) halo-exchange stepper.

    The input/output are sharded P(batch_axis, row_axis, col_axis).  This is
    the distribution primitive the ``halo`` backend of ``core.plan.make_plan``
    wraps (and jits); user-facing entry points are
    ``stencil_apply(..., backend="halo", mesh=...)`` for a fixed step count
    and ``core.solver.solve(..., backend="halo", mesh=...)`` for a full
    run-to-convergence time loop.

    ``fuse=k`` exchanges an ``r*k``-deep halo once per ``k`` local
    iterations (must divide ``iterations``; depth bounded by the local tile
    extent — see :func:`max_halo_fuse`).
    """
    if spec.ndim != 2:
        raise ValueError("distributed jacobi is 2D (the paper's fig-5 path)")
    mesh = auto_axes(mesh)
    r = spec.radius
    n_row = mesh.shape[row_axis]
    n_col = mesh.shape[col_axis]
    if H % n_row or W % n_col:
        raise ValueError(f"grid {H}x{W} must tile over {n_row}x{n_col}")
    h_loc, w_loc = H // n_row, W // n_col
    if fuse < 1:
        raise ValueError(f"fuse must be >= 1, got {fuse}")
    if iterations % fuse:
        raise ValueError(
            f"iterations={iterations} not divisible by fuse={fuse}")
    R = r * fuse                 # exchanged halo depth
    Rf = R - r                   # field halo depth = deepest output margin
    if R > min(h_loc, w_loc):
        raise ValueError(
            f"fuse={fuse} needs a {R}-deep halo but the local tile is only "
            f"{h_loc}x{w_loc} over the {n_row}x{n_col} mesh (max fuse "
            f"{max_halo_fuse(r, h_loc, w_loc)})")
    var_fields = np.stack([w.array for _, w in spec.taps
                           if isinstance(w, WeightField)]) \
        if spec.is_variable else None
    # The interior/rim split needs a non-empty interior window; degenerate
    # tiles (extent < 2r) fall back to the monolithic rim-only update.
    split = min(h_loc, w_loc) >= 2 * r

    def local_fn(x_local, *field_args):
        # x_local: (b_loc, h_loc, w_loc)
        ri = jax.lax.axis_index(row_axis)
        ci = jax.lax.axis_index(col_axis)
        row0 = ri * h_loc
        col0 = ci * w_loc

        def coords(m):
            """Global coordinates of the margin-``m`` output region (the
            local tile extended by m on every side; m=-r is the interior)."""
            grows = row0 + jnp.arange(-m, h_loc + m)[:, None]
            gcols = col0 + jnp.arange(-m, w_loc + m)[None, :]
            return grows, gcols

        if field_args:
            f_local = field_args[0]          # (n_var, h_loc, w_loc)
            f_aug = f_local if Rf == 0 else exchange_halo_2d(
                f_local, row_axis, col_axis, n_row, n_col, Rf)
        else:
            f_local = f_aug = None

        def field_slice(m):
            """Output-aligned weight fields for the margin-``m`` region."""
            if f_aug is None:
                return None
            return f_aug[:, Rf - m: Rf + h_loc + m, Rf - m: Rf + w_loc + m]

        def update(xb, m):
            """Full margin-``m`` update from a margin-``m+r`` input block."""
            grows, gcols = coords(m)
            return _mask_zones(_stencil_acc(xb, spec, r, field_slice(m)),
                               bc_value, grows, gcols, H, W, x_local.dtype)

        def split_update(x, xp, m):
            """The exchange-consuming step, interior/rim decomposed.

            ``x`` is the plain local tile, ``xp`` the halo-augmented tile
            (margin m+r).  The interior block depends only on ``x`` — no
            ppermute in its dependency cone — so XLA can schedule it
            concurrently with the exchange; the four rim strips read ``xp``
            and the pieces are concatenated into a fresh margin-``m``
            buffer.
            """
            h, w = h_loc, w_loc
            gi, gj = coords(-r)
            interior = _mask_zones(
                _stencil_acc(x, spec, r,
                             None if f_local is None
                             else f_local[:, r:h - r, r:w - r]),
                bc_value, gi, gj, H, W, x.dtype)
            gr, gc = coords(m)

            def strip(rows, cols, out_rows, out_cols):
                # f_aug carries margin Rf == m, so its index space coincides
                # with the output's — the out ranges slice both.
                acc = _stencil_acc(
                    xp[..., rows[0]:rows[1], cols[0]:cols[1]], spec, r,
                    None if f_aug is None
                    else f_aug[:, out_rows[0]:out_rows[1],
                               out_cols[0]:out_cols[1]])
                return _mask_zones(acc, bc_value,
                                   gr[out_rows[0]:out_rows[1], :],
                                   gc[:, out_cols[0]:out_cols[1]],
                                   H, W, x.dtype)

            s = m + r  # rim strip width (in output cells)
            top = strip((0, s + 2 * r), (0, w + 2 * m + 2 * r),
                        (0, s), (0, w + 2 * m))
            bot = strip((h + m - r, h + 2 * m + 2 * r),
                        (0, w + 2 * m + 2 * r),
                        (h + m - r, h + 2 * m), (0, w + 2 * m))
            left = strip((s, h + m + r), (0, s + 2 * r),
                         (s, h + m - r), (0, s))
            right = strip((s, h + m + r),
                          (w + m - r, w + 2 * m + 2 * r),
                          (s, h + m - r), (w + m - r, w + 2 * m))
            mid = jnp.concatenate([left, interior, right], axis=-1)
            return jnp.concatenate([top, mid, bot], axis=-2)

        def body(x, _):
            xp = exchange_halo_2d(x, row_axis, col_axis, n_row, n_col, R)
            m = R - r
            y = split_update(x, xp, m) if split else update(xp, m)
            for _s in range(1, fuse):
                m -= r
                y = update(y, m)
            return y, None

        y, _ = jax.lax.scan(body, x_local, None, length=iterations // fuse)
        return y

    in_spec = P(batch_axis, row_axis, col_axis)
    field_spec = P(None, row_axis, col_axis)
    in_specs = (in_spec, field_spec) if var_fields is not None else (in_spec,)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=in_spec, check_vma=False)

    def run(x0):
        bc = DirichletBC(bc_value)
        x0 = jax.vmap(bc.set_boundary)(x0)
        x0 = jax.lax.with_sharding_constraint(
            x0, NamedSharding(mesh, in_spec))
        if var_fields is None:
            return fn(x0)
        f = jax.lax.with_sharding_constraint(
            jnp.asarray(var_fields), NamedSharding(mesh, field_spec))
        return fn(x0, f)

    return run
