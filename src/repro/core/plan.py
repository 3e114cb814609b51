"""Unified stencil dispatch — one spec, every encoding, one entry point.

``stencil.py`` promises that "every backend computes the same operator and can
be cross-validated"; this module is where that promise becomes an API.  A
``StencilSpec`` + grid shape + boundary condition can be lowered through any
of the repo's executable encodings:

  reference     pure-jnp shifted-add oracle           (core/reference.py)
  dense         N×N dense-layer matmul, BCs in-matrix (core/dense_encoding.py)
  conv          conv layer; 3D rides Conv2D channels  (core/conv_encoding.py)
  conv3d_native true Conv3D (what the CS-1 lacked)    (core/conv_encoding.py)
  pallas        direct Pallas stencil kernel          (kernels/stencil{2,3}d.py)
  pallas_fused  temporally-blocked Pallas kernel      (kernels/jacobi_fused.py)
  halo          shard_map halo-exchange distribution  (parallel/halo.py)

``backend="auto"`` picks via a small analytic cost model: per-point FLOPs for
the encoding (core/metrics.py), bytes streamed per iteration, the device
kind's vector/matmul throughput and memory bandwidth, and the arithmetic-
intensity boost temporal fusion buys.  ``backend_support`` answers *which
backends are legal* for a given (spec, grid, boundary mode, device) cell —
the conformance matrix in tests/conformance/ walks every cell and either
cross-validates it against the oracle or records the reason it is skipped.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.boundary import BoundaryMode, DirichletBC, runtime_bc_grids
from repro.core.metrics import encoding_flops_per_point
from repro.core.reference import apply_stencil
from repro.core.stencil import StencilSpec

BACKENDS = (
    "reference",
    "dense",
    "conv",
    "conv3d_native",
    "pallas",
    "pallas_fused",
    "halo",
)


# ---------------------------------------------------------------------------
# Support matrix
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BackendSupport:
    """Whether a backend can execute a cell, and if not, why not."""

    ok: bool
    reason: str = ""

    def __bool__(self) -> bool:
        return self.ok


def _no(reason: str) -> BackendSupport:
    return BackendSupport(False, reason)


_OK = BackendSupport(True)


def backend_support(
    backend: str,
    spec: StencilSpec,
    *,
    grid_shape: tuple[int, ...] | None = None,
    mode: BoundaryMode = BoundaryMode.MASK,
    bc: DirichletBC | float | None = 0.0,
    mesh=None,
    device_kind: str | None = None,
) -> BackendSupport:
    """Is ``backend`` legal for this (spec, grid, mode, bc) cell?

    Returns a BackendSupport whose ``reason`` string is suitable for a test
    skip message — the conformance matrix relies on this being exhaustive.
    ``device_kind`` (None: this process's device) sets the scoped VMEM a
    Pallas kernel must fit.
    """
    if backend not in BACKENDS:
        return _no(f"unknown backend {backend!r} (known: {BACKENDS})")
    nd = spec.ndim
    raw = bc is None
    variable = spec.is_variable
    scalar_bc = raw or isinstance(bc, (int, float)) or (
        isinstance(bc, DirichletBC) and isinstance(bc.value, (int, float))
    )

    if variable and grid_shape is not None and \
            spec.weights_shape != tuple(grid_shape):
        return _no(f"spec carries {spec.weights_shape}-shaped weight fields "
                   f"but the grid is {tuple(grid_shape)}")

    if backend == "reference":
        return _OK  # the oracle runs everywhere; mode is a no-op for it

    if backend == "dense":
        if raw:
            return _no("dense encoding folds BCs into identity matrix rows; "
                       "raw (bc=None) zero-pad semantics not expressible")
        if mode is not BoundaryMode.MATRIX:
            return _no("dense encoding applies BCs as identity matrix rows "
                       "(BoundaryMode.MATRIX only)")
        return _OK  # per-cell fields fold into the matrix columns for free

    if backend == "conv":
        if nd == 1:
            return _no("no 1D conv encoding (use dense or reference)")
        if variable and nd == 3:
            return _no("channels-trick Conv2D shares its band weights across "
                       "the X-Y plane; per-cell weight fields not "
                       "expressible (use conv3d_native, dense, or pallas)")
        if nd == 3 and mode is not BoundaryMode.MASK:
            return _no("3D channels-trick conv supports the mask trick only")
        if raw:
            return _no("conv encoding paths bake in the Dirichlet fixup")
        if mode is BoundaryMode.MATRIX:
            return _no("MATRIX mode is the dense encoding's BC scheme")
        if variable and mode is not BoundaryMode.MASK:
            return _no("the variable-coefficient gather trick bakes in the "
                       "mask fixup (BoundaryMode.MASK only)")
        if mode is BoundaryMode.PAD and spec.radius != 1:
            return _no("BoundaryMode.PAD reconstructs the shell only for "
                       "radius-1 stencils")
        return _OK

    if backend == "conv3d_native":
        if nd != 3:
            return _no("conv3d_native is the 3D-only Conv3D path")
        if raw:
            return _no("conv encoding paths bake in the Dirichlet fixup")
        if mode is not BoundaryMode.MASK:
            return _no("conv3d_native supports the mask trick only")
        return _OK  # variable taps ride the gather trick (one-hot channels)

    if backend in ("pallas", "pallas_fused"):
        if backend == "pallas_fused" and nd != 2:
            return _no("temporal fusion kernel is 2D only (jacobi_fused.py)")
        if nd not in (2, 3):
            return _no(f"no {nd}D Pallas kernel (stencil2d/stencil3d only)")
        if not raw and mode is not BoundaryMode.MASK:
            return _no("Pallas kernels fuse the mask trick in-kernel "
                       "(BoundaryMode.MASK only)")
        if not scalar_bc:
            return _no("Pallas kernels pin the shell to a scalar bc_value; "
                       "array-valued DirichletBC unsupported")
        device = device_profile(device_kind)
        if grid_shape is not None and not _pallas_fits(spec, grid_shape, 1,
                                                       device):
            return _no(f"even the shallowest Pallas block of a "
                       f"{tuple(grid_shape)} grid needs more than the "
                       f"{device.scoped_vmem_bytes} bytes of scoped VMEM a "
                       f"{device.kind} kernel gets (kernels/tiling.py "
                       f"fits_vmem)")
        return _OK

    if backend == "halo":
        if nd != 2:
            return _no("halo-exchange distribution is 2D (distributed.py)")
        if raw:
            return _no("distributed jacobi bakes in the Dirichlet fixup")
        if mode is not BoundaryMode.MASK:
            return _no("distributed jacobi applies BCs via the mask trick")
        if not scalar_bc:
            return _no("distributed jacobi needs a scalar bc_value")
        tiling = _mesh_tiling(mesh)
        if tiling is None:
            return _no("halo distribution needs a mesh with >= 2 axes "
                       "(rows x cols)")
        if grid_shape is not None:
            n_row, n_col = tiling
            if grid_shape[0] % n_row or grid_shape[1] % n_col:
                return _no(f"grid {grid_shape} does not tile over the "
                           f"{n_row}x{n_col} device mesh")
        return _OK

    raise AssertionError(backend)


def _pallas_fits(spec: StencilSpec, grid_shape, fuse: int,
                 device: "DeviceProfile", *, block_h: int | None = None,
                 rim: str | None = None, itemsize: int = 4) -> bool:
    """Whether the Pallas kernel for this cell fits ``device``'s scoped
    VMEM at temporal depth ``fuse``."""
    from repro.kernels.tiling import fits_vmem
    return fits_vmem(tuple(grid_shape), fuse, spec.radius,
                     budget=device.scoped_vmem_bytes, itemsize=itemsize,
                     planes=1 + spec.num_variable_taps, block_h=block_h,
                     rim=rim or "trapezoid")


def _halo_fuse_legal(fuse: int, spec: StencilSpec,
                     grid_shape: tuple[int, ...], mesh) -> bool:
    """Whether a depth-``fuse`` halo schedule is executable on this cell:
    the exchanged depth ``radius*fuse`` cannot exceed the local tile extent
    (one exchange phase only reaches the adjacent shard)."""
    tiling = _mesh_tiling(mesh)
    if tiling is None:
        return False
    n_row, n_col = tiling
    if grid_shape[0] % n_row or grid_shape[1] % n_col:
        return False
    from repro.core.distributed import max_halo_fuse
    return fuse <= max_halo_fuse(spec.radius, grid_shape[0] // n_row,
                                 grid_shape[1] // n_col)


def _mesh_tiling(mesh) -> tuple[int, int] | None:
    """(n_row, n_col) of the first two mesh axes; None if the mesh can't
    host a 2D tile decomposition.  Accepts a bare (n_row, n_col) tuple so
    cost-model callers (and tuned-table validation) can price a mesh shape
    without materializing devices."""
    if mesh is None:
        return 1, 1
    if isinstance(mesh, tuple):
        return (int(mesh[0]), int(mesh[1])) if len(mesh) >= 2 else None
    names = mesh.axis_names
    if len(names) < 2:
        return None
    return mesh.shape[names[0]], mesh.shape[names[1]]


# ---------------------------------------------------------------------------
# Cost model for backend="auto"
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """Coarse per-device-kind rates the auto cost model prices against."""

    kind: str
    vector_flops: float   # elementwise / VPU FLOP/s
    matmul_flops: float   # MXU / GEMM FLOP/s
    mem_bw: float         # HBM / DRAM bytes/s
    pallas_native: bool   # False => Pallas runs in interpret mode
    scoped_vmem_bytes: int  # VMEM the compiler grants one Pallas kernel
    collective_bw: float = 5e10  # inter-device (ICI/NVLink/net) bytes/s


# Keyed by ``jax.Device.device_kind``.  A device that is not here is an
# error (``device_profile``), never a silent default.
_MiB = 1 << 20

DEVICE_PROFILES = {
    # One CPU core; Pallas falls back to the (slow) interpreter, which has
    # no VMEM.  Its budget is the v5e's, so a plan that runs here also
    # compiles on the chip.
    "cpu": DeviceProfile("cpu", 5e10, 2e11, 5e10, pallas_native=False,
                         scoped_vmem_bytes=16 * _MiB, collective_bw=1e9),
    # TPU v5e.  Published (Google Cloud documentation, "TPU v5e"):
    # 197 TFLOP/s bf16 matmul, 819 GB/s HBM, 1,600 Gbit/s interchip
    # interconnect.  The VPU rate is not published; 4e12 is an estimate
    # that puts the ridge near the ~240 FLOP/byte the kernels cite.  The
    # scoped VMEM is the compiler's default limit, which its out-of-VMEM
    # errors quote.
    "TPU v5 lite": DeviceProfile("TPU v5 lite", 4e12, 197e12, 819e9,
                                 pallas_native=True,
                                 scoped_vmem_bytes=16 * _MiB,
                                 collective_bw=2e11),
}


def current_device_kind() -> str:
    """The ``device_kind`` of the first device JAX sees (what this process
    runs on) — the identity every profile and tuned-table lookup uses."""
    return jax.devices()[0].device_kind


def device_profile(device_kind: str | None = None) -> DeviceProfile:
    """The roofline rates of ``device_kind`` (None: this process's
    device); an unknown kind raises instead of borrowing another's peaks."""
    kind = current_device_kind() if device_kind is None else device_kind
    try:
        return DEVICE_PROFILES[kind]
    except KeyError:
        raise ValueError(
            f"no device profile for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_PROFILES)}); add its published peaks to "
            f"core/plan.py DEVICE_PROFILES") from None


# Interpret-mode Pallas re-traces every lane op in Python — orders of
# magnitude off; the model only needs it to never win on CPU.
_INTERPRET_PENALTY = 1e4

# Fixed latency of one ppermute round (dispatch + link setup), per the four
# rounds each halo exchange runs; deep-halo fusion divides the rounds by the
# fuse depth, which is exactly what this term lets the model see.
_PERMUTE_LATENCY = 2.5e-6


def _resolve_fuse(iters: int, spec: StencilSpec, grid_shape,
                  device: DeviceProfile, *, block_h: int | None = None,
                  rim: str | None = None, itemsize: int = 4) -> int:
    """The default depth of pallas_fused for ``iters``: the deepest of 8, 4,
    2 that divides it and fits ``device``'s scoped VMEM, else 1.  make_plan
    runs this depth and the cost model prices it — never a phantom deeper
    fusion."""
    return next((f for f in (8, 4, 2) if iters % f == 0 and _pallas_fits(
        spec, grid_shape, f, device, block_h=block_h, rim=rim,
        itemsize=itemsize)), 1)


def estimate_seconds(
    backend: str,
    spec: StencilSpec,
    grid_shape: tuple[int, ...],
    iters: int,
    device: DeviceProfile,
    *,
    itemsize: int = 4,
    fuse: int | None = None,
    mesh_shape: tuple[int, int] | None = None,
) -> float:
    """Roofline-style time estimate for ``iters`` applications on one step.

    time = max(compute, memory) per iteration; temporal fusion divides the
    streamed bytes by the fuse depth (the whole point of jacobi_fused.py) but
    pays the trapezoid's rim recompute.  ``fuse=None`` prices the depth
    ``make_plan`` would resolve for ``iters``; passing an explicit depth lets
    callers (the solver's fuse auto-selection) compare candidate depths.

    For ``halo`` the model adds a communication term per exchange — perimeter
    bytes over ``collective_bw`` plus four ppermute latencies — divided by
    the fuse depth (deep-halo fusion's whole point), with the trapezoid rim
    recompute scaling the local compute.  ``mesh_shape`` is the (n_row,
    n_col) device tiling the perimeter is measured against; None prices a
    1x1 mesh (per-device compute unchanged, latency floor still paid).
    """
    n = int(np.prod(grid_shape))
    n_var = spec.num_variable_taps
    # Read + write the grid once per iteration; per-cell weight fields add
    # one grid-sized read per varying tap on every streaming backend.
    stream = (2 + n_var) * n * itemsize

    if backend == "dense":
        flops = encoding_flops_per_point(spec, "dense", n_total=n)
        compute = flops * n / device.matmul_flops
        # The fields are baked into the matrix, which re-streams anyway.
        mem = (n * n * itemsize + 2 * n * itemsize) / device.mem_bw
    elif backend in ("conv", "conv3d_native"):
        if spec.is_variable:
            # Gather trick: direct-form MACs for the one-hot conv plus an
            # elementwise multiply + add + reduce per varying tap.
            flops = encoding_flops_per_point(spec, "direct") + 3 * n_var
        elif spec.ndim == 3 and backend == "conv":
            flops = encoding_flops_per_point(spec, "conv3d_channels",
                                             n_total=grid_shape[0])
        else:
            flops = encoding_flops_per_point(spec, "conv")
        compute = flops * n / device.vector_flops
        mem = stream / device.mem_bw
    else:  # reference / pallas / pallas_fused / halo: direct shifted adds
        flops = encoding_flops_per_point(spec, "direct")
        compute = flops * n / device.vector_flops
        mem = stream / device.mem_bw
        if fuse is None:
            fuse = _resolve_fuse(iters, spec, grid_shape, device,
                                 itemsize=itemsize) \
                if backend == "pallas_fused" and spec.ndim == 2 else 1
        if backend in ("pallas", "pallas_fused") and fuse > 1 and spec.ndim == 2:
            from repro.kernels.tiling import fuse_redundancy
            mem /= fuse  # fuse-depth fewer HBM round-trips ...
            # ... at the price of recomputing the overlapping block rims
            compute *= fuse_redundancy(grid_shape, fuse, spec.radius)

    if backend == "halo":
        from repro.kernels.tiling import (halo_exchange_bytes,
                                          halo_fuse_redundancy)
        n_row, n_col = mesh_shape or (1, 1)
        local = (grid_shape[0] // max(n_row, 1),
                 grid_shape[1] // max(n_col, 1))
        f = fuse if fuse and fuse > 1 else 1
        # Per-device compute: each device owns 1/(n_row*n_col) of the grid
        # but recomputes the trapezoid rim at depth f.
        shard = max(n_row * n_col, 1)
        per_iter = max(compute * halo_fuse_redundancy(local, f, spec.radius),
                       mem) / shard
        # A 1x1 mesh still dispatches the four (non-wrapping) permute rounds
        # but moves no neighbour data — latency floor only.
        wire_bytes = halo_exchange_bytes(local, f, spec.radius, itemsize) \
            if shard > 1 else 0
        comm_per_exchange = (wire_bytes / device.collective_bw
                             + 4 * _PERMUTE_LATENCY)
        return per_iter * iters + (iters / f) * comm_per_exchange

    per_iter = max(compute, mem)
    total = per_iter * iters
    if backend in ("pallas", "pallas_fused") and not device.pallas_native:
        total *= _INTERPRET_PENALTY
    return total


def choose_backend(
    spec: StencilSpec,
    grid_shape: tuple[int, ...],
    *,
    mode: BoundaryMode = BoundaryMode.MASK,
    bc: DirichletBC | float | None = 0.0,
    iters: int = 1,
    device_kind: str | None = None,
    mesh=None,
    fuse: int | None = None,
    dtype=jnp.float32,
    interpret: bool | None = None,
    tuned="default",
) -> tuple[str, dict[str, float]]:
    """Pick the cheapest supported backend; returns (name, cost table).

    Measured entries take priority over the roofline: when the tuned table
    (``tuned="default"`` → the committed ``TUNED_stencil.json``; pass a
    ``TunedTable`` to override or ``None`` to disable) holds measurements
    for this (device, family, shape-bucket, dtype) cell, the returned cost
    table contains those *measured* per-backend seconds and the pick is
    their argmin — interpret-mode measurements are structurally excluded, so
    an interpreted Pallas run can never be priced as a compiled one.  When
    no entry applies (unknown cell, stale table, unsupported backend) the
    analytic roofline below is the explicit fallback.

    Two backends are special-cased: ``halo`` is a *distribution strategy*,
    not a local encoding, so it is only considered when a mesh is explicitly
    supplied; ``reference`` is the cross-validation oracle, so auto only
    falls back to it when no real encoding supports the cell (otherwise
    "auto matches the oracle" would be circular).

    ``fuse`` prices the Pallas paths at an explicit temporal depth (e.g. the
    deepest depth the caller's chunking can actually run — the solver passes
    this); None prices the depth make_plan itself would resolve for
    ``iters``.  ``interpret=True`` declares that any Pallas plan built from
    this choice will be forced into interpret mode, so the Pallas paths are
    priced with the interpreter penalty regardless of the device profile.
    """
    device = device_profile(device_kind)
    device_kind = device.kind
    mesh_shape = _mesh_tiling(mesh) if mesh is not None else None

    # -- measured table first ---------------------------------------------
    from repro.core import autotune
    table = autotune.resolve_table(tuned)
    if table is not None and len(table):
        cell = table.lookup_cell(device_kind, autotune.spec_family(spec),
                                 tuple(grid_shape), autotune.dtype_key(dtype),
                                 mesh_shape=mesh_shape)
        measured: dict[str, float] = {}
        for e in cell:
            if e.interpreted or e.backend in measured and \
                    e.seconds(iters) >= measured[e.backend]:
                continue
            if e.backend == "halo" and mesh is None:
                continue
            if not backend_support(e.backend, spec, grid_shape=grid_shape,
                                   mode=mode, bc=bc, mesh=mesh,
                                   device_kind=device_kind):
                continue
            measured[e.backend] = e.seconds(iters)
        if measured:
            best = min(measured, key=measured.__getitem__)
            return best, measured

    # -- explicit roofline fallback ---------------------------------------
    costs: dict[str, float] = {}
    for b in BACKENDS:
        if b == "halo" and mesh is None:
            continue
        if b == "reference":
            continue
        if not backend_support(b, spec, grid_shape=grid_shape, mode=mode,
                               bc=bc, mesh=mesh, device_kind=device_kind):
            continue
        costs[b] = estimate_seconds(b, spec, grid_shape, iters, device,
                                    fuse=fuse,
                                    mesh_shape=mesh_shape if b == "halo"
                                    else None)
        if interpret is True and b in ("pallas", "pallas_fused") \
                and device.pallas_native:
            costs[b] *= _INTERPRET_PENALTY
    if not costs:
        # Oracle fallback: always legal, never preferred.
        costs["reference"] = estimate_seconds("reference", spec, grid_shape,
                                              iters, device)
    best = min(costs, key=costs.__getitem__)
    return best, costs


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StencilPlan:
    """A prepared (batch, *grid) -> (batch, *grid) stencil executor.

    ``make_plan`` does the one-time work (backend choice, dense-matrix
    materialization, distributed-solver tracing) so repeated calls — the
    benchmark loops — pay only the jitted execution.

    Beyond the input field, a plan may accept *runtime operands* — traced
    arrays that change per call without recompiling, the mechanism the
    differentiable/adjoint path is built on:

      fields    (V, *grid) per-cell weight stack overriding the spec's baked
                values (canonical tap order, ``StencilSpec.field_stack``);
      source    additive interior term per iteration ((*grid) or
                (batch, *grid)) — the fixed-point form ``x <- M (S x + s) + g``;
      bc_value  Dirichlet value (scalar or full grid), possibly traced.

    ``operands`` names what this backend/mode combination supports; passing
    an unsupported operand raises at call time (Python level, not trace
    time).
    """

    spec: StencilSpec
    backend: str
    grid_shape: tuple[int, ...]
    mode: BoundaryMode
    iters: int
    fuse: int
    costs: dict[str, float]
    _fn: Callable[..., jnp.ndarray]
    # Whether the Pallas kernels behind this plan actually run interpreted
    # (False for every non-Pallas backend) — benchmarks and the autotuner
    # use this to tag rows structurally instead of trusting name suffixes.
    interpreted: bool = False
    # Where the backend choice came from: "explicit" (caller named it),
    # "tuned" (measured-table hit), or "roofline" (analytic fallback).
    source: str = "explicit"
    rim: str | None = None
    operands: frozenset = frozenset()

    def __call__(self, x: jnp.ndarray, *, fields=None, source=None,
                 bc_value=None) -> jnp.ndarray:
        for name, val in (("fields", fields), ("source", source),
                          ("bc_value", bc_value)):
            if val is not None and name not in self.operands:
                sup = ", ".join(sorted(self.operands)) or "none"
                raise ValueError(
                    f"this {self.backend!r} plan takes no runtime {name} "
                    f"operand (supported here: {sup})")
        if fields is not None:
            want = (self.spec.num_variable_taps, *self.grid_shape)
            if tuple(fields.shape) != want:
                raise ValueError(
                    f"fields operand must be shaped {want} (tap-major stack "
                    f"over the variable taps), got {tuple(fields.shape)}")
        squeeze = x.ndim == self.spec.ndim
        if squeeze:
            x = x[None]
        if x.shape[1:] != self.grid_shape:
            raise ValueError(
                f"plan built for grid {self.grid_shape}, got {x.shape[1:]}")
        out = self._fn(x, fields, source, bc_value)
        return out[0] if squeeze else out


def _as_bc(bc: DirichletBC | float | None) -> DirichletBC | None:
    if bc is None or isinstance(bc, DirichletBC):
        return bc
    return DirichletBC(float(bc))


def _scalar_bc_value(bc: DirichletBC | None) -> float | None:
    if bc is None:
        return None
    if not isinstance(bc.value, (int, float)):
        raise ValueError("this backend needs a scalar Dirichlet value")
    return float(bc.value)


def _raw_reference(x, spec, iters, fields=None):
    def one(g):
        def body(t, _):
            return apply_stencil(t, spec, fields), None
        y, _ = jax.lax.scan(body, g, None, length=iters)
        return y
    return jax.vmap(one)(x)


def _bc_reference(x, spec, bc, iters, fields=None, source=None,
                  bc_value=None, dtype=jnp.float32):
    # Same math as jacobi_reference, but the iteration loop is a lax.scan:
    # the oracle's unrolled Python loop is fine for the conformance matrix's
    # 2 iterations, but XLA compile time explodes super-linearly once the
    # solver asks for O(100)-iteration chunks.  Runtime operands ride the
    # mask-trick form directly: x <- mask * (S x + source) + bc_grid.
    grid = x.shape[1:]
    if bc_value is None:
        mask = bc.interior_mask(grid, dtype)
        bcg = bc.bc_grid(grid, dtype)
    else:
        mask, bcg = runtime_bc_grids(grid, bc_value, dtype)

    def one(g, s):
        g = g * mask + bcg
        def body(t, _):
            y = apply_stencil(t, spec, fields)
            if s is not None:
                y = y + s
            return y * mask + bcg, None
        y, _ = jax.lax.scan(body, g, None, length=iters)
        return y

    if source is None:
        return jax.vmap(lambda g: one(g, None))(x)
    src = jnp.broadcast_to(jnp.asarray(source, dtype), x.shape)
    return jax.vmap(one)(x, src)


def make_plan(
    spec: StencilSpec,
    grid_shape: tuple[int, ...],
    *,
    backend: str = "auto",
    bc: DirichletBC | float | None = 0.0,
    mode: BoundaryMode = BoundaryMode.MASK,
    iters: int = 1,
    fuse: int | None = None,
    dtype=jnp.float32,
    mesh=None,
    interpret: bool | None = None,
    device_kind: str | None = None,
    block_h: int | None = None,
    rim: str | None = None,
    tuned="default",
) -> StencilPlan:
    """Lower ``spec`` on ``grid_shape`` through one backend into a callable.

    backend="auto" routes through :func:`choose_backend` — a measured
    tuned-table entry (``tuned``) supplies the whole schedule (backend, fuse
    depth, block shape, rim strategy) when one applies; the roofline is the
    fallback.  ``bc=None`` means raw zero-padded stencil application (no
    Dirichlet fixup) — only the reference and Pallas backends can express
    it.  ``block_h``/``rim`` tune the 2D Pallas block geometry (other
    backends ignore them).
    """
    if spec.ndim != len(grid_shape):
        raise ValueError(f"spec is {spec.ndim}D but grid is {len(grid_shape)}D")
    if spec.is_variable and spec.weights_shape != tuple(grid_shape):
        raise ValueError(
            f"spec carries {spec.weights_shape}-shaped weight fields but the "
            f"grid is {tuple(grid_shape)}")
    if iters < 1:
        raise ValueError("iters must be >= 1")
    bc = _as_bc(bc)

    costs: dict[str, float] = {}
    source = "explicit"
    if backend == "auto":
        backend, costs = choose_backend(
            spec, grid_shape, mode=mode, bc=bc, iters=iters,
            device_kind=device_kind, mesh=mesh, dtype=dtype,
            interpret=interpret, tuned=tuned)
        source = "roofline"
        # A measured entry carries the whole schedule, not just the backend:
        # inherit its fuse depth / block shape / rim strategy where the
        # caller left them open.
        from repro.core import autotune
        table = autotune.resolve_table(tuned)
        entry = table.lookup(
            device_profile(device_kind).kind, autotune.spec_family(spec),
            tuple(grid_shape), autotune.dtype_key(dtype),
            mesh_shape=_mesh_tiling(mesh) if mesh is not None else None) \
            if table else None
        if entry is not None and entry.backend == backend:
            source = "tuned"
            if fuse is None and entry.fuse > 1 and iters % entry.fuse == 0 \
                    and (backend != "halo"
                         or _halo_fuse_legal(entry.fuse, spec, grid_shape,
                                             mesh)):
                fuse = entry.fuse
            if block_h is None:
                block_h = entry.block_h
            if rim is None:
                rim = entry.rim
    sup = backend_support(backend, spec, grid_shape=grid_shape, mode=mode,
                          bc=bc, mesh=mesh, device_kind=device_kind)
    if not sup:
        raise ValueError(f"backend {backend!r} unsupported here: {sup.reason}")
    device = device_profile(device_kind)
    itemsize = jnp.dtype(dtype).itemsize

    # ``fuse`` is a hint for the 2D Pallas paths (both scalar-bc and raw
    # execute in fuse-sized chunks) and for halo (one deep-halo exchange per
    # ``fuse`` local iterations); every other backend ignores it and the
    # plan records fuse=1 so its metadata reflects what actually runs.
    if backend == "halo":
        rim = None  # depth-vs-tile legality is make_halo_runner's check
        if fuse is None:
            fuse = 1
        elif iters % fuse:
            raise ValueError(f"iters={iters} not divisible by fuse={fuse}")
    else:
        fusing = backend == "pallas_fused" or (backend == "pallas"
                                               and spec.ndim == 2)
        if not fusing:
            fuse = 1
            rim = None
        elif fuse is None:
            if rim == "resident":
                fuse = iters  # the whole chunk stays resident in VMEM
            elif backend == "pallas_fused":
                fuse = _resolve_fuse(iters, spec, grid_shape, device,
                                     block_h=block_h, rim=rim,
                                     itemsize=itemsize)
            else:
                fuse = 1
        elif iters % fuse:
            raise ValueError(f"iters={iters} not divisible by fuse={fuse}")
        if fusing and rim is None and fuse > 1:
            rim = "trapezoid"
        if fusing and spec.ndim == 2 and not _pallas_fits(
                spec, grid_shape, fuse, device, block_h=block_h, rim=rim,
                itemsize=itemsize):
            raise ValueError(
                f"fuse={fuse} {rim or 'trapezoid'} blocks of a {grid_shape} "
                f"grid need more than the scoped VMEM a {device.kind} "
                f"kernel gets; use a shallower fuse")

    from repro.kernels.tiling import default_interpret
    interpreted = backend in ("pallas", "pallas_fused") \
        and default_interpret(interpret)

    fn, operands = _build_fn(spec, grid_shape, backend, bc, mode, iters, fuse,
                             dtype, mesh, interpret, block_h, rim)
    # One jit over the whole closure: the per-call preamble (conv-kernel
    # build, set_boundary, mask/bc grids, halo sharding constraint) traces
    # into constants, so repeated plan calls pay only compiled execution.
    # Runtime operands (fields/source/bc_value) are traced arguments; a None
    # operand is a structure change, so each used combination compiles once.
    fn = jax.jit(fn)
    return StencilPlan(spec=spec, backend=backend, grid_shape=grid_shape,
                       mode=mode, iters=iters, fuse=fuse, costs=costs, _fn=fn,
                       interpreted=interpreted, source=source, rim=rim,
                       operands=operands)


def _build_fn(spec, grid_shape, backend, bc, mode, iters, fuse, dtype, mesh,
              interpret, block_h=None, rim=None):
    """One closure per backend; all share (batch, *grid) -> same semantics.

    Returns ``(fn, operands)``: ``fn(x, fields, source, bc_value)`` and the
    frozenset of runtime-operand names this cell supports (see StencilPlan).
    """
    # Imports deferred so importing repro.core never drags in the Pallas /
    # shard_map machinery for users who only want the specs.
    var_ops = frozenset(("fields",)) if spec.is_variable else frozenset()

    if backend == "reference":
        if bc is None:
            return (lambda x, fields, source, bc_value:
                    _raw_reference(x.astype(dtype), spec, iters, fields),
                    var_ops)
        return (lambda x, fields, source, bc_value:
                _bc_reference(x.astype(dtype), spec, bc, iters, fields,
                              source, bc_value, dtype),
                var_ops | {"source", "bc_value"})

    if backend == "dense":
        from repro.core.dense_encoding import (build_dense_matrix,
                                               dense_jacobi, var_tap_indices)
        matrix = jnp.asarray(build_dense_matrix(grid_shape, spec), dtype)
        if spec.is_variable:
            matrix0 = jnp.asarray(
                build_dense_matrix(grid_shape, spec, include_variable=False),
                dtype)
            tap_k, flat_j, flat_i = var_tap_indices(grid_shape, spec)
        nvar = spec.num_variable_taps

        def run_dense(x, fields, source, bc_value):
            x = x.astype(dtype)
            if bc_value is None:
                x = jax.vmap(bc.set_boundary)(x)
                mask = bc.interior_mask(grid_shape, dtype)
            else:
                mask, bcg = runtime_bc_grids(grid_shape, bc_value, dtype)
                x = x * mask + bcg
            m = matrix
            if fields is not None:
                vals = jnp.asarray(fields, dtype).reshape(nvar, -1)
                m = matrix0.at[flat_j, flat_i].add(vals[tap_k, flat_i])
            drive = None
            if source is not None:
                s = jnp.broadcast_to(jnp.asarray(source, dtype), x.shape)
                drive = (s * mask).reshape(x.shape[0], -1)
            return dense_jacobi(x, m, iters, drive)
        return run_dense, var_ops | {"source", "bc_value"}

    if backend == "conv":
        from repro.core.conv_encoding import (conv_jacobi_2d,
                                              conv_jacobi_3d_channels,
                                              conv_var_jacobi)
        if spec.is_variable:
            return (lambda x, fields, source, bc_value:
                    conv_var_jacobi(x, spec, bc, iters, dtype=dtype,
                                    fields=fields, source=source,
                                    bc_value=bc_value),
                    frozenset(("fields", "source", "bc_value")))
        if spec.ndim == 2:
            ops = frozenset(("source", "bc_value")) \
                if mode is BoundaryMode.MASK else frozenset()
            return (lambda x, fields, source, bc_value:
                    conv_jacobi_2d(x, spec, bc, iters, mode, dtype=dtype,
                                   source=source, bc_value=bc_value), ops)
        return (lambda x, fields, source, bc_value:
                conv_jacobi_3d_channels(x, spec, bc, iters, dtype=dtype,
                                        source=source, bc_value=bc_value),
                frozenset(("source", "bc_value")))

    if backend == "conv3d_native":
        from repro.core.conv_encoding import (conv_jacobi_3d_native,
                                              conv_var_jacobi)
        if spec.is_variable:
            return (lambda x, fields, source, bc_value:
                    conv_var_jacobi(x, spec, bc, iters, dtype=dtype,
                                    fields=fields, source=source,
                                    bc_value=bc_value),
                    frozenset(("fields", "source", "bc_value")))
        return (lambda x, fields, source, bc_value:
                conv_jacobi_3d_native(x, spec, bc, iters, dtype=dtype,
                                      source=source, bc_value=bc_value),
                frozenset(("source", "bc_value")))

    if backend in ("pallas", "pallas_fused"):
        bc_value_s = _scalar_bc_value(bc)
        rim = rim or "trapezoid"
        kw2d = {"block_h": block_h} if block_h else {}
        if spec.ndim == 3:
            from repro.kernels import jacobi3d, stencil3d
            kw3d = {"block_x": block_h} if block_h else {}
            if bc_value_s is not None:
                return (lambda x, fields, source, bc_value:
                        jacobi3d(x.astype(dtype), spec, bc_value=bc_value_s,
                                 iterations=iters, interpret=interpret,
                                 **kw3d),
                        frozenset())

            def run_raw3d(x, fields, source, bc_value):
                def body(t, _):
                    return stencil3d(t, spec, interpret=interpret,
                                     **kw3d), None
                y, _ = jax.lax.scan(body, x.astype(dtype), None, length=iters)
                return y
            return run_raw3d, frozenset()

        if bc_value_s is not None:
            from repro.kernels import jacobi2d
            return (lambda x, fields, source, bc_value:
                    jacobi2d(x.astype(dtype), spec, bc_value=bc_value_s,
                             iterations=iters, fuse=fuse, interpret=interpret,
                             rim=rim, fields=fields, **kw2d),
                    var_ops)
        if spec.is_variable:
            from repro.kernels import stencil2d

            def run_raw2d_var(x, fields, source, bc_value):
                def body(t, _):
                    return stencil2d(t, spec, interpret=interpret,
                                     fields=fields, **kw2d), None
                y, _ = jax.lax.scan(body, x.astype(dtype), None, length=iters)
                return y
            return run_raw2d_var, var_ops
        from repro.kernels import jacobi2d_fused_step

        def run_raw2d(x, fields, source, bc_value):
            def body(t, _):
                return jacobi2d_fused_step(t, spec, fuse=fuse,
                                           interpret=interpret, rim=rim,
                                           **kw2d), None
            y, _ = jax.lax.scan(body, x.astype(dtype), None,
                                length=iters // fuse)
            return y
        return run_raw2d, frozenset()

    if backend == "halo":
        from repro.core.distributed import make_halo_runner
        bc_value_s = _scalar_bc_value(bc)
        if mesh is None:
            mesh = jax.make_mesh((1, 1), ("halo_row", "halo_col"))
        row_axis, col_axis = mesh.axis_names[0], mesh.axis_names[1]
        run = make_halo_runner(
            mesh, spec, H=grid_shape[0], W=grid_shape[1], bc_value=bc_value_s,
            iterations=iters, row_axis=row_axis, col_axis=col_axis, fuse=fuse)
        return (lambda x, fields, source, bc_value: run(x.astype(dtype)),
                frozenset())

    raise AssertionError(backend)


# ---------------------------------------------------------------------------
# One-shot convenience
# ---------------------------------------------------------------------------

def stencil_apply(
    spec: StencilSpec,
    x: jnp.ndarray,
    *,
    backend: str = "auto",
    bc: DirichletBC | float | None = 0.0,
    mode: BoundaryMode = BoundaryMode.MASK,
    iters: int = 1,
    fuse: int | None = None,
    mesh=None,
    interpret: bool | None = None,
    device_kind: str | None = None,
    block_h: int | None = None,
    rim: str | None = None,
    tuned="default",
) -> jnp.ndarray:
    """Apply ``iters`` stencil steps to ``x`` through any backend.

    ``x`` is (batch, *grid) or bare (*grid).  Semantics match
    ``jacobi_reference``: the Dirichlet shell is seeded, then each iteration
    applies the stencil and re-pins the shell (``bc=None`` skips both and
    iterates the raw zero-padded operator).  Every backend is cross-validated
    against the oracle in tests/conformance/.
    """
    if x.ndim not in (spec.ndim, spec.ndim + 1):
        raise ValueError(
            f"x.ndim={x.ndim} incompatible with a {spec.ndim}D spec "
            f"(expect grid or batch+grid)")
    grid_shape = tuple(x.shape[-spec.ndim:])
    plan = make_plan(spec, grid_shape, backend=backend, bc=bc, mode=mode,
                     iters=iters, fuse=fuse, dtype=x.dtype, mesh=mesh,
                     interpret=interpret, device_kind=device_kind,
                     block_h=block_h, rim=rim, tuned=tuned)
    return plan(x)
