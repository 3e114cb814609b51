"""Unified stencil dispatch — one spec, every encoding, one entry point.

``stencil.py`` promises that "every backend computes the same operator and can
be cross-validated"; this module is where that promise becomes an API.  A
``StencilSpec`` + grid shape + boundary condition can be lowered through any
of the repo's executable encodings:

  reference     pure-jnp shifted-add oracle           (core/reference.py)
  dense         N×N dense-layer matmul, BCs in-matrix (core/dense_encoding.py)
  conv          conv layer; 3D rides Conv2D channels  (core/conv_encoding.py)
  conv3d_native true Conv3D (what the CS-1 lacked)    (core/conv_encoding.py)
  pallas        Pallas kernels: in 2D kernels/jacobi_fused.py at any fuse
                depth (the direct kernels/stencil2d.py for variable taps at
                depth 1); in 3D kernels/stencil3d.py, depth 1
  halo          shard_map halo-exchange distribution  (parallel/halo.py)

``choose_schedule`` decides once what a plan call runs — backend, fuse
depth, rim, block height — from a measured tuned-table entry where one
applies, else from a small analytic cost model: per-point FLOPs for the
encoding (core/metrics.py), bytes streamed per iteration, the device kind's
vector/matmul throughput and memory bandwidth, and the arithmetic-intensity
boost temporal fusion buys.  ``backend_support`` answers *which backends
are legal* for a given (spec, grid, boundary mode, device) cell — the
conformance matrix in tests/conformance/ walks every cell and either
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
    "halo",
)


def _check_backend(backend: str) -> None:
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r} (known: {', '.join(BACKENDS)})")


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
    Pallas kernel must fit.  An unknown ``backend`` raises ValueError.
    """
    _check_backend(backend)
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

    if backend == "pallas":
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


# The depths the roofline chooses among, deepest first: a tie goes deeper.
_FUSE_DEPTHS = (16, 8, 4, 2, 1)


def _fuses(backend: str, spec: StencilSpec) -> bool:
    """Whether ``backend`` runs ``spec`` in passes of several sweeps: the 2D
    Pallas kernels, and the halo exchange (one exchange a pass)."""
    return spec.ndim == 2 and backend in ("pallas", "halo")


def fuse_depth(backend: str, spec: StencilSpec, grid_shape, chunk: int,
               device: DeviceProfile, *, entry=None,
               mesh_shape: tuple[int, int] | None = None,
               block_h: int | None = None, rim: str | None = None) -> int:
    """Sweeps per pass of a plan call that runs ``chunk`` sweeps.

    1 where ``backend`` does not fuse; else a tuned ``entry``'s depth,
    clamped to a divisor of ``chunk`` (passes end on chunk boundaries) and,
    for ``halo``, to what the local tile of the (n_row, n_col)
    ``mesh_shape`` hosts (``max_halo_fuse``); else the whole chunk for the
    resident rim; else the roofline's cheapest of 16, 8, 4, 2 and 1 among
    the depths that divide ``chunk`` and fit (the tile, or ``device``'s
    scoped VMEM): HBM traffic saved against rim recomputed, and for
    ``halo`` exchanges saved.  Depths are fitted and priced in float32,
    the type the kernels compute in, which bounds a 16-bit grid's blocks.
    """
    if not _fuses(backend, spec):
        return 1
    halo = backend == "halo"
    deepest = chunk
    if halo:
        from repro.core.distributed import max_halo_fuse
        n_row, n_col = mesh_shape or (1, 1)
        if grid_shape[0] % n_row or grid_shape[1] % n_col:
            return 1
        deepest = min(chunk, max_halo_fuse(
            spec.radius, grid_shape[0] // n_row, grid_shape[1] // n_col))

    def fits(f):
        return halo or _pallas_fits(spec, grid_shape, f, device,
                                    block_h=block_h, rim=rim)

    if entry is not None:
        f = min(entry.fuse, deepest)
        while chunk % f:
            f -= 1
        if fits(f):
            return f
    if rim == "resident" and not halo:
        return chunk
    return min((f for f in _FUSE_DEPTHS
                if chunk % f == 0 and f <= deepest and fits(f)),
               key=lambda f: estimate_seconds(
                   backend, spec, grid_shape, chunk, device, fuse=f,
                   mesh_shape=mesh_shape),
               default=1)


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
    :func:`fuse_depth` gives a plan call of ``iters`` sweeps (the roofline's,
    with no tuned entry); an explicit depth lets callers compare depths.

    For ``halo`` the model adds a communication term per exchange — perimeter
    bytes over ``collective_bw`` plus four ppermute latencies — divided by
    the fuse depth (deep-halo fusion's whole point), with the trapezoid rim
    recompute scaling the local compute.  ``mesh_shape`` is the (n_row,
    n_col) device tiling the perimeter is measured against; None prices a
    1x1 mesh (per-device compute unchanged, latency floor still paid).
    """
    _check_backend(backend)
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
    else:  # reference / pallas / halo: direct shifted adds
        flops = encoding_flops_per_point(spec, "direct")
        compute = flops * n / device.vector_flops
        mem = stream / device.mem_bw
        if fuse is None:
            fuse = fuse_depth(backend, spec, grid_shape, iters, device,
                              mesh_shape=mesh_shape)
        if backend == "pallas" and fuse > 1 and spec.ndim == 2:
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
    if backend == "pallas" and not device.pallas_native:
        total *= _INTERPRET_PENALTY
    return total


@dataclasses.dataclass(frozen=True)
class Schedule:
    """What every call of a plan runs: decided once by
    :func:`choose_schedule`, built by :func:`make_plan`."""

    backend: str
    fuse: int              # sweeps a pass; 1 where the backend does not fuse
    rim: str | None        # the 2D Pallas trapezoid geometry, else None
    block_h: int | None    # the Pallas block height; None: the kernel's
    # Where the backend came from: "explicit" (the caller named it), "tuned"
    # (a measured tuned-table entry) or "roofline" (the cost model).
    source: str
    # Per-backend seconds the choice was made by (empty when explicit).
    costs: dict[str, float] = dataclasses.field(default_factory=dict)


def choose_schedule(
    spec: StencilSpec,
    grid_shape: tuple[int, ...],
    *,
    backend: str = "auto",
    chunk: int = 1,
    iters: int | None = None,
    mode: BoundaryMode = BoundaryMode.MASK,
    bc: DirichletBC | float | None = 0.0,
    mesh=None,
    fuse: int | None = None,
    block_h: int | None = None,
    rim: str | None = None,
    dtype=jnp.float32,
    interpret: bool | None = None,
    device_kind: str | None = None,
    tuned="default",
    backends: tuple[str, ...] | None = None,
) -> Schedule:
    """The schedule of a plan whose every call runs ``chunk`` sweeps.

    The backend is the caller's, or for "auto" the cheapest of ``backends``
    over ``iters`` sweeps (default ``chunk``; a solver prices its whole
    budget).  Measurements come first: where the tuned table
    (``tuned="default"`` → the committed ``TUNED_stencil.json``; a
    ``TunedTable``; ``None`` disables it) holds compiled entries of
    supported backends for this (device, family, shape bucket, dtype) cell,
    the pick is their argmin (an interpreted Pallas run is never priced as
    a compiled one).  Otherwise :func:`estimate_seconds` prices each
    supported backend at the depth a chunk runs; ``interpret=True`` makes
    Pallas pay the interpreter penalty whatever the device.

    ``backends`` defaults to all but two: ``halo``, a distribution strategy,
    counts only with a mesh, and ``reference``, the cross-validation
    oracle, only where nothing else is legal (else "auto matches the
    oracle" would be circular).

    The depth is ``fuse``, else :func:`fuse_depth`'s; ``block_h`` and
    ``rim`` the caller's, else the chosen backend's tuned entry's.  The
    tuned table is read once.
    """
    if backend != "auto":
        _check_backend(backend)
    device = device_profile(device_kind)
    mesh_shape = _mesh_tiling(mesh) if mesh is not None else None
    iters = chunk if iters is None else iters

    # The cell's compiled measurements: the fastest entry of each backend.
    from repro.core import autotune
    table = autotune.resolve_table(tuned)
    measured: dict = {}
    for e in table.lookup_cell(device.kind, autotune.spec_family(spec),
                               tuple(grid_shape), autotune.dtype_key(dtype),
                               mesh_shape=mesh_shape) if table else ():
        if not e.interpreted and (e.backend not in measured or e.us_per_iter
                                  < measured[e.backend].us_per_iter):
            measured[e.backend] = e

    costs: dict[str, float] = {}
    source = "explicit"
    if backend == "auto":
        if backends is None:
            backends = [b for b in BACKENDS if b != "reference"
                        and (b != "halo" or mesh is not None)]
        legal = [b for b in backends
                 if backend_support(b, spec, grid_shape=grid_shape, mode=mode,
                                    bc=bc, mesh=mesh, device_kind=device.kind)]
        source = "tuned"
        costs = {b: measured[b].seconds(iters) for b in legal
                 if b in measured}
        if not costs:
            source = "roofline"
            for b in legal:
                costs[b] = estimate_seconds(
                    b, spec, grid_shape, iters, device,
                    fuse=fuse or fuse_depth(
                        b, spec, grid_shape, chunk, device,
                        mesh_shape=mesh_shape, block_h=block_h, rim=rim),
                    mesh_shape=mesh_shape if b == "halo" else None)
                if interpret is True and b == "pallas" \
                        and device.pallas_native:
                    costs[b] *= _INTERPRET_PENALTY
        if not costs:
            # Oracle fallback: always legal, never preferred.
            costs["reference"] = estimate_seconds(
                "reference", spec, grid_shape, iters, device)
        backend = min(costs, key=costs.__getitem__)

    # A measured entry carries the whole schedule, not just the backend: its
    # depth, block height and rim fill what the caller left open.
    entry = measured.get(backend)
    if entry is not None:
        block_h = entry.block_h if block_h is None else block_h
        rim = entry.rim if rim is None else rim
    if not _fuses(backend, spec):
        return Schedule(backend, 1, None, block_h, source, costs)
    if fuse is None:
        fuse = fuse_depth(backend, spec, grid_shape, chunk, device,
                          entry=entry, mesh_shape=mesh_shape,
                          block_h=block_h, rim=rim)
    if backend == "halo":
        rim = None  # depth-vs-tile legality is make_halo_runner's check
    elif rim is None and fuse > 1:
        rim = "trapezoid"
    return Schedule(backend, fuse, rim, block_h, source, costs)


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
    # The 2D Pallas kernel's row blocks (``tiling.fused_block_geometry``):
    # rows per block and the halo rows each block reads from each of its
    # neighbours.  A grid that is one block reads none (``halo_rows`` 0);
    # None for every other path.
    block_rows: int | None = None
    halo_rows: int | None = None
    # ``tiling.lane_axis`` bound to this plan's grid, spec, boundary, type
    # and device: what the 2D Jacobi kernel puts on the lanes for a batch
    # size.  None for every other path.
    _lane_rule: Callable[[int], str] | None = dataclasses.field(
        default=None, repr=False)

    def lane_axis(self, batch: int) -> str | None:
        """What the 2D Pallas kernel puts on the lanes for a batch of
        ``batch`` instances: "batch" (``jacobi2d_lanes_step``, the batch
        stored (H, W, batch)) or "cols" (``jacobi2d_fused_step``); None
        where no 2D Pallas kernel runs."""
        return None if self._lane_rule is None else self._lane_rule(batch)

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
    backend: str | Schedule = "auto",
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

    The plan's calls run ``iters`` sweeps on the schedule
    :func:`choose_schedule` decides from ``backend`` ("auto" or a name),
    ``fuse``, ``block_h``, ``rim`` and ``tuned``; a ``Schedule`` passed as
    ``backend`` is built as it stands (it carries its own depth, block and
    rim).  ``bc=None`` means raw zero-padded stencil application (no
    Dirichlet fixup) — only the reference and Pallas backends can express
    it.  ``block_h``/``rim`` tune the Pallas block geometry (other backends
    ignore them).
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

    if isinstance(backend, Schedule):
        if (fuse, block_h, rim) != (None, None, None):
            raise ValueError(
                "a Schedule carries its own fuse, block_h and rim")
        sched = backend
    else:
        sched = choose_schedule(
            spec, grid_shape, backend=backend, chunk=iters, mode=mode, bc=bc,
            mesh=mesh, fuse=fuse, block_h=block_h, rim=rim, dtype=dtype,
            interpret=interpret, device_kind=device_kind, tuned=tuned)
    backend, fuse, rim, block_h = (sched.backend, sched.fuse, sched.rim,
                                   sched.block_h)
    sup = backend_support(backend, spec, grid_shape=grid_shape, mode=mode,
                          bc=bc, mesh=mesh, device_kind=device_kind)
    if not sup:
        raise ValueError(f"backend {backend!r} unsupported here: {sup.reason}")
    device = device_profile(device_kind)
    itemsize = jnp.dtype(dtype).itemsize

    # ``fuse`` groups the sweeps of the 2D Pallas paths (both scalar-bc and
    # raw execute in fuse-sized passes) and of halo (one deep-halo exchange
    # per ``fuse`` local iterations); every other backend's schedule records
    # fuse=1, what actually runs.
    if iters % fuse:
        raise ValueError(f"iters={iters} not divisible by fuse={fuse}")
    pallas2d = backend == "pallas" and spec.ndim == 2
    if pallas2d and not _pallas_fits(spec, grid_shape, fuse, device,
                                     block_h=block_h, rim=rim,
                                     itemsize=itemsize):
        raise ValueError(
            f"fuse={fuse} {rim or 'trapezoid'} blocks of a {grid_shape} "
            f"grid need more than the scoped VMEM a {device.kind} "
            f"kernel gets; use a shallower fuse")

    from repro.kernels.tiling import (default_interpret, fused_block_geometry,
                                      lane_axis)
    interpreted = backend == "pallas" and default_interpret(interpret)
    block_rows = halo_rows = lane_rule = None
    if pallas2d:
        block_rows, halo_rows = fused_block_geometry(
            *grid_shape, fuse, spec.radius, block_h or 256,
            rim or "trapezoid", itemsize, planes=1 + spec.num_variable_taps)
        bc_value = _scalar_bc_value(bc)

        def lane_rule(batch):
            return lane_axis((batch, *grid_shape), spec, bc_value,
                             itemsize=itemsize,
                             budget=device.scoped_vmem_bytes)

    fn, operands = _build_fn(spec, grid_shape, backend, bc, mode, iters, fuse,
                             dtype, mesh, interpret, block_h, rim, lane_rule)
    # One jit over the whole closure: the per-call preamble (conv-kernel
    # build, set_boundary, mask/bc grids, halo sharding constraint) traces
    # into constants, so repeated plan calls pay only compiled execution.
    # Runtime operands (fields/source/bc_value) are traced arguments; a None
    # operand is a structure change, so each used combination compiles once.
    fn = jax.jit(fn)
    return StencilPlan(spec=spec, backend=backend, grid_shape=grid_shape,
                       mode=mode, iters=iters, fuse=fuse, costs=sched.costs,
                       _fn=fn, interpreted=interpreted, source=sched.source,
                       rim=rim, operands=operands, block_rows=block_rows,
                       halo_rows=halo_rows, _lane_rule=lane_rule)


def _build_fn(spec, grid_shape, backend, bc, mode, iters, fuse, dtype, mesh,
              interpret, block_h=None, rim=None, lane_rule=None):
    """One closure per backend; all share (batch, *grid) -> same semantics.

    Returns ``(fn, operands)``: ``fn(x, fields, source, bc_value)`` and the
    frozenset of runtime-operand names this cell supports (see StencilPlan).
    ``lane_rule`` gives the 2D Jacobi kernel its lane axis from the batch
    size (``StencilPlan._lane_rule``).
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

    if backend == "pallas":
        from repro.kernels.ops import sweep_scan
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
                return sweep_scan(
                    lambda t: stencil3d(t, spec, interpret=interpret, **kw3d),
                    x.astype(dtype), iters)
            return run_raw3d, frozenset()

        if bc_value_s is not None:
            from repro.kernels import jacobi2d
            return (lambda x, fields, source, bc_value:
                    jacobi2d(x.astype(dtype), spec, bc_value=bc_value_s,
                             iterations=iters, fuse=fuse, interpret=interpret,
                             rim=rim, fields=fields,
                             lane_axis=lane_rule(x.shape[0]), **kw2d),
                    var_ops)
        if spec.is_variable:
            from repro.kernels import stencil2d

            def run_raw2d_var(x, fields, source, bc_value):
                return sweep_scan(
                    lambda t: stencil2d(t, spec, interpret=interpret,
                                        fields=fields, **kw2d),
                    x.astype(dtype), iters)
            return run_raw2d_var, var_ops
        from repro.kernels import jacobi2d_fused_step

        def run_raw2d(x, fields, source, bc_value):
            return sweep_scan(
                lambda t: jacobi2d_fused_step(t, spec, fuse=fuse,
                                              interpret=interpret, rim=rim,
                                              **kw2d),
                x.astype(dtype), iters // fuse)
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
