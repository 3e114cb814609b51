"""Iterative solver engine — the paper's time loop as one compiled program.

The paper's headline numbers are not one stencil application but an entire
Jacobi *solve* run to convergence on the wafer (Table 1 / Fig 6): thousands
of timesteps resident on-device, with the residual checked only periodically
so the hot loop never leaves the fabric.  This module is that time dimension
for the PR 1 dispatcher: ``solve(spec, x0, ...)`` lowers the spec through any
``make_plan`` backend and runs the whole iteration loop inside a single
``lax.while_loop``, so host round-trips happen once per *solve*, not once per
step.

Structure of a solve:

  * the plan executes ``check_every`` stencil iterations per chunk (the hot
    loop — fully fused, jitted once, Pallas temporal blocking inside it);
  * between chunks the residual ``||x_{k+1} - x_k||`` (relative L2 / Linf,
    the paper's Jacobi criterion) is measured on-device;
  * a ``lax.while_loop`` carries (field, per-instance residuals, iteration
    counts, residual history) until every instance converges or ``max_iters``
    is exhausted.

Batched mode is native: ``x0`` may carry a leading instance axis (the
"millions of users" scenario — every backend chunk executor is vmapped over
it) and convergence is tracked *per instance*: an instance that converges is
frozen (its field stops updating, its history stops recording) while the
rest keep iterating, so a batched solve reproduces the per-instance results
of solving each problem alone.

Distribution rides the same entry point: ``backend="halo"`` with a device
mesh runs each chunk as the shard_map halo-exchange program from
``core/distributed.py``, with residuals computed on the sharded global
array — the whole distributed time loop is still one compiled program.

For the 2D Pallas paths the temporal fuse depth is auto-selected against the
PR 1 roofline model (``estimate_seconds(..., fuse=...)`` prices each depth's
HBM-traffic saving against its trapezoid rim recompute).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.boundary import BoundaryMode, DirichletBC
from repro.core.plan import (
    StencilPlan,
    _pallas_fits,
    choose_backend,
    device_profile,
    estimate_seconds,
    make_plan,
)
from repro.core.stencil import StencilSpec

_FUSE_CANDIDATES = (16, 8, 4, 2, 1)
_DEFAULT_CHECK_EVERY = 16


@dataclasses.dataclass
class SolveResult:
    """Outcome of one :meth:`Solver.solve` call.

    Scalar-vs-array convention: for an unbatched ``x0`` (bare grid) the
    per-instance fields are Python scalars; for a batched ``x0`` they are
    arrays over the instance axis.

    Attributes:
      x: final field, same shape as ``x0``.
      iterations: stencil iterations actually run (a multiple of
        ``check_every``; frozen instances stop counting when they converge).
      converged: whether the residual criterion was met before ``max_iters``.
      residual: last measured residual (absolute update norm).
      residual_history: one row per executed chunk; entry ``k`` is the
        residual measured after chunk ``k`` (NaN for instances already
        frozen).  Empty for fixed-iteration solves.
      backend/fuse/check_every: what actually ran.
      wall_seconds: wall time of the solve call (includes compilation on the
        first call through a given Solver).
      est_seconds: the roofline model's estimate for the iterations run.
      costs: per-backend cost table when ``backend="auto"`` chose.
    """

    x: jnp.ndarray
    iterations: int | np.ndarray
    converged: bool | np.ndarray
    residual: float | np.ndarray
    residual_history: np.ndarray
    backend: str
    fuse: int
    check_every: int
    wall_seconds: float
    est_seconds: float
    costs: dict[str, float]


def select_fuse(backend: str, spec: StencilSpec, grid_shape: tuple[int, ...],
                check_every: int, device_kind: str | None = None,
                tuned="default", dtype=jnp.float32, mesh=None) -> int | None:
    """Temporal fuse depth for one chunk: measured if tuned, else roofline.

    The 2D Pallas paths and ``halo`` fuse; every other backend gets ``None``
    (the plan records fuse=1).  A tuned-table entry for this cell whose
    backend matches supplies the measured depth first (clamped to the
    largest divisor of ``check_every`` so chunk boundaries land on whole
    fused passes); the roofline model prices the candidate depths otherwise.

    For ``halo`` the depth is additionally clamped to what the local tile
    can host (``max_halo_fuse``) on the (n_row, n_col) tiling of ``mesh``,
    tuned entries are matched mesh-exactly, and the roofline prices the
    communication term each depth divides.
    """
    halo = backend == "halo" and spec.ndim == 2
    if not halo and (backend not in ("pallas", "pallas_fused")
                     or spec.ndim != 2):
        return None
    device = device_profile(device_kind)

    mesh_shape = deepest = None
    if halo:
        from repro.core.distributed import max_halo_fuse
        from repro.core.plan import _mesh_tiling
        mesh_shape = _mesh_tiling(mesh) if mesh is not None else None
        n_row, n_col = mesh_shape or (1, 1)
        if grid_shape[0] % n_row or grid_shape[1] % n_col:
            return None
        deepest = max_halo_fuse(spec.radius, grid_shape[0] // n_row,
                                grid_shape[1] // n_col)

    from repro.core import autotune
    table = autotune.resolve_table(tuned)
    if table is not None and len(table):
        entry = table.lookup(device.kind, autotune.spec_family(spec),
                             tuple(grid_shape), autotune.dtype_key(dtype),
                             mesh_shape=mesh_shape)
        if entry is not None and entry.backend == backend and entry.fuse >= 1:
            f = min(entry.fuse, check_every)
            if deepest is not None:
                f = min(f, deepest)
            while check_every % f:
                f -= 1
            if halo or _pallas_fits(spec, grid_shape, f, device):
                return f

    candidates = [f for f in _FUSE_CANDIDATES if check_every % f == 0
                  and (deepest is None or f <= deepest)
                  and (halo or _pallas_fits(spec, grid_shape, f, device))] or [1]
    return min(candidates,
               key=lambda f: estimate_seconds(backend, spec, grid_shape,
                                              check_every, device, fuse=f,
                                              mesh_shape=mesh_shape))


class Solver:
    """A prepared run-to-convergence executor for one (spec, grid, backend).

    Construction does all one-time work — backend choice, fuse-depth
    selection, plan building — and the first :meth:`solve` call compiles the
    full time loop; repeated solves (parameter sweeps, batched workloads)
    pay only compiled execution.

    Convergence: an instance is converged when

        ||x_{k+1} - x_k||  <=  atol + rtol * ||x_{k+1}||

    in the chosen norm (``"l2"`` or ``"linf"``), checked every
    ``check_every`` iterations.  ``rtol=None, atol=None`` disables checking
    entirely: the solve runs exactly ``max_iters`` iterations as one fused
    chunk (the benchmark / fixed-step mode).
    """

    def __init__(
        self,
        spec: StencilSpec,
        grid_shape: tuple[int, ...],
        *,
        backend: str = "auto",
        bc: DirichletBC | float | None = 0.0,
        mode: BoundaryMode = BoundaryMode.MASK,
        rtol: float | None = 1e-5,
        atol: float | None = 0.0,
        norm: str = "l2",
        check_every: int | None = None,
        # iteration budget; the loop runs floor(max_iters / check_every)
        # whole chunks, so the budget rounds DOWN to a multiple of
        # check_every (a convergent solve never exceeds max_iters)
        max_iters: int = 10_000,
        fuse: int | None = None,
        dtype=jnp.float32,
        mesh=None,
        interpret: bool | None = None,
        device_kind: str | None = None,
        tuned="default",
    ):
        if norm not in ("l2", "linf"):
            raise ValueError(f"norm must be 'l2' or 'linf', got {norm!r}")
        if max_iters < 1:
            raise ValueError("max_iters must be >= 1")
        if check_every is not None and check_every < 1:
            raise ValueError("check_every must be >= 1")
        self.spec = spec
        self.grid_shape = tuple(grid_shape)
        self.mode = mode
        self.norm = norm
        self.fixed = rtol is None and atol is None
        self.rtol = 0.0 if rtol is None else float(rtol)
        self.atol = 0.0 if atol is None else float(atol)
        if not self.fixed and self.rtol <= 0.0 and self.atol <= 0.0:
            raise ValueError(
                "unsatisfiable convergence criterion (rtol and atol both "
                "zero/None): set one > 0, or pass rtol=None, atol=None for "
                "fixed-iteration mode")
        self.max_iters = int(max_iters)
        self.dtype = dtype
        self.device_kind = device_kind

        if self.fixed:
            # One chunk of exactly max_iters iterations; no residual pass.
            self.check_every = self.max_iters
        else:
            self.check_every = (min(_DEFAULT_CHECK_EVERY, self.max_iters)
                                if check_every is None
                                else min(int(check_every), self.max_iters))
        self.n_chunks = max(1, self.max_iters // self.check_every)

        self.costs: dict[str, float] = {}
        was_auto = backend == "auto"
        if backend == "auto":
            # Price the whole solve (max_iters), not one chunk — fusion and
            # fixed per-iteration overheads amortize over the full loop —
            # but at a fuse depth a check_every-sized chunk can actually run,
            # not the phantom depth _resolve_fuse(max_iters) would pick.
            pricing_fuse = fuse
            if pricing_fuse is None:
                pricing_fuse = select_fuse("pallas_fused", spec,
                                           self.grid_shape, self.check_every,
                                           device_kind, tuned=tuned)
            backend, self.costs = choose_backend(
                spec, self.grid_shape, mode=mode, bc=bc,
                iters=self.max_iters, device_kind=device_kind, mesh=mesh,
                fuse=pricing_fuse, dtype=dtype, interpret=interpret,
                tuned=tuned)

        if fuse is None:
            fuse = select_fuse(backend, spec, self.grid_shape,
                               self.check_every, device_kind, tuned=tuned,
                               dtype=dtype, mesh=mesh)
        # A measured entry for this cell carries the rest of the schedule
        # (block shape, rim strategy) beside the fuse depth select_fuse
        # already took from it.
        block_h = rim = None
        entry = None
        from repro.core import autotune
        from repro.core.plan import _mesh_tiling
        table = autotune.resolve_table(tuned)
        if table is not None and len(table):
            entry = table.lookup(
                device_profile(device_kind).kind,
                autotune.spec_family(spec), self.grid_shape,
                autotune.dtype_key(dtype),
                mesh_shape=_mesh_tiling(mesh) if mesh is not None else None)
            if entry is not None and entry.backend == backend:
                block_h, rim = entry.block_h, entry.rim
        # (an explicit fuse that does not divide check_every is rejected by
        # make_plan's iters/fuse divisibility check)
        self.plan: StencilPlan = make_plan(
            spec, self.grid_shape, backend=backend, bc=bc, mode=mode,
            iters=self.check_every, fuse=fuse, dtype=dtype, mesh=mesh,
            interpret=interpret, device_kind=device_kind, tuned=tuned,
            block_h=block_h, rim=rim)
        if was_auto:
            # The solver resolved "auto" itself (to price the whole solve),
            # so the plan saw an explicit backend name — restore where the
            # choice actually came from.
            self.plan.source = ("tuned" if entry is not None
                                and entry.backend == backend else "roofline")
        self.backend = self.plan.backend
        self.fuse = self.plan.fuse
        self.mesh_shape = _mesh_tiling(mesh) if mesh is not None else None
        if not self.fixed:
            self._loop = jax.jit(self._build_loop())

    # -- the compiled while_loop ------------------------------------------

    def _build_loop(self):
        plan = self.plan
        n_chunks, check_every = self.n_chunks, self.check_every
        rtol, atol = self.rtol, self.atol
        linf = self.norm == "linf"

        def grid_norm(v, axes):
            v = v.astype(jnp.float32)
            if linf:
                return jnp.max(jnp.abs(v), axis=axes)
            return jnp.sqrt(jnp.sum(v * v, axis=axes))

        def loop(x0, fields=None, source=None, bc_value=None):
            axes = tuple(range(1, x0.ndim))
            b = x0.shape[0]
            state = (
                jnp.int32(0),                              # chunks executed
                x0,                                        # field
                jnp.ones((b,), bool),                      # still iterating?
                jnp.full((b,), jnp.inf, jnp.float32),      # last residual
                jnp.zeros((b,), jnp.int32),                # iterations run
                jnp.full((n_chunks, b), jnp.nan, jnp.float32),  # history
            )

            def cond(s):
                k, _, active, *_ = s
                return (k < n_chunks) & jnp.any(active)

            def body(s):
                k, x, active, res, iters, hist = s
                y = plan(x, fields=fields, source=source, bc_value=bc_value)
                err = grid_norm(y - x, axes)
                done = err <= atol + rtol * grid_norm(y, axes)
                keep = active.reshape(active.shape + (1,) * (x.ndim - 1))
                x = jnp.where(keep, y, x)           # frozen instances hold
                res = jnp.where(active, err, res)
                hist = hist.at[k].set(jnp.where(active, err, jnp.nan))
                iters = iters + jnp.where(active, check_every, 0)
                active = active & ~done
                return (k + 1, x, active, res, iters, hist)

            return jax.lax.while_loop(cond, body, state)

        return loop

    # -- public API --------------------------------------------------------

    def run(self, x0: jnp.ndarray, *, fields=None, source=None,
            bc_value=None):
        """Trace-safe solve: ``(x, iterations, converged, residual)`` arrays.

        The differentiable / jittable core of :meth:`solve` — no host sync,
        no numpy conversion, no timing.  Operands beyond ``x0`` are runtime
        plan operands (per-cell weight ``fields``, additive ``source``,
        Dirichlet ``bc_value``) and may be traced; a plan that does not take
        an operand rejects a non-None value (see ``StencilPlan.operands``).
        The adjoint machinery (``core/adjoint.py``) builds on this.
        """
        x0 = jnp.asarray(x0, self.dtype)
        squeeze = x0.ndim == self.spec.ndim
        if squeeze:
            x0 = x0[None]
        if x0.shape[1:] != self.grid_shape:
            raise ValueError(
                f"solver built for grid {self.grid_shape}, got {x0.shape[1:]}")
        b = x0.shape[0]
        if self.fixed:
            x = self.plan(x0, fields=fields, source=source, bc_value=bc_value)
            iters = jnp.full((b,), self.max_iters, jnp.int32)
            converged = jnp.zeros((b,), bool)
            res = jnp.full((b,), jnp.nan, jnp.float32)
        else:
            _, x, active, res, iters, _ = self._loop(
                x0, fields, source, bc_value)
            converged = ~active
        if squeeze:
            return x[0], iters[0], converged[0], res[0]
        return x, iters, converged, res

    def solve(self, x0: jnp.ndarray, *, fields=None, source=None,
              bc_value=None) -> SolveResult:
        """Run the time loop from ``x0`` ((batch, *grid) or bare (*grid))."""
        x0 = jnp.asarray(x0, self.dtype)
        squeeze = x0.ndim == self.spec.ndim
        if squeeze:
            x0 = x0[None]
        if x0.shape[1:] != self.grid_shape:
            raise ValueError(
                f"solver built for grid {self.grid_shape}, got {x0.shape[1:]}")
        b = x0.shape[0]

        t0 = time.perf_counter()
        if self.fixed:
            x = self.plan(x0, fields=fields, source=source, bc_value=bc_value)
            jax.block_until_ready(x)
            wall = time.perf_counter() - t0
            iterations = np.full((b,), self.max_iters, np.int64)
            converged = np.zeros((b,), bool)
            residual = np.full((b,), np.nan, np.float32)
            history = np.empty((0, b), np.float32)
        else:
            k, x, active, res, iters, hist = self._loop(
                x0, fields, source, bc_value)
            jax.block_until_ready(x)
            wall = time.perf_counter() - t0
            iterations = np.asarray(iters, np.int64)
            converged = ~np.asarray(active)
            residual = np.asarray(res)
            history = np.asarray(hist)[: int(k)]

        est = estimate_seconds(
            self.backend, self.spec, self.grid_shape,
            max(int(iterations.max()), 1), device_profile(self.device_kind),
            fuse=self.fuse,
            mesh_shape=self.mesh_shape)

        if squeeze:
            return SolveResult(
                x=x[0], iterations=int(iterations[0]),
                converged=bool(converged[0]), residual=float(residual[0]),
                residual_history=history[:, 0], backend=self.backend,
                fuse=self.fuse, check_every=self.check_every,
                wall_seconds=wall, est_seconds=est, costs=self.costs)
        return SolveResult(
            x=x, iterations=iterations, converged=converged,
            residual=residual, residual_history=history,
            backend=self.backend, fuse=self.fuse,
            check_every=self.check_every, wall_seconds=wall,
            est_seconds=est, costs=self.costs)

    __call__ = solve


def solve(
    spec: StencilSpec,
    x0: jnp.ndarray,
    *,
    backend: str = "auto",
    bc: DirichletBC | float | None = 0.0,
    mode: BoundaryMode = BoundaryMode.MASK,
    rtol: float | None = 1e-5,
    atol: float | None = 0.0,
    norm: str = "l2",
    check_every: int | None = None,
    max_iters: int = 10_000,
    fuse: int | None = None,
    mesh=None,
    interpret: bool | None = None,
    device_kind: str | None = None,
    tuned="default",
    fields=None,
    source=None,
    bc_value=None,
) -> SolveResult:
    """One-shot iterative solve: run ``spec``'s time loop from ``x0``.

    ``x0`` is (batch, *grid) or bare (*grid); see :class:`Solver` for the
    convergence criterion and :class:`SolveResult` for what comes back.
    Build a :class:`Solver` directly to amortize compilation over repeated
    solves.  ``fields`` / ``source`` / ``bc_value`` are runtime plan
    operands (per-cell weights, additive source term, Dirichlet value); for
    a *differentiable* solve use ``core.adjoint.implicit_solve``.
    """
    x0 = jnp.asarray(x0)
    if x0.ndim not in (spec.ndim, spec.ndim + 1):
        raise ValueError(
            f"x0.ndim={x0.ndim} incompatible with a {spec.ndim}D spec "
            f"(expect grid or batch+grid)")
    grid_shape = tuple(x0.shape[-spec.ndim:])
    dtype = x0.dtype if jnp.issubdtype(x0.dtype, jnp.floating) else jnp.float32
    solver = Solver(
        spec, grid_shape, backend=backend, bc=bc, mode=mode, rtol=rtol,
        atol=atol, norm=norm, check_every=check_every, max_iters=max_iters,
        fuse=fuse, dtype=dtype, mesh=mesh, interpret=interpret,
        device_kind=device_kind, tuned=tuned)
    return solver.solve(x0, fields=fields, source=source, bc_value=bc_value)
