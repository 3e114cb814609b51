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

The schedule (backend, fuse depth, rim, block height) is decided once, by
``core/plan.py::choose_schedule``: backends are priced over the whole
``max_iters`` budget, each at the depth a ``check_every`` chunk runs.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.boundary import BoundaryMode, DirichletBC
from repro.core.plan import StencilPlan, choose_schedule, make_plan
from repro.core.stencil import StencilSpec

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
    costs: dict[str, float]


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

        if self.fixed:
            # One chunk of exactly max_iters iterations; no residual pass.
            self.check_every = self.max_iters
        else:
            self.check_every = (min(_DEFAULT_CHECK_EVERY, self.max_iters)
                                if check_every is None
                                else min(int(check_every), self.max_iters))
        self.n_chunks = max(1, self.max_iters // self.check_every)

        # Price the whole solve (max_iters), not one chunk — fusion and
        # fixed per-iteration overheads amortize over the full loop — with
        # each backend at the depth a check_every chunk runs.
        sched = choose_schedule(
            spec, self.grid_shape, backend=backend, chunk=self.check_every,
            iters=self.max_iters, mode=mode, bc=bc, mesh=mesh, fuse=fuse,
            dtype=dtype, interpret=interpret, device_kind=device_kind,
            tuned=tuned)
        self.costs: dict[str, float] = sched.costs
        self.plan: StencilPlan = make_plan(
            spec, self.grid_shape, backend=sched, bc=bc, mode=mode,
            iters=self.check_every, dtype=dtype, mesh=mesh,
            interpret=interpret, device_kind=device_kind)
        self.backend = self.plan.backend
        self.fuse = self.plan.fuse
        if not self.fixed:
            self._loop = jax.jit(self._build_loop())
        self._solve_ids = itertools.count()   # tags each solve's spans

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
                with jax.named_scope("repro.check"):
                    err = grid_norm(y - x, axes)
                    done = err <= atol + rtol * grid_norm(y, axes)
                    keep = active.reshape(active.shape + (1,) * (x.ndim - 1))
                    x = jnp.where(keep, y, x)       # frozen instances hold
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

        # Host spans of one solve, tagged with its id: the dispatch, the wait
        # for the device, and the host's reads after it.
        sid = next(self._solve_ids)
        t0 = time.perf_counter()
        with TraceAnnotation("repro.solve.dispatch", solve=sid):
            if self.fixed:
                x = self.plan(x0, fields=fields, source=source,
                              bc_value=bc_value)
            else:
                k, x, active, res, iters, hist = self._loop(
                    x0, fields, source, bc_value)
        with TraceAnnotation("repro.solve.wait", solve=sid):
            jax.block_until_ready(x)
        wall = time.perf_counter() - t0
        with TraceAnnotation("repro.solve.readback", solve=sid):
            if self.fixed:
                iterations = np.full((b,), self.max_iters, np.int64)
                converged = np.zeros((b,), bool)
                residual = np.full((b,), np.nan, np.float32)
                history = np.empty((0, b), np.float32)
            else:
                iterations = np.asarray(iters, np.int64)
                converged = ~np.asarray(active)
                residual = np.asarray(res)
                history = np.asarray(hist)[: int(k)]
            if squeeze:
                return SolveResult(
                    x=x[0], iterations=int(iterations[0]),
                    converged=bool(converged[0]),
                    residual=float(residual[0]),
                    residual_history=history[:, 0], backend=self.backend,
                    fuse=self.fuse, check_every=self.check_every,
                    wall_seconds=wall, costs=self.costs)
            return SolveResult(
                x=x, iterations=iterations, converged=converged,
                residual=residual, residual_history=history,
                backend=self.backend, fuse=self.fuse,
                check_every=self.check_every, wall_seconds=wall,
                costs=self.costs)

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
