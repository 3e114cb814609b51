"""Solve-to-tolerance traffic: time to a solution, the ROADMAP's first metric.

One batch of ``tiles`` instances, drawn on the device from the seed, is
solved by ``Solver.solve`` to ``rtol`` (residual checked every
``check_every`` sweeps, at most ``max_iters``), from the same first field
every time, as long as the window lasts.  The warm-up solve compiles.

``solve_s``: the time from the window's start to the end of the last solve
started in it, over the number of those solves.

Correctness: sampled instances of the last solve against the reference's
solve of the same instances under the same rule: the largest difference of
the fields, and of the sweep counts.
"""
from __future__ import annotations

import time

import numpy as np

from stencilbench import data, reference


class Run:
    def __init__(self, ctx):
        import jax.numpy as jnp
        from repro.core import Solver, laplace_jacobi
        cfg, mix = ctx.config, ctx.traffic
        self.ctx = ctx
        self.ndim, self.bc = cfg["ndim"], float(cfg["bc"])
        self.tile = tuple(cfg["tile"])
        self.tiles = int(cfg["tiles"])
        self.rule = dict(rtol=float(mix["rtol"]),
                         check_every=int(mix["check_every"]),
                         max_iters=int(mix["max_iters"]))
        self.x0 = data.random_field((self.tiles, *self.tile), ctx.seed,
                                    ctx.dtype)
        self.ids = data.sample_ids(self.tiles, mix["sample"], ctx.seed)
        self.first = self.x0[jnp.asarray(self.ids)]
        self.solver = Solver(
            laplace_jacobi(self.ndim), self.tile, bc=self.bc,
            backend=mix.get("backend", "auto"), dtype=ctx.dtype, **self.rule)
        plan = self.solver.plan
        ctx.note(backend=plan.backend, fuse=plan.fuse, rim=plan.rim,
                 interpreted=plan.interpreted, tiles=self.tiles,
                 tile=list(self.tile), **self.rule)
        self.last = self.solver.solve(self.x0)

    def window(self, seconds: float) -> dict:
        n, longest = 0, 0.0
        t0 = t = time.perf_counter()
        deadline = t0 + seconds
        while t < deadline:
            with self.ctx.span("bench.solve"):
                self.last = self.solver.solve(self.x0)
            n += 1
            t, start = time.perf_counter(), t
            longest = max(longest, t - start)
        elapsed = t - t0
        return {"attempted": n, "failed": 0,
                "metrics": {"solve_s": elapsed / n},
                "counters": {"solves": n, "sweeps_per_solve":
                             int(np.max(self.last.iterations)),
                             "longest_solve_s": longest}}

    def check(self) -> dict:
        import jax.numpy as jnp
        ids = jnp.asarray(self.ids)
        got = np.asarray(self.last.x[ids], np.float32)
        got_iters = np.asarray(self.last.iterations)[self.ids]
        del self.last, self.x0, self.solver
        want, want_iters, _ = reference.converge(
            self.first, self.ndim, self.bc, **self.rule)
        return {"max_abs_err": float(np.max(np.abs(got - np.asarray(want)))),
                "iteration_gap": int(np.max(np.abs(
                    got_iters - np.asarray(want_iters))))}
