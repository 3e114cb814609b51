"""Fixed-sweep traffic: the paper's Table 1 / Fig 6 rows.

One batch of ``tiles`` instances, drawn on the device from the seed, goes
through ``Solver.solve`` in calls of ``sweeps_per_call`` sweeps, each
call's output feeding the next, as long as the window lasts.  The warm-up
call compiles and is the first link of the chain.

``gpts_per_s``: point-sweeps (``data.point_sweeps``) of the calls started in
the window, over the time from the window's start to the end of the last.

Correctness: the first and the last call of the window, each on instances
sampled from the seed: the reference sweeps the call's own input as many
times, and the largest difference from the call's output is compared.
The window does nothing but call the solver, bar one gather of the first
call's output: the first input is sampled in set-up, the last call's input
is held by reference and sampled with its output once the window closes.
Both calls are checked because the chain converges: with no source and one
Dirichlet value, every sweep brings the field nearer the constant fixed
point, which a 10x64x64 tile reaches to the last bit within a few thousand
sweeps, so the last call of a long window may have nothing left to get
wrong.  The first call of the window still has a field far from it.
"""
from __future__ import annotations

import time

import numpy as np

from stencilbench import data, reference


class Run:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from repro.core import Solver, laplace_jacobi
        cfg, mix = ctx.config, ctx.traffic
        self.ctx = ctx
        self.ndim, self.bc = cfg["ndim"], float(cfg["bc"])
        self.tile = tuple(cfg["tile"])
        self.tiles = int(cfg["tiles"])
        self.sweeps = int(mix["sweeps_per_call"])
        x = data.random_field((self.tiles, *self.tile), ctx.seed, ctx.dtype)
        self.ids = data.sample_ids(self.tiles, mix["sample"], ctx.seed)
        ids = jnp.asarray(self.ids)
        # one compiled gather: indexing eagerly dispatches several small ops
        self.sample = jax.jit(lambda a: a[ids])
        self.solver = Solver(
            laplace_jacobi(self.ndim), self.tile, bc=self.bc,
            backend=mix.get("backend", "auto"), rtol=None, atol=None,
            max_iters=self.sweeps, dtype=ctx.dtype)
        plan = self.solver.plan
        ctx.note(backend=plan.backend, fuse=plan.fuse, rim=plan.rim,
                 interpreted=plan.interpreted, tiles=self.tiles,
                 tile=list(self.tile), sweeps_per_call=self.sweeps)
        self.x = self.solver.solve(x).x
        del x
        self.first_in = self.sample(self.x).block_until_ready()

    def window(self, seconds: float) -> dict:
        n, longest, first_out = 0, 0.0, None
        t0 = t = time.perf_counter()
        deadline = t0 + seconds
        while t < deadline:
            prev = self.x   # the previous input is freed before the call
            with self.ctx.span("bench.solve"):
                self.x = self.solver.solve(prev).x
            if not n:
                first_out = self.sample(self.x)
            n += 1
            t, start = time.perf_counter(), t
            longest = max(longest, t - start)
        elapsed = t - t0
        # (input sample, output sample) of the first and the last call
        self.checked = [(self.first_in, first_out),
                        (self.sample(prev), self.sample(self.x))]
        del prev
        pts = data.point_sweeps(self.tiles, self.tile, self.sweeps) * n
        return {"attempted": n, "failed": 0,
                "metrics": {"gpts_per_s": pts / elapsed / 1e9},
                "counters": {"point_sweeps": pts, "calls": n,
                             "longest_call_s": longest}}

    def check(self) -> dict:
        del self.x, self.solver
        err = 0.0
        for before, after in self.checked:
            want = reference.sweeps(before, self.ndim, self.bc, self.sweeps)
            err = max(err, float(np.max(np.abs(
                np.asarray(after, np.float32) - np.asarray(want)))))
        return {"max_abs_err": err}
