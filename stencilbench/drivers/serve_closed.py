"""Served solves in a closed loop: callers that each wait for their answer.

``clients`` callers share one ``ServingEngine(PlanCache(), max_batch,
max_wait)``.  Each submits one grid, waits for its result, and submits the
next, until the window closes; the requests still out are then waited for.
The grids come from a pool drawn from the seed: as many of each of
``shapes`` (each the same share), in an order the seed shuffles, Dirichlet
value from the configuration, solved to ``rtol``.

Warm-up submits, for each shape, a burst of every padded batch size the
engine can form (1, 2, 4, ... ``max_batch``), so the bucket's probe and
every loop signature compile before the window.

``serve_p95_ms``: the 95th percentile (nearest rank) of submit-to-result
time over every request submitted in the window; a failed or refused
request counts as infinitely late.  ``serve_solves_per_s``: requests
answered before the window closed, over the window.

Correctness: a sample of the answered requests, drawn from the seed and
holding the one that ran the most sweeps, each against the reference's
solve of that grid alone.
"""
from __future__ import annotations

import asyncio
import math
import time

import numpy as np

from stencilbench import data, reference


class Run:
    def __init__(self, ctx):
        from repro.core import PlanCache, laplace_jacobi
        from repro.serve import ServingEngine
        cfg, mix = ctx.config, ctx.traffic
        self.ctx = ctx
        self.ndim, self.bc = cfg["ndim"], float(cfg["bc"])
        self.spec = laplace_jacobi(self.ndim)
        self.clients = int(mix["clients"])
        self.rule = dict(rtol=float(mix["rtol"]),
                         max_iters=int(mix["max_iters"]))
        shapes = [tuple(s) for s in mix["shapes"]]
        per_shape = int(mix["pool"]) // len(shapes)
        r = data.rng(ctx.seed, 2)
        pool = [r.random(s, dtype=np.float32)
                for s in shapes for _ in range(per_shape)]
        self.pool = [pool[i] for i in r.permutation(len(pool))]
        self.loop = asyncio.new_event_loop()
        self.engine = ServingEngine(PlanCache(),
                                    max_batch=int(mix["max_batch"]),
                                    max_wait=float(mix["max_wait"]))
        self.loop.run_until_complete(self.engine.start())

        sizes = [1 << i for i in range(int(mix["max_batch"]).bit_length())]
        backends = set()
        for s in shapes:
            grids = [g for g in self.pool if g.shape == s]
            for b in sizes:
                out = self.loop.run_until_complete(self._burst(grids[:b]))
                backends.update(res.backend for res in out)
        ctx.note(backend=sorted(backends), clients=self.clients,
                 shapes=[list(s) for s in shapes], warm_batches=sizes,
                 cache=self.engine.cache.stats.as_dict(), **self.rule)
        self.answers = []

    async def _burst(self, grids):
        return await asyncio.gather(*(self._submit(g) for g in grids))

    def _submit(self, grid):
        return self.engine.submit(self.spec, grid, bc=self.bc,
                                  dtype=self.ctx.dtype, **self.rule)

    def window(self, seconds: float) -> dict:
        eng = self.engine
        done0, batches0 = eng.stats.completed, eng.stats.batches
        misses0 = eng.cache.stats.misses
        records = []            # (pool index, submitted, answered, result)

        async def client(c: int):
            k = c
            while time.perf_counter() < deadline:
                i = k % len(self.pool)
                k += self.clients
                t = time.perf_counter()
                try:
                    with self.ctx.span("bench.submit"):
                        res = await self._submit(self.pool[i])
                except Exception:  # a failed or refused request
                    records.append((i, t, math.inf, None))
                else:
                    records.append((i, t, time.perf_counter(), res))

        async def all_clients():
            await asyncio.gather(*(client(c) for c in range(self.clients)))

        t0 = time.perf_counter()
        deadline = t0 + seconds
        self.loop.run_until_complete(all_clients())
        lat = sorted(end - start for _, start, end, _ in records)
        p95 = lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
        answered = sum(end <= deadline for _, _, end, _ in records)
        batches = eng.stats.batches - batches0
        self.answers = [(i, res) for i, _, _, res in records
                        if res is not None]
        return {"attempted": len(records),
                "failed": sum(res is None for *_, res in records),
                "metrics": {"serve_p95_ms": p95 * 1e3,
                            "serve_solves_per_s": answered / seconds},
                "counters": {
                    "mean_batch": ((eng.stats.completed - done0) / batches
                                   if batches else None),
                    "window_misses": eng.cache.stats.misses - misses0}}

    def check(self) -> dict:
        self.loop.run_until_complete(self.engine.stop())
        self.loop.close()
        mix = self.ctx.traffic
        longest = max(range(len(self.answers)),
                      key=lambda j: self.answers[j][1].iterations)
        picked = set(data.sample_ids(len(self.answers), mix["sample"] - 1,
                                     self.ctx.seed).tolist()) | {longest}
        sample = [self.answers[j] for j in sorted(picked)]
        self.answers = []
        err, gap = 0.0, 0
        for shape in {self.pool[i].shape for i, _ in sample}:
            group = [(i, res) for i, res in sample
                     if self.pool[i].shape == shape]
            want, want_iters, _ = reference.converge(
                np.stack([self.pool[i] for i, _ in group]), self.ndim,
                self.bc, check_every=group[0][1].check_every, **self.rule)
            for j, (_, res) in enumerate(group):
                got = np.asarray(res.x, np.float32)
                err = max(err, float(np.max(np.abs(got - want[j]))))
                gap = max(gap, abs(int(res.iterations) - int(want_iters[j])))
        return {"max_abs_err": err, "iteration_gap": gap}
