#!/usr/bin/env python3
"""Chip smoke test: the stencil solver's main path, once, on one TPU chip.

    python chip_smoke.py                # one chip: four phases, below
    python chip_smoke.py --four-chips   # four chips: the halo phase only
    python chip_smoke.py --rehearse     # tiny sizes off the chip (CPU)

Every phase runs through the entry points a user calls (``Solver``,
``solve``, ``ServingEngine``), in this one process, fp32, Dirichlet value
1.0, data drawn on the device from a fixed seed, and is checked against the
plain oracle (``backend="reference"``, ``core/reference.py``) run on the
same chip:

  table1  paper Table 1: laplace_jacobi(2) on 131072 tiles of 64x64 (537 M
          elements, 2.1 GB a copy — a quarter of the paper's 2048 M), 500
          fixed sweeps through ``Solver(backend="auto")``; then 1024 tiles
          solved to rtol=1e-5.  Oracle: 8 sampled tiles.
  grid    one 8192x8192 grid streamed from HBM, 200 fixed sweeps.  Oracle:
          the whole grid.
  fig6    paper Fig 6: laplace_jacobi(3) on 8192 tiles of 10x64x64 (1.3 GB
          a copy), 200 fixed sweeps.  Oracle: 8 sampled tiles.
  serve   a ServingEngine answers 64 requests, 64x64 and 60x60 mixed, at
          rtol=1e-5.  Oracle: 8 sampled requests solved alone.
  halo    (--four-chips only) backend="halo" on a 2x2 mesh of 4 chips,
          16384^2 global (268 MB a chip), 200 sweeps, fuse chosen by the
          solver; compared with the same solve on one chip.

Each phase prints one JSON line: the backend, fuse depth and rim the solver
chose, whether its Pallas kernels ran interpreted (must be false), compile
seconds, run seconds (a warmed solve, no compilation in it), the compiled
program's ``memory_analysis()``, the device's ``peak_bytes_in_use`` and the
largest deviation from the oracle.  The last line is ``{"ok": true,
"device": {"platform", "kind", "count"}}`` as JAX reports the device.
Without a TPU (and without ``--rehearse``) the script exits non-zero and
prints no result; ``--rehearse`` is refused on a TPU.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import re
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Largest |x - oracle| admitted after a fixed number of fp32 sweeps (values
# are O(1); Jacobi sweeps do not amplify rounding), and for converged
# solves, which may stop one residual check apart.
FIXED_TOL = 1e-4
CONVERGED_TOL = 1e-3

FULL = dict(tiles=131072, tile=(64, 64), sweeps=500, conv_tiles=1024,
            grid=(8192, 8192), grid_sweeps=200, fig6_tiles=8192,
            fig6_tile=(10, 64, 64), fig6_sweeps=200, requests=64,
            req_shapes=((64, 64), (60, 60)), halo_grid=(16384, 16384),
            halo_sweeps=200, rtol=1e-5, max_iters=20_000, sample=8)
REHEARSE = dict(tiles=16, tile=(16, 16), sweeps=12, conv_tiles=4,
                grid=(80, 144), grid_sweeps=8, fig6_tiles=3,
                fig6_tile=(6, 12, 16), fig6_sweeps=4, requests=8,
                req_shapes=((16, 16), (14, 14)), halo_grid=(64, 64),
                halo_sweeps=8, rtol=1e-4, max_iters=4000, sample=2)


def die(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def emit(**row) -> None:
    print(json.dumps(row, default=str), flush=True)


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(m, k)}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def random_field(shape, seed: int):
    """Uniform [0, 1) data drawn on the device (bulk, no host copy)."""
    import jax
    import jax.numpy as jnp
    draw = jax.jit(lambda k: jax.random.uniform(k, shape, jnp.float32))
    return draw(jax.random.key(seed))


def sample_ids(n: int, k: int, seed: int):
    import numpy as np
    return np.sort(np.random.default_rng(seed).choice(n, min(k, n),
                                                      replace=False))


def max_err(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


def compile_then_solve(solver, program, x0):
    """Compile ``program`` (the solver's jitted function) ahead of time for
    its compile seconds and memory analysis, warm ``solver.solve`` once (it
    traces again and finds the executable in JAX's compilation cache), then
    solve again: that second call's ``wall_seconds`` is the run time, with
    no compilation in it.  Returns ``(compiled, compile_s, result)``."""
    t0 = time.perf_counter()
    compiled = program.lower(x0, None, None, None).compile()
    compile_s = time.perf_counter() - t0
    solver.solve(x0)
    return compiled, compile_s, solver.solve(x0)


def run_fixed(name, spec, x0, sweeps, *, sample, tol, check_all=False,
              **solver_kw):
    """``sweeps`` fixed sweeps through ``Solver(backend="auto")`` (or the
    given backend), compiled ahead to read its memory analysis, then
    checked against the oracle on sampled instances or the whole batch."""
    import jax
    import jax.numpy as jnp
    from repro.core import Solver, solve
    solver = Solver(spec, x0.shape[1:], bc=1.0, rtol=None, atol=None,
                    max_iters=sweeps, **solver_kw)
    compiled, compile_s, res = compile_then_solve(solver, solver.plan._fn, x0)
    ids = slice(None) if check_all else sample_ids(x0.shape[0], sample, 1)
    got = res.x[ids]
    finite = bool(jnp.all(jnp.isfinite(res.x)))
    want = solve(spec, x0[ids], backend="reference", bc=1.0, rtol=None,
                 atol=None, max_iters=sweeps).x
    err = max_err(got, want)
    plan = solver.plan
    row = dict(phase=name, shape=list(x0.shape), sweeps=sweeps,
               backend=plan.backend, fuse=plan.fuse, rim=plan.rim,
               interpreted=plan.interpreted, compile_s=compile_s,
               run_s=res.wall_seconds, memory=memory(compiled),
               peak_bytes_in_use=peak_bytes(jax.devices()[0]),
               oracle="whole batch" if check_all else f"{len(got)} sampled",
               max_abs_err=err, tol=tol, finite=finite,
               ok=finite and err <= tol and not plan.interpreted)
    return row, res


def phase_table1(cfg):
    from repro.core import Solver, laplace_jacobi, solve
    spec = laplace_jacobi(2)
    x0 = random_field((cfg["tiles"], *cfg["tile"]), 11)
    row, res = run_fixed("table1", spec, x0, cfg["sweeps"],
                         sample=cfg["sample"], tol=FIXED_TOL)
    emit(**row)
    del x0, res
    ok = row["ok"]

    # One convergent solve of a smaller batch through the compiled loop.
    import jax
    x0 = random_field((cfg["conv_tiles"], *cfg["tile"]), 12)
    solver = Solver(spec, cfg["tile"], bc=1.0, rtol=cfg["rtol"],
                    max_iters=cfg["max_iters"])
    compiled, compile_s, res = compile_then_solve(solver, solver._loop, x0)
    ids = sample_ids(cfg["conv_tiles"], cfg["sample"], 2)
    ref = solve(spec, x0[ids], backend="reference", bc=1.0,
                rtol=cfg["rtol"], check_every=solver.check_every,
                max_iters=cfg["max_iters"])
    err = max_err(res.x[ids], ref.x)
    iters_gap = int(abs(res.iterations[ids] - ref.iterations).max())
    conv_ok = bool(res.converged.all()) and bool(ref.converged.all()) \
        and err <= CONVERGED_TOL and iters_gap <= solver.check_every \
        and not solver.plan.interpreted
    emit(phase="table1-converge", shape=list(x0.shape), rtol=cfg["rtol"],
         backend=solver.backend, fuse=solver.fuse, rim=solver.plan.rim,
         interpreted=solver.plan.interpreted,
         check_every=solver.check_every, compile_s=compile_s,
         run_s=res.wall_seconds, memory=memory(compiled),
         peak_bytes_in_use=peak_bytes(jax.devices()[0]),
         converged=int(res.converged.sum()),
         iterations=[int(res.iterations.min()), int(res.iterations.max())],
         oracle_iterations=[int(v) for v in ref.iterations],
         max_abs_err=err, tol=CONVERGED_TOL, ok=conv_ok)
    return ok and conv_ok


def phase_grid(cfg):
    from repro.core import laplace_jacobi
    x0 = random_field((1, *cfg["grid"]), 21)
    row, _ = run_fixed("grid", laplace_jacobi(2), x0, cfg["grid_sweeps"],
                       sample=1, tol=FIXED_TOL, check_all=True)
    emit(**row)
    return row["ok"]


def phase_fig6(cfg):
    from repro.core import laplace_jacobi
    x0 = random_field((cfg["fig6_tiles"], *cfg["fig6_tile"]), 31)
    row, _ = run_fixed("fig6", laplace_jacobi(3), x0, cfg["fig6_sweeps"],
                       sample=cfg["sample"], tol=FIXED_TOL)
    emit(**row)
    return row["ok"]


def phase_serve(cfg):
    import jax
    import numpy as np
    from repro.core import PlanCache, laplace_jacobi, solve
    from repro.serve import ServingEngine
    spec = laplace_jacobi(2)
    rng = np.random.default_rng(41)
    shapes = [cfg["req_shapes"][i % 2] for i in range(cfg["requests"])]
    problems = [rng.random(s, dtype=np.float32) for s in shapes]
    cache = PlanCache()

    async def serve():
        async with ServingEngine(cache, max_batch=16, max_wait=0.02) as eng:
            return await asyncio.gather(*(
                eng.submit(spec, x0, bc=1.0, rtol=cfg["rtol"],
                           max_iters=cfg["max_iters"])
                for x0 in problems)), eng.stats.as_dict()

    t0 = time.perf_counter()
    results, stats = asyncio.run(serve())
    wall = time.perf_counter() - t0
    errs, gaps, ok = [], [], all(r.converged for r in results)
    for i in sample_ids(len(problems), cfg["sample"], 3):
        res = results[i]
        ref = solve(spec, problems[i], backend="reference", bc=1.0,
                    rtol=cfg["rtol"], check_every=res.check_every,
                    max_iters=cfg["max_iters"])
        errs.append(max_err(res.x, ref.x))
        gaps.append(abs(res.iterations - ref.iterations))
        ok = ok and ref.converged and errs[-1] <= CONVERGED_TOL \
            and gaps[-1] <= res.check_every
    emit(phase="serve", requests=len(problems),
         shapes=[list(s) for s in cfg["req_shapes"]], rtol=cfg["rtol"],
         backends=sorted({r.backend for r in results}),
         wall_s=wall, compile_s=cache.stats.compile_seconds,
         cache=cache.stats.as_dict(), engine=stats,
         peak_bytes_in_use=peak_bytes(jax.devices()[0]),
         max_abs_err=max(errs), max_iteration_gap=int(max(gaps)),
         tol=CONVERGED_TOL, ok=bool(ok))
    return bool(ok)


def phase_halo(cfg):
    """backend="halo" on a 2x2 mesh of four chips against one chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import Solver, laplace_jacobi
    devices = jax.devices()
    if len(devices) < 4:
        die(f"--four-chips needs 4 devices, JAX sees {len(devices)}")
    spec = laplace_jacobi(2)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    grid, sweeps = cfg["halo_grid"], cfg["halo_sweeps"]
    one = jax.device_put(random_field((1, *grid), 51), devices[0])
    x0 = jax.device_put(one, NamedSharding(mesh, P(None, "data", "model")))
    solver = Solver(spec, grid, backend="halo", mesh=mesh, bc=1.0,
                    rtol=None, atol=None, max_iters=sweeps)
    compiled, compile_s, res = compile_then_solve(solver, solver.plan._fn, x0)
    permutes = len(re.findall(r" collective-permute(?:-start)?\(",
                              compiled.as_text()))

    row, single = run_fixed("halo-single-chip", spec, one, sweeps, sample=1,
                            tol=FIXED_TOL, check_all=True)
    emit(**row)
    err = max_err(jax.device_put(res.x, devices[0]), single.x)
    ok = err <= FIXED_TOL and row["ok"]
    emit(phase="halo", mesh=[2, 2], shape=list(x0.shape), sweeps=sweeps,
         backend=solver.backend, fuse=solver.fuse,
         compile_s=compile_s, run_s=res.wall_seconds,
         memory_per_device=memory(compiled),
         collective_permutes=permutes,
         peak_bytes_in_use=[peak_bytes(d) for d in devices[:4]],
         oracle="same solve on one chip", max_abs_err=err, tol=FIXED_TOL,
         ok=ok)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the halo phase on a 2x2 mesh of 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, off the chip only (refused on a TPU, "
                         "so its last line never names one)")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
        from repro.core import laplace_jacobi  # noqa: F401
    except ImportError as e:
        die(f"cannot import the repro package from {ROOT}/src ({e}); run "
            f"from a checkout of the repository")
    enable_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse and platform == "tpu":
        die("--rehearse runs tiny sizes off the chip only; on a TPU run "
            "the full smoke test")
    if platform != "tpu" and not args.rehearse:
        die(f"no TPU: JAX runs on {platform!r} ({devices[0].device_kind}); "
            f"this smoke test only reports chip runs (use --rehearse to "
            f"check the script itself at tiny sizes)")
    cfg = REHEARSE if args.rehearse else FULL

    phases = [phase_halo] if args.four_chips else \
        [phase_table1, phase_grid, phase_fig6, phase_serve]
    ok = True
    for phase in phases:
        try:
            ok = phase(cfg) and ok
        except Exception as e:  # report every phase, then fail
            traceback.print_exc()
            emit(phase=phase.__name__[len("phase_"):], ok=False,
                 error=f"{type(e).__name__}: {e}"[:2000])
            ok = False
    emit(ok=ok, device={"platform": platform, "kind": devices[0].device_kind,
                        "count": len(devices)})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
