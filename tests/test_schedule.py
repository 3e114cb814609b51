"""One chunk, one schedule: ``core/plan.py::choose_schedule`` decides what a
plan call runs, and every entry point asks it.

For the benchmark cells' shapes and chunks, and for shapes where two depth
rules once disagreed, ``make_plan(backend="auto")``, ``stencil_apply`` and
``Solver`` build the same schedule on the v5e profile: backend, fuse depth,
rim, the 2D kernel's row blocks and its lane axis.  Nothing is compiled or
run (``stencil_apply`` is traced abstractly), so full sizes are cheap.  The
kernel layer takes its schedule from the plan and imports nothing from the
dispatcher above it.
"""
import ast
import pathlib

import jax
import jax.numpy as jnp
import pytest

import repro.kernels
from repro.core import Solver, laplace_jacobi, make_plan, star
from repro.core import plan as plan_mod

V5E = "TPU v5 lite"
STAR2 = star(2, [0.1, 0.1])

FIXED = dict(rtol=None, atol=None)
# batch+grid shape, spec, the solver's arguments (their chunk is what the
# other entry points run), and the schedule: backend, fuse, rim,
# block_rows, halo_rows, lane axis of the batch
CASES = {
    "table1-tiles-fixed": (
        (131072, 64, 64), laplace_jacobi(2), dict(FIXED, max_iters=100),
        ("pallas", 4, "trapezoid", 64, 0, "batch")),
    "fig6-tiles-fixed": (
        (12288, 10, 64, 64), laplace_jacobi(3), dict(FIXED, max_iters=100),
        ("pallas", 1, None, None, None, None)),
    "table1-tiles-converge": (
        (4096, 64, 64), laplace_jacobi(2),
        dict(rtol=1e-5, check_every=16, max_iters=20000),
        ("pallas", 16, "trapezoid", 64, 0, "batch")),
    "table1-grid-fixed": (
        (1, 16384, 16384), laplace_jacobi(2), dict(FIXED, max_iters=100),
        ("pallas", 4, "trapezoid", 16, 4, "cols")),
    # where make_plan once took the deepest of 8/4/2 dividing the chunk
    # and the solver the roofline's cheapest of 16/8/4/2/1 (the converge
    # cell was one of them)
    "chunk-32": (
        (4096, 64, 64), laplace_jacobi(2), dict(FIXED, max_iters=32),
        ("pallas", 16, "trapezoid", 64, 0, "batch")),
    "radius-2-512": (
        (1, 512, 512), STAR2, dict(FIXED, max_iters=16),
        ("pallas", 4, "trapezoid", 256, 8, "cols")),
    "radius-2-wide-rows": (
        (1, 4096, 16384), STAR2, dict(FIXED, max_iters=16),
        ("pallas", 2, "trapezoid", 16, 4, "cols")),
}


def _schedule(plan, batch):
    return (plan.backend, plan.fuse, plan.rim, plan.block_rows,
            plan.halo_rows, plan.lane_axis(batch))


def _traced_plan(monkeypatch, spec, shape, chunk):
    """The plan ``stencil_apply`` builds, from an abstract trace of it."""
    real, built = plan_mod.make_plan, []
    monkeypatch.setattr(plan_mod, "make_plan", lambda *a, **kw: (
        built.append(real(*a, **kw)) or built[-1]))
    jax.eval_shape(lambda x: plan_mod.stencil_apply(
        spec, x, bc=1.0, iters=chunk, device_kind=V5E),
        jax.ShapeDtypeStruct(shape, jnp.float32))
    (plan,) = built
    return plan


@pytest.mark.parametrize("case", list(CASES))
def test_one_chunk_one_schedule(case, monkeypatch):
    shape, spec, solver_kw, want = CASES[case]
    grid, batch = shape[1:], shape[0]
    solver = Solver(spec, grid, bc=1.0, device_kind=V5E, **solver_kw)
    chunk = solver.check_every
    planned = make_plan(spec, grid, bc=1.0, iters=chunk, device_kind=V5E)
    applied = _traced_plan(monkeypatch, spec, shape, chunk)
    for plan in (solver.plan, planned, applied):
        assert _schedule(plan, batch) == want
        assert plan.source == "roofline" and plan.iters == chunk


def test_kernels_do_not_import_the_dispatcher():
    # the kernel wrappers run the schedule the plan hands them
    above = {"repro.core.plan", "repro.core.solver"}
    paths = sorted(pathlib.Path(repro.kernels.__file__).parent.glob("*.py"))
    assert len(paths) > 5
    for path in paths:
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = {a.name for a in node.names}
            elif isinstance(node, ast.ImportFrom) and node.module:
                names = {node.module} | {f"{node.module}.{a.name}"
                                         for a in node.names}
            else:
                continue
            assert not names & above, (path.name, sorted(names & above))
