"""The solver names its phases for a trace, on the CPU.

* device scopes: the compiled programs carry ``repro.check`` (the
  convergence check of the solve loop), ``repro.boundary`` (the Dirichlet
  pin before each chunk) and ``repro.sweep`` (the kernel passes) in their
  HLO ``op_name`` metadata, which a device trace shows for each op;
* host spans: each ``Solver.solve`` writes ``repro.solve.dispatch``,
  ``.wait`` and ``.readback`` in that order, without overlapping, all three
  tagged with the same solve id.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import Solver, laplace_jacobi

PHASES = ("repro.solve.dispatch", "repro.solve.wait", "repro.solve.readback")


def _scopes(compiled_text: str) -> set[str]:
    """The ``repro.*`` components of every op_name in a compiled program."""
    names = re.findall(r'op_name="([^"]*)"', compiled_text)
    return {c for n in names for c in n.split("/") if c.startswith("repro.")}


def _x0(solver, batch=2):
    return jnp.asarray(np.random.default_rng(7).random(
        (batch, *solver.grid_shape), np.float32))


def test_converge_loop_names_check_and_boundary():
    s = Solver(laplace_jacobi(2), (16, 16), backend="pallas", bc=1.0,
               rtol=1e-3, check_every=8, max_iters=64)
    text = s._loop.lower(_x0(s), None, None, None).compile().as_text()
    assert {"repro.check", "repro.boundary", "repro.sweep"} <= _scopes(text)


@pytest.mark.parametrize("ndim,grid,bc,want", [
    (2, (16, 16), 1.0, {"repro.sweep", "repro.boundary"}),
    (3, (4, 8, 16), 1.0, {"repro.sweep", "repro.boundary"}),
    (2, (16, 16), None, {"repro.sweep"}),
], ids=["2d", "3d", "2d-raw"])
def test_fixed_plan_names_sweep_and_boundary(ndim, grid, bc, want):
    s = Solver(laplace_jacobi(ndim), grid, backend="pallas", bc=bc,
               rtol=None, atol=None, max_iters=4)
    text = s.plan._fn.lower(_x0(s), None, None, None).compile().as_text()
    assert _scopes(text) == want


def _spans(tmp_path):
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(path) == 1
    spans = []
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro.solve."):
                    spans.append((e.start_ns, e.end_ns, e.name,
                                  dict(e.stats).get("solve")))
    return sorted(spans)


@pytest.mark.parametrize("fixed", [False, True], ids=["converge", "fixed"])
def test_solve_writes_three_spans_per_solve(fixed, tmp_path):
    rule = (dict(rtol=None, atol=None, max_iters=8) if fixed
            else dict(rtol=1e-3, check_every=8, max_iters=64))
    s = Solver(laplace_jacobi(2), (16, 16), backend="pallas", bc=1.0,
               **rule)
    x0 = _x0(s)
    s.solve(x0)   # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            s.solve(x0)
    spans = _spans(tmp_path)
    assert [name for _, _, name, _ in spans] == list(PHASES) * 3
    ids = [solve for _, _, _, solve in spans]
    assert ids[0::3] == ids[1::3] == ids[2::3]
    assert len(set(ids)) == 3 and ids[0::3] == sorted(ids[0::3])
    ends = [end for _, end, _, _ in spans]
    starts = [start for start, _, _, _ in spans]
    assert all(end <= start for end, start in zip(ends, starts[1:]))
