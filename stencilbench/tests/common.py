"""Helpers for the benchmark's CPU tests: a rehearsal run in this process,
and faults planted in the timed path underneath the drivers."""
import dataclasses
import json
import os

from stencilbench import run as bench

ROOT = bench.ROOT


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


FAULTS = ["unchanged", "half_batch", "altered"]


def plant(monkeypatch, fault: str) -> None:
    """Break ``Solver.solve``, which every driver's timed path goes through:

    * ``unchanged``: every solve hands back its input;
    * ``half_batch``: the second half of every batch is left unsolved;
    * ``altered``: one point of every answer is moved by 1e-2 where it is
      made.
    """
    import jax.numpy as jnp
    from repro.core.solver import Solver
    solve = Solver.solve

    def broken(self, x0, **kw):
        res = solve(self, x0, **kw)
        x0 = jnp.asarray(x0, res.x.dtype).reshape(res.x.shape)
        if fault == "unchanged":
            x = x0
        elif fault == "half_batch":
            half = (x0.shape[0] + 1) // 2
            x = jnp.concatenate([res.x[:half], x0[half:]])
        else:
            centre = (Ellipsis,) + tuple(n // 2 for n in self.grid_shape)
            x = res.x.at[centre].add(1e-2)
        return dataclasses.replace(res, x=x)
    monkeypatch.setattr(Solver, "solve", broken)


def rehearse(capsys, cell, *, seed=2147483659, trace=0, control=False,
             seconds=0.5) -> dict:
    """One rehearsal of ``cell`` at its tiny sizes; returns the last line."""
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearse"]
    if control:
        argv.append("--control")
    capsys.readouterr()
    rc = bench.main(argv)
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1])
