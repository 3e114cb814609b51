"""Distributed scaling benchmark — the paper's wafer-scaling story on the
forced-8-host-device mesh.

The WSE papers report weak/strong scaling of the halo-decomposed stencil;
this benchmark records the TPU-mesh analogue for the ``halo`` backend plus
the communication-avoiding fuse sweep this repo adds:

  * **weak scaling** — fixed 64x64 local tile over growing meshes (1x1 →
    2x4): s/iter should stay roughly flat as devices are added;
  * **strong scaling** — fixed global grid over the same meshes: s/iter
    should drop as the tile shrinks;
  * **fuse sweep** — fixed 2x4 mesh, fuse depth 1/2/4: ``ppermute`` rounds
    drop by the fuse depth (``halo_comm_rounds`` — analytic: ``lax.scan``
    keeps the HLO rolled, so the trip count is the round count) while
    measured s/iter must not regress;
  * **equivalence** — a converged fused distributed solve against the
    single-device reference solve (max abs error).

The measurements need eight devices.  On a CPU host ``XLA_FLAGS=
--xla_force_host_platform_device_count=8`` must be set before jax imports —
so there ``run()`` (the benchmarks/run.py section) spawns a child process
(``--child``) and parses its JSON back.  On an accelerator it never forks (a
child could not reach the chip the parent holds): it runs in-process on the
devices present, or skips with a message when there are fewer than eight.
Metric keys are prefixed ``scaling/`` and land in BENCH_stencil.json's
schema-5 ``scaling`` section.

CLI:

  PYTHONPATH=src python -m benchmarks.scaling_bench [--smoke] [--json PATH]
  PYTHONPATH=src python -m benchmarks.scaling_bench --validate PATH
  PYTHONPATH=src python -m benchmarks.scaling_bench --write-tuned [PATH]

``--smoke`` is the CI tier (``scripts/ci.sh --scaling-smoke``): one weak-
scaling row plus the fuse sweep and equivalence check.  ``--validate``
checks a written artifact's ``scaling`` section (structure + the >=2x
comm-round reduction at fuse>=2).  ``--write-tuned`` measures the halo
fuse-depth sweep on the 2x4 mesh (``core/autotune.py::autotune_halo_cell``)
and merges the mesh-keyed entries into the committed TUNED_stencil.json.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD_MARK = "SCALING_JSON:"
_DEVICES = 8
WEAK_MESHES = ((1, 1), (1, 2), (2, 2), (2, 4))
FUSE_SWEEP = (1, 2, 4)


# ---------------------------------------------------------------------------
# Child: runs under the forced-device flag, prints metrics as JSON
# ---------------------------------------------------------------------------

def _child(cfg: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import laplace_jacobi, solve
    from repro.core.distributed import halo_comm_rounds
    from repro.core.solver import Solver

    from benchmarks.common import time_callable

    smoke = cfg["smoke"]
    spec = laplace_jacobi(2)
    rng = np.random.default_rng(0)
    metrics: dict[str, dict] = {}
    repeats = 1 if smoke else 3

    def timed_plan(grid, mesh_shape, fuse, iters):
        mesh = jax.make_mesh(mesh_shape, ("data", "model"))
        sv = Solver(spec, grid, backend="halo", mesh=mesh, bc=1.0,
                    rtol=None, atol=None, max_iters=iters, fuse=fuse,
                    tuned=None)
        x = jnp.asarray(rng.standard_normal((1, *grid)), jnp.float32)
        sec = time_callable(sv.plan, x, iters=repeats)
        return {
            "mesh": list(mesh_shape), "grid": list(grid),
            "local": [grid[0] // mesh_shape[0], grid[1] // mesh_shape[1]],
            "fuse": int(sv.fuse), "iters": int(iters),
            "s_per_iter": sec / iters,
            "comm_rounds": halo_comm_rounds(iters, sv.fuse),
        }

    # -- weak scaling: fixed local tile, growing mesh -----------------------
    local = (64, 64)
    iters = 8 if smoke else 32
    meshes = WEAK_MESHES[-1:] if smoke else WEAK_MESHES
    for ms in meshes:
        grid = (local[0] * ms[0], local[1] * ms[1])
        metrics[f"scaling/weak/{ms[0]}x{ms[1]}"] = timed_plan(
            grid, ms, 1, iters)

    # -- strong scaling: fixed global grid, growing mesh --------------------
    if not smoke:
        grid = (128, 128)
        for ms in WEAK_MESHES:
            metrics[f"scaling/strong/{ms[0]}x{ms[1]}"] = timed_plan(
                grid, ms, 1, iters)

    # -- fuse sweep on the full 2x4 mesh ------------------------------------
    ms = WEAK_MESHES[-1]
    grid = (64, 128) if smoke else (128, 256)
    sweep_iters = 8 if smoke else 16
    base = None
    for f in FUSE_SWEEP:
        row = timed_plan(grid, ms, f, sweep_iters)
        if base is None:
            base = row
        row["rounds_ratio_vs_f1"] = row["comm_rounds"] / base["comm_rounds"]
        row["s_per_iter_ratio_vs_f1"] = row["s_per_iter"] / base["s_per_iter"]
        metrics[f"scaling/fuse/f{f}"] = row

    # -- converged fused solve vs the single-device reference ---------------
    g = (16, 24)
    mesh = jax.make_mesh(ms, ("data", "model"))
    x0 = jnp.asarray(rng.standard_normal(g), jnp.float32)
    dist = solve(spec, x0, backend="halo", mesh=mesh, bc=1.0, fuse=4,
                 check_every=16, max_iters=2000, tuned=None)
    ref = solve(spec, x0, backend="reference", bc=1.0, check_every=16,
                max_iters=2000)
    err = float(jnp.max(jnp.abs(dist.x - ref.x)))
    metrics["scaling/equivalence"] = {
        "mesh": list(ms), "grid": list(g), "fuse": int(dist.fuse),
        "iters": int(dist.iterations), "max_err": err,
        "converged": bool(dist.converged) and bool(ref.converged),
    }
    return metrics


# ---------------------------------------------------------------------------
# Parent: spawn the child, parse, format
# ---------------------------------------------------------------------------

def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_child(mode: str, cfg: dict, timeout: int = 1800) -> dict:
    root = _repo_root()
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={_DEVICES}"
                        ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.scaling_bench",
         f"--{mode}", json.dumps(cfg)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=root)
    if r.returncode != 0:
        raise RuntimeError(
            f"scaling child failed:\n{r.stdout}\n{r.stderr[-4000:]}")
    for line in r.stdout.splitlines():
        if line.startswith(_CHILD_MARK):
            return json.loads(line[len(_CHILD_MARK):])
    raise RuntimeError(f"scaling child printed no result:\n{r.stdout}")


def run(smoke: bool = False):
    """The benchmarks/run.py section: (csv rows, ``scaling/``-keyed metrics).

    On a CPU host this spawns the forced-8-device child; on an accelerator
    it runs in this process on the devices present, or skips (no rows) when
    there are fewer than eight.  Every metric row lands in the JSON
    artifact's ``scaling`` section (schema 5).
    """
    import jax

    from benchmarks.common import csv_row
    devices = jax.devices()
    if devices[0].platform == "cpu":
        metrics = _spawn_child("child", {"smoke": smoke})
    elif len(devices) >= _DEVICES:
        metrics = _child({"smoke": smoke})
    else:
        print(f"# scaling: skipped — the 2x4 mesh needs {_DEVICES} devices "
              f"and this host has {len(devices)} {devices[0].device_kind}",
              file=sys.stderr)
        return [], {}
    rows = []
    for name in sorted(metrics):
        m = metrics[name]
        if "s_per_iter" in m:
            rows.append(csv_row(
                name, m["s_per_iter"] * m["iters"],
                f"mesh={m['mesh'][0]}x{m['mesh'][1]} fuse={m['fuse']} "
                f"s/iter={m['s_per_iter']:.2e} rounds={m['comm_rounds']}"))
        else:
            rows.append(csv_row(
                name, 0.0, f"max_err={m['max_err']:.2e} "
                f"converged={m['converged']}"))
    return rows, metrics


# ---------------------------------------------------------------------------
# Validation (scripts/ci.sh --scaling-smoke)
# ---------------------------------------------------------------------------

def validate_scaling(data: dict) -> list[str]:
    """Errors in an artifact's ``scaling`` section; [] means valid.

    Accepts either a full BENCH_stencil.json (schema 5/6) or the mini
    artifact ``--json`` writes.  Beyond structure, this enforces the
    acceptance bar: fuse>=2 must record at most half the ppermute rounds of
    fuse=1, and the converged distributed solve must match the reference to
    1e-5.
    """
    errors: list[str] = []
    if "schema" in data and data["schema"] not in (5, 6, 7):
        errors.append(f"schema {data['schema']!r} not in (5, 6, 7)")
    sc = data.get("scaling")
    if not isinstance(sc, dict) or not sc:
        return errors + ["missing or empty 'scaling' section"]
    weak = [k for k in sc if k.startswith("scaling/weak/")]
    if not weak:
        errors.append("no scaling/weak/* rows")
    for k, m in sc.items():
        if not isinstance(m, dict):
            errors.append(f"{k}: not an object")
            continue
        if "s_per_iter" in m and not m["s_per_iter"] > 0:
            errors.append(f"{k}: non-positive s_per_iter")
        if "comm_rounds" in m and (not isinstance(m["comm_rounds"], int)
                                   or m["comm_rounds"] < 1):
            errors.append(f"{k}: malformed comm_rounds")
    f1 = sc.get("scaling/fuse/f1")
    deep = [m for k, m in sc.items()
            if k.startswith("scaling/fuse/f") and isinstance(m, dict)
            and m.get("fuse", 1) >= 2]
    if f1 is None or not deep:
        errors.append("fuse sweep must record f1 and at least one f>=2 row")
    elif not any(m["comm_rounds"] * 2 <= f1["comm_rounds"] for m in deep):
        errors.append(
            f"no fuse>=2 row halves the ppermute rounds of fuse=1 "
            f"({f1['comm_rounds']} rounds at f1)")
    eq = sc.get("scaling/equivalence")
    if eq is None:
        errors.append("missing scaling/equivalence row")
    else:
        if not eq.get("converged"):
            errors.append("equivalence solve did not converge")
        if not eq.get("max_err", 1.0) <= 1e-5:
            errors.append(f"equivalence max_err {eq.get('max_err')} > 1e-5")
    return errors


# ---------------------------------------------------------------------------
# Tuned-table persistence (--write-tuned)
# ---------------------------------------------------------------------------

def _child_tune(cfg: dict) -> dict:
    import jax

    from repro.core import laplace_jacobi
    from repro.core.autotune import TunedTable, autotune_halo_cell

    mesh = jax.make_mesh(tuple(cfg["mesh"]), ("data", "model"))
    table = autotune_halo_cell(laplace_jacobi(2), tuple(cfg["grid"]), mesh,
                               iters=cfg["iters"], bc=1.0, verbose=True)
    return table.to_json()


def write_tuned(path: str, grid=(128, 256), mesh=(2, 4),
                iters: int = 16) -> int:
    """Measure halo schedules on the forced mesh and merge into ``path``."""
    from repro.core.autotune import TunedTable
    data = _spawn_child("child-tune", {"grid": list(grid),
                                       "mesh": list(mesh), "iters": iters})
    measured = TunedTable.parse(data)
    table = TunedTable.load(path)
    for e in measured.entries:
        table.add(e)
    table.save(path)
    return len(measured)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="one weak-scaling row + fuse sweep (CI tier)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write {'schema': 5, 'scaling': ...} to PATH")
    ap.add_argument("--validate", default=None, metavar="PATH",
                    help="validate an artifact's scaling section and exit")
    ap.add_argument("--write-tuned", nargs="?", const="default", default=None,
                    metavar="PATH", help="measure halo schedules on the 2x4 "
                    "mesh into the tuned table (default: the committed one)")
    # internal: child modes run under the forced-device flag
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--child-tune", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child is not None:
        print(_CHILD_MARK + json.dumps(_child(json.loads(args.child))))
        return 0
    if args.child_tune is not None:
        print(_CHILD_MARK + json.dumps(_child_tune(json.loads(
            args.child_tune))))
        return 0
    if args.validate is not None:
        with open(args.validate) as f:
            errors = validate_scaling(json.load(f))
        if errors:
            for e in errors:
                print(f"SCALING-CHECK FAIL: {e}")
            return 1
        print(f"scaling-check OK: {args.validate}")
        return 0
    if args.write_tuned is not None:
        from repro.core.autotune import default_table_path
        path = default_table_path() if args.write_tuned == "default" \
            else args.write_tuned
        n = write_tuned(path)
        print(f"# merged {n} mesh-keyed halo entries into {path}")
        return 0

    rows, metrics = run(smoke=args.smoke)
    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": 5, "scaling": metrics}, f, indent=2,
                      sort_keys=True)
            f.write("\n")
        print(f"# wrote {len(metrics)} scaling rows to {args.json}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
