"""Autotune before/after: roofline pick vs measured-table pick on the
Table-1 cell (and a second, larger cell to exercise bucket matching).

``before`` lowers ``backend="auto"`` with the tuned table disabled
(``tuned=None``) — the pure analytic roofline pick.  The tuner then measures
every legal schedule for the cell (``core/autotune.py``), and ``after``
lowers the same cell against the freshly measured table.  Both plans are
wall-clock timed through the same harness, so the artifact row pair answers
"did the measured table actually beat the model on this host?".

``run`` returns (csv rows, metrics); benchmarks/run.py folds metric keys
prefixed ``autotune/`` into BENCH_stencil.json's ``autotune`` section.

Regenerate the committed table with:

  PYTHONPATH=src python -m benchmarks.autotune_bench --write

and validate it with ``scripts/ci.sh --tune-check``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import laplace_jacobi, make_plan
from repro.core.autotune import (
    TunedTable,
    autotune_cell,
    default_table_path,
    dtype_key,
    spec_family,
)

from benchmarks.common import csv_row, time_callable


def _plan_metric(plan, s_per_iter: float, n_candidates: int = 0) -> dict:
    """One row of BENCH_stencil.json's ``autotune`` section."""
    return {
        "backend": plan.backend,
        "source": plan.source,
        "fuse": int(plan.fuse),
        "rim": plan.rim,
        "s_per_iter": float(s_per_iter),
        "interpreted": bool(plan.interpreted),
        "candidates_measured": int(n_candidates),
    }


def run(grid=(64, 64), iters: int = 100, tune_iters: int = 20,
        steps: int = 4, repeats: int = 3, table: TunedTable | None = None):
    spec = laplace_jacobi(2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((steps, *grid)), jnp.float32)
    rows: list[str] = []
    metrics: dict[str, dict] = {}

    # -- before: pure roofline dispatch (tuned table disabled) --------------
    before = make_plan(spec, grid, backend="auto", bc=1.0, iters=iters,
                       tuned=None)
    sec_b = time_callable(before, x, iters=repeats)
    name = f"autotune/before={before.backend}/fp32"
    rows.append(csv_row(name, sec_b,
                        f"roofline pick fuse={before.fuse} "
                        f"s/iter={sec_b / iters:.2e}"))
    metrics[name] = _plan_metric(before, sec_b / iters)

    # -- tune: measure every legal schedule for the cell --------------------
    table = autotune_cell(spec, grid, iters=tune_iters, bc=1.0,
                          table=table, repeats=repeats)
    n_cand = len(table)

    # -- after: dispatch against the freshly measured table -----------------
    after = make_plan(spec, grid, backend="auto", bc=1.0, iters=iters,
                      tuned=table)
    sec_a = time_callable(after, x, iters=repeats)
    name = f"autotune/after={after.backend}/fp32"
    rows.append(csv_row(name, sec_a,
                        f"{after.source} pick fuse={after.fuse} "
                        f"rim={after.rim} s/iter={sec_a / iters:.2e} "
                        f"({n_cand} schedules measured)"))
    metrics[name] = _plan_metric(after, sec_a / iters, n_cand)

    # The winning measured schedule itself, for the trajectory record.
    entry = table.lookup(_device_kind(), spec_family(spec), grid,
                         dtype_key(jnp.float32))
    if entry is not None:
        key = "autotune/best-entry"
        metrics[key] = {
            "backend": entry.backend, "source": "tuned",
            "fuse": int(entry.fuse), "rim": entry.rim,
            "s_per_iter": entry.us_per_iter * 1e-6,
            "interpreted": bool(entry.interpreted),
            "candidates_measured": n_cand,
        }
        rows.append(csv_row(key, entry.us_per_iter * 1e-6 * iters,
                            f"{entry.backend} fuse={entry.fuse} "
                            f"block_h={entry.block_h} rim={entry.rim}"))
    return rows, metrics


def _device_kind() -> str:
    from repro.core.plan import current_device_kind
    return current_device_kind()


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--write", nargs="?", const=default_table_path(),
                    default=None, metavar="PATH",
                    help="persist the measured table (default path: the "
                         "committed TUNED_stencil.json)")
    ap.add_argument("--grid", type=int, nargs=2, default=(64, 64))
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--tune-iters", type=int, default=20)
    args = ap.parse_args(argv)
    table = TunedTable()
    rows, _ = run(grid=tuple(args.grid), iters=args.iters,
                  tune_iters=args.tune_iters, table=table)
    for r in rows:
        print(r)
    if args.write:
        table.save(args.write)
        print(f"# wrote {len(table)} tuned entries to {args.write}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
