"""Benchmark runner — one section per paper table/figure plus the roofline.
Prints ``name,us_per_call,derived`` CSV rows and (with ``--json``) writes a
machine-readable artifact so the perf trajectory is trackable across commits.

JSON schema (stable, version 7):

  {"schema": 7,
   "us_per_call": {row name: microseconds per timed call},
   "interpreted_rows": [row names whose timing came from interpret-mode
                        Pallas — structurally tagged so consumers exclude
                        them from fastest-backend comparisons instead of
                        pattern-matching "(interp)" name suffixes],
   "solver":      {row name: {"mode": "fixed"|"converged",
                              "iters": int, "s_per_iter": float,
                              # interpret-mode rows carry "interpreted": true
                              # converged rows additionally carry:
                              "backend": str, "residual": float,
                              "converged": bool}},
   "multigrid":   {row name: {"cycles": int, "s_per_cycle": float,
                              "work_units": float, "work_per_cycle": float,
                              "levels": int, "backend": str,
                              "residual": float, "converged": bool,
                              # rows with a Jacobi baseline additionally:
                              "jacobi_iters": int,
                              "work_ratio_vs_jacobi": float}},
   "autotune":    {row name: {"backend": str,
                              "source": "roofline"|"tuned"|"explicit",
                              "fuse": int, "rim": str|null,
                              "s_per_iter": float, "interpreted": bool,
                              "candidates_measured": int}},
   "scaling":     {row name: {"mesh": [n_row, n_col], "grid": [H, W],
                              "fuse": int, "iters": int,
                              # timed rows (weak/strong/fuse-sweep):
                              "s_per_iter": float, "comm_rounds": int,
                              # the scaling/equivalence row instead:
                              "max_err": float, "converged": bool}},
   "adjoint":     {row name: {"grid": [H, W], "iters": int, "backend": str,
                              "fwd_s": float, "grad_s": float,
                              "grad_over_fwd": float}},
   "serving":     {row name: {"requests": int, "solves_per_sec": float,
                              "p50_ms": float, "p99_ms": float, ...} and
                   the serving/*/speedup + serving/*/cache summary rows —
                   see benchmarks/serving_bench.py}}

Sections may return either a list of CSV rows or (rows, metrics dict);
metric keys starting with ``multigrid/`` land in the ``multigrid`` section,
``autotune/`` in ``autotune``, ``scaling/`` in ``scaling`` (the
forced-8-device distributed rows from benchmarks/scaling_bench.py),
``adjoint/`` in ``adjoint`` (differentiable-solve forward-vs-grad cost),
``serving/`` in ``serving`` (plan-cache + coalescing engine throughput),
everything else in ``solver``.  Any metric row carrying
``"interpreted": true`` also lands its name in the top-level
``interpreted_rows`` list.  A section whose run produced no metric rows is
omitted from the payload entirely — an empty ``{}`` section is invalid
(``serving_bench.validate_serving`` rejects it).

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only table1_2d ...]
                                          [--json BENCH_stencil.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


# --only accepts either the section key or the benchmark module name.
_ALIASES = {
    "table1_2d": "table1",
    "fig5_shapes": "fig5",
    "fig6_3d": "fig6",
    "stencil_fuse_sweep": "stencil-fuse",
    "multigrid_bench": "multigrid",
    "autotune_bench": "autotune",
    "scaling_bench": "scaling",
    "adjoint_bench": "adjoint",
    "serving_bench": "serving",
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller step counts (CI)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the schema-7 JSON artifact "
                         "({schema, us_per_call, interpreted_rows, solver, "
                         "multigrid, autotune, scaling, adjoint, serving})")
    args = ap.parse_args()
    only = ({_ALIASES.get(o, o) for o in args.only} if args.only else None)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (adjoint_bench, autotune_bench, fig5_shapes,
                            fig6_3d, multigrid_bench, roofline, scaling_bench,
                            serving_bench, stencil_fuse_sweep, table1_2d)

    sections = {
        "table1": lambda: table1_2d.run(steps=4 if args.fast else 8,
                                        iters_conv=20 if args.fast else 100),
        "fig5": lambda: fig5_shapes.run(iters=20 if args.fast else 100),
        "fig6": lambda: fig6_3d.run(iters=10 if args.fast else 50),
        "stencil-fuse": stencil_fuse_sweep.run,
        "roofline": roofline.run,
        "multigrid": lambda: multigrid_bench.run(
            rtol=1e-5 if args.fast else 1e-6),
        "autotune": lambda: autotune_bench.run(
            iters=20 if args.fast else 100,
            tune_iters=20, repeats=1 if args.fast else 3),
        "scaling": lambda: scaling_bench.run(smoke=args.fast),
        "adjoint": lambda: adjoint_bench.run(
            iters=50 if args.fast else 200),
        "serving": lambda: serving_bench.run(smoke=args.fast),
    }
    failed = 0
    if only:
        unknown = only - sections.keys()
        if unknown:
            print(f"# unknown --only section(s) {sorted(unknown)}; known: "
                  f"{sorted(sections) + sorted(_ALIASES)}", file=sys.stderr)
            failed += len(unknown)
    results: dict[str, float] = {}
    solver_metrics: dict[str, dict] = {}
    mg_metrics: dict[str, dict] = {}
    tune_metrics: dict[str, dict] = {}
    scaling_metrics: dict[str, dict] = {}
    adjoint_metrics: dict[str, dict] = {}
    serving_metrics: dict[str, dict] = {}
    interpreted_rows: list[str] = []
    print("name,us_per_call,derived")
    for name, fn in sections.items():
        if only and name not in only:
            continue
        try:
            out = fn()
            if isinstance(out, tuple):
                rows, metrics = out
                for k, v in metrics.items():
                    if k.startswith("multigrid/"):
                        mg_metrics[k] = v
                    elif k.startswith("autotune/"):
                        tune_metrics[k] = v
                    elif k.startswith("scaling/"):
                        scaling_metrics[k] = v
                    elif k.startswith("adjoint/"):
                        adjoint_metrics[k] = v
                    elif k.startswith("serving/"):
                        serving_metrics[k] = v
                    else:
                        solver_metrics[k] = v
                    if isinstance(v, dict) and v.get("interpreted"):
                        interpreted_rows.append(k)
            else:
                rows = out
            for row in rows:
                print(row, flush=True)
                parts = row.split(",")
                if len(parts) >= 2:
                    try:
                        us = float(parts[1])
                    except ValueError:
                        continue
                    if us > 0.0:
                        # Analytic rows (memory models, roofline bounds)
                        # print a literal 0.0 — not timings, keep them out
                        # of the perf-trajectory artifact.
                        results[parts[0]] = us
        except Exception:
            failed += 1
            print(f"{name},0.0,ERROR", flush=True)
            traceback.print_exc()
    if args.json:
        payload = {"schema": 7, "us_per_call": results,
                   "interpreted_rows": sorted(interpreted_rows)}
        # A section that ran produces rows; one that was skipped (--only) or
        # errored would otherwise land as {} — omit it, empty-dict sections
        # fail validation (serving_bench.validate_serving).
        for key, metrics in (("solver", solver_metrics),
                             ("multigrid", mg_metrics),
                             ("autotune", tune_metrics),
                             ("scaling", scaling_metrics),
                             ("adjoint", adjoint_metrics),
                             ("serving", serving_metrics)):
            if metrics:
                payload[key] = metrics
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {len(results)} timing rows + {len(solver_metrics)} "
              f"solver rows + {len(mg_metrics)} multigrid rows + "
              f"{len(tune_metrics)} autotune rows + {len(scaling_metrics)} "
              f"scaling rows + {len(adjoint_metrics)} adjoint rows + "
              f"{len(serving_metrics)} serving rows to "
              f"{args.json}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
