"""Hillclimb harness: lower one cell with config overrides, print the three
roofline terms (EXPERIMENTS §Perf methodology).

  PYTHONPATH=src python -m benchmarks.hillclimb --arch glm4-9b \\
      --shape train_4k --set remat_group=8 q_chunk=512

Runs in-process; invoke once per iteration (fresh XLA state per run).
"""
import argparse
import dataclasses
import json
import os
import time


def _force_host_devices(n: int = 512) -> None:
    """Expose ``n`` fake host devices for the mesh dry-run by *appending* to
    XLA_FLAGS.  Only ``main()`` calls this — importing the module must never
    mutate process env (clobbering a caller's own XLA_FLAGS was a bug), and
    an already-present device-count flag is left alone.
    """
    if "--xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        return
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip()


def parse_override(s: str):
    k, v = s.split("=", 1)
    for cast in (int, float):
        try:
            return k, cast(v)
        except ValueError:
            continue
    return k, v


def run(arch: str, shape: str, overrides: dict, multi_pod=False,
        device_kind: str = "TPU v5 lite") -> dict:
    import jax
    from repro.core.plan import DEVICE_PROFILES
    from repro.launch import hlo_cost
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import (
        input_shardings, input_specs, make_cell, make_sharder, make_step_fn,
    )

    # Price against the same per-device table the stencil cost model uses
    # (core/plan.py DEVICE_PROFILES) — the three roofline denominators used
    # to be free-floating constants here that could drift from the model.
    prof = DEVICE_PROFILES[device_kind]

    cell = make_cell(arch, shape)
    if overrides:
        cell = dataclasses.replace(cell, cfg=dataclasses.replace(
            cell.cfg, **overrides))
        from repro.models.model_zoo import build
        cell = dataclasses.replace(cell, api=build(cell.cfg))
    mesh = make_production_mesh(multi_pod=multi_pod)
    sharder = make_sharder(cell, mesh)
    structs, dims = input_specs(cell)
    in_sh = input_shardings(cell, sharder, structs, dims)
    step = make_step_fn(cell, sharder)
    t0 = time.time()
    with mesh:
        compiled = jax.jit(step, in_shardings=in_sh).lower(*structs).compile()
    r = hlo_cost.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    out = {
        "arch": arch, "shape": shape, "overrides": overrides,
        "device_kind": device_kind,
        "compute_s": r["flops"] / prof.matmul_flops,
        "memory_s": r["hbm_bytes"] / prof.mem_bw,
        "collective_s": r["collective_bytes_total"] / prof.collective_bw,
        "flops_per_dev": r["flops"],
        "hbm_gb_per_dev": r["hbm_bytes"] / 1e9,
        "coll_gb_per_dev": r["collective_bytes_total"] / 1e9,
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "args_gb": mem.argument_size_in_bytes / 1e9,
        "compile_s": round(time.time() - t0, 1),
    }
    n_active = cell.cfg.active_param_count()
    tokens = cell.batch * (cell.seq if cell.kind in ("train", "prefill") else 1)
    mult = 6 if cell.kind == "train" else 2
    model_flops_dev = mult * n_active * tokens / mesh.size
    bound = max(out["compute_s"], out["memory_s"], out["collective_s"])
    out["useful_ratio"] = model_flops_dev / r["flops"] if r["flops"] else 0
    out["roofline_frac"] = (model_flops_dev / prof.matmul_flops) / bound \
        if bound else 0
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--set", nargs="*", default=[])
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--device-kind", default="TPU v5 lite",
                    help="DEVICE_PROFILES row to price the roofline against")
    args = ap.parse_args()
    _force_host_devices()
    overrides = dict(parse_override(s) for s in args.set)
    out = run(args.arch, args.shape, overrides, args.multipod,
              device_kind=args.device_kind)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
