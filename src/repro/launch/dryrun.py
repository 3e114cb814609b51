import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_EXTRA", "") + " --xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract the roofline terms from the compiled artifact.

MUST be run as a module entry point (python -m repro.launch.dryrun) so the
XLA_FLAGS line above executes before any jax initialization.

Per cell it records to artifacts/dryrun/<arch>__<shape>__<mesh>.json:
  * memory_analysis (per-device argument/output/temp/peak bytes)
  * cost_analysis flops/bytes
  * per-collective byte totals parsed from the optimized HLO
  * analytic MODEL_FLOPS (6*N*D dense / 6*N_active*D MoE) for the
    useful-compute ratio.
"""
import argparse
import json
import re
import sys
import time


def parse_collectives(hlo_text: str) -> dict:
    """Sum operand bytes of collective ops in optimized HLO text.

    Builds a name->bytes table from instruction definitions, then for each
    collective sums the byte sizes of its operands (the data each device
    contributes).  Returns {op_kind: {"count": n, "operand_bytes": b,
    "result_bytes": r}}.
    """
    dtype_bytes = {
        "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
        "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
        "u8": 1, "pred": 1, "c64": 8, "c128": 16,
    }

    def shape_bytes(shape_str: str) -> int:
        # e.g. "f32[16,1024]{1,0}" or "bf16[]" or tuple "(f32[...], s32[...])"
        total = 0
        for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
            dt, dims = m.group(1), m.group(2)
            if dt not in dtype_bytes:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * dtype_bytes[dt]
        return total

    # First pass: instruction name -> result shape bytes.
    name_bytes: dict[str, int] = {}
    inst_re = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+(\S+?)\(")
    for line in hlo_text.splitlines():
        m = inst_re.match(line)
        if m:
            name_bytes[m.group(1).lstrip("%")] = shape_bytes(m.group(2))

    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    out = {k: {"count": 0, "operand_bytes": 0, "result_bytes": 0} for k in kinds}
    for line in hlo_text.splitlines():
        m = inst_re.match(line)
        if not m:
            continue
        op = m.group(3)
        kind = next((k for k in kinds if op == k or op.startswith(k + ".")
                     or op == k + "-start" or op.startswith(k + "-start")), None)
        if kind is None:
            continue
        out[kind]["count"] += 1
        out[kind]["result_bytes"] += shape_bytes(m.group(2))
        # operands: %name tokens inside the parens
        paren = line[line.index(op) + len(op):]
        ops_bytes = 0
        for om in re.finditer(r"%?([\w.\-]+)", paren):
            nb = name_bytes.get(om.group(1))
            if nb:
                ops_bytes += nb
        out[kind]["operand_bytes"] += ops_bytes
    return out


def analytic_model_flops(cfg, kind: str, batch: int, seq: int) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) for train; 2*N*D for inference."""
    n_active = cfg.active_param_count()
    tokens = batch * (seq if kind in ("train", "prefill") else 1)
    mult = 6 if kind == "train" else 2
    return float(mult * n_active * tokens)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             smoke: bool = False) -> dict:
    import jax
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import (
        cell_is_applicable, input_shardings, input_specs, make_cell,
        make_sharder, make_step_fn,
    )

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = make_cell(arch, shape_name, smoke=smoke)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "seq": cell.seq, "batch": cell.batch,
        "profile": cell.cfg.sharding_profile,
    }
    ok, why = cell_is_applicable(cell.cfg, shape_name)
    if not ok:
        record["status"] = "SKIP"
        record["skip_reason"] = why
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    sharder = make_sharder(cell, mesh)
    structs, dims = input_specs(cell)
    in_shardings = input_shardings(cell, sharder, structs, dims)
    step = make_step_fn(cell, sharder)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(step, in_shardings=in_shardings)
        lowered = jitted.lower(*structs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    record["memory_analysis"] = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    cost = compiled.cost_analysis()
    record["cost_analysis"] = {
        k: float(v) for k, v in cost.items()
        if isinstance(v, (int, float)) and (
            k in ("flops", "bytes accessed", "optimal_seconds")
            or k.startswith("bytes accessed"))
    }
    hlo = compiled.as_text()
    from repro.launch.hlo_cost import analyze as hlo_analyze
    record["hlo_cost"] = hlo_analyze(hlo)   # trip-count-aware (see hlo_cost.py)
    record["collectives_static"] = parse_collectives(hlo)
    record["hlo_chars"] = len(hlo)
    record["model_flops"] = analytic_model_flops(
        cell.cfg, cell.kind, cell.batch, cell.seq)
    record["n_params"] = cell.cfg.param_count()
    record["n_active_params"] = cell.cfg.active_param_count()
    record["lower_s"] = round(t_lower, 2)
    record["compile_s"] = round(t_compile, 2)
    record["n_devices"] = mesh.size
    record["status"] = "OK"
    print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
          f"compile {t_compile:.1f}s, "
          f"flops={record['cost_analysis'].get('flops', 0):.3e}", flush=True)
    print(f"  memory_analysis: {record['memory_analysis']}", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(
        ("train_4k", "prefill_32k", "decode_32k", "long_500k")))
    ap.add_argument("--mesh", default="pod", choices=("pod", "multipod"))
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CI sanity only)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    rec = run_cell(args.arch, args.shape, args.mesh == "multipod", args.out,
                   smoke=args.smoke)
    mesh_name = rec["mesh"]
    path = os.path.join(
        args.out, f"{args.arch}__{args.shape}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] wrote {path} status={rec['status']}")
    return 0 if rec["status"] in ("OK", "SKIP") else 1


if __name__ == "__main__":
    sys.exit(main())
